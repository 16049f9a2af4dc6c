"""Compile a configuration's train step at its real size for a described
v5e, here on the CPU, with no chip: what the chip's compiler refuses (memory,
a kernel that cannot be partitioned) costs no chip time to find.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_for_v5e.py <config> <chips> <batch> [<batch> ...]

Prints, per batch size, whether XLA:TPU accepted the program and what it
holds on a device. The largest accepted power of two is the job's
``batch_size``. A compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(config_name, chips, batches):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", f"v5litepod-{chips}")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2" if chips == 4 else "v5e:1x1",
        **({} if chips == 4 else {"chips_per_host_bounds": (1, 1, 1)}))
    jax.default_backend = lambda: "tpu"  # the kernels ask; compile them real
    import paddle_tpu  # noqa: F401
    from paddle_tpu.distributed.mesh import choose_mesh_shape
    from paddle_tpu.framework.jit32 import jit32
    from paddle_tpu.models import gpt_spmd

    from benchmark.drivers.train import model_config, program_bytes

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        cfgj = json.load(f)
    job = cfgj["train"]
    cfg = model_config(cfgj, job)
    dtype = jnp.dtype(job["state_dtype"])
    shape = choose_mesh_shape(chips)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(
        shape["dp"], shape["pp"], shape["mp"]), ("dp", "pp", "mp"))
    shapes = jax.eval_shape(
        lambda: gpt_spmd.init_params(cfg, mesh, 0, dtype))
    shard = gpt_spmd.param_shardings(mesh, shapes)
    p_av = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shard)
    data = NamedSharding(mesh, P("dp", None))
    num_micro = job["num_micro"]

    def step(params, mom, ids, labels):
        # build_spmd_train_step's own step (it places real arrays, which a
        # described device cannot hold, so its body is repeated here)
        loss, grads = jax.value_and_grad(gpt_spmd.loss_fn)(
            params, ids, labels, cfg, mesh, num_micro)
        mom2 = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
        params2 = jax.tree.map(lambda p, m: p - 1e-3 * m, params, mom2)
        return params2, mom2, loss

    fn = jit32(step, in_shardings=(shard, shard, data, data),
               out_shardings=(shard, shard, NamedSharding(mesh, P())),
               donate_argnums=(0, 1))
    for batch in batches:
        ids = jax.ShapeDtypeStruct((batch, job["seq_len"]), jnp.int32,
                                   sharding=data)
        t0 = time.time()
        try:
            with jax.set_mesh(mesh):
                compiled = fn.lower(p_av, p_av, ids, ids).compile()
        except Exception as e:  # the compiler's refusal is the answer
            print(f"{config_name} chips={chips} batch={batch} REFUSED "
                  f"{str(e)[:400]}", flush=True)
            continue
        print(f"{config_name} chips={chips} mesh={dict(mesh.shape)} "
              f"batch={batch} num_micro={num_micro} ACCEPTED in "
              f"{time.time() - t0:.0f}s, program holds "
              f"{program_bytes(compiled) / 1e9:.2f} GB per device",
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), [int(b) for b in sys.argv[3:]])
