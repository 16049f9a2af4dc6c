"""Compile the Ouro serving step (``configs/ouro-2.6b.json``) at its real size
for a described v5e, here on the CPU, with no chip: what the chip's compiler
refuses (memory above all: 48 planes of the pool beside the weights) costs no
chip time.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_serve_looped_for_v5e.py [<num_pages> ...]

Prints, per pool size (default: the configuration's 512 pages; the second
choice is 448), whether XLA:TPU accepted the step, what it holds on the
device (arguments and temp), which kernels are Mosaic calls in it, and every
copy or slice of the pool's shape outside the ``cow`` scope: the loop over
the stack carries the pools, and a copy at its edge would show here. A
compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "ouro-2.6b"


def step_avals(cfg, dep, num_pages, dev, dtype):
    """The unified step's arguments as shapes on ``dev`` (the GPT block's
    signature, ``build_unified_step``'s docstring: two donated pools of a
    plane a (loop, layer))."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.ouro import param_shapes

    def sds(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    i32 = jnp.int32
    params = jax.tree.map(lambda s: sds(*s.shape), param_shapes(cfg, dtype))
    t, b, ps = dep["token_budget"], dep["max_batch"], dep["page_size"]
    pps = -(-dep["max_seq_len"] // ps)
    pool = sds(cfg.cache_planes, num_pages, cfg.num_kv_heads, ps,
               cfg.head_dim)
    tok = sds(t, dtype=i32)
    lane = sds(b, dtype=i32)
    return (params, tok, tok, tok, lane, lane, lane, tok, lane, lane, lane,
            pool, pool, sds(b, pps, dtype=i32), lane, lane,
            sds(b, 2, dtype=jnp.uint32), sds(b, dtype=jnp.float32), lane,
            sds(b, dtype=jnp.float32)), pool


def main(pages):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-1")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1",
        chips_per_host_bounds=(1, 1, 1))
    dev = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # the kernels ask; compile them real
    import paddle_tpu  # noqa: F401
    from paddle_tpu.models.gpt import build_unified_step

    import chip_smoke
    from benchmark.drivers._program import mosaic_calls, program_bytes
    from benchmark.drivers.serve_looped import model_config

    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        cfgj = json.load(f)
    dep = cfgj["serve_looped"]
    cfg = model_config(cfgj, dep)
    dtype = jnp.dtype(cfgj["dtype"])
    step = build_unified_step(cfg, dep["page_size"], dep["chunk"])
    for num_pages in pages or [dep["num_pages"]]:
        t0 = time.time()
        avals, pool = step_avals(cfg, dep, num_pages, dev, dtype)
        try:
            compiled = step.lower(*avals).compile()
        except Exception as e:  # the compiler's refusal is the answer
            print(f"{CONFIG} num_pages={num_pages} REFUSED {str(e)[:1200]}",
                  flush=True)
            continue
        m = compiled.memory_analysis()
        calls = mosaic_calls(compiled, ("ragged_paged_attention",
                                        "paged_kv_write"))
        copies = chip_smoke.pool_copies(compiled.as_text(), tuple(pool.shape))
        print(f"{CONFIG} num_pages={num_pages} ACCEPTED in "
              f"{time.time() - t0:.0f}s: program holds "
              f"{program_bytes(compiled) / 1e9:.2f} GB (arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f}, temp "
              f"{m.temp_size_in_bytes / 1e9:.2f}); Mosaic calls {calls}; "
              f"pool-sized copies outside cow: {copies or 'none'}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main([int(p) for p in sys.argv[1:]]))
