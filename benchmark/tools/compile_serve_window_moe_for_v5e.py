"""Compile the serving step of a configuration whose cache has a window group
(``configs/command-a-plus-05-2026.json``) at its real size for a described
v5e, here on the CPU, with no chip: what the chip's compiler refuses (memory,
a kernel's tiling at 16 query heads a key-value head) costs no chip time.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_serve_window_moe_for_v5e.py <config> [<num_pages> ...]

Prints, per full-group pool size (default: the configuration's), whether
XLA:TPU accepted the step, what it holds on the device (arguments,
temporaries), which kernels are Mosaic calls in it, and every copy or slice of
a pool's shape outside the ``cow`` scope (there should be none). A compile
that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def step_avals(cfg, dep, num_pages, dev, dtype):
    """The unified step's arguments as shapes on ``dev`` (signature in
    ``build_unified_step``'s docstring; four donated pools, and the window
    group's table and bases after the tail)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.cohere2_moe import param_shapes

    def sds(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    i32 = jnp.int32
    params = jax.tree.map(lambda s: sds(*s.shape), param_shapes(cfg, dtype))
    t, b, ps = dep["token_budget"], dep["max_batch"], dep["page_size"]
    pps = -(-dep["max_seq_len"] // ps)
    pps_w = -(-(cfg.sliding_window + dep["chunk"] - 1) // ps) + 1
    full = sds(cfg.num_layers - cfg.num_window_layers, num_pages,
               cfg.num_kv_heads, ps, cfg.head_dim)
    win = sds(cfg.num_window_layers, b * pps_w, cfg.num_kv_heads, ps,
              cfg.head_dim)
    tok = sds(t, dtype=i32)
    lane = sds(b, dtype=i32)
    return (params, tok, tok, tok, lane, lane, lane, tok, lane, lane, lane,
            full, full, win, win, sds(b, pps, dtype=i32), lane, lane,
            sds(b, 2, dtype=jnp.uint32), sds(b, dtype=jnp.float32), lane,
            sds(b, dtype=jnp.float32), sds(b, pps_w, dtype=i32),
            lane), (full, win)


def main(config_name, pages):
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
    from benchmark.drivers.serve_window_moe import model_config

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        cfgj = json.load(f)
    dep = cfgj["serve_window_moe"]
    cfg = model_config(cfgj, dep)
    dtype = jnp.dtype(cfgj["dtype"])
    step = build_unified_step(cfg, dep["page_size"], dep["chunk"])
    for num_pages in pages or [dep["num_pages"]]:
        t0 = time.time()
        avals, pools = step_avals(cfg, dep, num_pages, dev, dtype)
        try:
            compiled = step.lower(*avals).compile()
        except Exception as e:  # the compiler's refusal is the answer
            print(f"{config_name} num_pages={num_pages} REFUSED "
                  f"{str(e)[:1200]}", flush=True)
            continue
        m = compiled.memory_analysis()
        calls = mosaic_calls(compiled, (
            "ragged_paged_attention", "grouped_matmul", "paged_kv_write"))
        copies = [c for p in pools for c in chip_smoke.pool_copies(
            compiled.as_text(), tuple(p.shape))]
        print(f"{config_name} num_pages={num_pages} ACCEPTED in "
              f"{time.time() - t0:.0f}s: program holds "
              f"{program_bytes(compiled) / 1e9:.2f} GB (arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f}, temp "
              f"{m.temp_size_in_bytes / 1e9:.2f}); Mosaic calls {calls}; "
              f"pool-sized copies outside cow: {copies or 'none'}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], [int(p) for p in sys.argv[2:]]))
