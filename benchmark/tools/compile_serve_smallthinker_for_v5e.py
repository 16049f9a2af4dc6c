"""Compile the SmallThinker serving step (``configs/smallthinker-21ba3b-
instruct.json``) at its real size for a described v5e, here on the CPU, with no
chip: what the chip's compiler refuses (memory, the kernel's tiling at 7 query
heads a key-value head) costs no chip time.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_serve_smallthinker_for_v5e.py [<num_pages> ...]

Prints, per full-group pool size (default: the configuration's), what
``compile_serve_window_moe_for_v5e.py`` prints for its model: whether XLA:TPU
accepted the step, what it holds on the device, which kernels are Mosaic calls
in it, and every copy or slice of a pool's shape outside the ``cow`` scope.
A compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "smallthinker-21ba3b-instruct"


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
    from paddle_tpu.models import smallthinker
    from paddle_tpu.models.gpt import build_unified_step

    import chip_smoke
    from benchmark.drivers._program import mosaic_calls, program_bytes
    from benchmark.drivers.serve_smallthinker import model_config
    from benchmark.tools import compile_serve_window_moe_for_v5e as window

    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        cfgj = json.load(f)
    dep = cfgj["serve_smallthinker"]
    cfg = model_config(cfgj, dep)
    dtype = jnp.dtype(cfgj["dtype"])
    step = build_unified_step(cfg, dep["page_size"], dep["chunk"])
    for num_pages in pages or [dep["num_pages"]]:
        t0 = time.time()
        avals, pools = _avals(window, smallthinker, cfg, dep, num_pages, dev,
                              dtype)
        try:
            compiled = step.lower(*avals).compile()
        except Exception as e:  # the compiler's refusal is the answer
            print(f"{CONFIG} num_pages={num_pages} REFUSED {str(e)[:1200]}",
                  flush=True)
            continue
        m = compiled.memory_analysis()
        calls = mosaic_calls(compiled, (
            "ragged_paged_attention", "grouped_matmul", "paged_kv_write"))
        copies = [c for p in pools for c in chip_smoke.pool_copies(
            compiled.as_text(), tuple(p.shape))]
        print(f"{CONFIG} num_pages={num_pages} ACCEPTED in "
              f"{time.time() - t0:.0f}s: program holds "
              f"{program_bytes(compiled) / 1e9:.2f} GB (arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f}, temp "
              f"{m.temp_size_in_bytes / 1e9:.2f}); Mosaic calls {calls}; "
              f"pool-sized copies outside cow: {copies or 'none'}",
              flush=True)
    return 0


def _avals(window, family, cfg, dep, num_pages, dev, dtype):
    """``compile_serve_window_moe_for_v5e.step_avals`` (the window-group
    step's arguments) with ``family``'s weight tree in place of Command
    A+'s, which it imports when called."""
    from paddle_tpu.models import cohere2_moe

    real = cohere2_moe.param_shapes
    cohere2_moe.param_shapes = family.param_shapes
    try:
        return window.step_avals(cfg, dep, num_pages, dev, dtype)
    finally:
        cohere2_moe.param_shapes = real


if __name__ == "__main__":
    sys.exit(main([int(p) for p in sys.argv[1:]]))
