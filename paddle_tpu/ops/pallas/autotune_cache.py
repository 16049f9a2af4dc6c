"""Persisted block-autotune cache shared by the Pallas kernel family.

Reference: phi/kernels/autotune/cache.h — a per-op algorithm cache keyed by
shape signature, persisted across runs. Here ONE JSON file holds the swept
block sizes for every Pallas kernel (flash attention q/k blocks, fused-MLP
row blocks); each kernel module forms its own signature strings and sweeps
its own candidates, but the load/save/packaged-defaults plumbing lives here
so a new kernel gets persistence for free.

Layout: ``{signature: [block, ...]}``. Signatures are free-form strings; the
convention is ``<shape-sig>:<dtype>:<which>`` (see the kernels' ``_sig``
helpers). Two sources merge at load:

- the user cache file (``PADDLE_TPU_PALLAS_AUTOTUNE``, legacy spelling
  ``PADDLE_TPU_FLASH_AUTOTUNE``, default ``~/.paddle_tpu_flash_autotune.json``)
  — written by explicit ``autotune*`` sweeps;
- packaged factory defaults (``flash_autotune_defaults.json`` next to this
  module) swept on the benchmark chip — fresh containers have no user cache.

User-swept entries take precedence, and :func:`save` persists ONLY entries
that differ from the packaged snapshot, so package updates keep taking
effect (a persisted snapshot would permanently shadow them).
"""
from __future__ import annotations

CACHE: dict = {}
_LOADED = [False]
# entries that came from the packaged defaults, with their packaged values
_PACKAGED_SNAPSHOT: dict = {}


def cache_path() -> str:
    import os

    return os.environ.get(
        "PADDLE_TPU_PALLAS_AUTOTUNE",
        os.environ.get(
            "PADDLE_TPU_FLASH_AUTOTUNE",
            os.path.join(os.path.expanduser("~"),
                         ".paddle_tpu_flash_autotune.json")))


def load() -> None:
    if _LOADED[0]:
        return
    _LOADED[0] = True
    import json
    import os

    p = cache_path()
    if os.path.exists(p):
        try:
            with open(p) as f:
                CACHE.update(json.load(f))
        except Exception:
            pass
    pkg = os.path.join(os.path.dirname(__file__),
                       "flash_autotune_defaults.json")
    if os.path.exists(pkg):
        try:
            with open(pkg) as f:
                for k, v in json.load(f).items():
                    if k not in CACHE:
                        CACHE[k] = v
                        _PACKAGED_SNAPSHOT[k] = list(v)
        except Exception:
            pass


def save() -> None:
    import json

    out = {k: v for k, v in CACHE.items()
           if _PACKAGED_SNAPSHOT.get(k) != list(v)}
    try:
        with open(cache_path(), "w") as f:
            json.dump(out, f, indent=1)
    except OSError:
        pass


def x64_off():
    """Context manager tracing kernels in 32-bit mode (the framework runs
    with jax_enable_x64, and int64 scalars are not lowerable in Mosaic).
    Only engaged when lowering for TPU: in interpret mode (CPU tests) the
    int64 scalars are harmless, and flipping the x64 config mid-trace
    poisons the surrounding jit's lowering (i32/i64 operand mismatches in
    the emitted calls). Under the compiled hot paths (``framework.jit32``)
    the whole trace is already 32-bit and this is a no-op."""
    import contextlib

    import jax

    if jax.default_backend() != "tpu":
        return contextlib.nullcontext()
    return jax.enable_x64(False)


def lookup(sig: str):
    """The cached value for ``sig`` (or None). Loads lazily on first use."""
    load()
    return CACHE.get(sig)
