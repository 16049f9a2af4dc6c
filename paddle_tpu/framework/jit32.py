"""The 32-bit scope of the compiled hot paths.

``import paddle_tpu`` turns ``jax_enable_x64`` on: Paddle semantics make
int64 / float64 real dtypes for the eager tensor API. The compiled hot
paths — the serving steps (``models/gpt.py``) and the SPMD train step
(``models/gpt_spmd.py``) — run in 32-bit mode instead. Every array they
take is explicitly typed, Mosaic lowers no 64-bit scalar, and 64-bit index
arithmetic costs device time and memory. That choice is a property of the
path, made here once: whoever calls the builders (a user, ``chip_smoke.py``,
the bench scripts) gets the same program, with no process-wide config flip.
"""
from __future__ import annotations

import functools

import jax


def jit32(fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` whose trace, lowering and every call
    happen with x64 off (the config is part of jit's cache key, so the
    scope must wrap the call, not sit inside the traced body). The result
    is a plain function carrying ``.lower`` — attributes may be set on it
    like on a jit object."""
    jitted = jax.jit(fn, **jit_kwargs)

    @functools.wraps(fn)
    def call(*args):
        with jax.enable_x64(False):
            return jitted(*args)

    def lower(*args):
        with jax.enable_x64(False):
            return jitted.lower(*args)

    call.lower = lower
    return call
