"""paddle_tpu: a TPU-native deep-learning framework with the PaddlePaddle
feature surface, built on JAX/XLA/Pallas (see /root/repo/SURVEY.md for the
capability blueprint into the reference).

Public API mirrors `paddle.*`: tensor ops at top level, plus `nn`, `optimizer`,
`amp`, `io`, `jit`, `static`, `autograd`, `distributed`, `linalg`, `fft`,
`metric`, `vision`, `distribution`, `incubate`, `profiler`, `sparse`.
"""
from __future__ import annotations

__version__ = "0.1.0"

import jax as _jax

# Paddle semantics: int64/float64 are real dtypes (to_tensor of python ints is
# int64 — reference python/paddle/tensor/creation.py), and float32 math is true
# float32 (low-precision compute is opt-in via AMP/bf16 dtypes, not silent).
_jax.config.update("jax_enable_x64", True)
_jax.config.update("jax_default_matmul_precision", "highest")

from . import framework
from .framework import (  # dtypes & device & rng
    CPUPlace,
    CustomPlace,
    DType,
    Place,
    TPUPlace,
    bfloat16,
    bool_,
    complex64,
    complex128,
    device_count,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_device,
    get_flags,
    get_rng_state,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_cuda,
    seed,
    set_default_dtype,
    set_device,
    set_flags,
    set_rng_state,
    uint8,
)

from . import autograd
from .autograd import enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled

from . import tensor
from .tensor import Parameter, Tensor
from .tensor.creation import *  # noqa: F401,F403
from .tensor.math import *  # noqa: F401,F403
from .tensor.manipulation import *  # noqa: F401,F403
from .tensor.logic import *  # noqa: F401,F403
from .tensor.search import *  # noqa: F401,F403
from .tensor.stat import *  # noqa: F401,F403
from .tensor.random import *  # noqa: F401,F403
from .tensor.inplace import *  # noqa: F401,F403  module-level op_ spellings
from .tensor.einsum import einsum
from .tensor import linalg
from .tensor.linalg import cdist, cross, dist  # top-level parity re-exports
from .tensor.tensor import set_printoptions
from .framework.dtype import DType as dtype, finfo, iinfo  # noqa: A001
from .framework.param_attr import ParamAttr
from .batch_reader import batch
from . import fft


def pdist(x, p=2.0, name=None):
    """Top-level re-export of nn.functional.pdist (reference exports both)."""
    from .nn.functional import pdist as _pdist

    return _pdist(x, p=p, name=name)


# CUDA-compat aliases: the reference exports these at top level; on the TPU
# backend the device RNG/state is singular, so the cuda-spelled entry points
# are honest aliases of the device-generic ones (SURVEY §1: one device axis).
def get_cuda_rng_state():
    return get_rng_state()


def set_cuda_rng_state(state):
    return set_rng_state(state)


def disable_signal_handler():
    """Reference parity (paddle.disable_signal_handler): the reference
    uninstalls its C++ fault handlers. This runtime installs none, so there
    is nothing to disable — documented no-op."""


class CUDAPlace(TPUPlace):
    """Compat alias: reference code says CUDAPlace(n); the accelerator here
    is the TPU, so this is the TPU place under the CUDA-compat name."""


class CUDAPinnedPlace(CPUPlace):
    """Compat alias: pinned-host memory staging place; host memory on this
    runtime is the CPU place."""

# Subpackages (populated as layers come online; see SURVEY.md §7.2 build order).
# Imported lazily-but-eagerly here; each block is enabled as the layer lands.
import importlib as _importlib


def __getattr__(name):
    # Lazy subpackage import (PEP 562): keeps core import fast and lets
    # subpackages import the core without cycles.
    _subpackages = {
        "nn",
        "optimizer",
        "amp",
        "io",
        "jit",
        "static",
        "distributed",
        "metric",
        "models",
        "device",
        "vision",
        "distribution",
        "incubate",
        "observability",
        "profiler",
        "sparse",
        "hapi",
        "utils",
        "inference",
        "quantization",
        "audio",
        "text",
        "onnx",
        "signal",
        "geometric",
    }
    if name in _subpackages:
        return _importlib.import_module(f".{name}", __name__)
    if name in ("save", "load"):
        mod = _importlib.import_module(".framework_io", __name__)
        return getattr(mod, name)
    if name == "Layer":
        return _importlib.import_module(".nn", __name__).Layer
    if name == "DataParallel":
        return _importlib.import_module(".distributed", __name__).DataParallel
    if name == "Model":
        return _importlib.import_module(".hapi", __name__).Model
    if name == "summary":
        return _importlib.import_module(".hapi", __name__).summary
    if name == "flops":
        return _importlib.import_module(".hapi", __name__).flops
    if name == "create_parameter":
        return _importlib.import_module(".static.misc", __name__).create_parameter
    if name == "LazyGuard":
        return _importlib.import_module(
            ".nn.initializer.lazy_init", __name__).LazyGuard
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")

# `bool` dtype alias must not shadow the builtin during module definition;
# expose it last under the paddle spelling.
bool = bool_  # noqa: A001

def enable_static():
    """Enter static graph mode: ops record into the default main Program
    (executed later by paddle_tpu.static.Executor as one XLA step)."""
    from .static import program as _static_program

    _static_program.enable_static()


def disable_static():
    from .static import program as _static_program

    _static_program.disable_static()


def in_dynamic_mode() -> bool:
    from .static import program as _static_program

    return not _static_program.in_static_mode()


def is_grad_enabled_():
    return is_grad_enabled()
