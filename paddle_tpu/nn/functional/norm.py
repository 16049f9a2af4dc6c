"""Normalization functionals (parity: python/paddle/nn/functional/norm.py).

Stats are computed in float32 regardless of input dtype (bf16-safe on TPU),
then cast back — the same accumulation-dtype discipline the reference's fused
kernels use.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...autograd.engine import apply_op
from ...tensor.tensor import Tensor


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(list(normalized_shape))
    # close over BOOLEANS, not the weight/bias Tensors: a Tensor in a closure
    # cell disables the eager executable cache (mutation hazard), which made
    # every eager layer_norm pay full uncached dispatch (~4 ms vs 125 us
    # on the chip, BENCH_OPS r5); the values themselves flow via rest
    has_w, has_b = weight is not None, bias is not None

    def fn(v, *rest):
        axes = tuple(range(v.ndim - n_axes, v.ndim))
        x32 = v.astype(jnp.float32)
        mean = jnp.mean(x32, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=axes, keepdims=True)
        out = (x32 - mean) / jnp.sqrt(var + epsilon)
        out = out.astype(v.dtype)
        i = 0
        if has_w:
            out = out * rest[i]
            i += 1
        if has_b:
            out = out + rest[i]
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return apply_op("layer_norm", fn, *args)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    def fn(v, *rest):
        x32 = v.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = (x32 * jnp.reciprocal(jnp.sqrt(var + epsilon))).astype(v.dtype)
        if rest:
            out = out * rest[0]
        return out

    args = [x] + ([weight] if weight is not None else [])
    return apply_op("rms_norm", fn, *args)


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-05,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    channel_axis = 1 if data_format.startswith("NC") else x._data.ndim - 1
    use_batch_stats = training and not use_global_stats
    has_w, has_b = weight is not None, bias is not None  # cacheable closure

    def fn(v, rm, rv, *rest):
        axes = tuple(i for i in range(v.ndim) if i != channel_axis)
        shape = [1] * v.ndim
        shape[channel_axis] = v.shape[channel_axis]
        x32 = v.astype(jnp.float32)
        if use_batch_stats:
            mean = jnp.mean(x32, axis=axes)
            var = jnp.var(x32, axis=axes)
        else:
            mean, var = rm.astype(jnp.float32), rv.astype(jnp.float32)
        out = (x32 - mean.reshape(shape)) / jnp.sqrt(var.reshape(shape) + epsilon)
        out = out.astype(v.dtype)
        i = 0
        if has_w:
            out = out * rest[i].reshape(shape)
            i += 1
        if has_b:
            out = out + rest[i].reshape(shape)
        return out, mean, var

    args = [x, running_mean, running_var] + [t for t in (weight, bias) if t is not None]
    out, batch_mean, batch_var = apply_op("batch_norm", fn, *args)

    if use_batch_stats:
        # update running stats (functional rebind, momentum convention:
        # running = momentum * running + (1 - momentum) * batch)
        n = x._data.size // x._data.shape[channel_axis]
        unbiased = batch_var._data * (n / max(n - 1, 1))
        running_mean._data = (
            momentum * running_mean._data + (1 - momentum) * batch_mean._data
        ).astype(running_mean._data.dtype)
        running_var._data = (
            momentum * running_var._data + (1 - momentum) * unbiased
        ).astype(running_var._data.dtype)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None, use_input_stats=True, momentum=0.9, eps=1e-05, data_format="NCHW", name=None):
    has_w, has_b = weight is not None, bias is not None  # cacheable closure

    def fn(v, *rest):
        axes = tuple(range(2, v.ndim))
        x32 = v.astype(jnp.float32)
        mean = jnp.mean(x32, axis=axes, keepdims=True)
        var = jnp.var(x32, axis=axes, keepdims=True)
        out = ((x32 - mean) / jnp.sqrt(var + eps)).astype(v.dtype)
        shape = [1, v.shape[1]] + [1] * (v.ndim - 2)
        i = 0
        if has_w:
            out = out * rest[i].reshape(shape)
            i += 1
        if has_b:
            out = out + rest[i].reshape(shape)
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return apply_op("instance_norm", fn, *args)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None, data_format="NCHW", name=None):
    has_w, has_b = weight is not None, bias is not None  # cacheable closure

    def fn(v, *rest):
        if data_format == "NCHW" or v.ndim == 2:
            n, c = v.shape[0], v.shape[1]
            spatial = v.shape[2:]
            g = v.reshape(n, num_groups, c // num_groups, *spatial)
            axes = tuple(range(2, g.ndim))
            x32 = g.astype(jnp.float32)
            mean = jnp.mean(x32, axis=axes, keepdims=True)
            var = jnp.var(x32, axis=axes, keepdims=True)
            out = ((x32 - mean) / jnp.sqrt(var + epsilon)).astype(v.dtype).reshape(v.shape)
            shape = [1, c] + [1] * len(spatial)
        else:  # NHWC
            n, c = v.shape[0], v.shape[-1]
            spatial = v.shape[1:-1]
            g = v.reshape(n, *spatial, num_groups, c // num_groups)
            axes = tuple(range(1, g.ndim - 2)) + (g.ndim - 1,)
            x32 = g.astype(jnp.float32)
            mean = jnp.mean(x32, axis=axes, keepdims=True)
            var = jnp.var(x32, axis=axes, keepdims=True)
            out = ((x32 - mean) / jnp.sqrt(var + epsilon)).astype(v.dtype).reshape(v.shape)
            shape = [1] * (v.ndim - 1) + [c]
        i = 0
        if has_w:
            out = out * rest[i].reshape(shape)
            i += 1
        if has_b:
            out = out + rest[i].reshape(shape)
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return apply_op("group_norm", fn, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    def fn(v):
        c_ax = 1 if data_format.startswith("NC") else v.ndim - 1
        sq = jnp.square(v)
        half = size // 2
        moved = jnp.moveaxis(sq, c_ax, -1)
        padded = jnp.pad(moved, [(0, 0)] * (moved.ndim - 1) + [(half, size - half - 1)])
        windows = jnp.stack([padded[..., i : i + moved.shape[-1]] for i in range(size)], -1)
        summed = jnp.sum(windows, axis=-1)
        div = jnp.power(k + alpha * summed / size, beta)
        return v / jnp.moveaxis(div, -1, c_ax)

    return apply_op("local_response_norm", fn, x)
