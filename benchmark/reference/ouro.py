"""Plain reference of the Ouro looped decoder (``ByteDance/Ouro-2.6B``), as
the configuration ``configs/ouro-2.6b.json`` states it: one stack of
sandwich-normed decoder layers run ``total_ut_steps`` times per token.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no
kernel, no cache, no batching; one layer at a time over the whole sequence,
its weights widened for it, so that a 2k-token sequence at the published
widths fits beside the served model. It imports nothing of the code under
test and is what decides ``correct``.

The equations, for the token at position p, ``RMS(x; g) = x / sqrt(mean(x^2)
+ rms_norm_eps) * g``, on every loop t = 0 .. total_ut_steps - 1 and layer l
= 0 .. L - 1:

1. ``y = RMS(x; ln1_g)``; ``q, k, v = y Wq, y Wk, y Wv`` as
   ``num_attention_heads`` heads of ``head_dim`` (as many key-value heads; no
   bias, no query/key norm); rotary on q and k over the whole head in
   HALF-SPLIT pairs ``(x[j], x[j + d/2])`` at ``rope_theta^(-2j/d)``, by the
   token's own position (the same in every loop).
2. Causal softmax over ``q.k / sqrt(head_dim)``: loop t of layer l reads the
   keys and values loop t of layer l made for positions 0 .. p (the served
   cache's plane ``t * L + l``).
3. ``h = x + RMS(attn Wo; ln1b_g)``.
4. ``x' = h + RMS((silu(z W_g) * (z W_u)) W_d; ln2b_g)``, ``z = RMS(h;
   ln2_g)``.
5. After layer L - 1 of each loop: ``x = RMS(x; lnf_g)``. After the last loop
   ``logits = x W_head`` (untied).

Readings that are not a key's plain meaning (each also under ``assumed`` in
the configuration file): the sandwich norms of steps 3 and 4; that the state
between loops is the normed one (step 5); no biases and no query/key norm;
``early_exit_threshold`` 1.0 runs every loop.

Weights are the tree the program serves from (``paddle_tpu/models/ouro.py``):
``tok_emb lnf_g lm_head`` and ``layers``, stacked ``[L, ...]``; ``wqkv = [Wq
| Wk | Wv]``, ``w_gu = [W_g | W_u]``. They stay in the dtype they are served
in and are widened a layer at a time.

``variant`` (the controls, ``tools/looped_controls.py``): a tuple of changes,
each named: ``one_loop`` (the stack once), ``no_loop_norm`` (no norm between
loops; the last loop's, the head's input, stays), ``no_post_norms`` (no norm
after the sublayers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
VARIANTS = ("one_loop", "no_loop_norm", "no_post_norms")


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, positions, theta):
    """``x [s, heads, d]`` rotated by position in half-split pairs."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]                       # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "eps",
                                             "theta", "post_norms"))
def _layer(x, p, positions, *, heads, head_dim, eps, theta, post_norms):
    """One layer over the whole sequence ``x [s, h]`` float32: ``(x', k,
    v)``, the keys and values it made ``[s, heads, head_dim]``."""
    s = x.shape[0]
    qkv = _mm(_rms(x, p["ln1_g"], eps), p["wqkv"]).reshape(
        s, 3, heads, head_dim)
    q = _rope(qkv[:, 0], positions, theta)
    k = _rope(qkv[:, 1], positions, theta)
    v = qkv[:, 2]
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) \
        / np.sqrt(head_dim)
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI).reshape(
        s, heads * head_dim)
    o = _mm(attn, p["wo"])
    h = x + (_rms(o, p["ln1b_g"], eps) if post_norms else o)
    gu = _mm(_rms(h, p["ln2_g"], eps), p["w_gu"])
    half = gu.shape[-1] // 2
    f = _mm(jax.nn.silu(gu[:, :half]) * gu[:, half:], p["w_d"])
    return h + (_rms(f, p["ln2b_g"], eps) if post_norms else f), k, v


def hidden(params, ids, cfgj, variant=(), kv=None):
    """The head's input ``[s, h]`` float32 for token ids ``ids [s]``.
    ``kv``: a list that gets, per loop and then per layer, the ``(k, v)``
    that (loop, layer) made."""
    unknown = set(variant) - set(VARIANTS)
    if unknown:
        raise ValueError(f"unknown variant {sorted(unknown)}")
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    eps = float(cfgj["rms_norm_eps"])
    how = dict(heads=int(cfgj["num_attention_heads"]),
               head_dim=int(cfgj["head_dim"]), eps=eps,
               theta=float(cfgj["rope_theta"]),
               post_norms="no_post_norms" not in variant)
    loops = 1 if "one_loop" in variant else int(cfgj["total_ut_steps"])
    stack = params["layers"]
    n = stack["ln1_g"].shape[0]
    x = jnp.take(params["tok_emb"], ids, axis=0).astype(jnp.float32)
    for t in range(loops):
        for layer in range(n):
            x, k, v = _layer(x, {key: w[layer] for key, w in stack.items()},
                             positions, **how)
            if kv is not None:
                kv.append((k, v))
        if t == loops - 1 or "no_loop_norm" not in variant:
            x = _rms(x, params["lnf_g"], eps)
    return x


def logits_at(params, ids, positions, cfgj, variant=()):
    """Next-token logits ``[len(positions), vocab]`` float32 at the given
    positions of token ids ``ids [s]`` (causal: a position sees itself and
    those before it)."""
    x = hidden(params, ids, cfgj, variant)
    rows = x[jnp.asarray(positions, jnp.int32)]
    return _mm(rows, params["lm_head"])
