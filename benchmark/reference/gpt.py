"""Plain reference of the GPT-2/GPT-3 style decoder the benchmark's
configurations describe (Radford et al. 2019; Brown et al. 2020; the block
Cerebras-GPT uses, arXiv:2304.03208 section 2).

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no
kernel, no cache, no batching tricks, no sharding. It is independent of the
code under test and is what decides ``correct``.

Weights are one tree, the layout both program paths already share:

    tok_emb [V, h]   pos_emb [S, h]   lnf_g, lnf_b [h]
    layers: ln1_g ln1_b [L, h]  wqkv [L, h, 3h]  bqkv [L, 3h]  wo [L, h, h]
            bo [L, h]  ln2_g ln2_b [L, h]  w1 [L, h, f]  b1 [L, f]
            w2 [L, f, h]  b2 [L, h]

The fused projection's columns are ordered (q | k | v), heads within each.
The output head is tied to ``tok_emb``.

Departure from the published description, noted here and in the
configuration files: GELU is computed in its tanh approximation, because that
is the only form the program computes; Cerebras-GPT states exact GELU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, num_heads, eps):
    """One pre-LN block on ``x`` [b, s, h]."""
    b, s, h = x.shape
    hd = h // num_heads
    y = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = jnp.matmul(y, p["wqkv"], precision=_HI) + p["bqkv"]
    q, k, v = (t.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k, precision=_HI)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bnqk,bnkd->bnqd", attn, v, precision=_HI)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + jnp.matmul(o, p["wo"], precision=_HI) + p["bo"]
    y = _layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    y = _gelu_tanh(jnp.matmul(y, p["w1"], precision=_HI) + p["b1"])
    return x + jnp.matmul(y, p["w2"], precision=_HI) + p["b2"]


def hidden(params, ids, *, num_heads, eps):
    """Final-LayerNorm hidden states [b, s, h] of token ids [b, s]."""
    p = _f32(params)
    s = ids.shape[1]
    x = jnp.take(p["tok_emb"], ids, axis=0) + p["pos_emb"][:s]
    num_layers = p["layers"]["wqkv"].shape[0]
    for i in range(num_layers):
        layer = {k: v[i] for k, v in p["layers"].items()}
        x = _block(layer, x, num_heads, eps)
    return _layer_norm(x, p["lnf_g"], p["lnf_b"], eps)


def logits_at(params, ids, position, *, num_heads, eps):
    """Next-token logits [V] after ``ids[0, :position + 1]``: the full
    forward over one (right-padded) sequence [1, s], read at ``position``.
    Causality makes the padding after ``position`` irrelevant."""
    h = hidden(params, ids, num_heads=num_heads, eps=eps)[0]
    row = jax.lax.dynamic_index_in_dim(h, position, axis=0, keepdims=False)
    return jnp.matmul(params["tok_emb"].astype(jnp.float32), row,
                      precision=_HI)


def loss(params, ids, labels, *, num_heads, eps):
    """Mean next-token cross entropy: position t predicts ``labels[t + 1]``,
    the last position has no target."""
    h = hidden(params, ids, num_heads=num_heads, eps=eps)
    emb = params["tok_emb"].astype(jnp.float32)
    lg = jnp.einsum("bsh,vh->bsv", h[:, :-1], emb, precision=_HI)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def from_stages(params):
    """The training tree (layer stacks ``[pp, layers_per_stage, ...]`` under
    ``stages``) as this module's tree (``[L, ...]`` under ``layers``)."""
    out = {k: v for k, v in params.items() if k != "stages"}
    out["layers"] = {k: v.reshape((-1,) + v.shape[2:])
                     for k, v in params["stages"].items()}
    return out
