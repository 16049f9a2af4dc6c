"""Plain reference of the DeepSeek-V2 decoder (DeepSeek-AI, "DeepSeek-V2: A
Strong, Economical, and Efficient Mixture-of-Experts Language Model",
arXiv:2405.04434; ``model_type: deepseek_v2``), as the configuration
``configs/deepseek-v2-lite.json`` states it.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no
kernel, no cache, no batching, attention in its expanded form only (every
head's K and V made from the latent), routing by ``jax.lax.top_k`` on the
float32 softmax scores, the experts one after another over all tokens. It is
independent of the code under test (it imports nothing of it) and is what
decides ``correct``.

The equations. Pre-norm residual block, RMSNorm with a weight alone, no bias
anywhere, final RMSNorm, untied head.

- attention (``q_lora_rank`` null): ``q = x W_q``, per head ``[q_nope |
  q_pe]``; ``x W_kv_a = [c | k_pe]``, ``c <- RMSNorm(c)``; ``c W_kv_b``, per
  head ``[k_nope | v]``; rotary on ``q_pe`` and on the one ``k_pe`` all heads
  share, YaRN frequencies; scores ``(q_nope . k_nope + q_pe . k_pe) *
  head_dim^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
  causal softmax; ``o = p v``; ``[heads x v] W_o``.
- the leading ``first_k_dense_replace`` layers: ``(silu(x W_g) * x W_u) W_d``.
- the other layers: ``s = softmax(x W_r)`` in float32 over all routed
  experts; the ``num_experts_per_tok`` largest (ties to the lowest index);
  their weights are those entries of ``s`` as they are (``norm_topk_prob``
  false) times ``routed_scaling_factor``; ``sum_k s_k expert_k(x)``, each
  expert the gated SiLU MLP at ``moe_intermediate_size``; plus the gated SiLU
  MLP at ``n_shared_experts * moe_intermediate_size`` for every token. No
  token is dropped.

Weights are one tree, the layout the program serves from (its docstring,
``paddle_tpu/models/deepseek_v2.py``): ``tok_emb [V, h]``, ``lnf_g``,
``lm_head [h, V]``, and per stack (``dense_layers [Ld, ...]``, ``layers [Lm,
...]``) ``ln1_g wq wkv_a kv_ln_g wkv_b wo ln2_g`` with ``w_gu w_d`` or
``moe_gate moe_w_gu moe_w_d sh_w_gu sh_w_d``; a ``*_gu`` matrix is ``[W_g |
W_u]``. The weights stay in the dtype they are served in and are widened a
layer, and an expert, at a time.

Departures from the published model: none in the mathematics. Seeded random
weights; the depth the configuration file states; rotary pairs in the
half-split order applied to the stored columns (the checkpoint stores them
interleaved and permutes first: the same function under seeded weights, and
the program makes the same choice).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg):
    """Rotary frequencies ``[qk_rope_head_dim / 2]`` under ``rope_scaling``
    (YaRN): dimension i turns ``original_max / (2 pi theta^(2i/d))`` times
    over the original context; those that turn more than ``beta_fast`` times
    keep ``theta^(-2i/d)``, those that turn fewer than ``beta_slow`` times
    are divided by ``factor``, linear in i between."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return plain

    def dim_that_turns(n):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_that_turns(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_that_turns(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    slow = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - slow) + plain / rs["factor"] * slow


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, positions, cfg):
    """``x [s, ..., d]`` rotated by its position, half-split pairing."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg), jnp.float32)
    rs = cfg.get("rope_scaling") or {}
    mag = (_mscale(rs["factor"], rs.get("mscale", 1.0))
           / _mscale(rs["factor"], rs.get("mscale_all_dim", 0.0))
           if rs else 1.0)
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],))
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) * mag + rot * jnp.sin(ang) * mag


def _attention(p, y, cfg):
    s = y.shape[0]
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd, r = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(s)
    q = _mm(y, p["wq"]).reshape(s, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, cfg)
    ckv = _mm(y, p["wkv_a"])
    c = _rms_norm(ckv[:, :r], p["kv_ln_g"], cfg["rms_norm_eps"])
    k_pe = _rope(ckv[:, r:], pos, cfg)
    kv = _mm(c, p["wkv_b"]).reshape(s, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=_HI)
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe, precision=_HI))
    scores = scores * softmax_scale(cfg)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                   precision=_HI)
    return _mm(o.reshape(s, nh * vd), p["wo"])


def _gated_mlp(y, w_gu, w_d):
    gu = _mm(y, w_gu)
    half = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[:, :half]) * gu[:, half:], w_d)


def _routed(p, y, cfg):
    scores = jax.nn.softmax(_mm(y, p["moe_gate"]), -1)
    weight, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * cfg.get("routed_scaling_factor", 1.0)

    def add_expert(e, out):
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)   # [s]
        w_gu = jax.lax.dynamic_index_in_dim(p["moe_w_gu"], e, keepdims=False)
        w_d = jax.lax.dynamic_index_in_dim(p["moe_w_d"], e, keepdims=False)
        return out + mine[:, None] * _gated_mlp(y, w_gu, w_d)

    out = jax.lax.fori_loop(0, p["moe_gate"].shape[-1], add_expert,
                            jnp.zeros_like(y))
    return out + _gated_mlp(y, p["sh_w_gu"], p["sh_w_d"])


@jax.jit
def _embed(tok_emb, ids):
    return jnp.take(tok_emb, ids, axis=0).astype(jnp.float32)


@jax.jit
def _head(row, lm_head):
    return _mm(row, lm_head)


def _layer(stack, i, x, *, cfg_items):
    """Layer ``i`` of one stack on ``x [s, h]``: only this layer's weights
    are read, and of its experts one at a time."""
    cfg = _config(cfg_items)
    p = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
         for k, v in stack.items()}
    eps = cfg["rms_norm_eps"]
    x = x + _attention(p, _rms_norm(x, p["ln1_g"], eps), cfg)
    y = _rms_norm(x, p["ln2_g"], eps)
    return x + (_routed(p, y, cfg) if "moe_gate" in p
                else _gated_mlp(y, p["w_gu"], p["w_d"]))


_layer_jit = jax.jit(_layer, static_argnames=("cfg_items",))


def _hashable(cfg):
    """The configuration's numbers as something ``jax.jit`` can key on."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, bool, dict, type(None)))))


def _config(items):
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in items}


def hidden(params, ids, cfg):
    """Final-norm hidden states ``[s, h]`` of one sequence ``ids [s]``;
    ``cfg`` holds the published keys (``rope_scaling`` a dict or None)."""
    x = _embed(params["tok_emb"], jnp.asarray(ids, jnp.int32))
    for group in ("dense_layers", "layers"):
        stack = params.get(group)
        if stack is None:
            continue
        for i in range(stack["ln1_g"].shape[0]):
            x = _layer_jit(stack, jnp.int32(i), x, cfg_items=_hashable(cfg))
    return _rms_norm(x, params["lnf_g"], cfg["rms_norm_eps"])


def logits_at(params, ids, position, cfg):
    """Next-token logits ``[V]`` after ``ids[:position + 1]``: the full
    forward over one (right-padded) sequence ``ids [s]``, read at
    ``position``. Causality makes the padding after it irrelevant."""
    return _head(hidden(params, ids, cfg)[position], params["lm_head"])
