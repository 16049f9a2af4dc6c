"""Plain reference of the GLM-5 decoder (``model_type: glm_moe_dsa``), as the
configuration ``configs/glm-5.2.json`` states it: DeepSeek-V2's latent
attention with a low-rank query (DeepSeek-V2, arXiv:2405.04434), DeepSeek
Sparse Attention's indexer and top-k (DeepSeek-V3.2-Exp), the selection shared
by the layers ``indexer_types`` marks ``shared``, and DeepSeek-V3's sigmoid
``noaux_tc`` routing (arXiv:2412.19437).

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no
kernel, no cache, no batching; attention in its expanded form (every head's K
and V made from the latent), one head at a time so that a 6k-token sequence
at the published widths fits beside the served model; the selection by
``jax.lax.top_k`` on the float32 index scores; the experts one after another
over all tokens. It imports nothing of the code under test and is what
decides ``correct``.

The equations, for token t with ``y_t = RMSNorm(x_t)`` in one layer:

1. ``cq_t = RMSNorm(y_t Wq_a)``; per head ``[q_nope | q_pe] = (cq_t Wq_b)_h``,
   rotary on ``q_pe``; ``[c_t | kpe_t] = y_t Wkv_a``, ``c <- RMSNorm(c)``,
   rotary on ``kpe``; per head ``k_nope = c Wkv_b^K``, ``v = c Wkv_b^V``;
   score ``(q_nope . k_nope + q_pe . kpe) * (nope + rope)^-1/2``; plain rotary
   frequencies ``theta^(-2i/d)`` (``rope_type: default``).
2. a layer with an indexer (``idx_*`` weights; ``indexer_types: full``):
   ``qI_j = (cq_t WqI)_j``, ``kI_s = LayerNorm(y_s WkI)`` with weight and
   bias, rotary on the first ``qk_rope_head_dim`` values of both, ``w_j =
   (y_t WwI)_j * heads^-1/2 * dim^-1/2``, ``I[t, s] = sum_j w_j relu(qI_j .
   kI_s)`` for ``s <= t``; ``S_t`` = the ``index_topk`` positions of largest
   ``I[t, s]`` (all while ``t < index_topk``; ties to the lower position).
3. attention's softmax runs over ``S_t`` alone.
4. a layer without an indexer (``shared``) uses the ``S_t`` of the nearest
   layer with one before it.
5. dense layers ``(silu(y W_g) * y W_u) W_d``; routed layers ``s = sigmoid(y
   W_r)`` over all published experts, the ``num_experts_per_tok`` largest of
   ``s + b`` (``moe_bias``: ``e_score_correction_bias``; ``n_group`` 1, no
   group limit), gates ``s_e / sum_chosen s * routed_scaling_factor``, gated
   SiLU experts, plus the shared expert for every token.

The chip's share: ``moe_w_gu`` / ``moe_w_d`` hold the experts
``experts_held_first .. + n_routed_experts`` of the published
``n_routed_experts_published``; what the absent experts would add is left
out (no stand-in), and the vocabulary is the slice the tree holds.

Departures from the published model, each also in the configuration file:
the Hadamard rotation the published inference code applies to ``qI`` and
``kI`` before quantising them to fp8 is orthogonal on both sides of a dot
product and is left out (index keys in the serving dtype); rotary pairs in
the half-split order on the stored columns (as ``reference/deepseek_v2.py``);
LayerNorm's epsilon is ``rms_norm_eps``; the multi-token-prediction module
(``num_nextn_predict_layers``) is a drafter beside the main forward pass and
is not run; seeded random weights; the depth and share the file states.

Weights are one tree, the layout the program serves from
(``paddle_tpu/models/glm_moe_dsa.py``): ``tok_emb lnf_g lm_head`` and
``stacks``, a tuple of stacks ``[n, ...]`` of equal layers in order. They
stay in the dtype they are served in and are widened a layer, a head and an
expert at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1024      # rows of a sequence one block of the wide MLPs takes


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope(x, positions, cfg):
    """``x [s, ..., d]`` rotated by its position, half-split pairing, plain
    frequencies."""
    d = x.shape[-1]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def select_topk(scores, causal, k):
    """Equation 2's ``S_t`` as a mask ``[s, s]``."""
    s = scores.shape[-1]
    _, chosen = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                              min(int(k), s))
    picked = jnp.zeros((s, s), bool).at[
        jnp.arange(s)[:, None], chosen].set(True)
    return picked & causal


def _index_scores(p, y, cq, cfg):
    s = y.shape[0]
    hi, di, rope = (cfg["index_n_heads"], cfg["index_head_dim"],
                    cfg["qk_rope_head_dim"])
    pos = jnp.arange(s)
    k = _layer_norm(_mm(y, p["idx_wk"]), p["idx_k_ln_g"], p["idx_k_ln_b"],
                    cfg["rms_norm_eps"])
    k = jnp.concatenate([_rope(k[:, :rope], pos, cfg), k[:, rope:]], -1)
    w = _mm(y, p["idx_ww"]) * (hi ** -0.5 * di ** -0.5)          # [s, hi]
    wq = p["idx_wq"].reshape(-1, hi, di)

    def add_head(j, total):
        q = _mm(cq, jax.lax.dynamic_index_in_dim(wq, j, 1, keepdims=False))
        q = jnp.concatenate([_rope(q[:, :rope], pos, cfg), q[:, rope:]], -1)
        dots = jnp.matmul(q, k.T, precision=_HI)
        return total + jax.lax.dynamic_index_in_dim(
            w, j, 1, keepdims=True) * jax.nn.relu(dots)

    return jax.lax.fori_loop(0, hi, add_head, jnp.zeros((s, s), jnp.float32))


def _attention(p, y, cq, selected, cfg):
    s = y.shape[0]
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd, r = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(s)
    ckv = _mm(y, p["wkv_a"])
    c = _rms_norm(ckv[:, :r], p["kv_ln_g"], cfg["rms_norm_eps"])
    k_pe = _rope(ckv[:, r:], pos, cfg)
    wq = p["wq_b"].reshape(-1, nh, nope + rope)
    wkv = p["wkv_b"].reshape(r, nh, nope + vd)
    wo = p["wo"].reshape(nh, vd, -1)
    scale = (nope + rope) ** -0.5

    def add_head(h, out):
        def of(w):
            return jax.lax.dynamic_index_in_dim(w, h, 1, keepdims=False)
        q = _mm(cq, of(wq))
        kv = _mm(c, of(wkv))
        scores = (jnp.matmul(q[:, :nope], kv[:, :nope].T, precision=_HI)
                  + jnp.matmul(_rope(q[:, nope:], pos, cfg), k_pe.T,
                               precision=_HI)) * scale
        probs = jax.nn.softmax(jnp.where(selected, scores, -jnp.inf), -1)
        o = jnp.matmul(probs, kv[:, nope:], precision=_HI)
        return out + _mm(o, jax.lax.dynamic_index_in_dim(
            wo, h, 0, keepdims=False))

    return jax.lax.fori_loop(
        0, nh, add_head, jnp.zeros((s, p["wo"].shape[-1]), jnp.float32))


def _gated_mlp(y, w_gu, w_d):
    def rows(block):
        gu = _mm(block, w_gu)
        half = gu.shape[-1] // 2
        return _mm(jax.nn.silu(gu[:, :half]) * gu[:, half:], w_d)

    s = y.shape[0]
    if s <= ROW_BLOCK:
        return rows(y)
    pad = -s % ROW_BLOCK
    blocks = jnp.pad(y, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, y.shape[1])
    return jax.lax.map(rows, blocks).reshape(-1, y.shape[1])[:s]


def _routed(p, y, cfg):
    scores = jax.nn.sigmoid(_mm(y, p["moe_gate"]))
    _, chosen = jax.lax.top_k(scores + p["moe_bias"].astype(jnp.float32),
                              cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, -1)
    if cfg.get("norm_topk_prob"):
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * cfg.get("routed_scaling_factor", 1.0)
    first = cfg.get("experts_held_first", 0)

    def one(w, e):
        # ``w``: (the whole stack [layers, held, ...], this layer): an expert
        # is read out of the stack by itself, never a layer's experts at once
        stack, layer = w
        return jax.lax.dynamic_slice(
            stack, (layer, e) + (0,) * (stack.ndim - 2),
            (1, 1) + stack.shape[2:])[0, 0]

    def add_expert(e, out):
        # held expert e is published expert first + e
        mine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        return out + mine[:, None] * _gated_mlp(
            y, one(p["moe_w_gu"], e), one(p["moe_w_d"], e))

    out = jax.lax.fori_loop(0, p["moe_w_gu"][0].shape[1], add_expert,
                            jnp.zeros_like(y))
    return out + _gated_mlp(y, p["sh_w_gu"], p["sh_w_d"])


@jax.jit
def _embed(tok_emb, ids):
    return jnp.take(tok_emb, ids, axis=0).astype(jnp.float32)


@jax.jit
def _head(rows, lm_head):
    return _mm(rows, lm_head)


def _layer(stack, i, x, selected, *, cfg_items, select):
    """Layer ``i`` of one stack on ``x [s, h]`` with the selection that
    reaches it: ``(x, the selection it used)``."""
    cfg = dict(cfg_items)
    cfg["rope_parameters"] = dict(cfg["rope_parameters"])
    p = {k: (v, i) if k in ("moe_w_gu", "moe_w_d")
         else jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
         for k, v in stack.items()}
    eps = cfg["rms_norm_eps"]
    y = _rms_norm(x, p["ln1_g"], eps)
    cq = _rms_norm(_mm(y, p["wq_a"]), p["q_ln_g"], eps)
    if "idx_wq" in p:
        causal = jnp.tril(jnp.ones((x.shape[0],) * 2, bool))
        selected = select(_index_scores(p, y, cq, cfg), causal,
                          cfg["index_topk"])
    x = x + _attention(p, y, cq, selected, cfg)
    y = _rms_norm(x, p["ln2_g"], eps)
    return x + (_routed(p, y, cfg) if "moe_gate" in p
                else _gated_mlp(y, p["w_gu"], p["w_d"])), selected


_layer_jit = jax.jit(_layer, static_argnames=("cfg_items", "select"))


def _hashable(cfg):
    """The configuration's numbers as something ``jax.jit`` can key on."""
    flat = dict(cfg)
    flat["rope_parameters"] = tuple(sorted(cfg["rope_parameters"].items()))
    return tuple(sorted((k, v) for k, v in flat.items()
                        if isinstance(v, (int, float, bool, tuple))))


def hidden(params, ids, cfg, select=select_topk):
    """Final-norm hidden states ``[s, h]`` of one sequence ``ids [s]`` and
    the selections ``[layers with an indexer, s, s]`` (bool); ``cfg`` holds
    the published keys. ``select(scores, causal, k)``: equation 2's choice
    (the controls of ``tools/`` pass another)."""
    x = _embed(params["tok_emb"], jnp.asarray(ids, jnp.int32))
    selected, made = None, []
    for stack in params["stacks"]:
        for i in range(stack["ln1_g"].shape[0]):
            x, selected = _layer_jit(stack, jnp.int32(i), x, selected,
                                     cfg_items=_hashable(cfg), select=select)
            if "idx_wq" in stack:
                made.append(selected)
    return _rms_norm(x, params["lnf_g"], cfg["rms_norm_eps"]), made


def logits_at(params, ids, positions, cfg, select=select_topk):
    """Next-token logits ``[len(positions), V]`` after ``ids[:p + 1]`` for
    each ``p`` of ``positions``, and ``[layers with an indexer,
    len(positions), s]`` bool, the keys those rows selected: ONE full forward
    over the (right-padded) sequence ``ids [s]``, read at the positions.
    Causality (of attention and of the selection) makes what follows a
    position irrelevant to it."""
    at = jnp.asarray(positions, jnp.int32)
    h, made = hidden(params, ids, cfg, select)
    return _head(h[at], params["lm_head"]), jnp.stack([m[at] for m in made])
