"""Plain reference of the Cohere2 decoder with routed experts (``model_type:
cohere2_moe``), as the configuration ``configs/command-a-plus-05-2026.json``
states it: the published Cohere2 PARALLEL block (one weight-only LayerNorm
feeds attention and the expert layer, both added to the residual) over
grouped-query attention whose layers are rotary WINDOW layers or position-free
FULL layers, with sigmoid-routed experts beside four AVERAGED shared experts.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no kernel,
no cache, no batching; attention one query head at a time over the whole
sequence (its key-value head projected again for it), so that a 9k-token
sequence at the published widths fits beside the served model; the experts one
after another over all tokens, the shared experts one by one. It imports
nothing of the code under test and is what decides ``correct``.

The equations, for token t at position t with ``y_t = LN(x_t)``, ``LN(x) = (x
- mean) / sqrt(var + layer_norm_eps) * g`` (a weight, no bias):

1. ``q = y Wq`` as ``num_attention_heads`` heads of ``head_dim``, ``k = y Wk``
   and ``v = y Wv`` as ``num_key_value_heads`` heads; query head i reads
   key-value head ``i // (heads / kv heads)``. No bias, no query/key norm.
2. a ``sliding_attention`` layer: rotary on q and k over the whole head,
   INTERLEAVED pairs ``(x[2j], x[2j+1])`` at ``theta^(-2j/d)``
   (``rope_gptj``, ``rotary_pct`` 1); the row at position p sees keys ``p -
   sliding_window + 1 .. p``. A ``full_attention`` layer: NO positional
   encoding, causal mask. Scores over ``sqrt(head_dim)``, softmax, ``a =
   concat(heads) Wo``.
3. experts (every layer): ``s = sigmoid(y W_r)`` over all published experts,
   the ``num_experts_per_tok`` largest, gates ``s_e / sum_chosen s``;
   ``E(y) = (silu(y W_g) * (y W_u)) W_d``; ``routed = sum gate_e E_e(y)``;
   ``shared = (1 / num_shared_experts) sum_j S_j(y)``; ``m = routed +
   shared``.
4. ``x' = x + a + m``. After the last layer ``LN``, then ``logits = LN(x) E^T
   * logit_scale`` with the embedding tied.

The chip's share: ``moe_w_gu`` / ``moe_w_d`` hold the experts
``experts_held_first .. + num_experts`` of the published
``num_experts_published``; what the absent experts would add is left out (no
stand-in), and the vocabulary is the slice the tree holds.

Readings that are not a key's plain meaning (each also under ``assumed`` in the
configuration file): the window counts the row itself; the rotary pairing;
the mean of the four shared experts added to the routed sum;
``intermediate_size`` as one expert's width. Not run: the vision tower.

Weights are one tree, the layout the program serves from
(``paddle_tpu/models/cohere2_moe.py``): ``tok_emb lnf_g`` and ``stacks``, a
tuple with one entry per run of equal layers, stacked ``[n, ...]`` or, a run of
one layer, unstacked; ``wqkv = [Wq | Wk | Wv]``, ``moe_w_gu = [W_g | W_u]`` per
held expert, ``sh_w_gu`` / ``sh_w_d`` the four shared experts side by side.
They stay in the dtype they are served in and are widened a head and an expert
at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1024      # rows of a sequence one block of an expert takes


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)


def _layer_norm(x, g, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, theta):
    """``x [s, d]``, row i at position i, rotated in interleaved pairs."""
    s, d = x.shape
    inv = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    pairs = x.reshape(s, d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)], -1).reshape(s, d)


def _attention(p, y, window, theta, cfg):
    """Equations 1 and 2 over one sequence: ``window`` keys a row (None: all
    before it), rotary at ``theta`` (None: no positions)."""
    s = y.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    wq = p["wqkv"][:, :nh * hd].reshape(-1, nh, hd)
    wk = p["wqkv"][:, nh * hd:(nh + nkv) * hd].reshape(-1, nkv, hd)
    wv = p["wqkv"][:, (nh + nkv) * hd:].reshape(-1, nkv, hd)
    wo = p["wo"].reshape(nh, hd, -1)
    pos = jnp.arange(s)
    seen = pos[:, None] >= pos[None, :]
    if window is not None:
        seen &= pos[:, None] - pos[None, :] < window

    def add_head(h, out):
        def of(w, i):
            return jax.lax.dynamic_index_in_dim(w, i, 1, keepdims=False)
        q, k = _mm(y, of(wq, h)), _mm(y, of(wk, h // (nh // nkv)))
        v = _mm(y, of(wv, h // (nh // nkv)))
        if theta is not None:
            q, k = _rope(q, theta), _rope(k, theta)
        scores = jnp.matmul(q, k.T, precision=_HI) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        o = jnp.matmul(probs, v, precision=_HI)
        return out + _mm(o, jax.lax.dynamic_index_in_dim(
            wo, h, 0, keepdims=False))

    return jax.lax.fori_loop(
        0, nh, add_head, jnp.zeros((s, p["wo"].shape[-1]), jnp.float32))


def _gated_mlp(y, w_g, w_u, w_d):
    def rows(block):
        return _mm(jax.nn.silu(_mm(block, w_g)) * _mm(block, w_u), w_d)

    s = y.shape[0]
    if s <= ROW_BLOCK:
        return rows(y)
    pad = -s % ROW_BLOCK
    blocks = jnp.pad(y, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, y.shape[1])
    return jax.lax.map(rows, blocks).reshape(-1, y.shape[1])[:s]


def _experts(p, y, cfg):
    """Equation 3: the held routed experts' part and the shared mean."""
    f = cfg["intermediate_size"]
    scores = jax.nn.sigmoid(_mm(y, p["moe_gate"]))
    k = cfg["num_experts_per_tok"]
    # the k largest, and the next one: how near a token's choice was to
    # falling otherwise is what ``logits_at(routing=)`` hands back
    ranked = jax.lax.top_k(scores, k + 1)
    weight, chosen = ranked[0][:, :k], ranked[1][:, :k]
    if cfg.get("norm_topk_prob"):
        weight = weight / weight.sum(-1, keepdims=True)
    first = cfg.get("experts_held_first", 0)

    def one(w, e):
        # ``w``: (the whole stack [layers, held, ...], this layer): an expert
        # is read out of the stack by itself, never a layer's experts at once
        stack, layer = w
        at = [jnp.asarray(i, jnp.int32)
              for i in (layer, e) + (0,) * (stack.ndim - 2)]
        return jax.lax.dynamic_slice(stack, at,
                                     (1, 1) + stack.shape[2:])[0, 0]

    def add_expert(e, out):
        # held expert e is published expert first + e
        mine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        gu = one(p["moe_w_gu"], e)
        return out + mine[:, None] * _gated_mlp(
            y, gu[:, :f], gu[:, f:], one(p["moe_w_d"], e))

    out = jax.lax.fori_loop(0, p["moe_w_gu"][0].shape[1], add_expert,
                            jnp.zeros_like(y))
    n = cfg["num_shared_experts"]
    shared = jnp.zeros_like(y)
    for j in range(n):            # the four shared experts, one by one
        shared = shared + _gated_mlp(
            y, p["sh_w_gu"][:, j * f:(j + 1) * f],
            p["sh_w_gu"][:, (n + j) * f:(n + j + 1) * f],
            p["sh_w_d"][j * f:(j + 1) * f])
    return out + shared / n, ranked


@jax.jit
def _embed(tok_emb, ids):
    return jnp.take(tok_emb, ids, axis=0).astype(jnp.float32)


@jax.jit
def _head(rows, tok_emb):
    return jnp.matmul(rows, tok_emb.astype(jnp.float32).T, precision=_HI)


def _layer(stack, i, x, *, cfg_items, window, theta):
    """Layer ``i`` of one run (``i`` None: the run is one unstacked layer) on
    ``x [s, h]``; beside it every token's ``num_experts_per_tok + 1`` largest
    router scores and their experts, in order."""
    cfg = dict(cfg_items)
    whole = ("moe_w_gu", "moe_w_d")
    if i is None:
        p = {k: (v[None], 0) if k in whole else v for k, v in stack.items()}
    else:
        p = {k: (v, i) if k in whole
             else jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
             for k, v in stack.items()}
    y = _layer_norm(x, p["ln1_g"], cfg["layer_norm_eps"])
    m, ranked = _experts(p, y, cfg)
    return x + _attention(p, y, window, theta, cfg) + m, ranked


_layer_jit = jax.jit(_layer, static_argnames=("cfg_items", "window", "theta"))


def _hashable(cfg):
    """The configuration's numbers as something ``jax.jit`` can key on."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


def layer_rule(kind, cfg):
    """Equation 2's ``(window, rotary theta)`` of a layer kind. The controls
    of ``tools/`` pass another rule."""
    if kind == "sliding_attention":
        return cfg["sliding_window"], cfg["rope_parameters"]["rope_theta"]
    return None, None


def hidden(params, ids, cfg, rule=layer_rule, routing=None):
    """Final-norm hidden states ``[s, h]`` of one sequence ``ids [s]``;
    ``cfg`` holds the published keys. ``routing``: a list that is handed each
    layer's ranked router scores and experts, ``([s, k + 1], [s, k + 1])``."""
    x = _embed(params["tok_emb"], jnp.asarray(ids, jnp.int32))
    kinds = iter(cfg["layer_types"])
    for stack in params["stacks"]:
        single = stack["ln1_g"].ndim == 1
        for i in range(1 if single else stack["ln1_g"].shape[0]):
            window, theta = rule(next(kinds), cfg)
            x, ranked = _layer_jit(stack, None if single else jnp.int32(i),
                                   x, cfg_items=_hashable(cfg), window=window,
                                   theta=theta)
            if routing is not None:
                routing.append(ranked)
    return _layer_norm(x, params["lnf_g"], cfg["layer_norm_eps"])


def logits_at(params, ids, positions, cfg, rule=layer_rule, routing=None):
    """Next-token logits ``[len(positions), V]`` after ``ids[:p + 1]`` for
    each ``p`` of ``positions``: ONE full forward over the (right-padded)
    sequence ``ids [s]``, read at the positions. Causality makes what follows
    a position irrelevant to it. ``routing``: a list that is handed, a layer
    an entry, the positions' ranked router scores and experts
    (``num_experts_per_tok + 1`` of each: the chosen and the runner-up)."""
    at = jnp.asarray(positions, jnp.int32)
    ranked = None if routing is None else []
    h = hidden(params, ids, cfg, rule, ranked)
    if routing is not None:
        routing.extend((np.asarray(s[at]), np.asarray(e[at]))
                       for s, e in ranked)
    return _head(h[at], params["tok_emb"]) * cfg.get("logit_scale", 1)
