"""Plain reference of the SmallThinker decoder
(``PowerInfer/SmallThinker-21BA3B-Instruct``), as the configuration
``configs/smallthinker-21ba3b-instruct.json`` states it: a pre-norm block of
two RMSNorms over grouped-query attention whose layers are rotary WINDOW layers
or position-free FULL layers, and routed ReGLU experts whose router reads the
PRE-ATTENTION norm.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``: no kernel,
no cache, no batching; attention one query head at a time over the whole
sequence (its key-value head projected again for it), the experts one after
another over all tokens, the head a block of the vocabulary at a time, so that
a 6k-token sequence at the published widths fits beside the served model. It
imports nothing of the code under test and is what decides ``correct``.

The equations, for token t at position t, ``RMS(x; g) = x / sqrt(mean(x^2) +
rms_norm_eps) * g``:

1. ``y1 = RMS(x; ln1_g)``.
2. ``q = y1 Wq`` as ``num_attention_heads`` heads of ``head_dim``, ``k = y1
   Wk`` and ``v = y1 Wv`` as ``num_key_value_heads`` heads; query head i reads
   key-value head ``i // (heads / kv heads)``. No bias, no query/key norm. A
   layer i with ``rope_layout[i]`` and ``sliding_window_layout[i]`` 1: rotary
   on q and k over the whole head in HALF-SPLIT pairs ``(x[j], x[j + d/2])``
   at ``theta^(-2j/d)``, and the row at position p sees keys ``p -
   sliding_window_size + 1 .. p``; both 0: NO positional encoding, causal
   mask. Scores over ``sqrt(head_dim)``, softmax, ``h = x + concat(heads)
   Wo``.
3. ``y2 = RMS(h; ln2_g)``.
4. ``r = y1 W_r`` over ``moe_num_primary_experts``; the
   ``moe_num_active_primary_experts`` largest; gates the softmax over those
   (``moe_primary_router_apply_softmax``). ``E(y) = (relu(y W_g) * (y W_u))
   W_d``; ``x' = h + sum gate_e E_e(y2)``. No shared expert.
5. After the last layer ``RMS``, then ``logits = RMS(x) W_head`` (untied).

Readings that are not a key's plain meaning (each also under ``assumed`` in the
configuration file): the router reads ``y1`` ("router placed before
attention"); the experts' gate is ReLU ("sparse ReGLU"); the pairing is
half-split; the window counts the row itself. The published model's activation
sparsity ("primary and secondary experts") is not a change to this mathematics
and is not modelled.

Weights are one tree, the layout the program serves from
(``paddle_tpu/models/smallthinker.py``): ``tok_emb lnf_g lm_head`` and
``stacks``, a tuple with one entry per run of equal layers, stacked ``[n,
...]`` or, a run of one layer, unstacked; ``wqkv = [Wq | Wk | Wv]``, ``moe_w_gu
= [W_g | W_u]`` per expert. They stay in the dtype they are served in and are
widened a head and an expert at a time.

``variant`` (for the controls of ``tools/smallthinker_controls.py``): a tuple
of changes to the equations, each named: ``router_y2`` (the router reads
``y2``), ``silu`` (SiLU for ReLU), ``interleaved`` (rotary in interleaved
pairs); ``rule`` chooses a layer's window and rotary as ``layer_rule`` does.

``logits_at(alternative=True)`` also gives each compared position's second
answer: its token's routing, in the layer where the last chosen expert and
the runner-up lay nearest, taken the other way, the row alone continued from
there over the other positions' keys and values (``continue_row``). The cell
holds a served row to the nearer of the two (``drivers/serve_smallthinker.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1024      # rows of a sequence one block of an expert takes
HEAD_BLOCKS = 16      # blocks of the vocabulary the head is read in


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta, interleaved=False, positions=None):
    """``x [s, d]``, row i at position i (or ``positions[i]``), rotated in
    half-split pairs (or interleaved ones)."""
    s, d = x.shape
    inv = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    pos = jnp.arange(s) if positions is None else positions
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    if interleaved:
        a, b = x[:, 0::2], x[:, 1::2]
        return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                          b * jnp.cos(ang) + a * jnp.sin(ang)],
                         -1).reshape(s, d)
    a, b = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _attention(p, y, window, theta, cfg, interleaved):
    """Equation 2 over one sequence: ``window`` keys a row (None: all before
    it), rotary at ``theta`` (None: no positions)."""
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
            q, k = _rope(q, theta, interleaved), _rope(k, theta, interleaved)
        scores = jnp.matmul(q, k.T, precision=_HI) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        o = jnp.matmul(probs, v, precision=_HI)
        return out + _mm(o, jax.lax.dynamic_index_in_dim(
            wo, h, 0, keepdims=False))

    return jax.lax.fori_loop(
        0, nh, add_head, jnp.zeros((s, p["wo"].shape[-1]), jnp.float32))


def _kv(p, y, theta, cfg, interleaved):
    """Every position's key (rotated where the layer rotates) and value
    ``[s, kv heads, head_dim]``: what a later row's continuation attends."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    k = _mm(y, p["wqkv"][:, nh * hd:(nh + nkv) * hd]).reshape(-1, nkv, hd)
    v = _mm(y, p["wqkv"][:, (nh + nkv) * hd:]).reshape(-1, nkv, hd)
    if theta is not None:
        k = jax.vmap(lambda h: _rope(h, theta, interleaved), 1, 1)(k)
    return k, v


def _expert_rows(y, w_g, w_u, w_d, act):
    def rows(block):
        return _mm(act(_mm(block, w_g)) * _mm(block, w_u), w_d)

    s = y.shape[0]
    if s <= ROW_BLOCK:
        return rows(y)
    pad = -s % ROW_BLOCK
    blocks = jnp.pad(y, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, y.shape[1])
    return jax.lax.map(rows, blocks).reshape(-1, y.shape[1])[:s]


def _experts(p, route, y, cfg, act, swap=False):
    """Equation 4: the router over ``route``, the experts over ``y``; beside
    the result every token's ``k + 1`` largest router outputs and their
    experts, in order. ``swap``: the k-th and the (k+1)-th trade places (the
    choice a router whose two outputs lay within rounding of each other
    could have made)."""
    f = cfg["moe_ffn_hidden_size"]
    k = cfg["moe_num_active_primary_experts"]
    ranked = jax.lax.top_k(_mm(route, p["moe_gate"]), k + 1)
    take = list(range(k - 1)) + [k] if swap else list(range(k))
    weight = jax.nn.softmax(ranked[0][:, take], -1)
    chosen = ranked[1][:, take]

    def one(w, e):
        # ``w``: (the whole stack [layers, experts, ...], this layer): an
        # expert is read out of the stack by itself
        stack, layer = w
        at = [jnp.asarray(i, jnp.int32)
              for i in (layer, e) + (0,) * (stack.ndim - 2)]
        return jax.lax.dynamic_slice(stack, at,
                                     (1, 1) + stack.shape[2:])[0, 0]

    def add_expert(e, out):
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
        gu = one(p["moe_w_gu"], e)
        return out + mine[:, None] * _expert_rows(
            y, gu[:, :f], gu[:, f:], one(p["moe_w_d"], e), act)

    out = jax.lax.fori_loop(0, p["moe_w_gu"][0].shape[1], add_expert,
                            jnp.zeros_like(y))
    return out, ranked


@jax.jit
def _embed(tok_emb, ids):
    return jnp.take(tok_emb, ids, axis=0).astype(jnp.float32)


@jax.jit
def _head(rows, lm_head):
    """``rows [n, h]`` times the head ``[h, V]``, widened a block of the
    vocabulary at a time."""
    v = lm_head.shape[1]
    size = -(-v // HEAD_BLOCKS)

    def block(i, out):
        start = jnp.minimum(i * size, v - size)
        w = jax.lax.dynamic_slice_in_dim(lm_head, start, size, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.matmul(rows, w.astype(jnp.float32), precision=_HI),
            start, axis=1)

    return jax.lax.fori_loop(0, HEAD_BLOCKS, block,
                             jnp.zeros((rows.shape[0], v), jnp.float32))


def _weights(stack, i):
    """Layer ``i`` of one run (``i`` None: the run is one unstacked layer);
    the experts as (their whole stack, this layer)."""
    whole = ("moe_w_gu", "moe_w_d")
    if i is None:
        return {k: (v[None], 0) if k in whole else v for k, v in stack.items()}
    return {k: (v, i) if k in whole
            else jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
            for k, v in stack.items()}


def _ffn(p, h, y1, cfg, variant, swap=False):
    """Equations 3 and 4 after attention: ``x' = h + experts``."""
    y2 = _rms(h, p["ln2_g"], cfg["rms_norm_eps"])
    act = jax.nn.silu if "silu" in variant else jax.nn.relu
    m, ranked = _experts(p, y2 if "router_y2" in variant else y1, y2, cfg,
                         act, swap)
    return h + m, ranked


def _layer(stack, i, x, *, cfg_items, window, theta, variant):
    """Layer ``i`` of one run on ``x [s, h]``; the router's ranked outputs;
    every position's key and value."""
    cfg = dict(cfg_items)
    p = _weights(stack, i)
    y1 = _rms(x, p["ln1_g"], cfg["rms_norm_eps"])
    interleaved = "interleaved" in variant
    h = x + _attention(p, y1, window, theta, cfg, interleaved)
    x, ranked = _ffn(p, h, y1, cfg, variant)
    return x, ranked, _kv(p, y1, theta, cfg, interleaved)


def _one_row(stack, i, x, keys, values, pos, *, cfg_items, window, theta,
             variant, swap):
    """The same layer for ONE row ``x [h]`` at position ``pos``, attending the
    other positions' ``keys`` / ``values`` as they were and its own new ones;
    ``swap``: its routing takes the runner-up for the last chosen expert."""
    cfg = dict(cfg_items)
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    p = _weights(stack, i)
    y1 = _rms(x[None], p["ln1_g"], cfg["rms_norm_eps"])
    interleaved = "interleaved" in variant
    q = _mm(y1, p["wqkv"][:, :nh * hd]).reshape(nh, hd)
    k, v = _kv(p, y1, None, cfg, interleaved)
    if theta is not None:
        # every head's row at the one position
        q = _rope(q, theta, interleaved, jnp.full((nh,), pos))
        k = _rope(k[0], theta, interleaved, jnp.full((nkv,), pos))[None]
    keys = jax.lax.dynamic_update_index_in_dim(keys, k[0], pos, 0)
    values = jax.lax.dynamic_update_index_in_dim(values, v[0], pos, 0)
    at = jnp.arange(keys.shape[0])
    seen = at <= pos
    if window is not None:
        seen &= pos - at < window
    qg = q.reshape(nkv, nh // nkv, hd)
    scores = jnp.einsum("kgd,skd->kgs", qg, keys, precision=_HI) * hd ** -0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    o = jnp.einsum("kgs,skd->kgd", probs, values, precision=_HI)
    h = x[None] + _mm(o.reshape(1, nh * hd), p["wo"])
    out, _ = _ffn(p, h, y1, cfg, variant, swap)
    return out[0]


_layer_jit = jax.jit(_layer, static_argnames=("cfg_items", "window", "theta",
                                              "variant"))
_one_row_jit = jax.jit(_one_row, static_argnames=(
    "cfg_items", "window", "theta", "variant", "swap"))


def _hashable(cfg):
    """The configuration's numbers as something ``jax.jit`` can key on."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


def layer_rule(layer, cfg):
    """Equation 2's ``(window, rotary theta)`` of published layer
    ``layer``. The controls of ``tools/`` pass another rule."""
    rope = cfg["rope_layout"][layer]
    window = cfg["sliding_window_layout"][layer]
    return (cfg["sliding_window_size"] if window else None,
            cfg["rope_theta"] if rope else None)


def _layers(params):
    """``[(stack, index in it or None)]``, the layers in order."""
    return [(stack, None if stack["ln1_g"].ndim == 1 else jnp.int32(i))
            for stack in params["stacks"]
            for i in range(1 if stack["ln1_g"].ndim == 1
                           else stack["ln1_g"].shape[0])]


def hidden(params, ids, cfg, rule=layer_rule, routing=None, variant=(),
           kept=None, at=None):
    """Final-norm hidden states ``[s, h]`` of one sequence ``ids [s]``;
    ``cfg`` holds the published keys. ``routing``: a list that is handed each
    layer's ranked router outputs and experts, ``([s, k + 1], [s, k +
    1])``; ``kept``: a list that is handed each layer's input rows at the
    positions ``at`` and every position's keys and values."""
    x = _embed(params["tok_emb"], jnp.asarray(ids, jnp.int32))
    for layer, (stack, i) in enumerate(_layers(params)):
        window, theta = rule(layer, cfg)
        x_in = x
        x, ranked, kv = _layer_jit(stack, i, x, cfg_items=_hashable(cfg),
                                   window=window, theta=theta,
                                   variant=tuple(variant))
        if routing is not None:
            routing.append(ranked)
        if kept is not None:
            kept.append((x_in[at], *kv))
    return _rms(x, params["lnf_g"], cfg["rms_norm_eps"])


def continue_row(params, cfg, kept, n, pos, swap_at, rule=layer_rule,
                 variant=(), swap=True):
    """Final-norm hidden row of position ``pos`` (the ``n``-th kept) taken
    again from layer ``swap_at`` on, alone, over the other positions' keys
    and values as ``hidden`` kept them, with its routing at ``swap_at``
    swapped (``swap``) and as computed in every later layer."""
    x = kept[swap_at][0][n]
    for layer, (stack, i) in enumerate(_layers(params)):
        if layer < swap_at:
            continue
        window, theta = rule(layer, cfg)
        x = _one_row_jit(stack, i, x, *kept[layer][1:], jnp.int32(pos),
                         cfg_items=_hashable(cfg), window=window,
                         theta=theta, variant=tuple(variant),
                         swap=swap and layer == swap_at)
    return _rms(x, params["lnf_g"], cfg["rms_norm_eps"])


def logits_at(params, ids, positions, cfg, rule=layer_rule, routing=None,
              variant=(), alternative=False):
    """Next-token logits ``[len(positions), V]`` after ``ids[:p + 1]`` for
    each ``p`` of ``positions``: ONE full forward over the (right-padded)
    sequence ``ids [s]``, read at the positions. Causality makes what follows
    a position irrelevant to it.

    ``alternative``: also, per position, the logits its token would give had
    its router, in the layer where its last chosen expert and the runner-up
    lay nearest (their outputs' difference), taken the runner-up: the other
    answer a rounding of the router's input could give there. Returns
    ``(logits, alternative logits, [(layer, margin)])``."""
    at = jnp.asarray(positions, jnp.int32)
    ranked = None if routing is None and not alternative else []
    kept = [] if alternative else None
    h = hidden(params, ids, cfg, rule, ranked, variant, kept, at)
    if ranked is not None:
        rows = [(np.asarray(s[at]), np.asarray(e[at])) for s, e in ranked]
        if routing is not None:
            routing.extend(rows)
    logits = _head(h[at], params["lm_head"])
    if not alternative:
        return logits
    k = cfg["moe_num_active_primary_experts"]
    ties, alt = [], []
    for n, pos in enumerate(positions):
        margins = [float(s[n, k - 1] - s[n, k]) for s, _ in rows]
        layer = int(np.argmin(margins))
        ties.append((layer, margins[layer]))
        alt.append(continue_row(params, cfg, kept, n, pos, layer, rule,
                                variant))
    return logits, _head(jnp.stack(alt), params["lm_head"]), ties
