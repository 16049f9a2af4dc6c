"""Mixture-of-Experts routing + expert FFN — the GPT MoE subsystem.

One functional core serves every consumer, so the serving step, the eager
oracle and the SPMD training block cannot drift apart:

- :func:`route_topk` — deterministic top-k softmax routing (iterative
  argmax + one-hot masking: ties break to the LOWEST expert index on
  every path, so greedy serving stays bit-reproducible);
- :func:`moe_capacity` / :func:`capacity_positions` — GShard capacity
  math: per-(token, choice) slot ranks in choice-major priority (all
  first choices queue before any second choice, the ``top2_gating``
  discipline), tokens past an expert's capacity DROP — their FFN
  contribution is exactly zero so the residual carries them through;
- :func:`moe_ffn` — the grouped-GEMM formulation (sort token-choice
  pairs by expert, one ragged ``ops/pallas/grouped_matmul`` per FFN
  matmul, combine by renormalized gates). This is THE spelling both the
  eager :class:`GPTMoE` module and the serving blocks call — greedy
  serving == full-forward oracle is structural, not a numerical
  accident;
- :func:`topk_dispatch_combine` — the einsum (dispatch/combine mask)
  formulation the SPMD training block uses: dense ``[N, E, C]`` masks
  lower cleanly under GSPMD with experts sharded over the ``ep`` axis
  (``gpt_spmd._moe_block``), generalizing the orphaned
  ``meta_parallel/moe_layer.py`` top-1/top-2 gates to any k (that module
  now re-exports these primitives);
- aux load-balance loss: ``E * sum(frac_tokens_per_expert *
  mean_router_prob_per_expert)`` over the FIRST choice (GShard eq. 13 /
  Switch eq. 4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.param_attr import ParamAttr
from ..nn import Layer
from ..nn.initializer import Normal


def moe_capacity(n_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-expert slot budget (static): the ``moe_layer`` formula
    generalized to k — ``max(int(factor * n / E) * k, 4)``. A factor >=
    ``num_experts`` can never drop a token (an expert sees at most ``n``
    of the ``n * k`` choices)."""
    return max(int(float(capacity_factor) * int(n_tokens)
                   / int(num_experts)) * int(top_k), 4)


def route_topk(logits, top_k: int, renormalize: bool = True,
               scoring: str = "softmax", bias=None):
    """Deterministic top-k routing over router ``logits [N, E]``.

    Returns ``(gates [N, k] fp32, idx [N, k] int32, probs [N, E] fp32,
    masks)`` — gates renormalized over the k selections (GShard denom)
    unless ``renormalize`` is off, when they are the k softmax scores as
    they are (``norm_topk_prob: false``); ``masks`` the per-choice one-hot
    ``[N, E]`` list. ``jnp.argmax`` breaks ties to the lowest index, and
    the iterative masking keeps the k experts distinct.

    ``scoring="sigmoid"`` with ``bias [E]`` is DeepSeek-V3's ``noaux_tc``
    choice: the scores are ``sigmoid(logits)``, the k experts are those with
    the largest ``score + bias`` (``e_score_correction_bias``), and the
    gates are the UNBIASED scores of the chosen."""
    n, e = logits.shape
    if scoring == "softmax" and bias is None:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        p, taken = probs, None
    else:
        probs = (jax.nn.sigmoid(logits.astype(jnp.float32))
                 if scoring == "sigmoid"
                 else jax.nn.softmax(logits.astype(jnp.float32), axis=-1))
        p = probs if bias is None else probs + bias.astype(jnp.float32)
        taken = -jnp.inf      # a biased score may be negative: mask, not zero
    idxs, raw, masks = [], [], []
    for _ in range(int(top_k)):
        i = jnp.argmax(p, axis=-1)
        m = jax.nn.one_hot(i, e, dtype=jnp.float32)
        idxs.append(i.astype(jnp.int32))
        raw.append(((p if taken is None else probs) * m).sum(axis=-1))
        masks.append(m)
        p = p * (1.0 - m) if taken is None else jnp.where(m > 0, taken, p)
    gates = jnp.stack(raw, axis=1)                       # [N, k]
    if renormalize:
        gates = gates / jnp.maximum(gates.sum(axis=1, keepdims=True), 1e-9)
    return gates, jnp.stack(idxs, axis=1), probs, masks


def load_balance_aux(probs, mask1, valid=None):
    """GShard aux loss: ``E * sum(frac_per_expert * mean_prob_per_expert)``
    over FIRST choices; ``valid [N]`` excludes padding rows."""
    e = probs.shape[-1]
    if valid is None:
        frac = mask1.mean(axis=0)
        pmean = probs.mean(axis=0)
    else:
        vw = valid.astype(jnp.float32)[:, None]
        denom = jnp.maximum(vw.sum(), 1.0)
        frac = (mask1 * vw).sum(axis=0) / denom
        pmean = (probs * vw).sum(axis=0) / denom
    return jnp.sum(frac * pmean) * e


def capacity_positions(masks, capacity: int, valid=None):
    """Per-(token, choice) slot index in the chosen expert's capacity
    buffer, choice-major priority (``top2_gating``'s offset discipline
    generalized): returns ``pos [N, k]`` — ``pos >= capacity`` means the
    choice DROPS. ``valid`` rows never consume a slot (pos -1)."""
    e = masks[0].shape[-1]
    offset = jnp.zeros((e,), jnp.float32)
    poss = []
    for m in masks:
        mv = m if valid is None else m * valid.astype(jnp.float32)[:, None]
        ranks = jnp.cumsum(mv, axis=0) + offset[None, :]
        poss.append((ranks * mv).sum(axis=-1) - 1.0)
        offset = offset + mv.sum(axis=0)
    return jnp.stack(poss, axis=1)                       # [N, k] float


def _earlier_same_choice(chose, block: int = 128):
    """For one-hot rows ``chose [M, E]`` (bool): how many EARLIER rows made
    each row's choice, ``[M]`` int32. In blocks of 128 rows the count is a
    product with a triangle (exact: the sums stay far under 2**24) plus a
    running count over the few blocks; a running sum down all M rows is a
    slow reduction on the chip."""
    m, e = chose.shape
    nb = -(-m // block)
    rows = jnp.pad(chose, ((0, nb * block - m), (0, 0))).astype(
        jnp.float32).reshape(nb, block, e)
    before = jnp.arange(block)
    triangle = (before[:, None] > before[None, :]).astype(jnp.float32)
    within = jnp.einsum("ij,bje->bie", triangle, rows,
                        preferred_element_type=jnp.float32)
    per_block = rows.sum(axis=1)                                  # [nb, E]
    blocks_before = jnp.cumsum(per_block, axis=0) - per_block
    earlier = ((within + blocks_before[:, None, :]) * rows).sum(axis=-1)
    return earlier.reshape(-1)[:m].astype(jnp.int32)


def _grouped_mm(xs, w, offsets, use_kernel, layer=None):
    """fp stack or quantized ``{"q", "s"}`` dict through the ragged
    grouped GEMM (the ``_srv_mm`` convention per expert stack); with
    ``layer``, layer ``layer`` of an ``[L, E, ...]`` float stack."""
    from ..ops.pallas.grouped_matmul import grouped_matmul

    if isinstance(w, dict):
        return grouped_matmul(xs, w["q"], offsets, scales=w["s"],
                              use_kernel=use_kernel)
    return grouped_matmul(xs, w, offsets, use_kernel=use_kernel, layer=layer)


def _expert_bias(b, eids):
    """Per-row bias gather from an ``[E, F]`` stack."""
    return jnp.take(b, eids, axis=0)


def moe_ffn(x, gate_w, w1, b1, w2, b2, *, top_k: int,
            capacity_factor: float | None, use_kernel=None, valid=None,
            with_stats: bool = False, renormalize: bool = True,
            gated: bool = False, gate_scale: float = 1.0, layer=None,
            scoring: str = "softmax", route_bias=None, experts_held=None):
    """The MoE FFN over 2D tokens ``x [N, d]``.

    gate_w ``[d, E]``; w1 ``[E, d, f]`` / w2 ``[E, f, d]`` (fp stacks or
    quantized ``{"q", "s"}`` dicts — inference/quantize.py layout); b1
    ``[E, f]``; b2 ``[E, d]`` (``None``: no bias). ``valid [N]`` masks
    padding rows (serving's packed stream): invalid rows route nowhere —
    zero gates, no capacity slot, zero output. Dropped token-choice pairs
    (capacity overflow) keep their expert assignment in the grouped layout
    but combine with gate 0 — the token rides the residual.

    What a model's configuration chooses: ``capacity_factor=None`` routes
    DROPLESS (no clamp: every choice of every valid token is computed);
    ``renormalize`` as in :func:`route_topk`; ``gate_scale`` multiplies
    the gates (``routed_scaling_factor``); ``gated``: ``w1`` is ``[E, d,
    2f]``, the gate and up projections of a gated SiLU expert in ONE
    grouped GEMM, ``(silu(x W_g) * x W_u) W_d``, instead of the biased
    GELU pair. ``layer`` (a traced int32 scalar): ``w1`` / ``w2`` are the
    whole model's stacks ``[L, E, ...]`` and this call is layer ``layer``
    of them (``grouped_matmul(layer=)``). ``scoring`` / ``route_bias`` as
    :func:`route_topk`. ``experts_held=(first, count)``: this chip's SHARE
    of an expert-parallel layer. The router keeps its full width ``E`` and
    its k; ``w1`` / ``w2`` hold the ``count`` experts ``first ..`` alone;
    only the pairs whose choice is held (and whose row is valid) are sorted
    into the grouped layout and computed, and what the absent experts would
    have added is left out: no stand-in. ``rows`` then counts the held
    experts' rows, ``[count]``.

    Returns ``(out [N, d], aux_loss)`` — plus a stats dict (``load [E]``
    kept-pair fraction per expert, ``drop_rate``, ``rows [E]`` int32 kept
    pairs per expert) when ``with_stats``.
    """
    from ..observability.tracing import step_scope

    n, d = x.shape
    e = gate_w.shape[-1]
    k = int(top_k)
    with step_scope("moe_route"):
        logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        gates, idx, probs, masks = route_topk(
            logits, k, renormalize, scoring=scoring, bias=route_bias)
        aux = load_balance_aux(probs, masks[0], valid=valid)
        if capacity_factor is None:
            cap = n
            keep = jnp.ones((n, k), bool)
        else:
            cap = moe_capacity(n, e, k, capacity_factor)
            pos = capacity_positions(masks, cap, valid=valid)
            keep = (pos >= 0.0) & (pos < cap)                 # [N, k]
        if valid is not None:
            keep = keep & valid[:, None]
        gates = gates * keep.astype(gates.dtype) * gate_scale

        # token-choice pairs grouped by expert (stable: deterministic intra-
        # expert order = token-major arrival) — the ragged grouped layout.
        # A counting sort: a pair's place is its expert's offset plus how
        # many earlier pairs chose that expert, and the rows are scattered
        # there. (On the v5e an argsort of the 6,144 pairs, the scalar
        # scatter that inverts the permutation and a running sum down the
        # pairs each cost about 3 ms a step of seven layers: PERF.md, PR 28.)
        eid = idx.reshape(-1)                                     # [N*k]
        if experts_held is not None:
            # this chip's share: a pair is sorted in only where its expert
            # is one of the held ones and its row is real; every other
            # pair has no place (dropped by the scatter, gate zero)
            first, e = int(experts_held[0]), int(experts_held[1])
            keep = keep & (idx >= first) & (idx < first + e)
            gates = gates * keep.astype(gates.dtype)
            eid = jnp.where(keep.reshape(-1), eid - first, e)
        chose = eid[:, None] == jnp.arange(e, dtype=eid.dtype)[None]
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(chose.sum(axis=0, dtype=jnp.int32))])
        if experts_held is None:
            place = offsets[eid] + _earlier_same_choice(chose)  # pair -> row
            xs = jnp.zeros((n * k, d), x.dtype).at[place].set(
                jnp.repeat(x, k, axis=0))                         # [N*k, d]
        else:
            place = jnp.where(
                eid < e, offsets[jnp.minimum(eid, e - 1)]
                + _earlier_same_choice(chose), n * k)
            xs = jnp.zeros((n * k, d), x.dtype).at[place].set(
                jnp.repeat(x, k, axis=0), mode="drop")
        if b1 is not None or b2 is not None:
            from ..ops.pallas.grouped_matmul import token_group_ids

            eid_sorted = token_group_ids(offsets, n * k)

    with step_scope("moe_experts"):
        h = _grouped_mm(xs, w1, offsets, use_kernel, layer)
        if b1 is not None:
            h = h + _expert_bias(b1, eid_sorted).astype(h.dtype)
        if gated:
            f = h.shape[-1] // 2
            h = jax.nn.silu(h[:, :f]) * h[:, f:]
        else:
            h = jax.nn.gelu(h, approximate=True)
        y = _grouped_mm(h.astype(x.dtype), w2, offsets, use_kernel, layer)
        if b2 is not None:
            y = y + _expert_bias(b2, eid_sorted).astype(x.dtype)

    with step_scope("moe_route"):
        # each token gathers its k experts' rows and sums them by its gates
        # (a gather and a small product, not a scatter-add over N*k rows)
        if experts_held is None:
            mine = jnp.take(y, place, axis=0).reshape(n, k, d)
        else:
            # rows past the held pairs are whatever the grouped GEMM left
            mine = jnp.where(keep[:, :, None], jnp.take(
                y, place, axis=0, mode="clip").reshape(n, k, d), 0)
        out = jnp.einsum("nkd,nk->nd", mine.astype(jnp.float32),
                         gates.astype(jnp.float32)).astype(x.dtype)
    if not with_stats:
        return out, aux
    kept = keep.astype(jnp.float32)
    n_pairs = (jnp.maximum(valid.astype(jnp.float32).sum(), 1.0) * k
               if valid is not None else jnp.float32(n * k))
    load = jnp.sum(chose * kept.reshape(-1, 1), axis=0)   # kept pairs [E]
    stats = {
        "load": load / jnp.maximum(load.sum(), 1.0),
        "drop_rate": 1.0 - jnp.minimum(kept.sum() / n_pairs, 1.0),
        "capacity": jnp.float32(cap),
        "rows": load.astype(jnp.int32),
    }
    return out, aux, stats


# ---------------------------------------------------------------------------
# einsum (dispatch/combine) formulation — the SPMD training spelling
# ---------------------------------------------------------------------------


def _combine_one(gate, mask, pos, capacity: int):
    keep = (pos >= 0) & (pos < capacity)
    mask = mask * keep[:, None].astype(mask.dtype)
    slots = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    oh = jax.nn.one_hot(slots, capacity, dtype=jnp.float32) * keep[:, None]
    return (gate * keep)[:, None, None] * mask[:, :, None] * oh[:, None, :]


def topk_dispatch_combine(logits, capacity: int, top_k: int):
    """GShard dense-mask gating generalized to any k: returns
    ``(combine [N, E, C], dispatch [N, E, C], aux_loss)``. ``k == 1``
    reproduces ``top1_gating`` (Switch), ``k == 2`` reproduces
    ``top2_gating`` — same argmax tie-breaks, same choice-major slot
    priority, same renormalized gates as :func:`moe_ffn`, so the einsum
    and grouped formulations compute the SAME function."""
    gates, _idx, probs, masks = route_topk(logits, top_k)
    aux = load_balance_aux(probs, masks[0])
    pos = capacity_positions(masks, capacity)
    combine = jnp.zeros(
        (logits.shape[0], logits.shape[1], int(capacity)), jnp.float32)
    for j, m in enumerate(masks):
        combine = combine + _combine_one(gates[:, j], m, pos[:, j],
                                         int(capacity))
    dispatch = (combine > 0).astype(logits.dtype)
    return combine, dispatch, aux


def moe_ffn_einsum(x, gate_w, w1, b1, w2, b2, *, top_k: int,
                   capacity_factor: float):
    """Capacity-dense einsum MoE (the GShard global_scatter/global_gather
    spelling): the training-path twin of :func:`moe_ffn`, and the parity
    oracle for ``moe_layer.MoELayer``. Returns ``(out [N, d], aux)``."""
    n = x.shape[0]
    e = gate_w.shape[-1]
    cap = moe_capacity(n, e, top_k, capacity_factor)
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    combine, dispatch, aux = topk_dispatch_combine(logits, cap, top_k)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w1)
                    + b1[:, None, :], approximate=True)
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    out = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), expert_out)
    return out, aux


def active_params_frac(config) -> float:
    """Analytic fraction of per-layer decoder weights a token actually
    streams under top-k routing (the bench's ``active_params_frac``):
    attention + router always stream, expert FFNs stream k of E."""
    e = int(getattr(config, "moe_experts", 0) or 0)
    if not e:
        return 1.0
    h, f = config.hidden_size, config.ffn_size
    k = int(config.moe_top_k)
    attn = 4 * h * h + 4 * h
    gate = h * e
    expert = 2 * h * f + h + f
    total = attn + gate + e * expert
    active = attn + gate + min(k, e) * expert
    return float(active) / float(total)


# ---------------------------------------------------------------------------
# eager module (GPTDecoderLayer's MLP when config.moe_experts > 0)
# ---------------------------------------------------------------------------


class GPTMoE(Layer):
    """Eager MoE FFN block — the GPTMLP drop-in for MoE configs.

    Expert weights are ONE stacked parameter per role (``w1 [E, h, f]``
    ...) so serving extraction stacks them ``[L, E, ...]`` exactly like
    the dense keys. Forward calls the SAME :func:`moe_ffn` the serving
    blocks run — full-forward oracle == serving step by construction.
    ``aux_loss`` and host-readable ``router_stats`` refresh per call
    (the bench's routing report reads them)."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        h, f, e = config.hidden_size, config.ffn_size, config.moe_experts
        attr = ParamAttr(initializer=Normal(
            mean=0.0, std=config.initializer_range))
        self.gate_weight = self.create_parameter([h, e], attr=attr)
        self.w1 = self.create_parameter([e, h, f], attr=attr)
        self.b1 = self.create_parameter([e, f], is_bias=True)
        self.w2 = self.create_parameter([e, f, h], attr=attr)
        self.b2 = self.create_parameter([e, h], is_bias=True)
        self.aux_loss = None
        self.router_stats = None

    def forward(self, x):
        from ..autograd.engine import apply_op

        cfg = self.config

        def pure(xv, gw, w1, b1, w2, b2):
            tokens = xv.reshape(-1, xv.shape[-1])
            out, aux, stats = moe_ffn(
                tokens, gw, w1, b1, w2, b2,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                with_stats=True)
            return (out.reshape(xv.shape), aux, stats["load"],
                    stats["drop_rate"])

        out, aux, load, drop = apply_op(
            "moe_layer", pure, x, self.gate_weight, self.w1,
            self.b1, self.w2, self.b2)
        self.aux_loss = aux
        self.router_stats = {"load": load, "drop_rate": drop}
        return out
