"""tpulint static HBM cost model (JX007) — the round-23 certification.

The repo's headline serving claim — ``hbm_bytes_per_token`` — lived in ONE
hand-written analytic model inside ``bench_serve.py``. This module splits
that claim into two independently-derived sides and gates their agreement:

- the **analytic side** (:func:`analytic_hbm_bytes_per_token`): the bench
  formula, now owned here so ``bench_serve.py`` and the lint gate share one
  set of constants (:data:`PER_OP_SHARDED_ACT_H` etc. — the per-layer
  activation accounting ARCHITECTURE.md documents);
- the **static side** (:func:`static_hbm_report`): the same quantity derived
  from the TRACED JAXPR of the serving step — weight bytes measured off the
  program's parameter invars, layer count and hidden width read from the
  layer scan, and the KV term from the pool invar geometry.

**JX007** fires when the two sides drift beyond the per-target tolerance
declared in :mod:`.contracts` — i.e. when someone changes the traced program
(a new param leaf, a different carry layout, a forgotten scale plane) without
updating the bench model, or vice versa. The drift is caught by
``python -m paddle_tpu.analysis`` exit-2 before a bench ever runs.

The module also carries the generic per-eqn dataflow walker
(:func:`program_flow_bytes`): bytes read + written per equation, recursing
``pjit``/``scan``/``remat``/``shard_map`` sub-jaxprs with scan-length
multipliers — the gross upper bound the report ships as diagnostic data.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from .findings import Finding, rule
from .jaxpr_checks import _aval_bytes, _jaxprs_in

JX007 = rule("JX007", "static jaxpr HBM model drifts from the bench "
                      "analytic model")

# ---------------------------------------------------------------------------
# the shared analytic constants (bench_serve.py imports these)
# ---------------------------------------------------------------------------

#: per-op layer chain, head/column-sharded intermediates (shrink /mp per
#: chip): qkv 3h + attention out h + MLP hidden 4h + gelu out 4h
PER_OP_SHARDED_ACT_H = 12
#: per-op layer chain, full-width on every chip: LN1/LN2 outs, the
#: residual, and the post-psum wo/MLP outputs
PER_OP_FULL_ACT_H = 5
#: every inter-kernel intermediate crosses HBM twice (write + read)
HBM_ROUNDTRIPS = 2


def activation_elems_per_layer(h: int, mp: int = 1) -> float:
    """Per-layer per-token activation ELEMENTS crossing HBM between the
    step's kernels (one direction; multiply by :data:`HBM_ROUNDTRIPS`)."""
    return PER_OP_SHARDED_ACT_H * h / mp + PER_OP_FULL_ACT_H * h


def bytes_on_the_wire(num_elements: int, world: int, *, elem_bytes: int = 4,
                      quant=None) -> int:
    """Re-export of the dp gradient-sync wire model (one shared constants
    module: ``bench.py``'s dpquant leg and the JX009 HLO contract both read
    the analytic wire bytes from here)."""
    from ..distributed.compressed_collectives import bytes_on_the_wire as f

    return f(num_elements, world, elem_bytes=elem_bytes, quant=quant)


@dataclass(frozen=True)
class ServingGeometry:
    """The analytic model's inputs — everything the bench formula reads."""

    layer_weight_bytes: int        # per-layer stacks (mp-sharded)
    replicated_weight_bytes: int   # embeddings / LM head / final LN
    num_layers: int
    kv_heads: int
    head_dim: int
    kv_itemsize: int
    kv_quantized: bool
    act_itemsize: int
    mp: int
    batch: int
    avg_ctx: float
    # round-25 MoE: the expert stacks' bytes ride separately — a decode
    # token streams only its top-k experts' weights, not all E
    moe_experts: int = 0
    moe_top_k: int = 0
    expert_weight_bytes: int = 0


def analytic_hbm_bytes_per_token(g: ServingGeometry) -> int:
    """The bench analytic model (moved verbatim from ``bench_serve.py``):
    steady-state HBM read bytes PER CHIP per decode token — every weight
    byte once per step (amortized over the batch's lanes) + the token's own
    KV context (+ fp32 scale planes for int8 pools) + the inter-kernel
    activation round-trips."""
    lb = g.layer_weight_bytes
    if g.moe_experts:
        # routed experts: each token's FFN reads top_k of the E expert
        # stacks — the other experts' weights never stream for it
        lb += (g.expert_weight_bytes * g.moe_top_k
               / max(g.moe_experts, 1))
    wb = (lb / g.mp + g.replicated_weight_bytes) / max(g.batch, 1)
    kv = (2 * g.num_layers * g.avg_ctx
          * g.kv_heads * g.head_dim * g.kv_itemsize) / g.mp
    if g.kv_quantized:
        kv += 2 * g.num_layers * g.avg_ctx * g.kv_heads * 4 / g.mp
    h = g.kv_heads * g.head_dim
    act = (HBM_ROUNDTRIPS * g.num_layers
           * activation_elems_per_layer(h, g.mp) * g.act_itemsize)
    return int(wb + kv + act)


#: the serving-pytree layer stacks whose bytes scale with routing (the
#: per-expert FFN tree; the gate is dense — every token reads it)
MOE_EXPERT_STACK_KEYS = ("moe_w1", "moe_b1", "moe_w2", "moe_b2")


def geometry(params, cache, *, batch: int, avg_ctx: float, mp: int = 1,
             moe_experts: int = 0, moe_top_k: int = 0) -> ServingGeometry:
    """Build the analytic geometry from a live (params, KVCacheManager)
    pair — the adapter both ``bench_serve.py`` and the cert targets use.
    ``moe_experts``/``moe_top_k`` (round 25) split the expert stacks out
    of ``layer_weight_bytes`` so the analytic model charges a decode
    token only its top-k experts' weights."""
    import jax.numpy as jnp

    from ..inference.quantize import serving_weight_bytes

    layers = params["layers"]
    expert_b = 0
    if moe_experts:
        expert_b = serving_weight_bytes(
            {"layers": {k: v for k, v in layers.items()
                        if k in MOE_EXPERT_STACK_KEYS}})
    layer_b = serving_weight_bytes({"layers": layers}) - expert_b
    total_b = serving_weight_bytes(params)
    return ServingGeometry(
        layer_weight_bytes=layer_b,
        replicated_weight_bytes=total_b - layer_b - expert_b,
        num_layers=cache.num_layers,
        kv_heads=cache.num_kv_heads,
        head_dim=cache.head_dim,
        kv_itemsize=jnp.dtype(cache.k_pages.dtype).itemsize,
        kv_quantized=bool(cache.quantize_kv),
        act_itemsize=jnp.dtype(params["tok_emb"].dtype).itemsize,
        mp=mp, batch=batch, avg_ctx=avg_ctx,
        moe_experts=moe_experts, moe_top_k=moe_top_k,
        expert_weight_bytes=expert_b)


# ---------------------------------------------------------------------------
# the per-eqn dataflow walker
# ---------------------------------------------------------------------------

_SCOPE_PRIMS_LOOP = ("scan",)


def eqn_io_bytes(eqn) -> int:
    """Bytes one equation reads + writes if every operand crossed HBM."""
    read = sum(_aval_bytes(getattr(v, "aval", None)) for v in eqn.invars
               if hasattr(v, "aval"))
    written = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    return read + written


def program_flow_bytes(jaxpr, mult: int = 1) -> int:
    """Gross dataflow bytes of a jaxpr: per-eqn read+write totals, recursing
    sub-jaxprs (``pjit``/``shard_map``/``cond``/``remat`` at x1, ``scan``
    bodies multiplied by their trip count). An upper bound — XLA fuses most
    of it away — shipped as diagnostic data next to the role-aware model."""
    total = 0
    for eqn in jaxpr.eqns:
        sub = [s for val in eqn.params.values() for s in _jaxprs_in(val)]
        if sub:
            inner_mult = mult
            if eqn.primitive.name in _SCOPE_PRIMS_LOOP:
                inner_mult = mult * int(eqn.params.get("length", 1))
            for s in sub:
                total += program_flow_bytes(s, inner_mult)
        else:
            total += eqn_io_bytes(eqn) * mult
    return total


# ---------------------------------------------------------------------------
# the static (jaxpr-derived) side
# ---------------------------------------------------------------------------


def find_layer_scan(jaxpr):
    """The layer scan of a serving step: the ``scan`` equation with the most
    xs bytes (the stacked per-layer weights dominate every other loop's
    scanned inputs; the unified step's KV pools ride its carry, not its
    xs). Recurses sub-jaxprs."""
    best, best_bytes = None, -1
    for eqn in _iter_eqns_all(jaxpr):
        if eqn.primitive.name != "scan":
            continue
        n_lead = (int(eqn.params.get("num_consts", 0))
                  + int(eqn.params.get("num_carry", 0)))
        xs_bytes = sum(_aval_bytes(getattr(v, "aval", None))
                       for v in eqn.invars[n_lead:])
        if xs_bytes > best_bytes:
            best, best_bytes = eqn, xs_bytes
    return best


def _iter_eqns_all(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _jaxprs_in(val):
                yield from _iter_eqns_all(sub)


def static_hbm_report(closed, n_param_leaves: int, pool_avals, *,
                      batch: int, avg_ctx: float, mp: int = 1,
                      moe_experts: int = 0, moe_top_k: int = 0) -> dict:
    """Derive ``hbm_bytes_per_token`` from the traced step jaxpr.

    ``n_param_leaves``: flattened leaf count of the params argument (the
    step's argument 0 — its leaves are the program's first invars in tree
    order). ``pool_avals``: the KV pool (and scale plane) avals at their
    argument positions — 5D pools, 4D fp32 scale planes.
    """
    jaxpr = closed.jaxpr
    scan = find_layer_scan(jaxpr)
    if scan is None:
        raise ValueError("no layer scan found in the traced program")
    num_layers = int(scan.params["length"])

    # the scan carries the packed [t, h] stream: h is the carry's minor dim,
    # act dtype its dtype. The unified step carries its stacked pools and
    # scale planes too (rank 5 and 4, each chip's head shard under a mesh):
    # told apart by rank.
    n_consts = int(scan.params.get("num_consts", 0))
    n_carry = int(scan.params.get("num_carry", 0))
    pool_ranks = {len(a.shape) for a in pool_avals if a is not None}
    carries = [getattr(v, "aval", None)
               for v in scan.invars[n_consts:n_consts + n_carry]]
    carries = [a for a in carries if a is not None and len(a.shape)
               and len(a.shape) not in pool_ranks]
    if not carries:
        raise ValueError("layer scan has no activation carry")
    carry = max(carries, key=_aval_bytes)
    hidden = int(carry.shape[-1])
    act_itemsize = carry.dtype.itemsize

    # weight bytes off the program's own parameter invars: layer stacks are
    # the leaves with a leading num_layers dim (the scanned xs), the rest
    # (embeddings / LM head / final LN) is replicated under mp
    param_avals = [v.aval for v in jaxpr.invars[:n_param_leaves]]

    def _layer_leaf_bytes(a):
        if not (a.shape and a.shape[0] == num_layers):
            return 0.0
        b = _aval_bytes(a)
        # round-25 MoE: an expert stack ([L, E, ...] — the leading-E
        # leaves, incl. quantized {"q","s"} planes) streams only the
        # token's top-k experts' slices, not all E
        if (moe_experts and len(a.shape) >= 3
                and a.shape[1] == moe_experts):
            return b * moe_top_k / max(moe_experts, 1)
        return float(b)

    layer_bytes = sum(_layer_leaf_bytes(a) for a in param_avals)
    repl_bytes = (sum(_aval_bytes(a) for a in param_avals)
                  - sum(_aval_bytes(a) for a in param_avals
                        if a.shape and a.shape[0] == num_layers))
    wb = (layer_bytes / mp + repl_bytes) / max(batch, 1)

    # KV term off the pool invar geometry (pools [L, pages, heads, page,
    # hd]; scale planes [L, pages, heads, page] fp32)
    kv = 0.0
    for a in pool_avals:
        if a is None:
            continue
        heads = a.shape[2]
        if len(a.shape) == 5:
            kv += (num_layers * avg_ctx * heads * a.shape[4]
                   * a.dtype.itemsize / mp)
        elif len(a.shape) == 4:
            kv += num_layers * avg_ctx * heads * a.dtype.itemsize / mp

    act = (HBM_ROUNDTRIPS * num_layers
           * activation_elems_per_layer(hidden, mp) * act_itemsize)

    return {
        "hbm_bytes_per_token": int(wb + kv + act),
        "weight_bytes_per_token": int(wb),
        "kv_bytes_per_token": int(kv),
        "act_bytes_per_token": int(act),
        "num_layers": num_layers,
        "hidden": hidden,
        "flow_bytes_upper_bound": program_flow_bytes(jaxpr),
    }


def check_hbm_model(closed, n_param_leaves: int, pool_avals, geom,
                    tolerance: float, target: str) -> list[Finding]:
    """JX007: the jaxpr-derived static number must agree with the bench
    analytic model within ``tolerance`` (relative)."""
    findings: list[Finding] = []
    try:
        static = static_hbm_report(closed, n_param_leaves, pool_avals,
                                   batch=geom.batch, avg_ctx=geom.avg_ctx,
                                   mp=geom.mp,
                                   moe_experts=geom.moe_experts,
                                   moe_top_k=geom.moe_top_k)
    except ValueError as e:
        return [Finding(rule=JX007, target=target, detail="no-layer-scan",
                        message=f"static HBM model underivable: {e}")]
    if static["num_layers"] != geom.num_layers:
        findings.append(Finding(
            rule=JX007, target=target, detail="layer-scan-length",
            message=f"layer scan runs {static['num_layers']} trips but the "
                    f"geometry declares {geom.num_layers} layers"))
    analytic = analytic_hbm_bytes_per_token(geom)
    drift = abs(static["hbm_bytes_per_token"] - analytic) / max(analytic, 1)
    if not math.isfinite(drift) or drift > tolerance:
        findings.append(Finding(
            rule=JX007, target=target, detail="hbm-drift",
            message=f"static hbm_bytes_per_token "
                    f"{static['hbm_bytes_per_token']} drifts "
                    f"{drift:.1%} from the bench analytic model {analytic} "
                    f"(tolerance {tolerance:.1%}) — the traced program and "
                    "the bench formula no longer describe the same step",
            data={"static": static, "analytic": analytic}))
    return findings


def static_hbm_for_predictor(sp, batch: int, avg_ctx: float):
    """The bench-side static entry: trace the predictor's OWN unified step
    (same builder, the predictor's live params/pools) and derive the static
    number at the bench geometry."""
    import jax.numpy as jnp

    from ..models.gpt import build_unified_step
    from .jaxpr_checks import trace_callable

    cfg, cache, chunk = sp.config, sp.cache, sp.chunk
    spec_k = int(getattr(sp, "spec_k", 0) or 0)
    kv_quant = bool(cache.quantize_kv)
    mesh = sp.mesh
    step = build_unified_step(cfg, cache.page_size, chunk,
                              kv_quant=kv_quant, spec_k=spec_k,
                              mesh=mesh)
    b = cache.max_batch
    budget = int(getattr(sp, "token_budget", 0)
                 or b * (1 + spec_k) + chunk)
    lead = [sp.params,
            jnp.zeros((budget,), jnp.int32),              # tok_ids
            jnp.zeros((budget,), jnp.int32),              # tok_slot
            jnp.zeros((budget,), jnp.int32),              # tok_pos
            jnp.ones((b,), jnp.int32),                    # q_lens
            jnp.zeros((b,), jnp.int32),                   # kv_lens
            jnp.zeros((b,), jnp.int32)]                   # last_idx
    if spec_k:
        lead.append(jnp.zeros((b,), jnp.int32))           # spec_len
    lead += [jnp.zeros((budget,), jnp.int32),             # feedback
             jnp.zeros((b,), jnp.int32),                  # prev_toks
             jnp.ones((b,), jnp.int32),                   # emit_mask
             jnp.zeros((b,), jnp.int32)]                  # produced
    pools = ((cache.k_pages, cache.v_pages, cache.k_scales, cache.v_scales)
             if kv_quant else (cache.k_pages, cache.v_pages))
    no_cow = jnp.full((b,), cache.num_pages, jnp.int32)
    args = tuple(lead) + pools + (
        cache.page_table_device(), no_cow, no_cow,
        jnp.zeros((b, 2), jnp.uint32), jnp.zeros((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.float32))
    closed = trace_callable(step, *args)
    import jax

    mp = 1 if mesh is None else int(mesh.shape["mp"])
    return static_hbm_report(
        closed, len(jax.tree.leaves(sp.params)), pools,
        batch=batch, avg_ctx=avg_ctx, mp=mp,
        moe_experts=int(getattr(cfg, "moe_experts", 0) or 0),
        moe_top_k=int(getattr(cfg, "moe_top_k", 0) or 0),
    )["hbm_bytes_per_token"]
