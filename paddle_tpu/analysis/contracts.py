"""tpulint cost-certification contracts — the committed expectations table.

One frozen :class:`CostContract` per certified flagship sub-target (keys
match the ``Finding.target`` strings the analyze functions emit). The
contract is DATA: the declared serving geometry the static models evaluate
at (``avg_ctx``/``batch``/``mp``), the JX007 drift tolerance against the
bench analytic model, the JX008 per-geometry VMEM budget, the JX009
collective inventory, and the dpquant HLO wire expectations. The checking
logic lives in :mod:`.cost_model`, :mod:`.vmem` and
:mod:`.collectives_audit`; changing a claim means editing THIS table in the
same commit that changes the program — anything else exits 2.

The VMEM budgets are per the ANALYSIS geometry (the tiny 2-layer h=32
configs the targets trace): snug numbers a structural regression (a block
suddenly spanning the full token axis, a scratch buffer scaling with the
pool) blows through, not production-HBM sizing.
"""
from __future__ import annotations

from dataclasses import dataclass

from .findings import Finding

#: JX008 budget for the tiny-geometry serving kernels (measured footprints
#: sit well under half of this; a block picking up a pool-sized axis
#: overshoots it immediately)
_SERVING_VMEM_BUDGET = 1 << 20


@dataclass(frozen=True)
class CostContract:
    """Declared cost expectations for one certified target."""

    avg_ctx: float = 8.0          # declared steady-state context tokens
    batch: int = 2                # lanes amortizing the weight sweep
    mp: int = 1                   # model-parallel ways
    hbm_tolerance: float | None = None      # JX007 relative drift gate
    vmem_budget_bytes: int | None = None    # JX008 per-kernel budget
    collectives: dict | None = None         # JX009 exact jaxpr inventory
    hlo_require_s8: bool = False            # JX009 HLO: s8 on the wire
    hlo_fp_allreduce_max_elems: int = 1024  # JX009 HLO: fp allowance
    moe_experts: int = 0                    # JX007 routed-expert count
    moe_top_k: int = 0                      # JX007 experts read per token


def _serving(mp: int = 1, *, vmem: bool = True,
             collectives: dict | None = None) -> CostContract:
    return CostContract(
        mp=mp, hbm_tolerance=0.02,
        vmem_budget_bytes=_SERVING_VMEM_BUDGET if vmem else None,
        collectives={} if collectives is None else collectives)


CONTRACTS: dict[str, CostContract] = {
    # round-9/10 unified steps (fp and int8w+int8kv)
    "serving-unified-step": _serving(),
    "serving-quant-unified-step": _serving(),
    # round-11 mp=2 sharded step: exactly 2 row-parallel fp psums per
    # layer x 2 layers at the analysis geometry — and NOTHING else
    "serving-spmd-unified-step": _serving(
        mp=2, vmem=False, collectives={"psum:float32": 4}),
    # round-12/13 spec + async steps ride the same per-op accounting
    "serving-spec-step": _serving(vmem=False),
    "serving-spec-quant-step": _serving(vmem=False),
    "serving-async-step": _serving(vmem=False),
    # round-21 tiered restore landings: pure scatter, collective-free
    "serving-tiered-restore-fp": CostContract(collectives={}),
    "serving-tiered-restore-int8": CostContract(collectives={}),
    "serving-tiered-restore-scale": CostContract(collectives={}),
    # round-14 quantized-dp train step: certified on COMPILED HLO — no
    # gradient-sized fp all-reduce, s8 payloads actually on the wire
    "train-dpquant-step": CostContract(
        collectives=None, hlo_require_s8=True,
        hlo_fp_allreduce_max_elems=1024),
    # round-25 MoE unified step: the hbm model charges a token only its
    # top-k experts' weights — matching the analysis config
    # (moe_experts=4, moe_top_k=2)
    "serving-moe-step": CostContract(
        hbm_tolerance=0.02, collectives={},
        moe_experts=4, moe_top_k=2),
    # round-25 expert-parallel train step: certified on COMPILED HLO —
    # the ep combine rides s8 collective-permutes. The fp all-reduce
    # allowance is WIDER than dpquant's: the mp axis legitimately psums
    # fp activations (~seq*h elems at the analysis geometry); only the
    # expert combine and gradient sync must stay quantized
    "train-moe-ep-step": CostContract(
        collectives=None, hlo_require_s8=True,
        hlo_fp_allreduce_max_elems=1 << 16),
}


def _pools(cache):
    if getattr(cache, "quantize_kv", False):
        return (cache.k_pages, cache.v_pages, cache.k_scales,
                cache.v_scales)
    return (cache.k_pages, cache.v_pages)


def cost_certify(target: str, closed, *, params=None,
                 cache=None) -> list[Finding]:
    """Run every contracted static check for ``target`` over one traced
    program. Targets without a table entry certify vacuously (returns [])
    — adding a target to the table is what opts it in."""
    contract = CONTRACTS.get(target)
    if contract is None:
        return []
    findings: list[Finding] = []
    if contract.hbm_tolerance is not None:
        import jax

        from . import cost_model

        geom = cost_model.geometry(
            params, cache, batch=contract.batch, avg_ctx=contract.avg_ctx,
            mp=contract.mp,
            moe_experts=contract.moe_experts,
            moe_top_k=contract.moe_top_k)
        findings += cost_model.check_hbm_model(
            closed, len(jax.tree.leaves(params)), _pools(cache), geom,
            contract.hbm_tolerance, target)
    if contract.vmem_budget_bytes is not None:
        from . import vmem

        findings += vmem.check_vmem(closed, contract.vmem_budget_bytes,
                                    target)
    if contract.collectives is not None:
        from . import collectives_audit

        findings += collectives_audit.check_collectives(
            closed, contract.collectives, target)
    return findings


def hlo_certify(target: str, fn, args, *, donate_argnums=(),
                mesh=None) -> list[Finding]:
    """Run the contracted compiled-HLO audit for ``target`` (the dpquant
    wire contract): collectives the partitioner materializes never appear
    in the jaxpr, so this side compiles."""
    contract = CONTRACTS.get(target)
    if contract is None:
        return []
    from . import collectives_audit

    entries = collectives_audit.hlo_collectives(
        fn, args, donate_argnums=donate_argnums, mesh=mesh)
    return collectives_audit.check_hlo_collectives(
        entries, target,
        fp_allreduce_max_elems=contract.hlo_fp_allreduce_max_elems,
        require_s8=contract.hlo_require_s8)
