"""tpulint VMEM footprint estimator (JX008).

Every ``pallas_call`` reached by the jaxpr walk carries its full launch
geometry in the equation params: ``grid_mapping.block_mappings`` hold the
per-operand BlockSpec block shapes (the autotuned ``(bm, bn, bk)``/chunk
tiles the callers picked) and ``num_scratch_operands`` counts the
``pltpu.VMEM`` scratch buffers (their avals are the kernel jaxpr's trailing
invars). From that we bound the kernel's live VMEM per grid step:

    2 x sum(block bytes over in/out operands)   # double-buffered pipeline
      + sum(scratch aval bytes)                 # persistent across steps

and gate it against the per-geometry budget the target's contract declares.
The x2 models Mosaic's default input/output window double-buffering — a
deliberate over- rather than under-estimate, and deterministic either way.
"""
from __future__ import annotations

import math

from .findings import Finding, rule
from .jaxpr_checks import _aval_bytes, iter_eqns

JX008 = rule("JX008", "pallas kernel VMEM footprint over budget")

#: live buffer multiplier for in/out block windows (double-buffered)
LIVE_BUFFERS = 2


def _block_bytes(bm) -> int:
    """One operand's block window bytes: BlockSpec block shape (squeezed /
    ``Mapped`` dims count 1) x the operand dtype."""
    return math.prod(bm.ref_aval.shape) * bm.array_aval.dtype.itemsize


def pallas_footprints(closed) -> list[dict]:
    """Per-``pallas_call`` VMEM footprint estimates for a traced program."""
    out = []
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params["grid_mapping"]
        blocks = sum(_block_bytes(bm) for bm in gm.block_mappings)
        scratch = 0
        n_scratch = int(getattr(gm, "num_scratch_operands", 0))
        if n_scratch:
            inner = eqn.params["jaxpr"]
            scratch = sum(_aval_bytes(v.aval)
                          for v in inner.invars[-n_scratch:])
        out.append({
            "kernel": (eqn.params["name"]
                       or eqn.params["jaxpr"].debug_info.func_name),
            # a dynamic grid bound (``ragged_paged_attention``'s work
            # items) is known only at run time: None in the report
            "grid": tuple(int(g) if isinstance(g, int) else None
                          for g in gm.grid),
            "block_bytes": blocks,
            "scratch_bytes": scratch,
            "vmem_bytes": LIVE_BUFFERS * blocks + scratch,
        })
    return out


def check_vmem(closed, budget_bytes: int, target: str) -> list[Finding]:
    """JX008 over one traced step: the per-kernel budget gate."""
    findings: list[Finding] = []
    for fp in pallas_footprints(closed):
        if fp["vmem_bytes"] > budget_bytes:
            findings.append(Finding(
                rule=JX008, target=target,
                detail=f"vmem-budget:{fp['kernel']}",
                message=f"kernel {fp['kernel']} needs "
                        f"~{fp['vmem_bytes']} VMEM bytes per grid step "
                        f"(blocks {fp['block_bytes']} x{LIVE_BUFFERS} "
                        f"+ scratch {fp['scratch_bytes']}) over the "
                        f"declared budget {budget_bytes}",
                data=fp))
    return findings
