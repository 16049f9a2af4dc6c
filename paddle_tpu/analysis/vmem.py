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

The second check is structural: the megakernel contract says the 4h MLP
hidden state NEVER materializes in HBM — inside the layer scan every
inter-kernel value is at most h wide (the ``(y2, s)`` pair at mp=1, the
pre-psum partials under mp). So for ``mega_vmem_resident`` targets we walk
the layer-scan body OUTSIDE the pallas kernels and flag any equation
output shaped like a 4h-wide ACTIVATION: a 4h dim on a token-extent row
axis (the packed stream ``t = b * chunk`` from the scan carry, its
8-padded kernel extent, or the lane count ``b``) with more than one row.
The row-axis condition is what separates the hidden state from parameter
plumbing — a ``b1.reshape(1, 4h)`` bias operand or a ``[h, 4h]`` weight
tile is HBM-resident by design; ``gelu(y2 @ w1)`` coming back at
``[t, 4h]`` is the leak the contract forbids.
"""
from __future__ import annotations

import math

from .cost_model import find_layer_scan
from .findings import Finding, rule
from .jaxpr_checks import _aval_bytes, _jaxprs_in, iter_eqns

JX008 = rule("JX008", "pallas kernel VMEM footprint over budget, or a "
                      "mega-resident value materializes in HBM")

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


def _eqns_outside_pallas(jaxpr):
    """Walk a jaxpr's equations recursively, NOT descending into
    ``pallas_call`` kernels (their internals live in VMEM by definition)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in _jaxprs_in(val):
                yield from _eqns_outside_pallas(sub)


def check_vmem(closed, budget_bytes: int | None, mega_resident: bool,
               target: str) -> list[Finding]:
    """JX008 over one traced step: per-kernel budget gate + (for mega
    targets) the 4h-never-in-HBM structural contract."""
    findings: list[Finding] = []
    fps = pallas_footprints(closed)
    if budget_bytes is not None:
        for fp in fps:
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
    if mega_resident:
        scan = find_layer_scan(closed.jaxpr)
        if scan is None:
            return findings + [Finding(
                rule=JX008, target=target, detail="no-layer-scan",
                message="mega_vmem_resident contract declared but the "
                        "traced step has no layer scan to check")]
        n_consts = int(scan.params.get("num_consts", 0))
        n_carry = int(scan.params.get("num_carry", 0))
        carries = [getattr(v, "aval", None)
                   for v in scan.invars[n_consts:n_consts + n_carry]]
        carries = [a for a in carries if a is not None and len(a.shape)]
        carry = max(carries, key=_aval_bytes)
        hidden = int(carry.shape[-1])
        # token extents an activation rides: the packed stream, its
        # 8-padded kernel extent, and the lane axis (carry is [b, chunk,
        # h] on the mega path)
        t = int(carry.shape[0] * carry.shape[1]) if len(carry.shape) == 3 \
            else int(carry.shape[0])
        token_dims = {d for d in (t, max(8, -(-t // 8) * 8),
                                  int(carry.shape[0])) if d > 1}
        body = scan.params["jaxpr"].jaxpr
        for eqn in _eqns_outside_pallas(body):
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is None or not getattr(aval, "shape", None):
                    continue
                shape = tuple(int(s) for s in aval.shape)
                size = 1
                for s in shape:
                    size *= s
                if (4 * hidden in shape and size > 4 * hidden
                        and shape[0] in token_dims):
                    findings.append(Finding(
                        rule=JX008, target=target,
                        detail=f"mega-hbm-residency:{eqn.primitive.name}",
                        message=f"mega layer scan materializes a 4h-wide "
                                f"value ({eqn.primitive.name} -> "
                                f"{tuple(aval.shape)}, h={hidden}) outside "
                                "the pallas kernels — the MLP hidden state "
                                "is supposed to live and die in VMEM",
                        data={"shape": tuple(int(s) for s in aval.shape)}))
                    break
    return findings
