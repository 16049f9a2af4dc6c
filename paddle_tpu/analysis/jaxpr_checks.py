"""tpulint trace-level rules — walk closed jaxprs + eager op-dtype traces.

The MPK lever (PAPERS.md: compiler-level analysis over traced tensor
programs) applied defensively: abstract-trace a framework callable, walk the
closed jaxpr (recursing through ``pjit``/``scan``/``while``/``cond``/
``remat``/``custom_vjp`` sub-jaxprs) and flag the TPU hazard classes this
repo has repeatedly caught by hand.

Jaxpr rules:

- **JX001 f64-leak** — an equation produces ``float64`` while no input or
  constant of the program is f64: a weak-typed Python scalar / numpy default
  promoted the chain under the framework's x64 mode (the hsigmoid-loss
  accumulator bug class — 2x HBM + no-MXU on TPU).
- **JX002 dot-relayout** — a ``dot_general`` contracts an *interior* dim of
  a large operand (contractions not a prefix/suffix of the non-batch dims):
  Mosaic/XLA must physically relayout the operand before the MXU pass.
- **JX003 big-broadcast** — ``broadcast_in_dim`` materializes an
  intermediate over the size threshold with a large expansion factor (a
  mask/outer-product the fused consumer could have formed lazily).
- **JX004 host-callback** — callback/debug/infeed primitives inside a hot
  jit: every call is a device->host round trip serializing the step.
- **JX005 donated-unconsumed** — a donated argument whose (shape, dtype)
  matches no output: XLA cannot alias it, the donation silently buys
  nothing and the buffer is dead weight (checked via ``jax.eval_shape``).
- **JX006 const-bloat** — closed-over constants above the size threshold
  baked into the program (re-uploaded per executable, invisible to
  donation; thread them as arguments instead).

Eager-trace rule (the op-registry AMP cross-check — hooks
``autograd.engine.op_dtype_hook`` during a real model forward):

- **TR001 op-dtype-promotion** — an op's output dtype is *wider* than its
  widest floating input and the registry row does not justify it: f64 out
  of <=f32 inputs is always a leak; bf16->f32 is expected only for
  ``amp="black"`` rows (precision-sensitive ops hold fp32 by design).
"""
from __future__ import annotations

import math
from collections import Counter

from .findings import Finding, rule

JX001 = rule("JX001", "float64 produced in a program with no f64 inputs")
JX002 = rule("JX002", "dot_general contracts an interior dim (forced relayout)")
JX003 = rule("JX003", "materialized broadcast intermediate above threshold")
JX004 = rule("JX004", "host callback / sync primitive inside a jit")
JX005 = rule("JX005", "donated buffer matches no output (donation wasted)")
JX006 = rule("JX006", "closed-over constants bloat the program")
TR001 = rule("TR001", "op output dtype wider than inputs (AMP cross-check)")

_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback", "outside_call", "infeed", "outfeed",
}

BROADCAST_BYTES = 16 << 20   # JX003: flag materialized expansions >= 16 MiB
BROADCAST_RATIO = 64         # ... that blew up >= 64x over their input
CONST_BYTES = 1 << 20        # JX006: closed-over consts >= 1 MiB total
DOT_OPERAND_BYTES = 1 << 20  # JX002: only large operands are worth a report


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------


def _jaxprs_in(val):
    from jax.extend import core as jex_core

    if isinstance(val, jex_core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex_core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _jaxprs_in(v)


def iter_eqns(jaxpr):
    """Every equation in ``jaxpr``, recursing through sub-jaxpr params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _jaxprs_in(val):
                yield from iter_eqns(sub)


def _aval_bytes(aval) -> int:
    try:
        return int(math.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _mesh_scope(fn, mesh=None):
    """``(traceable, context)`` for abstract-tracing ``fn``. The spmd train
    step enters its mesh on every call, which jax refuses under a trace
    (``set_mesh`` is only legal outside ``jit``): its inner jit (``fn.jit``)
    is traced instead, with the mesh (``fn.mesh``) entered out here."""
    import contextlib

    import jax

    mesh = mesh if mesh is not None else getattr(fn, "mesh", None)
    ctx = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    return getattr(fn, "jit", fn), ctx


def trace_callable(fn, *args, mesh=None, **kwargs):
    """Abstract-trace ``fn`` to a ClosedJaxpr (no FLOPs run). ``mesh``
    supplies the sharding context the spmd paths need for bare
    PartitionSpec constraints."""
    import jax

    fn, ctx = _mesh_scope(fn, mesh)
    with ctx:
        return jax.make_jaxpr(fn, **kwargs)(*args)


# ---------------------------------------------------------------------------
# jaxpr rules
# ---------------------------------------------------------------------------


def analyze_jaxpr(closed_jaxpr, target: str) -> list[Finding]:
    """Run JX001/JX002/JX003/JX004/JX006 over one closed jaxpr."""
    import jax.numpy as jnp
    import numpy as np

    findings: list[Finding] = []
    jaxpr = closed_jaxpr.jaxpr

    def _is_f64(aval):
        return getattr(aval, "dtype", None) == jnp.float64

    input_f64 = any(_is_f64(v.aval) for v in jaxpr.invars) or any(
        np.asarray(c).dtype == np.float64 for c in closed_jaxpr.consts)

    f64_prims: Counter = Counter()
    seen_dot: set = set()
    seen_bcast: set = set()
    seen_cb: set = set()
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        # JX004 — host callbacks
        if prim in _CALLBACK_PRIMS and prim not in seen_cb:
            seen_cb.add(prim)
            findings.append(Finding(
                rule=JX004, target=target, detail=prim,
                message=f"host-callback primitive '{prim}' inside the "
                        "traced program — each call is a device->host "
                        "round trip serializing the step"))
        # JX001 — f64 leak
        if not input_f64 and prim != "convert_element_type":
            for v in eqn.outvars:
                if _is_f64(getattr(v, "aval", None)):
                    f64_prims[prim] += 1
                    break
        if not input_f64 and prim == "convert_element_type":
            if any(_is_f64(getattr(v, "aval", None)) for v in eqn.outvars):
                f64_prims[prim] += 1
        # JX002 — interior contraction
        if prim == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            for side, cdims, bdims, var in (
                    ("lhs", lc, lb, eqn.invars[0]),
                    ("rhs", rc, rb, eqn.invars[1])):
                aval = getattr(var, "aval", None)
                if aval is None or _aval_bytes(aval) < DOT_OPERAND_BYTES:
                    continue
                nonbatch = [d for d in range(len(aval.shape))
                            if d not in bdims]
                cpos = sorted(nonbatch.index(d) for d in cdims
                              if d in nonbatch)
                if not cpos:
                    continue
                contiguous = cpos == list(range(cpos[0], cpos[-1] + 1))
                touches_edge = cpos[0] == 0 or cpos[-1] == len(nonbatch) - 1
                if contiguous and touches_edge:
                    continue
                key = (side, tuple(aval.shape), tuple(cdims))
                if key in seen_dot:
                    continue
                seen_dot.add(key)
                findings.append(Finding(
                    rule=JX002, target=target,
                    detail=f"{side}:{'x'.join(map(str, aval.shape))}"
                           f":c{','.join(map(str, cdims))}",
                    message=f"dot_general contracts interior dims {cdims} "
                            f"of its {side} {tuple(aval.shape)} "
                            f"({aval.dtype}) — the operand must be "
                            "relayouted before the MXU pass; transpose at "
                            "construction instead"))
        # JX003 — materialized broadcast
        if prim == "broadcast_in_dim":
            out = eqn.outvars[0].aval
            inb = _aval_bytes(getattr(eqn.invars[0], "aval", None)) or 1
            outb = _aval_bytes(out)
            if outb >= BROADCAST_BYTES and outb // inb >= BROADCAST_RATIO:
                key = tuple(out.shape)
                if key in seen_bcast:
                    continue
                seen_bcast.add(key)
                findings.append(Finding(
                    rule=JX003, target=target,
                    detail=f"{'x'.join(map(str, out.shape))}:{out.dtype}",
                    message=f"broadcast materializes {tuple(out.shape)} "
                            f"({out.dtype}, {outb >> 20} MiB, "
                            f"{outb // inb}x its input) — keep masks/outer "
                            "products lazy inside the consuming op"))
    for prim, n in sorted(f64_prims.items()):
        findings.append(Finding(
            rule=JX001, target=target, detail=prim,
            message=f"'{prim}' produces float64 ({n} site{'s' * (n > 1)}) "
                    "in a program whose inputs are <= f32 — a weak-typed "
                    "python/numpy constant promoted the chain under x64 "
                    "(2x HBM, off the MXU fast path)"))
    # JX006 — const bloat
    total = sum(int(np.asarray(c).nbytes) for c in closed_jaxpr.consts)
    if total >= CONST_BYTES:
        biggest = max(closed_jaxpr.consts, key=lambda c: np.asarray(c).nbytes)
        findings.append(Finding(
            rule=JX006, target=target, detail="consts",
            message=f"{total >> 20} MiB of closed-over constants baked into "
                    f"the program (largest {np.asarray(biggest).shape}) — "
                    "thread them as arguments so they can be donated/"
                    "deduplicated"))
    return findings


def check_donation(fn, args, donate_argnums, target: str) -> list[Finding]:
    """JX005: every donated argument must have a (shape, dtype)-matching
    output, or XLA cannot alias it and the donation is silently wasted."""
    import jax

    fn, ctx = _mesh_scope(fn)
    with ctx:
        out_shape = jax.eval_shape(fn, *args)
    out_leaves = jax.tree.leaves(out_shape)
    avail = Counter((tuple(o.shape), str(o.dtype)) for o in out_leaves)
    findings: list[Finding] = []
    for i in donate_argnums:
        for leaf in jax.tree.leaves(args[i]):
            key = (tuple(leaf.shape), str(leaf.dtype))
            if avail[key] > 0:
                avail[key] -= 1
            else:
                findings.append(Finding(
                    rule=JX005, target=target,
                    detail=f"arg{i}:{'x'.join(map(str, leaf.shape))}"
                           f":{leaf.dtype}",
                    message=f"donated argument {i} "
                            f"({tuple(leaf.shape)}, {leaf.dtype}) matches "
                            "no output shape/dtype — XLA cannot alias it; "
                            "the donation buys nothing"))
    return findings


# ---------------------------------------------------------------------------
# eager op-dtype trace (TR001 — the op-registry AMP cross-check)
# ---------------------------------------------------------------------------


def _float_width(dtype) -> int:
    import jax.numpy as jnp

    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.dtype(dtype).itemsize
    return 0


class OpDtypeTrace:
    """Context manager: records (op, input dtypes, output dtypes) for every
    framework op dispatched while active, via ``engine.op_dtype_hook``."""

    def __init__(self):
        self.records: list[tuple] = []

    def __enter__(self):
        from ..autograd import engine

        self._engine = engine
        self._prev = engine.op_dtype_hook
        engine.op_dtype_hook = self._record
        return self

    def __exit__(self, *exc):
        self._engine.op_dtype_hook = self._prev
        return False

    def _record(self, name, in_dtypes, out_dtypes):
        self.records.append((name, tuple(in_dtypes), tuple(out_dtypes)))

    def findings(self, target: str) -> list[Finding]:
        from ..framework.op_registry import OP_TABLE

        out: list[Finding] = []
        seen: set = set()
        for name, ins, outs in self.records:
            float_ins = [d for d in ins if _float_width(d)]
            if not float_ins:
                continue
            widest_in = max(_float_width(d) for d in float_ins)
            for od in outs:
                w = _float_width(od)
                if w <= widest_in:
                    continue
                spec = OP_TABLE.get(name)
                # precision-sensitive rows hold fp32 by design; wider than
                # fp32 is never justified by any AMP class
                if (spec is not None and spec.amp == "black" and w <= 4):
                    continue
                if name.endswith("_grad"):
                    continue  # backward mirrors forward; report the fwd op
                key = (name, str(od))
                if key in seen:
                    continue
                seen.add(key)
                amp_cls = spec.amp if spec is not None else "<unregistered>"
                out.append(Finding(
                    rule=TR001, target=target, detail=name,
                    message=f"op '{name}' promotes {min(float_ins, key=_float_width)}"
                            f"->{od} (registry amp class: {amp_cls}) — "
                            "dtype-promotion leak; keep compute in the "
                            "input dtype or register the op amp='black'"))
        return out
