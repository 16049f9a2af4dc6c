"""Define-by-run autograd over JAX.

This is the TPU-native answer to paddle's eager engine (reference:
paddle/fluid/eager/ — GradNodeBase grad_node_info.h:197, TensorWrapper
tensor_wrapper.h, generated ad_funcs): instead of codegen'd per-op C++ grad
nodes, every op application records ONE generic ``GradNode`` whose backward is
the ``jax.vjp`` of the op's pure function. Eager execution *is* jax eager
execution; under ``jax.jit`` tracing the same tape works on tracers, so jit and
eager share one code path (SURVEY.md §7.1 "one IR").
"""
from __future__ import annotations

import weakref
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from ..framework import flags
from ..framework import op_registry as _op_registry
from . import saved_tensors_hooks as _saved_hooks
from .grad_mode import is_grad_enabled

# Hook installed by paddle_tpu.amp to auto-cast inputs per-op (O1/O2).
# Signature: amp_cast_hook(op_name, leaves) -> leaves
amp_cast_hook: Callable | None = None

# Hook installed by the profiler to wrap op execution in RecordEvent ranges.
op_profile_hook: Callable | None = None

# Hook installed by paddle_tpu.analysis (tpulint TR001) to observe per-op
# input/output dtypes during a trace run. Signature:
# op_dtype_hook(op_name, in_dtypes, out_dtypes)
op_dtype_hook: Callable | None = None

# Hook installed by paddle_tpu.static while a Program is recording: called as
# hook(name, fn, treedef, leaves, out_tensors) after each op executes so the
# Program can append a replayable statement (define-by-run becomes
# record-and-replay; SURVEY.md §2.3 ProgramDesc parity).
static_record_hook: Callable | None = None

# Ops whose outputs are never differentiable (comparisons, index producers,
# predicates). Skipping the vjp for these avoids residual construction and
# dead GradNode allocation in hot training loops. Derived from the
# single-source op registry (framework/op_registry.py) — add ops THERE.
from ..framework.op_registry import non_diff_ops as _non_diff_ops

NON_DIFF_OPS = _non_diff_ops()


def _is_tensor(x) -> bool:
    from ..tensor.tensor import Tensor

    return isinstance(x, Tensor)


def _float0_zeros(aval):
    return np.zeros(aval.shape, dtype=jax.dtypes.float0)


def _is_diff_dtype(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.floating) or jnp.issubdtype(dtype, jnp.complexfloating)


class GradNode:
    """One recorded op application.

    ``vjp_fn`` is the jax.vjp closure (first-order fast path). For
    ``create_graph=True`` backward, the node re-applies the vjp *through the
    tape* using the saved pure function + input tensors (TensorWrapper parity),
    so higher-order gradients chain correctly.
    """

    __slots__ = (
        "name",
        "vjp_fn",
        "pure_fn",
        "input_tensors",
        "input_edges",
        "out_avals",
        "out_tensor_refs",
        "released",
        "saved_packed",
        "unpack_hook",
        "saved_low_prec",
        "unpin_closure",
        "__weakref__",
    )

    def __init__(self, name, vjp_fn, pure_fn, input_tensors, out_avals):
        self.name = name
        self.vjp_fn = vjp_fn
        self.pure_fn = pure_fn
        self.input_tensors = input_tensors  # strong refs, like TensorWrapper
        self.saved_packed = None  # saved_tensors_hooks storage (pack output)
        self.unpack_hook = None
        self.saved_low_prec = False
        # set by apply_op NEXT TO the closure it releases: drops the
        # closure's pinned copies of the saved (diff) inputs — they are
        # re-supplied as call arguments, so after a saved_tensors_hooks
        # pack they are dead weight holding device memory
        self.unpin_closure = None
        self.out_avals = out_avals
        self.out_tensor_refs: list = [None] * len(out_avals)
        self.released = False
        # edges: per diff-input, either ("node", producer, out_idx) or ("leaf", tensor)
        edges = []
        for t in input_tensors:
            if t._grad_node is not None:
                edges.append(("node", t._grad_node, t._out_index))
            else:
                edges.append(("leaf", t))
        self.input_edges = edges

    def release(self):
        self.vjp_fn = None
        self.pure_fn = None
        self.input_tensors = None
        self.saved_packed = None
        self.unpack_hook = None
        self.unpin_closure = None  # captures the op's input buffers
        self.released = True

    def attach_saved_hooks(self, pack_hook, unpack_hook):
        """saved_tensors_hooks capture: pack every saved input, drop the
        node's strong refs AND the eager vjp closure (its residuals pin
        device memory); backward unpacks and re-derives the vjp through
        ``pure_fn`` — one recomputed forward, remat-style. Backward ALWAYS
        sees the pack->unpack round trip (reference contract: lossy pairs
        like quantization must flow through). Intermediates truly unpin;
        LEAF inputs stay alive through their grad-accumulation edge
        (``input_edges``), so offloading a leaf saves no device memory —
        inherent to grad accumulation, not to the hooks."""
        if any(isinstance(t._data, jax.core.Tracer)
               for t in self.input_tensors):
            return  # under jit/static tracing hooks are inert (eager-only)
        with _saved_hooks.hooks_suspended():
            self.saved_packed = [pack_hook(t) for t in self.input_tensors]
        self.unpack_hook = unpack_hook
        self.input_tensors = None
        self.vjp_fn = None

    def _unpack_one(self, packed):
        from ..tensor.tensor import Tensor

        with _saved_hooks.hooks_suspended():
            v = self.unpack_hook(packed)
        return v if isinstance(v, Tensor) else Tensor(jnp.asarray(v))

    def zero_cotangents(self):
        cots = []
        for aval in self.out_avals:
            if _is_diff_dtype(aval.dtype):
                cots.append(jnp.zeros(aval.shape, aval.dtype))
            else:
                cots.append(_float0_zeros(aval))
        return cots

    def run_vjp(self, cotangents):
        """First-order backward: raw arrays in, raw arrays out."""
        if self.released:
            raise RuntimeError(
                f"GradNode for op '{self.name}' has been released. "
                "Call backward(retain_graph=True) to backward a graph twice."
            )
        if self.saved_packed is not None:
            # saved_tensors_hooks path: re-derive the vjp through the saved
            # pure function over the pack->unpack ROUND TRIP of every saved
            # input — always, never a live-buffer shortcut: a lossy hook
            # pair (quantized offload) must shape the gradients, and the
            # packed copy is immune to in-place mutation of the original
            datas = [self._unpack_one(p)._data for p in self.saved_packed]
            import contextlib

            # replay the forward's matmul-precision context: a half-
            # precision op captured under DEFAULT must not recompute its
            # vjp under the framework-global "highest" (3-6x emulation
            # cost and numerics that diverge from the non-hooked path)
            prec = (jax.default_matmul_precision("default")
                    if self.saved_low_prec else contextlib.nullcontext())
            with prec:
                _, vjp_fn = jax.vjp(self.pure_fn, *datas)
                return vjp_fn(tuple(cotangents))
        return self.vjp_fn(tuple(cotangents))

    def run_vjp_recorded(self, cotangent_tensors):
        """Higher-order backward: re-derive the vjp through the tape so the
        gradient computation itself is differentiable (create_graph=True)."""
        if self.released:
            raise RuntimeError(
                f"GradNode for op '{self.name}' has been released; cannot "
                "create_graph over a released graph."
            )
        pure_fn = self.pure_fn
        if self.saved_packed is not None:
            # intermediates: unpack (round-trip contract) and RESURRECT the
            # producer identity recorded in input_edges, so the
            # d(grad)/d(earlier) path through a dead intermediate is not
            # silently severed. Leaves: the original tensor (its edge is
            # where grad-of-grad must accumulate; it is alive by the edge
            # pin) — create_graph keeps leaf identity over lossy replay.
            input_tensors = []
            for i, packed in enumerate(self.saved_packed):
                kind, *rest = self.input_edges[i]
                if kind == "leaf":
                    input_tensors.append(rest[0])
                    continue
                t = self._unpack_one(packed)
                t.stop_gradient = False
                t._grad_node, t._out_index = rest
                input_tensors.append(t)
        else:
            input_tensors = self.input_tensors
        n_in = len(input_tensors)
        non_diff = [not _is_diff_dtype(a.dtype) for a in self.out_avals]
        avals = self.out_avals

        def grad_fn(*primals_and_cots):
            primals = primals_and_cots[:n_in]
            cots = list(primals_and_cots[n_in:])
            # Re-insert float0 zeros for non-differentiable outputs.
            full = []
            ci = 0
            for i, nd in enumerate(non_diff):
                if nd:
                    full.append(_float0_zeros(avals[i]))
                else:
                    full.append(cots[ci])
                    ci += 1
            _, vjp_fn = jax.vjp(pure_fn, *primals)
            return vjp_fn(tuple(full))

        diff_cots = [c for c, nd in zip(cotangent_tensors, non_diff) if not nd]
        return apply_op(self.name + "_grad", grad_fn, *input_tensors, *diff_cots)


def _check_nan_inf(name, arrays):
    for a in arrays:
        if isinstance(a, jax.core.Tracer) or not _is_diff_dtype(a.dtype):
            continue
        if bool(jnp.any(~jnp.isfinite(a))):
            msg = f"Operator {name} output contains NaN/Inf"
            if flags.flag("check_nan_inf_level") == 0:
                raise FloatingPointError(msg)
            print("WARNING:", msg)


def apply_op(name: str, fn: Callable, *args, **kwargs):
    """Execute ``fn`` (a pure jax function over unwrapped args) on Tensor
    arguments, recording a GradNode when grad is required.

    Tensors may appear anywhere in the (args, kwargs) pytree. Non-Tensor leaves
    and non-differentiable Tensors are closed over; the vjp runs only over
    differentiable (floating, stop_gradient=False) inputs.
    """
    from ..tensor.tensor import Tensor

    leaves, treedef = jax.tree.flatten((args, kwargs), is_leaf=_is_tensor)

    if _op_registry.STRICT[0] and not _op_registry.is_registered(name):
        raise AssertionError(
            f"op '{name}' dispatched via apply_op without a registry row — "
            "add it to framework/op_registry.py (single source of truth)")

    if amp_cast_hook is not None:
        leaves = amp_cast_hook(name, leaves)

    grad_on = is_grad_enabled() and name not in NON_DIFF_OPS
    diff_pos = []
    if grad_on:
        for i, leaf in enumerate(leaves):
            if (
                isinstance(leaf, Tensor)
                and not leaf.stop_gradient
                and _is_diff_dtype(leaf._data.dtype)
            ):
                diff_pos.append(i)

    out_treedef_box = [None]

    def rebuild(diff_datas):
        from ..framework.random import RngKey

        rebuilt = list(leaves)
        for p, d in zip(diff_pos, diff_datas):
            rebuilt[p] = d
        rebuilt = [
            l._data if isinstance(l, Tensor)
            else l.key if isinstance(l, RngKey)
            else l
            for l in rebuilt
        ]
        a, kw = jax.tree.unflatten(treedef, rebuilt)
        return a, kw

    def pure_fn(*diff_datas):
        a, kw = rebuild(diff_datas)
        out = fn(*a, **kw)
        out_leaves, out_td = jax.tree.flatten(out)
        out_treedef_box[0] = out_td
        return tuple(out_leaves)

    # hook returns an end-callback closing the dispatch range (or None)
    end_profile = op_profile_hook(name) if op_profile_hook is not None else None

    # capture input dtypes NOW: the saved-tensors-hooks path nulls the diff
    # leaves (unpin_closure) before dispatch returns, which would drop
    # exactly the float inputs from the TR001 dtype cross-check
    dtype_hook_ins = ([l._data.dtype for l in leaves if isinstance(l, Tensor)]
                      if op_dtype_hook is not None else None)

    # The framework default is matmul precision "highest" (true-fp32
    # semantics for user-facing float32). For HALF-precision ops that
    # default makes XLA emulate bf16 matmuls with multi-pass passes — 3-6x
    # slower and never what a user who cast to bf16 wants. When every
    # floating input is half precision, trace the op under native MXU
    # precision; fp32 ops keep the accurate default.
    low_prec = None
    for leaf in leaves:
        if isinstance(leaf, Tensor) and _is_diff_dtype(leaf._data.dtype):
            if leaf._data.dtype in (jnp.bfloat16, jnp.float16):
                low_prec = True if low_prec is None else low_prec
            else:
                low_prec = False
    import contextlib

    prec_ctx = (jax.default_matmul_precision("default") if low_prec
                else contextlib.nullcontext())

    # Eager executable cache: one jitted fwd (and vjp) per signature.
    # Only outside tracing (inside jit the surrounding trace fuses anyway)
    # and outside Program recording.
    cache_hit = False
    if (flags.flag("eager_op_cache") and static_record_hook is None
            and name not in _EAGER_CACHE_SKIP):
        from ..framework.random import RngKey

        tracer = any(
            isinstance(l._data, jax.core.Tracer) for l in leaves
            if isinstance(l, Tensor))
        if not tracer:
            entry, arg_pos, cache_key = _cached_entry(
                name, fn, leaves, treedef, diff_pos)
            cache_hit = entry is not None

    node = None
    try:
        with prec_ctx:
            if cache_hit:
                arg_datas = [
                    leaves[p]._data if isinstance(leaves[p], Tensor)
                    else leaves[p].key
                    for p in arg_pos
                ]
                try:
                    out_flat = entry.fwd(arg_datas)
                except (jax.errors.TracerArrayConversionError,
                        jax.errors.ConcretizationTypeError,
                        jax.errors.TracerIntegerConversionError,
                        jax.errors.TracerBoolConversionError,
                        jax.errors.NonConcreteBooleanIndexError):
                    # op body needs concrete values (data-dependent shapes /
                    # host math): blacklist this signature, run uncached
                    _EAGER_CACHE[cache_key] = False
                    cache_hit = False
                if cache_hit:
                    out_treedef_box[0] = entry.out_treedef
                    if diff_pos:
                        out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype)
                                     for o in out_flat]
                        didx = entry.diff_arg_idx

                        def vjp_fn(cots, _e=entry, _a=arg_datas):
                            return _e.vjp(_a, list(cots))

                        def pure_fn_c(*diff_datas, _e=entry, _a=arg_datas,
                                      _d=didx):
                            full = list(_a)
                            for j, d in zip(_d, diff_datas):
                                full[j] = d
                            return _e.fwd(full)

                        node = GradNode(name, vjp_fn, pure_fn_c,
                                        [leaves[p] for p in diff_pos],
                                        out_avals)

                        def _unpin(_a=arg_datas, _d=didx):
                            for j in _d:
                                _a[j] = None

                        node.unpin_closure = _unpin
            if not cache_hit and diff_pos:
                diff_datas = [leaves[p]._data for p in diff_pos]
                out_flat, vjp_fn = jax.vjp(pure_fn, *diff_datas)
                out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_flat]
                node = GradNode(name, vjp_fn, pure_fn, [leaves[p] for p in diff_pos], out_avals)

                def _unpin():
                    # pure_fn rebuilds from ``leaves``; diff rows are
                    # re-supplied as call arguments
                    for p in diff_pos:
                        leaves[p] = None

                node.unpin_closure = _unpin
            elif not cache_hit:
                out_flat = pure_fn()
    finally:
        # record the range even when dispatch raises — the failing op is
        # exactly the one worth seeing in the trace
        if end_profile is not None:
            end_profile()

    if node is not None and static_record_hook is None:
        # saved_tensors_hooks capture: pack the node's saved inputs (eager
        # only — attach_saved_hooks is a no-op on tracer inputs)
        _hooks = _saved_hooks.current_hooks()
        if _hooks is not None:
            node.attach_saved_hooks(*_hooks)
            node.saved_low_prec = bool(low_prec)
            if node.saved_packed is not None and node.unpin_closure:
                node.unpin_closure()

    if op_dtype_hook is not None:
        op_dtype_hook(name, dtype_hook_ins, [o.dtype for o in out_flat])

    if flags.flag("check_nan_inf"):
        _check_nan_inf(name, out_flat)

    out_tensors = []
    for i, data in enumerate(out_flat):
        if node is not None and _is_diff_dtype(data.dtype):
            t = Tensor(data, stop_gradient=False)
            t._grad_node = node
            t._out_index = i
            node.out_tensor_refs[i] = weakref.ref(t)
        else:
            t = Tensor(data, stop_gradient=True)
        out_tensors.append(t)

    if static_record_hook is not None:
        static_record_hook(name, fn, treedef, leaves, out_tensors)

    result = jax.tree.unflatten(out_treedef_box[0], out_tensors)
    return result


def make_op(name: str, fn: Callable) -> Callable:
    """Wrap a pure jax function as a framework op."""

    def op(*args, **kwargs):
        return apply_op(name, fn, *args, **kwargs)

    op.__name__ = name
    return op


# ---------------------------------------------------------------------------
# Eager executable cache (FLAGS_eager_op_cache)
#
# The reference treats eager dispatch latency as first-class (SURVEY §3.1:
# cached kernel selection, pre-generated ad_funcs). The TPU equivalent:
# ONE jitted executable per (op name, input signature) for forward, and one
# for backward. A composite framework op (layer_norm ≈ 8 jnp calls) then
# costs one device dispatch instead of eight. Backward recomputes the forward inside the cached vjp
# executable (remat semantics: less residency, ~30% extra FLOPs) — the
# classic eager-over-compiler trade, opt-in via the flag.
# ---------------------------------------------------------------------------

_EAGER_CACHE: dict = {}

# Ops that must NEVER dispatch through the cache: placement ops whose point
# is the output SHARDING (a cached executable would bake/ignore it), and ops
# that consult hidden global state inside their body (distribution samplers
# drawing from the default generator — caching would freeze the noise and
# leak traced keys into the generator).
_EAGER_CACHE_SKIP: set = {"reshard"}


def never_eager_cache(name: str):
    """Register ``name`` as uncacheable for eager dispatch."""
    _EAGER_CACHE_SKIP.add(name)


class _CachedOp:
    __slots__ = ("fwd", "vjp", "out_treedef", "diff_arg_idx")

    def __init__(self):
        self.fwd = None
        self.vjp = None
        self.out_treedef = None
        self.diff_arg_idx = ()


def _leaf_sig(leaves, diff_set):
    from ..framework.random import RngKey
    from ..tensor.tensor import Tensor

    sig = []
    for i, l in enumerate(leaves):
        if isinstance(l, Tensor):
            sig.append(("T", l._data.shape, str(l._data.dtype), i in diff_set))
        elif isinstance(l, RngKey):
            sig.append(("R",))
        else:
            try:
                hash(l)
            except TypeError:
                return None  # unhashable python leaf: fall back to uncached
            # type(l) is part of the key: 0 == 0.0 == False under dict
            # lookup, but full(shape, 1) and full(shape, True) trace to
            # different dtypes (jax.jit keys weak-typed scalars the same way)
            sig.append(("P", type(l), l))
    return tuple(sig)


def _fn_sig(fn, depth=0):
    """Identity of ``fn``'s BEHAVIOR: its code object plus the values it
    closes over. Op wrappers build a fresh closure per call (``x[idx]``,
    conv with stride/padding) — the closed-over config MUST be part of the
    cache key or two calls with equal tensor signatures but different
    config would share one compiled program. Unhashable cell contents
    (arrays) disable caching; nested function cells key by their own
    behavior signature (depth-limited)."""
    import types

    if not isinstance(fn, types.FunctionType):
        # bound methods, functools.partial, jax custom_vjp wrappers: key by
        # identity when hashable (stable for module-level callables)
        try:
            hash(fn)
        except TypeError:
            return None
        return ("obj", fn)

    def canon(v, d=0):
        # canonicalize common config containers (conv padding is a list of
        # tuples, interpolate sizes are lists) into hashable tuples
        from ..tensor.tensor import Tensor

        if isinstance(v, Tensor):
            # Tensor hashes by identity but its _data can be mutated in
            # place (optimizer update, set_value) after the executable baked
            # the traced value as a constant — caching would serve stale
            # results. Disable caching for Tensor-capturing closures.
            return None
        if isinstance(v, types.FunctionType):
            if d >= 2:
                return None
            sub = _fn_sig(v, d + 1)
            return None if sub is None else ("F", sub)
        if isinstance(v, (list, tuple)):
            items = []
            for it in v:
                ci = canon(it, d + 1)
                if ci is None and it is not None:
                    return None
                items.append(ci)
            return ("L", tuple(items))
        if isinstance(v, dict):
            try:
                entries = sorted(v.items())
            except TypeError:
                return None
            out = []
            for k, it in entries:
                ci = canon(it, d + 1)
                if ci is None and it is not None:
                    return None
                out.append((k, ci))
            return ("D", tuple(out))
        try:
            hash(v)
        except TypeError:
            return None
        # wrap with the concrete type so 2 / 2.0 / True closure configs do
        # not collide under dict ==-lookup (same rationale as _leaf_sig)
        return ("V", type(v), v)

    cells = []
    if fn.__closure__:
        for c in fn.__closure__:
            try:
                v = c.cell_contents
            except ValueError:
                return None  # unfilled cell
            cv = canon(v)
            if cv is None and v is not None:
                return None
            cells.append(cv)
    # Default args are config too: ``lambda v, i=i: ...`` stores i in
    # __defaults__, NOT the closure — two such lambdas share a code object
    # and must not share an executable.
    defaults = []
    for v in (fn.__defaults__ or ()):
        cv = canon(v)
        if cv is None and v is not None:
            return None
        defaults.append(cv)
    for k, v in sorted((fn.__kwdefaults__ or {}).items()):
        cv = canon(v)
        if cv is None and v is not None:
            return None
        defaults.append((k, cv))
    return (fn.__code__, tuple(cells), tuple(defaults))


def _cached_entry(name, fn, leaves, treedef, diff_pos):
    """(entry, arg positions, cache key) for this signature — or Nones."""
    from ..framework.random import RngKey
    from ..tensor.tensor import Tensor

    diff_set = frozenset(diff_pos)
    sig = _leaf_sig(leaves, diff_set)
    if sig is None:
        return None, None, None
    fsig = _fn_sig(fn)
    if fsig is None:
        return None, None, None
    key = (name, fsig, treedef, sig)
    entry = _EAGER_CACHE.get(key)
    if entry is not None:
        # LRU: a hit refreshes recency (plain dicts iterate in insertion
        # order; re-inserting moves the key to the back). FIFO eviction was
        # round-4 weak #9: a long-running mixed workload evicted its HOTTEST
        # executables first once the cache filled. Blacklist markers (False)
        # refresh too — evicting a hot marker would re-pay the failed trace
        # that created it on the next call.
        del _EAGER_CACHE[key]
        _EAGER_CACHE[key] = entry
        if entry is False:  # blacklisted: op body needs concrete values
            return None, None, None
    elif len(_EAGER_CACHE) >= 4096:
        # bounded cache: drop the least-recently-used quarter
        for old in list(_EAGER_CACHE)[:1024]:
            del _EAGER_CACHE[old]
    arg_pos = [i for i, l in enumerate(leaves)
               if isinstance(l, (Tensor, RngKey))]
    if entry is None:
        entry = _CachedOp()
        entry.diff_arg_idx = tuple(
            arg_pos.index(p) for p in diff_pos)
        template = [None if isinstance(l, (Tensor, RngKey)) else l
                    for l in leaves]

        def pure_all(arg_datas):
            rebuilt = list(template)
            for p, d in zip(arg_pos, arg_datas):
                rebuilt[p] = d
            a, kw = jax.tree.unflatten(treedef, rebuilt)
            out = fn(*a, **kw)
            out_leaves, out_td = jax.tree.flatten(out)
            entry.out_treedef = out_td
            return tuple(out_leaves)

        entry.fwd = jax.jit(pure_all)

        if diff_pos:
            didx = entry.diff_arg_idx

            def vjp_all(arg_datas, cots):
                def pd(*diff_datas):
                    full = list(arg_datas)
                    for j, d in zip(didx, diff_datas):
                        full[j] = d
                    return pure_all(full)

                _, vf = jax.vjp(pd, *[arg_datas[j] for j in didx])
                return vf(tuple(cots))

            entry.vjp = jax.jit(vjp_all)
        _EAGER_CACHE[key] = entry
    return entry, arg_pos, key
