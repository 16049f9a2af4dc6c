"""Host-span tracing API — the serving/training timeline half of
``paddle_tpu/observability`` (round 15).

Thin, hot-path-safe wrappers over the profiler's one in-process event
buffer (``profiler/record.py``):

- :func:`span` — a named host range (``with span("pack_dispatch"): ...``)
  recorded as a Chrome ``X`` duration event when a profiler RECORD window
  is open, and nested inside a ``jax.profiler.TraceAnnotation`` so the
  host range lines up with device activity in an xplane/TensorBoard
  capture (host/device correlation). When no window is open the call
  returns a shared no-op context manager — one flag check, no allocation.
- :func:`request_begin` / :func:`request_event` / :func:`request_end` —
  per-request ASYNC span lanes (Chrome ``b``/``n``/``e`` phases matched
  by ``(category, id, name)``): one lane per request showing its whole
  lifecycle (admit → prefill chunks → decode/spec steps → preemption /
  replay → eos) across the scheduler steps that interleave it.
- :func:`step_scope` — ``jax.named_scope`` for one name of
  :data:`STEP_SCOPES`, the closed list of parts of the two benchmarked
  step programs, or of :data:`STEP_SUBSCOPES`, parts of those parts. The name rides every HLO operation's ``op_name`` path
  into the device trace, where ``benchmark/scope_trace.py`` charges each
  operation's own time to the innermost such name.
- :func:`phase` — a named SET-UP range (``with phase("weights.make"):``):
  what :func:`span` records while a window is open, and always, window or
  not, an entry of ``setup_record`` and the seconds on the process
  registry's ``setup_seconds{phase=}`` (``observability/startup.py``).
  A phase runs once per build, never on a step's call path.
- :func:`monotonic` / :func:`monotonic_ns` — THE timing clock for
  ``paddle_tpu/inference`` and ``paddle_tpu/distributed`` (tpulint AL006
  flags raw ``time.perf_counter()`` there; timing belongs to this layer
  so instrumented durations and trace timestamps share one clock).

Everything exports through the existing profiler facade: run under
``profiler.Profiler`` (or anything that flips ``recorder.enabled``) and
``export_chrome_tracing`` writes one trace with the op ranges, the
serving spans and the request lanes together.
"""
from __future__ import annotations

import contextlib
import threading
import time

from ..profiler.record import now_ns, recorder
from .startup import (SetupEntry, process_registry, record_seconds,
                      setup_record)

__all__ = [
    "span", "phase", "request_begin", "request_event", "request_end",
    "tracing_active", "monotonic", "monotonic_ns",
    "device_annotation", "set_device_tracing", "STEP_SCOPES",
    "STEP_SUBSCOPES", "step_scope",
]

monotonic = time.perf_counter
monotonic_ns = time.perf_counter_ns


def tracing_active() -> bool:
    """True while a profiler RECORD window is open (spans are recorded)."""
    return recorder.enabled


#: shared no-op context manager — the disabled fast path (re-enterable;
#: no caller binds the span value)
_NULL = contextlib.nullcontext()


#: flipped by the profiler facade while a jax/PJRT xplane capture is live;
#: spans only pay the TraceAnnotation (C++ TraceMe) when a device trace
#: can actually consume it — host-only tracing stays append-cheap
_DEVICE_TRACING = [False]


def set_device_tracing(active: bool) -> None:
    _DEVICE_TRACING[0] = bool(active)


def device_annotation(name: str):
    """``jax.profiler.TraceAnnotation`` while a device (xplane) capture is
    live — host ranges then correlate with device lanes in the capture
    viewed next to the chrome trace; the shared no-op otherwise."""
    if not _DEVICE_TRACING[0]:
        return _NULL
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return _NULL


class _Span:
    __slots__ = ("name", "category", "_start", "_ann")

    def __init__(self, name, category):
        self.name = name
        self.category = category
        self._start = None
        self._ann = None

    def __enter__(self):
        self._ann = device_annotation(self.name)
        self._ann.__enter__()
        self._start = now_ns()
        return self

    def __exit__(self, *exc):
        end = now_ns()
        if self._start is not None:
            self._ended(self._start, end)
            self._start = None
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(*exc)
        return False

    def _ended(self, start_ns, end_ns):
        # a no-op unless a record window is open
        recorder.record(self.name, start_ns, end_ns, category=self.category)


def span(name: str, category: str = "serving"):
    """A named host range. One flag check + shared no-op when no profiler
    window is open; a recorded ``X`` event (and a device-side
    TraceAnnotation) when one is."""
    if not recorder.enabled:
        return _NULL
    return _Span(name, category)


# -- set-up phases ---------------------------------------------------------

_SETUP_SECONDS = process_registry.counter(
    "setup_seconds", "seconds of each set-up phase (observability.phase)",
    labels=("phase",))

#: the phases open on each thread, innermost last: a phase's parent
_OPEN = threading.local()


class _Phase(_Span):
    __slots__ = ("_parent", "_arrays")

    def __init__(self, name):
        super().__init__(name, "setup")
        self._parent = self._arrays = None

    def __enter__(self):
        stack = _OPEN.__dict__.setdefault("stack", [])
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        return super().__enter__()

    def end_when_ready(self, tree):
        """End this phase when the device holds the arrays of ``tree``, not
        when its block does, and without waiting for them here: the device
        work the block dispatched then counts to it, and what follows the
        block still overlaps that work
        (:meth:`~.startup.SetupRecord.end_when_ready`)."""
        import jax

        self._arrays = [a for a in jax.tree.leaves(tree)
                        if isinstance(a, jax.Array)]

    def __exit__(self, *exc):
        _OPEN.stack.pop()
        return super().__exit__(*exc)

    def _ended(self, start_ns, end_ns):
        super()._ended(start_ns, end_ns)
        t0 = monotonic()
        counter = _SETUP_SECONDS.labels(phase=self.name)
        start = start_ns * 1e-9
        if self._arrays is not None:
            setup_record.end_when_ready(self.name, start, self._parent,
                                        self._arrays, counter)
            self._arrays = None
        else:
            end = end_ns * 1e-9
            setup_record.add(SetupEntry(self.name, start, end, self._parent))
            counter.inc(end - start)
        record_seconds.inc(monotonic() - t0)


def phase(name: str) -> _Phase:
    """A named set-up range: weights made (``weights.make``) or placed
    (``weights.place``), the KV pools (``kv.pools``), the host side of a
    step program's build (``step.build``), a rung of its row ladder while
    jax traces it (``step.rung.<rows>``). Always recorded, in
    ``setup_record`` on :func:`monotonic` (with the phase open around it on
    its thread as ``parent``) and as ``setup_seconds{phase=name}`` on the
    process registry; as a Chrome ``X`` event and a TraceAnnotation too
    while a record window or capture is open, like :func:`span`. Never on a
    step's call path: a phase inside a traced function runs only while jax
    traces it."""
    return _Phase(name)


# -- per-request async lanes -------------------------------------------------

#: async lane name shared by every request span; Chrome matches b/n/e
#: phases on (category, id, name), so the id (req_id) is the lane key
REQUEST_SPAN = "request"
_REQ_CAT = "request"


def request_begin(req_id, args=None) -> bool:
    """Open the async lifecycle lane of one request. Returns whether the
    begin was recorded — the caller gates matching ``request_end`` on it
    (an ``e`` with no ``b`` renders as an unmatched phase)."""
    if not recorder.enabled:
        return False
    recorder.record_raw(REQUEST_SPAN, "b", id=req_id, category=_REQ_CAT,
                        args=args)
    return True


def request_event(req_id, name: str, args=None) -> None:
    """An instant on one request's lane (admit / prefill_chunk / decode /
    preempt / spec_accept / eos ...)."""
    if not recorder.enabled:
        return
    recorder.record_raw(name, "n", id=req_id, category=_REQ_CAT, args=args)


def request_end(req_id, args=None) -> None:
    if not recorder.enabled:
        return
    recorder.record_raw(REQUEST_SPAN, "e", id=req_id, category=_REQ_CAT,
                        args=args)


# -- device-side scopes of the step programs ----------------------------------

#: the parts of the serving step (``models/gpt.py build_unified_step``) and
#: of the train step (``models/gpt_spmd.py``). ``layers`` wraps the layer
#: scan itself, so the scan's own slicing and stacking of what it carries
#: fall under ``layers`` and under no part. The benchmark's scope readers
#: depend on these names letter for letter (``benchmark/scope_trace.py``
#: holds the same tuple, and a test of its own holds the two equal).
STEP_SCOPES = (
    "cow", "embed", "layers", "ln", "qkv", "kv_write", "attn", "attn_out",
    "mlp", "head", "sample", "head_loss", "optimizer", "pipeline",
)

#: parts OF a part (PR 28), each used only inside the scope named with it: a
#: reader that knows :data:`STEP_SCOPES` alone charges them to that scope,
#: one that knows these too sees the split. Inside ``mlp``, a routed layer's
#: ``moe_route`` (router product, top-k, the sort and gather into expert
#: order, the weighted sum back), ``moe_experts`` (the grouped GEMMs and the
#: activation between them) and ``moe_shared`` (the shared experts); inside
#: ``attn``, a latent cache's ``attn_absorb`` (``W_kv_b``'s key half on the
#: query, its value half behind the softmax) and, where a learned indexer
#: chooses the keys attention reads (``models/glm_moe_dsa.py``), ``attn_index``
#: (the indexer's projections, the index-key write, the score kernel) and
#: ``attn_select`` (the exact top-k that turns scores into the selection);
#: where a model's attention layers come in kinds (``models/cohere2_moe.py``),
#: ``attn_window`` and ``attn_full``, so that the paged kernel's time divides
#: by layer kind.
STEP_SUBSCOPES = {
    "moe_route": "mlp", "moe_experts": "mlp", "moe_shared": "mlp",
    "attn_absorb": "attn", "attn_index": "attn", "attn_select": "attn",
    "attn_window": "attn", "attn_full": "attn",
}


def step_scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`STEP_SCOPES`. Metadata
    only: it names the operations traced under it and adds none. Any other
    name is an error at trace time."""
    if name not in STEP_SCOPES and name not in STEP_SUBSCOPES:
        raise ValueError(f"step_scope: {name!r} is not one of STEP_SCOPES "
                         f"{STEP_SCOPES} or STEP_SUBSCOPES "
                         f"{tuple(STEP_SUBSCOPES)}")
    import jax

    return jax.named_scope(name)
