"""The set-up record: where a process's time goes before it is ready (PR 38).

The window's instruments (``step_scope`` on the device, ``span()`` while a
capture is open, the serving registry's counters differenced over a window)
stop where set-up starts. This module keeps what comes before, always on and
in memory, on the clock of :func:`~.tracing.monotonic`:

- :data:`process_registry`, a :class:`~.metrics.MetricsRegistry` that is
  always enabled: ``setup_seconds{phase=}`` (each :func:`~.tracing.phase`),
  and, from the compile listener, ``jax_trace_seconds{fun=}``,
  ``jax_lower_seconds{fun=}``, ``jax_compile_seconds{fun=}`` (a backend
  compile, or a load from the persistent cache on a hit),
  ``jax_lowerings{fun=}``, ``jax_cache_hits{fun=}`` and
  ``jax_cache_misses{fun=}``. A stage's seconds are the UNION of its
  intervals: a function's counter takes only the seconds no earlier interval
  of the stage covered, so a jit nested in another (whose interval lies
  inside the outer one's and closes first) is not counted twice, and the
  counters of a stage sum to its union. ``setup_record_seconds`` is what the
  record and the listener cost the process themselves.
- :data:`setup_record`, a bounded list of :class:`SetupEntry` ``(name,
  start, end, parent, fun, cache)``: each phase, and each of jax's
  ``jaxpr_trace`` / ``jaxpr_to_mlir_module`` / ``backend_compile`` spans as
  ``jax.trace`` / ``jax.lower`` / ``jax.compile`` with its function. Read
  against a moment (a server's "ready", a benchmark's window) it says what
  was traced, lowered and compiled before and after it.

jax (0.9) emits its spans through ``jax.monitoring`` on ``time.time()``;
one offset taken at registration puts them on the record's clock. The
persistent cache's ``cache_hits`` / ``cache_misses`` events carry no
function name and are emitted synchronously inside the backend-compile span
they belong to, so each goes to the next such span that closes on the same
thread. The listener is registered once, when this package is imported.
"""
from __future__ import annotations

import bisect
import collections
import re
import threading
import time
from typing import NamedTuple

from .metrics import MetricsRegistry

__all__ = ["SetupEntry", "SetupRecord", "process_registry", "setup_record",
           "record_seconds", "fun_label"]

#: the record's clock: ``tracing.monotonic`` is this same function
_clock = time.perf_counter

#: the set-up instruments' registry: always on, one per process
process_registry = MetricsRegistry(enabled=True)

record_seconds = process_registry.counter(
    "setup_record_seconds",
    "seconds the set-up record and the compile listener spent themselves")


class SetupEntry(NamedTuple):
    """One interval of the set-up record. ``parent``: the phase open around
    a phase on its thread; ``fun``: the function of a jax span; ``cache``:
    ``"hit"`` / ``"miss"`` where the persistent cache answered a compile."""

    name: str
    start: float
    end: float
    parent: str | None = None
    fun: str | None = None
    cache: str | None = None


class SetupRecord:
    """Bounded, thread-safe list of :class:`SetupEntry`. Past ``maxlen`` the
    oldest entries go: the counters keep the totals."""

    def __init__(self, maxlen: int = 1 << 16):
        self._entries: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._waiters: list[threading.Thread] = []

    def add(self, entry: SetupEntry) -> None:
        with self._lock:
            self._entries.append(entry)

    def __len__(self) -> int:
        return len(self._entries)

    def entries(self) -> list[SetupEntry]:
        """The entries so far, once the phases still waiting for their arrays
        (:meth:`end_when_ready`) have ended (10 s at most)."""
        self.settle()
        with self._lock:
            return list(self._entries)

    def settle(self, timeout_s: float = 10.0) -> bool:
        """Wait for the pending :meth:`end_when_ready` phases; whether none is
        left."""
        deadline = _clock() + timeout_s
        with self._lock:
            waiters = list(self._waiters)
        for t in waiters:
            t.join(max(0.0, deadline - _clock()))
        with self._lock:
            self._waiters = [t for t in self._waiters if t.is_alive()]
            return not self._waiters

    def end_when_ready(self, name, start, parent, arrays, counter) -> None:
        """End the phase ``name`` when the device holds ``arrays``, without
        making the caller wait: a thread of its own waits for them. The
        device's work on them then counts to the phase, as it would if the
        caller blocked, and what the caller does meanwhile still overlaps
        it."""
        def wait():
            for a in arrays:
                try:
                    a.block_until_ready()
                except RuntimeError:
                    # deleted (donated) before it was waited for: its
                    # producer was dispatched; the wait ends here
                    pass
            end = _clock()
            self.add(SetupEntry(name, start, end, parent))
            counter.inc(end - start)

        t = threading.Thread(target=wait, name=f"setup-{name}", daemon=True)
        with self._lock:
            self._waiters.append(t)
        t.start()


#: THE process's set-up record
setup_record = SetupRecord()


class _Union:
    """Disjoint intervals, sorted: ``add`` returns the seconds of a new
    interval that none before it covered. Bounded: past ``limit`` intervals
    the older half goes (an interval that reaches back past them is charged
    in full)."""

    def __init__(self, limit: int = 1 << 13):
        self._starts: list[float] = []
        self._ends: list[float] = []
        self._limit = limit

    def add(self, s: float, e: float) -> float:
        i = bisect.bisect_left(self._ends, s)
        j, lo, hi, covered = i, s, e, 0.0
        while j < len(self._starts) and self._starts[j] <= e:
            a, b = self._starts[j], self._ends[j]
            covered += max(0.0, min(b, e) - max(a, s))
            lo, hi = min(lo, a), max(hi, b)
            j += 1
        self._starts[i:j] = [lo]
        self._ends[i:j] = [hi]
        if len(self._starts) > self._limit:
            half = self._limit // 2
            del self._starts[:half], self._ends[:half]
        return max(0.0, (e - s) - covered)


_JAX = "/jax/core/compile/"
_CACHE = "/jax/compilation_cache/"
#: jax's span -> (record name, seconds counter)
_STAGES = {
    _JAX + "jaxpr_trace_duration": ("jax.trace", "jax_trace_seconds"),
    _JAX + "jaxpr_to_mlir_module_duration": ("jax.lower",
                                             "jax_lower_seconds"),
    _JAX + "backend_compile_duration": ("jax.compile",
                                        "jax_compile_seconds"),
}
_WRAPPED = re.compile(r"^(?:jit|pmap)\((.*)\)$")


def fun_label(name) -> str:
    """One label for a function at every stage: jax names the trace by the
    Python function (``step``) and the lowering and compile by the module
    (``jit(step)``)."""
    m = _WRAPPED.match(str(name))
    return m.group(1) if m else str(name)


class _CompileListener:
    def __init__(self, record: SetupRecord, registry: MetricsRegistry):
        self.record = record
        # jax's spans are on time.time(); the record's clock is _clock
        self.offset = _clock() - time.time()
        self._lock = threading.Lock()
        self._unions = {stage: _Union() for stage in _STAGES}
        self._seconds = {
            stage: registry.counter(
                counter, f"union seconds of jax's {name[4:]} spans, by the "
                "function charged (nested spans count once)",
                labels=("fun",))
            for stage, (name, counter) in _STAGES.items()}
        self._lowerings = registry.counter(
            "jax_lowerings", "programs lowered to MLIR, by function",
            labels=("fun",))
        self._hits = registry.counter(
            "jax_cache_hits", "compiles the persistent cache answered",
            labels=("fun",))
        self._misses = registry.counter(
            "jax_cache_misses",
            "compiles the persistent cache missed (and was written)",
            labels=("fun",))
        self._pending = threading.local()

    def on_event(self, event, **kwargs):
        if event in (_CACHE + "cache_hits", _CACHE + "cache_misses"):
            self._pending.__dict__.setdefault("events", []).append(
                "hit" if event.endswith("hits") else "miss")

    def on_span(self, event, start, end, **kwargs):
        stage = _STAGES.get(event)
        if stage is None:
            return
        t0 = _clock()
        fun = fun_label(kwargs.get("fun_name", "?"))
        s, e = start + self.offset, end + self.offset
        cache = None
        if stage[0] == "jax.compile":
            pending = self._pending.__dict__.pop("events", ())
            for what in pending:
                (self._hits if what == "hit" else self._misses).labels(
                    fun=fun).inc()
            cache = ("hit" if "hit" in pending
                     else "miss" if "miss" in pending else None)
        elif stage[0] == "jax.lower":
            self._lowerings.labels(fun=fun).inc()
        with self._lock:
            new = self._unions[event].add(s, e)
        self._seconds[event].labels(fun=fun).inc(new)
        self.record.add(SetupEntry(stage[0], s, e, None, fun, cache))
        record_seconds.inc(_clock() - t0)


def _register():
    import jax.monitoring

    listener = _CompileListener(setup_record, process_registry)
    jax.monitoring.register_event_listener(listener.on_event)
    jax.monitoring.register_event_time_span_listener(listener.on_span)
    return listener


_listener = _register()
