"""One profiler capture around a stretch of the measured window.

``start`` opens a ``jax.profiler`` capture in a fixed directory of the
checkout and turns on the program's own host spans (``observability/
tracing.py`` writes each as a ``TraceAnnotation`` while a capture is open);
``stop`` closes it and reduces the ``.xplane.pb`` with ``reduce_trace``.
"""
from __future__ import annotations

import os
import shutil
import time

from . import reduce_trace


class Capture:
    def __init__(self, directory):
        self.directory = directory
        self.reduced = None
        self.started = False   # a capture was opened (it may be closed)
        self.t_start = None    # host clock (time.perf_counter) at opening
        self._open = False

    def start(self):
        import jax

        from paddle_tpu.observability.tracing import set_device_tracing
        from paddle_tpu.profiler.record import recorder

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans come from annotations
        jax.profiler.start_trace(self.directory, profiler_options=options)
        recorder.clear()
        recorder.enabled = True
        set_device_tracing(True)
        self._open = self.started = True
        self.t_start = time.perf_counter()

    def stop(self):
        if not self._open:
            return
        import jax

        from paddle_tpu.observability.tracing import set_device_tracing
        from paddle_tpu.profiler.record import recorder

        self._open = False
        try:
            jax.profiler.stop_trace()
        finally:
            set_device_tracing(False)
            recorder.enabled = False
            recorder.clear()
        self.reduced = reduce_trace.reduce(reduce_trace.load_xplane(
            reduce_trace.find_xplane(self.directory)))


def span(name):
    """A host span of the benchmark's own, on the capture's clock."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts the programs JAX lowers while it is armed: the measured window
    must hold none (a cache hit still lowers, so it counts too)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.armed and event == self.EVENT:
            self.count += 1
