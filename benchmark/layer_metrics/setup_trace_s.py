"""Set-up seconds in which jax traced or lowered a program: the union of the
``jax.trace`` (``jaxpr_trace``) and ``jax.lower`` (``jaxpr_to_mlir_module``)
intervals of every function that ended before the window, as the program's
listener recorded them (a jit nested in another counts once). The note
gives the two stages apart, the five largest functions, the step program's
seconds by rung of its row ladder (its ``step.rung.<rows>`` phases), and
what the record and its listener cost the process themselves."""
from . import _setup

LAYER, UNIT, BETTER, SOURCE = "set-up", "s", "lower", "program_counter"


def read(run):
    mine = _setup.before_window(run, ("jax.trace", "jax.lower"))
    if mine is None:
        return None
    from paddle_tpu.observability import process_registry

    stages = _setup.by(mine, lambda e: e.name)
    note = {"trace_s": round(stages.get("jax.trace", 0.0), 3),
            "lower_s": round(stages.get("jax.lower", 0.0), 3),
            "largest_s": _setup.largest(_setup.by(mine, lambda e: e.fun)),
            "step_rungs_s": _setup.rungs(
                _setup.before_window(run, ("step.rung.",)) or ()),
            "record_own_s": round(process_registry.counter(
                "setup_record_seconds").value, 3)}
    return _setup.union_s(mine), note
