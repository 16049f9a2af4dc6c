"""Share of the real rows the scheduler packed into the window's steps that
were prefill rows, from its own counters ``serving_rows_prefill`` and
``serving_rows_decode`` (incremented where ``_pack_dispatch`` packs them).
The note gives rows per dispatched step, to hold against the count the
benchmark makes from outside."""
LAYER, UNIT, BETTER, SOURCE = "scheduler", "%", "higher", "program_counter"


def read(run):
    counters = run.get("counters") or {}
    if "serving_rows_prefill" not in counters:
        return None
    prefill = counters["serving_rows_prefill"]
    rows = prefill + counters["serving_rows_decode"]
    if not rows:
        return None
    steps = counters.get("serving_steps")
    return 100.0 * prefill / rows, {
        "rows": int(rows), "steps": int(steps or 0),
        "rows_per_step": rows / steps if steps else None}
