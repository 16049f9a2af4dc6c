"""Share of the rows the step program ran that held a token: the scheduler's
``serving_rows_prefill`` + ``serving_rows_decode`` over ``serving_rows_run``,
the program's counter of the rows it ran (per dispatched step, the rung of
``models/gpt.py step_row_ladder`` that holds the rows packed: the rung the
program itself picks from ``q_lens``; labelled by rung). The note gives the
share of the window's steps at each rung. A program without the counter (one
that runs its whole token budget every step) reads nothing."""
LAYER, UNIT, BETTER, SOURCE = "step program", "%", "higher", "program_counter"

_KEY = "serving_rows_run{rung="


def read(run):
    counters = run.get("counters") or {}
    run_rows = {int(k[len(_KEY):-1]): v for k, v in counters.items()
                if k.startswith(_KEY) and v}
    total = sum(run_rows.values())
    if not total:
        return None
    held = (counters.get("serving_rows_prefill", 0.0)
            + counters.get("serving_rows_decode", 0.0))
    steps = {rung: rows / rung for rung, rows in run_rows.items()}
    return 100.0 * held / total, {
        "rows_held": int(held), "rows_run": int(total),
        "step_share_by_rung": {
            str(rung): round(100.0 * n / sum(steps.values()), 2)
            for rung, n in sorted(steps.items())}}
