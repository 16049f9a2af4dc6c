"""Share of the rows of the query blocks the serving step builds for
``ragged_paged_attention`` that hold a query row the step was fed, from the
program's counters ``serving_attn_query_rows`` (per scheduled row and attention
layer, its query heads) over ``serving_attn_query_block_rows`` (per attention
layer, lanes x key-value heads x a lane's rows a head at the step's rung,
``lane_block_rows`` padded to whole sublane tiles). What is left of 100 is
rows the kernel's operand carries for nothing: a decode lane's one row in a
block cut to the rung's rows, an idle lane's whole block. A program without
the counters (an earlier commit) gives nothing to read."""
LAYER, UNIT, BETTER, SOURCE = ("kernels, serving", "%", "higher",
                               "program_counter")


def read(run):
    counters = run.get("counters") or {}
    blocks = counters.get("serving_attn_query_block_rows")
    if not blocks:
        return None
    rows = counters.get("serving_attn_query_rows", 0)
    steps = counters.get("serving_steps")
    return 100.0 * rows / blocks, {
        "query_rows": int(rows), "block_rows": int(blocks),
        "block_rows_per_step": blocks / steps if steps else None}
