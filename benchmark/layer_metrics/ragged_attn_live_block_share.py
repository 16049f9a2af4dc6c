"""Share of the grid steps ``ragged_paged_attention`` launched over the window
that held a scheduled lane's keys, from the scheduler's own counters
``serving_attn_blocks_live`` and ``serving_attn_blocks_grid`` (incremented in
``_pack_dispatch`` with the block size and grid the kernel module gives for
the deployment's shapes). What is left of 100 are grid steps that hold no
keys: an idle lane's one step, or every page slot of the table past a lane's
context where the grid visits them all. A program without the counters
(another kernel, an earlier commit) gives nothing to read."""
LAYER, UNIT, BETTER, SOURCE = ("kernels, serving", "%", "higher",
                               "program_counter")


def read(run):
    counters = run.get("counters") or {}
    grid = counters.get("serving_attn_blocks_grid")
    if not grid:
        return None
    live = counters.get("serving_attn_blocks_live", 0)
    steps = counters.get("serving_steps")
    return 100.0 * live / grid, {
        "live": int(live), "grid": int(grid),
        "grid_steps_per_call": grid / steps if steps else None}
