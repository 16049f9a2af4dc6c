"""Passes ``ragged_paged_attention`` makes over a scheduled lane's keys in an
attention layer, from the program's counters ``serving_attn_kv_passes`` (per
scheduled lane and attention layer, the head groups of the kernel's plan at
the step's rung: the grid walks a lane's key blocks once a group of key-value
heads) over ``serving_attn_lane_layers`` (per scheduled lane and attention
layer, 1). 1.0 where one grid step takes every key-value head of a lane. A
program without the counters (an earlier commit) gives nothing to read."""
LAYER, UNIT, BETTER, SOURCE = ("kernels, serving", "passes", "lower",
                               "program_counter")


def read(run):
    counters = run.get("counters") or {}
    lanes = counters.get("serving_attn_lane_layers")
    if not lanes:
        return None
    passes = counters.get("serving_attn_kv_passes", 0)
    return passes / lanes, {"kv_passes": int(passes),
                            "lane_layers": int(lanes)}
