"""Model FLOP/s utilization: the operations the forward and backward passes
need per token (6 per parameter plus causal attention's; recomputed
operations not counted) times tokens per second, over chips times peak."""
LAYER, UNIT, BETTER, SOURCE = "train step", "%", "higher", "host_clock"


def read(run):
    if "train" not in run:
        return None
    t, c = run["train"], run["clock"]
    tok_s = t["steps"] * t["tokens_per_step"] / (c["window_s"] - c["paused_s"])
    return 100.0 * t["model_ops_per_token"] * tok_s / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])
