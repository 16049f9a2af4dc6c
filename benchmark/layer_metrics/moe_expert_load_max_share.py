"""How uneven the routing is: the rows the fullest expert received over the
window, as a percentage of the mean over all experts (100: even), from the
program's counter ``serving_moe_expert_rows{expert=}`` summed over the routed
layers. The fullest expert sets how long a layer's grouped GEMM runs."""
LAYER, UNIT, BETTER, SOURCE = "step program", "%", "lower", "program_counter"

_KEY = "serving_moe_expert_rows{expert="


def read(run):
    counters = run.get("counters") or {}
    experts = (run.get("serve") or {}).get("experts")
    rows = [v for k, v in counters.items() if k.startswith(_KEY)]
    if not experts or not rows or not sum(rows):
        return None
    return 100.0 * max(rows) * experts / sum(rows), {
        "rows_routed": int(sum(rows)), "experts_with_rows": len(rows)}
