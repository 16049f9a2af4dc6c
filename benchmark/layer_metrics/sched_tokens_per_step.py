"""Real tokens packed per step: the rows each scheduled lane fed, from the
cache manager's own counts, averaged over the window's steps."""
LAYER, UNIT, BETTER, SOURCE = "scheduler", "tokens", "higher", "program_counter"


def read(run):
    steps = run.get("serve", {}).get("steps")
    if not steps:
        return None
    return sum(q for _, _, lanes in steps for q, _ in lanes) / len(steps)
