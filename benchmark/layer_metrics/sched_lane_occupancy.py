"""Mean share of the deployment's lanes that hold a request after a step."""
LAYER, UNIT, BETTER, SOURCE = "scheduler", "%", "higher", "program_counter"


def read(run):
    s = run.get("serve", {})
    if not s.get("steps"):
        return None
    return 100.0 * sum(n for _, n, _ in s["steps"]) / (
        len(s["steps"]) * s["lanes"])
