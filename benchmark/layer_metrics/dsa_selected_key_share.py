"""Share of the keys the window's scheduled rows saw that their attention
read, from the program's counters ``serving_dsa_keys_selected`` over
``serving_dsa_keys_context`` (both per scheduled row and attention layer):
what the selection leaves of a dense read."""
LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "program_counter"


def read(run):
    counters = run.get("counters") or {}
    seen = counters.get("serving_dsa_keys_context")
    if not seen:
        return None
    read_ = counters.get("serving_dsa_keys_selected", 0.0)
    return 100.0 * read_ / seen, {
        "keys_selected": int(read_), "keys_context": int(seen),
        "keys_scored": int(counters.get("serving_dsa_keys_scored", 0))}
