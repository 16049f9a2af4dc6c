"""Share of the keys the window's scheduled rows would read with no window
that their attention read, from the program's counters
``serving_window_keys_read`` over ``serving_window_keys_context`` (both per
scheduled row and attention layer, full layers included): what the window
layers leave of a dense read."""
LAYER, UNIT, BETTER, SOURCE = "kernels, serving", "%", "lower", "program_counter"


def read(run):
    counters = run.get("counters") or {}
    seen = counters.get("serving_window_keys_context")
    if not seen:
        return None
    read_ = counters.get("serving_window_keys_read", 0.0)
    return 100.0 * read_ / seen, {"keys_read": int(read_),
                                  "keys_context": int(seen)}
