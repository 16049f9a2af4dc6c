"""Pages the window group's lanes hold as the window closes (the program's
gauge ``kv_window_pages_held``) over the pages the same lanes would hold had
nothing been released (a page a ``page_size`` positions of each lane's
context; the group's layers cancel): what the allocator leaves of a uniform
pool."""
LAYER, UNIT, BETTER, SOURCE = "step program", "%", "lower", "program_counter"


def read(run):
    cache = run.get("window_cache") or {}
    held, whole = cache.get("pages_held"), cache.get("pages_unreleased")
    if held is None or not whole:
        return None
    return 100.0 * held / whole, {
        "pages_held": int(held), "pages_unreleased": int(whole),
        "lanes_over_window": cache.get("lanes_over_window")}
