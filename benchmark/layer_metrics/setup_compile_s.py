"""Set-up seconds in which jax compiled a program or loaded it from the
persistent compile cache: the union of the ``jax.compile``
(``backend_compile``, which spans the cache's lookup) intervals that ended
before the window, as the program's listener recorded them. The note gives
the cache's hits and misses in all and for the five largest functions, and
``jax_lowerings_in_window``: by function, the programs lowered after the
window opened, as the program's ``jax_lowerings`` counts them (none where
nothing compiles in the window)."""
from . import _setup

LAYER, UNIT, BETTER, SOURCE = "set-up", "s", "lower", "program_counter"


def read(run):
    mine = _setup.before_window(run, ("jax.compile",))
    if mine is None:
        return None
    seconds = _setup.by(mine, lambda e: e.fun)
    cache = {}
    for e in mine:
        if e.cache:
            cache.setdefault(e.fun, {"hit": 0, "miss": 0})[e.cache] += 1
    t_open = run["clock"]["t_open"]
    late = {}
    for e in _setup.record(run):
        if e.name == "jax.lower" and e.end > t_open:
            late[e.fun] = late.get(e.fun, 0) + 1
    return _setup.union_s(mine), {
        "programs": len(mine),
        "cache_hits": sum(c["hit"] for c in cache.values()),
        "cache_misses": sum(c["miss"] for c in cache.values()),
        "largest_s": {fun: [s, cache.get(fun)] for fun, s in
                      _setup.largest(seconds).items()},
        "jax_lowerings_in_window": late}
