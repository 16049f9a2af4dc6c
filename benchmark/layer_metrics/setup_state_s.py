"""Set-up seconds spent making the state the program holds: the union of the
program's ``weights.make`` (the models' parameter generation, ended when the
device holds the weights), ``weights.place`` (the serving stacks, casts and
shardings, the train step's ``device_put``) and ``kv.pools`` (the KV cache's
pools and tables) phases that ended before the window. The note gives the
seconds of each phase, and ``to_first_record_s``, the seconds from the
process's start to the first thing the record holds (imports, reaching the
chip)."""
from . import _setup

LAYER, UNIT, BETTER, SOURCE = "set-up", "s", "lower", "program_counter"

PHASES = ("weights.make", "weights.place", "kv.pools")


def read(run):
    mine = _setup.before_window(run, PHASES)
    if mine is None:
        return None
    note = {"by_phase_s": _setup.largest(_setup.by(mine, lambda e: e.name))}
    everything = _setup.record(run)
    if everything and run["clock"].get("set_up") is not None:
        t_start = run["clock"]["t_open"] - run["clock"]["set_up"]
        note["to_first_record_s"] = round(
            min(e.start for e in everything) - t_start, 3)
    return _setup.union_s(mine), note
