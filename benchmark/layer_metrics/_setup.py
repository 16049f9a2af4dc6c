"""What the three set-up readers share: the program's own set-up record
(``paddle_tpu.observability.setup_record``, PR 38: ``observability/startup.py``), of which they keep the
entries that ended before the window opened. The drivers difference the
predictor's telemetry over the window, where set-up totals vanish, so these
read the record itself, in the process the run took place in. A program
without the record reads nothing."""
from __future__ import annotations

import re


def record(run):
    """Every entry of the record, or ``None`` where the program keeps none."""
    try:
        from paddle_tpu.observability import setup_record
    except ImportError:
        return None
    return setup_record.entries()


def before_window(run, names):
    """The entries whose name starts with one of ``names`` that ended before
    the window opened; ``None`` where there is no record or none of them."""
    entries = record(run)
    if entries is None:
        return None
    t_open = run["clock"]["t_open"]
    kept = [e for e in entries if e.name.startswith(names)
            and e.end <= t_open]
    return kept or None


def union_s(entries):
    """Seconds covered by the entries' intervals, overlaps counted once."""
    total, reach = 0.0, float("-inf")
    for e in sorted(entries, key=lambda e: e.start):
        if e.end > reach:
            total += e.end - max(e.start, reach)
            reach = e.end
    return total


def by(entries, key):
    """``{key(entry): union seconds of its entries}``, largest first."""
    groups = {}
    for e in entries:
        groups.setdefault(key(e), []).append(e)
    return dict(sorted(((k, union_s(v)) for k, v in groups.items()),
                       key=lambda kv: -kv[1]))


def largest(seconds, n=5):
    return {k: round(v, 3) for k, v in list(seconds.items())[:n]}


_RUNG = re.compile(r"^step\.rung\.(\d+)$")


def rungs(entries):
    """``{rows: seconds}`` of the step's ``step.rung.<rows>`` phases: each
    rung of the row ladder while jax traced it."""
    return {int(m.group(1)): round(e.end - e.start, 3) for e in entries
            if (m := _RUNG.match(e.name))}
