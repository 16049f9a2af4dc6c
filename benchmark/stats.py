"""The arithmetic between a run's clock records and its metrics.

A serving run keeps, on the benchmark's own clock:

- ``deliveries``: ``[(time, request key, tokens)]``, one entry per request
  per ``step()`` call that handed tokens back, in time order;
- ``requests``: ``{request key: {"submit", "prompt", "answer"}}``.

The functions below read nothing else, so they are tested on hand-made lists.
"""
from __future__ import annotations

from collections import defaultdict


def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between the
    two nearest ranks; ``None`` for no values."""
    if not values:
        return None
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def by_request(deliveries):
    """``{request key: [(time, tokens)]}`` in time order."""
    out = defaultdict(list)
    for t, key, n in deliveries:
        out[key].append((t, n))
    return out


def first_token_times(deliveries):
    """``{request key: time of its first delivery}``."""
    first = {}
    for t, key, _ in deliveries:
        first.setdefault(key, t)
    return first


def served_tokens(deliveries, requests, t_open, t_close):
    """Prompt tokens of the requests whose first token came inside
    ``(t_open, t_close]``, credited at that token, plus every output token
    delivered inside it."""
    total = sum(n for t, _, n in deliveries if t_open < t <= t_close)
    for key, t in first_token_times(deliveries).items():
        if t_open < t <= t_close:
            total += requests[key]["prompt"]
    return total


def token_gaps(deliveries, t_open, t_close):
    """One gap per output token delivered inside the window, the first token
    of a request excepted (its wait is the time to first token). A call that
    hands back n tokens of one request gives each of them the time since
    that request's previous delivery divided by n."""
    gaps = []
    for events in by_request(deliveries).values():
        for (t_prev, _), (t, n) in zip(events, events[1:]):
            if t_open < t <= t_close:
                gaps.extend([(t - t_prev) / n] * n)
    return gaps


def delivery_stalls(deliveries, t_open, t_close):
    """The time between consecutive deliveries to one request, undivided,
    for the deliveries inside the window."""
    return [t - t_prev
            for events in by_request(deliveries).values()
            for (t_prev, _), (t, _) in zip(events, events[1:])
            if t_open < t <= t_close]


def times_to_first_token(deliveries, requests, t_open, t_close):
    """Submit-to-first-token of the requests submitted inside the window
    whose first token also came inside it."""
    first = first_token_times(deliveries)
    return [first[key] - r["submit"] for key, r in requests.items()
            if key in first and t_open < r["submit"]
            and first[key] <= t_close]


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, the way the driver reads a set of runs."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
