"""What spreads a serving cell's metrics: arithmetic on ``dump_runs.py``'s dumps.

    python benchmark/tools/spread_report.py chiprun_out/dumps/<cell> [--seconds S ...]

No JAX, no chip. Every metric is recomputed with ``stats.py`` from the dumped
``deliveries`` and ``requests`` for a window of ``--seconds`` (any length up to
the dumped run's; the window closes with the first ``step()`` that returns
after it, as in the drivers, and the schedule does not depend on the clock, so
a shorter window cut from a longer run is the run ``run.py`` would have made).
Printed per run: the metrics, steps a second, tokens a step, the rate in
thirds of the window, the probes; per metric the sets' spreads the way the
driver reads them; per cell the edge term, each run's time per ``step()``
call against the median of the runs at that call (the stalls), the gap
histogram in step periods, the mass near each percentile, and where two runs'
schedules part.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402
from benchmark.prove import without_farthest  # noqa: E402

PERCENTILES = (50, 75, 90, 95, 97, 99)
NEAR_MS = 0.5


def load(path):
    z = np.load(path)
    with open(path[:-4] + ".json") as f:
        rec = json.load(f)
    deliveries = [(float(t), (int(k[0]), int(k[1])), int(n))
                  for t, k, n in zip(z["deliv_t"], z["deliv_key"],
                                     z["deliv_n"])]
    requests = {(int(k[0]), int(k[1])): {"submit": float(s), "prompt": int(p),
                                         "answer": int(a)}
                for k, s, p, a in zip(z["req_key"], z["req_submit"],
                                      z["req_prompt"], z["req_answer"])}
    return {"tag": os.path.basename(path)[:-4], "rec": rec,
            "step_t": z["step_t"], "deliveries": deliveries,
            "in_step": z["in_step"] if "in_step" in z.files else None,
            "gc": z["gc"] if "gc" in z.files else np.zeros((0, 3)),
            "deliv_call": z["deliv_call"], "requests": requests,
            "fin_t": z["fin_t"]}


def window(run, seconds):
    """``(t_open, t_close, index of the first call inside, of the last)``."""
    t_open = run["rec"]["clock"]["t_open"]
    st = run["step_t"]
    first = int(np.searchsorted(st, t_open, side="right"))
    last = int(np.searchsorted(st, t_open + seconds, side="left"))
    last = min(last, len(st) - 1)
    return t_open, float(st[last]), first, last


def metrics(run, seconds):
    t_open, t_close, first, last = window(run, seconds)
    d, r = run["deliveries"], run["requests"]
    w = t_close - t_open
    gaps = stats.token_gaps(d, t_open, t_close)
    stalls = stats.delivery_stalls(d, t_open, t_close)
    steps = last - first + 1
    tokens = stats.served_tokens(d, r, t_open, t_close)
    out = {"served_tok_s": tokens / w, "steps_per_s": steps / w,
           "tokens_per_step": tokens / steps, "window_s": w,
           "finished": int(((run["fin_t"] > t_open)
                            & (run["fin_t"] <= t_close)).sum()),
           "first_tokens": sum(t_open < t <= t_close for t in
                               stats.first_token_times(d).values())}
    for q in PERCENTILES:
        out[f"gap_p{q}_ms"] = stats.percentile(gaps, q) * 1e3
        out[f"stall_p{q}_ms"] = stats.percentile(stalls, q) * 1e3
    out["gap_mean_ms"] = statistics.fmean(gaps) * 1e3
    thirds = []
    for i in range(3):
        a, b = t_open + w * i / 3, t_open + w * (i + 1) / 3
        thirds.append(stats.served_tokens(d, r, a, b) / (b - a))
    out["thirds_tok_s"] = thirds
    st = run["step_t"]
    cut = [first + (steps * i) // 3 for i in range(4)]
    at = [t_open] + [float(st[c - 1]) for c in cut[1:]]
    out["thirds_steps_per_s"] = [(cut[i + 1] - cut[i]) / (at[i + 1] - at[i])
                                 for i in range(3)]
    return out, gaps, stalls


def edge_term(run, seconds, shifts=8):
    """Range over the rate of windows of one step count whose two edges
    are shifted together by 0..``shifts`` steps, over their median."""
    _, _, first, last = window(run, seconds)
    st, d, r = run["step_t"], run["deliveries"], run["requests"]
    rates = []
    for k in range(shifts + 1):
        a, b = st[first - 1 + k], st[last - shifts + k]
        rates.append(stats.served_tokens(d, r, a, b) / (b - a))
    return (max(rates) - min(rates)) / statistics.median(rates)


STALL_S = 0.015


def late_calls(runs, seconds):
    """The schedule is one, so call i does the same work in every run: each
    run's time per call against the median of the runs at that call. Prints
    a run's excess and every call more than ``STALL_S`` late, with the time
    inside ``ServingPredictor.step()`` and the collections that overlap it."""
    per = []
    for run in runs:
        t_open, _, first, last = window(run, seconds)
        st = run["step_t"]
        dt = np.diff(st[first - 1:last + 1])
        dt[0] = st[first] - t_open  # the probes ran before the window
        per.append((dt, first))
    n = min(len(dt) for dt, _ in per)
    floor = np.median(np.stack([dt[:n] for dt, _ in per]), axis=0)
    print(f" -- time per step() call against the median of the runs at that "
          f"call ({n} calls, medians sum to {floor.sum():.3f} s)")
    for run, (dt, first) in zip(runs, per):
        excess = dt[:n] - floor
        late = np.where(excess > STALL_S)[0]
        g = run["gc"]
        shown = []
        for i in late:
            end = run["step_t"][first + i]
            over = g[(g[:, 0] + g[:, 1] > end - dt[i]) & (g[:, 0] < end)]
            inside = ("" if run["in_step"] is None else
                      f" ({run['in_step'][first + i] * 1e3:.0f} in step())")
            shown.append(f"call {i}: {dt[i] * 1e3:.0f} ms{inside}" + "".join(
                f" gc{int(gen)} {d * 1e3:.0f} ms" for _, d, gen in over))
        print(f"  {run['tag']} excess {excess.sum():.3f} s, {len(late)} "
              f"late: " + "; ".join(shown))


def set_rows(runs, name, values):
    """Print what the driver reads of one metric's runs."""
    sets = {}
    for run, v in zip(runs, values):
        sets.setdefault(run["tag"][0], []).append(v)
    row = {}
    for s, vals in sorted(sets.items()):
        row[f"median_{s}"] = statistics.median(vals)
        if len(vals) >= 4:
            row[f"spread_{s}"] = stats.spread(vals)
            row[f"trimmed_{s}"] = stats.spread(without_farthest(vals))
    names = sorted(sets)
    if len(names) == 2:
        row["median_shift"] = (row[f"median_{names[1]}"]
                               / row[f"median_{names[0]}"] - 1)
    print(f"  {name}: values {[round(v, 4) for v in values]}")
    print("    " + json.dumps({k: round(v, 6) for k, v in row.items()}))


def near_share(values, at, width):
    v = np.asarray(values)
    return float((np.abs(v - at) <= width).mean())


def report(directory, seconds):
    paths = sorted(glob.glob(os.path.join(directory, "*.npz")))
    runs = [load(p) for p in paths]
    if not runs:
        raise SystemExit(f"no dumps under {directory}")
    print(f"== {directory}: {len(runs)} runs, window {seconds} s")
    per, gaps_of, stalls_of = [], [], []
    for run in runs:
        m, gaps, stalls = metrics(run, seconds)
        per.append(m)
        gaps_of.append(gaps)
        stalls_of.append(stalls)
        pr = run["rec"].get("probes", {})
        probe = {w: {k: round(statistics.median(v), 3)
                     for k, v in pr[w].items()} for w in pr}
        print(f" {run['tag']} served {m['served_tok_s']:.2f} steps/s "
              f"{m['steps_per_s']:.3f} tok/step {m['tokens_per_step']:.4f} "
              f"gap95 {m['gap_p95_ms']:.3f} stall95 {m['stall_p95_ms']:.3f} "
              f"finished {m['finished']} first {m['first_tokens']} thirds "
              f"{[round(x, 1) for x in m['thirds_tok_s']]} steps/s thirds "
              f"{[round(x, 3) for x in m['thirds_steps_per_s']]} probes "
              f"{json.dumps(probe)} cpu "
              f"{run['rec'].get('cpu_in_window_s', 0):.1f}")
    print(" -- per metric, the sets as the driver reads them")
    for name in ["served_tok_s", "steps_per_s", "tokens_per_step",
                 "gap_mean_ms"] \
            + [f"gap_p{q}_ms" for q in PERCENTILES] \
            + [f"stall_p{q}_ms" for q in PERCENTILES]:
        set_rows(runs, name, [m[name] for m in per])
    for w in ("before", "after"):
        for k in ("memory_ms", "matmul_ms"):
            vals = [statistics.median(r["rec"]["probes"][w][k])
                    for r in runs if r["rec"].get("probes", {}).get(w)]
            if len(vals) == len(runs):
                set_rows(runs, f"probe.{k}.{w}", vals)
    # within runs against between runs
    within = [statistics.pstdev(m["thirds_steps_per_s"])
              / statistics.fmean(m["thirds_steps_per_s"]) for m in per]
    means = [m["steps_per_s"] for m in per]
    print(f" -- steps/s: std of a run's thirds over its mean, median over "
          f"runs {statistics.median(within):.5f} (max {max(within):.5f}); "
          f"std of run means over their mean "
          f"{statistics.pstdev(means) / statistics.fmean(means):.5f}")
    within = [statistics.pstdev(m["thirds_tok_s"])
              / statistics.fmean(m["thirds_tok_s"]) for m in per]
    means = [m["served_tok_s"] for m in per]
    print(f" -- served_tok_s: the same, {statistics.median(within):.5f} "
          f"(max {max(within):.5f}) within; "
          f"{statistics.pstdev(means) / statistics.fmean(means):.5f} between")
    edges = [edge_term(r, seconds) for r in runs]
    print(f" -- edge term of served_tok_s (range over shifts of 0-8 steps): "
          f"median {statistics.median(edges):.5f} max {max(edges):.5f}")
    late_calls(runs, seconds)
    # the gaps in units of the run's mean step period
    print(" -- gaps over the run's mean step period, share per bin of 1/12")
    for run, m, gaps in list(zip(runs, per, gaps_of))[:2]:
        unit = 1.0 / m["steps_per_s"]
        hist, _ = np.histogram(np.asarray(gaps) / unit,
                               bins=np.arange(0, 4.0001, 1 / 12))
        share = hist / len(gaps)
        shown = {f"{i / 12:.2f}": round(float(s), 4)
                 for i, s in enumerate(share) if s >= 0.002}
        print(f"  {run['tag']} ({len(gaps)} gaps): {shown}")
    print(f" -- share of samples within {NEAR_MS} ms of each percentile "
          f"(median over runs), and samples beyond it")
    for kind, series in (("gap", gaps_of), ("stall", stalls_of)):
        for q in PERCENTILES:
            shares = [near_share(np.asarray(g) * 1e3, m[f"{kind}_p{q}_ms"],
                                 NEAR_MS) for g, m in zip(series, per)]
            beyond = [int(len(g) * (100 - q) / 100) for g in series]
            print(f"  {kind}_p{q}: near {statistics.median(shares):.4f} "
                  f"beyond {statistics.median(beyond):.0f} of "
                  f"{statistics.median(len(g) for g in series):.0f}")
    # one schedule or two
    print(" -- schedules: runs of one seed against each other")
    by_seed = {}
    for run in runs:
        by_seed.setdefault(run["tag"][1:], []).append(run)
    for seed, pair in sorted(by_seed.items()):
        if len(pair) < 2:
            continue
        a, b = pair[0], pair[1]
        ka = list(zip(a["deliv_call"].tolist(),
                      [(k, n) for _, k, n in a["deliveries"]]))
        kb = list(zip(b["deliv_call"].tolist(),
                      [(k, n) for _, k, n in b["deliveries"]]))
        same = next((i for i, (x, y) in enumerate(zip(ka, kb)) if x != y),
                    min(len(ka), len(kb)))
        print(f"  seed {seed}: {a['tag']} and {b['tag']} agree on the first "
              f"{same} deliveries of {len(ka)} / {len(kb)}"
              + ("" if same == min(len(ka), len(kb))
                 else f"; part at call {ka[same][0]} / {kb[same][0]}"))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("directory")
    p.add_argument("--seconds", type=float, nargs="+", default=[30.0])
    args = p.parse_args(argv)
    for s in args.seconds:
        report(args.directory, s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
