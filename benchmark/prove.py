"""Prove several cells in one call to the chip tool.

    python benchmark/prove.py --cells a,b --runs 6 [--seconds S] [--traced N]
                              [--out chiprun_out/prove]

For each cell: ``--traced`` traced runs (on seeds of their own), then two sets
of ``--runs`` runs with the same seeds in both sets, each run a child process
of its own (``run.py``), one after another. This parent never imports JAX, so the chip
is the child's alone; all children share the checkout's compile cache. Every
run's output goes to ``<out>/<cell>/<set><i>.out`` and its last line into
``<out>/<cell>/summary.json`` with, per metric, what the driver's check
reads: each set's median, the wider of the two spreads (interquartile
distance over the median), the mean of the spreads with each set's farthest
run left out, and how far the second set's median lies from the first's.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.stats import spread  # noqa: E402  (plain arithmetic, no JAX)

#: large and small, as the driver's are; more than 32 signed bits hold
SEEDS = (2147483659, 3000000019, 77, 1234567891, 42, 2999999929, 4000000007,
         5, 2222222222, 1000003, 31337, 3999999979)
RUN_LIMIT_S = 1300  # a first run may compile


def without_farthest(values):
    med = statistics.median(values)
    rest = list(values)
    rest.remove(max(values, key=lambda v: abs(v - med)))
    return rest


def summarise(by_metric):
    """``{metric: {"A": [...], "B": [...]}}`` -> what the check reads."""
    out = {}
    for name, sets in by_metric.items():
        a, b = sets["A"], sets["B"]
        row = {"A": a, "B": b, "median_A": statistics.median(a),
               "median_B": statistics.median(b) if b else None}
        if len(a) >= 2 and len(b) >= 2:
            row["spread"] = max(spread(a), spread(b))
            row["median_shift"] = row["median_B"] / row["median_A"] - 1
        if len(a) >= 3 and len(b) >= 3:
            row["spread_trimmed_mean"] = (
                spread(without_farthest(a)) + spread(without_farthest(b))) / 2
        out[name] = row
    return out


def one_run(cell, seed, seconds, trace, out_path):
    """Run ``run.py`` once; returns (exit code, last line or None)."""
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    with open(out_path, "w") as out, open(out_path[:-4] + ".err", "w") as err:
        try:
            rc = subprocess.run(cmd, cwd=ROOT, stdout=out, stderr=err,
                                timeout=RUN_LIMIT_S).returncode
        except subprocess.TimeoutExpired:
            rc = 124
    took = time.perf_counter() - t0
    last = None
    with open(out_path) as f:
        lines = f.read().strip().splitlines()
    if rc == 0 and lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            rc = 1
    return rc, last, took


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--traced", type=int, default=1)
    parser.add_argument("--sets", default="A,B")
    parser.add_argument("--out", default=os.path.join("chiprun_out", "prove"))
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]

    failed = 0
    for cell in args.cells.split(","):
        out_dir = os.path.join(ROOT, args.out, cell)
        os.makedirs(out_dir, exist_ok=True)
        # traced runs take the seeds the sets leave unused
        plan = [("T", i, 1, SEEDS[(args.runs + i) % len(SEEDS)])
                for i in range(args.traced)]
        plan += [(s, i, 0, SEEDS[i % len(SEEDS)])
                 for s in args.sets.split(",") for i in range(args.runs)]
        by_metric, lines = {}, {}
        for set_name, i, trace, seed in plan:
            tag = f"{set_name}{i}"
            rc, last, took = one_run(cell, seed, seconds, trace,
                                     os.path.join(out_dir, tag + ".out"))
            lines[tag] = last
            ok = rc == 0 and last is not None and last["correct"] \
                and last["failed"] == 0
            failed += not ok
            shown = {k: v["value"] for k, v in
                     (last or {}).get("metrics", {}).items()}
            print(f"{cell} {tag} seed={seed} rc={rc} "
                  f"ok={ok} took={took:.1f}s {json.dumps(shown)}",
                  flush=True)
            if ok and not trace:
                for name, v in last["metrics"].items():
                    by_metric.setdefault(name, {"A": [], "B": []})[
                        set_name].append(v["value"])
        summary = summarise(by_metric)
        with open(os.path.join(out_dir, "summary.json"), "w") as f:
            json.dump({"cell": cell, "seconds": seconds, "lines": lines,
                       "summary": summary}, f, indent=1)
        for name, row in summary.items():
            print(f"{cell} {name}: " + json.dumps(
                {k: v for k, v in row.items() if k not in ("A", "B")}),
                flush=True)
        for tag, line in lines.items():
            if tag.startswith("T") and line:
                print(f"{cell} traced {tag}: {json.dumps(line)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
