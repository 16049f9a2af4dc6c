"""Run serving cells as ``run.py`` does and keep what each run clocked.

    python benchmark/tools/dump_runs.py --cells a,b --runs 6 [--sets A,B]
            [--seconds S] [--out chiprun_out/dumps]

For each cell, sets of ``--runs`` untraced runs with ``prove.py``'s seeds, the
same seeds in every set, each run a child process of its own (this file
again, with ``--child``). The parent never imports JAX. A child calls
``run.main`` unchanged and listens at three places: every ``Loop.step()``
call's return time, the run record the readers get (``deliveries``,
``requests``, ``finished``, ``clock``, ``counters``), and the window's two
edges, where it times two fixed probes on the device (a memory-bound
elementwise pass over 256 MiB and a chain of bf16 matrix products), before
the window opens and after it has closed; it also clocks every collection of
Python's garbage collector and the time inside ``ServingPredictor.step()``.
``--bare SECONDS`` runs no cell at all: one process times a 1 ms jitted call
and its wait in a loop and counts the waits that come back late (the chip
tool's machine shows no ``cpu.stat``, no pressure file and no context-switch
count, so the host says nothing more about them). ``<out>/<cell>/<set><i>.npz`` holds
the arrays and ``.json`` the rest with the run's result line;
``spread_report.py`` reads them, here or on the chip. ``run.py`` gets no flag
for any of this: what the driver's command measures stays what it was.
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
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

PROBE_MIB = 256
PROBE_PASSES = 200     # elementwise passes a timed call
PROBE_PRODUCTS = 200   # 4096 x 4096 bf16 products a timed call
PROBE_REPEATS = 7


def _sweep(x):
    import jax

    return jax.lax.fori_loop(0, PROBE_PASSES,
                             lambda i, y: y * 1.0001 + 1e-6, x)


def _chain(m):
    import jax
    import jax.numpy as jnp

    return jax.lax.fori_loop(
        0, PROBE_PRODUCTS, lambda i, y: (y @ m).astype(jnp.bfloat16), m)


_JITTED = {}


def time_probes():
    """``{name: [ms per timed call]}``. Operands are made here and freed on
    return, so the window runs beside nothing of the probes'. One untimed
    call first, which also waits for whatever the device has in flight."""
    import jax
    import jax.numpy as jnp

    if not _JITTED:
        _JITTED.update(memory_ms=jax.jit(_sweep), matmul_ms=jax.jit(_chain))
    operands = {
        "memory_ms": jnp.ones((PROBE_MIB * 2 ** 20 // 4,), jnp.float32),
        "matmul_ms": (jax.random.normal(jax.random.key(0), (4096, 4096),
                                        jnp.float32) / 64.0
                      ).astype(jnp.bfloat16),
    }
    out = {}
    for name, x in operands.items():
        fn = _JITTED[name]
        fn(x).block_until_ready()
        times = []
        for _ in range(PROBE_REPEATS):
            t0 = time.perf_counter()
            fn(x).block_until_ready()
            times.append((time.perf_counter() - t0) * 1e3)
        out[name] = times
    return out


def bare(args):
    """A loop of one short jitted call and its wait, nothing else in the
    process: are late waits the platform's own?"""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((16 * 2 ** 20,), jnp.float32)  # 64 MiB: 1 ms for 12 passes
    fn = jax.jit(lambda v: jax.lax.fori_loop(
        0, 12, lambda i, y: y * 1.0001 + 1e-6, v))
    fn(x).block_until_ready()
    times, t_end = [], time.perf_counter() + args.bare
    while time.perf_counter() < t_end:
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    late = [round(t * 1e3, 1) for t in times if t > med + 0.02]
    record = {"calls": len(times), "median_ms": med * 1e3,
              "seconds": args.bare, "late_ms": late,
              "device": str(jax.devices()[0])}
    os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
    with open(os.path.join(ROOT, args.out, "bare.json"), "w") as f:
        json.dump(record, f)
    print(json.dumps(record), flush=True)
    return 0


def child(args):
    import numpy as np

    import benchmark  # noqa: F401  (the package the drivers import from)
    from benchmark import run as R
    from benchmark.drivers import serve

    import gc

    seen = {"step_t": [], "in_step": [], "probes": {}, "loop": None,
            "gc": []}

    def on_gc(phase, info):
        now = time.perf_counter()
        if phase == "start":
            on_gc.t0 = now
        else:
            seen["gc"].append((on_gc.t0, now - on_gc.t0, info["generation"]))

    gc.callbacks.append(on_gc)

    loop_init, loop_step = serve.Loop.__init__, serve.Loop.step

    def init(self, sp, *a, **k):
        loop_init(self, sp, *a, **k)
        seen["loop"] = self
        sp_step = sp.step

        def timed_step():
            t0 = time.perf_counter()
            out = sp_step()
            seen["in_step"].append(time.perf_counter() - t0)
            return out

        sp.step = timed_step

    def step(self):
        now = loop_step(self)
        seen["step_t"].append(now)
        return now

    serve.Loop.__init__, serve.Loop.step = init, step

    opens, closes = R.Context.window_opens, R.Context.window_closes

    def window_opens(self):
        seen["probes"]["before"] = time_probes()
        seen["cpu_open"] = time.process_time()
        return opens(self)

    def window_closes(self, at=None):
        now = closes(self, at)
        seen["cpu_close"] = time.process_time()
        seen["probes"]["after"] = time_probes()
        return now

    R.Context.window_opens, R.Context.window_closes = (window_opens,
                                                       window_closes)
    read_metrics = R.read_metrics

    def dump(kind, metrics, run):
        s, loop = run["serve"], seen["loop"]
        keys = sorted(s["requests"])
        d = s["deliveries"]
        np.savez_compressed(
            args.dump + ".npz",
            step_t=np.asarray(seen["step_t"], np.float64),
            in_step=np.asarray(seen["in_step"], np.float64),
            gc=np.asarray(seen["gc"], np.float64).reshape(-1, 3),
            deliv_t=np.asarray([x[0] for x in d], np.float64),
            deliv_key=np.asarray([x[1] for x in d], np.int32).reshape(-1, 2),
            deliv_n=np.asarray([x[2] for x in d], np.int32),
            deliv_call=np.asarray(loop.delivered_at, np.int32),
            req_key=np.asarray(keys, np.int32).reshape(-1, 2),
            req_submit=np.asarray([s["requests"][k]["submit"] for k in keys]),
            req_prompt=np.asarray([s["requests"][k]["prompt"] for k in keys]),
            req_answer=np.asarray([s["requests"][k]["answer"] for k in keys]),
            fin_t=np.asarray([t for t, _ in loop.finished], np.float64),
            fin_key=np.asarray([k for _, k in loop.finished],
                               np.int32).reshape(-1, 2))
        seen["record"] = {
            "clock": run["clock"], "info": run["info"],
            "probes": seen["probes"],
            "cpu_in_window_s": seen["cpu_close"] - seen["cpu_open"],
            "counters": {k: v for k, v in run["counters"].items()
                         if v and "{" not in k},
        }
        return read_metrics(kind, metrics, run)

    R.read_metrics = dump
    rc = R.main(["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", "0"])
    with open(args.dump + ".json", "w") as f:
        json.dump(seen.get("record", {}), f)
    return rc


def parent(args):
    from benchmark.prove import SEEDS

    failed = 0
    for cell in args.cells.split(","):
        out_dir = os.path.join(ROOT, args.out, cell)
        os.makedirs(out_dir, exist_ok=True)
        for set_name in args.sets.split(","):
            for i in range(args.runs):
                tag, seed = f"{set_name}{i}", SEEDS[i % len(SEEDS)]
                base = os.path.join(out_dir, tag)
                cmd = [sys.executable, os.path.abspath(__file__), "--child",
                       "--workload", cell, "--seed", str(seed), "--seconds",
                       str(args.seconds), "--dump", base]
                t0 = time.perf_counter()
                with open(base + ".out", "w") as o, \
                        open(base + ".err", "w") as e:
                    rc = subprocess.run(cmd, cwd=ROOT, stdout=o,
                                        stderr=e).returncode
                took = time.perf_counter() - t0
                last, shown = None, {}
                with open(base + ".out") as f:
                    lines = f.read().strip().splitlines()
                if rc == 0 and lines:
                    last = json.loads(lines[-1])
                    shown = {k: v["value"]
                             for k, v in last["metrics"].items()}
                    with open(base + ".json") as f:
                        rec = json.load(f)
                    rec["result"] = last
                    with open(base + ".json", "w") as f:
                        json.dump(rec, f)
                    shown["memory_ms"] = [
                        round(statistics.median(rec["probes"][w]["memory_ms"]),
                              3) for w in ("before", "after")]
                ok = bool(last and last["correct"] and not last["failed"])
                failed += not ok
                print(f"{cell} {tag} seed={seed} rc={rc} ok={ok} "
                      f"took={took:.1f}s {json.dumps(shown)}", flush=True)
    return 1 if failed else 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cells")
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--sets", default="A,B")
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--out", default=os.path.join("chiprun_out", "dumps"))
    p.add_argument("--bare", type=float, default=None, metavar="SECONDS",
                   help="no cell: time a short jitted call and its wait in "
                        "a loop for this long, and count the late ones")
    p.add_argument("--child", action="store_true")
    p.add_argument("--workload")
    p.add_argument("--seed", type=int)
    p.add_argument("--dump")
    args = p.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = json.load(f)["run_seconds"]
    if args.bare:
        return bare(args)
    return child(args) if args.child else parent(args)


if __name__ == "__main__":
    sys.exit(main())
