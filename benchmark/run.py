"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, sets the system up (timed as set-up), measures for ``--seconds`` and prints one JSON object as the
last line of its output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, traced ``breakdown``, and last ``compared`` (each number held
against the reference, with its limit; also the last lines on standard
error). Every line it prints names the device.
It needs a TPU and fails without one.

This file knows no cell, configuration or metric by name. A cell's
configuration is ``configs/<config>.json``, its traffic ``traffic/<mix>.json``
(which names a driver under ``drivers/`` and a generator under
``generators/``), and each metric has a reader under ``end_to_end/`` or
``layer_metrics/``, found by the metric's name up to its first dot.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PACKAGE = os.path.basename(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``<benchmark>/<kind>/<name>.py`` as a module of the package (so that
    it can import its neighbours relatively)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: no {kind}/{name}.py for {name!r}")
    full = f"{PACKAGE}.{kind}.{name.replace('.', '_')}"
    if full in sys.modules:
        return sys.modules[full]
    spec = importlib.util.spec_from_file_location(full, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[full] = module
    spec.loader.exec_module(module)
    return module


def reader_for(kind, metric_name):
    return load_module(kind, metric_name.split(".")[0])


def load_cell(name, manifest=None):
    """Everything the manifest and the data files say about one cell."""
    manifest = manifest or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(known: {', '.join(cells)})")
    cell = cells[name]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return {"cell": cell, "config": load_json(ROOT, entry["file"]),
            "traffic": traffic,
            "end_to_end": mine(manifest["end_to_end"]),
            "per_layer": mine(manifest["per_layer"])}


def peak_for(device_kind):
    peaks = load_json(HERE, "peaks.json")
    if device_kind not in peaks or device_kind.startswith("_"):
        raise SystemExit(
            f"run.py: no peaks known for device_kind {device_kind!r}; add "
            f"it to {PACKAGE}/peaks.json with its source")
    return peaks[device_kind]


def find_device(chips):
    """Refuse anything but a TPU with enough chips."""
    import jax

    found = jax.devices()
    dev = {"platform": found[0].platform, "kind": found[0].device_kind,
           "count": len(found)}
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"run.py needs a TPU: jax found platform {dev['platform']!r} "
            f"({dev['kind']} x{dev['count']}); no result")
    if dev["count"] < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, jax found "
                         f"{dev['count']}; no result")
    return dev, found[:chips]


class Context:
    """What a driver gets: the cell's data, the clock, the capture, and the
    hooks that mark set-up phases and the window's edges."""

    def __init__(self, cell, args, say):
        from . import capture

        self.config, self.traffic = cell["config"], cell["traffic"]
        self.chips = cell["cell"]["chips"]
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.generator = load_module("generators",
                                     self.traffic["generator"])
        self.clock = time.perf_counter
        self.capture = (capture.Capture(os.path.join(
            ROOT, ".bench_trace", cell["cell"]["name"]))
            if args.trace else None)
        self.span = capture.span if args.trace else _no_span
        self.compiles = capture.CompileCounter()
        self._say = say
        self.phases, self._last = {}, T_START
        self.set_up = None

    def mark(self, phase):
        now = self.clock()
        self.phases[phase] = round(now - self._last, 3)
        self._last = now

    def window_opens(self):
        self.mark("rest")
        self._say(set_up_phases_s=self.phases)
        self.compiles.armed = True
        now = self.clock()
        self.set_up = now - T_START
        return now

    def window_closes(self, at=None):
        now = self.clock() if at is None else at
        self.compiles.armed = False
        if self.capture:
            self.capture.stop()
        return now


def _no_span(name):
    return contextlib.nullcontext()


def read_metrics(kind, metrics, run):
    """``{name: {"value", "unit"}}`` and the readers' notes. A reader that
    finds nothing to read returns ``None`` and its metric is left out."""
    out, notes = {}, {}
    for m in metrics:
        got = reader_for(kind, m["name"]).read(run)
        if isinstance(got, tuple):
            got, notes[m["name"]] = got
        if got is not None:
            out[m["name"]] = {"value": float(got), "unit": m["unit"]}
    return out, notes


def memory_peak(devices, program_bytes):
    """The fullest chip's peak. The allocator's ``peak_bytes_in_use`` counts
    live arrays and not a running program's temporaries on this backend, so
    the compiled step's own footprint stands in where it is larger."""
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return max(peak, int(program_bytes or 0))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = load_cell(args.workload)
    sys.path.insert(0, ROOT)
    try:
        from paddle_tpu.framework.compile_cache import configure_compile_cache
    except ImportError as e:
        raise SystemExit(f"run.py: the system under test is not in this "
                         f"checkout ({e}); no result")
    # kernel block sizes from the packaged defaults, never from $HOME
    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = os.path.join(
        ROOT, ".pallas_autotune.json")
    cache_dir = configure_compile_cache()
    import jax

    # keep every program, however quick to compile: a warm run then finds
    # all of its set-up in the cache, and set-up is steadier for it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device, devices = find_device(cell["cell"]["chips"])
    peak = peak_for(device["kind"])

    def say(**fields):
        print(json.dumps({"device": device, **fields}), flush=True)

    say(workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, compile_cache=cache_dir)
    ctx = Context(cell, args, say)
    driver = load_module("drivers", cell["traffic"]["driver"])
    run = driver.run(ctx)
    run["clock"]["set_up"] = ctx.set_up
    run["clock"]["trace_t0"] = ctx.capture.t_start if ctx.capture else None
    run.update(config=cell["config"], peak=peak, chips=ctx.chips,
               trace=ctx.capture.reduced if ctx.capture else None)

    compiled_in_window = ctx.compiles.count
    kind, wanted = (("layer_metrics", cell["per_layer"]) if args.trace
                    else ("end_to_end", cell["end_to_end"]))
    metrics, notes = read_metrics(kind, wanted, run)
    missing = [m["name"] for m in wanted if m["name"] not in metrics]
    if missing and not args.trace:
        raise SystemExit(f"run.py: no value for {missing}; no result")
    say(info=run.get("info", {}), notes=notes, not_reported=missing,
        compiled_in_window=compiled_in_window,
        window_s=run["clock"]["window_s"], paused_s=run["clock"]["paused_s"])

    out_device = dict(device, memory_peak_bytes=memory_peak(
        devices, run.get("program_bytes")))
    result = {
        "correct": bool(run["correct"] and compiled_in_window == 0),
        "attempted": int(run["attempted"]), "failed": int(run["failed"]),
        "metrics": metrics, "device": out_device,
    }
    if args.trace:
        from . import reduce_trace

        busy_s, window_s = reduce_trace.busy_and_window(run["trace"])
        if busy_s <= 0:
            raise SystemExit("run.py: the capture holds no device "
                             "operation; no result")
        out_device.update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = reduce_trace.breakdown(run["trace"])
    # each number the driver held against the reference, beside its limit:
    # last in the line and last on standard error
    result["compared"] = run.get("compared", {})
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # run as a script: make this directory importable as a package, so that
    # drivers and readers can import their neighbours
    sys.path.insert(0, ROOT)
    __package__ = PACKAGE
    importlib.import_module(PACKAGE)
    sys.exit(main())
