"""``python -m paddle_tpu.analysis`` — the tpulint CLI gate.

Exit codes: 0 = every finding baselined (or none), 2 = new findings.

Usage::

    python -m paddle_tpu.analysis                  # all passes, gate mode
    python -m paddle_tpu.analysis --passes source,bench
    python -m paddle_tpu.analysis --json           # machine-readable report
    python -m paddle_tpu.analysis --write-baseline # accept current findings
    python -m paddle_tpu.analysis --list-targets   # flagship target names
    python -m paddle_tpu.analysis --target serving-unified
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m paddle_tpu.analysis")
    ap.add_argument("--passes", default=",".join(
        ("source", "trace", "registry", "bench")),
        help="comma list: source,trace,registry,bench")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept the current finding set into baseline.json")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON report object instead of text")
    ap.add_argument("--rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--target", default=None,
                    help="comma list of flagship targets: run ONLY the "
                         "trace pass over these (local iteration / CI "
                         "shards skip the full sweep)")
    ap.add_argument("--list-targets", action="store_true",
                    help="print the flagship target names and exit")
    args = ap.parse_args(argv)

    if args.list_targets:
        # target registration is import-cheap (the analyze functions do
        # their heavy imports lazily) — no jax init needed to list
        from .targets import TARGETS
        for name in TARGETS:
            print(name)
        return 0

    targets = None
    if args.target is not None:
        from .targets import TARGETS
        targets = {t.strip() for t in args.target.split(",") if t.strip()}
        unknown = targets - set(TARGETS)
        if unknown:
            ap.error(f"unknown target(s) {sorted(unknown)}; "
                     "see --list-targets")
        # a target-restricted run is a trace-pass run by definition
        args.passes = "trace"

    # deterministic gate environment: an 8-way virtual CPU mesh (the trace
    # pass analyzes the dp2/pp2/mp2 step), pinned before jax initializes —
    # same strategy as tests/conftest.py
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from . import (RULES, diff_against_baseline, load_baseline,
                   pass_of_fingerprint, run_all, write_baseline)

    if args.rules:
        # importing the pass modules populates the catalog
        from . import (astlint, bench_schema, collectives_audit,  # noqa: F401
                       cost_model, jaxpr_checks, registry_audit,
                       threadlint, vmem)
        for rid, desc in sorted(RULES.items()):
            print(f"{rid}  {desc}")
        return 0

    passes = tuple(p.strip() for p in args.passes.split(",") if p.strip())
    if targets is not None and args.write_baseline:
        ap.error("--write-baseline needs the full trace sweep; drop "
                 "--target")
    findings = run_all(passes, targets=targets)

    if args.write_baseline:
        # a partial run only owns its passes' entries: preserve the rest so
        # --passes source --write-baseline can't drop accepted trace findings
        keep = {fp for fp in load_baseline()
                if pass_of_fingerprint(fp) not in passes}
        doc = write_baseline(findings, keep=keep)
        print(f"baseline written: {len(doc['findings'])} fingerprints"
              + (f" ({len(keep)} preserved from passes that did not run)"
                 if keep else ""))
        return 0

    # a partial run only owns its passes' baseline entries: diffing against
    # the full set would report still-live findings of passes that did not
    # run as "stale" (same ownership filter as --write-baseline above). A
    # --target run narrows further, to trace fingerprints whose target
    # component (rule::target::detail) starts with a selected target name
    base = {fp for fp in load_baseline()
            if pass_of_fingerprint(fp) in passes}
    if targets is not None:
        base = {fp for fp in base
                if any(fp.split("::", 2)[1].startswith(t)
                       for t in targets)}
    new, accepted, fixed = diff_against_baseline(findings, base)
    if args.json:
        print(json.dumps({
            "passes": list(passes),
            "new": [f.to_json() for f in new],
            "accepted": [f.to_json() for f in accepted],
            "fixed_baseline_entries": fixed,
        }, indent=1))
    else:
        for f in new:
            print(f"NEW      {f}")
        for f in accepted:
            print(f"accepted {f}")
        for fp in fixed:
            print(f"fixed    {fp} (baselined but no longer fires — "
                  "rewrite the baseline to drop it)")
        print(f"tpulint: {len(new)} new, {len(accepted)} baselined, "
              f"{len(fixed)} stale baseline entr"
              f"{'y' if len(fixed) == 1 else 'ies'} "
              f"over passes {','.join(passes)}")
    return 2 if new else 0


if __name__ == "__main__":
    sys.exit(main())
