"""Bench JSON-line schema lint (tpulint BL rules).

Every bench driver in this repo (bench.py, bench_serve.py,
bench_flash_ab.py) speaks one-line JSON records with the driver contract
``{"metric": str, "value": number, "unit": str, ...}``; round-over-round
deltas (BASELINE.md, the VERDICT tables) are computed off those lines. A
malformed line — a NaN value, a unit typo, a metric renamed mid-era —
silently drops out of the delta and skews the comparison instead of
failing. This module is the loud failure:

- :func:`validate_line` — the schema check the emitters call at print time
  (a bad line raises at the bench, not two rounds later in a diff).
- :func:`lint_artifacts` — **BL001**: sweep the checked-in ``BENCH_*.json``
  driver artifacts, re-validating every JSON line embedded in their
  ``tail`` transcripts.
"""
from __future__ import annotations

import glob
import json
import math
import os

from .findings import Finding, rule

BL001 = rule("BL001", "malformed bench JSON line in a checked-in artifact")

#: required keys -> type predicate
_REQUIRED = {
    "metric": lambda v: isinstance(v, str) and v.strip(),
    "value": lambda v: isinstance(v, (int, float))
    and not isinstance(v, bool) and math.isfinite(v),
    "unit": lambda v: isinstance(v, str) and v.strip(),
}
_OPTIONAL_NUMERIC = ("vs_baseline", "p50_ms", "p99_ms", "anchor_tflops",
                     "anchor_frac_peak", "ttft_p50_ms", "ttft_p99_ms",
                     "prefix_hit_rate", "decode_retraces",
                     "hbm_bytes_per_token",
                     # round 23: the jaxpr-derived static HBM model and
                     # its relative drift against the analytic one — the
                     # pair the tpulint JX007 cost contracts gate
                     "hbm_bytes_per_token_static", "hbm_model_drift_frac",
                     "mesh_chips", "tokens_per_s_per_chip",
                     "accepted_tokens_per_step", "draft_acceptance_rate",
                     # round 13: sync-vs-async serving A/B — the
                     # no-step-in-flight wall-clock fraction (device-idle
                     # upper bound), host scheduling ms outside blocking
                     # waits, and the greedy emission-identity gate of
                     # the async leg against the sync leg (1.0 = every
                     # common request's stream bit-identical)
                     "step_gap_frac", "host_ms_per_step",
                     "async_emissions_match", "sync_tokens_per_s",
                     "sync_step_gap_frac",
                     # round 14: quantized dp gradient allreduce A/B —
                     # analytic per-replica wire bytes of one gradient
                     # sync (int8 leg / fp oracle leg), their ratio, the
                     # max relative loss-trajectory deviation of the int8
                     # leg vs the fp oracle over the N benched steps, and
                     # the bit-equality gate of the synced params across
                     # dp replicas (1.0 = every leaf's device shards
                     # byte-identical)
                     "bytes_on_the_wire", "bytes_on_the_wire_fp",
                     "wire_reduction", "loss_parity_delta",
                     "replicas_bit_identical",
                     # round 15: the observability A/B — tokens/s of the
                     # untraced (observability-disabled) interleaved
                     # partner riding the traced leg's line, and the
                     # host trace events the traced windows recorded
                     "obs_off_tokens_per_s", "trace_events",
                     # round 16: wall ms per dispatched step with work
                     # in flight (the host-observable device-time proxy)
                     "device_ms_per_step",
                     # round 17: the overload/resilience leg — admissions
                     # shed by the SLO policy and deadline misses as
                     # fractions of attempted arrivals, terminal FAILED
                     # requests, and the interleaved nominal-load
                     # partner's rates riding the overload line (the
                     # shed_rate == 0 at-nominal-load half of the gate)
                     "shed_rate", "deadline_miss_rate", "failed_requests",
                     "nominal_shed_rate", "nominal_deadline_miss_rate",
                     # round 18: the multi-replica fleet leg — aggregate
                     # throughput split per live replica, the fraction of
                     # placements the prefix-affinity map decided, and the
                     # request migrations the injected replica churn
                     # forced (failover as a routing event: the leg's
                     # tokens/s stays live through them)
                     "tokens_per_s_per_replica", "affinity_hit_rate",
                     "failover_count",
                     # round 20: the disaggregated prefill/decode leg —
                     # wire bytes per emitted token over the fault-free
                     # windows (int8-KV payloads + scale planes; the fp
                     # partner's figure rides the line for the ~4x wire
                     #-thrift ratio), frame retransmits, colocated-
                     # fallback degradations (the fault-free figure must
                     # be exactly 0; the chaos-window total must not
                     # be), and the interleaved colocated partner's
                     # throughput/TTFT the no-worse gates compare
                     # against
                     "transfer_bytes_per_token",
                     "fp_transfer_bytes_per_token", "kv_transfer_retries",
                     "prefill_fallback_count", "fault_free_fallback_count",
                     "colocated_tokens_per_s", "colocated_ttft_p99_ms",
                     # round 19: the model-draft speculative leg — the
                     # fraction of step() wall time the truncated-layer
                     # draft pass costs, the interleaved n-gram partner's
                     # stats riding the model line, and the
                     # cross-proposer greedy emission identity gate
                     # (speculation never changes output, so two draft
                     # sources over one churn must emit identically)
                     "draft_overhead_frac", "ngram_tokens_per_s",
                     "ngram_accepted_tokens_per_step",
                     "spec_emissions_match",
                     # round 21: the tiered-KV leg — host-tier hit rate
                     # over the fault-free windows, spill/restore payload
                     # bytes, cross-replica prefix pulls (drain-forced:
                     # never a probabilistic race), pull degradations,
                     # the chaos pass's fired-and-detected counts (the
                     # fault-free corruption figure must be exactly 0),
                     # and the interleaved no-tier partner's stats the
                     # strictly-higher-hit-rate / strictly-lower-TTFT
                     # gates compare against
                     "tier_hit_rate", "spill_bytes", "restore_bytes",
                     "cross_replica_pulls", "pull_fallback_count",
                     "tier_spill_drops", "tier_corrupt_detected",
                     "fault_free_corrupt_detected", "notier_tokens_per_s",
                     "notier_prefix_hit_rate", "notier_ttft_p99_ms",
                     # round 25: the dense-vs-MoE interleaved A/B — the
                     # router's per-window load dispersion (max expert
                     # load / mean, 1.0 = perfectly balanced), the
                     # capacity-drop fraction, the active-parameter
                     # fraction a routed token touches, and the paired
                     # dense leg's throughput on the MoE line
                     "expert_load_imbalance", "router_drop_rate",
                     "active_params_frac", "dense_tokens_per_s")
_OPTIONAL_STRING = ("mesh_shape", "comm_quant")

#: the bench_serve leg-name enum (round 16): every serving line carries
#: ``leg`` and it must be one of these — a typo'd leg name used to pass
#: the schema silently (the name only lived inside the metric string) and
#: drop out of round-over-round deltas exactly like the malformed lines
#: this module exists to stop.
KNOWN_LEGS = frozenset((
    "unified-step", "unified-async", "unified-obs",
    "unified-spmd", "unified-spec-base", "unified-spec-k4",
    "unified-spec-model", "unified-int8w", "unified-int8w-int8kv",
    "unified-overload",
    "fleet-churn", "fleet-disagg", "fleet-tiered", "moe-churn",
))


def validate_line(obj) -> list[str]:
    """Problems with one bench JSON record (empty list == valid).

    Error lines (``value == 0`` with an ``error`` string) are part of the
    driver contract and validate like any other line.
    """
    if not isinstance(obj, dict):
        return [f"bench line must be a JSON object, got {type(obj).__name__}"]
    problems = []
    for key, ok in _REQUIRED.items():
        if key not in obj:
            problems.append(f"missing required key '{key}'")
        elif not ok(obj[key]):
            problems.append(f"key '{key}' malformed: {obj[key]!r}")
    for key in _OPTIONAL_NUMERIC:
        if key in obj and not (
                isinstance(obj[key], (int, float))
                and not isinstance(obj[key], bool)
                and math.isfinite(obj[key])):
            problems.append(f"key '{key}' must be a finite number, "
                            f"got {obj[key]!r}")
    for key in _OPTIONAL_STRING:
        if key in obj and not (isinstance(obj[key], str) and obj[key].strip()):
            problems.append(f"key '{key}' must be a non-empty string, "
                            f"got {obj[key]!r}")
    if "error" in obj and not isinstance(obj["error"], str):
        problems.append(f"key 'error' must be a string, got {obj['error']!r}")
    # round 16: serving lines name their leg — and the name must be real
    if "leg" in obj:
        leg = obj["leg"]
        if leg not in KNOWN_LEGS:
            problems.append(
                f"key 'leg' {leg!r} is not a known bench_serve leg "
                f"(known: {', '.join(sorted(KNOWN_LEGS))})")
        elif (isinstance(obj.get("metric"), str)
              and f"[{leg}]" not in obj["metric"]):
            problems.append(
                f"key 'leg' {leg!r} does not match the metric suffix "
                f"in {obj['metric']!r}")
    # round 15: the telemetry snapshot sub-object (the flat
    # MetricsRegistry.snapshot_flat() export riding bench lines) — a
    # non-finite counter or a non-numeric value fails at the bench, so a
    # regression in e.g. prefix hits or wire bytes stays machine-diffable
    if "telemetry" in obj:
        problems.extend(_telemetry_problems(obj["telemetry"]))
    return problems


def _telemetry_problems(tel) -> list[str]:
    if not isinstance(tel, dict) or not tel:
        return [f"key 'telemetry' must be a non-empty flat object, "
                f"got {tel!r}"]
    problems = []
    for k, v in tel.items():
        if not isinstance(k, str) or not k.strip():
            problems.append(f"telemetry key {k!r} must be a non-empty "
                            "string")
        if not (isinstance(v, (int, float)) and not isinstance(v, bool)
                and math.isfinite(v)):
            problems.append(f"telemetry['{k}'] must be a finite number, "
                            f"got {v!r}")
    return problems


def checked_line(obj) -> str:
    """json.dumps with the schema enforced — the emitter entry: a malformed
    bench line fails AT THE BENCH instead of silently skewing deltas."""
    problems = validate_line(obj)
    if problems:
        raise ValueError(
            f"malformed bench line {obj!r}: {'; '.join(problems)}")
    return json.dumps(obj)


def _iter_tail_json_lines(text: str):
    """Complete JSON-looking lines inside a driver-artifact tail transcript
    (tails are tail-truncated, so a clipped first line is skipped)."""
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            yield line


def lint_artifacts(root: str | None = None) -> list[Finding]:
    """BL001 over the repo-root BENCH_*.json driver artifacts."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    findings = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        rel = os.path.basename(path)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            findings.append(Finding(
                rule=BL001, target=rel, detail="artifact-parse",
                message=f"driver artifact is not valid JSON: {e}"))
            continue
        tail = doc.get("tail", "")
        if not isinstance(tail, str):
            continue
        for line in _iter_tail_json_lines(tail):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                findings.append(Finding(
                    rule=BL001, target=rel, detail="line-parse",
                    message=f"unparseable JSON line in tail: {line[:80]}"))
                continue
            problems = validate_line(obj)
            if problems:
                findings.append(Finding(
                    rule=BL001, target=rel,
                    detail=str(obj.get("metric", "?"))[:60],
                    message=f"bench line fails schema: {'; '.join(problems)}"))
    return findings
