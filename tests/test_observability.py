"""Round-15 observability subsystem: the structured metrics registry
(Counter/Gauge/Histogram, labels, disabled path, thread-safety), the host
span + per-request async-lane tracing API, and the end-to-end acceptance
gate — a CPU-smoke serving run under the profiler facade exports ONE
chrome trace with pack_dispatch/reconcile host spans and a complete
per-request lifecycle lane (admit -> ... -> eos), and the serving
telemetry snapshot passes the bench schema gate."""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (MetricsRegistry, default_registry,
                                      merge_snapshots, span)
from paddle_tpu.profiler.record import recorder

TINY = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=96)


def _tiny_model(**over):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(7)
    cfg = GPTConfig(**{**TINY, **over})
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


# ---------------------------------------------------------------------------
# metrics registry core
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("steps", "help text")
        c.inc()
        c.inc(3)
        assert c.value == 4
        with pytest.raises(ValueError):
            c.inc(-1)   # counters only go up
        g = reg.gauge("depth")
        g.set(5)
        g.dec(2)
        g.inc()
        assert g.value == 4
        h = reg.histogram("lat", buckets=(1.0, 10.0))
        for v in (0.5, 0.7, 5.0, 50.0):
            h.observe(v)
        assert h.count == 4 and h.sum == pytest.approx(56.2)
        assert 0.0 < h.quantile(0.5) <= 1.0     # 2 of 4 in the <=1 bucket
        assert h.quantile(0.99) == 10.0         # overflow clamps to last

    def test_labels_and_snapshot_shapes(self):
        reg = MetricsRegistry()
        fam = reg.counter("wire", labels=("op", "quant"))
        fam.labels(op="all_reduce", quant="int8").inc(100)
        fam.labels(op="all_reduce", quant="fp").inc(400)
        # same assignment -> same child (cached, not a new series)
        fam.labels(op="all_reduce", quant="int8").inc(11)
        with pytest.raises(ValueError):
            fam.labels(op="all_reduce")   # missing label name
        with pytest.raises(ValueError):
            reg.counter("wire", labels=("op",))   # schema conflict
        with pytest.raises(ValueError):
            reg.gauge("wire", labels=("op", "quant"))   # kind conflict
        snap = reg.snapshot()
        assert snap["counters"]["wire{op=all_reduce,quant=int8}"] == 111
        flat = reg.snapshot_flat()
        assert flat["wire{op=all_reduce,quant=fp}"] == 400
        # an unlabeled family proxies to its single child
        reg.counter("plain").inc(2)
        assert reg.snapshot_flat()["plain"] == 2
        with pytest.raises(ValueError):
            reg.counter("plain2", labels=("x",)).inc()   # needs .labels()

    def test_disabled_path_is_noop_and_flippable(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("c")
        g = reg.gauge("g")
        h = reg.histogram("h", buckets=(1,))
        c.inc(5)
        g.set(9)
        h.observe(2)
        assert c.value == 0 and g.value == 0 and h.count == 0
        reg.enable()
        c.inc(5)
        assert c.value == 5
        reg.disable()
        c.inc(5)
        assert c.value == 5

    def test_reset_zeroes_in_place(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        h = reg.histogram("h", buckets=(1,))
        c.inc(3)
        h.observe(0.5)
        reg.reset()
        assert c.value == 0 and h.count == 0 and h.sum == 0
        c.inc()   # the same child object keeps working
        assert reg.snapshot_flat()["c"] == 1

    def test_thread_safety_no_lost_increments(self):
        """The async engine's dispatch/reconcile split and the watchdog
        monitor thread share counters; the registry lock must not lose
        increments under contention."""
        reg = MetricsRegistry()
        c = reg.counter("hot")
        n, per = 4, 5000

        def worker():
            for _ in range(per):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n * per

    def test_snapshot_flat_rejects_nonfinite(self):
        reg = MetricsRegistry()
        reg.histogram("h", buckets=(1,)).observe(float("inf"))
        with pytest.raises(ValueError, match="non-finite"):
            reg.snapshot_flat()

    def test_merge_snapshots_conflict(self):
        assert merge_snapshots({"a": 1}, {"b": 2}) == {"a": 1, "b": 2}
        assert merge_snapshots({"a": 1}, {"a": 1}) == {"a": 1}
        with pytest.raises(ValueError, match="conflicting"):
            merge_snapshots({"a": 1}, {"a": 2})

    def test_default_registry_off_by_default(self):
        assert not default_registry.enabled


# ---------------------------------------------------------------------------
# span / request-lane tracing
# ---------------------------------------------------------------------------


class TestTracing:
    def test_span_noop_when_recorder_disabled(self):
        assert not recorder.enabled
        s1 = span("a")
        s2 = span("b")
        assert s1 is s2   # the shared null context manager: no allocation
        before = len(recorder.events)
        with span("nothing"):
            pass
        assert len(recorder.events) == before

    def test_span_records_into_recorder_when_enabled(self):
        recorder.clear()
        recorder.enabled = True
        try:
            with span("outer"):
                with span("inner", category="custom"):
                    pass
        finally:
            recorder.enabled = False
        names = [(e.name, e.category) for e in recorder.events]
        assert ("inner", "custom") in names and ("outer", "serving") in names
        for e in recorder.events:
            assert e.end_ns >= e.start_ns
        recorder.clear()


# ---------------------------------------------------------------------------
# instrumented serving stack
# ---------------------------------------------------------------------------


class TestServingTelemetry:
    def test_predictor_registry_backcompat_and_snapshot(self, rng):
        from paddle_tpu.analysis.bench_schema import validate_line
        from paddle_tpu.inference import ServingPredictor

        model = _tiny_model()
        sp = ServingPredictor(model, max_batch=2, page_size=8,
                              max_seq_len=64, use_kernel=False)
        prompts = [rng.randint(0, TINY["vocab_size"], (9,)) for _ in range(3)]
        outs = sp.generate(prompts, max_new_tokens=5)
        assert all(len(o) == 5 for o in outs)
        # back-compat reads mirror the registry counters
        flat = sp.telemetry()
        assert sp.tokens_emitted == 15 == flat["serving_tokens_emitted"]
        assert sp.steps == flat["serving_steps"] > 0
        assert flat["serving_requests_admitted"] >= 3
        assert flat["serving_requests_finished"] == 3
        assert flat["serving_ttft_ms_count"] == 3
        # the KV cache shares the registry: pool gauges are live
        assert flat["kv_slots_free"] == 2.0   # all requests retired
        assert flat["kv_pages_free"] >= 0
        # the snapshot IS bench-line-shaped (the schema gate)
        line = {"metric": "m", "value": 1.0, "unit": "tokens/s",
                "telemetry": flat}
        assert validate_line(line) == []

    def test_preemption_and_prefix_counters(self, rng):
        from paddle_tpu.inference import ServingPredictor

        model = _tiny_model()
        # tight pool: both prompts admit (1 page each + 1 headroom), then
        # growth across the page boundary exhausts the pool and preempts
        # the youngest back to the queue
        sp = ServingPredictor(model, max_batch=2, max_seq_len=16,
                              page_size=4, num_pages=3, use_kernel=False)
        prompts = [[3, 1, 4, 1], [5, 9, 2, 6]]
        outs = sp.generate(prompts, max_new_tokens=6)
        assert all(len(o) == 6 for o in outs)
        flat = sp.telemetry()
        assert flat["serving_preemptions"] > 0
        # repeated prompt -> prefix hits counted through the registry
        sp2 = ServingPredictor(model, max_batch=2, page_size=4,
                               max_seq_len=32, use_kernel=False)
        p = rng.randint(0, TINY["vocab_size"], (8,))
        sp2.generate([p], max_new_tokens=2)
        sp2.generate([p], max_new_tokens=2)
        f2 = sp2.telemetry()
        assert f2["kv_prefix_hit_tokens"] > 0
        assert sp2.cache.prefix_hit_tokens == f2["kv_prefix_hit_tokens"]
        assert sp2.prefix_hit_rate > 0

    def test_serving_trace_acceptance_gate(self, rng, tmp_path):
        """THE round-15 acceptance criterion: a CPU-smoke serving run with
        tracing enabled exports a chrome trace containing
        pack_dispatch/reconcile host spans and >= 1 COMPLETE per-request
        async lane (b 'admit' ... eos e), and the telemetry snapshot
        passes the schema gate."""
        from paddle_tpu.inference import ServingPredictor
        from paddle_tpu.profiler import Profiler, export_chrome_tracing

        model = _tiny_model()
        sp = ServingPredictor(model, max_batch=2, page_size=8,
                              max_seq_len=64, use_kernel=False)
        prompts = [rng.randint(0, TINY["vocab_size"], (9,))
                   for _ in range(2)]
        p = Profiler(on_trace_ready=export_chrome_tracing(str(tmp_path),
                                                          "serve"))
        p.start()
        sp.generate(prompts, max_new_tokens=4)
        p.stop()
        assert p._last_export is not None
        with open(p._last_export) as f:
            events = json.load(f)["traceEvents"]
        x_names = {e["name"] for e in events if e["ph"] == "X"}
        assert "pack_dispatch" in x_names
        assert "reconcile" in x_names
        assert "dispatch" in x_names
        # complete request lanes: every 'b' has a matching 'e' (same id),
        # with admit and eos instants in between
        begins = {e["id"] for e in events if e["ph"] == "b"}
        ends = {e["id"] for e in events if e["ph"] == "e"}
        assert begins and begins == ends
        instants = {}
        for e in events:
            if e["ph"] == "n":
                instants.setdefault(e["id"], set()).add(e["name"])
        for rid in begins:
            assert "admit" in instants[rid]
            assert "eos" in instants[rid]
            assert "decode" in instants[rid] or \
                "prefill_chunk" in instants[rid]
        # tracing OFF again after stop(): spans are the shared no-op
        assert not recorder.enabled

    def test_disabled_path_two_percent_contract(self, rng):
        """THE round-15 overhead contract, gated deterministically: with
        observability disabled, the per-step instrumentation budget
        (every span()/counter/gauge call a serving step makes, at the
        MEASURED disabled-path cost on this box) must stay under 2% of
        this box's measured serving step time. Both sides of the ratio
        scale with interpreter speed, so the gate is machine-portable
        where an end-to-end tokens/s A/B (see bench_serve unified-obs)
        drowns in churn noise."""
        import timeit

        from paddle_tpu.inference import ServingPredictor

        # measured disabled-path primitive costs (tight loops: stable
        # under load in a way wall-clock churn is not)
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("c")
        n = 20000
        t_inc = timeit.timeit(c.inc, number=n) / n
        t_span = timeit.timeit(lambda: span("x"), number=n) / n
        # generous per-step call budget: ~8 span enters/exits + ~40
        # counter/gauge touches (predictor + cache mutators), doubled
        budget_s = 2 * (8 * t_span + 40 * t_inc)
        # this box's real per-step host time, from the instrumented churn
        model = _tiny_model()
        sp = ServingPredictor(model, max_batch=2, page_size=8,
                              max_seq_len=64, use_kernel=False)
        prompts = [rng.randint(0, TINY["vocab_size"], (9,))
                   for _ in range(4)]
        sp.generate(prompts, max_new_tokens=8)
        flat = sp.telemetry()
        step_s = flat["serving_step_seconds"] / flat["serving_step_calls"]
        assert budget_s < 0.02 * step_s, (
            f"disabled-path instrumentation budget {budget_s * 1e6:.1f}us "
            f"is not <2% of the {step_s * 1e6:.0f}us serving step")

    @pytest.mark.parametrize("async_engine", [True, False])
    def test_scheduler_counts_its_own_rows_and_waits(self, rng,
                                                     async_engine):
        """PR 26: the rows ``_pack_dispatch`` counts as it packs them are
        the rows ``KVCacheManager.seq_len`` shows were written; queue wait
        is observed once per request, at first admission; prefill once, at
        the dispatch of the prompt's last chunk; the reconcile lag is a
        count of steps and is 0 for the synchronous engine."""
        from paddle_tpu.inference import ServingPredictor

        sp = ServingPredictor(_tiny_model(), max_batch=2, page_size=8,
                              max_seq_len=64, use_kernel=False, chunk=8,
                              token_budget=16, async_engine=async_engine)
        prompts = [rng.randint(0, TINY["vocab_size"], (n,))
                   for n in (5, 19, 9, 26)]   # 4 requests on 2 lanes
        reqs = [sp.add_request(p, max_new_tokens=6) for p in prompts]
        written, before = 0, {}
        while sp.has_work():
            sp.step()
            # a lane is retired at the start of the step after its last
            # row, so every row a lane was fed shows here
            after = {slot: (r.req_id, sp.cache.seq_len(slot))
                     for slot, r in sp.running.items()}
            for slot, (rid, kv) in after.items():
                had = before.get(slot)
                written += kv - (had[1] if had and had[0] == rid else 0)
            before = after
        sp.flush()
        flat = sp.telemetry()
        rows = flat["serving_rows_prefill"] + flat["serving_rows_decode"]
        assert rows == written
        # every context token but the last generated one is fed once
        assert rows == sum(len(r.prompt_ids) + len(r.output_ids) - 1
                           for r in reqs)
        assert flat["serving_rows_prefill"] == sum(map(len, prompts))
        assert flat["serving_rows_decode"] == 4 * (6 - 1)
        assert flat["serving_queue_wait_ms_count"] == 4   # first admissions
        assert flat["serving_requests_admitted"] >= 4
        assert flat["serving_prefill_ms_count"] == 4
        assert flat["serving_prefill_ms_sum"] >= 0
        for r in reqs:
            assert r.submit_time <= r.admit_time <= r.prefill_end_time \
                <= r.first_token_time
        assert flat["serving_reconcile_lag_steps_count"] == \
            flat["serving_steps"]
        if async_engine:
            assert flat["serving_reconcile_lag_steps_sum"] >= 1
        else:
            assert flat["serving_reconcile_lag_steps_sum"] == 0

    def test_queue_wait_is_not_observed_again_on_replay(self, rng):
        """A preempted request is admitted twice and waited for once."""
        from paddle_tpu.inference import ServingPredictor

        sp = ServingPredictor(_tiny_model(), max_batch=2, page_size=4,
                              num_pages=6, max_seq_len=32, use_kernel=False)
        for n in (8, 8):
            sp.add_request(rng.randint(0, TINY["vocab_size"], (n,)),
                           max_new_tokens=10)
        while sp.has_work():
            sp.step()
        sp.flush()
        flat = sp.telemetry()
        assert flat["serving_preemptions"] >= 1
        assert flat["serving_requests_admitted"] > 2
        assert flat["serving_queue_wait_ms_count"] == 2
        assert flat["serving_prefill_ms_count"] == 2

    def test_disabled_registry_rejected_loudly(self):
        """The predictor's (and KV manager's) counters back the
        behavioral read surface — a disabled registry (e.g. the off-by-
        default library-wide default_registry) would silently report
        zeros, so the constructors fail loud instead."""
        from paddle_tpu.inference import KVCacheManager, ServingPredictor

        model = _tiny_model()
        with pytest.raises(ValueError, match="enabled metrics registry"):
            ServingPredictor(model, max_batch=2, page_size=8,
                             max_seq_len=64, use_kernel=False,
                             metrics=MetricsRegistry(enabled=False))
        with pytest.raises(ValueError, match="enabled metrics registry"):
            KVCacheManager(2, 4, 8, num_pages=8, max_batch=2,
                           max_seq_len=64, page_size=8,
                           metrics=MetricsRegistry(enabled=False))

    def test_midstream_window_has_no_orphan_lane_phases(self, rng):
        """A RECORD window opening MID-request (or a second window after
        a clear discarded the first window's begins) must stay
        self-consistent: every 'n'/'e' lane phase in the buffer has an
        in-window 'b' — mid-flight lanes are re-opened, never emitted
        orphaned."""
        from paddle_tpu.inference import ServingPredictor

        model = _tiny_model()
        sp = ServingPredictor(model, max_batch=2, page_size=8,
                              max_seq_len=64, use_kernel=False)
        for p in [rng.randint(0, TINY["vocab_size"], (9,))
                  for _ in range(2)]:
            sp.add_request(p, max_new_tokens=6)
        recorder.clear()
        recorder.enabled = True
        sp.step()   # window 1: admits recorded ('b' + admit)
        sp.step()
        recorder.clear()   # window boundary: window 1's begins are GONE
        try:
            while sp.running or sp.waiting:
                sp.step()
            sp.flush()
        finally:
            recorder.enabled = False
        begins = {e.id for e in recorder.aux if e.ph == "b"}
        laned = {e.id for e in recorder.aux if e.ph in ("n", "e")}
        assert laned               # window 2 did see the lanes...
        assert laned <= begins     # ...re-opened, with NO orphan phases
        ends = {e.id for e in recorder.aux if e.ph == "e"}
        assert ends == begins      # finished in-window: lanes complete
        # the scheduler spans still recorded
        assert any(e.name == "pack_dispatch" for e in recorder.events)
        recorder.clear()

    def test_tracing_preserves_emissions(self, rng):
        """Greedy output with tracing enabled is bit-identical to the
        untraced run (instrumentation must observe, never steer)."""
        from paddle_tpu.inference import ServingPredictor
        from paddle_tpu.profiler import Profiler

        prompts = [rng.randint(0, TINY["vocab_size"], (7,))
                   for _ in range(3)]
        model = _tiny_model()
        sp = ServingPredictor(model, max_batch=2, page_size=8,
                              max_seq_len=64, use_kernel=False)
        want = sp.generate(prompts, max_new_tokens=6)
        sp2 = ServingPredictor(model, max_batch=2, page_size=8,
                               max_seq_len=64, use_kernel=False)
        p = Profiler()
        p.start()
        got = sp2.generate(prompts, max_new_tokens=6)
        p.stop()
        assert got == want
        recorder.clear()

    # -- round 17: the resilience layer's load-signal surface ---------------

    #: the healthz() contract the fleet router consumes — key -> type
    #: predicate; a key added or dropped fails HERE, not in the router
    _HEALTHZ_SCHEMA = {
        "status": lambda v: v in ("ok", "shedding"),
        "shed_reason": lambda v: v is None or (isinstance(v, str) and v),
        # round 18: fleet identity + the staleness stamp (seconds since
        # the last COMPLETED scheduler round) — how a router tells a
        # stale/stuck replica from a merely quiet one
        "replica_id": lambda v: isinstance(v, int) and v >= 0,
        # round 20: the disaggregation role and the sender-side unacked
        # KV-frame backlog (stamped by the fleet router's transfer
        # drive) — the role-aware routing/scoring surface
        "role": lambda v: v in ("colocated", "prefill", "decode"),
        "transfer_backlog": lambda v: isinstance(v, int) and v >= 0,
        "snapshot_age_s": lambda v: isinstance(v, float) and v >= 0,
        "waiting": lambda v: isinstance(v, int) and v >= 0,
        "running": lambda v: isinstance(v, int) and v >= 0,
        "inflight_steps": lambda v: isinstance(v, int) and v >= 0,
        "free_slots": lambda v: isinstance(v, int) and v >= 0,
        "pool_occupancy": lambda v: isinstance(v, float) and 0 <= v <= 1,
        "withheld_pages": lambda v: isinstance(v, int) and v >= 0,
        # round 21: the host-DRAM spill tier — occupancy of the byte
        # budget plus resident bytes; a router scoring pull sources
        # reads restore capacity straight off this surface
        "host_tier_occupancy": lambda v: (isinstance(v, float)
                                          and 0 <= v <= 1),
        "host_tier_bytes": lambda v: isinstance(v, int) and v >= 0,
        "ttft_p99_ema_ms": lambda v: isinstance(v, float) and v >= 0,
        # round 19: the draft-acceptance EMA — a router scoring replicas
        # can prefer ones whose speculation is paying off
        "spec_accept_ema": lambda v: (isinstance(v, float)
                                      and 0 <= v <= 1),
        "steps": lambda v: isinstance(v, int) and v >= 0,
        "tokens_emitted": lambda v: isinstance(v, int) and v >= 0,
        "requests_shed": lambda v: isinstance(v, int) and v >= 0,
        "deadline_misses": lambda v: isinstance(v, int) and v >= 0,
        "requests_failed": lambda v: isinstance(v, int) and v >= 0,
        "step_failures": lambda v: isinstance(v, int) and v >= 0,
        "step_retries": lambda v: isinstance(v, int) and v >= 0,
    }

    def _check_healthz(self, hz):
        assert set(hz) == set(self._HEALTHZ_SCHEMA), (
            "healthz() schema drifted: the fleet router's surface is "
            f"locked here (got {sorted(hz)})")
        for key, ok in self._HEALTHZ_SCHEMA.items():
            assert ok(hz[key]), f"healthz[{key!r}] malformed: {hz[key]!r}"
        json.dumps(hz)   # the surface is a JSON endpoint: must serialize

    def test_healthz_snapshot_schema_and_shed_counters(self, rng):
        """Round-17 satellite: the healthz() snapshot schema is locked,
        and the shed / deadline / retry / fault counters land on the
        registry (flat-snapshot keys the bench telemetry gate rides)."""
        from paddle_tpu.inference import (FaultPlan, ServingPredictor,
                                          SLOConfig)
        from paddle_tpu.inference.serving import FAILED

        model = _tiny_model()
        sp = ServingPredictor(model, max_batch=1, page_size=8,
                              max_seq_len=64, use_kernel=False,
                              retry_backoff_s=0.0,
                              slo=SLOConfig(max_waiting=2))
        self._check_healthz(sp.healthz())
        assert sp.healthz()["status"] == "ok"
        p = rng.randint(0, TINY["vocab_size"], (6,))
        ok = sp.add_request(p, max_new_tokens=3)
        filler = sp.add_request(p, max_new_tokens=3)       # queue now full
        hz = sp.healthz()
        self._check_healthz(hz)
        assert hz["status"] == "shedding"
        assert hz["shed_reason"] == "queue_full"
        shed = sp.add_request(p, max_new_tokens=3)         # shed terminal
        assert shed.state == FAILED
        sp.step()                    # ok admitted: the queue has headroom
        expired = sp.add_request([1, 2], max_new_tokens=2, deadline_s=0.0)
        sp.step()                                          # TTL sweep
        with FaultPlan(seed=0, dispatch=1.0):
            sp.step()                                      # one injected crash
        while sp.has_work():
            sp.step()
        sp.flush()
        assert ok.state == "finished" and filler.state == "finished"
        assert expired.error["code"] == "deadline_exceeded"
        # every resilience counter is live on the flat snapshot
        flat = sp.telemetry()
        assert flat["serving_requests_shed"] == 1
        assert flat["serving_deadline_misses"] == 1
        assert flat["serving_step_failures"] == 1
        assert flat["serving_step_retries"] >= 1
        assert flat["serving_faults_injected{seam=dispatch}"] == 1
        assert flat["serving_requests_failed"] == 2        # shed + expired
        assert flat["serving_fail_reasons{reason=shed_queue_full}"] == 1
        assert flat["serving_fail_reasons{reason=deadline_exceeded}"] == 1
        # healthz mirrors the registry after the churn
        hz = sp.healthz()
        self._check_healthz(hz)
        assert hz["requests_shed"] == 1 and hz["deadline_misses"] == 1
        assert hz["requests_failed"] == 2 and hz["step_failures"] == 1
        assert hz["status"] == "ok"                        # backlog drained

    def test_healthz_replica_identity_and_staleness_stamp(self, rng):
        """Round-18 satellite: healthz() carries the fleet identity
        (``replica_id``, a constructor knob) and a monotonic
        ``snapshot_age_s`` that resets on every completed scheduler
        round and grows while the replica makes no progress."""
        from paddle_tpu.inference import ServingPredictor

        model = _tiny_model()
        sp = ServingPredictor(model, max_batch=1, page_size=8,
                              max_seq_len=64, use_kernel=False,
                              replica_id=3)
        self._check_healthz(sp.healthz())
        assert sp.healthz()["replica_id"] == 3
        # round-20 satellite: the role label rides healthz (default
        # colocated; the fleet router assigns prefill/decode) and the
        # transfer backlog starts empty
        assert sp.healthz()["role"] == "colocated"
        assert sp.healthz()["transfer_backlog"] == 0
        pre = ServingPredictor(model, max_batch=1, page_size=8,
                               max_seq_len=64, use_kernel=False,
                               role="prefill")
        assert pre.healthz()["role"] == "prefill"
        self._check_healthz(pre.healthz())
        with pytest.raises(ValueError, match="role"):
            ServingPredictor(model, max_batch=1, page_size=8,
                             max_seq_len=64, use_kernel=False,
                             role="router")
        sp.add_request(rng.randint(0, TINY["vocab_size"], (5,)),
                       max_new_tokens=2)
        while sp.has_work():
            sp.step()
        sp.flush()
        fresh = sp.healthz()["snapshot_age_s"]
        time.sleep(0.05)                 # a stuck replica stops stamping
        aged = sp.healthz()["snapshot_age_s"]
        assert aged >= fresh + 0.04
        sp.step()                        # one driven round: fresh again
        assert sp.healthz()["snapshot_age_s"] < aged
        with pytest.raises(ValueError, match="replica_id"):
            ServingPredictor(model, max_batch=1, page_size=8,
                             max_seq_len=64, use_kernel=False,
                             replica_id=-1)

    def test_deadline_at_nominal_load_emits_zero_sheds(self, rng):
        """Round-17 satellite: deadlines + an armed SLO at NOMINAL load
        are free — every request finishes, zero sheds, zero deadline
        misses, zero failures (the disarmed-path half of the overload
        bench gate, deterministic here)."""
        from paddle_tpu.inference import ServingPredictor, SLOConfig

        model = _tiny_model()
        sp = ServingPredictor(
            model, max_batch=2, page_size=8, max_seq_len=64,
            use_kernel=False,
            slo=SLOConfig(max_waiting=16, max_pool_occupancy=0.95,
                          max_inflight_depth=8, ttft_p99_slo_ms=6e4))
        reqs = [sp.add_request(rng.randint(0, TINY["vocab_size"], (6,)),
                               max_new_tokens=4, deadline_s=60.0)
                for _ in range(6)]
        while sp.has_work():
            sp.step()
        sp.flush()
        assert all(r.state == "finished" for r in reqs)
        flat = sp.telemetry()
        assert flat["serving_requests_shed"] == 0
        assert flat["serving_deadline_misses"] == 0
        assert flat["serving_requests_failed"] == 0
        hz = sp.healthz()
        self._check_healthz(hz)
        assert hz["status"] == "ok" and hz["shed_reason"] is None


# ---------------------------------------------------------------------------
# PR 26: named scopes inside the two step programs
# ---------------------------------------------------------------------------

SERVE_SCOPES = ("cow", "embed", "layers", "ln", "qkv", "kv_write", "attn",
                "attn_out", "mlp", "head", "sample")
TRAIN_SCOPES = ("embed", "layers", "ln", "qkv", "attn", "attn_out", "mlp",
                "pipeline", "head_loss", "optimizer")


def _scope_components(lowered_text):
    """Every ``/``-separated component of every location path in a lowered
    module's text, wrappers such as ``transpose(jvp(ln))`` peeled off."""
    import re

    found = set()
    for path in re.findall(r'loc\("([^"]+)"', lowered_text):
        for part in path.split("/"):
            found.add(part)
            found.update(re.findall(r"[\w.]+", part))
    return found


class TestStepScopes:
    def test_step_scope_takes_only_the_closed_list(self):
        from paddle_tpu.observability import (STEP_SCOPES, STEP_SUBSCOPES,
                                              step_scope)

        assert set(SERVE_SCOPES) | set(TRAIN_SCOPES) == set(STEP_SCOPES)
        with step_scope("ln"):
            pass
        with pytest.raises(ValueError, match="STEP_SCOPES"):
            step_scope("layer_norm")
        # PR 28: parts of a part, each inside a scope of the closed list
        # (PR 34: the learned indexer's two, inside "attn"; PR 36: the
        # paged kernel's time by layer kind, inside "attn")
        assert set(STEP_SUBSCOPES) == {"moe_route", "moe_experts",
                                       "moe_shared", "attn_absorb",
                                       "attn_index", "attn_select",
                                       "attn_window", "attn_full"}
        assert STEP_SUBSCOPES["attn_window"] == "attn" \
            == STEP_SUBSCOPES["attn_full"]
        assert STEP_SUBSCOPES["attn_index"] == "attn" \
            == STEP_SUBSCOPES["attn_select"]
        assert set(STEP_SUBSCOPES.values()) <= set(STEP_SCOPES)
        assert not set(STEP_SUBSCOPES) & set(STEP_SCOPES)
        with step_scope("moe_route"):
            pass

    def test_named_scope_is_used_through_the_helper_only(self):
        import os
        import re

        from paddle_tpu.observability import STEP_SCOPES, STEP_SUBSCOPES

        root = os.path.dirname(paddle.__file__)
        raw, used = [], {}
        for folder, _, files in os.walk(root):
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(folder, name)
                with open(path) as f:
                    text = f.read()
                rel = os.path.relpath(path, root)
                if "named_scope" in text:
                    raw.append(rel)
                names = re.findall(r'step_scope\("(\w+)"\)', text)
                if names and rel != os.path.join("observability",
                                                 "tracing.py"):
                    used[rel] = set(names)
        assert raw == [os.path.join("observability", "tracing.py")]
        assert sorted(used) == [os.path.join("models", "deepseek_v2.py"),
                                os.path.join("models", "gpt.py"),
                                os.path.join("models", "gpt_spmd.py"),
                                os.path.join("models", "moe.py")]
        assert used[os.path.join("models", "gpt.py")] == \
            set(SERVE_SCOPES) | {"attn_absorb", "attn_index", "attn_select",
                                 "attn_window", "attn_full"}
        assert used[os.path.join("models", "gpt_spmd.py")] == \
            set(TRAIN_SCOPES)
        # a routed layer's parts, inside the serving step's "mlp"
        assert used[os.path.join("models", "moe.py")] == {"moe_route",
                                                          "moe_experts"}
        assert used[os.path.join("models", "deepseek_v2.py")] == \
            {"moe_shared"}
        assert set().union(*used.values()) <= \
            set(STEP_SCOPES) | set(STEP_SUBSCOPES)

    def test_unified_step_lowers_with_every_serving_scope(self, rng):
        import jax

        from paddle_tpu.inference import ServingPredictor

        sp = ServingPredictor(_tiny_model(), max_batch=2, page_size=8,
                              max_seq_len=64, use_kernel=False)
        step_fn, seen = sp._unified, []

        def tapped(*args):
            seen.append(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
            return step_fn(*args)

        tapped.trace_count = step_fn.trace_count
        sp._unified = tapped
        sp.generate([rng.randint(0, TINY["vocab_size"], (9,))],
                    max_new_tokens=2)
        found = _scope_components(
            step_fn.lower(*seen[0]).as_text(debug_info=True))
        assert set(SERVE_SCOPES) <= found, set(SERVE_SCOPES) - found
        assert not {"head_loss", "optimizer", "pipeline"} & found

    @pytest.mark.parametrize("pp", [1, 2])
    def test_train_step_lowers_with_every_training_scope(self, pp):
        import jax
        from jax.sharding import Mesh

        from paddle_tpu.models.gpt import GPTConfig
        from paddle_tpu.models.gpt_spmd import build_spmd_train_step

        if len(jax.devices()) < pp:
            pytest.skip(f"needs {pp} host devices")
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32, recompute=True)
        mesh = Mesh(np.array(jax.devices()[:pp]).reshape(1, pp, 1),
                    ("dp", "pp", "mp"))
        step, params, mom, (ids, labels) = build_spmd_train_step(
            cfg, mesh, batch_size=4, seq_len=32, num_micro=2)
        with jax.set_mesh(mesh):
            text = step.lower(params, mom, ids, labels).as_text(
                debug_info=True)
        found = _scope_components(text)
        assert set(TRAIN_SCOPES) <= found, set(TRAIN_SCOPES) - found
        # the scopes survive the backward pass and jax.checkpoint
        assert "transpose(jvp(pipeline))" in text
        assert "rematted_computation/ln" in text
        assert not {"cow", "kv_write", "sample"} & found


# ---------------------------------------------------------------------------
# train-step + collective telemetry (library-wide registry)
# ---------------------------------------------------------------------------


class TestTrainTelemetry:
    def test_spmd_train_step_counts_steps_and_wire(self):
        import jax
        from jax.sharding import Mesh

        from paddle_tpu.models.gpt import GPTConfig
        from paddle_tpu.models.gpt_spmd import build_spmd_train_step

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 host devices")
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32)
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                    ("dp", "pp", "mp"))
        step, params, mom, (ids, labels) = build_spmd_train_step(
            cfg, mesh, batch_size=4, seq_len=32)
        default_registry.reset()
        default_registry.enable()
        try:
            params, mom, _ = step(params, mom, ids, labels)
            params, mom, _ = step(params, mom, ids, labels)
        finally:
            default_registry.disable()
        flat = default_registry.snapshot_flat()
        assert flat["train_steps"] == 2
        assert flat["train_dispatch_seconds"] > 0
        assert flat["train_wire_bytes{quant=fp}"] > 0   # dp=2 sync
        # disabled again: further steps cost one flag check, count nothing
        step(params, mom, ids, labels)
        assert default_registry.snapshot_flat()["train_steps"] == 2

    def test_eager_all_reduce_wire_counter(self):
        import jax.numpy as jnp

        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.collective import _init_default_group
        from paddle_tpu.distributed.compressed_collectives import (
            bytes_on_the_wire)
        from paddle_tpu.tensor.tensor import Tensor

        g = _init_default_group()
        if g.nranks < 2:
            pytest.skip("needs >= 2 devices")
        x = Tensor(jnp.ones((g.nranks, 64), jnp.float32))
        default_registry.reset()
        default_registry.enable()
        try:
            dist.all_reduce(x, group=g)
        finally:
            default_registry.disable()
        flat = default_registry.snapshot_flat()
        want = bytes_on_the_wire(64, g.nranks, elem_bytes=4)
        assert flat["collective_wire_bytes{op=all_reduce,quant=fp}"] == want
        assert flat["collective_calls{op=all_reduce}"] == 1
