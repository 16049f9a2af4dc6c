"""Serving benchmark: the unified ragged serving step, with the round-10
quantized A/B legs (fp vs int8-weights vs int8-weights + int8-KV) and the
round-13 sync-vs-async engine A/B.

Joins the bench trajectory next to bench.py's training lines. Drives the
continuous-batching ServingPredictor through a two-wave workload (admit half the lanes, then admit the SAME prompts into
the remaining lanes while the first wave decodes — the prefix-cache +
chunked-prefill steady state) and emits ONE JSON line per leg (same
schema/contract as bench.py — the flagship quantized line LAST):

- ``value``/``unit``: decode tokens/sec/chip over the timed steady phase
- ``vs_baseline``: the leg over its baseline leg (each quantized leg and
  the mesh leg over the fp unified step; a pair over its partner)
- ``p50_ms``/``p99_ms``: per-step latency percentiles (timed phase)
- ``ttft_p50_ms``/``ttft_p99_ms``: time-to-first-token percentiles over
  the SECOND wave (warm executables — steady-state serving TTFT; chunked
  prefill interleaves with decode)
- ``prefix_hit_rate``: fraction of admitted context tokens served from
  the prefix cache
- ``decode_retraces``: unified-step traces during the timed phase
  + 1 — MUST stay 1 (compile once, replay fixed-shape)
- ``hbm_bytes_per_token``: analytic HBM bytes a steady-state decode token
  reads (weights amortized over the batch + that token's KV context,
  scale planes included) — the quantity the round-10 weight-only int8 /
  int4 and int8-KV legs shrink (2-4x), decode being bandwidth-bound
- ``mesh_chips``/``mesh_shape``/``tokens_per_s_per_chip``: the round-11
  mesh scaling leg (``unified-spmd``) runs the SAME churn workload with
  the unified step tensor-parallel over ``Mesh(("mp",))`` — the mp=1 vs
  mp=N A/B; every leg stamps its mesh so round-over-round deltas compare
  like against like (per-chip throughput is the roofline that matters:
  N chips buy aggregate bandwidth, the psums spend some of it back)
- ``accepted_tokens_per_step``/``draft_acceptance_rate``: the round-12
  speculative A/B (``unified-spec-base`` vs ``unified-spec-k4``) on a
  repetitive-prompt churn — tokens emitted per completing decode
  lane-step (1.0 = plain decode; > 1.0 = each weight-read amortized over
  accepted drafts + the bonus token) and the fraction of proposed drafts
  the verify pass accepted; the k4 leg's ``vs_baseline`` over the
  spec-off leg is the effective speculation speedup
- ``step_gap_frac``/``host_ms_per_step``/``async_emissions_match``: the
  round-13 engine A/B (``unified-step`` vs ``unified-async``) — the
  no-step-in-flight wall-clock fraction (host-observable upper bound on
  device idle between steps), host scheduling ms outside blocking waits,
  and the greedy emission bit-identity gate of the async leg against the
  sync leg. The pair is measured as ONE run with their timed windows
  INTERLEAVED (sync, async, sync, ...) and per-leg MEDIANS reported, so
  machine drift on a small CI box (GC, neighbors, cpufreq) hits both
  engines alike instead of inverting a strict single-window comparison;
  the paired sync stats ride the async line (``sync_tokens_per_s`` /
  ``sync_step_gap_frac``) and its ``vs_baseline`` self-baselines on
  them, so the strict gates never compare across workloads (the pair
  floors gen_len/batch/prompt — a 2-3 token output budget would leave
  no deferral headroom to measure).

- ``telemetry``/``obs_off_tokens_per_s``/``trace_events``: round 15 —
  every leg carries the schema-checked flat snapshot of its serving
  metrics registry (``ServingPredictor.telemetry()``: steps, syncs,
  preemptions, prefix/CoW/eviction counters, draft rollback pages, TTFT
  histogram stats), and the ``unified-obs`` interleaved pair measures
  the SAME churn with host tracing off vs on — its ``vs_baseline`` is
  the observability overhead ratio the smoke test gates near 1.0
  (the disabled path is one flag check; the traced path records
  pack_dispatch/reconcile spans + per-request lanes every step).

- ``tokens_per_s_per_replica``/``affinity_hit_rate``/``failover_count``:
  round 18 — the ``fleet-churn`` leg runs the same churn shape through a
  two-replica :class:`FleetRouter` with replica churn injected (one
  deterministic kill + seeded ``replica_stall`` faults): aggregate
  fleet tokens/s stays live through replica loss, placements split
  between the prefix-affinity map and power-of-two-choices, and the
  bounded per-replica SLO sheds the flood (``shed_rate``).

- ``transfer_bytes_per_token``/``prefill_fallback_count``/...: round 20
  — the ``fleet-disagg`` leg runs a MIXED churn (short decode-bound
  prompts + fresh multi-page longs) through a colocated 3-replica fleet
  vs a 1-prefill + 2-decode disaggregated fleet, windows interleaved:
  finished KV pages stream prefill -> decode over the checksummed
  ``kv_transfer`` wire (int8 payloads + scale planes ~4x below the fp
  partner's figure, per TRANSFERRED token), long-prompt TTFT p99 rides
  the line against the colocated partner's, and a certainty-armed
  ``transfer_drop`` chaos pass shows graceful colocated fallback
  (``fault_free_fallback_count`` exactly 0; ``prefill_fallback_count``
  > 0 after the pass) — degradation, never an outage.

``--smoke``: tiny CPU config — always runnable (CI leg, rc 0; gather
reference attention keeps it fast, kernel parity is the test suite's
job); its lines name the CPU. Without ``--smoke`` the script needs a TPU
and exits non-zero naming the platform it found. A leg that raises prints
its traceback and a structured ``error`` line, the remaining legs still
run, and the exit code is non-zero.
"""
from __future__ import annotations

import json
import time

import numpy as np

FLAGSHIP_METRIC = "paged-decode serving tokens/sec/chip"


def _error_line(msg, metric=FLAGSHIP_METRIC):
    # full driver contract even on errors (value 0 + unit): a keys-missing
    # error line would silently drop out of round-over-round deltas — the
    # exact failure mode the round-8 bench schema lint exists to stop
    return json.dumps({"metric": metric, "value": 0, "unit": "tokens/s",
                       "vs_baseline": 0.0, "error": msg[:300]})


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def _hbm_bytes_per_token(sp, batch, avg_ctx):
    """Analytic steady-state HBM read bytes PER CHIP per decode token:
    every weight byte once per step (amortized over the batch's lanes) +
    the token's own KV context (int8 pools count 1 byte/elt + their fp32
    scale planes) + the INTER-KERNEL ACTIVATION round-trips (round 16).
    Under an mp mesh the layer stacks and the KV pages are
    head/column-sharded — each chip reads 1/mp of them — while the
    embeddings/LM head/LN leaves are replicated and read whole: exactly
    the per-chip bandwidth the round-11 tensor-parallel leg buys down.

    Activation accounting: the layer chain writes-then-reads every
    intermediate between its kernels — LN1 out (h) -> qkv (3h) -> attention out (h) -> output-GEMM
    out (h) -> residual (h) -> LN2 out (h) -> MLP hidden and gelu out
    (4h each) -> MLP out (h): 17h elements per token per layer crossing
    HBM twice. Under mp only the head/column-sharded intermediates (qkv
    3h, attention out h, MLP hidden + gelu out 8h = 12h) shrink per chip;
    the LN outs, the residual, and the post-psum wo/MLP outputs (5h) are
    full-width on every chip. Kernel-internal scratch blocks are written
    once and never re-read — not counted.

    Round 23: the formula (and the per-layer activation constants the
    paragraphs above derive) moved to ``paddle_tpu.analysis.cost_model``
    so this bench and the tpulint JX007 gate evaluate ONE model; this
    wrapper just builds the geometry from the live predictor.
    ``report()`` emits the jaxpr-derived counterpart next to it
    (``hbm_bytes_per_token_static``) and ``python -m paddle_tpu.analysis``
    exits 2 when the two diverge past the contracted tolerance."""
    from paddle_tpu.analysis.cost_model import (analytic_hbm_bytes_per_token,
                                                geometry)

    mp = 1 if sp.mesh is None else int(sp.mesh.shape["mp"])
    cfg = sp.config
    return analytic_hbm_bytes_per_token(geometry(
        sp.params, sp.cache, batch=batch, avg_ctx=avg_ctx, mp=mp,
        moe_experts=getattr(cfg, "moe_experts", 0),
        moe_top_k=getattr(cfg, "moe_top_k", 0)))


class _ChurnLeg:
    """One continuous-arrival churn over one predictor: ``batch``
    concurrent requests drawn round-robin from a small prompt pool
    (production repeated-system-prompt traffic — prefix hits for the
    unified legs); every finished request is immediately replaced, so a
    timed window mixes admissions, chunked prefill and decode the way a
    serving fleet does. ``window(steps)`` times one measurement window
    (flush INSIDE the timing, so deferred async emissions count);
    ``report()`` aggregates per-window MEDIANS into the JSON-line dict.
    """

    def __init__(self, *, hidden, layers, heads, vocab, batch, prompt,
                 gen_len, page_size, chunk, use_kernel, on_tpu,
                 dtype=None, weight_dtype=None, kv_cache_dtype=None,
                 mesh_chips=1, spec_decode_k=0, spec_workload=False,
                 async_engine=False, observability=False,
                 slo=None, draft_source=None,
                 draft_layers=None, spec_report=False,
                 moe_experts=0, moe_top_k=2, moe_capacity_factor=1.25):
        # async_engine stays EXPLICIT here (default False = the sync
        # baseline leg) even though round 14 flipped the predictor's own
        # default to async: the quant/spec/spmd legs are the
        # like-for-like round-over-round baselines, and the round-13
        # interleaved sync-vs-async pair is the one engine A/B
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu.inference import ServingPredictor
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        if spec_workload or spec_report:
            gen_len = max(gen_len, 12)
        self.batch, self.prompt, self.gen_len = batch, prompt, gen_len
        self.mesh_chips = mesh_chips
        self.spec_workload = spec_workload
        # round 19: spec_report adds the speculation metrics to the line
        # WITHOUT the repetitive-motif workload — the model-draft leg's
        # whole point is acceptance on non-repetitive (random) prompts
        self.spec_report = bool(spec_report or spec_workload)
        self.draft_source = draft_source
        max_len = prompt + gen_len + 32
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=max_len, weight_dtype=weight_dtype,
                        kv_cache_dtype=kv_cache_dtype,
                        moe_experts=moe_experts, moe_top_k=moe_top_k,
                        moe_capacity_factor=moe_capacity_factor)
        model = GPTForCausalLM(cfg)
        model.eval()
        # kept for the round-25 MoE leg's eager router probe (the
        # serving predictor only holds the extracted param tree)
        self.model = model
        mesh = None
        if mesh_chips > 1:
            from paddle_tpu.distributed.mesh import make_serving_mesh

            mesh = make_serving_mesh(mesh_chips)
        self.sp = ServingPredictor(
            model, max_batch=batch, page_size=page_size,
            max_seq_len=max_len, use_kernel=use_kernel, chunk=chunk,
            dtype=jnp.bfloat16 if (on_tpu and dtype is None) else dtype,
            mesh=mesh, spec_decode_k=spec_decode_k,
            async_engine=async_engine, slo=slo,
            draft_source=draft_source, draft_layers=draft_layers)
        rng = np.random.RandomState(0)
        if spec_workload:
            # tiled 4-token motifs: every prompt internally repetitive
            self.pool = [np.tile(rng.randint(0, vocab, (4,)),
                                 (prompt + 3) // 4)[:prompt]
                         for _ in range(max(2, batch // 2))]
        else:
            self.pool = [rng.randint(0, vocab, (prompt,))
                         for _ in range(max(2, batch // 2))]
        self.arrivals = 0
        self.reqs = []
        self.lat = []
        self.win_vals, self.win_gaps, self.win_host = [], [], []
        self.win_dev = []
        self.win_draft = []
        self.first_wave = None
        self.timed_from = 0
        self.decode_before = 0
        self.emitted_before = 0
        # round 15: observability=True runs the timed windows with host
        # tracing ENABLED (pack_dispatch/reconcile spans + per-request
        # lanes recorded into the profiler buffer) — the traced half of
        # the overhead A/B; the metrics registry is per-predictor and
        # always on (its counters ARE these bench metrics)
        self.observability = bool(observability)
        self.trace_events = 0

    def top_up(self):
        # keep the lanes full: every finished request is replaced by a
        # fresh one on the NEXT pool prompt (round-robin -> prefix reuse);
        # terminal means FINISHED or (round 17) FAILED
        live = sum(1 for r in self.reqs
                   if r.state not in ("finished", "failed"))
        while live < self.batch:
            self.reqs.append(self.sp.add_request(
                self.pool[self.arrivals % len(self.pool)],
                max_new_tokens=self.gen_len))
            self.arrivals += 1
            live += 1

    def warm(self):
        """Fill the lanes and run until every first-wave request has
        produced (compiles the unified step), then drain any async
        deferrals."""
        self.top_up()
        self.first_wave = list(self.reqs)
        while any(not r.output_ids for r in self.first_wave):
            self.sp.step()
        self.sp.flush()
        self.decode_before = self.sp.decode_trace_count
        self.timed_from = len(self.reqs)
        self.emitted_before = self.sp.tokens_emitted

    def window(self, steps):
        """One timed measurement window. The sync engine pays one host
        sync per step; the async engine dispatches ahead and reconciles
        behind-by-one / at the closing flush. ``observability=True``
        windows run with the recorder open (spans + request lanes land in
        the profiler buffer, drained per window so memory stays flat)."""
        from paddle_tpu.profiler.record import recorder

        sp = self.sp
        sp.reset_perf_stats()
        w_emitted = sp.tokens_emitted
        w_steps = sp.steps
        if self.observability:
            recorder.enabled = True
        try:
            tw = time.perf_counter()
            for _ in range(steps):
                self.top_up()
                t1 = time.perf_counter()
                sp.step()
                self.lat.append((time.perf_counter() - t1) * 1e3)
            sp.flush()
            dw = time.perf_counter() - tw
        finally:
            if self.observability:
                recorder.enabled = False
                self.trace_events += (len(recorder.events)
                                      + len(recorder.aux))
                recorder.clear()
        self.win_vals.append((sp.tokens_emitted - w_emitted) / dw)
        self.win_gaps.append(sp.step_gap_frac)
        self.win_host.append(sp.host_ms_per_step)
        self.win_draft.append(sp.draft_overhead_frac)
        # wall ms per dispatched step with work IN FLIGHT — the
        # host-observable per-step device-time proxy (the gap fraction
        # subtracts the host-only bubbles, so this never credits
        # scheduler stalls to the device)
        self.win_dev.append(dw * (1.0 - sp.step_gap_frac) * 1e3
                            / max(1, sp.steps - w_steps))

    def report(self):
        """The emitted-metrics dict (medians over the measured windows —
        robust to one GC pause / CI-neighbor burst per window)."""
        sp = self.sp
        produced_total = sp.tokens_emitted - self.emitted_before
        # explicit raise (not assert): python -O must not let a dead
        # scheduler emit a zero-looking-valid line
        if not produced_total:
            raise RuntimeError("no tokens produced over the timed phase")
        # TTFT over requests ADMITTED during the timed churn (warm
        # executables, steady state); falls back to the warmup wave when
        # the window was too short for any churn admission to produce
        ttfts = [r.ttft * 1e3 for r in self.reqs[self.timed_from:]
                 if r.ttft is not None]
        if not ttfts:
            ttfts = [r.ttft * 1e3 for r in self.first_wave]
        value = round(float(np.median(self.win_vals)), 1)
        out = dict(
            value=value,
            unit="tokens/s",
            p50_ms=round(_percentile(self.lat, 50), 2),
            p99_ms=round(_percentile(self.lat, 99), 2),
            ttft_p50_ms=round(_percentile(ttfts, 50), 2),
            ttft_p99_ms=round(_percentile(ttfts, 99), 2),
            prefix_hit_rate=round(sp.prefix_hit_rate, 3),
            decode_retraces=sp.decode_trace_count - self.decode_before + 1,
            hbm_bytes_per_token=_hbm_bytes_per_token(
                sp, self.batch, self.prompt + self.gen_len // 2),
            mesh_chips=self.mesh_chips,
            mesh_shape=f"mp{self.mesh_chips}",
            tokens_per_s_per_chip=round(value / self.mesh_chips, 1),
            # round 13: the host-bubble metrics the async engine buys down
            step_gap_frac=round(float(np.median(self.win_gaps)), 4),
            host_ms_per_step=round(float(np.median(self.win_host)), 3),
            # round 16: per-step wall time with work in flight
            device_ms_per_step=round(float(np.median(self.win_dev)), 3),
            # round 15: the schema-checked telemetry snapshot — the
            # serving-stack registry (predictor + KV cache) flat export,
            # so a per-RUN regression in e.g. prefix hits, preemptions or
            # draft rollback pages is visible in the line itself
            telemetry=sp.telemetry(),
        )
        # round 23: the jaxpr-derived static HBM model next to the
        # analytic one, plus their relative drift — the same pair the
        # tpulint JX007 contracts gate. On a derivation failure the keys
        # are simply absent, and the smoke tests assert their presence so
        # a silent failure still fails CI
        try:
            from paddle_tpu.analysis.cost_model import \
                static_hbm_for_predictor
            static = static_hbm_for_predictor(
                sp, self.batch, self.prompt + self.gen_len // 2)
        except Exception:
            static = None
        if static is not None:
            analytic = out["hbm_bytes_per_token"]
            out["hbm_bytes_per_token_static"] = int(static)
            out["hbm_model_drift_frac"] = round(
                (static - analytic) / analytic, 4)
        if self.observability:
            # traced leg: how many host events the windows recorded
            # (spans + request-lane phases — 0 would mean the tracing
            # leg silently measured nothing)
            out["trace_events"] = self.trace_events
        # per-arrival-index greedy emission streams + finished flag (NOT
        # part of the JSON line): main() compares the async leg's streams
        # against the sync leg's for the bit-identity gate — FULL
        # equality for requests finished in both legs, prefix equality
        # for in-progress tails
        out["_streams"] = {i: (r.state == "finished", list(r.output_ids))
                           for i, r in enumerate(self.reqs)}
        if self.spec_report:
            # the round-12 speculation A/B metrics: the spec-off leg
            # anchors accepted_tokens_per_step at exactly 1.0
            out["accepted_tokens_per_step"] = round(
                sp.accepted_tokens_per_step, 3)
            out["draft_acceptance_rate"] = round(
                sp.draft_acceptance_rate, 3)
        if self.draft_source == "model":
            # round 19: what the truncated-layer draft pass costs against
            # the accepted tokens it buys (fraction of step() wall time,
            # median over the timed windows)
            out["draft_overhead_frac"] = round(
                float(np.median(self.win_draft)), 4)
        return out


class _OverloadLeg(_ChurnLeg):
    """The round-17 overload churn: arrivals deliberately exceed capacity
    (``overload``x the lane count stays live, so the bounded waiting
    queue overflows every round and the armed SLO sheds), and every
    ``deadline_every``-th arrival carries an already-expired deadline
    (``deadline_s=0.0`` — the queue-TTL sweep fails it deterministically
    at the next scheduler round; the rest get a generous deadline that
    never fires). The predictor keeps serving the admitted lanes
    throughout — ``value`` stays a real tokens/s — while the leg reports
    the shed / deadline-miss / terminal-failure accounting the fleet
    router consumes. ``overload=1`` with no expired deadlines is the
    nominal-load partner whose rates the gate holds at exactly zero."""

    def __init__(self, *, overload=3, deadline_every=0, **kw):
        from paddle_tpu.inference import SLOConfig

        super().__init__(slo=SLOConfig(max_waiting=kw["batch"] + 2), **kw)
        self.target_live = self.batch * overload
        self.deadline_every = deadline_every

    def _add_one(self):
        n = self.arrivals
        deadline = (0.0 if self.deadline_every
                    and n % self.deadline_every == 0 else 60.0)
        self.reqs.append(self.sp.add_request(
            self.pool[n % len(self.pool)], max_new_tokens=self.gen_len,
            deadline_s=deadline))
        self.arrivals += 1
        return self.reqs[-1]

    def top_up(self):
        # flood: submit until target_live requests are non-terminal, but
        # at most target_live attempts per round — a shed admission comes
        # back terminal instantly and must not trigger an unbounded
        # resubmit storm within one scheduler round
        live = sum(1 for r in self.reqs
                   if r.state not in ("finished", "failed"))
        for _ in range(self.target_live):
            if live >= self.target_live:
                break
            if self._add_one().state != "failed":
                live += 1

    def warm(self):
        # the base warm-up waits for every first-wave request to produce
        # — under overload some of the first wave is shed or TTL-expired
        # and never will: wait for produced-or-terminal instead
        self.top_up()
        self.first_wave = list(self.reqs)
        while any(r.state not in ("finished", "failed")
                  and not r.output_ids for r in self.first_wave):
            self.sp.step()
        self.sp.flush()
        self.decode_before = self.sp.decode_trace_count
        self.timed_from = len(self.reqs)
        self.emitted_before = self.sp.tokens_emitted

    def report(self):
        out = super().report()
        flat = self.sp.telemetry()
        arrivals = max(1, self.arrivals)
        out["shed_rate"] = round(flat["serving_requests_shed"] / arrivals, 4)
        out["deadline_miss_rate"] = round(
            flat["serving_deadline_misses"] / arrivals, 4)
        out["failed_requests"] = int(flat["serving_requests_failed"])
        return out


class _FleetLeg:
    """The round-18 fleet-churn leg: N ``ServingPredictor`` replicas
    behind a :class:`FleetRouter` on the shared round-robin prompt-pool
    churn — repeated prompts exercise the prefix-affinity map (a
    submission lands where its chain-keyed pages already live), the
    flood past fleet capacity exercises the health-gated SLO shedding,
    and the injected replica churn (one deterministic kill between
    windows + the seeded ``replica_stall`` seam) exercises failover as a
    ROUTING EVENT: the leg's tokens/s stays live through replica loss.
    ``value`` is fleet-aggregate tokens/s (median over windows, flush
    inside the timing); the checked line carries
    ``tokens_per_s_per_replica`` / ``affinity_hit_rate`` /
    ``failover_count`` / ``shed_rate`` and the fleet registry snapshot.
    """

    def __init__(self, *, hidden, layers, heads, vocab, batch, prompt,
                 gen_len, page_size, chunk, use_kernel, on_tpu,
                 num_replicas=2, overload=3, prefill_replicas=0,
                 kv_cache_dtype=None, mixed=False, transfer=None,
                 host_tier_bytes=0, prefix_pulls=False,
                 tiered_churn=False):
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu.inference import FleetRouter, SLOConfig
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        self.batch, self.gen_len = batch, gen_len
        self.num_replicas = num_replicas
        self.vocab = vocab
        # round 20: mixed churn — mostly short decode-bound prompts with
        # every 4th arrival a FRESH long (multi-page, partial-tail)
        # prompt: the prefill-interference workload disaggregation
        # exists for. Fresh longs keep real prefill work recurring (a
        # repeated long would serve from the prefix cache on both
        # sides); the dedicated long-prompt RNG makes the interleaved
        # colocated/disaggregated legs draw IDENTICAL arrival sequences.
        self.mixed = bool(mixed)
        self.long_len = 2 * prompt + max(1, page_size // 2)
        self._long_rng = np.random.RandomState(7)
        # live long prompts are capped at one replica's lane count so
        # the dedicated prefill replica always has headroom — the
        # fault-free zero-fallback gate must measure the wire, not a
        # saturated prefill queue (the colocated partner runs the same
        # cap: same long pressure on both legs)
        self._long_reqs = []
        max_len = ((self.long_len if (mixed or tiered_churn) else prompt)
                   + gen_len + 32)
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=max_len,
                        kv_cache_dtype=kv_cache_dtype)
        model = GPTForCausalLM(cfg)
        model.eval()
        self.router = FleetRouter(
            model, num_replicas=num_replicas, seed=0,
            prefill_replicas=prefill_replicas, transfer=transfer,
            prefix_pulls=prefix_pulls,
            replica_kw=dict(
                max_batch=batch, page_size=page_size, max_seq_len=max_len,
                use_kernel=use_kernel, chunk=chunk,
                dtype=jnp.bfloat16 if on_tpu else None,
                # round 21: 0 keeps the pre-tier drop-on-evict behavior
                host_tier_bytes=host_tier_bytes,
                # the bounded queue makes the flood shed deterministically
                slo=SLOConfig(max_waiting=batch + 2)))
        rng = np.random.RandomState(0)
        if tiered_churn:
            # round 21: a REUSED working set of distinct multi-page
            # prompts that deliberately OVERFLOWS the HBM pool's
            # zero-ref headroom — by the time a prompt comes back
            # around the cycle, its prefix pages have been LRU-evicted.
            # Without a host tier that eviction is a drop (the repeat
            # recomputes); with one it is a spill (the repeat restores)
            # — exactly the gap the tiered A/B measures.
            self.pool = [rng.randint(0, vocab, (self.long_len,))
                         for _ in range(3 * num_replicas * batch)]
        else:
            self.pool = [rng.randint(0, vocab, (max(2, prompt // 2)
                                                if mixed else prompt,))
                         for _ in range(max(2, batch // 2))]
        self.arrivals = 0
        self.reqs = []
        self.target_live = num_replicas * batch * overload
        self.win_vals = []
        self.timed_from = 0

    def _tokens_total(self):
        return sum(v for k, v in self.router.telemetry().items()
                   if k.startswith("fleet_tokens_emitted"))

    def top_up(self):
        # flood: bounded attempts per round — a shed submission comes
        # back terminal instantly and must not resubmit unboundedly
        live = sum(1 for r in self.reqs
                   if r.state not in ("finished", "failed"))
        live_longs = sum(1 for r in self._long_reqs
                         if r.state not in ("finished", "failed"))
        for _ in range(self.target_live):
            if live >= self.target_live:
                break
            take_long = (self.mixed and self.arrivals % 4 == 3
                         and live_longs < self.batch)
            p = (self._long_rng.randint(0, self.vocab, (self.long_len,))
                 if take_long
                 else self.pool[self.arrivals % len(self.pool)])
            r = self.router.submit(p, max_new_tokens=self.gen_len)
            self.reqs.append(r)
            self.arrivals += 1
            if take_long:
                self._long_reqs.append(r)
                live_longs += 1
            if r.state != "failed":
                live += 1

    def warm(self):
        self.top_up()
        first = list(self.reqs)
        ticks = 0
        while any(r.state not in ("finished", "failed")
                  and not r.output_ids for r in first):
            self.top_up()
            self.router.tick()
            ticks += 1
            if ticks > 10000:
                raise RuntimeError("fleet warmup stuck")
        self.router.flush()
        self.timed_from = len(self.reqs)

    def window(self, steps, record=True):
        t0 = time.perf_counter()
        w_tokens = self._tokens_total()
        for _ in range(steps):
            self.top_up()
            self.router.tick()
        self.router.flush()
        dw = time.perf_counter() - t0
        if record:
            self.win_vals.append((self._tokens_total() - w_tokens) / dw)

    def ttft_ms(self, longs_only=False, upto=None):
        """Fleet-side TTFTs (ms) of the timed-phase submissions (falls
        back to the whole run when a short window admitted none).
        ``longs_only`` restricts to the long-prompt arrivals — the
        prefill-INTERFERED class whose tail the disagg leg compares
        (short decode-bound prompts see the same decode queues either
        way; the long prompts are where colocated prefill competes with
        decode for the budget and the lanes). ``upto`` is an arrival-
        index cutoff: the disagg leg passes its pre-chaos request count
        so chaos-degraded arrivals never pollute the fault-free TTFT
        comparison."""
        def pick(rs):
            if longs_only:
                ids = {id(r) for r in self._long_reqs}
                rs = [r for r in rs if id(r) in ids]
            return [r.ttft * 1e3 for r in rs if r.ttft is not None]

        return (pick(self.reqs[self.timed_from:upto])
                or pick(self.reqs[:upto]))

    def report(self):
        flat = self.router.telemetry()
        value = round(float(np.median(self.win_vals)), 1)
        if not value:
            raise RuntimeError("no tokens produced over the fleet churn")
        arrivals = max(1, self.arrivals)
        return dict(
            value=value, unit="tokens/s",
            tokens_per_s_per_replica=round(value / self.num_replicas, 1),
            affinity_hit_rate=round(self.router.affinity_hit_rate, 3),
            failover_count=int(flat["fleet_failovers"]),
            shed_rate=round(flat["fleet_requests_shed"] / arrivals, 4),
            failed_requests=int(flat["fleet_requests_failed"]),
            telemetry=flat,
        )


def bench_serving_fleet(*, steps, windows, **leg_kw):
    """The round-18 fleet churn with replica churn injected mid-run: the
    seeded ``replica_stall`` seam armed across every timed window, plus
    ONE deterministic ``kill_replica`` between the first two windows —
    the failover gate (``failover_count >= 1``) never rides on a
    probabilistic draw. Faults disarm (plan scope) before report()."""
    from paddle_tpu.inference import FaultPlan

    leg = _FleetLeg(**leg_kw)
    leg.warm()
    with _gc_frozen():
        with FaultPlan(seed=5, replica_stall=0.05, stall_ticks=2):
            for w in range(windows):
                leg.window(steps)
                if w == 0:
                    leg.router.kill_replica(0, reason="bench_churn")
    return leg.report()


def bench_serving_disagg(*, steps, windows, **leg_kw):
    """The round-20 disaggregated prefill/decode leg: the SAME
    mixed-churn workload (short decode-bound prompts + fresh multi-page
    longs every 4th arrival) through a colocated 3-replica fleet vs a
    1-prefill + 2-decode disaggregated fleet, windows interleaved so
    machine drift hits both alike — the TTFT-tail workload
    disaggregation exists for. Both fleets serve int8-KV (the EQuARX-
    style wire thrift: page payloads 4x cheaper than fp); a short fp
    partner run supplies the fp wire figure for the ratio. After the
    fault-free windows (``fault_free_fallback_count`` must be exactly
    0), a chaos pass arms ``transfer_drop`` at certainty — every
    transfer exhausts its retries and every affected request DEGRADES
    to colocated prefill (``prefill_fallback_count > 0``) while the
    fleet keeps serving: graceful degradation on display, not an
    outage. Returns ``(colo_out, disagg_out)`` — the partner keys ride
    the disagg dict."""
    from paddle_tpu.inference import FaultPlan, TransferConfig

    # tight wire knobs: a failed frame must resolve within the smoke
    # window (retries are the chaos pass's business, not the gate's)
    tcfg = TransferConfig(window=4, max_retries=1, timeout_ticks=1)
    # overload=2 floods the DECODE side (colocated long prompts queue
    # behind it — the interference the leg measures) while the live
    # long-prompt cap in _FleetLeg.top_up keeps the dedicated prefill
    # replica inside its admission bounds, so the fault-free window's
    # zero-fallback gate never trips on a capacity race (the full-flood
    # shed exercise is the fleet-churn leg's job)
    common = dict(num_replicas=3, overload=2, mixed=True,
                  kv_cache_dtype="int8", **leg_kw)
    colo = _FleetLeg(prefill_replicas=0, **common)
    disagg = _FleetLeg(prefill_replicas=1, transfer=tcfg, **common)
    fp = _FleetLeg(prefill_replicas=1, transfer=tcfg,
                   **dict(common, kv_cache_dtype=None))
    colo.warm()
    disagg.warm()
    fp.warm()
    with _gc_frozen():
        for _ in range(windows):
            colo.window(steps)
            disagg.window(steps)
        fp.window(steps)
        ff = disagg.router.telemetry()
        # pre-chaos arrival cutoff: the TTFT population must be
        # fault-free (same reason the wire bytes snapshot above it is)
        ff_reqs = len(disagg.reqs)
        # the chaos pass: certainty-armed frame loss — bounded repeats
        # until a transfer actually opened and degraded (a tiny window
        # may admit no long prompt); NOT recorded into the medians
        with FaultPlan(seed=11, transfer_drop=1.0):
            for _ in range(6):
                disagg.window(steps, record=False)
                flat = disagg.router.telemetry()
                if (flat["fleet_prefill_fallbacks"]
                        > ff["fleet_prefill_fallbacks"]):
                    break
    colo_out = colo.report()
    out = disagg.report()
    flat = disagg.router.telemetry()   # post-chaos totals
    # the TTFT pair compares the INTERFERED class: long-prompt p99 —
    # colocated longs share their replica's budget and queue with the
    # decode flood; disaggregated longs prefill on the dedicated
    # replica (short prompts see the same decode queues either way)
    out["ttft_p50_ms"] = round(
        _percentile(disagg.ttft_ms(longs_only=True, upto=ff_reqs), 50), 2)
    out["ttft_p99_ms"] = round(
        _percentile(disagg.ttft_ms(longs_only=True, upto=ff_reqs), 99), 2)
    out["colocated_tokens_per_s"] = colo_out["value"]
    out["colocated_ttft_p99_ms"] = round(
        _percentile(colo.ttft_ms(longs_only=True), 99), 2)
    out["vs_baseline"] = (round(out["value"] / colo_out["value"], 3)
                          if colo_out["value"] else 0.0)
    # wire thrift: bytes per TRANSFERRED KV token (frames + headers
    # over the tokens their acked frames landed) — invariant to run
    # length and scheduling, so the fp/int8 ratio is the per-token
    # frame cost itself (~4x at head_dim 64; 3.1x at the smoke's
    # head_dim 16, the fp32 scale planes being the difference).
    # Snapshotted pre-chaos: retransmitted bytes must not skew it.
    out["transfer_bytes_per_token"] = round(
        ff["fleet_kv_transfer_bytes"]
        / max(1.0, ff["fleet_kv_transfer_tokens"]), 1)
    fp_flat = fp.router.telemetry()
    out["fp_transfer_bytes_per_token"] = round(
        fp_flat["fleet_kv_transfer_bytes"]
        / max(1.0, fp_flat["fleet_kv_transfer_tokens"]), 1)
    out["kv_transfer_retries"] = int(flat["fleet_kv_transfer_retries"])
    out["prefill_fallback_count"] = int(flat["fleet_prefill_fallbacks"])
    out["fault_free_fallback_count"] = int(ff["fleet_prefill_fallbacks"])
    out["telemetry"] = flat
    return colo_out, out


def _fleet_kv_flat(leg) -> dict:
    """Fleet-aggregate KV-cache telemetry: the per-replica serving
    registries summed over live replicas (the tier counters and the
    prefix hit/query token counters live there, not on the fleet
    registry)."""
    out = {}
    for rep in leg.router.replicas:
        if rep.sp is None:
            continue
        for k, v in rep.sp.telemetry().items():
            if k.startswith("kv_"):
                out[k] = out.get(k, 0.0) + v
    return out


def bench_serving_tiered(*, steps, windows, **leg_kw):
    """The round-21 tiered-KV leg: the SAME reused-prompt churn — a
    working set of distinct multi-page prompts that deliberately
    OVERFLOWS the HBM pool's zero-ref headroom — through a fleet with
    the host-DRAM spill tier + cross-replica pulls armed vs a no-tier
    partner, windows interleaved so machine drift hits both alike. On
    the no-tier fleet a prompt's second coming recomputes its prefix
    (the pages were dropped at eviction); on the tiered fleet it
    restores from the host tier (or pulls from the owning replica), so
    the strict gates are ``prefix_hit_rate`` strictly HIGHER and TTFT
    p99 strictly LOWER than the partner on the same arrival sequence.

    After the fault-free windows, a drain on the busiest-affinity
    replica forces the pulls deterministically (its repeats must route
    elsewhere and pull over the wire — ``cross_replica_pulls >= 1``
    never rides on a probabilistic race), then a chaos pass arms the
    round-21 seams (``host_spill_drop`` + ``tier_restore_corrupt``):
    lost spills and corrupted payloads are DETECTED and degrade to
    recompute — counted, never failed, never scattered into the pool.
    Returns ``(notier_out, tiered_out)``; the partner keys ride the
    tiered dict."""
    from paddle_tpu.inference import FaultPlan, TransferConfig

    tcfg = TransferConfig(window=4, max_retries=1, timeout_ticks=1)
    common = dict(num_replicas=2, overload=2, tiered_churn=True, **leg_kw)
    tier = _FleetLeg(host_tier_bytes=64 << 20, prefix_pulls=True,
                     transfer=tcfg, **common)
    base = _FleetLeg(**common)
    tier.warm()
    base.warm()
    with _gc_frozen():
        # one unrecorded window each: the first eviction cycle is where
        # the tier's spills first READ their payloads and the restore
        # scatter compiles its pad widths — the timed windows compare
        # warm executables on both sides, like every other A/B here
        tier.window(steps, record=False)
        base.window(steps, record=False)
        # the TTFT population starts at the timed phase too
        tier.timed_from = len(tier.reqs)
        base.timed_from = len(base.reqs)
        for _ in range(windows):
            tier.window(steps)
            base.window(steps)
        # fault-free snapshots: the gated tier counters and the TTFT
        # populations must exclude the drain exercise and the chaos
        # pass. TTFT lists are captured NOW, not at report time — a
        # request still pending here would otherwise collect its first
        # token during the drain/chaos windows and bill their wall
        # clock to the fault-free tail (the no-tier partner never ticks
        # again, so its pending requests would silently drop instead:
        # an asymmetric population, not a comparison)
        ff_kv = _fleet_kv_flat(tier)
        tier_ttfts = list(tier.ttft_ms())
        base_ttfts = list(base.ttft_ms())
        # deterministic cross-replica pull: drain the replica owning
        # the deepest share of the affinity map — its repeats must
        # route to the other replica, which misses locally and PULLS
        # the prefix over the transfer wire (a DRAINING replica is a
        # valid pull source) instead of recomputing
        aff = list(tier.router._affinity.values())
        owner = max(set(aff), key=aff.count) if aff else 0
        tier.router.drain(owner)
        for _ in range(6):
            tier.window(steps, record=False)
            if tier.router.telemetry()[
                    "fleet_prefix_pulls_completed"] >= 1:
                break
        tier.router.resume(owner)
        # the chaos pass: lost spills + corrupted host payloads —
        # bounded repeats until both seams demonstrably fired AND the
        # corruption was detected (dropped + counted, degraded to a
        # recompute miss); NOT recorded into the medians
        with FaultPlan(seed=13, host_spill_drop=0.75,
                       tier_restore_corrupt=1.0):
            for _ in range(6):
                tier.window(steps, record=False)
                chaos_kv = _fleet_kv_flat(tier)
                if (chaos_kv["kv_tier_spill_drops"]
                        > ff_kv["kv_tier_spill_drops"]
                        and chaos_kv["kv_tier_restore_corrupt"]
                        > ff_kv["kv_tier_restore_corrupt"]):
                    break
    base_out = base.report()
    out = tier.report()
    post_kv = _fleet_kv_flat(tier)
    flat = tier.router.telemetry()   # post-pull/post-chaos fleet totals
    # both hit-rate figures are fault-free-window snapshots on the SAME
    # arrival sequence — the strictly-higher gate compares like for like
    out["prefix_hit_rate"] = round(
        ff_kv["kv_prefix_hit_tokens"]
        / max(1.0, ff_kv["kv_prefix_query_tokens"]), 4)
    base_kv = _fleet_kv_flat(base)
    out["notier_prefix_hit_rate"] = round(
        base_kv["kv_prefix_hit_tokens"]
        / max(1.0, base_kv["kv_prefix_query_tokens"]), 4)
    out["tier_hit_rate"] = round(
        ff_kv["kv_tier_hits"] / max(1.0, ff_kv["kv_tier_lookups"]), 4)
    out["spill_bytes"] = int(ff_kv["kv_tier_spill_bytes"])
    out["restore_bytes"] = int(ff_kv["kv_tier_restore_bytes"])
    out["cross_replica_pulls"] = int(flat["fleet_prefix_pulls_completed"])
    out["pull_fallback_count"] = int(flat["fleet_prefix_pull_fallbacks"])
    # chaos accounting: fired-and-detected, on top of the fault-free
    # figures (which must be exactly 0 — no corruption without the seam)
    out["tier_spill_drops"] = int(post_kv["kv_tier_spill_drops"])
    out["tier_corrupt_detected"] = int(post_kv["kv_tier_restore_corrupt"])
    out["fault_free_corrupt_detected"] = int(
        ff_kv["kv_tier_restore_corrupt"])
    out["ttft_p50_ms"] = round(_percentile(tier_ttfts, 50), 2)
    out["ttft_p99_ms"] = round(_percentile(tier_ttfts, 99), 2)
    out["notier_tokens_per_s"] = base_out["value"]
    out["notier_ttft_p99_ms"] = round(_percentile(base_ttfts, 99), 2)
    out["vs_baseline"] = (round(out["value"] / base_out["value"], 3)
                          if base_out["value"] else 0.0)
    out["telemetry"] = flat
    return base_out, out


def bench_serving_overload(*, steps, windows, **leg_kw):
    """The round-17 resilience pair: the SAME churn shape at overload
    (3x arrivals, bounded queue, expired-deadline stragglers — the SLO
    sheds every round) vs nominal load (the armed-but-quiet partner),
    windows interleaved like the engine A/B. Returns
    ``(overload_out, nominal_out)``; the emitted overload line carries
    the nominal partner's rates — the schema-gated contract is
    ``shed_rate > 0`` under overload and ``== 0`` at nominal load."""
    over_leg = _OverloadLeg(overload=3, deadline_every=3,
                            async_engine=True, **leg_kw)
    nom_leg = _OverloadLeg(overload=1, deadline_every=0,
                           async_engine=True, **leg_kw)
    over_leg.warm()
    nom_leg.warm()
    with _gc_frozen():
        for _ in range(windows):
            over_leg.window(steps)
            nom_leg.window(steps)
    return over_leg.report(), nom_leg.report()


class _gc_frozen:
    """Collect once, then hold GC off across the timed windows: a cyclic
    collection landing inside one leg's window is the single biggest
    single-window distortion on a small CI box."""

    def __enter__(self):
        import gc

        gc.collect()
        self._was = gc.isenabled()
        gc.disable()

    def __exit__(self, *exc):
        import gc

        if self._was:
            gc.enable()
        return False


def bench_serving(*, steps, windows=1, **leg_kw):
    """One serving leg (see :class:`_ChurnLeg` for the workload).
    Returns a dict of the emitted metrics; ``windows > 1`` reports
    per-leg medians over several timed windows."""
    leg = _ChurnLeg(**leg_kw)
    leg.warm()
    with _gc_frozen():
        for _ in range(windows):
            leg.window(steps)
    return leg.report()


def bench_serving_ab(*, steps, windows, **leg_kw):
    """The round-13 sync-vs-async pair as ONE measurement: two engines
    over identical churns, their timed windows INTERLEAVED (sync w0,
    async w0, sync w1, ...) so slow machine drift hits both legs alike,
    each leg reporting its median window. Returns (sync_out, async_out).
    """
    sync_leg = _ChurnLeg(async_engine=False, **leg_kw)
    async_leg = _ChurnLeg(async_engine=True, **leg_kw)
    sync_leg.warm()
    async_leg.warm()
    with _gc_frozen():
        for _ in range(windows):
            sync_leg.window(steps)
            async_leg.window(steps)
    return sync_leg.report(), async_leg.report()


def bench_serving_spec_model_ab(*, steps, windows, draft_layers,
                                **leg_kw):
    """The round-19 model-draft pair: the SAME seeded-random-prompt
    (NON-repetitive) churn speculating k=4 with the n-gram proposer (the
    round-12 source — its lookup collapses to plain decode on this
    workload and the adaptive k prices it off) vs the truncated-layer
    MODEL draft source, windows interleaved like the engine A/B. Both
    legs run the production async engine, so the model line's
    ``step_gap_frac`` is measured with spec_k > 0 dispatching
    behind-by-one — the async x spec composition the round-19 tentpole
    unlocks. Returns ``(ngram_out, model_out)``; the emitted model line
    carries the paired n-gram stats and the cross-proposer greedy
    emission identity gate (speculation must never change output, so two
    DIFFERENT draft sources over one workload must emit identical
    streams)."""
    ngram_leg = _ChurnLeg(spec_decode_k=4, draft_source="ngram",
                          async_engine=True, spec_report=True, **leg_kw)
    model_leg = _ChurnLeg(spec_decode_k=4, draft_source="model",
                          draft_layers=draft_layers, async_engine=True,
                          spec_report=True, **leg_kw)
    ngram_leg.warm()
    model_leg.warm()
    with _gc_frozen():
        for _ in range(windows):
            ngram_leg.window(steps)
            model_leg.window(steps)
    return ngram_leg.report(), model_leg.report()


def bench_serving_obs_ab(*, steps, windows, **leg_kw):
    """The round-15 observability-overhead pair: the SAME churn with host
    tracing OFF (the disabled-path baseline — spans are one flag check)
    vs ON (spans + per-request lanes recorded every step), windows
    interleaved like the engine A/B so machine drift hits both alike.
    Returns ``(off_out, on_out, ratio)`` where ``ratio`` is the median of
    the PAIRED per-window on/off ratios — pairing adjacent windows
    cancels slow drift a ratio-of-medians would alias. The smoke gate
    holds it near 1.0 as the gross-regression guard; the strict 2%
    disabled-path contract is deterministic-gated in
    tests/test_observability.py (an end-to-end 2% tokens/s assertion is
    below the A/A noise floor of a small shared CI box)."""
    # the ASYNC engine (the round-14 production default): host-side span/
    # counter cost matters precisely where host scheduling is the
    # overlapped resource — tracing must not re-open the host bubble
    off_leg = _ChurnLeg(observability=False, async_engine=True, **leg_kw)
    on_leg = _ChurnLeg(observability=True, async_engine=True, **leg_kw)
    off_leg.warm()
    on_leg.warm()
    with _gc_frozen():
        for _ in range(windows):
            off_leg.window(steps)
            on_leg.window(steps)
    paired = [a / b for a, b in zip(on_leg.win_vals, off_leg.win_vals)
              if b > 0]
    ratio = round(float(np.median(paired)), 3) if paired else 0.0
    return off_leg.report(), on_leg.report(), ratio


class _MoEChurnLeg(_ChurnLeg):
    """The round-25 MoE churn: the standard continuous-arrival churn over
    a top-k routed predictor, plus the router-health metrics on the
    line. ``expert_load_imbalance`` (max/mean kept-pair load over
    experts, layer-averaged) and ``router_drop_rate`` come from one
    eager forward probe over a pool prompt after the timed windows —
    every :class:`GPTMoE` layer refreshes host-readable
    ``router_stats`` per call, so the probe reads the same routing the
    serving step runs (same weights, same capacity math).
    ``active_params_frac`` is the static per-token compute fraction a
    top-k router activates (< 1 is the whole point of the A/B: total
    params grew ~E-fold, tokens/s must not shrink E-fold)."""

    def report(self):
        out = super().report()
        import paddle_tpu as paddle
        from paddle_tpu.models.moe import active_params_frac

        out["active_params_frac"] = round(
            active_params_frac(self.sp.config), 4)
        self.model(paddle.to_tensor(
            np.asarray([self.pool[0]], dtype="int64")))
        loads, drops = [], []
        for layer in self.model.gpt.layers:
            st = layer.mlp.router_stats
            loads.append(np.asarray(st["load"], dtype=np.float64))
            drops.append(float(st["drop_rate"]))
        load = np.mean(loads, axis=0)
        out["expert_load_imbalance"] = round(
            float(load.max() / max(float(load.mean()), 1e-9)), 3)
        out["router_drop_rate"] = round(float(np.mean(drops)), 4)
        return out


def bench_serving_moe_ab(*, steps, windows, **leg_kw):
    """The round-25 dense-vs-MoE pair: the SAME churn shape through the
    dense unified predictor vs a 4-expert top-2 routed one (capacity
    factor 1.25 — the production setting, drops allowed and REPORTED),
    windows interleaved so machine drift hits both legs alike. Both
    legs run the production async engine. There is no emission-identity
    gate — the two legs run different math by construction; the contract is the schema one: the MoE line must
    carry the router-health keys (imbalance, drop rate, active-param
    fraction), its static-vs-analytic HBM drift must stay inside the
    JX007 tolerance (the top_k/E expert-stack scaling on BOTH model
    sides), and the paired dense tokens/s rides the line as the
    efficiency anchor."""
    dense_leg = _ChurnLeg(async_engine=True, **leg_kw)
    moe_leg = _MoEChurnLeg(moe_experts=4, moe_top_k=2,
                           moe_capacity_factor=1.25,
                           async_engine=True, **leg_kw)
    dense_leg.warm()
    moe_leg.warm()
    with _gc_frozen():
        for _ in range(windows):
            dense_leg.window(steps)
            moe_leg.window(steps)
    return dense_leg.report(), moe_leg.report()


def main():
    import sys

    smoke = "--smoke" in sys.argv

    def arg(name, default):
        pre = f"--{name}="
        v = next((a[len(pre):] for a in sys.argv if a.startswith(pre)), None)
        return int(v) if v is not None else default

    if smoke:
        # CPU-runnable CI leg: tiny shapes, gather reference attention.
        # The mesh scaling leg needs >= 2 devices: force virtual host
        # devices BEFORE the backend initializes (no-op when the caller —
        # e.g. the pytest conftest — already forced a device count)
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2")
        import jax as _j

        _j.config.update("jax_platforms", "cpu")
    import paddle_tpu  # noqa: F401  (framework config)
    import jax

    from paddle_tpu.framework.compile_cache import configure_compile_cache

    configure_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    if not (on_tpu or smoke):
        raise SystemExit(
            f"bench_serve.py needs a TPU: jax found platform "
            f"{jax.devices()[0].platform!r} "
            f"({jax.devices()[0].device_kind}). --smoke is the CPU leg.")

    # round 16: --legs=a,b,c runs (and emits) only the named legs — the
    # tier-1 smoke gate selects its gated subset instead of paying every
    # leg's churn; names validate against the schema's known-legs enum so
    # a typo fails HERE, not as a silently-missing line two rounds later
    legs_arg = next((a[len("--legs="):] for a in sys.argv
                     if a.startswith("--legs=")), None)
    selected = None
    if legs_arg is not None:
        from paddle_tpu.analysis.bench_schema import KNOWN_LEGS

        selected = [s.strip() for s in legs_arg.split(",") if s.strip()]
        unknown = sorted(set(selected) - KNOWN_LEGS)
        if unknown:
            raise SystemExit(
                f"--legs: unknown leg(s): {', '.join(unknown)} (known: "
                f"{', '.join(sorted(KNOWN_LEGS))})")

    if smoke:
        shape = dict(hidden=64, layers=2, heads=4, vocab=128,
                     batch=arg("batch", 4), prompt=arg("prompt", 16),
                     steps=arg("steps", 12), gen_len=arg("gen-len", 4),
                     page_size=arg("page-size", 8), chunk=arg("chunk", 8))
    else:
        # flagship: gpt3-125m geometry at the acceptance shape (bs >= 8,
        # 1024-token contexts churning through the lanes)
        shape = dict(hidden=768, layers=12, heads=12, vocab=50304,
                     batch=arg("batch", 8), prompt=arg("prompt", 1024),
                     steps=arg("steps", 64), gen_len=arg("gen-len", 32),
                     page_size=arg("page-size", 0) or None,
                     chunk=arg("chunk", 0) or None)
    label = (f"smoke bs{shape['batch']}" if smoke
             else f"gpt3-125m bs{shape['batch']}")
    chip = (jax.devices()[0].device_kind if on_tpu else "cpu")
    use_kernel = None if on_tpu else False

    # round-10 quantized A/B (fp unified vs int8-weights vs int8-weights +
    # int8-KV) + the round-11 mesh scaling leg: the unified step
    # tensor-parallel over every chip (mp=1 vs mp=N on the same churn).
    # Each leg rebuilds the model from the same seed, so the quantizers
    # and the sharder see identical fp weights.
    # mp must divide BOTH the head count and the ffn width (heads/columns
    # shard whole): the largest such divisor within the device budget —
    # e.g. 12 heads on an 8-chip pod serves mp=6, not an error line
    cap = len(jax.devices()) if on_tpu else min(2, len(jax.devices()))
    n_mp = max(d for d in range(1, cap + 1)
               if shape["heads"] % d == 0 and 4 * shape["hidden"] % d == 0)
    # the round-13 sync-vs-async pair is SELF-CONTAINED: both engines
    # run the same floored workload (a 2-3 token output budget would make
    # every step an emission boundary — no deferral headroom to measure —
    # and a 6-step window is all noise) with their windows interleaved,
    # and the PAIRED sync stats ride the async line (sync_tokens_per_s /
    # sync_step_gap_frac) so its strict gates never compare across
    # workloads. The emitted unified-step leg keeps the SHARED shape and
    # stays the like-for-like baseline for the spmd/quant ratios.
    ab_kw = dict(steps=max(12, shape["steps"]), windows=7)
    ab_shape = dict({k: v for k, v in shape.items() if k != "steps"},
                    gen_len=max(16, shape["gen_len"]),
                    batch=max(4, shape["batch"]),
                    prompt=max(16, shape["prompt"]))
    legs = [
        ("unified-step", {}),
        # round-13 A/B: the SAME churn through the sync engine and the
        # async double-buffered engine — dispatch-ahead + deferred
        # reconcile vs one blocking sync per step; measured as one
        # interleaved pair, greedy emissions bit-identical
        ("unified-async", None),
        # round-15 A/B: the SAME churn with host tracing off vs on —
        # the observability overhead contract, measured interleaved
        ("unified-obs", None),
        ("unified-spmd", dict(mesh_chips=n_mp)),
        # round-12 speculation A/B: the SAME repetitive-prompt churn with
        # drafting off (the 1.0-tokens/lane-step anchor) vs k=4
        ("unified-spec-base", dict(spec_workload=True)),
        ("unified-spec-k4", dict(spec_workload=True, spec_decode_k=4)),
        # round-19 A/B: the SAME seeded-random (NON-repetitive) churn
        # speculating k=4 through the n-gram proposer vs the truncated-
        # layer model draft source, both on the async engine (spec steps
        # dispatch behind-by-one) — measured interleaved, cross-proposer
        # greedy emissions bit-identical
        ("unified-spec-model", None),
        ("unified-int8w", dict(weight_dtype="int8")),
        ("unified-int8w-int8kv", dict(weight_dtype="int8",
                                      kv_cache_dtype="int8")),
        # round-17 resilience A/B: the SAME churn shape flooded past
        # capacity (bounded queue + expired-deadline stragglers, SLO
        # armed) vs nominal load — shed/deadline/failure accounting on
        # the line, nominal partner's rates riding it at exactly zero
        ("unified-overload", None),
        # round-18 fleet leg: N=2 replicas behind the FleetRouter on the
        # same churn shape with replica churn injected (one kill +
        # seeded stalls) — per-replica tokens/s, affinity hit rate,
        # failover and shed accounting on the checked line
        ("fleet-churn", None),
        # round-20 disaggregation A/B: the SAME mixed churn (short
        # decode-bound prompts + fresh multi-page longs) through a
        # colocated fleet vs 1-prefill + 2-decode with checksummed
        # KV-page streaming (int8 payloads + scale planes), measured
        # interleaved; a certainty-armed transfer_drop chaos pass shows
        # graceful colocated fallback on the same line
        ("fleet-disagg", None),
        # round-21 tiered-KV A/B: the SAME reused-prompt churn (a
        # working set overflowing the HBM pool's zero-ref headroom)
        # through a host-tiered fleet with cross-replica pulls vs a
        # no-tier partner, measured interleaved — spill/restore bytes,
        # tier hit rate and deterministic drain-forced pulls on the
        # line; a chaos pass arms the host_spill_drop /
        # tier_restore_corrupt seams (detected, degraded, never failed)
        ("fleet-tiered", None),
        # round-25 MoE A/B: the SAME churn through the dense unified
        # predictor vs a 4-expert top-2 routed one (capacity 1.25,
        # drops reported) — router-health keys (load imbalance, drop
        # rate, active-param fraction) on the line, the paired dense
        # tokens/s riding it as the efficiency anchor
        ("moe-churn", None),
    ]
    if selected is not None:
        keep = set(selected)
        legs = [(n, o) for n, o in legs if n in keep]
    results = {}

    def _streams_match(a, b):
        # per-arrival greedy emission bit-identity across an interleaved
        # pair: FULL equality for requests finished in both legs, prefix
        # equality for in-progress tails (shared by the interleaved A/Bs)
        def _same(i):
            (af, at), (bf, bt) = a[i], b[i]
            if af and bf:
                # finished in BOTH legs: the streams must be
                # bit-identical INCLUDING length (a dropped
                # trailing token must fail the gate)
                return at == bt
            n = min(len(at), len(bt))
            return at[:n] == bt[:n]

        common = set(a) & set(b)
        return float(bool(common) and all(_same(i) for i in common))

    def metric_for(name):
        return (f"{FLAGSHIP_METRIC} ({label} prompt{shape['prompt']}"
                f"+{shape['steps']} steps, {chip}) [{name}]")

    def ab_metric_for(name):
        # the interleaved A/B pairs run the FLOORED workload: their
        # metric label must say so, not inherit the shared shape's
        return ((f"{FLAGSHIP_METRIC} (smoke bs{ab_shape['batch']}"
                 if smoke else
                 f"{FLAGSHIP_METRIC} (gpt3-125m bs{ab_shape['batch']}")
                + (f" prompt{ab_shape['prompt']}+{ab_kw['steps']}x"
                   f"{ab_kw['windows']} steps, {chip}) [{name}]"))

    failed = []
    for name, over in legs:
        try:
            if name == "unified-async":
                sync_out, async_out = bench_serving_ab(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    **ab_shape, **ab_kw)
                out = dict(metric=ab_metric_for(name), **async_out)
                # the paired sync stats ride the async line — its strict
                # gates (tokens/s higher, gap lower, streams identical)
                # compare within the interleaved pair, one workload
                out["sync_tokens_per_s"] = sync_out["value"]
                out["sync_step_gap_frac"] = sync_out["step_gap_frac"]
                out["vs_baseline"] = (
                    round(out["value"] / sync_out["value"], 3)
                    if sync_out["value"] else 0.0)
                out["async_emissions_match"] = _streams_match(
                    async_out["_streams"], sync_out["_streams"])
                results[name] = out
            elif name == "unified-spec-model":
                # the truncated self-draft keeps the first quarter of the
                # stack (>= 1): 12 layers -> 3, the 2-layer smoke -> 1
                ngram_out, model_out = bench_serving_spec_model_ab(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    draft_layers=max(1, ab_shape["layers"] // 4),
                    **ab_shape, **ab_kw)
                out = dict(metric=ab_metric_for(name), **model_out)
                # the paired n-gram stats ride the model line: its strict
                # gates (accepted/step > 1 on NON-repetitive churn, low
                # step_gap_frac with spec_k > 0, identical emissions)
                # compare within the interleaved pair, one workload
                out["ngram_tokens_per_s"] = ngram_out["value"]
                out["ngram_accepted_tokens_per_step"] = (
                    ngram_out["accepted_tokens_per_step"])
                out["vs_baseline"] = (
                    round(out["value"] / ngram_out["value"], 3)
                    if ngram_out["value"] else 0.0)
                out["spec_emissions_match"] = _streams_match(
                    model_out["_streams"], ngram_out["_streams"])
                results[name] = out
            elif name == "unified-overload":
                over_out, nom_out = bench_serving_overload(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    **ab_shape, **ab_kw)
                out = dict(metric=ab_metric_for(name), **over_out)
                # the nominal partner's rates ride the overload line: the
                # schema-gated contract is shed_rate > 0 under overload,
                # exactly 0 at nominal load (same predictor config)
                out["nominal_shed_rate"] = nom_out["shed_rate"]
                out["nominal_deadline_miss_rate"] = (
                    nom_out["deadline_miss_rate"])
                out["vs_baseline"] = (
                    round(out["value"] / nom_out["value"], 3)
                    if nom_out["value"] else 0.0)
                results[name] = out
            elif name == "fleet-churn":
                out = bench_serving_fleet(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    steps=shape["steps"], windows=2,
                    **{k: v for k, v in shape.items() if k != "steps"})
                results[name] = dict(metric=metric_for(name), **out)
            elif name == "fleet-disagg":
                _colo_out, out = bench_serving_disagg(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    steps=shape["steps"], windows=2,
                    **{k: v for k, v in shape.items() if k != "steps"})
                # the colocated partner's throughput/TTFT already ride
                # the disagg line (colocated_* keys; vs_baseline is
                # disagg/colocated on the interleaved pair)
                results[name] = dict(metric=metric_for(name), **out)
            elif name == "fleet-tiered":
                _base_out, out = bench_serving_tiered(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    steps=shape["steps"], windows=2,
                    **{k: v for k, v in shape.items() if k != "steps"})
                # the no-tier partner's throughput/hit-rate/TTFT already
                # ride the tiered line (notier_* keys; vs_baseline is
                # tiered/no-tier on the interleaved pair)
                results[name] = dict(metric=metric_for(name), **out)
            elif name == "moe-churn":
                dense_out, moe_out = bench_serving_moe_ab(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    **ab_shape, **ab_kw)
                out = dict(metric=ab_metric_for(name), **moe_out)
                # the paired dense stats ride the MoE line: vs_baseline
                # = moe/dense tokens/s on the SAME interleaved churn —
                # read it against active_params_frac (total params grew
                # ~E-fold; throughput must track ACTIVE params, not
                # total)
                out["dense_tokens_per_s"] = dense_out["value"]
                out["vs_baseline"] = (
                    round(out["value"] / dense_out["value"], 3)
                    if dense_out["value"] else 0.0)
                results[name] = out
            elif name == "unified-obs":
                off_out, on_out, ratio = bench_serving_obs_ab(
                    on_tpu=on_tpu, use_kernel=use_kernel,
                    **ab_shape, **ab_kw)
                out = dict(metric=ab_metric_for(name), **on_out)
                # the untraced partner rides the traced line; vs_baseline
                # IS the overhead ratio (paired-window median — the
                # round-15 contract holds it near 1.0: tracing must not
                # buy back the async wins)
                out["obs_off_tokens_per_s"] = off_out["value"]
                out["vs_baseline"] = ratio
                results[name] = out
            else:
                out = bench_serving(on_tpu=on_tpu, use_kernel=use_kernel,
                                    steps=shape["steps"],
                                    **{k: v for k, v in shape.items()
                                       if k != "steps"}, **over)
                results[name] = dict(metric=metric_for(name), **out)
        except Exception as e:
            # the remaining legs still run; the run fails at the end
            import traceback

            traceback.print_exc(file=sys.stderr)
            print(_error_line(f"{type(e).__name__}: {e}"[:200],
                              metric=metric_for(name)))
            failed.append(name)

    # line order = leg order, flagship (quantized unified) LAST.
    # vs_baseline: each quantized leg over the FP UNIFIED step (> 1 = the
    # HBM bytes bought back turned into tokens/s)
    from paddle_tpu.analysis.bench_schema import checked_line

    def _emit(name, base):
        if name not in results:
            return
        out = results[name]
        out.pop("_streams", None)
        out["leg"] = name   # schema-checked against the known-legs enum
        if "vs_baseline" in out:
            pass   # self-baselined (the async pair)
        elif base is None:
            out["vs_baseline"] = 1.0
        elif base in results and results[base]["value"]:
            out["vs_baseline"] = round(
                out["value"] / results[base]["value"], 3)
        elif selected is not None and base not in selected:
            # the baseline leg was excluded by --legs, not dead: a
            # partial run has no comparison to make — omit the (schema-
            # optional) ratio rather than emit the 0.0 error signal
            pass
        else:
            out["vs_baseline"] = 0.0
        print(checked_line(out))

    # mesh leg baselines the fp unified step (mp=1): its vs_baseline IS
    # the mesh scaling factor on aggregate tokens/s; the spec leg
    # baselines the spec-off run of its OWN (repetitive) workload, so its
    # vs_baseline is the effective speculation speedup; the async leg
    # baselines the sync engine on the SAME interleaved churn
    _emit("unified-step", None)
    _emit("unified-async", None)
    _emit("unified-obs", None)
    _emit("unified-spmd", "unified-step")
    _emit("unified-spec-base", None)
    _emit("unified-spec-k4", "unified-spec-base")
    # round-19 model-draft leg (self-baselined on its interleaved n-gram
    # partner: vs_baseline = model/ngram tokens/s on the SAME
    # non-repetitive churn — the speedup a drafter that accepts on
    # realistic traffic buys over one that collapses to plain decode)
    _emit("unified-spec-model", None)
    _emit("unified-int8w", "unified-step")
    _emit("unified-int8w-int8kv", "unified-step")
    # round-17 resilience leg (self-baselined on its interleaved
    # nominal-load partner: vs_baseline = overload/nominal tokens/s —
    # how much throughput the shed storm costs the served lanes)
    _emit("unified-overload", None)
    # round-18 fleet leg (no baseline partner: a one-replica fleet IS
    # the unified-step leg — the line's value is fleet-aggregate)
    _emit("fleet-churn", None)
    # round-20 disaggregation leg (self-baselined on its interleaved
    # colocated partner: vs_baseline = disagg/colocated tokens/s on the
    # SAME mixed churn; the TTFT-p99 pair is the headline comparison)
    _emit("fleet-disagg", None)
    # round-21 tiered-KV leg (self-baselined on its interleaved no-tier
    # partner: vs_baseline = tiered/no-tier tokens/s on the SAME
    # pool-overflowing reused churn; the hit-rate/TTFT-p99 pair is the
    # headline comparison)
    _emit("fleet-tiered", None)
    # round-25 MoE leg (self-baselined on its interleaved dense partner:
    # vs_baseline = moe/dense tokens/s on the SAME churn; the
    # router-health keys are the headline — drop rate and imbalance at
    # capacity 1.25, throughput tracking active not total params)
    _emit("moe-churn", None)
    if failed:
        raise SystemExit(f"bench_serve.py: legs failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
