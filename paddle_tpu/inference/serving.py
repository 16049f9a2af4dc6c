"""Continuous-batching autoregressive serving over the paged KV cache.

The serving front end schedules a UNIFIED ragged step — ONE fixed-shape jit
(``models/gpt.py build_unified_step``) serves decode tokens and
chunked-prefill tokens in the same program, driven by a per-step token
budget. It is the only serving step program.

Scheduling (the Ragged-Paged-Attention / chunked-prefill shape, PAPERS.md):

- every running slot with exactly one context token left to feed is a
  DECODE lane — those pack first, one token each, so admission never
  head-of-line-blocks the decode batch behind a full prompt forward;
- the remaining token budget fills with PREFILL CHUNKS (FIFO by request
  age, up to ``chunk`` tokens per slot per step) from admitting or
  preemption-replaying sequences;
- a chunk that reaches the end of its context yields that slot's next
  token (greedy argmax, or the fused seeded temperature/top-k/top-p
  epilogue).

Prefix caching: admission matches the prompt against the page-granular
content-hash registry (``KVCacheManager.admit_prefix``) and skips the
prefill compute for every hit page; fully-prefilled prompts register their
pages for later requests. Divergent writes into shared pages ride the
step's copy-on-write lanes.

Request lifecycle: WAITING (queued) -> RUNNING (owns a slot + pages;
prefilling until its context is fully fed, then decoding) -> FINISHED
(eos / max_new_tokens / length ceiling). Capacity pressure preempts the
YOUNGEST running request back to the queue (recompute-mode, vLLM policy);
its replay re-hits its own registered prefix pages.

Round 12 adds SPECULATIVE DECODING on the unified step
(``spec_decode_k``): every decode lane consults its request's n-gram /
prompt-lookup draft proposer (``inference/draft.py``, per-request table
fed from the already-tracked context ids, adaptive k backing off to plain
decode on low acceptance) and packs ``1 + k`` verify rows into the SAME
token budget (decode lanes first, prefill chunks still fill the
remainder — no new geometry). The step's fused accept epilogue emits the
accepted prefix + one bonus token (greedy bit-identical to plain decode;
sampled rows ride the per-request seeded streams keyed by
tokens-produced), and rejected drafts' over-allocated pages roll back
host-side (``KVCacheManager.trim_pages``) so page/refcount accounting
stays identical to a never-speculated run.

Round 13 adds the ASYNC DOUBLE-BUFFERED ENGINE (``async_engine=True``):
``step()`` packs and DISPATCHES step N, then reconciles step N-1's
deferred results — the host scheduler and the device execute
concurrently (JAX async dispatch), so the TPU never idles through the
pack/bookkeeping gap the synchronous loop pays between steps (the
inter-step host bubble MPK diagnoses). The enabler is device-resident
sampled-token feedback: the unified step returns a per-lane ``next_toks``
carry that the next step consumes as a traced input (``feedback`` mask +
``prev_toks``), so decode lanes advance WITHOUT materializing the token
on the host. Host bookkeeping that only needs token COUNTS (page growth,
capacity, admission, budget-retirement, prefix registration) runs at
pack time; bookkeeping that needs token VALUES (``output_ids``, eos
detection, TTFT, preemption-replay contexts, spec drafts/rollback)
reconciles one step behind on the deferred results. The hard host syncs
are exactly the emission boundaries: a step whose emissions could finish
a request (eos configured / output budget reachable) reconciles
behind-by-one; steps that cannot complete anything defer up to
``max_inflight_steps`` and drain in one batched materialization
(``flush()``). Greedy output is bit-identical and seeded sampling
stream-identical to the synchronous engine (per-request streams are
batch-order invariant); async is the DEFAULT since round 14 —
``async_engine=False`` keeps the synchronous engine as the oracle — both
drive the SAME pack/capacity code, the sync engine simply reconciles at
pipeline depth zero.

Round 17 adds the RESILIENCE LAYER. Requests gain a terminal ``FAILED``
state with a per-request ``error`` record ``{"code", "message"}`` —
never-admittable prompts, retry-exhausted step failures, shed
admissions and missed deadlines fail INDIVIDUALLY while the predictor
keeps serving everyone else. ``deadline_s`` gives a request a wall-clock
budget (expired WAITING requests shed as ``deadline_exceeded`` at the
next scheduler round — the queue TTL; RUNNING requests past deadline
retire at the next round's reconcile point). ``slo=SLOConfig(...)``
arms admission control at ``add_request``: a bounded waiting queue plus
SLO-aware load shedding off the round-15 telemetry signals (pool
occupancy, in-flight ring depth, TTFT-p99 EMA); the verdicts
(:meth:`ServingPredictor.admission_verdict`) and the
:meth:`~ServingPredictor.healthz` snapshot are the load-signal surface
the fleet router consumes. Step execution is CRASH-CONSISTENT: an
exception inside ``_pack_dispatch`` (pack, H2D upload, launch) or
``_reconcile_one`` (materialization) drops the failed in-flight entry,
un-charges its dispatched-unmaterialized tokens, and requeues every
affected lane through the existing preemption-replay path (already
value-barriered and bit-identical on replay) with bounded retry +
exponential backoff before the affected requests FAIL — page / slot /
refcount / prefix-pin accounting is exact after any failure.
``inference/faults.py`` injects deterministic seeded faults at the
named seams (pool squeeze, h2d, dispatch, slow_step, reconcile);
disarmed, every seam is one module-global check. With no faults armed,
no deadlines set and shedding off, the engine is bit-identical to the
round-16 engine.

Round 19 makes speculation MODEL-BASED and composes it with the async
engine. ``draft_source="model"`` (or ``config.spec_draft_layers > 0``)
swaps the n-gram proposer for a truncated-layer SELF-DRAFT: a shared
:class:`~paddle_tpu.inference.draft.ModelDraftEngine` runs the first
``draft_layers`` layers of the SAME serving param stacks (shared
embeddings/LM head — zero extra weights) as its own small fixed-shape
unified-step jit over a DEDICATED draft KV pool, proposing k tokens per
decode lane in ONE device-chained pass per scheduler round (catch-up
prefill chunks + a chunk-1 decode chain threaded through the feedback
carry; one host sync lands every lane's drafts). Acceptance then tracks
truncation quality instead of workload repetitiveness — the n-gram
table's collapse on non-repetitive traffic. Per-request adaptive-k /
EMA / cooldown state rides the same ``_drafts`` dict (and survives
preemption replay); the draft pool self-heals against the lane's
CURRENT context, so replays, rejected drafts and dropped in-flight
steps all reconcile through one prefix comparison. Async x spec: a
DRAFTED spec step now dispatches BEHIND-BY-ONE — its n_emit-variable
advance/rollback reconciles at the START of the next round (every
completing lane charges one pending token, the guaranteed minimum
emission) — and DRAFTLESS spec rounds (adaptive k backed off) ride the
plain engine's deferral + steady-pack cache untouched, so speculation
and dispatch-ahead multiply instead of excluding each other
(``serving_spec_async_deferred_steps`` counts both shapes). Greedy and
seeded emissions stay bit-identical to the sync spec engine, with page
accounting in lockstep at every drain.

Knobs: ``max_batch`` (lanes), ``num_pages``/``page_size`` (pool geometry),
``max_seq_len`` (page-table width), ``chunk`` (per-slot prefill chunk,
autotuned default), ``token_budget`` (the MOST rows a step packs, default
``max_batch * (1 + spec_k) + chunk``: a ceiling on rows and on the step's
memory, not what a step costs — the one step program computes over the
smallest rung of ``models/gpt.py step_row_ladder`` that holds the rows
packed, so a decode-only step of a large budget runs its decode rows;
``serving_rows_run`` counts the rungs taken), ``prefix_cache`` (on by
default), ``spec_decode_k`` (speculation build geometry, default
``config.spec_decode_k``), ``async_engine`` (the round-13 pipelined
engine) + ``max_inflight_steps`` (deferral bound for steps that cannot
complete any request).
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

import jax.numpy as jnp

from ..observability import (MetricsRegistry, merge_snapshots, monotonic,
                             phase, process_registry, request_begin,
                             request_end, request_event, span,
                             tracing_active)
from ..profiler.record import recorder as _recorder
from .faults import InjectedFault, fault_point
from .kv_cache import KVCacheManager, kv_cache_quantized, pages_needed

WAITING, RUNNING, FINISHED, FAILED = ("waiting", "running", "finished",
                                      "failed")


def stream_done(output_ids, max_new_tokens, eos_token_id) -> bool:
    """The budget/eos stop rule over a MATERIALIZED output stream — the
    ONE spelling shared by the async emission-drop rule
    (:meth:`ServingPredictor._landed_done`) and the fleet router's
    failover dedup (``FleetRequest.done``): the two deciding the same
    question from different layers must never drift apart."""
    if len(output_ids) >= max_new_tokens:
        return True
    return (eos_token_id is not None and bool(output_ids)
            and output_ids[-1] == eos_token_id)


def deadline_passed(submit_time, deadline_s, now=None) -> bool:
    """Absolute-deadline check anchored at the ORIGINAL submission —
    shared by :class:`Request` and the fleet router's request handle."""
    if deadline_s is None:
        return False
    return (monotonic() if now is None else now) >= submit_time + deadline_s


class Request:
    """One generation request; ``output_ids`` fills as steps land."""

    _next_id = [0]

    def __init__(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
                 temperature=0.0, top_k=0, top_p=1.0, seed=None,
                 deadline_s=None, submit_time=None, sample_offset=0):
        self.req_id = Request._next_id[0]
        Request._next_id[0] += 1
        self.prompt_ids = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not self.prompt_ids:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        # round 17: wall-clock budget (seconds from submission; None =
        # no deadline) and the terminal-failure record — a FAILED request
        # carries {"code", "message"} in ``error``
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        if self.deadline_s is not None and self.deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
        self.error: dict | None = None
        # failure-driven requeues (NOT ordinary preemptions): bounded by
        # the predictor's max_step_retries before the request FAILS
        self.retry_count = 0
        self._finish_counted = False
        # sampling params (temperature == 0 -> greedy argmax); seed defaults
        # to the request id so replays after preemption re-sample the SAME
        # stream (keyed by tokens produced)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = self.req_id if seed is None else int(seed)
        # round 20: the tokens-produced base of the in-jit sample-key
        # fold. A re-admission that carries ALREADY-RECEIVED tokens in
        # its prompt (the fleet router's failover resume and the
        # disaggregated prefill->decode handoff both feed
        # ``original_prompt + received``) passes the received count
        # here, so token r+i samples with fold(base_key, r+i) — the
        # seeded stream continues bit-identically to an uninterrupted
        # run instead of restarting its fold at 0
        self.sample_offset = int(sample_offset)
        if self.sample_offset < 0:
            raise ValueError(f"sample_offset must be >= 0, "
                             f"got {sample_offset}")
        self.output_ids: list[int] = []
        # tokens the async engine has dispatched for this request but not
        # yet materialized on the host (always 0 in the sync engine once
        # a step returns): they count toward the output budget and the
        # context length, their VALUES land at reconcile
        self._pending_n = 0
        self.state = WAITING
        self.preempt_count = 0
        self.truncated = False  # stopped by the max_seq_len ceiling
        # serving metrics: time-to-first-token + prefix-cache hit size.
        # round 18: ``submit_time`` may be supplied by a RE-ADMISSION path
        # (the fleet router's failover re-admit): a request's wall-clock
        # budget is anchored at its ORIGINAL submission — re-admitting
        # must never restart the TTL (``past_deadline`` reads
        # submit_time + deadline_s, so carrying the stamp carries the
        # absolute deadline). In-predictor preemption replay requeues the
        # SAME Request object, which preserves the stamp by construction.
        self.submit_time = (monotonic() if submit_time is None
                            else float(submit_time))
        self.first_token_time: float | None = None
        # first admission (not replay), and the dispatch of the step that
        # fed the prompt's last chunk: queue wait and prefill, on monotonic()
        self.admit_time: float | None = None
        self.prefill_end_time: float | None = None
        self.cached_prefix_len = 0   # tokens served from the prefix cache
        self._registered = False     # prompt pages in the prefix registry

    @property
    def done(self) -> bool:
        if self.truncated:
            return True
        if len(self.output_ids) + self._pending_n >= self.max_new_tokens:
            return True
        return (self.eos_token_id is not None and self.output_ids
                and self.output_ids[-1] == self.eos_token_id)

    @property
    def _ctx_len(self) -> int:
        """Context length INCLUDING dispatched-unmaterialized tokens —
        what the scheduler's count-based packing sees."""
        return len(self.prompt_ids) + len(self.output_ids) + self._pending_n

    @property
    def ttft(self) -> float | None:
        """Seconds from submission to the first generated token."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    def _context_ids(self) -> list[int]:
        """Prompt + generated-so-far — what a re-prefill after preemption
        replays."""
        return self.prompt_ids + self.output_ids

    def past_deadline(self, now=None) -> bool:
        return deadline_passed(self.submit_time, self.deadline_s, now)


class SLOConfig:
    """Admission-control / load-shedding policy for one predictor
    (round 17). ``slo=None`` (the default) disables shedding entirely;
    an armed config sheds at :meth:`ServingPredictor.add_request` — the
    request comes back terminal FAILED with a ``shed_*`` error code
    instead of queueing into an overload the SLO can never recover from.

    - ``max_waiting`` — the bounded waiting queue (always enforced once
      armed; ``shed_queue_full``).
    - ``max_pool_occupancy`` — shed while the KV pool's claimed fraction
      (1 - available/total) is at/above this AND a backlog exists
      (``shed_pool_pressure``).
    - ``max_inflight_depth`` — shed while the async in-flight ring sits
      at/above this depth with a backlog (``shed_inflight_depth``).
    - ``ttft_p99_slo_ms`` — shed while the TTFT-p99 EMA (an EMA over the
      registry histogram's p99 estimate, updated per first token) is
      above the SLO with a backlog (``shed_ttft_slo``).

    The thresholds other than ``max_waiting`` default to None (off) so a
    config can arm exactly the signals its deployment trusts.
    """

    def __init__(self, *, max_waiting=256, max_pool_occupancy=None,
                 max_inflight_depth=None, ttft_p99_slo_ms=None,
                 ema_alpha=0.2):
        self.max_waiting = None if max_waiting is None else int(max_waiting)
        if self.max_waiting is not None and self.max_waiting < 1:
            raise ValueError(f"max_waiting must be >= 1, got {max_waiting}")
        self.max_pool_occupancy = (None if max_pool_occupancy is None
                                   else float(max_pool_occupancy))
        if self.max_pool_occupancy is not None \
                and not 0.0 < self.max_pool_occupancy <= 1.0:
            raise ValueError(f"max_pool_occupancy is a fraction in (0, 1], "
                             f"got {max_pool_occupancy}")
        self.max_inflight_depth = (None if max_inflight_depth is None
                                   else int(max_inflight_depth))
        if self.max_inflight_depth is not None and self.max_inflight_depth < 0:
            raise ValueError(f"max_inflight_depth must be >= 0, "
                             f"got {max_inflight_depth}")
        self.ttft_p99_slo_ms = (None if ttft_p99_slo_ms is None
                                else float(ttft_p99_slo_ms))
        if self.ttft_p99_slo_ms is not None and self.ttft_p99_slo_ms <= 0:
            raise ValueError(f"ttft_p99_slo_ms must be > 0, "
                             f"got {ttft_p99_slo_ms}")
        self.ema_alpha = float(ema_alpha)
        if not 0.0 < self.ema_alpha <= 1.0:
            raise ValueError(f"ema_alpha must be in (0, 1], got {ema_alpha}")


class _Pending:
    """One dispatched-but-unreconciled unified step — an entry of the
    async engine's in-flight ring. Holds the DEVICE handles of the step's
    emission outputs (unmaterialized jax arrays) plus the host records
    needed to land them one step behind: materializing ``out``/``ne`` is
    the engine's ONE hard sync."""

    __slots__ = ("out", "ne", "completing", "spec", "spec_slots",
                 "must_sync", "step_no")

    def __init__(self, out, ne, completing, spec, spec_slots, must_sync):
        self.out = out                 # device next_toks / out_ids
        self.ne = ne                   # device n_emit (spec builds)
        self.completing = completing   # [(slot, req, k_i, was_decode)]
        self.spec = spec
        self.spec_slots = spec_slots   # lanes advancing by n_emit + trim
        self.must_sync = must_sync     # some emission could finish a req
        self.step_no = 0               # serving_steps at its dispatch


class ServingPredictor:
    """Continuous-batching predictor for a GPT model.

    ``add_request`` enqueues; ``step`` runs one scheduler round (admit /
    grow / preempt around ONE unified-step launch); ``generate`` drives
    ``step`` until a set of prompts finishes. ``async_engine`` (round 13;
    the DEFAULT since round 14) overlaps host scheduling with device
    execution: ``step()`` dispatches round N and reconciles round N-1's
    deferred emissions (see the module docstring for the sync-boundary
    contract); ``flush()`` drains the in-flight ring; ``False`` selects
    the synchronous oracle engine.
    """

    def __init__(self, model, *, max_batch=8, num_pages=None, page_size=None,
                 max_seq_len=None, use_kernel=None, dtype=None, chunk=None,
                 token_budget=None, prefix_cache=None, kv_cache_dtype=None,
                 mesh=None, spec_decode_k=None, async_engine=None,
                 max_inflight_steps=4, metrics=None, slo=None,
                 max_step_retries=3, retry_backoff_s=0.02,
                 replica_id=0, role="colocated", draft_source=None,
                 draft_layers=None, draft_num_pages=None,
                 host_tier_bytes=0):
        from ..distributed.mesh import as_serving_mesh
        from ..models.gpt import (_serving_params_cached, build_unified_step,
                                  serving_params, shard_serving_params,
                                  step_row_ladder)

        gpt = model.gpt if hasattr(model, "gpt") else model
        self.config = gpt.config
        cfg = self.config
        # round 15: the structured metrics registry — every counter/timer
        # this predictor used to keep as ad-hoc attributes lives here
        # (always-enabled by default: these ARE the bench metrics), shared
        # with the KV cache manager so ONE snapshot covers the serving
        # stack; back-compat read properties keep the round-13/14 surface
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if not self.metrics.enabled:
            # these counters BACK the behavioral read surface
            # (tokens_emitted/steps/TTFT/step_gap_frac/telemetry): a
            # disabled registry would silently report zeros — fail loud
            # (the library-wide default_registry is off by default; pass
            # a dedicated MetricsRegistry() or enable it first)
            raise ValueError(
                "ServingPredictor requires an enabled metrics registry; "
                "the one passed is disabled")
        self._init_instruments()
        # a model with dropless routed experts: rows routed, and rows per
        # expert (the fullest expert's share of the mean is max over mean of
        # these). A model without them reports neither.
        self._m_moe_rows = self._m_moe_expert_rows = self._m_moe_fed = None
        self._m_moe_elsewhere = None
        self._moe_unread: list = []
        self._moe_pairs_unread: list = []
        if getattr(cfg, "experts_held", None):
            # a chip's share of the experts: the counters below count the
            # HELD experts and their rows; the rest of a row's choices went
            # to experts on other chips
            self._m_moe_elsewhere = self.metrics.counter(
                "serving_moe_rows_elsewhere",
                "token-expert pairs whose expert is not held here")
            self._moe_pairs_per_row = (cfg.num_moe_layers
                                       * cfg.num_experts_per_tok)
        if getattr(cfg, "n_routed_experts", 0):
            self._m_moe_fed = self.metrics.counter(
                "serving_moe_experts_fed",
                "(layer, expert) pairs that received a row in a step: whose "
                "weights the step had to read")
            self._m_moe_rows = self.metrics.counter(
                "serving_moe_rows_routed",
                "token-expert pairs computed by the routed layers")
            self._m_moe_expert_rows = self.metrics.counter(
                "serving_moe_expert_rows", "the same, by expert",
                labels=("expert",))
        # round 11: mesh (None | int mp degree | Mesh(("mp",))) serves the
        # steps tensor-parallel — params + KV pools sharded by head, the
        # scheduler and page/slot/prefix bookkeeping below stay host-global
        self.mesh = as_serving_mesh(mesh)
        # a model with a LATENT cache (multi-head latent attention,
        # models/deepseek_v2.py) brings its weight tree as the serving step
        # scans it, in its serving dtype, on the device: nothing is extracted
        # or copied. What this path does not extend to it yet fails here.
        self.latent = bool(getattr(cfg, "kv_lora_rank", 0))
        # a model whose layers retain different things (window layers keep
        # their last ``sliding_window`` positions alone; models/
        # cohere2_moe.py): a second cache group, and the same refusals
        self.windowed = not self.latent and bool(
            getattr(cfg, "sliding_window", 0))
        if self.latent or self.windowed:
            unsupported = [name for name, on in (
                ("kv_cache_dtype", kv_cache_dtype), ("mesh", mesh),
                ("spec_decode_k", spec_decode_k),
                ("host_tier_bytes", host_tier_bytes),
                ("draft_source='model'", draft_source == "model"),
                ("draft_layers", draft_layers),
                ("prefix_cache", self.windowed and prefix_cache)) if on]
            if unsupported:
                raise NotImplementedError(
                    f"not supported for a "
                    f"{'latent (MLA) cache' if self.latent else 'cache with a window group'}"
                    f" yet: {', '.join(unsupported)}")
        with phase("weights.place"):
            if self.latent or self.windowed:
                import jax

                self.params = (
                    model.params if dtype is None else jax.tree.map(
                        lambda a: a if a.dtype == dtype else a.astype(dtype),
                        model.params))
            elif dtype is None:
                # share the weak-keyed extraction with generate() — a
                # second predictor (or generate call) on one model reuses
                # the stacks (quantized per cfg.weight_dtype, sharded per
                # mesh signature, inside the cache)
                self.params = _serving_params_cached(model, mesh=self.mesh)
                # the round-19 draft engine slices its truncated stacks
                # off the UNSHARDED extraction (it re-shards with its own
                # config)
                params_unsharded = (self.params if self.mesh is None
                                    else _serving_params_cached(model,
                                                                mesh=None))
            else:
                import jax

                self.params = jax.tree.map(lambda a: a.astype(dtype),
                                           serving_params(model))
                if cfg.weight_dtype is not None:
                    from .quantize import quantize_serving_params

                    self.params = quantize_serving_params(
                        self.params, cfg.weight_dtype,
                        cfg.weight_quant_group_size)
                params_unsharded = self.params
                if self.mesh is not None:
                    self.params = shard_serving_params(
                        self.params, self.mesh, cfg)
        # the model's position table bounds every context
        self.max_seq_len = min(int(max_seq_len or cfg.max_seq_len),
                               cfg.max_seq_len)
        self.max_batch = int(max_batch)
        self.kv_quant = kv_cache_quantized(
            kv_cache_dtype or getattr(cfg, "kv_cache_dtype", None))
        kv_dtype = self.params["tok_emb"].dtype
        from ..ops.pallas.paged_attention import (preferred_chunk_size,
                                                  preferred_page_size)

        if num_pages is None:
            # default pool: every lane can reach max_seq_len
            ps = page_size or preferred_page_size(
                cfg.num_heads, cfg.num_heads, cfg.head_dim, kv_dtype)
            num_pages = self.max_batch * pages_needed(self.max_seq_len, ps)
        if prefix_cache is None:
            # (a hit needs the last window's pages alive in a window group)
            prefix_cache = not self.windowed
        # a latent cache: one pool of one row per token, the row padded to
        # whole 128-lane tiles (compiled for the chip, an unpadded 576-wide
        # row costs a copy of the whole pool at every kernel call)
        kv_heads, kv_width = ((1, -(-cfg.latent_dim // 128) * 128)
                              if self.latent
                              else (getattr(cfg, "num_kv_heads", None)
                                    or cfg.num_heads, cfg.head_dim))
        self.chunk = int(chunk or preferred_chunk_size(
            cfg.num_heads, cfg.num_heads, cfg.head_dim, kv_dtype))
        # learned sparse attention: the indexer layers' keys, a second plane
        self.sparse = self.latent and bool(getattr(cfg, "index_topk", 0))
        with phase("kv.pools"):
            self.cache = KVCacheManager(
                cfg.num_layers, kv_heads, kv_width, latent=self.latent,
                **({"index_plane": (cfg.num_index_layers, cfg.index_head_dim)}
                   if self.sparse else {}),
                **({"window": (cfg.num_window_layers, cfg.sliding_window,
                               self.chunk)} if self.windowed else {}),
                num_pages=num_pages, max_batch=self.max_batch,
                max_seq_len=self.max_seq_len, page_size=page_size,
                num_q_heads=cfg.num_heads, dtype=kv_dtype,
                enable_prefix_cache=prefix_cache, quantize_kv=self.kv_quant,
                mesh=self.mesh, metrics=self.metrics,
                # round 21: the host-DRAM spill tier under the HBM pool
                # (0 disables — evictions drop exactly like pre-21)
                host_tier_bytes=host_tier_bytes)
        # round 12: speculative decoding — build geometry for the verify
        # rows ([b, k+1] outputs); per-request adaptive k only varies the
        # spec_len values, so one executable serves every k <= spec_k
        self.spec_k = int(spec_decode_k if spec_decode_k is not None
                          else getattr(cfg, "spec_decode_k", 0) or 0)
        if self.spec_k < 0:
            raise ValueError(f"spec_decode_k must be >= 0, got "
                             f"{self.spec_k}")
        if self.spec_k and self.spec_k >= self.chunk:
            raise ValueError(
                f"spec_decode_k {self.spec_k} needs 1 + k <= chunk "
                f"{self.chunk} (verify rows ride the per-slot chunk "
                "block)")
        self.token_budget = int(
            token_budget
            or (self.max_batch * (1 + self.spec_k) + self.chunk))
        with phase("step.build"):
            self._unified = build_unified_step(
                cfg, self.cache.page_size, self.chunk, use_kernel=use_kernel,
                kv_quant=self.kv_quant, mesh=self.mesh, spec_k=self.spec_k)
        # the row counts that one program runs at: it takes the smallest that
        # holds the rows a step packs, by this same function
        self._row_ladder = step_row_ladder(
            self.max_batch, self.spec_k, self.chunk, self.token_budget)
        # what a scheduled lane's rows and context cost the step's attention
        # kernel in grid steps, by the kernel module's own function (per chip
        # under a mesh)
        if self.latent:
            from ..ops.pallas.mla_paged_attention import (tile_for_heads,
                                                          tile_grid)

            self._attn_grid = tile_grid(
                self.max_batch, self.token_budget, self.cache.pages_per_slot,
                self.cache.page_size, tile_for_heads(cfg.num_heads))
        else:
            from ..ops.pallas.paged_attention import (lane_block_rows,
                                                      ragged_grid)

            mp = self.mesh.shape["mp"] if self.mesh is not None else 1
            self._attn_grid = ragged_grid(
                self.max_batch, self.cache.pages_per_slot, self.chunk,
                cfg.num_heads // mp, kv_heads // mp, self.cache.page_size,
                cfg.head_dim, "int8" if self.kv_quant else kv_dtype,
                kv_dtype)
        self._m_attn_live = self.metrics.counter(
            "serving_attn_blocks_live",
            "grid steps of the step's paged attention kernel that hold "
            "keys a scheduled lane's rows see, summed over dispatched steps")
        self._m_attn_grid = self.metrics.counter(
            "serving_attn_blocks_grid",
            "grid steps a call of the step's paged attention kernel "
            "launches, summed over dispatched steps")
        if self.windowed:
            # a call a layer, of two kinds: the counters above then sum a
            # step's calls, a window layer's steps from its first live block
            # (in its own table's coordinates)
            # (``lane_block_rows``: a grid pair a rung)
            def grids(rung):
                rows = lane_block_rows(self.chunk, rung, cfg.num_heads,
                                       kv_heads)
                return tuple(ragged_grid(
                    self.max_batch, pps, rows, cfg.num_heads, kv_heads,
                    self.cache.page_size, cfg.head_dim, kv_dtype, kv_dtype)
                    for pps in (self.cache.pages_per_slot,
                                self.cache.window.pages_per_slot))

            self._attn_grids = {rung: grids(rung)
                                for rung in self._row_ladder}
            self._attn_grid, self._attn_grid_window = grids(
                self._row_ladder[-1])
            self._m_window_read = self.metrics.counter(
                "serving_window_keys_read",
                "keys attention read: per scheduled row and attention layer, "
                "the keys its mask admits (a window layer: at most the "
                "window)")
            self._m_window_context = self.metrics.counter(
                "serving_window_keys_context",
                "keys attention would read with no window: per scheduled row "
                "and attention layer, its context")
        # learned sparse attention: per scheduled row, the keys its indexer
        # scored, the keys its attention read and the keys it would have
        # read without a selection, each summed over the layers that do it
        # (by the kernels' own count, ``dsa_index.keys_of``)
        self.last_selected = None
        if self.sparse:
            from ..ops.pallas.dsa_index import keys_of

            self._dsa_keys_of = keys_of
            self._m_dsa_scored = self.metrics.counter(
                "serving_dsa_keys_scored",
                "keys the indexer scored: per scheduled row and layer with "
                "an indexer, the row's context")
            self._m_dsa_selected = self.metrics.counter(
                "serving_dsa_keys_selected",
                "keys attention read: per scheduled row and attention "
                "layer, min(context, index_topk)")
            self._m_dsa_context = self.metrics.counter(
                "serving_dsa_keys_context",
                "keys attention would read with no selection: per "
                "scheduled row and attention layer, its context")
        # round 19: the draft SOURCE behind spec_decode_k — "ngram" (the
        # round-12 prompt-lookup table) or "model" (the truncated-layer
        # self-draft: ModelDraftEngine runs the first draft_layers layers
        # of the SAME param stacks over a dedicated draft KV pool and
        # proposes k tokens per decode lane in one device-chained pass
        # per round). Defaults follow the config: spec_draft_layers > 0
        # selects the model source.
        self.draft_layers = int(
            draft_layers if draft_layers is not None
            else getattr(cfg, "spec_draft_layers", 0) or 0)
        if draft_source is None:
            draft_source = ("model" if (self.spec_k and self.draft_layers)
                            else "ngram")
        if draft_source not in ("ngram", "model"):
            raise ValueError(f"draft_source must be 'ngram' or 'model', "
                             f"got {draft_source!r}")
        self.draft_source = draft_source
        self._draft_engine = None
        if self.draft_source == "model":
            if not self.spec_k:
                raise ValueError(
                    "draft_source='model' needs spec_decode_k > 0 "
                    "(there is nothing to draft)")
            from .draft import ModelDraftEngine

            # draft_config inside the engine rejects draft_layers < 1
            # and >= num_layers loudly AT CONSTRUCTION
            self._draft_engine = ModelDraftEngine(
                cfg, params_unsharded, self.draft_layers,
                page_size=self.cache.page_size, chunk=self.chunk,
                max_batch=self.max_batch, max_seq_len=self.max_seq_len,
                num_pages=draft_num_pages, use_kernel=use_kernel,
                kv_quant=self.kv_quant, mesh=self.mesh,
                on_launch=self._note_draft_launch,
                # round 22: pin the fused chain's build geometry to the
                # predictor's spec_k (one executable for every round)
                max_k=self.spec_k)
        # round 13: the async double-buffered engine — dispatch-ahead on
        # the unified step's device-resident token feedback; the sync
        # engine is the same pack/capacity code at pipeline depth zero.
        # round 14: async is the DEFAULT (PR 8 soaked: greedy bit-identical
        # + seeded stream-identical to sync); pass async_engine=False for
        # the explicit sync baseline
        self.async_engine = True if async_engine is None else bool(
            async_engine)
        self.max_inflight_steps = max(1, int(max_inflight_steps))
        self._inflight: deque[_Pending] = deque()
        self._did_sync = False   # set by _reconcile_one, charged per call
        self.waiting: deque[Request] = deque()
        self.running: dict[int, Request] = {}   # slot -> request
        self._next_token = np.zeros((self.max_batch,), np.int32)
        self._no_cow = jnp.full((self.max_batch,), self.cache.num_pages,
                                jnp.int32)
        # feedback plumbing: the carry chains device-side step to step in
        # the async engine; the sync engine pins the all-zero constants
        # (no per-step upload, the in-jit where() degenerates to identity)
        self._no_feedback = jnp.zeros((self.token_budget,), jnp.int32)
        self._zero_prev = jnp.zeros((self.max_batch,), jnp.int32)
        if self.mesh is not None:
            # the carry comes back replicated over the mesh; its first-step
            # stand-in must be placed the same way, or the second call shows
            # jit a new input sharding and the step is traced twice
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            self._zero_prev = jax.device_put(
                self._zero_prev, NamedSharding(self.mesh, PartitionSpec()))
        self._carry = None       # device next_toks of the LAST dispatch
        # per-lane base PRNG keys ([b, 2], content-cached upload: rows
        # only change on admission) — the in-jit fold keys row j by
        # tokens-produced (+ j under speculation)
        self._lane_keys = np.zeros((self.max_batch, 2), np.uint32)
        # slowly-changing host arrays -> cached device uploads
        self._feed_cache: dict[str, tuple[np.ndarray, object]] = {}
        # steady-decode pack cache (async): previous step's device arrays
        # re-served while the schedule signature holds
        self._steady: dict | None = None
        self._base_keys: dict[int, np.ndarray] = {}   # req_id -> PRNGKey
        # perf accounting (bench_serve step_gap_frac / host_ms_per_step):
        # wall-clock intervals with NO dispatched-unmaterialized step are
        # the host-observable upper bound on device idle between steps;
        # the accumulated durations live on the registry, the window marks
        # (reset_perf_stats) stay plain timestamps
        self._span_start = None
        self._last_event = None
        self._idle_since = None
        self._w_marks = {"step_s": 0.0, "sync_s": 0.0, "gap_s": 0.0,
                         "calls": 0.0, "draft_s": 0.0}
        # round 17: resilience knobs — SLO-aware admission control (off
        # when slo is None), bounded step retry + exponential backoff,
        # and the deadline sweep (armed lazily by the first deadlined
        # request so the disarmed path pays one bool check)
        if slo is not None and not isinstance(slo, SLOConfig):
            raise ValueError(f"slo must be an SLOConfig or None, "
                             f"got {type(slo).__name__}")
        self.slo = slo
        # round 18: fleet identity + liveness stamp — ``replica_id``
        # names this predictor in a fleet's healthz feeds, and
        # ``_last_round_end`` (bumped every completed step()/flush()
        # round) is the monotonic progress mark behind healthz's
        # ``snapshot_age_s``: a STUCK replica's age grows while a merely
        # QUIET one, still being driven, keeps stamping fresh snapshots
        self.replica_id = int(replica_id)
        if self.replica_id < 0:
            raise ValueError(f"replica_id must be >= 0, got {replica_id}")
        # round 20: disaggregation identity — the fleet role this
        # predictor plays ("prefill" runs prompts and streams KV pages
        # out; "decode" receives pages and serves the decode phase;
        # "colocated" is the single-role default — the predictor itself
        # behaves identically in all three, the label steers the fleet
        # router) and the sender-side transfer backlog (unacked KV-page
        # frames originating here, stamped by the router's transfer
        # drive) the healthz surface exposes for role-aware scoring
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(f"role must be 'colocated', 'prefill' or "
                             f"'decode', got {role!r}")
        self.role = role
        self.transfer_backlog = 0
        self._last_round_end = monotonic()
        self.max_step_retries = int(max_step_retries)
        if self.max_step_retries < 0:
            raise ValueError(f"max_step_retries must be >= 0, "
                             f"got {max_step_retries}")
        self.retry_backoff_s = float(retry_backoff_s)
        self._deadlines_armed = False
        self._consec_failures = 0
        self._ttft_ema_ms: float | None = None
        # round 19: predictor-level draft-acceptance EMA (healthz exposes
        # it so the fleet router can score spec-effective replicas)
        self._accept_ema: float | None = None
        # req_id -> DraftProposer (kept across preemption — the request's
        # context replays identically, so the table stays consistent)
        self._drafts: dict[int, object] = {}
        # req_id -> recorder generation of its recorded lane 'b' (tracing
        # only): a lane is OPEN iff its generation matches the recorder's
        # CURRENT one — a window clear discards recorded begins, so a
        # stale entry means "re-open before emitting" (each RECORD window
        # must be self-consistent: no 'n'/'e' without an in-window 'b')
        self._traced_reqs: dict[int, int] = {}

    def _init_instruments(self):
        """Declare this predictor's registry instruments (round 15). The
        names are the snapshot/telemetry schema ARCHITECTURE.md documents;
        the back-compat properties below read them."""
        m = self.metrics
        self._m_steps = m.counter(
            "serving_steps", "scheduler rounds that dispatched a step")
        self._m_step_calls = m.counter(
            "serving_step_calls", "step() invocations (perf-window unit)")
        self._m_tokens = m.counter(
            "serving_tokens_emitted", "tokens emitted, all paths")
        self._m_hard_syncs = m.counter(
            "serving_hard_syncs", "step()/flush() calls that materialized")
        self._m_steady = m.counter(
            "serving_steady_hits", "async steady-decode pack-cache hits")
        self._m_preempt = m.counter(
            "serving_preemptions", "requests preempted back to the queue")
        self._m_admitted = m.counter(
            "serving_requests_admitted", "admissions incl. replay")
        self._m_finished = m.counter(
            "serving_requests_finished", "requests reaching FINISHED")
        self._m_step_s = m.counter(
            "serving_step_seconds", "host wall seconds inside step()/flush()")
        self._m_sync_s = m.counter(
            "serving_sync_seconds", "seconds blocked materializing outputs")
        self._m_gap_s = m.counter(
            "serving_gap_seconds", "wall seconds with no step in flight")
        self._m_ttft = m.histogram(
            "serving_ttft_ms", "submit -> first generated token",
            buckets=(1, 2, 5, 10, 25, 50, 100, 250, 1000, 5000))
        self._m_rows_prefill = m.counter(
            "serving_rows_prefill",
            "real rows fed by lanes still feeding known context")
        self._m_rows_decode = m.counter(
            "serving_rows_decode",
            "real rows fed by decode lanes (drafts included)")
        self._m_rows_run = m.counter(
            "serving_rows_run",
            "rows the step program ran: per dispatched step the rung of its "
            "row ladder that holds the rows packed, by rung",
            labels=("rung",))
        self._m_queue_wait = m.histogram(
            "serving_queue_wait_ms", "submit -> first admission",
            buckets=(1, 10, 100, 1000, 10000, 100000))
        self._m_prefill = m.histogram(
            "serving_prefill_ms",
            "first admission -> dispatch of the prompt's last chunk",
            buckets=(1, 10, 100, 1000, 10000, 100000))
        self._m_reconcile_lag = m.histogram(
            "serving_reconcile_lag_steps",
            "steps dispatched between an entry's dispatch and its reconcile",
            buckets=(0, 1, 2, 4, 8, 16, 64))
        self._m_inflight = m.gauge(
            "serving_inflight_depth", "dispatched-unreconciled steps")
        self._m_running = m.gauge(
            "serving_running_lanes", "slots in RUNNING after a step")
        self._m_waiting = m.gauge(
            "serving_waiting_requests", "queued requests after a step")
        # speculative decoding: per completing DECODE lane-step
        self._m_spec_lane_steps = m.counter(
            "serving_spec_lane_steps", "decode lane-steps while spec is on")
        self._m_spec_emitted = m.counter(
            "serving_spec_tokens_emitted", "tokens emitted by spec lanes")
        self._m_draft_proposed = m.counter(
            "serving_draft_proposed", "draft tokens proposed")
        self._m_draft_accepted = m.counter(
            "serving_draft_accepted", "draft tokens accepted by verify")
        self._m_draft_rollback = m.counter(
            "serving_draft_rollback_pages", "over-allocated pages trimmed")
        # round 19: the model-based draft source + async x spec
        self._m_draft_model_steps = m.counter(
            "serving_draft_model_steps",
            "draft-model jit launches (catch-up chunks + chain steps)")
        self._m_draft_src = m.counter(
            "serving_draft_tokens_proposed",
            "draft tokens proposed, by source", labels=("source",))
        self._m_spec_deferred = m.counter(
            "serving_spec_async_deferred_steps",
            "spec-build dispatches reconciled behind-by-one or deferred")
        self._m_draft_s = m.counter(
            "serving_draft_seconds",
            "host wall seconds inside the draft-model proposal pass")
        # round 17: resilience — shed / deadline / fault / retry counters
        self._m_failed = m.counter(
            "serving_requests_failed", "requests reaching terminal FAILED")
        self._m_fail_reasons = m.counter(
            "serving_fail_reasons", "terminal failures by error code",
            labels=("reason",))
        self._m_shed = m.counter(
            "serving_requests_shed", "admissions shed by the SLO policy")
        self._m_deadline = m.counter(
            "serving_deadline_misses", "requests failed past their deadline")
        self._m_step_failures = m.counter(
            "serving_step_failures", "pack/dispatch/reconcile exceptions")
        self._m_retries = m.counter(
            "serving_step_retries", "lane requeues after a failed step")
        self._m_faults = m.counter(
            "serving_faults_injected", "injected faults observed, by seam",
            labels=("seam",))

    # -- back-compat metric reads (pre-round-15 attribute surface) ---------

    @property
    def steps(self) -> int:
        return int(self._m_steps.value)

    @property
    def tokens_emitted(self) -> int:
        return int(self._m_tokens.value)

    @property
    def hard_syncs(self) -> int:
        return int(self._m_hard_syncs.value)

    @property
    def steady_hits(self) -> int:
        return int(self._m_steady.value)

    @property
    def spec_lane_steps(self) -> int:
        return int(self._m_spec_lane_steps.value)

    @property
    def spec_emitted(self) -> int:
        return int(self._m_spec_emitted.value)

    @property
    def spec_proposed(self) -> int:
        return int(self._m_draft_proposed.value)

    @property
    def spec_accepted(self) -> int:
        return int(self._m_draft_accepted.value)

    def telemetry(self) -> dict[str, float]:
        """Flat snapshot of the serving-stack registry (predictor + KV
        cache instruments) — the ``telemetry`` sub-object bench_serve
        rides on its JSON lines — merged with the process registry's
        (``observability.process_registry``): time to ready by set-up phase,
        and jax's traces, lowerings and compiles by function, so that
        ``jax_lowerings`` read after ready is a recompile alarm."""
        return merge_snapshots(self.metrics.snapshot_flat(),
                               process_registry.snapshot_flat())

    # -- queue API ---------------------------------------------------------

    def add_request(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
                    temperature=0.0, top_k=0, top_p=1.0, seed=None,
                    deadline_s=None, submit_time=None,
                    sample_offset=0) -> Request:
        req = Request(prompt_ids, max_new_tokens, eos_token_id,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      seed=seed, deadline_s=deadline_s,
                      submit_time=submit_time, sample_offset=sample_offset)
        if len(req.prompt_ids) > self.max_seq_len:
            raise ValueError(
                f"prompt of {len(req.prompt_ids)} tokens exceeds "
                f"max_seq_len {self.max_seq_len}")
        if self.slo is not None:
            verdict = self.admission_verdict()
            if verdict is not None:
                # shed: the request comes back terminal FAILED with a
                # loud error record instead of queueing into an overload
                self._m_shed.inc()
                self._fail(req, "shed_" + verdict,
                           f"admission shed under load ({verdict}): "
                           f"{len(self.waiting)} waiting, "
                           f"{len(self.running)} running")
                return req
        if req.deadline_s is not None:
            self._deadlines_armed = True
        self.waiting.append(req)
        return req

    # -- round 17: load-signal surface (the fleet router's view) -----------

    @property
    def pool_occupancy(self) -> float:
        """Claimed fraction of the KV page pool (evictable prefix-LRU
        pages count as available)."""
        cache = self.cache
        return 1.0 - cache.available_page_count / max(1, cache.num_pages)

    @property
    def ttft_p99_ema_ms(self) -> float:
        """EMA over the TTFT histogram's p99 estimate (0.0 before the
        first token) — the SLO shedding signal."""
        return 0.0 if self._ttft_ema_ms is None else self._ttft_ema_ms

    def admission_verdict(self) -> str | None:
        """Would :meth:`add_request` shed right now? ``None`` admits;
        otherwise the shed reason (``queue_full`` / ``pool_pressure`` /
        ``inflight_depth`` / ``ttft_slo``). Pure read — the fleet router
        polls this (and :meth:`healthz`) to steer traffic before paying
        a request submission."""
        slo = self.slo
        if slo is None:
            return None
        if (slo.max_waiting is not None
                and len(self.waiting) >= slo.max_waiting):
            return "queue_full"
        # backlog-gated signals: a full pool with an empty queue is the
        # healthy steady state of a saturated batch, not an overload
        if self.waiting:
            if (slo.max_pool_occupancy is not None
                    and self.pool_occupancy >= slo.max_pool_occupancy):
                return "pool_pressure"
            if (slo.max_inflight_depth is not None
                    and len(self._inflight) >= slo.max_inflight_depth):
                return "inflight_depth"
            if (slo.ttft_p99_slo_ms is not None
                    and self.ttft_p99_ema_ms > slo.ttft_p99_slo_ms):
                return "ttft_slo"
        return None

    def healthz(self) -> dict:
        """One JSON-able health/load snapshot — the per-predictor surface
        the fleet router consumes (schema locked by
        tests/test_observability.py)."""
        verdict = self.admission_verdict()
        cache = self.cache
        return {
            "status": "shedding" if verdict is not None else "ok",
            "shed_reason": verdict,
            # round 18: fleet identity + staleness — seconds since the
            # last COMPLETED scheduler round; a router distinguishes a
            # stale/stuck replica (age grows without bound) from a quiet
            # one (its driver keeps stepping it, age stays small)
            "replica_id": self.replica_id,
            # round 20: the disaggregation role + the sender-side
            # unacked-frame backlog (the router's prefill-scoring and
            # drain signals)
            "role": self.role,
            "transfer_backlog": int(self.transfer_backlog),
            "snapshot_age_s": round(
                max(0.0, monotonic() - self._last_round_end), 6),
            "waiting": len(self.waiting),
            "running": len(self.running),
            "inflight_steps": len(self._inflight),
            "free_slots": cache.free_slot_count,
            "pool_occupancy": round(self.pool_occupancy, 4),
            "withheld_pages": cache.withheld_page_count,
            # round 21: the host tier under the HBM pool — byte-budget
            # occupancy (0.0 when no tier) + absolute bytes resident
            "host_tier_occupancy": round(cache.host_tier_occupancy, 4),
            "host_tier_bytes": int(cache.host_tier_bytes_used),
            "ttft_p99_ema_ms": round(self.ttft_p99_ema_ms, 3),
            # round 19: the draft-acceptance EMA — a router scoring
            # replicas can prefer ones whose speculation is paying off
            "spec_accept_ema": round(self.spec_accept_ema, 4),
            "steps": self.steps,
            "tokens_emitted": self.tokens_emitted,
            "requests_shed": int(self._m_shed.value),
            "deadline_misses": int(self._m_deadline.value),
            "requests_failed": int(self._m_failed.value),
            "step_failures": int(self._m_step_failures.value),
            "step_retries": int(self._m_retries.value),
        }

    @property
    def decode_trace_count(self) -> int:
        """Times the serving step has been (re)traced — the no-retrace
        gate asserts this stays constant after warmup."""
        return self._unified.trace_count[0]

    @property
    def prefix_hit_rate(self) -> float:
        return self.cache.prefix_hit_rate

    @property
    def accepted_tokens_per_step(self) -> float:
        """Tokens emitted per completing decode lane-step — the
        speculation multiplier (1.0 = plain decode: one token per lane
        per step; > 1.0 = accepted drafts amortizing each weight-read
        over multiple tokens)."""
        if not self.spec_lane_steps:
            return 1.0
        return self.spec_emitted / self.spec_lane_steps

    @property
    def draft_acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the verify pass accepted."""
        if not self.spec_proposed:
            return 0.0
        return self.spec_accepted / self.spec_proposed

    @property
    def draft_overhead_frac(self) -> float:
        """Fraction of the measured window's step() wall time spent in
        the draft-model proposal pass (0.0 for the n-gram source — its
        table lookups are noise) — what the model drafter costs against
        the accepted tokens it buys."""
        step = self._window("step_s", self._m_step_s)
        if step <= 0:
            return 0.0
        return min(1.0, self._window("draft_s", self._m_draft_s) / step)

    @property
    def spec_accept_ema(self) -> float:
        """EMA over per-step draft acceptance fractions (0.0 before any
        drafted step) — the healthz signal a fleet router scores
        spec-effective replicas by."""
        return 0.0 if self._accept_ema is None else self._accept_ema

    def _note_draft_launch(self) -> None:
        """One draft-engine jit launch: counted, and marked as a dispatch
        so the gap accounting knows the device has draft work (the chain
        runs while the host packs the verify step around it)."""
        self._m_draft_model_steps.inc()
        self._mark_dispatch()

    # -- perf accounting (the round-13 bench metrics) ----------------------

    def _mark_dispatch(self) -> None:
        """A step was dispatched: any interval since the pipeline last
        drained was a host-side bubble the device could not fill."""
        now = monotonic()
        if self._span_start is None:
            self._span_start = now
        if self._idle_since is not None:
            self._m_gap_s.inc(now - self._idle_since)
            self._idle_since = None
        self._last_event = now

    def _mark_drained(self) -> None:
        """No dispatched-unmaterialized work remains: the device has
        nothing of ours to run until the next dispatch."""
        now = monotonic()
        self._idle_since = now
        self._last_event = now

    def _window(self, key: str, counter) -> float:
        """A duration counter's accumulation since the last
        :meth:`reset_perf_stats` (the bench measurement window)."""
        return max(0.0, counter.value - self._w_marks[key])

    @property
    def step_gap_frac(self) -> float:
        """Fraction of the measured window with NO step in flight — the
        host-observable upper bound on the device-idle gap between steps
        (the sync engine's pack/bookkeeping bubble; ~0 for the async
        engine, which always has the next step dispatched before it
        materializes the previous one). Window starts at the first
        dispatch after :meth:`reset_perf_stats`."""
        if self._span_start is None or self._last_event is None:
            return 0.0
        window = self._last_event - self._span_start
        if window <= 0:
            return 0.0
        return min(1.0, self._window("gap_s", self._m_gap_s) / window)

    @property
    def host_ms_per_step(self) -> float:
        """Host milliseconds spent per ``step()`` OUTSIDE the blocking
        device waits — the scheduling/bookkeeping cost the async engine
        overlaps with device execution."""
        calls = self._window("calls", self._m_step_calls)
        if not calls:
            return 0.0
        busy = (self._window("step_s", self._m_step_s)
                - self._window("sync_s", self._m_sync_s))
        return max(0.0, busy * 1e3 / calls)

    def reset_perf_stats(self) -> None:
        """Start a fresh measurement window (bench: call after warmup).
        The registry counters are monotonic; the window is their delta
        against the marks taken here."""
        self._span_start = None
        self._last_event = None
        self._idle_since = None if self._inflight else monotonic()
        if self._idle_since is not None:
            self._span_start = self._idle_since
            self._last_event = self._idle_since
        self._w_marks = {"step_s": self._m_step_s.value,
                         "sync_s": self._m_sync_s.value,
                         "gap_s": self._m_gap_s.value,
                         "calls": self._m_step_calls.value,
                         "draft_s": self._m_draft_s.value}

    # -- shared scheduler internals ----------------------------------------

    def _preempt_youngest(self) -> bool:
        """Free the youngest running request back to the waiting queue."""
        if not self.running:
            return False
        slot = max(self.running,
                   key=lambda s: self.running[s].req_id)
        req = self.running.pop(slot)
        self.cache.free(slot)
        req.state = WAITING
        req.preempt_count += 1
        req._registered = False   # fresh pages on replay; re-register
        self.waiting.appendleft(req)
        self._m_preempt.inc()
        self._req_event(req.req_id, "preempt",
                        args={"count": req.preempt_count})
        return True

    def _close_request(self, req: Request, event: str, args) -> None:
        """Terminal teardown shared by BOTH terminal paths: drop
        per-request scheduler state (a retained n-gram table or PRNG key
        would leak per request over a long-lived predictor) and close the
        request's async trace lane (_req_event (re-)opens it if this
        window has no 'b' yet)."""
        self._base_keys.pop(req.req_id, None)
        self._drafts.pop(req.req_id, None)
        if self._draft_engine is not None:
            # the draft KV lane goes with the request (preemption KEEPS
            # it — the replayed context self-heals against the pool)
            self._draft_engine.release(req.req_id)
        if tracing_active():
            self._req_event(req.req_id, event, args=args)
            request_end(req.req_id)
        self._traced_reqs.pop(req.req_id, None)

    def _count_finished(self, req: Request) -> None:
        """Increment the finished counter once per request, and only once
        its emissions are VALUE-final (no dispatched-unmaterialized
        tokens): a count-finished request whose final tokens are lost
        with a dropped ring entry re-opens for replay, and its eventual
        terminal state may be FAILED — counting early would make
        finished + failed overshoot the requests submitted."""
        if not req._finish_counted and req._pending_n == 0:
            req._finish_counted = True
            self._m_finished.inc()

    def _finish(self, req: Request) -> None:
        """Mark FINISHED — EVERY finish path must come through here."""
        req.state = FINISHED
        self._count_finished(req)
        self._close_request(req, "eos" if not req.truncated
                            else "truncated",
                            {"outputs": len(req.output_ids)})

    def _fail(self, req: Request, code: str, message) -> None:
        """Terminal FAILED with a loud error record — EVERY failure path
        (shed, deadline, never-admittable, retry-exhausted, stuck) comes
        through here; the predictor keeps serving everyone else. The
        caller releases any slot/pages the request held FIRST."""
        req.state = FAILED
        req.error = {"code": code, "message": str(message)[:300]}
        self._m_failed.inc()
        self._m_fail_reasons.labels(reason=code).inc()
        self._close_request(req, "failed", dict(req.error))

    def _retire_finished(self) -> None:
        for slot in [s for s, r in self.running.items() if r.done]:
            req = self.running.pop(slot)
            self.cache.free(slot)
            self._finish(req)

    def _finish_waiting_unservable(self, req: Request) -> bool:
        """Queue-head checks shared by both admission paths. Returns True
        when the request was consumed (finished) off the queue."""
        if req.done:
            # finished while waiting (e.g. budget satisfied by its prefill
            # token before a preemption parked it)
            self.waiting.popleft()
            self._finish(req)
            return True
        if req._ctx_len > self.max_seq_len:
            # preempted while sitting AT the length ceiling (its own
            # truncation check never ran that round): finish it as
            # truncated, same as the in-loop ceiling stop
            self.waiting.popleft()
            req.truncated = True
            self._finish(req)
            return True
        return False

    def _fail_never_admittable(self, req: Request, need: int) -> None:
        """A context that can NEVER fit the pool fails individually (loud
        error record) instead of poisoning the predictor for everyone
        (the pre-round-17 behavior raised out of step()). The caller has
        already popped ``req`` off the waiting queue."""
        self._fail(req, "never_admittable",
                   f"context of {len(req._context_ids())} tokens needs "
                   f"{need} pages but the pool only has "
                   f"{self.cache.num_pages} — raise num_pages or "
                   "page_size")

    def _shed_expired(self) -> None:
        """The deadline sweep (one scheduler round granularity): expired
        WAITING requests shed off the queue (the queue TTL); RUNNING
        requests past deadline retire — both terminal FAILED
        ``deadline_exceeded``. Runs only once a deadlined request has
        ever been submitted."""
        now = monotonic()
        if any(r.deadline_s is not None for r in self.waiting):
            keep: deque[Request] = deque()
            while self.waiting:
                req = self.waiting.popleft()
                if req.past_deadline(now):
                    self._m_deadline.inc()
                    self._fail(req, "deadline_exceeded",
                               f"queued past its {req.deadline_s}s "
                               "deadline")
                else:
                    keep.append(req)
            self.waiting = keep
        for slot in [s for s, r in self.running.items()
                     if r.past_deadline(now)]:
            req = self.running.pop(slot)
            self.cache.free(slot)
            self._m_deadline.inc()
            self._fail(req, "deadline_exceeded",
                       f"still running past its {req.deadline_s}s "
                       f"deadline with {len(req.output_ids)} tokens out")

    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self._inflight)

    # -- unified path ------------------------------------------------------

    def _admit_one_unified(self, req: Request) -> bool:
        """Claim a slot + pages (prefix-cache hits attach shared pages);
        the context feeds through chunks in subsequent steps."""
        # vLLM-style watermark: with other sequences running, keep one
        # free page of growth headroom — an exactly-fitting admission
        # would be preempted (its prefill work discarded) by the same
        # step's growth pass
        headroom = 1 if self.running else 0
        hit = self.cache.admit_prefix(req._context_ids(),
                                      headroom=headroom, soft=True)
        if hit is None:
            return False
        slot, cached = hit
        req.cached_prefix_len = cached
        req.state = RUNNING
        self.running[slot] = req
        self._note_admit(req, slot, cached)
        return True

    def _note_admit(self, req, slot, cached) -> None:
        """Telemetry for one (re-)admission: counter + the request's
        async trace lane ('b' once per window; replays get an instant)."""
        self._m_admitted.inc()
        if req.admit_time is None:
            req.admit_time = monotonic()
            self._m_queue_wait.observe(
                (req.admit_time - req.submit_time) * 1e3)
        if not tracing_active():
            return
        already_open = self._lane_open(req.req_id)
        self._req_event(req.req_id, "readmit" if already_open else "admit",
                        args={"slot": slot, "cached_prefix": int(cached)})

    def _lane_open(self, req_id) -> bool:
        return self._traced_reqs.get(req_id) == _recorder.generation

    def _req_event(self, req_id, name, args=None) -> None:
        """An instant on one request's trace lane. The lane must be open
        IN THE CURRENT RECORDER WINDOW — a 'b' recorded before a window
        clear is gone from the buffer, and an 'n'/'e' without its 'b'
        renders as an unmatched phase — so a stale (or absent) lane is
        (re-)opened here first: every window's trace is self-consistent
        and a request spanning windows appears in each of them."""
        if not tracing_active():
            return
        if not self._lane_open(req_id):
            if request_begin(req_id, args={"req_id": req_id}):
                self._traced_reqs[req_id] = _recorder.generation
        request_event(req_id, name, args=args)

    def _admit_waiting_unified(self) -> None:
        while self.waiting and self.cache.free_slot_count:
            req = self.waiting[0]
            if self._finish_waiting_unservable(req):
                continue
            if not self._admit_one_unified(req):
                # head-of-line blocking keeps FIFO order — but if nothing
                # is running and the whole pool is free, this request can
                # NEVER fit: fail IT (not the predictor) with the real
                # cause and keep admitting behind it
                if (not self.running and self.cache.available_page_count
                        == self.cache.num_pages):
                    self.waiting.popleft()
                    self._fail_never_admittable(
                        req, self.cache.pages_needed(
                            len(req._context_ids())))
                    continue
                break
            self.waiting.popleft()

    def _req_key(self, req: Request) -> np.ndarray:
        """Per-request base PRNG key; the per-token key folds in the count
        of tokens produced, so a preemption replay re-samples the same
        stream."""
        hit = self._base_keys.get(req.req_id)
        if hit is None:
            import jax

            hit = np.asarray(jax.random.PRNGKey(req.seed), np.uint32)
            self._base_keys[req.req_id] = hit
        return hit

    def _proposer_for(self, req: Request):
        """The request's draft proposer (created on first use; persists
        across preemption replay so the adaptive-k EMA AND the cooldown
        re-probe state survive — round-19 satellite: a replay must resume
        the backoff where it left off, not restart from the floor)."""
        prop = self._drafts.get(req.req_id)
        if prop is None:
            from .draft import DraftProposer, ModelDraftProposer

            if self._draft_engine is not None:
                prop = ModelDraftProposer(self.spec_k, self._draft_engine,
                                          req.req_id)
            else:
                prop = DraftProposer(self.spec_k)
            self._drafts[req.req_id] = prop
        return prop

    def _proposer_k(self, req: Request) -> int:
        """The lane's CURRENT adaptive speculation length without
        creating a proposer (a fresh request starts optimistic at the
        build k)."""
        prop = self._drafts.get(req.req_id)
        return prop.k if prop is not None else self.spec_k

    def _draft_room(self, slot, req, budget_room: int) -> int:
        """The per-lane draft clamp shared by both sources: the token
        budget, the per-slot chunk block, the request's remaining output
        budget, the length ceiling, and — via ``draft_allowance`` — pages
        claimable WITHOUT evicting prefix pages or preempting anyone
        (rejected drafts must cost nothing). Re-checked at claim time in
        the capacity loop; this propose-time clamp only avoids wasted
        draft work."""
        written = self.cache.seq_len(slot)
        return min(budget_room, self._proposer_k(req), self.chunk - 1,
                   req.max_new_tokens - len(req.output_ids) - 1,
                   self.max_seq_len - written - 1,
                   self.cache.draft_allowance(slot))

    def _draft_propose(self, slot, req, budget_room: int) -> list:
        """N-gram drafts for one decode lane (the model source batches
        through :meth:`_propose_model_drafts` instead)."""
        prop = self._proposer_for(req)
        room = self._draft_room(slot, req, budget_room)
        return prop.propose(req._context_ids(), room) if room > 0 else []

    def _propose_model_drafts(self, decode_slots, budget: int) -> dict:
        """ONE batched draft-engine pass for every decode lane that may
        speculate this round: per-lane rooms follow the n-gram path's
        sequential budget split (each lane's base token reserved before
        anyone's drafts), then the engine catch-up + k-step chain runs
        all lanes together — k draft jit launches per ROUND, not per
        lane, with the intermediate tokens device-resident. Contexts are
        value-complete here: the round-start reconcile landed any
        in-flight token of a lane whose proposer still speculates."""
        lanes: dict[int, tuple] = {}
        n_left = len(decode_slots)
        for slot in decode_slots:
            n_left -= 1
            room = budget - 1 - n_left
            req = self.running[slot]
            self._proposer_for(req)
            r = self._draft_room(slot, req, room)
            budget -= 1
            if r > 0:
                lanes[slot] = (req.req_id, req._context_ids(), r)
                budget -= r
        if not lanes:
            return {}
        t0 = monotonic()
        try:
            return self._draft_engine.propose(lanes)
        finally:
            self._m_draft_s.inc(monotonic() - t0)

    @staticmethod
    def _merge_produced(dst: dict, src: dict) -> None:
        for rid, toks in src.items():
            dst.setdefault(rid, []).extend(toks)

    @staticmethod
    def _landed_done(req: Request) -> bool:
        """``done`` over MATERIALIZED tokens only — the emission drop
        rule. Deliberately ignores pending counts (they are what is being
        landed) and the truncation flag (a truncation decision at pack
        N+1 must not discard the legitimate token step N produced —
        matching the sync engine, where that token landed a step before
        the truncation check ran)."""
        return stream_done(req.output_ids, req.max_new_tokens,
                           req.eos_token_id)

    def _put_cached(self, name: str, arr: np.ndarray):
        """Content-keyed device-upload cache for slowly-changing per-step
        arrays (sampling params, per-lane base keys): a steady greedy
        churn re-serves the same device array with zero H2D traffic."""
        import jax

        hit = self._feed_cache.get(name)
        if hit is not None and np.array_equal(hit[0], arr):
            return hit[1]
        host = arr.copy()   # private: the caller's buffer may mutate
        dev = jax.device_put(host)
        self._feed_cache[name] = (host, dev)
        return dev

    def flush(self) -> dict[int, list[int]]:
        """Materialize every in-flight step (the async engine's OUTPUT
        FLUSH — a hard sync boundary). Returns the landed tokens merged
        in emission order; no-op for the sync engine."""
        t0 = monotonic()
        self._did_sync = False
        try:
            with span("flush"):
                out = self._reconcile_all()
                # round 19: a drained spec advance may complete a prompt
                # whose tail page registration was one round short (the
                # behind-by-one dispatch) — finish it so post-flush state
                # matches the sync engine's exactly
                self._register_prefixes()
                return out
        finally:
            if self._did_sync:
                self._m_hard_syncs.inc()
            self._m_step_s.inc(monotonic() - t0)
            self._last_round_end = monotonic()

    def _reconcile_all(self) -> dict[int, list[int]]:
        produced: dict[int, list[int]] = {}
        # bounded by the ring depth at entry (round 17): every iteration
        # pops exactly one entry (or a failure recovery clears the ring),
        # so a drain can never spin past the work that existed when it
        # started
        for _ in range(len(self._inflight)):
            if not self._inflight:
                break
            self._merge_produced(produced, self._reconcile_one())
        assert not self._inflight, "reconcile drain left ring entries"
        return produced

    def _reconcile_one(self) -> dict[int, list[int]]:
        """Land the OLDEST in-flight step's deferred results: materialize
        its emission outputs (the hard sync), append tokens / TTFT /
        metrics, and settle the value-dependent cache accounting
        (speculative advance + rollback). Count-based accounting (page
        growth, plain advance, prefix registration) already ran at pack
        time — this is the reconcile-behind half of the contract.

        Exception-safe (round 17): a materialization failure drops the
        popped entry AND everything younger (they consumed its device
        carry), un-charges their dispatched-unmaterialized tokens, and
        requeues every affected lane through the preemption-replay path
        — see :meth:`_recover_reconcile_failure`."""
        with span("reconcile"):
            e = self._inflight.popleft()
            self._m_inflight.set(len(self._inflight))
            self._m_reconcile_lag.observe(self.steps - e.step_no)
            try:
                return self._reconcile_one_impl(e)
            except Exception as exc:
                # EVERY Exception is owned by the recovery (a host-side
                # code bug is indistinguishable from a device fault here;
                # the bounded retry keeps either from looping forever and
                # the error record carries repr(exc) for attribution)
                self._recover_reconcile_failure(e, exc)
                return {}

    def _note_expert_rows(self) -> None:
        """Feed the routed-expert counters from the row counts of the steps
        dispatched so far. Called right after a reconcile materialized a
        step's tokens: every count but those of the steps still in flight
        behind it is complete by then, and those wait for their turn."""
        ready = len(self._moe_unread) - len(self._inflight)
        if ready <= 0:
            return
        rows, fed = np.sum([np.asarray(a) for a in self._moe_unread[:ready]],
                           axis=0)
        del self._moe_unread[:ready]
        if self._m_moe_elsewhere is not None:
            # what the steps' rows chose in all, less what was held here
            self._m_moe_elsewhere.inc(
                sum(self._moe_pairs_unread[:ready]) - int(rows.sum()))
            del self._moe_pairs_unread[:ready]
        self._m_moe_rows.inc(int(rows.sum()))
        self._m_moe_fed.inc(int(fed.sum()))
        for expert in np.flatnonzero(rows):
            self._m_moe_expert_rows.labels(expert=str(expert)).inc(
                int(rows[expert]))

    def _note_window_step(self, slots, contexts, fed, rung) -> None:
        """A dispatched step's attention over two kinds of layer, counted on
        the host from the lanes' lengths: the kernel's live and launched
        grid steps summed over the step's calls (a window layer's from its
        first live block, in its own table's coordinates), and the keys the
        scheduled rows read against those they would read with no window."""
        cfg, win = self.config, self.cache.window
        full_layers = cfg.num_layers - win.layers
        base = [int(win.first[s]) * win.page_size for s in slots]
        rel = [c - b for c, b in zip(contexts, base)]
        (grid, wgrid), w = self._attn_grids[rung], win.tokens
        self._m_attn_live.inc(
            full_layers * sum(map(grid.live_steps, contexts, fed))
            + win.layers * sum(wgrid.live_steps(c, n, w)
                               for c, n in zip(rel, fed)))
        self._m_attn_grid.inc(
            full_layers * grid.steps(contexts, fed)
            + win.layers * wgrid.steps(rel, fed, w))
        seen = read = 0
        for c, n in zip(contexts, fed):
            # rows at positions c - n .. c - 1 see p + 1 keys each
            seen += n * (c - n) + n * (n + 1) // 2
            read += sum(min(p + 1, w) for p in range(c - n, c))
        self._m_window_context.inc(cfg.num_layers * seen)
        self._m_window_read.inc(full_layers * seen + win.layers * read)

    def _note_first_token(self, req: Request) -> None:
        req.first_token_time = monotonic()
        self._m_ttft.observe((req.first_token_time - req.submit_time) * 1e3)
        # TTFT-p99 EMA (the round-17 shedding signal): smooth the
        # histogram's p99 estimate so one straggler neither trips nor
        # un-trips the SLO verdict on its own
        a = self.slo.ema_alpha if self.slo is not None else 0.2
        p99 = self._m_ttft.quantile(0.99)
        self._ttft_ema_ms = (p99 if self._ttft_ema_ms is None
                             else (1 - a) * self._ttft_ema_ms + a * p99)
        self._req_event(req.req_id, "first_token")

    def _reconcile_one_impl(self, e: _Pending) -> dict[int, list[int]]:
        cache = self.cache
        out = ne = None
        if e.completing:
            fault_point("reconcile")
            t0 = monotonic()
            out = np.asarray(e.out)
            if e.spec and e.spec_slots:
                # n_emit only matters when some lane actually drafted (a
                # draftless spec round emits exactly 1 per lane)
                ne = np.asarray(e.ne)
            self._m_sync_s.inc(monotonic() - t0)
            self._did_sync = True
            if self._moe_unread:
                self._note_expert_rows()
        if not self._inflight:
            self._mark_drained()
        for slot in e.spec_slots:
            # speculative lane: the context token + accepted drafts are
            # the valid K/V; rejected drafts' over-allocated pages roll
            # back to the pool (refcounts/free lists end identical to a
            # never-speculated run)
            cache.advance(slot, int(ne[slot]))
            self._m_draft_rollback.inc(cache.trim_pages(slot))
        produced: dict[int, list[int]] = {}
        for slot, req, k_i, was_decode in e.completing:
            if e.spec:
                m = int(ne[slot]) if k_i else 1
                toks = [int(x) for x in out[slot, :m]]
            else:
                toks = [int(out[slot])]
            emitted = 0
            for tok in toks:
                if req.state == FAILED or self._landed_done(req):
                    # budget/eos hit mid-batch (drop the overhang), or
                    # the request failed with tokens in flight (deadline
                    # retire): its late emissions are discarded
                    break
                req.output_ids.append(tok)
                emitted += 1
                if req.first_token_time is None:
                    self._note_first_token(req)
                produced.setdefault(req.req_id, []).append(tok)
            # the pack charged ONE pending token per completing lane
            # (plain AND spec since round 19); it just landed — a spec
            # lane's extra accepted tokens are a same-instant surplus
            req._pending_n = max(0, req._pending_n - 1)
            if req.state == FINISHED:
                # a count-finished request's deferred finished-counter
                # lands with its final token values
                self._count_finished(req)
            self._m_tokens.inc(emitted)
            if self.spec_k and was_decode:
                acc = int(ne[slot]) - 1 if k_i else 0
                self._m_spec_lane_steps.inc()
                self._m_spec_emitted.inc(emitted)
                self._m_draft_proposed.inc(k_i)
                self._m_draft_src.labels(source=self.draft_source).inc(k_i)
                self._m_draft_accepted.inc(acc)
                if k_i:
                    # predictor-level acceptance EMA (healthz surface)
                    frac = acc / k_i
                    self._accept_ema = (
                        frac if self._accept_ema is None
                        else 0.8 * self._accept_ema + 0.2 * frac)
                    self._req_event(req.req_id, "spec_accept",
                                    args={"proposed": k_i, "accepted": acc})
                prop = self._drafts.get(req.req_id)
                if prop is not None:
                    prop.update(k_i, acc)
        return produced

    # -- round 17: crash-consistent step retry -----------------------------

    def _note_step_failure(self, exc) -> None:
        self._m_step_failures.inc()
        self._consec_failures += 1
        if isinstance(exc, InjectedFault):
            self._m_faults.labels(seam=exc.seam).inc()

    def _after_failure_backoff(self) -> None:
        """Exponential backoff after a failed step (consecutive failures
        double it, capped at 1s); a successful dispatch resets it. Only
        ever runs on the failure path."""
        if self.retry_backoff_s > 0:
            time.sleep(min(
                self.retry_backoff_s * (2 ** (self._consec_failures - 1)),
                1.0))

    def _requeue_req(self, req: Request, exc, code: str) -> None:
        """THE bounded-retry policy (one site): bump the request's
        failure-requeue count, FAIL it past ``max_step_retries``,
        otherwise send it back through the value-barriered
        preemption-replay path. The caller has already released any
        slot/pages/ring charge the request held."""
        req._registered = False
        req.retry_count += 1
        if req.retry_count > self.max_step_retries:
            self._fail(req, code,
                       f"step failed {req.retry_count} times over this "
                       f"request; last: {exc!r}")
            return
        req.state = WAITING
        self._m_retries.inc()
        self._req_event(req.req_id, "retry",
                        args={"count": req.retry_count})
        self.waiting.appendleft(req)

    def _requeue_one(self, slot: int, exc,
                     code: str = "step_retry_exhausted") -> None:
        """Requeue one running lane through the preemption-replay path
        after a failed step: ``free()`` returns its growth/CoW page
        claims exactly (shared and registered pages stay pinned by their
        other references), and the replay is value-barriered and
        bit-identical. Bounded: past ``max_step_retries`` the request
        FAILS instead."""
        req = self.running.pop(slot)
        self.cache.free(slot)
        if req.done and req._pending_n == 0:
            # its landed output is already value-final (e.g. eos landed
            # at an earlier reconcile, retirement hadn't run yet): there
            # is nothing to replay — retire it instead of spending a
            # retry (or worse, a spurious terminal FAIL) on a complete,
            # correct stream
            self._finish(req)
            return
        self._requeue_req(req, exc, code)

    def _requeue_running(self, exc) -> None:
        # youngest-first appendleft leaves the queue front oldest-first
        for slot in sorted(self.running,
                           key=lambda s: -self.running[s].req_id):
            self._requeue_one(slot, exc)

    def _recover_dispatch_failure(self, exc) -> None:
        """A failure inside ``_pack_dispatch`` (pack bookkeeping, H2D
        upload, or the launch itself): the entry never entered the ring
        and nothing advanced, so the transaction rolls back by requeueing
        every running lane — page/slot/prefix claims this step made are
        returned through ``free()``. Older ring entries dispatched
        healthy and stay; the requeued lanes' pending tokens force the
        value barrier to land them before any replay admission."""
        self._note_step_failure(exc)
        self._requeue_running(exc)
        self._steady = None
        self._after_failure_backoff()

    def _recover_reconcile_failure(self, e: _Pending, exc) -> None:
        """A failure materializing in-flight entry ``e``: its token
        values are lost and every YOUNGER entry consumed its device
        carry, so the whole remaining ring is poisoned — drop it all,
        un-charge the dispatched-unmaterialized tokens each dropped
        entry charged, re-open count-finished requests whose final
        tokens were in the dropped entries, and requeue every running
        lane for bit-identical replay."""
        self._note_step_failure(exc)
        dropped = [e] + list(self._inflight)
        self._inflight.clear()
        self._moe_unread.clear()   # the dropped steps' expert rows with them
        self._m_inflight.set(0)
        reopen: dict[int, Request] = {}
        for entry in dropped:
            # round 19: spec entries charge one pending token per
            # completing lane too (behind-by-one dispatch) — un-charge
            # them exactly like plain entries
            for _slot, req, _k, _decode in entry.completing:
                req._pending_n = max(0, req._pending_n - 1)
                if req.state == FINISHED and not req.done:
                    # finished by COUNT, final token values lost with the
                    # dropped entry: back to the queue for replay
                    reopen[req.req_id] = req
                elif req.state == FINISHED:
                    # FINISHED and still done after the un-charge (eos
                    # landed earlier; the dropped token was pure
                    # overhang): its deferred finished-counter lands
                    # here — no other path will ever see it again
                    self._count_finished(req)
        self._requeue_running(exc)
        for req in reopen.values():
            # count-finished with the final token values lost: no slot
            # to free (retirement already freed it) — straight through
            # the shared bounded-retry policy
            self._requeue_req(req, exc, "step_retry_exhausted")
        self._carry = None
        self._steady = None
        self._mark_drained()
        self._after_failure_backoff()

    def _step_unified(self) -> dict[int, list[int]]:
        produced: dict[int, list[int]] = {}
        # round 19 — the behind-by-one half of async x spec: a DRAFTED
        # spec step's n_emit-variable advance/rollback (and the proposer
        # feedback + context values the next proposal depends on) must
        # land before this round schedules anything — INCLUDING the
        # deadline sweep, which frees slots the in-flight entry's
        # value-based advance still references — so a ring holding
        # drafted entries reconciles HERE, one round after its dispatch,
        # instead of inside it (the pre-round-19 hard sync). A draftless
        # spec ring defers like the plain engine and only syncs when a
        # lane that would draft again has its input token still in
        # flight (its proposal needs the value-complete context).
        if self._inflight and self.spec_k and (
                any(p.spec_slots for p in self._inflight)
                or any(r._pending_n and self._proposer_k(r) > 0
                       for r in self.running.values())):
            self._merge_produced(produced, self._reconcile_all())
            # the spec advance just landed: a lane whose final prompt
            # token rode the drained verify step can only NOW register
            # its partial tail page — complete the registration the
            # behind-by-one dispatch left one round short (idempotent),
            # BEFORE this round's admissions walk the registry (the sync
            # engine registered it last round)
            self._register_prefixes()
        if self._deadlines_armed:
            self._shed_expired()
        # value barrier: admission replays a preempted request's context
        # (token VALUES), so a waiting request with pending tokens forces
        # a full reconcile before the admission pass
        if self._inflight and any(r._pending_n for r in self.waiting):
            self._merge_produced(produced, self._reconcile_all())
        self._retire_finished()
        self._admit_waiting_unified()
        if not self.running:
            self._merge_produced(produced, self._reconcile_all())
            return produced
        with span("pack_dispatch"):
            try:
                entry = self._pack_dispatch()
            except Exception as exc:
                # transactional pack: the recovery requeues every lane
                # (claims returned exactly) and the next step() retries
                self._recover_dispatch_failure(exc)
                return produced
        if entry is None:
            self._merge_produced(produced, self._reconcile_all())
            return produced
        self._consec_failures = 0
        self._inflight.append(entry)
        self._m_inflight.set(len(self._inflight))
        self._m_steps.inc()
        entry.step_no = self.steps
        if not self.async_engine:
            # sync engine: pipeline depth zero, reconcile the step just
            # dispatched (the oracle the async engine is gated against)
            self._merge_produced(produced, self._reconcile_all())
        elif entry.spec_slots:
            # round 19: a DRAFTED spec step dispatches BEHIND-BY-ONE —
            # its value-based advance/rollback reconciles at the START
            # of the next round (see _step_unified's ring drain), so the
            # device executes the verify step while the host runs the
            # next round's bookkeeping instead of blocking right here
            # (the pre-round-19 behavior: spec forced depth zero)
            pass
        else:
            # the double-buffer contract: reconcile BEHIND-BY-ONE while
            # an emission boundary (a step whose tokens could finish a
            # request) is in the ring; steps that cannot complete
            # anything defer — up to max_inflight_steps — and drain in
            # one batched materialization later (the general
            # no-completion-possible fast path). Round 19: DRAFTLESS
            # spec-build rounds ride this path too — their emission is
            # count-deterministic (n_emit == 1), exactly a plain step
            while self._inflight and (
                    len(self._inflight) > self.max_inflight_steps
                    or (len(self._inflight) > 1
                        and any(p.must_sync
                                for p in list(self._inflight)[:-1]))):
                self._merge_produced(produced, self._reconcile_one())
        if (self.spec_k and self._inflight
                and self._inflight[-1] is entry):
            # a spec-build dispatch whose reconcile outlived this call —
            # the async x spec multiplier the round-19 bench leg gates
            self._m_spec_deferred.inc()
        self._register_prefixes()
        return produced

    def _register_prefixes(self) -> None:
        """Register prompt prefills in the prefix cache PROGRESSIVELY —
        full pages as their chunks land (a request arriving one step
        later already hits them), the partial tail once the whole prompt
        is in (its K/V writes have been issued to the device pool).
        Prompt-progress only (token counts + prompt values the host owns)
        — runs after the step's cache accounting settles."""
        cache = self.cache
        for slot, req in self.running.items():
            if req._registered:
                continue
            plen = len(req.prompt_ids)
            written = min(cache.seq_len(slot), plen)
            if written >= plen:
                cache.register_prefix(slot, req.prompt_ids)
                req._registered = True
            elif written >= cache.page_size:
                cache.register_prefix(slot, req.prompt_ids[:written],
                                      include_tail=False)

    def _pack_dispatch(self) -> _Pending | None:
        """Pack the token budget, run capacity/CoW, build the step arrays
        and DISPATCH the unified step — everything that only needs token
        COUNTS. Returns the in-flight entry (None when nothing was
        scheduled). Does not materialize any device value.

        Exception-safe (round 17): every mutation before the launch is a
        CLAIM (pages, slots, CoW copies) the caller's recovery returns
        exactly by requeueing the lanes through ``free()`` — see
        :meth:`_recover_dispatch_failure`. The named fault seams
        (``pool``/``h2d``/``slow_step``/``dispatch``) cost one
        module-global check each when no plan is armed."""
        cache = self.cache
        # -- token-budget packing: decode lanes first, then prefill chunks
        budget = self.token_budget
        sched: dict[int, int] = {}          # slot -> tokens this step
        drafts: dict[int, list] = {}        # slot -> draft tokens
        decode_slots = []
        prefill_slots = []
        for slot in sorted(self.running):
            req = self.running[slot]
            remaining = req._ctx_len - cache.seq_len(slot)
            (decode_slots if remaining == 1 else prefill_slots).append(slot)
        # round 19: the model draft source proposes every lane in ONE
        # batched engine pass (k chain launches per round, not per lane)
        model_drafts: dict[int, list] = {}
        if self.spec_k and self._draft_engine is not None and decode_slots:
            model_drafts = self._propose_model_drafts(decode_slots, budget)
        for idx, slot in enumerate(decode_slots):
            if budget <= 0:
                break
            # drafts may only spend budget left after EVERY decode lane
            # still to pack has its base token reserved — one lane's
            # speculation must not starve another lane's plain decode
            # (a tight custom token_budget would otherwise skip the same
            # trailing lanes every step)
            room = budget - 1 - (len(decode_slots) - idx - 1)
            if self._draft_engine is not None:
                d = model_drafts.get(slot, [])[:max(0, room)]
            else:
                d = (self._draft_propose(slot, self.running[slot], room)
                     if self.spec_k else [])
            if d:
                drafts[slot] = d
            sched[slot] = 1 + len(d)
            budget -= 1 + len(d)
        # prefill fills the remainder, FIFO by request age
        for slot in sorted(prefill_slots,
                           key=lambda s: self.running[s].req_id):
            if budget <= 0:
                break
            req = self.running[slot]
            remaining = req._ctx_len - cache.seq_len(slot)
            n = min(self.chunk, remaining, budget)
            if n > 0:
                sched[slot] = n
                budget -= n
        # -- capacity: ceiling stops, page growth, CoW page claims -------
        # pages every scheduled slot will claim for its PLAIN tokens
        # (chunk growth + CoW): charged against draft allowances so a
        # draft can never consume a free page a later prefill chunk in
        # this same step needs (which would push IT into LRU eviction or
        # preemption — costs a plain step never pays). Only drafted
        # steps pay the bookkeeping: its one consumer is the draft clamp
        plain_need: dict[int, int] = {}
        pending_need = 0
        if drafts:
            plain_need = {s: cache.plain_step_page_need(
                s, sched[s] - len(drafts.get(s, []))) for s in sched}
            pending_need = sum(plain_need.values())
        cows: dict[int, tuple[int, int]] = {}
        for slot in sorted(sched):
            pending_need -= plain_need.pop(slot, 0)
            if slot not in self.running:
                continue
            req = self.running[slot]
            written = cache.seq_len(slot)
            if written + 1 > self.max_seq_len:
                # length ceiling: stop NOW (truncation-stop) before any
                # write past the page-table width
                del sched[slot]
                self.running.pop(slot)
                req.truncated = True
                cache.free(slot)
                self._finish(req)
                continue
            n = min(sched[slot], self.max_seq_len - written)
            # a window group's pages that no row from here on can see
            cache.release_window(slot)
            if slot in drafts:
                # AUTHORITATIVE draft clamp, at claim time: earlier slots
                # in this loop may have consumed the free pages counted
                # at propose time, and slots still to come have their
                # plain needs reserved (pending_need) — shrink the drafts
                # (ceiling included) rather than let anyone's growth
                # evict prefix pages or preempt (costs plain decode
                # never pays)
                keep = max(0, min(len(drafts[slot]), n - 1,
                                  cache.draft_allowance(
                                      slot, reserve=pending_need)))
                if keep < len(drafts[slot]):
                    drafts[slot] = drafts[slot][:keep]
                if not drafts[slot]:
                    del drafts[slot]
                n = 1 + keep
            sched[slot] = n
            while True:
                # prepare_write ALLOCATES the copy's destination page
                # right here, so a later slot's CoW can never race this
                # one for the last page — the claim IS the reservation
                if cache.ensure_capacity(slot, written + n) and (
                        not cache.needs_cow(slot, written)
                        or cache.available_page_count >= 1):
                    cow = cache.prepare_write(slot, written)
                    if cow is not None:
                        cows[slot] = cow
                    break
                # page pressure: shed the youngest request
                victim_is_self = (max(self.running,
                                      key=lambda s: self.running[s].req_id)
                                  == slot)
                if victim_is_self and len(self.running) == 1:
                    # even with the pool to itself this sequence cannot
                    # grow (transient pressure, or a genuinely undersized
                    # pool): requeue through the bounded retry path —
                    # transient pressure heals on replay, a permanent
                    # exhaustion FAILS this one request after
                    # max_step_retries while the predictor keeps serving
                    self._requeue_one(slot, RuntimeError(
                        f"slot {slot}: cannot grow to {written + n} "
                        "tokens — page pool too small for this "
                        "sequence"), code="pool_exhausted")
                    break
                self._preempt_youngest()
                if slot not in self.running:  # preempted itself
                    break
            if slot not in self.running:
                sched.pop(slot, None)
        # a preemption may have freed slots mid-loop; drop stale schedule
        sched = {s: n for s, n in sched.items() if s in self.running}
        if not sched:
            return None
        import jax

        from ..models.gpt import step_row_rung

        b = self.max_batch
        decode_set = set(decode_slots)
        t = self.token_budget
        step_fn = self._unified
        spec_len = np.zeros((b,), np.int32)
        # -- steady-decode fast path (async only) ------------------------
        # when EVERY scheduled lane is a feedback decode lane (its input
        # token rides the device carry) and the schedule matches the
        # previous step's, the packed arrays are CONTENT-FREE on the host
        # side: tok_ids is overridden by feedback, and tok_slot / q_lens /
        # last_idx / emit_mask / feedback are unchanged — so the host
        # re-serves the previous step's device arrays and uploads only
        # the advancing positions (+ produced counts for the in-jit key
        # folds). The sync engine can never take this path: it must ship
        # the token VALUES every step.
        steady_sig = None
        if (self.async_engine and not drafts and not cows
                and all(n == 1 for n in sched.values())
                and all(self.running[s]._pending_n > 0 for s in sched)):
            steady_sig = tuple(
                (s, self.running[s].req_id) for s in sorted(sched))
        st = self._steady
        if steady_sig is not None and st is not None \
                and st["sig"] == steady_sig:
            self._m_steady.inc()
            completing = st["completing"]
            tok_pos = np.zeros((t,), np.int32)
            produced_n = np.zeros((b,), np.int32)
            for w_i, (slot, req, _, _) in enumerate(completing):
                tok_pos[w_i] = cache.seq_len(slot)
                produced_n[slot] = (req.sample_offset
                                    + len(req.output_ids)
                                    + req._pending_n)
            fault_point("h2d")
            d_pos, d_prod = jax.device_put((tok_pos, produced_n))
            d_ids, d_slot, d_qlens, d_last, d_fb, d_emit = (
                st["d_ids"], st["d_slot"], st["d_qlens"], st["d_last"],
                st["d_fb"], st["d_emit"])
            # round 19: a spec-build steady round re-serves the all-zero
            # spec_len device array too (steady implies no drafts)
            d_spec = st["d_spec"]
            d_cow_src = d_cow_dst = self._no_cow
            temp, top_k, top_p = st["temp"], st["top_k"], st["top_p"]
        else:
            cow_src = np.full((b,), self.cache.num_pages, np.int32)
            cow_dst = cow_src.copy()
            live_cows = False
            for slot, (src, dst) in cows.items():
                if slot in sched:
                    cow_src[slot], cow_dst[slot] = src, dst
                    live_cows = True
            # -- build the fixed-shape packed step arrays ----------------
            tok_ids = np.zeros((t,), np.int32)
            tok_slot = np.full((t,), -1, np.int32)
            tok_pos = np.zeros((t,), np.int32)
            feedback = np.zeros((t,), np.int32)
            last_idx = np.full((b,), t, np.int32)   # idle-lane sentinel
            q_lens = np.zeros((b,), np.int32)
            emit_mask = np.zeros((b,), np.int32)
            produced_n = np.zeros((b,), np.int32)
            temp = np.zeros((b,), np.float32)
            top_k = np.zeros((b,), np.int32)
            top_p = np.ones((b,), np.float32)
            completing = []   # (slot, req, k_i, was_decode)
            w = 0
            for slot in sorted(sched):
                n = sched[slot]
                req = self.running[slot]
                written = cache.seq_len(slot)
                ctx = req._context_ids()
                d = drafts.get(slot, [])
                # a speculating decode lane feeds its last context token
                # then its draft tokens at the following positions;
                # everyone else feeds the next n context tokens (decode
                # or prefill chunk). A decode lane whose input token is
                # still IN FLIGHT (async deferral) reads it from the
                # device-side carry instead — the host never
                # materialized it.
                if d:
                    tok_ids[w:w + n] = [ctx[written]] + d
                elif req._pending_n:
                    # pending > 0 only ever holds for pure decode lanes
                    # (prefill/replay contexts are value-barriered), and
                    # only the final context token can be pending —
                    # exactly the one token this lane feeds
                    feedback[w] = 1
                else:
                    tok_ids[w:w + n] = ctx[written:written + n]
                tok_slot[w:w + n] = slot
                tok_pos[w:w + n] = np.arange(written, written + n)
                # the row whose logits decide the lane's next token: the
                # FIRST verify row when speculating, else the last fed
                last_idx[slot] = w + n - 1 - len(d)
                spec_len[slot] = len(d)
                q_lens[slot] = n
                w += n
                if written + n - len(d) == req._ctx_len:
                    emit_mask[slot] = 1
                    produced_n[slot] = (req.sample_offset
                                        + len(req.output_ids)
                                        + req._pending_n)
                    temp[slot] = req.temperature
                    top_k[slot] = req.top_k
                    top_p[slot] = req.top_p
                    if req.temperature > 0:
                        self._lane_keys[slot] = self._req_key(req)
                    completing.append((slot, req, len(d),
                                       slot in decode_set))
            # -- batched upload -----------------------------------------
            # ONE device_put for the per-step volatile arrays (replacing
            # ~10 separate jnp.asarray transfers on the latency path);
            # sampling params and base keys ride the content-keyed cache,
            # the CoW sentinel and feedback constants never re-upload
            volatile = [tok_ids, tok_slot, tok_pos, q_lens, last_idx,
                        feedback, emit_mask, produced_n]
            if self.spec_k:
                volatile.append(spec_len)
            if live_cows:
                volatile += [cow_src, cow_dst]
            fault_point("h2d")
            dev = jax.device_put(tuple(volatile))
            (d_ids, d_slot, d_pos, d_qlens, d_last, d_fb, d_emit,
             d_prod) = dev[:8]
            rest = list(dev[8:])
            d_spec = rest.pop(0) if self.spec_k else None
            d_cow_src, d_cow_dst = ((rest[0], rest[1]) if live_cows
                                    else (self._no_cow, self._no_cow))
            # prime the steady-decode cache for the next step
            self._steady = (dict(sig=steady_sig, completing=completing,
                                 d_ids=d_ids, d_slot=d_slot,
                                 d_qlens=d_qlens, d_last=d_last,
                                 d_fb=d_fb, d_emit=d_emit, d_spec=d_spec,
                                 temp=temp, top_k=top_k, top_p=top_p)
                            if steady_sig is not None else None)
        # could any of this step's emissions FINISH a request? (the async
        # engine's sync-boundary predicate: eos configured, or the output
        # budget reachable by this emission — up to 1 + k_i tokens for a
        # drafted spec lane) — recomputed on the steady path too: the
        # output budget closes in as pending grows
        must_sync = any(
            req.eos_token_id is not None
            or len(req.output_ids) + req._pending_n + 1 + k_i
            >= req.max_new_tokens
            for _, req, k_i, _ in completing)
        prev = (self._carry
                if (self.async_engine and self._carry is not None)
                else self._zero_prev)
        head = (self.params, d_ids, d_slot, d_pos, d_qlens,
                cache.seq_lens_device(), d_last)
        if self.spec_k:
            head = head + (d_spec,)
        head = head + (d_fb, prev, d_emit, d_prod)
        tail = (cache.page_table_device(), d_cow_src, d_cow_dst,
                self._put_cached("keys", self._lane_keys),
                self._put_cached("temp", temp),
                self._put_cached("top_k", top_k),
                self._put_cached("top_p", top_p))
        if self.windowed:
            tail += cache.window.device()
        pools = cache.pools()
        # per-lane trace instants on the request lanes (tracing only):
        # what kind of work each scheduled request got this step
        if tracing_active():
            for slot, n in sched.items():
                req = self.running.get(slot)
                if req is None:
                    continue
                kind = (("spec_verify" if spec_len[slot] else "decode")
                        if slot in decode_set else "prefill_chunk")
                self._req_event(req.req_id, kind, args={"tokens": int(n)})
        fault_point("slow_step")
        fault_point("dispatch")
        with span("dispatch"):
            res = step_fn(*head, *pools, *tail)
        self._mark_dispatch()
        if self.spec_k:
            out_dev, ne_dev, carry = res[0], res[1], res[2]
            cache.update_pages(*res[4:])
        else:
            out_dev, ne_dev, carry = res[0], None, res[0]
            cache.update_pages(*res[2:2 + len(pools)])
        if self._m_moe_rows is not None:
            # the step's last result: rows per expert, summed over layers.
            # It stays on the device until a reconcile materializes tokens
            # anyway (no sync of its own)
            self._moe_unread.append(res[2 + len(pools)])
            if self._m_moe_elsewhere is not None:
                self._moe_pairs_unread.append(
                    sum(sched.values()) * self._moe_pairs_per_row)
        if self.sparse:
            # [layers with an indexer, lanes, key slots] bool: the keys each
            # lane's last row read; left on the device
            self.last_selected = res[3 + len(pools)]
        self._carry = carry
        # charge the dispatched-unmaterialized token per completing lane
        # only once the launch SUCCEEDED (round 17: a failed launch must
        # leave no pending to un-charge). Round 19 generalizes the charge
        # to SPEC lanes too (n_emit-variable emission): one pending token
        # is the GUARANTEED minimum — the accepted drafts beyond it land
        # as a reconcile-time surplus the output budget absorbs exactly
        # like the sync engine's multi-token emission
        for _, req, _, _ in completing:
            req._pending_n += 1
        # the scheduler's own count of what it packed: a lane feeds prefill
        # rows while its context is known ahead (prompt chunks, replay), and
        # decode rows once it feeds one generated token (plus its drafts)
        now = None
        contexts, fed = [], []
        for slot, n in sched.items():
            req = self.running[slot]
            written = cache.seq_len(slot)
            n_prompt = len(req.prompt_ids)
            contexts.append(written + n)
            fed.append(n)
            if slot in decode_set and written >= n_prompt:
                self._m_rows_decode.inc(n)
            else:
                self._m_rows_prefill.inc(n)
            if (req.prefill_end_time is None
                    and written + n - int(spec_len[slot]) >= n_prompt):
                now = monotonic() if now is None else now
                req.prefill_end_time = now
                self._m_prefill.observe((now - req.admit_time) * 1e3)
            # count-based cache accounting at pack time: plain lanes
            # advance by what they fed; speculative lanes advance at
            # reconcile (their watermark is n_emit, a device value)
            if not spec_len[slot]:
                cache.advance(slot, n)
        rung = self._row_ladder[step_row_rung(self._row_ladder, sum(fed))]
        self._m_rows_run.labels(rung=str(rung)).inc(rung)
        if self.windowed:
            self._note_window_step(list(sched), contexts, fed, rung)
        else:
            self._m_attn_live.inc(
                sum(map(self._attn_grid.live_steps, contexts, fed)))
            self._m_attn_grid.inc(self._attn_grid.steps(contexts, fed))
        if self.sparse:
            cfg = self.config
            seen, read = map(sum, zip(*(
                self._dsa_keys_of(c, n, cfg.index_topk)
                for c, n in zip(contexts, fed)))) if fed else (0, 0)
            self._m_dsa_scored.inc(cfg.num_index_layers * seen)
            self._m_dsa_context.inc(cfg.num_layers * seen)
            self._m_dsa_selected.inc(cfg.num_layers * read)
        spec_slots = [s for s in sched if spec_len[s]]
        # a speculating lane always completes, so a prefill-only round
        # (completing empty) carries nothing to materialize — the entry
        # still occupies the ring so the gap accounting knows the device
        # has work
        return _Pending(out_dev if completing else None,
                        ne_dev if (completing and self.spec_k) else None,
                        completing, bool(self.spec_k), spec_slots,
                        must_sync)

    # -- the step ----------------------------------------------------------

    def step(self) -> dict[int, list[int]]:
        """One scheduler round. Returns ``{req_id: [tokens]}`` for the
        tokens produced this step, in emission order — a speculative
        decode lane can emit several (accepted drafts + bonus) in one
        round; a round that only advanced prefill chunks produces none.
        The async engine returns the tokens RECONCILED by this call (one
        step behind the dispatch; drain with :meth:`flush`)."""
        t0 = monotonic()
        self._did_sync = False
        # the pool-squeeze seam ticks EVERY scheduler round (never
        # raises): it must sit above the empty-running early returns or
        # an active squeeze could never expire while its withheld pages
        # are exactly what blocks the next admission
        fault_point("pool", cache=self.cache)
        try:
            return self._step_unified()
        finally:
            if self._did_sync:
                # ONE hard sync per step()/flush() call no matter how
                # many ring entries it landed: a drain materializes the
                # oldest (blocking) and the rest are already resident
                self._m_hard_syncs.inc()
            self._m_step_s.inc(monotonic() - t0)
            self._m_step_calls.inc()
            self._m_running.set(len(self.running))
            self._m_waiting.set(len(self.waiting))
            self._last_round_end = monotonic()

    # -- convenience -------------------------------------------------------

    def generate(self, prompts, max_new_tokens=32, eos_token_id=None,
                 max_steps=None, **sampling):
        """Enqueue ``prompts`` (list of id lists) and drive steps until all
        finish. Returns a list of output-id lists, in prompt order.
        ``sampling`` forwards temperature/top_k/top_p/seed to every
        request."""
        reqs = [self.add_request(p, max_new_tokens, eos_token_id, **sampling)
                for p in prompts]
        # budget covers the chunked-prefill rounds too: EVERY prompt feeds
        # ceil(len/chunk) chunks before its first token (prompts can
        # serialize through one lane, so the rounds sum, not max)
        pre_rounds = sum(len(r.prompt_ids) // self.chunk + 1 for r in reqs)
        limit = max_steps or ((len(prompts) * (max_new_tokens + 2)
                               + pre_rounds)
                              * (self.max_batch + 1))
        n = 0
        while any(r.state not in (FINISHED, FAILED) for r in reqs):
            self.step()
            # a drained scheduler with unfinished requests means they can
            # never be admitted (oversized); surface rather than spin
            if not self.has_work():
                break
            n += 1
            if n > limit:
                # round 17: mark every straggler terminal FAILED before
                # raising — no request is ever left non-terminal, and the
                # predictor stays serviceable for everyone else
                self._fail_stragglers(
                    reqs, f"serving loop exceeded step budget ({limit})")
                raise RuntimeError("serving loop exceeded step budget "
                                   f"({limit}) — scheduler stuck")
        # a request can finish by COUNT with its final tokens still in
        # flight (async deferral): drain before reading the outputs
        self.flush()
        return [list(r.output_ids) for r in reqs]

    def _fail_stragglers(self, reqs, message: str) -> None:
        """Terminal-FAIL every non-terminal request in ``reqs`` with
        ``scheduler_stuck``, releasing any slot/pages held — the
        step-budget overflow path must never leave a request in a
        non-terminal state."""
        stuck = [r for r in reqs if r.state not in (FINISHED, FAILED)]
        if not stuck:
            return
        ids = {id(r) for r in stuck}
        for slot in [s for s, r in self.running.items() if id(r) in ids]:
            self.running.pop(slot)
            self.cache.free(slot)
        if any(id(r) in ids for r in self.waiting):
            self.waiting = deque(r for r in self.waiting
                                 if id(r) not in ids)
        for req in stuck:
            self._fail(req, "scheduler_stuck", message)


__all__ = ["Request", "ServingPredictor", "SLOConfig", "WAITING",
           "RUNNING", "FINISHED", "FAILED"]
