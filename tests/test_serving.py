"""Round-7 serving subsystem: paged-cache greedy generate vs the no-cache
full-forward oracle, KVCacheManager admission/eviction, the
continuous-batching ServingPredictor, and the bench_serve.py --smoke
contract. CPU suite: the Pallas kernel runs the jnp reference path here
(kernel parity is tests/test_paged_attention.py's job); these tests pin the
cache/scheduler/jit plumbing around it.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import KVCacheManager, Request, ServingPredictor
from paddle_tpu.inference.serving import FINISHED, RUNNING, WAITING
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

TINY = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=96)


def _tiny_model(**over):
    paddle.seed(7)
    cfg = GPTConfig(**{**TINY, **over})
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _oracle_greedy(model, ids_np, max_new_tokens):
    """No-cache oracle: full forward over the growing context, argmax at
    the last position — the token-for-token golden for generate."""
    ctx = ids_np.copy()
    out = []
    for _ in range(max_new_tokens):
        logits = model(paddle.to_tensor(ctx)).numpy()
        nxt = np.argmax(logits[:, -1, :], axis=-1).astype(ctx.dtype)
        out.append(nxt)
        ctx = np.concatenate([ctx, nxt[:, None]], axis=1)
    return np.stack(out, axis=1)


# -- generate: golden parity + jit-shape policy -----------------------------


# ``chunk4``: a prompt fed in several 4-token chunks over 8-token pages
_CHUNKS = pytest.mark.parametrize(
    "geometry", [dict(), dict(page_size=8, chunk=4)],
    ids=["default", "chunk4"])


@_CHUNKS
def test_generate_matches_full_forward_oracle(rng, geometry):
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 11)).astype(np.int64)
    want = _oracle_greedy(model, ids, 8)
    got = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                         **geometry).numpy()
    np.testing.assert_array_equal(got, want)


@_CHUNKS
def test_generate_kernel_leg_matches_oracle(rng, geometry):
    """Same golden with the Pallas kernel forced (interpret mode on CPU) —
    the acceptance-criteria path."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 5)).astype(np.int64)
    want = _oracle_greedy(model, ids, 6)
    got = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                         use_kernel=True,
                         **{"page_size": 8, **geometry}).numpy()
    np.testing.assert_array_equal(got, want)


def test_generate_no_per_token_retrace(rng):
    """The decode step compiles at most ONCE per call (0 when the shared
    jit cache already holds the shape); every token replays it."""
    from paddle_tpu.models.gpt import generate_paged

    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 4)).astype(np.int64)
    model.generate(paddle.to_tensor(ids), max_new_tokens=10)
    assert generate_paged.last_decode_trace_count <= 1
    # second call, same geometry: the cached jit replays with ZERO traces
    model.generate(paddle.to_tensor(ids), max_new_tokens=10)
    assert generate_paged.last_decode_trace_count == 0


def test_generate_on_gptmodel_and_eos(rng):
    """GPTModel (no LM head) generates through the tied embedding; eos
    stops early."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (1, 6)).astype(np.int64)
    out = model.gpt.generate(paddle.to_tensor(ids), max_new_tokens=5).numpy()
    assert out.shape == (1, 5)
    eos = int(out[0, 1])
    stopped = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                             eos_token_id=eos).numpy()
    assert stopped.shape[1] <= 5
    assert eos in stopped[0]


def test_generate_rejects_overlong(rng):
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (1, 90)).astype(np.int64)
    with pytest.raises(ValueError, match="max_seq_len"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=32)


# -- KVCacheManager: pages, slots, admission, eviction ----------------------


def _mgr(**over):
    kw = dict(num_layers=2, num_kv_heads=4, head_dim=8, num_pages=8,
              max_batch=3, max_seq_len=32, page_size=8)
    kw.update(over)
    return KVCacheManager(**kw)


def test_cache_admit_allocates_pages():
    m = _mgr()
    slot = m.admit(10)  # 10 tokens @ page_size 8 -> 2 pages
    assert m.seq_len(slot) == 10
    assert m.free_page_count == 6
    assert int((np.asarray(m._page_table[slot]) >= 0).sum()) == 2


def test_cache_free_returns_pages_and_slot():
    m = _mgr()
    s0, s1 = m.admit(8), m.admit(9)
    pages_held = 1 + 2
    assert m.free_page_count == 8 - pages_held
    m.free(s0)
    assert m.free_page_count == 6
    assert m.free_slot_count == 2
    assert m.seq_len(s0) == 0
    # the freed slot is reusable and gets fresh pages
    s2 = m.admit(24)
    assert s2 == s0
    assert m.free_page_count == 6 - 3
    m.free(s1), m.free(s2)
    assert m.free_page_count == 8 and m.free_slot_count == 3


def test_cache_growth_and_exhaustion():
    m = _mgr(num_pages=3)
    slot = m.admit(8)  # 1 page, exactly full
    assert m.ensure_capacity(slot, 9)  # crosses into page 2
    assert m.free_page_count == 1
    assert m.ensure_capacity(slot, 16)  # still page 2
    assert m.ensure_capacity(slot, 17)  # page 3
    assert m.free_page_count == 0
    assert not m.ensure_capacity(slot, 25)  # pool dry
    assert not m.ensure_capacity(slot, 99)  # beyond max_seq_len


def test_cache_admit_raises_when_full():
    m = _mgr(max_batch=1, num_pages=2)
    m.admit(16)
    assert not m.can_admit(1)
    with pytest.raises(RuntimeError, match="slot"):
        m.admit(1)
    m2 = _mgr(num_pages=1)
    with pytest.raises(RuntimeError, match="exhausted"):
        m2.admit(9)


# -- ServingPredictor: continuous batching ----------------------------------


def test_predictor_matches_generate(rng):
    """Continuous-batching outputs == the plain paged generate, per prompt,
    even when prompts outnumber decode lanes (slot reuse across waves)."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (3, 7, 5, 9, 4)]
    sp = ServingPredictor(model, max_batch=2, max_seq_len=48, page_size=8)
    got = sp.generate(prompts, max_new_tokens=6)
    for p, g in zip(prompts, got):
        ids = np.asarray([p], np.int64)
        want = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                              page_size=8).numpy()[0]
        np.testing.assert_array_equal(np.asarray(g), want)


def test_predictor_admit_evict_lifecycle(rng):
    """WAITING -> RUNNING -> FINISHED; finished slots free mid-flight and
    waiting requests join the running batch without restarting it."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=48, page_size=8)
    short = sp.add_request([5, 6], max_new_tokens=2)
    long = sp.add_request([7, 8, 9], max_new_tokens=8)
    queued = sp.add_request([1, 2, 3, 4], max_new_tokens=3)
    assert [r.state for r in (short, long, queued)] == [WAITING] * 3
    sp.step()
    assert short.state == RUNNING and long.state == RUNNING
    assert queued.state == WAITING  # both lanes busy
    while short.state != FINISHED:
        sp.step()
    # short's slot must be recycled into queued WITHOUT long stopping
    assert long.state == RUNNING
    while any(r.state != FINISHED for r in (long, queued)):
        sp.step()
    assert len(short.output_ids) == 2
    assert len(long.output_ids) == 8
    assert len(queued.output_ids) == 3
    assert not sp.has_work()
    assert sp.cache.free_slot_count == sp.max_batch


def test_predictor_decode_fixed_shape(rng):
    """One trace for the decode step across admissions/evictions — the
    continuous batch never changes the compiled shape."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=48, page_size=8)
    sp.generate([[3, 1], [4, 1, 5], [9, 2], [6]], max_new_tokens=4)
    assert sp.decode_trace_count == 1


def test_predictor_preemption_under_page_pressure(rng):
    """A pool too small for all admitted sequences preempts the youngest
    back to WAITING (recompute mode) and still finishes everything with
    the right token streams."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (6,)).tolist()
               for _ in range(3)]
    # 5 pages of 8 tokens = 40 cached tokens; each sequence peaks at 15
    # cached tokens = 2 pages, so 3 concurrent need 6 pages — growth must
    # preempt the youngest at least once
    sp = ServingPredictor(model, max_batch=3, max_seq_len=24, page_size=8,
                          num_pages=5)
    reqs = [sp.add_request(p, max_new_tokens=10) for p in prompts]
    while sp.has_work():
        sp.step()
    # the geometry above cannot finish without preempting: 3 seqs * 16
    # tokens peak > the 48-token pool while all three run
    assert sum(r.preempt_count for r in reqs) >= 1
    for p, r in zip(prompts, reqs):
        assert r.state == FINISHED
        ids = np.asarray([p], np.int64)
        want = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                              page_size=8).numpy()[0]
        np.testing.assert_array_equal(np.asarray(r.output_ids), want)


def test_predictor_rejects_oversized_prompt():
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=1, max_seq_len=16, page_size=8)
    with pytest.raises(ValueError, match="max_seq_len"):
        sp.add_request(list(range(17)))


def test_request_done_logic():
    r = Request([1, 2], max_new_tokens=2, eos_token_id=9)
    assert not r.done
    r.output_ids.append(3)
    assert not r.done
    r.output_ids.append(9)
    assert r.done  # eos
    r2 = Request([1], max_new_tokens=1)
    r2.output_ids.append(4)
    assert r2.done  # budget


# -- round 9: unified step, prefix caching, fused sampling ------------------


def test_unified_prefix_cache_hits_preserve_tokens(rng):
    """A repeated prompt must serve from the prefix cache (hit rate up,
    prefill work skipped) and still emit exactly the same greedy tokens."""
    model = _tiny_model()
    prompt = rng.randint(0, TINY["vocab_size"], (17,)).tolist()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=48, page_size=8,
                          chunk=8)
    first = sp.generate([prompt], max_new_tokens=5)[0]
    assert sp.prefix_hit_rate == 0.0
    second_req = sp.add_request(prompt, max_new_tokens=5)
    while sp.has_work():
        sp.step()
    assert second_req.cached_prefix_len >= 16   # both full pages + tail
    assert sp.prefix_hit_rate > 0.0
    np.testing.assert_array_equal(np.asarray(second_req.output_ids),
                                  np.asarray(first))


def test_unified_shared_prefix_divergence_cow(rng):
    """Two prompts sharing a long prefix: the second attaches the shared
    pages and copy-on-writes at divergence — outputs must equal a
    cache-disabled run for BOTH, and the first request's pages must not
    be corrupted by the second's writes (they decode concurrently)."""
    model = _tiny_model()
    shared = rng.randint(0, TINY["vocab_size"], (12,)).tolist()
    prompts = [shared + [1, 2], shared + [3, 4, 5]]
    plain = ServingPredictor(model, max_batch=2, max_seq_len=48,
                             page_size=8, prefix_cache=False, chunk=8)
    want = plain.generate(prompts, max_new_tokens=6)
    cached = ServingPredictor(model, max_batch=2, max_seq_len=48,
                              page_size=8, chunk=8)
    r0 = cached.add_request(prompts[0], max_new_tokens=6)
    # finish r0 so its prompt registers, then run r1 + r0b CONCURRENTLY:
    # r0b re-hits r0's pages while r1 CoWs off the shared prefix
    while cached.has_work():
        cached.step()
    r1 = cached.add_request(prompts[1], max_new_tokens=6)
    r0b = cached.add_request(prompts[0], max_new_tokens=6)
    while cached.has_work():
        cached.step()
    np.testing.assert_array_equal(np.asarray(r0.output_ids),
                                  np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(r1.output_ids),
                                  np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(r0b.output_ids),
                                  np.asarray(want[0]))
    assert r1.cached_prefix_len >= 8    # the shared full page hit
    assert r0b.cached_prefix_len >= 12


def test_unified_two_cow_claims_one_free_page_preempts_not_crashes(rng):
    """Two lanes hitting the same shared tail page both need copy-on-write
    in one step with a single allocatable page left: the first claim must
    RESERVE it and the second must fall into the preemption path — not
    crash out of step() with a mid-prep pool-exhausted error."""
    model = _tiny_model()
    prompt = rng.randint(0, TINY["vocab_size"], (7,)).tolist()  # 2 pages
    # register the prompt's pages (full page + 3-token partial tail)
    sp = ServingPredictor(model, max_batch=2, max_seq_len=16, page_size=4,
                          num_pages=3, chunk=4)
    want = sp.generate([prompt], max_new_tokens=3)[0]
    # both pages now parked on the LRU, registered. Admit TWO copies of
    # the prompt: each matches both pages (2 shared + 1 free page left);
    # both diverge into the shared tail page on their first feed step
    r1 = sp.add_request(prompt, max_new_tokens=3)
    r2 = sp.add_request(prompt, max_new_tokens=3)
    while sp.has_work():
        sp.step()   # must never raise
    np.testing.assert_array_equal(np.asarray(r1.output_ids),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(r2.output_ids),
                                  np.asarray(want))
    assert r2.preempt_count >= 1   # the loser of the last page backed off


def test_unified_progressive_registration_hits_inflight_prefill(rng):
    """Full prompt pages register as their chunks land, NOT only at prompt
    completion: a same-prompt request arriving while the first is still
    mid-prefill hits the already-written pages."""
    model = _tiny_model()
    prompt = rng.randint(0, TINY["vocab_size"], (33,)).tolist()
    # chunk 8 + budget 8: the 33-token prompt needs 5 prefill rounds
    sp = ServingPredictor(model, max_batch=2, max_seq_len=64, page_size=8,
                          chunk=8, token_budget=8)
    first = sp.add_request(prompt, max_new_tokens=4)
    sp.step()   # admits + feeds the first 8-token chunk (page 1 full)
    late = sp.add_request(prompt, max_new_tokens=4)
    while sp.has_work():
        sp.step()
    assert late.cached_prefix_len >= 8   # hit the in-flight prefix
    np.testing.assert_array_equal(np.asarray(late.output_ids),
                                  np.asarray(first.output_ids))


def test_unified_seeded_top_p_determinism(rng):
    """Seeded temperature/top-k/top-p on the CPU interpret (kernel) path:
    same seed -> identical streams, different seed -> different streams,
    and temperature=0 lanes stay bit-identical to greedy."""
    model = _tiny_model()
    prompt = rng.randint(0, TINY["vocab_size"], (9,)).tolist()

    def run(seed, temperature=0.8):
        sp = ServingPredictor(model, max_batch=2, max_seq_len=48,
                              page_size=8, chunk=8, use_kernel=True)
        return sp.generate([prompt], max_new_tokens=8,
                           temperature=temperature, top_p=0.9, top_k=40,
                           seed=seed)[0]

    a, b, c = run(123), run(123), run(321)
    assert a == b                      # seeded: reproducible
    assert a != c                      # seed actually flows
    greedy = run(0, temperature=0.0)
    ids = np.asarray([prompt], np.int64)
    oracle = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                            page_size=8, use_kernel=True).numpy()[0]
    np.testing.assert_array_equal(np.asarray(greedy), oracle)


def test_unified_sampling_survives_preemption_replay(rng):
    """The per-request sample stream is keyed by tokens-produced, so a
    preempted-and-replayed request samples the SAME continuation."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (6,)).tolist()
               for _ in range(3)]
    roomy = ServingPredictor(model, max_batch=3, max_seq_len=24,
                             page_size=8, chunk=8)
    want = [roomy.generate([p], max_new_tokens=10, temperature=0.7,
                           top_p=0.95, seed=77)[0] for p in prompts]
    tight = ServingPredictor(model, max_batch=3, max_seq_len=24,
                             page_size=8, num_pages=5, chunk=8)
    reqs = [tight.add_request(p, max_new_tokens=10, temperature=0.7,
                              top_p=0.95, seed=77) for p in prompts]
    while tight.has_work():
        tight.step()
    assert sum(r.preempt_count for r in reqs) >= 1
    for r, w in zip(reqs, want):
        np.testing.assert_array_equal(np.asarray(r.output_ids),
                                      np.asarray(w))


def test_unified_no_head_of_line_blocking(rng):
    """A long admitting prompt must NOT stall running decodes: with
    chunked prefill the decode lane keeps producing every step while the
    long prompt prefills over several chunks."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=90, page_size=8,
                          chunk=4, token_budget=6)
    short = sp.add_request(rng.randint(0, TINY["vocab_size"],
                                       (3,)).tolist(), max_new_tokens=30)
    sp.step()   # short admitted + prefilled (3 <= chunk+budget)
    while not short.output_ids:
        sp.step()
    long = sp.add_request(rng.randint(0, TINY["vocab_size"],
                                      (40,)).tolist(), max_new_tokens=2)
    stalls = 0
    before = len(short.output_ids)
    while not long.output_ids and sp.has_work():
        produced = sp.step()
        if short.req_id not in produced and short.state == RUNNING:
            stalls += 1
    # the 40-token prompt needs ceil(40/4) = 10 chunk rounds; the decode
    # lane must have produced on every one of them
    assert len(short.output_ids) - before >= 9
    assert stalls == 0
    while sp.has_work():
        sp.step()
    assert long.state == FINISHED and len(long.output_ids) == 2


def test_unified_ttft_recorded(rng):
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=48, page_size=8)
    req = sp.add_request(rng.randint(0, TINY["vocab_size"], (5,)).tolist(),
                         max_new_tokens=3)
    assert req.ttft is None
    while sp.has_work():
        sp.step()
    assert req.ttft is not None and req.ttft >= 0.0


# -- bench_serve.py --smoke: the tier-1-adjacent CI leg ---------------------


def test_bench_serve_smoke_schema():
    """bench_serve.py --smoke must run green on CPU and emit bench.py's
    one-line JSON schema with the round-9 serving fields (TTFT, prefix
    hit rate, the retrace gate), the round-10 quantized
    A/B legs (fp vs int8-weights vs int8-weights+int8-KV) with the
    hbm-bytes-per-token accounting, the round-11 mesh scaling leg
    (mp=1 vs mp=N unified step) with per-chip throughput, and the
    round-12 speculative A/B (spec off vs k=4 on a repetitive-prompt
    churn) with accepted-tokens-per-step > 1.0; flagship quantized line
    last. Best-of-2: the strict within-pair perf gates (async tokens/s
    > paired sync) sit near a loaded CI box's noise floor — one retry
    shields the load spike without weakening a deterministic failure
    (same idiom as the round-7 shm-ring best-of-3)."""
    try:
        _bench_serve_smoke_once()
    except AssertionError:
        _bench_serve_smoke_once()


_SMOKE_LEGS = ("unified-step,unified-async,unified-obs,"
               "unified-spmd,unified-spec-base,unified-spec-k4,"
               "unified-int8w,unified-int8w-int8kv")


def _bench_serve_smoke_once():
    # round 16: the tier-1 smoke runs its gated subset through the
    # --legs selector
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         f"--legs={_SMOKE_LEGS}"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 8, proc.stdout
    for line, want_leg in zip(lines, _SMOKE_LEGS.split(",")):
        rec = json.loads(line)
        assert "error" not in rec, rec
        # round 16: every serving line names its leg (enum-checked by
        # the schema) and it matches the emit order
        assert rec["leg"] == want_leg
        assert rec["device_ms_per_step"] > 0
        assert rec["unit"] == "tokens/s" and rec["value"] > 0
        assert rec["p50_ms"] > 0 and rec["p99_ms"] >= rec["p50_ms"]
        assert rec["ttft_p50_ms"] > 0
        assert rec["ttft_p99_ms"] >= rec["ttft_p50_ms"]
        assert rec["decode_retraces"] == 1  # the no-retrace gate
        assert "vs_baseline" in rec and "prefix_hit_rate" in rec
        assert rec["hbm_bytes_per_token"] > 0
        # round 23: every leg carries the jaxpr-derived static HBM model
        # next to the analytic one and the two agree within the JX007
        # contract tolerance (presence is asserted so a silent derivation
        # failure fails here, not just in the tpulint gate)
        assert rec["hbm_bytes_per_token_static"] > 0
        assert abs(rec["hbm_model_drift_frac"]) <= 0.02
        # round 11: every leg stamps its mesh geometry
        assert rec["mesh_shape"] == f"mp{rec['mesh_chips']}"
        assert rec["tokens_per_s_per_chip"] == pytest.approx(
            rec["value"] / rec["mesh_chips"], rel=0.01)
        # round 15: the schema-checked telemetry snapshot rides EVERY
        # leg — the serving registry's counters must be live and agree
        # with the line's own accounting
        tel = rec["telemetry"]
        assert tel["serving_steps"] > 0
        assert tel["serving_tokens_emitted"] > 0
        # (requests_finished can legitimately be 0 on a leg whose output
        # budget exceeds its short smoke window — e.g. spec-base at 1
        # token/lane-step — so it is not gated per-line)
        assert tel["serving_requests_admitted"] > 0
        assert tel["serving_ttft_ms_count"] > 0
        assert tel["kv_pages_free"] >= 0
    (unified, uasync, uobs, spmd, specb, speck, int8w,
     int8kv) = (json.loads(l) for l in lines)
    assert "[unified-step]" in unified["metric"]
    assert "[unified-async]" in uasync["metric"]
    assert "[unified-obs]" in uobs["metric"]
    assert "[unified-spmd]" in spmd["metric"]
    assert "[unified-spec-base]" in specb["metric"]
    assert "[unified-spec-k4]" in speck["metric"]
    assert "[unified-int8w]" in int8w["metric"]
    assert "[unified-int8w-int8kv]" in int8kv["metric"]  # flagship LAST
    # the round-15 observability A/B, measured as an interleaved pair on
    # the same churn: vs_baseline is the paired-window median of traced/
    # untraced tokens/s. This end-to-end gate is the GROSS-regression
    # guard (e.g. a hot span accidentally re-growing a per-call jax
    # TraceAnnotation showed up here as ~6%); the strict 2% disabled-path
    # contract is gated deterministically in test_observability.py —
    # this box's A/A churn noise floor (~±7%) swamps a 2% tokens/s
    # assertion. The traced leg must also have actually recorded events
    # (a silently-no-op tracing leg must fail, not pass).
    assert uobs["vs_baseline"] >= 0.9, uobs
    assert uobs["obs_off_tokens_per_s"] > 0
    assert uobs["trace_events"] > 0
    # prefix/preemption/draft counters ride the spec legs' telemetry
    assert speck["telemetry"]["serving_draft_proposed"] > 0
    assert speck["telemetry"]["serving_draft_accepted"] > 0
    # the round-13 sync-vs-async A/B, gated in the checked schema: the
    # async engine must close the inter-step host bubble (strictly lower
    # no-step-in-flight fraction), turn that into throughput (strictly
    # higher decode tokens/s than the sync engine), and emit
    # bit-identical greedy streams while doing it — all compared WITHIN
    # the interleaved pair (the paired sync stats ride the async line)
    assert uasync["step_gap_frac"] < uasync["sync_step_gap_frac"]
    assert uasync["value"] > uasync["sync_tokens_per_s"]
    assert uasync["vs_baseline"] > 1.0
    assert uasync["async_emissions_match"] == 1.0
    for rec in (unified, uasync):
        assert 0.0 <= rec["step_gap_frac"] <= 1.0
        assert rec["host_ms_per_step"] >= 0.0
    # the round-12 speculation gates: the spec-off leg anchors exactly
    # 1.0 token per decode lane-step on the same repetitive workload;
    # the k=4 leg must ACTUALLY accept drafts — more than one token per
    # weight-read — with a real acceptance rate behind it
    assert specb["accepted_tokens_per_step"] == 1.0
    assert specb["draft_acceptance_rate"] == 0.0
    assert speck["accepted_tokens_per_step"] > 1.0
    assert 0.0 < speck["draft_acceptance_rate"] <= 1.0
    # the churn workload (repeated prompts) must actually hit the prefix
    # cache
    assert unified["prefix_hit_rate"] > 0.0
    assert int8kv["prefix_hit_rate"] > 0.0
    # the round-11 mesh A/B: the spmd leg ran tensor-parallel (the test
    # env forces >= 2 host devices) on the same churn, and its analytic
    # per-chip HBM bytes dropped below the mp=1 leg's (sharded stacks +
    # sharded KV pages; replicated embeddings keep it above value/mp)
    assert spmd["mesh_chips"] >= 2
    assert spmd["hbm_bytes_per_token"] < unified["hbm_bytes_per_token"]
    # the round-10 memory contract: each quantization leg strictly cuts
    # HBM bytes per decode token (weights 2x+, then the KV context)
    assert int8w["hbm_bytes_per_token"] < unified["hbm_bytes_per_token"]
    assert int8kv["hbm_bytes_per_token"] < int8w["hbm_bytes_per_token"]


def test_predictor_tight_pool_serializes_instead_of_livelock(rng):
    """A pool that can only hold ONE growing sequence must serve requests
    one at a time (preempt + re-admit), not livelock evicting everybody:
    the growth loop skips slots already freed mid-iteration."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=16, page_size=4,
                          num_pages=2)
    prompts = [[3, 1, 4, 1], [5, 9, 2, 6]]
    got = sp.generate(prompts, max_new_tokens=5)
    for p, g in zip(prompts, got):
        ids = np.asarray([p], np.int64)
        want = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                              page_size=4).numpy()[0]
        np.testing.assert_array_equal(np.asarray(g), want)
    # no page leaked into a parked slot's table across all the churn —
    # every page is free or parked on the prefix-cache LRU (evictable)
    assert sp.cache.available_page_count == 2
    assert (np.asarray(sp.cache._page_table) == -1).all()


def test_generate_raises_on_undersized_pool(rng):
    """generate with a num_pages too small for the decode growth must fail
    loudly, not silently drop K/V writes and emit wrong tokens."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (1, 8)).astype(np.int64)
    with pytest.raises(RuntimeError, match="exhausted"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=12,
                       page_size=4, num_pages=2)


def test_predictor_prefill_finished_request_never_decodes(rng):
    """A request whose prefill token already exhausts its budget (or hits
    eos) must retire with exactly that token — no extra decode step."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=32, page_size=8)
    got = sp.generate([[5]], max_new_tokens=1)
    assert len(got[0]) == 1
    want = model.generate(paddle.to_tensor(np.array([[5]], np.int64)),
                          max_new_tokens=1, page_size=8).numpy()[0]
    np.testing.assert_array_equal(np.asarray(got[0]), want)
    # eos produced BY the prefill: nothing may follow it
    eos = int(want[0])
    sp2 = ServingPredictor(model, max_batch=2, max_seq_len=32, page_size=8)
    got2 = sp2.generate([[5]], max_new_tokens=8, eos_token_id=eos)
    assert got2[0] == [eos]


def test_generate_eos_frees_pages_and_pads(rng):
    """A row that hits eos frees its cache pages mid-generate and its
    remaining columns pad with the eos id."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 6)).astype(np.int64)
    free_run = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                              page_size=8).numpy()
    eos = int(free_run[0, 2])  # row 0 stops at step 3; row 1 may not
    out = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                         page_size=8, eos_token_id=eos).numpy()
    row = out[0]
    hit = int(np.argmax(row == eos))
    assert row[hit] == eos
    assert (row[hit:] == eos).all()  # eos padding, not garbage decode
    # rows agree with the unconstrained run up to their eos
    np.testing.assert_array_equal(row[:hit + 1], free_run[0, :hit + 1])


def test_generate_params_cache_tracks_weight_updates(rng):
    """Repeated generate reuses the extracted params; rebinding a weight
    buffer (an optimizer step) invalidates the per-model cache."""
    from paddle_tpu.models.gpt import _SERVING_PARAMS_CACHE

    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (1, 5)).astype(np.int64)
    a = model.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy()
    cached = _SERVING_PARAMS_CACHE.get(model)
    assert cached is not None
    b = model.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy()
    assert _SERVING_PARAMS_CACHE.get(model)[1] is cached[1]  # reused
    np.testing.assert_array_equal(a, b)
    # "train": rebind one layer weight buffer -> fresh extraction
    w = model.gpt.layers[0].mlp.fc1.weight
    w.set_value(paddle.to_tensor(np.asarray(w.numpy()) + 0.5))
    c = model.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy()
    assert _SERVING_PARAMS_CACHE.get(model)[1] is not cached[1]
    ctx = ids.copy()
    for _ in range(4):
        logits = model(paddle.to_tensor(ctx)).numpy()
        nxt = np.argmax(logits[:, -1, :], -1).astype(np.int64)
        ctx = np.concatenate([ctx, nxt[:, None]], 1)
    np.testing.assert_array_equal(c, ctx[:, 5:])  # new weights served


def test_predictor_fails_never_admittable_request_individually(rng):
    """Round-17 regression (the pre-17 behavior RAISED out of step() and
    wedged the predictor for everyone): a prompt that can never fit the
    page pool fails ONLY that request — terminal FAILED with a loud
    error record naming the real cause — and the scheduler keeps
    serving the requests behind it."""
    from paddle_tpu.inference.serving import FAILED

    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=1, max_seq_len=32, page_size=4,
                          num_pages=2)  # pool holds 8 tokens total
    doomed = sp.add_request(list(rng.randint(0, TINY["vocab_size"], (20,))),
                            max_new_tokens=4)
    ok = sp.add_request(list(rng.randint(0, TINY["vocab_size"], (4,))),
                        max_new_tokens=3)
    while sp.has_work():
        sp.step()
    sp.flush()
    assert doomed.state == FAILED
    assert doomed.error["code"] == "never_admittable"
    assert "num_pages" in doomed.error["message"]
    assert doomed.output_ids == []
    # the request QUEUED BEHIND the doomed one was served normally
    assert ok.state == FINISHED and len(ok.output_ids) == 3
    flat = sp.telemetry()
    assert flat["serving_requests_failed"] == 1
    assert flat["serving_fail_reasons{reason=never_admittable}"] == 1

def test_generate_zero_budget_returns_empty(rng):
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 4)).astype(np.int64)
    out = model.generate(paddle.to_tensor(ids), max_new_tokens=0)
    assert tuple(out.shape) == (2, 0)


def test_predictor_truncation_flag_preserves_budget(rng):
    """The length-ceiling stop flags the request as truncated without
    corrupting its original max_new_tokens."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=1, max_seq_len=8, page_size=4)
    req = sp.add_request([1, 2, 3, 4, 5], max_new_tokens=50)
    while sp.has_work():
        sp.step()
    assert req.state == FINISHED
    assert req.truncated
    assert req.max_new_tokens == 50  # caller's budget untouched
    assert len(req.output_ids) < 50


def test_predictor_readmission_at_length_ceiling_truncates(rng):
    """A request preempted while sitting exactly at max_seq_len re-enters
    the queue with context = max_seq_len + 1; admission must finish it as
    truncated instead of raising and killing the serving loop."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=8, page_size=4)
    stuck = sp.add_request([1, 2, 3], max_new_tokens=20)
    stuck.output_ids = [4, 5, 6, 7, 8, 9]  # 3 + 6 = max_seq_len + 1
    other = sp.add_request([2, 1], max_new_tokens=3)
    while sp.has_work():
        sp.step()
    assert stuck.state == FINISHED and stuck.truncated
    assert other.state == FINISHED and len(other.output_ids) == 3


def test_generate_eos_reclaim_feeds_tight_pool(rng):
    """Pages freed by an eos lane must be visible to another lane's growth
    in the SAME step — grow-before-free would raise a spurious
    cache-exhausted error."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 6)).astype(np.int64)
    free_run = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                              page_size=4).numpy()
    eos = int(free_run[0, 1])  # lane 0 finishes after 2 tokens
    # pool: lane 0 peaks at 7 cached tokens (2 pages), lane 1 needs 4
    # pages for its full 15 — 5 pages only works if lane 0's free lands
    # before lane 1's growth check
    out = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                         page_size=4, num_pages=5,
                         eos_token_id=eos).numpy()
    hit1 = int(np.argmax(out[1] == eos)) if eos in out[1] else len(out[1])
    np.testing.assert_array_equal(out[1][:hit1], free_run[1][:hit1])


def test_generate_rejects_empty_prompt(rng):
    model = _tiny_model()
    with pytest.raises(ValueError, match="empty prompt"):
        model.generate(paddle.to_tensor(np.zeros((2, 0), np.int64)),
                       max_new_tokens=3)


def test_predictor_admission_keeps_growth_headroom(rng):
    """With sequences running, admission leaves one free page of growth
    headroom — an exactly-fitting admission would be preempted (prefill
    discarded) by the same step's growth pass."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=2, max_seq_len=24, page_size=4,
                          num_pages=3)
    a = sp.add_request([1, 2, 3, 4, 5], max_new_tokens=4)  # prefix 4 -> 1pg
    sp.step()
    assert a.state == RUNNING
    # 2 pages free, b's prefix needs 2 — exactly fits, but zero headroom:
    # must wait rather than admit-then-preempt
    b = sp.add_request([6, 7, 8, 9, 1, 2, 3, 4, 5], max_new_tokens=2)
    sp.step()
    assert b.state == WAITING and b.preempt_count == 0
    while sp.has_work():
        sp.step()
    assert a.state == FINISHED and b.state == FINISHED
    assert b.preempt_count == 0  # never admitted into a doomed fit
    assert len(b.output_ids) == 2


# -- round 10: quantized serving (int8/int4 weights + int8 KV cache) --------


def _token_match_rate(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float((got == want).mean())


@pytest.mark.parametrize("group", [-1, 8], ids=["per-channel", "g8"])
def test_quantized_generate_matches_fp_oracle(rng, group):
    """The acceptance gate: generate_paged with int8 weights (a scale per
    output channel, or per group of 8 inputs) + int8 KV matches the fp
    greedy oracle on >= 99% of tokens in the smoke config (quantization
    noise may flip near-tie argmaxes — the explicit tolerance), and the
    unified-step retrace gate is unchanged."""
    from paddle_tpu.models.gpt import generate_paged

    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 11)).astype(np.int64)
    want = _oracle_greedy(model, ids, 16)
    model.config.weight_dtype = "int8"
    model.config.weight_quant_group_size = group
    model.config.kv_cache_dtype = "int8"
    try:
        got = model.generate(paddle.to_tensor(ids), max_new_tokens=16).numpy()
        assert _token_match_rate(got, want) >= 0.99
        # ONE trace for the quantized unified step, never per-token
        assert generate_paged.last_decode_trace_count <= 1
    finally:
        model.config.weight_dtype = None
        model.config.weight_quant_group_size = -1
        model.config.kv_cache_dtype = None


def test_quantized_generate_int4_grouped(rng):
    """int4 nibble-packed weights with per-group scales serve through the
    same path (coarser: the group scales keep argmax flips rare)."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 7)).astype(np.int64)
    want = _oracle_greedy(model, ids, 10)
    model.config.weight_dtype = "int4"
    model.config.weight_quant_group_size = 8
    try:
        got = model.generate(paddle.to_tensor(ids), max_new_tokens=10).numpy()
        assert _token_match_rate(got, want) >= 0.9
    finally:
        model.config.weight_dtype = None
        model.config.weight_quant_group_size = -1


def test_quantized_weight_only_generate_exactness_unaffected_by_cache(rng):
    """Flipping weight_dtype on one model must re-extract the serving
    params (the cache cannot serve the fp pytree to the quantized config)
    and flipping back must restore bit-exact fp serving."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (1, 6)).astype(np.int64)
    want = _oracle_greedy(model, ids, 6)
    got_fp = model.generate(paddle.to_tensor(ids), max_new_tokens=6).numpy()
    np.testing.assert_array_equal(got_fp, want)
    model.config.weight_dtype = "int8"
    try:
        from paddle_tpu.inference.quantize import is_quantized_params
        from paddle_tpu.models.gpt import _serving_params_cached

        assert is_quantized_params(_serving_params_cached(model))
    finally:
        model.config.weight_dtype = None
    got_fp2 = model.generate(paddle.to_tensor(ids), max_new_tokens=6).numpy()
    np.testing.assert_array_equal(got_fp2, want)


def test_quantized_predictor_matches_fp_and_no_retrace(rng):
    """ServingPredictor with int8 weights + int8 KV: >= 99% token match
    vs the fp predictor over continuous batching, prefix caching still
    composes, and the unified step compiles exactly ONCE."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (9, 5, 13)]
    sp_fp = ServingPredictor(model, max_batch=3, page_size=8,
                             max_seq_len=64)
    fp_out = sp_fp.generate(prompts, max_new_tokens=10)
    model.config.weight_dtype = "int8"
    model.config.kv_cache_dtype = "int8"
    try:
        sp_q = ServingPredictor(model, max_batch=3, page_size=8,
                                max_seq_len=64)
        q_out = sp_q.generate(prompts, max_new_tokens=10)
        toks = [(a, b) for ao, bo in zip(fp_out, q_out)
                for a, b in zip(ao, bo)]
        match = np.mean([a == b for a, b in toks])
        assert match >= 0.99, f"token match {match}"
        assert sp_q.decode_trace_count == 1     # retrace gate unchanged
        # second wave: prefix pages (stored int8 WITH their scales) hit
        sp_q.generate(prompts, max_new_tokens=4)
        assert sp_q.prefix_hit_rate > 0.0
        assert sp_q.decode_trace_count == 1
    finally:
        model.config.weight_dtype = None
        model.config.kv_cache_dtype = None


def test_int8_kv_cache_pools_are_int8(rng):
    """The memory contract: pools live int8 end-to-end with per-(page,
    slot, head) fp32 scale planes — 2x KV bytes saved (scales ~1/head_dim
    overhead)."""
    model = _tiny_model()
    model.config.kv_cache_dtype = "int8"
    try:
        sp = ServingPredictor(model, max_batch=2, page_size=8,
                              max_seq_len=32)
        r = sp.add_request(rng.randint(0, 97, (9,)).tolist(),
                           max_new_tokens=3)
        while sp.has_work():
            sp.step()
        assert sp.cache.k_pages.dtype == jnp.int8
        assert sp.cache.v_pages.dtype == jnp.int8
        assert sp.cache.k_scales.shape == (2, sp.cache.num_pages, 4, 8)
        assert len(r.output_ids) == 3
    finally:
        model.config.kv_cache_dtype = None


def test_unsupported_kv_cache_dtype_fails_loudly(rng):
    """An unsupported kv_cache_dtype must raise, not silently serve a
    full-precision cache (the config claims quantized memory)."""
    model = _tiny_model()
    model.config.kv_cache_dtype = "int4"
    try:
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            ServingPredictor(model, max_batch=2)
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            model.generate(paddle.to_tensor(
                rng.randint(0, 97, (1, 4)).astype(np.int64)),
                max_new_tokens=2)
    finally:
        model.config.kv_cache_dtype = None


# -- round 11: multi-chip SPMD serving over a Mesh(("mp",)) -----------------


def _need_devices(n):
    """Skip-with-reason when the forced multi-device CPU mesh is missing
    (conftest sets XLA_FLAGS=--xla_force_host_platform_device_count=8; a
    bare run without it only sees one host device)."""
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} devices (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=2)")


def test_spmd_mesh1_token_identical_to_single_chip(rng):
    """THE mesh=1 equivalence gate: the sharded unified step (head-major
    qkv layout, shard_map over a 1-chip mesh, size-1 psums) reproduces
    the single-chip step token-for-token on mixed prefill+decode packing,
    and compiles exactly once."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (3, 19, 7, 1, 12)]
    plain = ServingPredictor(model, max_batch=3, max_seq_len=48,
                             page_size=8, chunk=8)
    want = plain.generate(prompts, max_new_tokens=6)
    mesh1 = ServingPredictor(model, max_batch=3, max_seq_len=48,
                             page_size=8, chunk=8, mesh=1)
    got = mesh1.generate(prompts, max_new_tokens=6)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert mesh1.decode_trace_count == 1


@pytest.mark.parametrize("mesh", [1, 2], ids=["mesh1", "mesh2"])
def test_spmd_generate_matches_oracle(rng, mesh):
    """The acceptance gate: greedy generate over an mp mesh (the sharded
    program on one chip, and on two) matches the full-forward oracle
    token-for-token, one trace per geometry, zero on replay."""
    from paddle_tpu.models.gpt import generate_paged

    _need_devices(mesh)
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 11)).astype(np.int64)
    want = _oracle_greedy(model, ids, 8)
    got = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                         mesh=mesh).numpy()
    np.testing.assert_array_equal(got, want)
    assert generate_paged.last_decode_trace_count <= 1
    model.generate(paddle.to_tensor(ids), max_new_tokens=8, mesh=mesh)
    assert generate_paged.last_decode_trace_count == 0


def test_spmd_predictor_mesh2_continuous_batching(rng):
    """ServingPredictor over a 2-chip mesh: continuous batching with
    chunked prefill, prefix caching and CoW — the page pools stay
    head-sharded on device while the host scheduler stays global — and
    every request matches the single-chip outputs."""
    import jax

    _need_devices(2)
    model = _tiny_model()
    shared = rng.randint(0, TINY["vocab_size"], (12,)).tolist()
    prompts = [shared + [1, 2], shared + [3, 4, 5],
               rng.randint(0, TINY["vocab_size"], (7,)).tolist()]
    plain = ServingPredictor(model, max_batch=2, max_seq_len=48,
                             page_size=8, chunk=8)
    want = plain.generate(prompts, max_new_tokens=6)
    sp = ServingPredictor(model, max_batch=2, max_seq_len=48, page_size=8,
                          chunk=8, mesh=2)
    got = sp.generate(prompts, max_new_tokens=6)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert sp.decode_trace_count == 1
    # the pools live sharded on the head axis end to end
    spec = sp.cache.k_pages.sharding.spec
    assert "mp" in tuple(spec)
    assert len(sp.cache.k_pages.sharding.mesh.devices.flat) == 2
    # second wave re-hits the prefix pages (sharded pages register/share)
    sp.generate(prompts[:2], max_new_tokens=3)
    assert sp.prefix_hit_rate > 0.0
    assert sp.decode_trace_count == 1
    del jax


def test_spmd_mesh2_kernel_leg_matches_oracle(rng):
    """use_kernel=True at mesh=2: the ragged Pallas kernel runs per chip
    over its own heads' pages INSIDE shard_map (interpret mode on CPU) —
    the layout GSPMD could never partition."""
    _need_devices(2)
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 5)).astype(np.int64)
    want = _oracle_greedy(model, ids, 6)
    got = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                         use_kernel=True, page_size=8, mesh=2).numpy()
    np.testing.assert_array_equal(got, want)


def test_spmd_mesh2_quantized_token_match(rng):
    """int8 weights + int8 KV over a 2-chip mesh: the quantized stacks
    shard by output column / K rows, the scale PLANES shard with their
    head pages, and greedy decoding still matches the fp oracle on
    >= 99% of tokens with the retrace gate intact."""
    _need_devices(2)
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (9, 5, 13)]
    sp_fp = ServingPredictor(model, max_batch=3, page_size=8,
                             max_seq_len=64)
    fp_out = sp_fp.generate(prompts, max_new_tokens=10)
    model.config.weight_dtype = "int8"
    model.config.kv_cache_dtype = "int8"
    try:
        sp_q = ServingPredictor(model, max_batch=3, page_size=8,
                                max_seq_len=64, mesh=2)
        q_out = sp_q.generate(prompts, max_new_tokens=10)
        toks = [(a, b) for ao, bo in zip(fp_out, q_out)
                for a, b in zip(ao, bo)]
        assert np.mean([a == b for a, b in toks]) >= 0.99
        assert sp_q.decode_trace_count == 1
        assert sp_q.cache.k_pages.dtype == jnp.int8
        assert "mp" in tuple(sp_q.cache.k_scales.sharding.spec)
    finally:
        model.config.weight_dtype = None
        model.config.kv_cache_dtype = None


def test_spmd_params_cache_and_jits_keyed_by_mesh(rng):
    """The satellite gate: the per-model params cache and the jit cache
    key on the MESH SIGNATURE alongside the quant signature — two mesh
    sizes neither collide (distinct sharded pytrees from one extraction)
    nor retrace each other (replays at both sizes stay at zero traces)."""
    import jax

    from paddle_tpu.models.gpt import (_SERVING_PARAMS_CACHE,
                                       generate_paged)

    _need_devices(2)
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (1, 5)).astype(np.int64)
    a = model.generate(paddle.to_tensor(ids), max_new_tokens=4).numpy()
    b = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                       mesh=1).numpy()
    c = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                       mesh=2).numpy()
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
    from paddle_tpu.distributed.mesh import (make_serving_mesh,
                                             mesh_signature)

    sig1 = mesh_signature(make_serving_mesh(1))
    sig2 = mesh_signature(make_serving_mesh(2))
    by_mesh = _SERVING_PARAMS_CACHE.get(model)[1]
    assert set(by_mesh) == {None, sig1, sig2}
    # one base extraction, one sharded derivation per signature — and the
    # sharded trees are distinct objects over distinct device sets
    assert by_mesh[sig1] is not by_mesh[sig2]
    # interleaved replays: every geometry's unified jit is already
    # compiled; switching meshes must not retrace any of them
    for mesh in (2, None, 1, 2, None):
        model.generate(paddle.to_tensor(ids), max_new_tokens=4, mesh=mesh)
        assert generate_paged.last_decode_trace_count == 0
    del jax


def test_spmd_mesh_validation_errors(rng):
    """Indivisible geometries and int4 row stacks fail loudly at build
    time, not as garbage tokens."""
    model = _tiny_model()  # 4 heads
    ids = rng.randint(0, TINY["vocab_size"], (1, 4)).astype(np.int64)
    _need_devices(3)
    with pytest.raises(ValueError, match="num_heads"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2, mesh=3)
    model.config.weight_dtype = "int4"
    try:
        with pytest.raises(ValueError, match="int4"):
            model.generate(paddle.to_tensor(ids), max_new_tokens=2, mesh=2)
    finally:
        model.config.weight_dtype = None


# -- round 12: speculative decoding on the unified step ---------------------


def test_spec_generate_matches_oracle_at_k124(rng):
    """THE acceptance gate: greedy speculative decoding is token-for-token
    identical to the full-forward oracle at k in {1, 2, 4} — the accept
    rule only keeps drafts the plain greedy stream would have produced,
    so speculation can never change the output, only its cost."""
    from paddle_tpu.models.gpt import generate_paged

    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 11)).astype(np.int64)
    want = _oracle_greedy(model, ids, 16)
    for k in (1, 2, 4):
        got = model.generate(paddle.to_tensor(ids), max_new_tokens=16,
                             spec_decode_k=k, chunk=8, page_size=8).numpy()
        np.testing.assert_array_equal(got, want)
        assert generate_paged.last_decode_trace_count <= 1


def test_spec_generate_kernel_leg_matches_oracle(rng):
    """Same golden with the ragged Pallas kernel forced (interpret mode on
    CPU): the verify rows ride the kernel's per-row causal limits."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 5)).astype(np.int64)
    want = _oracle_greedy(model, ids, 8)
    got = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                         spec_decode_k=3, use_kernel=True, chunk=8,
                         page_size=8).numpy()
    np.testing.assert_array_equal(got, want)


def test_spec_predictor_matches_plain_and_counts_acceptance(rng):
    """Speculative continuous batching: token-for-token identical to the
    plain unified predictor across mixed prompt lengths (chunked prefill
    + spec decode packing in the same steps), ONE trace, and the tiny
    model's repetition attractor drives real draft acceptance."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (3, 19, 7, 1, 12)]
    plain = ServingPredictor(model, max_batch=3, max_seq_len=48,
                             page_size=8, chunk=8)
    want = plain.generate(prompts, max_new_tokens=10)
    spec = ServingPredictor(model, max_batch=3, max_seq_len=48,
                            page_size=8, chunk=8, spec_decode_k=4)
    got = spec.generate(prompts, max_new_tokens=10)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert spec.decode_trace_count == 1      # one executable for all of it
    # the workload's greedy repetition must actually be captured
    assert spec.spec_proposed > 0
    assert spec.accepted_tokens_per_step > 1.0
    assert 0.0 < spec.draft_acceptance_rate <= 1.0
    # rollback left nothing behind: every page free or parked on the LRU
    assert spec.cache.available_page_count == spec.cache.num_pages


def test_spec_sampled_stream_identical_to_plain(rng):
    """Seeded sampling through the verify rows: row j samples token
    #produced+j of the request's stream, so the speculative output is
    BIT-identical to the plain seeded predictor — speculation is exact
    for sampling too, not just greedy."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (9, 5)]
    plain = ServingPredictor(model, max_batch=2, max_seq_len=48,
                             page_size=8, chunk=8)
    want = plain.generate(prompts, max_new_tokens=8, temperature=0.8,
                          top_p=0.9, top_k=40, seed=123)
    spec = ServingPredictor(model, max_batch=2, max_seq_len=48,
                            page_size=8, chunk=8, spec_decode_k=3)
    got = spec.generate(prompts, max_new_tokens=8, temperature=0.8,
                        top_p=0.9, top_k=40, seed=123)
    assert got == want


def test_spec_generate_sampled_stream_identical_across_k(rng):
    """Seeded sampled generate is BIT-identical at every spec k,
    INCLUDING k=0: both paths key row j of lane i by (i, tokens-produced
    + j), so turning speculation on changes only cost, never output."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 7)).astype(np.int64)

    def run(k):
        return model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                              temperature=0.8, top_k=40, top_p=0.9,
                              seed=7, chunk=8, page_size=8,
                              spec_decode_k=k).numpy()

    base = run(0)
    for k in (1, 3):
        np.testing.assert_array_equal(run(k), base)


def test_spec_retraces_only_on_geometry_change(rng):
    """Adaptive/varying per-request k changes only spec_len VALUES — zero
    retraces; changing the BUILD spec_k is a new geometry: one fresh
    trace, then replays from the shared jit cache at every k."""
    from paddle_tpu.models.gpt import generate_paged

    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (1, 6)).astype(np.int64)
    model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                   spec_decode_k=2, chunk=8, page_size=8)
    assert generate_paged.last_decode_trace_count == 1
    # same geometry replays (the run mixes draft lengths 0..k already)
    model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                   spec_decode_k=2, chunk=8, page_size=8)
    assert generate_paged.last_decode_trace_count == 0
    # k=4 is a different [b, k+1] geometry: exactly one new trace
    model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                   spec_decode_k=4, chunk=8, page_size=8)
    assert generate_paged.last_decode_trace_count == 1
    # interleaving the two geometries never retraces either again
    for k in (2, 4, 2):
        model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                       spec_decode_k=k, chunk=8, page_size=8)
        assert generate_paged.last_decode_trace_count == 0


def test_spec_quantized_token_match(rng):
    """int8 weights + int8 KV under speculation: drafts quantize-on-write
    like any token, rejected pages roll back, and greedy output matches
    the fp oracle on >= 99% of tokens (the round-10 tolerance) with the
    retrace gate intact."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (9, 5, 13)]
    sp_fp = ServingPredictor(model, max_batch=3, page_size=8,
                             max_seq_len=64)
    fp_out = sp_fp.generate(prompts, max_new_tokens=10)
    model.config.weight_dtype = "int8"
    model.config.kv_cache_dtype = "int8"
    try:
        sp_q = ServingPredictor(model, max_batch=3, page_size=8,
                                max_seq_len=64, chunk=8, spec_decode_k=4)
        q_out = sp_q.generate(prompts, max_new_tokens=10)
        toks = [(a, b) for ao, bo in zip(fp_out, q_out)
                for a, b in zip(ao, bo)]
        assert np.mean([a == b for a, b in toks]) >= 0.99
        assert sp_q.decode_trace_count == 1
        assert sp_q.cache.k_pages.dtype == jnp.int8
    finally:
        model.config.weight_dtype = None
        model.config.kv_cache_dtype = None


def test_spec_mesh2_matches_oracle(rng):
    """The mesh gate: speculative greedy generate over a 2-chip mp mesh
    (verify rows through the shard_map'd step, accept epilogue
    replicated) matches the full-forward oracle token-for-token."""
    _need_devices(2)
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 11)).astype(np.int64)
    want = _oracle_greedy(model, ids, 10)
    got = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                         spec_decode_k=4, chunk=8, page_size=8,
                         mesh=2).numpy()
    np.testing.assert_array_equal(got, want)


def test_spec_composes_with_prefix_cache_and_preemption(rng):
    """Speculation under page pressure: shared prefixes, CoW divergence
    and preemption replay all compose — outputs still match the plain
    predictor and no page leaks (drafts are opportunistic: they never
    evict prefix pages or preempt anyone)."""
    model = _tiny_model()
    shared = rng.randint(0, TINY["vocab_size"], (12,)).tolist()
    prompts = [shared + [1, 2], shared + [3, 4, 5],
               rng.randint(0, TINY["vocab_size"], (6,)).tolist()]
    plain = ServingPredictor(model, max_batch=3, max_seq_len=24,
                             page_size=8, chunk=8, prefix_cache=False)
    want = plain.generate(prompts, max_new_tokens=8)
    tight = ServingPredictor(model, max_batch=3, max_seq_len=24,
                             page_size=8, num_pages=7, chunk=8,
                             spec_decode_k=4)
    reqs = [tight.add_request(p, max_new_tokens=8) for p in prompts]
    while tight.has_work():
        tight.step()
    for r, w in zip(reqs, want):
        np.testing.assert_array_equal(np.asarray(r.output_ids),
                                      np.asarray(w))
    assert tight.cache.available_page_count == tight.cache.num_pages


def test_spec_validation_errors(rng):
    model = _tiny_model()
    with pytest.raises(ValueError, match="chunk"):
        ServingPredictor(model, max_batch=2, chunk=4, spec_decode_k=4)
    ids = rng.randint(0, TINY["vocab_size"], (1, 4)).astype(np.int64)
    with pytest.raises(ValueError, match="chunk"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                       spec_decode_k=8, chunk=8)


def test_spec_request_state_dropped_on_every_finish_path(rng):
    """Per-request proposer tables and PRNG keys must drop on EVERY
    finish path — the ceiling-truncation stop and the waiting-queue
    finishes included, not just the ordinary retire (a retained n-gram
    table per request is an unbounded leak on a long-lived predictor)."""
    model = _tiny_model()
    sp = ServingPredictor(model, max_batch=1, max_seq_len=8, page_size=4,
                          chunk=4, spec_decode_k=2)
    req = sp.add_request([1, 2, 3], max_new_tokens=50,
                         temperature=0.5, seed=3)
    while sp.has_work():
        sp.step()
    assert req.state == FINISHED and req.truncated   # ceiling stop
    assert sp._drafts == {} and sp._base_keys == {}
    # finished-while-waiting path: a parked request whose budget is
    # already met must also drop its state
    sp2 = ServingPredictor(model, max_batch=1, max_seq_len=16,
                           page_size=4, chunk=4, spec_decode_k=2)
    r2 = sp2.add_request([4, 5], max_new_tokens=4, temperature=0.5)
    while not r2.output_ids:
        sp2.step()
    sp2._preempt_youngest()
    r2.output_ids.extend(r2.output_ids[-1:] * 4)   # budget met while parked
    while sp2.has_work():
        sp2.step()
    assert r2.state == FINISHED
    assert sp2._drafts == {} and sp2._base_keys == {}


def test_spec_generate_eos_tight_pool_matches_plain(rng):
    """A pool an eos-stopping plain run fits must not crash under
    speculation: draft room clamps to the pages no live row needs, so
    generate stays opportunistic and emits the identical tokens."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 6)).astype(np.int64)
    free_run = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                              page_size=4).numpy()
    eos = int(free_run[0, 1])
    plain = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                           page_size=4, num_pages=5, chunk=8,
                           eos_token_id=eos).numpy()
    spec = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                          page_size=4, num_pages=5, chunk=8,
                          eos_token_id=eos, spec_decode_k=4).numpy()
    np.testing.assert_array_equal(spec, plain)


def test_spec_tight_token_budget_never_starves_decode_lanes(rng):
    """Drafts spend only the budget left after EVERY decode lane still
    to pack has its base token reserved: with a tight custom
    token_budget, one lane's speculation must not skip the trailing
    lanes (deterministic packing order would starve the same lanes
    every step — requests that never finish)."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (3,)).tolist()
               for _ in range(3)]
    plain = ServingPredictor(model, max_batch=3, max_seq_len=48,
                             page_size=8, chunk=8)
    want = plain.generate(prompts, max_new_tokens=8)
    # budget 5 = 3 base decode tokens + 2 tokens of draft room
    sp = ServingPredictor(model, max_batch=3, max_seq_len=48, page_size=8,
                          chunk=8, spec_decode_k=4, token_budget=5)
    got = sp.generate(prompts, max_new_tokens=8)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_spec_drafts_never_preempt_scheduled_prefill(rng):
    """A decode lane's drafts must not consume the free pages a LATER
    slot's prefill chunk needs in the same step: the capacity loop
    charges every scheduled slot's plain page needs against the draft
    allowance, so a tight pool serves speculation + concurrent prefill
    with ZERO preemptions (exactly like plain decode on the same
    geometry) and identical outputs."""
    model = _tiny_model()
    a_prompt = rng.randint(0, TINY["vocab_size"], (3,)).tolist()
    b_prompt = rng.randint(0, TINY["vocab_size"], (14,)).tolist()

    def run(spec_k):
        sp = ServingPredictor(model, max_batch=2, max_seq_len=24,
                              page_size=4, num_pages=8, chunk=5,
                              spec_decode_k=spec_k)
        ra = sp.add_request(a_prompt, max_new_tokens=9)
        while not ra.output_ids:     # A reaches decode before B arrives
            sp.step()
        rb = sp.add_request(b_prompt, max_new_tokens=2)
        while sp.has_work():
            sp.step()
        return ra, rb

    ra0, rb0 = run(0)
    assert ra0.preempt_count == 0 and rb0.preempt_count == 0
    ra, rb = run(4)
    # speculating A decodes while B's chunks prefill through the tight
    # pool: drafts yield the pages, nobody gets preempted
    assert ra.preempt_count == 0 and rb.preempt_count == 0
    assert ra.output_ids == ra0.output_ids
    assert rb.output_ids == rb0.output_ids


def test_draft_allowance_reserves_base_growth_and_cow():
    """Drafts may only claim strictly-free pages AFTER the base decode
    token's own growth page and (when the write position is shared) its
    CoW destination are reserved — the claim-time clamp that keeps a
    rejected draft from ever evicting a prefix page or preempting."""
    m = KVCacheManager(num_layers=1, num_kv_heads=2, head_dim=4,
                       num_pages=4, max_batch=2, max_seq_len=32,
                       page_size=4, enable_prefix_cache=True)
    slot, _ = m.admit_prefix([1, 2, 3, 4])   # 1 page, 3 free
    m.advance(slot, 4)
    # base token needs a growth page (page boundary): 1 reserved, 2 spare
    # -> cap (1 + 1 + 2) * 4 = 16 tokens, minus written+1
    assert m.draft_allowance(slot) == 16 - 5
    # free list dry: drafts may still fill the base token's OWN page
    # (they cost no extra page), nothing beyond
    m2 = KVCacheManager(num_layers=1, num_kv_heads=2, head_dim=4,
                        num_pages=1, max_batch=1, max_seq_len=32,
                        page_size=4, enable_prefix_cache=True)
    s2 = m2.admit(1)                          # page allocated, 0 free
    assert m2.draft_allowance(s2) == 4 - 2    # in-page rows only
    # CoW reservation: a shared write page costs one more free page
    m3 = KVCacheManager(num_layers=1, num_kv_heads=2, head_dim=4,
                        num_pages=4, max_batch=2, max_seq_len=32,
                        page_size=4, enable_prefix_cache=True)
    toks = list(range(6))                     # page 0 full, page 1 partial
    s0, _ = m3.admit_prefix(toks)
    m3.advance(s0, 6)
    m3.register_prefix(s0, toks)
    s1, c1 = m3.admit_prefix(toks)            # shares both pages
    assert c1 == 5 and m3.needs_cow(s1, 5)
    # 2 free pages, write page shared: 1 reserved for the CoW copy,
    # base token fits the (about-to-be-copied) page -> 1 spare page
    have = 2
    assert m3.draft_allowance(s1) == (have + 1) * 4 - 6


def _spec_rollback_sim(spec_mgr, plain_mgr, rng, steps=1000):
    """Mirror a speculating and a never-speculating run over two managers:
    identical admissions/registrations/frees; decode steps speculate k
    drafts with m <= k accepted on the spec manager (ensure_capacity for
    1 + k, ONE prepare_write, advance 1 + m, trim) vs the plain manager
    emitting the same m + 1 tokens one step at a time."""
    base = [int(x) for x in rng.randint(0, 50, (8,))]
    prompts = [base[:4] + [int(x) for x in rng.randint(50, 99, (k,))]
               for k in (1, 3, 5, 8)] + [base, base[:6]]
    active: dict[int, list[int]] = {}
    registered: dict[int, list[int]] = {}

    def canon(m):
        """Canonical cache state, invariant to the page-ID permutation a
        one-shot (grow k, then CoW) allocation order introduces vs the
        plain run's interleaved per-token pops: per-slot (refcount,
        registration-key) at every table index, the LRU as its key
        sequence, the registry keyed by content with each page's
        refcount + LRU membership, and the free-pool size. Equal canon =
        every refcount, every pin and every free page accounted — a
        leaked draft page or a stolen pin cannot hide in a renaming."""
        rows = tuple(
            tuple((int(m._refcount[p]), m._page_key.get(int(p)))
                  if p >= 0 else None for p in row)
            for row in m._page_table)
        lru_keys = tuple(m._page_key[p] for p in m._lru)
        reg = {key: (int(m._refcount[p]), p in m._lru)
               for key, p in m._prefix_pages.items()}
        return (tuple(int(x) for x in m._seq_lens), rows,
                len(m._free_pages), lru_keys, reg)

    def check_mirror():
        assert canon(spec_mgr) == canon(plain_mgr)

    for step in range(steps):
        op = rng.rand()
        if op < 0.3 and spec_mgr.free_slot_count:
            ctx = list(prompts[rng.randint(len(prompts))])
            if spec_mgr.pages_needed(len(ctx)) <= \
                    spec_mgr.available_page_count:
                slot, cached = spec_mgr.admit_prefix(ctx)
                slot_p, cached_p = plain_mgr.admit_prefix(ctx)
                assert (slot, cached) == (slot_p, cached_p)
                active[slot] = ctx
                registered[slot] = list(ctx)
        elif op < 0.75 and active:
            slot = list(active)[rng.randint(len(active))]
            written = spec_mgr.seq_len(slot)
            ctx = active[slot]
            if written < len(ctx) - 1:
                # prefill chunk: identical on both managers
                n = min(int(rng.randint(1, 5)), len(ctx) - 1 - written)
                if not spec_mgr.ensure_capacity(slot, written + n):
                    continue
                assert plain_mgr.ensure_capacity(slot, written + n)
                cow_s = spec_mgr.prepare_write(slot, written)
                cow_p = plain_mgr.prepare_write(slot, written)
                assert (cow_s is None) == (cow_p is None)
                spec_mgr.advance(slot, n)
                plain_mgr.advance(slot, n)
            else:
                # decode: speculate k, accept m — vs m+1 plain steps
                k = int(rng.randint(0, 5))
                k = max(0, min(k, spec_mgr.draft_allowance(slot)))
                if written + 1 > spec_mgr.max_seq_len or not \
                        spec_mgr.ensure_capacity(slot, written + 1 + k):
                    spec_mgr.free(slot)
                    plain_mgr.free(slot)
                    del active[slot]
                    registered.pop(slot, None)
                    continue
                spec_mgr.prepare_write(slot, written)
                # the spec-step immutability invariant: every verify-row
                # write position owns its page exclusively
                for pos in range(written, written + 1 + k):
                    pg = int(spec_mgr._page_table[slot,
                                                  pos // spec_mgr.page_size])
                    assert pg >= 0 and spec_mgr._refcount[pg] == 1
                m = int(rng.randint(0, k + 1))
                spec_mgr.advance(slot, 1 + m)
                spec_mgr.trim_pages(slot)
                for _ in range(1 + m):
                    w = plain_mgr.seq_len(slot)
                    assert plain_mgr.ensure_capacity(slot, w + 1)
                    plain_mgr.prepare_write(slot, w)
                    plain_mgr.advance(slot, 1)
                while len(ctx) < spec_mgr.seq_len(slot) + 1:
                    ctx.append(int(rng.randint(0, 99)))   # "emitted"
            if (slot in registered
                    and spec_mgr.seq_len(slot) >= len(registered[slot])):
                spec_mgr.register_prefix(slot, registered[slot])
                plain_mgr.register_prefix(slot, registered.pop(slot))
        elif active:
            slot = list(active)[rng.randint(len(active))]
            spec_mgr.free(slot)
            plain_mgr.free(slot)
            del active[slot]
            registered.pop(slot, None)
        check_mirror()
    for slot in list(active):
        spec_mgr.free(slot)
        plain_mgr.free(slot)
    check_mirror()


def test_spec_rollback_1k_churn_identical_to_never_speculated(rng):
    """THE rollback property gate: 1k random admit / prefill / speculate
    (random accept/reject) / preempt churn leaves page refcounts, free
    lists and prefix-cache pins IDENTICAL to a mirrored never-speculated
    run (up to the pool's page-ID renaming — see ``canon``): rejected
    drafts cost exactly nothing."""
    from test_prefix_cache import _check_invariants

    def mk():
        return KVCacheManager(num_layers=2, num_kv_heads=2, head_dim=8,
                              num_pages=10, max_batch=3, max_seq_len=48,
                              page_size=4, enable_prefix_cache=True)

    spec_mgr, plain_mgr = mk(), mk()
    _spec_rollback_sim(spec_mgr, plain_mgr, rng, steps=1000)
    _check_invariants(spec_mgr)
    _check_invariants(plain_mgr)
    assert spec_mgr.available_page_count == spec_mgr.num_pages
    assert spec_mgr.prefix_hit_rate > 0.0    # the churn actually shared


def test_quantized_generate_kernel_leg_matches_oracle(rng):
    """use_kernel=True drives the fused quant GEMM + int8-KV ragged
    attention kernels in interpret mode INSIDE the serving jit (the
    use_kernel contract threads into _srv_mm, not just attention)."""
    model = _tiny_model()
    ids = rng.randint(0, TINY["vocab_size"], (2, 5)).astype(np.int64)
    want = _oracle_greedy(model, ids, 6)
    model.config.weight_dtype = "int8"
    model.config.kv_cache_dtype = "int8"
    try:
        got = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             use_kernel=True, page_size=8).numpy()
        assert _token_match_rate(got, want) >= 0.99
    finally:
        model.config.weight_dtype = None
        model.config.kv_cache_dtype = None


# -- round 13: async double-buffered engine ---------------------------------


def _cache_state(mgr):
    """Snapshot of the manager's page/refcount/prefix-pin accounting —
    everything the deferred-reconciliation property compares."""
    return dict(
        page_table=np.asarray(mgr._page_table).copy(),
        seq_lens=np.asarray(mgr._seq_lens).copy(),
        refcount=np.asarray(mgr._refcount).copy(),
        free_pages=sorted(mgr._free_pages),
        free_slots=sorted(mgr._free_slots),
        lru=list(mgr._lru),
        prefix_keys=set(mgr._prefix_pages),
    )


def _assert_cache_consistent(mgr):
    """Conservation invariants that must hold after EVERY step: refcounts
    mirror slot references, free/LRU/referenced partition the pool, and
    registered pages never sit on the free list."""
    refs = np.zeros((mgr.num_pages,), np.int64)
    for slot in range(mgr.max_batch):
        for pg in mgr._page_table[slot]:
            if pg >= 0:
                refs[int(pg)] += 1
    np.testing.assert_array_equal(refs, mgr._refcount)
    free = set(mgr._free_pages)
    lru = set(mgr._lru)
    held = {p for p in range(mgr.num_pages) if mgr._refcount[p] > 0}
    assert not free & lru and not free & held and not lru & held
    assert len(free) + len(lru) + len(held) == mgr.num_pages
    assert not any(p in mgr._page_key for p in free)
    for p in lru:
        assert p in mgr._page_key   # LRU pages stay registered (pinned)


def _churn_prompts(rng, n, max_len=20):
    return [rng.randint(0, TINY["vocab_size"],
                        (int(rng.randint(1, max_len)),)).tolist()
            for _ in range(n)]


def _drive_churn(sp, prompts, gen_len, lockstep=None, **sampling):
    """Continuous-arrival churn: keep the lanes full from ``prompts`` in
    arrival order, step until all finish + flush. Returns per-arrival
    output streams; ``lockstep`` (a callback) runs after every step."""
    queued = list(prompts)
    reqs = []
    live = lambda: sum(  # noqa: E731
        1 for r in reqs if r.state != FINISHED)
    steps = 0
    while queued or sp.has_work():
        while queued and live() < sp.max_batch:
            reqs.append(sp.add_request(queued.pop(0), gen_len, **sampling))
        sp.step()
        steps += 1
        if lockstep is not None:
            lockstep()
        assert steps < 20000, "churn stuck"
    sp.flush()
    return [list(r.output_ids) for r in reqs], steps


def test_async_matches_sync_1k_churn_greedy_and_sampled(rng):
    """THE round-13 identity gate: the async double-buffered engine must
    reproduce the synchronous engine token-for-token over a 1k-step
    continuous-arrival churn (mixed prompt lengths, admissions/
    retirements every few steps) — greedy AND seeded sampling (streams
    keyed by tokens-produced are batch-order invariant)."""
    model = _tiny_model()
    prompts = _churn_prompts(rng, 220)
    kw = dict(max_batch=3, max_seq_len=48, page_size=8, chunk=8)
    eos = None
    for sampling in (dict(),
                     dict(temperature=0.8, top_k=12, top_p=0.9, seed=3),
                     "eos"):
        if sampling == "eos":
            # third leg: eos configured — the subtlest reconcile path
            # (eos discovered one step behind the dispatch, the wasted
            # post-eos lane-step dropped as overhang, retirement one
            # step late). eos is a frequently-EMITTED token from the
            # greedy leg, so many requests genuinely stop early.
            sampling = dict(eos_token_id=eos)
        sp_sync = ServingPredictor(model, **kw)
        want, steps_sync = _drive_churn(sp_sync, prompts, 5, **sampling)
        sp_async = ServingPredictor(model, async_engine=True, **kw)
        got, steps_async = _drive_churn(sp_async, prompts, 5, **sampling)
        assert steps_sync >= 300   # a real churn, not a toy trace
        for i, (w, g) in enumerate(zip(want, got)):
            assert g == w, f"request {i} diverged ({sampling})"
        # same ONE executable, no retrace (the async feedback inputs are
        # geometry-stable)
        assert sp_async.decode_trace_count == 1
        if eos is None:
            flat = [t for w in want for t in w]
            eos = int(np.bincount(np.asarray(flat)).argmax())
    assert any(len(w) < 5 for w in want)   # eos really stopped requests


def test_async_no_completion_fast_path_defers_all_syncs(rng):
    """Satellite: a step that cannot complete any request (no eos
    configured, output budget unreachable) must not hard-sync at all —
    the general no-completion-possible fast path. The whole run defers
    until the ring fills / the final flush."""
    model = _tiny_model()
    prompt = rng.randint(0, TINY["vocab_size"], (6,)).tolist()
    sp = ServingPredictor(model, max_batch=1, max_seq_len=64, page_size=8,
                          chunk=8, async_engine=True,
                          max_inflight_steps=64)
    req = sp.add_request(prompt, max_new_tokens=30)
    for _ in range(12):
        sp.step()
    # prefill round + 11 decode dispatches, none reconciled: no token
    # has crossed to the host, no hard sync has happened
    assert sp.hard_syncs == 0
    assert req.output_ids == []
    assert req._pending_n > 0
    # and the steady-decode pack cache served most of those dispatches
    # (all-feedback steps re-serve the previous step's device arrays)
    assert sp.steady_hits >= 8
    sp.flush()
    # ONE batched materialization landed everything dispatched so far
    assert sp.hard_syncs == 1
    assert len(req.output_ids) == req._pending_n + len(req.output_ids)
    got_prefix = list(req.output_ids)
    while sp.has_work():
        sp.step()
    sp.flush()
    want = model.generate(
        paddle.to_tensor(np.asarray([prompt], np.int64)),
        max_new_tokens=30, page_size=8).numpy()[0]
    np.testing.assert_array_equal(np.asarray(req.output_ids), want)
    assert req.output_ids[:len(got_prefix)] == got_prefix
    # an eos-configured request is an emission boundary EVERY decode
    # step: the engine reconciles behind-by-one instead of deferring
    sp2 = ServingPredictor(model, max_batch=1, max_seq_len=64, page_size=8,
                           chunk=8, async_engine=True,
                           max_inflight_steps=64)
    sp2.add_request(prompt, max_new_tokens=8, eos_token_id=int(want[0]))
    sp2.step()   # prefill (+ first decode dispatch)
    syncs0 = sp2.hard_syncs
    for _ in range(3):
        sp2.step()
    assert sp2.hard_syncs > syncs0   # behind-by-one, not deferred


def test_async_deferred_reconciliation_accounting_matches_sync(rng):
    """Satellite property test: on an eos-free churn the async engine's
    scheduling is COUNT-driven and therefore step-for-step identical to
    the sync engine — after every step the page table, seq lens,
    refcounts, free lists, prefix registry and LRU pins must equal the
    sync run's, and the conservation invariants must hold throughout
    (deferral moves token VALUES, never page accounting)."""
    model = _tiny_model()
    prompts = _churn_prompts(rng, 40, max_len=24)
    kw = dict(max_batch=3, max_seq_len=48, page_size=8, chunk=8,
              num_pages=14)   # tight pool: preemption + LRU eviction
    sp_sync = ServingPredictor(model, **kw)
    sp_async = ServingPredictor(model, async_engine=True, **kw)
    queued_s, queued_a = list(prompts), list(prompts)
    reqs_s, reqs_a = [], []

    def admit(sp, queued, reqs):
        while queued and sum(1 for r in reqs
                             if r.state != FINISHED) < sp.max_batch:
            reqs.append(sp.add_request(queued.pop(0), 5))

    steps = 0
    while (queued_s or sp_sync.has_work()
           or queued_a or sp_async.has_work()):
        admit(sp_sync, queued_s, reqs_s)
        admit(sp_async, queued_a, reqs_a)
        sp_sync.step()
        sp_async.step()
        _assert_cache_consistent(sp_async.cache)
        a, b = _cache_state(sp_sync.cache), _cache_state(sp_async.cache)
        for key in a:
            if isinstance(a[key], np.ndarray):
                np.testing.assert_array_equal(a[key], b[key], err_msg=key)
            else:
                assert a[key] == b[key], f"{key} diverged at step {steps}"
        steps += 1
        assert steps < 5000, "churn stuck"
    sp_async.flush()
    for w, g in zip(reqs_s, reqs_a):
        assert g.output_ids == w.output_ids
    # quiesced: both pools fully released (prefix LRU pages may persist)
    assert (sp_async.cache.available_page_count
            == sp_sync.cache.available_page_count)


def test_async_spec_k4_composition(rng):
    """spec-decode k=4 under the async engine: drafts/rollback are
    host-value-dependent, so the engine reconciles in-step — output and
    rollback accounting must match the sync spec engine exactly."""
    model = _tiny_model()
    motifs = [np.tile(rng.randint(0, TINY["vocab_size"], (4,)),
                      6).tolist() for _ in range(5)]
    kw = dict(max_batch=2, max_seq_len=96, page_size=8, chunk=8,
              spec_decode_k=4)
    sp_s = ServingPredictor(model, **kw)
    want = sp_s.generate(motifs, max_new_tokens=10)
    sp_a = ServingPredictor(model, async_engine=True, **kw)
    got = sp_a.generate(motifs, max_new_tokens=10)
    for w, g in zip(want, got):
        assert g == w
    assert sp_a.accepted_tokens_per_step == pytest.approx(
        sp_s.accepted_tokens_per_step)
    assert sp_a.cache.available_page_count == sp_s.cache.available_page_count


def test_async_quantized_int8w_int8kv_composition(rng):
    """int8 weights + int8 KV under the async engine: bit-identical to
    the sync quantized engine (same numerics, deferred emission)."""
    model = _tiny_model()
    prompts = _churn_prompts(rng, 8, max_len=14)
    model.config.weight_dtype = "int8"
    model.config.kv_cache_dtype = "int8"
    try:
        kw = dict(max_batch=3, page_size=8, max_seq_len=64)
        want = ServingPredictor(model, **kw).generate(
            prompts, max_new_tokens=8)
        got = ServingPredictor(model, async_engine=True, **kw).generate(
            prompts, max_new_tokens=8)
        for w, g in zip(want, got):
            assert g == w
    finally:
        model.config.weight_dtype = None
        model.config.kv_cache_dtype = None


def test_async_mesh2_composition(rng):
    """mesh=2 SPMD serving under the async engine: the replicated
    emission outputs defer like single-chip ones; token streams match
    the sync mesh engine."""
    _need_devices(2)
    model = _tiny_model()
    prompts = _churn_prompts(rng, 6, max_len=12)
    kw = dict(max_batch=2, max_seq_len=48, page_size=8, chunk=8, mesh=2)
    want = ServingPredictor(model, **kw).generate(prompts,
                                                  max_new_tokens=6)
    got = ServingPredictor(model, async_engine=True, **kw).generate(
        prompts, max_new_tokens=6)
    for w, g in zip(want, got):
        assert g == w


def test_async_steady_pack_cache_identity_greedy_and_sampled(rng):
    """The steady-decode pack cache (all-feedback steps re-serving the
    previous step's device arrays) must trigger on long decode runs and
    stay token-identical to the sync engine — greedy AND seeded sampling
    (the in-jit key folds read the freshly-uploaded produced counts)."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (5, 9)]
    kw = dict(max_batch=2, max_seq_len=64, page_size=8, chunk=8)
    for sampling in (dict(),
                     dict(temperature=0.7, top_k=20, top_p=0.9, seed=11)):
        want = ServingPredictor(model, **kw).generate(
            prompts, max_new_tokens=20, **sampling)
        sp = ServingPredictor(model, async_engine=True, **kw)
        got = sp.generate(prompts, max_new_tokens=20, **sampling)
        assert got == want, f"steady-path divergence ({sampling})"
        assert sp.steady_hits > 5


def test_async_engine_is_the_default(rng):
    """Round 14 (ROADMAP item-3 follow-up): the soaked PR-8 async engine
    is the default, and async_engine=False still selects the sync oracle
    explicitly."""
    model = _tiny_model()
    assert ServingPredictor(model, max_batch=2).async_engine is True
    assert ServingPredictor(model, max_batch=2,
                            async_engine=False).async_engine is False
    # the default engine still matches the explicit sync oracle
    prompts = [rng.randint(0, TINY["vocab_size"], (5,)).tolist()
               for _ in range(2)]
    kw = dict(max_batch=2, max_seq_len=32, page_size=8)
    want = ServingPredictor(model, async_engine=False, **kw).generate(
        prompts, max_new_tokens=6)
    got = ServingPredictor(model, **kw).generate(prompts, max_new_tokens=6)
    assert got == want


def test_async_preemption_replay_flushes_pending(rng):
    """A preempted request re-admits with its full context — the engine
    must flush in-flight tokens before the replay (the value barrier).
    Under page pressure the async streams still match the per-prompt
    oracle."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (6,)).tolist()
               for _ in range(3)]
    sp = ServingPredictor(model, max_batch=3, max_seq_len=24, page_size=8,
                          num_pages=5, async_engine=True)
    reqs = [sp.add_request(p, max_new_tokens=10) for p in prompts]
    while sp.has_work():
        sp.step()
    sp.flush()
    assert sum(r.preempt_count for r in reqs) >= 1
    for p, r in zip(prompts, reqs):
        want = model.generate(
            paddle.to_tensor(np.asarray([p], np.int64)),
            max_new_tokens=10, page_size=8).numpy()[0]
        np.testing.assert_array_equal(np.asarray(r.output_ids), want)


def test_device_view_caches_skip_unchanged_uploads():
    """Satellite: the manager's device views re-serve the SAME array
    until the backing bookkeeping mutates (page table stays put over
    steady decode inside a page; seq lens invalidate on advance)."""
    m = _mgr()
    slot = m.admit(4)
    pt0 = m.page_table_device()
    sl0 = m.seq_lens_device()
    assert m.page_table_device() is pt0
    assert m.seq_lens_device() is sl0
    m.advance(slot, 1)             # within the page: seq lens only
    assert m.seq_lens_device() is not sl0
    assert m.page_table_device() is pt0
    assert m.ensure_capacity(slot, 9)   # crosses into a second page
    assert m.page_table_device() is not pt0
    # the views are snapshots: mutating the live numpy bookkeeping must
    # never reach an already-returned device array (the async engine
    # mutates right after dispatch)
    dev = m.page_table_device()
    snapshot = np.asarray(dev).copy()
    m.free(slot)
    np.testing.assert_array_equal(np.asarray(dev), snapshot)


def test_async_step_returns_tokens_one_behind(rng):
    """step() returns the tokens RECONCILED by the call: behind-by-one
    for emission-boundary steps, and the union over a flush — the sum
    over all step()/flush() returns equals every request's stream."""
    model = _tiny_model()
    prompts = _churn_prompts(rng, 6, max_len=10)
    sp = ServingPredictor(model, max_batch=2, max_seq_len=48, page_size=8,
                          chunk=8, async_engine=True)
    collected: dict[int, list[int]] = {}
    queued = list(prompts)
    reqs = []
    while queued or sp.has_work():
        while queued and sum(1 for r in reqs
                             if r.state != FINISHED) < sp.max_batch:
            reqs.append(sp.add_request(queued.pop(0), 4))
        for rid, toks in sp.step().items():
            collected.setdefault(rid, []).extend(toks)
    for rid, toks in sp.flush().items():
        collected.setdefault(rid, []).extend(toks)
    for r in reqs:
        assert collected.get(r.req_id, []) == r.output_ids


# -- bench_serve.py legs, gated through --legs ------------------------------


def test_bench_serve_overload_leg_gates():
    """The round-17 bench acceptance (via --legs, the tier-1 smoke
    subset selector): under synthetic overload the armed SLO actually
    sheds (``shed_rate > 0``) and the expired-deadline stragglers
    actually miss (``deadline_miss_rate > 0``) while the served lanes
    keep emitting (``value > 0``, no retrace) — and the interleaved
    nominal-load partner, same predictor config, sheds and misses
    EXACTLY nothing (its rates ride the overload line)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         "--legs=unified-overload"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert "error" not in rec, rec
    assert rec["leg"] == "unified-overload"
    # the overload half: sheds and deadline misses really happened, and
    # the predictor SURVIVED them serving tokens the whole time
    assert rec["value"] > 0
    assert rec["shed_rate"] > 0
    assert rec["deadline_miss_rate"] > 0
    assert 0 < rec["failed_requests"]
    assert rec["decode_retraces"] == 1            # shedding never retraces
    # failure accounting agrees with the line's own telemetry
    tel = rec["telemetry"]
    assert tel["serving_requests_shed"] > 0
    assert tel["serving_deadline_misses"] > 0
    assert (rec["failed_requests"]
            == tel["serving_requests_failed"]
            >= tel["serving_requests_shed"] + tel["serving_deadline_misses"])
    # ... and the served lanes really finished requests under the storm
    assert tel["serving_requests_finished"] > 0
    # the nominal half: the SAME armed SLO + deadlines at nominal load
    # shed and miss exactly nothing
    assert rec["nominal_shed_rate"] == 0.0
    assert rec["nominal_deadline_miss_rate"] == 0.0


def test_bench_serve_fleet_leg_gates():
    """The round-18 bench acceptance (via --legs, the tier-1 smoke
    subset selector): the two-replica fleet churn keeps serving tokens
    through injected replica churn (one deterministic kill + seeded
    stalls) — ``value > 0`` with ``failover_count >= 1`` — the
    prefix-affinity map actually decides placements on the
    round-robin prompt pool (``affinity_hit_rate > 0``), and the
    health-gated SLO sheds the flood (``shed_rate > 0``), all on the
    schema-checked line with the fleet registry telemetry riding it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         "--legs=fleet-churn"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert "error" not in rec, rec
    assert rec["leg"] == "fleet-churn"
    # replica failure was a routing event, not an outage
    assert rec["value"] > 0
    assert rec["failover_count"] >= 1
    assert rec["tokens_per_s_per_replica"] == pytest.approx(
        rec["value"] / 2, rel=0.01)
    assert 0 < rec["affinity_hit_rate"] <= 1
    assert rec["shed_rate"] > 0
    # the fleet registry rides the line and agrees with it
    tel = rec["telemetry"]
    assert tel["fleet_replica_crashes"] >= 1
    assert tel["fleet_replica_restarts"] >= 1
    assert tel["fleet_failovers"] == rec["failover_count"]
    assert tel["fleet_requests_finished"] > 0
    assert (tel["fleet_requests_submitted"]
            >= tel["fleet_requests_finished"]
            + tel["fleet_requests_failed"])


def test_bench_serve_disagg_leg_gates():
    """The round-20 bench acceptance (via --legs): the disaggregated
    1-prefill + 2-decode fleet on the mixed churn keeps serving
    (``value > 0``) with real page streaming (transfers completed,
    bytes and tokens on the wire), long-prompt TTFT p99 no worse than
    the interleaved colocated partner (1.5x + 25ms noise tolerance on
    a tiny shared CI box), ZERO fallbacks over the fault-free windows,
    fallbacks AND retries > 0 once the chaos pass arms certainty frame
    loss (graceful degradation, not an outage), and the int8-KV wire
    figure sitting well below the fp partner's (~3.1x at the smoke's
    head_dim 16; ~4x at the flagship's 64 — the scale planes are the
    difference)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         "--legs=fleet-disagg"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert "error" not in rec, rec
    assert rec["leg"] == "fleet-disagg"
    assert rec["value"] > 0
    # fault-free: disaggregation never degraded; chaos pass: it
    # degraded GRACEFULLY (fallbacks counted, the leg kept serving)
    assert rec["fault_free_fallback_count"] == 0
    assert rec["prefill_fallback_count"] > 0
    assert rec["kv_transfer_retries"] > 0
    # the wire carried real pages, 4x-cheaper int8 payloads
    assert rec["transfer_bytes_per_token"] > 0
    assert (rec["fp_transfer_bytes_per_token"]
            >= 2.5 * rec["transfer_bytes_per_token"])
    # long-prompt TTFT p99 no worse than the colocated partner (within
    # the tiny smoke shape's noise envelope)
    assert rec["ttft_p99_ms"] <= rec["colocated_ttft_p99_ms"] * 1.5 + 25
    tel = rec["telemetry"]
    assert tel["fleet_kv_transfers_completed"] > 0
    assert tel["fleet_kv_transfers_failed"] > 0       # the chaos pass
    assert tel["fleet_kv_transfer_frames_dropped"] > 0
    assert tel["fleet_kv_transfer_tokens"] > 0
    assert tel["fleet_prefill_admissions"] > 0


def test_bench_serve_tiered_leg_gates():
    """The round-21 bench acceptance (via --legs): on a reused-prompt
    churn whose prefix working set deliberately overflows the HBM pool,
    the host-tiered fleet beats its interleaved no-tier partner on BOTH
    headline axes — prefix_hit_rate strictly higher and TTFT p99
    strictly lower — with real tier traffic on the line (spills,
    restores, a verified tier hit rate), at least one drain-forced
    cross-replica pull, and a chaos pass whose lost spills + corrupted
    host payloads are DETECTED and degrade to recompute (the
    fault-free corruption figure stays exactly 0). Best-of-2: the
    strict wall-clock TTFT inequality sits near a loaded CI box's
    noise floor — one retry shields the load spike without weakening
    the deterministic counter gates (same idiom as the smoke schema
    test)."""
    try:
        _bench_serve_tiered_once()
    except AssertionError:
        _bench_serve_tiered_once()


def _bench_serve_tiered_once():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         "--legs=fleet-tiered"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert "error" not in rec, rec
    assert rec["leg"] == "fleet-tiered"
    assert rec["value"] > 0 and rec["notier_tokens_per_s"] > 0
    # the headline pair: strictly higher hit rate, strictly lower TTFT
    # p99 than the no-tier partner on the SAME arrival sequence
    assert rec["prefix_hit_rate"] > rec["notier_prefix_hit_rate"]
    assert rec["ttft_p99_ms"] < rec["notier_ttft_p99_ms"]
    # real tier traffic over the fault-free windows
    assert rec["spill_bytes"] > 0
    assert rec["restore_bytes"] > 0
    assert 0 < rec["tier_hit_rate"] <= 1
    # the drain exercise forced at least one pull over the wire
    assert rec["cross_replica_pulls"] >= 1
    # chaos: both round-21 seams fired AND the corruption was detected
    # (dropped + counted, degraded to recompute — never scattered into
    # the pool, never a failed request); fault-free windows spotless
    assert rec["tier_spill_drops"] > 0
    assert rec["tier_corrupt_detected"] > 0
    assert rec["fault_free_corrupt_detected"] == 0
    tel = rec["telemetry"]
    assert tel["fleet_prefix_pulls_completed"] >= 1
    assert (tel["fleet_prefix_pulls_started"]
            >= tel["fleet_prefix_pulls_completed"]
            + tel["fleet_prefix_pull_fallbacks"])
    assert tel["fleet_requests_finished"] > 0


def test_bench_serve_legs_filtered_baseline_omits_ratio():
    """--legs selecting a leg WITHOUT its baseline leg must omit the
    (schema-optional) vs_baseline rather than emit the 0.0 dead-baseline
    error signal on a healthy partial run."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         "--legs=unified-int8w"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert "error" not in rec, rec
    assert rec["leg"] == "unified-int8w"
    assert rec["value"] > 0
    assert "vs_baseline" not in rec, rec


def test_bench_serve_legs_selector_rejects_typo():
    """A typo'd leg name fails AT THE CLI (the known-legs enum), not as a
    silently-missing line two rounds later."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke",
         "--legs=unified-stpe"],
        cwd=root, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "unknown leg" in (proc.stderr + proc.stdout)


# -- round 19: model-based self-draft + async x spec ------------------------
# ServingPredictor(draft_source="model", draft_layers=D) swaps the n-gram
# proposer for the truncated-layer self-draft (ModelDraftEngine: the first
# D layers of the SAME serving stacks over a dedicated draft KV pool, one
# device-chained k-step proposal pass per round), and spec_k > 0 now
# composes with the async engine: drafted spec steps dispatch BEHIND-BY-ONE
# (reconciled at the next round's start) and draftless spec rounds ride the
# plain deferral + steady-pack cache. The gates: model-draft greedy ==
# plain decode token-for-token (the accept rule is unchanged), seeded
# streams identical, async spec bit-identical to sync spec with the page
# accounting in lockstep at every drain barrier, int8/mesh composition, and
# loud rejection of degenerate draft depths.


def test_model_draft_generate_matches_plain_at_k124(rng):
    """THE round-19 acceptance gate: greedy speculation with the
    truncated-layer MODEL draft source is token-for-token identical to
    plain decode at k in {1, 2, 4} — AND it actually accepts on
    NON-repetitive prompts (the n-gram proposer's blind spot): the
    1-of-2-layer draft shares the residual stream, so its argmax tracks
    the target's."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (3, 19, 7, 1, 12)]
    kw = dict(max_batch=3, max_seq_len=48, page_size=8, chunk=8)
    want = ServingPredictor(model, **kw).generate(prompts,
                                                  max_new_tokens=10)
    for k in (1, 2, 4):
        sp = ServingPredictor(model, spec_decode_k=k, draft_source="model",
                              draft_layers=1, **kw)
        got = sp.generate(prompts, max_new_tokens=10)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert sp.decode_trace_count == 1     # the verify step: one trace
        assert sp.spec_proposed > 0
        assert sp.accepted_tokens_per_step > 1.0
        assert 0.0 < sp.draft_acceptance_rate <= 1.0
        # the draft engine ran (catch-up + chain launches) and its
        # telemetry landed on the predictor registry
        flat = sp.telemetry()
        assert flat["serving_draft_model_steps"] > 0
        assert flat["serving_draft_tokens_proposed{source=model}"] > 0
        # terminal requests released their draft lanes: the draft pool
        # drains completely alongside the main pool
        assert (sp._draft_engine.cache.available_page_count
                == sp._draft_engine.cache.num_pages)
        # the healthz acceptance EMA is live (fleet routers score it)
        assert 0.0 < sp.healthz()["spec_accept_ema"] <= 1.0


def test_model_draft_kernel_leg_matches_plain(rng):
    """Same golden with the Pallas kernels forced (interpret mode on
    CPU): the draft jit rides the same ragged-attention kernel path."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (5, 9)]
    kw = dict(max_batch=2, max_seq_len=48, page_size=8, chunk=8,
              use_kernel=True)
    want = ServingPredictor(model, **kw).generate(prompts,
                                                  max_new_tokens=6)
    got = ServingPredictor(model, spec_decode_k=3, draft_source="model",
                           draft_layers=1, **kw).generate(
        prompts, max_new_tokens=6)
    assert got == want


def test_model_draft_sampled_stream_identical_to_plain(rng):
    """Seeded sampling through the verify rows with MODEL drafts: the
    accept rule keys row j by tokens-produced + j exactly as the n-gram
    path does, so the speculative output is BIT-identical to the plain
    seeded predictor — the draft source changes cost, never output."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (9, 5)]
    kw = dict(max_batch=2, max_seq_len=48, page_size=8, chunk=8)
    samp = dict(temperature=0.8, top_p=0.9, top_k=40, seed=123)
    want = ServingPredictor(model, **kw).generate(
        prompts, max_new_tokens=8, **samp)
    got = ServingPredictor(model, spec_decode_k=3, draft_source="model",
                           draft_layers=1, **kw).generate(
        prompts, max_new_tokens=8, **samp)
    assert got == want


def test_async_spec_bit_identical_to_sync_spec_1k_churn(rng):
    """THE round-19 async x spec gate: with spec_k > 0 the async engine
    (drafted steps dispatching BEHIND-BY-ONE, draftless spec rounds
    deferring like plain ones) must reproduce the sync spec engine
    token-for-token over a continuous churn — for BOTH draft sources —
    with the page/refcount/prefix-pin accounting in LOCKSTEP at every
    drain barrier and the conservation invariants holding after every
    async step."""
    model = _tiny_model()
    for source, n_prompts, layers, min_steps in (("ngram", 160, None, 200),
                                                 ("model", 90, 1, 100)):
        prompts = _churn_prompts(rng, n_prompts)
        kw = dict(max_batch=3, max_seq_len=48, page_size=8, chunk=8,
                  spec_decode_k=4, draft_source=source,
                  draft_layers=layers)
        sp_s = ServingPredictor(model, async_engine=False, **kw)
        sp_a = ServingPredictor(model, async_engine=True, **kw)
        queued_s, queued_a = list(prompts), list(prompts)
        reqs_s, reqs_a = [], []

        def admit(sp, queued, reqs):
            while queued and sum(1 for r in reqs
                                 if r.state != FINISHED) < sp.max_batch:
                reqs.append(sp.add_request(queued.pop(0), 5))

        steps = 0
        while (queued_s or sp_s.has_work()
               or queued_a or sp_a.has_work()):
            admit(sp_s, queued_s, reqs_s)
            admit(sp_a, queued_a, reqs_a)
            sp_s.step()
            sp_a.step()
            _assert_cache_consistent(sp_a.cache)
            steps += 1
            if steps % 9 == 0:
                # drain barrier: land the in-flight ring, then the whole
                # accounting must be in lockstep with the sync run
                sp_a.flush()
                a, b = _cache_state(sp_s.cache), _cache_state(sp_a.cache)
                for key in a:
                    if isinstance(a[key], np.ndarray):
                        np.testing.assert_array_equal(
                            a[key], b[key], err_msg=f"{key} ({source})")
                    else:
                        assert a[key] == b[key], (
                            f"{key} diverged at step {steps} ({source})")
            assert steps < 20000, "churn stuck"
        sp_a.flush()
        # a real churn (the model source legitimately needs FEWER steps:
        # ~3.8 accepted tokens per lane-step on this workload)
        assert steps >= min_steps
        for i, (w, g) in enumerate(zip(reqs_s, reqs_a)):
            assert g.output_ids == w.output_ids, (
                f"request {i} diverged ({source})")
        # identical speculation economics, one executable each
        assert sp_a.accepted_tokens_per_step == pytest.approx(
            sp_s.accepted_tokens_per_step)
        assert sp_a.spec_proposed == sp_s.spec_proposed
        assert sp_a.decode_trace_count == 1
        # the async engine really dispatched ahead (behind-by-one or
        # deferred) instead of forcing depth-zero reconciles
        assert sp_a.telemetry()["serving_spec_async_deferred_steps"] > 0
        assert sp_s.telemetry()["serving_spec_async_deferred_steps"] == 0


def test_model_draft_quantized_int8w_int8kv_identical_to_plain(rng):
    """int8 weights + int8 KV with MODEL drafts: the draft pool
    quantizes-on-write like the main pool, and within the quantized
    config speculation stays BIT-exact against the plain int8
    predictor (the accept rule compares the quantized model to
    itself)."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (9, 5, 13)]
    model.config.weight_dtype = "int8"
    model.config.kv_cache_dtype = "int8"
    try:
        kw = dict(max_batch=3, page_size=8, max_seq_len=64)
        want = ServingPredictor(model, **kw).generate(prompts,
                                                      max_new_tokens=8)
        sp = ServingPredictor(model, spec_decode_k=3, draft_source="model",
                              draft_layers=1, **kw)
        got = sp.generate(prompts, max_new_tokens=8)
        assert got == want
        # the draft pool really is int8 (pools follow kv_cache_dtype)
        assert sp._draft_engine.cache.k_pages.dtype == jnp.int8
    finally:
        model.config.weight_dtype = None
        model.config.kv_cache_dtype = None


def test_model_draft_mesh2_matches_plain(rng):
    """mesh=2 SPMD serving with MODEL drafts: the truncated stacks
    re-shard Megatron-style with the draft config (head-major qkv), the
    draft pool head-shards like the main one, and emissions match the
    plain mesh predictor token-for-token."""
    _need_devices(2)
    model = _tiny_model()
    prompts = _churn_prompts(rng, 6, max_len=12)
    kw = dict(max_batch=2, max_seq_len=48, page_size=8, chunk=8, mesh=2)
    want = ServingPredictor(model, **kw).generate(prompts,
                                                  max_new_tokens=6)
    got = ServingPredictor(model, spec_decode_k=3, draft_source="model",
                           draft_layers=1, **kw).generate(
        prompts, max_new_tokens=6)
    for w, g in zip(want, got):
        assert g == w


def test_model_draft_tiny_pool_stays_opportunistic(rng):
    """A draft pool too small for every lane (draft_num_pages=4) evicts
    idle draft lanes / skips proposing rather than failing — model
    drafts are as opportunistic as the n-gram ones, and emissions stay
    identical to plain decode throughout."""
    model = _tiny_model()
    prompts = [rng.randint(0, TINY["vocab_size"], (n,)).tolist()
               for n in (11, 7, 9, 5)]
    kw = dict(max_batch=3, max_seq_len=48, page_size=8, chunk=8)
    want = ServingPredictor(model, **kw).generate(prompts,
                                                  max_new_tokens=8)
    sp = ServingPredictor(model, spec_decode_k=3, draft_source="model",
                          draft_layers=1, draft_num_pages=4, **kw)
    got = sp.generate(prompts, max_new_tokens=8)
    assert got == want
    assert sp._draft_engine.cache.num_pages == 4


def test_model_draft_rejections_are_loud():
    """Degenerate draft configs fail AT CONSTRUCTION with the real
    cause: a full-depth 'draft' (draft_layers >= num_layers), a
    depth-0 model source, an unknown source name, and a model source
    with speculation off."""
    model = _tiny_model()
    kw = dict(max_batch=2, max_seq_len=48, page_size=8)
    with pytest.raises(ValueError, match="num_layers"):
        ServingPredictor(model, spec_decode_k=2, draft_source="model",
                         draft_layers=TINY["num_layers"], **kw)
    with pytest.raises(ValueError, match="num_layers"):
        ServingPredictor(model, spec_decode_k=2, draft_source="model",
                         draft_layers=TINY["num_layers"] + 3, **kw)
    with pytest.raises(ValueError, match=">= 1"):
        ServingPredictor(model, spec_decode_k=2, draft_source="model",
                         draft_layers=0, **kw)
    with pytest.raises(ValueError, match="draft_source"):
        ServingPredictor(model, spec_decode_k=2, draft_source="eagle",
                         **kw)
    with pytest.raises(ValueError, match="spec_decode_k"):
        ServingPredictor(model, draft_source="model", draft_layers=1,
                         **kw)
    # the config spelling routes the same way: spec_draft_layers > 0
    # selects the model source and validates identically
    model.config.spec_draft_layers = TINY["num_layers"]
    try:
        with pytest.raises(ValueError, match="num_layers"):
            ServingPredictor(model, spec_decode_k=2, **kw)
    finally:
        model.config.spec_draft_layers = 0


def test_draft_backoff_state_survives_preemption_replay(rng):
    """Round-19 satellite regression: a preemption replay must RESUME
    the proposer's adaptive backoff ((ema, cooldown) in
    ServingPredictor._drafts) — not restart it from the optimistic
    floor. Pinned for both sources by forcing a preempt/readmit around
    a proposer parked mid-cooldown."""
    model = _tiny_model()
    for source, layers in (("ngram", None), ("model", 1)):
        sp = ServingPredictor(model, max_batch=2, max_seq_len=48,
                              page_size=8, chunk=8, spec_decode_k=4,
                              draft_source=source, draft_layers=layers,
                              async_engine=False)
        reqs = [sp.add_request(
            rng.randint(0, TINY["vocab_size"], (6,)).tolist(),
            max_new_tokens=12) for _ in range(2)]
        for _ in range(3):
            sp.step()
        victim = reqs[-1]
        prop = sp._drafts.get(victim.req_id)
        assert prop is not None, source
        # park the proposer mid-backoff (rejections drove the EMA under
        # the floor, two cooldown ticks spent)
        prop._ema = 0.1
        prop._cool = 2
        assert prop.k == 0
        sp._preempt_youngest()
        assert victim.state == WAITING and victim.preempt_count == 1
        seen_replay = False
        while sp.has_work():
            sp.step()
            cur = sp._drafts.get(victim.req_id)
            if cur is not None and victim.state == RUNNING:
                # the replay serves the SAME proposer object with the
                # parked backoff intact: the EMA stays at the parked
                # 0.1 (the output budget is far too short to reach the
                # retry_after=16 probe re-arm) and the cooldown only
                # ever ACCUMULATES from its pre-preemption 2
                assert cur is prop, f"proposer replaced on replay ({source})"
                assert cur._ema == pytest.approx(0.1)
                assert cur._cool >= 2
                seen_replay = True
        sp.flush()
        assert seen_replay, source
        assert all(r.state == FINISHED for r in reqs)


def test_bench_serve_spec_model_leg_gates():
    """The round-19 bench acceptance (via --legs, the tier-1 smoke
    subset selector): on the NON-repetitive seeded-random churn the
    model-draft leg actually speculates (``accepted_tokens_per_step >
    1.0`` — the ROADMAP item-2 gate), keeps the async engine's
    dispatch-ahead alive with spec_k > 0 (bounded ``step_gap_frac``),
    and emits greedy streams bit-identical to its interleaved n-gram
    partner (two draft sources, one workload, one output)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         "--legs=unified-spec-model"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert "error" not in rec, rec
    assert rec["leg"] == "unified-spec-model"
    assert rec["value"] > 0 and rec["ngram_tokens_per_s"] > 0
    assert rec["decode_retraces"] == 1
    # the ROADMAP item-2 acceptance gate, on the checked line
    assert rec["accepted_tokens_per_step"] > 1.0
    assert 0.0 < rec["draft_acceptance_rate"] <= 1.0
    # the host-bubble bound was 0.2 when the draft pass cost k
    # dispatches per round; round 22's single-dispatch fused chain cut
    # whole-step wall time ~40% on this smoke shape, so the SAME
    # absolute per-step bubble is a larger fraction of a faster step —
    # the bound moves with the denominator, the bubble itself did not
    # grow (host_ms_per_step and p50_ms both DROPPED)
    assert rec["step_gap_frac"] < 0.4
    assert rec["spec_emissions_match"] == 1.0
    assert 0.0 < rec["draft_overhead_frac"] < 1.0
    # the engine + deferral telemetry is live on the line
    tel = rec["telemetry"]
    assert tel["serving_draft_model_steps"] > 0
    assert tel["serving_draft_tokens_proposed{source=model}"] > 0
    assert tel["serving_spec_async_deferred_steps"] > 0


# -- round 25: MoE serving -------------------------------------------------
# The routed-expert FFN serves through the SAME unified step as dense.
# Greedy decode must equal the no-cache full-forward oracle token-for-token — fp AND
# int8w (the expert stacks quantize per expert; _oracle_greedy over a
# dequantized-weights model is the int8w golden). Capacity drops are
# deterministic, and the async engine stays stream-identical.

MOE = dict(moe_experts=4, moe_top_k=2, moe_capacity_factor=4.0)
# capacity_factor == num_experts -> capacity >= all tokens: ZERO drops, so
# the per-decode-batch capacity race can't diverge from the full-context
# oracle's (routing is per-token; capacity is the only cross-token term).


def test_moe_predictor_matches_full_forward_oracle(rng):
    """THE round-25 acceptance gate (fp): MoE greedy via ServingPredictor
    == the eager full-forward oracle token-for-token."""
    model = _tiny_model(**MOE)
    ids = rng.randint(0, TINY["vocab_size"], (2, 9)).astype(np.int64)
    want = _oracle_greedy(model, ids, 8)
    sp = ServingPredictor(model, max_batch=2, page_size=8, max_seq_len=64)
    got = sp.generate([r.tolist() for r in ids], max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert sp.decode_trace_count == 1          # ONE unified program


def test_moe_generate_matches_oracle(rng):
    """model.generate (paged path) hits the same golden."""
    model = _tiny_model(**MOE)
    ids = rng.randint(0, TINY["vocab_size"], (2, 7)).astype(np.int64)
    want = _oracle_greedy(model, ids, 6)
    got = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                         page_size=8).numpy()
    np.testing.assert_array_equal(got, want)


def _dequantized_clone(model, weight_dtype="int8", group_size=-1):
    """Clone-in-place oracle prep: replace every stack the serving
    conversion quantizes (wqkv/wo + the MoE expert w1/w2) with its
    quantize->dequantize fp image, so the eager full-forward computes
    exactly what the quantized serving step computes."""
    import jax

    from paddle_tpu.nn.quant import _qmax, _weight_quantize_fn
    from paddle_tpu.ops.pallas.quant_matmul import dequantize_weight

    def deq(w):
        fn = lambda v: _weight_quantize_fn(
            v, qmax=_qmax(f"weight_only_{weight_dtype}"),
            int4=weight_dtype == "int4", group_size=group_size)
        if w.ndim == 3:                        # [E, K, N] expert stack
            q, s = jax.vmap(fn)(w)
            return jax.vmap(lambda qq, ss: dequantize_weight(
                qq, ss, out_dtype=w.dtype))(q, s)
        q, s = fn(w)
        return dequantize_weight(q, s, out_dtype=w.dtype)

    gpt = model.gpt if hasattr(model, "gpt") else model
    for l in gpt.layers:
        l.attn.qkv_proj.weight._data = deq(l.attn.qkv_proj.weight._data)
        l.attn.out_proj.weight._data = deq(l.attn.out_proj.weight._data)
        l.mlp.w1._data = deq(l.mlp.w1._data)
        l.mlp.w2._data = deq(l.mlp.w2._data)
    return model


def test_moe_predictor_int8w_matches_dequantized_oracle(rng):
    """THE round-25 acceptance gate (int8w): quantized-expert MoE greedy
    == the full-forward oracle over the dequantized weights,
    token-for-token (per-channel int8 dequant is one fp spelling)."""
    model = _tiny_model(**MOE)
    ids = rng.randint(0, TINY["vocab_size"], (2, 9)).astype(np.int64)
    want = _oracle_greedy(_dequantized_clone(_tiny_model(**MOE)), ids, 8)
    model.config.weight_dtype = "int8"
    try:
        sp = ServingPredictor(model, max_batch=2, page_size=8,
                              max_seq_len=64)
        got = sp.generate([r.tolist() for r in ids], max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        model.config.weight_dtype = None


def test_moe_sampled_stream_identical_sync_async(rng):
    """Seeded-sampled MoE streams: the async engine reproduces the sync
    engine token-for-token (greedy AND sampled) over churn."""
    prompts = _churn_prompts(rng, 6, max_len=12)
    kw = dict(max_batch=3, max_seq_len=64, page_size=8, chunk=8)
    for sampling in ({}, dict(temperature=0.8, top_k=12, seed=11)):
        model = _tiny_model(**MOE)
        want = ServingPredictor(model, async_engine=False, **kw).generate(
            prompts, max_new_tokens=8, **sampling)
        got = ServingPredictor(model, async_engine=True, **kw).generate(
            prompts, max_new_tokens=8, **sampling)
        assert got == want, f"moe async divergence ({sampling})"


def test_moe_capacity_drop_determinism(rng):
    """With a TIGHT capacity (drops happening), two fresh predictors
    produce identical streams — routing tie-breaks and the capacity race
    are deterministic, never dependent on engine warmup state."""
    prompts = _churn_prompts(rng, 5, max_len=14)
    kw = dict(max_batch=2, max_seq_len=64, page_size=8, chunk=8)
    runs = []
    for _ in range(2):
        model = _tiny_model(**{**MOE, "moe_capacity_factor": 0.5})
        runs.append(ServingPredictor(model, **kw).generate(
            prompts, max_new_tokens=8))
    assert runs[0] == runs[1]


def test_bench_serve_moe_leg_gates():
    """The round-25 bench acceptance (via --legs, the tier-1 smoke
    subset selector): the dense-vs-MoE interleaved A/B emits ONE
    schema-checked line carrying the router-health keys —
    expert_load_imbalance (>= 1 by construction), router_drop_rate
    (in [0, 1] at the production 1.25 capacity factor),
    active_params_frac (< 1: top-2 of 4 experts) — the paired dense
    tokens/s as the efficiency anchor, and a static-vs-analytic HBM
    drift inside the JX007 tolerance (the top_k/E expert-stack scaling
    applied on BOTH model sides)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "bench_serve.py", "--smoke", "--steps=6",
         "--batch=2", "--prompt=8", "--gen-len=3",
         "--legs=moe-churn"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert "error" not in rec, rec
    assert rec["leg"] == "moe-churn"
    assert rec["value"] > 0 and rec["dense_tokens_per_s"] > 0
    assert rec["decode_retraces"] == 1        # ONE routed program
    # the router-health contract: the keys must be LIVE, not defaulted
    assert rec["expert_load_imbalance"] >= 1.0
    assert 0.0 <= rec["router_drop_rate"] <= 1.0
    assert 0.0 < rec["active_params_frac"] < 1.0
    # the acceptance criterion: both HBM models scale the expert stacks
    # by top_k/E and agree within the serving-moe-step contract
    assert rec["hbm_bytes_per_token_static"] > 0
    assert abs(rec["hbm_model_drift_frac"]) <= 0.02


# -- PR 27: the pools stay one buffer through the step ----------------------


@pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "spec2"])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("use_kernel", [None, True],
                         ids=["scatter", "kernels"])
def test_layer_scan_carries_the_pools(rng, use_kernel, kv_quant, spec_k):
    """The traced unified step: the stacked pools (and scale planes) ride
    the layer scan's CARRY. Nothing pool-shaped is a scanned input or
    output, and no equation of the body slices a layer out of a stack or
    stacks one back (``pools[i]`` and the scan's own handling of xs / ys
    are ``dynamic_slice`` / ``dynamic_update_slice`` of the whole stack:
    five pool-sized copies per layer on the chip)."""
    import jax

    from paddle_tpu.analysis.cost_model import _iter_eqns_all, find_layer_scan

    model = _tiny_model()
    if kv_quant:
        model.config.kv_cache_dtype = "int8"
    try:
        sp = ServingPredictor(model, max_batch=2, max_seq_len=48,
                              page_size=8, use_kernel=use_kernel,
                              spec_decode_k=spec_k)
    finally:
        model.config.kv_cache_dtype = None
    step, seen = sp._unified, []

    def tapped(*args):
        seen.append(args)
        return step(*args)

    tapped.trace_count = step.trace_count
    sp._unified = tapped
    sp.generate([rng.randint(0, TINY["vocab_size"], (9,)).tolist()],
                max_new_tokens=2)
    # shapes only: the pools of the recorded call were donated to it
    closed = jax.make_jaxpr(step)(*jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), seen[0]))

    cache = sp.cache
    pools = [cache.k_pages, cache.v_pages]
    if kv_quant:
        pools += [cache.k_scales, cache.v_scales]
    stacked = {(p.shape, p.dtype) for p in pools}
    one_layer = {(p.shape[1:], p.dtype) for p in pools}

    def pooled(var, shapes=stacked | one_layer):
        aval = getattr(var, "aval", None)
        return aval is not None and (tuple(aval.shape), aval.dtype) in shapes

    scan = find_layer_scan(closed.jaxpr)
    assert scan.params["length"] == TINY["num_layers"]
    n_lead = scan.params["num_consts"] + scan.params["num_carry"]
    carried = scan.invars[scan.params["num_consts"]:n_lead]
    assert sum(pooled(v, stacked) for v in carried) == len(pools)
    assert not any(pooled(v) for v in scan.invars[n_lead:])           # xs
    assert not any(pooled(v)
                   for v in scan.outvars[scan.params["num_carry"]:])  # ys
    moves = [eqn.primitive.name
             for eqn in _iter_eqns_all(scan.params["jaxpr"].jaxpr)
             if eqn.primitive.name in ("dynamic_slice",
                                       "dynamic_update_slice")
             and pooled(eqn.invars[0])]
    assert moves == []
