"""Round-12 n-gram / prompt-lookup draft proposer (inference/draft.py):
lookup edge cases (empty/short contexts, most-recent-match preference,
chained copying), determinism across preemption replay, and adaptive-k
backoff monotonicity: host-only — no model, no jit. Then the round-22
single-dispatch draft chain (``models/gpt.py build_draft_chain``) against k
single-step dispatches, on a tiny model in interpret mode.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (framework config: x64 off, cpu)
from paddle_tpu.inference.draft import DraftProposer


def test_validation():
    with pytest.raises(ValueError, match="max_k"):
        DraftProposer(0)
    with pytest.raises(ValueError, match="max_ngram"):
        DraftProposer(4, max_ngram=0)


def test_empty_and_short_contexts_propose_nothing():
    p = DraftProposer(4)
    assert p.propose([], 4) == []
    assert p.propose([7], 4) == []          # 1 token: no earlier match
    assert p.propose([7, 8], 0) == []       # zero budget
    assert p.propose([7, 8], -1) == []
    # two distinct tokens: nothing recurs
    assert p.propose([7, 8], 4) == []


def test_lookup_copies_continuation_of_earlier_match():
    # ... A B C x y A B C -> the trailing "A B C" matched earlier, copy
    # what followed it: x y
    p = DraftProposer(4, max_ngram=3)
    ctx = [1, 2, 3, 50, 60, 1, 2, 3]
    assert p.propose(ctx, 2) == [50, 60]


def test_repeated_ngrams_pick_most_recent_match():
    # "A B" occurs twice earlier with different continuations: the MOST
    # RECENT one (-> 77) must win, not the older (-> 66)
    p = DraftProposer(1, max_ngram=2)
    ctx = [1, 2, 66, 9, 1, 2, 77, 9, 1, 2]
    assert p.propose(ctx, 1) == [77]


def test_longest_ngram_preferred():
    # trailing "B C" has a 2-gram match (-> 88) but the longer "A B C"
    # also matches (-> 99): the longer context wins
    p = DraftProposer(1, max_ngram=3)
    ctx = [5, 2, 3, 88, 1, 2, 3, 99, 4, 1, 2, 3]
    assert p.propose(ctx, 1) == [99]


def test_chained_lookup_fills_k_on_short_period():
    # the greedy-decode attractor: a period-1 tail. The most recent
    # 1-gram match only has ONE following token in the real context; the
    # chained lookup extends through its own drafts to fill the budget
    p = DraftProposer(6, max_ngram=3)
    ctx = [9, 4, 7, 7, 7]
    assert p.propose(ctx, 6) == [7] * 6
    # period-2 tail chains the alternation forward
    p2 = DraftProposer(6, max_ngram=3)
    ctx2 = [9, 1, 2, 1, 2, 1, 2]
    assert p2.propose(ctx2, 4) == [1, 2, 1, 2]


def test_table_survives_preemption_replay():
    """A preemption replay re-feeds the identical context: the proposer
    (its index high-water mark included) must produce the identical
    drafts — the draft-side twin of the seeded sample streams."""
    rng = np.random.RandomState(0)
    base = [int(x) for x in rng.randint(0, 50, (24,))]
    ctx = base + base[:8]            # long self-repetition
    p = DraftProposer(4)
    first = p.propose(ctx, 4)
    assert first == p.propose(ctx, 4)     # replay: same table, same drafts
    # growing the context keeps earlier entries consistent (incremental
    # sync must equal a fresh proposer's full sync)
    grown = ctx + base[8:12]
    fresh = DraftProposer(4)
    assert p.propose(grown, 4) == fresh.propose(grown, 4)


def test_adaptive_k_backoff_monotone_and_recovers():
    """Backoff monotonicity: under a stream of total rejections k never
    increases and reaches 0 (speculation priced off); under acceptances
    it never decreases back at full k; while disabled, the cooldown
    re-arms a probe so a workload that turns repetitive gets retried."""
    p = DraftProposer(4, retry_after=3)
    assert p.k == 4                  # optimistic start
    ks = [p.k]
    for _ in range(12):
        p.update(4, 0)               # every draft rejected
        ks.append(p.k)
    assert all(a >= b for a, b in zip(ks, ks[1:]))   # monotone backoff
    assert ks[-1] == 0
    # disabled: plain-decode steps tick the cooldown, then a probe re-arms
    for _ in range(2):
        p.update(0, 0)
        assert p.k == 0
    p.update(0, 0)
    assert p.k > 0                   # probe re-armed
    # full acceptance: k climbs monotonically back to max
    ks = [p.k]
    for _ in range(12):
        p.update(ks[-1] or 1, ks[-1] or 1)
        ks.append(p.k)
    assert all(a <= b for a, b in zip(ks, ks[1:]))
    assert ks[-1] == 4


def test_propose_respects_adaptive_k_and_budget():
    p = DraftProposer(4)
    ctx = [3, 7, 7, 7, 7]
    assert len(p.propose(ctx, 2)) == 2     # budget clamps
    while p.k > 0:
        p.update(4, 0)
    assert p.propose(ctx, 4) == []         # backed off: plain decode


def test_model_draft_proposer_shares_adaptive_k_surface():
    """Round 19: ModelDraftProposer keeps the n-gram proposer's
    adaptive-k / EMA / cooldown machinery verbatim (so the scheduler's
    clamps and the preemption-replay persistence apply unchanged); only
    the proposal source changes — it delegates to the shared engine,
    and a backed-off proposer never consults the engine at all."""
    from paddle_tpu.inference.draft import ModelDraftProposer

    class FakeEngine:
        def __init__(self):
            self.calls = []

        def propose(self, lanes):
            self.calls.append(lanes)
            return {k: [1] * min(v[2], 2) for k, v in lanes.items()}

    eng = FakeEngine()
    p = ModelDraftProposer(4, eng, 7)
    assert p.k == 4                          # optimistic start, inherited
    assert p.propose([5, 6, 7], 3) == [1, 1]
    assert eng.calls[0][0][0] == 7           # req_id threaded through
    assert eng.calls[0][0][2] == 3           # k clamped by budget
    for _ in range(12):
        p.update(4, 0)                       # every draft rejected
    assert p.k == 0                          # EMA backoff, inherited
    assert p.propose([5, 6, 7], 3) == []     # backed off: no engine call
    assert len(eng.calls) == 1


# -- round 22: the single-dispatch draft chain ------------------------------

H, HD = 32, 8                 # 4 heads — tiny but MXU-shaped
PAGE = 8


def _pools(rng, num_pages, nh, kv_quant):
    if kv_quant:
        kq = jnp.asarray(rng.randint(-127, 128,
                                     (num_pages, nh, PAGE, HD)), jnp.int8)
        vq = jnp.asarray(rng.randint(-127, 128,
                                     (num_pages, nh, PAGE, HD)), jnp.int8)
        ks = jnp.asarray(np.abs(rng.randn(num_pages, nh, PAGE)) * 0.01
                         + 1e-3, jnp.float32)
        vs = jnp.asarray(np.abs(rng.randn(num_pages, nh, PAGE)) * 0.01
                         + 1e-3, jnp.float32)
        return kq, vq, ks, vs
    kq = jnp.asarray(rng.randn(num_pages, nh, PAGE, HD), jnp.float32)
    vq = jnp.asarray(rng.randn(num_pages, nh, PAGE, HD), jnp.float32)
    return kq, vq, None, None


VOCAB = 97


def _draft_cfg_params(draft_layers=1):
    """A tiny 2-layer target model's serving params, sliced to the
    truncated draft stack — the chain runs the SAME weights the engine
    would."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       draft_serving_params, serving_params)

    paddle.seed(11)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=H, num_layers=2,
                    num_heads=H // HD, max_seq_len=64)
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model, draft_serving_params(serving_params(model),
                                            draft_layers)


def _chain_geometry(rng, b=3, pps=2, kv_quant=False):
    """Per-lane draft-pool state: a mid-context lane, a deeper lane, an
    idle lane (steps 0) — page capacity pre-reserved for kv0 + k like the
    engine does."""
    nh = H // HD
    # serving pools carry a leading LAYER axis (the chain's inner scan
    # runs over it); the truncated draft stack has 1 layer
    pools = tuple(None if x is None else x[None]
                  for x in _pools(rng, b * pps, nh, kv_quant))
    pt = np.arange(b * pps, dtype=np.int32).reshape(b, pps)
    kv0 = np.array([5, 9, 0][:b], np.int32)
    first = rng.randint(0, VOCAB, (b,)).astype(np.int32)
    return pools, jnp.asarray(pt), kv0, first


@pytest.mark.parametrize("k", [1, 2, 4])
def test_draft_chain_bit_identical_to_per_step_chain(rng, k):
    """THE round-22 draft-chain contract: the fused k-step chain (one
    dispatch, device-side scan) is BIT-identical — drafts AND pool
    writes — to k separate single-step dispatches chained through the
    host, at ragged per-lane depths (one lane a step behind, one idle)."""
    from paddle_tpu.models.gpt import build_draft_chain

    cfg, _, dparams = _draft_cfg_params()
    (kp0, vp0, _, _), pt, kv0, first = _chain_geometry(rng)
    steps = np.array([k, max(k - 1, 1), 0], np.int32)
    kp_np, vp_np = np.asarray(kp0), np.asarray(vp0)

    fused = build_draft_chain(cfg, 1, PAGE, k)
    res = fused(dparams, jnp.asarray(first), jnp.asarray(steps),
                jnp.asarray(kv0), jnp.asarray(kp_np), jnp.asarray(vp_np),
                pt)
    drafts_fused = np.asarray(res[0])

    single = build_draft_chain(cfg, 1, PAGE, 1)
    kp, vp = jnp.asarray(kp_np), jnp.asarray(vp_np)
    ids = np.asarray(first)
    per_step = []
    for j in range(k):
        active = steps > j
        r = single(dparams, jnp.asarray(ids),
                   jnp.asarray(active.astype(np.int32)),
                   jnp.asarray(kv0 + j), kp, vp, pt)
        d = np.asarray(r[0])[:, 0]
        per_step.append(np.where(active, d, 0))
        ids = np.where(active, d, ids).astype(np.int32)
        kp, vp = r[1], r[2]
    np.testing.assert_array_equal(drafts_fused, np.stack(per_step, 1))
    np.testing.assert_array_equal(np.asarray(res[1]), np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(res[2]), np.asarray(vp))
    # the idle lane proposed nothing and wrote nothing
    assert not drafts_fused[2].any()
    lane2 = np.asarray(pt)[2]
    np.testing.assert_array_equal(np.asarray(res[1])[0][lane2],
                                  kp_np[0][lane2])


def test_draft_chain_int8kv_payloads_bit_identical(rng):
    """The int8-KV chain: fused vs per-step single dispatches — the
    quantized payloads AND scale rows land bit-identically (both sides
    share the paged_write_packed_quant formula)."""
    from paddle_tpu.models.gpt import build_draft_chain

    cfg, _, dparams = _draft_cfg_params()
    (kp0, vp0, ks0, vs0), pt, kv0, first = _chain_geometry(rng,
                                                           kv_quant=True)
    steps = np.array([2, 2, 0], np.int32)
    raw = tuple(np.asarray(x) for x in (kp0, vp0, ks0, vs0))

    fused = build_draft_chain(cfg, 1, PAGE, 2, kv_quant=True)
    res = fused(dparams, jnp.asarray(first), jnp.asarray(steps),
                jnp.asarray(kv0), *(jnp.asarray(x) for x in raw), pt)

    single = build_draft_chain(cfg, 1, PAGE, 1, kv_quant=True)
    pools = tuple(jnp.asarray(x) for x in raw)
    ids = np.asarray(first)
    for j in range(2):
        active = steps > j
        r = single(dparams, jnp.asarray(ids),
                   jnp.asarray(active.astype(np.int32)),
                   jnp.asarray(kv0 + j), *pools, pt)
        ids = np.where(active, np.asarray(r[0])[:, 0], ids).astype(np.int32)
        pools = r[1:]
    for got, want in zip(res[1:], pools):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert res[1].dtype == jnp.int8


def test_draft_chain_preemption_replay_self_heals(rng):
    """The engine-level self-heal (round 22, fused chain): after a
    proposal round, a DIVERGED continuation (the target rejected mid-
    draft) and a SHORTER context (preemption replay) must both roll the
    draft KV back to the longest common fed prefix and propose exactly
    what a fresh engine proposes — no commit protocol, one comparison."""
    from paddle_tpu.inference.draft import ModelDraftEngine
    from paddle_tpu.models.gpt import serving_params

    cfg, model, _ = _draft_cfg_params()
    params = serving_params(model)
    kw = dict(page_size=PAGE, chunk=4, max_batch=2, max_seq_len=64,
              max_k=3)
    eng = ModelDraftEngine(cfg, params, 1, **kw)
    ctx = rng.randint(0, VOCAB, (9,)).tolist()
    d1 = eng.propose({0: (7, ctx, 3)})[0]
    assert len(d1) == 3

    # diverged continuation: the target accepted d1[0] then emitted its
    # own token — the fed tail past the fork must be rolled back
    ctx2 = ctx + [int(d1[0]), (int(d1[1]) + 1) % VOCAB]
    got = eng.propose({0: (7, ctx2, 3)})[0]
    want = ModelDraftEngine(cfg, params, 1, **kw).propose(
        {0: (7, ctx2, 3)})[0]
    assert got == want and len(got) == 3

    # preemption replay: the request returns with a SHORTER context
    ctx3 = ctx[:5]
    got = eng.propose({0: (7, ctx3, 2)})[0]
    want = ModelDraftEngine(cfg, params, 1, **kw).propose(
        {0: (7, ctx3, 2)})[0]
    assert got == want and len(got) == 2
