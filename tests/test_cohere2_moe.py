"""Command A+ (``cohere2_moe``) on the serving path, at a small size on the CPU:
the eager model and the serving step against the plain reference
(``benchmark/reference/cohere2_moe.py``), the chip's shares adding up to the
uncut layer, the cache's window group, the windowed paged kernel against its
gather reference at two group sizes, the planner unchanged with no window, and
the new counters against a hand count."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import cohere2_moe as reference  # noqa: E402
from paddle_tpu.inference import KVCacheManager, ServingPredictor  # noqa: E402
from paddle_tpu.models import cohere2_moe as model  # noqa: E402
from paddle_tpu.models.cohere2_moe import (FULL, WINDOW,  # noqa: E402
                                           Cohere2MoeConfig,
                                           Cohere2MoeForCausalLM)
from paddle_tpu.models.deepseek_v2 import gated_mlp, routed_ffn  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402

#: hidden 64, 8 query heads over 2 key-value heads of 16, window 16, layers
#: [S, S, S, F], 8 experts top-2, 2 shared (the issue's small size)
TINY = dict(vocab_size=128, hidden_size=64, num_layers=4, num_heads=8,
            num_kv_heads=2, head_dim=16, max_seq_len=128,
            moe_intermediate_size=32, n_routed_experts=8,
            n_routed_experts_published=8, n_shared_experts=2,
            num_experts_per_tok=2, sliding_window=16,
            initializer_range=0.1)


def _cfgj(cfg):
    """The configuration as the reference reads it (the published keys)."""
    return {
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.moe_intermediate_size,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "num_shared_experts": cfg.n_shared_experts,
        "norm_topk_prob": cfg.norm_topk_prob,
        "experts_held_first": cfg.experts_held_first,
        "layer_norm_eps": cfg.layer_norm_eps,
        "sliding_window": cfg.sliding_window,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
        "layer_types": list(cfg.layer_types), "logit_scale": cfg.logit_scale}


@pytest.fixture(scope="module")
def tiny():
    cfg = Cohere2MoeConfig(**TINY)
    return cfg, Cohere2MoeForCausalLM(cfg, seed=3, dtype=jnp.float32)


# ---- the model ------------------------------------------------------------------

def test_config_and_tree(tiny):
    cfg, m = tiny
    assert cfg.layer_types == (WINDOW, WINDOW, WINDOW, FULL)
    assert model.stack_runs(cfg) == [(WINDOW, 3), (FULL, 1)]
    window_run, full_run = m.params["stacks"]
    # a run of one layer holds its weights unstacked
    assert window_run["wqkv"].shape == (3, 64, (8 + 4) * 16)
    assert full_run["wqkv"].shape == (64, (8 + 4) * 16)
    assert full_run["sh_w_gu"].shape == (64, 2 * 2 * 32)
    assert "lm_head" not in m.params and cfg.experts_held is None
    assert cfg.shared_expert_scale == 0.5
    with pytest.raises(ValueError, match="layer_types"):
        Cohere2MoeConfig(**dict(TINY, layer_types=("global",) * 4))
    with pytest.raises(ValueError, match="multiple"):
        Cohere2MoeConfig(**dict(TINY, num_kv_heads=3))


def test_rope_interleaved_rotates_pairs():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3, 16)),
                    jnp.float32)
    pos = jnp.asarray([0, 1, 7, 30, 100], jnp.int32)
    got = model.rope_interleaved(x, pos, 50000.0)
    want = jnp.stack([jnp.stack([reference._rope(
        jnp.zeros((int(p) + 1, 16)).at[int(p)].set(x[i, h]), 50000.0)[int(p)]
        for h in range(3)]) for i, p in enumerate(pos)])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got[0], x[0], atol=1e-6)   # position 0


def test_eager_forward_is_the_reference(tiny):
    cfg, m = tiny
    ids = np.random.default_rng(1).integers(0, 128, 50)
    got = np.asarray(model.forward(cfg, m.params, jnp.asarray([ids]))[0])
    want = np.asarray(reference.logits_at(m.params, ids, list(range(50)),
                                          _cfgj(cfg)))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    # the window and the missing rotary of the full layer both show: each
    # wrong variant of the reference is far from the model
    for rule in (lambda k, c: (None, 50000.0 if k == WINDOW else None),
                 lambda k, c: (16 if k == WINDOW else None, 50000.0)):
        off = np.asarray(reference.logits_at(m.params, ids, [49], _cfgj(cfg),
                                             rule=rule))
        assert np.abs(off - want[49]).max() > 0.02 * np.abs(want).max()


@pytest.mark.parametrize("use_kernel", [None, True], ids=["jnp", "pallas"])
def test_step_through_the_paged_cache_is_the_reference(tiny, use_kernel):
    """Prefill in chunks, then decode, contexts crossing the window (16) by
    several pages (4): the step's logits against the reference's full
    forward, released pages and the mask's lower edge included."""
    cfg, m = tiny
    sp = ServingPredictor(m, max_batch=3, max_seq_len=128, page_size=4,
                          num_pages=96, token_budget=24, chunk=8,
                          use_kernel=use_kernel)
    rng = np.random.default_rng(0)
    lens = (5, 41, 70) if use_kernel is None else (5, 41)
    step_fn, logits = sp._unified, []

    def tapped(*a):
        res = step_fn(*a)
        logits.append(res[1])
        return res

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped
    reqs = [sp.add_request(rng.integers(0, 128, n).tolist(), max_new_tokens=6)
            for n in lens]
    seen = []
    while sp.has_work():
        n0 = len(logits)
        sp.step()
        if len(logits) > n0:
            seen.append((logits[-1], {r.req_id: (s, sp.cache.seq_len(s))
                                      for s, r in sp.running.items()}))
    sp.flush()
    assert sp.decode_trace_count == 1
    compared = 0
    for r in reqs:
        ctx = r.prompt_ids + r.output_ids
        assert len(r.output_ids) == 6
        want = np.asarray(reference.logits_at(
            m.params, np.asarray(ctx), list(range(len(ctx))), _cfgj(cfg)))
        for lg, at in seen:
            slot, written = at.get(r.req_id, (None, 0))
            if slot is not None and len(r.prompt_ids) <= written < len(ctx):
                got = np.asarray(lg[slot])
                assert np.abs(got - want[written - 1]).max() \
                    < 3e-4 * np.abs(want).max()
                compared += 1
    assert compared == 6 * len(lens)
    assert sp.telemetry()["kv_window_pages_released"] > 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen published experts, eight shares of two: the held parts of all
    the shares, the shared experts and attention counted once, sum to the
    uncut reference's layer."""
    whole = Cohere2MoeConfig(**dict(TINY, n_routed_experts=16,
                                    n_routed_experts_published=16,
                                    num_layers=1, layer_types=(WINDOW,)))
    params = model.init_params(whole, 5, jnp.float32)
    p, = params["stacks"]                       # one layer, unstacked
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    y = model.layer_norm(x, p["ln1_g"], whole.layer_norm_eps)
    shared = gated_mlp(y, p["sh_w_gu"], p["sh_w_d"]) * 0.5
    total = jnp.zeros_like(x)
    for r in range(8):
        share = Cohere2MoeConfig(**dict(
            TINY, n_routed_experts=2, n_routed_experts_published=16,
            experts_held_first=2 * r, num_layers=1, layer_types=(WINDOW,)))
        assert share.experts_held == (2 * r, 2)
        held = dict(p, moe_w_gu=p["moe_w_gu"][2 * r:2 * r + 2],
                    moe_w_d=p["moe_w_d"][2 * r:2 * r + 2])
        part = routed_ffn(share, held, y)
        # the reference given the same share gives the same part
        ref_part, ranked = reference._experts(
            {k: (v[None], 0) if k in ("moe_w_gu", "moe_w_d") else v
             for k, v in held.items()}, y, _cfgj(share))
        # ... and beside it each row's chosen experts and the runner-up
        assert ranked[1].shape == (24, 3) and bool(
            (ranked[0][:, :-1] >= ranked[0][:, 1:]).all())
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + (part - shared)         # its held experts' part
    a = model.attention(whole, p, y, jnp.arange(24), WINDOW) @ p["wo"]
    got = x + a + total + shared                # attention and shared: once
    want, _ = reference._layer(p, None, x, cfg_items=reference._hashable(
        _cfgj(whole)), window=16, theta=whole.rope_theta)
    np.testing.assert_allclose(got, want, atol=5e-5)


# ---- the cache's window group ---------------------------------------------------

def _cache(**kw):
    return KVCacheManager(4, 2, 16, num_pages=64, max_batch=3,
                          max_seq_len=128, page_size=4, dtype=jnp.float32,
                          **{"window": (3, 16, 8), **kw})


def test_window_group_holds_a_bounded_number_of_pages():
    cache = _cache()
    win = cache.window
    # 16 - 1 positions behind, 8 new rows, any alignment: 6 pages and a spare
    assert win.pages_per_slot == 7 and win.num_pages == 21
    assert [p.shape for p in cache.pools()] == [
        (1, 64, 2, 4, 16)] * 2 + [(3, 21, 2, 4, 16)] * 2
    slot, _ = cache.admit_prefix(list(range(50)))
    assert win.held(slot) == 0                  # claimed as the rows come
    owners, written = {}, 0
    while written < 120:
        n = min(8, 120 - written)
        released = cache.release_window(slot)
        assert cache.ensure_capacity(slot, written + n)
        table, first = win.table[slot], int(win.first[slot])
        held = [int(pg) for pg in table if pg >= 0]
        assert len(held) == win.held(slot) <= win.pages_per_slot
        assert len(set(held)) == len(held)
        # the table covers what the step's rows see and write
        assert first * 4 <= max(0, written - 15)
        assert (first + len(held)) * 4 >= written + n
        # a released page is in no later table (until it is claimed again)
        for k, pg in enumerate(held):
            owners.setdefault(pg, first + k)
            assert owners[pg] == first + k or released
            owners[pg] = first + k
        cache.advance(slot, n)
        written += n
    assert cache.metrics.snapshot_flat()["kv_window_pages_released"] \
        == (120 - 8 - 15) // 4
    before = len(win.free)
    cache.free(slot)
    assert len(win.free) == before + 7 - 0 or len(win.free) == win.num_pages
    assert win.held() == 0 and int(win.first[slot]) == 0


def test_window_group_device_view_counts_from_the_first_held_page():
    cache = _cache()
    slot, _ = cache.admit_prefix(list(range(60)))
    for written in range(0, 40, 8):
        cache.release_window(slot)
        cache.ensure_capacity(slot, written + 8)
        cache.advance(slot, 8)
    cache.release_window(slot)
    cache.ensure_capacity(slot, 41)
    table, base = cache.window.device()
    assert int(base[slot]) == ((40 - 15) // 4) * 4 == 24
    assert table.shape == (3, 7) and int(table[slot, 0]) >= 0
    # unchanged bookkeeping: the same device arrays again
    assert cache.window.device()[0] is table


def test_refusals_over_a_window_group():
    for kw, word in ((dict(enable_prefix_cache=True), "prefix"),
                     (dict(quantize_kv=True), "quantize_kv"),
                     (dict(host_tier_bytes=1 << 20), "host_tier"),
                     (dict(window=(4, 16, 8)), "split")):
        with pytest.raises(NotImplementedError, match=word):
            _cache(**kw)
    with pytest.raises(NotImplementedError, match="window group"):
        _cache()._planes()


@pytest.mark.parametrize("kw, word", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(spec_decode_k=2), "spec_decode_k"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(draft_layers=1), "draft_layers"),
    (dict(mesh=2), "mesh")])
def test_predictor_refuses_what_is_not_built_over_a_window_group(tiny, kw,
                                                                 word):
    with pytest.raises(NotImplementedError, match=word):
        ServingPredictor(tiny[1], max_batch=2, max_seq_len=64, page_size=4,
                         num_pages=32, token_budget=16, chunk=8, **kw)


def test_grouped_heads_are_refused_under_the_mesh():
    from paddle_tpu.models.gpt import build_unified_step, shard_serving_params

    cfg = Cohere2MoeConfig(**TINY)

    class Mesh:
        shape = {"mp": 2}

    with pytest.raises(NotImplementedError, match="key-value heads"):
        shard_serving_params({"layers": {}}, Mesh(), cfg)
    with pytest.raises(NotImplementedError, match="window group"):
        build_unified_step(cfg, 4, 8, kv_quant=True)


def test_preemption_replays_and_nothing_is_lost(tiny):
    """A full pool too small for both lanes: the younger is preempted, its
    window pages freed, and its prompt replays to the same tokens; an async
    engine with steps in flight releases as the sync engine does."""
    cfg, m = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 128, n).tolist() for n in (30, 44)]

    def serve(num_pages, **kw):
        sp = ServingPredictor(m, max_batch=2, max_seq_len=128, page_size=4,
                              num_pages=num_pages, token_budget=16, chunk=8,
                              **kw)
        out = sp.generate(prompts, max_new_tokens=20)
        t = sp.telemetry()
        assert sp.cache.window.held() == 0      # every lane freed its pages
        assert len(sp.cache.window.free) == sp.cache.window.num_pages
        return out, t

    roomy, t0 = serve(64, async_engine=False)
    tight, t1 = serve(26, async_engine=False)
    ahead, t2 = serve(64, async_engine=True, max_inflight_steps=4)
    assert t0["serving_preemptions"] == 0 < t1["serving_preemptions"]
    assert roomy == tight == ahead
    assert t0["kv_window_pages_released"] == t2["kv_window_pages_released"]
    assert t1["kv_window_pages_released"] > t0["kv_window_pages_released"]


def test_a_released_page_is_claimed_again_under_steps_in_flight(tiny):
    """Two lanes decode past the window with steps in flight. A page one lane
    releases as a step is packed is claimed by the OTHER lane in the same
    step or the next, while steps dispatched before the release (whose rows
    may still read it) are unreconciled: the device runs them in order, so
    what is served is what the sync engine serves."""
    cfg, m = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 128, n).tolist() for n in (22, 23)]

    def serve(**kw):
        sp = ServingPredictor(m, max_batch=2, max_seq_len=128, page_size=4,
                              num_pages=64, token_budget=16, chunk=8, **kw)
        win, log = sp.cache.window, []
        inner = {"release": win.release_behind, "claim": win.grow}

        def logged(what):
            def call(slot, n):
                held = set(win.table[slot][win.table[slot] >= 0].tolist())
                res = inner[what](slot, n)
                now = set(win.table[slot][win.table[slot] >= 0].tolist())
                for page in held ^ now:
                    log.append((what, sp.steps, len(sp._inflight), slot,
                                page))
                return res
            return call

        win.release_behind, win.grow = logged("release"), logged("claim")
        return sp.generate(prompts, max_new_tokens=40), log

    sync, _ = serve(async_engine=False)
    ahead, log = serve(async_engine=True, max_inflight_steps=3)
    handed_on = [
        (page, step, inflight)
        for what, step, inflight, slot, page in log if what == "release"
        and inflight >= 1 and any(
            w == "claim" and pg == page and sl != slot
            and step <= st <= step + 1 for w, st, _, sl, pg in log)]
    assert handed_on, log
    assert ahead == sync


# ---- the kernel and its planner ----------------------------------------------------

@pytest.mark.parametrize("hq, hkv", [(16, 1), (4, 4), (28, 4)],
                         ids=["group16", "group1", "group7"])
@pytest.mark.parametrize("window", [16, 9, None])
def test_windowed_kernel_is_the_gather_reference(hq, hkv, window):
    rng = np.random.default_rng(0)
    b, c, pps, ps, d = 3, 8, 12, 4, 16
    num_pages = b * pps + 1
    kp, vp = (jnp.asarray(rng.normal(size=(2, num_pages, hkv, ps, d)),
                          jnp.float32) for _ in range(2))
    pt = jnp.asarray(rng.permutation(num_pages - 1)[:b * pps].reshape(b, pps),
                     jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32)
    for kv, ql in (([40, 17, 0], [1, 8, 0]), ([48, 5, 33], [8, 1, 1]),
                   ([29, 30, 31], [8, 8, 8])):
        kv, ql = jnp.asarray(kv, jnp.int32), jnp.asarray(ql, jnp.int32)
        want = pa.ragged_paged_attention_reference(
            q, kp, vp, pt, kv, ql, layer=1, window=window)
        got = pa.ragged_paged_attention(q, kp, vp, pt, kv, ql, layer=1,
                                        window=window, use_kernel=True)
        rows = (jnp.arange(c)[None, :] < ql[:, None])[:, :, None, None]
        assert float(jnp.max(jnp.abs(jnp.where(rows, got - want, 0)))) < 2e-5
    if window is not None:
        # the window is seen: a lane past it differs from full attention
        full = pa.ragged_paged_attention_reference(q, kp, vp, pt, kv, ql,
                                                   layer=1)
        assert float(jnp.max(jnp.abs((full - want)[0, :8]))) > 1e-3


def _work_items_pr35(page_table, seen, fed, *, pages, page_size, blocks, num_pages,
               keep, lane=None):
    """``work_items`` as it stood before it took ``first`` (PR 35), kept
    as the oracle: with no window the planner must list what this lists."""
    g, pps = seen.shape[0], page_table.shape[1]
    i32 = jnp.int32
    n = g * blocks
    live_blocks = jnp.where(fed > 0, -(-seen // i32(pages * page_size)), 0)
    per_group = jnp.maximum(live_blocks, 1) if keep else live_blocks
    ends = jnp.cumsum(per_group)
    item = jnp.arange(n, dtype=i32)
    group = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1),
                        g - 1).astype(i32)
    block = item - (ends - per_group)[group]
    last = (block == per_group[group] - 1).astype(i32)
    # operand k's slot of a group's key block j holds keys while j * pages +
    # k is one of the group's live page slots: in its first ``reach[g, k]``
    # blocks. Past them it names what it named in the last of them, or where
    # the group has none, in the last group before that has one (``held``: a
    # dense comparison over groups, not a scan over items; -1: nothing named
    # yet, so what item 0 would name). Which page that is, is worked out per
    # group and operand; the items only pick rows of these small tables and
    # ``pages`` adjacent entries of the page table (an element-by-element
    # gather over items x pages costs the chip 9 ns an element)
    assert pages <= pps <= blocks * pages, (pages, pps, blocks)
    k = jnp.arange(pages, dtype=i32)
    slots = jnp.where(fed > 0, jnp.minimum(-(-seen // i32(page_size)), pps),
                      0)
    reach = jnp.maximum(-(-(slots[:, None] - k[None, :]) // i32(pages)), 0)
    of = jnp.arange(g, dtype=i32)
    held = jnp.max(jnp.where((reach > 0)[None] & (of[None, :] <= of[:, None]
                                                 )[:, :, None],
                             of[None, :, None], -1), axis=1)      # [g, pages]
    table = jnp.pad(jnp.clip(page_table, 0, num_pages - 1),
                    ((0, 0), (0, blocks * pages - pps))
                    ).reshape(-1, blocks, pages)
    rows = of if lane is None else lane
    held_in = jnp.where(held < 0, group[0], held)
    held_block = jnp.where(held < 0, 0, reach[held_in, k[None, :]] - 1)
    held_page = table[rows[held_in], held_block, k[None, :]]      # [g, pages]
    own = table[rows[group], jnp.minimum(block, blocks - 1)]      # [n, pages]
    named = jnp.where(block[:, None] < reach[group], own, held_page[group])
    total = ends[-1] if keep else jnp.maximum(ends[-1], 1)
    return total.astype(i32), group, block.astype(i32), last, named.reshape(-1)



@pytest.mark.parametrize("lanes, pps, heads, head_dim", [
    (24, 32, 12, 128), (32, 96, 8, 128)], ids=["590m", "longer"])
def test_planner_lists_the_same_items_with_no_window(lanes, pps, heads,
                                                     head_dim):
    """On the GPT cells' shapes (24 lanes of 2,048 tokens, 12 heads of 128, page 64, chunk 64)
    the plan and the work items are what they were, item for item."""
    plan = pa.ragged_grid(lanes, pps, 64, heads, heads, 64, head_dim,
                          jnp.bfloat16, jnp.bfloat16)
    assert plan.vmem == 0 and plan == pa.RaggedGrid(*plan[:9])
    rng = np.random.default_rng(lanes)
    table = jnp.asarray(rng.integers(0, 700, (lanes, pps)), jnp.int32)
    for _ in range(4):
        kv = rng.integers(1, pps * 64, lanes)
        ql = np.minimum(rng.choice([0, 1, 1, 1, 256, 77], lanes), kv)
        kv, ql = jnp.asarray(kv, jnp.int32), jnp.asarray(ql, jnp.int32)
        new = pa._work_items(table, kv, ql, plan, 768)
        old = _work_items_pr35(table, kv, ql, pages=plan.pages,
                               page_size=plan.page_size, blocks=plan.blocks,
                               num_pages=768, keep=True)
        for a, b in zip(new, old):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and a window that reaches past every context changes nothing
        wide = pa._work_items(table, kv, ql, plan, 768, window=pps * 64 + 256)
        for a, b in zip(wide, old):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_planner_starts_a_window_lane_at_its_first_live_block():
    plan = pa.ragged_grid(2, 32, 8, 16, 1, 4, 16, jnp.float32, jnp.float32)
    assert plan.keys == 16
    table = jnp.arange(64, dtype=jnp.int32).reshape(2, 32)
    kv, ql = jnp.asarray([100, 9], jnp.int32), jnp.asarray([1, 8], jnp.int32)
    total, lane, block, last, named = pa._work_items(table, kv, ql, plan,
                                                     64, window=16)
    # lane 0: its row at 99 sees 84..99: blocks 5 and 6 of 7; lane 1: block 0
    assert int(total) == 3
    assert lane[:3].tolist() == [0, 0, 1] and block[:3].tolist() == [0, 1, 0]
    assert last[:3].tolist() == [0, 1, 1]
    # a slot past the context names what its operand named the item before
    assert named.reshape(-1, plan.pages)[:3].tolist() == [
        [20, 21, 22, 23], [24, 21, 22, 23], [32, 33, 34, 23]]
    assert pa.first_live_key(100, 1, 16) == 84
    assert pa.first_live_key(9, 8, 16) == 0 == pa.first_live_key(9, 8, None)
    assert plan.live_steps(100, 1, 16) == 2 and plan.live_steps(100, 1) == 7
    assert plan.steps([100, 9], [1, 8], 16) == 3


def test_a_chunk_past_the_budget_asks_for_its_fast_memory():
    # 16 query heads a key-value head x 256 rows: one head's blocks are past
    # Mosaic's scoped default; the chip's fast memory holds 4 of the 8, and
    # the call asks Mosaic for what they take
    plan = pa.ragged_grid(32, 896, 256, 128, 8, 64, 128, jnp.bfloat16,
                          jnp.bfloat16)
    assert plan.heads == 4 and plan.rows == 4096 and plan.few_rows == 16
    assert pa.VMEM_DEFAULT < plan.vmem <= pa.V5E_VMEM
    # the decode rung's block is cut to the rung's 32 rows, 512 a head: a
    # grid step takes all 8 heads, past the default, and asks for them
    assert pa.lane_block_rows(256, 32, 128, 8) == 32
    assert pa.lane_block_rows(256, 544, 128, 8) == 256
    small = pa.ragged_grid(32, 896, 32, 128, 8, 64, 128, jnp.bfloat16,
                           jnp.bfloat16)
    assert (small.rows, small.heads, small.groups) == (512, 8, 1)
    assert pa.VMEM_DEFAULT < small.vmem < 24 << 20
    # one query head a key-value head: one block shape at every rung
    assert pa.lane_block_rows(64, 24, 12, 12) == 64
    gpt = pa.ragged_grid(24, 32, 64, 12, 12, 64, 128, jnp.bfloat16,
                         jnp.bfloat16)
    assert gpt.heads == 12 and gpt.vmem == 0


# ---- the counters ---------------------------------------------------------------

def test_window_counters_against_a_hand_count(tiny):
    cfg, m = tiny
    sp = ServingPredictor(m, max_batch=2, max_seq_len=128, page_size=4,
                          num_pages=64, token_budget=16, chunk=8,
                          async_engine=False)
    prompt = np.random.default_rng(7).integers(0, 128, 37).tolist()
    sp.generate([prompt], max_new_tokens=5)
    t = sp.telemetry()
    fed = 37 + 4                                # every context token but one
    assert t["serving_rows_prefill"] + t["serving_rows_decode"] == fed
    seen = sum(p + 1 for p in range(fed))
    read = sum(min(p + 1, 16) for p in range(fed))
    assert t["serving_window_keys_context"] == 4 * seen
    assert t["serving_window_keys_read"] == seen + 3 * read
    # the grid's steps: five chunks of 8 (from 0, 8, .. 32), then 5 + 4 rows
    grid, wgrid = sp._attn_grid, sp._attn_grid_window
    assert (grid.keys, wgrid.keys, grid.groups) == (16, 16, 1)
    rows = [(8 * i + 8, 8) for i in range(4)] + [(37, 5)] + [
        (38 + i, 1) for i in range(4)]
    live_full = sum(-(-c // 16) for c, _ in rows)
    # a window layer's table starts at the lane's first held page
    live_window = 0
    for c, n in rows:
        base = (max(0, c - n - 15) // 4) * 4
        live_window += -(-(c - base) // 16) - max(0, c - base - n - 15) // 16
    assert t["serving_attn_blocks_live"] == live_full + 3 * live_window
    assert t["serving_attn_blocks_grid"] == (live_full + 3 * live_window
                                             + 4 * len(rows))   # an idle lane
    assert t["kv_window_pages_released"] == (41 - 1 - 15) // 4
    assert t["serving_moe_rows_routed"] == fed * 4 * 2
