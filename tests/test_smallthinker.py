"""SmallThinker on the serving path, at a small size on the CPU: the
configuration and its tree, the eager model and the serving step (every rung,
kernels interpreted and jnp) against the plain reference
(``benchmark/reference/smallthinker.py``), the controls that the cell's
comparison must tell apart, the window group under a period that starts with a
full layer, its refusals, and the counters against a hand count."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import smallthinker as reference  # noqa: E402
from paddle_tpu.inference import ServingPredictor  # noqa: E402
from paddle_tpu.models import smallthinker as model  # noqa: E402
from paddle_tpu.models.layer_kinds import (FULL, WINDOW,  # noqa: E402
                                           rope_interleaved)
from paddle_tpu.models.smallthinker import (  # noqa: E402
    SmallThinkerConfig, SmallThinkerForCausalLM)

#: hidden 64, 14 query heads over 2 key-value heads of 16 (the published
#: group of 7), window 16, two periods [F, S, S, S], 8 experts top-2
TINY = dict(vocab_size=128, hidden_size=64, num_layers=8, num_heads=14,
            num_kv_heads=2, head_dim=16, max_seq_len=128,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, sliding_window=16, initializer_range=0.1)


def _cfgj(cfg):
    """The configuration as the reference reads it (the published keys)."""
    layout = [int(k == WINDOW) for k in cfg.layer_types]
    return {
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "moe_ffn_hidden_size": cfg.moe_intermediate_size,
        "moe_num_active_primary_experts": cfg.num_experts_per_tok,
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "sliding_window_size": cfg.sliding_window, "rope_layout": layout,
        "sliding_window_layout": layout}


@pytest.fixture(scope="module")
def tiny():
    cfg = SmallThinkerConfig(**TINY)
    return cfg, SmallThinkerForCausalLM(cfg, seed=3, dtype=jnp.float32)


def _rms_share(got, want):
    """The cell's statistic per row: rms difference over the reference's
    std."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.sqrt(np.mean((got - want) ** 2, -1)) / want.std(-1)


# ---- the model --------------------------------------------------------------

def test_config_and_tree(tiny):
    cfg, m = tiny
    assert cfg.layer_types == (FULL, WINDOW, WINDOW, WINDOW) * 2
    assert model.stack_runs(cfg) == [(FULL, 1), (WINDOW, 3)] * 2
    full, window, _, _ = m.params["stacks"]
    # a run of one layer holds its weights unstacked
    assert full["wqkv"].shape == (64, (14 + 4) * 16)
    assert window["wqkv"].shape == (3, 64, (14 + 4) * 16)
    assert window["moe_w_gu"].shape == (3, 8, 64, 64)
    assert set(full) == {"ln1_g", "ln2_g", "wqkv", "wo", "moe_gate",
                         "moe_w_gu", "moe_w_d"}
    assert m.params["lm_head"].shape == (64, 128)          # untied
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(m.params))
    # the published layouts: a full layer leads each period of four
    published = SmallThinkerConfig(num_layers=8, rope_layout=[0, 1, 1, 1] * 13,
                                   sliding_window_layout=[0, 1, 1, 1] * 13)
    assert published.layer_types == cfg.layer_types
    assert published.num_window_layers == 6
    with pytest.raises(ValueError, match="same"):
        SmallThinkerConfig(**dict(TINY, rope_layout=[1] * 8))
    with pytest.raises(ValueError, match="multiple"):
        SmallThinkerConfig(**dict(TINY, num_kv_heads=3))


def test_rope_half_rotates_half_split_pairs():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3, 16)),
                    jnp.float32)
    pos = jnp.asarray([0, 1, 7, 30, 100], jnp.int32)
    got = model.rope_half(x, pos, 1.5e6)
    want = jnp.stack([jnp.stack([reference._rope(
        jnp.zeros((int(p) + 1, 16)).at[int(p)].set(x[i, h]), 1.5e6)[int(p)]
        for h in range(3)]) for i, p in enumerate(pos)])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got[0], x[0], atol=1e-6)   # position 0
    # not the interleaved pairing
    assert float(jnp.abs(got - rope_interleaved(x, pos, 1.5e6)).max()) > 0.1


def test_eager_forward_is_the_reference(tiny):
    cfg, m = tiny
    ids = np.random.default_rng(1).integers(0, 128, 50)
    got = np.asarray(model.forward(cfg, m.params, jnp.asarray([ids]))[0])
    want = np.asarray(reference.logits_at(m.params, ids, list(range(50)),
                                          _cfgj(cfg)))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()


def test_reference_continues_one_row_as_its_full_forward(tiny):
    """The reference's second answer for a row (its router's nearest choice
    taken the other way) comes from the row alone, continued from that layer
    over the other positions' keys and values: continued WITHOUT the swap it
    is the full forward's row, from any layer; with it, another answer."""
    cfg, m = tiny
    ids = np.random.default_rng(1).integers(0, 128, 50)
    positions, kept = [20, 49], []
    h = reference.hidden(m.params, ids, _cfgj(cfg), kept=kept,
                         at=jnp.asarray(positions))
    for n, pos in enumerate(positions):
        for layer in (0, 3, 7):
            same = reference.continue_row(m.params, _cfgj(cfg), kept, n, pos,
                                          layer, swap=False)
            np.testing.assert_allclose(same, h[pos], atol=2e-5)
    want, alt, ties = reference.logits_at(m.params, ids, positions,
                                          _cfgj(cfg), alternative=True)
    routing = []
    np.testing.assert_allclose(want, reference.logits_at(
        m.params, ids, positions, _cfgj(cfg), routing=routing), atol=1e-5)
    for n, (layer, margin) in enumerate(ties):
        # the layer where the 6th and 7th outputs lay nearest
        gaps = [s[n, 1] - s[n, 2] for s, _ in routing]
        assert layer == int(np.argmin(gaps)) and margin == min(gaps) >= 0
    assert (_rms_share(alt, want) > 1e-3).all()


def _rope_on_full(layer, cfg):
    window, _ = reference.layer_rule(layer, cfg)
    return window, cfg["rope_theta"]


def _no_window_edge(layer, cfg):
    return None, reference.layer_rule(layer, cfg)[1]


def _e4m3(params):
    return jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(
        a.dtype), params)


@pytest.mark.parametrize("control", [
    dict(variant=("router_y2",)), dict(variant=("silu",)),
    dict(variant=("interleaved",)), dict(rule=_rope_on_full),
    dict(rule=_no_window_edge), dict(weights=_e4m3)],
    ids=["router_y2", "silu", "interleaved", "rope_on_full",
         "no_window_edge", "e4m3"])
def test_each_control_is_far_from_the_reference(tiny, control):
    """The cell's six controls, each a reference with one thing wrong, over
    the last six rows of a prompt past the window: each fails both of the
    cell's limits, on a request's mean (0.05) and on its median (0.03)."""
    from benchmark.drivers.serve_smallthinker import (LOGITS_TOL_RMS,
                                                      LOGITS_TOL_RMS_MEDIAN,
                                                      rms_shares, verdict)

    cfg, m = tiny
    ids = np.random.default_rng(5).integers(0, 128, 60)
    rows = list(range(54, 60))
    want, alt, _ = reference.logits_at(m.params, ids, rows, _cfgj(cfg),
                                       alternative=True)
    weights = control.pop("weights", lambda p: p)
    off = reference.logits_at(weights(m.params), ids, rows, _cfgj(cfg),
                              **control)
    # each row against the nearer of the reference's two answers, as the
    # cell holds a served row
    (mean,), (median,), ok = verdict([rms_shares(off, want, alt)[0].tolist()])
    assert not ok and mean > LOGITS_TOL_RMS and \
        median > LOGITS_TOL_RMS_MEDIAN, (mean, median)


def _serve_and_compare(m, cfg, lens, **kw):
    """Serve prompts of ``lens`` tokens through the predictor, holding every
    step's logits row of each lane past its prompt against the reference's
    full forward; returns the predictor and how many rows were compared."""
    sp = ServingPredictor(m, max_batch=3, max_seq_len=128, page_size=4,
                          num_pages=96, chunk=8, **kw)
    rng = np.random.default_rng(0)
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
    return sp, compared


@pytest.mark.parametrize("use_kernel", [None, True], ids=["jnp", "pallas"])
def test_step_through_the_paged_cache_is_the_reference(tiny, use_kernel):
    """Prefill in chunks, then decode, contexts crossing the window (16) by
    several pages (4), with a budget whose ladder has two rungs: the step's
    logits against the reference's full forward at every rung, released
    pages and the mask's lower edge included."""
    cfg, m = tiny
    lens = (5, 41, 70) if use_kernel is None else (12, 20, 41)
    sp, compared = _serve_and_compare(m, cfg, lens, token_budget=24,
                                      use_kernel=use_kernel)
    assert compared == 6 * len(lens)
    t = sp.telemetry()
    assert t["kv_window_pages_released"] > 0
    assert sp._row_ladder == (16, 24)
    assert all(t.get(f"serving_rows_run{{rung={r}}}", 0) > 0
               for r in sp._row_ladder)
    assert sp.cache.window.held() == 0
    # two full layers in the full group, six window layers in the window
    assert [p.shape[0] for p in sp.cache.pools()] == [2, 2, 6, 6]


# ---- the window group and its refusals --------------------------------------

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


# ---- the counters -----------------------------------------------------------

def test_counters_against_a_hand_count(tiny):
    cfg, m = tiny
    sp = ServingPredictor(m, max_batch=2, max_seq_len=128, page_size=4,
                          num_pages=64, token_budget=16, chunk=8,
                          async_engine=False)
    prompt = np.random.default_rng(7).integers(0, 128, 37).tolist()
    sp.generate([prompt], max_new_tokens=5)
    t = sp.telemetry()
    fed = 37 + 4                                # every context token but one
    assert t["serving_rows_prefill"] + t["serving_rows_decode"] == fed
    # the window's keys: two full layers read every key, six the window
    seen = sum(p + 1 for p in range(fed))
    read = sum(min(p + 1, 16) for p in range(fed))
    assert t["serving_window_keys_context"] == 8 * seen
    assert t["serving_window_keys_read"] == 2 * seen + 6 * read
    # five chunks of 8 (from 0, 8, .. 32), then 5 + 4 rows: nine steps
    steps = 4 + 1 + 4
    assert t["serving_steps"] == steps
    # the query blocks: 14 heads a row fed; each step builds, per layer, two
    # lanes x two key-value heads x 8 rows x a group of 7 (56: whole
    # sublane tiles already)
    assert t["serving_attn_query_rows"] == 8 * fed * 14
    assert t["serving_attn_query_block_rows"] == steps * 8 * 2 * 2 * 56
    assert t["kv_window_pages_released"] == (41 - 1 - 15) // 4
    assert t["serving_moe_rows_routed"] == fed * 8 * 2
    from benchmark import run

    share, _ = run.reader_for(
        "layer_metrics", "attn_query_block_fill_share").read(
            {"counters": t})
    assert share == pytest.approx(100 * fed * 14 / (steps * 2 * 2 * 56))


def test_kv_passes_follow_the_kernels_plan_at_each_rung(tiny, monkeypatch):
    """``serving_attn_kv_passes`` over ``serving_attn_lane_layers`` is, step
    by step, the head groups of the kernel's plan at the rung the step ran.
    A fast memory (patched small) that holds both key-value heads of a
    decode rung's 112-row lane blocks but one head of a chunk rung's 224
    makes a chunk step walk every lane's keys twice, the decode lanes beside
    the chunk lane included."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "vmem_capacity", lambda: 1_000_000)
    cfg, m = tiny
    sp = ServingPredictor(m, max_batch=4, max_seq_len=128, page_size=4,
                          num_pages=96, token_budget=64, chunk=32,
                          async_engine=False)
    ladder = sp._row_ladder
    groups = {rung: [g.groups for g in sp._attn_grids[rung]]
              for rung in ladder}
    assert groups == {ladder[0]: [1, 1],
                      **{rung: [2, 2] for rung in ladder[1:]}}
    rng = np.random.default_rng(11)
    for n in (3, 4, 5, 70):
        sp.add_request(rng.integers(0, 128, n).tolist(), max_new_tokens=6)
    passes = layers = 0
    chunk_beside_decode = False
    while sp.has_work():
        before = sp.telemetry()
        sp.step()
        after = sp.telemetry()
        ran = [int(k.split("=")[1][:-1]) for k in after
               if k.startswith("serving_rows_run{")
               and after[k] != before.get(k, 0)]
        if not ran:
            continue
        (rung,) = ran
        lanes = (after["serving_attn_lane_layers"]
                 - before.get("serving_attn_lane_layers", 0)) // 8
        step_passes = (after["serving_attn_kv_passes"]
                       - before.get("serving_attn_kv_passes", 0))
        assert step_passes == lanes * 8 * groups[rung][0], rung
        decoded = (after["serving_rows_decode"]
                   - before.get("serving_rows_decode", 0))
        chunk_beside_decode |= rung != ladder[0] and decoded >= 2
        passes, layers = passes + step_passes, layers + lanes * 8
    assert chunk_beside_decode
    t = sp.telemetry()
    assert (t["serving_attn_kv_passes"], t["serving_attn_lane_layers"]) == (
        passes, layers)
    from benchmark import run

    ratio, note = run.reader_for(
        "layer_metrics", "attn_kv_passes_per_lane").read({"counters": t})
    assert 1.0 < ratio < 2.0 and ratio == passes / layers
    assert note == {"kv_passes": passes, "lane_layers": layers}
