"""Ouro on the serving path, at a small size on the CPU: the configuration
and its tree, the eager model and the serving step (kernels interpreted and
jnp) against the plain reference (``benchmark/reference/ouro.py``), each
(loop, layer)'s own plane of the pools, the controls that the cell's
comparison must tell apart, the refusals, one traced layer body for every
loop, and the counters against a hand count."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import ouro as reference  # noqa: E402
from paddle_tpu.inference import ServingPredictor  # noqa: E402
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM  # noqa: E402

#: two layers of hidden 256, 4 heads of 64, three loops
TINY = dict(vocab_size=256, hidden_size=256, num_layers=2, num_heads=4,
            num_kv_heads=4, head_dim=64, intermediate_size=512,
            max_seq_len=128, total_ut_steps=3, initializer_range=0.05)
#: the predictor's geometry: pages of 4 tokens, chunks of 8 rows
SERVE = dict(max_batch=3, max_seq_len=128, page_size=4, num_pages=96,
             chunk=8)


def _cfgj(cfg):
    """The configuration as the reference reads it (the published keys)."""
    return {"num_attention_heads": cfg.num_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "total_ut_steps": cfg.total_ut_steps}


@pytest.fixture(scope="module")
def tiny():
    cfg = OuroConfig(**TINY)
    return cfg, OuroForCausalLM(cfg, seed=3, dtype=jnp.float32)


# ---- the model --------------------------------------------------------------

def test_config_and_tree(tiny):
    cfg, m = tiny
    layers = m.params["layers"]
    assert set(layers) == {"ln1_g", "ln1b_g", "ln2_g", "ln2b_g", "wqkv",
                           "wo", "w_gu", "w_d"}
    assert layers["wqkv"].shape == (2, 256, 3 * 4 * 64)
    assert layers["w_gu"].shape == (2, 256, 2 * 512)
    assert m.params["lm_head"].shape == (256, 256)          # untied
    assert m.params["exit_gate_w"].shape == (256, 1)
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(m.params))
    assert cfg.cache_planes == 6
    assert [cfg.cache_plane(t, l) for t in range(3) for l in range(2)] \
        == list(range(6))
    # the published widths: 51,388,416 parameters a layer, 201,330,689
    # outside the layers
    published = OuroConfig(num_layers=1)
    assert published.num_params() == 51_388_416 + 201_330_689


def test_a_threshold_under_one_is_refused():
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        OuroConfig(**dict(TINY, early_exit_threshold=0.9))
    with pytest.raises(ValueError, match="multi-head"):
        OuroConfig(**dict(TINY, num_kv_heads=2))


def test_eager_forward_is_the_reference(tiny):
    cfg, m = tiny
    ids = np.random.default_rng(1).integers(0, 256, 40)
    got = np.asarray(m(jnp.asarray([ids]))._data[0])
    want = np.asarray(reference.logits_at(m.params, ids, list(range(40)),
                                          _cfgj(cfg)))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # and the loops matter: one loop is another model
    once = np.asarray(reference.logits_at(m.params, ids, list(range(40)),
                                          _cfgj(cfg), variant=("one_loop",)))
    assert np.abs(once - want).max() > 0.1 * np.abs(want).max()


# ---- the serving step against the reference ---------------------------------

def _rms_share(got, want):
    """The cell's statistic per row: rms difference over the reference's
    std."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.sqrt(np.mean((got - want) ** 2, -1)) / want.std(-1)


def _serve(m, lens, *, answer=6, **kw):
    """Serve prompts of ``lens`` tokens; returns the predictor, the requests
    and per dispatched step ``(logits, {req_id: (slot, tokens written)})``."""
    sp = ServingPredictor(m, **dict(SERVE, **kw))
    rng = np.random.default_rng(0)
    step_fn, logits = sp._unified, []

    def tapped(*a):
        res = step_fn(*a)
        logits.append(res[1])
        return res

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped
    reqs = [sp.add_request(rng.integers(0, 256, n).tolist(),
                           max_new_tokens=answer) for n in lens]
    seen = []
    while sp.has_work():
        n0 = len(logits)
        sp.step()
        if len(logits) > n0:
            seen.append((logits[-1], {r.req_id: (s, sp.cache.seq_len(s))
                                      for s, r in sp.running.items()}))
    sp.flush()
    assert sp.decode_trace_count == 1
    return sp, reqs, seen


def _served_rows(reqs, seen):
    """Per request: its context and, in order, the logits rows the step
    produced for it once past its prompt, with the position each predicts
    from."""
    out = []
    for r in reqs:
        ctx = r.prompt_ids + r.output_ids
        rows = [(written - 1, np.asarray(lg[slot]))
                for lg, at in seen
                for slot, written in [at.get(r.req_id, (None, 0))]
                if slot is not None and len(r.prompt_ids) <= written
                < len(ctx)]
        out.append((ctx, rows))
    return out


@pytest.mark.parametrize("use_kernel", [None, True], ids=["jnp", "pallas"])
def test_step_through_the_paged_cache_is_the_reference(tiny, use_kernel):
    """Prefill in chunks of 8 over pages of 4, then decode through the
    cache, with a budget whose ladder has two rungs: every step's logits
    row of a lane past its prompt against the reference's full forward."""
    cfg, m = tiny
    lens = (5, 19, 30) if use_kernel is None else (12, 20)
    sp, reqs, seen = _serve(m, lens, token_budget=24, use_kernel=use_kernel)
    compared = 0
    for ctx, rows in _served_rows(reqs, seen):
        assert len(rows) == 6
        want = np.asarray(reference.logits_at(
            m.params, np.asarray(ctx), [p for p, _ in rows], _cfgj(cfg)))
        for (_, got), w in zip(rows, want):
            assert np.abs(got - w).max() < 3e-4 * np.abs(want).max()
            compared += 1
    assert compared == 6 * len(lens)
    assert sp._row_ladder == (16, 24)
    assert [p.shape[0] for p in sp.cache.pools()] == [6, 6]


def test_each_loop_writes_its_own_plane(tiny):
    """After a prefill, plane ``t * L + l`` of the pools holds, at the
    lane's pages, the keys and values that loop t of layer l made (the
    reference's), and the pages no lane was given hold nothing in any
    plane."""
    cfg, m = tiny
    sp, reqs, _ = _serve(m, (22,), answer=1, async_engine=False)
    ctx = reqs[0].prompt_ids
    kv = []
    reference.hidden(m.params, np.asarray(ctx), _cfgj(cfg), kv=kv)
    assert len(kv) == cfg.cache_planes
    k_pool, v_pool = (np.asarray(p) for p in sp.cache.pools())
    pages = [p for p in range(SERVE["num_pages"])
             if np.abs(k_pool[:, p]).max() > 0]
    assert len(pages) == -(-22 // 4)          # the prompt's pages alone
    for plane, (k, v) in enumerate(kv):
        got_k = np.concatenate([k_pool[plane, p] for p in pages], axis=1)
        got_v = np.concatenate([v_pool[plane, p] for p in pages], axis=1)
        # [kv heads, positions, head_dim], the prompt's 22 positions
        np.testing.assert_allclose(got_k[:, :22], np.swapaxes(k, 0, 1),
                                   atol=2e-4)
        np.testing.assert_allclose(got_v[:, :22], np.swapaxes(v, 0, 1),
                                   atol=2e-4)
    # every other page is untouched in every plane
    rest = [p for p in range(SERVE["num_pages"]) if p not in pages]
    assert np.abs(k_pool[:, rest]).max() == 0
    assert np.abs(v_pool[:, rest]).max() == 0


# ---- the controls -----------------------------------------------------------

def _shared_plane(monkeypatch):
    """The system with one plane per layer shared by every loop."""
    monkeypatch.setattr(OuroConfig, "cache_plane",
                        lambda self, loop, layer: layer + 0 * loop)
    return ()


@pytest.mark.parametrize("control", [
    "one_loop", "no_loop_norm", "no_post_norms", "shared_plane"])
def test_each_control_fails_the_comparison(tiny, control, monkeypatch):
    """The cell's four controls: three references with one thing wrong held
    against the served rows, and the system with one cache plane shared by
    every loop held against the reference as it is. Each fails the cell's
    verdict on a request's six rows (``drivers/serve_looped.py``)."""
    from benchmark.drivers.serve_looped import verdict

    cfg, m = tiny
    variant = ((control,) if control != "shared_plane"
               else _shared_plane(monkeypatch))
    sp, reqs, seen = _serve(m, (30,))
    (ctx, rows), = _served_rows(reqs, seen)
    want = np.asarray(reference.logits_at(
        m.params, np.asarray(ctx), [p for p, _ in rows], _cfgj(cfg),
        variant=variant))
    rms = _rms_share(np.stack([got for _, got in rows]), want)
    means, medians, ok = verdict([rms.tolist()])
    assert not ok, (control, means, medians)


# ---- what the predictor refuses, and one traced body --------------------------

@pytest.mark.parametrize("kw, word", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(spec_decode_k=2), "spec_decode_k"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(draft_layers=1), "draft_layers"),
    (dict(draft_source="model"), "draft_source"),
    (dict(mesh=2), "mesh")])
def test_predictor_refuses_what_is_not_built_over_loops(tiny, kw, word):
    with pytest.raises(NotImplementedError, match=word):
        ServingPredictor(tiny[1], max_batch=2, max_seq_len=64, page_size=4,
                         num_pages=32, token_budget=16, chunk=8, **kw)


@pytest.mark.parametrize("kw", [dict(prefill_replicas=1),
                                dict(prefix_pulls=True)],
                         ids=["prefill_replicas", "prefix_pulls"])
def test_fleet_refuses_kv_transfer_over_loops(tiny, kw):
    """A page of ``L x T`` planes has no payload format on the wire
    (``inference/kv_transfer.py``): the fleet refuses before it builds a
    replica."""
    from paddle_tpu.inference.fleet_serving import FleetRouter

    with pytest.raises(NotImplementedError, match="looped stack"):
        FleetRouter(tiny[1], num_replicas=2, **kw)


def test_one_traced_layer_body_for_every_loop(tiny):
    """The step's program does not grow with the loops: at two loops and at
    five it has as many lines (the loop count is a trip count, the pools'
    planes a shape)."""
    from benchmark.drivers._program import abstract

    _, m = tiny
    texts = []
    for loops in (2, 5):
        cfg = OuroConfig(**dict(TINY, total_ut_steps=loops))
        sp = ServingPredictor(OuroForCausalLM(cfg, params=m.params),
                              **dict(SERVE, token_budget=24))
        step_fn, sig = sp._unified, []

        def tapped(*a, step_fn=step_fn, sig=sig):
            sig.append(abstract(a))
            return step_fn(*a)

        tapped.trace_count = step_fn.trace_count
        sp._unified = tapped
        sp.generate([[1, 2, 3]], max_new_tokens=2)
        texts.append(step_fn.lower(*sig[0]).as_text())
    assert len(texts[0].splitlines()) == len(texts[1].splitlines())
    # (the pool stacks: a plane a loop and layer)
    assert "4x96x4x4x64" in texts[0] and "10x96x4x4x64" in texts[1]


# ---- the counters -----------------------------------------------------------

def test_counters_and_gauge_against_a_hand_count(tiny):
    cfg, m = tiny
    sp = ServingPredictor(m, max_batch=2, max_seq_len=128, page_size=4,
                          num_pages=64, token_budget=16, chunk=8,
                          async_engine=False)
    prompt = np.random.default_rng(7).integers(0, 256, 21).tolist()
    sp.generate([prompt], max_new_tokens=5)
    t = sp.telemetry()
    fed = 21 + 4                                # every context token but one
    assert t["serving_rows_prefill"] + t["serving_rows_decode"] == fed
    # every row runs every loop over both layers
    for loop in range(3):
        assert t[f"serving_loop_passes{{loop={loop}}}"] == fed * 2
    assert "serving_loop_passes{loop=3}" not in t
    assert t["kv_pool_planes"] == 6
    # attention runs in each (loop, layer): six calls a step
    assert t["serving_attn_query_rows"] == 6 * fed * 4
    steps = 3 + 4             # chunks of 8 from 0, 8, 16, then four decodes
    assert t["serving_steps"] == steps
    assert t["serving_attn_lane_layers"] == 6 * steps
