"""The GLM-5 block (``models/glm_moe_dsa.py``) through the normal serving path,
at a tiny size on the CPU in float32, held to the plain reference
(``benchmark/reference/glm_moe_dsa.py``): a low-rank query, a learned indexer
whose exact top-k chooses the keys attention reads, the selection shared by
the layers after it, a second cache plane for the index keys, sigmoid routing
with a correction bias, and a chip's share of the experts. Logits and
selected SETS are compared, not tokens."""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu  # noqa: E402,F401
from paddle_tpu.inference import ServingPredictor  # noqa: E402
from paddle_tpu.models import glm_moe_dsa as glm  # noqa: E402
from paddle_tpu.models import moe  # noqa: E402
from paddle_tpu.ops.pallas import dsa_index as dsa  # noqa: E402
from paddle_tpu.ops.pallas import mla_paged_attention as mla  # noqa: E402

from benchmark.reference import glm_moe_dsa as reference  # noqa: E402

TOPK = 8
KINDS = ("full", "shared", "shared", "shared", "full")
CFG = glm.GlmMoeDsaConfig(
    vocab_size=128, hidden_size=64, num_layers=5, num_heads=4,
    max_seq_len=128, intermediate_size=96, moe_intermediate_size=48,
    n_routed_experts=4, n_routed_experts_published=16, experts_held_first=4,
    num_experts_per_tok=3, first_k_dense_replace=1, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
    index_n_heads=2, index_head_dim=16, index_topk=TOPK,
    indexer_types=KINDS, initializer_range=0.1)
#: the same numbers under the published keys, as the reference reads them
CFGJ = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "index_n_heads": 2, "index_head_dim": 16, "index_topk": TOPK,
    "indexer_types": list(KINDS), "n_routed_experts": 4,
    "n_routed_experts_published": 16, "experts_held_first": 4,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "n_shared_experts": 1,
    "moe_intermediate_size": 48}
SERVE = dict(max_batch=3, max_seq_len=128, page_size=8, num_pages=48,
             token_budget=24, chunk=8)


@pytest.fixture(scope="module")
def model():
    return glm.GlmMoeDsaForCausalLM(CFG, seed=1, dtype=jnp.float32)


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(0).integers(0, 128, 37).tolist()


def reference_rows(params, ids, upto, cfgj=CFGJ, pad=64):
    """The reference's next-token logits after ``ids[:upto]`` and the keys
    that row selected in each layer with an indexer, ``[layers, upto]``."""
    padded = np.zeros((pad,), np.int32)
    padded[:upto] = ids[:upto]
    with jax.enable_x64(False):
        logits, chosen = reference.logits_at(params, jnp.asarray(padded),
                                             [upto - 1], cfgj)
    return np.asarray(logits)[0], np.asarray(chosen)[:, 0, :upto]


def rms_share(got, want):
    return float(np.sqrt(np.mean((np.asarray(got) - want) ** 2))
                 / want.std())


def serve_and_tap(sp, prompts, answer=5):
    """Serve ``prompts``; ``{request index: [(tokens written, logits row,
    selection rows [layers with an indexer, key slots])]}`` for every step."""
    step_fn, captured = sp._unified, []

    def tapped(*args):
        res = step_fn(*args)
        captured.append((res[1], res[-1]))
        return res

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped
    reqs = [sp.add_request(p, max_new_tokens=answer) for p in prompts]
    seen = {i: [] for i in range(len(reqs))}
    try:
        while sp.has_work():
            n0 = len(captured)
            sp.step()
            if len(captured) > n0:
                logits, chosen = captured[-1]
                for slot, r in sp.running.items():
                    seen[reqs.index(r)].append(
                        (sp.cache.seq_len(slot), np.asarray(logits[slot]),
                         np.asarray(chosen[:, slot])))
        sp.flush()
    finally:
        sp._unified = step_fn
    return reqs, seen


# ---- the model's tree and the eager forward ----------------------------------

def test_the_layers_come_in_runs_of_equal_kind(model):
    assert glm.stack_runs(CFG) == [(False, True, 1), (True, False, 3),
                                   (True, True, 1)]
    dense, shared, full = model.params["stacks"]
    assert "idx_wq" in dense and "w_gu" in dense and "moe_gate" not in dense
    assert "idx_wq" not in shared and shared["moe_w_gu"].shape == (
        3, 4, 64, 96)                                  # 4 held of 16
    assert shared["moe_gate"].shape == (3, 64, 16) and "idx_wk" in full
    published = glm.GlmMoeDsaConfig()
    assert published.indexer_types[:8] == ("full",) * 3 + ("shared",) * 3 \
        + ("full", "shared")
    assert published.num_index_layers == 21 and published.experts_held is None
    with pytest.raises(ValueError, match="indexer_types"):
        dataclasses.replace(CFG, indexer_types=("shared",) * 5)


def test_eager_model_agrees_with_the_reference(model, prompt):
    for upto in (5, 30):               # on both sides of index_topk
        want, chosen = reference_rows(model.params, prompt, upto)
        logits, masks = glm.forward(CFG, model.params,
                                    jnp.asarray([prompt[:upto]]),
                                    with_selection=True)
        assert rms_share(np.asarray(logits)[0, -1], want) < 1e-5
        assert (np.asarray(masks)[0, :, -1] == chosen).all()
        assert chosen.sum(-1).tolist() == [min(upto, TOPK)] * 2


# ---- routing: sigmoid scores, biased choice, a chip's share -------------------

def test_sigmoid_routing_chooses_by_the_bias_and_gates_without_it():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([-5.0, 0.0, 0.0, 3.0])
    gates, idx, _, _ = moe.route_topk(logits, 2, True, scoring="sigmoid",
                                      bias=bias)
    assert idx.tolist() == [[3, 1]]        # by score + bias
    s = jax.nn.sigmoid(logits)[0]
    np.testing.assert_allclose(np.asarray(gates)[0],
                               np.asarray([s[3], s[1]]) / (s[3] + s[1]),
                               rtol=1e-6)
    # the softmax path is the one it was
    g0, i0, _, _ = moe.route_topk(logits, 2, True)
    assert i0.tolist() == [[0, 1]]


def _uncut(params_layer):
    """A routed layer's weights with ALL 16 experts: the held four at their
    published places, seeded others around them."""
    rng = np.random.default_rng(11)
    p = dict(params_layer)
    for k in ("moe_w_gu", "moe_w_d"):
        whole = rng.normal(size=(16,) + p[k].shape[1:]).astype(np.float32) \
            * 0.1
        whole[4:8] = np.asarray(p[k])
        p[k] = jnp.asarray(whole)
    return p


def test_the_shares_add_up_to_the_uncut_layer_in_the_reference(model):
    """Four chips hold four experts each. Their routed parts, with the shared
    expert (which every chip computes alike) counted once, are the uncut
    layer's output; and the program's share is the reference's."""
    p = _uncut({k: v[0] for k, v in model.params["stacks"][1].items()})
    y = jnp.asarray(np.random.default_rng(2).normal(size=(19, 64)),
                    jnp.float32)
    def ref_routed(p, first):
        # the reference reads an expert out of (the layers' stack, layer)
        return reference._routed(
            dict(p, moe_w_gu=(p["moe_w_gu"][None], 0),
                 moe_w_d=(p["moe_w_d"][None], 0)),
            y, dict(CFGJ, experts_held_first=first))

    with jax.enable_x64(False):
        whole = ref_routed(p, 0)
        shared = reference._gated_mlp(y, p["sh_w_gu"], p["sh_w_d"])
        parts = []
        for first in (0, 4, 8, 12):
            mine = dict(p, moe_w_gu=p["moe_w_gu"][first:first + 4],
                        moe_w_d=p["moe_w_d"][first:first + 4])
            parts.append(ref_routed(mine, first) - shared)
            cfg = dataclasses.replace(CFG, experts_held_first=first)
            got, counts = glm.routed_ffn(cfg, mine, y, with_counts=True)
            np.testing.assert_allclose(np.asarray(got - shared),
                                       np.asarray(parts[-1]), atol=2e-5)
            assert counts.shape == (2, 4)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=2e-5)
    assert float(jnp.abs(parts[1]).max()) > 1e-2      # each part is something


def test_vocabulary_slices_give_the_uncut_logits(model):
    h = jnp.asarray(np.random.default_rng(3).normal(size=(3, 64)), jnp.float32)
    head = model.params["lm_head"]
    with jax.enable_x64(False):
        whole = reference._head(h, head)
        slices = [reference._head(h, head[:, a:a + 32])
                  for a in range(0, 128, 32)]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(slices, -1)),
                               np.asarray(whole), atol=1e-6)


# ---- the kernels against their jnp forms --------------------------------------

def _step(rng, b=3, pps=8, ps=8, pool_pages=40, t=24):
    """A hand-made step: lane 0 decodes at context 37, lane 1 feeds a
    10-row chunk that ends at 29, lane 2 idles."""
    pt = jnp.asarray(rng.permutation(pool_pages)[:b * pps].reshape(b, pps),
                     jnp.int32)
    q_lens = jnp.asarray([1, 10, 0], jnp.int32)
    ctx = jnp.asarray([37, 29, 0], jnp.int32)
    slot, off = np.full(t, -1, np.int32), np.zeros(t, np.int32)
    slot[0], slot[1:11], off[1:11] = 0, 1, np.arange(10)
    return pt, ctx, q_lens, jnp.asarray(slot), jnp.asarray(off)


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_index_and_selection_kernels_against_the_jnp_forms(ties):
    rng = np.random.default_rng(0)
    pt, ctx, q_lens, slot, off = _step(rng)
    ipool = jnp.asarray(rng.normal(size=(2, 40, 1, 8, 16)), jnp.float32)
    q_idx = jnp.asarray(rng.normal(size=(24, 2, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, 2)), jnp.float32)
    want = dsa.index_scores_reference(q_idx, w, ipool, pt, ctx, q_lens, slot,
                                      off, layer=1)
    seen = dsa.seen_keys(pt, ctx, q_lens, slot, off, 8)
    grid = mla.tile_grid(3, 24, 8, 8, 16, 2)
    plan = mla.tile_plan(slot, off, q_lens, ctx, pt, page_size=8,
                         num_pages=40, tile=16, pages_per_step=2)
    got = dsa.index_scores(q_idx, w, ipool, plan, 1, grid=grid)
    dest = np.asarray(plan.dest)
    for r in range(11):
        np.testing.assert_allclose(
            np.asarray(got[dest[r], :64])[np.asarray(seen[r])],
            np.asarray(want[r])[np.asarray(seen[r])], rtol=1e-5, atol=1e-5)
    if ties:      # whole numbers: many equal scores at the threshold
        got = jnp.where(jnp.isfinite(got), jnp.round(got), got)
        want = jnp.where(seen, jnp.round(want), want)
    mask = dsa.select_mask(got, plan, grid=grid, k=TOPK)
    chosen = dsa.select_topk(want, seen, TOPK)
    assert chosen.sum(-1)[:11].tolist() == [8] * 11
    for r in range(11):
        assert (np.asarray(mask[dest[r], :64]) > 0).tolist() \
            == np.asarray(chosen[r]).tolist(), r
    assert not np.asarray(mask[dest[0] + 1:16]).any()   # a one-token tile


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp-path", "kernel-interpreted"])
def test_selected_attention_equals_dense_when_everything_is_selected(
        use_kernel):
    rng = np.random.default_rng(4)
    pt, ctx, q_lens, slot, off = _step(rng)
    pool = jnp.asarray(rng.normal(size=(3, 40, 1, 8, 128)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(24, 4, 128)), jnp.float32)
    kw = dict(v_dim=32, scale=0.1, layer=2, use_kernel=use_kernel, tile=16,
              pages_per_step=2)
    dense = mla.mla_ragged_paged_attention(q, pool, pt, ctx, q_lens, slot,
                                           off, **kw)
    seen = dsa.seen_keys(pt, ctx, q_lens, slot, off, 8)
    if use_kernel:
        plan = mla.tile_plan(slot, off, q_lens, ctx, pt, page_size=8,
                             num_pages=40, tile=16, pages_per_step=2)
        everything = jnp.ones((4 * 16, 64), jnp.bfloat16)
        some = jnp.zeros((4 * 16, 64), jnp.bfloat16).at[plan.dest].set(
            (seen & (jnp.arange(64) % 3 == 0)).astype(jnp.bfloat16),
            mode="drop")
    else:
        everything, some = jnp.ones((24, 64), bool), seen & (
            jnp.arange(64) % 3 == 0)
    got = mla.mla_ragged_paged_attention(
        q, pool, pt, ctx, q_lens, slot, off, selected=everything,
        name=mla.SPARSE_MLA_KERNEL_NAME, **kw)
    np.testing.assert_allclose(np.asarray(got[:11]), np.asarray(dense[:11]),
                               rtol=2e-4, atol=2e-5)
    third = mla.mla_ragged_paged_attention(
        q, pool, pt, ctx, q_lens, slot, off, selected=some,
        name=mla.SPARSE_MLA_KERNEL_NAME, **kw)
    want = mla.mla_ragged_paged_attention_reference(
        q, pool, pt, ctx, q_lens, slot, off, v_dim=32, scale=0.1, layer=2,
        selected=seen & (jnp.arange(64) % 3 == 0))
    np.testing.assert_allclose(np.asarray(third[:11]), np.asarray(want[:11]),
                               rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(third[:11] - dense[:11]).max()) > 1e-2


def test_tiles_shrink_with_the_heads_they_hold():
    assert mla.tile_for_heads(16) == mla.TILE_DEFAULT == 64
    assert mla.tile_for_heads(64) == 16 and mla.tile_for_heads(4) == 64
    assert mla.tile_for_heads(128) == 16


def test_keys_of_counts_a_lanes_rows():
    assert dsa.keys_of(37, 1, 8) == (37, 8)
    assert dsa.keys_of(10, 8, 8) == (sum(range(3, 11)), 3 + 4 + 5 + 6 + 7
                                     + 8 + 8 + 8)


# ---- the serving path ---------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp-path", "kernels-interpreted"])
def test_chunked_prefill_then_decode_agree_with_the_reference(
        model, prompt, use_kernel):
    """37 prompt tokens come in 8-row chunks, five decode steps follow:
    contexts on both sides of ``index_topk`` (8), three layers that share the
    first layer's selection and one that selects anew. Logits agree and the
    served selection IS the reference's, layer by layer."""
    sp = ServingPredictor(model, use_kernel=use_kernel, **SERVE)
    latent, index = sp.cache.pools()
    assert latent.shape == (5, 48, 1, 8, 128) and index.shape == (
        2, 48, 1, 8, 16)
    assert sp.params is model.params
    short = prompt[:6]                     # never past index_topk
    reqs, seen = serve_and_tap(sp, [prompt, short])
    assert sp.decode_trace_count == 1
    checked = 0
    for i, req in enumerate(reqs):
        context = req.prompt_ids + req.output_ids
        assert len(req.output_ids) == 5
        for written, row, chosen in seen[i]:
            if written < len(req.prompt_ids):
                continue
            want, want_chosen = reference_rows(model.params, context, written)
            assert rms_share(row, want) < 1e-4
            assert (chosen[:, :written] == want_chosen).all()
            assert not chosen[:, written:].any()
            checked += 1
    assert checked == 10
    t = sp.telemetry()
    rows = [(37, 37), (6, 6)] + [(37 + k, 1) for k in range(1, 5)] \
        + [(6 + k, 1) for k in range(1, 5)]
    scored, read = map(sum, zip(*(dsa.keys_of(c, n, TOPK) for c, n in rows)))
    assert t["serving_dsa_keys_scored"] == 2 * scored
    assert t["serving_dsa_keys_context"] == 5 * scored
    assert t["serving_dsa_keys_selected"] == 5 * read
    n_rows = sum(n for _, n in rows)
    assert t["serving_moe_rows_routed"] + t["serving_moe_rows_elsewhere"] \
        == n_rows * 4 * 3
    assert 0 < t["serving_moe_rows_routed"] < n_rows * 4 * 3 / 2
    assert sorted(k for k in t if k.startswith("serving_moe_expert_rows{")) \
        <= [f"serving_moe_expert_rows{{expert={e}}}" for e in range(4)]


def test_prefix_cache_hit_and_cow_keep_the_index_plane_with_its_page(
        model, prompt):
    """A prompt served again, twice at once: its pages come from the prefix
    cache, the shared half-full tail page is copied on write, and the new
    lanes' indexers score index keys they did not write: the second plane
    came with its page, or the selection and the logits would differ."""
    sp = ServingPredictor(model, use_kernel=False, **SERVE)
    asked = prompt[:20]                  # two whole pages and half a third
    first = sp.generate([asked], max_new_tokens=3)[0]
    hits0 = sp.cache.prefix_hit_tokens
    cows0 = sp.telemetry()["kv_cow_copies"]
    reqs, seen = serve_and_tap(sp, [asked, asked], 4)
    assert sp.cache.prefix_hit_tokens - hits0 >= 2 * 16
    assert sp.telemetry()["kv_cow_copies"] > cows0
    for i, req in enumerate(reqs):
        assert req.output_ids[:3] == first[-3:]
        context = req.prompt_ids + req.output_ids
        for written, row, chosen in seen[i]:
            if written >= len(asked):
                want, want_chosen = reference_rows(model.params, context,
                                                   written)
                assert rms_share(row, want) < 1e-4
                assert (chosen[:, :written] == want_chosen).all()
    # pages freed and handed out again: another prompt over the same pool
    other = np.random.default_rng(8).integers(0, 128, 90).tolist()
    for _ in range(3):
        reqs, seen = serve_and_tap(sp, [other], 2)
    written, row, chosen = seen[0][-1]
    want, want_chosen = reference_rows(
        model.params, reqs[0].prompt_ids + reqs[0].output_ids, written,
        pad=128)
    assert rms_share(row, want) < 1e-4
    assert (chosen[:, :written] == want_chosen).all()


@pytest.mark.parametrize("wrong", ["no selection", "first keys"])
def test_a_wrong_selection_is_told_from_the_right_one(model, prompt, wrong):
    """The reference with the selection switched off, or with the first
    ``index_topk`` positions instead of the best: the selected sets differ
    from the served ones, whatever the logits do."""
    def select(scores, causal, k):
        if wrong == "no selection":
            return causal
        return causal & (jnp.arange(scores.shape[-1])[None, :] < k)

    padded = np.zeros((64,), np.int32)
    padded[:37] = prompt
    with jax.enable_x64(False):
        logits, chosen = reference.logits_at(
            model.params, jnp.asarray(padded), [36], CFGJ, select=select)
    want, want_chosen = reference_rows(model.params, prompt, 37)
    share = (np.asarray(chosen)[:, 0, :37] & want_chosen).sum() \
        / want_chosen.sum()
    assert share < 0.9 or np.asarray(chosen)[:, 0].sum() > want_chosen.sum()
    assert rms_share(np.asarray(logits)[0], want) > 1e-3


# ---- what is not extended to the latent cache still raises --------------------

@pytest.mark.parametrize("option", [
    dict(kv_cache_dtype="int8"), dict(mesh=1), dict(spec_decode_k=2),
    dict(host_tier_bytes=1 << 20), dict(draft_layers=1)],
    ids=lambda o: next(iter(o)))
def test_unsupported_options_raise_for_the_sparse_latent_cache(model, option):
    with pytest.raises(NotImplementedError, match="latent"):
        ServingPredictor(model, **dict(SERVE, **option))


def test_an_index_plane_belongs_to_a_latent_cache():
    from paddle_tpu.inference.kv_cache import KVCacheManager

    with pytest.raises(NotImplementedError, match="latent"):
        KVCacheManager(2, 2, 16, num_pages=8, max_batch=2, max_seq_len=32,
                       page_size=8, index_plane=(1, 16))
    cache = KVCacheManager(3, 1, 128, num_pages=8, max_batch=2,
                           max_seq_len=32, page_size=8, latent=True,
                           index_plane=(2, 16))
    assert [p.shape for p in cache.pools()] == [(3, 8, 1, 8, 128),
                                                (2, 8, 1, 8, 16)]
    cache.update_pages(cache.k_pages + 1, cache.index_pages + 2)
    assert float(cache.index_pages.max()) == 2.0 and cache.v_pages is None
