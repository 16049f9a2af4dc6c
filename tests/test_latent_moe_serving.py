"""DeepSeek-V2's block through the normal serving path, at a tiny size on the
CPU in float32, held to the plain reference (``benchmark/reference/
deepseek_v2.py``): latent (MLA) paged cache, absorbed attention, dropless
top-k routing with gates as they are, shared experts, a leading dense layer.
Logits are compared, not tokens. The tolerance is the benchmark cell's, and
each named wrong variant has to fail it."""
import dataclasses
import math
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
from paddle_tpu.models import deepseek_v2 as dsv2  # noqa: E402
from paddle_tpu.models import moe  # noqa: E402

from benchmark.drivers.serve_latent_moe import LOGITS_TOL_RMS  # noqa: E402
from benchmark.reference import deepseek_v2 as reference  # noqa: E402

ROPE = {"factor": 40, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
CFG = dsv2.DeepseekV2Config(
    vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
    max_seq_len=128, intermediate_size=96, moe_intermediate_size=48,
    n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=2,
    first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_scaling=dict(ROPE),
    # wide enough that every sublayer moves the residual stream
    initializer_range=0.1)
#: the same numbers under the published keys, as the reference reads them
CFGJ = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "intermediate_size": 96,
    "moe_intermediate_size": 48, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": dict(ROPE, type="yarn")}
SERVE = dict(max_batch=3, max_seq_len=128, page_size=8, num_pages=48,
             token_budget=24, chunk=8)


@pytest.fixture(scope="module")
def model():
    return dsv2.DeepseekV2ForCausalLM(CFG, seed=3, dtype=jnp.float32)


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(5).integers(0, 256, 37).tolist()


def reference_logits(params, ids, upto):
    """The reference's next-token logits after ``ids[:upto]``."""
    padded = np.zeros((64,), np.int32)
    padded[:upto] = ids[:upto]
    with jax.enable_x64(False):
        return np.asarray(reference.logits_at(params, jnp.asarray(padded),
                                              upto - 1, CFGJ))


def rms_share(got, want):
    return float(np.sqrt(np.mean((np.asarray(got) - want) ** 2))
                 / want.std())


def eager_logits(m, ids, upto):
    return np.asarray(m(np.asarray([ids[:upto]]))._data)[0, -1]


# ---- the eager model and the two attention forms ----------------------------

def test_eager_model_agrees_with_the_reference(model, prompt):
    want = reference_logits(model.params, prompt, 30)
    assert rms_share(eager_logits(model, prompt, 30), want) < 1e-5


def test_absorbed_attention_equals_expanded(model):
    rng = np.random.default_rng(0)
    p = {k: v[1] for k, v in model.params["layers"].items()}
    y = jnp.asarray(rng.normal(size=(21, 64)), jnp.float32)
    parts = dsv2.latent_qkv(CFG, p, y, jnp.arange(21))
    expanded = dsv2.attention_expanded(CFG, p, *parts)
    absorbed = dsv2.attention_absorbed(CFG, p, *parts)
    assert expanded.shape == (21, 4 * 16)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=2e-4, atol=2e-6)


def test_yarn_frequencies_and_softmax_scale_against_the_formula():
    full = dsv2.DeepseekV2Config()      # the published numbers
    d, theta, factor, orig = 64, 1e4, 40, 4096
    turns_dim = lambda n: (d * math.log(orig / (n * 2 * math.pi))  # noqa: E731
                           / (2 * math.log(theta)))
    low, high = math.floor(turns_dim(32)), math.ceil(turns_dim(1))
    assert (low, high) == (10, 23)
    want = []
    for i in range(d // 2):
        plain = theta ** (-2 * i / d)
        slow = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain * (1 - slow) + plain / factor * slow)
    np.testing.assert_allclose(dsv2.rope_inv_freq(full), want, rtol=1e-6)
    cfgj = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
            "rope_theta": 10000, "rope_scaling": dict(
                ROPE, original_max_position_embeddings=4096)}
    np.testing.assert_allclose(reference.yarn_inv_freq(cfgj), want,
                               rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert dsv2.softmax_scale(full) == pytest.approx(192 ** -0.5 * m * m)
    assert reference.softmax_scale(cfgj) == pytest.approx(
        192 ** -0.5 * m * m)
    # mscale(40, 0.707) / mscale(40, 0.707)
    assert dsv2.rope_magnitude(full) == pytest.approx(1.0)
    assert dsv2.rope_magnitude(dataclasses.replace(
        full, rope_scaling=None)) == 1.0


# ---- routing ----------------------------------------------------------------

def _one_layer(model):
    return {k: v[0] for k, v in model.params["layers"].items()}


def test_every_token_on_one_expert_and_none_dropped(model):
    """A router that sends every row to expert 5 first: the capacity clamp
    would keep a fraction; dropless keeps all, and the counts say so."""
    p = dict(_one_layer(model))
    gate = np.zeros((64, 8), np.float32)
    gate[:, 5] = 1.0
    y = jnp.abs(jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                            jnp.float32)) + 0.1    # y @ gate[:, 5] > 0
    p["moe_gate"] = jnp.asarray(gate)
    out, counts = dsv2.routed_ffn(CFG, p, y, with_counts=True)
    rows, fed = np.asarray(counts)
    assert rows[5] == 40 and rows.sum() == 40 * 2 and fed[5] == 1
    # by hand: expert 5 at its softmax score, the runner-up (lowest index
    # among the ties: expert 0) at its own, plus the shared experts
    s = np.asarray(jax.nn.softmax(y @ p["moe_gate"], axis=-1))
    assert rows[0] == 40
    want = dsv2.gated_mlp(y, p["sh_w_gu"], p["sh_w_d"])
    for e in (5, 0):
        want = want + s[:, e:e + 1] * dsv2.gated_mlp(
            y, p["moe_w_gu"][e], p["moe_w_d"][e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    # the clamp this model must not have: GShard's capacity drops rows here
    _, _, stats = moe.moe_ffn(
        y, p["moe_gate"], p["moe_w_gu"], None, p["moe_w_d"], None, top_k=2,
        capacity_factor=1.25, renormalize=False, gated=True, with_stats=True)
    assert float(stats["drop_rate"]) > 0.3


def test_gates_are_the_softmax_scores_not_renormalised(model):
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(9, 8)),
                         jnp.float32)
    gates, idx, probs, _ = moe.route_topk(logits, 2, renormalize=False)
    top = np.sort(np.asarray(probs), axis=-1)[:, ::-1][:, :2]
    np.testing.assert_allclose(np.asarray(gates), top, rtol=1e-6)
    assert np.all(np.asarray(gates).sum(-1) < 0.999)
    renorm, idx2, _, _ = moe.route_topk(logits, 2)
    np.testing.assert_allclose(np.asarray(renorm).sum(-1), 1.0, rtol=1e-6)
    assert np.array_equal(np.asarray(idx), np.asarray(idx2))
    # ties go to the lowest index
    _, tied, _, _ = moe.route_topk(jnp.zeros((3, 8)), 2, renormalize=False)
    assert np.asarray(tied).tolist() == [[0, 1]] * 3


# ---- through ServingPredictor and the paged latent cache ---------------------

def serve_and_tap(sp, prompts, answer=5):
    """Serve ``prompts``; ``{request index: [(tokens written, logits row)]}``
    for every step, the rows as the step produced them."""
    step_fn, captured = sp._unified, []

    def tapped(*args):
        res = step_fn(*args)
        captured.append(res[1])
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
                for slot, r in sp.running.items():
                    seen[reqs.index(r)].append(
                        (sp.cache.seq_len(slot),
                         np.asarray(captured[-1][slot])))
        sp.flush()
    finally:
        sp._unified = step_fn
    return reqs, seen


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp-path", "kernels-interpreted"])
def test_chunked_prefill_then_decode_agree_with_the_reference(
        model, prompt, use_kernel):
    sp = ServingPredictor(model, use_kernel=use_kernel, **SERVE)
    assert sp.cache.k_pages.shape == (3, 48, 1, 8, 128)   # 40 values, padded
    assert sp.cache.v_pages is None and sp.params is model.params
    short = prompt[:6]
    reqs, seen = serve_and_tap(sp, [prompt, short])
    assert sp.decode_trace_count == 1
    checked = 0
    for i, req in enumerate(reqs):
        context = req.prompt_ids + req.output_ids
        assert len(req.output_ids) == 5
        for written, row in seen[i]:
            # the end of the prefill (37 tokens came in 8-row chunks) and
            # every decode step after it
            if written >= len(req.prompt_ids):
                want = reference_logits(model.params, context, written)
                assert rms_share(row, want) < 1e-4, (i, written)
                checked += 1
    assert checked >= 8
    flat = sp.telemetry()
    fed = flat["serving_rows_prefill"] + flat["serving_rows_decode"]
    assert flat["serving_moe_rows_routed"] == fed * 2 * 2    # top-2, 2 layers
    assert sum(v for k, v in flat.items()
               if k.startswith("serving_moe_expert_rows{")) == fed * 4


def test_scheduler_counts_the_latent_kernels_grid_steps_of_known_lanes(model):
    """PR 33: the latent predictor counts its kernel's grid steps as the GPT
    one does, by the kernel module's own ``tile_grid`` for the deployment:
    64 keys a grid step here (8 pages of 8), tiles of 64 tokens, so a lane's
    8-row chunk or one decode row is one tile, live over ceil(context / 64)
    key blocks; the grid launches no other step."""
    from paddle_tpu.ops.pallas.mla_paged_attention import tile_grid

    sp = ServingPredictor(model, use_kernel=False, async_engine=False,
                          **SERVE)
    grid = tile_grid(3, 24, 16, 8)
    assert (grid.keys, grid.tiles, grid.blocks) == (64, 3, 2)
    long = np.random.default_rng(9).integers(0, 256, 100).tolist()
    sp.add_request(long, max_new_tokens=3)
    sp.add_request(long[:5], max_new_tokens=3)
    while sp.has_work():
        sp.step()
    t = sp.telemetry()
    # lane A: twelve 8-row chunks, the last 4 rows, two decode rows; lane B:
    # 5 rows, two decode rows
    lanes = ([(8 * k, 8) for k in range(1, 13)] + [(100, 4), (101, 1),
                                                   (102, 1)]
             + [(5, 5), (6, 1), (7, 1)])
    assert t["serving_rows_prefill"] + t["serving_rows_decode"] == sum(
        q for _, q in lanes)
    live = sum(grid.live_steps(kv, q) for kv, q in lanes)
    assert live == 8 + 2 * 7 + 3
    assert t["serving_attn_blocks_live"] == live
    assert t["serving_attn_blocks_grid"] == live


def test_a_gpt_predictor_reports_no_expert_counters():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle_tpu.seed(0)
    gpt = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                   num_layers=1, num_heads=2, max_seq_len=32))
    sp = ServingPredictor(gpt, max_batch=2, page_size=8, max_seq_len=32,
                          use_kernel=False)
    # (a family's name; the process registry's labels name functions)
    assert not [k for k in sp.telemetry() if "moe" in k.split("{")[0]]


def test_prefix_cache_hit_and_cow_divergence_on_the_latent_pool(model,
                                                                prompt):
    sp = ServingPredictor(model, use_kernel=False, **SERVE)
    asked = prompt[:20]                  # two whole pages and half a third
    first = sp.generate([asked], max_new_tokens=3)[0]
    hits0 = sp.cache.prefix_hit_tokens
    cows0 = sp.telemetry()["kv_cow_copies"]
    # the same prompt again, twice at once: every page comes from the cache,
    # the half-full tail page too, and each lane's first new row would land
    # in that shared page, so it is copied first (CoW) and the lanes diverge
    reqs, seen = serve_and_tap(sp, [asked, asked], 4)
    assert sp.cache.prefix_hit_tokens - hits0 >= 2 * 16
    assert sp.telemetry()["kv_cow_copies"] > cows0
    for i, req in enumerate(reqs):
        assert req.output_ids[:3] == first[-3:]
        context = req.prompt_ids + req.output_ids
        for written, row in seen[i]:
            if written >= len(asked):
                want = reference_logits(model.params, context, written)
                assert rms_share(row, want) < 1e-4


# ---- the tolerance fails what it has to fail ---------------------------------

def _variant(model, name, monkeypatch):
    """The eager model with one thing wrong."""
    cfg, params = CFG, model.params
    if name == "renormalised gates":
        cfg = dataclasses.replace(CFG, norm_topk_prob=True)
    elif name == "missing shared expert":
        params = dict(params, layers=dict(
            params["layers"],
            sh_w_d=jnp.zeros_like(params["layers"]["sh_w_d"])))
    elif name == "unscaled softmax":
        monkeypatch.setattr(dsv2, "softmax_scale",
                            lambda c: c.head_dim ** -0.5)
    elif name == "dropped token":
        real = moe.moe_ffn
        monkeypatch.setattr(moe, "moe_ffn", lambda *a, **k: real(
            *a, **dict(k, capacity_factor=0.5)))
    return dsv2.DeepseekV2ForCausalLM(cfg, params=params)


@pytest.mark.parametrize("name", [
    "renormalised gates", "missing shared expert", "unscaled softmax",
    "dropped token"])
def test_the_tolerance_fails_a_wrong_variant(model, prompt, name,
                                             monkeypatch):
    want = reference_logits(model.params, prompt, 37)
    assert rms_share(eager_logits(model, prompt, 37), want) < 1e-5
    wrong = _variant(model, name, monkeypatch)
    assert rms_share(eager_logits(wrong, prompt, 37), want) > LOGITS_TOL_RMS


def test_the_tolerance_fails_a_wrong_page(model, prompt):
    """A lane's first page-table entry pointed at its third page after the
    prefill: the decode step reads eight rows twice and eight not at all.
    (Swapping two whole pages is NOT wrong: the cached rows carry their
    positions, and a softmax over them does not care for their order.)"""
    sp = ServingPredictor(model, use_kernel=False, async_engine=False,
                          **SERVE)
    req = sp.add_request(prompt, max_new_tokens=3)
    while not req.output_ids:
        sp.step()
    (slot,) = sp.running
    table = sp.cache._page_table
    table[slot, 0] = table[slot, 2]
    sp.cache._pt_rev += 1
    captured = []
    step_fn = sp._unified

    def tapped(*args):
        captured.append(step_fn(*args))
        return captured[-1]

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped
    sp.step()
    sp._unified = step_fn
    written = sp.cache.seq_len(slot)
    context = req.prompt_ids + req.output_ids
    want = reference_logits(model.params, context, written)
    assert rms_share(np.asarray(captured[-1][1][slot]), want) \
        > LOGITS_TOL_RMS


# ---- what is not extended to the latent cache raises at construction ---------

@pytest.mark.parametrize("option", [
    dict(kv_cache_dtype="int8"), dict(mesh=1), dict(spec_decode_k=2),
    dict(host_tier_bytes=1 << 20), dict(draft_layers=1)],
    ids=lambda o: next(iter(o)))
def test_unsupported_options_raise_for_the_latent_cache(model, option):
    with pytest.raises(NotImplementedError, match="latent"):
        ServingPredictor(model, **dict(SERVE, **option))


@pytest.mark.parametrize("build", ["unified", "cache", "fleet"])
def test_the_builders_refuse_what_the_latent_cache_lacks(model, build):
    from paddle_tpu.inference.fleet_serving import FleetRouter
    from paddle_tpu.inference.kv_cache import KVCacheManager
    from paddle_tpu.models.gpt import build_unified_step

    with pytest.raises(NotImplementedError, match="latent"):
        if build == "unified":
            build_unified_step(CFG, 8, 8, kv_quant=True)
        elif build == "cache":
            KVCacheManager(3, 1, 128, num_pages=8, max_batch=2,
                           max_seq_len=32, page_size=8, latent=True
                           ).read_page_payload(0, 4)
        else:
            FleetRouter(model, num_replicas=2, prefix_pulls=True)
