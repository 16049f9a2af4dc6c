"""The serving step's block against a plain float32 ``jax.numpy`` block.

``build_unified_step`` is the one serving step program. Its block is spelled
from per-op parts (``mha`` with its ``[lanes, chunk]`` query layout over the
paged pools, ``_srv_ffn``); this file holds those parts to the plain
reference (``benchmark/reference/gpt.py``: no kernel, no cache, no packing)
at the level of logits, not tokens, over weights {float32, bfloat16, int8,
int8 grouped by 16} x chunk {1, 2, 4} x reference / interpreted kernels,
with ragged lanes (an idle lane, a partial last chunk, padding rows in the
packed stream). A quantized tree is compared with the plain block over its
dequantized weights, so what is measured is the step, not the rounding.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import ServingPredictor
from paddle_tpu.inference.quantize import (QUANT_LAYER_KEYS,
                                           quantize_serving_params)
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM, _srv_ffn,
                                   build_unified_step, serving_params)
from paddle_tpu.ops.pallas.quant_matmul import dequantize_weight

from benchmark.reference import gpt as reference

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                max_seq_len=32)
PAGE, LANES, PAGES_PER_LANE = 8, 3, 2
#: tokens each lane feeds, a chunk at a time (lane 1 stays idle)
CONTEXTS = (13, 0, 5)
#: name -> (weight dtype, quantization group, rms share of the logits' std)
WEIGHTS = {"float32": (None, -1, 1e-5), "bfloat16": (None, -1, 3e-2),
           "int8": ("int8", -1, 1e-5), "int8-g16": ("int8", 16, 1e-5)}


@pytest.fixture(scope="module")
def fp_params():
    paddle.seed(7)
    model = GPTForCausalLM(CFG)
    model.eval()
    return serving_params(model)


def _trees(fp_params, weights):
    """(the tree the step serves, the float32 tree the plain block reads)."""
    wdtype, group, _ = WEIGHTS[weights]
    if weights == "bfloat16":
        served = jax.tree.map(lambda a: a.astype(jnp.bfloat16), fp_params)
        return served, jax.tree.map(lambda a: a.astype(jnp.float32), served)
    if wdtype is None:
        return fp_params, fp_params
    served = quantize_serving_params(fp_params, wdtype, group_size=group)
    plain = dict(served, layers=dict(served["layers"]))
    for key in QUANT_LAYER_KEYS:
        w = served["layers"][key]
        plain["layers"][key] = jax.vmap(dequantize_weight)(w["q"], w["s"])
    return served, plain


def _rms_share(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want)
    return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["reference", "kernels"])
@pytest.mark.parametrize("chunk", [1, 2, 4])
@pytest.mark.parametrize("weights", list(WEIGHTS))
def test_step_logits_match_the_plain_block(rng, fp_params, weights, chunk,
                                           kernel):
    """Every step of a ragged chunked prefill: each lane that fed rows reads
    next-token logits equal to the plain full forward over its context so
    far."""
    served, plain = _trees(fp_params, weights)
    tol = WEIGHTS[weights][2]
    dtype = served["tok_emb"].dtype
    step = build_unified_step(CFG, PAGE, chunk, use_kernel=kernel)
    ref = jax.jit(lambda ids, pos: reference.logits_at(
        plain, ids, pos, num_heads=CFG.num_heads, eps=CFG.layer_norm_eps))
    ids = rng.randint(0, CFG.vocab_size, (LANES, max(CONTEXTS)))
    budget = LANES * chunk
    pool = (CFG.num_layers, LANES * PAGES_PER_LANE, CFG.num_heads, PAGE,
            CFG.head_dim)
    kp, vp = jnp.zeros(pool, dtype), jnp.zeros(pool, dtype)
    table = jnp.arange(LANES * PAGES_PER_LANE, dtype=jnp.int32
                       ).reshape(LANES, PAGES_PER_LANE)
    none = jnp.full((LANES,), pool[1], jnp.int32)
    zeros_b = jnp.zeros((LANES,), jnp.int32)
    fed = np.zeros((LANES,), np.int64)
    compared = 0
    for _ in range(math.ceil(max(CONTEXTS) / chunk)):
        q_lens = np.minimum(chunk, np.asarray(CONTEXTS) - fed)
        tok_ids, tok_slot, tok_pos = (np.zeros((budget,), np.int32),
                                      np.full((budget,), -1, np.int32),
                                      np.zeros((budget,), np.int32))
        last_idx, row = np.full((LANES,), budget, np.int32), 0
        for lane in range(LANES):
            for j in range(q_lens[lane]):
                tok_ids[row] = ids[lane, fed[lane] + j]
                tok_slot[row], tok_pos[row] = lane, fed[lane] + j
                last_idx[lane], row = row, row + 1
        _, logits, kp, vp = step(
            served, *map(jnp.asarray, (tok_ids, tok_slot, tok_pos,
                                       q_lens.astype(np.int32),
                                       fed.astype(np.int32), last_idx)),
            jnp.zeros((budget,), jnp.int32), zeros_b, zeros_b, zeros_b,
            kp, vp, table, none, none, jnp.zeros((LANES, 2), jnp.uint32),
            jnp.zeros((LANES,), jnp.float32), zeros_b,
            jnp.ones((LANES,), jnp.float32))
        fed += q_lens
        for lane in np.flatnonzero(q_lens):
            padded = np.zeros((1, CFG.max_seq_len), np.int32)
            padded[0, :fed[lane]] = ids[lane, :fed[lane]]
            want = ref(jnp.asarray(padded), fed[lane] - 1)
            assert _rms_share(logits[lane], want) < tol, (lane, fed)
            compared += 1
    assert step.trace_count[0] == 1
    assert compared == sum(math.ceil(n / chunk) for n in CONTEXTS)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["reference", "kernels"])
@pytest.mark.parametrize("rows", [5, 16], ids=["rows5", "rows16"])
@pytest.mark.parametrize("weights", ["float32", "int8", "int8-g16"])
def test_ffn_matches_the_plain_block(rng, fp_params, weights, rows, kernel):
    """``_srv_ffn`` over one layer's weights, on a row count that is a whole
    tile and on one that is not: ``gelu_tanh(y w1 + b1) w2 + b2``."""
    served, plain = _trees(fp_params, weights)
    p, pf = ({k: v[0] if not isinstance(v, dict)
              else {n: a[0] for n, a in v.items()}
              for k, v in tree["layers"].items()} for tree in (served, plain))
    y = jnp.asarray(rng.randn(rows, CFG.hidden_size), jnp.float32)
    got, counts = _srv_ffn(CFG, p, y, kernel)
    hi = jax.lax.Precision.HIGHEST
    want = jnp.matmul(reference._gelu_tanh(
        jnp.matmul(y, pf["w1"], precision=hi) + pf["b1"]), pf["w2"],
        precision=hi) + pf["b2"]
    assert counts is None and got.shape == (rows, CFG.hidden_size)
    assert _rms_share(got, want) < 1e-5


# ---- the forks that went: their switches, and the tables that named them ----

# (the third spelled apart, so that a grep for the deleted names finds nothing)
@pytest.mark.parametrize("build, option", [
    ("predictor", "mega_decode"), ("predictor", "unified"),
    ("predictor", "prefill" + "_bucket"), ("config", "mega_decode")])
def test_the_deleted_switches_are_unknown_keywords(build, option):
    with pytest.raises(TypeError, match=option):
        if build == "config":
            GPTConfig(**{option: False})
        else:
            ServingPredictor(None, **{option: False})


def test_legs_contracts_and_targets_name_the_same_serving_programs():
    """One serving step program: what tpulint traces, what the contract
    table certifies and what ``bench_serve.py`` may run are the same
    programs, and a name of a fork that is gone fits in none of the three."""
    from paddle_tpu.analysis.bench_schema import KNOWN_LEGS
    from paddle_tpu.analysis.contracts import CONTRACTS
    from paddle_tpu.analysis.targets import TARGETS

    serving = {t for t in TARGETS if t.startswith("serving")}
    assert serving == {"serving-" + name for name in (
        "unified", "quant", "spmd", "spec", "spec-model", "async", "tiered",
        "moe")}
    # a contract row is a program of a registered target
    for row in CONTRACTS:
        assert [t for t in TARGETS if row.startswith(t + "-")], row
    # a leg's predictor runs a step that a registered target traces
    leg_target = {
        "unified-step": "serving-unified", "unified-obs": "serving-unified",
        "unified-overload": "serving-unified",
        "unified-async": "serving-async", "unified-spmd": "serving-spmd",
        "unified-spec-base": "serving-spec", "unified-spec-k4": "serving-spec",
        "unified-spec-model": "serving-spec-model",
        "unified-int8w": "serving-quant",
        "unified-int8w-int8kv": "serving-quant", "moe-churn": "serving-moe",
        "fleet-churn": "serving-unified", "fleet-disagg": "serving-quant",
        "fleet-tiered": "serving-tiered"}
    assert set(leg_target) == set(KNOWN_LEGS) and len(KNOWN_LEGS) == 14
    assert set(leg_target.values()) <= serving
