"""The serving step runs over the rows it holds (PR 35): one program, its row
count a rung of ``models/gpt.py step_row_ladder`` that the program picks from
``q_lens``. Held here, on the CPU at a tiny size: at every rung the program
gives what the program that always runs its whole budget gives (tokens, pool
contents, logits); it is traced once; host and program pick the same rung for
every row count; a one-rung predictor has no conditional of the ladder's; the
scheduler's counter ``serving_rows_run`` sums the rungs the program took."""
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import ServingPredictor  # noqa: E402
from paddle_tpu.models import gpt  # noqa: E402
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,  # noqa: E402
                                   build_unified_step, serving_params,
                                   step_row_ladder, step_row_rung)

#: six lanes, chunks of 8, a budget of 48: rungs 16 (decode rows alone), 32
#: (and two chunks), 48 (the budget)
SERVE = dict(max_batch=6, max_seq_len=64, page_size=8, num_pages=48,
             token_budget=48, chunk=8)
LADDER = (16, 32, 48)
PROMPT_LENGTHS = (20, 9, 13, 30, 17, 25)
KINDS = ("gpt", "gpt-int8kv", "gpt-spec", "latent", "sparse")
GPT_CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                    max_seq_len=64)


def top_rung_only(lanes, spec_k, chunk, budget):
    """The ladder of the program as it was: its whole budget, every step."""
    return (budget,)


def _predictor(kind, **serve):
    """A predictor of ``kind`` under ``SERVE`` (changed by ``serve``) and its
    vocabulary."""
    serve = dict(SERVE, **serve)
    if kind.startswith("gpt"):
        paddle.seed(7)
        model = GPTForCausalLM(GPT_CFG)
        model.eval()
        extra = {"gpt-int8kv": dict(kv_cache_dtype="int8"),
                 "gpt-spec": dict(spec_decode_k=1)}.get(kind, {})
        return ServingPredictor(model, dtype=jnp.float32, **serve, **extra), 97
    if kind == "latent":
        import test_latent_moe_serving as tiny
        from paddle_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM

        model = DeepseekV2ForCausalLM(tiny.CFG, seed=1, dtype=jnp.float32)
    else:
        import test_sparse_latent_serving as tiny
        from paddle_tpu.models.glm_moe_dsa import GlmMoeDsaForCausalLM

        model = GlmMoeDsaForCausalLM(tiny.CFG, seed=1, dtype=jnp.float32)
    return ServingPredictor(model, **dict(tiny.SERVE, **serve)), 128


def _serve(kind):
    """Serve six prompts to their end through a predictor of ``kind``;
    per dispatched step: the rows it held, its results ahead of the pools and
    the pools after it, as numpy arrays."""
    sp, vocab = _predictor(kind)
    step_fn, steps = sp._unified, []
    n_pool = len(sp.cache.pools())
    lead = 4 if sp.spec_k else 2

    def tapped(*args):
        res = step_fn(*args)
        steps.append(dict(
            rows=int(np.asarray(args[4]).sum()),
            fed=np.asarray(args[4]) > 0,
            emits=np.asarray(args[10 if sp.spec_k else 9]) > 0,
            results=[np.asarray(r) for r in res[:lead]],
            pools=[np.asarray(r) for r in res[lead:lead + n_pool]]))
        return res

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped
    rng = np.random.default_rng(3)
    # a repeated pattern, so that the n-gram drafts of ``gpt-spec`` propose
    reqs = [sp.add_request((rng.integers(0, vocab, 5).tolist() * 8)[:n],
                           max_new_tokens=6) for n in PROMPT_LENGTHS]
    while sp.has_work():
        sp.step()
    sp.flush()
    return dict(steps=steps, traces=sp.decode_trace_count,
                ladder=sp._row_ladder, telemetry=sp.telemetry(),
                outputs=[list(r.output_ids) for r in reqs])


@pytest.fixture(scope="module")
def served():
    """``kind -> (the program with its ladder, the program held to its top
    rung)``, each served once for the module."""
    runs = {}

    def get(kind):
        if kind not in runs:
            ladder = _serve(kind)
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(gpt, "step_row_ladder", top_rung_only)
                whole = _serve(kind)
            runs[kind] = ladder, whole
        return runs[kind]
    return get


@pytest.mark.parametrize("budget, ladder", [
    (dict(lanes=24, spec_k=0, chunk=64, budget=512), (32, 160, 512)),
    (dict(lanes=16, spec_k=0, chunk=128, budget=256), (16, 256)),
    (dict(lanes=32, spec_k=0, chunk=256, budget=1024), (32, 544, 1024)),
    (dict(lanes=4, spec_k=2, chunk=8, budget=40), (16, 32, 40)),
    (dict(lanes=8, spec_k=3, chunk=16, budget=48), (32, 48)),
    (dict(lanes=4, spec_k=0, chunk=8, budget=12), (12,)),
    (dict(lanes=3, spec_k=0, chunk=8, budget=16), (16,)),
    (dict(lanes=1, spec_k=0, chunk=1, budget=1), (1,))],
    ids=["590m", "glm-5.2", "dsv2-lite", "spec", "two-rungs", "default-budget",
         "budget-is-the-decode-rung", "one-row"])
def test_the_ladder_stands_where_the_schedulers_steps_land(budget, ladder):
    """Decode rows (a whole bf16 tile of them), decode rows plus two chunks,
    where below the budget, then the budget; one rung where the budget is no
    larger than the decode rows."""
    got = step_row_ladder(**budget)
    assert got == ladder
    assert list(got) == sorted(set(got)) and got[-1] == budget["budget"]
    assert len(got) <= len(gpt.STEP_ROW_RUNG_CHUNKS) + 1


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("rung", LADDER)
def test_every_rung_gives_what_the_whole_budget_gives(served, kind, rung):
    """Step by step, the steps that took ``rung``: tokens (and, speculating,
    accepted counts) equal, pool contents equal (float32 pools to rounding:
    a product over fewer rows is tiled otherwise), logits of the lanes that
    fed rows within the tolerance the block tests hold float32 to."""
    ladder, whole = served(kind)
    assert ladder["ladder"] == LADDER and whole["ladder"] == LADDER[-1:]
    assert len(ladder["steps"]) == len(whole["steps"])
    took = [i for i, s in enumerate(ladder["steps"])
            if LADDER[step_row_rung(LADDER, s["rows"])] == rung]
    assert took, f"no step of this schedule took the rung of {rung} rows"
    for i in took:
        got, want = ladder["steps"][i], whole["steps"][i]
        assert got["rows"] == want["rows"] <= rung
        np.testing.assert_array_equal(got["emits"], want["emits"])
        fed, emits = got["fed"], got["emits"]
        *ahead, next_toks, logits = got["results"]
        *ahead_w, next_toks_w, logits_w = want["results"]
        np.testing.assert_array_equal(next_toks, next_toks_w)
        if ahead:
            # speculating: the tokens a lane emits are its first ``n_emit``
            (out_ids, n_emit), (out_ids_w, n_emit_w) = ahead, ahead_w
            np.testing.assert_array_equal(n_emit[emits], n_emit_w[emits])
            keep = emits[:, None] & (np.arange(out_ids.shape[1])[None]
                                     < n_emit[:, None])
            np.testing.assert_array_equal(out_ids[keep], out_ids_w[keep])
        # an idle lane reads its logits off a row that holds nothing
        scale = float(np.std(logits_w[fed])) or 1.0
        assert float(np.sqrt(np.mean(
            (logits[fed] - logits_w[fed]) ** 2))) < 1e-5 * scale
        for a, b in zip(got["pools"], want["pools"]):
            if a.dtype == np.int8:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert ladder["outputs"] == whole["outputs"]


@pytest.mark.parametrize("kind", KINDS)
def test_one_trace_serves_every_rung_and_the_counter_sums_them(served, kind):
    """``decode_trace_count`` stays 1 over steps that took every rung, and
    ``serving_rows_run`` is the sum of the rungs that held each step's rows,
    by rung."""
    ladder, whole = served(kind)
    assert ladder["traces"] == 1 and whole["traces"] == 1
    want = {}
    for s in ladder["steps"]:
        rung = LADDER[step_row_rung(LADDER, s["rows"])]
        want[rung] = want.get(rung, 0) + rung
    t = ladder["telemetry"]
    got = {int(k[len("serving_rows_run{rung="):-1]): int(v)
           for k, v in t.items() if k.startswith("serving_rows_run{")}
    assert got == want and set(got) == set(LADDER)
    held = t["serving_rows_prefill"] + t["serving_rows_decode"]
    assert held == sum(s["rows"] for s in ladder["steps"]) <= sum(got.values())
    # held to its top rung, the same schedule ran the budget every step
    t = whole["telemetry"]
    assert t["serving_rows_run{rung=48}"] == 48 * len(whole["steps"])


def test_host_and_program_take_the_same_rung_for_every_row_count():
    """Every row of the budget is given a token of lane 0, and ``q_lens``
    says how many the step holds: the program writes K and V for the rows of
    the rung it took and no others, so the count of written positions IS its
    rung. For every row count from 0 to the budget it is the rung the host's
    function gives."""
    lanes, chunk, budget, page = 2, 8, 40, 8
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=1, num_heads=2,
                    max_seq_len=64)
    ladder = step_row_ladder(lanes, 0, chunk, budget)
    assert ladder == (16, 32, 40)
    paddle.seed(5)
    model = GPTForCausalLM(cfg)
    model.eval()
    params = serving_params(model)
    step = build_unified_step(cfg, page, chunk, use_kernel=False)
    pool = (1, 8, cfg.num_heads, page, cfg.head_dim)
    table = jnp.arange(16, dtype=jnp.int32).reshape(lanes, 8) % 8
    tok = jnp.arange(budget, dtype=jnp.int32)
    zeros_t, zeros_b = jnp.zeros((budget,), jnp.int32), jnp.zeros(
        (lanes,), jnp.int32)
    none = jnp.full((lanes,), pool[1], jnp.int32)
    for rows in range(budget + 1):
        _, _, kp, _ = step(
            params, 1 + tok % 90, zeros_t, tok,
            jnp.asarray([rows, 0], jnp.int32), zeros_b,
            jnp.asarray([max(rows - 1, 0), budget], jnp.int32), zeros_t,
            zeros_b, zeros_b, zeros_b, jnp.zeros(pool, jnp.float32),
            jnp.zeros(pool, jnp.float32), table, none, none,
            jnp.zeros((lanes, 2), jnp.uint32), jnp.zeros((lanes,), jnp.float32),
            zeros_b, jnp.ones((lanes,), jnp.float32))
        written = int((np.abs(np.asarray(kp)).sum(axis=(0, 2, 4)) > 0).sum())
        assert written == ladder[step_row_rung(ladder, rows)], rows
    assert step.trace_count[0] == 1


@pytest.mark.parametrize("kind", ["gpt", "latent", "sparse"])
def test_a_one_rung_program_holds_no_conditional_of_the_ladders(kind,
                                                                monkeypatch):
    """A budget no larger than the decode rows: the lowered text is the
    text of the program that knows no ladder, byte for byte (compared with
    the parent commit's by hand in PR 35: ``PERF.md``), and several rungs add
    a conditional each."""
    def lowered(**serve):
        sp, _ = _predictor(kind, **serve)
        step_fn, text = sp._unified, []

        def tapped(*args):
            text.append(step_fn.lower(*args).as_text())
            return step_fn(*args)

        tapped.trace_count = step_fn.trace_count
        sp._unified = tapped
        sp.add_request(list(range(1, 12)), max_new_tokens=2)
        sp.step()
        return sp._row_ladder, text[0]

    one = dict(token_budget=16)
    ladder, text = lowered(**one)
    assert ladder == (16,)
    ladder, several = lowered()
    assert ladder == LADDER
    monkeypatch.setattr(gpt, "step_row_ladder", top_rung_only)
    assert lowered(**one)[1] == text
    whole = lowered()[1]
    assert several.count("stablehlo.case") - whole.count(
        "stablehlo.case") >= len(LADDER)
