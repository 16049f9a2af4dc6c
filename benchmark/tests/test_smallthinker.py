"""CPU checks of what the SmallThinker cell adds to the benchmark: the driver
end to end at a tiny size against the new reference (and its verdict on a
fault planted in the program), the configuration file against the catalog's
rules and the program's own parameter count, the traffic file, and the new
reader on hand-made counters. No test starts a chip run."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.tests.test_benchmark import FakeContext  # noqa: E402

PEAK = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
CELL = "serve-smallthinker-docqa16k"
CONFIG = "smallthinker-21ba3b-instruct"

#: 8 layers (two periods: full, window x 3), 14 query heads over 2 key-value
#: heads (the published group of 7), window 16, 8 experts top-2
TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 2, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000,
    "rope_layout": [0, 1, 1, 1] * 3, "sliding_window_layout": [0, 1, 1, 1] * 3,
    "sliding_window_size": 16, "max_position_embeddings": 4096,
    "assumed": {"initializer_range": 0.1}, "dtype": "float32",
    "serve_smallthinker": {
        "max_batch": 4, "max_seq_len": 160, "page_size": 4, "num_pages": 160,
        "token_budget": 32, "chunk": 8},
}
TINY_TRAFFIC = {
    "clients": 4, "schedule_seed": 7,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.6,
               "min": 8, "max": 40},
    "answer": {"dist": "uniform", "min": 12, "max": 16},
    "first_round_answer": {"dist": "uniform", "min": 2, "max": 8},
    "fill_requests": 4, "trace_seconds": 1,
}


def _tiny_run(monkeypatch):
    from benchmark.drivers import serve_smallthinker as driver

    traffic = {"driver": "serve_smallthinker",
               "generator": "closed_loop_smallthinker",
               "params": TINY_TRAFFIC}
    monkeypatch.setattr(driver, "CHECK_PROMPTS", (6, 45))
    monkeypatch.setattr(driver, "CHECK_PADS", (32, 64))
    return driver, driver.run(FakeContext(TINY, traffic, seconds=0.05))


def test_driver_agrees_with_the_reference_at_a_tiny_size(monkeypatch):
    driver, out = _tiny_run(monkeypatch)
    check = out["info"]["reference_check"]
    # float32 on both sides: a prefill in chunks across the window's edge
    # (45 tokens, window 16, chunk 8), then decode through both cache groups,
    # against ONE full forward a request over its six compared rows
    assert [len(r["rms"]) for r in check["rows"]] == [6, 6]
    assert max(max(r["rms"]) for r in check["rows"]) < 1e-4, check
    assert check["window_pages_released"] > 0 and check["ok"], check
    # a LIVE lane after the fill, past the window: six rows against the
    # reference's forward over its own prompt and answer so far
    live = check["live_lane"]
    assert live["lanes"] == 4 and live["context"] > 16
    assert live["window_first_page"] > 0 and live["served"], live
    assert live["rows"][0]["written"] == list(range(
        live["context"] + 1, live["context"] + 7))
    assert max(live["rows"][0]["rms"]) < 1e-4
    # each row is held to the nearer of the reference's two answers: the
    # reference's routing, and its nearest choice taken the other way
    for r in check["rows"] + live["rows"]:
        assert r["rms"] == [min(a, b) for a, b in zip(r["rms_as_routed"],
                                                      r["rms_swapped"])]
        assert len(r["nearest"]) == 6 and max(r["rms_swapped"]) > 1e-3
    assert out["compared"]["logits_rms_share_of_std_row_median_max"] == {
        "value": max(check["rms_share_of_std_row_median"]),
        "limit": driver.LOGITS_TOL_RMS_MEDIAN}
    assert out["failed"] == 0 and out["info"]["step_traces"] == 1
    assert set(out["info"]["mosaic_calls"]) == {
        "ragged_paged_attention", "grouped_matmul", "paged_kv_write"}
    assert not out["correct"]      # off the chip no kernel is a Mosaic call
    # prompts arrive and are prefilled inside the window
    c = out["counters"]
    assert c["serving_rows_prefill"] > 0 and c["serving_rows_decode"] > 0
    assert out["info"]["finished_in_window"] > 0
    record = dict(out, chips=1, peak=PEAK)
    assert run.reader_for("end_to_end", "served_tok_s").read(record) > 0
    share, note = run.reader_for(
        "layer_metrics", "attn_query_block_fill_share").read(record)
    assert 0 < share < 100
    assert note["query_rows"] == c["serving_attn_query_rows"]
    for name in ("window_key_share", "kv_window_page_share",
                 "ragged_attn_live_block_share", "step_row_fill_share",
                 "moe_expert_load_max_share", "queue_wait_mean_ms",
                 "prefill_mean_ms", "sched_prefill_row_share"):
        got = run.reader_for("layer_metrics", name).read(record)
        assert got is not None, name
    assert out["serve"]["experts"] == 8 and out["serve"]["kv_heads"] == 2


def _no_lower_edge(monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as pa

    real = pa.ragged_paged_attention
    monkeypatch.setattr(
        pa, "ragged_paged_attention",
        lambda *a, window=None, **kw: real(*a, window=None, **kw))


def _router_after_attention(monkeypatch):
    import dataclasses

    from benchmark.drivers import serve_smallthinker as driver

    real = driver.model_config
    monkeypatch.setattr(driver, "model_config", lambda *a: dataclasses.replace(
        real(*a), router_before_attention=False))


@pytest.mark.parametrize("plant", [_no_lower_edge, _router_after_attention])
def test_a_fault_planted_in_the_program_comes_out_not_correct(monkeypatch,
                                                              plant):
    """The served step's window layers read every key their table holds (no
    lower edge), or its router reads the FFN's norm: the driver's own
    comparison, with its limits, says not correct."""
    plant(monkeypatch)
    driver, out = _tiny_run(monkeypatch)
    check = out["info"]["reference_check"]
    assert not check["ok"] and check["served"] and check["live_lane"]["served"]
    assert check["rms_share_of_std"][1] > driver.LOGITS_TOL_RMS
    assert out["compared"]["logits_rms_share_of_std_max"]["value"] \
        > out["compared"]["logits_rms_share_of_std_max"]["limit"]


# ---- the configuration file -------------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_configuration_file_counts_what_the_program_makes():
    from benchmark.drivers.serve_smallthinker import model_config
    from paddle_tpu.models.layer_kinds import stack_runs

    cfgj = _config()
    dep = cfgj["serve_smallthinker"]
    cfg = model_config(cfgj, dep)
    assert cfg.num_params() == 3_966_937_600
    assert "3,966,937,600" in cfgj["arithmetic"]["weights"]
    assert stack_runs(cfg) == [("full_attention", 1),
                               ("sliding_attention", 3)] * 2
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (28, 4, 128)
    assert (cfg.hidden_act, cfg.router_before_attention) == ("relu", True)
    assert (dep["max_batch"], dep["max_seq_len"], dep["page_size"],
            dep["chunk"], dep["token_budget"]) == (32, 16384, 64, 256, 1024)
    per_page = 4 * 64 * 128 * 2 * 2          # K and V, bf16, one layer
    assert 2 * dep["num_pages"] * per_page == 2_147_483_648
    assert 6 * 32 * 69 * per_page == 1_736_441_856
    # weights and cache take 74% of one chip's 16 GB before temporaries
    assert 0.73 < (2 * cfg.num_params() + 2_147_483_648
                   + 1_736_441_856) / 16e9 < 0.75
    # all 64 experts held: no share cut
    assert cfg.n_routed_experts == cfgj["moe_num_primary_experts"] == 64


def test_configuration_file_keeps_the_published_numbers():
    """Every number of the catalog's config under the same key but
    ``num_hidden_layers``; the layouts kept whole (the program reads their
    first layers); no width among the cut keys."""
    cfgj = _config()
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64, "num_attention_heads": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1500000, "sliding_window_size": 4096,
        "vocab_size": 151936}
    for key, value in published.items():
        assert cfgj[key] == value, key
    assert cfgj["moe_primary_router_apply_softmax"] is True
    assert cfgj["norm_topk_prob"] is True
    assert cfgj["tie_word_embeddings"] is False
    layout = [0, 1, 1, 1] * 13
    assert cfgj["rope_layout"] == layout == cfgj["sliding_window_layout"]
    assert cfgj["reduced"] == ["num_hidden_layers"]
    assert set(cfgj["reduced_why"]) == set(cfgj["reduced"])
    assert (cfgj["num_hidden_layers"],
            cfgj["num_hidden_layers_published"]) == (8, 52)
    for word in ("router_input", "expert_activation", "rotary_pairing",
                 "sliding_window", "activation_sparsity", "dtype"):
        assert word in cfgj["assumed"], word
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfgj["reduced"]
    assert entry["source"] == cfgj["source"]


def test_traffic_file_is_the_issues():
    cell = run.load_cell(CELL)
    p = cell["traffic"]["params"]
    assert p["clients"] == 32 == cell["config"]["serve_smallthinker"][
        "max_batch"]
    assert p["prompt"] == {"dist": "lognormal", "median": 4096, "sigma": 0.6,
                           "min": 1024, "max": 12288}
    assert p["answer"] == {"dist": "lognormal", "median": 128, "sigma": 0.5,
                           "min": 32, "max": 384}
    assert p["first_round_answer"] == {"dist": "uniform", "min": 8,
                                       "max": 128}
    assert p["fill_requests"] == 32 and "temperature" not in p
    gen = run.load_module("generators", cell["traffic"]["generator"]).build(
        p, 1, vocab_size=151936, max_seq_len=16384)

    def over(seed):
        g = run.load_module("generators", cell["traffic"]["generator"]).build(
            dict(p, schedule_seed=seed), 1, vocab_size=151936,
            max_seq_len=16384)
        return sum(g.lengths(c, 0)[0] > 4096 for c in range(32))

    # the first seed from 40000 with at least 14 of 32 first prompts over
    # the window
    assert p["schedule_seed"] == next(
        s for s in range(40000, 40100) if over(s) >= 14)
    prompts = [gen.lengths(c, 0)[0] for c in range(32)]
    assert " ".join(map(str, sorted(prompts))) in cell["traffic"]["why"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"attn_query_block_fill_share", "window_attn_busy_share",
            "full_attn_busy_share", "window_attn_roofline",
            "full_attn_roofline", "window_key_share", "kv_window_page_share",
            "moe_grouped_mm_roofline", "queue_wait_mean_ms",
            "prefill_mean_ms", "setup_compile_s"} <= names
    # its NEED reckons ``num_heads`` K and V heads over the whole context
    assert "ragged_attn_roofline" not in names
    assert cell["cell"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s",
                                                       "served_tok_s"]


# ---- the new reader ---------------------------------------------------------

def test_query_block_fill_share_on_hand_made_counters():
    # a decode step of 32 lanes at 28 query heads over 4 key-value heads, 8
    # layers: 32 rows x 28 heads fed, 32 lanes x 4 heads x 32 rows x 7 built
    rows, blocks = 8 * 32 * 28, 8 * 32 * 4 * 224
    share, note = run.reader_for(
        "layer_metrics", "attn_query_block_fill_share").read(
            {"counters": {"serving_attn_query_rows": float(rows),
                          "serving_attn_query_block_rows": float(blocks),
                          "serving_steps": 1.0}})
    assert share == pytest.approx(100 / 32)
    assert note == {"query_rows": rows, "block_rows": blocks,
                    "block_rows_per_step": blocks}
    # an earlier program has no such counters: nothing to read
    assert run.reader_for("layer_metrics",
                          "attn_query_block_fill_share").read(
        {"counters": {"serving_steps": 3.0}}) is None
