"""CPU checks of what the Command A+ cell adds to the benchmark: the driver
end to end at a tiny size against the new reference, the configuration file
against the catalog's rules and the program's own parameter count, the kernel's
operation and byte counts on hand-made lanes, the six new readers on a
hand-built trace and hand-made counters. No test starts a chip run."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.kernels import gqa_window_paged_attention as need  # noqa: E402
from benchmark.layer_metrics import _window  # noqa: E402
from benchmark.tests.test_benchmark import FakeContext  # noqa: E402

US = 1e-6
PEAK = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
CELL = "serve-cmdaplus-mixedlen-decode"

TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 32, "num_experts": 4, "num_experts_published": 16,
    "experts_held_first": 4, "num_shared_experts": 2,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "expert_selection_fn": "sigmoid", "layer_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "sliding_window": 16, "logit_scale": 1, "use_parallel_block": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "max_position_embeddings": 4096,
    "assumed": {"initializer_range": 0.1}, "dtype": "float32",
    "serve_window_moe": {
        "max_batch": 4, "max_seq_len": 160, "page_size": 4, "num_pages": 160,
        "token_budget": 32, "chunk": 8},
}
TINY_DECODE = {
    "clients": 4, "schedule_seed": 7,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.8,
               "min": 6, "max": 60},
    "answer": {"dist": "uniform", "min": 80, "max": 88},
    "fill_tokens_per_lane": 3, "trace_seconds": 1,
}


# ---- the driver ------------------------------------------------------------

def _tiny_run(monkeypatch):
    from benchmark.drivers import serve_window_moe as driver

    traffic = {"driver": "serve_window_moe",
               "generator": "closed_loop_window_moe", "params": TINY_DECODE}
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
    # a LIVE lane after the fill, one of four, past the window: six rows
    # against the reference's forward over its own prompt and answer so far
    live = check["live_lane"]
    assert live["lanes"] == 4 and live["window_first_page"] > 0
    assert live["context"] > 16 and len(live["rows"][0]["rms"]) == 6
    assert live["rows"][0]["written"] == list(range(
        live["context"] + 1, live["context"] + 7))
    assert max(live["rows"][0]["rms"]) < 1e-4 and live["served"], live
    assert len(check["rms_share_of_std"]) == 3
    # beside each row, its routing: in float32 the served step feeds the
    # held experts the reference chose (where it fed that row alone)
    notes = [n for r in check["rows"] + live["rows"] for n in r["routing"]]
    assert len(notes) == 18
    alone = [n for n in notes if "held_chosen_served" in n]
    assert alone and all(n["held_chosen_served"] == n["held_chosen"]
                         for n in alone)
    assert all(n["edge_margin"] is None or n["edge_margin"] >= 0
               for n in notes)
    assert out["compared"]["logits_rms_share_of_std_max"]["limit"] \
        == driver.LOGITS_TOL_RMS
    assert out["compared"]["logits_rms_share_of_std_row_median_max"] == {
        "value": max(check["rms_share_of_std_row_median"]),
        "limit": driver.LOGITS_TOL_RMS_MEDIAN}
    assert driver.LOGITS_TOL_RMS_MEDIAN < driver.LOGITS_TOL_RMS
    assert out["failed"] == 0 and out["info"]["step_traces"] == 1
    # nothing finishes inside the window; every lane was handed tokens in it
    assert out["info"]["finished_in_window"] == 0 and out["attempted"] == 4
    assert out["info"]["deliveries_in_window"] > 0
    assert set(out["info"]["mosaic_calls"]) == {
        "ragged_paged_attention", "grouped_matmul", "paged_kv_write"}
    assert not out["correct"]      # off the chip no kernel is a Mosaic call
    record = dict(out, chips=1, peak=PEAK)
    assert run.reader_for("end_to_end", "served_tok_s").read(record) > 0
    c = out["counters"]
    assert c["serving_rows_prefill"] == 0 and c["serving_rows_decode"] > 0
    share, note = run.reader_for(
        "layer_metrics", "window_key_share").read(record)
    assert 0 < share < 100
    assert note["keys_read"] == c["serving_window_keys_read"]
    # by hand from the steps' lanes: three of four layers read at most 16
    wc = out["window_cache"]
    assert wc["lanes_over_window"] == 4 and len(wc["contexts"]) == 4
    pages, note = run.reader_for(
        "layer_metrics", "kv_window_page_share").read(record)
    # a lane over the window holds 4-6 pages of its ceil(context / 4)
    assert note["pages_unreleased"] == sum(-(-n // 4) for n in wc["contexts"])
    assert 4 * 4 <= note["pages_held"] <= 4 * 7 and 0 < pages < 100
    assert c["serving_moe_rows_elsewhere"] > c["serving_moe_rows_routed"] > 0
    load, _ = run.reader_for(
        "layer_metrics", "moe_expert_load_max_share").read(record)
    assert load >= 100.0 and out["serve"]["experts"] == 4


def _no_lower_edge(monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as pa

    real = pa.ragged_paged_attention
    monkeypatch.setattr(
        pa, "ragged_paged_attention",
        lambda *a, window=None, **kw: real(*a, window=None, **kw))


def _no_rotary(monkeypatch):
    from paddle_tpu.models import cohere2_moe

    monkeypatch.setattr(cohere2_moe, "rope_interleaved",
                        lambda x, positions, theta: x)


@pytest.mark.parametrize("plant", [_no_lower_edge, _no_rotary])
def test_a_fault_planted_in_the_program_comes_out_not_correct(monkeypatch,
                                                              plant):
    """The served step's window layers read every key their table holds (no
    lower edge), or its window layers get no positions: the driver's own
    comparison, with its limits, says not correct, in the check requests and
    in the live lane."""
    plant(monkeypatch)
    driver, out = _tiny_run(monkeypatch)
    check = out["info"]["reference_check"]
    assert not check["ok"] and check["served"] and check["live_lane"]["served"]
    assert [len(r["rms"]) for r in check["rows"]] == [6, 6]
    # the long check prompt and the live lane are past the window
    assert check["rms_share_of_std"][1] > driver.LOGITS_TOL_RMS
    assert check["rms_share_of_std"][2] > driver.LOGITS_TOL_RMS
    assert check["rms_share_of_std_row_median"][2] \
        > driver.LOGITS_TOL_RMS_MEDIAN
    assert out["compared"]["logits_rms_share_of_std_max"]["value"] \
        > out["compared"]["logits_rms_share_of_std_max"]["limit"]


def test_the_controls_go_through_the_cells_own_verdict():
    from benchmark.drivers.serve_window_moe import verdict

    served = [[0.009, 0.0638, 0.065, 0.0084, 0.0092, 0.0084], [0.009] * 6]
    assert verdict(served) == ([pytest.approx(0.0273),
                                pytest.approx(0.009)],
                               [pytest.approx(0.0091), pytest.approx(0.009)],
                               True)
    assert not verdict([[0.009] * 5])[2]        # a row short
    assert not verdict([])[2]
    assert not verdict([[0.101, 0.11, 0.13, 0.1, 0.12, 0.14]])[2]   # e4m3
    # the median alone: three rows of six off
    assert not verdict([[0.009, 0.07, 0.009, 0.07, 0.07, 0.009]])[2]
    with open(os.path.join(ROOT, "benchmark", "tools",
                           "window_moe_controls.py")) as f:
        tool = f.read()
    assert "verdict([rms.tolist()])" in tool and 'c["correct"]' in tool


# ---- the configuration file ---------------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "command-a-plus-05-2026.json")) as f:
        return json.load(f)


def test_configuration_file_counts_what_the_program_makes():
    from benchmark.drivers.serve_window_moe import model_config
    from paddle_tpu.models.cohere2_moe import stack_runs

    cfgj = _config()
    dep = cfgj["serve_window_moe"]
    cfg = model_config(cfgj, dep)
    assert cfg.num_params() == 4_733_292_544
    assert "4,733,292,544" in cfgj["arithmetic"]["weights"]
    assert stack_runs(cfg) == [("sliding_attention", 3),
                               ("full_attention", 1)]
    assert cfg.experts_held == (0, 16) and cfg.shared_expert_scale == 0.25
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (128, 8, 128)
    # the deployment the issue names: 32 lanes of 57,344 tokens
    assert (dep["max_batch"], dep["max_seq_len"], dep["page_size"],
            dep["chunk"], dep["token_budget"]) == (32, 57344, 64, 256, 1024)
    per_page = 8 * 64 * 128 * 2 * 2          # K and V, bf16, one layer
    assert dep["num_pages"] * per_page == 2_147_483_648
    assert 3 * 32 * 69 * per_page == 1_736_441_856


def test_configuration_file_keeps_the_published_numbers():
    """Every number of the catalog's config under the same key, but the keys
    listed in ``reduced``; no width among those."""
    cfgj = _config()
    published = {
        "head_dim": 128, "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_shared_experts": 4, "first_k_dense_replace": 0,
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rope_theta": 50000,
        "rotary_pct": 1, "sliding_window": 4096}
    for key, value in published.items():
        assert cfgj[key] == value, key
    assert cfgj["rope_parameters"] == {"rope_theta": 50000,
                                       "rope_type": "default"}
    assert cfgj["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "layer_types"]
    assert set(cfgj["reduced_why"]) == set(cfgj["reduced"])
    assert (cfgj["num_hidden_layers"], cfgj["num_experts"],
            cfgj["vocab_size"]) == (4, 16, 32768)
    assert (cfgj["num_hidden_layers_published"],
            cfgj["num_experts_published"],
            cfgj["vocab_size_published"]) == (32, 128, 262144)
    assert cfgj["vocab_size"] * 8 == cfgj["vocab_size_published"]
    for word in ("sliding_window", "rotary_pairing", "shared_experts",
                 "expert_width"):
        assert word in cfgj["assumed"], word
    assert "vision tower" in cfgj["assumed"]["not_run"]
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "command-a-plus-05-2026")
    assert entry["reduced"] == cfgj["reduced"]
    assert entry["source"] == cfgj["source"]


def test_traffic_file_is_the_issues():
    cell = run.load_cell(CELL)
    p = cell["traffic"]["params"]
    assert p["clients"] == 32 == cell["config"]["serve_window_moe"]["max_batch"]
    assert p["prompt"] == {"dist": "lognormal", "median": 8192, "sigma": 1.0,
                           "min": 1024, "max": 49152}
    assert p["answer"] == {"dist": "uniform", "min": 6144, "max": 8192}
    # the clients decode greedily, as every serving cell's do
    assert p["fill_tokens_per_lane"] == 64 and "temperature" not in p
    gen = run.load_module("generators", cell["traffic"]["generator"]).build(
        p, 1, vocab_size=32768, max_seq_len=57344)
    prompts = [gen.lengths(c, 0)[0] for c in range(32)]
    assert 380_000 <= sum(prompts) == 405_432 <= 420_000
    assert sum(n < 4096 for n in prompts) == 9
    assert sum(n > 24576 for n in prompts) == 5
    assert " ".join(map(str, sorted(prompts))) in cell["traffic"]["why"]
    # nothing finishes in a window, and every request fits its lane
    assert all(sum(gen.lengths(c, 0)) <= 57344 for c in range(32))
    names = {m["name"] for m in cell["per_layer"]}
    assert {"window_attn_busy_share", "full_attn_busy_share",
            "window_attn_roofline", "full_attn_roofline", "window_key_share",
            "kv_window_page_share", "ragged_attn_busy_share",
            "ragged_attn_live_block_share",
            "moe_grouped_mm_roofline"} <= names
    # its NEED reckons ``num_heads`` K and V heads over the whole context
    assert "ragged_attn_roofline" not in names
    assert cell["cell"]["chips"] == 1


# ---- what a call needs -------------------------------------------------------------

def test_needs_count_the_masks_keys_and_the_kv_heads_bytes():
    kw = dict(num_heads=128, kv_heads=8, head_dim=128, kv_bytes=2, q_bytes=2,
              out_bytes=2)
    # a decode row at position 9,999: 4,096 keys under the window, 10,000
    # without; K and V of 8 heads over the positions any row sees
    ops, nbytes = need.needs([(1, 10_000)], window=4096, **kw)
    assert ops == 4 * 128 * 128 * 4096
    assert nbytes == 2 * 4096 * 8 * 128 * 2 + 128 * 128 * 4
    ops, nbytes = need.needs([(1, 10_000), (0, 77)], window=None, **kw)
    assert ops == 4 * 128 * 128 * 10_000
    assert nbytes == 2 * 10_000 * 8 * 128 * 2 + 128 * 128 * 4
    # a chunk of 256 rows from position 4,000: the rows before the edge see
    # all before them, the rest 4,096; every position so far is read. From
    # position 8,744: 4,096 keys a row, positions 4,649 .. 8,999 are read
    assert need.keys_admitted(256, 4256, 4096) == sum(
        min(p + 1, 4096) for p in range(4000, 4256))
    _, nbytes = need.needs([(256, 4256)], window=4096, **kw)
    assert nbytes == 2 * 4256 * 8 * 128 * 2 + 256 * 128 * 128 * 4
    ops, nbytes = need.needs([(256, 9000)], window=4096, **kw)
    assert ops == 4 * 128 * 128 * 256 * 4096
    assert nbytes == 2 * (4096 + 255) * 8 * 128 * 2 + 256 * 128 * 128 * 4
    assert need.keys_admitted(3, 10, None) == 8 + 9 + 10


# ---- the readers on a hand-built trace ------------------------------------------------

def _events():
    k = "%ragged_paged_attention.1 = f32[32,8,512,128]{3,2,1,0} custom-call("
    other = "%fusion.3 = bf16[32,4096]{1,0} fusion("
    base = "jit(step)/layers/while/body/attn/"
    return [(k, 0.0, 30 * US, base + "attn_window/pallas_call"),
            (k, 40 * US, 30 * US, base + "attn_window/pallas_call"),
            (k, 80 * US, 30 * US, base + "attn_window/pallas_call"),
            (k, 120 * US, 50 * US, "jit(step)/layers/attn/attn_full/x"),
            (other, 170 * US, 10 * US, base + "attn_window/scatter"),
            (other, 180 * US, 50 * US, "jit(step)/layers/mlp/moe_experts/y")]


def test_kernel_time_divides_by_layer_kind():
    charged, busy = _window.charge(_events())
    assert charged["attn_window"] == [pytest.approx(90 * US), 3]
    assert charged["attn_full"] == [pytest.approx(50 * US), 1]
    assert busy == pytest.approx(200 * US)
    assert _window.kind_of("jit(step)/layers/attn/x") is None
    run_ = {"serve": {}, "window_attn_table": {
        "attn_window": (90 * US, 3), "attn_full": (50 * US, 1),
        "busy": 200 * US}}
    assert _window.busy_share(run_, "attn_window") == pytest.approx(45.0)
    assert run.reader_for("layer_metrics", "full_attn_busy_share").read(
        run_) == pytest.approx(25.0)


def test_rooflines_read_the_captured_steps_lanes():
    lanes = [(1, 10_000), (1, 3_000)]
    serve = {"heads": 128, "kv_heads": 8, "head_dim": 128, "kv_bytes": 2,
             "window": 4096, "steps": [(1.0, 2, lanes), (2.0, 2, lanes)]}
    run_ = {"serve": serve, "peak": PEAK, "clock": {"trace_t0": 0.5},
            "window_attn_table": {"attn_window": (90 * US, 3),
                                  "attn_full": (100 * US, 1),
                                  "busy": 250 * US}}
    nbytes = 2 * (4096 + 3000) * 8 * 128 * 2 + 2 * 128 * 128 * 4
    share, note = run.reader_for("layer_metrics",
                                 "window_attn_roofline").read(run_)
    assert share == pytest.approx(100 * (nbytes / 800e9) * 3 / (90 * US))
    assert note["bound_by"] == {"compute": 0, "memory": 2}
    nbytes = 2 * 13_000 * 8 * 128 * 2 + 2 * 128 * 128 * 4
    share, note = run.reader_for("layer_metrics",
                                 "full_attn_roofline").read(run_)
    assert share == pytest.approx(100 * (nbytes / 800e9) / (100 * US))
    assert note["calls"] == 1 and share < 100


def test_readers_find_nothing_in_a_program_without_the_window_group():
    older = {"serve": {"heads": 12, "head_dim": 64, "kv_bytes": 2,
                       "steps": []},
             "counters": {"serving_steps": 10.0}, "trace": None,
             "peak": PEAK, "clock": {}}
    for name in ("window_attn_busy_share", "full_attn_busy_share",
                 "window_attn_roofline", "full_attn_roofline",
                 "window_key_share", "kv_window_page_share"):
        assert run.reader_for("layer_metrics", name).read(older) is None, name
