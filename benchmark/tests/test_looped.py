"""CPU checks of what the Ouro cell adds to the benchmark: the driver end to
end at a tiny size against the new reference (and its verdict on faults
planted in the program), the configuration file against the catalog's rules
and the program's own parameter count, the traffic file, and the new reader
and its NEED on hand-made numbers. No test starts a chip run."""
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
CELL = "serve-ouro-reasoning2k"
CONFIG = "ouro-2.6b"

#: 2 layers of hidden 64, 4 heads of 16, 3 loops
TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 96, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "total_ut_steps": 3, "early_exit_threshold": 1,
    "max_position_embeddings": 4096,
    "assumed": {"initializer_range": 0.1}, "dtype": "float32",
    "serve_looped": {
        "max_batch": 4, "max_seq_len": 96, "page_size": 4, "num_pages": 120,
        "token_budget": 32, "chunk": 8},
}
TINY_TRAFFIC = {
    "clients": 4, "schedule_seed": 7,
    "prompt": {"dist": "lognormal", "median": 10, "sigma": 0.5,
               "min": 4, "max": 16},
    "answer": {"dist": "uniform", "min": 20, "max": 30},
    "first_round_answer": {"dist": "uniform", "min": 2, "max": 8},
    "fill_requests": 4, "trace_seconds": 1,
}


def _tiny_run(monkeypatch):
    from benchmark.drivers import serve_looped as driver

    traffic = {"driver": "serve_looped", "generator": "closed_loop_looped",
               "params": TINY_TRAFFIC}
    monkeypatch.setattr(driver, "CHECK_PROMPTS", (6, 30))
    monkeypatch.setattr(driver, "CHECK_PADS", (16, 48))
    return driver, driver.run(FakeContext(TINY, traffic, seconds=0.05))


def test_driver_agrees_with_the_reference_at_a_tiny_size(monkeypatch):
    driver, out = _tiny_run(monkeypatch)
    check = out["info"]["reference_check"]
    # float32 on both sides: a prefill in chunks of 8 over pages of 4, then
    # decode through every (loop, layer)'s plane, against ONE full forward
    # a request over its six compared rows
    assert [len(r["rms"]) for r in check["rows"]] == [6, 6]
    assert max(max(r["rms"]) for r in check["rows"]) < 1e-4, check
    assert check["ok"], check
    live = check["live_lane"]
    assert live["lanes"] == 4 and live["served"], live
    assert live["rows"][0]["written"] == list(range(
        live["context"] + 1, live["context"] + 7))
    assert max(live["rows"][0]["rms"]) < 1e-4
    assert out["compared"]["logits_rms_share_of_std_max"] == {
        "value": max(check["rms_share_of_std"]),
        "limit": driver.LOGITS_TOL_RMS}
    assert out["failed"] == 0 and out["info"]["step_traces"] == 1
    assert set(out["info"]["mosaic_calls"]) == {"ragged_paged_attention",
                                               "paged_kv_write"}
    assert not out["correct"]      # off the chip no kernel is a Mosaic call
    assert out["info"]["pool_planes"] == 6
    # prompts arrive and are prefilled inside the window; every row runs
    # every loop over both layers
    c = out["counters"]
    assert c["serving_rows_prefill"] > 0 and c["serving_rows_decode"] > 0
    fed = c["serving_rows_prefill"] + c["serving_rows_decode"]
    assert [c[f"serving_loop_passes{{loop={t}}}"] for t in range(3)] \
        == [2 * fed] * 3
    record = dict(out, chips=1, peak=PEAK)
    assert run.reader_for("end_to_end", "served_tok_s").read(record) > 0
    for name in ("ragged_attn_live_block_share", "step_row_fill_share",
                 "attn_kv_passes_per_lane", "sched_prefill_row_share"):
        got = run.reader_for("layer_metrics", name).read(record)
        assert got is not None, name
    s = out["serve"]
    assert (s["layers"], s["loops"], s["heads"], s["ffn"]) == (2, 3, 4, 96)


def _shared_plane(monkeypatch):
    from paddle_tpu.models.ouro import OuroConfig

    monkeypatch.setattr(OuroConfig, "cache_plane",
                        lambda self, loop, layer: layer + 0 * loop)


def _no_loop_norm(monkeypatch):
    from paddle_tpu.models import gpt

    real = gpt._srv_norm
    monkeypatch.setattr(gpt, "_srv_norm", lambda cfg, x, p, name: (
        x if name == "lnf" and "layers" in p else real(cfg, x, p, name)))


@pytest.mark.parametrize("plant", [_shared_plane, _no_loop_norm])
def test_a_fault_planted_in_the_program_comes_out_not_correct(monkeypatch,
                                                              plant):
    """The served step keeps one plane a layer for every loop, or skips the
    norm between loops: the driver's own comparison, with its limits, says
    not correct."""
    plant(monkeypatch)
    driver, out = _tiny_run(monkeypatch)
    check = out["info"]["reference_check"]
    assert not check["ok"] and check["served"] and check["live_lane"]["served"]
    assert out["compared"]["logits_rms_share_of_std_max"]["value"] \
        > out["compared"]["logits_rms_share_of_std_max"]["limit"]


# ---- the configuration file -------------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def test_configuration_file_counts_what_the_program_makes():
    from benchmark.drivers.serve_looped import model_config

    cfgj = _config()
    dep = cfgj["serve_looped"]
    cfg = model_config(cfgj, dep)
    assert cfg.num_params() == 817_991_681
    assert "817,991,681" in cfgj["arithmetic"]["weights"]
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.num_layers, cfg.total_ut_steps, cfg.cache_planes) \
        == (12, 4, 48)
    assert (dep["max_batch"], dep["max_seq_len"], dep["page_size"],
            dep["chunk"], dep["token_budget"], dep["num_pages"]) \
        == (16, 2048, 64, 256, 512, 512)
    per_token = 48 * 2 * 16 * 128 * 2             # every plane's K and V
    assert per_token == 393_216 and 64 * per_token == 24 << 20
    assert dep["num_pages"] * 64 * per_token == 12_884_901_888
    # weights and pool take 91% of one chip's 16 GB before temporaries
    assert 0.90 < (2 * cfg.num_params() + 12_884_901_888) / 16e9 < 0.92
    whole = model_config(dict(cfgj, num_hidden_layers=48), dep)
    assert whole.num_params() == 2_667_974_657
    assert "2,667,974,657" in cfgj["arithmetic"]["whole"]


def test_configuration_file_keeps_the_published_numbers():
    """Every number of the catalog's config under the same key but
    ``num_hidden_layers``; ``layer_types`` kept whole (the program reads its
    first layers); no width among the cut keys."""
    cfgj = _config()
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152}
    for key, value in published.items():
        assert cfgj[key] == value, key
    assert cfgj["layer_types"] == ["full_attention"] * 48
    assert cfgj["tie_word_embeddings"] is False
    assert cfgj["use_sliding_window"] is False
    assert cfgj["sliding_window"] is None and cfgj["rope_scaling"] is None
    assert (cfgj["model_type"], cfgj["hidden_act"]) == ("ouro", "silu")
    assert cfgj["reduced"] == ["num_hidden_layers"]
    assert set(cfgj["reduced_why"]) == set(cfgj["reduced"])
    assert (cfgj["num_hidden_layers"],
            cfgj["num_hidden_layers_published"]) == (12, 48)
    for word in ("sandwich_norms", "state_between_loops", "no_biases",
                 "early_exit", "rotary_pairing", "cache_planes", "dtype"):
        assert word in cfgj["assumed"], word
    assert "four-stage pipeline" in cfgj["stands_for"]
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfgj["reduced"]
    assert entry["source"] == cfgj["source"]


def test_traffic_file_is_the_issues():
    cell = run.load_cell(CELL)
    p = cell["traffic"]["params"]
    assert p["clients"] == 16 == cell["config"]["serve_looped"]["max_batch"]
    assert p["prompt"] == {"dist": "lognormal", "median": 160, "sigma": 0.5,
                           "min": 48, "max": 256}
    assert p["answer"] == {"dist": "lognormal", "median": 1024, "sigma": 0.4,
                           "min": 512, "max": 1792}
    assert p["first_round_answer"] == {"dist": "uniform", "min": 32,
                                       "max": 512}
    assert p["fill_requests"] == 16 and "temperature" not in p
    gen = run.load_module("generators", cell["traffic"]["generator"]).build(
        p, 1, vocab_size=49152, max_seq_len=2048)
    first = sorted((gen.lengths(c, 0) for c in range(16)),
                   key=lambda pa: pa[1])
    assert " ".join(f"({a}, {b})" for a, b in first) in cell["traffic"]["why"]
    # a request's prompt and answer fit a lane's 2,048 positions
    assert p["prompt"]["max"] + p["answer"]["max"] == 2048
    names = {m["name"] for m in cell["per_layer"]}
    assert {"ragged_attn_busy_share", "ragged_attn_roofline",
            "attn_kv_passes_per_lane", "ragged_attn_live_block_share",
            "loop_weight_stream_roofline", "setup_compile_s",
            "step_row_fill_share", "device_idle_share.serve"} <= names
    assert cell["cell"]["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s",
                                                       "served_tok_s"]


# ---- the new reader ---------------------------------------------------------

def test_weight_stream_need_by_hand():
    from benchmark.kernels import loop_weight_stream as kernel

    dims = dict(hidden=2048, heads=16, head_dim=128, ffn=5632)
    assert kernel.layer_values(**dims) == 51_380_224   # the four matrices
    ops, nbytes = kernel.needs(16 * 48, 48, w_bytes=2, x_bytes=2, **dims)
    assert ops == 2 * 51_380_224 * 768
    rows_io = (2048 + 6144) + (2048 + 2048) + (2048 + 11264) + (5632 + 2048)
    assert nbytes == 51_380_224 * 2 * 48 + rows_io * 2 * 768
    # a decode step of 16 rows streams the weights: memory bound
    assert nbytes / PEAK["hbm_bytes_per_s"] > ops / PEAK["bf16_flops_per_s"]


def test_weight_stream_roofline_on_a_hand_made_run(monkeypatch):
    from benchmark import scope_trace
    from benchmark.kernels import loop_weight_stream as kernel

    reader = run.reader_for("layer_metrics", "loop_weight_stream_roofline")
    s = {"layers": 12, "loops": 4, "heads": 16, "head_dim": 128,
         "hidden": 2048, "ffn": 5632, "kv_bytes": 2}
    counters = {f"serving_loop_passes{{loop={t}}}": 100 * 16 * 12
                for t in range(4)}
    counters["serving_steps"] = 100
    dev = {"ops": {"ragged_paged_attention": {"seconds": 0.1,
                                              "calls": 10 * 48}},
           "busy_s": 1.0, "window": [0, 1]}
    run_ = {"serve": s, "counters": counters, "peak": PEAK,
            "trace": {"devices": [dev]}}
    charged = {("qkv", False, "fusion"): [0.02, 480],
               ("attn_out", False, "fusion"): [0.01, 480],
               ("mlp", False, "fusion"): [0.06, 480],
               ("layers.carry", False, "slice"): [0.01, 480],
               ("attn", False, "x"): [0.5, 480]}
    monkeypatch.setattr(scope_trace, "table", lambda r: charged)
    share, note = reader.read(run_)
    ops, nbytes = kernel.needs(16 * 48, 48, hidden=2048, heads=16,
                               head_dim=128, ffn=5632, w_bytes=2, x_bytes=2)
    least = max(ops / PEAK["bf16_flops_per_s"],
                nbytes / PEAK["hbm_bytes_per_s"])
    assert note["captured_steps"] == 10 and note["bound_by"] == "memory"
    assert share == pytest.approx(100 * least * 10 / 0.1)
    # a program that runs its stack once counts no loop passes
    assert reader.read(dict(run_, counters={"serving_steps": 100})) is None
