"""CPU checks of what the GLM-5.2 cell adds to the benchmark: the driver end
to end at a tiny size against the new reference (logits and selected sets),
the configuration file against the program's own parameter count, the three
kernels' operation and byte counts on hand-made shapes, the new readers on a
hand-built trace. No test starts a chip run."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.kernels import (dsa_index_scores, dsa_topk_select,  # noqa: E402
                               sparse_mla_paged_attention)
from benchmark.layer_metrics import _dsa  # noqa: E402
from benchmark.tests.test_benchmark import FakeContext  # noqa: E402

US = 1e-6
PEAK = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
CELL = "serve-glm52-longctx-decode"

TINY = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "intermediate_size": 96,
    "moe_intermediate_size": 48, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "experts_held_first": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "index_n_heads": 2, "index_head_dim": 16, "index_topk": 8,
    "indexer_types": ["full", "shared", "shared", "shared", "full"],
    "max_position_embeddings": 4096,
    "assumed": {"initializer_range": 0.1}, "dtype": "float32",
    "serve_sparse_latent_moe": {
        "max_batch": 4, "max_seq_len": 128, "page_size": 8, "num_pages": 64,
        "token_budget": 32, "chunk": 8},
}
TINY_DECODE = {
    "clients": 4, "schedule_seed": 7,
    "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.3,
               "min": 12, "max": 40},
    "answer": {"dist": "uniform", "min": 80, "max": 88},
    "fill_tokens_per_lane": 3, "trace_seconds": 1,
}


# ---- the driver ------------------------------------------------------------

def test_driver_agrees_with_the_reference_at_a_tiny_size():
    from benchmark.drivers import serve_sparse_latent_moe as driver

    traffic = {"driver": "serve_sparse_latent_moe",
               "generator": "closed_loop_sparse_latent_moe",
               "params": TINY_DECODE}
    driver.CHECK_PROMPTS, driver.CHECK_PADS = (6, 45), (32, 64)
    out = driver.run(FakeContext(TINY, traffic, seconds=0.05))
    check = out["info"]["reference_check"]
    # float32 on both sides: a prefill in chunks (45 tokens, chunk 8), then
    # decode through the paged cache and its index plane, against ONE full
    # forward a request over its six compared rows
    assert len(check["rms_share_of_std"]) == 2
    assert [len(r["rms"]) for r in check["rows"]] == [6, 6]
    assert max(check["rms_share_of_std"]) < 1e-4, check
    # the long request's rows are all past index_topk (8), the short one's
    # from its third decode row on; per request and layer with an indexer
    # the mean share of the reference's set held: all of it
    assert [len(r["selection_share"]) for r in check["rows"]] == [3, 6]
    assert check["selection_share"] == [1.0] * 4 and check["ok"], check
    assert out["compared"]["selection_share_of_reference_min"] == {
        "value": 1.0, "limit": driver.SELECTION_SHARE_MIN}
    assert out["failed"] == 0 and out["info"]["step_traces"] == 1
    # nothing finishes inside the window; every lane was handed tokens in it
    assert out["info"]["finished_in_window"] == 0 and out["attempted"] == 4
    assert out["info"]["deliveries_in_window"] > 0
    assert out["info"]["fill_prompt_tokens"] == sum(
        r["prompt"] for r in out["serve"]["requests"].values())
    assert set(out["info"]["mosaic_calls"]) == {
        "sparse_mla_paged_attention", "dsa_index_scores", "dsa_topk_select",
        "grouped_matmul", "paged_kv_write"}
    assert not out["correct"]      # off the chip no kernel is a Mosaic call
    record = dict(out, chips=1, peak=PEAK)
    assert run.reader_for("end_to_end", "served_tok_s").read(record) > 0
    c = out["counters"]
    assert c["serving_rows_prefill"] == 0 and c["serving_rows_decode"] > 0
    # five attention layers, two of them with an indexer
    assert c["serving_dsa_keys_context"] * 2 == c["serving_dsa_keys_scored"] * 5
    share, note = run.reader_for(
        "layer_metrics", "dsa_selected_key_share").read(record)
    assert 0 < share < 100 and note["keys_selected"] == c[
        "serving_dsa_keys_selected"]
    assert c["serving_moe_rows_elsewhere"] > c["serving_moe_rows_routed"] > 0
    load, _ = run.reader_for(
        "layer_metrics", "moe_expert_load_max_share").read(record)
    assert load >= 100.0 and out["serve"]["experts"] == 4


# ---- the configuration file ---------------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.2.json")) as f:
        return json.load(f)


def test_configuration_file_counts_what_the_program_makes():
    from benchmark.drivers.serve_sparse_latent_moe import model_config
    from paddle_tpu.models.glm_moe_dsa import stack_runs

    cfgj = _config()
    dep = cfgj["serve_sparse_latent_moe"]
    cfg = model_config(cfgj, dep)
    assert cfg.num_params() == 3_881_517_056
    assert "3,881,517,056" in cfgj["arithmetic"]["weights"]
    assert stack_runs(cfg) == [(False, True, 1), (True, False, 3),
                               (True, True, 1)]
    assert cfg.experts_held == (0, 16) and cfg.num_index_layers == 2
    per_token = 640 * 2 * cfg.num_layers + cfg.index_head_dim * 2 * 2
    assert per_token == 6912
    assert dep["num_pages"] * dep["page_size"] * per_token == 3_623_878_656
    # the published widths, untouched
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.head_dim,
            cfg.v_head_dim, cfg.moe_intermediate_size, cfg.index_topk,
            cfg.n_routed_experts_published, cfg.num_experts_per_tok) == (
        6144, 64, 2048, 256, 256, 2048, 2048, 256, 8)
    for key in ("reduced_why", "stands_for", "arithmetic", "assumed"):
        assert cfgj[key]
    assert set(cfgj["reduced"]) == set(cfgj["reduced_why"])


def test_configuration_file_keeps_the_catalogs_numbers():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    cfgj = _config()
    assert cfgj["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfgj["reduced"]:
            assert cfgj[key] == value, key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "glm-5.2")
    assert entry["reduced"] == cfgj["reduced"]
    assert entry["source"] == cfgj["source"]


def test_the_traffic_fits_the_deployment():
    from benchmark.generators.closed_loop import ClosedLoop

    cell = run.load_cell(CELL)
    tp, dep = cell["traffic"]["params"], cell["config"][
        "serve_sparse_latent_moe"]
    gen = ClosedLoop(tp, 1, vocab_size=cell["config"]["vocab_size"],
                     max_seq_len=dep["max_seq_len"])
    lengths = [gen.lengths(c, 0) for c in range(tp["clients"])]
    assert tp["clients"] == dep["max_batch"] == 16
    assert all(12288 <= p <= 40960 and a >= 6144 for p, a in lengths)
    assert 350_000 < sum(p for p, _ in lengths) < 450_000
    pages = sum(-(-(p + a) // dep["page_size"]) for p, a in lengths)
    assert pages <= dep["num_pages"]
    assert "first_round_answer" not in tp
    ids = gen.start()[0]["prompt"]
    assert ids.max() < cell["config"]["vocab_size"] == 19360
    names = {m["name"] for m in cell["per_layer"]}
    assert {"dsa_index_roofline", "sparse_mla_attn_roofline",
            "dsa_selected_key_share", "moe_grouped_mm_roofline"} <= names
    # a decode-only window admits nothing and ends no prefill: these two
    # histograms see nothing there, and the dense kernel never runs
    assert not names & {"queue_wait_mean_ms", "prefill_mean_ms",
                        "mla_attn_roofline", "mla_attn_busy_share"}


# ---- what the kernels need -------------------------------------------------

def test_index_scores_needs_on_hand_made_lanes():
    need = lambda lanes: dsa_index_scores.needs(  # noqa: E731
        lanes, heads=2, dim=4, key_bytes=2, q_bytes=2)
    # one decode row over 10 keys: 10 scored in 2 heads, 4 + 1 multiply-adds
    # each; the 10 keys read once, the row's 2 x 4 query values and 2 weights
    assert need([(1, 10)]) == (2 * 2 * 5 * 10, 10 * 4 * 2 + 2 * (4 * 2 + 4))
    # a 3-row chunk after 4 cached: rows score 5, 6 and 7 keys
    ops, nbytes = need([(3, 7)])
    assert ops == 2 * 2 * 5 * 18 and nbytes == 7 * 8 + 3 * 2 * 12
    assert need([(0, 9)]) == (0, 0)
    assert need([(1, 10), (3, 7)])[0] == need([(1, 10)])[0] + ops


def test_selection_needs_on_hand_made_lanes():
    # a row that sees 10 scores keeps 4: 10 read, 4 written, 4 bytes each
    assert dsa_topk_select.needs([(1, 10)], topk=4) == (10, 4 * 14)
    # rows that see 2 and 3 keep them all
    assert dsa_topk_select.needs([(2, 3)], topk=4) == (5, 4 * 10)
    assert dsa_topk_select.needs([(0, 9)], topk=4) == (0, 0)


def test_sparse_attention_needs_on_hand_made_lanes():
    need = lambda lanes: sparse_mla_paged_attention.needs(  # noqa: E731
        lanes, topk=4, num_heads=2, row=6, value=4, kv_bytes=2, q_bytes=2,
        out_bytes=2)
    # one decode row over 10 cached rows reads its 4 selected, in 2 heads
    assert need([(1, 10)]) == (2 * 10 * 2 * 4, 4 * 6 * 2 + 2 * (6 + 4) * 2)
    # a 3-row chunk after 1 cached: rows read 2, 3 and 4 (all they see, then
    # the selection's size), each row its own rows
    ops, nbytes = need([(3, 4)])
    assert ops == 2 * 10 * 2 * 9 and nbytes == 9 * 12 + 3 * 2 * 20
    # never more than the dense kernel's need of the same lanes' operations
    from benchmark.kernels import mla_paged_attention
    dense = mla_paged_attention.needs([(1, 10)], num_heads=2, row=6, value=4,
                                      kv_bytes=2, q_bytes=2, out_bytes=2)
    assert need([(1, 10)])[0] < dense[0]


# ---- the readers on a hand-built trace --------------------------------------

def _path(*scopes):
    return "jit(step)/" + "/".join(scopes) + "/dot_general:"


def sparse_step_events():
    """One layer with an indexer, 100 us: ``(name, start, duration, path)``."""
    body = ("layers", "while", "body")
    return [
        ("%fusion.1 = bf16[256,6144] fusion(", 0 * US, 5 * US,
         _path(*body, "ln")),
        ("%fusion.2 = bf16[256,32,128] fusion(", 5 * US, 5 * US,
         _path(*body, "attn", "attn_index")),
        ("%dsa_index_scores.1 = f32[640,49152] custom-call(", 10 * US,
         10 * US, _path(*body, "attn", "attn_index")),
        ("%dsa_topk_select.1 = bf16[640,49152] custom-call(", 20 * US,
         15 * US, _path(*body, "attn", "attn_select")),
        ("%fusion.3 = bf16[256,64,640] fusion(", 35 * US, 5 * US,
         _path(*body, "attn", "attn_absorb")),
        ("%sparse_mla_paged_attention.1 = bf16[40,1024,512] custom-call(",
         40 * US, 20 * US, _path(*body, "attn")),
        ("%grouped_matmul.1 = f32[2048,4096] custom-call(", 60 * US, 30 * US,
         _path(*body, "mlp", "moe_experts")),
        ("%fusion.4 = bf16[256,6144] fusion(", 90 * US, 10 * US,
         _path(*body, "mlp")),
    ]


def test_the_indexers_scopes_are_charged_to_themselves():
    from benchmark import scope_trace
    from benchmark.layer_metrics import _subscopes

    events = sparse_step_events()
    mine = _dsa.charge(events)
    assert mine["attn_index"] == pytest.approx(15 * US)
    assert mine["attn_select"] == pytest.approx(15 * US)
    assert mine["attn_absorb"] == pytest.approx(5 * US)
    assert mine["attn"] == pytest.approx(20 * US)
    assert mine["moe_experts"] == pytest.approx(30 * US)
    # the accepted readers know neither and charge both to "attn"
    assert _subscopes.charge(events)["attn"] == pytest.approx(50 * US)
    older = scope_trace.charge(events)
    assert scope_trace.seconds(older, "attn")[0] == pytest.approx(55 * US)
    assert scope_trace.seconds(older, scope_trace.UNSCOPED)[0] == 0
    assert _dsa.scope_of(_path("layers", "attn", "attn_select")) \
        == "attn_select"


def _traced_run():
    dev = {"busy_s": 100 * US, "window": (0.0, 100 * US), "ops": {
        "dsa_index_scores": {"seconds": 10 * US, "calls": 1},
        "dsa_topk_select": {"seconds": 15 * US, "calls": 1},
        "sparse_mla_paged_attention": {"seconds": 20 * US, "calls": 1},
        "grouped_matmul": {"seconds": 30 * US, "calls": 2},
        "fusion": {"seconds": 25 * US, "calls": 4}}}
    return {
        "trace": {"devices": [dev]}, "peak": PEAK, "chips": 1,
        "clock": {"trace_t0": None},
        "serve": {"steps": [(0.0, 2, [(1, 30000), (1, 1000)])],
                  "heads": 64, "kv_bytes": 2, "latent_row": 576,
                  "latent_value": 512, "index_heads": 32, "index_dim": 128,
                  "index_topk": 2048, "index_layers": 2},
        "counters": {"serving_steps": 1,
                     "serving_dsa_keys_selected": 5 * (2048 + 1000),
                     "serving_dsa_keys_context": 5 * 31000,
                     "serving_dsa_keys_scored": 2 * 31000},
        "dsa_subscope_table": _dsa.charge(sparse_step_events()),
    }


def _least(needs):
    ops, nbytes = needs
    return max(ops / PEAK["bf16_flops_per_s"],
               nbytes / PEAK["hbm_bytes_per_s"])


def test_new_readers_on_a_hand_built_run():
    record = _traced_run()
    read = lambda name: run.reader_for("layer_metrics", name).read(record)  # noqa: E731
    lanes = [(1, 30000), (1, 1000)]
    assert read("dsa_index_busy_share") == pytest.approx(15.0)
    assert read("dsa_select_busy_share") == pytest.approx(15.0)
    assert read("sparse_mla_attn_busy_share") == pytest.approx(20.0)
    share, note = read("dsa_selected_key_share")
    assert share == pytest.approx(100 * 3048 / 31000)
    assert note["keys_scored"] == 62000

    share, note = read("dsa_index_roofline")
    assert share == pytest.approx(100 * _least(dsa_index_scores.needs(
        lanes, heads=32, dim=128, key_bytes=2, q_bytes=2)) / (10 * US))
    assert note["calls"] == 1 and note["bound_by"] == {"compute": 0,
                                                       "memory": 1}
    share, _ = read("dsa_select_roofline")
    assert share == pytest.approx(100 * _least(dsa_topk_select.needs(
        lanes, topk=2048)) / (15 * US))
    share, _ = read("sparse_mla_attn_roofline")
    assert share == pytest.approx(100 * _least(
        sparse_mla_paged_attention.needs(
            lanes, topk=2048, num_heads=64, row=576, value=512, kv_bytes=2,
            q_bytes=2, out_bytes=2)) / (20 * US))
    assert 0 < share < 100


@pytest.mark.parametrize("name", [
    "dsa_index_busy_share", "dsa_select_busy_share", "dsa_index_roofline",
    "dsa_select_roofline", "sparse_mla_attn_roofline",
    "sparse_mla_attn_busy_share", "dsa_selected_key_share"])
def test_a_program_without_an_indexer_gives_nothing_to_read(name):
    """The parent's program, or another cell's: no such kernel, scope or
    counter. The reader returns nothing and does not raise."""
    dev = {"busy_s": 1.0, "window": (0.0, 1.0), "ops": {
        "mla_ragged_paged_attention": {"seconds": 0.5, "calls": 4}}}
    record = {"trace": {"devices": [dev]}, "peak": PEAK, "chips": 1,
              "clock": {"trace_t0": None},
              "serve": {"steps": [(0.0, 1, [(1, 10)])], "heads": 16,
                        "latent_row": 576, "latent_value": 512,
                        "kv_bytes": 2, "lanes": 32},
              "counters": {"serving_steps": 3, "serving_rows_decode": 9},
              "dsa_subscope_table": None}
    assert run.reader_for("layer_metrics", name).read(record) is None
    assert run.reader_for("layer_metrics", name).read({"trace": None}) is None
