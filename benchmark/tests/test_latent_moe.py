"""CPU checks of what the DeepSeek-V2-Lite cell adds to the benchmark: the
driver end to end at a tiny size against the new reference, the two kernels'
operation and byte counts on hand-made shapes, the new readers on a
hand-built trace. No test starts a chip run."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.kernels import grouped_matmul, mla_paged_attention  # noqa: E402
from benchmark.layer_metrics import _subscopes  # noqa: E402
from benchmark.tests.test_benchmark import FakeContext  # noqa: E402

US = 1e-6
PEAK = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}

TINY = {
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "intermediate_size": 96,
    "moe_intermediate_size": 48, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "max_position_embeddings": 4096,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
    "dtype": "float32",
    "serve_latent_moe": {"max_batch": 4, "max_seq_len": 128, "page_size": 8,
                         "num_pages": 64, "token_budget": 32, "chunk": 8},
}
TINY_LONGDOC = {
    "clients": 4, "schedule_seed": 7,
    "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.5,
               "min": 12, "max": 70},
    "answer": {"dist": "lognormal", "median": 8, "sigma": 0.4,
               "min": 4, "max": 16},
    "first_round_answer": {"dist": "uniform", "min": 2, "max": 8},
    "fill_requests": 4, "trace_seconds": 1,
}


# ---- the driver ------------------------------------------------------------

def test_driver_agrees_with_the_reference_at_a_tiny_size():
    from benchmark.drivers import serve_latent_moe as driver

    traffic = {"driver": "serve_latent_moe",
               "generator": "closed_loop_latent_moe", "params": TINY_LONGDOC}
    driver.CHECK_PROMPTS, driver.CHECK_PAD = (11, 45), 64
    out = driver.run(FakeContext(TINY, traffic, seconds=0.5))
    check = out["info"]["reference_check"]
    # float32 on both sides: a prefill in chunks (45 tokens, chunk 8), then
    # decode through the paged latent cache, against the full forward
    assert len(check["rms_share_of_std"]) == 4
    assert max(check["rms_share_of_std"]) < 1e-4 and check["ok"], check
    assert out["failed"] == 0 and out["info"]["step_traces"] == 1
    assert out["info"]["deliveries_in_window"] > 0
    # off the chip the kernels are their jnp references: no Mosaic call, so
    # the run is not "correct" here, and says why
    assert set(out["info"]["mosaic_calls"]) == {
        "mla_ragged_paged_attention", "grouped_matmul", "paged_kv_write"}
    assert not out["correct"]
    record = dict(out, chips=1, peak=PEAK)
    assert run.reader_for("end_to_end", "served_tok_s").read(record) > 0
    # the program's own counters reach the readers that need them
    rows = out["counters"]["serving_moe_rows_routed"]
    layers = out["counters"]["serving_steps"] * 2
    fed = (out["counters"]["serving_rows_prefill"]
           + out["counters"]["serving_rows_decode"])
    # top-2 in each of 2 routed layers; the expert counts are read with the
    # tokens, so each edge of the window may miss the steps then in flight
    assert abs(rows - 2 * 2 * fed) <= 2 * 2 * 32 * 4
    assert 0 < out["counters"]["serving_moe_experts_fed"] <= 8 * layers
    load, note = run.reader_for(
        "layer_metrics", "moe_expert_load_max_share").read(record)
    assert load >= 100.0 and note["rows_routed"] == rows


# ---- what the kernels need -------------------------------------------------

def test_latent_attention_needs_on_hand_made_lanes():
    need = lambda lanes: mla_paged_attention.needs(  # noqa: E731
        lanes, num_heads=2, row=6, value=4, kv_bytes=2, q_bytes=2,
        out_bytes=2)
    # one decode row over 10 cached rows: 10 scored, in 2 heads, 6 + 4
    # multiply-adds each; the 10 rows read once (not once a head)
    assert need([(1, 10)]) == (2 * 10 * 2 * 10, 10 * 6 * 2 + 2 * (6 + 4) * 2)
    # a 3-row chunk after 4 cached: rows score 5, 6 and 7
    ops, nbytes = need([(3, 7)])
    assert ops == 2 * 10 * 2 * (5 + 6 + 7)
    assert nbytes == 7 * 6 * 2 + 3 * 2 * (6 + 4) * 2
    # an idle lane costs nothing; lanes add
    assert need([(0, 9)]) == (0, 0)
    assert need([(1, 10), (3, 7)])[0] == need([(1, 10)])[0] + ops


def test_grouped_matmul_needs_on_hand_made_counts():
    ops, nbytes = grouped_matmul.needs(12, 3, hidden=8, width=4, w_bytes=2,
                                       x_bytes=2)
    # a routed row: gate, up and down products, 3 * 8 * 4 multiply-adds
    assert ops == 2 * 3 * 8 * 4 * 12
    # three experts' weights once each, the rows in and out at 8 wide
    assert nbytes == 3 * (3 * 8 * 4) * 2 + 2 * 12 * 8 * 2
    # an expert nobody chose costs nothing
    assert grouped_matmul.needs(12, 2, hidden=8, width=4, w_bytes=2,
                                x_bytes=2)[1] < nbytes


# ---- the readers on a hand-built trace --------------------------------------

def _path(*scopes):
    return "jit(step)/" + "/".join(scopes) + "/dot_general:"


def latent_step_events():
    """One layer of a step, 100 us: ``(name, start, duration, path)``."""
    return [
        ("%fusion.1 = bf16[64,2048] fusion(", 0 * US, 5 * US,
         _path("layers", "while", "body", "ln")),
        ("%fusion.2 = bf16[64,16,576] fusion(", 5 * US, 5 * US,
         _path("layers", "while", "body", "attn", "attn_absorb")),
        ("%mla_ragged_paged_attention.1 = bf16[8,256,512] custom-call(",
         10 * US, 20 * US, _path("layers", "while", "body", "attn")),
        ("%fusion.3 = f32[64,64] fusion(", 30 * US, 10 * US,
         _path("layers", "while", "body", "mlp", "moe_route")),
        ("%grouped_matmul.1 = f32[512,2816] custom-call(", 40 * US, 30 * US,
         _path("layers", "while", "body", "mlp", "moe_experts")),
        ("%grouped_matmul.2 = f32[512,2048] custom-call(", 70 * US, 10 * US,
         _path("layers", "while", "body", "mlp", "moe_experts")),
        ("%fusion.4 = bf16[64,2048] fusion(", 80 * US, 15 * US,
         _path("layers", "while", "body", "mlp", "moe_shared")),
        ("%fusion.5 = bf16[64,2048] fusion(", 95 * US, 5 * US,
         _path("layers", "while", "body", "mlp")),
    ]


def test_subscopes_are_charged_to_themselves_and_the_rest_as_before():
    from benchmark import scope_trace

    events = latent_step_events()
    mine = _subscopes.charge(events)
    assert mine["moe_experts"] == pytest.approx(40 * US)
    assert mine["moe_route"] == pytest.approx(10 * US)
    assert mine["moe_shared"] == pytest.approx(15 * US)
    assert mine["attn_absorb"] == pytest.approx(5 * US)
    assert mine["attn"] == pytest.approx(20 * US)
    assert mine["mlp"] == pytest.approx(5 * US)
    # the accepted reader knows none of the four and charges each to the
    # part around it: nothing falls to the scan's carry or out of scope
    older = scope_trace.charge(events)
    by = lambda s: scope_trace.seconds(older, s)[0]  # noqa: E731
    assert by("mlp") == pytest.approx(70 * US)
    assert by("attn") == pytest.approx(25 * US)
    assert by(scope_trace.CARRY) == 0 and by(scope_trace.UNSCOPED) == 0
    # a path with no sub-scope reads as the accepted reader reads it
    assert _subscopes.scope_of(_path("layers", "while", "body", "qkv")) == \
        "qkv"
    assert _subscopes.scope_of("jit(step)/convert_element_type:") is None


def _traced_run():
    dev = {"busy_s": 100 * US, "window": (0.0, 100 * US), "ops": {
        "mla_ragged_paged_attention": {"seconds": 20 * US, "calls": 1},
        "grouped_matmul": {"seconds": 40 * US, "calls": 2},
        "fusion": {"seconds": 40 * US, "calls": 5}}}
    return {
        "trace": {"devices": [dev]}, "peak": PEAK, "chips": 1,
        "clock": {"trace_t0": None},
        "serve": {"steps": [(0.0, 2, [(1, 4096), (256, 1024)])],
                  "heads": 16, "kv_bytes": 2, "latent_row": 576,
                  "latent_value": 512, "moe_layers": 1, "hidden": 2048,
                  "expert_width": 1408, "experts": 64},
        "counters": {"serving_steps": 1, "serving_moe_rows_routed": 257 * 6,
                     "serving_moe_experts_fed": 64,
                     "serving_moe_expert_rows{expert=0}": 48,
                     "serving_moe_expert_rows{expert=5}": 16},
        "subscope_table": _subscopes.charge(latent_step_events()),
    }


def test_new_readers_on_a_hand_built_run():
    record = _traced_run()
    read = lambda name: run.reader_for("layer_metrics", name).read(record)  # noqa: E731
    assert read("mla_attn_busy_share") == pytest.approx(20.0)
    assert read("moe_experts_busy_share") == pytest.approx(40.0)
    assert read("moe_route_busy_share") == pytest.approx(10.0)
    # two experts got 48 and 16 of 64 rows over 64 experts: the fullest has
    # 48 times the mean of one
    assert read("moe_expert_load_max_share")[0] == pytest.approx(4800.0)

    share, note = read("mla_attn_roofline")
    ops, nbytes = mla_paged_attention.needs(
        [(1, 4096), (256, 1024)], num_heads=16, row=576, value=512,
        kv_bytes=2, q_bytes=2, out_bytes=2)
    least = max(ops / PEAK["bf16_flops_per_s"],
                nbytes / PEAK["hbm_bytes_per_s"])
    assert share == pytest.approx(100 * least / (20 * US))
    assert note["calls"] == 1 and sum(note["bound_by"].values()) == 1

    share, note = read("moe_grouped_mm_roofline")
    ops, nbytes = grouped_matmul.needs(257 * 6, 64, hidden=2048, width=1408,
                                       w_bytes=2, x_bytes=2)
    least = max(ops / PEAK["bf16_flops_per_s"],
                nbytes / PEAK["hbm_bytes_per_s"])
    # one layer in the window, one pair of calls in the capture
    assert share == pytest.approx(100 * least / (40 * US))
    assert note["bound_by"] == "memory"


@pytest.mark.parametrize("name", [
    "mla_attn_busy_share", "mla_attn_roofline", "moe_experts_busy_share",
    "moe_grouped_mm_roofline", "moe_route_busy_share",
    "moe_expert_load_max_share"])
def test_a_program_without_the_new_parts_gives_nothing_to_read(name):
    """The parent's program, or a GPT cell's: no such kernel, scope or
    counter. The reader returns nothing and does not raise."""
    dev = {"busy_s": 1.0, "window": (0.0, 1.0),
           "ops": {"ragged_paged_attention": {"seconds": 0.5, "calls": 4}}}
    record = {"trace": {"devices": [dev]}, "peak": PEAK, "chips": 1,
              "clock": {"trace_t0": None},
              "serve": {"steps": [(0.0, 1, [(1, 10)])], "heads": 12,
                        "head_dim": 128, "kv_bytes": 2, "lanes": 24},
              "counters": {"serving_steps": 3, "serving_rows_decode": 9},
              "subscope_table": None}
    assert run.reader_for("layer_metrics", name).read(record) is None
    assert run.reader_for("layer_metrics", name).read({"trace": None}) is None
