"""CPU checks of what PR 29 adds to the benchmark: the reader of the
scheduler's count of ``ragged_paged_attention``'s grid steps on a hand-made
run, and the counters themselves on a tiny predictor whose lanes' contexts
are known. No test starts a chip run."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.drivers import serve_latent_moe  # noqa: E402
from benchmark.tests.test_latent_moe import TINY  # noqa: E402

NAME = "ragged_attn_live_block_share"


def test_live_block_share_on_a_hand_made_run():
    read = run.reader_for("layer_metrics", NAME).read
    share, note = read({"counters": {
        "serving_attn_blocks_live": 270, "serving_attn_blocks_grid": 960,
        "serving_steps": 10}})
    assert share == pytest.approx(28.125)
    assert note == {"live": 270, "grid": 960, "grid_steps_per_call": 96.0}
    # a window in which every launched grid step held keys, and an idle one
    assert read({"counters": {"serving_attn_blocks_live": 96,
                              "serving_attn_blocks_grid": 96}})[0] == 100.0
    assert read({"counters": {"serving_attn_blocks_live": 0,
                              "serving_attn_blocks_grid": 96}})[0] == 0.0


@pytest.mark.parametrize("record", [
    {"counters": {"serving_steps": 3, "serving_rows_decode": 9,
                  "serving_moe_rows_routed": 64}},   # the latent cell
    {"counters": {}}, {"counters": None}, {"trace": None}],
    ids=["latent-cell", "no-counters", "counters-none", "no-run"])
def test_live_block_share_reads_nothing_where_the_counters_are_absent(record):
    """The latent cell's program, or the parent's: no such counter. The
    reader returns nothing and does not raise."""
    assert run.reader_for("layer_metrics", NAME).read(record) is None


def test_scheduler_counts_the_kernels_grid_steps_of_known_lanes():
    """Two lanes of known lengths through a CPU predictor: every dispatched
    step adds the grid the kernel module gives for the deployment, and each
    scheduled lane ceil(context / keys a grid step) live steps of it."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingPredictor
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.ops.pallas.paged_attention import ragged_grid

    paddle.seed(1)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64)
    model = GPTForCausalLM(cfg)
    model.eval()
    sp = ServingPredictor(model, dtype=jnp.float32, max_batch=2,
                          max_seq_len=64, page_size=4, num_pages=32,
                          token_budget=16, chunk=8, async_engine=False)
    grid = ragged_grid(2, 16, 8, 2, 2, 4, cfg.head_dim, jnp.float32,
                       jnp.float32)
    # 4 pages of 4 keys a grid step, 16 page slots: four key blocks a lane
    assert (grid.keys, grid.groups, grid.blocks) == (16, 1, 4)
    sp.add_request(list(range(1, 41)), max_new_tokens=3)   # 40 tokens
    sp.add_request(list(range(1, 6)), max_new_tokens=3)    # 5 tokens
    while sp.has_work():
        sp.step()
    t = sp.telemetry()
    # lane A feeds 8 rows a step: contexts 8..40 (16 -> one block, 24 -> two,
    # 40 -> three), then decodes at 41 and 42; lane B: 5, then 6 and 7
    contexts = [8, 16, 24, 32, 40, 41, 42] + [5, 6, 7]
    assert t["serving_rows_prefill"] + t["serving_rows_decode"] == 40 + 5 + 4
    live = sum(grid.live_steps(n) for n in contexts)
    assert t["serving_attn_blocks_live"] == live == 18
    # a call launches the live key blocks and one grid step for a lane that
    # is idle in that step: two lanes a step, ten of them scheduled
    assert t["serving_attn_blocks_grid"] == live + (
        2 * t["serving_steps"] - len(contexts))
    assert grid.steps([24, 7]) == 3 and grid.steps([40]) == 4


def test_a_latent_predictor_has_neither_counter():
    import jax.numpy as jnp

    from paddle_tpu.inference import ServingPredictor
    from paddle_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM

    dep = TINY["serve_latent_moe"]
    cfg = serve_latent_moe.model_config(TINY, dep)
    model = DeepseekV2ForCausalLM(cfg, seed=1, dtype=jnp.float32)
    sp = ServingPredictor(model, max_batch=dep["max_batch"],
                          max_seq_len=dep["max_seq_len"],
                          page_size=dep["page_size"],
                          num_pages=dep["num_pages"],
                          token_budget=dep["token_budget"],
                          chunk=dep["chunk"])
    assert not [k for k in sp.telemetry() if k.startswith("serving_attn_")]
