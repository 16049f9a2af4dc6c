"""Drives ``models/gpt_spmd.build_spmd_train_step`` for one measured window.

Set-up: build the step over ``make_mesh(chips)``, make the weights on the
device from the seed in one jitted call, make the feed, check the first
warm-up step's loss against the plain reference on one sequence, take the
warm-up steps (which compile), check that the loss falls and that the flash
kernels are Mosaic calls of the compiled program. Then the window: steps
dispatched one ahead, each closed by ``block_until_ready`` on its loss.
"""
from __future__ import annotations

import math

#: the step's loss (bf16 weights and activations, fp32 loss math) against
#: the float32 reference on the same bf16 weights: activations round to 8
#: mantissa bits through every layer, but the loss is a mean over 2047
#: positions of a log-sum-exp near ln(vocabulary), so the roundings average
#: out. Measured on the v5e: 1e-6 to 1e-4 relative. A wrong mask, shift or
#: layer order moves a random-weight loss by far more than half a percent.
LOSS_TOL_REL = 0.005


def model_config(cfgj, job):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfgj["assumed"]["padded_vocab_size"],
        hidden_size=cfgj["n_embd"], num_layers=cfgj["n_layer"],
        num_heads=cfgj["n_head"], intermediate_size=cfgj["n_inner"],
        max_seq_len=job["seq_len"],
        layer_norm_eps=cfgj["layer_norm_epsilon"],
        initializer_range=cfgj["initializer_range"],
        recompute=job["recompute"],
        use_flash_attention=job["flash_attention"])


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu  # noqa: F401  framework configuration
    from paddle_tpu.models import gpt_spmd
    from paddle_tpu.ops.pallas.flash_attention import (BWD_KERNEL_NAME,
                                                       FWD_KERNEL_NAME)

    from ..reference import gpt as reference
    from ._program import abstract, mosaic_calls, program_bytes

    cfgj, job, tp = ctx.config, ctx.config["train"], ctx.traffic["params"]
    cfg = model_config(cfgj, job)
    batch, seq = job["batch_size"], job["seq_len"]
    dtype = jnp.dtype(job["state_dtype"])
    mesh = gpt_spmd.make_mesh(ctx.chips)
    step, params, mom, _ = gpt_spmd.build_spmd_train_step(
        cfg, mesh, batch_size=batch, seq_len=seq,
        num_micro=job["num_micro"], dtype=dtype)
    ctx.mark("built")

    # weights from the seed, on the device, in one call, in place of the
    # builder's seed-0 ones
    shardings = gpt_spmd.param_shardings(mesh, params)
    with jax.enable_x64(False):
        params = jax.jit(
            lambda s: gpt_spmd.init_params(cfg, mesh, s, dtype),
            out_shardings=shardings)(jnp.uint32(ctx.seed & 0xFFFFFFFF))
    data = NamedSharding(mesh, P("dp", None))
    ids = ctx.generator.build(
        tp, ctx.seed, vocab_size=cfgj["vocab_size"], batch_size=batch,
        seq_len=seq, sharding=NamedSharding(mesh, P(None, "dp", None)))
    feed = [jax.device_put(ids[i], data) for i in range(ids.shape[0])]
    warm, feed = feed[0], feed[1:]
    ctx.mark("weights_and_feed")

    # the plain reference's loss on the warm-up batch's one sequence, from
    # the weights the first step will start from (the step donates them)
    with jax.enable_x64(False):
        want = float(jax.jit(
            lambda p, rows: reference.loss(
                reference.from_stages(p), rows, rows,
                num_heads=cfg.num_heads, eps=cfg.layer_norm_eps))(
            params, warm[:1]))
    ctx.mark("reference")

    signature = abstract((params, mom, warm, warm))
    losses = []
    for _ in range(int(tp["warmup_steps"])):
        params, mom, loss = step(params, mom, warm, warm)
        losses.append(float(loss))
    ctx.mark("warmup")
    rel = abs(losses[0] - want) / abs(want)
    checks = {
        "first_loss": losses[0], "reference_loss": want,
        "first_loss_rel_err": rel, "tolerance": LOSS_TOL_REL,
        "warmup_losses": losses,
        "loss_matches_reference": rel <= LOSS_TOL_REL,
        "loss_falls": all(math.isfinite(x) for x in losses)
        and losses[-1] < losses[0],
    }

    with jax.set_mesh(mesh):
        compiled = step.lower(*signature).compile()
    calls = mosaic_calls(compiled, (FWD_KERNEL_NAME, BWD_KERNEL_NAME))
    checks["mosaic_calls"] = calls
    checks["kernels_are_mosaic_calls"] = all(calls.values())
    hbm = program_bytes(compiled)
    del compiled
    ctx.mark("program_check")

    # ---- the window -------------------------------------------------------
    clock = ctx.clock
    trace_from = ctx.seconds - min(float(tp["trace_seconds"]), ctx.seconds)
    loss.block_until_ready()
    t_open = ctx.window_opens()
    started, pending, last, paused = 0, None, loss, 0.0
    while True:
        now = clock()
        if now - t_open >= ctx.seconds:
            break
        if ctx.capture and not ctx.capture.started \
                and now - t_open >= trace_from:
            if pending is not None:
                pending.block_until_ready()
            ctx.capture.start()
            paused += clock() - now
        with ctx.span("bench.step"):
            x = feed[started % len(feed)]
            params, mom, last = step(params, mom, x, x)
        started += 1
        if pending is not None:
            pending.block_until_ready()
        pending = last
    last.block_until_ready()
    t_close = ctx.window_closes()
    final = float(last)
    checks["final_loss_finite"] = math.isfinite(final)
    checks["final_loss"] = final

    tokens_per_step = batch * seq
    n_params = cfg.num_params()
    return {
        "correct": all(checks[k] for k in (
            "loss_matches_reference", "loss_falls",
            "kernels_are_mosaic_calls", "final_loss_finite")),
        "attempted": started,
        "failed": 0 if math.isfinite(final) else started,
        "clock": {"t_open": t_open, "t_close": t_close,
                  "window_s": t_close - t_open, "paused_s": paused},
        "train": {
            "steps": started, "tokens_per_step": tokens_per_step,
            "batch": batch, "seq": seq, "chips": ctx.chips,
            "num_micro": job["num_micro"], "mesh": dict(mesh.shape),
            "params": n_params,
            # forward and backward matrix products: 6 per parameter per
            # token, and causal attention's 6 * layers * hidden * seq
            # (bench.py's arithmetic); recomputed operations not counted
            "model_ops_per_token": 6 * n_params
            + 6 * cfg.num_layers * cfg.hidden_size * seq,
            "heads": cfg.num_heads, "head_dim": cfg.head_dim,
            "layers": cfg.num_layers,
            "elem_bytes": dtype.itemsize,
        },
        "program_bytes": hbm,
        "info": checks,
        "compared": {"first_loss_rel_err": {"value": rel,
                                            "limit": LOSS_TOL_REL}},
    }
