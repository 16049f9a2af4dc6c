"""Drives ``inference.ServingPredictor`` over a model with a latent (MLA)
cache and routed experts (``paddle_tpu/models/deepseek_v2.py``) for one
measured window.

The loop, the window and the fill are ``drivers/serve.py``'s (its ``Loop``
and ``schedule_digest``, imported). What differs is the set-up: the model is
made in its serving dtype on the device from the seed (no float32 model
exists), and the reference is ``reference/deepseek_v2.py``. Two requests are
served through the normal path, of ``CHECK_PROMPTS`` tokens (the second
crosses 36 pages and nine 256-row chunks, so the latent kernel reads many
pages and the prefill is chunked), and the logits row of the step that ended
each one's prefill and of a later decode step is held against the reference's
full forward.
"""
from __future__ import annotations

from .serve import Loop, schedule_digest

#: rms difference between the served step's logits and the float32
#: reference's, over the compared rows' whole vocabulary, as a share of the
#: reference logits' standard deviation: the GPT cells' limit, for the same
#: reason (bf16 rounds each layer's output to 8 mantissa bits, and the
#: differences ride the residual stream). One thing more moves a row here: a
#: bf16 activation can swap the 6th and 7th expert of a token whose scores
#: nearly tie, which exchanges one small-weight expert's output for
#: another's on that row. ``PERF.md`` gives what was measured on the chip and
#: the float32 and wrong-variant readings that bracket the limit.
LOGITS_TOL_RMS = 0.05

CHECK_PROMPTS = (200, 2304)  # tokens
CHECK_ANSWER = 6
CHECK_PAD = 2368             # the reference runs one padded shape


def model_config(cfgj, dep):
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config

    rs = cfgj.get("rope_scaling")
    return DeepseekV2Config(
        vocab_size=cfgj["vocab_size"], hidden_size=cfgj["hidden_size"],
        num_layers=cfgj["num_hidden_layers"],
        num_heads=cfgj["num_attention_heads"],
        max_seq_len=min(cfgj["max_position_embeddings"],
                        dep["max_seq_len"]),
        intermediate_size=cfgj["intermediate_size"],
        moe_intermediate_size=cfgj["moe_intermediate_size"],
        n_routed_experts=cfgj["n_routed_experts"],
        n_shared_experts=cfgj["n_shared_experts"],
        num_experts_per_tok=cfgj["num_experts_per_tok"],
        first_k_dense_replace=cfgj["first_k_dense_replace"],
        norm_topk_prob=cfgj["norm_topk_prob"],
        routed_scaling_factor=cfgj["routed_scaling_factor"],
        kv_lora_rank=cfgj["kv_lora_rank"],
        qk_nope_head_dim=cfgj["qk_nope_head_dim"],
        qk_rope_head_dim=cfgj["qk_rope_head_dim"],
        v_head_dim=cfgj["v_head_dim"], rms_norm_eps=cfgj["rms_norm_eps"],
        rope_theta=cfgj["rope_theta"],
        rope_scaling=({k: v for k, v in rs.items() if k != "type"}
                      if rs else None))


def _check_against_reference(sp, cfgj, seed):
    """As ``drivers/serve.py``'s check: the logits row of a lane at a step
    is the next-token distribution after the tokens written so far, which the
    reference computes by one full forward."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..reference import deepseek_v2 as reference
    from ._program import abstract

    rng = np.random.default_rng([seed, 999_983])
    step_fn, captured, signature = sp._unified, [], []

    def tapped(*args):
        if not signature:
            signature.append(abstract(args))
        res = step_fn(*args)
        captured.append(res[1])  # [lanes, vocabulary] logits, on the device
        return res

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped
    reqs = [sp.add_request(rng.integers(0, cfgj["vocab_size"], n).tolist(),
                           max_new_tokens=CHECK_ANSWER)
            for n in CHECK_PROMPTS]
    seen = []  # (logits, {req_id: (slot, tokens written)})
    try:
        while sp.has_work():
            n0 = len(captured)
            sp.step()
            if len(captured) > n0:
                seen.append((captured[-1], {
                    r.req_id: (slot, sp.cache.seq_len(slot))
                    for slot, r in sp.running.items()}))
        sp.flush()
    finally:
        sp._unified = step_fn

    errs = []
    for req in reqs:
        context = req.prompt_ids + req.output_ids
        mine = [(lg, at[req.req_id]) for lg, at in seen if req.req_id in at]
        prefill_end = next(m for m in mine
                           if m[1][1] == len(req.prompt_ids))
        decode = max((m for m in mine if m[1][1] < len(context)),
                     key=lambda m: m[1][1])
        for lg, (slot, written) in (prefill_end, decode):
            ids = np.zeros((CHECK_PAD,), np.int32)
            ids[:written] = context[:written]
            with jax.enable_x64(False):
                want = np.asarray(reference.logits_at(
                    sp.params, jnp.asarray(ids), written - 1, cfgj),
                    np.float32)
            got = np.asarray(lg[slot], np.float32)
            errs.append(float(np.sqrt(np.mean((got - want) ** 2))
                              / want.std()))
    del seen, captured
    finished = all(len(r.output_ids) == CHECK_ANSWER for r in reqs)
    return {"rms_share_of_std": errs, "tolerance": LOGITS_TOL_RMS,
            "prompts": list(CHECK_PROMPTS),
            "ok": finished and len(errs) == 2 * len(reqs)
            and all(e <= LOGITS_TOL_RMS for e in errs)}, signature[0]


def run(ctx):
    import jax.numpy as jnp

    from paddle_tpu.inference import ServingPredictor
    from paddle_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM
    from paddle_tpu.ops.pallas.grouped_matmul import GROUPED_KERNEL_NAME
    from paddle_tpu.ops.pallas.mla_paged_attention import MLA_KERNEL_NAME
    from paddle_tpu.ops.pallas.paged_write import KV_WRITE_KERNEL_NAME

    from ._program import mosaic_calls, program_bytes

    cfgj, tp = ctx.config, ctx.traffic["params"]
    dep = cfgj[ctx.traffic["driver"]]
    cfg = model_config(cfgj, dep)
    dtype = jnp.dtype(cfgj["dtype"])
    model = DeepseekV2ForCausalLM(
        cfg, seed=(ctx.seed ^ (ctx.seed >> 31)) & 0x7FFFFFFF, dtype=dtype)
    ctx.mark("model")
    sp = ServingPredictor(
        model, max_batch=dep["max_batch"], max_seq_len=dep["max_seq_len"],
        page_size=dep["page_size"], num_pages=dep["num_pages"],
        token_budget=dep["token_budget"], chunk=dep["chunk"])
    del model  # the predictor holds the same tree
    ctx.mark("predictor")

    check, signature = _check_against_reference(sp, cfgj, ctx.seed)
    ctx.mark("reference_check")

    compiled = sp._unified.lower(*signature).compile()
    kernels = (MLA_KERNEL_NAME, GROUPED_KERNEL_NAME, KV_WRITE_KERNEL_NAME)
    calls = mosaic_calls(compiled, kernels)
    hbm = program_bytes(compiled)
    del compiled
    ctx.mark("program_check")

    gen = ctx.generator.build(tp, ctx.seed, vocab_size=cfgj["vocab_size"],
                              max_seq_len=dep["max_seq_len"])
    loop = Loop(sp, gen, ctx.clock, ctx.span, observe=bool(ctx.capture))
    loop.submit(gen.start(), ctx.clock())
    while len(loop.finished) < int(tp["fill_requests"]):
        loop.step()
    fill_steps = loop.calls
    ctx.mark("fill")

    # ---- the window -------------------------------------------------------
    trace_from = ctx.seconds - min(float(tp["trace_seconds"]), ctx.seconds)
    before = sp.telemetry()
    traces_before = sp.decode_trace_count
    n_fill = (len(loop.deliveries), len(loop.finished), len(loop.steps))
    t_open = now = ctx.window_opens()
    paused = 0.0
    while now - t_open < ctx.seconds:
        if ctx.capture and not ctx.capture.started \
                and now - t_open >= trace_from:
            ctx.capture.start()
            paused += ctx.clock() - now
        now = loop.step()
    t_close = ctx.window_closes(now)
    after = sp.telemetry()
    sp.flush()
    health = sp.healthz()

    finished = loop.finished[n_fill[1]:]
    failed = int(health["requests_failed"])
    counters = {k: after[k] - before.get(k, 0.0) for k in after
                if isinstance(after[k], (int, float))}
    info = {
        "reference_check": check,
        "mosaic_calls": calls,
        "step_traces": sp.decode_trace_count,
        "fill_steps": fill_steps,
        "step_calls_in_window": loop.calls - fill_steps,
        "deliveries_in_window": len(loop.deliveries) - n_fill[0],
        "finished_in_window": len(finished),
        "schedule_digest": schedule_digest(loop, fill_steps + 100),
        "overruns": loop.overruns,
        "counters": {k: v for k, v in sorted(counters.items())
                     if v and k.startswith("serving_") and "{" not in k
                     and "_ms_" not in k},
    }
    return {
        "correct": bool(check["ok"] and all(calls[k] >= 1 for k in kernels)
                        and failed == 0 and loop.overruns == 0
                        and sp.decode_trace_count == traces_before == 1),
        "attempted": len(finished) + failed,
        "failed": failed,
        "clock": {"t_open": t_open, "t_close": t_close,
                  "window_s": t_close - t_open, "paused_s": paused},
        "serve": {
            "deliveries": loop.deliveries, "requests": loop.requests,
            "finished": finished, "steps": loop.steps[n_fill[2]:],
            "lanes": dep["max_batch"], "token_budget": dep["token_budget"],
            "layers": cfg.num_layers, "heads": cfg.num_heads,
            "head_dim": cfg.head_dim, "kv_bytes": dtype.itemsize,
            # what the latent kernel and the expert GEMMs work on
            "latent_row": cfg.latent_dim, "latent_value": cfg.kv_lora_rank,
            "moe_layers": cfg.num_moe_layers, "hidden": cfg.hidden_size,
            "expert_width": cfg.moe_intermediate_size,
            "experts": cfg.n_routed_experts,
        },
        "counters": counters,
        "program_bytes": hbm,
        "info": info,
        "compared": {"logits_rms_share_of_std_max": {
            "value": max(check["rms_share_of_std"], default=None),
            "limit": LOGITS_TOL_RMS}},
    }
