"""Drives ``inference.ServingPredictor`` over SmallThinker
(``paddle_tpu/models/smallthinker.py``: grouped-query attention at 7 query
heads a key-value head whose layers are WINDOW layers and FULL layers, two
cache groups, and 64 routed ReGLU experts whose router reads the pre-attention
norm) for one measured window in which prompts arrive and are prefilled.

The loop, the window and the digest are ``drivers/serve.py``'s (its ``Loop``
and ``schedule_digest``); the tap of the step's logits, the statistic and its
limits are ``drivers/serve_window_moe.py``'s (``_Tap``, ``verdict``, imported:
a request's six compared rows held by their mean and their median). What
differs:

- the reference is ``reference/smallthinker.py``. Two requests are served
  through the normal path, of ``CHECK_PROMPTS`` tokens: the second crosses the
  window's edge in 256-row chunks, the window group releases pages under it,
  and its compared rows' window layers read through the lower edge of the
  mask. The logits rows of the step that ended each one's prefill and of every
  decode step after it (six rows a request) are held against ONE full forward
  of the reference per request, each row against the nearer of the
  reference's two answers where its router's nearest choice could fall
  either way (``_compare``);
- a LIVE lane is held to the same once the fill is done: of the lanes that
  decode, have rows enough left and fit the long check prompt's padded shape,
  the one with the longest context (past the window, with this traffic's
  schedule), tapped over ``LIVE_STEPS`` steps of all the lanes;
- the window is ``drivers/serve_latent_moe.py``'s: the fill serves the
  clients until ``fill_requests`` of their requests have finished, and
  prompts keep arriving and are prefilled inside the window, beside decode
  rows;
- what the window group holds when the window closes, beside what the same
  lanes would hold with no release, for ``kv_window_page_share``.
"""
from __future__ import annotations

from .serve import Loop, schedule_digest
from .serve_window_moe import (CHECK_ANSWER, LIVE_STEPS,  # noqa: F401
                               LOGITS_TOL_RMS, LOGITS_TOL_RMS_MEDIAN, _Tap,
                               verdict)

CHECK_PROMPTS = (200, 6400)  # tokens; the second past the window
CHECK_PADS = (256, 6464)     # the reference runs one padded shape a request


def model_config(cfgj, dep):
    from paddle_tpu.models.smallthinker import SmallThinkerConfig

    n = cfgj["num_hidden_layers"]
    return SmallThinkerConfig(
        vocab_size=cfgj["vocab_size"], hidden_size=cfgj["hidden_size"],
        num_layers=n, num_heads=cfgj["num_attention_heads"],
        num_kv_heads=cfgj["num_key_value_heads"], head_dim=cfgj["head_dim"],
        max_seq_len=min(cfgj["max_position_embeddings"],
                        dep["max_seq_len"]),
        moe_intermediate_size=cfgj["moe_ffn_hidden_size"],
        n_routed_experts=cfgj["moe_num_primary_experts"],
        num_experts_per_tok=cfgj["moe_num_active_primary_experts"],
        norm_topk_prob=cfgj["norm_topk_prob"],
        rms_norm_eps=cfgj["rms_norm_eps"], rope_theta=cfgj["rope_theta"],
        sliding_window=cfgj["sliding_window_size"],
        rope_layout=tuple(cfgj["rope_layout"][:n]),
        sliding_window_layout=tuple(cfgj["sliding_window_layout"][:n]),
        initializer_range=cfgj["assumed"]["initializer_range"])


def rms_shares(got, want, alt):
    """Per row, the rms difference of ``got`` from the reference's logits
    over their std: from ``want`` (the reference's routing) or from ``alt``
    (its routing with the one choice that lay nearest to falling otherwise
    taken the other way), whichever is nearer; and both."""
    import numpy as np

    got, want, alt = (np.asarray(a, np.float32) for a in (got, want, alt))
    std = want.std(-1)
    routed = np.sqrt(np.mean((got - want) ** 2, -1)) / std
    swapped = np.sqrt(np.mean((got - alt) ** 2, -1)) / std
    return np.minimum(routed, swapped), routed, swapped


def _compare(sp, cfgj, seen, requests):
    """``requests``: (request, the padded length its reference runs at). The
    logits row of a lane at a step is the next-token distribution after the
    tokens written so far; the reference computes all of a request's compared
    rows by one full forward over its prompt and answer, and for each row the
    answer its token gives with the router's nearest choice taken the other
    way (``reference.logits_at(alternative=True)``): under seeded weights the
    6th and 7th of 64 router outputs lie so near that bf16's rounding of a
    row's residual stream swaps them in some layer for about a third of the
    rows (41 of 126 compared rows of seven runs on the chip read 0.015-0.074
    where their neighbours read 0.006-0.011 against the reference's routing
    alone; ``PERF.md``, PR 40), and a request with four such rows of six
    would pass neither limit. A row is held to the nearer of the two.
    Returns per request its rows' tokens written, rms shares, both readings
    and the layer and margin of the nearest choice."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..reference import smallthinker as reference

    per_row = []
    for req, pad in requests:
        context = req.prompt_ids + req.output_ids
        rows = sorted(((out, at[req.req_id]) for out, at, _ in seen
                       if req.req_id in at
                       and at[req.req_id][1] <= min(len(context), pad)),
                      key=lambda m: m[1][1])[:CHECK_ANSWER]
        ids = np.zeros((pad,), np.int32)
        ids[:min(len(context), pad)] = context[:pad]
        entry = {"written": [at[1] for _, at in rows], "rms": [],
                 "rms_as_routed": [], "rms_swapped": [], "nearest": []}
        if rows:
            with jax.enable_x64(False):
                want, alt, ties = reference.logits_at(
                    sp.params, jnp.asarray(ids),
                    [written - 1 for _, (_, written) in rows], cfgj,
                    alternative=True)
            got = np.stack([np.asarray(lg[slot], np.float32)
                            for (lg, _), (slot, _) in rows])
            near, routed, swapped = rms_shares(got, want, alt)
            entry.update(rms=near.tolist(), rms_as_routed=routed.tolist(),
                         rms_swapped=swapped.tolist(), nearest=ties)
        per_row.append(entry)
    return per_row


def _check_against_reference(sp, cfgj, seed):
    """The check requests through the normal path, before anything else is
    served."""
    import numpy as np

    rng = np.random.default_rng([seed, 999_983])
    released0 = sp.telemetry().get("kv_window_pages_released", 0)
    with _Tap(sp) as tap:
        reqs = [sp.add_request(
            rng.integers(0, cfgj["vocab_size"], n).tolist(),
            max_new_tokens=CHECK_ANSWER) for n in CHECK_PROMPTS]
        watch = {r.req_id for r in reqs}
        while sp.has_work():
            sp.step()
            tap.note(watch)
        sp.flush()
    released = sp.telemetry().get("kv_window_pages_released", 0) - released0
    per_row = _compare(sp, cfgj, tap.seen, list(zip(reqs, CHECK_PADS)))
    finished = all(len(r.output_ids) == CHECK_ANSWER for r in reqs)
    return {"prompts": list(CHECK_PROMPTS), "rows": per_row,
            # the long prompt's prefill went past the window: the window
            # group released pages under it
            "window_pages_released": released,
            "served": finished and released > 0}, tap.signature


def _check_a_live_lane(sp, cfgj, loop):
    """``LIVE_STEPS`` more steps of the fill, tapped, and the rows of one of
    the lanes in them against the reference's forward over that lane's
    prompt and its answer so far: of the lanes that decode and have more
    than ``LIVE_STEPS`` tokens left to write, the one with the longest
    context that the long check prompt's padded shape holds."""
    room = CHECK_PADS[-1] - LIVE_STEPS
    fits = []
    for slot, r in sp.running.items():
        n, prompt = sp.cache.seq_len(slot), len(r.prompt_ids)
        if prompt <= n <= room and prompt + r.max_new_tokens - n > LIVE_STEPS:
            fits.append((n, slot, r))
    if not fits:
        return {"rows": [], "served": False}
    context, slot, req = max(fits, key=lambda m: m[:2])
    with _Tap(sp) as tap:
        for _ in range(LIVE_STEPS):
            loop.step()
            tap.note({req.req_id})
    per_row = _compare(sp, cfgj, tap.seen, [(req, CHECK_PADS[-1])])
    return {"rows": per_row, "lanes": len(sp.running), "context": context,
            "window_first_page": int(sp.cache.window.first[slot]),
            "served": slot in sp.running
            and sp.running[slot].req_id == req.req_id}


def run(ctx):
    import jax.numpy as jnp

    from paddle_tpu.inference import ServingPredictor
    from paddle_tpu.models.smallthinker import SmallThinkerForCausalLM
    from paddle_tpu.ops.pallas.grouped_matmul import GROUPED_KERNEL_NAME
    from paddle_tpu.ops.pallas.paged_attention import RAGGED_KERNEL_NAME
    from paddle_tpu.ops.pallas.paged_write import KV_WRITE_KERNEL_NAME

    from ._program import mosaic_calls, program_bytes

    cfgj, tp = ctx.config, ctx.traffic["params"]
    dep = cfgj[ctx.traffic["driver"]]
    cfg = model_config(cfgj, dep)
    dtype = jnp.dtype(cfgj["dtype"])
    model = SmallThinkerForCausalLM(
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
    kernels = (RAGGED_KERNEL_NAME, KV_WRITE_KERNEL_NAME, GROUPED_KERNEL_NAME)
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
    ctx.mark("fill")
    live = _check_a_live_lane(sp, cfgj, loop)
    fill_steps = loop.calls
    means, medians, close = verdict(
        [r["rms"] for r in check["rows"] + live["rows"]])
    check.update(
        live_lane=live, rms_share_of_std=means, tolerance=LOGITS_TOL_RMS,
        rms_share_of_std_row_median=medians,
        tolerance_median=LOGITS_TOL_RMS_MEDIAN,
        ok=bool(check["served"] and live["served"] and close
                and len(means) == len(CHECK_PROMPTS) + 1))
    ctx.mark("live_lane_check")

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
    # what the window group holds as the window closes, and what the same
    # lanes would hold had nothing been released (a page a 64 positions)
    contexts = [sp.cache.seq_len(slot) for slot in sp.running]
    pages_whole = sum(-(-n // dep["page_size"]) for n in contexts)
    sp.flush()
    health = sp.healthz()

    finished = loop.finished[n_fill[1]:]
    failed = int(health["requests_failed"])
    counters = {k: after[k] - before.get(k, 0.0) for k in after
                if isinstance(after[k], (int, float))}
    window_cache = {
        "pages_held": after.get("kv_window_pages_held"),
        "pages_unreleased": pages_whole,
        "full_pages_held": after.get("kv_full_pages_held"),
        "lanes_over_window": sum(n > cfg.sliding_window for n in contexts),
        "contexts": sorted(contexts)}
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
        "window_cache": window_cache,
        "counters": {k: v for k, v in sorted(counters.items())
                     if v and (k.startswith("serving_")
                               or k.startswith("kv_window"))
                     and "{" not in k and "_ms_" not in k},
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
            # what the attention kernel works on, by layer kind
            "kv_heads": cfg.num_kv_heads, "window": cfg.sliding_window,
            "window_layers": cfg.num_window_layers,
            "full_layers": cfg.num_layers - cfg.num_window_layers,
            # what the expert GEMMs work on
            "moe_layers": cfg.num_moe_layers, "hidden": cfg.hidden_size,
            "expert_width": cfg.moe_intermediate_size,
            "experts": cfg.n_routed_experts,
        },
        "counters": counters,
        "window_cache": window_cache,
        "program_bytes": hbm,
        "info": info,
        "compared": {
            "logits_rms_share_of_std_max": {
                "value": max(check["rms_share_of_std"], default=None),
                "limit": LOGITS_TOL_RMS},
            "logits_rms_share_of_std_row_median_max": {
                "value": max(check["rms_share_of_std_row_median"],
                             default=None),
                "limit": LOGITS_TOL_RMS_MEDIAN}},
    }
