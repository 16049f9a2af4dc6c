"""Drives ``inference.ServingPredictor`` over a LOOPED model (Ouro,
``paddle_tpu/models/ouro.py``: one stack of sandwich-normed layers run
``total_ut_steps`` times a token, each (loop, layer) with its own plane of the
paged cache) for one measured window of reasoning traffic: short prompts,
long answers, prompts arriving and prefilled inside the window.

The loop, the window and the digest are ``drivers/serve.py``'s (its ``Loop``
and ``schedule_digest``). What differs:

- the reference is ``reference/ouro.py``. Two requests are served through the
  normal path, of ``CHECK_PROMPTS`` tokens (the second crosses 30 pages of 64
  and 8 chunks of 256). The logits rows of the step that ended each one's
  prefill and of every decode step after it (six rows a request) are held
  against ONE full forward of the reference per request, by their mean and
  by their median (:func:`verdict`);
- a LIVE lane is held to the same once the fill is done: of the lanes that
  decode and have rows enough left, the one with the longest context that the
  long check prompt's padded shape holds, tapped over ``LIVE_STEPS`` steps of
  all the lanes;
- the window is ``drivers/serve_latent_moe.py``'s: the fill serves the
  clients until ``fill_requests`` of their requests have finished, and prompts
  keep arriving and are prefilled inside the window, beside decode rows.
"""
from __future__ import annotations

from .serve import Loop, schedule_digest

#: MEAN over a request's six compared rows of the rms difference between the
#: served step's logits and the float32 reference's, as a share of the
#: reference logits' standard deviation. Set from two chip readings at the
#: cell's widths (``PERF.md``, PR 43): the served rows read at most 0.048
#: over thirteen seeds (bf16 through 48 (loop, layer) passes; the rows of one
#: request share most of their difference, so they read alike), and the
#: reference with every weight rounded to e4m3 reads 0.40; the wrong
#: references and the shared plane of ``tools/looped_controls.py`` read
#: 1.08-1.40. The limit sits between the first two with room on both sides
#: (2.5x above the served rows, 3.4x under e4m3).
LOGITS_TOL_RMS = 0.12

#: the same for the MEDIAN of a request's six rows: how the system computes,
#: apart from one row that lies far off (the same two readings: 0.048 and
#: 0.40; 2.1x above the first, 4x under the second)
LOGITS_TOL_RMS_MEDIAN = 0.10

CHECK_PROMPTS = (200, 1900)  # tokens; the second over 30 pages
CHECK_PADS = (256, 1920)     # the reference runs one padded shape a request
CHECK_ANSWER = 6
#: tapped steps of every lane once the fill is done
LIVE_STEPS = CHECK_ANSWER + 4


def verdict(rows_rms):
    """The comparison's statistic and what it decides. ``rows_rms``: per
    request, its compared rows' rms shares. Returns the requests' means,
    their medians, and whether every request has its six rows with both
    under their limits. ``tools/looped_controls.py`` holds its wrong
    references to this function."""
    import numpy as np

    means = [float(np.mean(r)) for r in rows_rms if len(r)]
    medians = [float(np.median(r)) for r in rows_rms if len(r)]
    ok = bool(rows_rms) and all(len(r) == CHECK_ANSWER for r in rows_rms) \
        and all(e <= LOGITS_TOL_RMS for e in means) \
        and all(e <= LOGITS_TOL_RMS_MEDIAN for e in medians)
    return means, medians, ok


def rms_shares(got, want):
    """Per row, the rms difference of ``got`` from the reference's logits
    ``want`` over their std."""
    import numpy as np

    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.sqrt(np.mean((got - want) ** 2, -1)) / want.std(-1)


def model_config(cfgj, dep):
    from paddle_tpu.models.ouro import OuroConfig

    return OuroConfig(
        vocab_size=cfgj["vocab_size"], hidden_size=cfgj["hidden_size"],
        num_layers=cfgj["num_hidden_layers"],
        num_heads=cfgj["num_attention_heads"],
        num_kv_heads=cfgj["num_key_value_heads"], head_dim=cfgj["head_dim"],
        intermediate_size=cfgj["intermediate_size"],
        max_seq_len=min(cfgj["max_position_embeddings"], dep["max_seq_len"]),
        rms_norm_eps=cfgj["rms_norm_eps"], rope_theta=cfgj["rope_theta"],
        total_ut_steps=cfgj["total_ut_steps"],
        early_exit_threshold=cfgj["early_exit_threshold"],
        initializer_range=cfgj["assumed"]["initializer_range"])


class _Tap:
    """The predictor's step with each call's logits kept, on the device,
    until :meth:`note` says whose they are."""

    def __init__(self, sp):
        self.sp, self.step_fn = sp, sp._unified
        self.signature = self.last = None
        self.seen = []      # (logits, {req_id: (slot, tokens written)})

    def __enter__(self):
        from ._program import abstract

        def tapped(*args):
            if self.signature is None:
                self.signature = abstract(args)
            res = self.step_fn(*args)
            self.last = res[1]          # [lanes, vocabulary] logits
            return res

        tapped.trace_count = self.step_fn.trace_count
        self.sp._unified = tapped
        return self

    def __exit__(self, *exc):
        self.sp._unified = self.step_fn

    def note(self, watch):
        """After a ``step()`` call: keep what it dispatched if a request of
        ``watch`` is past its prompt (the end of a prefill and the decode
        steps after it are what may be compared)."""
        at = {r.req_id: (slot, self.sp.cache.seq_len(slot))
              for slot, r in self.sp.running.items()
              if r.req_id in watch
              and self.sp.cache.seq_len(slot) >= len(r.prompt_ids)}
        if self.last is not None and at:
            self.seen.append((self.last, at))
        self.last = None


def compare(params, cfgj, seen, requests, variant=()):
    """``requests``: (request, the padded length its reference runs at). The
    logits row of a lane at a step is the next-token distribution after the
    tokens written so far; the reference computes all of a request's compared
    rows by one full forward over its prompt and answer. Returns per request
    its rows' tokens written and rms shares."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..reference import ouro as reference

    per_row = []
    for req, pad in requests:
        context = req.prompt_ids + req.output_ids
        rows = sorted(((lg, at[req.req_id]) for lg, at in seen
                       if req.req_id in at
                       and at[req.req_id][1] <= min(len(context), pad)),
                      key=lambda m: m[1][1])[:CHECK_ANSWER]
        entry = {"written": [at[1] for _, at in rows], "rms": []}
        if rows:
            ids = np.zeros((pad,), np.int32)
            ids[:min(len(context), pad)] = context[:pad]
            with jax.enable_x64(False):
                want = reference.logits_at(
                    params, jnp.asarray(ids),
                    [written - 1 for _, (_, written) in rows], cfgj,
                    variant=variant)
            got = np.stack([np.asarray(lg[slot], np.float32)
                            for lg, (slot, _) in rows])
            entry["rms"] = rms_shares(got, want).tolist()
        per_row.append(entry)
    return per_row


def check_against_reference(sp, cfgj, seed, prompts=None, pads=None):
    """The check requests through the normal path, before anything else is
    served (``prompts``, ``pads``: the check's own unless given): ``(per
    request its rows, whether all were served, the step's signature)``."""
    import numpy as np

    prompts, pads = prompts or CHECK_PROMPTS, pads or CHECK_PADS

    rng = np.random.default_rng([seed, 999_983])
    with _Tap(sp) as tap:
        reqs = [sp.add_request(
            rng.integers(0, cfgj["vocab_size"], n).tolist(),
            max_new_tokens=CHECK_ANSWER) for n in prompts]
        watch = {r.req_id for r in reqs}
        while sp.has_work():
            sp.step()
            tap.note(watch)
        sp.flush()
    per_row = compare(sp.params, cfgj, tap.seen,
                      list(zip(reqs, pads)))
    finished = all(len(r.output_ids) == CHECK_ANSWER for r in reqs)
    return per_row, finished, tap.signature


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
    per_row = compare(sp.params, cfgj, tap.seen, [(req, CHECK_PADS[-1])])
    return {"rows": per_row, "lanes": len(sp.running), "context": context,
            "served": slot in sp.running
            and sp.running[slot].req_id == req.req_id}


def build(cfgj, dep, seed, **kw):
    """The model and its predictor, as the cell deploys them (``kw``: the
    deployment's values to change)."""
    import jax.numpy as jnp

    from paddle_tpu.inference import ServingPredictor
    from paddle_tpu.models.ouro import OuroForCausalLM

    dep = dict(dep, **kw)
    model = OuroForCausalLM(model_config(cfgj, dep),
                            seed=(seed ^ (seed >> 31)) & 0x7FFFFFFF,
                            dtype=jnp.dtype(cfgj["dtype"]))
    return ServingPredictor(
        model, max_batch=dep["max_batch"], max_seq_len=dep["max_seq_len"],
        page_size=dep["page_size"], num_pages=dep["num_pages"],
        token_budget=dep["token_budget"], chunk=dep["chunk"])


def run(ctx):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import RAGGED_KERNEL_NAME
    from paddle_tpu.ops.pallas.paged_write import KV_WRITE_KERNEL_NAME

    from ._program import mosaic_calls, program_bytes

    cfgj, tp = ctx.config, ctx.traffic["params"]
    dep = cfgj[ctx.traffic["driver"]]
    cfg = model_config(cfgj, dep)
    dtype = jnp.dtype(cfgj["dtype"])
    sp = build(cfgj, dep, ctx.seed)
    ctx.mark("predictor")

    rows, served, signature = check_against_reference(sp, cfgj, ctx.seed)
    check = {"prompts": list(CHECK_PROMPTS), "rows": rows, "served": served}
    ctx.mark("reference_check")

    compiled = sp._unified.lower(*signature).compile()
    kernels = (RAGGED_KERNEL_NAME, KV_WRITE_KERNEL_NAME)
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
    contexts = sorted(sp.cache.seq_len(slot) for slot in sp.running)
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
        "pool_planes": after.get("kv_pool_planes"),
        "contexts_at_close": contexts,
        "counters": {k: v for k, v in sorted(counters.items())
                     if v and k.startswith("serving_") and "_ms_" not in k
                     and ("{" not in k or k.startswith("serving_loop"))},
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
            "layers": cfg.num_layers, "loops": cfg.total_ut_steps,
            "heads": cfg.num_heads, "head_dim": cfg.head_dim,
            "kv_bytes": dtype.itemsize, "kv_heads": cfg.num_kv_heads,
            # what the layers' matrices work on
            "hidden": cfg.hidden_size, "ffn": cfg.intermediate_size,
        },
        "counters": counters,
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
