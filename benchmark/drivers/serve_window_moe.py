"""Drives ``inference.ServingPredictor`` over a model with grouped-query
attention whose layers are WINDOW layers and FULL layers (two cache groups)
and a chip's share of routed experts (``paddle_tpu/models/cohere2_moe.py``),
for one measured window of DECODE rows over contexts of mixed length.

The loop, the window and the digest are ``drivers/serve.py``'s (its ``Loop``
and ``schedule_digest``, imported, as the other two serving drivers do; the
clients decode greedily, as theirs do). What differs:

- the reference is ``reference/cohere2_moe.py``. Two requests are served
  through the normal path, of ``CHECK_PROMPTS`` tokens: the second is past two
  windows, so its prefill crosses the window's edge in chunks, the window
  group releases pages under it, and its compared rows' window layers read
  through the lower edge of the mask. The logits rows of the step that ended
  each one's prefill and of every decode step after it (six rows a request)
  are held against ONE full forward of the reference per request, by their
  mean and by their median (``verdict``);
- a LIVE lane is held to the same: once the fill is done, six consecutive
  steps of all the lanes are tapped, and the rows of the lane with the longest
  context that the long check prompt's padded shape still holds (no further
  shape of the reference to compile) are compared with the reference's
  forward over that lane's own prompt and answer so far. It is one of 32
  lanes, past the window (its window table starts far from page 0), beside
  lanes of up to 50k tokens;
- beside every compared row, how near its routing was to falling otherwise
  (``_routing_note``): where the float32 reference's last chosen expert and
  its runner-up lie on two sides of the held share's edge, their scores'
  margin, and, where the step fed that row alone, the held experts the
  SERVED step fed against those the reference chose. Notes, not limits;
- the fill: every client's prompt (1k-49k tokens) is prefilled through the
  normal chunked path during set-up, and the window opens once every lane has
  delivered ``fill_tokens_per_lane`` tokens (and the live lane's rows are
  taken). Answers run to thousands of tokens, so no request finishes inside a
  window: it holds decode rows alone;
- what the window group holds when the window closes, beside what the same
  lanes would hold with no release, for ``kv_window_page_share``.
"""
from __future__ import annotations

from .serve import Loop, schedule_digest

#: MEAN over a request's six compared rows of the rms difference between the
#: served step's logits and the float32 reference's over the vocabulary
#: slice, as a share of the reference logits' standard deviation. bf16 rounds
#: each layer's output to 8 mantissa bits and the differences ride the
#: residual stream, as in the accepted serving cells (0.011-0.013 there); one
#: thing more moves a row here, as in the DeepSeek-V2-Lite cell: a bf16
#: activation can swap the 8th and 9th expert of a token whose scores nearly
#: tie, and where the chip holds a SHARE of the experts the swap can cross
#: its edge, so a whole expert's part (a gate of about an eighth) comes or
#: goes: 12 rows of 144 read 0.044-0.125 where their neighbours read
#: 0.007-0.013 (twelve seeds; ``PERF.md``, PR 36), in the 200-token prompt as
#: in the 9,000-token one. The mean over six rows read 0.0085-0.0279; the
#: reference with every weight rounded to e4m3 reads
#: 0.122 and 0.147, window layers with no lower edge 0.51-0.52, rotary on
#: the full layer 0.215-0.218 (``tools/window_moe_controls.py``).
LOGITS_TOL_RMS = 0.05

#: the same for the MEDIAN of a request's six rows, which a swapped expert in
#: one or two rows does not move: how the system computes, apart from how one
#: threshold fell. Served 0.0075-0.0125 over twelve seeds; e4m3 0.105 and
#: 0.128, the two wrong variants 0.21 and 0.52. (A first limit on the WORST
#: single row, 0.08, guessed before any reading, was past by one row of one
#: seed in six, 0.125, while e4m3's rows read from 0.101: the worst row tells
#: the two apart no better than luck, the median by a factor of eight.)
LOGITS_TOL_RMS_MEDIAN = 0.03

CHECK_PROMPTS = (200, 9000)  # tokens; the second past two windows
CHECK_ANSWER = 6
CHECK_PADS = (256, 9024)     # the reference runs one padded shape a request
#: steps tapped for the live lane: its six rows, and what the engine may
#: still hold back of the lane's answer when the last of them is dispatched
LIVE_STEPS = CHECK_ANSWER + 4


def verdict(rows_rms):
    """The comparison's statistic and what it decides. ``rows_rms``: per
    request, its compared rows' rms shares. Returns the requests' means,
    their medians, and whether every request has its six rows with both
    under their limits. ``tools/window_moe_controls.py`` holds its wrong
    references to this function."""
    import numpy as np

    means = [float(np.mean(r)) for r in rows_rms if len(r)]
    medians = [float(np.median(r)) for r in rows_rms if len(r)]
    ok = bool(rows_rms) and all(len(r) == CHECK_ANSWER for r in rows_rms) \
        and all(e <= LOGITS_TOL_RMS for e in means) \
        and all(e <= LOGITS_TOL_RMS_MEDIAN for e in medians)
    return means, medians, ok


def model_config(cfgj, dep):
    from paddle_tpu.models.cohere2_moe import Cohere2MoeConfig

    return Cohere2MoeConfig(
        vocab_size=cfgj["vocab_size"], hidden_size=cfgj["hidden_size"],
        num_layers=cfgj["num_hidden_layers"],
        num_heads=cfgj["num_attention_heads"],
        num_kv_heads=cfgj["num_key_value_heads"], head_dim=cfgj["head_dim"],
        max_seq_len=min(cfgj["max_position_embeddings"],
                        dep["max_seq_len"]),
        moe_intermediate_size=cfgj["intermediate_size"],
        n_routed_experts=cfgj["num_experts"],
        n_routed_experts_published=cfgj["num_experts_published"],
        experts_held_first=cfgj["experts_held_first"],
        n_shared_experts=cfgj["num_shared_experts"],
        num_experts_per_tok=cfgj["num_experts_per_tok"],
        norm_topk_prob=cfgj["norm_topk_prob"],
        scoring_func=cfgj["expert_selection_fn"],
        layer_norm_eps=cfgj["layer_norm_eps"],
        rope_theta=cfgj["rope_parameters"]["rope_theta"],
        sliding_window=cfgj["sliding_window"],
        layer_types=tuple(cfgj["layer_types"]),
        logit_scale=cfgj["logit_scale"],
        parallel_block=cfgj["use_parallel_block"],
        initializer_range=cfgj["assumed"]["initializer_range"])


class _Tap:
    """The predictor's step with each call's logits (and its rows per held
    expert) kept, on the device, until :meth:`note` says whose they are."""

    def __init__(self, sp):
        self.sp, self.step_fn = sp, sp._unified
        self.signature = self.last = None
        self.seen = []      # ((logits, expert rows), {req_id: (slot, n)}, fed)
        self._written = {}  # slot -> (req_id, tokens in the cache)
        self._moe = 2 + len(sp.cache.pools())

    def __enter__(self):
        from ._program import abstract

        def tapped(*args):
            if self.signature is None:
                self.signature = abstract(args)
            res = self.step_fn(*args)
            # [lanes, vocabulary] logits; [2, held experts] rows and fed
            self.last = (res[1], res[self._moe])
            return res

        tapped.trace_count = self.step_fn.trace_count
        self.sp._unified = tapped
        return self

    def __exit__(self, *exc):
        self.sp._unified = self.step_fn

    def note(self, watch):
        """After a ``step()`` call: keep what it dispatched if a request of
        ``watch`` is past its prompt (the end of a prefill and the decode
        steps after it are what may be compared), with the tokens each such
        lane has in the cache and the rows the step fed in all."""
        sp, at, written, fed = self.sp, {}, {}, 0
        for slot, r in sp.running.items():
            n = sp.cache.seq_len(slot)
            before = self._written.get(slot)
            fed += n - (before[1] if before and before[0] == r.req_id else 0)
            written[slot] = (r.req_id, n)
            if r.req_id in watch and n >= len(r.prompt_ids):
                at[r.req_id] = (slot, n)
        self._written = written
        if self.last is not None and at:
            self.seen.append((self.last, at, fed))
        self.last = None


def _routing_note(routing, n, cfgj, served):
    """How near row ``n``'s routing was to falling otherwise, from the
    reference's ranked scores (``routing``: a layer an entry): the smallest
    margin, as a share of the chosen score, between the last chosen expert
    and the runner-up over the layers where ONE of the two is held here (a
    swap of those two adds or takes a whole expert's part); the held experts
    the reference chose, a layer after another; and, where the served step
    fed this row alone (``served``: its rows per held expert), the same from
    the step."""
    import numpy as np

    first, held = cfgj.get("experts_held_first", 0), cfgj["num_experts"]
    k = cfgj["num_experts_per_tok"]
    note, chosen = {"edge_margin": None, "edge_layer": None}, []
    for layer, (scores, experts) in enumerate(routing):
        s, e = scores[n], experts[n] - first
        mine = (e >= 0) & (e < held)
        chosen += sorted(int(x) for x in e[:k][mine[:k]])
        if mine[k - 1] != mine[k]:
            margin = float((s[k - 1] - s[k]) / s[k - 1])
            if note["edge_margin"] is None or margin < note["edge_margin"]:
                note.update(edge_margin=margin, edge_layer=layer)
    note["held_chosen"] = sorted(chosen)
    if served is not None:
        note["held_chosen_served"] = np.repeat(
            np.arange(held), np.asarray(served[0]).astype(int)).tolist()
    return note


def _compare(sp, cfgj, seen, requests):
    """``requests``: (request, the padded length its reference runs at). The
    logits row of a lane at a step is the next-token distribution after the
    tokens written so far; the reference computes all of a request's compared
    rows by one full forward over its prompt and answer. Returns per request
    its rows' tokens written, rms shares and routing notes."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..reference import cohere2_moe as reference

    per_row = []
    for req, pad in requests:
        context = req.prompt_ids + req.output_ids
        rows = sorted(((out, at[req.req_id], fed) for out, at, fed in seen
                       if req.req_id in at
                       and at[req.req_id][1] <= min(len(context), pad)),
                      key=lambda m: m[1][1])[:CHECK_ANSWER]
        if not rows:
            per_row.append({"written": [], "rms": [], "routing": []})
            continue
        ids = np.zeros((pad,), np.int32)
        ids[:min(len(context), pad)] = context[:pad]
        routing = []
        with jax.enable_x64(False):
            want = np.asarray(reference.logits_at(
                sp.params, jnp.asarray(ids),
                [written - 1 for _, (_, written), _ in rows], cfgj,
                routing=routing), np.float32)
        mine = [float(np.sqrt(np.mean(
            (np.asarray(lg[slot], np.float32) - want[n]) ** 2))
            / want[n].std()) for n, ((lg, _), (slot, _), _) in enumerate(rows)]
        per_row.append({
            "written": [m[1][1] for m in rows], "rms": mine,
            "routing": [_routing_note(routing, n, cfgj,
                                      moe if fed == 1 else None)
                        for n, ((_, moe), _, fed) in enumerate(rows)]})
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
    prompt and its answer so far: the lane with the longest context that the
    long check prompt's padded shape holds."""
    room = CHECK_PADS[-1] - LIVE_STEPS
    fits = [(sp.cache.seq_len(slot), slot, r)
            for slot, r in sp.running.items()
            if sp.cache.seq_len(slot) <= room]
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
    from paddle_tpu.models.cohere2_moe import Cohere2MoeForCausalLM
    from paddle_tpu.ops.pallas.grouped_matmul import GROUPED_KERNEL_NAME
    from paddle_tpu.ops.pallas.paged_attention import RAGGED_KERNEL_NAME
    from paddle_tpu.ops.pallas.paged_write import KV_WRITE_KERNEL_NAME

    from ._program import mosaic_calls, program_bytes

    cfgj, tp = ctx.config, ctx.traffic["params"]
    dep = cfgj[ctx.traffic["driver"]]
    cfg = model_config(cfgj, dep)
    dtype = jnp.dtype(cfgj["dtype"])
    model = Cohere2MoeForCausalLM(
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
    t_fill = ctx.clock()
    loop.submit(gen.start(), t_fill)
    # the fill: every prompt through the chunked prefill, then decode until
    # each lane has handed back its first tokens
    owed = {key: int(tp["fill_tokens_per_lane"]) for key in loop.requests}
    seen = 0
    while any(n > 0 for n in owed.values()):
        loop.step()
        for _, key, n in loop.deliveries[seen:]:
            owed[key] -= n
        seen = len(loop.deliveries)
    fill_s = ctx.clock() - t_fill
    prompt_tokens = sum(r["prompt"] for r in loop.requests.values())
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
    # no request finishes inside a window: what was attempted is the
    # requests that were handed tokens in it
    served = {key for _, key, _ in loop.deliveries[n_fill[0]:]}
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
        "fill_s": fill_s,
        "fill_prompt_tokens": prompt_tokens,
        "fill_tok_s": prompt_tokens / fill_s,
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
        "attempted": len(served) + failed,
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
