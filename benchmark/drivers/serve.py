"""Drives ``inference.ServingPredictor`` for one measured window.

Set-up: build the eager model from the seed and the predictor from it (its
bf16 stacks; the fp32 model is dropped at once), serve two short requests
through the normal path and hold the logits of the step that ended each one's
prefill and of a later decode step against the plain reference's full
forward, check that the step program holds the ragged kernel as a Mosaic
call, then fill: the generator's clients are served until ``fill_requests``
of them have finished, so that the window opens on a mixed steady state.

The window: ``step()`` in a loop on one thread. Tokens are clocked when
``step()`` hands them back, and the generator is asked for new requests in
the same iteration, before the next ``step()``. Nothing in the loop depends
on the clock except where the window ends, so the sequence of steps is a
function of the traffic file and the seed.
"""
from __future__ import annotations

#: rms difference between the served step's logits and the float32
#: reference's, over the compared rows' whole vocabulary, as a share of the
#: reference logits' standard deviation. bf16 rounds each layer's output to
#: 8 mantissa bits and the differences ride the residual stream through every
#: layer: 1-2% is expected and measured (0.011 on the v5e at 18 layers). A
#: wrong page, mask or position gives unrelated logits: about 1.4.
#: chip_smoke.py gives the same tolerance and reason for its own check.
LOGITS_TOL_RMS = 0.05

CHECK_PROMPTS = (200, 256)   # tokens; both at most 256
CHECK_ANSWER = 6
CHECK_PAD = 320              # the reference runs one padded shape


def model_config(cfgj):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfgj["assumed"]["padded_vocab_size"],
        hidden_size=cfgj["n_embd"], num_layers=cfgj["n_layer"],
        num_heads=cfgj["n_head"], intermediate_size=cfgj["n_inner"],
        max_seq_len=cfgj["n_positions"],
        layer_norm_eps=cfgj["layer_norm_epsilon"],
        initializer_range=cfgj["initializer_range"])


class Loop:
    """The closed host loop around ``step()``: submits what the generator
    offers, clocks what ``step()`` hands back."""

    def __init__(self, sp, gen, clock, span, observe=False):
        self.sp, self.gen, self.clock, self.span = sp, gen, clock, span
        self.observe = observe
        self.live = {}        # req_id -> [key, tokens still owed]
        self.requests = {}    # key -> {"submit", "prompt", "answer"}
        self.deliveries = []  # (time, key, tokens)
        self.finished = []    # (time, key)
        self.steps = []       # per step() call: (time, lanes, [(q, kv)])
        self.calls = 0        # step() calls so far
        self.delivered_at = []  # the call that made each delivery
        self.overruns = 0     # requests handed more tokens than they asked
        self._written = {}    # slot -> (req_id, tokens in the cache)

    def submit(self, offered, now):
        with self.span("bench.admit"):
            for r in offered:
                req = self.sp.add_request(r["prompt"],
                                          max_new_tokens=r["answer"])
                self.live[req.req_id] = [r["key"], r["answer"]]
                self.requests[r["key"]] = {
                    "submit": now, "prompt": len(r["prompt"]),
                    "answer": r["answer"]}

    def step(self):
        with self.span("bench.step"):
            out = self.sp.step()
        now = self.clock()
        self.calls += 1
        done = []
        for req_id, toks in out.items():
            entry = self.live.get(req_id)
            if entry is None or not toks:
                continue
            self.deliveries.append((now, entry[0], len(toks)))
            self.delivered_at.append(self.calls)
            entry[1] -= len(toks)
            if entry[1] <= 0:
                self.overruns += entry[1] < 0
                done.append(entry[0])
                self.finished.append((now, entry[0]))
                del self.live[req_id]
        if self.observe:
            self._observe(now)
        self.submit(self.gen.after_step(now, done), now)
        return now

    def _observe(self, now):
        """What the step just dispatched, from the cache manager's own
        counts: per running lane the rows fed and its context length."""
        sp, lanes, written = self.sp, [], {}
        for slot, req in sp.running.items():
            kv = sp.cache.seq_len(slot)
            before = self._written.get(slot)
            base = (before[1] if before and before[0] == req.req_id
                    else req.cached_prefix_len)
            written[slot] = (req.req_id, kv)
            if kv > base:
                lanes.append((kv - base, kv))
        self._written = written
        self.steps.append((now, len(sp.running), lanes))


def schedule_digest(loop, upto_call):
    """A fingerprint of who was handed how many tokens by which ``step()``
    call, up to call ``upto_call``: two runs of one seed give the same."""
    import hashlib

    rows = [(call, key, n)
            for call, (_, key, n) in zip(loop.delivered_at, loop.deliveries)
            if call <= upto_call]
    return hashlib.sha1(repr(rows).encode()).hexdigest()[:16]


def _check_against_reference(sp, cfg, seed):
    """Two short requests through the normal path; the logits row of a
    lane at a step is the next-token distribution after the tokens written
    so far, which the reference computes by one full forward."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..reference import gpt as reference
    from ._program import abstract

    rng = np.random.default_rng([seed, 999_983])
    step_fn, captured = sp._unified, []

    signature = []

    def tapped(*args):
        if not signature:
            signature.append(abstract(args))
        res = step_fn(*args)
        captured.append(res[1])  # [lanes, vocabulary] logits, on the device
        return res

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped
    reqs = [sp.add_request(rng.integers(0, cfg.vocab_size, n).tolist(),
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

    ref = jax.jit(lambda p, ids, pos: reference.logits_at(
        p, ids, pos, num_heads=cfg.num_heads, eps=cfg.layer_norm_eps))
    errs = []
    for req in reqs:
        context = req.prompt_ids + req.output_ids
        mine = [(lg, at[req.req_id]) for lg, at in seen if req.req_id in at]
        prefill_end = next(m for m in mine
                           if m[1][1] == len(req.prompt_ids))
        decode = max((m for m in mine if m[1][1] < len(context)),
                     key=lambda m: m[1][1])
        for lg, (slot, written) in (prefill_end, decode):
            ids = np.zeros((1, CHECK_PAD), np.int32)
            ids[0, :written] = context[:written]
            with jax.enable_x64(False):
                want = np.asarray(ref(sp.params, jnp.asarray(ids),
                                      jnp.int32(written - 1)), np.float32)
            got = np.asarray(lg[slot], np.float32)
            errs.append(float(np.sqrt(np.mean((got - want) ** 2))
                              / want.std()))
    finished = all(len(r.output_ids) == CHECK_ANSWER for r in reqs)
    return {"rms_share_of_std": errs, "tolerance": LOGITS_TOL_RMS,
            "ok": finished and len(errs) == 2 * len(reqs)
            and all(e <= LOGITS_TOL_RMS for e in errs)}, signature[0]


def run(ctx):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingPredictor
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.ops.pallas.paged_attention import RAGGED_KERNEL_NAME

    from ._program import mosaic_calls, program_bytes

    cfgj, dep, tp = ctx.config, ctx.config["serve"], ctx.traffic["params"]
    cfg = model_config(cfgj)
    paddle.seed((ctx.seed ^ (ctx.seed >> 31)) & 0x7FFFFFFF)
    model = GPTForCausalLM(cfg)
    model.eval()
    ctx.mark("model")
    sp = ServingPredictor(
        model, dtype=jnp.dtype(cfgj["dtype"]), max_batch=dep["max_batch"],
        max_seq_len=dep["max_seq_len"], page_size=dep["page_size"],
        num_pages=dep["num_pages"], token_budget=dep["token_budget"],
        chunk=dep["chunk"])
    del model  # the predictor holds its own stacks
    ctx.mark("predictor")

    check, signature = _check_against_reference(sp, cfg, ctx.seed)
    ctx.mark("reference_check")

    compiled = sp._unified.lower(*signature).compile()
    calls = mosaic_calls(compiled, (RAGGED_KERNEL_NAME,))[RAGGED_KERNEL_NAME]
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
        "ragged_kernel_mosaic_calls": calls,
        "step_traces": sp.decode_trace_count,
        "fill_steps": fill_steps,
        "step_calls_in_window": loop.calls - fill_steps,
        "deliveries_in_window": len(loop.deliveries) - n_fill[0],
        "finished_in_window": len(finished),
        # the same for every run of one seed: the schedule through the fill
        # and the window's first hundred calls
        "schedule_digest": schedule_digest(loop, fill_steps + 100),
        "overruns": loop.overruns,
        "counters": {k: v for k, v in sorted(counters.items())
                     if v and k.startswith("serving_") and "{" not in k
                     and "_ms_" not in k},
    }
    return {
        "correct": bool(check["ok"] and calls >= 1 and failed == 0
                        and loop.overruns == 0
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
            "head_dim": cfg.head_dim,
            "kv_bytes": jnp.dtype(cfgj["dtype"]).itemsize,
        },
        "counters": counters,
        "program_bytes": hbm,
        "info": info,
        "compared": {"logits_rms_share_of_std_max": {
            "value": max(check["rms_share_of_std"], default=None),
            "limit": LOGITS_TOL_RMS}},
    }
