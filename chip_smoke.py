"""Does the system still start on the chip?

    python chip_smoke.py             # one chip: serving phase, then training
    python chip_smoke.py --chips 4   # the same two phases over four chips

Drives the two hot paths once through the entry points a user calls, at the
full width and depth of GPT-3 760M (hidden 1536, 24 layers, 12 heads of 128,
ffn 6144, vocab 50304, bf16) with seeded random weights:

- *serve*: ``ServingPredictor`` with its defaults (unified ragged step, async
  engine, prefix cache, ``use_kernel=None``) answers more requests than it
  has lanes. Every request must finish; the compiled step must hold the
  Mosaic custom call of the ragged paged-attention kernel and must not copy,
  slice or restack the KV pools (they stay one donated buffer: its temp is
  under one stacked pool's bytes); the step must have been traced once; and,
  outside the timed window, one recorded step's logits from the kernel path
  must agree with ``use_kernel=False`` on the same chip.
- *train*: ``build_spmd_train_step`` (recompute + flash attention, bs8
  seq1024, bf16 state) takes a few steps on its fixed batch: finite,
  decreasing loss, flash forward and backward custom calls in the compiled
  program, peak device memory printed.

A chip belongs to one process: this parent never imports JAX and runs the
phases as sequential children (so each phase's peak memory is its own).
Every child refuses to run unless ``jax.devices()[0].platform == "tpu"``.
Any failed phase or assertion ends the run with a non-zero exit and no
result line. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

PHASES = ("serve", "train")
#: per-child wall limit; both phases together stay far under the 1200 s the
#: smoke is given, cold compile included
PHASE_TIMEOUT_S = 540

WIDTH = dict(vocab_size=50304, hidden_size=1536, num_layers=24, num_heads=12)
SEED = 0

# serving workload: more requests than the predictor's 8 default lanes, so
# admission waits on a free lane and freed pages are reused; the last four
# prompts repeat the first 256 tokens of the first four (prefix-cache hits)
N_REQUESTS, NEW_TOKENS, SHARED_PREFIX = 12, 32, 256
PROMPT_LEN = (512, 1024)
FRESH_LEN = (300, 700)  # the logits check's two prompts still in prefill
MAX_SEQ_LEN = 1088  # longest prompt + new tokens, rounded up to 64-token pages

# Kernel-vs-reference logits: the rms difference over the compared lanes'
# whole vocabulary, as a fraction of the reference logits' standard deviation
# (the scale an argmax or a softmax sees). Both paths round each layer's
# attention output to bf16 (8 mantissa bits, 2**-8 = 0.4% per element) after
# summing in different orders — online softmax page by page against one
# gathered softmax — and the differences ride the residual stream through 24
# layers, so 1-2% is expected. Measured on the v5e: 0.012 on one chip, 0.016
# over four (the largest single entry 0.055 and 0.082: about 5 times the rms,
# as the largest of 10**5 entries should be). A wrong page, mask or scale
# gives two unrelated logit vectors: 1.4.
LOGITS_TOL_RMS = 0.05

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 4


def _say(**fields):
    print(json.dumps(fields), flush=True)


def _require(ok, message) -> None:
    """The smoke's assertion (a plain ``assert`` vanishes under ``-O``)."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {message}")


def _device():
    """Refuse anything but a TPU; report what JAX found first."""
    import jax
    import jaxlib

    d = jax.devices()
    if d[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU: jax found platform "
            f"{d[0].platform!r} ({d[0].device_kind} x{len(d)})")
    from importlib import metadata

    dev = {"platform": d[0].platform, "kind": d[0].device_kind,
           "count": len(d)}
    _say(device=dev, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=metadata.version("libtpu"))
    return dev


def _is_mosaic_call(line: str, kernel: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in line and kernel in line


_POOL_MOVERS = ("copy", "dynamic-slice", "dynamic-update-slice")


def pool_copies(hlo_text: str, stack_shape) -> list[str]:
    """The instructions of a compiled serving step that move a whole KV
    pool: a ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` (alone
    or inside a fusion) whose result has the stacked pool's shape
    ``[layers, pages, heads, page, hd]`` or one layer's. The copy-on-write
    lanes are let through (scope ``cow``, read off the instruction or off
    the nearest fusion or loop around it that has a name): they update the
    stack in place, a page at a time, through a ``dynamic-update-slice`` of
    its shape."""
    import re

    dims = "|".join(",".join(map(str, shape))
                    for shape in (stack_shape, stack_shape[1:]))
    moved = re.compile(r"= \w+\[(%s)\]\S* (%s)\("
                       % (dims, "|".join(_POOL_MOVERS)))
    scope = re.compile(r'op_name="([^"]*)"')
    calls = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
    computation, found, called_from = None, [], {}
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            computation = head.group(1)
            continue
        named = scope.search(line)
        name = named.group(1) if named else None
        for callee in calls.findall(line):
            called_from[callee] = (computation, name)
        if moved.search(line):
            found.append((computation, name, line.strip()))

    def owner(computation, name):
        while name is None and computation in called_from:
            computation, name = called_from[computation]
        return name or ""

    return [line for computation, name, line in found
            if "/cow/" not in owner(computation, name)]


def _abstract(args):
    """Shape/dtype/sharding of a call's arguments (committed device arrays
    keep their sharding; the rest is placed by jit as it was for the
    call)."""
    import jax

    def one(a):
        placed = isinstance(a, jax.Array) and a.committed
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=a.sharding if placed else None)

    return jax.tree.map(one, args)


# positions in the unified step's signature (models/gpt.py
# build_unified_step): fn(params, tok_ids, tok_slot, tok_pos, q_lens,
# kv_lens, last_idx, feedback, prev_toks, emit_mask, produced, k_pages,
# v_pages, page_table, ...), the two pools donated
_TOK_IDS, _LAST_IDX, _POOLS = 1, 6, (11, 12)


def _with_pool_copies(args):
    import jax.numpy as jnp

    return tuple(jnp.copy(a) if i in _POOLS else a
                 for i, a in enumerate(args))


def _shards_everywhere(what, tree, chips):
    """Every array of ``tree`` is split (no shard is the whole array) and has
    shards on ``chips`` distinct devices — code that has only met virtual CPU
    devices may put everything on the first."""
    import jax

    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        _require(len(devs) == chips,
                 f"{what}: a {leaf.shape} leaf lives on {len(devs)} of "
                 f"{chips} devices")
        _require(all(s.data.shape != leaf.shape for s in shards),
                 f"{what}: a {leaf.shape} leaf is replicated, not split")


def _memory(chips, compiled):
    """Per-device allocator figures, and the compiled step's own analysis:
    on this backend ``peak_bytes_in_use`` counts live arrays, not a running
    program's temporaries, so the program's temp bytes are printed beside
    it."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    for i, s in enumerate(stats):
        _require(s["bytes_in_use"] > 0, f"device {i} reports no memory in use")
    program = compiled.memory_analysis()
    return {"peak_bytes_in_use": [s["peak_bytes_in_use"] for s in stats],
            "bytes_in_use": [s["bytes_in_use"] for s in stats],
            "program_argument_bytes": program.argument_size_in_bytes,
            "program_temp_bytes": program.temp_size_in_bytes}


def phase_serve(chips: int) -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingPredictor
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       build_unified_step)
    from paddle_tpu.ops.pallas.paged_attention import RAGGED_KERNEL_NAME

    cfg = GPTConfig(max_seq_len=MAX_SEQ_LEN, **WIDTH)
    paddle.seed(SEED)
    model = GPTForCausalLM(cfg)
    model.eval()
    sp = ServingPredictor(model, dtype=jnp.bfloat16,
                          mesh=chips if chips > 1 else None)
    del model  # the predictor holds its own bf16 stacks

    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(0, cfg.vocab_size, (int(n),)).tolist()
               for n in rng.randint(PROMPT_LEN[0], PROMPT_LEN[1] + 1,
                                    (N_REQUESTS,))]
    for i in range(4):
        prompts[N_REQUESTS - 4 + i][:SHARED_PREFIX] = \
            prompts[i][:SHARED_PREFIX]

    # the step function, with a tap on its arguments: the first call's
    # abstract signature (for the compiled-program check) and, when asked,
    # one call's concrete arguments (for the logits comparison; the pools
    # are donated to the step, so they are copied)
    step_fn = sp._unified
    tap = {"signature": None, "record_in": None, "args": None}

    def tapped(*args):
        if tap["signature"] is None:
            tap["signature"] = _abstract(args)
        if tap["record_in"] is not None:
            if tap["record_in"] == 0:
                tap["args"] = _with_pool_copies(args)
            tap["record_in"] -= 1
        return step_fn(*args)

    tapped.trace_count = step_fn.trace_count
    sp._unified = tapped

    # warm-up: one short request compiles the one step program
    t0 = time.perf_counter()
    warm = sp.generate([prompts[0][:40]], max_new_tokens=2)
    compile_s = time.perf_counter() - t0
    _require(len(warm[0]) == 2, warm)

    t0 = time.perf_counter()
    outs = sp.generate(prompts, max_new_tokens=NEW_TOKENS)
    run_s = time.perf_counter() - t0
    _require([len(o) for o in outs] == [NEW_TOKENS] * N_REQUESTS,
             f"unfinished requests: {[len(o) for o in outs]}")
    health = sp.healthz()
    _require(health["requests_failed"] == 0, health)
    _require(sp.decode_trace_count == 1,
             f"the serving step was traced {sp.decode_trace_count} times")
    steps, emitted = sp.steps, sp.tokens_emitted
    hit_rate = sp.prefix_hit_rate

    # the compiled step must hold the ragged paged-attention Mosaic call
    compiled = step_fn.lower(*tap["signature"]).compile()
    kernel_calls = sum(_is_mosaic_call(line, RAGGED_KERNEL_NAME)
                       for line in compiled.as_text().splitlines())
    _require(kernel_calls >= 1,
             "no Mosaic custom call of the ragged paged-attention kernel in "
             "the compiled serving step")

    # the pools stay one donated buffer through the step (PR 27): nothing
    # pool-shaped is copied, sliced out of the stack or stacked back, and
    # the step's temporaries are far under one stacked pool (the scanned
    # pools cost a second copy of both stacks)
    stack = sp.cache.k_pages
    local = (stack.shape[:2] + (stack.shape[2] // chips,) + stack.shape[3:])
    moved = pool_copies(compiled.as_text(), local)
    _require(not moved,
             f"the compiled serving step moves a whole KV pool "
             f"{len(moved)} times, first: {moved[:1]}")
    mem = _memory(chips, compiled)
    pool_bytes = stack.nbytes // chips
    _require(mem["program_temp_bytes"] < pool_bytes,
             f"the serving step's temp is {mem['program_temp_bytes']} bytes, "
             f"not under one stacked pool's {pool_bytes}")
    if chips > 1:
        _shards_everywhere("serving pools",
                           (sp.cache.k_pages, sp.cache.v_pages), chips)
        _shards_everywhere(
            "serving weights",
            {k: sp.params["layers"][k] for k in ("wqkv", "wo", "w1", "w2")},
            chips)

    # outside the timed window: one cached prompt (it reaches decode at
    # depth within a few steps) beside two fresh ones still prefilling; the
    # sixth step's arguments are recorded and that one step replayed through
    # the kernel build and a use_kernel=False build
    tap["record_in"] = 5
    fresh = [rng.randint(0, cfg.vocab_size, (n,)).tolist() for n in FRESH_LEN]
    for p in [prompts[1]] + fresh:
        sp.add_request(p, max_new_tokens=4)
    while sp.has_work():
        sp.step()
    sp.flush()
    _require(tap["args"] is not None, "no step recorded for the logits check")
    reference = build_unified_step(cfg, sp.cache.page_size, sp.chunk,
                                   use_kernel=False, mesh=sp.mesh)
    again = _with_pool_copies(tap["args"])
    got = np.asarray(step_fn(*tap["args"])[1], np.float32)
    want = np.asarray(reference(*again)[1], np.float32)
    # lanes that decide a token this step: last_idx < token budget
    live = (np.asarray(again[_LAST_IDX])
            < np.asarray(again[_TOK_IDS]).shape[0])
    _require(live.any() and np.isfinite(got[live]).all(),
             "no live lane, or non-finite kernel logits")
    diff, scale = got[live] - want[live], want[live].std()
    err = float(np.abs(diff).max() / scale)
    err_rms = float(np.sqrt(np.mean(diff ** 2)) / scale)
    _require(err_rms <= LOGITS_TOL_RMS,
             f"kernel and reference logits differ by {err_rms:.4f} rms of "
             f"the reference std (tolerance {LOGITS_TOL_RMS}; largest entry "
             f"{err:.4f})")

    return {"cold_compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "requests": N_REQUESTS, "steps": steps, "tokens": emitted,
            "prefix_hit_rate": round(hit_rate, 4),
            "step_traces": sp.decode_trace_count,
            "ragged_kernel_calls": kernel_calls,
            "stacked_pool_bytes": pool_bytes,
            "logits_err_max_frac_std": round(err, 5),
            "logits_err_rms_frac_std": round(err_rms, 5),
            "logits_lanes": int(live.sum()), **mem}


def phase_train(chips: int) -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step, make_mesh
    from paddle_tpu.ops.pallas.flash_attention import (BWD_KERNEL_NAME,
                                                       FWD_KERNEL_NAME)

    cfg = GPTConfig(max_seq_len=TRAIN_SEQ, recompute=True,
                    use_flash_attention=True, **WIDTH)
    mesh = make_mesh(chips)
    # microbatches only feed a pipeline: without one (pp == 1) the batch
    # stays whole, as in every recorded flagship run; fp32 state does not
    # fit one chip at this size (16.2 of 15.75 GB), bf16 state does
    pp = mesh.shape["pp"]
    step, params, mom, (ids, labels) = build_spmd_train_step(
        cfg, mesh, batch_size=TRAIN_BATCH, seq_len=TRAIN_SEQ,
        num_micro=2 * pp if pp > 1 else 1, dtype=jnp.bfloat16)
    if chips > 1:
        _shards_everywhere("train params", params["stages"], chips)
        _shards_everywhere("train momentum", mom["stages"], chips)
    signature = _abstract((params, mom, ids, labels))

    t0 = time.perf_counter()
    params, mom, loss = step(params, mom, ids, labels)
    losses = [float(loss)]
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(TRAIN_STEPS):
        params, mom, loss = step(params, mom, ids, labels)
        losses.append(float(loss))
    run_s = time.perf_counter() - t0
    _require(np.isfinite(losses).all(), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0], f"loss did not decrease: {losses}")

    with jax.set_mesh(mesh):
        compiled = step.lower(*signature).compile()
    hlo = compiled.as_text().splitlines()
    calls = {name: sum(_is_mosaic_call(line, name) for line in hlo)
             for name in (FWD_KERNEL_NAME, BWD_KERNEL_NAME)}
    _require(all(calls.values()),
             f"flash attention Mosaic calls missing from the compiled train "
             f"step: {calls}")

    if chips > 1:
        _shards_everywhere("train params after steps", params["stages"],
                           chips)
    return {"cold_compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "steps": TRAIN_STEPS, "mesh": dict(mesh.shape),
            "losses": [round(x, 4) for x in losses],
            "flash_kernel_calls": calls, **_memory(chips, compiled)}


def run_phase(name: str, chips: int) -> None:
    """Child entry: one phase in this process, its result as the last line."""
    from paddle_tpu.framework.compile_cache import configure_compile_cache

    # kernel block sizes come from the packaged defaults and whatever this
    # checkout swept, never from a file under $HOME (no sweep runs here)
    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".pallas_autotune.json")
    cache_dir = configure_compile_cache()
    dev = _device()
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips}: jax found {dev['count']}")
    result = {"serve": phase_serve, "train": phase_train}[name](chips)
    _say(phase=name, chips=chips, compile_cache=cache_dir, device=dev,
         **result)


def main(argv) -> int:
    chips = 1
    if "--chips" in argv:
        chips = int(argv[argv.index("--chips") + 1])
    if "--phase" in argv:
        run_phase(argv[argv.index("--phase") + 1], chips)
        return 0

    device = None
    for name in PHASES:
        # the child inherits stderr; its stdout is relayed when it ends, and
        # its last line is the phase's result (run() kills it at the limit)
        try:
            proc = subprocess.run(
                [sys.executable, "-u", __file__, "--phase", name,
                 "--chips", str(chips)],
                stdout=subprocess.PIPE, text=True, timeout=PHASE_TIMEOUT_S)
        except subprocess.TimeoutExpired as late:
            sys.stdout.write(late.stdout or "")
            print(f"chip_smoke: phase {name} exceeded {PHASE_TIMEOUT_S}s",
                  file=sys.stderr)
            return 1
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
        if proc.returncode != 0:
            print(f"chip_smoke: phase {name} failed (exit "
                  f"{proc.returncode})", file=sys.stderr)
            return proc.returncode
        device = json.loads(proc.stdout.strip().splitlines()[-1])["device"]
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
