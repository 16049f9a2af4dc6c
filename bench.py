"""Benchmark: flagship GPT training throughput on one chip.

Prints ONE JSON line (driver contract): the flagship GPT-760M fused train
step. ``--all`` additionally benches the north-star-shaped secondary configs
(BASELINE.md): GPT-125M, ResNet-50 eager (config 1), BERT-base via jit
(config 2), GPT-1.3B — one JSON line each, flagship line last. Every
``--all`` line also carries the ideal-GEMM anchor (:func:`gemm_anchor`)
measured in the same run. ``--fused-mlp`` flips the GPT configs onto the
fused MLP-block Pallas kernels (ops/pallas/fused_mlp) — same metric names,
same contract; run with and without for the kernel A/B.

The script needs a TPU: it exits non-zero naming the platform it found
otherwise, an unknown ``device_kind`` is an error (no default peak), and a
failed phase fails the run. The one CPU mode is ``--dpquant --cpu``, the
tier-1 smoke of the dp=2 gradient-sync A/B, whose line names the CPU and
carries counts (wire bytes, loss parity), not a device rate.

Methodology: the GPT configs run the library's own train step
(``models.gpt_spmd.build_spmd_train_step``: forward + backward +
momentum-SGD update, bf16 state / fp32 loss, params and momentum donated);
one warm-up call compiles, then K steps are dispatched back to back with
one device->host sync at the end. tokens/sec = K * batch * seq / elapsed.
The reference publishes no absolute numbers (BASELINE.md), so vs_baseline
reports measured MFU vs chip peak — the honest utilization signal.

GPT-760M (h=1536, 24L, head_dim 128) is the flagship: it is the largest
BASELINE-shaped config that fits one 16 GB chip (with block rematerialization
+ chunked-remat CE), and its MXU-shaped matmuls make the MFU number
comparable to the A100 north star. The 125M config stays as a secondary line
for round-over-round comparability.
"""
from __future__ import annotations

import time

import numpy as np

# bf16 peak FLOP/s per chip, keyed by ``device_kind``. v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16); the others from the same
# product pages. A device that is not here is an error, not a default.
PEAKS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5p": 459e12,
         "TPU v4": 275e12, "TPU v6 lite": 918e12}


def _chip_peak(device_kind):
    """(table key, bf16 peak FLOP/s) for a ``device_kind`` string."""
    matched = next((k for k in PEAKS if k in device_kind), None)
    if matched is None:
        raise ValueError(
            f"no peak FLOP/s known for device_kind {device_kind!r} "
            f"(known: {', '.join(PEAKS)}); add it to PEAKS with its source")
    return matched, PEAKS[matched]


def bench_gpt(label, hidden, layers, heads, batch, seq, K, recompute,
              flash=True, save_attn=True, fused_mlp=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_spmd
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(
        vocab_size=50304, hidden_size=hidden, num_layers=layers,
        num_heads=heads, max_seq_len=seq, recompute=recompute,
        use_flash_attention=flash, remat_save_attn=save_attn,
        # --fused-mlp A/B: same metric name, same driver contract — only the
        # block's elementwise implementation flips (fused Pallas kernels vs
        # XLA)
        fused_mlp=fused_mlp,
    )
    step, params, mom, (ids, labels) = gpt_spmd.build_spmd_train_step(
        cfg, gpt_spmd.make_mesh(1), batch_size=batch, seq_len=seq,
        num_micro=1, lr=1e-4, dtype=jnp.bfloat16)
    params, mom, loss = step(params, mom, ids, labels)  # compile + warm-up
    losses = [float(loss)]
    t0 = time.perf_counter()
    for _ in range(K):
        params, mom, loss = step(params, mom, ids, labels)
    losses.append(float(loss))  # the one device->host sync
    elapsed = time.perf_counter() - t0

    tps = K * batch * seq / elapsed
    n_params = cfg.num_params()
    flops_per_token = 6 * n_params + 6 * layers * hidden * seq
    chip, peak = _chip_peak(jax.devices()[0].device_kind)
    mfu = tps * flops_per_token / peak
    assert np.all(np.isfinite(losses)), "non-finite training loss"
    out = {
        "metric": f"{label} fused train step tokens/sec/chip "
                  f"(bs{batch} seq{seq}, {chip})",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu, 4),
    }
    if fused_mlp:
        out["fused_mlp"] = True
    return out


def gemm_anchor(n=4096, iters=24):
    """Normalization anchor: a fixed-shape bf16 matmul chain (one compiled
    dispatch, lax.scan inside, one sync). Emitted alongside every ``--all``
    config's JSON: a config move that tracks the anchor's move is
    run-to-run noise, not a regression. Fixed probe = fixed FLOPs;
    ``anchor_frac_peak`` is the achievable fraction of chip peak on ideal
    GEMM content in this run."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dtype = jnp.bfloat16
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(n, n) * 0.02, dtype)
    b = jnp.asarray(rng.randn(n, n) * 0.02, dtype)

    def chain(a, b):
        # data-dependent chain: no two matmuls can run concurrently and
        # none can be DCE'd; 0.02 scale keeps bf16 values finite
        def body(c, _):
            return a @ c, None

        c, _ = lax.scan(body, b, None, length=iters)
        return c

    with jax.default_matmul_precision("default"):
        f = jax.jit(chain)
        f(a, b).block_until_ready()  # compile + warmup
        t0 = time.perf_counter()
        f(a, b).block_until_ready()
        elapsed = time.perf_counter() - t0
    flops = 2 * n ** 3 * iters
    chip, peak = _chip_peak(jax.devices()[0].device_kind)
    return {
        "anchor_gemm": f"{n}x{n}x{n}x{iters} {jnp.dtype(dtype).name} ({chip})",
        "anchor_tflops": round(flops / elapsed / 1e12, 2),
        "anchor_frac_peak": round(flops / elapsed / peak, 4),
    }


def bench_resnet_eager():
    """BASELINE config 1: ResNet-50 dygraph on CIFAR-10-shaped data.

    True eager: one framework-op dispatch per layer, backward on the tape,
    optimizer step — no jit of the step. FLAGS_eager_op_cache is on (the
    framework's cached per-op executables — reference parity: cached kernel
    selection + pregenerated ad_funcs), so each composite op costs ONE
    dispatch."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import flags as _flags
    from paddle_tpu.vision.models import resnet50

    _prev_cache = _flags.flag("eager_op_cache")
    _flags.set_flags({"eager_op_cache": True})

    batch, K = 64, 5
    m = resnet50(num_classes=10)
    opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                    parameters=m.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 3, 32, 32).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 10, (batch,)), dtype="int64")

    def step():
        loss = paddle.nn.functional.cross_entropy(m(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    try:
        loss = step()  # warmup (lazy compiles inside eager ops)
        _ = float(loss.numpy())
        t0 = time.perf_counter()
        for _ in range(K):
            loss = step()
        _ = float(loss.numpy())
        elapsed = time.perf_counter() - t0
    finally:
        _flags.set_flags({"eager_op_cache": _prev_cache})
    return {
        "metric": f"resnet50 eager train step images/sec (bs{batch}, "
                  "CIFAR-10 shapes)",
        "value": round(K * batch / elapsed, 1),
        "unit": "images/s",
        "vs_baseline": 0.0,
    }


def bench_resnet_jit():
    """ResNet-50 train step jit-compiled (what eager mode costs vs compiled:
    the eager number measures per-op dispatch, this one the chip)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    import paddle_tpu as paddle
    from paddle_tpu.autograd import no_grad
    from paddle_tpu.jit.api import _named_state, functional_call
    from paddle_tpu.vision.models import resnet50

    batch, K = 256, 10
    paddle.seed(0)
    m = resnet50(num_classes=10)
    # train-mode BN: running-stat updates are captured as functional state
    # (functional_call return_state) and ride the scan carry — full
    # reference train-step semantics, no eval-BN shortcut
    m.train()
    state = {n: t._data for n, t in _named_state(m).items()}
    buf_names = {n for n, _ in m.named_buffers()}
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, 3, 32, 32), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, (batch,)), jnp.int32)

    def loss_fn(params, x, y):
        with no_grad():
            logits, new_state = functional_call(
                m, params, paddle.Tensor(x), return_state=True)
            loss = paddle.nn.functional.cross_entropy(
                logits, paddle.Tensor(y))
        bufs = {k: v._data if hasattr(v, "_data") else v
                for k, v in new_state.items() if k in buf_names}
        return loss._data.astype(jnp.float32), bufs

    trainable = {k for k, v in state.items()
                 if jnp.issubdtype(v.dtype, jnp.floating)
                 and k not in buf_names}
    p_f = {k: v for k, v in state.items() if k in trainable}
    p_i = {k: v for k, v in state.items() if k not in trainable}

    def many(p_f, bufs, x, y):
        def body(carry, _):
            p, bf = carry
            (loss, bf2), g = jax.value_and_grad(
                lambda pf: loss_fn({**pf, **p_i, **bf}, x, y),
                has_aux=True)(p)
            p = jax.tree.map(lambda a, b: a - 1e-8 * b, p, g)  # tiny lr: keeps the scan carry live (no loop-invariant hoisting) without divergence
            return (p, bf2), loss

        return lax.scan(body, (p_f, bufs), None, length=K)

    bufs0 = {k: v for k, v in state.items() if k in buf_names}
    f = jax.jit(many)
    _, losses = f(p_f, bufs0, x, y)
    first = np.asarray(losses)
    t0 = time.perf_counter()
    _, losses = f(p_f, bufs0, x, y)
    _ = np.asarray(losses)
    elapsed = time.perf_counter() - t0
    assert np.all(np.isfinite(first)), "non-finite resnet loss"
    return {
        "metric": f"resnet50 jit train step images/sec (bs{batch}, "
                  "CIFAR-10 shapes)",
        "value": round(K * batch / elapsed, 1),
        "unit": "images/s",
        "vs_baseline": 0.0,
    }


def bench_bert_jit():
    """BASELINE config 2: BERT-base pretraining step via jit compile."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    import paddle_tpu as paddle
    from paddle_tpu.jit.api import _named_state, functional_call
    from paddle_tpu.models import BertForPretraining
    from paddle_tpu.models.bert import BertConfig

    batch, seq, K = 128, 128, 10
    cfg = BertConfig(hidden_dropout=0.0, attn_dropout=0.0)  # bert-base
    paddle.seed(0)
    m = BertForPretraining(cfg)
    dtype = jnp.bfloat16
    params = {n: t._data.astype(dtype) if jnp.issubdtype(t._data.dtype, jnp.floating)
              else t._data
              for n, t in _named_state(m).items()}
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int64)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int64)
    nsp = jnp.asarray(rng.randint(0, 2, (batch,)), jnp.int64)

    def loss_fn(params, ids, labels, nsp):
        # no_grad: outer value_and_grad differentiates through the jax graph
        # (incl. the flash kernel's custom_vjp); the framework tape would
        # build a redundant inner jax.vjp around each op — wasted tracing and
        # a Mosaic lowering bug with nested custom-vjp on this toolchain.
        from paddle_tpu.autograd import no_grad

        with no_grad():
            out = functional_call(
                m, params, paddle.Tensor(ids),
                masked_lm_labels=paddle.Tensor(labels),
                next_sentence_label=paddle.Tensor(nsp))
        return out._data.astype(jnp.float32)

    def one_step(p, mom, ids, labels, nsp):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids, labels, nsp)
        mom2 = jax.tree.map(lambda a, g: 0.9 * a + g.astype(a.dtype), mom, grads)
        p2 = jax.tree.map(lambda a, b: a - 1e-4 * b, p, mom2)
        return p2, mom2, loss

    def many(p, mom, ids, labels, nsp):
        def body(carry, _):
            p, mom = carry
            p, mom, loss = one_step(p, mom, ids, labels, nsp)
            return (p, mom), loss

        (p, mom), losses = lax.scan(body, (p, mom), None, length=K)
        return p, mom, losses

    mom = jax.tree.map(
        lambda a: jnp.zeros_like(a) if jnp.issubdtype(a.dtype, jnp.floating)
        else None, params)
    mom = {k: v for k, v in mom.items() if v is not None}
    params_f = {k: v for k, v in params.items() if k in mom}
    params_i = {k: v for k, v in params.items() if k not in mom}

    def many_wrap(p_f, mom, ids, labels, nsp):
        return many({**p_f, **params_i}, mom, ids, labels, nsp)

    f = jax.jit(many_wrap)
    _, _, losses = f(params_f, mom, ids, labels, nsp)
    first = np.asarray(losses)
    t0 = time.perf_counter()
    _, _, losses = f(params_f, mom, ids, labels, nsp)
    _ = np.asarray(losses)
    elapsed = time.perf_counter() - t0
    tps = K * batch * seq / elapsed
    n_params = sum(int(np.prod(v.shape)) for v in params_f.values())
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * cfg.hidden_size * seq
    chip, peak = _chip_peak(jax.devices()[0].device_kind)
    assert np.all(np.isfinite(first)), "non-finite BERT loss"
    return {
        "metric": f"bert-base jit pretraining tokens/sec/chip "
                  f"(bs{batch} seq{seq}, {chip})",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps * flops_per_token / peak, 4),
    }


def bench_dp_quant(on_tpu):
    """Round-14 dp=2 gradient-sync A/B: implicit GSPMD fp allreduce vs the
    int8 quantized ring (``distributed.compressed_collectives`` behind
    ``build_spmd_train_step(comm_quant="int8")``).

    One JSON line: the int8 leg's throughput (``vs_baseline`` = speedup
    over the fp leg — ~1.0 on the CPU smoke where the virtual-device
    "wire" is memcpy; there the line names the CPU and what it carries is
    the wire-byte model, not a device rate),
    ``bytes_on_the_wire``/``bytes_on_the_wire_fp``/``wire_reduction`` from
    the analytic per-replica ring model, ``loss_parity_delta`` (max
    relative deviation of the int8 loss trajectory vs the fp oracle over
    the benched steps — both runs deterministic, same init/data), and
    ``replicas_bit_identical`` (params after the int8 steps byte-equal
    across the dp replicas' shards). Needs >= 2 devices (main() forces 2
    virtual host devices off-TPU, like bench_serve's spmd leg)."""
    import jax
    import jax.numpy as jnp

    # round 23: the wire model rides the shared analysis constants module
    # (same import the JX009 HLO contract reads) — one source of truth
    # for the analytic bytes this line carries
    from paddle_tpu.analysis.cost_model import bytes_on_the_wire
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    from jax.sharding import Mesh

    from paddle_tpu.observability import default_registry

    if len(jax.devices()) < 2:
        raise RuntimeError("dp-quant A/B needs >= 2 devices")
    if on_tpu:
        hidden, layers, heads, batch, seq, steps = 768, 12, 12, 8, 1024, 8
    else:
        hidden, layers, heads, batch, seq, steps = 64, 2, 4, 8, 64, 6
    cfg = GPTConfig(vocab_size=256, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_seq_len=seq)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("dp", "pp", "mp"))

    def run(comm_quant):
        step, params, mom, (ids, labels) = build_spmd_train_step(
            cfg, mesh, batch_size=batch, seq_len=seq, comm_quant=comm_quant)
        # warmup = step 1 of the deterministic trajectory (params/mom are
        # donated, so training continues from the returned state); only
        # the post-compile steps are timed
        params, mom, loss = step(params, mom, ids, labels)
        losses = [float(loss)]
        t0 = time.perf_counter()
        for _ in range(steps - 1):
            params, mom, loss = step(params, mom, ids, labels)
            losses.append(float(loss))
        elapsed = time.perf_counter() - t0
        return losses, params, (steps - 1) * batch * seq / elapsed

    # round 15: the library-wide metrics registry records both legs'
    # train-step counters + the analytic wire bytes actually charged per
    # step (labeled fp vs int8) — the snapshot rides the emitted line
    default_registry.reset()
    default_registry.enable()
    try:
        fp_losses, _, fp_tps = run(None)
        q_losses, q_params, q_tps = run("int8")
    finally:
        default_registry.disable()
    telemetry = default_registry.snapshot_flat()
    parity = max(abs(a - b) / max(abs(a), 1e-9)
                 for a, b in zip(fp_losses, q_losses))
    bit_identical = 1.0
    for leaf in jax.tree.leaves(q_params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        full = [s for s in shards if s.shape == leaf.shape]
        if any(not np.array_equal(full[0], s) for s in full[1:]):
            bit_identical = 0.0
    n_elems = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(q_params))
    elem_bytes = jnp.dtype(jax.tree.leaves(q_params)[0].dtype).itemsize
    wire_fp = bytes_on_the_wire(n_elems, 2, elem_bytes=elem_bytes)
    wire_q = bytes_on_the_wire(n_elems, 2, elem_bytes=elem_bytes,
                               quant="int8")
    chip = (_chip_peak(jax.devices()[0].device_kind)[0] if on_tpu
            else "cpu smoke: counts only, not a device rate")
    return {
        "metric": f"gpt dp2 int8-quantized gradient allreduce train step "
                  f"tokens/sec/chip (bs{batch} seq{seq}, {chip})",
        "value": round(q_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(q_tps / fp_tps, 4),
        "comm_quant": "int8",
        "bytes_on_the_wire": wire_q,
        "bytes_on_the_wire_fp": wire_fp,
        "wire_reduction": round(wire_fp / wire_q, 4),
        "loss_parity_delta": parity,
        "replicas_bit_identical": bit_identical,
        "telemetry": telemetry,
    }


def main():
    import os
    import sys

    dpquant = "--dpquant" in sys.argv
    cpu = "--cpu" in sys.argv
    if cpu and not dpquant:
        raise SystemExit(
            "bench.py: --cpu is the tier-1 smoke of --dpquant only; a "
            "throughput line needs a TPU")
    if dpquant:
        # the dp=2 A/B needs two devices: force virtual host devices
        # BEFORE the backend initializes (CPU backend only — a real TPU
        # ignores the host-platform flag), like bench_serve --smoke
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2")
    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu  # noqa: F401  framework config (matmul precision)
    from paddle_tpu.analysis.bench_schema import checked_line
    from paddle_tpu.framework.compile_cache import configure_compile_cache

    configure_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu:
        raise SystemExit(
            f"bench.py needs a TPU: jax found platform {platform!r} "
            f"({jax.devices()[0].device_kind}). Only --dpquant --cpu runs "
            "without a chip.")
    fused_mlp = "--fused-mlp" in sys.argv

    if dpquant:
        # round-14 standalone mode (the tier-1 gate in
        # tests/test_distributed.py drives it): ONE schema-checked line
        print(checked_line(bench_dp_quant(platform == "tpu")))
        return

    # the anchor is measured ONCE per --all run and merged into every line,
    # so each config's JSON carries the run's ideal-GEMM throughput
    anchor = {}
    if "--all" in sys.argv or "--anchor" in sys.argv:
        anchor = gemm_anchor()

    def emit(d):
        # schema-checked emit (tpulint BL001 contract): a malformed line
        # fails HERE, not two rounds later as a silently skewed delta
        print(checked_line({**d, **anchor}))

    if "--all" in sys.argv:
        emit(bench_gpt("gpt3-125m", 768, 12, 12, 8, 1024, 20, False,
                       fused_mlp=fused_mlp))
        emit(bench_resnet_eager())
        emit(bench_resnet_jit())
        emit(bench_bert_jit())
        # BASELINE config 3 (single-chip line): the builder's donation
        # keeps params + momentum single-buffered, which is what lets 1.3B
        # fit 16 GB; save_attn=False keeps the memory-edge config's smaller
        # footprint (the attention re-forward costs less than an OOM)
        emit(bench_gpt("gpt3-1.3b(+remat)", 2048, 24, 16, 4, 1024, 5, True,
                       save_attn=False, fused_mlp=fused_mlp))

    # flagship line LAST (the driver reads one line; keep it the final one)
    emit(bench_gpt("gpt3-760m(+remat)", 1536, 24, 12, 8, 1024, 10, True,
                   fused_mlp=fused_mlp))


if __name__ == "__main__":
    main()
