"""Per-op performance regression harness.

Reference: tools/ci_op_benchmark.sh + tools/check_op_benchmark_result.py —
the reference gates op perf in CI by comparing per-op timings against a
stored baseline. This sweeps the hottest registry ops at fixed
transformer-ish shapes through the REAL dispatch path (apply_op, eager
cache at its default state) and emits one JSON object:

    {"device": "...", "platform": "tpu|cpu", "ops": {name: {"us": median,
     "shape": "..."}}}

Usage:
    python bench_ops.py                     # print JSON to stdout
    python bench_ops.py --out BENCH_OPS_r04.json
    python bench_ops.py --iters 50

The gate test (tests/test_bench_ops.py, opt-in via -m bench) compares a
fresh sweep against the committed file for the SAME platform and fails on
>TOL regressions.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from statistics import median as _median


def build_cases():
    """(name, thunk) pairs. Shapes: decoder-block-ish at b=8, s=512,
    h=1024 — big enough that the kernel dominates on TPU, small enough
    that a CPU sweep finishes in ~a minute."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.nn import functional as F

    rng = np.random.RandomState(0)
    B, S, H = 8, 512, 1024
    x = paddle.to_tensor(rng.randn(B * S, H).astype("float32"))
    x3 = paddle.to_tensor(rng.randn(B, S, H).astype("float32"))
    w = paddle.to_tensor(rng.randn(H, H).astype("float32"))
    w4 = paddle.to_tensor(rng.randn(H, 4 * H).astype("float32"))
    big = paddle.to_tensor(rng.randn(B, S, 4 * H).astype("float32"))
    qkv = paddle.to_tensor(rng.randn(B, S, 16, 64).astype("float32"))
    logits = paddle.to_tensor(rng.randn(B * S, 32000).astype("float32"))
    labels = paddle.to_tensor(rng.randint(0, 32000, (B * S,)).astype("int64"))
    ids = paddle.to_tensor(rng.randint(0, 32000, (B, S)).astype("int64"))
    img = paddle.to_tensor(rng.randn(8, 64, 56, 56).astype("float32"))
    kern = paddle.to_tensor(rng.randn(64, 64, 3, 3).astype("float32"))
    emb_w = paddle.to_tensor(rng.randn(32000, H).astype("float32"))
    ln = nn.LayerNorm(H)
    rms = nn.RMSNorm(H)
    bn = nn.BatchNorm2D(64)
    bn.eval()
    idx = paddle.to_tensor(rng.randint(0, B * S, (4096,)).astype("int64"))
    b_h = paddle.to_tensor(rng.randn(H).astype("float32"))

    cases = [
        ("matmul", lambda: paddle.matmul(x, w)),
        ("matmul_4h", lambda: paddle.matmul(x3, w4)),
        ("linear_bias", lambda: F.linear(x, w, b_h)),
        ("layer_norm", lambda: ln(x3)),
        ("rms_norm", lambda: rms(x3)),
        ("softmax", lambda: F.softmax(x3, axis=-1)),
        ("sdpa_attention", lambda: F.scaled_dot_product_attention(
            qkv, qkv, qkv, is_causal=True)),
        ("cross_entropy", lambda: F.cross_entropy(logits, labels)),
        ("embedding", lambda: F.embedding(ids, emb_w)),
        ("gelu", lambda: F.gelu(big)),
        ("silu", lambda: F.silu(big)),
        ("relu", lambda: F.relu(big)),
        ("tanh", lambda: paddle.tanh(x3)),
        ("add", lambda: x3 + x3),
        ("mul", lambda: x3 * x3),
        ("add_scalar", lambda: x3 + 1.0),
        ("transpose", lambda: paddle.transpose(x3, [0, 2, 1])),
        ("reshape", lambda: paddle.reshape(x3, [B * S, H])),
        ("concat", lambda: paddle.concat([x3, x3], axis=-1)),
        ("split", lambda: paddle.split(x3, 2, axis=-1)),
        ("reduce_sum", lambda: x3.sum()),
        ("reduce_mean_axis", lambda: x3.mean(axis=-1)),
        ("cumsum", lambda: paddle.cumsum(x3, axis=1)),
        ("argmax", lambda: paddle.argmax(logits, axis=-1)),
        ("topk", lambda: paddle.topk(logits, 8, axis=-1)),
        ("gather", lambda: paddle.gather(x, idx)),
        ("where", lambda: paddle.where(x3 > 0, x3, x3 * 0.1)),
        ("conv2d", lambda: F.conv2d(img, kern, padding=1)),
        ("batch_norm", lambda: bn(img)),
        ("max_pool2d", lambda: F.max_pool2d(img, 2, 2)),
        ("dropout_train", lambda: F.dropout(x3, 0.1, training=True)),
        ("clip", lambda: paddle.clip(x3, -1.0, 1.0)),
    ]
    return cases


def bench(iters: int = 30, warmup: int = 5):
    import jax

    import paddle_tpu  # noqa: F401

    dev = jax.devices()[0]
    cases = build_cases()
    ops = {}
    for name, thunk in cases:
        try:
            for _ in range(warmup):
                out = thunk()
            _block(out)
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                out = thunk()
                _block(out)
                ts.append((time.perf_counter() - t0) * 1e6)
            ops[name] = {"us": round(_median(ts), 2)}
        except Exception as e:  # keep sweeping; record the failure
            ops[name] = {"error": f"{type(e).__name__}: {e}"}
    return {
        "device": str(dev),
        "platform": dev.platform,
        "iters": iters,
        "ops": ops,
    }


def _block(out):
    import jax

    leaves = out if isinstance(out, (list, tuple)) else [out]
    for l in leaves:
        data = getattr(l, "_data", l)
        if hasattr(data, "block_until_ready"):
            jax.block_until_ready(data)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    result = bench(iters=args.iters)
    text = json.dumps(result, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    sys.stdout.write(text + "\n")


if __name__ == "__main__":
    main()
