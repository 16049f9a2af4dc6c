"""MLP-side bandwidth attribution (round-5 verdict #5).

Times ONE decoder layer's MLP branch (LN + h->4h GEMM + gelu + 4h->h GEMM +
residual) fwd+bwd at the flagship shape against (a) the same two GEMMs alone
and (b) the branch with remat (the training configuration), then sets the
measured elementwise overhead against its minimum HBM traffic at the chip's
~819 GB/s — the roofline argument for whether a fused LN/residual Pallas
kernel has anything left to win.

Usage: python mlp_roofline.py [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

HBM_GBPS = {"TPU v5 lite": 819e9, "TPU v5p": 2765e9, "TPU v4": 1228e9}


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bench import _chip_peak

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("-K", type=int, default=32)
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    B, S, H = (8, 1024, 1536) if on_tpu else (2, 128, 256)
    K = args.K if on_tpu else 2
    eps = 1e-5
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, H), jnp.bfloat16)
    g = jnp.asarray(rng.randn(H), jnp.bfloat16)
    b = jnp.asarray(rng.randn(H), jnp.bfloat16)
    w1 = jnp.asarray(rng.randn(H, 4 * H) * 0.02, jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(4 * H, H) * 0.02, jnp.bfloat16)

    def ln(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * g + b

    def mlp(x, g, b, w1, w2):
        y = ln(x, g, b)
        y = jax.nn.gelu(y @ w1, approximate=True)
        return x + y @ w2

    def gemms_only(x, w1, w2):
        # same GEMM content as the branch (fwd 2, bwd 4), no LN/gelu/residual
        return (x @ w1) @ w2

    def timed(fn, *inp):
        def loss(*a):
            return jnp.sum(fn(*a).astype(jnp.float32)) * 1e-30

        def many(x0):
            def body(c, _):
                grads = jax.grad(loss, argnums=tuple(range(len(inp))))(
                    x0 + c.astype(x0.dtype), *inp[1:])
                s = sum(jnp.sum(gr).astype(jnp.float32) for gr in grads)
                return c + s * 1e-30, None

            out, _ = lax.scan(body, jnp.zeros((), jnp.float32), None, length=K)
            return out

        with jax.default_matmul_precision("default"):
            f = jax.jit(many)
            np.asarray(f(inp[0]))
            t0 = time.perf_counter()
            np.asarray(f(inp[0]))
            return (time.perf_counter() - t0) / K * 1e3  # ms

    t_mlp = timed(mlp, x, g, b, w1, w2)
    t_mlp_remat = timed(jax.checkpoint(
        mlp, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    ), x, g, b, w1, w2)
    t_gemm = timed(gemms_only, x, w1, w2)

    # Minimum HBM traffic of the NON-GEMM work, assuming perfect epilogue
    # fusion (gelu/residual ride the GEMM tiles): fwd LN read+write 2*BSH,
    # bwd LN read dy + x + write dx ~ 3*BSH, remat re-forward LN another
    # 2*BSH; gelu bwd reads the saved w1-output 4*BSH... counted at bf16.
    bsh = B * S * H * 2  # bytes
    min_bytes = (2 + 3 + 2) * bsh + 2 * 4 * bsh  # LN legs + gelu-grad read/write
    chip, _ = _chip_peak(jax.devices()[0].device_kind)
    bw = HBM_GBPS.get(chip, 819e9)
    roofline_ms = min_bytes / bw * 1e3

    out = {
        "shape": f"B{B} S{S} H{H} bf16, one layer, fwd+bwd",
        "mlp_branch_ms": round(t_mlp, 3),
        "mlp_branch_remat_ms": round(t_mlp_remat, 3),
        "gemms_only_ms": round(t_gemm, 3),
        "elementwise_overhead_ms": round(t_mlp_remat - t_gemm, 3),
        "min_hbm_bytes_nongemm": min_bytes,
        "roofline_ms_at_bw": round(roofline_ms, 3),
        "chip": chip,
        "verdict": None,
    }
    ratio = (t_mlp_remat - t_gemm) / max(roofline_ms, 1e-9)
    out["verdict"] = (
        f"measured elementwise overhead is {ratio:.2f}x its HBM roofline — "
        + ("XLA fusion is near-optimal; a Pallas LN kernel has <~"
           f"{max(0.0, (t_mlp_remat - t_gemm) - roofline_ms):.1f} ms/layer to win"
           if ratio < 1.6 else
           "fusion gap: a fused LN/residual Pallas kernel is worth building"))
    print(json.dumps(out, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            f.write(json.dumps(out, indent=1) + "\n")


if __name__ == "__main__":
    main()
