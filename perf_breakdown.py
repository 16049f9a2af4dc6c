"""Per-phase attribution of the flagship GPT train step (round-4 verdict #1).

Differential timing on the real chip: the full fused K-step scan is timed
against variants with one phase removed (attention branch, MLP branch,
softmax-CE math) and against structural splits (forward-only,
forward+backward without the update). Phase cost = full − ablated. A pure
ideal-matmul scan of the model's exact GEMM set gives the attainable-MFU
ceiling for the same shapes — the roofline the model step is chasing
(answers "where do the other ~44% go" and makes the GPT-125M h=768
ceiling a measured number, not a sentence).

Methodology notes: K steps inside one scan, the timed call replaying the
warm-up call's inputs; ablated variants change compiled memory behavior minimally (the "ce"
ablation keeps the chunked-remat structure and head matmuls).

Usage: python perf_breakdown.py [--model 760m|125m] [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from bench import _chip_peak  # shared chip table / methodology


def _step_time(cfg, mesh, batch, seq, K, mode):
    """Seconds/step for one variant of the train step.

    mode: 'full' (fwd+bwd+update), 'grad' (fwd+bwd), 'fwd' (loss only).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.models import gpt_spmd

    lr, momentum = 1e-4, 0.9
    params = gpt_spmd.init_params(cfg, mesh, dtype=jnp.bfloat16)
    mom = jax.tree.map(jnp.zeros_like, params)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)

    def one_full(p, m, ids_, labels_):
        loss, grads = jax.value_and_grad(gpt_spmd.loss_fn)(
            p, ids_, labels_, cfg, mesh, 1)
        m2 = jax.tree.map(lambda a, g: momentum * a + g.astype(a.dtype),
                          m, grads)
        p2 = jax.tree.map(lambda a, b: a - lr * b, p, m2)
        return p2, m2, loss

    def one_grad(p, ids_, labels_):
        return jax.value_and_grad(gpt_spmd.loss_fn)(p, ids_, labels_, cfg,
                                                    mesh, 1)

    def one_fwd(p, ids_, labels_):
        return gpt_spmd.loss_fn(p, ids_, labels_, cfg, mesh, 1)

    def many_mode(params, mom, ids, labels):
        def body(carry, _):
            p, m, salt = carry
            if mode != "full":
                # defeat loop-invariant hoisting: the params must depend on
                # the previous iteration's loss or XLA computes the (fixed-
                # input) body ONCE outside the scan
                p = dict(p)
                p["lnf_g"] = p["lnf_g"] + (salt * 1e-30).astype(
                    p["lnf_g"].dtype)
            if mode == "full":
                p2, m2, loss = one_full(p, m, ids, labels)
                return (p2, m2, loss.astype(jnp.float32)), loss
            if mode == "grad":
                loss, grads = one_grad(p, ids, labels)
                # consume grads at a non-zero weight so XLA cannot DCE the
                # backward (literal *0.0 would be constant-folded away)
                gsum = sum(jnp.sum(jnp.abs(g).astype(jnp.float32))
                           for g in jax.tree.leaves(grads))
                loss = loss + gsum * 1e-30
                return (p, m, loss.astype(jnp.float32)), loss
            loss = one_fwd(p, ids, labels)
            return (p, m, loss.astype(jnp.float32)), loss

        salt0 = jnp.zeros((), jnp.float32)
        _, losses = lax.scan(body, (params, mom, salt0), None, length=K)
        return losses

    with jax.set_mesh(mesh):
        jit = jax.jit(many_mode)
        losses = jit(params, mom, ids, labels)
        np.asarray(losses)
        t0 = time.perf_counter()
        losses = jit(params, mom, ids, labels)
        np.asarray(losses)
        return (time.perf_counter() - t0) / K


def matmul_roofline(cfg, batch, seq, K):
    """Seconds/step for the model's exact GEMM set alone, fwd+bwd shapes:
    per layer fwd (qkv, proj, mlp-in, mlp-out + attention einsums) plus the
    2x backward passes, plus 3x head matmul (fwd + bwd + remat-CE extra
    pass). Everything bf16 on the MXU, no LN/softmax/residuals — the
    attainable ceiling for this model's shapes."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    h, L = cfg.hidden_size, cfg.num_layers
    nh, hd = cfg.num_heads, cfg.head_dim
    v = cfg.vocab_size
    T = batch * seq
    rng = np.random.RandomState(0)

    def t(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    x = t(T, h)
    wqkv, wo = t(h, 3 * h), t(h, h)
    w1, w2 = t(h, 4 * h), t(4 * h, h)
    emb = t(v, h)
    q = t(batch, nh, seq, hd)

    def gemms(x, q, wqkv, wo, w1, w2, emb, salt):
        # Every repetition is perturbed by the running accumulator so XLA
        # cannot CSE the 3xL identical GEMM sets into one, and every output
        # is fully consumed (a partial slice would let XLA narrow the GEMM).
        acc = salt
        with jax.default_matmul_precision("default"):
            for _ in range(3):  # fwd + 2 bwd passes (dgrad + wgrad)
                for _l in range(L):
                    a = x @ wqkv
                    s_ = jnp.einsum("bnqd,bnkd->bnqk", q, q)
                    o = jnp.einsum("bnqk,bnkd->bnqd", s_, q)
                    b_ = x @ wo
                    c = x @ w1
                    d = c @ w2
                    acc = acc + (jnp.sum(a) + jnp.sum(o) + jnp.sum(b_)
                                 + jnp.sum(d)).astype(jnp.float32) * 1e-30
                    x = x + (acc * 1e-20).astype(x.dtype)
                    q = q + (acc * 1e-20).astype(q.dtype)
                lg = x @ emb.T
                acc = acc + jnp.sum(lg).astype(jnp.float32) * 1e-30
        return acc

    def many(x, q, wqkv, wo, w1, w2, emb):
        def body(carry, _):
            return gemms(x, q, wqkv, wo, w1, w2, emb, carry), None

        out, _ = lax.scan(body, jnp.zeros((), jnp.float32), None, length=K)
        return out

    jit = jax.jit(many)
    out = jit(x, q, wqkv, wo, w1, w2, emb)
    np.asarray(out)
    t0 = time.perf_counter()
    np.asarray(jit(x, q, wqkv, wo, w1, w2, emb))
    per_step = (time.perf_counter() - t0) / K

    # FLOPs of that GEMM set
    per_layer = (2 * T * h * 3 * h + 2 * batch * nh * seq * seq * hd * 2
                 + 2 * T * h * h + 2 * T * h * 4 * h + 2 * T * 4 * h * h)
    total = 3 * (L * per_layer + 2 * T * h * v)
    return per_step, total


def attention_ab(batch, nh, seq, hd, K=16):
    """Isolated fwd+bwd A/B: Pallas flash kernel vs XLA fused attention at
    one (batch, heads, seq, head_dim) shape, bf16, causal. Returns ms/step
    for each — the direct evidence for where the flash routing threshold
    belongs at this shape."""
    import math
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(batch, seq, nh, hd), jnp.bfloat16)
    k = jnp.asarray(rng.randn(batch, seq, nh, hd), jnp.bfloat16)
    v = jnp.asarray(rng.randn(batch, seq, nh, hd), jnp.bfloat16)

    def xla_attn(q, k, v):
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(vt.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
        return o.transpose(0, 2, 1, 3)

    def run(fn):
        # grad wrt ALL of (q, k, v): XLA would DCE the dk/dv einsums of the
        # reference attention otherwise, while the fused Pallas backward
        # always computes them — a q-only grad would bias the A/B
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)) * 1e-30

        def many(q):
            def body(carry, _):
                gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(
                    q + carry.astype(q.dtype), k, v)
                s = (jnp.sum(gq) + jnp.sum(gk)
                     + jnp.sum(gv)).astype(jnp.float32)
                return carry + s * 1e-30, None

            out, _ = lax.scan(body, jnp.zeros((), jnp.float32), None,
                              length=K)
            return out

        with jax.default_matmul_precision("default"):
            jit = jax.jit(many)
            np.asarray(jit(q))
            t0 = time.perf_counter()
            np.asarray(jit(q))
            return (time.perf_counter() - t0) / K * 1e3

    return {
        "shape": f"b{batch} h{nh} s{seq} d{hd} bf16 causal",
        "flash_ms": round(run(lambda q, k, v: flash_attention(
            q, k, v, causal=True)), 3),
        "xla_ms": round(run(xla_attn), 3),
    }


def main():
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="760m", choices=["760m", "125m"])
    ap.add_argument("--json", default=None)
    ap.add_argument("-K", type=int, default=8)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    ap.add_argument("--attn", action="store_true",
                    help="isolated flash-vs-XLA attention A/B only")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.attn:
        on_tpu = jax.default_backend() == "tpu"
        shapes = ((12, 128), (12, 64)) if on_tpu else ((4, 64),)
        seqs = (512, 1024, 2048) if on_tpu else (256,)
        b = 8 if on_tpu else 2
        for nh, hd in shapes:
            for seq in seqs:
                print(json.dumps(attention_ab(b, nh, seq, hd,
                                              K=16 if on_tpu else 2)))
        return

    from paddle_tpu.models import gpt_spmd
    from paddle_tpu.models.gpt import GPTConfig

    on_tpu = jax.default_backend() == "tpu"
    if args.model == "760m":
        base = dict(hidden_size=1536, num_layers=24, num_heads=12,
                    recompute=True)
        batch, seq = 8, 1024
    else:
        base = dict(hidden_size=768, num_layers=12, num_heads=12,
                    recompute=False)
        batch, seq = 8, 1024
    if not on_tpu:
        batch, seq = 2, 256
        args.K = 2
    K = args.K
    mesh = gpt_spmd.make_mesh(1)

    def cfg_with(**kw):
        return GPTConfig(vocab_size=50304, max_seq_len=seq, **{**base, **kw})

    cfg = cfg_with()
    t_full = _step_time(cfg, mesh, batch, seq, K, "full")
    t_grad = _step_time(cfg, mesh, batch, seq, K, "grad")
    t_fwd = _step_time(cfg, mesh, batch, seq, K, "fwd")
    t_noattn = _step_time(cfg_with(ablate=("attn",)), mesh, batch, seq, K,
                          "full")
    t_nomlp = _step_time(cfg_with(ablate=("mlp",)), mesh, batch, seq, K,
                         "full")
    t_noce = _step_time(cfg_with(ablate=("ce",)), mesh, batch, seq, K,
                        "full")
    mm_time, mm_flops = matmul_roofline(cfg, batch, seq, K)

    chip, peak = _chip_peak(jax.devices()[0].device_kind)
    n_params = cfg.num_params()
    tok = batch * seq
    flops_per_token = 6 * n_params + 6 * cfg.num_layers * cfg.hidden_size * seq
    step_flops = flops_per_token * tok

    phases = {
        "full_step_ms": t_full * 1e3,
        "forward_ms": t_fwd * 1e3,
        "backward_ms": (t_grad - t_fwd) * 1e3,
        "optimizer_update_ms": (t_full - t_grad) * 1e3,
        "attention_total_ms": (t_full - t_noattn) * 1e3,
        "mlp_total_ms": (t_full - t_nomlp) * 1e3,
        "softmax_ce_math_ms": (t_full - t_noce) * 1e3,
        "ideal_gemm_set_ms": mm_time * 1e3,
    }
    result = {
        "model": args.model,
        "chip": chip,
        "batch": batch,
        "seq": seq,
        "K": K,
        "phases_ms": {k: round(v, 2) for k, v in phases.items()},
        "mfu_full_step": round(step_flops / t_full / peak, 4),
        "mfu_ideal_gemms": round(mm_flops / mm_time / peak, 4),
        "tokens_per_s": round(tok / t_full, 1),
    }
    text = json.dumps(result, indent=1)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
