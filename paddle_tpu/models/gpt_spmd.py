"""GPT SPMD training step: dp × pp × mp (+sequence-parallel) over one mesh.

This is the compiled hybrid-parallel path — the TPU-native equivalent of the
reference's fleet hybrid engine (SURVEY.md §3.3: CommunicateTopology +
PipelineParallel 1F1B + Megatron TP + sequence parallel), expressed the XLA
way:

- **dp**: batch dim sharded over ``dp``; gradient all-reduce emitted by GSPMD
  (params replicated over dp).
- **mp (TP)**: Megatron column/row sharding on qkv/mlp weights + vocab-sharded
  embedding (reference mp_layers.py:47,:333,:540); collectives emitted by
  GSPMD from the weight shardings + activation constraints.
- **sp**: between attention/mlp regions activations are sharded over ``mp`` on
  the *sequence* dim (reference sequence_parallel_utils.py) via sharding
  constraints — GSPMD turns the row-linear all-reduce into
  reduce-scatter + all-gather exactly like the reference's SP layers.
- **pp**: stacked-stage GSPMD pipelining (stage weights stacked on a leading
  dim sharded over ``pp``): all stages compute in parallel under ``vmap``
  over the stacked dim and the microbatch ring shifts via ``jnp.roll`` on it
  (GSPMD emits the collective-permute) — the 1F1B-equivalent schedule with
  bubble (S-1)/(M+S-1), with every mesh axis staying GSPMD-automatic.

Everything is a pure function over a params pytree -> works under jit, grad,
and donation; the single entry is :func:`build_spmd_train_step`.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.jit32 import jit32
from ..observability.tracing import phase, step_scope
from .gpt import GPTConfig


# the ONE mesh-shape heuristic lives in distributed.mesh (round 11 —
# serving shares it); these names stay importable from here
from ..distributed.mesh import (choose_mesh_shape,  # noqa: F401
                                make_training_mesh as make_mesh)


# ---------------------------------------------------------------------------
# Parameter init + shardings
# ---------------------------------------------------------------------------


def init_params(config: GPTConfig, mesh: Mesh, seed: int = 0, dtype=jnp.float32):
    pp = mesh.shape["pp"]
    assert config.num_layers % pp == 0, "num_layers must divide pp"
    lps = config.num_layers // pp
    h, f, v, s = config.hidden_size, config.ffn_size, config.vocab_size, config.max_seq_len
    std = config.initializer_range
    key = jax.random.PRNGKey(seed)
    ks = iter(jax.random.split(key, 16))

    def norm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    stages = {
        "ln1_g": jnp.ones((pp, lps, h), dtype),
        "ln1_b": jnp.zeros((pp, lps, h), dtype),
        "wqkv": norm(next(ks), (pp, lps, h, 3 * h)),
        "bqkv": jnp.zeros((pp, lps, 3 * h), dtype),
        "wo": norm(next(ks), (pp, lps, h, h)),
        "bo": jnp.zeros((pp, lps, h), dtype),
        "ln2_g": jnp.ones((pp, lps, h), dtype),
        "ln2_b": jnp.zeros((pp, lps, h), dtype),
    }
    e = int(getattr(config, "moe_experts", 0) or 0)
    if e:
        # MoE block: router gate + stacked expert FFNs replace the dense
        # MLP (the leading [E] expert dim shards over "ep" when present)
        stages.update({
            "moe_gate": norm(next(ks), (pp, lps, h, e)),
            "moe_w1": norm(next(ks), (pp, lps, e, h, f)),
            "moe_b1": jnp.zeros((pp, lps, e, f), dtype),
            "moe_w2": norm(next(ks), (pp, lps, e, f, h)),
            "moe_b2": jnp.zeros((pp, lps, e, h), dtype),
        })
    else:
        stages.update({
            "w1": norm(next(ks), (pp, lps, h, f)),
            "b1": jnp.zeros((pp, lps, f), dtype),
            "w2": norm(next(ks), (pp, lps, f, h)),
            "b2": jnp.zeros((pp, lps, h), dtype),
        })
    params = {
        "tok_emb": norm(next(ks), (v, h)),
        "pos_emb": norm(next(ks), (s, h)),
        "stages": stages,
        "lnf_g": jnp.ones((h,), dtype),
        "lnf_b": jnp.zeros((h,), dtype),
    }
    return params


def param_specs(moe: bool = False, ep_axis: str | None = None) -> dict:
    """PartitionSpecs: pp stacks stages, mp is the Megatron dim.

    ``moe=True`` swaps the dense-MLP rows for the expert stacks;
    ``ep_axis`` ("ep" on the round-25 4-axis mesh, None on a 3-axis one)
    shards the expert dim — the mp axis stays on attention only (expert
    GEMMs are already parallel over experts)."""
    stages = {
        "ln1_g": P("pp", None, None),
        "ln1_b": P("pp", None, None),
        "wqkv": P("pp", None, None, "mp"),   # column parallel
        "bqkv": P("pp", None, "mp"),
        "wo": P("pp", None, "mp", None),     # row parallel
        "bo": P("pp", None, None),
        "ln2_g": P("pp", None, None),
        "ln2_b": P("pp", None, None),
    }
    if moe:
        stages.update({
            "moe_gate": P("pp", None, None, None),
            "moe_w1": P("pp", None, ep_axis, None, None),
            "moe_b1": P("pp", None, ep_axis, None),
            "moe_w2": P("pp", None, ep_axis, None, None),
            "moe_b2": P("pp", None, ep_axis, None),
        })
    else:
        stages.update({
            "w1": P("pp", None, None, "mp"),
            "b1": P("pp", None, "mp"),
            "w2": P("pp", None, "mp", None),
            "b2": P("pp", None, None),
        })
    return {
        "tok_emb": P("mp", None),  # vocab-parallel embedding
        "pos_emb": P(),
        "stages": stages,
        "lnf_g": P(),
        "lnf_b": P(),
    }


def _specs_for(params, mesh: Mesh) -> dict:
    """The spec tree matching a params pytree on this mesh (MoE and the
    ep axis inferred — keeps every pre-MoE caller signature intact)."""
    moe = "moe_w1" in params["stages"]
    ep_axis = "ep" if (moe and "ep" in mesh.axis_names
                       and mesh.shape["ep"] > 1) else None
    return param_specs(moe=moe, ep_axis=ep_axis)


def param_shardings(mesh: Mesh, params=None):
    specs = (param_specs() if params is None
             else _specs_for(params, mesh))
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _add_dp_dim(spec: P, shape, dp: int) -> P:
    """Extend ``spec`` with "dp" on the first unsharded dim divisible by dp.

    The compiled-ZeRO primitive: sharding a state tensor over the data axis
    is exactly the reference's DygraphShardingOptimizer parameter split
    (dygraph_sharding_optimizer.py) — XLA inserts the all-gather on use and
    reduce-scatter on update that stages 1-3 hand-code."""
    if dp <= 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (ps, sz) in enumerate(zip(parts, shape)):
        if ps is None and sz % dp == 0:
            parts[i] = "dp"
            return P(*parts)
    return spec  # nothing divides: stays replicated (small biases/norms)


def zero_shardings(params, mesh: Mesh, stage: int):
    """(param shardings, optimizer-state shardings) for ZeRO stage 0-3.

    stage>=1: optimizer state sharded over dp (ZeRO-1; reference
    DygraphShardingOptimizer). stage>=2: gradients are reduce-scattered by
    GSPMD as a consequence of the state shardings (ZeRO-2; reference
    GroupShardedOptimizerStage2 — in the compiled world XLA chooses
    reduce-scatter over all-reduce when the consumer is dp-sharded).
    stage>=3: parameters themselves sharded over dp, gathered on use
    (ZeRO-3; reference GroupShardedStage3 pre-forward allgather)."""
    dp = mesh.shape["dp"]
    base = _specs_for(params, mesh)

    def opt_spec(spec, leaf):
        return NamedSharding(mesh, _add_dp_dim(spec, leaf.shape, dp))

    specs_flat = jax.tree.leaves(base, is_leaf=lambda x: isinstance(x, P))
    leaves_flat = jax.tree.leaves(params)
    treedef = jax.tree.structure(params)
    opt = treedef.unflatten(
        [opt_spec(s, l) for s, l in zip(specs_flat, leaves_flat)])
    if stage >= 3:
        p_shard = opt
    else:
        p_shard = treedef.unflatten(
            [NamedSharding(mesh, s) for s in specs_flat])
    return p_shard, (opt if stage >= 1 else p_shard)


# ---------------------------------------------------------------------------
# Model math (pure, global-view except the pp ring)
# ---------------------------------------------------------------------------


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _fused_mlp_on(config: GPTConfig, mesh: Mesh) -> bool:
    """Whether the fused MLP-block Pallas kernels (ops/pallas/fused_mlp)
    replace the XLA elementwise chains in this block. GSPMD cannot
    partition a pallas_call, so the fused path is single-shard only —
    exactly the flagship 1-chip config it targets. Off-TPU the kernels run
    in interpret mode and only when ``force_fused_mlp`` asks for them
    (CPU tests); a compiled CPU run would pay interpreter dispatch."""
    if not getattr(config, "fused_mlp", False):
        return False
    if getattr(config, "moe_experts", 0):
        return False  # the fused MLP kernels are dense-only
    if math.prod(mesh.shape.values()) != 1:
        return False
    if jax.default_backend() != "tpu":
        return bool(getattr(config, "force_fused_mlp", False))
    return True


def _mk_cs(mesh: Mesh):
    # Plain PartitionSpecs resolve against the context mesh (jax.set_mesh),
    # so the same constraints hold inside vmapped/scanned bodies where a
    # concrete NamedSharding's rank could mismatch the batched view.
    def cs(x, spec):
        return lax.with_sharding_constraint(x, spec)

    return cs


def _block(p, x, config: GPTConfig, mesh: Mesh, dp_axis="dp"):
    """One decoder block on [mb, s, h] with TP/SP sharding constraints.
    Returns ``(x, aux)`` — the MoE load-balance loss for this layer (0.0
    on dense configs), accumulated up the scan/pipeline.

    ``dp_axis=None`` drops the batch-dim constraints: the comm-quant dp
    train step vmaps this math over an explicit replica dim (the leading
    stacked dim carries the "dp" sharding), so binding "dp" again inside
    would double-use the mesh axis."""
    nh, hd = config.num_heads, config.head_dim
    mb, s, h = x.shape
    cs = _mk_cs(mesh)

    fused = _fused_mlp_on(config, mesh)
    # SP region: sequence sharded over mp
    x = cs(x, P(dp_axis, "mp", None))
    if "attn" in config.ablate:  # perf attribution: skip the whole branch
        return _block_mlp(p, x, config, cs, dp_axis, mesh)
    with step_scope("ln"):
        if fused:
            from ..ops.pallas import fused_mlp as _fm

            # single-pass LN kernel (fp32 stats, mean/rstd saved for
            # backward; tags its outputs "ln_out" so remat_save_ln keeps
            # working)
            y = _fm.fused_layer_norm(x, p["ln1_g"], p["ln1_b"],
                                     eps=config.layer_norm_eps,
                                     use_kernel=True)
        else:
            y = _layer_norm(x, p["ln1_g"], p["ln1_b"],
                            config.layer_norm_eps)
        if not fused and getattr(config, "remat_save_ln", False):
            from jax.ad_checkpoint import checkpoint_name

            y = checkpoint_name(y, "ln_out")
    with step_scope("qkv"):
        qkv = y @ p["wqkv"] + p["bqkv"]       # column-parallel -> [mb,s,3h]/mp
        qkv = cs(qkv, P(dp_axis, None, "mp"))
        q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):  # [mb, s, h] -> [mb, nh, s, hd], heads sharded over mp
        t = t.reshape(mb, s, nh, hd).transpose(0, 2, 1, 3)
        return cs(t, P(dp_axis, "mp", None, None))

    if jax.default_backend() == "tpu":
        use_flash = config.use_flash_attention and s % 128 == 0
    else:
        use_flash = config.force_flash  # interpret-mode kernel for CPU tests
    with step_scope("attn"):
        if use_flash:
            # fused Pallas kernel: no S x S residuals in fwd or bwd. On a
            # mesh the kernel runs per-device via shard_map: heads (mp) and
            # batch (dp) are embarrassingly parallel in flash attention, so
            # no collectives are needed inside the region — reference never
            # shards a head *across* devices either (mp_layers.py splits by
            # whole heads).
            from ..ops.pallas.flash_attention import flash_attention

            qh = q.reshape(mb, s, nh, hd)
            kh = k.reshape(mb, s, nh, hd)
            vh = v.reshape(mb, s, nh, hd)
            if math.prod(mesh.shape.values()) > 1:
                # manual over EVERY mesh axis (Mosaic calls cannot be
                # partitioned automatically): under pp the stage dim
                # arrives through ``_pipeline``'s vmap(spmd_axis_name="pp")
                spec = P(dp_axis, None, "mp", None)

                def local_flash(qs, ks, vs):
                    return flash_attention(qs, ks, vs, causal=True)

                o = jax.shard_map(
                    local_flash,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                    check_vma=False,
                )(qh, kh, vh)
            else:
                o = flash_attention(qh, kh, vh, causal=True)
            o = o.reshape(mb, s, h)
        else:
            q, k, v = heads(q), heads(k), heads(v)
            scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / math.sqrt(hd)
            causal = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(causal, scores, -1e30)
            attn = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("bnqk,bnkd->bnqd", attn, v)
            o = o.transpose(0, 2, 1, 3).reshape(mb, s, h)
    with step_scope("attn_out"):
        o = o @ p["wo"] + p["bo"]              # row-parallel
        if not fused:
            # reduce-scatter onto SP layout
            x = x + cs(o, P(dp_axis, "mp", None))
    if fused:
        return _block_mlp_fused(p, x, o, config), jnp.float32(0.0)
    return _block_mlp(p, x, config, cs, dp_axis, mesh)


def _block_mlp_fused(p, x, branch, config: GPTConfig):
    """Fused-kernel MLP half (single shard): the attention branch's residual
    add + LN2 ride ONE residual-in/residual-out kernel (one HBM round-trip
    instead of three), and fc1's bias+gelu ride one epilogue kernel after
    the GEMM — the round-5 roofline's ~1.3 ms/layer of elementwise traffic."""
    from ..ops.pallas import fused_mlp as _fm

    if "mlp" in config.ablate:  # perf attribution: skip the whole branch
        return x + branch
    with step_scope("ln"):
        y, s = _fm.fused_ln_residual(branch, x, p["ln2_g"], p["ln2_b"],
                                     eps=config.layer_norm_eps,
                                     use_kernel=True)
    with step_scope("mlp"):
        y = _fm.fused_bias_gelu(y @ p["w1"], p["b1"], use_kernel=True)
        return s + (y @ p["w2"] + p["b2"])


def _block_mlp(p, x, config: GPTConfig, cs, dp_axis="dp", mesh=None):
    if "mlp" in config.ablate:  # perf attribution: skip the whole branch
        return x, jnp.float32(0.0)
    with step_scope("ln"):
        y = _layer_norm(x, p["ln2_g"], p["ln2_b"], config.layer_norm_eps)
        if getattr(config, "remat_save_ln", False):
            from jax.ad_checkpoint import checkpoint_name

            y = checkpoint_name(y, "ln_out")
    with step_scope("mlp"):
        if getattr(config, "moe_experts", 0):
            return _moe_mlp(p, x, y, config, cs, dp_axis, mesh)
        y = jax.nn.gelu(y @ p["w1"] + p["b1"], approximate=True)
        y = cs(y, P(dp_axis, None, "mp"))
        y = y @ p["w2"] + p["b2"]
        x = x + cs(y, P(dp_axis, "mp", None))
    return x, jnp.float32(0.0)


def _moe_mlp(p, x, y, config: GPTConfig, cs, dp_axis, mesh):
    """The expert-sharded MoE FFN half of a block: GShard dense-mask
    gating (``models.moe.topk_dispatch_combine`` — the einsum twin of the
    serving grouped-GEMM path, same routing/capacity/tie-break math) with
    the expert dim sharded over "ep".

    Dispatch is collective-FREE: ``y`` is replicated over ep, so each ep
    shard builds its local experts' [E/ep, C, d] buffers with a slice of
    the dispatch mask. The COMBINE is the wire: each shard's partial
    outputs stack [ep, n, d] and reduce over the ep ring through the
    PR-9 int8 wire-quant surface (``quantized_all_reduce_stacked``) —
    ~4x fewer bytes than an fp all-reduce, s8 collectives on the HLO
    (the JX009 contract). ep == 1 keeps plain einsums, no collectives."""
    from ..distributed.compressed_collectives import (
        quantized_all_reduce_stacked)
    from .moe import moe_capacity, topk_dispatch_combine

    mb, s, h = y.shape
    e = int(config.moe_experts)
    n = mb * s
    tok = y.reshape(n, h)
    logits = tok.astype(jnp.float32) @ p["moe_gate"].astype(jnp.float32)
    cap = moe_capacity(n, e, config.moe_top_k, config.moe_capacity_factor)
    combine, dispatch, aux = topk_dispatch_combine(
        logits, cap, config.moe_top_k)
    ep = 1
    if mesh is not None and "ep" in mesh.axis_names:
        ep = mesh.shape["ep"]
    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(tok.dtype), tok)
    if ep > 1:
        expert_in = cs(expert_in, P("ep", None, None))
    hmid = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", expert_in, p["moe_w1"])
        + p["moe_b1"][:, None, :], approximate=True)
    expert_out = (jnp.einsum("ecf,efd->ecd", hmid, p["moe_w2"])
                  + p["moe_b2"][:, None, :])
    if ep > 1:
        expert_out = cs(expert_out, P("ep", None, None))
        eg = e // ep
        out_g = expert_out.reshape(ep, eg, cap, h)
        comb_g = combine.reshape(n, ep, eg, cap).transpose(1, 0, 2, 3)
        partial = jnp.einsum("gnec,gecd->gnd", comb_g.astype(tok.dtype),
                             out_g)
        partial = cs(partial, P("ep", None, None))
        # [ep, n, d] in, every slot the ring sum out — take slot 0
        out = quantized_all_reduce_stacked(partial, mesh=mesh, axis="ep",
                                           mean=False)[0]
    else:
        out = jnp.einsum("nec,ecd->nd", combine.astype(tok.dtype),
                         expert_out)
    out = out.reshape(mb, s, h).astype(x.dtype)
    x = x + cs(out, P(dp_axis, "mp", None))
    return x, aux


def _stage_fn(p_stage, x, config: GPTConfig, mesh: Mesh, dp_axis="dp"):
    """Apply this pp rank's layers (scan over the layer-in-stage dim).

    With ``config.recompute`` the block is rematerialized in backward
    (activations per layer drop from ~6 stacked [mb,s,4h] buffers to the
    layer input — SURVEY §2.7 recompute strategy; on TPU this is what lets
    batch scale past HBM), at ~30% recompute FLOPs. Matmul outputs are kept
    (checkpoint_dots policy) so the MXU work is not redone.
    """

    def body(carry, p_layer):
        x, aux = carry
        x2, a = _block(p_layer, x, config, mesh, dp_axis)
        return (x2, aux + a), None

    if getattr(config, "recompute", False):
        # weight-GEMM outputs AND (by default) the flash kernel's o/lse are
        # saved; the backward recomputes only elementwise/LN (cheap) —
        # remat trades the minimum FLOPs for the activation-memory win
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        names = []
        if getattr(config, "remat_save_attn", True):
            names.append("flash_out")
        if getattr(config, "remat_save_ln", False):
            names.append("ln_out")
        if names:
            policy = jax.checkpoint_policies.save_from_both_policies(
                policy,
                jax.checkpoint_policies.save_only_these_names(*names))
        body = jax.checkpoint(body, policy=policy)
    # the scan itself is scoped: its own slicing of the stacked weights and
    # stacking of the saved residuals fall under "layers" and under no part
    with step_scope("layers"):
        (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), p_stage)
    return x, aux


def _pipeline(stages, mbs, mesh: Mesh, config: GPTConfig, dp_axis="dp"):
    """Microbatch pipeline over the pp axis (GSPMD-pipelined stacked stages).

    stages: pytree with leading [pp, lps, ...] dims. mbs: [M, mb, s, h].
    Returns ``([M, mb, s, h], aux)`` — last-stage outputs (replicated
    over pp) and the MoE aux loss summed over every (microbatch, layer)
    the SCHEDULE actually ran (the warm-up/drain garbage slots mask out;
    0.0 on dense configs).

    Roll formulation (praxis-style GSPMD pipelining): every stage computes
    in parallel under ``vmap`` over the pp-sharded stacked dim, and the ring
    shift is ``jnp.roll`` on that dim — GSPMD emits the collective-permute
    itself and every mesh axis stays automatic. The earlier partial-manual
    ``shard_map`` ring is gone: ``lax.axis_index``/``lax.ppermute`` inside a
    partially-auto manual region lower through PartitionId / mismatched
    manual-subgroup shardings that the jax-0.4.x SPMD partitioner rejects
    (CPU: hard UNIMPLEMENTED / partitioner check failure).
    """
    num_stages = mesh.shape["pp"]
    num_micro = mbs.shape[0]
    if num_stages == 1:
        p_one = jax.tree.map(lambda a: a[0], stages)

        def one(mb):
            return _stage_fn(p_one, mb, config, mesh, dp_axis)

        with step_scope("pipeline"):
            ys, auxs = jax.lax.map(one, mbs)
        return ys, jnp.sum(auxs)

    total = num_micro + num_stages - 1
    last = num_stages - 1
    cs = _mk_cs(mesh)

    stage_v = jax.vmap(lambda p, x: _stage_fn(p, x, config, mesh, dp_axis),
                       spmd_axis_name="pp")

    def step(carry, t):
        # inject microbatch t into stage 0 (clipped past the schedule; the
        # recycled garbage is never collected), run ALL stages in parallel,
        # shift stage s's output to stage s+1's next input via the roll
        acts = carry.at[0].set(mbs[jnp.clip(t, 0, num_micro - 1)])
        acts = cs(acts, P("pp", dp_axis, None, None))
        y, aux = stage_v(stages, acts)
        return jnp.roll(y, 1, axis=0), (y[last], aux)

    # the schedule's own work (inject, ring shift, collecting the last
    # stage's outputs) is what falls under "pipeline" and under no part
    with step_scope("pipeline"):
        init = jnp.zeros((num_stages,) + mbs.shape[1:], mbs.dtype)
        _, (outs, auxs) = lax.scan(step, init,
                                   jnp.arange(total, dtype=jnp.int32))
    # stage s at time t runs microbatch t - s; everything else in the
    # warm-up/drain window is recycled garbage — mask its aux out
    t_idx = jnp.arange(total)[:, None]
    s_idx = jnp.arange(num_stages)[None, :]
    sched = ((t_idx - s_idx >= 0)
             & (t_idx - s_idx < num_micro)).astype(jnp.float32)
    # microbatch m reaches the last stage at t = m + (S-1)
    return outs[last : last + num_micro], jnp.sum(auxs * sched)


def loss_fn(params, ids, labels, config: GPTConfig, mesh: Mesh, num_micro: int,
            dp_axis="dp"):
    # MXU-native matmul precision: the framework default is "highest" (true
    # fp32 semantics for user-facing float32 ops), which would emulate even
    # bf16 matmuls with multi-pass fp32 — 6x slower. The training path wants
    # native bf16 MXU passes; loss math below is explicitly fp32.
    # dp_axis=None: the comm-quant step vmaps this over an explicit replica
    # dim, so the batch constraints must not re-bind the "dp" mesh axis.
    with jax.default_matmul_precision("default"):
        return _loss_fn_inner(params, ids, labels, config, mesh, num_micro,
                              dp_axis)


def _loss_fn_inner(params, ids, labels, config: GPTConfig, mesh: Mesh,
                   num_micro: int, dp_axis="dp"):
    cs = _mk_cs(mesh)
    b, s = ids.shape
    with step_scope("embed"):
        x = jnp.take(params["tok_emb"], ids, axis=0) + params["pos_emb"][:s]
        x = cs(x, P(dp_axis, None, None))
    mb = b // num_micro
    mbs = x.reshape(num_micro, mb, s, x.shape[-1])
    y, moe_aux = _pipeline(params["stages"], mbs, mesh, config, dp_axis)
    y = y.reshape(b, s, -1)
    # the final norm, the chunked tied head and the cross-entropy
    with step_scope("head_loss"):
        y = _layer_norm(y, params["lnf_g"], params["lnf_b"], config.layer_norm_eps)

        # Shifted next-token CE, chunked over the sequence with remat: the full
        # [b, s, vocab] fp32 logits (3.2 GB at bs16/seq1024/50k vocab) never
        # materialize — each chunk's logits are recomputed in backward. Costs one
        # extra head matmul pass (~2hv/token, ~8% of step FLOPs at 125M) and
        # buys 2-4x batch on a 16 GB chip, a clear MFU win.
        emb = params["tok_emb"]
        # shift labels left; the last position has no target (masked below)
        lb = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
        chunk = s
        while chunk > 128 or s % chunk:
            chunk //= 2
        nchunks = s // chunk
        yc = y.reshape(b, nchunks, chunk, -1).transpose(1, 0, 2, 3)
        lbc = lb.reshape(b, nchunks, chunk).transpose(1, 0, 2)

        def chunk_nll(args):
            y_ch, lb_ch = args
            lg = (y_ch @ emb.T).astype(jnp.float32)  # [b, chunk, v]
            lg = cs(lg, P(dp_axis, None, "mp"))  # vocab-sharded over mp (tied head)
            if "ce" in config.ablate:
                # perf attribution: keep the head matmul (and the chunked remat
                # structure), drop the softmax-CE math
                return jnp.sum(lg, axis=-1) * 1e-9
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, lb_ch[..., None], axis=-1)[..., 0]
            return lse - tgt  # [b, chunk]

        nll = lax.map(jax.checkpoint(chunk_nll), (yc, lbc))  # [nchunks, b, chunk]
        nll = nll.transpose(1, 0, 2).reshape(b, s)
        valid = (jnp.arange(s) < s - 1).astype(jnp.float32)
        loss = jnp.sum(nll * valid) / (b * (s - 1))
    if getattr(config, "moe_experts", 0):
        # mean aux per (layer, microbatch), weighted into the objective
        loss = loss + (getattr(config, "moe_aux_weight", 0.01)
                       * moe_aux / (num_micro * config.num_layers))
    return loss


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def sgd_init(params):
    return jax.tree.map(jnp.zeros_like, params)


def build_spmd_train_step(
    config: GPTConfig,
    mesh: Mesh,
    batch_size: int,
    seq_len: int,
    num_micro: int | None = None,
    lr: float = 1e-3,
    momentum: float = 0.9,
    zero_stage: int = 0,
    comm_quant=None,
    dtype=jnp.float32,
):
    """Returns (jitted step, params, opt_state, example (ids, labels)).

    ``dtype`` is the parameter / momentum dtype (:func:`init_params`):
    fp32 by default; GPT-760M at bs8 seq1024 needs ``jnp.bfloat16`` state
    to fit a 16 GB chip (the loss math is fp32 either way).

    The step is jit-compiled over the mesh with full in/out shardings and
    donated state: ``step(params, momentum, ids, labels) -> (params, momentum,
    loss)``. ``zero_stage`` 1-3 shards optimizer state (and for 3, params)
    over the dp axis — see :func:`zero_shardings`.

    ``comm_quant`` ("int8" or a ``CommQuantConfig``) replaces the implicit
    GSPMD gradient allreduce over ``dp`` with the EXPLICIT int8 quantized
    ring of ``distributed.compressed_collectives``: per-replica gradients
    are computed stacked (``vmap`` over the dp-sharded replica dim, the
    model math running with ``dp_axis=None``), bucketed, ring-reduced with
    deterministic per-hop requantization and decoded identically on every
    replica — ~4x fewer gradient bytes on the interconnect. With
    ``zero_stage >= 2`` the decoded gradient feeds the dp-sharded state
    update (GSPMD slices the replicated decode into the reduce-scattered
    consumption — same bytes, ZeRO placements preserved).
    """
    from ..distributed.compressed_collectives import (
        as_comm_quant_config, quantized_all_reduce_pytree)

    num_micro = num_micro or max(1, 2 * mesh.shape["pp"])
    assert batch_size % num_micro == 0
    dp = mesh.shape["dp"]
    cq = as_comm_quant_config(comm_quant)
    use_cq = cq is not None and dp > 1
    if use_cq:
        if batch_size % (dp * num_micro):
            raise ValueError(
                f"comm_quant needs batch_size {batch_size} divisible by "
                f"dp * num_micro = {dp} * {num_micro}")

    if getattr(config, "moe_experts", 0):
        ep = mesh.shape.get("ep", 1) if "ep" in mesh.axis_names else 1
        if ep > 1 and config.moe_experts % ep:
            raise ValueError(
                f"moe_experts={config.moe_experts} must divide the ep "
                f"mesh axis ({ep}) — each ep shard owns whole experts")
        if getattr(config, "fused_mlp", False):
            raise ValueError(
                "fused_mlp has no MoE path — the fused MLP kernels are "
                "dense-only (disable fused_mlp for moe_experts > 0)")
    with phase("weights.make") as made:
        params = init_params(config, mesh, dtype=dtype)
        made.end_when_ready(params)
    if zero_stage:
        p_shard, m_shard = zero_shardings(params, mesh, zero_stage)
    else:
        p_shard = m_shard = param_shardings(mesh, params)
    with phase("weights.place"):
        params = jax.device_put(params, p_shard)
        mom = jax.device_put(sgd_init(params), m_shard)
    data_shard = NamedSharding(mesh, P("dp", None))

    def sync_grads(params, ids, labels):
        """(loss, synced grads): implicit GSPMD allreduce, or the explicit
        int8 quantized ring when comm_quant is on."""
        if not use_cq:
            return jax.value_and_grad(loss_fn)(
                params, ids, labels, config, mesh, num_micro)
        # explicit dp sync: stack the batch replica-major, compute each
        # replica's local gradient under vmap (dp_axis=None — the stacked
        # dim carries the dp sharding), then ring-reduce int8 chunks
        st = NamedSharding(mesh, P("dp", None, None))
        ids_st = lax.with_sharding_constraint(
            ids.reshape(dp, batch_size // dp, seq_len), st)
        lbl_st = lax.with_sharding_constraint(
            labels.reshape(dp, batch_size // dp, seq_len), st)

        def local_grad(i, l):
            return jax.value_and_grad(loss_fn)(
                params, i, l, config, mesh, num_micro, None)

        losses, g_st = jax.vmap(local_grad)(ids_st, lbl_st)
        g_st = jax.tree.map(
            lambda g: lax.with_sharding_constraint(
                g, NamedSharding(mesh, P("dp"))), g_st)
        grads = quantized_all_reduce_pytree(g_st, mesh=mesh, axis="dp",
                                            cfg=cq, mean=True)
        return jnp.mean(losses), grads

    def step(params, mom, ids, labels):
        loss, grads = sync_grads(params, ids, labels)
        with step_scope("optimizer"):
            mom2 = jax.tree.map(lambda m, g: momentum * m + g, mom, grads)
            params2 = jax.tree.map(lambda p, m: p - lr * m, params, mom2)
        return params2, mom2, loss

    with phase("step.build"):
        jitted_inner = jit32(
            step,
            in_shardings=(p_shard, m_shard, data_shard, data_shard),
            out_shardings=(p_shard, m_shard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )

    # round-15 telemetry on the library-wide registry (off by default;
    # observability.enable_metrics() turns it on): step counter, host
    # dispatch seconds, and the analytic per-replica dp gradient-sync
    # wire bytes per step (the round-14 bytes_on_the_wire ring model,
    # labeled by wire dtype) — so a long training run's interconnect
    # spend is a snapshot read, not a post-hoc estimate
    from ..distributed.compressed_collectives import bytes_on_the_wire
    from ..observability import default_registry, monotonic, tracing_active
    from ..observability import span as _span

    _m_steps = default_registry.counter(
        "train_steps", "spmd train-step invocations")
    _m_host_s = default_registry.counter(
        "train_dispatch_seconds", "host seconds dispatching train steps")
    _m_wire = default_registry.counter(
        "train_wire_bytes", "per-replica dp gradient-sync wire bytes",
        labels=("quant",)).labels(quant="int8" if use_cq else "fp")
    wire_per_step = 0
    if dp > 1:
        wire_per_step = sum(
            bytes_on_the_wire(int(np.prod(l.shape)), int(dp),
                              elem_bytes=jnp.dtype(l.dtype).itemsize,
                              quant=cq if use_cq else None)
            for l in jax.tree.leaves(params))

    # the first call through the jit traces + XLA-compiles (seconds);
    # charging that to "dispatch seconds" would make the per-step read
    # compile-dominated, so the timer starts at the second call
    _compiled = [False]

    def jitted(*args):
        # metrics (registry) and tracing (profiler window) are
        # independent toggles: profiling a training run must record the
        # span even with the registry off, and vice versa
        if not (default_registry.enabled or tracing_active()):
            with jax.set_mesh(mesh):
                out = jitted_inner(*args)
            _compiled[0] = True
            return out
        t0 = monotonic()
        with _span("spmd_train_step", category="train"):
            with jax.set_mesh(mesh):
                out = jitted_inner(*args)
        _m_steps.inc()
        if _compiled[0]:
            _m_host_s.inc(monotonic() - t0)
        _compiled[0] = True
        _m_wire.inc(wire_per_step)
        return out

    jitted.lower = jitted_inner.lower
    # abstract tracers (analysis/jaxpr_checks) cannot call ``jitted`` itself:
    # jax refuses ``set_mesh`` under a trace
    jitted.jit, jitted.mesh = jitted_inner, mesh
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, config.vocab_size, (batch_size, seq_len)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, config.vocab_size, (batch_size, seq_len)), jnp.int32)
    ids = jax.device_put(ids, data_shard)
    labels = jax.device_put(labels, data_shard)
    return jitted, params, mom, (ids, labels)
