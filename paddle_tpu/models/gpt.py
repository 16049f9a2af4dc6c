"""GPT model family — the flagship benchmark model.

Architecture parity: the reference's fleet GPT test models
(test/collective/fleet/hybrid_parallel_pp_transformer.py,
hybrid_parallel_mp_model.py) and the GPT-3 paper sizes named in BASELINE.md.
Pre-LN decoder blocks, learned positional embeddings, GELU MLP (4x), causal
self-attention through ``F.scaled_dot_product_attention`` (flash-attention
Pallas kernel on TPU when available).

Tensor parallelism: with ``mp_degree > 1`` (or fleet initialised), qkv/out and
mlp projections become Column/RowParallelLinear and the token embedding
VocabParallelEmbedding — the Megatron layout (reference: fleet/layers/mpu/
mp_layers.py:47,:333,:540) where GSPMD emits the collectives.
"""
from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass

from ..framework.jit32 import jit32
from ..framework.param_attr import ParamAttr
from ..nn import Layer, functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import LayerNorm
from ..observability.tracing import phase
from ..tensor.creation import arange
from ..tensor.manipulation import concat, reshape
from ..tensor.math import matmul


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    intermediate_size: int | None = None  # default 4*hidden
    hidden_dropout: float = 0.0
    attn_dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_flash_attention: bool = True
    # run the Pallas kernel in interpret mode off-TPU too (CPU-mesh tests of
    # the sharded kernel path; never set in production configs)
    force_flash: bool = False
    # fused MLP-block Pallas kernels (ops/pallas/fused_mlp): single-pass
    # LN (+ residual-in/out) and bias+gelu epilogues replace the XLA
    # elementwise chains in the decoder block — the round-5 roofline's
    # ~20 ms/step of LN/gelu/residual HBM round-trips. bench.py flips this
    # via --fused-mlp; off by default until the on-chip A/B confirms it.
    fused_mlp: bool = False
    # run the fused MLP kernels in interpret mode off-TPU too (CPU tests)
    force_fused_mlp: bool = False
    # parallel knobs
    tensor_parallel: bool = False  # force TP layers even without fleet
    recompute: bool = False  # rematerialize blocks in backward (activation
    # memory ~O(layers*s*h) instead of O(layers*s*4h stacks))
    remat_save_attn: bool = True  # under recompute, also save the flash
    # kernel's o/lse (backward skips the attention re-forward for
    # ~layers*s*h*2B extra residency); memory-edge configs (1.3B on 16 GB)
    # set False to keep the smaller footprint
    remat_save_ln: bool = False  # under recompute, also save both LN
    # outputs per layer (2*layers*s*h*2B extra residency, ~1.2 GB at 760M
    # bs8): backward skips the LN re-forward (mean/var/normalize passes)
    # perf-attribution ablations (perf_breakdown.py only — differential
    # timing of step phases; never set in training configs): any of
    # {"attn", "mlp", "ce"} ("ce" keeps the lm-head matmul, drops the
    # softmax-CE math)
    ablate: tuple = ()
    # round-10 quantized serving: "int8"/"int4" quantizes the decoder
    # matmul weight stacks at serving-params extraction (fused weight-only
    # Pallas GEMM keeps them quantized in HBM); None serves fp. Group size
    # -1 = per-output-channel scales, > 0 = per-group along the in-dim.
    weight_dtype: str | None = None
    weight_quant_group_size: int = -1
    # "int8" stores the paged KV cache int8 with per-(page-slot, head)
    # scales: quantize-on-write in the unified step, dequant fused in the
    # ragged attention kernel. None keeps the compute-dtype pools.
    kv_cache_dtype: str | None = None
    # round-12 speculative decoding: > 0 verifies up to this many n-gram
    # draft tokens per decode lane per unified step (1 + k query rows
    # through the ragged attention, fused in-jit accept epilogue emitting
    # the accepted prefix + one bonus token). 0 = plain decode. The value
    # is BUILD geometry (the step's output is [batch, k + 1]); per-request
    # adaptive k varies only the spec_len inputs, never the shape.
    spec_decode_k: int = 0
    # round-19 model-based speculative drafting: > 0 selects the truncated-
    # layer SELF-DRAFT proposer for serving (the first spec_draft_layers
    # layers of the SAME serving stack — shared embeddings/positional
    # table/final LN/LM head, zero extra weights to load — run as their own
    # small fixed-shape unified-step jit over a dedicated draft KV pool,
    # proposing spec_decode_k tokens autoregressively per decode lane).
    # 0 keeps the round-12 n-gram proposer. Must be < num_layers (a full-
    # depth "draft" would just run the target twice — rejected loudly).
    spec_draft_layers: int = 0
    # round-25 Mixture-of-Experts: moe_experts > 0 replaces every block's
    # dense MLP with a top-k routed expert FFN (models/moe.py — capacity
    # clamping drops overflow token-choices onto the residual, ragged
    # grouped Pallas GEMM streams only the routed experts' tiles).
    # Serving runs through the unified step; training shards the expert
    # stacks over the optional "ep" mesh axis (gpt_spmd +
    # distributed/mesh.py).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01  # load-balance loss weight (training)

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def num_params(self) -> int:
        h, v, l = self.hidden_size, self.vocab_size, self.num_layers
        f, e = self.ffn_size, self.moe_experts
        if e:
            # router gate + E stacked expert FFNs replace the dense MLP
            mlp = h * e + e * (2 * h * f + h + f)
        else:
            mlp = 2 * h * f + h + f
        per_layer = 4 * h * h + 4 * h + mlp + 4 * h
        emb = v * h + self.max_seq_len * h
        return emb + l * per_layer + 2 * h


# GPT-3 paper table 2.1 sizes (the BASELINE.md benchmark ladder).
GPT_CONFIGS: dict[str, GPTConfig] = {
    "gpt3-tiny": GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4, max_seq_len=128),
    "gpt3-125m": GPTConfig(hidden_size=768, num_layers=12, num_heads=12),
    "gpt3-350m": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt3-760m": GPTConfig(hidden_size=1536, num_layers=24, num_heads=16),
    "gpt3-1.3b": GPTConfig(hidden_size=2048, num_layers=24, num_heads=32, max_seq_len=2048),
    "gpt3-2.7b": GPTConfig(hidden_size=2560, num_layers=32, num_heads=32, max_seq_len=2048),
    "gpt3-6.7b": GPTConfig(hidden_size=4096, num_layers=32, num_heads=32, max_seq_len=2048),
    "gpt3-13b": GPTConfig(hidden_size=5120, num_layers=40, num_heads=40, max_seq_len=2048),
}


def _w(config: GPTConfig) -> ParamAttr:
    """GPT init: N(0, initializer_range) on all weight matrices (the paper's
    scheme; the reference test models use Normal(std=0.02) likewise)."""
    return ParamAttr(initializer=Normal(mean=0.0, std=config.initializer_range))


from ._tp import tp_enabled as _tp_enabled  # noqa: E402 (shared TP wiring)


def _linear(config, in_f, out_f, kind):
    """kind: 'col' | 'row' | 'plain' — GPT linears keep their biases."""
    from ._tp import tp_linear

    return tp_linear(config, in_f, out_f, kind, _w(config), has_bias=True)


class GPTEmbeddings(Layer):
    """Token + learned position embeddings with dropout."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        if _tp_enabled(config):
            from ..distributed.fleet.meta_parallel.mp_layers import VocabParallelEmbedding

            self.word_embeddings = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size, weight_attr=_w(config)
            )
        else:
            self.word_embeddings = Embedding(
                config.vocab_size, config.hidden_size, weight_attr=_w(config)
            )
        self.position_embeddings = Embedding(
            config.max_seq_len, config.hidden_size, weight_attr=_w(config)
        )
        self.dropout = Dropout(config.hidden_dropout)

    def forward(self, input_ids, position_ids=None, past_len: int = 0):
        if position_ids is None:
            seq_len = input_ids.shape[-1]
            position_ids = arange(past_len, past_len + seq_len, dtype="int64")
        return self.dropout(
            self.word_embeddings(input_ids)
            + self.position_embeddings(position_ids)
        )


class GPTAttention(Layer):
    """Causal multi-head self-attention (fused qkv projection)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.qkv_proj = _linear(config, h, 3 * h, "col")
        self.out_proj = _linear(config, h, h, "row")
        self.attn_dropout = config.attn_dropout
        self.resid_dropout = Dropout(config.hidden_dropout)

    def forward(self, x, attn_mask=None, cache=None):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)  # [b, s, 3h]
        qkv = reshape(qkv, [b, s, 3, cfg.num_heads, cfg.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [b, s, nh, hd]
        new_cache = None
        past_len = 0
        if cache is not None:
            k_past, v_past = cache
            if k_past is not None:
                past_len = k_past.shape[1]
                k = concat([k_past, k], axis=1)
                v = concat([v_past, v], axis=1)
            new_cache = (k, v)
        # causal handles the cached-prefix case too: _sdpa_ref offsets the
        # tril by (k_len - q_len), i.e. query t attends keys <= past_len + t.
        causal = attn_mask is None and s > 1
        out = F.scaled_dot_product_attention(
            q, k, v,
            attn_mask=attn_mask,
            is_causal=causal,
            dropout_p=self.attn_dropout if self.training else 0.0,
        )  # [b, s, nh, hd]
        out = reshape(out, [b, s, cfg.num_heads * cfg.head_dim])
        out = self.resid_dropout(self.out_proj(out))
        if cache is not None:
            return out, new_cache
        return out


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        h, f = config.hidden_size, config.ffn_size
        self.fc1 = _linear(config, h, f, "col")
        self.fc2 = _linear(config, f, h, "row")
        self.dropout = Dropout(config.hidden_dropout)

    def forward(self, x):
        if _fused_mlp_on(self.config):
            from ..incubate.nn import functional as FI

            # bias+gelu ride ONE Pallas epilogue kernel after the GEMM
            y = FI.fused_bias_gelu(
                matmul(x, self.fc1.weight), self.fc1.bias,
                use_pallas=True if self.config.force_fused_mlp else None)
            return self.dropout(self.fc2(y))
        return self.dropout(self.fc2(F.gelu(self.fc1(x), approximate=True)))


def _fused_mlp_on(config: GPTConfig) -> bool:
    # under TP the block runs global-view with mp-sharded weights; GSPMD
    # cannot partition a pallas_call, so the fused path is single-shard only
    return getattr(config, "fused_mlp", False) and not _tp_enabled(config)


class GPTDecoderLayer(Layer):
    """Pre-LN transformer decoder block."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.ln_1 = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        if getattr(config, "moe_experts", 0):
            from .moe import GPTMoE

            self.mlp = GPTMoE(config)
        else:
            self.mlp = GPTMLP(config)

    def forward(self, x, attn_mask=None, cache=None):
        if _fused_mlp_on(self.config):
            return self._forward_fused(x, attn_mask=attn_mask, cache=cache)
        if cache is not None:
            a, new_cache = self.attn(self.ln_1(x), attn_mask=attn_mask, cache=cache)
            x = x + a
            x = x + self.mlp(self.ln_2(x))
            return x, new_cache
        x = x + self.attn(self.ln_1(x), attn_mask=attn_mask)
        x = x + self.mlp(self.ln_2(x))
        return x

    def _forward_fused(self, x, attn_mask=None, cache=None):
        """Fused-kernel block: LN1 single-pass, then the attention branch's
        residual add + LN2 in ONE residual-in/residual-out kernel."""
        from ..incubate.nn import functional as FI

        cfg = self.config
        uk = True if cfg.force_fused_mlp else None
        y1 = FI.fused_layer_norm(x, self.ln_1.weight, self.ln_1.bias,
                                 epsilon=cfg.layer_norm_eps, use_pallas=uk)
        new_cache = None
        if cache is not None:
            a, new_cache = self.attn(y1, attn_mask=attn_mask, cache=cache)
        else:
            a = self.attn(y1, attn_mask=attn_mask)
        # s = x + a (residual-out) and y2 = LN(s), one kernel
        y2, s = FI.fused_ln_residual(a, x, self.ln_2.weight, self.ln_2.bias,
                                     epsilon=cfg.layer_norm_eps, use_pallas=uk)
        x = s + self.mlp(y2)
        if cache is not None:
            return x, new_cache
        return x


class GPTModel(Layer):
    """Embeddings + decoder stack + final LN."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = LayerList([GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.ln_f = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, attn_mask=None, caches=None):
        past_len = 0
        if caches is not None and caches[0][0] is not None:
            past_len = caches[0][0].shape[1]
        x = self.embeddings(input_ids, position_ids, past_len=past_len)
        new_caches = [] if caches is not None else None
        use_recompute = (getattr(self.config, "recompute", False)
                         and self.training and caches is None)
        if use_recompute:
            from ..distributed.fleet.utils import recompute

        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, attn_mask=attn_mask, cache=caches[i])
                new_caches.append(c)
            elif use_recompute:
                x = recompute(layer, x, attn_mask=attn_mask)
            else:
                x = layer(x, attn_mask=attn_mask)
        x = self.ln_f(x)
        if caches is not None:
            return x, new_caches
        return x

    def generate(self, input_ids, max_new_tokens=20, **kw):
        """Greedy decoding over the paged KV cache with the tied-embedding
        LM head — see :func:`generate_paged`."""
        return generate_paged(self, input_ids, max_new_tokens, **kw)


class GPTPretrainingCriterion(Layer):
    """Shifted next-token cross-entropy (mean over tokens)."""

    def forward(self, logits, labels):
        # logits [b, s, v], labels [b, s]
        loss = F.cross_entropy(
            reshape(logits, [-1, logits.shape[-1]]),
            reshape(labels, [-1]),
            reduction="mean",
        )
        return loss


class GPTForCausalLM(Layer):
    """GPTModel + LM head (weight-tied by default) + optional loss."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        with phase("weights.make") as made:
            self.gpt = GPTModel(config)
            if not config.tie_word_embeddings:
                self.lm_head = Linear(
                    config.hidden_size, config.vocab_size,
                    weight_attr=_w(config), bias_attr=False,
                )
            made.end_when_ready([p._data for p in self.parameters()])
        self.criterion = GPTPretrainingCriterion()

    def _logits(self, hidden):
        if self.config.tie_word_embeddings:
            w = self.gpt.embeddings.word_embeddings.weight  # [v, h]
            return matmul(hidden, w, transpose_y=True)
        return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, position_ids=None, attn_mask=None, caches=None):
        if caches is not None:
            hidden, new_caches = self.gpt(
                input_ids, position_ids=position_ids, attn_mask=attn_mask, caches=caches
            )
            return self._logits(hidden), new_caches
        hidden = self.gpt(input_ids, position_ids=position_ids, attn_mask=attn_mask)
        logits = self._logits(hidden)
        if labels is None:
            return logits
        # standard LM shift: predict token t+1 from prefix ..t
        shift_logits = logits[:, :-1, :]
        shift_labels = labels[:, 1:]
        return self.criterion(shift_logits, shift_labels)

    def generate(self, input_ids, max_new_tokens=20, **kw):
        """Greedy autoregressive decoding over the paged KV cache — see
        :func:`generate_paged`."""
        return generate_paged(self, input_ids, max_new_tokens, **kw)


# ---------------------------------------------------------------------------
# Round-7 serving path: paged KV cache + fixed-shape decode step.
#
# The autoregressive analog of gpt_spmd's training step: pure functions over
# a params pytree EXTRACTED from the Layer model (one-time, zero-copy on the
# underlying arrays), so prefill compiles as ONE jit and every decode step
# replays ONE fixed-shape jit — no per-token Python dispatch, no retrace
# (MPK's whole-step-as-one-program argument, arxiv 2512.22219). K/V live in
# the paged pool managed by inference.kv_cache.KVCacheManager and attention
# over the ragged batch runs the Pallas paged decode kernel
# (ops/pallas/paged_attention, arxiv 2604.15464).
# ---------------------------------------------------------------------------


# the ONE per-layer weight table: serving_params' stacks AND the params
# cache's staleness walk both derive from it, so adding a per-layer weight
# cannot desync the cache oracle from the extraction
_SRV_LAYER_WEIGHTS = (
    ("ln1_g", lambda l: l.ln_1.weight), ("ln1_b", lambda l: l.ln_1.bias),
    ("wqkv", lambda l: l.attn.qkv_proj.weight),
    ("bqkv", lambda l: l.attn.qkv_proj.bias),
    ("wo", lambda l: l.attn.out_proj.weight),
    ("bo", lambda l: l.attn.out_proj.bias),
    ("ln2_g", lambda l: l.ln_2.weight), ("ln2_b", lambda l: l.ln_2.bias),
    ("w1", lambda l: l.mlp.fc1.weight), ("b1", lambda l: l.mlp.fc1.bias),
    ("w2", lambda l: l.mlp.fc2.weight), ("b2", lambda l: l.mlp.fc2.bias),
)

# MoE blocks swap the dense-MLP rows for the stacked expert tree (the
# [E, ...] stacks gain the usual leading [L] dim at extraction)
_SRV_MOE_WEIGHTS = (
    ("moe_gate", lambda l: l.mlp.gate_weight),
    ("moe_w1", lambda l: l.mlp.w1), ("moe_b1", lambda l: l.mlp.b1),
    ("moe_w2", lambda l: l.mlp.w2), ("moe_b2", lambda l: l.mlp.b2),
)
_DENSE_MLP_KEYS = ("w1", "b1", "w2", "b2")


def _srv_layer_weight_table(config):
    if getattr(config, "moe_experts", 0):
        return tuple(kv for kv in _SRV_LAYER_WEIGHTS
                     if kv[0] not in _DENSE_MLP_KEYS) + _SRV_MOE_WEIGHTS
    return _SRV_LAYER_WEIGHTS


def _srv_nonlayer_weights(model):
    gpt = model.gpt if hasattr(model, "gpt") else model
    ws = [("tok_emb", gpt.embeddings.word_embeddings.weight),
          ("pos_emb", gpt.embeddings.position_embeddings.weight),
          ("lnf_g", gpt.ln_f.weight), ("lnf_b", gpt.ln_f.bias)]
    if getattr(model, "lm_head", None) is not None:
        ws.append(("lm_head", model.lm_head.weight))
    return ws


def _serving_weight_buffers(model):
    """The model's live weight buffers — buffer identity is the staleness
    key for the per-model params cache (an optimizer step rebinds
    ``._data``, so stale ids mean re-extract)."""
    gpt = model.gpt if hasattr(model, "gpt") else model
    bufs = [t._data for _, t in _srv_nonlayer_weights(model)]
    table = _srv_layer_weight_table(gpt.config)
    for l in gpt.layers:
        bufs += [get(l)._data for _, get in table]
    return bufs


def serving_params(model):
    """Extract the serving params pytree from a GPTForCausalLM / GPTModel.

    Per-layer weights stack on a leading [L, ...] dim so the blocks run
    under ``lax.scan`` (one compiled block, not L unrolled copies). The
    stacks are device COPIES (~1x extra weight memory while they live);
    the embeddings / final-LN / lm-head leaves are views of the live
    buffers. ``generate_paged`` caches the extraction per model (see
    :func:`_serving_params_cached`) so repeated calls don't re-stack.
    """
    import jax.numpy as jnp

    gpt = model.gpt if hasattr(model, "gpt") else model
    cfg = gpt.config
    if _tp_enabled(cfg):
        raise NotImplementedError(
            "serving params extract from a single-shard eager model; for "
            "multi-chip serving pass mesh=... to generate_paged / "
            "ServingPredictor (round-11 SPMD serving) instead of enabling "
            "the eager TP layers")

    params = {k: t._data for k, t in _srv_nonlayer_weights(model)}
    params["layers"] = {
        k: jnp.stack([get(l)._data for l in gpt.layers])
        for k, get in _srv_layer_weight_table(cfg)
    }
    return params  # lm_head (when untied) rides _srv_nonlayer_weights


# NOTE: _srv_ln/_srv_mlp are the serving-side pure spellings of the
# decoder block — keep their math in lockstep with the eager Layer
# classes above AND gpt_spmd's _layer_norm/_block_mlp (same
# params-dict key schema); a drift in eps/gelu/LN-stat handling makes
# generate() disagree with the trained model. The fp32 LN statistics here
# are intentional (decode runs the weights' dtype, stats stay fp32).
def _srv_ln(x, g, b, eps):
    import jax

    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    out = ((xf - mu) * jax.lax.rsqrt(var + eps)) * g
    # ``b`` None: a LayerNorm with a weight and no bias (Cohere's)
    return (out if b is None else out + b).astype(x.dtype)


def _srv_norm(config, x, p, name):
    """The block's norm ``name`` (``ln1`` / ``ln2`` / ``lnf``) over the
    weights ``p``, as the configuration has it: RMSNorm with a weight alone
    where it states ``rms_norm_eps``, else LayerNorm, biased where the
    weights hold a bias."""
    if hasattr(config, "rms_norm_eps"):
        from .deepseek_v2 import rms_norm

        return rms_norm(x, p[name + "_g"], config.rms_norm_eps)
    return _srv_ln(x, p[name + "_g"], p.get(name + "_b"),
                   config.layer_norm_eps)


def _srv_logits(params, h):
    """h [..., hidden] -> logits [..., vocab] (tied head unless lm_head)."""
    import jax.numpy as jnp

    if "lm_head" in params:
        return h @ params["lm_head"]
    return jnp.einsum("...h,vh->...v", h, params["tok_emb"])


def _srv_mm(y, w, use_kernel=None):
    """The serving matmul: fp weights ride the plain dot; quantized stacks
    (``{"q": int8|packed-int4, "s": scales}`` — see inference/quantize.py)
    ride the fused weight-only Pallas GEMM, staying quantized in HBM.
    ``use_kernel`` follows the paged-attention contract (None = kernel on
    TPU / jnp oracle elsewhere; True forces interpret mode — CPU tests;
    False forces the dequant-matmul reference)."""
    if isinstance(w, dict):
        from ..ops.pallas.quant_matmul import quant_matmul

        return quant_matmul(y, w["q"], w["s"], use_kernel=use_kernel)
    return y @ w


def _srv_psum(x, axis):
    """The serving collective hook: under the mp mesh the row-parallel
    matmul partials all-reduce here; single-chip (axis None) it is the
    identity — ONE spelling of the block math serves both paths."""
    import jax

    return jax.lax.psum(x, axis) if axis else x


def _srv_mlp(p, y, use_kernel=None, axis=None):
    import jax

    return (_srv_psum(
        _srv_mm(jax.nn.gelu(_srv_mm(y, p["w1"], use_kernel) + p["b1"],
                            approximate=True), p["w2"], use_kernel), axis)
            + p["b2"])


def _srv_moe(config, p, y, use_kernel=None, valid=None):
    """The serving MoE FFN: the SAME :func:`models.moe.moe_ffn` the eager
    oracle runs, over the packed token rows. ``valid`` (tok_slot >= 0 in
    the unified step) keeps padding rows out of the capacity race — they
    route nowhere and output zero. Expert stacks are replicated under the
    mp mesh (``serving_param_specs`` P() fallback), so there is no psum:
    each chip computes the full MoE output redundantly — acceptable for
    the per-op path this round (experts are small relative to KV)."""
    lead = y.shape[:-1]
    tokens = y.reshape(-1, y.shape[-1])
    v = None if valid is None else valid.reshape(-1)
    from .moe import moe_ffn

    out, _aux = moe_ffn(
        tokens, p["moe_gate"], p["moe_w1"], p["moe_b1"], p["moe_w2"],
        p["moe_b2"], top_k=config.moe_top_k,
        capacity_factor=config.moe_capacity_factor,
        use_kernel=use_kernel, valid=v)
    return out.reshape(*lead, out.shape[-1])


def _srv_ffn(config, p, y, use_kernel=None, axis=None, valid=None,
             layer=None, route=None):
    """Block FFN dispatch — the ONE switch every serving builder goes
    through. Returns ``(out, rows)``: ``rows [2, E]`` int32, how many rows
    each expert of a dropless routed layer received and whether it received
    any, else ``None``. By what the
    layer's weights ``p`` hold: routed gated experts beside shared ones
    (``moe_w_gu``; ``models/deepseek_v2.py``), a dense gated SiLU MLP
    (``w_gu``), the GShard-style ``_srv_moe``, or the biased GELU
    ``_srv_mlp``. ``layer``: the expert stacks in ``p`` are whole, and this
    is layer ``layer`` of them (``deepseek_v2.STACKED_BY_INDEX``).
    ``route``: the rows the router reads where they are not ``y``."""
    if "moe_w_gu" in p:
        from .deepseek_v2 import routed_ffn

        return routed_ffn(config, p, y, use_kernel, valid=valid,
                          with_counts=True, layer=layer, route=route)
    if "w_gu" in p:
        from .deepseek_v2 import gated_mlp

        return gated_mlp(y, p["w_gu"], p["w_d"]), None
    if getattr(config, "moe_experts", 0):
        return _srv_moe(config, p, y, use_kernel, valid=valid), None
    return _srv_mlp(p, y, use_kernel, axis), None


def _split_qkv(qkv, nh, hd, head_major, nkv=None):
    """[..., 3*nh*hd] -> (q, k, v) each [..., nh, hd]. The eager layout
    orders the fused projection's columns [3, nh, hd]; the mesh layout is
    HEAD-MAJOR [nh, 3, hd] (``shard_serving_params`` permutes the columns)
    so a contiguous mp shard owns whole heads. Both splits read the same
    dot products — bit-identical outputs, only column order moves.
    GROUPED queries (``nkv`` key-value heads, fewer than ``nh``): the
    columns are ``[q: nh, hd | k: nkv, hd | v: nkv, hd]`` and k and v come
    back ``[..., nkv, hd]``."""
    lead = qkv.shape[:-1]
    if nkv is not None and nkv != nh:
        assert not head_major, "grouped queries have no head-major layout"
        q, k, v = (qkv[..., :nh * hd], qkv[..., nh * hd:(nh + nkv) * hd],
                   qkv[..., (nh + nkv) * hd:])
        return (q.reshape(*lead, nh, hd), k.reshape(*lead, nkv, hd),
                v.reshape(*lead, nkv, hd))
    if head_major:
        q4 = qkv.reshape(*lead, nh, 3, hd)
        return q4[..., 0, :], q4[..., 1, :], q4[..., 2, :]
    q4 = qkv.reshape(*lead, 3, nh, hd)
    return q4[..., 0, :, :], q4[..., 1, :, :], q4[..., 2, :, :]


# ---------------------------------------------------------------------------
# Round-11 multi-chip SPMD serving: Megatron tensor-parallel layout for the
# serving pytree over a Mesh(("mp",)). Column-parallel stacks (wqkv, w1 —
# qkv permuted head-major first) shard their output dim, row-parallel
# stacks (wo, w2) their input dim; embeddings / LM head / LN / row biases
# stay replicated. The KV page pools and their int8 scale planes shard on
# the HEAD axis (each chip owns its heads' pages end to end — zero KV
# bytes on the wire); the only collectives in a serving step are the two
# row-parallel psums per layer (_srv_psum).
# ---------------------------------------------------------------------------


def _head_major_perm(nh, hd):
    """Column permutation taking the fused qkv projection's [3, nh, hd]
    output order to [nh, 3, hd] — whole heads become contiguous so the mp
    axis shards them (a contiguous chunk of the eager layout would split
    the q/k/v thirds, not the heads)."""
    import numpy as np

    return np.arange(3 * nh * hd).reshape(3, nh, hd).transpose(
        1, 0, 2).reshape(-1)


def serving_param_specs(params, axis="mp"):
    """PartitionSpec tree mirroring a serving params pytree (fp or
    quantized) — the serving twin of ``gpt_spmd.param_specs``. Quantized
    ``{"q", "s"}`` stacks shard with their weight: column scales follow
    the output dim; row (K-sharded) group scales shard over the group dim,
    per-channel row scales replicate (each chip's partial product scales
    by the same output-channel factor before the psum)."""
    from jax.sharding import PartitionSpec as P

    col = {"wqkv", "w1"}
    row = {"wo", "w2"}
    cbias = {"bqkv", "b1"}

    def stack_spec(key, leaf):
        if key in col:
            if isinstance(leaf, dict):
                return {"q": P(None, None, axis), "s": P(None, None, axis)}
            return P(None, None, axis)
        if key in row:
            if isinstance(leaf, dict):
                s_spec = (P(None, axis, None) if leaf["s"].shape[1] > 1
                          else P())
                return {"q": P(None, axis, None), "s": s_spec}
            return P(None, axis, None)
        if key in cbias:
            return P(None, axis)
        return P()

    out = {k: P() for k in params if k != "layers"}
    out["layers"] = {k: stack_spec(k, v)
                     for k, v in params["layers"].items()}
    return out


def shard_serving_params(params, mesh, config):
    """Lay a serving params pytree (fp or quantized) out over the mp mesh:
    permute wqkv/bqkv head-major, validate divisibility, and device_put
    every leaf under :func:`serving_param_specs`. Returns a NEW pytree of
    committed sharded arrays (the unsharded source stays usable)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..inference.quantize import assert_quant_shardable

    mp = int(mesh.shape["mp"])
    nh, hd = config.num_heads, config.head_dim
    nkv = getattr(config, "num_kv_heads", None) or nh
    if nkv != nh:
        raise NotImplementedError(
            f"grouped queries ({nh} query heads over {nkv} key-value heads) "
            "are not laid out over the mp mesh yet: the head-major "
            "permutation of wqkv assumes as many key-value heads as query "
            "heads")
    if nh % mp:
        raise ValueError(
            f"the mp mesh size {mp} must divide num_heads {nh} "
            "(heads shard whole)")
    if config.ffn_size % mp:
        raise ValueError(
            f"the mp mesh size {mp} must divide ffn_size {config.ffn_size}")
    assert_quant_shardable(params["layers"], mp,
                           getattr(config, "weight_dtype", None))
    perm = jnp.asarray(_head_major_perm(nh, hd))

    def permute(leaf):
        if isinstance(leaf, dict):
            return {"q": leaf["q"][..., perm], "s": leaf["s"][..., perm]}
        return leaf[..., perm]

    layers = dict(params["layers"])
    layers["wqkv"] = permute(layers["wqkv"])
    layers["bqkv"] = layers["bqkv"][..., perm]
    out = dict(params)
    out["layers"] = layers
    specs = serving_param_specs(out)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(out, shardings)


def _mesh_mp(mesh):
    """(mp degree, psum axis name or None) for a serving mesh argument."""
    if mesh is None:
        return 1, None
    return int(mesh.shape["mp"]), "mp"


# KV pool / scale-plane PartitionSpecs under the serving mesh: pools are
# [L, num_pages, kv_heads, page_size, head_dim] (scales drop the trailing
# head_dim) — the HEAD axis shards, so every chip owns its heads' pages
# (and their scales) end to end: quantize-on-write, CoW copies and prefix
# reuse all stay chip-local, zero KV bytes cross the interconnect.
def _kv_specs():
    from jax.sharding import PartitionSpec as P

    return P(None, None, "mp", None, None), P(None, None, "mp", None)


def _sample_epilogue(logits, keys, temperature, top_k, top_p):
    """Seeded temperature / top-k / top-p sampling, fused into the unified
    step (one [batch, vocab] sort + categorical — no host round-trip).

    logits: [b, v] fp32; keys: [b, 2] uint32 per-lane PRNG keys;
    temperature/top_p: [b] f32; top_k: [b] i32 (<= 0 disables the k
    filter, top_p outside (0, 1) disables the p filter). Ties at the k-th
    /p-th value all stay in the candidate set. Returns sampled ids [b]
    int32 — the caller selects argmax instead wherever temperature == 0.
    """
    import jax
    import jax.numpy as jnp

    v = logits.shape[-1]
    t = jnp.maximum(temperature, 1e-6).astype(jnp.float32)
    scaled = (logits / t[:, None]).astype(jnp.float32)
    sorted_desc = -jnp.sort(-scaled, axis=-1)                 # [b, v]
    k = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=1)
    keep = scaled >= kth
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum_exclusive = jnp.cumsum(probs, axis=-1) - probs
    p_active = (top_p > 0.0) & (top_p < 1.0)
    # tokens whose preceding cumulative mass is < p stay (>= 1 survivor)
    n_keep = jnp.maximum(
        jnp.sum((cum_exclusive < top_p[:, None]).astype(jnp.int32),
                axis=-1), 1)
    n_keep = jnp.where(p_active, n_keep, v).astype(jnp.int32)
    pth = jnp.take_along_axis(sorted_desc, (n_keep - 1)[:, None], axis=1)
    keep &= scaled >= pth
    masked = jnp.where(keep, scaled, jnp.float32(-1e30))
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return sampled.astype(jnp.int32)


#: chunks beside the decode rows at the rungs under the budget: each rung is
#: the whole step traced, lowered and compiled once more (set-up time), so
#: there are two, the decode-only step's and one for a step with a few chunks
STEP_ROW_RUNG_CHUNKS = (0, 2)


def step_row_ladder(lanes: int, spec_k: int, chunk: int, budget: int):
    """The row counts ONE serving step program runs at (``build_unified_step``
    picks a rung per step, inside the program, from ``q_lens``;
    ``ServingPredictor`` counts the rung the same way): ascending, the last
    one ``budget``. The rungs stand where the scheduler's steps land: a
    decode-only step holds at most ``lanes * (1 + spec_k)`` rows (rounded up
    to the 16 rows of a bf16 tile), and every lane still feeding its prompt
    adds at most ``chunk`` rows in place of its decode rows. So a rung is
    the decode rows plus ``k`` chunks, ``k`` of ``STEP_ROW_RUNG_CHUNKS``,
    where that stays under the budget. One rung (a budget no larger than the
    decode rows): a program without a conditional."""
    decode = -(-lanes * (1 + spec_k) // 16) * 16
    rungs = [decode + k * chunk for k in STEP_ROW_RUNG_CHUNKS]
    return tuple(r for r in rungs if r < budget) + (budget,)


def step_row_rung(ladder, rows):
    """Index of the smallest rung of ``ladder`` that holds ``rows`` rows (an
    ``int`` on the host, a traced scalar inside the program: one spelling
    for both)."""
    return sum((rows > r) * 1 for r in ladder[:-1])


def build_unified_step(config: GPTConfig, page_size: int, chunk: int,
                       use_kernel: bool | None = None,
                       kv_quant: bool = False, mesh=None,
                       spec_k: int = 0):
    """ONE fixed-shape serving step for mixed ragged prefill + decode,
    driven by a per-step TOKEN BUDGET.

    The round-9 replacement for the prefill/decode jit split. The step's
    dense compute (embeddings, qkv/out/mlp matmuls, LNs, logits) runs over
    a PACKED token stream — ``tok_ids[budget]`` with per-token owning slot
    and absolute position — so a step that decodes 7 lanes and prefills a
    9-token chunk spends exactly 16 tokens of matmul, not
    ``batch * chunk``. Only the paged-attention kernel sees the per-slot
    ``[batch, chunk]`` chunk blocks (queries scatter in, outputs gather
    back); every slot contributes 0..chunk tokens per step, causal within
    its chunk, so admission never head-of-line-blocks decode behind a full
    prompt forward.

    Signature::

        fn(params, tok_ids[t], tok_slot[t], tok_pos[t],
           q_lens[b], kv_lens[b], last_idx[b],
           feedback[t], prev_toks[b], emit_mask[b], produced[b],
           k_pages, v_pages,
           page_table[b,pps], cow_src[b], cow_dst[b], base_keys[b,2],
           temperature[b], top_k[b], top_p[b])
        -> (next_toks[b], logits[b,v], k_pages, v_pages)

    ``tok_slot < 0`` marks padding tokens (their writes drop, their rows
    compute garbage nothing reads). ``kv_lens`` counts tokens already
    cached per slot BEFORE this step; ``q_lens`` the tokens it feeds now;
    ``last_idx[b]`` indexes each slot's LAST packed token (sentinel ``t``
    when idle) — the position whose logits become the slot's next-token
    decision, meaningful only when the chunk reaches the end of the
    slot's context (the scheduler knows). Copy-on-write lanes duplicate
    page ``cow_src -> cow_dst`` across every layer before any write
    (``cow_dst == num_pages`` is the no-op sentinel). Greedy lanes
    (``temperature == 0``) take the argmax of the logits; sampling lanes
    run the fused seeded epilogue.
    Every array argument keeps its shape step over step: one trace, one
    executable (``fn.trace_count[0]`` is the gate) — and several ROW COUNTS
    inside it (PR 35): the budget ``t`` is a ceiling, and a step computes
    over the first ``R`` rows alone, ``R`` the smallest rung of
    :func:`step_row_ladder` that holds the rows the host packed
    (``q_lens.sum()``, from row 0 on). The program picks the rung itself from
    ``q_lens``; embedding, projections, attention's operands, FFN, router
    and the write plan all run over ``R`` rows, and what they did for the
    rows left out (``tok_slot == -1``) nothing read. A budget no larger than
    the decode rows has one rung and no conditional (``_at_rung``).

    WHERE THE POOLS LIVE (PR 27): ``k_pages`` / ``v_pages`` (and, int8, the
    scale planes) are stacked ``[num_layers, num_pages, kv_heads, page_size,
    head_dim]`` and stay ONE donated buffer from argument to result. The
    layer scan carries them (its scanned inputs are the stacked weights and
    the layer index, its scanned outputs nothing); layer ``i`` writes its
    new rows into the stack at ``[i, page, :, row]``
    (``paged_write_packed*(layer=i)``; where the kernels run, through the
    Pallas kernel that aliases the stack) and ``ragged_paged_attention(
    layer=i)`` reads layer ``i`` of it through its block index maps. No
    layer's pool is sliced out of the stack or stacked back: as scanned
    inputs and outputs the pools cost five pool-sized copies per layer and
    step and a second copy of both stacks in the program's temp.

    DEVICE-RESIDENT FEEDBACK (round 13, the async engine's enabler):
    ``feedback[t]`` marks packed tokens whose id the HOST DOES NOT KNOW
    YET — the step reads them from ``prev_toks[tok_slot]`` instead of
    ``tok_ids``, where ``prev_toks`` is the previous step's ``next_toks``
    output passed back UNMATERIALIZED. ``next_toks`` is a per-lane CARRY:
    lanes with ``emit_mask[b] != 0`` (the scheduler's completing lanes)
    update it to the token decided this step, everyone else passes
    ``prev_toks`` through — so a lane that skips a step (budget) still
    feeds its latest token next time. The synchronous engine passes
    all-zero ``feedback``/``prev_toks`` and the step degenerates to the
    round-9 behavior bit-for-bit. Sample keys moved ON-DEVICE with the
    same round: the host sends each lane's BASE PRNG key (``base_keys``,
    constant per request) + its tokens-produced count (``produced``) and
    the sampling branch folds them in-jit (vmapped threefry — bit-
    identical to the host-side ``fold_in`` it replaces), so a sampling
    step uploads two tiny arrays instead of deriving per-token keys on
    the host latency path.

    ``kv_quant=True`` (round 10) stores the page pools int8: the signature
    gains ``k_scales``/``v_scales`` (the per-(page-slot, head) fp32 scale
    planes, donated alongside the pools and returned updated), K/V
    quantize on write inside the step (per-token-per-head symmetric) and
    dequantize inside the ragged attention kernel — pages stay int8
    end-to-end, composing with CoW (the copy lanes duplicate scale planes
    too) and prefix caching (a shared page's scales travel with it)::

        fn(params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens, last_idx,
           feedback, prev_toks, emit_mask, produced,
           k_pages, v_pages, k_scales, v_scales, page_table, cow_src,
           cow_dst, base_keys, temperature, top_k, top_p)
        -> (next_toks, logits, k_pages, v_pages, k_scales, v_scales)

    ``mesh`` (round 11) shards the whole step over ``Mesh(("mp",))`` via
    ``shard_map``: params per :func:`serving_param_specs` (qkv head-major
    — see :func:`shard_serving_params`), pools AND scale planes on the
    head axis, so quantize-on-write, the CoW lanes and the ragged
    attention kernel all run chip-local over each chip's heads — the only
    wire traffic is the two row-parallel psums per layer. Embeddings/LM
    head/logits/sampling replicate (every chip computes the identical
    epilogue). Signature, donation of all pools + scale planes, and the
    one-trace-per-geometry guarantee are unchanged.

    ``spec_k > 0`` (round 12) builds the SPECULATIVE step: a decode lane
    may feed ``1 + spec_len[slot]`` packed rows — its last context token
    followed by n-gram draft tokens (``inference/draft.py``) at the next
    positions — and the step verifies them all in the ONE ragged pass
    (per-row causal limits make row i attend the just-written K/V of rows
    < i). The signature gains ``spec_len[b]`` after ``last_idx`` (0 = the
    lane speculates nothing this step — adaptive k varies VALUES, never
    the shape) and ``last_idx`` becomes the lane's FIRST verify row (for
    a plain/prefill lane that is its last packed row, unchanged meaning).
    ``base_keys`` stays ``[b, 2]``: verify row j folds ``produced + j``
    in-jit, so the per-request seeded streams stay bit-identical to
    plain decode. The fused accept epilogue computes logits at rows
    ``last_idx .. last_idx+spec_k``, samples each (greedy argmax on
    temperature-0 lanes, bit-identical to the plain step), and accepts
    drafts while ``draft[i] == sampled[i-1]`` — returning::

        -> (out_ids[b, spec_k+1], n_emit[b], next_toks[b], logits[b,v],
            k_pages, v_pages[, k_scales, v_scales])

    where ``next_toks`` is the same per-lane carry as the plain build
    (an emitting lane carries its LAST emitted token,
    ``out_ids[b, n_emit-1]``).

    where each lane's first ``n_emit`` tokens of ``out_ids`` are its
    emissions this step (accepted prefix + one bonus token; always >= 1
    for a completing lane). Rejected drafts' K/V sits above the advanced
    watermark — the scheduler rolls their pages back host-side
    (``KVCacheManager.trim_pages``). ``spec_k`` is geometry: one trace
    per (budget, batch, spec_k), its row ladder inside it, composing with
    ``kv_quant`` and ``mesh`` (the epilogue replicates; donation covers the
    same pools).

    WHAT THE CONFIGURATION CHOOSES (PR 28). The step's plumbing — packed
    stream, feedback, CoW lanes, the pool-carrying scan, head, sampling — is
    one; the block inside it is built from what ``config`` and the weight
    tree state: the norm (``_srv_norm``), positions (a learned table added
    at the embedding, or rotary angles from ``tok_pos`` inside attention),
    the attention part (``mha`` over per-head K and V pools, or ``mla`` over
    ONE latent pool), the FFN (``_srv_ffn``) and the head (``_srv_logits``).
    A model whose stack is not uniform brings its stacks in order
    (``dense_layers`` ahead of ``layers``): one scan each, one layer index
    through both. A LATENT cache (``config.kv_lora_rank``; DeepSeek-V2's
    MLA, ``models/deepseek_v2.py``) has one donated pool ``[num_layers,
    num_pages, 1, page_size, row]``, the row ``[c | k_pe]`` padded to whole
    128-lane tiles, written by the same in-place kernel and read by
    ``ops/pallas/mla_paged_attention`` addressed by layer index; a layer of
    routed experts adds a last result, ``expert_rows [2, E]`` int32: the
    rows every expert received, and the layers in which it received any,
    summed over the layers::

        fn(params, ...the same 11 arrays..., latent_pages, page_table, ...)
        -> (next_toks, logits, latent_pages, expert_rows)

    A model whose layers come in more kinds than two brings ``stacks``, one
    stack per RUN of equal layers (``deepseek_v2.layer_stacks``), each one
    scan. LEARNED SPARSE attention over the latent cache (``config.
    index_topk``; ``models/glm_moe_dsa.py``, PR 34) adds a second donated
    pool, the indexer layers' keys ``[indexer layers, num_pages, 1,
    page_size, index_head_dim]`` under the same page ids, and carries the
    SELECTION through the scans beside ``x`` and the pools: a layer with an
    indexer writes its index keys, scores every key a row sees and keeps the
    exact top ``index_topk`` (``ops/pallas/dsa_index.py``), the layers after
    it read through that selection until the next such layer: in a step of
    decode lanes alone each row reads the pool rows it names, gathered by
    index (``mla_selected_attention``; the rows are found once a selection),
    a step that holds a prefill chunk reads every lane's pages under the mask
    (``mla_ragged_paged_attention(selected=)``). A last result hands out the
    keys each lane's last row read::

        fn(params, ..., latent_pages, index_pages, page_table, ...)
        -> (next_toks, logits, latent_pages, index_pages, expert_rows,
            selected[indexer layers, b, key slots] bool)

    ``kv_quant``, ``mesh`` and ``spec_k`` are not extended to the latent
    cache and raise ``NotImplementedError`` here.

    ``mha`` is ONE function for every model with per-head K and V pools: the
    GPT block is it with as many key-value heads as query heads, no rotary
    and no window. GROUPED queries (``config.num_kv_heads``) give the pools
    fewer heads than the queries; a layer KIND (``models/cohere2_moe.py
    attention_kind``) may rotate q and k by ``tok_pos`` and may see a WINDOW.
    A model whose layers retain different things (``config.sliding_window``:
    window layers keep their last positions alone) has a pool pair per cache
    GROUP, the full layers' ``[full layers, num_pages, ...]`` then the window
    layers' ``[window layers, window pages, ...]``, all four donated, and
    after the seven-array tail the window group's own page table and, per
    lane, the position of its first held page's first row (``inference/
    kv_cache.py``)::

        fn(params, ...the same 11 arrays..., k_pages, v_pages, k_window,
           v_window, page_table, ...the tail..., window_table[b, pps_w],
           window_base[b])
        -> (next_toks, logits, k_pages, v_pages, k_window, v_window,
            expert_rows)

    The block of such a model may be PARALLEL (``config.parallel_block``: one
    norm feeds attention and the FFN, both added to ``x``), and a run of ONE
    layer brings its weights unstacked and is not scanned. ``kv_quant``,
    ``mesh`` and ``spec_k`` are not extended to a window group either.

    A LOOPED model (``config.total_ut_steps``; ``models/ouro.py``) runs its
    one stack once a loop: a loop around the layer scan, one traced body for
    all loops, the final norm after each loop (the next loop's input, and
    after the last the head's). Its pools hold a plane per (loop, layer),
    ``[num_layers * loops, num_pages, ...]`` under the one page table, loop t
    of layer l at plane ``config.cache_plane(t, l)``; its full layers may
    rotate (``rope_full_layers``) and its block may norm what each sublayer
    adds (``ln1b_g`` / ``ln2b_g``). The signature is the GPT block's.
    ``kv_quant``, ``mesh`` and ``spec_k`` are not extended to it.
    """
    import jax
    import jax.numpy as jnp
    from jax.extend.core import jaxpr_as_fun

    from ..inference.kv_cache import (packed_write_plan, paged_copy_pages,
                                      paged_write_packed,
                                      paged_write_packed_quant)
    from ..observability.tracing import step_scope
    from ..ops.pallas.paged_attention import (lane_block_rows,
                                              ragged_paged_attention,
                                              use_kernel_default)
    from .deepseek_v2 import STACKED_BY_INDEX, layer_stacks

    cfg = config
    trace_count = [0]
    # the Pallas kernels or the jnp forms, once for the program: the
    # predictor's counters read the same answer (``jitted.kernels``)
    kernels = use_kernel_default() if use_kernel is None else bool(use_kernel)
    mp, axis = _mesh_mp(mesh)
    nh_l, hd = cfg.num_heads // mp, cfg.head_dim
    nkv_l = (getattr(cfg, "num_kv_heads", None) or cfg.num_heads) // mp
    # a LATENT cache (multi-head latent attention, models/deepseek_v2.py):
    # one pool whose row is [c | k_pe], shared by every head
    latent = bool(getattr(cfg, "kv_lora_rank", 0))
    if latent:
        unsupported = [name for name, on in (
            ("kv_cache_dtype='int8'", kv_quant), ("mesh", mesh is not None),
            ("spec_decode_k", spec_k)) if on]
        if unsupported:
            raise NotImplementedError(
                f"the latent (MLA) cache does not serve "
                f"{', '.join(unsupported)} yet: its one pool has no scale "
                "planes and no head axis to shard")
        from ..ops.pallas.mla_paged_attention import (
            MLA_KERNEL_NAME, SPARSE_MLA_KERNEL_NAME, mla_ragged_paged_attention,
            mla_selected_attention, selected_layout, selected_plan,
            selected_rows_of, tile_for_heads, tile_grid, tile_plan)
        from .deepseek_v2 import (absorb_query, latent_qkv, softmax_scale,
                                  unabsorb_output)
    # LEARNED SPARSE attention over the latent cache (``index_topk``;
    # models/glm_moe_dsa.py): a second donated pool, the indexer's keys, and
    # the selection a layer with an indexer makes is carried to the layers
    # after it
    sparse = latent and bool(getattr(cfg, "index_topk", 0))
    if sparse:
        from ..ops.pallas import dsa_index as dsa
        from .glm_moe_dsa import indexer_qkw
    # layers that retain different things (``sliding_window``; models/
    # cohere2_moe.py): a pool pair per cache group, the window group with a
    # page table of its own
    windowed = not latent and bool(getattr(cfg, "sliding_window", 0))
    # a LOOPED stack (``total_ut_steps``; models/ouro.py): the layers run once
    # a loop, each (loop, layer) with its own plane of the pools
    looped = not latent and bool(getattr(cfg, "total_ut_steps", 0))
    if windowed or looped:
        unsupported = [name for name, on in (
            ("kv_cache_dtype='int8'", kv_quant), ("mesh", mesh is not None),
            ("spec_decode_k", spec_k)) if on]
        if unsupported:
            raise NotImplementedError(
                f"a cache with "
                f"{'a window group' if windowed else 'a plane a loop'} does "
                f"not serve {', '.join(unsupported)} yet: its pools have no "
                "scale planes, no sharding rule and no rollback")
        from .layer_kinds import WINDOW, attention_kind, stack_runs
    # argument layout (shared by the wrappers, shard_map specs and the
    # donation indices): params + 6 packed/lane arrays [+ spec_len] + the
    # 4 feedback arrays (feedback mask, prev_toks carry, emit_mask,
    # produced), then the donated pools [+ scale planes], then the
    # 7-array tail [+ the window group's table and bases]
    n_lead = 12 if spec_k else 11
    n_pool = (2 if sparse else 1 if latent
              else 4 if kv_quant or windowed else 2)
    n_out_lead = 4 if spec_k else 2

    def _body(*args):
        lead = args[:n_lead]
        pools = tuple(args[n_lead:n_lead + n_pool])
        (page_table, cow_src, cow_dst, base_keys, temperature, top_k,
         top_p, *win) = args[n_lead + n_pool:]
        spec_len = lead[7] if spec_k else None
        feedback, prev_toks, emit_mask, produced = lead[n_lead - 4:]
        return _step_inner(*lead[:7], spec_len, feedback, prev_toks,
                           emit_mask, produced, pools, page_table, cow_src,
                           cow_dst, base_keys, temperature, top_k, top_p,
                           tuple(win))

    def _at_rung(rows_of, tok_ids, tok_slot, tok_pos, feedback, q_lens,
                 pools):
        """What of a step depends on its packed rows (``rows_of``: embedding,
        the layers, the final norm and the rows the head reads), over the
        rows the step HOLDS. The host packs a step's rows from row 0 on
        (``q_lens.sum()`` of them; every row after them has ``tok_slot ==
        -1`` and a result nothing reads), so it runs over the first ``R``
        rows alone, ``R`` the smallest rung of :func:`step_row_ladder` that
        holds them. Pools and results have one shape at every rung.

        One conditional a rung, in sequence, each "this rung, or hand the
        pools and results on as they are"; exactly one runs. Not one
        ``lax.switch`` over the rungs: from its third branch on XLA:TPU
        copies every pool at the branch's edge (two branches updating one
        donated buffer in place it accepts, a third it does not, nested
        ``cond``s the same), and a pool-sized copy a step is what PR 27
        removed. In this form the pools stay one buffer from argument to
        result (``tests/test_tpu_compile.py``)."""
        def at(rows, pools):
            # runs once a rung, while jax traces the step
            with phase(f"step.rung.{rows}"):
                return rows_of(tok_ids[:rows], tok_slot[:rows],
                               tok_pos[:rows], feedback[:rows], pools)

        ladder = step_row_ladder(q_lens.shape[0], spec_k, chunk,
                                 tok_ids.shape[0])
        if len(ladder) == 1:
            return at(ladder[0], pools)
        # before any rung ran: the pools as they came, among results of the
        # shapes a rung gives. The first rung is traced here, for those
        # shapes, and its conditional replays the equations (a trace of the
        # step is set-up time: no rung is traced twice)
        first, shapes = jax.make_jaxpr(
            functools.partial(at, ladder[0]), return_shape=True)(pools)
        res = tuple(jnp.zeros(s.shape, s.dtype) for s in shapes)
        res = tuple(pools) + res[n_pool:]
        rung = step_row_rung(ladder, q_lens.sum())
        for i, rows in enumerate(ladder):
            res = jax.lax.cond(
                rung == i,
                (lambda res: tuple(jaxpr_as_fun(first)(*res[:n_pool])))
                if i == 0 else
                lambda res, rows=rows: tuple(at(rows, res[:n_pool])),
                lambda res: res, res)
        return res

    replays = {}

    def traced_once(fn, *args, kind=None):
        """``fn(*args)``: traced when it first meets arguments of these
        shapes, replayed from its equations after that, within one trace of
        the step (``fn`` takes every array it reads as an argument; ``kind``:
        what else tells two such functions apart)."""
        flat, tree = jax.tree.flatten(args)
        key = (kind, tree, tuple(map(jax.typeof, flat)))
        if key not in replays:
            replays[key] = jax.make_jaxpr(
                lambda *flat: fn(*jax.tree.unflatten(tree, flat)))(*flat)
        out, = jaxpr_as_fun(replays[key])(*flat)
        return out

    def step(*args):
        trace_count[0] += 1
        replays.clear()
        body = _body
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            kv_spec, sc_spec = _kv_specs()
            rep = P()
            pool_specs = ((kv_spec, kv_spec, sc_spec, sc_spec) if kv_quant
                          else (kv_spec, kv_spec))
            body = jax.shard_map(
                _body, mesh=mesh,
                in_specs=(serving_param_specs(args[0]),)
                + (rep,) * (n_lead - 1) + pool_specs + (rep,) * 7,
                out_specs=(rep,) * n_out_lead + pool_specs,
                check_vma=False)
        # MXU-native matmul precision (gpt_spmd.loss_fn convention): the
        # framework-global "highest" would emulate bf16 serving matmuls
        # multi-pass, 3-6x slower; attention scores stay explicit fp32
        with jax.default_matmul_precision("default"):
            return body(*args)

    def _step_inner(params, tok_ids, tok_slot, tok_pos, q_lens, kv_lens,
                    last_idx, spec_len, feedback, prev_toks, emit_mask,
                    produced, pools, page_table, cow_src, cow_dst, base_keys,
                    temperature, top_k, top_p, win=()):
        # copy-on-write BEFORE any write: diverging lanes get a private
        # copy of their shared tail page across every layer (scale planes
        # are page-keyed, so they ride the same copy lanes; a window group's
        # pages are never shared, and the page ids are the full group's)
        n_cow = 2 if windowed else n_pool
        with step_scope("cow"):
            pools = tuple(paged_copy_pages(pool, cow_src, cow_dst,
                                           lane_by_lane=latent)
                          for pool in pools[:n_cow]) + tuple(pools[n_cow:])
        # what depends on the packed rows runs at a rung of the row ladder
        # (``_at_rung``); the head and the sampling after it are per lane
        out = _at_rung(
            functools.partial(_rows_part, params, q_lens, kv_lens, last_idx,
                              prev_toks, page_table, win),
            tok_ids, tok_slot, tok_pos, feedback, q_lens, pools)
        return _lanes_part(params, out[:n_pool], out[n_pool],
                           out[n_pool + 1:], spec_len, prev_toks, emit_mask,
                           produced, base_keys, temperature, top_k, top_p)

    def _rows_part(params, q_lens, kv_lens, last_idx, prev_toks, page_table,
                   win, tok_ids, tok_slot, tok_pos, feedback, pools):
        """Embedding, the layers and the final norm over the packed rows
        given, and of them the rows the head reads: ``(*pools, h_rows[,
        drafts][, expert_rows][, selected])``."""
        t = tok_ids.shape[0]
        b = q_lens.shape[0]
        valid = tok_slot >= 0
        slot_c = jnp.clip(tok_slot, 0, b - 1)
        with step_scope("embed"):
            # device-resident feedback: tokens the host scheduled before
            # materializing their value read the previous step's carry —
            # the async engine's device-side half of the pipeline
            tok_ids = jnp.where((feedback > 0) & valid, prev_toks[slot_c],
                                tok_ids)
            x = jnp.take(params["tok_emb"], jnp.maximum(tok_ids, 0), axis=0)
            if "pos_emb" in params:
                # a learned position table; rotary models turn tok_pos into
                # angles inside their attention part instead
                x = x + params["pos_emb"][
                    jnp.clip(tok_pos, 0, params["pos_emb"].shape[0] - 1)]
        ctx = (kv_lens + q_lens).astype(jnp.int32)
        # packed <-> chunk-block index plumbing (shared by every layer):
        # each token's row in the attention kernel's [b, chunk] blocks
        off = tok_pos - kv_lens[slot_c]              # position in chunk
        off_c = jnp.clip(off, 0, chunk - 1)
        scatter_b = jnp.where(valid, tok_slot, b)    # b = dropped row

        # The layer scan CARRIES the stacked pools (and scale planes): one
        # donated buffer from argument to result. A layer writes its rows
        # into it at [layer, page, :, row] and the ragged kernel reads
        # layer i of it by index, so nothing pool-shaped is ever sliced
        # out of the stack or stacked back (as scanned inputs/outputs the
        # pools cost five pool-sized copies per layer and step on the
        # chip). Where the kernels run, the write is one too: XLA's scatter
        # would move the whole stack to a layout of its own and back.
        dest = (page_table, tok_slot, tok_pos, page_size)
        with step_scope("kv_write"):
            plan = (packed_write_plan(*dest, pools[0].shape[1]) if kernels
                    else None)
            if windowed:
                # the window group's table starts at each lane's first held
                # page: its positions count from that page's first row, for
                # the write and for attention (causality and the window are
                # both differences of positions)
                win_table, win_base = win
                dest_w = (win_table, tok_slot, tok_pos - win_base[slot_c],
                          page_size)
                plan_w = (packed_write_plan(*dest_w, pools[2].shape[1])
                          if kernels else None)
                ctx_w = ctx - win_base.astype(jnp.int32)
        if latent:
            with step_scope("attn"):
                # the latent kernel's tiled layout of this step's rows and
                # its grid's work items: what every layer's call shares
                mla_tile = tile_for_heads(cfg.num_heads)
                tiles = (tile_plan(tok_slot, off, q_lens, ctx, page_table,
                                   page_size=page_size,
                                   num_pages=pools[0].shape[1],
                                   tile=mla_tile)
                         if kernels else None)
        if sparse:
            # the selection as the attention part reads it: where the
            # kernels run, a 0/1 mask in the plan's tiled layout and, in a
            # step of decode lanes alone (``rowwise``), each lane's selected
            # keys as pool rows (the GATHERED form: a row reads its selected
            # rows alone; a step with a chunk reads every lane's pages under
            # the mask, one call of the kernel a layer either way); else
            # bool over the packed rows. Until a layer with an indexer fills
            # it, nothing
            slots = page_table.shape[1] * page_size
            last_c = jnp.clip(last_idx, 0, t - 1)
            if kernels:
                tgrid = tile_grid(b, t, page_table.shape[1], page_size,
                                  mla_tile)
                with step_scope("attn"):
                    gsel = selected_plan(q_lens, ctx, topk=cfg.index_topk)
                rowwise = jnp.all(q_lens <= 1)
                no_selection = (
                    jnp.zeros((tgrid.tiles * tgrid.tile,
                               tgrid.blocks * tgrid.keys), jnp.bfloat16),
                    jnp.zeros((b, selected_layout(cfg.index_topk)[1]),
                              jnp.int32))
            else:
                no_selection = (jnp.zeros((t, slots), bool), None)

        def mha(p, y, pools, li, kind=None):
            """Multi-head attention over per-head K and V pools: packed
            rows ``y [t, h]`` to ``[t, heads * head_dim]``. ``kind``: the
            layer's kind where the model has several (a window layer rotates
            q and k by position, sees its window and lives in the window
            group's pools; ``models/cohere2_moe.py``); None: the GPT block's,
            no rotary, no window, the one group."""
            window = theta = None
            sub = contextlib.nullcontext()
            to, at, kv_ctx, tbl, group_plan = dest, 0, ctx, page_table, plan
            if kind is not None:
                window, theta = attention_kind(cfg, kind)
                if windowed:
                    sub = step_scope("attn_full")
                if kind == WINDOW:
                    sub = step_scope("attn_window")
                    to, at, kv_ctx, tbl, group_plan = (dest_w, 2, ctx_w,
                                                       win_table, plan_w)
            kp, vp, *scales = pools[at:at + 2] if windowed else pools
            ks, vs = scales or (None, None)
            with step_scope("qkv"):
                qkv = _srv_mm(y, p["wqkv"], use_kernel)
                if "bqkv" in p:
                    qkv = qkv + p["bqkv"]
                q, k_t, v_t = _split_qkv(qkv, nh_l, hd,
                                         head_major=mesh is not None,
                                         nkv=nkv_l)
                if theta is not None:
                    # in the configuration's pairing (``models/layer_kinds``)
                    q = cfg.rotary(q, tok_pos, theta)
                    k_t = cfg.rotary(k_t, tok_pos, theta)
            with step_scope("kv_write"):
                if kv_quant:
                    kp, ks = paged_write_packed_quant(kp, ks, k_t, *dest,
                                                      layer=li, plan=plan)
                    vp, vs = paged_write_packed_quant(vp, vs, v_t, *dest,
                                                      layer=li, plan=plan)
                else:
                    kp = paged_write_packed(kp, k_t, *to, layer=li,
                                            plan=group_plan)
                    vp = paged_write_packed(vp, v_t, *to, layer=li,
                                            plan=group_plan)
            c = lane_block_rows(chunk, t, nh_l, nkv_l)
            row = off_c if c == chunk else jnp.minimum(off_c, c - 1)
            with step_scope("attn"), sub:
                qb = jnp.zeros((b, c, nh_l, hd), q.dtype
                               ).at[scatter_b, row].set(q, mode="drop")
                ab = traced_once(
                    lambda *ops: ragged_paged_attention(
                        *ops[:6], use_kernel=use_kernel, k_scales=ops[6],
                        v_scales=ops[7], layer=ops[8], window=window),
                    qb, kp, vp, tbl, kv_ctx, q_lens, ks, vs, li,
                    kind=window)
                a = ab[slot_c, row]                  # back to packed [t]
            if windowed:
                pools = pools[:at] + (kp, vp) + pools[at + 2:]
                return a.reshape(t, nh_l * hd), pools
            return (a.reshape(t, nh_l * hd),
                    (kp, vp, ks, vs) if kv_quant else (kp, vp))

        def select(p, y, cq, ipool, fi):
            """A layer's INDEXER (``models/glm_moe_dsa.py``): its keys
            written into layer ``fi`` of the index plane, every row's seen
            keys scored, and the ``index_topk`` best of each row kept.
            Returns the plane, the selection as the attention part reads it
            and, ``[b, slots]`` bool, the selection of each lane's last
            row."""
            with step_scope("attn_index"):
                q_idx, k_idx, w = indexer_qkw(cfg, p, y, cq, tok_pos)
                ipool = paged_write_packed(ipool, k_idx[:, None, :], *dest,
                                           layer=fi, plan=plan)
                scores = (
                    dsa.index_scores(q_idx, w, ipool, tiles, fi, grid=tgrid)
                    if kernels else dsa.index_scores_reference(
                        q_idx, w, ipool, page_table, ctx, q_lens, tok_slot,
                        off, layer=fi))
            with step_scope("attn_select"):
                if not kernels:
                    sel = dsa.select_topk(scores, scores > -jnp.inf,
                                          cfg.index_topk)
                    return ipool, (sel, None), sel[last_c]
                sel = dsa.select_mask(scores, tiles, grid=tgrid,
                                      k=cfg.index_topk)
                mine = jnp.minimum(tiles.dest[last_c], sel.shape[0] - 1)
                last = sel[mine][:, :slots] > 0
            with step_scope("attn_gather"):
                # where each lane's last row reads: once a selection, for
                # every layer that reads through it; a step with a chunk
                # reads through the mask alone
                keys = selected_layout(cfg.index_topk)[1]
                rows = jax.lax.cond(
                    rowwise,
                    lambda *a: dsa.selected_rows(
                        *a, k=keys, page_size=page_size,
                        num_pages=ipool.shape[1]),
                    lambda *a: jnp.zeros((b, keys), jnp.int32),
                    last, page_table)
            return ipool, (sel, rows), last

        def sparse_read(q_abs, pool, li, mask, rows, shared):
            """The selected read of one layer, ONE call of the kernel
            either way: in a step of decode lanes alone over each row's
            selected rows gathered out of the stack, else over every
            lane's pages under the mask. ``shared``: what every layer's
            call takes of the step."""
            table, kv, ql, slot, at, plan_m, plan_g, pick, last_i, lane = (
                shared)

            def gathered(q_abs, pool, li, mask, rows):
                with step_scope("attn_gather"):
                    keys = selected_rows_of(pool, rows, li)
                o = mla_selected_attention(
                    q_abs[last_i], keys, plan_g, v_dim=cfg.kv_lora_rank,
                    scale=softmax_scale(cfg))
                return jnp.where((slot >= 0)[:, None, None], o[lane], 0)

            def masked(q_abs, pool, li, mask, rows):
                return mla_ragged_paged_attention(
                    q_abs, pool, table, kv, ql, slot, at,
                    v_dim=cfg.kv_lora_rank, scale=softmax_scale(cfg),
                    layer=li, use_kernel=use_kernel, plan=plan_m,
                    tile=mla_tile, selected=mask,
                    name=SPARSE_MLA_KERNEL_NAME)

            return jax.lax.cond(pick, gathered, masked, q_abs, pool, li,
                                mask, rows)

        def mla(p, y, pools, li, sel=None, fi=None):
            """Latent attention: ONE row ``[c | k_pe]`` written per token,
            read back in the absorbed form by every row of the step (a
            decode row and a prefill chunk's rows alike: all of them read
            the paged context, see ``models/deepseek_v2.py``). With an
            indexer anywhere in the model (``sparse``): over the selected
            keys ``sel`` alone, which a layer that has an indexer makes anew
            (:func:`select`; it then also returns the selection and its
            last rows), in the form the step takes (:func:`sparse_read`)."""
            pool = pools[0]
            pad = pool.shape[-1] - cfg.latent_dim    # lanes to a whole tile
            with step_scope("qkv"):
                q_nope, q_pe, row, *cq = latent_qkv(
                    cfg, p, y, tok_pos, with_query_latent=sparse)
                row = jnp.pad(row, ((0, 0), (0, pad)))
            with step_scope("kv_write"):
                pool = paged_write_packed(pool, row[:, None, :], *dest,
                                          layer=li, plan=plan)
            pools, last_rows = (pool,) + tuple(pools[1:]), None
            with step_scope("attn"):
                if "idx_wq" in p:
                    ipool, sel, last_rows = select(p, y, cq[0], pools[1], fi)
                    pools = (pool, ipool)
                with step_scope("attn_absorb"):
                    q_abs = jnp.pad(absorb_query(cfg, p, q_nope, q_pe),
                                    ((0, 0), (0, 0), (0, pad)))
                # one shape in every scan of a rung: the kernel's body is
                # traced for the first
                if sparse and kernels:
                    o_lat = traced_once(
                        sparse_read, q_abs, pool, li, *sel,
                        (page_table, ctx, q_lens, tok_slot, off, tiles, gsel,
                         rowwise, last_c, slot_c))
                else:
                    o_lat = traced_once(
                        lambda *ops: mla_ragged_paged_attention(
                            *ops[:7], v_dim=cfg.kv_lora_rank,
                            scale=softmax_scale(cfg), layer=ops[7],
                            use_kernel=use_kernel, plan=ops[8],
                            tile=mla_tile, selected=ops[9],
                            name=SPARSE_MLA_KERNEL_NAME if sparse
                            else MLA_KERNEL_NAME),
                        q_abs, pool, page_table, ctx, q_lens, tok_slot, off,
                        li, tiles, sel[0] if sparse else sel)
                with step_scope("attn_absorb"):
                    a = unabsorb_output(cfg, p, o_lat)
            return (a, pools, sel, last_rows) if sparse else (a, pools)

        attention = mla if latent else mha

        def block(carry, layer, whole=None, kind=None):
            x, pools, *sel = carry
            p, li, lj, *fi = layer
            if whole:
                # stacks read by index inside their kernel: layer lj of them
                p = dict(p, **whole)
            with step_scope("ln"):
                y = _srv_norm(cfg, x, p, "ln1")
            # a router placed BEFORE attention reads this norm, not the FFN's
            # (``models/smallthinker.py``)
            route = (y if getattr(cfg, "router_before_attention", False)
                     else None)
            a, pools, *chosen = attention(
                p, y, pools, li, *sel, *fi,
                **({} if kind is None else {"kind": kind}))
            with step_scope("attn_out"):
                o = _srv_psum(_srv_mm(a, p["wo"], use_kernel), axis)
                if "ln1b_g" in p:
                    # a SANDWICH block norms what each sublayer adds too
                    # (``models/ouro.py``)
                    with step_scope("ln"):
                        o = _srv_norm(cfg, o, p, "ln1b")
                x = x + o
                if "bo" in p:
                    x = x + p["bo"]
            if not getattr(cfg, "parallel_block", False):
                # (a PARALLEL block's one norm feeds the FFN too)
                with step_scope("ln"):
                    y = _srv_norm(cfg, x, p, "ln2")
            with step_scope("mlp"):
                f, rows = _srv_ffn(cfg, p, y, use_kernel, axis, valid=valid,
                                   layer=lj if whole else None, route=route)
                if "ln2b_g" in p:
                    with step_scope("ln"):
                        f = _srv_norm(cfg, f, p, "ln2b")
                x = x + f
            if sparse:
                # the selection goes on to the next layer; its last rows
                # (None from a layer that shares) come out with the counts
                return (x, pools, chosen[0]), (rows, chosen[1])
            return (x, pools), rows

        def loops_over(carry, stack, kind):
            """A LOOPED stack: the whole stack once a loop, ONE traced body
            for every loop (a loop around the layer scan), loop t of layer
            l writing and reading plane ``cfg.cache_plane(t, l)`` of the
            pools (which the carry holds throughout), and the final norm
            after each loop: the next loop's input, and after the last
            loop the head's."""
            n = stack["ln1_g"].shape[0]
            within = jnp.arange(n, dtype=jnp.int32)

            def one_loop(i, carry):
                carry, _ = jax.lax.scan(
                    functools.partial(block, kind=kind), carry,
                    (stack, cfg.cache_plane(i, within), within))
                with step_scope("ln"):
                    x = _srv_norm(cfg, carry[0], params, "lnf")
                return (x, *carry[1:])

            return jax.lax.fori_loop(0, cfg.total_ut_steps, one_loop, carry)

        # the stacks the model's layers come in, in order: one for a uniform
        # model, the leading dense layers' and then the routed layers' for
        # one whose stack is not uniform (models/deepseek_v2.py). Each is
        # one scan; the layer index counts through them all, since the pool
        # stack is [all layers, pages, ...]
        # (a model of more kinds of layer brings one stack per run of equal
        # layers, ``stacks``: models/glm_moe_dsa.py)
        groups = layer_stacks(params)
        # a model of several attention kinds: the kind of each run, and the
        # layers of each cache group counted apart (a group's pool stack is
        # [its layers, its pages, ...])
        kinds = ([k for k, _ in stack_runs(cfg)] if windowed or looped
                 else [None] * len(groups))
        layers_run = {}      # cache group (the window's?) -> its layers so far
        # the scans are scoped, so their own slicing of the stacked weights
        # falls under "layers" alone
        carry, expert_rows = (x, pools), None
        if sparse:
            carry, indexed, selected = carry + (no_selection,), 0, []
        with step_scope("layers"):
            for stack, kind in zip(groups, kinds):
                if looped:
                    carry = loops_over(carry, stack, kind)
                    continue
                group = windowed and kind == WINDOW
                first = layers_run.get(group, 0)
                if stack["ln1_g"].ndim == 1:
                    # a run of ONE layer, its weights unstacked: no scan,
                    # and no copy of a weight out of a stack of one
                    carry, rows = block(
                        carry, (stack, jnp.int32(first), jnp.int32(0)),
                        kind=kind)
                    layers_run[group] = first + 1
                    if rows is not None:
                        expert_rows = (rows if expert_rows is None
                                       else expert_rows + rows)
                    continue
                n = jax.tree.leaves(stack)[0].shape[0]
                # what a kernel reads by layer index stays out of the scanned
                # slices (none for a GPT block)
                whole = {k: stack[k] for k in STACKED_BY_INDEX if k in stack}
                within = jnp.arange(n, dtype=jnp.int32)
                counters = (first + within, within)
                if sparse:
                    # a layer with an indexer: which layer of the index plane
                    counters += (indexed + within,)
                    indexed += n if "idx_wq" in stack else 0
                carry, rows = jax.lax.scan(
                    functools.partial(block, whole=whole, kind=kind)
                    if whole or kind else block,
                    carry,
                    ({k: v for k, v in stack.items() if k not in whole},
                     *counters))
                layers_run[group] = first + n
                if sparse:
                    rows, last_rows = rows
                    if last_rows is not None:
                        selected.append(last_rows)
                if rows is not None:
                    # [2, E] int32: the rows every expert received, and
                    # the layers in which it received any
                    rows = rows.sum(axis=0)
                    expert_rows = (rows if expert_rows is None
                                   else expert_rows + rows)
        x, pools = carry[:2]
        # the rows the head reads: each slot's LAST packed token yields its
        # next-token decision; speculating, rows last_idx .. last_idx+spec_k
        # are the lane's verify rows (its last context token, then its
        # packed draft tokens; a non-speculating lane has spec_len 0 and
        # only row 0 matters)
        rows = (last_idx[:, None] + jnp.arange(spec_k + 1)[None] if spec_k
                else last_idx)
        with step_scope("head"):
            if not looped:      # (a looped stack's last loop normed it)
                x = _srv_norm(cfg, x, params, "lnf")
            out = (*pools, x[jnp.clip(rows, 0, t - 1)])  # [b, (k1,) h]
        if spec_k:
            # drafts ride the packed token stream: [b, k]
            out += (tok_ids[jnp.clip(rows[:, 1:], 0, t - 1)],)
        if expert_rows is not None:
            out += (expert_rows,)
        if sparse:
            # per scan with an indexer: [its layers, b, key slots] bool, the
            # keys each lane's last row read
            out += tuple(selected)
        return out

    def _lanes_part(params, pools, h_rows, extras, spec_len, prev_toks,
                    emit_mask, produced, base_keys, temperature, top_k,
                    top_p):
        """Head and sampling over the rows ``_rows_part`` picked, and the
        step's results in their order (``extras``: its results after the
        rows, as they came)."""
        b = prev_toks.shape[0]
        if spec_k:
            # -- speculative verify + fused accept epilogue --------------
            k1 = spec_k + 1
            drafts, *extras = extras
            with step_scope("head"):
                logits_rows = _srv_logits(params,
                                          h_rows).astype(jnp.float32)
            v = logits_rows.shape[-1]

            def _samp():
                # row j of a lane samples with the base key folded by
                # tokens-produced + j — the on-device spelling of the
                # former host-side fold_in (vmapped threefry, bit-
                # identical), so the per-request stream matches plain
                # seeded decode
                keys = jax.vmap(
                    lambda bk, p: jax.vmap(jax.random.fold_in,
                                           in_axes=(None, 0))(
                        bk, p + jnp.arange(k1)))(base_keys, produced)
                rep = lambda a: jnp.repeat(a, k1)  # noqa: E731
                return _sample_epilogue(
                    logits_rows.reshape(b * k1, v),
                    keys.reshape(b * k1, 2), rep(temperature), rep(top_k),
                    rep(top_p)).reshape(b, k1)

            with step_scope("sample"):
                greedy = jnp.argmax(logits_rows, -1).astype(jnp.int32)
                sampled = jax.lax.cond(jnp.any(temperature > 0.0), _samp,
                                       lambda: greedy)
                out_ids = jnp.where((temperature > 0.0)[:, None], sampled,
                                    greedy)
                # accept while draft i matches the token the model
                # actually emits at its position
                ok = ((drafts == out_ids[:, :spec_k])
                      & (jnp.arange(spec_k)[None] < spec_len[:, None]))
                n_emit = (1 + jnp.cumprod(ok.astype(jnp.int32),
                                          axis=1).sum(1)).astype(jnp.int32)
                # per-lane carry: an emitting lane's LAST emitted token
                last_emit = jnp.take_along_axis(
                    out_ids, jnp.maximum(n_emit - 1, 0)[:, None],
                    axis=1)[:, 0]
                next_toks = jnp.where(emit_mask > 0, last_emit, prev_toks)
            return (out_ids, n_emit, next_toks, logits_rows[:, 0], *pools,
                    *extras)
        with step_scope("head"):
            logits = _srv_logits(params, h_rows).astype(jnp.float32)

        # the epilogue's [b, vocab] sort/softmax/cumsum (and the key
        # folds) only EXECUTE on steps where some lane actually samples —
        # all-greedy steps (the flagship greedy serving loop) pay just
        # the argmax + predicate
        def _samp():
            keys = jax.vmap(jax.random.fold_in)(base_keys, produced)
            return _sample_epilogue(logits, keys, temperature, top_k,
                                    top_p)

        with step_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            sampled = jax.lax.cond(jnp.any(temperature > 0.0), _samp,
                                   lambda: greedy)
            next_ids = jnp.where(temperature > 0.0, sampled, greedy)
            # per-lane carry: emitting lanes refresh, everyone else passes
            # the previous token through (a lane skipped by the budget
            # still feeds its latest token through feedback next step)
            next_toks = jnp.where(emit_mask > 0, next_ids, prev_toks)
        # after the pools: ``expert_rows`` where experts are routed, then,
        # where the model has an indexer, the selected keys, last:
        # [layers with an indexer, b, key slots] bool
        if sparse:
            extras = [e for e in extras if e.dtype != jnp.bool_] + [
                jnp.concatenate([e for e in extras if e.dtype == jnp.bool_])]
        return (next_toks, logits, *pools, *extras)

    jitted = jit32(step,
                   donate_argnums=tuple(range(n_lead, n_lead + n_pool)))
    jitted.trace_count = trace_count
    jitted.kernels = kernels
    return jitted


# generate_paged's compiled programs, keyed by (config fields, page_size,
# use_kernel): repeated generate() calls replay the same jit instead of
# re-tracing + re-compiling the whole model each call. ServingPredictor
# holds its own per-instance pair (its trace counter is a per-predictor
# gate), so only the convenience path shares.
_SERVING_JIT_CACHE: dict = {}

# per-model extracted params (the [L, ...] stacks are device copies):
# weak-keyed so a collected model drops its stacks, id-validated so an
# optimizer step (which rebinds every ._data) forces re-extraction
import weakref as _weakref  # noqa: E402

_SERVING_PARAMS_CACHE = _weakref.WeakKeyDictionary()


def _quant_sig(cfg: GPTConfig):
    """The config fields that change what _serving_params_cached extracts
    (a flipped weight_dtype must invalidate the cached fp pytree even
    though the underlying buffers are unchanged)."""
    return (getattr(cfg, "weight_dtype", None),
            getattr(cfg, "weight_quant_group_size", -1))


def _serving_params_cached(model, mesh=None):
    # staleness check by buffer IDENTITY against WEAKLY-held capture-time
    # buffers: identity comparison is immune to CPython id reuse, and the
    # weakrefs mean an optimizer step's rebinding doesn't leave ~1x model
    # weights of dead buffers pinned by the cache key (a dead ref simply
    # reads as stale). Round 11: the cached value is a per-MESH-SIGNATURE
    # dict (None = the unsharded extraction; every sharded layout derives
    # from it), so two mesh sizes neither collide nor evict each other.
    from ..distributed.mesh import mesh_signature

    cfg = (model.gpt if hasattr(model, "gpt") else model).config
    qsig = _quant_sig(cfg)
    msig = mesh_signature(mesh)
    bufs = _serving_weight_buffers(model)
    hit = _SERVING_PARAMS_CACHE.get(model)
    if (hit is not None and len(hit[0]) == len(bufs)
            and hit[2] == qsig
            and all(ref() is cur for ref, cur in zip(hit[0], bufs))):
        by_mesh = hit[1]
    else:
        by_mesh = {}
        try:
            _SERVING_PARAMS_CACHE[model] = (
                [_weakref.ref(b) for b in bufs], by_mesh, qsig)
        except TypeError:
            pass  # un-weakrefable model object: just skip the cache
    if None not in by_mesh:
        params = serving_params(model)
        if cfg.weight_dtype is not None:
            from ..inference.quantize import quantize_serving_params

            params = quantize_serving_params(
                params, cfg.weight_dtype, cfg.weight_quant_group_size)
        by_mesh[None] = params
    if msig is None:
        return by_mesh[None]
    if msig not in by_mesh:
        by_mesh[msig] = shard_serving_params(by_mesh[None], mesh, cfg)
    return by_mesh[msig]


def _jit_cache_get(key, build):
    hit = _SERVING_JIT_CACHE.get(key)
    if hit is None:
        # bounded LRU (same policy as the engine's eager-op cache): a
        # process sweeping geometries must not pin executables forever
        while len(_SERVING_JIT_CACHE) >= 32:
            _SERVING_JIT_CACHE.pop(next(iter(_SERVING_JIT_CACHE)))
        hit = build()
    else:
        _SERVING_JIT_CACHE.pop(key)  # refresh recency
    _SERVING_JIT_CACHE[key] = hit
    return hit


def _cfg_key(config: GPTConfig):
    import dataclasses

    return tuple((f.name, getattr(config, f.name))
                 for f in dataclasses.fields(config))


def _unified_fn(config: GPTConfig, page_size: int, chunk: int, use_kernel,
                kv_quant=False, mesh=None, spec_k=0):
    # the mesh SIGNATURE keys the cache (satellite of round 11): two mesh
    # sizes get two entries — neither collides with nor retraces the other.
    # spec_k is build GEOMETRY (the [b, k+1] output): two k values get two
    # executables, each compiled once; adaptive per-request k never keys.
    from ..distributed.mesh import mesh_signature

    return _jit_cache_get(
        ("unified", _cfg_key(config), page_size, chunk, use_kernel,
         kv_quant, mesh_signature(mesh), spec_k),
        lambda: build_unified_step(config, page_size, chunk,
                                   use_kernel=use_kernel,
                                   kv_quant=kv_quant, mesh=mesh,
                                   spec_k=spec_k))


# ---------------------------------------------------------------------------
# Round-19 model-based self-draft: the draft "model" is the first
# ``draft_layers`` decoder layers of the SAME serving stack (shared
# embeddings / positional table / final LN / LM head — zero extra weights
# to load; a distinct EAGLE-style draft param pytree can ride the same
# surface later by swapping what draft_serving_params returns). The draft
# pass is just the round-9 unified step built from a truncated config, so
# it inherits the packed token budget, the paged-KV write/ragged-attention
# discipline, the device-resident feedback carry (the k-token draft chain
# never materializes intermediate tokens on the host) and the
# one-trace-per-geometry contract for free.
# ---------------------------------------------------------------------------


def draft_config(config: GPTConfig, draft_layers: int) -> GPTConfig:
    """The truncated-stack config the draft jits build from. Rejects
    degenerate depths loudly: ``draft_layers >= num_layers`` would run the
    full target as its own drafter (all cost, no speedup) and is always a
    configuration mistake."""
    import dataclasses

    draft_layers = int(draft_layers)
    if draft_layers < 1:
        raise ValueError(
            f"spec_draft_layers must be >= 1, got {draft_layers}")
    if draft_layers >= config.num_layers:
        raise ValueError(
            f"spec_draft_layers {draft_layers} must be < num_layers "
            f"{config.num_layers} (a full-depth draft would run the "
            "target twice per token instead of a cheap proposer)")
    # the draft stack serves plain decode only: no nested speculation
    return dataclasses.replace(config, num_layers=draft_layers,
                               spec_decode_k=0, spec_draft_layers=0)


def draft_serving_params(params, draft_layers: int):
    """Slice a serving params pytree down to the first ``draft_layers``
    scan stacks. The non-layer leaves (embeddings, final LN, LM head) are
    SHARED by reference — the self-draft loads zero extra weights; only
    the truncated layer stacks are (small) device slices. Works on fp and
    quantized (``{"q", "s"}``) stacks alike."""
    import jax

    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = jax.tree.map(lambda a: a[:draft_layers],
                                 params["layers"])
    return out


def build_draft_step(config: GPTConfig, draft_layers: int, page_size: int,
                     chunk: int, use_kernel=None, kv_quant: bool = False,
                     mesh=None):
    """The draft pass's fixed-shape jit: the unified serving step built
    from the TRUNCATED config (validated by :func:`draft_config`) — one
    build serves both the catch-up prefill chunks and the chunk-1 decode
    chain geometry (the caller picks ``chunk``). Shares the process-wide
    jit cache, so every predictor with the same draft geometry replays one
    executable."""
    return _unified_fn(draft_config(config, draft_layers), page_size,
                       chunk, use_kernel, kv_quant=kv_quant, mesh=mesh)


def build_draft_chain(config: GPTConfig, draft_layers: int, page_size: int,
                      k: int, use_kernel=None, kv_quant: bool = False,
                      mesh=None):
    """The WHOLE k-step draft proposal chain as ONE jit (round 22).

    The round-19 engine launched the chunk-1 draft step k times per
    round, chaining tokens through the device feedback carry — k
    dispatches, k host pack loops. This builder rolls the chain into a
    single program: a ``lax.scan`` over the k chain steps, each step the
    truncated stack at chunk-1 geometry, device-chained, so a speculative
    round costs ONE draft dispatch + ONE verify dispatch.

    Signature::

        fn(params, first_toks[b], steps[b], kv_lens[b],
           k_pages, v_pages[, k_scales, v_scales], page_table)
        -> (drafts[b, k], k_pages, v_pages[, k_scales, v_scales])

    ``first_toks[lane]`` is the lane's live last context token (chain
    step 0's input), ``steps[lane]`` how many chain steps the lane runs
    (0 = idle — the lane writes nothing and its drafts read 0),
    ``kv_lens[lane]`` the draft pool's watermark at chain start. Chain
    step j writes the lane's K/V at position ``kv_lens + j`` and feeds
    its greedy argmax to step j+1 — bit-identical to k separate chunk-1
    unified-step dispatches chained through the feedback carry. The
    caller pre-reserves page capacity for ``kv_lens + steps`` (the page
    table is fixed for the whole chain) and advances its host watermark
    by the steps actually run. Pools donate; the trace-count contract
    matches the unified step.
    """
    import jax
    import jax.numpy as jnp

    from ..inference.kv_cache import (paged_write_packed,
                                      paged_write_packed_quant)
    from ..ops.pallas.paged_attention import ragged_paged_attention

    cfg = draft_config(config, draft_layers)
    eps = cfg.layer_norm_eps
    trace_count = [0]
    mp, axis = _mesh_mp(mesh)
    nh_l, hd = cfg.num_heads // mp, cfg.head_dim
    k = int(k)
    if k < 1:
        raise ValueError(f"draft chain length k must be >= 1, got {k}")
    n_pool = 4 if kv_quant else 2

    def _chain_inner(params, first_toks, steps, kv_lens0, *rest):
        pools0 = rest[:n_pool]
        page_table = rest[n_pool]
        b = first_toks.shape[0]
        lane = jnp.arange(b, dtype=jnp.int32)
        kv_lens0 = kv_lens0.astype(jnp.int32)

        def one_step(carry, j):
            ids, pools = carry
            if kv_quant:
                k_pages, v_pages, k_scales, v_scales = pools
            else:
                k_pages, v_pages = pools
                k_scales = v_scales = None
            active = j < steps
            q_lens = jnp.where(active, 1, 0).astype(jnp.int32)
            tok_slot = jnp.where(active, lane, -1).astype(jnp.int32)
            tok_pos = kv_lens0 + j
            kv_lens = kv_lens0 + j
            ctx = (kv_lens + q_lens).astype(jnp.int32)
            valid = tok_slot >= 0
            slot_c = jnp.clip(tok_slot, 0, b - 1)
            scatter_b = jnp.where(valid, tok_slot, b)
            x = (jnp.take(params["tok_emb"], jnp.maximum(ids, 0), axis=0)
                 + params["pos_emb"][
                     jnp.clip(tok_pos, 0,
                              params["pos_emb"].shape[0] - 1)])

            def block(x, layer):
                # the per-op layer at chunk-1 geometry — the exact
                # _step_inner spelling (one packed row per lane)
                if kv_quant:
                    p, kp, vp, ks, vs = layer
                else:
                    p, kp, vp = layer
                    ks = vs = None
                y = _srv_ln(x, p["ln1_g"], p["ln1_b"], eps)
                qkv = _srv_mm(y, p["wqkv"], use_kernel) + p["bqkv"]
                q, k_t, v_t = _split_qkv(qkv, nh_l, hd,
                                         head_major=mesh is not None)
                if kv_quant:
                    kp, ks = paged_write_packed_quant(
                        kp, ks, k_t, page_table, tok_slot, tok_pos,
                        page_size)
                    vp, vs = paged_write_packed_quant(
                        vp, vs, v_t, page_table, tok_slot, tok_pos,
                        page_size)
                else:
                    kp = paged_write_packed(kp, k_t, page_table, tok_slot,
                                            tok_pos, page_size)
                    vp = paged_write_packed(vp, v_t, page_table, tok_slot,
                                            tok_pos, page_size)
                qb = jnp.zeros((b, 1, nh_l, hd), q.dtype
                               ).at[scatter_b, 0].set(q, mode="drop")
                ab = ragged_paged_attention(qb, kp, vp, page_table, ctx,
                                            q_lens, use_kernel=use_kernel,
                                            k_scales=ks, v_scales=vs)
                a = ab[slot_c, 0]
                x = x + _srv_psum(_srv_mm(a.reshape(b, nh_l * hd),
                                          p["wo"], use_kernel),
                                  axis) + p["bo"]
                x = x + _srv_ffn(cfg, p, _srv_ln(x, p["ln2_g"],
                                                 p["ln2_b"], eps),
                                 use_kernel, axis, valid=valid)[0]
                return x, ((kp, vp, ks, vs) if kv_quant else (kp, vp))

            if kv_quant:
                x, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
                    block, x, (params["layers"], k_pages, v_pages,
                               k_scales, v_scales))
                pools = (k_pages, v_pages, k_scales, v_scales)
            else:
                x, (k_pages, v_pages) = jax.lax.scan(
                    block, x, (params["layers"], k_pages, v_pages))
                pools = (k_pages, v_pages)
            x = _srv_ln(x, params["lnf_g"], params["lnf_b"], eps)
            logits = _srv_logits(params, x).astype(jnp.float32)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ids_next = jnp.where(active, nxt, ids)
            return (ids_next, pools), jnp.where(active, nxt, 0)

        (_, pools), drafts = jax.lax.scan(
            one_step, (first_toks.astype(jnp.int32), pools0),
            jnp.arange(k, dtype=jnp.int32))
        return (drafts.T,) + tuple(pools)   # [b, k]

    def chain(*args):
        trace_count[0] += 1
        body = _chain_inner
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            kv_spec, sc_spec = _kv_specs()
            rep = P()
            pool_specs = ((kv_spec, kv_spec, sc_spec, sc_spec) if kv_quant
                          else (kv_spec, kv_spec))
            body = jax.shard_map(
                _chain_inner, mesh=mesh,
                in_specs=(serving_param_specs(args[0]),) + (rep,) * 3
                + pool_specs + (rep,),
                out_specs=(rep,) + pool_specs,
                check_vma=False)
        with jax.default_matmul_precision("default"):
            return body(*args)

    jitted = jit32(chain, donate_argnums=tuple(range(4, 4 + n_pool)))
    jitted.trace_count = trace_count
    return jitted


def _draft_chain_fn(config: GPTConfig, draft_layers: int, page_size: int,
                    k: int, use_kernel, kv_quant=False, mesh=None):
    """Process-wide jit cache for :func:`build_draft_chain` (same policy
    as ``_unified_fn``: every predictor with the same draft geometry
    replays one executable; ``k`` is build geometry)."""
    from ..distributed.mesh import mesh_signature

    return _jit_cache_get(
        ("draft_chain", _cfg_key(draft_config(config, draft_layers)),
         page_size, k, use_kernel, kv_quant, mesh_signature(mesh)),
        lambda: build_draft_chain(config, draft_layers, page_size, k,
                                  use_kernel=use_kernel,
                                  kv_quant=kv_quant, mesh=mesh))


def generate_paged(model, input_ids, max_new_tokens=20, *, page_size=None,
                   num_pages=None, use_kernel=None, eos_token_id=None,
                   chunk=None, temperature=0.0, top_k=0, top_p=1.0,
                   seed=0, mesh=None, spec_decode_k=None):
    """Autoregressive generation over the paged KV cache — round 9: ONE
    unified-step jit serves prefill chunks and decode tokens alike.

    ``input_ids``: [batch, prompt_len] (Tensor or array). Prompts feed in
    ``chunk``-token ragged chunks (autotuned default), then every decode
    token replays the SAME fixed-shape program — no per-bucket prefill
    executables, no retrace after warmup. Greedy (``temperature == 0``,
    the default) matches the full-forward oracle token for token.
    ``temperature > 0`` runs the fused seeded temperature/top-k/top-p
    epilogue (``seed`` makes it reproducible).
    With ``eos_token_id``, a row that stops early frees its cache pages,
    its lane goes inert, and its remaining columns pad with the eos id.

    Round 11: ``mesh`` (None, an int mp degree, or a ``Mesh(("mp",))``)
    serves the step tensor-parallel — params head/column-sharded, the KV
    pools and scale planes sharded by head — through the SAME scheduler
    loop; the host-side page/slot bookkeeping stays global. ``mesh=1``
    runs the sharded program on one chip, bit-identical to ``mesh=None``.

    Round 10: ``config.weight_dtype`` ("int8"/"int4") serves the decoder
    matmuls through the fused weight-only Pallas GEMM (weights stay
    quantized in HBM), and ``config.kv_cache_dtype == "int8"`` stores the
    page pools int8 with quantize-on-write + in-kernel dequant — greedy
    decoding then matches the fp oracle to within quantization noise
    (>= 99% of tokens in the smoke config) rather than bit-exactly.

    Round 12: ``spec_decode_k`` (default ``config.spec_decode_k``; > 0
    enables) runs the draft–verify–accept speculative loop: each row owns
    an n-gram/prompt-lookup :class:`~paddle_tpu.inference.draft.
    DraftProposer`, decode rounds feed ``1 + k`` verify rows through the
    SAME unified step (``spec_k`` build geometry) and emit the accepted
    prefix + one bonus token per round. Greedy output stays token-for-
    token identical to plain decode (the accept rule only keeps drafts
    the plain stream would have produced); rejected drafts' pages roll
    back via ``KVCacheManager.trim_pages``. Sampled rows key row j by
    (row, tokens-produced + j) so a seed reproduces the stream across k.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..inference.kv_cache import (KVCacheManager, kv_cache_quantized,
                                      pages_needed)
    from ..tensor.tensor import Tensor

    from ..distributed.mesh import as_serving_mesh

    mesh = as_serving_mesh(mesh)
    cfg = (model.gpt if hasattr(model, "gpt") else model).config
    ids_np = np.asarray(input_ids.numpy() if isinstance(input_ids, Tensor)
                        else input_ids).astype(np.int32)
    b, s = ids_np.shape
    if s == 0:
        raise ValueError("empty prompt")
    if max_new_tokens <= 0:
        generate_paged.last_decode_trace_count = 0
        return Tensor(jnp.zeros((b, 0), jnp.int64))
    total = s + max_new_tokens
    if total > cfg.max_seq_len:
        raise ValueError(
            f"prompt {s} + max_new_tokens {max_new_tokens} exceeds "
            f"max_seq_len {cfg.max_seq_len}")
    params = _serving_params_cached(model, mesh=mesh)
    dtype = params["tok_emb"].dtype
    if page_size is None or chunk is None:
        from ..ops.pallas.paged_attention import (preferred_chunk_size,
                                                  preferred_page_size)

        if page_size is None:
            page_size = preferred_page_size(cfg.num_heads, cfg.num_heads,
                                            cfg.head_dim, dtype)
        if chunk is None:
            chunk = preferred_chunk_size(cfg.num_heads, cfg.num_heads,
                                         cfg.head_dim, dtype)
    kv_quant = kv_cache_quantized(cfg.kv_cache_dtype)
    mgr = KVCacheManager(
        cfg.num_layers, cfg.num_heads, cfg.head_dim,
        num_pages=num_pages or b * pages_needed(total, page_size),
        max_batch=b, max_seq_len=total, page_size=page_size, dtype=dtype,
        quantize_kv=kv_quant, mesh=mesh)
    contexts = [[int(t) for t in row] for row in ids_np]
    slots: list = []
    for ctx in contexts:
        slot, _ = mgr.admit_prefix(ctx)   # no prefix sharing here: the
        slots.append(slot)                # ServingPredictor owns that path

    chunk = int(chunk)
    spec_k = int(cfg.spec_decode_k if spec_decode_k is None
                 else (spec_decode_k or 0))
    if spec_k < 0:
        raise ValueError(f"spec_decode_k must be >= 0, got {spec_k}")
    if spec_k and spec_k >= chunk:
        raise ValueError(
            f"spec_decode_k {spec_k} needs 1 + k <= chunk {chunk} (the "
            "verify rows ride the per-slot chunk block)")
    proposers = None
    if spec_k:
        from ..inference.draft import DraftProposer

        proposers = [DraftProposer(spec_k) for _ in range(b)]
    step = _unified_fn(cfg, mgr.page_size, chunk, use_kernel,
                       kv_quant=kv_quant, mesh=mesh, spec_k=spec_k)
    traces_at_entry = step.trace_count[0]
    # token budget: every row can feed a full chunk each round (generate
    # drives all rows in lockstep; the budget-packed scheduler lives in
    # ServingPredictor). constant per-call sampling plumbing; generate
    # never shares pages, so copy-on-write stays on the no-op sentinel
    t_budget = b * chunk
    no_cow = jnp.full((b,), mgr.num_pages, jnp.int32)
    temp_arr = jnp.full((b,), float(temperature), jnp.float32)
    topk_arr = jnp.full((b,), int(top_k), jnp.int32)
    topp_arr = jnp.full((b,), float(top_p), jnp.float32)
    # the synchronous convenience loop never defers emission: feedback
    # stays all-zero and the carry input is a constant (no upload)
    no_feedback = jnp.zeros((t_budget,), jnp.int32)
    zero_prev = jnp.zeros((b,), jnp.int32)
    base_keys = jnp.zeros((b, 2), jnp.uint32)
    if temperature > 0:
        # one vectorized fold per CALL for the per-row base keys; the
        # per-token keys fold IN-JIT from (base key, tokens produced) —
        # vmapped threefry, bit-identical to the former host-side folds
        base_key = jax.random.PRNGKey(int(seed))
        base_keys = jnp.asarray(np.asarray(
            jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                base_key, jnp.arange(b)), np.uint32))

    outs: list[list[int]] = [[] for _ in range(b)]
    done = np.zeros((b,), bool)
    while not done.all():
        # free ALL finished lanes first (their lane goes inert), THEN grow
        # the live ones: a tight pool must see the reclaimed pages before
        # any capacity check can fail
        for i, sl in enumerate(slots):
            if done[i] and sl is not None:
                mgr.free(sl)
                slots[i] = None
        t_route, fn, fb = t_budget, step, no_feedback
        q_lens = np.zeros((b,), np.int32)
        tok_ids = np.zeros((t_route,), np.int32)
        tok_slot = np.full((t_route,), -1, np.int32)
        tok_pos = np.zeros((t_route,), np.int32)
        last_idx = np.full((b,), t_route, np.int32)   # idle sentinel
        spec_len = np.zeros((b,), np.int32)
        emit_mask = np.zeros((b,), np.int32)
        produced = np.zeros((b,), np.int32)
        if spec_k:
            # pages every live row will claim for its PLAIN tokens this
            # round, charged against draft allowances (the serving-path
            # reservation): drafts stay opportunistic — a pool an eos-
            # stopping plain run fits must never crash under speculation
            plain_need = {
                sl: mgr.plain_step_page_need(
                    sl, min(chunk, len(contexts[i]) - mgr.seq_len(sl)))
                for i, sl in enumerate(slots)
                if sl is not None and not done[i]}
            pending_need = sum(plain_need.values())
        w = 0
        for i, sl in enumerate(slots):
            if sl is None or done[i]:
                continue
            if spec_k:
                pending_need -= plain_need.pop(sl, 0)
            written = mgr.seq_len(sl)
            remaining = len(contexts[i]) - written
            d: list[int] = []
            if spec_k and remaining == 1:
                # decode round: draft up to k tokens, clamped so emission
                # can't overshoot the output budget (a lane one token from
                # done drafts nothing) and so drafts only claim pages no
                # live row needs for its plain tokens
                room = min(spec_k, max_new_tokens - len(outs[i]) - 1,
                           mgr.draft_allowance(sl, reserve=pending_need))
                if room > 0:
                    d = proposers[i].propose(contexts[i], room)
            n = (1 + len(d)) if d else min(chunk, remaining)
            if not mgr.ensure_capacity(sl, written + n):
                # an undersized pool must fail loudly: the dropped K/V
                # write would otherwise silently corrupt every later token
                raise RuntimeError(
                    f"KV cache exhausted growing slot {sl} to "
                    f"{written + n} tokens — pass a larger "
                    "num_pages (or use ServingPredictor, which preempts)")
            q_lens[sl] = n
            tok_ids[w:w + n] = (([contexts[i][written]] + d) if d
                                else contexts[i][written:written + n])
            tok_slot[w:w + n] = sl
            tok_pos[w:w + n] = np.arange(written, written + n)
            # the row whose logits decide the next token: the FIRST verify
            # row for a speculating lane, the last fed row otherwise
            last_idx[sl] = w + n - 1 - len(d)
            spec_len[sl] = len(d)
            if written + n - len(d) == len(contexts[i]):
                # this chunk reaches the context end: the lane emits.
                # sampling row j folds (base key, produced + j) IN-JIT —
                # keying by tokens PRODUCED (the ServingPredictor
                # convention) makes the sampled stream identical across
                # every spec k, including k = 0: speculation changes
                # cost, never output
                emit_mask[sl] = 1
                produced[sl] = len(outs[i])
            w += n
        packed = (params, jnp.asarray(tok_ids), jnp.asarray(tok_slot),
                  jnp.asarray(tok_pos), jnp.asarray(q_lens),
                  mgr.seq_lens_device(), jnp.asarray(last_idx))
        if spec_k:
            packed = packed + (jnp.asarray(spec_len),)
        packed = packed + (fb, zero_prev, jnp.asarray(emit_mask),
                           jnp.asarray(produced))
        tail = (mgr.page_table_device(), no_cow, no_cow, base_keys,
                temp_arr, topk_arr, topp_arr)
        pools = ((mgr.k_pages, mgr.v_pages, mgr.k_scales, mgr.v_scales)
                 if kv_quant else (mgr.k_pages, mgr.v_pages))
        res = fn(*packed, *pools, *tail)
        if spec_k:
            out_ids, n_emit = np.asarray(res[0]), np.asarray(res[1])
            mgr.update_pages(*res[4:])
        else:
            out_ids, n_emit = np.asarray(res[0]), None
            mgr.update_pages(*res[2:])
        for i, sl in enumerate(slots):
            if sl is None or q_lens[sl] == 0:
                continue
            if spec_len[sl]:
                # speculative round: 1 + accepted tokens are valid; the
                # rejected drafts' over-allocated pages roll back
                m = int(n_emit[sl])
                mgr.advance(sl, m)
                mgr.trim_pages(sl)
                emitted = [int(t) for t in out_ids[sl, :m]]
                proposers[i].update(int(spec_len[sl]), m - 1)
            else:
                mgr.advance(sl, int(q_lens[sl]))
                if mgr.seq_len(sl) < len(contexts[i]):
                    continue   # mid-prefill round: nothing emitted yet
                emitted = [int(out_ids[sl, 0] if spec_k else out_ids[sl])]
                if spec_k:
                    proposers[i].update(0, 0)
            for tok in emitted:
                if done[i]:
                    break   # budget/eos hit mid-batch: drop the overhang
                outs[i].append(tok)
                contexts[i].append(tok)
                if eos_token_id is not None and tok == eos_token_id:
                    done[i] = True
                if len(outs[i]) >= max_new_tokens:
                    done[i] = True
    # traces THIS call added: 1 on a cold shape, 0 when the cached jit
    # already compiled it — never per-token (the no-retrace gate)
    generate_paged.last_decode_trace_count = (step.trace_count[0]
                                              - traces_at_entry)
    # rows that stopped early (eos) pad with the eos id, as before
    n_cols = max(len(o) for o in outs)
    pad = eos_token_id if eos_token_id is not None else 0
    arr = np.full((b, n_cols), pad, np.int64)
    for i, o in enumerate(outs):
        arr[i, :len(o)] = o
    return Tensor(jnp.asarray(arr, jnp.int64))
