"""GLM-5 family (``model_type: glm_moe_dsa``): DeepSeek-V2's latent attention
with a LOW-RANK query, DeepSeek Sparse Attention's learned indexer choosing
the keys every attention layer reads (DeepSeek-V3.2-Exp), the selection
SHARED by the layers after the one that made it (``indexer_types``), and
DeepSeek-V3's sigmoid ``noaux_tc`` routing. The block's parts are
``models/deepseek_v2.py``'s (``latent_qkv``, ``absorb_query``, ``rms_norm``,
``gated_mlp``, ``routed_ffn``: imported, not forked); this file adds what the
family adds.

Per token t with normalised residual ``y_t`` in one layer:

- query: ``cq = rms_norm(y W_q_a)`` (``q_lora_rank``), heads ``cq W_q_b``;
  plain rotary (``rope_type: default``), scores scaled by ``head_dim^-1/2``;
- a ``full`` layer's INDEXER: ``qI_j = (cq W_qI)_j`` for ``index_n_heads``
  heads of ``index_head_dim``, ``kI = LayerNorm(y W_kI)`` (weight and bias),
  rotary on the first ``qk_rope_head_dim`` values of both, ``w_j = (y
  W_wI)_j * heads^-1/2 * dim^-1/2``; ``I[t, s] = sum_j w_j relu(qI_j . kI_s)``
  for ``s <= t``; ``S_t`` = the ``index_topk`` positions with the largest
  ``I[t, s]`` (all of them while there are no more; ties to the lower
  position). ``kI`` is what the cache's second plane holds;
- attention of token t runs over ``S_t`` alone; a ``shared`` layer has no
  indexer and uses the ``S_t`` of the nearest ``full`` layer before it;
- routed layers: ``s = sigmoid(y W_g)``, the k largest of ``s + bias``
  (``moe_bias``: ``e_score_correction_bias``), gates ``s_e / sum_chosen s *
  routed_scaling_factor``; this chip may hold a SHARE of the experts
  (``experts_held``): it routes over all of them and computes its own.

The weight tree is ``tok_emb lnf_g lm_head`` and ``stacks``: a tuple with one
stack ``[n, ...]`` per RUN of equal layers, in order (:func:`stack_runs`; a
layer's kind is whether its FFN is routed and whether it has an indexer).
The serving step scans each run (``models/gpt.py build_unified_step``) and
carries the selection from run to run. Per layer::

    ln1_g wq_a [h, ql] q_ln_g [ql] wq_b [ql, nh*(nope+rope)] wkv_a kv_ln_g
    wkv_b wo ln2_g   (+ full: idx_wq [ql, hI*dI] idx_wk [h, dI] idx_k_ln_g
    idx_k_ln_b [dI] idx_ww [h, hI])
    dense: w_gu w_d      routed: moe_gate [h, E] moe_bias [E]
    moe_w_gu [held, h, 2f] moe_w_d [held, f, h] sh_w_gu sh_w_d

Not run: the multi-token-prediction module (a drafter beside the main
forward pass).
"""
from __future__ import annotations

from dataclasses import dataclass

from .deepseek_v2 import (DeepseekV2Config, apply_rope, attention_expanded,
                          gated_mlp, latent_qkv, rms_norm, rope_cos_sin,
                          routed_ffn)
from .deepseek_v2 import _layer_shapes as _dsv2_layer_shapes
from .deepseek_v2 import init_params as _init_params
from ..ops.pallas.dsa_index import select_topk


@dataclass
class GlmMoeDsaConfig(DeepseekV2Config):
    vocab_size: int = 154880
    hidden_size: int = 6144
    num_layers: int = 78
    num_heads: int = 64
    max_seq_len: int = 1048576
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    #: the routed experts HELD here (the weights' leading size); the router
    #: is ``n_routed_experts_published`` wide
    n_routed_experts: int = 256
    n_routed_experts_published: int = 256
    #: the first held expert's index among the published ones
    experts_held_first: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    q_lora_rank: int = 2048
    qk_nope_head_dim: int = 192
    v_head_dim: int = 256
    rms_norm_eps: float = 1e-5
    rope_theta: float = 8e6
    rope_scaling: dict | None = None
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    #: per layer ``"full"`` (has an indexer) or ``"shared"`` (uses the
    #: selection of the nearest full layer before it); layer 0 is full
    indexer_types: tuple = ()

    def __post_init__(self):
        self.indexer_types = tuple(self.indexer_types) or tuple(
            "full" if i < 3 or (i - 2) % 4 == 0 else "shared"
            for i in range(self.num_layers))
        if (len(self.indexer_types) != self.num_layers
                or self.indexer_types[0] != "full"
                or set(self.indexer_types) - {"full", "shared"}):
            raise ValueError(
                f"indexer_types {self.indexer_types} must name full or "
                f"shared for each of {self.num_layers} layers, the first "
                "full")

    @property
    def experts_held(self):
        """``(first, count)`` where this chip holds a share of the routed
        experts, else None."""
        if self.n_routed_experts == self.n_routed_experts_published:
            return None
        return (self.experts_held_first, self.n_routed_experts)

    @property
    def num_index_layers(self) -> int:
        return sum(k == "full" for k in self.indexer_types)

    def num_params(self) -> int:
        import math

        import jax

        return sum(int(math.prod(s.shape))
                   for s in jax.tree.leaves(param_shapes(self)))


def stack_runs(config):
    """``[(routed, full, layers)]``: the runs of equal layers, in order."""
    runs = []
    for i, kind in enumerate(config.indexer_types):
        key = (i >= config.num_dense_layers, kind == "full")
        if runs and runs[-1][:2] == key:
            runs[-1] = key + (runs[-1][2] + 1,)
        else:
            runs.append(key + (1,))
    return runs


def _layer_shapes(config, routed: bool, full: bool):
    """DeepSeek-V2's layer with the query in two factors, the router at its
    published width beside its bias, and, ``full``, the indexer."""
    cfg = config
    h, ql = cfg.hidden_size, cfg.q_lora_rank
    shapes = _dsv2_layer_shapes(cfg, routed)   # experts: the HELD ones
    del shapes["wq"]
    shapes.update({"wq_a": (h, ql), "q_ln_g": (ql,),
                   "wq_b": (ql, cfg.num_heads * cfg.head_dim)})
    if full:
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        shapes.update({"idx_wq": (ql, hi * di), "idx_wk": (h, di),
                       "idx_k_ln_g": (di,), "idx_k_ln_b": (di,),
                       "idx_ww": (h, hi)})
    if routed:
        shapes.update({"moe_gate": (h, cfg.n_routed_experts_published),
                       "moe_bias": (cfg.n_routed_experts_published,)})
    return shapes


def param_shapes(config, dtype=None):
    """The weight tree as ``jax.ShapeDtypeStruct`` leaves."""
    import jax
    import jax.numpy as jnp

    cfg = config
    dt = jnp.dtype(dtype or jnp.float32)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    return {"tok_emb": sds(cfg.vocab_size, cfg.hidden_size),
            "lnf_g": sds(cfg.hidden_size),
            "lm_head": sds(cfg.hidden_size, cfg.vocab_size),
            "stacks": tuple(
                {k: sds(n, *s)
                 for k, s in _layer_shapes(cfg, routed, full).items()}
                for routed, full, n in stack_runs(cfg))}


#: the correction bias's seeded std as a share of the weights': 0.001 at std
#: 0.02, the size of ONE of the steps by which DeepSeek-V3's training moves it
#: toward balance (gamma = 0.001, from zero). It exists to even the experts'
#: load; seeded as wide as a weight it does the opposite: the sigmoid is flat
#: where the chosen experts' scores lie, so 0.02 of bias moved an expert's
#: popularity by half, a chip's 16 held experts drew 7.5-9.0 pairs a layer
#: where 8 are due, from seed to seed, and the cell's step time followed
#: (PERF.md, PR 34)
BIAS_STD_SHARE = 0.05


def init_params(config, seed: int, dtype=None):
    """Seeded weights on the device in ``dtype``
    (``deepseek_v2.init_params`` over this family's tree)."""
    return _init_params(config, seed, dtype,
                        shapes=param_shapes(config, dtype),
                        std_share={"moe_bias": BIAS_STD_SHARE})


# ---------------------------------------------------------------------------
# the indexer
# ---------------------------------------------------------------------------


def _layer_norm(x, g, b, eps):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def indexer_qkw(config, p, y, cq, positions):
    """A full layer's indexer projections for rows ``y [..., h]`` with query
    latents ``cq [..., q_lora_rank]`` at ``positions``: ``(qI [..., hI, dI],
    kI [..., dI], w [..., hI] float32)``, the rotary applied, ``w`` with both
    scale factors in it."""
    import jax.numpy as jnp

    cfg = config
    hi, di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    lead = y.shape[:-1]
    cos, sin = rope_cos_sin(cfg, positions)
    q = (cq @ p["idx_wq"]).reshape(*lead, hi, di)
    q = jnp.concatenate([apply_rope(q[..., :rope], cos[..., None, :],
                                    sin[..., None, :]), q[..., rope:]], -1)
    k = _layer_norm(y @ p["idx_wk"], p["idx_k_ln_g"], p["idx_k_ln_b"],
                    cfg.rms_norm_eps)
    k = jnp.concatenate([apply_rope(k[..., :rope], cos, sin), k[..., rope:]],
                        -1)
    w = (y @ p["idx_ww"]).astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
    return q, k, w


def index_scores(q_idx, k_idx, w):
    """``I[t, s] = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])`` for one
    sequence, float32 ``[s, s]`` (not yet causal)."""
    import jax
    import jax.numpy as jnp

    dots = jnp.einsum("qjd,kd->qjk", q_idx, k_idx).astype(jnp.float32)
    return jnp.einsum("qjk,qj->qk", jax.nn.relu(dots), w)


# ---------------------------------------------------------------------------
# the eager full forward (expanded attention over the selection)
# ---------------------------------------------------------------------------


def forward(config, params, ids, with_selection=False):
    """Logits ``[b, s, V]`` of token ids ``[b, s]``: the whole stack over
    whole sequences, no cache, attention in its expanded form over each
    token's selected keys. ``with_selection``: also the masks ``[b, full
    layers, s, s]``."""
    import jax
    import jax.numpy as jnp

    cfg = config
    s = ids.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_sequence(row):
        x = jnp.take(params["tok_emb"], row, axis=0)
        selected, masks = causal, []
        for stack, (routed, full, n) in zip(params["stacks"],
                                            stack_runs(cfg)):
            for i in range(n):
                p = {k: v[i] for k, v in stack.items()}
                y = rms_norm(x, p["ln1_g"], cfg.rms_norm_eps)
                q_nope, q_pe, latent, cq = latent_qkv(
                    cfg, p, y, pos, with_query_latent=True)
                if full:
                    selected = select_topk(
                        index_scores(*indexer_qkw(cfg, p, y, cq, pos)),
                        causal, cfg.index_topk)
                    masks.append(selected)
                a = attention_expanded(cfg, p, q_nope, q_pe, latent,
                                       selected=selected)
                x = x + a @ p["wo"]
                y = rms_norm(x, p["ln2_g"], cfg.rms_norm_eps)
                x = x + (routed_ffn(cfg, p, y) if routed
                         else gated_mlp(y, p["w_gu"], p["w_d"]))
        return rms_norm(x, params["lnf_g"], cfg.rms_norm_eps), \
            jnp.stack(masks)

    h, masks = jax.lax.map(one_sequence, jnp.asarray(ids, jnp.int32))
    logits = h @ params["lm_head"]
    return (logits, masks) if with_selection else logits


class GlmMoeDsaForCausalLM:
    """The model as ``ServingPredictor`` takes it: ``config`` and the weight
    tree (:func:`init_params`), in ``dtype``, on the device. Inference only;
    ``__call__`` is the eager full forward."""

    def __init__(self, config: GlmMoeDsaConfig, *, seed: int = 0,
                 dtype=None, params=None):
        self.config = config
        self.params = params
        if params is None:
            from ..observability.tracing import phase

            with phase("weights.make") as made:
                self.params = init_params(config, seed, dtype)
                made.end_when_ready(self.params)

    def eval(self):
        return self

    def __call__(self, input_ids):
        import jax.numpy as jnp

        from ..tensor.tensor import Tensor

        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        return Tensor(forward(self.config, self.params,
                              jnp.asarray(ids, jnp.int32)))
