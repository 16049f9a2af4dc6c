"""Cohere2 with routed experts (``model_type: cohere2_moe``; Command A+): the
Cohere2 PARALLEL block over grouped-query attention whose layers come in two
kinds, and an expert layer in every block.

Per token t with residual ``x_t`` in one layer, ``y = LayerNorm(x)`` (a weight,
no bias, :func:`layer_norm`):

- attention: ``q = y Wq`` as ``num_heads`` heads, ``k = y Wk`` and ``v = y Wv``
  as ``num_kv_heads`` heads (one fused ``wqkv = [Wq | Wk | Wv]``, no bias, no
  query/key norm); query head i reads key-value head ``i // (num_heads //
  num_kv_heads)``. A ``window`` layer (``layer_types: sliding_attention``)
  rotates q and k over the whole head by position, INTERLEAVED pairs
  ``(x[2j], x[2j+1])`` (:func:`rope_interleaved`; ``rope_gptj``), and a row at
  position p sees keys ``p - sliding_window + 1 .. p``; a ``full`` layer
  (``full_attention``) has NO positional encoding and a causal mask. Scores
  over ``sqrt(head_dim)``, softmax in float32;
- experts (every layer): ``s = sigmoid(y W_r)`` over all published experts,
  the k largest, gates ``s_e / sum_chosen s`` (no bias, no scale); gated SiLU
  experts; this chip may hold a SHARE of them (``experts_held``); plus the
  MEAN of ``n_shared_experts`` shared gated MLPs, held as ONE MLP of their
  summed width whose output is scaled by ``1 / n_shared_experts``
  (``models/deepseek_v2.py routed_ffn``, ``shared_expert_scale``);
- ``x' = x + attention(y) Wo + experts(y)``: one norm feeds both.

After the last layer ``LayerNorm``, then the tied head ``logits = y E^T *
logit_scale``.

The weight tree is ``tok_emb lnf_g`` and ``stacks``: a tuple with one entry per
RUN of equal layers, in order (:func:`stack_runs`). A run of several layers is
stacked ``[n, ...]`` and scanned by the serving step; a run of ONE layer holds
its weights unstacked (no scan, so no copy of a weight out of a stack of one).
Per layer::

    ln1_g [h]  wqkv [h, (nh + 2 nkv) * hd]  wo [nh * hd, h]
    moe_gate [h, E published]  moe_w_gu [held, h, 2f]  moe_w_d [held, f, h]
    sh_w_gu [h, 2 fs]  sh_w_d [fs, h]      (fs = n_shared_experts * f)

The cache keeps a window layer's last ``sliding_window`` positions alone
(``inference/kv_cache.py``, the window group). Not run: the vision tower.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from .deepseek_v2 import routed_ffn
from .layer_kinds import (FULL, WINDOW, attention, rope_interleaved,
                          run_layers, stack_runs, stacked_shapes)
from .layer_kinds import CausalLM, init_params as _init_runs


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 200000
    moe_intermediate_size: int = 4096
    #: the routed experts HELD here (the weights' leading size); the router
    #: is ``n_routed_experts_published`` wide
    n_routed_experts: int = 128
    n_routed_experts_published: int = 128
    #: the first held expert's index among the published ones
    experts_held_first: int = 0
    n_shared_experts: int = 4
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    #: per layer :data:`WINDOW` or :data:`FULL`; published: three window
    #: layers, then a full one
    layer_types: tuple = ()
    logit_scale: float = 1.0
    initializer_range: float = 0.02
    #: one norm feeds attention and the expert layer, both added to x
    parallel_block: bool = True

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 3 else WINDOW for i in range(self.num_layers))
        if (len(self.layer_types) != self.num_layers
                or set(self.layer_types) - {WINDOW, FULL}):
            raise ValueError(
                f"layer_types {self.layer_types} must name {WINDOW} or "
                f"{FULL} for each of {self.num_layers} layers")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} must be a multiple of "
                f"num_kv_heads {self.num_kv_heads}")

    @property
    def experts_held(self):
        """``(first, count)`` where this chip holds a share of the routed
        experts, else None."""
        if self.n_routed_experts == self.n_routed_experts_published:
            return None
        return (self.experts_held_first, self.n_routed_experts)

    @property
    def shared_expert_scale(self) -> float:
        """The shared experts are AVERAGED: their one summed MLP, over their
        number."""
        return 1.0 / self.n_shared_experts

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers

    @property
    def num_window_layers(self) -> int:
        return sum(k == WINDOW for k in self.layer_types)

    @property
    def rotary(self):
        """The window layers' rotary pairing: interleaved (``rope_gptj``)."""
        return rope_interleaved

    def num_params(self) -> int:
        import jax

        return sum(int(math.prod(s.shape))
                   for s in jax.tree.leaves(param_shapes(self)))


def _layer_shapes(config):
    cfg = config
    h, hd, f = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    fs = cfg.n_shared_experts * f
    return {
        "ln1_g": (h,),
        "wqkv": (h, (cfg.num_heads + 2 * cfg.num_kv_heads) * hd),
        "wo": (cfg.num_heads * hd, h),
        "moe_gate": (h, cfg.n_routed_experts_published),
        "moe_w_gu": (cfg.n_routed_experts, h, 2 * f),
        "moe_w_d": (cfg.n_routed_experts, f, h),
        "sh_w_gu": (h, 2 * fs), "sh_w_d": (fs, h),
    }


def param_shapes(config, dtype=None):
    """The weight tree as ``jax.ShapeDtypeStruct`` leaves: a run of one layer
    unstacked."""
    import jax
    import jax.numpy as jnp

    cfg = config
    dt = jnp.dtype(dtype or jnp.float32)
    return {"tok_emb": jax.ShapeDtypeStruct((cfg.vocab_size, cfg.hidden_size),
                                            dt),
            "lnf_g": jax.ShapeDtypeStruct((cfg.hidden_size,), dt),
            "stacks": stacked_shapes(cfg, _layer_shapes(cfg), dt)}


def init_params(config, seed: int, dtype=None):
    """Seeded weights on the device in ``dtype`` (``layer_kinds.init_params``
    over this family's tree)."""
    return _init_runs(config, seed, param_shapes(config, dtype), dtype)


# ---------------------------------------------------------------------------
# the block's parts, as pure functions (the eager forward and the serving
# step both call these)
# ---------------------------------------------------------------------------


def layer_norm(x, g, eps):
    """Cohere's LayerNorm: statistics in float32, a weight and no bias (the
    serving step's own norm, ``models/gpt.py _srv_ln``, without its bias)."""
    from .gpt import _srv_ln

    return _srv_ln(x, g, None, eps)


def forward(config, params, ids):
    """Logits ``[b, s, V]`` of token ids ``[b, s]``: the whole stack over
    whole sequences, no cache."""
    import jax
    import jax.numpy as jnp

    cfg = config
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)

    def one_sequence(row):
        x = jnp.take(params["tok_emb"], row, axis=0)
        for stack, (kind, n) in zip(params["stacks"], stack_runs(cfg)):
            for p in run_layers(stack, n):
                y = layer_norm(x, p["ln1_g"], cfg.layer_norm_eps)
                a = attention(cfg, p, y, pos, kind)
                x = x + a @ p["wo"] + routed_ffn(cfg, p, y)
        return layer_norm(x, params["lnf_g"], cfg.layer_norm_eps)

    h = jax.lax.map(one_sequence, jnp.asarray(ids, jnp.int32))
    return jnp.einsum("bsh,vh->bsv", h, params["tok_emb"]) * cfg.logit_scale


class Cohere2MoeForCausalLM(CausalLM):
    """The model as ``ServingPredictor`` takes it (``layer_kinds.CausalLM``):
    ``Cohere2MoeForCausalLM(Cohere2MoeConfig(...), seed=, dtype=)``."""

    init_params = staticmethod(init_params)
    forward = staticmethod(forward)
