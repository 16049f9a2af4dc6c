"""SmallThinker (``PowerInfer/SmallThinker-21BA3B-Instruct``): a pre-norm
block over grouped-query attention whose layers come in two kinds, and an
expert layer in every block whose ROUTER is placed before attention.

Per token t with residual ``x_t`` in one layer (RMSNorm: a weight, no bias):

- ``y1 = RMSNorm(x; ln1_g)``;
- attention over ``y1``: ``q = y1 Wq`` as ``num_heads`` heads, ``k = y1 Wk``
  and ``v = y1 Wv`` as ``num_kv_heads`` heads (one fused ``wqkv = [Wq | Wk |
  Wv]``, no bias, no query/key norm); query head i reads key-value head ``i //
  (num_heads / num_kv_heads)``. A ``window`` layer (``sliding_window_layout``
  and ``rope_layout`` 1) rotates q and k over the whole head in HALF-SPLIT
  pairs (:func:`layer_kinds.rope_half`) and a row at position p sees keys ``p
  - sliding_window + 1 .. p``; a ``full`` layer (both 0) has NO positional
  encoding and a causal mask. Scores over ``sqrt(head_dim)``, softmax in
  float32; ``h = x + attn Wo``;
- ``y2 = RMSNorm(h; ln2_g)``;
- experts: the router reads ``y1`` (``r = y1 W_r`` over all experts), the k
  largest ``r``, gates the softmax over those k; ReGLU experts ``(relu(y2 W_g)
  * y2 W_u) W_d`` (``hidden_act``); no shared expert, no leading dense layer;
  ``x' = h + sum_e gate_e E_e(y2)``.

After the last layer ``RMSNorm``, then the untied ``lm_head``.

The weight tree is ``tok_emb lnf_g lm_head`` and ``stacks``, one entry per RUN
of equal layers (``layer_kinds.stack_runs``; a run of one layer unstacked).
Per layer::

    ln1_g [h]  ln2_g [h]  wqkv [h, (nh + 2 nkv) * hd]  wo [nh * hd, h]
    moe_gate [h, E]  moe_w_gu [E, h, 2f]  moe_w_d [E, f, h]

The cache keeps a window layer's last ``sliding_window`` positions alone
(``inference/kv_cache.py``, the window group). The published model's
activation sparsity (its "primary and secondary experts") changes nothing of
this mathematics and is not exploited.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from .deepseek_v2 import rms_norm, routed_ffn
from .layer_kinds import (FULL, WINDOW, attention, rope_half, run_layers,
                          stack_runs, stacked_shapes)
from .layer_kinds import CausalLM, init_params as _init_runs


@dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    max_seq_len: int = 16384
    moe_intermediate_size: int = 768
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    #: gates the softmax over the chosen experts' router outputs
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    sliding_window: int = 4096
    #: per layer 1 where it rotates / sees a window (published: layer i is
    #: position-free and full where i % 4 == 0); the first ``num_layers``
    rope_layout: tuple = ()
    sliding_window_layout: tuple = ()
    initializer_range: float = 0.02
    #: the experts' gate: ReGLU
    hidden_act: str = "relu"
    #: the router reads the pre-attention norm's rows
    router_before_attention: bool = True
    layer_types: tuple = ()

    def __post_init__(self):
        n = self.num_layers
        period = tuple(int(i % 4 != 0) for i in range(n))
        rope = tuple(self.rope_layout[:n]) or period
        win = tuple(self.sliding_window_layout[:n]) or period
        if len(rope) != n or len(win) != n or rope != win:
            raise ValueError(
                f"rope_layout {rope} and sliding_window_layout {win} must "
                f"name the same {n} layers: a layer rotates exactly where it "
                "sees a window")
        self.layer_types = tuple(WINDOW if w else FULL for w in win)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} must be a multiple of "
                f"num_kv_heads {self.num_kv_heads}")

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers

    @property
    def num_window_layers(self) -> int:
        return sum(k == WINDOW for k in self.layer_types)

    @property
    def rotary(self):
        """The window layers' rotary pairing: half-split."""
        return rope_half

    def num_params(self) -> int:
        import jax

        return sum(int(math.prod(s.shape))
                   for s in jax.tree.leaves(param_shapes(self)))


def _layer_shapes(config):
    cfg = config
    h, hd, f = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    e = cfg.n_routed_experts
    return {
        "ln1_g": (h,), "ln2_g": (h,),
        "wqkv": (h, (cfg.num_heads + 2 * cfg.num_kv_heads) * hd),
        "wo": (cfg.num_heads * hd, h),
        "moe_gate": (h, e), "moe_w_gu": (e, h, 2 * f), "moe_w_d": (e, f, h),
    }


def param_shapes(config, dtype=None):
    """The weight tree as ``jax.ShapeDtypeStruct`` leaves: a run of one layer
    unstacked."""
    import jax
    import jax.numpy as jnp

    cfg = config
    dt = jnp.dtype(dtype or jnp.float32)
    h, v = cfg.hidden_size, cfg.vocab_size
    return {"tok_emb": jax.ShapeDtypeStruct((v, h), dt),
            "lnf_g": jax.ShapeDtypeStruct((h,), dt),
            "lm_head": jax.ShapeDtypeStruct((h, v), dt),
            "stacks": stacked_shapes(cfg, _layer_shapes(cfg), dt)}


def init_params(config, seed: int, dtype=None):
    """Seeded weights on the device in ``dtype`` (``layer_kinds.init_params``
    over this family's tree)."""
    return _init_runs(config, seed, param_shapes(config, dtype), dtype)


def forward(config, params, ids):
    """Logits ``[b, s, V]`` of token ids ``[b, s]``: the whole stack over
    whole sequences, no cache, from the same parts the serving step calls."""
    import jax
    import jax.numpy as jnp

    cfg = config
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)

    def one_sequence(row):
        x = jnp.take(params["tok_emb"], row, axis=0)
        for stack, (kind, n) in zip(params["stacks"], stack_runs(cfg)):
            for p in run_layers(stack, n):
                y1 = rms_norm(x, p["ln1_g"], cfg.rms_norm_eps)
                x = x + attention(cfg, p, y1, pos, kind) @ p["wo"]
                y2 = rms_norm(x, p["ln2_g"], cfg.rms_norm_eps)
                x = x + routed_ffn(cfg, p, y2, route=y1)
        return rms_norm(x, params["lnf_g"], cfg.rms_norm_eps)

    h = jax.lax.map(one_sequence, jnp.asarray(ids, jnp.int32))
    return h @ params["lm_head"]


class SmallThinkerForCausalLM(CausalLM):
    """The model as ``ServingPredictor`` takes it (``layer_kinds.CausalLM``):
    ``SmallThinkerForCausalLM(SmallThinkerConfig(...), seed=, dtype=)``."""

    init_params = staticmethod(init_params)
    forward = staticmethod(forward)
