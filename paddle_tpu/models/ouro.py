"""Ouro (``ByteDance/Ouro-2.6B``, a looped language model): ONE stack of
decoder layers run ``total_ut_steps`` times per token, each loop taking the
last loop's output as its input.

Per token with residual ``x`` in layer l, on every loop t (RMSNorm: a weight,
no bias):

- ``y = RMSNorm(x; ln1_g)``; ``q, k, v = y Wq, y Wk, y Wv`` as ``num_heads``
  heads of ``head_dim`` (one fused ``wqkv = [Wq | Wk | Wv]``, no bias, no
  query/key norm; as many key-value heads as query heads); rotary on q and k
  over the whole head in HALF-SPLIT pairs (:func:`layer_kinds.rope_half`,
  ``rope_theta``) at the token's own position, the same in every loop; a
  causal softmax over scores divided by ``sqrt(head_dim)``, in float32. A row
  at loop t reads the keys and values that loop t wrote for its layer: the
  cache keeps them apart, plane :meth:`OuroConfig.cache_plane` ``(t, l) = t *
  num_layers + l`` of a pool stack of ``num_layers * total_ut_steps`` planes;
- ``h = x + RMSNorm(attn Wo; ln1b_g)``;
- ``x' = h + RMSNorm(W_d (silu(W_g z) * W_u z); ln2b_g)`` with ``z =
  RMSNorm(h; ln2_g)``: the SANDWICH block, a norm after each sublayer as well
  as before it (the published ``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2``);
- after layer ``num_layers - 1`` of loop t: ``x = RMSNorm(x; lnf_g)``, one
  norm shared by every loop. After the last loop that normed state is the
  head's input; the head is untied (``lm_head``).

``early_exit_gate`` (``exit_gate_w [h, 1]``, ``exit_gate_b [1]``) is built
with the weights. At the published ``early_exit_threshold`` 1.0 every token
runs every loop and the gate decides nothing; a threshold under 1 (rows that
leave after different loops in one step) is not built and is refused here.

The weight tree is ``tok_emb lnf_g lm_head exit_gate_w exit_gate_b`` and
``layers``, the stack scanned by the serving step (``models/gpt.py
build_unified_step``, which runs it once a loop). Per layer::

    ln1_g ln1b_g ln2_g ln2b_g [h]  wqkv [h, 3 nh hd]  wo [nh hd, h]
    w_gu [h, 2 f]  w_d [f, h]

:func:`forward` is the plain reference of this mathematics: float32
``jax.numpy`` at ``precision="highest"``, no kernel, cache or batching.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from .layer_kinds import FULL, CausalLM, rope_half


@dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    max_seq_len: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    #: loops over the whole stack per token
    total_ut_steps: int = 4
    #: the early-exit gate's threshold: 1.0 runs every loop for every token
    early_exit_threshold: float = 1.0
    initializer_range: float = 0.02
    #: every layer rotates (``layer_kinds.attention_kind``)
    rope_full_layers = True

    def __post_init__(self):
        if self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {self.early_exit_threshold} < 1: rows "
                "that leave the loop after different numbers of loops in one "
                "step are not built; every row runs all "
                f"{self.total_ut_steps} loops (threshold 1.0)")
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps must be >= 1, got {self.total_ut_steps}")
        if self.num_kv_heads != self.num_heads:
            raise ValueError(
                f"Ouro's attention is multi-head: num_kv_heads "
                f"{self.num_kv_heads} must equal num_heads {self.num_heads}")

    @property
    def layer_types(self):
        return (FULL,) * self.num_layers

    @property
    def rotary(self):
        """The rotary pairing: half-split."""
        return rope_half

    @property
    def cache_planes(self) -> int:
        """Planes of the pool stack: one a (loop, layer)."""
        return self.num_layers * self.total_ut_steps

    def cache_plane(self, loop, layer):
        """The plane of the pool stack that loop ``loop`` of layer ``layer``
        writes and reads (integers or traced)."""
        return loop * self.num_layers + layer

    def num_params(self) -> int:
        import jax

        return sum(int(math.prod(s.shape))
                   for s in jax.tree.leaves(param_shapes(self)))


def _layer_shapes(config):
    cfg = config
    h, f = cfg.hidden_size, cfg.intermediate_size
    return {"ln1_g": (h,), "ln1b_g": (h,), "ln2_g": (h,), "ln2b_g": (h,),
            "wqkv": (h, 3 * cfg.num_heads * cfg.head_dim),
            "wo": (cfg.num_heads * cfg.head_dim, h),
            "w_gu": (h, 2 * f), "w_d": (f, h)}


def param_shapes(config, dtype=None):
    """The weight tree as ``jax.ShapeDtypeStruct`` leaves."""
    import jax
    import jax.numpy as jnp

    cfg = config
    dt = jnp.dtype(dtype or jnp.float32)
    h, v, n = cfg.hidden_size, cfg.vocab_size, cfg.num_layers

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    return {"tok_emb": sds(v, h), "lnf_g": sds(h), "lm_head": sds(h, v),
            "exit_gate_w": sds(h, 1), "exit_gate_b": sds(1),
            "layers": {k: sds(n, *s) for k, s in _layer_shapes(cfg).items()}}


def init_params(config, seed: int, dtype=None):
    """Seeded weights on the device in ``dtype`` (``deepseek_v2.init_params``'s
    rule: normal at ``initializer_range``, norm weights one, a layer at a
    time)."""
    from .deepseek_v2 import init_params as _init_params

    return _init_params(config, seed, dtype,
                        shapes=param_shapes(config, dtype))


def forward(config, params, ids):
    """Logits ``[b, s, V]`` of token ids ``[b, s]``: the plain reference, in
    float32 at ``precision="highest"``."""
    import jax
    import jax.numpy as jnp

    cfg = config
    nh, hd = cfg.num_heads, cfg.head_dim
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    layers = [{k: v[i] for k, v in f32["layers"].items()}
              for i in range(cfg.num_layers)]
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)

    def rms(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + cfg.rms_norm_eps) * g

    def layer(x, p):
        s = x.shape[0]
        qkv = (rms(x, p["ln1_g"]) @ p["wqkv"]).reshape(s, 3, nh, hd)
        q = rope_half(qkv[:, 0], pos, cfg.rope_theta)
        k = rope_half(qkv[:, 1], pos, cfg.rope_theta)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                          qkv[:, 2]).reshape(s, nh * hd)
        h = x + rms(attn @ p["wo"], p["ln1b_g"])
        gu = rms(h, p["ln2_g"]) @ p["w_gu"]
        half = gu.shape[-1] // 2
        f = (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ p["w_d"]
        return h + rms(f, p["ln2b_g"])

    def one_sequence(row):
        x = f32["tok_emb"][row]
        for _ in range(cfg.total_ut_steps):
            for p in layers:
                x = layer(x, p)
            x = rms(x, f32["lnf_g"])
        return x @ f32["lm_head"]

    with jax.default_matmul_precision("highest"):
        return jnp.stack([one_sequence(row) for row in ids])


class OuroForCausalLM(CausalLM):
    """The model as ``ServingPredictor`` takes it (``layer_kinds.CausalLM``):
    ``OuroForCausalLM(OuroConfig(...), seed=, dtype=)``."""

    init_params = staticmethod(init_params)
    forward = staticmethod(forward)
