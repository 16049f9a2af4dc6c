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

from .deepseek_v2 import init_params as _init_params
from .deepseek_v2 import routed_ffn

WINDOW, FULL = "sliding_attention", "full_attention"


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

    def num_params(self) -> int:
        import jax

        return sum(int(math.prod(s.shape))
                   for s in jax.tree.leaves(param_shapes(self)))


def stack_runs(config):
    """``[(kind, layers)]``: the runs of equal layers, in order."""
    runs = []
    for kind in config.layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


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

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    return {"tok_emb": sds(cfg.vocab_size, cfg.hidden_size),
            "lnf_g": sds(cfg.hidden_size),
            "stacks": tuple(
                {k: sds(*((n,) if n > 1 else ()), *s)
                 for k, s in _layer_shapes(cfg).items()}
                for _, n in stack_runs(cfg))}


def init_params(config, seed: int, dtype=None):
    """Seeded weights on the device in ``dtype``
    (``deepseek_v2.init_params`` over this family's tree). That rule fills a
    leaf of a stack one layer at a time; an unstacked run's matrices are
    made as a stack of one and handed over without it (filled by their
    first dimension they would be 4,096 draws of one row each)."""
    import jax

    shapes = param_shapes(config, dtype)
    lifted = [{k for k, s in run.items() if n == 1 and s.ndim == 2}
              for run, (_, n) in zip(shapes["stacks"], stack_runs(config))]
    tree = _init_params(config, seed, dtype, shapes=dict(shapes, stacks=tuple(
        {k: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype) if k in up else s
         for k, s in run.items()}
        for run, up in zip(shapes["stacks"], lifted))))
    return dict(tree, stacks=tuple(
        {k: v[0] if k in up else v for k, v in run.items()}
        for run, up in zip(tree["stacks"], lifted)))


# ---------------------------------------------------------------------------
# the block's parts, as pure functions (the eager forward and the serving
# step both call these)
# ---------------------------------------------------------------------------


def layer_norm(x, g, eps):
    """Cohere's LayerNorm: statistics in float32, a weight and no bias (the
    serving step's own norm, ``models/gpt.py _srv_ln``, without its bias)."""
    from .gpt import _srv_ln

    return _srv_ln(x, g, None, eps)


def rope_interleaved(x, positions, theta):
    """Rotate ``x [t, heads, d]`` by its row's integer position over the whole
    head, INTERLEAVED pairs ``(x[2j], x[2j+1])`` at frequency ``theta ** (-2j
    / d)`` (``rope_gptj``; float32 inside, ``x``'s dtype out). The pair's
    partner comes by a roll along the head, not by a reshape to pairs: the
    values stay in whole 128-lane rows."""
    import jax.numpy as jnp
    import numpy as np

    d = x.shape[-1]
    inv = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(np.repeat(inv, 2), jnp.float32))[:, None, :]
    xf = x.astype(jnp.float32)
    even = (jnp.arange(d) % 2) == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * jnp.cos(ang) + partner * jnp.sin(ang)).astype(x.dtype)


def split_qkv(config, qkv):
    """``[..., (nh + 2 nkv) * hd]`` to ``q [..., nh, hd]``, ``k`` and ``v
    [..., nkv, hd]``."""
    from .gpt import _split_qkv

    return _split_qkv(qkv, config.num_heads, config.head_dim, False,
                      nkv=config.num_kv_heads)


def attention_kind(config, kind):
    """``(window, rope theta)`` of a layer kind: what ``mha`` does beyond the
    GPT block's (``models/gpt.py``): a window layer rotates and sees its
    window; a full layer has neither."""
    if kind == WINDOW:
        return config.sliding_window, config.rope_theta
    return None, None


def attention(config, p, y, positions, kind):
    """Causal grouped-query attention over ONE sequence ``y [s, h]``: ``[s,
    nh * hd]`` (plain products; the serving step reads the paged cache
    through its kernel instead)."""
    import jax
    import jax.numpy as jnp

    cfg = config
    window, theta = attention_kind(cfg, kind)
    q, k, v = split_qkv(cfg, y @ p["wqkv"])
    if theta is not None:
        q = rope_interleaved(q, positions, theta)
        k = rope_interleaved(k, positions, theta)
    s, group = y.shape[0], cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(s, cfg.num_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum("qhgd,khd->hgqk", qg, k).astype(jnp.float32)
    seen = positions[:, None] >= positions[None, :]
    if window is not None:
        seen &= positions[:, None] - positions[None, :] < window
    scores = jnp.where(seen, scores * cfg.head_dim ** -0.5, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(s, -1)


def run_layers(stack, n):
    """The ``n`` layers of one run, each as its own unstacked weights."""
    if n == 1:
        return [stack]
    return [{k: v[i] for k, v in stack.items()} for i in range(n)]


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


class Cohere2MoeForCausalLM:
    """The model as ``ServingPredictor`` takes it: ``config`` and the weight
    tree (:func:`init_params`), in ``dtype``, on the device. Inference only;
    ``__call__`` is the eager full forward."""

    def __init__(self, config: Cohere2MoeConfig, *, seed: int = 0,
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
