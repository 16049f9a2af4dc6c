"""What the models whose layers come in attention KINDS share
(``models/cohere2_moe.py``, ``models/smallthinker.py``): the two kinds, the
runs of equal layers the weight tree and the serving step are laid out in, the
two rotary pairings, and grouped-query attention over one sequence.

A ``window`` layer rotates q and k by position and a row at position p sees
keys ``p - sliding_window + 1 .. p``; a ``full`` layer has no positional
encoding and a causal mask. Which rotary pairing a model uses is its
configuration's (``config.rotary``): INTERLEAVED pairs ``(x[2j], x[2j+1])``
(:func:`rope_interleaved`) or the HALF-SPLIT pairs ``(x[j], x[j + d/2])``
(:func:`rope_half`, Llama's ``rotate_half``).

The weight tree of such a model is ``stacks``: one entry per RUN of equal
layers, in order (:func:`stack_runs`); a run of several layers stacked ``[n,
...]`` and scanned by the serving step, a run of ONE layer unstacked (no scan,
so no copy of a weight out of a stack of one).
"""
from __future__ import annotations

WINDOW, FULL = "sliding_attention", "full_attention"


def stack_runs(config):
    """``[(kind, layers)]``: the runs of equal layers, in order."""
    runs = []
    for kind in config.layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def attention_kind(config, kind):
    """``(window, rope theta)`` of a layer kind: what ``mha`` does beyond the
    GPT block's (``models/gpt.py``): a window layer rotates and sees its
    window; a full layer sees every causal key and rotates only where the
    configuration says so (``rope_full_layers``: ``models/ouro.py``)."""
    if kind == WINDOW:
        return config.sliding_window, config.rope_theta
    return None, (config.rope_theta
                  if getattr(config, "rope_full_layers", False) else None)


def _angles(positions, theta, d, repeat):
    """Rotation angles ``[t, 1, d]`` float32 for integer ``positions [t]``:
    frequency ``theta ** (-2j / d)`` for pair j, laid out per value of the
    head by ``repeat`` (each pair's two values side by side, or the pairs'
    first values and then their second)."""
    import jax.numpy as jnp
    import numpy as np

    inv = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    per_value = np.repeat(inv, 2) if repeat else np.tile(inv, 2)
    return (positions.astype(jnp.float32)[:, None]
            * jnp.asarray(per_value, jnp.float32))[:, None, :]


def rope_interleaved(x, positions, theta):
    """Rotate ``x [t, heads, d]`` by its row's integer position over the whole
    head, INTERLEAVED pairs ``(x[2j], x[2j+1])`` at frequency ``theta ** (-2j
    / d)`` (``rope_gptj``; float32 inside, ``x``'s dtype out). The pair's
    partner comes by a roll along the head, not by a reshape to pairs: the
    values stay in whole 128-lane rows."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = _angles(positions, theta, d, repeat=True)
    xf = x.astype(jnp.float32)
    even = (jnp.arange(d) % 2) == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    return (xf * jnp.cos(ang) + partner * jnp.sin(ang)).astype(x.dtype)


def rope_half(x, positions, theta):
    """The same rotation over HALF-SPLIT pairs ``(x[j], x[j + d/2])``:
    ``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2 | x1]``
    (Llama's form). The partner comes by a roll of half the head."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = _angles(positions, theta, d, repeat=False)
    xf = x.astype(jnp.float32)
    partner = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0) * jnp.roll(
        xf, d // 2, axis=-1)
    return (xf * jnp.cos(ang) + partner * jnp.sin(ang)).astype(x.dtype)


def split_qkv(config, qkv):
    """``[..., (nh + 2 nkv) * hd]`` to ``q [..., nh, hd]``, ``k`` and ``v
    [..., nkv, hd]``."""
    from .gpt import _split_qkv

    return _split_qkv(qkv, config.num_heads, config.head_dim, False,
                      nkv=config.num_kv_heads)


def attention(config, p, y, positions, kind):
    """Causal grouped-query attention over ONE sequence ``y [s, h]``: ``[s,
    nh * hd]`` (plain products; the serving step reads the paged cache
    through its kernel instead). Query head i reads key-value head ``i //
    (nh / nkv)``; scores over ``sqrt(head_dim)``, softmax in float32."""
    import jax
    import jax.numpy as jnp

    cfg = config
    window, theta = attention_kind(cfg, kind)
    q, k, v = split_qkv(cfg, y @ p["wqkv"])
    if theta is not None:
        q = cfg.rotary(q, positions, theta)
        k = cfg.rotary(k, positions, theta)
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


def stacked_shapes(config, layer_shapes, dtype):
    """``stacks`` of ``jax.ShapeDtypeStruct``: a run of one layer unstacked,
    ``layer_shapes`` one layer's ``{name: shape}``."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype or jnp.float32)
    return tuple({k: jax.ShapeDtypeStruct((*((n,) if n > 1 else ()), *s), dt)
                  for k, s in layer_shapes.items()}
                 for _, n in stack_runs(config))


def init_params(config, seed: int, shapes, dtype=None):
    """Seeded weights of the tree ``shapes`` on the device in ``dtype``
    (``deepseek_v2.init_params``'s rule). That rule fills a leaf of a stack
    one layer at a time; an unstacked run's matrices are made as a stack of
    one and handed over without it (filled by their first dimension they
    would be thousands of draws of one row each)."""
    import jax

    from .deepseek_v2 import init_params as _init_params

    lifted = [{k for k, s in run.items() if n == 1 and s.ndim == 2}
              for run, (_, n) in zip(shapes["stacks"], stack_runs(config))]
    tree = _init_params(config, seed, dtype, shapes=dict(shapes, stacks=tuple(
        {k: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype) if k in up else s
         for k, s in run.items()}
        for run, up in zip(shapes["stacks"], lifted))))
    return dict(tree, stacks=tuple(
        {k: v[0] if k in up else v for k, v in run.items()}
        for run, up in zip(tree["stacks"], lifted)))


class CausalLM:
    """The model as ``ServingPredictor`` takes it: ``config`` and the weight
    tree (the family's ``init_params``), in ``dtype``, on the device.
    Inference only; ``__call__`` is the family's eager full forward. A family
    names its two functions as ``init_params`` and ``forward``."""

    init_params = forward = None

    def __init__(self, config, *, seed: int = 0, dtype=None, params=None):
        self.config = config
        self.params = params
        if params is None:
            from ..observability.tracing import phase

            with phase("weights.make") as made:
                self.params = self.init_params(config, seed, dtype)
                made.end_when_ready(self.params)

    def eval(self):
        return self

    def __call__(self, input_ids):
        import jax.numpy as jnp

        from ..tensor.tensor import Tensor

        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        return Tensor(self.forward(self.config, self.params,
                                   jnp.asarray(ids, jnp.int32)))
