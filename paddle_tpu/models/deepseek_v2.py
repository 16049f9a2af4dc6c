"""DeepSeek-V2 family (DeepSeek-V2, arXiv:2405.04434; ``model_type:
deepseek_v2``): multi-head latent attention (MLA), a leading dense gated-SiLU
layer, then layers of routed experts beside shared ones.

One pre-norm residual block, RMSNorm (weight only), no bias anywhere, rotary
positions on a 64-wide slice of every query head and on ONE key slice shared
by all heads (YaRN frequencies), untied head. Per token and layer the cache
holds one LATENT row ``[c | k_pe]``: the normalised ``kv_lora_rank`` latent
followed by the rotated shared key slice; K and V of every head are linear in
``c``, so attention can run two ways:

- EXPANDED (:func:`attention_expanded`): ``c W_kv_b`` gives each head its
  ``[k_nope | v]`` and plain causal attention follows. What the eager full
  forward does, and what the float32 reference does.
- ABSORBED (:func:`absorb_query` / :func:`unabsorb_output`): ``W_kv_b``'s key
  half moves onto the query, its value half behind the softmax, and attention
  is multi-query over the latent rows themselves: score ``= q_lat . c +
  q_pe . k_pe``, ``o_lat = sum p c``. What every row of the serving step does
  (``models/gpt.py build_unified_step``), through
  ``ops/pallas/mla_paged_attention``: each row of a step reads the paged
  cache, and against a paged context the expanded form would expand the whole
  context again for every chunk.

The weights are ONE tree, made in the serving dtype on the device from a seed
(:func:`init_params`; no float32 model is ever built), already stacked the
way the serving step scans them, so ``ServingPredictor`` takes the model's
tree as it is:

    tok_emb [V, h]   lnf_g [h]   lm_head [h, V]
    dense_layers (the ``first_k_dense_replace`` leading layers, [Ld, ...]) and
    layers (the routed ones, [Lm, ...]), both with the attention keys
        ln1_g [h]  wq [h, nh*(nope+rope)]  wkv_a [h, r+rope]  kv_ln_g [r]
        wkv_b [r, nh*(nope+v)]  wo [nh*v, h]  ln2_g [h]
    dense_layers: w_gu [h, 2*I] (gate | up)  w_d [I, h]
    layers: moe_gate [h, E]  moe_w_gu [E, h, 2*f]  moe_w_d [E, f, h]
            sh_w_gu [h, 2*fs]  sh_w_d [fs, h]   (fs = n_shared * f)

Rotary pairing: the half-split ("rotate_half") form applied to the stored
order; the published checkpoint stores the pairs interleaved and permutes
first. Under seeded weights the two are the same model; the float32 reference
uses the same one.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 2048
    num_layers: int = 27
    num_heads: int = 16
    max_seq_len: int = 163840
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: ``{"factor", "original_max_position_embeddings", "beta_fast",
    #: "beta_slow", "mscale", "mscale_all_dim"}`` (YaRN) or None
    rope_scaling: dict | None = field(default_factory=lambda: dict(
        factor=40, original_max_position_embeddings=4096, beta_fast=32,
        beta_slow=1, mscale=0.707, mscale_all_dim=0.707))
    initializer_range: float = 0.02

    @property
    def head_dim(self) -> int:
        """Width of one query (and expanded key) head."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Width of the one cached row per token and layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def num_dense_layers(self) -> int:
        return min(self.first_k_dense_replace, self.num_layers)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    def num_params(self) -> int:
        import jax

        return sum(int(math.prod(s.shape))
                   for s in jax.tree.leaves(param_shapes(self)))


# ---------------------------------------------------------------------------
# rotary positions (YaRN) and the softmax scale
# ---------------------------------------------------------------------------


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(config):
    """The ``qk_rope_head_dim / 2`` rotary frequencies (numpy float32).
    YaRN (Peng et al., arXiv:2309.00071, as DeepSeek-V2 applies it): the
    slow dimensions are interpolated by ``factor``, the fast ones kept, with
    a linear ramp between the dimensions that turn ``beta_slow`` and
    ``beta_fast`` times over the original context."""
    import numpy as np

    dim, base = config.qk_rope_head_dim, float(config.rope_theta)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = config.rope_scaling
    if not rs:
        return extra.astype(np.float32)

    def turns_dim(turns):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (extra / rs["factor"] * (1.0 - keep) + extra * keep
            ).astype(np.float32)


def rope_magnitude(config) -> float:
    """The factor on cos and sin: ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``; 1 for the published configuration."""
    rs = config.rope_scaling
    if not rs:
        return 1.0
    return (_yarn_mscale(rs["factor"], rs.get("mscale", 1.0))
            / _yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)))


def softmax_scale(config) -> float:
    """``head_dim ** -0.5``, times ``mscale(factor, mscale_all_dim) ** 2``
    under YaRN."""
    scale = config.head_dim ** -0.5
    rs = config.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope_cos_sin(config, positions):
    """``(cos, sin)`` each ``[..., qk_rope_head_dim]`` float32 for integer
    ``positions [...]``."""
    import jax.numpy as jnp

    ang = (positions.astype(jnp.float32)[..., None]
           * jnp.asarray(rope_inv_freq(config)))
    ang = jnp.concatenate([ang, ang], axis=-1)
    mag = rope_magnitude(config)
    return jnp.cos(ang) * mag, jnp.sin(ang) * mag


def apply_rope(x, cos, sin):
    """Rotate ``x [..., d]`` (float32 inside, ``x``'s dtype out): the
    half-split form ``x cos + rotate_half(x) sin``."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


# ---------------------------------------------------------------------------
# the block's parts, as pure functions over one layer's weights
# ---------------------------------------------------------------------------


def rms_norm(x, g, eps):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * g).astype(x.dtype)


def gated_mlp(y, w_gu, w_d):
    """``(silu(y W_g) * y W_u) W_d`` with ``w_gu = [W_g | W_u]``."""
    import jax

    gu = y @ w_gu
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :half]) * gu[..., half:]) @ w_d


def latent_qkv(config, p, y, positions, with_query_latent=False):
    """The projections every attention form starts from, for rows ``y [...,
    h]`` at integer ``positions [...]``: ``(q_nope [..., nh, nope], q_pe
    [..., nh, rope]`` rotated, ``latent [..., r + rope])`` where ``latent``
    is the cached row ``[rms_norm(c) | rope(k_pe)]``. Where the layer holds a
    LOW-RANK query (``wq_a``, ``q_ln_g``, ``wq_b``: ``q_lora_rank``) the
    query is ``rms_norm(y W_q_a) W_q_b``; ``with_query_latent`` appends that
    normalised query latent ``[..., q_lora_rank]`` (what a learned indexer
    projects its own queries from; None for a full-rank query)."""
    import jax.numpy as jnp

    cfg = config
    nh, nope, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    lead = y.shape[:-1]
    cq = None
    if "wq_a" in p:
        cq = rms_norm(y @ p["wq_a"], p["q_ln_g"], cfg.rms_norm_eps)
        q = (cq @ p["wq_b"]).reshape(*lead, nh, cfg.head_dim)
    else:
        q = (y @ p["wq"]).reshape(*lead, nh, cfg.head_dim)
    ckv = y @ p["wkv_a"]
    cos, sin = rope_cos_sin(cfg, positions)
    c = rms_norm(ckv[..., :r], p["kv_ln_g"], cfg.rms_norm_eps)
    k_pe = apply_rope(ckv[..., r:], cos, sin)
    q_pe = apply_rope(q[..., nope:], cos[..., None, :], sin[..., None, :])
    out = (q[..., :nope], q_pe, jnp.concatenate([c, k_pe], axis=-1))
    return out + (cq,) if with_query_latent else out


def _wkv_b_heads(config, p):
    """``wkv_b`` as ``(W^K [r, nh, nope], W^V [r, nh, v])``."""
    cfg = config
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def absorb_query(config, p, q_nope, q_pe):
    """The absorbed query ``[q_nope W^K | q_pe]``, ``[..., nh, r + rope]``:
    its dot with a cached row is the expanded form's ``q . k``."""
    import jax.numpy as jnp

    wk, _ = _wkv_b_heads(config, p)
    q_lat = jnp.einsum("...hd,rhd->...hr", q_nope, wk)
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)


def unabsorb_output(config, p, o_lat):
    """``o_lat [..., nh, r]`` (the softmax-weighted sum of latents) to the
    heads' values ``[..., nh * v]``."""
    import jax.numpy as jnp

    _, wv = _wkv_b_heads(config, p)
    o = jnp.einsum("...hr,rhd->...hd", o_lat, wv)
    return o.reshape(*o.shape[:-2], -1)


def attention_expanded(config, p, q_nope, q_pe, latent, selected=None):
    """Causal attention over one sequence ``[s, ...]`` with every head's K
    and V expanded from the latent rows: ``[s, nh * v]``. ``selected [s, s]``
    (bool): the keys each row may read, where something chose them
    (``models/glm_moe_dsa.py``); causal itself."""
    import jax
    import jax.numpy as jnp

    cfg = config
    r = cfg.kv_lora_rank
    wk, wv = _wkv_b_heads(cfg, p)
    c, k_pe = latent[..., :r], latent[..., r:]
    k_nope = jnp.einsum("sr,rhd->shd", c, wk)
    v = jnp.einsum("sr,rhd->shd", c, wv)
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)).astype(jnp.float32)
    s = scores.shape[-1]
    causal = (jnp.tril(jnp.ones((s, s), bool)) if selected is None
              else selected)
    scores = jnp.where(causal, scores * softmax_scale(cfg), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)


def attention_absorbed(config, p, q_nope, q_pe, latent):
    """The same attention over one sequence, read from the latent rows
    alone (the serving step's form, without the pages)."""
    import jax
    import jax.numpy as jnp

    cfg = config
    q_abs = absorb_query(cfg, p, q_nope, q_pe)
    scores = jnp.einsum("qhc,kc->hqk", q_abs, latent).astype(jnp.float32)
    s = scores.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores * softmax_scale(cfg), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(latent.dtype)
    o_lat = jnp.einsum("hqk,kr->qhr", probs, latent[..., :cfg.kv_lora_rank])
    return unabsorb_output(cfg, p, o_lat)


#: the weights a layer scan addresses by layer index INSIDE the kernel that
#: reads them, instead of slicing its layer out of the stack: a Pallas call is
#: handed a copy of a slice, and a routed layer's experts are 1.1 GB
STACKED_BY_INDEX = ("moe_w_gu", "moe_w_d")


def routed_ffn(config, p, y, use_kernel=None, valid=None, with_counts=False,
               layer=None):
    """Routed experts plus the shared ones over rows ``y [n, h]``: dropless
    top-k over the softmax scores, gates as they are (``norm_topk_prob``
    decides), gated SiLU experts through the grouped GEMM
    (``models/moe.py moe_ffn``), and the shared gated MLP for every row.
    ``with_counts``: also ``[2, E]`` int32, the rows each expert received
    and whether it received any. ``layer``: ``p``'s expert weights
    (:data:`STACKED_BY_INDEX`) are the whole stacks ``[L, E, ...]`` and this
    is layer ``layer`` of them."""
    from ..observability.tracing import step_scope
    from .moe import moe_ffn

    cfg = config
    # what a configuration of this family may state beyond DeepSeek-V2's:
    # sigmoid scores chosen by score plus a correction bias (``moe_bias``;
    # none where the layer holds none), a chip's SHARE of the experts
    # (``experts_held``), and shared experts that are averaged
    more = {}
    if getattr(cfg, "scoring_func", "softmax") != "softmax":
        more.update(scoring=cfg.scoring_func, route_bias=p.get("moe_bias"))
    if getattr(cfg, "experts_held", None):
        more.update(experts_held=cfg.experts_held)
    out, _, stats = moe_ffn(
        y, p["moe_gate"], p["moe_w_gu"], None, p["moe_w_d"], None,
        top_k=cfg.num_experts_per_tok, capacity_factor=None,
        renormalize=bool(cfg.norm_topk_prob), gated=True,
        gate_scale=float(cfg.routed_scaling_factor),
        use_kernel=use_kernel, valid=valid, with_stats=True, layer=layer,
        **more)
    with step_scope("moe_shared"):
        shared = gated_mlp(y, p["sh_w_gu"], p["sh_w_d"])
        # shared experts that are AVERAGED, not summed (``models/
        # cohere2_moe.py``): their one summed MLP over their number
        scale = getattr(cfg, "shared_expert_scale", None)
        out = out + (shared if scale is None
                     else (shared * scale).astype(shared.dtype))
    if not with_counts:
        return out
    import jax.numpy as jnp

    rows = stats["rows"]
    return out, jnp.stack([rows, (rows > 0).astype(rows.dtype)])


# ---------------------------------------------------------------------------
# weights: shapes, and making them on the device in the serving dtype
# ---------------------------------------------------------------------------


def _layer_shapes(config, routed: bool):
    cfg = config
    h, nh, r = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    shapes = {
        "ln1_g": (h,), "wq": (h, nh * cfg.head_dim),
        "wkv_a": (h, cfg.latent_dim), "kv_ln_g": (r,),
        "wkv_b": (r, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (nh * cfg.v_head_dim, h), "ln2_g": (h,),
    }
    if routed:
        e, f = cfg.n_routed_experts, cfg.moe_intermediate_size
        fs = cfg.n_shared_experts * f
        shapes.update({"moe_gate": (h, e), "moe_w_gu": (e, h, 2 * f),
                       "moe_w_d": (e, f, h), "sh_w_gu": (h, 2 * fs),
                       "sh_w_d": (fs, h)})
    else:
        i = cfg.intermediate_size
        shapes.update({"w_gu": (h, 2 * i), "w_d": (i, h)})
    return shapes


def param_shapes(config, dtype=None):
    """The weight tree as ``jax.ShapeDtypeStruct`` leaves."""
    import jax
    import jax.numpy as jnp

    cfg = config
    dt = jnp.dtype(dtype or jnp.float32)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    tree = {"tok_emb": sds(cfg.vocab_size, cfg.hidden_size),
            "lnf_g": sds(cfg.hidden_size),
            "lm_head": sds(cfg.hidden_size, cfg.vocab_size)}
    for group, n, routed in (("dense_layers", cfg.num_dense_layers, False),
                             ("layers", cfg.num_moe_layers, True)):
        if n:
            tree[group] = {k: sds(n, *s)
                           for k, s in _layer_shapes(cfg, routed).items()}
    return tree


def init_params(config, seed: int, dtype=None, shapes=None, std_share=None):
    """Seeded weights (normal, std ``initializer_range``; norm weights one),
    made leaf by leaf ON the device in ``dtype``: each stacked leaf is filled
    a layer at a time, so the largest temporary is one layer's leaf and no
    float32 copy of the model ever exists. The bits come from the device's
    own generator (``impl="rbg"``): four billion values from threefry took
    85 s of a v5e, and a seed need only give the same weights on the same
    backend. ``shapes``: another family's tree over the same rule;
    ``std_share``: ``{leaf name: share of std}`` for leaves that are seeded
    narrower."""
    import jax
    import jax.numpy as jnp

    if shapes is None:
        shapes = param_shapes(config, dtype)

    def leaf(path, s, key):
        name = path[-1].key
        if name.endswith("_g"):                  # a norm's weight
            return jnp.ones(s.shape, s.dtype)
        std = float(config.initializer_range) * (std_share or {}).get(name, 1)
        if len(path) == 1:       # not a stack of layers: one piece
            return (jax.random.normal(key, s.shape, jnp.float32) * std
                    ).astype(s.dtype)
        keys = jax.random.split(key, s.shape[0])
        return jax.lax.map(
            lambda k: (jax.random.normal(k, s.shape[1:], jnp.float32) * std
                       ).astype(s.dtype), keys)

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    root = jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg")
    with jax.enable_x64(False):
        leaves = [jax.jit(lambda k, path=path, s=s: leaf(path, s, k))(
            jax.random.fold_in(root, i)) for i, (path, s) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def layer_stacks(params):
    """The stacks of equal layers the serving step scans, in order: the
    leading dense layers' and the routed layers', or, where the layers come
    in more kinds than two, the tree's own ``stacks`` (a tuple, one stack per
    run of equal layers: ``models/glm_moe_dsa.py``)."""
    if "stacks" in params:
        return list(params["stacks"])
    return [params[g] for g in ("dense_layers", "layers") if g in params]


def layer_groups(params):
    """The stacks the serving step runs in order: ``[(group, layers in
    it)]``."""
    return [(g, params[g]["ln1_g"].shape[0])
            for g in ("dense_layers", "layers") if g in params]


# ---------------------------------------------------------------------------
# the eager full forward (expanded attention)
# ---------------------------------------------------------------------------


def forward(config, params, ids):
    """Logits ``[b, s, V]`` of token ids ``[b, s]``: the whole stack over
    whole sequences, no cache, attention in its expanded form."""
    import jax
    import jax.numpy as jnp

    cfg = config
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)

    def one_sequence(row):
        x = jnp.take(params["tok_emb"], row, axis=0)
        for group, n in layer_groups(params):
            for i in range(n):
                p = {k: v[i] for k, v in params[group].items()}
                y = rms_norm(x, p["ln1_g"], cfg.rms_norm_eps)
                a = attention_expanded(cfg, p, *latent_qkv(cfg, p, y, pos))
                x = x + a @ p["wo"]
                y = rms_norm(x, p["ln2_g"], cfg.rms_norm_eps)
                x = x + (routed_ffn(cfg, p, y) if group == "layers"
                         else gated_mlp(y, p["w_gu"], p["w_d"]))
        return rms_norm(x, params["lnf_g"], cfg.rms_norm_eps)

    h = jax.lax.map(one_sequence, jnp.asarray(ids, jnp.int32))
    return h @ params["lm_head"]


class DeepseekV2ForCausalLM:
    """The model as ``ServingPredictor`` takes it: ``config`` and the weight
    tree (:func:`init_params`), in ``dtype``, on the device. Inference only;
    ``__call__`` is the eager full forward."""

    def __init__(self, config: DeepseekV2Config, *, seed: int = 0,
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
