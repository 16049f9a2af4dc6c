"""The controls that bracket the GLM-5.2 cell's two limits: the plain
reference with something wrong, against the same reference as it is, as the
cell's comparison measures them (rms difference of the next-token logits over
the reference's std; the share of the reference's selection that the
control's selection holds, per layer with an indexer).

- ``no_selection``: attention over every causal key (the indexer switched
  off);
- ``first_keys``: the first ``index_topk`` positions instead of the best;
- ``e4m3``: every weight rounded to the nearest precision below the one the
  configuration states (bfloat16 -> float8 e4m3's 3 mantissa bits, exponents
  kept, rounded on the bits: ``latent_moe_precision_reading.py`` says why).

    python benchmark/tools/sparse_latent_moe_controls.py <config> <seed> [<tokens>]

On the chip at the configuration's widths (weights made on the device, one
copy; rounded in place last). Prints one JSON line naming the device.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


ROWS = 6


def no_selection(scores, causal, k):
    return causal


def first_keys(scores, causal, k):
    import jax.numpy as jnp

    return causal & (jnp.arange(scores.shape[-1])[None, :] < k)


def main(config_name, seed, tokens=6400):
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from paddle_tpu.models.glm_moe_dsa import GlmMoeDsaForCausalLM

    from benchmark.drivers.serve_sparse_latent_moe import model_config
    from benchmark.reference import glm_moe_dsa as reference

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        cfgj = json.load(f)
    cfg = model_config(cfgj, cfgj["serve_sparse_latent_moe"])
    dtype = jnp.dtype(cfgj["dtype"])
    params = GlmMoeDsaForCausalLM(cfg, seed=seed, dtype=dtype).params
    ids = np.zeros((-(-tokens // 64) * 64,), np.int32)
    ids[:tokens] = np.random.default_rng(seed).integers(
        0, cfgj["vocab_size"], tokens)

    rows = list(range(tokens - ROWS, tokens))   # as the cell: six rows

    def read(tree, **how):
        with jax.enable_x64(False):
            logits, chosen = reference.logits_at(
                tree, jnp.asarray(ids), rows, cfgj, **how)
        return np.asarray(logits, np.float32), np.asarray(chosen)

    want, want_chosen = read(params)

    def against(got, chosen):
        """The cell's two statistics: the rows' mean rms difference, and per
        indexer layer the rows' mean share of the reference's selection."""
        rms = np.sqrt(np.mean((got - want) ** 2, -1)) / want.std(-1)
        held = (chosen & want_chosen).sum(-1) / want_chosen.sum(-1)
        return {"rms_share_of_std": float(rms.mean()),
                "rms_rows": rms.tolist(),
                "selection_share": held.mean(-1).tolist(),
                "keys_read": chosen.sum(-1).mean(-1).tolist()}

    out = {"no_selection": against(*read(params, select=no_selection)),
           "first_keys": against(*read(params, select=first_keys))}

    lower = jnp.float8_e4m3fn
    drop = jnp.finfo(dtype).nmant - jnp.finfo(lower).nmant
    word = {2: jnp.uint16, 4: jnp.uint32}[dtype.itemsize]

    def to_lower_mantissa(a):
        """Round to nearest even at ``lower``'s mantissa width."""
        bits = jax.lax.bitcast_convert_type(a, word)
        half = word((1 << (drop - 1)) - 1)
        bits = (bits + half + ((bits >> drop) & word(1))) \
            & word(~((1 << drop) - 1) & (2 ** (8 * dtype.itemsize) - 1))
        return jax.lax.bitcast_convert_type(bits, a.dtype)

    rounded = jax.jit(to_lower_mantissa, donate_argnums=0)
    with jax.enable_x64(False):
        params = jax.tree.map(rounded, params)
    out["e4m3"] = against(*read(params))
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "config": config_name, "seed": seed, "tokens": tokens,
        "stated": dtype.name, "lower": jnp.dtype(lower).name,
        "index_topk": cfgj["index_topk"], "controls": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]),
                  *(int(a) for a in sys.argv[3:4])))
