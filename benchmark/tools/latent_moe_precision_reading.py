"""The upper reading that brackets the DeepSeek-V2-Lite cell's tolerance: the
plain reference with every weight rounded to the nearest precision below the
one the configuration states (bfloat16 -> float8 e4m3: 3 mantissa bits),
against the same reference unrounded, as the cell's comparison measures it
(rms difference of the next-token logits over the reference's std). The
rounding keeps each weight's exponent (float8 as a deployment scales it, its
best case: unscaled, weights of std 0.02 fall among e4m3's subnormals) and is
done on the bits (on the v5e a cast to float8 and back comes out unchanged).
Computing the model in that precision cannot come closer than its rounded
weights allow, so this reading has to lie above the cell's limit; the
system's own readings (the result line's ``reference_check``) below it.

    python benchmark/tools/latent_moe_precision_reading.py <config> <seed> [<tokens>]

On the chip at the configuration's widths (weights made on the device, one
copy; rounded in place afterwards). Prints one JSON line naming the device.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(config_name, seed, tokens=200):
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from paddle_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM

    from benchmark.drivers.serve_latent_moe import model_config
    from benchmark.reference import deepseek_v2 as reference

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        cfgj = json.load(f)
    cfg = model_config(cfgj, cfgj["serve_latent_moe"])
    dtype = jnp.dtype(cfgj["dtype"])
    params = DeepseekV2ForCausalLM(cfg, seed=seed, dtype=dtype).params
    ids = np.zeros((-(-tokens // 64) * 64,), np.int32)
    ids[:tokens] = np.random.default_rng(seed).integers(
        0, cfgj["vocab_size"], tokens)

    def logits(tree):
        with jax.enable_x64(False):
            return np.asarray(reference.logits_at(
                tree, jnp.asarray(ids), tokens - 1, cfgj), np.float32)

    want = logits(params)
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
    got = logits(params)
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "config": config_name, "seed": seed, "tokens": tokens,
        "stated": dtype.name, "lower": jnp.dtype(lower).name,
        "rms_share_of_std": float(np.sqrt(np.mean((got - want) ** 2))
                                  / want.std())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]),
                  *(int(a) for a in sys.argv[3:4])))
