"""The controls that bracket the Command A+ cell's limits: the plain reference
with something wrong, against the same reference as it is, as the cell's
comparison measures them (rms difference of the next-token logits over the
reference's std: the six rows' mean, and their median).

- ``no_lower_edge``: the window layers attend every causal key (full attention
  everywhere: the mask's lower edge, the first live block and the released
  pages all gone wrong at once);
- ``rope_on_full``: rotary applied on the full layer too (it has none);
- ``e4m3``: every weight rounded to the nearest precision below the one the
  configuration states (bfloat16 -> float8 e4m3's 3 mantissa bits, exponents
  kept, rounded on the bits: ``latent_moe_precision_reading.py`` says why).

    python benchmark/tools/window_moe_controls.py <config> <seed> [<tokens>]

On the chip at the configuration's widths (weights made on the device, one
copy; rounded in place last). Prints one JSON line naming the device. Each
control's six rows go through the cell's own statistic and limits
(``drivers/serve_window_moe.py`` ``verdict``), as a served request's would:
every one must come out NOT correct, and the tool exits 1 if one passes.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

def no_lower_edge(kind, cfg):
    theta = cfg["rope_parameters"]["rope_theta"]
    return None, (theta if kind == "sliding_attention" else None)


def rope_on_full(kind, cfg):
    theta = cfg["rope_parameters"]["rope_theta"]
    return (cfg["sliding_window"] if kind == "sliding_attention"
            else None), theta


def main(config_name, seed, tokens=9000):
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from paddle_tpu.models.cohere2_moe import Cohere2MoeForCausalLM

    from benchmark.drivers.serve_window_moe import (CHECK_ANSWER,
                                                    LOGITS_TOL_RMS,
                                                    LOGITS_TOL_RMS_MEDIAN,
                                                    model_config, verdict)
    from benchmark.reference import cohere2_moe as reference

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        cfgj = json.load(f)
    cfg = model_config(cfgj, cfgj["serve_window_moe"])
    dtype = jnp.dtype(cfgj["dtype"])
    params = Cohere2MoeForCausalLM(cfg, seed=seed, dtype=dtype).params
    ids = np.zeros((-(-tokens // 64) * 64,), np.int32)
    ids[:tokens] = np.random.default_rng(seed).integers(
        0, cfgj["vocab_size"], tokens)
    rows = list(range(tokens - CHECK_ANSWER, tokens))   # as the cell: six

    def read(tree, **how):
        with jax.enable_x64(False):
            return np.asarray(reference.logits_at(
                tree, jnp.asarray(ids), rows, cfgj, **how), np.float32)

    want = read(params)

    def against(got):
        rms = np.sqrt(np.mean((got - want) ** 2, -1)) / want.std(-1)
        (mean,), (median,), ok = verdict([rms.tolist()])
        return {"rms_share_of_std": mean, "rms_row_median": median,
                "rms_row_max": float(rms.max()), "rms_rows": rms.tolist(),
                "correct": ok}

    out = {"no_lower_edge": against(read(params, rule=no_lower_edge)),
           "rope_on_full": against(read(params, rule=rope_on_full))}

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
    out["e4m3"] = against(read(params))
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "config": config_name, "seed": seed, "tokens": tokens,
        "stated": dtype.name, "lower": jnp.dtype(lower).name,
        "window": cfgj["sliding_window"],
        "limits": {"mean": LOGITS_TOL_RMS, "median": LOGITS_TOL_RMS_MEDIAN},
        "controls": out}), flush=True)
    return int(any(c["correct"] for c in out.values()))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]),
                  *(int(a) for a in sys.argv[3:4])))
