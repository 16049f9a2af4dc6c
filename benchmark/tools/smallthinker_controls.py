"""The controls that bracket the SmallThinker cell's limits: the plain
reference with one thing wrong, against the same reference as it is, as the
cell's comparison measures them (rms difference of the next-token logits over
the reference's std: the six rows' mean, and their median).

- ``router_y2``: the router reads the FFN's norm ``y2``, not ``y1``;
- ``silu``: SiLU experts in place of ReLU;
- ``interleaved``: rotary in interleaved pairs in place of half-split ones;
- ``rope_on_full``: rotary on the full layers too (they have none);
- ``no_window_edge``: the window layers attend every causal key;
- ``e4m3``: every weight rounded to the nearest precision below the one the
  configuration states (bfloat16 -> float8 e4m3's 3 mantissa bits, exponents
  kept, rounded on the bits, as ``window_moe_controls.py`` does).

    python benchmark/tools/smallthinker_controls.py <seed> [<tokens>]

On the chip at the configuration's widths (weights made on the device, one
copy; rounded in place last). Prints one JSON line naming the device. Each
control's six rows go through the cell's own statistic and limits
(``drivers/serve_smallthinker.py`` ``rms_shares``, each row against the
nearer of the reference's two answers, and ``verdict``): every one must come
out NOT correct, and the tool exits 1 if one passes.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "smallthinker-21ba3b-instruct"


def main(seed, tokens=6400):
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from paddle_tpu.models.smallthinker import SmallThinkerForCausalLM

    from benchmark.drivers.serve_smallthinker import (CHECK_ANSWER,
                                                      LOGITS_TOL_RMS,
                                                      LOGITS_TOL_RMS_MEDIAN,
                                                      model_config,
                                                      rms_shares, verdict)
    from benchmark.reference import smallthinker as reference

    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        cfgj = json.load(f)
    cfg = model_config(cfgj, cfgj["serve_smallthinker"])
    dtype = jnp.dtype(cfgj["dtype"])
    params = SmallThinkerForCausalLM(cfg, seed=seed, dtype=dtype).params
    ids = np.zeros((-(-tokens // 64) * 64,), np.int32)
    ids[:tokens] = np.random.default_rng(seed).integers(
        0, cfgj["vocab_size"], tokens)
    rows = list(range(tokens - CHECK_ANSWER, tokens))   # as the cell: six

    def read(tree, **how):
        with jax.enable_x64(False):
            return np.asarray(reference.logits_at(
                tree, jnp.asarray(ids), rows, cfgj, **how), np.float32)

    with jax.enable_x64(False):
        want, alt, _ = reference.logits_at(params, jnp.asarray(ids), rows,
                                           cfgj, alternative=True)

    def against(got):
        rms = rms_shares(got, want, alt)[0]
        (mean,), (median,), ok = verdict([rms.tolist()])
        return {"rms_share_of_std": mean, "rms_row_median": median,
                "rms_row_max": float(rms.max()), "rms_rows": rms.tolist(),
                "correct": ok}

    def rope_on_full(layer, c):
        return reference.layer_rule(layer, c)[0], c["rope_theta"]

    def no_window_edge(layer, c):
        return None, reference.layer_rule(layer, c)[1]

    out = {name: against(read(params, variant=(name,)))
           for name in ("router_y2", "silu", "interleaved")}
    out["rope_on_full"] = against(read(params, rule=rope_on_full))
    out["no_window_edge"] = against(read(params, rule=no_window_edge))

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
        "config": CONFIG, "seed": seed, "tokens": tokens,
        "stated": dtype.name, "lower": jnp.dtype(lower).name,
        "window": cfgj["sliding_window_size"],
        "limits": {"mean": LOGITS_TOL_RMS, "median": LOGITS_TOL_RMS_MEDIAN},
        "controls": out}), flush=True)
    return int(any(c["correct"] for c in out.values()))


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), *(int(a) for a in sys.argv[2:3])))
