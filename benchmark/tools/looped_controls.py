"""The controls that bracket the Ouro cell's limits, as the cell's comparison
measures them (rms difference of the next-token logits over the reference's
std: a request's six rows' mean, and their median, ``verdict``):

- ``one_loop``, ``no_loop_norm``, ``no_post_norms``: the plain reference with
  one thing wrong (``reference/ouro.py`` ``variant``: the stack once, no norm
  between loops, no norm after the sublayers), against the reference as it
  is, over the last six rows of a prompt;
- ``e4m3``: the reference with every weight rounded to the nearest precision
  below the one the configuration states (bfloat16 -> float8 e4m3's 3
  mantissa bits, exponents kept, rounded on the bits, as
  ``window_moe_controls.py`` does), against the reference as it is;
- ``shared_plane``: the SYSTEM with one cache plane a layer shared by every
  loop (``OuroConfig.cache_plane`` patched to the layer alone), served
  through the normal path at the cell's widths on a small pool, its rows
  held against the reference as it is; beside it ``served``, the same
  predictor unpatched: the system's own reading there.

Beside them, under ``readings``, ``stream_rounded``: the reference with its
residual stream rounded to the stated precision after every layer and every
norm between loops, against the reference as it is: what the precision the
step carries its stream in costs by itself, whatever the kernels do.

    python benchmark/tools/looped_controls.py <seed> [<tokens>]

On the chip at the configuration's widths (weights made on the device, one
copy; rounded in place last). Prints one JSON line naming the device. Every
control must come out NOT correct, ``served`` correct; the tool exits 1
otherwise.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = "ouro-2.6b"
#: the small pools the two served predictors take in turn (pages of 64)
SMALL = dict(max_batch=2, num_pages=64)


def main(seed, tokens=1900):
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from paddle_tpu.models.ouro import OuroConfig

    from benchmark.drivers import serve_looped as driver
    from benchmark.reference import ouro as reference

    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        cfgj = json.load(f)
    dep = cfgj["serve_looped"]
    dtype = jnp.dtype(cfgj["dtype"])

    def against(rows):
        rows = [float(r) for r in rows]
        (mean,), (median,), ok = driver.verdict([rows])
        return {"rms_share_of_std": mean, "rms_row_median": median,
                "rms_row_max": max(rows), "rms_rows": rows,
                "correct": ok}

    out = {}
    # the system, served through the normal path on a small pool: as it is,
    # then with every loop of a layer on one plane
    real = OuroConfig.cache_plane
    pads = (-(-tokens // 64) * 64,)
    for name, plane in (("served", real),
                        ("shared_plane", lambda self, t, layer: layer)):
        OuroConfig.cache_plane = plane
        try:
            sp = driver.build(cfgj, dep, seed, **SMALL)
            rows, served, _ = driver.check_against_reference(
                sp, cfgj, seed, prompts=(tokens,), pads=pads)
        finally:
            OuroConfig.cache_plane = real
        out[name] = dict(against(rows[0]["rms"]), served=served)
        params = sp.params
        del sp
    ids = np.zeros(pads, np.int32)
    ids[:tokens] = np.random.default_rng(seed).integers(
        0, cfgj["vocab_size"], tokens)
    positions = list(range(tokens - driver.CHECK_ANSWER, tokens))

    def read(tree, variant=()):
        with jax.enable_x64(False):
            return np.asarray(reference.logits_at(
                tree, jnp.asarray(ids), positions, cfgj, variant=variant),
                np.float32)

    want = read(params)

    def read_stream_rounded(tree):
        """The reference with its residual stream rounded to the stated
        precision after every layer and every norm between loops, as the
        served step carries it; every product and norm still in float32.
        Not a control: what the stated precision alone costs."""
        eps = float(cfgj["rms_norm_eps"])
        how = dict(heads=int(cfgj["num_attention_heads"]),
                   head_dim=int(cfgj["head_dim"]), eps=eps,
                   theta=float(cfgj["rope_theta"]), post_norms=True)
        stack = tree["layers"]
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)

        def rounded(x):
            return x.astype(dtype).astype(jnp.float32)

        with jax.enable_x64(False):
            x = rounded(jnp.take(tree["tok_emb"], jnp.asarray(ids), axis=0)
                        .astype(jnp.float32))
            for _ in range(int(cfgj["total_ut_steps"])):
                for layer in range(stack["ln1_g"].shape[0]):
                    x = rounded(reference._layer(
                        x, {k: w[layer] for k, w in stack.items()}, pos,
                        **how)[0])
                x = rounded(reference._rms(x, tree["lnf_g"], eps))
            rows = x[jnp.asarray(positions, jnp.int32)]
            return np.asarray(reference._mm(rows, tree["lm_head"]),
                              np.float32)

    readings = {"stream_rounded": against(
        driver.rms_shares(read_stream_rounded(params), want))}
    for name in reference.VARIANTS:
        out[name] = against(driver.rms_shares(read(params, (name,)), want))

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
    out["e4m3"] = against(driver.rms_shares(read(params), want))
    dev = jax.devices()[0]
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "config": CONFIG, "seed": seed, "tokens": tokens,
        "stated": dtype.name, "lower": jnp.dtype(lower).name,
        "limits": {"mean": driver.LOGITS_TOL_RMS,
                   "median": driver.LOGITS_TOL_RMS_MEDIAN},
        "controls": out, "readings": readings}), flush=True)
    wrong = [c["correct"] for name, c in out.items() if name != "served"]
    return int(any(wrong) or not out["served"]["correct"])


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), *(int(a) for a in sys.argv[2:3])))
