"""Training feed: ``batches`` seeded batches of token ids, made on the
device in one jitted call and cycled. Labels are the inputs; the train step
shifts them (position t predicts token t + 1).

Batch 0 is one sequence repeated over the batch, so that the loss of a step
on it is that sequence's loss, which the plain reference can compute. It is
the warm-up batch and is not used inside the measured window.
"""
from __future__ import annotations

KIND = "train"


def build(params, seed, *, vocab_size, batch_size, seq_len, sharding):
    """Returns ``ids`` [1 + batches, batch, seq] int32 on the device."""
    import jax
    import jax.numpy as jnp

    n = int(params["batches"])

    def make(seed32):
        key = jax.random.key(seed32)
        k0, k1 = jax.random.split(key)
        one = jax.random.randint(k0, (1, 1, seq_len), 0, vocab_size,
                                 jnp.int32)
        rest = jax.random.randint(k1, (n, batch_size, seq_len), 0,
                                  vocab_size, jnp.int32)
        return jnp.concatenate(
            [jnp.broadcast_to(one, (1, batch_size, seq_len)), rest])

    with jax.enable_x64(False):
        return jax.jit(make, out_shardings=sharding)(
            jnp.uint32(seed & 0xFFFFFFFF))
