"""Eager executable cache (FLAGS_eager_op_cache) correctness.

The cache keys an op's compiled executable on (op name, fn behavior
signature, tree structure, leaf signature). These tests pin the key
semantics the round-3 advisor flagged (scalar-type collisions, mutable
Tensor closures) and the end-to-end parity of cached vs uncached dispatch.

Reference analogue: eager dispatch latency is first-class in the reference
(cached kernel selection / pre-generated ad_funcs, SURVEY §3.1); OpTest
covers dispatch-path equivalence the same way.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.autograd import engine
from paddle_tpu.framework import flags


@pytest.fixture
def eager_cache():
    engine._EAGER_CACHE.clear()
    old = flags.flag("eager_op_cache")
    flags.set_flags({"FLAGS_eager_op_cache": True})
    yield engine._EAGER_CACHE
    flags.set_flags({"FLAGS_eager_op_cache": old})
    engine._EAGER_CACHE.clear()


def test_leaf_sig_distinguishes_scalar_types():
    """0 == 0.0 == False under dict lookup; the signature must not collide
    (advisor r3 medium: full(shape, 1) vs full(shape, True) shared one
    executable traced for the other dtype)."""
    sigs = {engine._leaf_sig([v], frozenset()) for v in (0, 0.0, False)}
    assert len(sigs) == 3
    sigs = {engine._leaf_sig([v], frozenset()) for v in (1, 1.0, True)}
    assert len(sigs) == 3


def test_fn_sig_distinguishes_closure_scalar_types():
    def make(v):
        def f(x):
            return x + v
        return f

    assert engine._fn_sig(make(2)) != engine._fn_sig(make(2.0))
    assert engine._fn_sig(make(1)) != engine._fn_sig(make(True))
    # equal configs of equal type DO share a signature (cache hits work)
    assert engine._fn_sig(make(2)) == engine._fn_sig(make(2))


def test_fn_sig_rejects_tensor_closures():
    """A closure-captured Tensor hashes by identity but its _data can be
    mutated in place after the executable baked the traced value as a
    constant — such closures must not be cached (advisor r3 low)."""
    t = paddle.to_tensor([1.0, 2.0])

    def f(x):
        return x + t

    assert engine._fn_sig(f) is None

    def g(x):
        return x + cfg["t"]

    cfg = {"t": t}
    assert engine._fn_sig(g) is None  # nested in containers too


def test_scalar_dtype_no_collision_end_to_end(eager_cache):
    """pow(int_tensor, 2) is int64; pow(int_tensor, 2.0) promotes to float.
    With the collision bug both returned whichever traced first."""
    x = paddle.to_tensor(np.array([1, 2, 3], dtype=np.int64))
    a = paddle.pow(x, 2)
    b = paddle.pow(x, 2.0)
    assert a.dtype != b.dtype
    np.testing.assert_allclose(a.numpy(), [1, 4, 9])
    np.testing.assert_allclose(b.numpy(), [1.0, 4.0, 9.0])
    # reversed trace order
    engine._EAGER_CACHE.clear()
    b = paddle.pow(x, 2.0)
    a = paddle.pow(x, 2)
    assert a.dtype != b.dtype


def test_cached_matches_uncached_fwd_bwd(eager_cache, rng):
    """Full fwd+bwd parity between cached and uncached dispatch on a small
    MLP (weights shared, same seed)."""
    from paddle_tpu import nn

    def run():
        paddle.seed(7)
        net = nn.Sequential(
            nn.Linear(8, 16), nn.GELU(), nn.LayerNorm(16), nn.Linear(16, 4))
        x = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
        x.stop_gradient = False
        loss = (net(x) ** 2).mean()
        loss.backward()
        return loss.numpy(), x.grad.numpy()

    rng_state = rng.get_state()
    l1, g1 = run()
    flags.set_flags({"FLAGS_eager_op_cache": False})
    rng.set_state(rng_state)
    l0, g0 = run()
    np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g1, g0, rtol=1e-5, atol=1e-6)


def test_cache_reuses_entries(eager_cache):
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    paddle.nn.functional.softmax(x)
    n = len(eager_cache)
    assert n >= 1
    for _ in range(3):
        paddle.nn.functional.softmax(x)
    assert len(eager_cache) == n  # same signature -> no new entries


def test_fn_sig_distinguishes_default_args():
    """``lambda v, i=i: ...`` keeps i in __defaults__, not the closure —
    two such lambdas share a code object and must not share an executable
    (bit the eager all_gather slice loop)."""
    fns = [(lambda v, i=i: v + i) for i in range(3)]
    sigs = {engine._fn_sig(f) for f in fns}
    assert len(sigs) == 3


def test_hot_functionals_are_cacheable(eager_cache):
    """Round-5 regression: layer_norm (and friends) captured their optional
    weight/bias TENSORS in the op closure just to None-test them, which
    disabled caching (every eager call paid full uncached dispatch — 4 ms vs
    125 us on the chip, BENCH_OPS r5). The hot functionals must
    close over booleans and stay cacheable."""
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(np.random.randn(2, 8, 16).astype("float32"))
    g = paddle.to_tensor(np.ones(16, np.float32))
    b = paddle.to_tensor(np.zeros(16, np.float32))
    xi = paddle.to_tensor(np.random.randn(2, 4, 6, 6).astype("float32"))
    rm = paddle.to_tensor(np.zeros(4, np.float32))
    rv = paddle.to_tensor(np.ones(4, np.float32))
    w = paddle.to_tensor(np.random.randn(16, 8).astype("float32"))

    cases = {
        "layer_norm": lambda: F.layer_norm(x, 16, weight=g, bias=b),
        "batch_norm": lambda: F.batch_norm(xi, rm, rv, training=True),
        "group_norm": lambda: F.group_norm(xi, 2),
        "instance_norm": lambda: F.instance_norm(xi),
        "bce_with_logits": lambda: F.binary_cross_entropy_with_logits(
            x, (x > 0).astype("float32")),
        "linear": lambda: F.linear(x, w),
    }
    for name, call in cases.items():
        call()  # prime
        n = len(eager_cache)
        call()
        call()
        assert len(eager_cache) == n and n > 0, (
            f"{name} is not eager-cacheable (closure captured a Tensor?)")


def test_cache_eviction_is_lru(eager_cache):
    """A hit must refresh recency so eviction drops cold entries, not the
    hottest executable (round-4 weak #9: FIFO dropped the oldest-INSERTED)."""
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    paddle.nn.functional.softmax(x)  # hot entry, inserted FIRST
    hot = next(iter(eager_cache))
    # fill with colder entries
    for i in range(3):
        paddle.scale(x, float(i))
    paddle.nn.functional.softmax(x)  # touch the hot entry
    assert next(iter(eager_cache)) != hot  # recency refreshed: no longer LRU
    # simulate the eviction sweep: the dropped quarter excludes the hot key
    order = list(eager_cache)
    assert hot == order[-1]
