"""Round-20 KV-page transfer wire (`inference/kv_transfer.py`):
frame serialization round-trips (fp16/fp32 and int8-KV payloads with
scale planes, partial tails), checksum detection of arbitrary byte
corruption, the bounded-window / timeout / backoff / bounded-retry
sender, idempotent double-delivery, and the failed-transfer unwind that
leaves the receiving cache's accounting indistinguishable from a run
where the transfer never happened.

Pure host-side suite: the caches are tiny `KVCacheManager`s whose pool
contents are written directly (deterministic per-token rows), no model.
"""
import numpy as np
import pytest

from paddle_tpu.inference.faults import FaultPlan
from paddle_tpu.inference.kv_cache import KVCacheManager
from paddle_tpu.inference.kv_transfer import (DONE, FAILED, SENDING,
                                              FrameError, KVPageTransfer,
                                              TransferConfig, decode_frame,
                                              encode_frame)

GEO = dict(num_layers=2, num_kv_heads=2, head_dim=4, num_pages=12,
           max_batch=4, max_seq_len=64, page_size=8,
           enable_prefix_cache=True)


def _mgr(**over):
    kw = dict(GEO)
    kw.update(over)
    return KVCacheManager(**kw)


def _fill_prefix(m, tokens, seed=0):
    """Admit ``tokens``, write deterministic per-token K/V rows (and
    scale rows on a quantized pool) into its pages, register the chain
    and free the slot — the state a finished prefill leaves behind."""
    import jax.numpy as jnp

    slot, _ = m.admit_prefix(list(tokens))
    rng = np.random.RandomState(seed)
    n = len(tokens)
    shape = (m.num_layers, n, m.num_kv_heads, m.head_dim)
    k = rng.randn(*shape)
    v = rng.randn(*shape)
    if m.quantize_kv:
        k, v = k.astype(np.int8), v.astype(np.int8)
        ks = rng.rand(*shape[:3]).astype(np.float32)
        vs = rng.rand(*shape[:3]).astype(np.float32)
    for i in range(0, n, m.page_size):
        pg = int(m._page_table[slot, i // m.page_size])
        t = min(m.page_size, n - i)
        m.k_pages = m.k_pages.at[:, pg, :, :t].set(
            jnp.asarray(k[:, i:i + t], m.k_pages.dtype).swapaxes(1, 2))
        m.v_pages = m.v_pages.at[:, pg, :, :t].set(
            jnp.asarray(v[:, i:i + t], m.v_pages.dtype).swapaxes(1, 2))
        if m.quantize_kv:
            m.k_scales = m.k_scales.at[:, pg, :, :t].set(
                jnp.asarray(ks[:, i:i + t]).swapaxes(1, 2))
            m.v_scales = m.v_scales.at[:, pg, :, :t].set(
                jnp.asarray(vs[:, i:i + t]).swapaxes(1, 2))
    m._seq_lens[slot] = n
    m.register_prefix(slot, list(tokens))
    m.free(slot)


def _acct(m):
    """The accounting fingerprint the unwind test compares: free pages
    (as a SET — order is an implementation detail other mutators also
    perturb), refcounts, registry and LRU membership."""
    return (sorted(m._free_pages), list(m._refcount),
            dict(m._prefix_pages), sorted(m._lru))


def _run(t, cap=200):
    ticks = 0
    while t.state == SENDING:
        t.tick()
        ticks += 1
        assert ticks < cap, "transfer stuck"
    return ticks


# -- frame serialization ----------------------------------------------------


@pytest.mark.parametrize("dtype,with_scales", [
    (np.float32, False), (np.float16, False), (np.int8, True)])
def test_frame_round_trip_exact(rng, dtype, with_scales):
    """Every payload dtype round-trips BIT-exactly — including partial
    tail shapes (ntok < page_size) — and the key/count ride along."""
    for ntok in (8, 3, 1):
        shape = (2, ntok, 2, 4)
        planes = {
            "k": (rng.randn(*shape) * 50).astype(dtype),
            "v": (rng.randn(*shape) * 50).astype(dtype),
        }
        if with_scales:
            planes["ks"] = rng.rand(*shape[:3]).astype(np.float32)
            planes["vs"] = rng.rand(*shape[:3]).astype(np.float32)
        key = bytes(rng.randint(0, 256, (20,), dtype=np.uint8))
        buf = encode_frame(key, ntok, planes)
        rkey, rntok, rplanes = decode_frame(buf)
        assert rkey == key and rntok == ntok
        assert set(rplanes) == set(planes)
        for name in planes:
            assert rplanes[name].dtype == planes[name].dtype
            assert rplanes[name].shape == planes[name].shape
            assert np.array_equal(rplanes[name], planes[name])


def test_frame_checksum_detects_any_byte_flip(rng):
    """The corruption contract: a flipped byte ANYWHERE in the frame —
    header, key, shape words, payload — raises FrameError; nothing is
    ever silently ingested. (Every position is tried: the frame is
    small enough to be exhaustive.)"""
    planes = {"k": rng.randn(2, 3, 2, 4).astype(np.float32)}
    buf = encode_frame(b"\x01" * 20, 3, planes)
    for pos in range(len(buf)):
        bad = bytearray(buf)
        bad[pos] ^= 0xFF
        with pytest.raises(FrameError):
            decode_frame(bytes(bad))


def test_frame_truncation_and_garbage_detected(rng):
    planes = {"k": rng.randn(2, 8, 2, 4).astype(np.float32)}
    buf = encode_frame(b"\x02" * 20, 8, planes)
    for cut in (0, 3, 8, len(buf) // 2, len(buf) - 1):
        with pytest.raises(FrameError):
            decode_frame(buf[:cut])
    with pytest.raises(FrameError):
        decode_frame(b"not a frame at all")


def test_transfer_config_validation():
    with pytest.raises(ValueError, match="window"):
        TransferConfig(window=0)
    with pytest.raises(ValueError, match="max_retries"):
        TransferConfig(max_retries=-1)
    with pytest.raises(ValueError, match="timeout_ticks"):
        TransferConfig(timeout_ticks=0)
    with pytest.raises(ValueError, match="backoff"):
        TransferConfig(backoff=0.5)
    with pytest.raises(ValueError, match="at least one page"):
        KVPageTransfer([], lambda: None, lambda: None)


# -- import / idempotency at the cache layer --------------------------------


@pytest.mark.parametrize("quant", [False, True])
def test_import_registers_serves_hits_and_is_idempotent(rng, quant):
    """An imported page registers under its chain key, zero-ref on the
    LRU, and the next admission pins it exactly like a locally
    prefilled page; re-delivery of the same key is a no-op
    ('present') that changes NO accounting."""
    src = _mgr(quantize_kv=quant)
    dst = _mgr(quantize_kv=quant)
    toks = list(range(20))                       # 2 full pages + tail 4
    _fill_prefix(src, toks, seed=3)
    recs = src.prefix_page_records(toks)
    assert [r[2] for r in recs] == [8, 8, 4]     # partial tail included
    for key, page, ntok in recs:
        got = dst.import_prefix_page(key, ntok,
                                     src.read_page_payload(page, ntok))
        assert got == "imported"
    before = _acct(dst)
    # idempotent double-delivery: every frame again, nothing changes
    for key, page, ntok in recs:
        got = dst.import_prefix_page(key, ntok,
                                     src.read_page_payload(page, ntok))
        assert got == "present"
    assert _acct(dst) == before
    # the transferred pages serve a hit (all but one token)
    slot, cached = dst.admit_prefix(toks)
    assert cached == 19
    # ...and the payload is BIT-identical to the source pages
    for i, (key, spage, ntok) in enumerate(recs):
        dpage = int(dst._page_table[slot, i])
        for plane in ("k", "v") + (("ks", "vs") if quant else ()):
            a = src.read_page_payload(spage, ntok)[plane]
            b = dst.read_page_payload(dpage, ntok)[plane]
            assert np.array_equal(a, b), (plane, i)


def test_import_rejects_mismatched_geometry_and_pressure(rng):
    src = _mgr()
    dst = _mgr()
    toks = list(range(8))
    _fill_prefix(src, toks)
    (key, page, ntok), = src.prefix_page_records(toks)
    payload = src.read_page_payload(page, ntok)
    with pytest.raises(ValueError, match="plane 'k'"):
        bad = dict(payload, k=payload["k"][:, :4])
        dst.import_prefix_page(key, ntok, bad)
    with pytest.raises(ValueError, match="planes"):
        dst.import_prefix_page(key, ntok, {"k": payload["k"]})
    with pytest.raises(ValueError, match="ntok"):
        dst.import_prefix_page(key, 0, payload)
    with pytest.raises(RuntimeError, match="enable_prefix_cache"):
        _mgr(enable_prefix_cache=False).import_prefix_page(
            key, ntok, payload)
    # pressure: no strictly-free page -> None (never evicts the LRU).
    # The resident prefix must NOT share our key's chain (same leading
    # tokens would make the import an idempotent 'present' no-op).
    tight = _mgr(num_pages=2)
    other = list(range(100, 116))
    s0, _ = tight.admit_prefix(other)
    tight.register_prefix(s0, other)
    tight.free(s0)                               # 2 pages, all on LRU
    assert tight.free_page_count == 0 and len(tight._lru) == 2
    assert tight.import_prefix_page(key, ntok, payload) is None
    assert len(tight._lru) == 2                  # nothing evicted


# -- the transfer engine ----------------------------------------------------


@pytest.mark.parametrize("quant", [False, True])
def test_happy_path_transfer_moves_pages(rng, quant):
    src = _mgr(quantize_kv=quant)
    dst = _mgr(quantize_kv=quant)
    toks = list(range(20))
    _fill_prefix(src, toks, seed=5)
    recs = src.prefix_page_records(toks)
    free_before = src.free_page_count
    t = KVPageTransfer(recs, lambda: src, lambda: dst,
                       config=TransferConfig(window=2))
    # source pages pinned for the stream's lifetime
    assert all(int(src._refcount[p]) == 1 for _, p, _ in recs)
    assert t.backlog == 3
    _run(t)
    assert t.state == DONE
    assert t.backlog == 0
    assert t.frames_sent == 3 and t.retries == 0
    assert t.bytes_sent > 0
    # pins released: source accounting back to zero-ref LRU
    assert all(int(src._refcount[p]) == 0 for _, p, _ in recs)
    assert src.free_page_count == free_before
    slot, cached = dst.admit_prefix(toks)
    assert cached == 19


def test_window_bounds_inflight_under_total_drop(rng):
    """With every frame dropped, at most ``window`` frames sit unacked;
    retries are bounded and the transfer FAILS (never hangs)."""
    src, dst = _mgr(), _mgr()
    toks = list(range(40))                       # 5 full pages
    _fill_prefix(src, toks)
    recs = src.prefix_page_records(toks)
    t = KVPageTransfer(recs, lambda: src, lambda: dst,
                       config=TransferConfig(window=2, max_retries=2,
                                             timeout_ticks=1))
    with FaultPlan(seed=0, transfer_drop=1.0) as plan:
        _run(t)
    assert t.state == FAILED
    assert "retries" in t.failure
    assert len(t._inflight) <= 2
    assert plan.fired["transfer_drop"] == t.frames_sent
    # per-frame retry bound held
    assert all(f.retries <= 2 for f in t._inflight.values())
    # pins released on failure too
    assert all(int(src._refcount[p]) == 0 for _, p, _ in recs)


def test_drop_then_recover_with_backoff(rng):
    """A lossy (not dead) wire: dropped frames retransmit after their
    timeout with exponential backoff and the transfer still completes;
    the retry count is visible."""
    src, dst = _mgr(), _mgr()
    toks = list(range(32))
    _fill_prefix(src, toks)
    recs = src.prefix_page_records(toks)
    t = KVPageTransfer(recs, lambda: src, lambda: dst,
                       config=TransferConfig(window=4, max_retries=5,
                                             timeout_ticks=1))
    with FaultPlan(seed=2, transfer_drop=0.5):
        ticks = _run(t, cap=500)
    assert t.state == DONE
    assert t.retries > 0 and ticks > 1
    assert dst.admit_prefix(toks)[1] == 31


def test_corrupt_frames_detected_then_retransmitted(rng):
    """The corruption contract end to end: every corrupt delivery is
    caught by the checksum (counted), the frame nacks + retransmits,
    and the eventually-clean copy lands BIT-identical — corruption can
    delay a transfer, never poison a pool."""
    class Inst:
        class _C:
            def __init__(self):
                self.v = 0

            def inc(self, n=1):
                self.v += n

        def __init__(self):
            for name in ("transfers_completed", "transfers_failed",
                         "transfer_frames", "transfer_bytes",
                         "transfer_tokens", "transfer_retries",
                         "transfer_drops", "transfer_corrupt"):
                setattr(self, name, self._C())

    src, dst = _mgr(), _mgr()
    toks = list(range(32))                       # 4 pages of draws
    _fill_prefix(src, toks, seed=9)
    recs = src.prefix_page_records(toks)
    inst = Inst()
    t = KVPageTransfer(recs, lambda: src, lambda: dst,
                       config=TransferConfig(window=2, max_retries=8,
                                             timeout_ticks=1),
                       instruments=inst)
    with FaultPlan(seed=4, transfer_corrupt=0.75) as plan:
        _run(t, cap=500)
    assert t.state == DONE
    assert plan.fired["transfer_corrupt"] > 0
    assert inst.transfer_corrupt.v == plan.fired["transfer_corrupt"]
    assert inst.transfer_retries.v >= inst.transfer_corrupt.v
    assert inst.transfer_tokens.v == 32
    for i, (key, spage, ntok) in enumerate(recs):
        dpage = dst._prefix_pages[key]
        assert np.array_equal(src.read_page_payload(spage, ntok)["k"],
                              dst.read_page_payload(dpage, ntok)["k"])


def test_failed_transfer_unwind_indistinguishable(rng):
    """THE decode-side contract: after a transfer fails mid-stream,
    the destination's accounting (free pages, refcounts, registry,
    LRU) is exactly what it was before the transfer — a mirror manager
    that never saw a transfer is indistinguishable."""
    src = _mgr()
    dst = _mgr()
    toks = list(range(24))
    _fill_prefix(src, toks)
    recs = src.prefix_page_records(toks)
    before = _acct(dst)
    # a lossy wire where SOME frames land and one exhausts its retries
    # (seed chosen so both happen): the landed imports must unwind
    t = KVPageTransfer(recs, lambda: src, lambda: dst,
                       config=TransferConfig(window=1, max_retries=1,
                                             timeout_ticks=1))
    saw_import = False
    with FaultPlan(seed=1, transfer_drop=0.6):
        ticks = 0
        while t.state == SENDING:
            t.tick()
            saw_import = saw_import or bool(t._imported)
            ticks += 1
            assert ticks < 300
    assert saw_import, "seed produced no partial import — pick another"
    assert t.state == FAILED
    assert _acct(dst) == before
    assert sorted(dst._free_pages) == before[0]
    # and a fault-free mirror run into a FRESH manager still works
    mirror = _mgr()
    t2 = KVPageTransfer(src.prefix_page_records(toks),
                        lambda: src, lambda: mirror)
    _run(t2)
    assert t2.state == DONE


def test_dead_endpoints_fail_transfer_without_touching_pools(rng):
    src = _mgr()
    dst = _mgr()
    toks = list(range(16))
    _fill_prefix(src, toks)
    recs = src.prefix_page_records(toks)
    # dead source at construction
    t = KVPageTransfer(recs, lambda: None, lambda: dst)
    assert t.state == FAILED and "source" in t.failure
    # source dies mid-stream (the wire held dark so frames are still
    # outstanding — a clean wire acks synchronously and would finish)
    alive = {"src": src}
    t2 = KVPageTransfer(recs, lambda: alive["src"], lambda: dst,
                        config=TransferConfig(window=1, max_retries=9))
    with FaultPlan(seed=0, transfer_drop=1.0):
        t2.tick()
    assert t2.state == SENDING
    alive["src"] = None
    t2.tick()
    assert t2.state == FAILED and "source" in t2.failure
    # destination dies mid-stream: imported pages are unreachable and
    # the transfer fails without raising
    src2, dst2 = _mgr(), _mgr()
    _fill_prefix(src2, toks)
    alive2 = {"dst": dst2}
    t3 = KVPageTransfer(src2.prefix_page_records(toks),
                        lambda: src2, lambda: alive2["dst"],
                        config=TransferConfig(window=1, max_retries=9))
    with FaultPlan(seed=0, transfer_drop=1.0):
        t3.tick()
    assert t3.state == SENDING
    alive2["dst"] = None
    t3.tick()
    assert t3.state == FAILED and "destination" in t3.failure
    # pins released wherever the source POOL is still reachable (the
    # dst-death path); a DEAD source's pins are moot — its pool died
    # with the replica and is never read again
    for _, p, _ in src2.prefix_page_records(toks):
        assert int(src2._refcount[p]) == 0


def test_receiver_pressure_aborts_and_unwinds(rng):
    """A destination with fewer free pages than the stream needs: the
    transfer fails on the pressure signal and the partial import
    unwinds completely."""
    src = _mgr()
    dst = _mgr(num_pages=2)
    toks = list(range(24))                       # needs 3 pages
    _fill_prefix(src, toks)
    before = _acct(dst)
    t = KVPageTransfer(src.prefix_page_records(toks),
                       lambda: src, lambda: dst,
                       config=TransferConfig(window=4))
    _run(t)
    assert t.state == FAILED and "pressure" in t.failure
    assert _acct(dst) == before
