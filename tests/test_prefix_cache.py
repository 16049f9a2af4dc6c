"""Round-9 prefix cache: page-granular content-hash registry on
KVCacheManager — refcount/ownership property test under randomized
admit/evict/preempt churn, plus targeted unit tests for matching,
registration, LRU eviction and copy-on-write.
"""
import numpy as np
import pytest

from paddle_tpu.inference.kv_cache import KVCacheManager


def _mgr(**over):
    kw = dict(num_layers=2, num_kv_heads=2, head_dim=8, num_pages=12,
              max_batch=4, max_seq_len=64, page_size=8,
              enable_prefix_cache=True)
    kw.update(over)
    return KVCacheManager(**kw)


# -- unit behavior ----------------------------------------------------------


def test_identical_prompt_hits_all_but_one_token():
    m = _mgr()
    toks = list(range(20))
    s0, c0 = m.admit_prefix(toks)
    assert c0 == 0
    m.register_prefix(s0, toks)
    s1, c1 = m.admit_prefix(toks)
    # full pages + partial tail all hit; one token is left to feed (the
    # cache stores K/V, not logits)
    assert c1 == 19
    assert (m._page_table[s0][:3] == m._page_table[s1][:3]).all()
    m.free(s0), m.free(s1)


def test_partial_prefix_hit_at_page_granularity():
    m = _mgr()
    toks = list(range(20))
    s0, _ = m.admit_prefix(toks)
    m.register_prefix(s0, toks)
    m.free(s0)
    # shares the first full page only (diverges at token 8)
    other = list(range(8)) + [99] * 8
    s1, c1 = m.admit_prefix(other)
    assert c1 == 8
    m.free(s1)
    # diverges inside page 1: no hit (page granularity)
    s2, c2 = m.admit_prefix([0, 1, 2, 99, 4, 5, 6, 7, 8, 9])
    assert c2 == 0
    m.free(s2)


def test_chain_keys_deterministic_across_independent_managers(rng):
    """Round-18 satellite: the sha1 chain keys are a pure function of
    (prior chain, tokens) — independently constructed managers derive
    IDENTICAL chains from identical tokens. This is the fleet router's
    correctness assumption: its prefix-affinity map hashes prompts with
    the module-level ``chain_key`` and expects the replica-local
    registries (different KVCacheManager instances, different pools,
    potentially different processes) to have registered the same pages
    under the same keys."""
    from paddle_tpu.inference.kv_cache import chain_key, prompt_chain_keys

    a, b = _mgr(), _mgr(num_pages=24, max_batch=2)   # different geometry
    toks = rng.randint(0, 50000, (40,)).tolist()
    h_a = h_b = b""
    for i in range(0, 40, 8):
        h_a = a._chain_key(h_a, toks[i:i + 8])
        h_b = b._chain_key(h_b, toks[i:i + 8])
        assert h_a == h_b
        # ...and the managers' chain IS the module-level chain the
        # router hashes with
        assert h_a == prompt_chain_keys(toks[:i + 8], 8)[-1]
    # the chain binds content AND position: any divergence (content,
    # order, fill count, prior chain) changes every key downstream
    assert chain_key(b"", toks[:8]) != chain_key(b"", toks[1:9])
    assert chain_key(b"", toks[:7]) != chain_key(b"", toks[:8])
    assert chain_key(b"x", toks[:8]) != chain_key(b"", toks[:8])
    # numpy vs list token spellings hash identically (the router hashes
    # host lists; register_prefix sees whatever the request carried)
    assert chain_key(b"", np.asarray(toks[:8])) == chain_key(b"", toks[:8])
    # sub-page prompts have no page-granular identity
    assert prompt_chain_keys(toks[:7], 8) == []


def test_transfer_addressing_is_the_same_chain_across_managers(rng):
    """Round-20 satellite: the KV-transfer wire addresses frames by the
    SAME sha1 chain the registries and the fleet affinity map hash —
    an export walk on one manager produces records whose keys a
    DIFFERENT-GEOMETRY manager derives identically, so an imported page
    is immediately addressable (and hit) there. Locks the cross-manager
    half of the disaggregation contract at the cache layer."""
    from paddle_tpu.inference.kv_cache import prompt_chain_keys

    a = _mgr()
    b = _mgr(num_pages=24, max_batch=2)          # different geometry
    toks = rng.randint(0, 50000, (20,)).tolist()  # 2 pages + tail 4
    s0, _ = a.admit_prefix(toks)
    a._seq_lens[s0] = len(toks)
    a.register_prefix(s0, toks)
    a.free(s0)
    recs = a.prefix_page_records(toks)
    assert [r[2] for r in recs] == [8, 8, 4]
    # full-page keys ARE the module-level chain the router hashes with
    assert [r[0] for r in recs[:2]] == prompt_chain_keys(toks, 8)
    # ...and manager B (never having seen A) derives the same chain:
    # importing under A's exported keys makes B's OWN admission walk
    # find every page, partial tail included
    for key, page, ntok in recs:
        got = b.import_prefix_page(key, ntok,
                                   a.read_page_payload(page, ntok))
        assert got == "imported"
    s1, cached = b.admit_prefix(toks)
    assert cached == 19                          # all but the one fed token
    # the export walk stops at the first unregistered link: a foreign
    # suffix exports only the shared prefix
    other = toks[:8] + [7] * 12
    assert [r[2] for r in a.prefix_page_records(other)] == [8]


def test_zero_ref_registered_pages_survive_on_lru_until_pressure():
    m = _mgr(num_pages=6)
    toks = list(range(16))
    s0, _ = m.admit_prefix(toks)
    m.register_prefix(s0, toks)
    m.free(s0)
    assert m.free_page_count == 4 and m.available_page_count == 6
    # hit survives the free
    s1, c1 = m.admit_prefix(toks)
    assert c1 == 15
    m.free(s1)
    # pool pressure evicts the LRU tail and reuses it
    big = [[1000 + i * 100 + j for j in range(16)] for i in range(3)]
    slots = [m.admit_prefix(t)[0] for t in big]
    assert m.free_page_count == 0
    for s in slots:
        m.free(s)
    # original prefix was (at least partly) evicted: hit shrinks or dies
    s2, c2 = m.admit_prefix(toks)
    assert c2 < 15
    m.free(s2)


def test_cow_on_divergent_write_into_shared_page():
    m = _mgr()
    toks = list(range(12))        # page 0 full, page 1 partial (4 tokens)
    s0, _ = m.admit_prefix(toks)
    m.register_prefix(s0, toks)
    s1, c1 = m.admit_prefix(toks)
    assert c1 == 11
    shared = int(m._page_table[s1][1])
    assert m._refcount[shared] == 2
    assert m.needs_cow(s1, 11)    # next write lands in the shared tail
    src, dst = m.prepare_write(s1, 11)
    assert src == shared and dst != shared
    assert int(m._page_table[s1][1]) == dst
    assert int(m._page_table[s0][1]) == shared   # owner untouched
    assert m._refcount[shared] == 1 and m._refcount[dst] == 1
    assert not m.needs_cow(s1, 11)
    # owner writing its own (now refcount-1) page needs no copy
    assert not m.needs_cow(s0, 11)
    m.free(s0), m.free(s1)


def test_pinned_pages_never_evicted():
    """Refcounted prefix pages are pinned: allocation pressure must raise
    rather than steal them."""
    m = _mgr(num_pages=2, max_batch=3)
    toks = list(range(16))
    s0, _ = m.admit_prefix(toks)
    m.register_prefix(s0, toks)
    s1, c1 = m.admit_prefix(toks)   # shares both pages (cap at 15)
    assert c1 == 15
    with pytest.raises(RuntimeError, match="exhausted"):
        m.admit_prefix([7] * 8)
    # the shared pages are still intact in both tables
    assert (m._page_table[s0][:2] == m._page_table[s1][:2]).all()
    m.free(s0), m.free(s1)


def test_admission_does_not_double_count_matched_lru_pages():
    """A matched page sitting on the LRU is about to be re-pinned by the
    admission itself — it must NOT also count as allocatable for the
    fresh-page need (double-count -> mid-admission alloc failure with
    partially mutated state)."""
    m = _mgr(num_pages=3, max_batch=2, max_seq_len=24)
    shared16 = list(range(16))
    s0, _ = m.admit_prefix(shared16)
    m.register_prefix(s0, shared16)
    m.free(s0)                        # both pages park on the LRU
    s1, _ = m.admit_prefix([99] * 8)  # pins the one remaining page
    assert m.free_page_count == 0 and m.available_page_count == 2
    # 20-token prompt: matches both LRU pages, needs ONE fresh page —
    # which doesn't exist once the match re-pins the LRU
    free_slots = m.free_slot_count
    assert m.admit_prefix(shared16 + [7] * 4, soft=True) is None
    assert m.free_slot_count == free_slots          # nothing mutated
    assert len(m._lru) == 2                         # LRU untouched
    with pytest.raises(RuntimeError, match="exhausted"):
        m.admit_prefix(shared16 + [7] * 4)
    _check_invariants(m)
    m.free(s1)


# -- the 1k-churn property test ---------------------------------------------


def _check_invariants(m: KVCacheManager):
    num_pages = m.num_pages
    free = set(m._free_pages)
    lru = set(m._lru)
    # refcounts recomputed from the tables must match the incremental ones
    counts = np.zeros((num_pages,), np.int64)
    for row in m._page_table:
        for p in row:
            if p >= 0:
                counts[p] += 1
    assert (counts == m._refcount).all(), "refcount drifted from tables"
    held = {p for p in range(num_pages) if counts[p] > 0}
    # every page in EXACTLY one of: free, LRU (zero-ref registered), held
    assert not (free & lru) and not (free & held) and not (lru & held)
    assert free | lru | held == set(range(num_pages)), "page leaked"
    # LRU pages are registered; free pages are not
    for p in lru:
        assert p in m._page_key
    for p in free:
        assert p not in m._page_key
    # registry is a bijection page <-> key
    assert len(m._prefix_pages) == len(m._page_key)
    for page, key in m._page_key.items():
        assert m._prefix_pages[key] == page


def test_prefix_refcounts_survive_1k_churn_steps(rng):
    """Randomized admit / chunk-write (CoW-guarded) / grow / preempt /
    evict churn: after every op no page is leaked, refcounts match the
    tables, and no write ever targets a page with refcount >= 2 (shared
    pages are immutable)."""
    m = _mgr(num_pages=10, max_batch=3, max_seq_len=48, page_size=4)
    # a small prompt pool with heavy shared prefixes drives real hits
    base = [int(x) for x in rng.randint(0, 50, (8,))]
    prompts = [base[:4] + [int(x) for x in rng.randint(50, 99, (k,))]
               for k in (1, 3, 5, 8)] + [base, base[:6]]
    active: dict[int, list[int]] = {}       # slot -> context
    registered: dict[int, list[int]] = {}   # slot -> prompt it must register
    for step in range(1000):
        op = rng.rand()
        if op < 0.35 and m.free_slot_count:
            ctx = list(prompts[rng.randint(len(prompts))])
            need = m.pages_needed(len(ctx))
            if need <= m.available_page_count:
                slot, cached = m.admit_prefix(ctx)
                assert 0 <= cached <= len(ctx) - 1
                active[slot] = ctx
                registered[slot] = list(ctx)
        elif op < 0.70 and active:
            # feed a chunk: grow, CoW-guard the first write page, advance
            slot = list(active)[rng.randint(len(active))]
            written = m.seq_len(slot)
            n = int(rng.randint(1, 5))
            n = min(n, m.max_seq_len - written)
            if n > 0 and m.ensure_capacity(slot, written + n):
                cow = m.prepare_write(slot, written)
                if cow is not None:
                    src, dst = cow
                    assert m._refcount[dst] == 1
                # THE immutability invariant: every page the chunk writes
                # now has exactly one reference
                for ppos in range(written, written + n):
                    page = int(m._page_table[slot, ppos // m.page_size])
                    assert page >= 0
                    assert m._refcount[page] == 1, \
                        f"write into shared page {page} (step {step})"
                m.advance(slot, n)
                ctx = active[slot]
                while len(ctx) < m.seq_len(slot):
                    ctx.append(int(rng.randint(0, 99)))   # generated
                if (slot in registered
                        and m.seq_len(slot) >= len(registered[slot])):
                    m.register_prefix(slot, registered.pop(slot))
        elif active:
            # preempt/finish: free the slot outright
            slot = list(active)[rng.randint(len(active))]
            m.free(slot)
            del active[slot]
            registered.pop(slot, None)
        _check_invariants(m)
    for slot in list(active):
        m.free(slot)
    _check_invariants(m)
    assert m.available_page_count == m.num_pages  # zero pages leaked
    assert m.prefix_hit_rate > 0.0                # the churn actually hit


# -- round 21: the host-DRAM spill tier -------------------------------------


def _fill(m, tokens, seed=0):
    """Admit ``tokens``, write deterministic per-token K/V rows (and
    scale rows on a quantized pool), register the chain and free the
    slot — the zero-ref LRU-parked state a finished request leaves."""
    import jax.numpy as jnp

    slot, _ = m.admit_prefix(list(tokens))
    rng = np.random.RandomState(seed)
    n = len(tokens)
    shape = (m.num_layers, n, m.num_kv_heads, m.head_dim)
    k = (rng.randn(*shape) * 50)
    v = (rng.randn(*shape) * 50)
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


def _payloads_by_key(m, tokens):
    """key -> host payload planes for every registered page of the
    chain (full pages + partial tail), via the export walk."""
    return {key: {name: np.array(a) for name, a in
                  m.read_page_payload(page, ntok).items()}
            for key, page, ntok in m.prefix_page_records(tokens)}


@pytest.mark.parametrize("kw", [
    dict(),                                      # fp32
    dict(dtype="float16"),                       # fp16 payloads
    dict(quantize_kv=True),                      # int8 + fp32 scales
], ids=["fp32", "fp16", "int8"])
def test_spilled_then_restored_pages_bit_exact(kw):
    """The tier round-trip contract: a prefix chain (partial tail
    included) evicted THROUGH the host tier and restored on the next
    admission is BIT-identical — payloads, hit counts, invariants —
    to a control manager whose pages were never evicted."""
    import jax.numpy as jnp

    if "dtype" in kw:
        kw = dict(kw, dtype=jnp.float16)
    tiered = _mgr(host_tier_bytes=1 << 20, **kw)
    control = _mgr(**kw)
    toks = list(range(100, 120))                 # 2 full pages + tail 4
    _fill(tiered, toks)
    _fill(control, toks)
    want = _payloads_by_key(control, toks)
    assert len(want) == 3
    # force the whole chain down the eviction ladder: every zero-ref
    # page spills (HBM -> host), the registry forgets it
    assert tiered.reserve_import_room(tiered.num_pages)
    assert not tiered._prefix_pages
    assert tiered.host_tier_page_count == 3
    assert tiered.host_tier_bytes_used > 0
    spill_bytes = int(tiered._m_tier_spill_bytes.value)
    assert spill_bytes > 0
    # the next admission restores the chain from the tier...
    s_t, hit_t = tiered.admit_prefix(toks)
    s_c, hit_c = control.admit_prefix(toks)
    assert hit_t == hit_c == 19                  # all but the fed token
    # ...bit-exactly, partial tail included
    got = _payloads_by_key(tiered, toks)
    assert got.keys() == want.keys()
    for key in want:
        for name in want[key]:
            assert np.array_equal(got[key][name], want[key][name]), \
                (key, name)
    assert int(tiered._m_tier_restore_bytes.value) == spill_bytes
    assert tiered.tier_hit_rate == 1.0
    # restored entries STAY resident (content-addressed): a later
    # re-eviction refreshes recency instead of re-copying
    assert tiered.host_tier_page_count == 3
    tiered.free(s_t)
    control.free(s_c)
    _check_invariants(tiered)
    _check_invariants(control)


def test_tier_accounting_parity_with_never_spilled_manager():
    """Scheduler-visible accounting after a spill + restore round-trip
    is IDENTICAL to a manager that never evicted: same free/available
    counts, same LRU population size, same hit tokens — the tier is
    cache state, invisible to capacity math."""
    tiered = _mgr(host_tier_bytes=1 << 20)
    control = _mgr()
    for base, seed in ((0, 1), (200, 2)):
        toks = list(range(base, base + 16))
        _fill(tiered, toks, seed=seed)
        _fill(control, toks, seed=seed)
    assert tiered.reserve_import_room(4)         # spill some of the LRU
    assert tiered.available_page_count == control.available_page_count
    for base in (0, 200):
        toks = list(range(base, base + 16))
        s_t, hit_t = tiered.admit_prefix(toks)
        s_c, hit_c = control.admit_prefix(toks)
        assert hit_t == hit_c == 15
        tiered.free(s_t)
        control.free(s_c)
    assert tiered.free_page_count == control.free_page_count
    assert tiered.available_page_count == control.available_page_count
    assert len(tiered._lru) == len(control._lru)
    assert tiered._prefix_pages.keys() == control._prefix_pages.keys()
    _check_invariants(tiered)
    _check_invariants(control)


def test_tier_disabled_keeps_pre21_drop_on_evict():
    """host_tier_bytes=0 (the default): eviction drops the payload
    exactly like pre-round-21 — nothing stored, the repeat admission
    recomputes."""
    m = _mgr()                                   # no tier
    toks = list(range(20))
    _fill(m, toks)
    assert m.reserve_import_room(m.num_pages)
    assert m.host_tier_page_count == 0
    assert m.host_tier_occupancy == 0.0
    s, hit = m.admit_prefix(toks)
    assert hit == 0                              # dropped -> recompute
    assert int(m._m_tier_lookups.value) == 0
    m.free(s)
    _check_invariants(m)
    with pytest.raises(ValueError, match="host_tier_bytes"):
        _mgr(host_tier_bytes=-1)


def test_tier_budget_evicts_its_own_lru_and_oversize_never_stores():
    """The tier is byte-bounded with its own LRU: pressure drops the
    OLDEST payload (the final rung of the ladder), and a payload bigger
    than the whole budget is never stored."""
    page_bytes = 2 * 2 * 8 * 2 * 8 * 4           # L*2(K,V)*ps*heads*hd*f32
    m = _mgr(host_tier_bytes=2 * page_bytes)     # room for two pages
    a, b, c = list(range(8)), list(range(50, 58)), list(range(80, 88))
    for toks, seed in ((a, 1), (b, 2), (c, 3)):
        _fill(m, toks, seed=seed)
    assert m.reserve_import_room(m.num_pages)
    # three spilled, budget holds two: the oldest (a's page) dropped
    assert m.host_tier_page_count == 2
    assert int(m._m_tier_evictions.value) == 1
    assert m.host_tier_bytes_used <= m.host_tier_limit
    s, hit = m.admit_prefix(a)
    assert hit == 0                              # a fell off the tier
    m.free(s)
    s, hit = m.admit_prefix(b)
    assert hit == 7                              # b survived
    m.free(s)
    # a budget smaller than one payload stores nothing, loudly counted
    tiny = _mgr(host_tier_bytes=16)
    _fill(tiny, list(range(8)))
    assert tiny.reserve_import_room(tiny.num_pages)
    assert tiny.host_tier_page_count == 0


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_batched_import_bit_identical_to_per_page_single_call_per_plane(
        rng, quant):
    """The round-21 batched landing zone: ``import_prefix_pages`` lands
    a whole round with ONE donated device scatter per (K, V, scale)
    plane — counted on ``kv_tier_restore_device_calls`` — and the
    landed payloads are BIT-identical to the eager per-page reference
    path (``import_prefix_page``, the bit-identity oracle)."""
    src = _mgr(quantize_kv=quant)
    toks = rng.randint(0, 50000, (20,)).tolist() # 2 pages + tail 4
    _fill(src, toks, seed=7)
    records = src.prefix_page_records(toks)
    entries = [(key, ntok, {n: np.array(a) for n, a in
                            src.read_page_payload(page, ntok).items()})
               for key, page, ntok in records]
    per_page = _mgr(quantize_kv=quant)
    for key, ntok, payload in entries:
        assert per_page.import_prefix_page(key, ntok, payload) \
            == "imported"
    batched = _mgr(quantize_kv=quant)
    calls0 = int(batched._m_restore_scatters.value)
    statuses = batched.import_prefix_pages(entries)
    assert statuses == ["imported"] * 3
    # ONE device scatter per plane for the WHOLE 3-page round
    nplanes = 4 if quant else 2
    assert int(batched._m_restore_scatters.value) - calls0 == nplanes
    want = _payloads_by_key(per_page, toks)
    got = _payloads_by_key(batched, toks)
    assert want.keys() == got.keys() and len(want) == 3
    for key in want:
        for name in want[key]:
            assert np.array_equal(got[key][name], want[key][name]), \
                (key, name)
    # ...and both registries serve the same hits afterwards
    s_b, hit_b = batched.admit_prefix(toks)
    s_p, hit_p = per_page.admit_prefix(toks)
    assert hit_b == hit_p == 19
    batched.free(s_b)
    per_page.free(s_p)
    _check_invariants(batched)
    _check_invariants(per_page)
    # idempotent re-delivery + in-batch duplicate keys read "present"
    assert batched.import_prefix_pages(entries) == ["present"] * 3
    dup = [entries[0], entries[0]]
    fresh = _mgr(quantize_kv=quant)
    assert fresh.import_prefix_pages(dup) == ["imported", "present"]
    # pressure mid-round: once the free list dries, later entries stay
    # None and nothing half-lands (same contract as the per-page path)
    tight = _mgr(num_pages=2, quantize_kv=quant)
    other = list(range(60000, 60016))
    s0, _ = tight.admit_prefix(other)
    tight.register_prefix(s0, other)
    tight.free(s0)                               # 2 pages, all on LRU
    assert tight.import_prefix_pages(entries) == [None] * 3
    _check_invariants(tight)
