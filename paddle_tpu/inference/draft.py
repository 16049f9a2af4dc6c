"""N-gram / prompt-lookup draft proposer for speculative decoding.

Round 12: the host-side half of the draft–verify–accept loop. Each request
owns one :class:`DraftProposer`; the serving scheduler feeds it the
request's context ids (prompt + generated so far — exactly what the
scheduler already tracks for preemption replay) and asks for up to ``k``
draft tokens per decode step. The unified step then verifies the drafts in
one ragged pass (1 + k query rows for the lane, per-row causal limits) and
the fused accept epilogue keeps the longest matching prefix plus one bonus
token — see ``models/gpt.py build_unified_step(spec_k=...)``.

Proposal scheme (prompt-lookup decoding, arxiv 2402.xxxx shape): find the
longest trailing n-gram of the context (``max_ngram`` down to 1) that also
occurred EARLIER in the context, preferring the MOST RECENT earlier match,
and copy the tokens that followed it. Lookups chain: copied tokens extend a
virtual context and the lookup repeats until ``k`` drafts are gathered or
no match remains — a period-1 repetition (the common greedy-decode
attractor) therefore fills all ``k`` slots from a single-token match.

The index is incremental and DETERMINISTIC in the context: n-grams ending
strictly before the last context token map to their latest start position,
extended as the context grows (``_synced`` high-water mark). A preemption
replay re-feeds the identical context, so the table — and every proposal —
replays identically (the same property the seeded sample streams rely on).

Adaptive k: acceptance feedback (``update(proposed, accepted)``) drives an
EMA; the effective ``k`` scales monotonically with the EMA down to 0
(plain decode — speculation priced off when the workload doesn't repeat).
While backed off to 0, a cooldown of plain-decode steps re-arms a probe so
a workload that turns repetitive later gets re-tried.

Round 19 adds the MODEL-BASED draft source: :class:`ModelDraftProposer`
(the same adaptive-k EMA surface, per request) backed by a shared
:class:`ModelDraftEngine` — a truncated-layer SELF-DRAFT of the serving
model (the first ``spec_draft_layers`` layers of the SAME
``serving_params`` stack, shared embeddings/LM head — see
``models/gpt.py draft_serving_params``) running as its own small
fixed-shape unified-step jit over a DEDICATED paged-KV pool. Unlike the
n-gram table, the model drafter accepts on non-repetitive text: its
proposal IS (approximately) what the target would emit, so acceptance
tracks truncation quality instead of workload repetitiveness. The engine
batches every proposing lane into ONE k-step decode chain per scheduler
round, chained DEVICE-SIDE through the unified step's feedback carry
(intermediate draft tokens never materialize on the host — one sync per
round lands all of them), and keeps its pool crash-consistent with
preemption replay by self-healing: each lane records the token ids it
fed (``_fed``), and a proposal first rolls the draft KV back to the
longest prefix of the lane's CURRENT context it already holds
(``KVCacheManager.rollback``) — a preemption replay, a rejected draft
tail, a clamped proposal or a dropped in-flight step all reconcile
through the same one comparison.
"""
from __future__ import annotations

__all__ = ["DraftProposer", "ModelDraftProposer", "ModelDraftEngine"]


class DraftProposer:
    """Per-request n-gram draft source with adaptive speculation length.

    ``max_k``: the ceiling on drafts per step (the unified step's build
    geometry — the scheduler may clamp lower per step for budget/capacity).
    ``max_ngram``: longest trailing n-gram tried first. ``alpha``: EMA
    weight of the newest acceptance sample. ``min_ema``: EMA below which
    speculation disables (k = 0). ``retry_after``: plain-decode steps spent
    disabled before the EMA re-arms to ``probe_ema``.
    """

    def __init__(self, max_k: int, *, max_ngram: int = 3, alpha: float = 0.5,
                 min_ema: float = 0.2, retry_after: int = 16,
                 probe_ema: float = 0.5):
        if max_k < 1:
            raise ValueError(f"max_k must be >= 1, got {max_k}")
        if max_ngram < 1:
            raise ValueError(f"max_ngram must be >= 1, got {max_ngram}")
        self.max_k = int(max_k)
        self.max_ngram = int(max_ngram)
        self.alpha = float(alpha)
        self.min_ema = float(min_ema)
        self.retry_after = int(retry_after)
        self.probe_ema = float(probe_ema)
        self._ema = 1.0          # optimistic start: speculate until priced
        self._cool = 0
        # n-gram (as tuple) -> latest start position, over context n-grams
        # ending STRICTLY before the last token (the tail n-gram itself must
        # never shadow its earlier occurrences)
        self._index: dict[tuple, int] = {}
        self._synced = 0         # context positions whose n-grams are indexed

    # -- adaptive k --------------------------------------------------------

    @property
    def k(self) -> int:
        """Current speculation length, monotone in the acceptance EMA:
        full ``max_k`` at EMA 1.0, 0 (plain decode) below ``min_ema``."""
        if self._ema < self.min_ema:
            return 0
        return min(self.max_k, int(self._ema * (self.max_k + 1)))

    def update(self, proposed: int, accepted: int) -> None:
        """Feed one decode step's outcome. ``proposed == 0`` (nothing
        drafted — disabled, no match, or no budget) leaves the EMA alone
        but ticks the re-arm cooldown while disabled."""
        if proposed <= 0:
            if self.k == 0:
                self._cool += 1
                if self._cool >= self.retry_after:
                    self._ema = self.probe_ema
                    self._cool = 0
            return
        accepted = max(0, min(int(accepted), int(proposed)))
        self._ema = ((1.0 - self.alpha) * self._ema
                     + self.alpha * (accepted / proposed))
        self._cool = 0

    # -- the n-gram table --------------------------------------------------

    def _sync(self, context) -> None:
        """Index the n-grams of ``context`` ending at positions <= len-2
        (monotone high-water mark: a preemption replay with the identical
        context is a no-op)."""
        n_ctx = len(context)
        # positions are n-gram END indices; the final token's n-grams stay
        # out so the tail lookup finds its latest EARLIER occurrence
        for end in range(self._synced, n_ctx - 1):
            for n in range(1, self.max_ngram + 1):
                start = end - n + 1
                if start < 0:
                    break
                self._index[tuple(context[start:end + 1])] = start
        self._synced = max(self._synced, n_ctx - 1)

    def propose(self, context, budget: int) -> list[int]:
        """Up to ``min(self.k, budget)`` draft tokens continuing
        ``context``. Empty when the context is too short (< 2 tokens), the
        adaptive k backed off, or no trailing n-gram recurs."""
        k = min(self.k, int(budget))
        if k <= 0 or len(context) < 2:
            return []
        self._sync(context)
        drafts: list[int] = []
        v = list(context)
        # chained-lookup overlay: n-grams ending inside the drafted
        # extension (later than anything in the main index, so it wins)
        overlay: dict[tuple, int] = {}

        def extend_overlay(upto):
            # index n-grams ending at position upto-2 (the new interior)
            end = upto - 2
            for n in range(1, self.max_ngram + 1):
                start = end - n + 1
                if start < 0:
                    break
                overlay[tuple(v[start:end + 1])] = start

        while len(drafts) < k:
            match = None
            for n in range(min(self.max_ngram, len(v) - 1), 0, -1):
                key = tuple(v[-n:])
                p = overlay.get(key, self._index.get(key))
                if p is not None and p + n < len(v):
                    match = (p, n)
                    break
            if match is None:
                break
            p, n = match
            take = v[p + n:p + n + (k - len(drafts))]
            if not take:
                break
            for t in take:
                drafts.append(t)
                v.append(t)
                extend_overlay(len(v))
        return drafts


class ModelDraftProposer(DraftProposer):
    """Per-request adaptive-k state for the MODEL-BASED draft source.

    The same ``k``/``update`` EMA-backoff surface as the n-gram proposer
    (so the scheduler's adaptive clamps, cooldown re-probe and
    preemption-replay persistence apply unchanged); proposals come from
    the shared :class:`ModelDraftEngine` instead of an n-gram table. The
    serving scheduler batches every proposing lane into one engine call
    per round — :meth:`propose` is the single-lane convenience spelling
    of the same thing.
    """

    def __init__(self, max_k: int, engine: "ModelDraftEngine", req_id,
                 **kw):
        super().__init__(max_k, **kw)
        self._engine = engine
        self._req_id = req_id

    def propose(self, context, budget: int) -> list[int]:
        k = min(self.k, int(budget))
        if k <= 0 or not len(context):
            return []
        return self._engine.propose(
            {0: (self._req_id, list(context), k)}).get(0, [])


class ModelDraftEngine:
    """The shared truncated-layer self-draft pass behind every
    :class:`ModelDraftProposer` of one predictor.

    Owns the DEDICATED draft KV pool (a :class:`KVCacheManager` with
    ``draft_layers`` layers — same page machinery, same int8-KV support,
    same head sharding under a serving mesh) and two fixed-shape builds
    of the truncated stack: a CATCH-UP geometry (``models/gpt.py
    build_draft_step`` at ``chunk`` tokens per lane per call — replaying
    context the pool does not hold yet) and, since round 22, the FUSED
    CHAIN (``models/gpt.py build_draft_chain``): the whole k-step
    autoregressive proposal pass as one jit — a device-side ``lax.scan``
    whose step 1 feeds each lane's live last context token and steps
    2..k feed the previous step's greedy argmax, so the intermediate
    draft tokens never touch the host and a speculative round costs ONE
    draft dispatch (+ the target's verify step).

    Crash consistency / preemption replay: per request the engine records
    the exact token ids it fed (``fed``). Every proposal starts by
    rolling the draft KV back to the longest common prefix of ``fed`` and
    the lane's CURRENT context (capped at ``len(context) - 1`` so the
    chain's first feed is always the live last token) — rejected drafts,
    clamped proposals, preemption replays and dropped in-flight steps all
    self-heal through that one comparison, with no commit protocol
    against the target's accept results. Draft capacity is opportunistic
    like the drafts themselves: a lane the pool cannot hold is evicted
    (oldest-proposer first) or simply proposes nothing this round.
    """

    def __init__(self, config, params, draft_layers: int, *, page_size,
                 chunk, max_batch, max_seq_len, num_pages=None,
                 use_kernel=None, kv_quant=False, mesh=None, dtype=None,
                 on_launch=None, max_k=None):
        from ..models.gpt import (build_draft_step, draft_config,
                                  draft_serving_params)
        from ..observability import MetricsRegistry
        from .kv_cache import KVCacheManager, pages_needed

        import jax.numpy as jnp
        import numpy as np

        self.draft_layers = int(draft_layers)
        dcfg = draft_config(config, self.draft_layers)  # validates depth
        # slice off the UNSHARDED extraction; under a serving mesh the
        # truncated stacks re-shard with the draft config (same Megatron
        # layout, head-major qkv permute included)
        self.params = draft_serving_params(params, self.draft_layers)
        if mesh is not None:
            from ..models.gpt import shard_serving_params

            self.params = shard_serving_params(self.params, mesh, dcfg)
        self.chunk = int(chunk)
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.kv_quant = bool(kv_quant)
        self._on_launch = on_launch
        kv_dtype = dtype if dtype is not None else self.params["tok_emb"].dtype
        if num_pages is None:
            # the draft pool mirrors the main pool's TOKEN capacity (the
            # draft attends over the same contexts); it is "tiny" because
            # it holds draft_layers layers, not num_layers
            num_pages = self.max_batch * pages_needed(self.max_seq_len,
                                                      page_size)
        # a PRIVATE registry: the manager's kv_* gauge names would
        # otherwise collide with (and overwrite) the main pool's on the
        # predictor's shared registry
        self.cache = KVCacheManager(
            self.draft_layers, config.num_heads, config.head_dim,
            num_pages=num_pages, max_batch=self.max_batch,
            max_seq_len=self.max_seq_len, page_size=page_size,
            num_q_heads=config.num_heads, dtype=kv_dtype,
            quantize_kv=self.kv_quant, mesh=mesh,
            metrics=MetricsRegistry())
        self._catchup = build_draft_step(
            config, self.draft_layers, self.cache.page_size, self.chunk,
            use_kernel=use_kernel, kv_quant=self.kv_quant, mesh=mesh)
        # round 22: the k-step proposal chain is ONE fused jit
        # (models/gpt.py build_draft_chain) — a lax.scan over the chain
        # steps, so a speculative round costs ONE draft dispatch instead
        # of k. Chains build lazily per requested depth through the
        # process-wide jit cache (an adaptive-k backoff round runs a
        # shorter scan, never masked steps it didn't ask for); ``max_k``
        # (the predictor passes its spec_k) pre-builds the steady-state
        # geometry so construction-time validation fires loudly.
        self.max_k = int(max_k) if max_k else 0
        self._config = config
        self._use_kernel = use_kernel
        self._mesh = mesh
        if self.max_k:
            self._chain_fn(self.max_k)   # build-time validation fires HERE
        self._t_catchup = self.max_batch * self.chunk
        b = self.max_batch
        self._no_cow = jnp.full((b,), self.cache.num_pages, jnp.int32)
        self._zero_prev = jnp.zeros((b,), jnp.int32)
        self._zero_keys = jnp.zeros((b, 2), jnp.uint32)
        self._zero_f32 = jnp.zeros((b,), jnp.float32)
        self._zero_i32 = jnp.zeros((b,), jnp.int32)
        self._one_f32 = jnp.ones((b,), jnp.float32)
        self._np = np
        self._jnp = jnp
        # req_id -> {"slot": draft slot, "fed": [token ids written]},
        # insertion-ordered oldest-proposer-first (the eviction order)
        from collections import OrderedDict

        self._lanes: "OrderedDict[int, dict]" = OrderedDict()
        self.model_steps = 0          # draft jit launches (all geometries)

    # -- lifecycle ---------------------------------------------------------

    def release(self, req_id) -> None:
        """Drop a request's draft lane (terminal teardown — the predictor
        calls this wherever it drops the request's proposer)."""
        st = self._lanes.pop(req_id, None)
        if st is not None:
            self.cache.free(st["slot"])

    def _evict_one(self, keep: set) -> bool:
        """Free the oldest draft lane not in ``keep``."""
        for rid in list(self._lanes):
            if rid not in keep:
                self.release(rid)
                return True
        return False

    def _lane_for(self, req_id, ctx, keep: set):
        """The request's draft lane, admitted on first use. Returns None
        when the pool cannot hold this context even after evicting every
        other idle lane (the lane then proposes nothing this round)."""
        st = self._lanes.get(req_id)
        if st is not None:
            self._lanes.move_to_end(req_id)
            return st
        while True:
            hit = self.cache.admit_prefix(ctx, soft=True)
            if hit is not None:
                st = {"slot": hit[0], "fed": [], "rid": req_id}
                self._lanes[req_id] = st
                return st
            if not self._evict_one(keep):
                return None

    # -- the per-round proposal pass ---------------------------------------

    def _dispatch(self, fn, t, rows, q_lens, last_idx, emit, prev):
        """One draft-step launch over packed ``rows`` (list of
        (w, slot, tok, pos) with tok None for feedback rows)."""
        np, jnp = self._np, self._jnp
        cache = self.cache
        b = self.max_batch
        tok_ids = np.zeros((t,), np.int32)
        tok_slot = np.full((t,), -1, np.int32)
        tok_pos = np.zeros((t,), np.int32)
        feedback = np.zeros((t,), np.int32)
        for w, slot, tok, pos in rows:
            tok_slot[w] = slot
            tok_pos[w] = pos
            if tok is None:
                feedback[w] = 1
            else:
                tok_ids[w] = tok
        args = (self.params, jnp.asarray(tok_ids), jnp.asarray(tok_slot),
                jnp.asarray(tok_pos), jnp.asarray(q_lens),
                cache.seq_lens_device(), jnp.asarray(last_idx),
                jnp.asarray(feedback), prev, jnp.asarray(emit),
                self._zero_i32)
        pools = ((cache.k_pages, cache.v_pages, cache.k_scales,
                  cache.v_scales) if self.kv_quant
                 else (cache.k_pages, cache.v_pages))
        tail = (cache.page_table_device(), self._no_cow, self._no_cow,
                self._zero_keys, self._zero_f32, self._zero_i32,
                self._one_f32)
        res = fn(*args, *pools, *tail)
        cache.update_pages(*res[2:])
        self.model_steps += 1
        if self._on_launch is not None:
            self._on_launch()
        return res[0]                 # next_toks [b] (greedy argmax)

    def propose(self, lanes: dict) -> dict:
        """Draft for every lane in one batched pass.

        ``lanes``: ``{key: (req_id, context, k)}`` — ``context`` is the
        lane's VALUE-COMPLETE context (prompt + landed outputs; the
        scheduler reconciles in-flight tokens before proposing) and ``k``
        the already-clamped draft count (> 0). Returns ``{key: [ints]}``
        (a lane the draft pool cannot hold maps to ``[]``).
        """
        np = self._np
        cache = self.cache
        keep = {rid for rid, _, _ in lanes.values()}
        active = {}                    # key -> (st, ctx, k)
        for key, (rid, ctx, k) in lanes.items():
            st = self._lane_for(rid, ctx, keep)
            if st is None:
                continue
            # self-heal: roll the draft KV back to the longest prefix of
            # the CURRENT context it holds (capped at len-1: the chain
            # must feed the live last token itself)
            fed, limit = st["fed"], len(ctx) - 1
            p = 0
            while p < min(len(fed), limit) and fed[p] == ctx[p]:
                p += 1
            if len(fed) > p:
                cache.rollback(st["slot"], p)
                del fed[p:]
            active[key] = (st, ctx, int(k))
        # -- catch-up: replay context the pool does not hold yet ----------
        while True:
            rows = []
            q_lens = np.zeros((self.max_batch,), np.int32)
            last_idx = np.full((self.max_batch,), self._t_catchup, np.int32)
            emit = np.zeros((self.max_batch,), np.int32)
            w = 0
            drop = []
            for key, (st, ctx, k) in active.items():
                need = len(ctx) - 1 - len(st["fed"])
                if need <= 0:
                    continue
                n = min(self.chunk, need, self._t_catchup - w)
                if n <= 0:
                    continue
                if not self._ensure(st, len(st["fed"]) + n, keep):
                    drop.append(key)
                    continue
                base = len(st["fed"])
                for i in range(n):
                    rows.append((w + i, st["slot"], ctx[base + i],
                                 base + i))
                q_lens[st["slot"]] = n
                w += n
            for key in drop:
                st, _, _ = active.pop(key)
                self.release(st["rid"])
            if not rows:
                break
            self._dispatch(self._catchup, self._t_catchup, rows, q_lens,
                           last_idx, emit, self._zero_prev)
            for key, (st, ctx, k) in active.items():
                n = int(q_lens[st["slot"]])
                if n:
                    cache.advance(st["slot"], n)
                    st["fed"].extend(ctx[len(st["fed"]):len(st["fed"]) + n])
        if not active:
            return {key: [] for key in lanes}
        # -- the fused k-step chain: ONE dispatch for the whole round -----
        # (round 22: the per-step loop collapsed into build_draft_chain's
        # device-side lax.scan — intermediates never touch the host). The
        # page table is FIXED for the whole chain, so capacity is
        # pre-reserved here: a lane the pool cannot grow for clamps its
        # chain length down to what fits (0 = it sits the round out).
        k_max = max(k for _, _, k in active.values())
        b = self.max_batch
        first = np.zeros((b,), np.int32)
        steps = np.zeros((b,), np.int32)
        reach = {}                     # key -> chain steps the lane runs
        for key, (st, ctx, k) in active.items():
            L = len(ctx)
            s = int(k)
            while s > 0 and not self._ensure(st, L - 1 + s, keep):
                s -= 1
            reach[key] = s
            if s > 0:
                first[st["slot"]] = ctx[-1]
                steps[st["slot"]] = s
        if not any(reach.values()):
            return {key: [] for key in lanes}
        fn = self._chain_fn(k_max)
        jnp = self._jnp
        res = fn(self.params, jnp.asarray(first), jnp.asarray(steps),
                 cache.seq_lens_device(),
                 *((cache.k_pages, cache.v_pages, cache.k_scales,
                    cache.v_scales) if self.kv_quant
                   else (cache.k_pages, cache.v_pages)),
                 cache.page_table_device())
        cache.update_pages(*res[1:])
        self.model_steps += 1
        if self._on_launch is not None:
            self._on_launch()
        # ONE hard sync lands every lane's whole chain
        arr = np.asarray(res[0])                      # [b, k_build]
        drafts = {key: [] for key in lanes}
        for key, (st, ctx, k) in active.items():
            r = reach[key]
            if r <= 0:
                continue
            cache.advance(st["slot"], r)
            d = [int(arr[st["slot"], i]) for i in range(r)]
            drafts[key] = d
            # KV now holds ctx[-1] + the first r-1 drafts
            st["fed"].extend([ctx[-1]] + d[:r - 1])
        return drafts

    def _chain_fn(self, k: int):
        """The fused chain jit at geometry ``k`` — the round's actual
        max requested depth, so an adaptive-k backoff round never pays
        masked scan steps it didn't ask for. The process-wide cache in
        models/gpt.py bounds this to one executable per distinct depth
        (at most ``max_k`` of them; the constructor pre-builds the
        steady-state ``max_k`` geometry)."""
        from ..models.gpt import _draft_chain_fn

        return _draft_chain_fn(
            self._config, self.draft_layers, self.cache.page_size,
            int(k), self._use_kernel,
            kv_quant=self.kv_quant, mesh=self._mesh)

    def _ensure(self, st, new_len: int, keep: set) -> bool:
        """Grow a draft lane, evicting idle lanes under pressure — but
        never another lane proposing THIS round (``keep``)."""
        while not self.cache.ensure_capacity(st["slot"], new_len):
            if new_len > self.max_seq_len or not self._evict_one(
                    keep | {st["rid"]}):
                return False
        return True
