"""Fault-tolerant multi-replica serving fleet (round 18).

"Millions of users means MANY predictors" (ROADMAP item 1): everything
below one :class:`~paddle_tpu.inference.serving.ServingPredictor` is
production-grade — this module is the fleet layer above it. A
:class:`FleetRouter` fronts N predictor replicas (each possibly mesh-TP)
and makes the headline property true: **replica failure is a routing
event, not an outage**.

Routing (admission-time placement, no per-token hop):

- **Prefix affinity** — the prompt hashes through the SAME sha1 chain
  keys the prefix cache computes (``kv_cache.chain_key``; one key per
  full page, page i folding page i-1). The router keeps a chain-key ->
  replica map; a submission walks its keys DEEPEST-first and lands on
  the replica that already served the longest shared prefix — so
  repeated-system-prompt traffic hits warm pages instead of re-prefilling
  on a random replica. The map is only sound because independently
  constructed :class:`~paddle_tpu.inference.kv_cache.KVCacheManager`
  instances derive identical keys from identical tokens (locked by
  tests/test_prefix_cache.py).
- **Power-of-two-choices fallback** — no affinity hit: two seeded-random
  admittable candidates are drawn and the one with the LOWER load score
  wins (the classic d=2 balancer: near-best-of-N balance at O(1) probes).
  The score reads :meth:`ServingPredictor.healthz` — queue + lanes
  occupied, KV pool occupancy, in-flight ring depth, TTFT-p99 EMA — the
  round-17 load-signal surface built for exactly this consumer.
- **Health gating** — a replica admits only while HEALTHY and its
  :meth:`~ServingPredictor.admission_verdict` is ``None``. The per-tick
  health refresh marks a replica UNHEALTHY while it is stalled or its
  ``healthz()["snapshot_age_s"]`` stamp is stale (a stuck replica stops
  stamping; a merely quiet one, still driven, does not); recovery flips
  it back. DRAINING (``drain()``/``resume()``, the operator surface)
  finishes in-flight work but admits nothing. When no healthy replica
  can admit, submissions queue at the router (``_unrouted``) unless
  healthy replicas exist and ALL of them shed — then the submission
  sheds terminally (fleet-level backpressure, same ``shed_*`` codes).

Failover (the crash-consistent half):

- A replica that raises out of its step — or stalls past
  ``dead_stall_ticks`` — is declared DEAD. Its process state is treated
  as UNREADABLE (a real crash leaves nothing to inspect): the router
  migrates every non-terminal request assigned to it using only what it
  already RECEIVED — the fleet-side ``output_ids`` merged from step
  results. The re-admit feeds ``prompt + received_outputs`` as the new
  context (already-emitted tokens are deduplicated by construction:
  resume from ``len(output_ids)``), carries the remaining output budget,
  and passes the ORIGINAL ``submit_time`` through
  ``add_request(submit_time=)`` so the request's absolute deadline never
  restarts. Greedy continuations are token-identical to an uninterrupted
  run; tokens a dead replica had dispatched but never reported are
  simply regenerated — never double-emitted, because a DEAD replica is
  never stepped or flushed again. Failovers are bounded:
  ``max_failovers`` migrations, then a terminal ``replica_lost`` FAILED
  record. A DEAD slot respawns a fresh predictor after ``restart_ticks``
  (its pages are gone, so its affinity-map entries are purged — routed
  prefixes rebuild warmth organically).

Disaggregated prefill/decode (round 20, ``prefill_replicas > 0``):

- **Roles.** The first ``prefill_replicas`` slots run PREFILL-role
  replicas; the rest are DECODE-role. A fresh submission whose prompt
  spans at least one page lands on the least-loaded healthy prefill
  replica first (p2c scored on the healthz load signals + the
  sender-side ``transfer_backlog``), runs its prompt through the
  ordinary unified step as a 1-token request (prefill chunks + the
  first generated token), and its registered prompt pages then STREAM
  to the decode replica the prefix-affinity map names — the replica
  that will keep serving that prefix — over the
  ``inference/kv_transfer.py`` wire: checksummed chain-key-addressed
  frames (int8-KV payloads ride with their fp32 scale planes; partial
  tails included), a bounded in-flight window, per-frame timeout +
  exponential backoff + bounded retries, idempotent receive. The
  decode admission's ``admit_prefix`` walk pins the imported pages
  exactly like locally-prefilled ones, so the decode replica never
  re-runs the prompt; its seeded sample stream continues bit-identically
  through the handoff (``add_request(sample_offset=)``).
- **Graceful degradation** — the headline robustness property: if no
  healthy prefill replica exists, the transfer exhausts its retries, a
  checksum fails terminally, the receiver has no free page, or either
  endpoint replica dies mid-stream, the request falls back to
  COLOCATED prefill on the decode fleet (today's path) — counted
  (``fleet_prefill_fallbacks``), never failed, and never charged
  against the failover budget: disaggregation existing must never cost
  a request its life. A FAILED transfer unwinds every page it imported,
  so the decode-side accounting (free lists, refcounts, LRU, scale
  planes) is indistinguishable from a colocated run after ANY fault.
- With ``prefill_replicas=0`` (the default) every replica is
  colocated-role and the router is bit-identical to round 18.

The chaos gate (tests/test_fleet_serving.py) extends round 17's
discipline to the fleet: a >= 1k-tick multi-replica churn with the
``replica_crash`` / ``replica_stall`` seams armed
(``inference/faults.py``) — and, disaggregated, the ``transfer_drop``
/ ``transfer_corrupt`` wire seams on top — where after EVERY tick the
fleet-wide invariant holds — submitted == finished + failed + live,
every request ends terminal exactly once, no token emitted twice, no
request lost, every FINISHED stream bit-identical to a fault-free
COLOCATED mirror — and with faults disarmed a single-replica fleet is
bit-identical to a bare ``ServingPredictor`` (and a disaggregated
fleet's emissions bit-identical, greedy and seeded-sampled, to the
colocated fleet's).
"""
from __future__ import annotations

from collections import deque

import numpy as np

from ..observability import FleetInstruments, monotonic, span
from .faults import fault_point
from .kv_cache import prompt_chain_keys
from .kv_transfer import DONE as T_DONE
from .kv_transfer import SENDING as T_SENDING
from .kv_transfer import KVPageTransfer, TransferConfig
from .serving import (FAILED, FINISHED, RUNNING, WAITING, ServingPredictor,
                      deadline_passed, stream_done)

#: replica lifecycle states (the fleet-side state machine; the
#: per-request one stays serving.py's WAITING/RUNNING/FINISHED/FAILED)
HEALTHY, UNHEALTHY, DRAINING, DEAD = ("healthy", "unhealthy", "draining",
                                      "dead")

__all__ = ["FleetRequest", "FleetRouter", "HEALTHY", "UNHEALTHY",
           "DRAINING", "DEAD"]


class FleetRequest:
    """The router-side request handle: fleet identity, the merged output
    stream (built ONLY from step/flush results the router actually
    received — the crash-consistency ledger), and the failover count.
    ``state`` follows serving.py's request states: WAITING while queued
    at the router, RUNNING once placed on a replica, then terminal
    FINISHED / FAILED (``error = {"code", "message"}``)."""

    _next_id = [0]

    def __init__(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
                 temperature=0.0, top_k=0, top_p=1.0, seed=None,
                 deadline_s=None):
        self.fleet_id = FleetRequest._next_id[0]
        FleetRequest._next_id[0] += 1
        self.prompt_ids = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not self.prompt_ids:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = seed
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        if self.deadline_s is not None and self.deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
        # the absolute-deadline anchor: every re-admit passes this stamp
        # through add_request(submit_time=) so the TTL never restarts
        self.submit_time = monotonic()
        self.output_ids: list[int] = []
        self.state = WAITING
        self.error: dict | None = None
        self.truncated = False
        self.replica_id: int | None = None   # current placement
        self.failover_count = 0
        self._inner = None                   # current inner Request
        # round 20 (disaggregation): the request's pipeline phase —
        # ``None`` on a colocated fleet (and for sub-page prompts that
        # never disaggregate), else "prefill" (running on a
        # prefill-role replica) -> "transfer" (KV pages streaming) ->
        # "decode" (on a decode replica; also the forced state after a
        # fallback — a degraded request never re-enters the prefill
        # stage). ``first_token_time`` stamps the first RECEIVED token
        # (the fleet-side TTFT the disagg bench leg gates).
        self.phase: str | None = None
        self.decode_rid: int | None = None
        self._transfer = None
        self.first_token_time: float | None = None
        # True once a prefill-role replica actually accepted this
        # request's prefill stage: from then on the fleet has spent
        # work on it, so later routing failures queue it instead of
        # shedding it (a submit-time degradation spent nothing and
        # stays shed-able — colocated-fleet parity under flood)
        self.prefill_spent = False
        # round 21: one cross-replica prefix pull per request, ever —
        # a failed pull (or a failover after a successful one) falls
        # back to colocated recompute instead of re-chasing pages
        # around a churning fleet
        self.pull_attempted = False

    @property
    def ttft(self) -> float | None:
        """Seconds from fleet submission to the first received token."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def done(self) -> bool:
        """Budget/eos satisfied by the RECEIVED stream — what failover
        consults before spending a re-admit on a complete request. The
        stop rule is serving.py's ``stream_done`` (one spelling: the
        dedup here must agree with the predictor's emission-drop rule)."""
        if self.truncated:
            return True
        return stream_done(self.output_ids, self.max_new_tokens,
                           self.eos_token_id)

    def past_deadline(self, now=None) -> bool:
        return deadline_passed(self.submit_time, self.deadline_s, now)


class _Replica:
    """One replica slot: the live predictor (``None`` while DEAD — a
    crashed process is unreadable), its fleet state, the inner-request
    -> fleet-request map, and the stall/restart tick counters."""

    __slots__ = ("rid", "sp", "state", "by_inner", "stall_ticks",
                 "stalled_for", "restart_in")

    def __init__(self, rid: int, sp: ServingPredictor):
        self.rid = rid
        self.sp = sp
        self.state = HEALTHY
        self.by_inner: dict[int, FleetRequest] = {}
        self.stall_ticks = 0     # ticks of hang still to serve
        self.stalled_for = 0     # consecutive ticks already hung
        self.restart_in = 0      # DEAD cooldown until respawn


class FleetRouter:
    """N ``ServingPredictor`` replicas behind one admission surface.

    ``submit()`` places a request (prefix-affinity, then
    power-of-two-choices on the healthz load signals, health-gated);
    ``tick()`` drives one fleet scheduler round — every live replica
    steps once, emissions merge into the fleet-side streams, terminal
    inner states sweep out, crashed/stalled replicas fail over;
    ``flush()`` drains the live replicas' in-flight rings. Replica
    construction kwargs forward to ``ServingPredictor`` via
    ``replica_kw`` (every replica is built identically — the fleet's
    page geometry must agree for the affinity keys to mean the same
    pages everywhere).
    """

    def __init__(self, model, num_replicas=2, *, seed=0, max_failovers=2,
                 stale_after_s=5.0, dead_stall_ticks=4, restart_ticks=1,
                 max_affinity_entries=1 << 16, metrics=None,
                 replica_kw=None, prefill_replicas=0, transfer=None,
                 min_transfer_tokens=None, prefix_pulls=False):
        self.num_replicas = int(num_replicas)
        if self.num_replicas < 1:
            raise ValueError(f"num_replicas must be >= 1, "
                             f"got {num_replicas}")
        # round 20: disaggregation — the first ``prefill_replicas``
        # slots take the prefill role; at least one decode replica must
        # remain (the decode fleet IS the fallback path, and a fleet
        # that can only prefill can never finish a request)
        self.prefill_replicas = int(prefill_replicas)
        if not 0 <= self.prefill_replicas < self.num_replicas:
            raise ValueError(
                f"prefill_replicas must be in [0, num_replicas), got "
                f"{prefill_replicas} of {num_replicas} (at least one "
                "decode replica must remain — it is the fallback path)")
        if transfer is not None and not isinstance(transfer,
                                                   TransferConfig):
            raise ValueError(f"transfer must be a TransferConfig or "
                             f"None, got {type(transfer).__name__}")
        self.transfer_cfg = (transfer if transfer is not None
                             else TransferConfig())
        # round 21: fleet-global tiered prefixes — a prefix miss on the
        # routed replica that hits on another replica (its pool OR its
        # host tier) becomes a KV-page pull over the transfer wire
        # instead of a recompute. Opt-in: pulls add a transfer phase in
        # front of the admission, so latency-sensitive small fleets can
        # keep the pre-21 place-and-recompute behavior.
        self.prefix_pulls = bool(prefix_pulls)
        cfg = getattr(model, "config", None)
        kind = ("a latent (MLA) cache" if getattr(cfg, "kv_lora_rank", 0)
                else "a looped stack" if getattr(cfg, "total_ut_steps", 0)
                else None)
        if kind and (self.prefill_replicas or self.prefix_pulls):
            raise NotImplementedError(
                "kv_transfer (prefill_replicas, prefix_pulls) is not "
                f"supported for {kind} yet: its pages have no payload "
                "format")
        self.max_failovers = int(max_failovers)
        if self.max_failovers < 0:
            raise ValueError(f"max_failovers must be >= 0, "
                             f"got {max_failovers}")
        self.stale_after_s = float(stale_after_s)
        if self.stale_after_s <= 0:
            # a non-positive threshold pins every replica UNHEALTHY
            # forever (snapshot_age_s >= 0 always) — a config typo must
            # fail loudly, not as a total routing outage
            raise ValueError(f"stale_after_s must be > 0, "
                             f"got {stale_after_s}")
        self.dead_stall_ticks = int(dead_stall_ticks)
        if self.dead_stall_ticks < 1:
            raise ValueError(f"dead_stall_ticks must be >= 1, "
                             f"got {dead_stall_ticks}")
        self.restart_ticks = max(1, int(restart_ticks))
        self._model = model
        self._replica_kw = dict(replica_kw or {})
        if "replica_id" in self._replica_kw:
            raise ValueError("replica_id is assigned by the router")
        if "role" in self._replica_kw:
            raise ValueError("role is assigned by the router "
                             "(prefill_replicas= decides the split)")
        # routing randomness (the two p2c probes) is seeded: a fleet run
        # is replayable from (seed, submission order, fault plan)
        self._rng = np.random.RandomState(seed)
        self.inst = FleetInstruments(metrics)
        if not self.inst.registry.enabled:
            # the fleet counters BACK fleet_accounting()/telemetry()
            # (the chaos gate's partition invariant and the bench line):
            # a disabled registry would silently report zeros — fail
            # loud, same contract as ServingPredictor's registry check
            raise ValueError(
                "FleetRouter requires an enabled metrics registry; "
                "the one passed is disabled")
        self.replicas = [_Replica(rid, self._spawn(rid))
                         for rid in range(self.num_replicas)]
        self.page_size = self.replicas[0].sp.cache.page_size
        self.max_seq_len = self.replicas[0].sp.max_seq_len
        # prompts below one page have no chain-key identity — nothing
        # addressable to transfer; they serve colocated even when
        # disaggregated (min_transfer_tokens may raise the bar further)
        self.min_transfer_tokens = max(
            self.page_size, int(min_transfer_tokens or 0))
        #: live KV-page streams: (transfer, fleet request, affinity hit)
        self._transfers: list[tuple] = []
        #: chain key -> replica id (the prefix-affinity map): insertion-
        #: ordered with re-registration refreshing recency, bounded by
        #: ``max_affinity_entries`` (oldest evicted — a cold entry only
        #: costs a p2c placement, never correctness), purged per replica
        #: on its death
        self._affinity: dict[bytes, int] = {}
        self.max_affinity_entries = int(max_affinity_entries)
        #: submissions with no admittable replica right now — retried at
        #: the top of every tick, deadline-swept at the router
        self._unrouted: deque[FleetRequest] = deque()
        #: fleet_id -> non-terminal request; terminal requests leave the
        #: router's working set (the caller keeps its handle, counters
        #: keep the history) — a long-lived router must not grow per
        #: request served
        self._live: dict[int, FleetRequest] = {}
        self.ticks = 0

    # -- construction / lifecycle ------------------------------------------

    def role_for(self, rid: int) -> str:
        """The fleet role of slot ``rid`` — a property of the SLOT, not
        the predictor instance, so a supervisor restart respawns the
        same role into the same slot."""
        if not self.prefill_replicas:
            return "colocated"
        return "prefill" if rid < self.prefill_replicas else "decode"

    def _spawn(self, rid: int) -> ServingPredictor:
        return ServingPredictor(self._model, replica_id=rid,
                                role=self.role_for(rid),
                                **self._replica_kw)

    def _decode_reps(self) -> list[_Replica]:
        """The replicas user submissions decode on (every replica when
        colocated) — the ONLY replicas the affinity map and the p2c
        fallback ever name."""
        return [r for r in self.replicas
                if self.role_for(r.rid) != "prefill"]

    def _prefill_reps(self) -> list[_Replica]:
        return [r for r in self.replicas
                if self.role_for(r.rid) == "prefill"]

    def _rep(self, rid: int) -> _Replica:
        for rep in self.replicas:
            if rep.rid == rid:
                return rep
        raise KeyError(f"no replica {rid}")

    def drain(self, rid: int) -> None:
        """Operator drain: the replica finishes its in-flight work but
        admits nothing until :meth:`resume`. DEAD replicas stay dead."""
        rep = self._rep(rid)
        if rep.state != DEAD:
            rep.state = DRAINING

    def resume(self, rid: int) -> None:
        rep = self._rep(rid)
        if rep.state == DRAINING:
            rep.state = HEALTHY

    def kill_replica(self, rid: int, reason="operator_kill") -> None:
        """Declare a replica lost NOW (the operator/chaos surface — the
        ``replica_crash`` fault seam lands on the same path)."""
        rep = self._rep(rid)
        if rep.state != DEAD:
            self._crash(rep, RuntimeError(
                f"replica {rid} declared lost: {reason}"))

    # -- routing ------------------------------------------------------------

    def _admittable(self, rep: _Replica) -> bool:
        return (rep.state == HEALTHY and rep.stall_ticks == 0
                and rep.sp.admission_verdict() is None)

    def _load_score(self, rep: _Replica) -> float:
        """The p2c comparison key, off the healthz snapshot: occupied
        lanes + backlog dominate, pool occupancy breaks near-ties, the
        in-flight ring depth and the TTFT-p99 EMA push away from a
        replica that is already running hot."""
        hz = rep.sp.healthz()
        return (hz["waiting"] + hz["running"] + hz["pool_occupancy"]
                + 0.25 * hz["inflight_steps"]
                + 0.001 * hz["ttft_p99_ema_ms"])

    def _affinity_walk(self, keys, ok, exclude=()):
        """THE deepest-chain-key-wins affinity walk (longest shared
        prefix decides the replica), shared by decode placement and
        transfer-destination picks so the two can never diverge on
        affinity semantics; ``ok`` is the caller's per-replica
        eligibility predicate. None on no eligible registered key."""
        for k in reversed(keys):
            rid = self._affinity.get(k)
            if rid is not None and rid not in exclude:
                rep = self._rep(rid)
                if ok(rep):
                    return rep
        return None

    def _pick_replica(self, keys, exclude=()):
        """(replica, affinity_hit) for one placement given the context's
        chain keys; replica is None when nothing admittable exists.
        Affinity first — DEEPEST registered chain key wins (longest
        shared prefix) — then two seeded candidates scored by load."""
        rep = self._affinity_walk(keys, self._admittable, exclude)
        if rep is not None:
            return rep, True
        cands = [r for r in self._decode_reps()
                 if r.rid not in exclude and self._admittable(r)]
        return self._p2c(cands, self._load_score), False

    def _p2c(self, cands, score):
        """THE power-of-two-choices draw (two seeded candidates, lower
        score wins, rid tie-break), shared by decode and prefill picks
        so the sampling policy can never diverge. None on no
        candidates."""
        if not cands:
            return None
        if len(cands) > 2:
            i, j = self._rng.choice(len(cands), size=2, replace=False)
            cands = [cands[int(i)], cands[int(j)]]
        return min(cands, key=lambda r: (score(r), r.rid))

    def _healthy_verdicts(self):
        """The shed decision's evidence: the admission verdicts of every
        HEALTHY, un-stalled DECODE replica (None entries mean 'would
        admit') — prefill replicas never hold user submissions, so
        their SLOs never decide a fleet shed."""
        return [r.sp.admission_verdict() for r in self._decode_reps()
                if r.state == HEALTHY and r.stall_ticks == 0]

    def _pick_prefill(self):
        """The least-loaded healthy prefill replica (p2c like the
        decode fallback, with the sender-side transfer backlog as an
        extra penalty — a replica still streaming pages out is a worse
        place for new prefill work); None when no prefill replica can
        admit (the colocated-fallback cue)."""
        cands = [r for r in self._prefill_reps() if self._admittable(r)]
        return self._p2c(cands, lambda r: (
            self._load_score(r) + 0.1 * r.sp.transfer_backlog))

    def _try_route(self, freq: FleetRequest) -> bool:
        """Place one request (initial submit or failover re-admit).
        Returns True when it landed on a replica; False leaves it either
        queued at the router (no healthy capacity — transient) or
        terminally shed (healthy replicas exist but every one of them
        sheds — fleet backpressure, not an outage)."""
        # round 20: a fresh page-spanning submission on a disaggregated
        # fleet prefills on a dedicated prefill replica first; if no
        # prefill replica can admit RIGHT NOW, it degrades to colocated
        # prefill on the decode fleet immediately (counted as a
        # fallback) — disaggregation may never delay or fail a request
        if self._wants_disagg(freq):
            prep = self._pick_prefill()
            if prep is not None and self._admit_prefill_on(freq, prep):
                return True
            freq.phase = "decode"
            self.inst.prefill_fallbacks.inc()
        # the context (and so its chain keys) is fixed for the whole
        # placement attempt: hash once, not per race-retry iteration
        keys = prompt_chain_keys(freq.prompt_ids + freq.output_ids,
                                 self.page_size)
        exclude: set[int] = set()
        while True:
            rep, hit = self._pick_replica(keys, exclude)
            if rep is None:
                verdicts = self._healthy_verdicts()
                # SLO shedding is backpressure on NEW ARRIVALS: a
                # request the fleet already accepted (a failover victim,
                # anything with received tokens, or a round-20 fallback
                # the fleet already spent PREFILL work on) queues
                # through the transient instead — discarding accepted
                # in-flight work because a crash landed during a
                # backlog spike would turn one replica's failure into
                # request loss. A submit-time disagg degradation spent
                # nothing yet and stays shed-able (colocated parity —
                # the unrouted queue must not grow unboundedly under a
                # flood just because prefill capacity was busy).
                fresh = (freq.failover_count == 0 and not freq.output_ids
                         and not freq.prefill_spent)
                if (fresh and verdicts
                        and all(v is not None for v in verdicts)):
                    self.inst.shed.inc()
                    self._fail(freq, "shed_" + verdicts[0],
                               f"every healthy replica sheds "
                               f"({verdicts[0]})")
                else:
                    freq.state = WAITING
                    self._unrouted.append(freq)
                return False
            # round 21: before a miss recomputes, try pulling the
            # prefix's pages off the replica that owns them (the
            # affinity map knows) — the request parks in a transfer
            # phase and admits where the pages land
            if not hit and self._maybe_pull(freq, rep, keys):
                return True
            if self._admit_on(freq, rep, keys, hit):
                return True
            # the verdict raced between the gate and the admission (the
            # inner SLO shed it): try the other replicas before queueing
            exclude.add(rep.rid)

    def _wants_disagg(self, freq: FleetRequest) -> bool:
        """Is this placement the prefill stage of a disaggregated
        request? Only a FRESH first placement qualifies: failover
        victims, fallbacks (phase forced to "decode") and sub-page
        prompts (no chain-key identity to address frames by) all serve
        colocated."""
        return (self.prefill_replicas > 0 and freq.phase is None
                and not freq.output_ids
                and len(freq.prompt_ids) >= self.min_transfer_tokens)

    def _admit_prefill_on(self, freq: FleetRequest, rep: _Replica) -> bool:
        """Place the PREFILL stage: a 1-token inner request (prefill
        chunks + the first generated token) on a prefill-role replica.
        The handoff to the decode fleet happens when it finishes
        (:meth:`_handoff`); prefill placements never register affinity
        entries — the map names only replicas that will keep serving
        the prefix."""
        inner = rep.sp.add_request(
            freq.prompt_ids, 1, freq.eos_token_id,
            temperature=freq.temperature, top_k=freq.top_k,
            top_p=freq.top_p, seed=freq.seed,
            deadline_s=freq.deadline_s, submit_time=freq.submit_time)
        if inner.state == FAILED:
            return False
        freq._inner = inner
        freq.replica_id = rep.rid
        freq.state = RUNNING
        freq.phase = "prefill"
        freq.prefill_spent = True
        rep.by_inner[inner.req_id] = freq
        self.inst.prefill_routed.inc()
        return True

    def _admit_on(self, freq: FleetRequest, rep: _Replica, keys,
                  hit: bool) -> bool:
        remaining = freq.max_new_tokens - len(freq.output_ids)
        inner = rep.sp.add_request(
            freq.prompt_ids + freq.output_ids, remaining,
            freq.eos_token_id, temperature=freq.temperature,
            top_k=freq.top_k, top_p=freq.top_p, seed=freq.seed,
            deadline_s=freq.deadline_s, submit_time=freq.submit_time,
            # received tokens ride the new context as prompt: the
            # sample-key fold continues at the received count, so a
            # seeded stream crosses failover AND the disaggregated
            # handoff bit-identically (round 20)
            sample_offset=len(freq.output_ids))
        if inner.state == FAILED:
            return False
        freq._inner = inner
        freq.replica_id = rep.rid
        freq.state = RUNNING
        if freq.phase is not None:
            freq.phase = "decode"
        rep.by_inner[inner.req_id] = freq
        self.inst.routed.inc()
        if hit:
            self.inst.affinity_hits.inc()
        for k in keys:
            if k in self._affinity:
                del self._affinity[k]        # refresh recency
            elif len(self._affinity) >= self.max_affinity_entries:
                self._affinity.pop(next(iter(self._affinity)))
            self._affinity[k] = rep.rid
        return True

    @property
    def affinity_hit_rate(self) -> float:
        return self.inst.affinity_hit_rate

    # -- submission ---------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
               temperature=0.0, top_k=0, top_p=1.0, seed=None,
               deadline_s=None) -> FleetRequest:
        """Admit one request into the fleet. Returns the fleet-side
        handle; a terminal-FAILED return means the fleet shed it (every
        healthy replica's SLO said no)."""
        freq = FleetRequest(prompt_ids, max_new_tokens, eos_token_id,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, seed=seed, deadline_s=deadline_s)
        # validate against the fleet-wide ceiling BEFORE any accounting:
        # a caller error must raise HERE (same contract as add_request),
        # never later out of tick() when a deferred route finally lands
        # on a replica — and never leave a phantom live request behind
        if len(freq.prompt_ids) > self.max_seq_len:
            raise ValueError(
                f"prompt of {len(freq.prompt_ids)} tokens exceeds "
                f"max_seq_len {self.max_seq_len}")
        self._live[freq.fleet_id] = freq
        self.inst.submitted.inc()
        if self._unrouted:
            # requests are already queued at the router: a new arrival
            # goes BEHIND them (FIFO — routing it now would let it claim
            # capacity freed since the last tick ahead of older work)
            freq.state = WAITING
            self._unrouted.append(freq)
        else:
            self._try_route(freq)
        return freq

    # -- terminal paths -----------------------------------------------------

    def _finish(self, freq: FleetRequest) -> None:
        freq.state = FINISHED
        freq.replica_id = None
        freq._inner = None
        freq._transfer = None
        self._live.pop(freq.fleet_id, None)
        self.inst.finished.inc()

    def _fail(self, freq: FleetRequest, code: str, message) -> None:
        freq.state = FAILED
        freq.error = {"code": code, "message": str(message)[:300]}
        freq.replica_id = None
        freq._inner = None
        freq._transfer = None
        self._live.pop(freq.fleet_id, None)
        self.inst.failed.inc()
        self.inst.fail_reasons.labels(reason=code).inc()

    # -- failure domain -----------------------------------------------------

    def _crash(self, rep: _Replica, exc) -> None:
        """Declare ``rep`` lost: its process state is unreadable from
        here on (never stepped, never flushed — nothing it had in flight
        can ever be double-reported), its affinity entries are purged
        (the pages died with it), and every non-terminal request it held
        migrates using only the fleet-side received streams."""
        self.inst.crashes.inc()
        rep.state = DEAD
        rep.sp = None
        rep.stall_ticks = 0
        rep.stalled_for = 0
        rep.restart_in = self.restart_ticks
        self._affinity = {k: r for k, r in self._affinity.items()
                          if r != rep.rid}
        victims = sorted(rep.by_inner.values(), key=lambda f: f.fleet_id)
        rep.by_inner = {}
        for freq in victims:
            if freq.state in (FINISHED, FAILED):
                continue
            if freq.phase == "prefill":
                # round 20: losing the prefill replica mid-prompt only
                # loses PREFILL work — the decode path never started.
                # Colocated fallback owns it, and it never burns the
                # failover budget (disaggregation existing must never
                # cost a request its bounded migrations)
                self._fallback(freq, "prefill replica lost mid-stream")
                continue
            self._failover(freq, exc)
        # transfers whose endpoints died abort on their next drive (the
        # replica-bound cache accessors read None for a DEAD slot) —
        # nothing to do here, and nothing of the dead pool is ever read

    def _failover(self, freq: FleetRequest, exc) -> None:
        """Migrate one request off a lost replica: resume from the
        received ``len(output_ids)``, original deadline carried, bounded
        by ``max_failovers`` before a terminal ``replica_lost``."""
        freq._inner = None
        freq.replica_id = None
        if freq.done:
            # the received stream already satisfies the contract: the
            # lost replica only owed us its retirement bookkeeping
            self._finish(freq)
            return
        freq.failover_count += 1
        if freq.failover_count > self.max_failovers:
            self._fail(freq, "replica_lost",
                       f"lost its replica {freq.failover_count} times "
                       f"({len(freq.output_ids)} tokens received); "
                       f"last: {exc!r}")
            return
        # counted only when a migration actually happens (a finished-in-
        # place or bound-exhausted victim is not a migration)
        self.inst.failovers.inc()
        self._try_route(freq)

    def _restart(self, rep: _Replica) -> None:
        """A fresh predictor into a DEAD slot (the supervisor restarting
        the pod): empty pools, same geometry, same replica id. The whole
        wrapper is replaced — `_Replica.__init__` is the one place that
        knows a fresh replica's state."""
        self.replicas[self.replicas.index(rep)] = _Replica(
            rep.rid, self._spawn(rep.rid))
        self.inst.restarts.inc()

    # -- round 20: the prefill -> decode handoff ----------------------------

    def _cache_fn(self, rep: _Replica):
        """A crash-consistent accessor for ``rep``'s cache: reads None
        once the slot is DEAD or the wrapper was replaced by a
        supervisor restart — a transfer must never read a dead pool,
        and a restart's FRESH cache must never be mistaken for it."""
        def fn():
            if rep.state == DEAD or rep.sp is None \
                    or rep not in self.replicas:
                return None
            return rep.sp.cache
        return fn

    def _pick_transfer_dst(self, freq: FleetRequest):
        """The decode replica the pages stream TO: the affinity map
        first (the replica that will keep serving this prefix), else
        the least-loaded LIVE decode replica. Deliberately NOT gated on
        the admission verdict — a transient queue-full must not abandon
        a transfer (the pages land, the decode admission rides the
        normal unrouted backpressure afterwards); only DEAD/DRAINING
        replicas are off the table. None only when every decode replica
        is dead/draining."""
        def live(r):
            return r.state not in (DEAD, DRAINING) and r.sp is not None

        keys = prompt_chain_keys(freq.prompt_ids + freq.output_ids,
                                 self.page_size)
        rep = self._affinity_walk(keys, live)
        if rep is not None:
            return rep, True
        cands = [r for r in self._decode_reps() if live(r)]
        if not cands:
            return None, False
        return min(cands, key=lambda r: (self._load_score(r), r.rid)), False

    def _handoff(self, freq: FleetRequest, rep: _Replica) -> None:
        """The prefill stage finished: export the prompt's registered
        pages off the prefill replica and stream them to the decode
        replica the affinity map names. Every unhappy path here is a
        FALLBACK, never a failure."""
        freq._inner = None
        freq.replica_id = None
        if freq.done:
            # budget 1 (or eos on the first token): the received stream
            # already satisfies the contract — nothing to hand off
            self._finish(freq)
            return
        records = (rep.sp.cache.prefix_page_records(freq.prompt_ids)
                   if rep.sp is not None else [])
        if not records:
            self._fallback(freq, "no transferable pages registered on "
                                 "the prefill replica")
            return
        dst, hit = self._pick_transfer_dst(freq)
        if dst is None:
            self._fallback(freq, "no live decode replica at handoff")
            return
        # started counts BEFORE construction so a transfer that fails
        # to open (unreadable source) keeps started >= completed+failed
        self.inst.transfers_started.inc()
        t = KVPageTransfer(
            records, self._cache_fn(rep), self._cache_fn(dst),
            config=self.transfer_cfg, instruments=self.inst,
            src_rid=rep.rid, dst_rid=dst.rid)
        if t.state != T_SENDING:
            self._fallback(freq, t.failure or "transfer failed to open")
            return
        freq.phase = "transfer"
        freq.decode_rid = dst.rid
        freq._transfer = t
        self._transfers.append((t, freq, hit, "handoff"))

    def _maybe_pull(self, freq: FleetRequest, dst: _Replica,
                    keys) -> bool:
        """Round 21: the routed replica ``dst`` misses this context's
        prefix, but the affinity map names another replica that owns it
        — open a KV-page pull over the transfer wire instead of
        recomputing. The source's export walk is restore-aware, so a
        prefix that slid into the OWNER's host tier still serves the
        pull. One attempt per request; every unhappy path degrades to
        the ordinary recompute placement (counted, never failed).
        Returns True when the request parked in the transfer phase."""
        if not self.prefix_pulls or freq.pull_attempted or not keys:
            return False
        ctx = freq.prompt_ids + freq.output_ids
        if len(ctx) < self.min_transfer_tokens:
            return False

        def owns(r):
            # DRAINING replicas are ideal pull sources (their warm
            # prefixes are about to be lost); only a DEAD replica's
            # pool is unreadable
            return (r.state != DEAD and r.sp is not None
                    and r.rid != dst.rid)

        src = self._affinity_walk(keys, owns)
        if src is None:
            return False
        records = src.sp.cache.prefix_page_records(ctx)
        if not records \
                or sum(r[2] for r in records) < self.min_transfer_tokens:
            return False
        # make room on the destination BEFORE opening the stream: the
        # import landing zone never evicts (the locked pressure
        # contract), so a saturated pool must shed its coldest zero-ref
        # pages down the eviction ladder first — if the room is not
        # there, recompute instead of opening a doomed transfer
        if not dst.sp.cache.reserve_import_room(len(records)):
            return False
        freq.pull_attempted = True
        # started counts BEFORE construction (same contract as
        # _handoff: started >= completed + failed always holds)
        self.inst.transfers_started.inc()
        self.inst.pulls_started.inc()
        t = KVPageTransfer(
            records, self._cache_fn(src), self._cache_fn(dst),
            config=self.transfer_cfg, instruments=self.inst,
            src_rid=src.rid, dst_rid=dst.rid)
        if t.state != T_SENDING:
            self.inst.pull_fallbacks.inc()
            return False                 # admit normally: recompute
        freq.phase = "transfer"
        freq.state = RUNNING
        freq.decode_rid = dst.rid
        freq._transfer = t
        self._transfers.append((t, freq, False, "pull"))
        return True

    def _complete_handoff(self, freq: FleetRequest, hit: bool) -> None:
        """Every page landed: admit the decode stage where the pages
        now live. If the pinned destination became unadmittable while
        the pages streamed, normal decode routing owns the request —
        the imported pages stay registered, so a later same-prefix
        admission still hits them."""
        freq._transfer = None
        freq.phase = "decode"
        rep = self._rep(freq.decode_rid)
        keys = prompt_chain_keys(freq.prompt_ids + freq.output_ids,
                                 self.page_size)
        if (rep.state != DEAD and rep.sp is not None
                and self._admittable(rep)
                and self._admit_on(freq, rep, keys, hit)):
            return
        self._try_route(freq)

    def _fallback(self, freq: FleetRequest, why: str) -> None:
        """Graceful degradation — the round-20 headline: the request
        serves COLOCATED on the decode fleet (today's path), counted
        but never failed and never charged a failover. ``why`` is
        telemetry-only: degradation is invisible to the caller beyond
        latency."""
        if freq.state in (FINISHED, FAILED):
            return          # racing a terminal request is not a degradation
        freq._transfer = None
        freq._inner = None
        freq.replica_id = None
        freq.phase = "decode"
        self.inst.prefill_fallbacks.inc()
        if freq.done:
            self._finish(freq)
            return
        self._try_route(freq)

    def _pull_fallback(self, freq: FleetRequest, why: str) -> None:
        """A cross-replica pull died on the wire: re-route the request
        for ordinary colocated recompute. Mirrors :meth:`_fallback` but
        charges the round-21 pull counter, NOT ``prefill_fallbacks`` —
        the disagg bench's fault-free-fallbacks-stay-zero gate must not
        see pull weather. ``why`` is telemetry-only."""
        if freq.state in (FINISHED, FAILED):
            return
        freq._transfer = None
        freq._inner = None
        freq.replica_id = None
        freq.phase = "decode"
        self.inst.pull_fallbacks.inc()
        if freq.done:
            self._finish(freq)
            return
        self._try_route(freq)

    def _drive_transfers(self) -> None:
        """One tick of wire work for every live transfer (prefill
        handoffs AND round-21 prefix pulls), plus the transfer-phase
        deadline sweep (a request streaming its pages is on no replica
        — nobody else's TTL sweep covers it) and the sender-side
        backlog stamps the healthz surface reads."""
        if self._transfers:
            now = monotonic()
            live = []
            for t, freq, hit, kind in self._transfers:
                if freq.state in (FINISHED, FAILED):
                    t.abort("fleet request terminal")
                    continue
                if freq.past_deadline(now):
                    t.abort("deadline exceeded mid-transfer")
                    self.inst.deadline_misses.inc()
                    self._fail(freq, "deadline_exceeded",
                               f"transfer-phase request past its "
                               f"{freq.deadline_s}s deadline")
                    continue
                state = t.tick()
                if state == T_SENDING:
                    live.append((t, freq, hit, kind))
                elif state == T_DONE:
                    if kind == "pull":
                        self.inst.pulls_completed.inc()
                    self._complete_handoff(freq, hit)
                elif kind == "pull":
                    self._pull_fallback(freq,
                                        t.failure or "pull failed")
                else:
                    self._fallback(freq, t.failure or "transfer failed")
            self._transfers = live
        backlog: dict[int, int] = {}
        for t, *_ in self._transfers:
            backlog[t.src_rid] = backlog.get(t.src_rid, 0) + t.backlog
        for rep in self._prefill_reps():
            if rep.sp is not None:
                rep.sp.transfer_backlog = backlog.get(rep.rid, 0)
        self.inst.transfer_backlog.set(sum(backlog.values()))

    # -- the tick -----------------------------------------------------------

    def _step_replica(self, rep: _Replica, produced: dict) -> None:
        """One replica's scheduler round inside the fleet tick, with the
        round-18 fault seams in front of it. A stalled replica makes no
        progress (its snapshot goes stale; past ``dead_stall_ticks`` the
        router escalates to a crash); a crashed one fails over."""
        if rep.stall_ticks > 0:
            rep.stall_ticks -= 1
            rep.stalled_for += 1
            if rep.stalled_for >= self.dead_stall_ticks:
                self._crash(rep, RuntimeError(
                    f"replica {rep.rid} stalled for {rep.stalled_for} "
                    "consecutive ticks — declared lost"))
            return
        rep.stalled_for = 0
        stall = fault_point("replica_stall")
        if stall:
            self.inst.stalls.inc()
            rep.stall_ticks = int(stall) - 1   # this tick is the first
            rep.stalled_for = 1
            return
        try:
            fault_point("replica_crash")
            out = rep.sp.step()
        except Exception as exc:
            # a replica crash is a ROUTING EVENT: the fleet recovery owns
            # every exception here (the replica's own round-17 machinery
            # already retried anything retryable before raising)
            self._crash(rep, exc)
            return
        self._merge(rep, out, produced)
        self._sweep(rep)

    def tick(self) -> dict[int, list[int]]:
        """One fleet scheduler round. Returns ``{fleet_id: [tokens]}``
        received this round, in emission order."""
        self.ticks += 1
        self.inst.ticks.inc()
        produced: dict[int, list[int]] = {}
        with span("fleet_tick"):
            self._sweep_unrouted()
            for rep in self.replicas:
                if rep.state == DEAD:
                    rep.restart_in -= 1
                    if rep.restart_in <= 0:
                        self._restart(rep)
                    continue
                self._step_replica(rep, produced)
            # round 20: one tick of KV-page wire work (new handoffs
            # created by the sweeps above send their first window NOW)
            self._drive_transfers()
            self._refresh_health()
        self.inst.live_replicas.set(
            sum(1 for r in self.replicas if r.state != DEAD))
        self.inst.unrouted.set(len(self._unrouted))
        return produced

    def flush(self) -> dict[int, list[int]]:
        """Drain every live replica's in-flight ring and sweep terminal
        states. A stalled replica cannot be drained — its deferred
        emissions land once the stall expires (keep ticking)."""
        produced: dict[int, list[int]] = {}
        for rep in self.replicas:
            if rep.state == DEAD or rep.stall_ticks > 0:
                continue
            self._merge(rep, rep.sp.flush(), produced)
            self._sweep(rep)
        return produced

    def has_work(self) -> bool:
        return bool(self._live)

    # -- merge / sweep ------------------------------------------------------

    def _merge(self, rep: _Replica, out: dict, produced: dict) -> None:
        """Land one replica's step/flush results into the fleet-side
        streams — the ONLY writer of ``FleetRequest.output_ids``, so the
        received ledger is exactly what failover resumes from."""
        for inner_id, toks in out.items():
            freq = rep.by_inner.get(inner_id)
            if freq is None or freq.state in (FINISHED, FAILED):
                continue
            landed = 0
            for tok in toks:
                if freq.done:
                    break   # guard: never exceed the fleet-side contract
                freq.output_ids.append(int(tok))
                produced.setdefault(freq.fleet_id, []).append(int(tok))
                landed += 1
            if landed:
                if freq.first_token_time is None:
                    freq.first_token_time = monotonic()
                self.inst.tokens.labels(replica=str(rep.rid)).inc(landed)

    def _sweep(self, rep: _Replica) -> None:
        """Propagate terminal inner states to the fleet requests. An
        inner request FINISHED by count with values still in flight
        (async deferral) stays mapped until its pending tokens land —
        finishing the fleet request early would drop its tail."""
        for inner_id in list(rep.by_inner):
            freq = rep.by_inner[inner_id]
            inner = freq._inner
            if inner is None or inner.req_id != inner_id:
                del rep.by_inner[inner_id]   # stale mapping (migrated)
                continue
            if inner.state == FINISHED and inner._pending_n == 0:
                del rep.by_inner[inner_id]
                freq.truncated = freq.truncated or inner.truncated
                if freq.phase == "prefill":
                    # round 20: the prefill stage retired — the fleet
                    # request is NOT done, its pages hand off to the
                    # decode fleet now
                    self._handoff(freq, rep)
                else:
                    self._finish(freq)
            elif inner.state == FAILED:
                del rep.by_inner[inner_id]
                if (freq.phase == "prefill"
                        and inner.error["code"] != "deadline_exceeded"):
                    # round 20: an intra-replica failure of the PREFILL
                    # stage (pool exhaustion, retry exhaustion, a raced
                    # shed) is not the request's failure — the
                    # colocated path may still serve it. Deadlines stay
                    # global: an expired request is expired everywhere.
                    self._fallback(
                        freq, f"prefill stage failed "
                              f"({inner.error['code']})")
                    continue
                # an intra-replica terminal verdict (deadline, pool
                # exhaustion, retry exhaustion, shed) is the REQUEST's
                # failure, not the replica's — it propagates, it does
                # not fail over (a deadline miss is global; the rest
                # would recur on any identically-sized replica)
                self._fail(freq, inner.error["code"],
                           inner.error["message"])

    # -- router-side queue maintenance --------------------------------------

    def _sweep_unrouted(self) -> None:
        """Retry placement for requests queued at the router, failing
        the ones past their deadline first (the router-level TTL — an
        unrouted request never reaches a predictor's own sweep)."""
        if not self._unrouted:
            return
        now = monotonic()
        pending = list(self._unrouted)
        self._unrouted.clear()
        for freq in pending:
            if freq.state in (FINISHED, FAILED):
                continue
            if freq.past_deadline(now):
                self.inst.deadline_misses.inc()
                self._fail(freq, "deadline_exceeded",
                           f"unrouted past its {freq.deadline_s}s "
                           "deadline (no admittable replica)")
                continue
            # re-queues itself via _try_route when still unplaceable
            self._try_route(freq)

    def _refresh_health(self) -> None:
        """The health gate's per-tick refresh: HEALTHY <-> UNHEALTHY off
        the stall state and the healthz staleness stamp. DRAINING and
        DEAD are sticky (operator / supervisor transitions)."""
        for rep in self.replicas:
            if rep.state in (DEAD, DRAINING):
                continue
            stale = (rep.stall_ticks > 0
                     or rep.sp.healthz()["snapshot_age_s"]
                     > self.stale_after_s)
            rep.state = UNHEALTHY if stale else HEALTHY

    # -- observability ------------------------------------------------------

    def telemetry(self) -> dict[str, float]:
        """Flat snapshot of the fleet registry (the bench ``telemetry``
        object). Per-replica serving registries stay per-replica —
        :meth:`replica_healthz` is the per-replica surface."""
        return self.inst.snapshot_flat()

    def replica_healthz(self) -> list[dict]:
        """Per-replica health: the fleet state machine's view joined
        with each live replica's own ``healthz()`` snapshot."""
        out = []
        for rep in self.replicas:
            row = {"replica_id": rep.rid, "fleet_state": rep.state,
                   "role": self.role_for(rep.rid),
                   "stall_ticks": rep.stall_ticks,
                   "assigned": len(rep.by_inner)}
            if rep.sp is not None:
                row["healthz"] = rep.sp.healthz()
            out.append(row)
        return out

    def fleet_accounting(self) -> dict[str, int]:
        """The partition the chaos gate asserts after every tick:
        ``submitted == finished + failed + live`` (and the counters
        agree with the request objects)."""
        snap = self.telemetry()
        return {
            "submitted": int(snap["fleet_requests_submitted"]),
            "finished": int(snap["fleet_requests_finished"]),
            "failed": int(snap["fleet_requests_failed"]),
            "live": len(self._live),
        }
