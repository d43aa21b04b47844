"""Fleet serving: health-checked prefix-affinity router over N engine
replicas (ISSUE 9 tentpole; docs/fleet_serving.md; ROADMAP item 2).

Millions of users means N :class:`~paddle_tpu.inference.serving.
ContinuousBatchingEngine` replicas behind a router, not one engine — and at
fleet scale the dominant failure mode is no longer a poisoned request
(PR 6's per-request isolation handles that inside one engine) but a whole
replica dying, stalling, or going slow.  The :class:`FleetRouter` is a
deterministic in-process fleet: one host control plane fronting N replicas
(each of which may itself be tensor-parallel, docs/tp_serving.md), built on
two primitives earlier PRs already shipped:

* the prefix cache's **hash-chained block ids** (PR 2) are a *global*
  content address — the same prompt hashes to the same chain on every
  replica, so "which replica holds this prefix" is a pure host-side lookup
  (`PrefixCache.match`, side-effect free);
* the snapshot **journal** (PR 6) resumes accepted work by teacher-forced
  recompute, token-identically for greedy AND seeded sampling — so losing a
  replica's KV pool loses *bytes*, never *streams*.

Three pillars:

**1. Cache-aware routing.**  An incoming prompt routes to the replica
holding the longest cached chain of its blocks (prefix affinity — reusing
resident KV beats rebalancing load), spilling to the least-loaded replica
when nothing matches.  Health gates affinity: a DEGRADED replica is chosen
only when no HEALTHY one can take the work (latency protection outranks a
warm cache).  Fleet admission layers on each engine's ``max_queue``: a
replica whose queue is full is not routable, and when EVERY routable
replica is full the fleet itself sheds the request as REJECTED
(``stats["fleet_rejected"]``) — backpressure composes, it does not hide.

**2. Replica health + failover.**  Replicas walk ``HEALTHY → DEGRADED →
DRAINING → DEAD``, driven by per-step heartbeats and surfaced engine
faults:

* a ``replica_slow`` streak (elevated step latency) degrades; a clean
  streak heals back to HEALTHY;
* ``drain(r)`` marks DRAINING: the replica accepts no new work but keeps
  stepping until its in-flight requests finish (rolling restart / scale-in
  primitive);
* a replica that makes **no progress** for ``stall_steps`` fleet steps
  while holding live work is stalled: the router hedge-dispatches its
  in-flight requests onto survivors (journal replay), keeping the primary
  as owner until **first-writer-wins** resolves — whichever copy first
  extends a request's stream becomes the owner and the loser is cancelled,
  so a stalled replica's late answer is discarded, never double-banked;
* a DEAD replica (``replica_crash`` injection, or an engine fault that
  escapes ``step()`` — only a persistent kernel failure can) triggers
  **failover**: the router replays the replica's journal — accepted
  prompts, emitted tokens, prefill cursors, maintained incrementally by
  each engine and pulled at the death boundary — onto survivors through
  ``engine.adopt()``'s teacher-forced recompute.  Every replayed request's
  completed output stream is token-identical (greedy and seeded) to an
  uninterrupted fleet's, because each stream depends only on its own
  ``(seed, position)`` keys and its own tokens — never on which replica
  computed it.  Replayed/hedged work is EXEMPT from backpressure (accepted
  work is never rejected) and deadlines re-arm with the journaled
  REMAINING budget only.

**3. Fleet chaos.**  The same ``PADDLE_TPU_FAULT_INJECT`` grammar grows
replica-scoped clauses (faults.REPLICA_KINDS): ``replica_crash`` /
``replica_stall`` / ``replica_slow`` ``@ step/replica/count/p+seed``,
polled once per replica per fleet step in replica-index order — a
randomized fleet chaos run is exactly replayable from its env string.
Engine-scoped kinds inside a fleet spec fan out to every replica's own
injector (scope one with ``replica=k``); a replica-scoped clause with NO
fleet running is rejected by the engine's parse (warn once, injection
disabled) instead of being a silent no-op.

With ``enable_host_kv_tier=True`` (ISSUE 13, docs/kv_tier.md) the fleet
shares ONE :class:`~paddle_tpu.inference.kv_tier.HostKVTier` across its
replicas — the fleet-wide prefix store.  Chain hashes are already the
routing key, so a chain any replica computed and demoted is re-admittable
by every other replica: affinity misses stop being full prefills, and
failover replay restores the dead replica's demoted chains page-by-page
through the ordinary tier-extended admission (O(pages shipped) for the
covered prefix; only the uncovered tail is teacher-forced).

Non-goals (docs/fleet_serving.md): the router does not move *live* KV
bytes between replicas (failover replays the journal; the shared host
tier moves only content-addressed finished pages), does not rebalance
running work (only failure moves it), and trusts one process's clock (it
is an in-process fleet — the distributed-systems problems it models are
scheduling ones, not Byzantine ones).

Audited invariant **I9** (``PADDLE_TPU_ENGINE_AUDIT=1``,
analysis/engine_audit.audit_fleet): every live rid is owned by exactly one
replica — a hedge-pending rid counts as the primary's until
first-writer-wins resolves — and no replica serves a rid the router does
not route to it.
"""

from __future__ import annotations

import os
import time

import numpy as np

from ..profiler import RecordEvent
from .faults import KNOWN_KEYS, KNOWN_KINDS, REPLICA_KINDS, FaultPlan
from .observability import (FLEET_STAT_SCHEMA, FlightRecorder,
                            MetricsRegistry, RequestTracer, SLOTracker,
                            StatsView)
from .serving import (TERMINAL_STATUSES, ContinuousBatchingEngine, Request,
                      journal_entry)

__all__ = ["FleetRouter", "REPLICA_STATES", "HEALTH_EDGES"]

#: replica health states, in degradation order (docs/fleet_serving.md)
REPLICA_STATES = ("HEALTHY", "DEGRADED", "DRAINING", "DEAD")

#: declared replica-health transition table, verified exhaustively against
#: every ``self.health[...]`` write site by the host-contract pass
#: (analysis/host_contracts.py; docs/analysis.md §"Host contracts").
#: Transitions move strictly down the degradation ladder except the single
#: declared heal edge DEGRADED->HEALTHY (_note_heartbeat after heal_after
#: clean beats); DEAD is absorbing.  DRAINING->DEAD covers killing a
#: replica mid-drain; HEALTHY/DEGRADED->DEAD is a hard _kill.
HEALTH_EDGES = frozenset({
    ("HEALTHY", "DEGRADED"), ("DEGRADED", "HEALTHY"),
    ("HEALTHY", "DRAINING"), ("DEGRADED", "DRAINING"),
    ("HEALTHY", "DEAD"), ("DEGRADED", "DEAD"), ("DRAINING", "DEAD"),
})


class FleetRouter:
    """Deterministic in-process fleet of ``n_replicas`` continuous-batching
    engines behind one cache-aware, health-checked router (module
    docstring; docs/fleet_serving.md).

    ``engine_kw`` passes through to every
    :class:`~paddle_tpu.inference.serving.ContinuousBatchingEngine`
    (replicas are homogeneous — heterogeneous fleets would break the
    token-identity failover contract only via *model* differences, which
    ``snapshot()``'s topology check already polices, but homogeneity keeps
    load comparable too).  ``params`` is shared by reference across
    replicas: JAX arrays are immutable and the engines donate only their
    own KV pools, so N replicas cost N pools + one weight set.

    ``stall_steps``: fleet steps without progress (while holding live
    work) before a replica counts as stalled and its in-flight requests
    hedge onto survivors; at ``stall_dead_steps`` the stall is declared
    crash-equivalent and the replica DEAD (so un-hedgeable work fails
    with a diagnosis instead of hanging the serve loop).  ``slow_after``
    / ``heal_after``: consecutive slow / clean heartbeats before
    DEGRADED / back to HEALTHY."""

    def __init__(self, cfg, params, n_replicas: int = 2, *,
                 stall_steps: int = 3, stall_dead_steps: int = 12,
                 slow_after: int = 2, heal_after: int = 2, **engine_kw):
        if int(n_replicas) < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        self.n_replicas = int(n_replicas)
        self.stall_steps = int(stall_steps)
        self.stall_dead_steps = int(stall_dead_steps)
        if self.stall_dead_steps <= self.stall_steps:
            raise ValueError(
                f"stall_dead_steps ({stall_dead_steps}) must exceed "
                f"stall_steps ({stall_steps}): hedging must get a chance "
                f"before the replica is declared dead")
        self.slow_after = int(slow_after)
        self.heal_after = int(heal_after)
        # observability (ISSUE 11, docs/observability.md): ONE shared
        # registry — every replica's engine registers the same metric
        # families with a {"replica": k} label set, so metrics.expose()
        # is the whole fleet's Prometheus snapshot; the fleet's own
        # stats/SLO/flight tiers layer on top with fleet-prefixed names.
        self.metrics = engine_kw.pop("metrics", None)
        if self.metrics is None:
            self.metrics = MetricsRegistry()
        # The router owns the replica label — a caller-provided label set
        # would collapse N replicas onto one labelled series.
        engine_kw.pop("metrics_labels", None)
        # hierarchical KV (ISSUE 13, docs/kv_tier.md): the fleet shares
        # ONE host tier across its replicas — chain hashes are already
        # the routing key, so a chain ANY replica computed and demoted is
        # re-admittable by every other replica (affinity misses stop
        # being full prefills, and adopt() failover restores the dead
        # replica's demoted chains in O(pages shipped) instead of
        # teacher-forced recompute).  shared=True switches ship_in to
        # keep-resident semantics and relaxes the I10 exclusivity check
        # to per-replica accounting (content-addressed duplicates across
        # replicas are byte-identical by construction).
        from ..utils.envflags import env_bool as _env_bool

        self.host_tier = engine_kw.pop("host_tier", None)
        if not _env_bool("PADDLE_TPU_HOST_KV_TIER", True):
            # the kill switch neutralizes the fleet tier TOTALLY — even an
            # explicitly-passed tier object is dropped (and left
            # unmutated), so `router.host_tier is None` is a truthful
            # "tier off" signal (the engines would each disable it anyway)
            self.host_tier = None
        elif self.host_tier is not None:
            self.host_tier.shared = True
        elif engine_kw.get("enable_host_kv_tier"):
            from .kv_tier import HostKVTier

            self.host_tier = HostKVTier(shared=True)
        if self.host_tier is not None:
            engine_kw["host_tier"] = self.host_tier
        # the engines must NOT parse a fleet chaos spec themselves: a
        # replica-scoped clause would (correctly) disable their whole plan
        # with a warning.  The router parses once with the full vocabulary
        # and installs each replica's engine-scoped share below.
        spec = os.environ.pop("PADDLE_TPU_FAULT_INJECT", None)
        try:
            self.replicas: list[ContinuousBatchingEngine | None] = [
                ContinuousBatchingEngine(cfg, params, metrics=self.metrics,
                                         metrics_labels={"replica": str(r)},
                                         **engine_kw)
                for r in range(self.n_replicas)]
        finally:
            if spec is not None:
                os.environ["PADDLE_TPU_FAULT_INJECT"] = spec
        self.health: list[str] = ["HEALTHY"] * self.n_replicas
        # fleet-level request registry: rid -> caller's Request, LIVE only
        # (terminal requests are pruned, mirroring the engine's journal)
        self._reqs: dict[int, Request] = {}
        # rid -> owning replica index (I9: exactly one owner per live rid)
        self._owner: dict[int, int] = {}
        # rid -> {replica index: replica-local Request copy}; owner always
        # holds one, a hedge-pending rid holds a second on the hedge target
        self._copies: dict[int, dict[int, Request]] = {}
        # rid -> hedge replica (first-writer-wins pending); ownership stays
        # with the primary until a copy extends the stream
        self._hedge: dict[int, int] = {}
        # per-replica journal (docs/async_runtime.md): the replicas
        # maintain their journals incrementally (O(changed rids), flushed
        # inside each engine's host-overlap window) and the router pulls
        # them ONLY at the boundaries that consume them — replica death
        # and stall hedging (_journal_pull)
        self._journal: list[dict | None] = [None] * self.n_replicas
        self._last_progress = [0] * self.n_replicas
        self._slow_streak = [0] * self.n_replicas
        self._ok_streak = [0] * self.n_replicas
        self._step_no = 0          # fleet step counter (replica-clause key)
        # fleet stats on the shared registry behind the same dict view the
        # engines use (keys + help: observability.FLEET_STAT_SCHEMA).
        # The fleet SLO tracker is fed in _mirror with the SAME timestamps
        # that set each request's ttft_s.
        self.stats = StatsView(self.metrics, FLEET_STAT_SCHEMA,
                               prefix="paddle_tpu_fleet")
        self.slo = SLOTracker(self.metrics, prefix="paddle_tpu_fleet")
        self._h_jupdate = self.metrics.histogram(
            "paddle_tpu_fleet_journal_update_seconds",
            "Host seconds per router journal refresh: one incremental "
            "pull per consumption boundary (failover/hedge; "
            "docs/async_runtime.md)").labels()
        # one flow-link tracer per replica lane (the engines' own tracers
        # already own the span traffic on those pids; the router only adds
        # the cross-replica failover/hedge arrows and health markers)
        self._tracers = [RequestTracer(pid=r)
                         for r in range(self.n_replicas)]
        self._flow_seq = 0
        self._flight = FlightRecorder(registry=self.metrics, name="fleet")
        self._faults = FaultPlan()
        self._arm_faults_from_env()
        from ..analysis.engine_audit import audit_enabled

        self._audit_every_step = audit_enabled()

    # ---------------- chaos plumbing ----------------

    def _arm_faults_from_env(self) -> None:
        """Parse ``PADDLE_TPU_FAULT_INJECT`` with the full fleet vocabulary
        and partition it: replica-scoped clauses arm the router's own plan,
        engine-scoped clauses fan out to each replica's injector — a clause
        carrying ``replica=k`` arms only replica k's engine, one without it
        arms every replica (each with its own independent clause state, so
        counts and seeded streams stay per-replica deterministic)."""
        from ..utils.envflags import env_fault_spec

        clauses = env_fault_spec("PADDLE_TPU_FAULT_INJECT",
                                 KNOWN_KINDS | REPLICA_KINDS,
                                 KNOWN_KEYS | {"replica"})
        self._faults = FaultPlan(
            [c for c in clauses if c["kind"] in REPLICA_KINDS])
        eng_clauses = [c for c in clauses if c["kind"] not in REPLICA_KINDS]
        for r, eng in enumerate(self.replicas):
            if eng is None:
                continue
            mine = []
            for c in eng_clauses:
                if c.get("replica") not in (None, r):
                    continue
                c2 = dict(c)
                # the engine polls never pass a replica key: strip the
                # scope so the clause matches its chosen engine's seams
                c2.pop("replica", None)
                mine.append(c2)
            eng._faults = FaultPlan(mine)

    # ---------------- routing (pillar 1) ----------------

    def _load(self, r: int) -> int:
        """Live accepted requests (running + queued) on replica ``r``."""
        return len(self.replicas[r]._reqs)

    def _full(self, r: int) -> bool:
        eng = self.replicas[r]
        return (eng.max_queue is not None
                and len(eng._queue) >= eng.max_queue)

    def _match_len(self, r: int, ids: np.ndarray) -> int:
        """Cached-chain length (full blocks) replica ``r`` holds for this
        stream — the global content address the router keys on.  Pure
        lookup: ``match`` touches no refcounts."""
        pc = self.replicas[r]._pcache
        return len(pc.match(ids)) if pc is not None else 0

    def _route(self, ids: np.ndarray, exclude=frozenset(),
               accepted: bool = False) -> tuple[int | None, int]:
        """Pick the target replica for a stream: HEALTHY before DEGRADED,
        then longest cached chain, then least-loaded, then lowest index
        (fully deterministic).  ``accepted=True`` (failover replay /
        hedging) lifts the queue-full filter — accepted work is never
        rejected — and falls back to a DRAINING replica when nothing else
        survives, because dropping accepted work is strictly worse than
        delaying a drain.  Returns (replica | None, match_len)."""
        alive = [r for r in range(self.n_replicas)
                 if self.replicas[r] is not None and r not in exclude]
        cands = [r for r in alive if self.health[r] in ("HEALTHY",
                                                        "DEGRADED")]
        if not accepted:
            cands = [r for r in cands if not self._full(r)]
        elif not cands:
            cands = [r for r in alive if self.health[r] == "DRAINING"]
        if not cands:
            return None, 0
        match = {r: self._match_len(r, ids) for r in cands}
        best = min(cands, key=lambda r: (
            0 if self.health[r] == "HEALTHY" else 1,
            -match[r], self._load(r), r))
        return best, match[best]

    def _reject(self, req: Request, msg: str) -> None:
        with RecordEvent("fleet/rejected"):
            req.status = "REJECTED"
            req.finished = True
            req.error = msg
            self.stats["fleet_rejected"] += 1
            self.slo.finish(req.rid, "REJECTED", time.perf_counter())
            self._flight.record("terminal", rid=req.rid,
                                status="REJECTED", error=msg)

    @staticmethod
    def _copy_req(req: Request) -> Request:
        """Replica-local twin of a fleet request.  Same rid and sampling
        params, so the engine's default ``seed = rid`` and its
        ``(seed, position)`` keys derive the SAME stream on every replica —
        the property that makes hedging and failover token-identical by
        construction."""
        return Request(
            rid=req.rid,
            prompt_ids=np.asarray(req.prompt_ids, np.int32).ravel(),
            max_new_tokens=req.max_new_tokens,
            eos_token_id=req.eos_token_id,
            temperature=req.temperature, top_p=req.top_p, seed=req.seed,
            deadline_s=req.deadline_s, trace_id=req.trace_id)

    def add_request(self, req: Request) -> None:
        """Route one request into the fleet (or shed it as REJECTED when
        no routable replica can take it — fleet-level backpressure)."""
        if req.rid in self._reqs:
            raise ValueError(f"request {req.rid}: rid already live in the "
                             f"fleet")
        req._submit_s = time.perf_counter()
        if req.trace_id is None:
            req.trace_id = f"req-{req.rid:x}"
        self.slo.begin(req.rid, req._submit_s)
        probe = next((e for e in self.replicas if e is not None), None)
        if probe is None:
            self._reject(req, "every replica is DEAD (fleet lost)")
            return
        try:
            probe._validate(req)
        except ValueError as e:
            # the engine's serve() contract, fleet edition: one bad request
            # must not raise out of the router
            self._reject(req, str(e))
            return
        ids = np.asarray(req.prompt_ids, np.int32).ravel()
        target, m = self._route(ids)
        if target is None:
            # name the ACTUAL cause: an operator who drained the whole
            # fleet must not be sent debugging max_queue backpressure
            routable = [r for r in range(self.n_replicas)
                        if self.replicas[r] is not None
                        and self.health[r] in ("HEALTHY", "DEGRADED")]
            if routable:
                msg = ("fleet backpressure: every routable replica's "
                       "queue is full")
            else:
                n_drain = self.health.count("DRAINING")
                n_dead = self.health.count("DEAD")
                msg = (f"no routable replica: {n_drain} DRAINING, "
                       f"{n_dead} DEAD of {self.n_replicas} (draining "
                       f"replicas accept no new work)")
            self._reject(req, msg)
            return
        self.stats["routed_affinity" if m > 0 else "routed_spill"] += 1
        self._flight.record("route", rid=req.rid, replica=target,
                            match_blocks=int(m))
        copy = self._copy_req(req)
        self.replicas[target].add_request(copy)
        if copy.status == "REJECTED":       # defensive: _route pre-filtered
            self._reject(req, copy.error or "replica rejected the request")
            return
        self._reqs[req.rid] = req
        self._owner[req.rid] = target
        self._copies[req.rid] = {target: copy}
        # a crash before the replica's next step still replays this: the
        # replica's incremental journal already tracks the dispatch
        # (add_request _jmarks the rid) and the router pulls it at the
        # death/stall boundary

    def cancel(self, rid: int) -> bool:
        """Fleet-level cancel: every replica copy (owner and any pending
        hedge) cancels, the fleet request goes terminal CANCELLED with its
        partial output.  False when the rid is unknown or already
        terminal."""
        f = self._reqs.get(rid)
        if f is None:
            return False
        for rr, cc in self._copies.pop(rid, {}).items():
            eng = self.replicas[rr]
            if eng is not None and not cc.finished:
                eng.cancel(rid)
        self._owner.pop(rid, None)
        self._hedge.pop(rid, None)
        self._reqs.pop(rid, None)
        f.status = "CANCELLED"
        f.finished = True
        f.error = "cancelled by caller"
        self.slo.finish(rid, "CANCELLED", time.perf_counter())
        return True

    # ---------------- health + failover (pillar 2) ----------------

    def drain(self, replica: int) -> None:
        """Mark a replica DRAINING: it accepts no new work (routing skips
        it; only a last-resort failover replay may still land) but keeps
        stepping until its in-flight requests finish — the rolling-restart
        / scale-in primitive."""
        if self.replicas[replica] is None or self.health[replica] == "DEAD":
            raise ValueError(f"replica {replica} is DEAD")
        self._health_to(replica, "DRAINING", "drain() by operator")

    def _has_live(self, r: int) -> bool:
        eng = self.replicas[r]
        return eng is not None and bool(eng._reqs)

    def _health_to(self, r: int, state: str, why: str) -> None:
        """Single choke point for health transitions, so every one lands
        in the flight recorder and on the replica's trace lane."""
        prev = self.health[r]
        if prev == state:
            return
        self.health[r] = state
        now = time.perf_counter()
        self._flight.record("health", replica=r, frm=prev, to=state,
                            why=why)
        self._tracers[r].instant(0, f"health:{state}", now,
                                 args={"replica": r, "from": prev,
                                       "why": why})

    def _note_heartbeat(self, r: int, ok: bool) -> None:
        """Latency-heartbeat bookkeeping: a slow/stalled step degrades
        after ``slow_after`` in a row, a clean streak of ``heal_after``
        heals a DEGRADED replica (DRAINING and DEAD never heal — one is an
        operator decision, the other is terminal)."""
        if ok:
            self._ok_streak[r] += 1
            self._slow_streak[r] = 0
            if (self.health[r] == "DEGRADED"
                    and self._ok_streak[r] >= self.heal_after):
                self._health_to(r, "HEALTHY",
                                f"{self._ok_streak[r]} clean heartbeats")
        else:
            self._slow_streak[r] += 1
            self._ok_streak[r] = 0
            if (self.health[r] == "HEALTHY"
                    and self._slow_streak[r] >= self.slow_after):
                self._health_to(r, "DEGRADED",
                                f"{self._slow_streak[r]} slow/stalled "
                                f"heartbeats")

    def _journal_pull(self, r: int) -> None:
        """Pull replica ``r``'s incrementally-maintained journal, at the
        boundaries that consume it (replica death, stall hedging;
        docs/async_runtime.md)."""
        eng = self.replicas[r]
        if eng is None:
            return
        t0 = time.perf_counter()
        self._journal[r] = (eng.journal() if eng._reqs
                            else {"running": [], "queued": []})
        self.stats["journal_incremental_updates"] += 1
        self._h_jupdate.observe(time.perf_counter() - t0)
        self._flight.record("journal_pull", replica=r)

    def _audit_journal_equiv(self, r: int) -> None:
        """Under PADDLE_TPU_ENGINE_AUDIT=1: assert replica ``r``'s
        incremental journal and a freshly-built ``snapshot()`` agree —
        the equivalence contract failover replay rides on once the
        router stops rebuilding snapshots itself.
        ``deadline_remaining_s`` is normalized out: both sides lazily
        recompute it from ``time.perf_counter()`` at their own read
        instants, so it legitimately differs by the nanoseconds between
        the two calls."""
        eng = self.replicas[r]
        if eng is None or not eng._reqs:
            return

        def _norm(d: dict) -> dict:
            return {**d,
                    "running": [dict(e, deadline_remaining_s=None)
                                for e in d["running"]],
                    "queued": [dict(e, deadline_remaining_s=None)
                               for e in d["queued"]]}

        j, s = _norm(eng.journal()), _norm(eng.snapshot())
        if j != s:
            from ..analysis.engine_audit import EngineAuditError

            self._flight.dump(f"journal_divergence replica={r}")
            raise EngineAuditError(
                f"incremental journal diverged from snapshot() on "
                f"replica {r} (async host runtime): "
                f"journal={j!r} snapshot={s!r}")

    def _journal_entry(self, r: int, rid: int) -> dict:
        """The journal entry to replay for ``rid`` of replica ``r``: the
        incrementally-maintained snapshot's, falling back to synthesizing
        one from the fleet-mirrored request via the SAME
        ``serving.journal_entry`` schema the snapshot uses (equivalent
        content minus the prefill-cursor provenance)."""
        j = self._journal[r] or {}
        for e in j.get("running", []) + j.get("queued", []):
            if e["rid"] == rid:
                return e
        return journal_entry(self._reqs[rid])

    def _replay(self, rid: int, entry: dict, exclude: set,
                source: int | None = None,
                link: str = "failover") -> int | None:
        """Adopt one journal entry onto the best survivor (affinity over
        the full prompt+generated stream, since retired generated blocks
        are content-addressed too).  Returns the target replica or None
        when nothing survives.  ``source`` (the dead/stalled replica)
        draws the cross-replica trace link: a chrome flow arrow from the
        source's lane to the adopting replica's, so a failover/hedge reads
        as one continuous request line across the fleet timeline."""
        ids = np.asarray(list(entry["prompt_ids"])
                         + list(entry["output_ids"]), np.int32)
        target, _ = self._route(ids, exclude=exclude, accepted=True)
        if target is None:
            return None
        copy = self.replicas[target].adopt(entry)
        self._copies.setdefault(rid, {})[target] = copy
        self.stats["replayed_tokens"] += len(entry["output_ids"])
        if source is not None:
            now = time.perf_counter()
            self._flow_seq += 1
            fid = f"{link}-{rid}-{self._flow_seq}"
            self._tracers[source].flow_out(rid, link, now, fid)
            self._tracers[target].flow_in(rid, link, now + 1e-6, fid)
        self._flight.record(link, rid=rid, frm=source, to=target,
                            replayed_tokens=len(entry["output_ids"]))
        return target

    def _kill(self, r: int, reason: str) -> None:
        """Replica death: mark DEAD, drop the engine, and replay its
        journal onto survivors.  A rid with a pending hedge needs no
        replay — its hedge twin already carries the stream and inherits
        ownership; a rid hedged ONTO the dead replica just loses the
        hedge.  With no survivors at all, the affected requests terminate
        FAILED (the fleet is lost; accepted work cannot outlive every
        replica)."""
        with RecordEvent("fleet/failover"):
            dead_eng = self.replicas[r]   # for the flight-recorder dump
            # the death boundary IS the journal's consumption point:
            # pull the incremental journal while the engine object is
            # still here, then replay from it
            self._journal_pull(r)
            self._health_to(r, "DEAD", reason)
            self.replicas[r] = None
            self.stats["failovers"] += 1
            for rid, h in list(self._hedge.items()):
                if h == r:                  # hedge twin died: drop it
                    del self._hedge[rid]
                    self._copies.get(rid, {}).pop(r, None)
            for rid in [rid for rid, o in list(self._owner.items())
                        if o == r]:
                self._copies.get(rid, {}).pop(r, None)
                h = self._hedge.pop(rid, None)
                if h is not None:
                    # first-writer-wins resolves by default: the survivor
                    # is the only writer left
                    self._owner[rid] = h
                    continue
                entry = self._journal_entry(r, rid)
                target = self._replay(rid, entry, exclude={r}, source=r)
                if target is None:
                    f = self._reqs.pop(rid)
                    self._owner.pop(rid, None)
                    self._copies.pop(rid, None)
                    f.status = "FAILED"
                    f.finished = True
                    f.error = (f"replica {r} died ({reason}) with no "
                               f"surviving replica to replay onto")
                    self.slo.finish(rid, "FAILED",
                                    time.perf_counter())
                    continue
                self._owner[rid] = target
            # replica death is a flight-recorder dump trigger: the
            # router's recent events + the DEAD replica's own ring + a
            # fleet metrics snapshot, so chaos triage reads what the
            # engine was doing when it died without a rerun
            self._flight.dump(
                f"replica {r} DEAD: {reason}",
                extra={"replica": r,
                       "engine_events": (
                           dead_eng._flight.events()
                           if dead_eng is not None else None)})
            # every live entry is replayed: holding the dead replica's
            # final snapshot past this point would retain its requests'
            # full token lists for the router's lifetime (the retention
            # class PR 6 fixed in the engine's rid journal)
            self._journal[r] = None

    def _detect_stalls(self) -> None:
        """Heartbeat-gap stall detection: a replica holding live work that
        has not completed a step for ``stall_steps`` fleet steps gets its
        in-flight requests hedge-dispatched onto survivors.  The primary
        stays the owner (I9) until first-writer-wins resolves in
        ``_mirror``.  A stall that persists to ``stall_dead_steps`` is
        crash-equivalent: the replica is declared DEAD (``_kill``), so its
        un-hedgeable work — a one-replica fleet, or every survivor already
        gone — terminates FAILED with a diagnosis instead of spinning
        ``serve()`` forever (the never-a-hang contract; deadlines cannot
        save it either, since expiry runs inside the engine step the
        stalled replica never executes)."""
        for r in range(self.n_replicas):
            if (self.replicas[r] is None or not self._has_live(r)):
                continue
            gap = self._step_no - self._last_progress[r]
            if gap < self.stall_steps:
                continue
            if gap >= self.stall_dead_steps:
                self._kill(r, f"stalled for {gap} fleet steps "
                              f"(stall_dead_steps={self.stall_dead_steps})")
                continue
            if self.health[r] == "HEALTHY":
                self._health_to(r, "DEGRADED",
                                f"no progress for {gap} fleet steps")
            # hedge boundary: refresh the stalled replica's journal
            # from its incremental entries before replaying them
            # (the stalled engine's host side is still reachable —
            # it is the device step that is not completing)
            self._journal_pull(r)
            for rid in [rid for rid, o in self._owner.items() if o == r]:
                if rid in self._hedge:
                    continue               # already hedge-pending
                with RecordEvent("fleet/hedge"):
                    entry = self._journal_entry(r, rid)
                    target = self._replay(rid, entry, exclude={r},
                                          source=r, link="hedge")
                    if target is None:
                        continue           # nobody to hedge onto: wait
                    self._hedge[rid] = target
                    self.stats["hedges"] += 1

    def _resolve_hedge(self, rid: int, winner: int) -> None:
        """First-writer-wins: ``winner`` extended the stream first and
        becomes the owner; the loser's copy is cancelled (its late answer
        — token-identical anyway, by the determinism contract — is
        discarded, its pages free)."""
        h = self._hedge.pop(rid)
        owner = self._owner[rid]
        loser = owner if winner == h else h
        self._owner[rid] = winner
        cc = self._copies.get(rid, {}).pop(loser, None)
        eng = self.replicas[loser]
        if cc is not None and eng is not None and not cc.finished:
            eng.cancel(rid)

    def _promote(self, rid: int, new_owner: int) -> None:
        """The primary terminated on its own (e.g. its engine failed the
        copy) while a hedge twin is mid-replay: promote the twin instead
        of failing the fleet request."""
        old = self._owner[rid]
        self._copies.get(rid, {}).pop(old, None)
        self._owner[rid] = new_owner

    def _finish(self, rid: int, copy: Request) -> None:
        """Mirror a terminal replica copy onto the fleet request and prune
        every live registry (I9: terminal means gone from the routing
        plane).  Any other copy still live (an unresolved hedge twin) is
        cancelled."""
        f = self._reqs.pop(rid)
        self._hedge.pop(rid, None)
        self._owner.pop(rid, None)
        for rr, cc in self._copies.pop(rid, {}).items():
            if cc is copy:
                continue
            eng = self.replicas[rr]
            if eng is not None and not cc.finished:
                eng.cancel(rid)
        f.status = copy.status
        f.finished = True
        f.error = copy.error
        self.slo.finish(rid, copy.status, time.perf_counter())

    def _mirror(self, r: int) -> None:
        """After replica ``r`` steps: bank its copies' new tokens onto the
        fleet requests (resolving first-writer-wins for hedge-pending
        rids) and mirror terminal transitions."""
        for rid in [rid for rid in list(self._reqs)
                    if self._owner.get(rid) == r
                    or self._hedge.get(rid) == r]:
            f = self._reqs.get(rid)
            c = self._copies.get(rid, {}).get(r)
            if f is None or c is None:
                continue
            if len(c.output_ids) > len(f.output_ids):
                if rid in self._hedge:
                    self._resolve_hedge(rid, winner=r)
                delta = len(c.output_ids) - len(f.output_ids)
                f.output_ids.extend(c.output_ids[len(f.output_ids):])
                now = time.perf_counter()
                if f.ttft_s is None:
                    # fleet-level TTFT: includes routing + queueing +
                    # (on failover) replay recompute — the number an SLO
                    # is written against
                    f.ttft_s = now - f._submit_s
                # the SAME `now` that stamps ttft_s: the SLO tracker's
                # records are exactly the figures the caller observes
                self.slo.tokens(rid, delta, now)
            if self._owner.get(rid) != r:
                # hedge twin that has not won: a self-inflicted terminal
                # (failed/expired on the hedge target) just drops the hedge
                if c.status in TERMINAL_STATUSES:
                    self._hedge.pop(rid, None)
                    self._copies.get(rid, {}).pop(r, None)
                continue
            if c.status in TERMINAL_STATUSES and c.status != "CANCELLED":
                if c.status != "FINISHED" and rid in self._hedge:
                    self._promote(rid, self._hedge.pop(rid))
                else:
                    self._finish(rid, c)
            elif not c.finished:
                f.status = c.status        # PENDING/RUNNING observability

    def step(self) -> bool:
        """One fleet round: poll replica-scoped chaos, step every live
        replica once (replica-index order — the deterministic clock every
        clause keys on), mirror outputs, advance health, and hedge
        stalled work.  Returns False when the whole fleet is
        idle."""
        self._step_no += 1
        busy = False
        stepped_any = False    # any live replica stepped (overlap counter)
        for r in range(self.n_replicas):
            if self.replicas[r] is None:
                continue
            if self._faults and self._faults.fire(
                    "replica_crash", step=self._step_no, replica=r):
                self._kill(r, f"injected replica_crash (fleet step "
                              f"{self._step_no})")
                busy = True
                continue
            stalled = bool(self._faults) and self._faults.fire(
                "replica_stall", step=self._step_no, replica=r)
            # a stalled step is already a missed heartbeat: polling the
            # slow clause too would burn its count on steps where it has
            # no distinct effect, silently skewing the spec's schedule
            slow = (not stalled and bool(self._faults)
                    and self._faults.fire("replica_slow",
                                          step=self._step_no, replica=r))
            if stalled:
                # the replica's step "hangs": no progress, no heartbeat,
                # no journal refresh — exactly what the router would see
                # from a wedged device
                self._note_heartbeat(r, ok=False)
                busy = busy or self._has_live(r)
                continue
            eng = self.replicas[r]
            try:
                stepped = eng.step()
            except Exception as e:
                # a fault that escapes the engine's step() is a
                # replica-fatal condition (persistent kernel failure):
                # surface it as death, not a router crash
                self._kill(r, f"engine fault escaped step(): {e}")
                busy = True
                continue
            self._last_progress[r] = self._step_no
            self._note_heartbeat(r, ok=not slow)
            self._mirror(r)
            # the replica flushed its dirty rids inside its own
            # host-overlap window; the router defers consumption to the
            # death/stall boundaries (_journal_pull) — no per-step cost
            stepped_any = True
            if self._audit_every_step:
                self._audit_journal_equiv(r)
            busy = busy or stepped or self._has_live(r)
        if stepped_any:
            self.stats["host_overlap_steps"] += 1
        self._detect_stalls()
        if self._audit_every_step:
            from ..analysis.engine_audit import (EngineAuditError,
                                                 audit_fleet)

            try:
                audit_fleet(self)
            except EngineAuditError:
                self._flight.dump("fleet_audit_error")
                raise
        return busy or bool(self._reqs)

    def export_trace(self, path: str) -> None:
        """Export (and drain) the buffered host spans — every replica's
        request-lifecycle spans plus the router's cross-replica
        failover/hedge flow links and health markers — as ONE chrome
        trace (chrome://tracing / Perfetto): pid = replica lane, tid =
        request lane (docs/observability.md)."""
        from ..profiler import Profiler

        Profiler().export(path)

    # ---------------- serve loop ----------------

    def serve(self, requests: list[Request]) -> dict[int, list[int]]:
        """Route and run all requests to completion;
        returns ``{rid: generated tokens}`` (the fleet-mirrored streams)."""
        for r in requests:
            self.add_request(r)
        while self.step():
            pass
        return {r.rid: r.output_ids for r in requests}
