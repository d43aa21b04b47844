"""Deterministic fault injection for the serving engine (ISSUE 6 tentpole).

The degradation paths the engine ships (preemption, LRU eviction, kernel
fallback, and now the full overload ladder — docs/fault_tolerance.md) are
only trustworthy if they are exercised *adversarially*: a fault that only
ever happens in production is a fault the test suite proves nothing about.
This module turns ``PADDLE_TPU_FAULT_INJECT`` into a :class:`FaultPlan` the
engine polls at its three seams:

* **allocator** (``_alloc_to``) — ``alloc_fail`` makes a page grab report
  the pool dry even when pages are free, driving the overload ladder
  (evict -> preempt -> fail-one) without needing a genuinely tiny pool;
* **kernel dispatch** (``_launch``) — ``kernel_error`` raises where the
  compiled step would be dispatched, BEFORE the call, so host and device
  state are untouched and the engine can retry the step;
* **sampler** — ``nan_logits`` sets a per-slot poison bit that the compiled
  step turns into a genuinely non-finite logits row IN-GRAPH, so the NaN/inf
  guard proves itself against the real failure shape, not a host-side
  simulation;

plus two host-side seams that exercise per-request isolation:

* ``slot_error`` — raises while banking one slot's generated token (the
  consume loop), proving a host-side per-request fault cannot take down the
  batch;
* ``cache_error`` — raises inside prefix-cache block registration; the
  engine degrades (the block stays private, a future request
  misses where it could have hit) without failing any request;
* ``tier_drop`` — a host-KV-tier entry vanishes between the admission's
  tier match and the ship_in restore (docs/kv_tier.md): the poll fires at
  the restore seam and force-discards the entry (pins ignored — exactly
  what a lost host buffer looks like), so the engine must fall back to
  ordinary prefill compute for the remaining blocks, never hang or
  corrupt — token streams are identical either way;

and — ISSUE 9, docs/fleet_serving.md — three REPLICA-scoped kinds the
:class:`~paddle_tpu.inference.fleet.FleetRouter` polls once per replica per
fleet step (never the engine: a replica dying is a fleet-tier event):

* ``replica_crash`` — the replica dies mid-serve: the router marks it DEAD
  and replays its journal onto survivors by teacher-forced recompute;
* ``replica_stall`` — the replica makes no progress for the fired step
  (its compiled step "hangs"); enough consecutive stalls trigger hedged
  re-dispatch with first-writer-wins dedup;
* ``replica_slow`` — the replica's step completes but its latency
  heartbeat is elevated; a streak degrades its health so the router stops
  preferring it for new work.

Replica-scoped kinds are rejected when no fleet is running
(``FaultPlan.from_env(fleet=False)``, the engine's parse): the clause would
otherwise be a silent no-op — the worst failure mode for a chaos lever — so
the parse warns once naming the fleet requirement and disables injection
entirely, exactly like a typo'd kind (utils/envflags.env_fault_spec).

Grammar (validated by ``utils/envflags.env_fault_spec``; a typo warns with a
did-you-mean and disables injection entirely)::

    PADDLE_TPU_FAULT_INJECT="alloc_fail@step=7;nan_logits@slot=2,step=11"
    PADDLE_TPU_FAULT_INJECT="replica_crash@step=9,replica=1"   # fleet only

Clause keys: ``step`` (engine step number, 1-based — for replica-scoped
clauses the FLEET step number; omitted = any step), ``slot`` / ``rid`` /
``replica`` (omitted = first match polled; ``replica`` is fleet-only),
``count`` (firings before the clause exhausts; default 1, ``-1`` =
unlimited), and ``p`` + ``seed`` for probabilistic chaos — each matching
poll fires with probability ``p`` drawn from a ``seed``-keyed private
stream, so a randomized chaos run is still exactly replayable.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["KNOWN_KINDS", "KNOWN_KEYS", "REPLICA_KINDS", "FaultClause",
           "FaultPlan", "FaultInjected"]


class FaultInjected(RuntimeError):
    """Raised at a host-side injection seam (kernel dispatch / token
    banking / cache registration) when a fault-plan clause fires.  A
    DISTINCT type so the graceful engine's recovery paths catch exactly the
    faults the plan injected — a genuine error raised by the same code is
    never silently swallowed as chaos noise.  The raise always happens
    BEFORE the seam's real work (a compiled launch is never entered), so
    host and device state are untouched and recovery can retry or fail just
    the affected request."""

#: fault kinds the engine polls for (the env_fault_spec vocabulary)
KNOWN_KINDS = frozenset({"alloc_fail", "kernel_error", "nan_logits",
                         "slot_error", "cache_error", "tier_drop"})

#: fleet-tier fault kinds the FleetRouter polls for (ISSUE 9); rejected by
#: the engine's own parse — a replica-scoped clause with no fleet running
#: would be a silent no-op
REPLICA_KINDS = frozenset({"replica_crash", "replica_stall", "replica_slow"})

#: clause keys the grammar accepts (``replica`` is fleet-only, same contract)
KNOWN_KEYS = frozenset({"step", "slot", "rid", "count", "p", "seed"})


@dataclasses.dataclass
class FaultClause:
    """One parsed clause of a fault plan.  ``count`` is decremented per
    firing; 0 means exhausted (-1 never exhausts)."""

    kind: str
    step: int | None = None
    slot: int | None = None
    rid: int | None = None
    replica: int | None = None
    count: int = 1
    p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        # private seeded stream per clause: probabilistic firing stays
        # replayable and independent of every other clause's draw order
        self._rng = np.random.RandomState(self.seed)

    def matches(self, kind: str, step, slot, rid, replica=None) -> bool:
        if self.kind != kind or self.count == 0:
            return False
        if self.step is not None and step != self.step:
            return False
        if self.slot is not None and slot != self.slot:
            return False
        if self.rid is not None and rid != self.rid:
            return False
        if self.replica is not None and replica != self.replica:
            return False
        return True


class FaultPlan:
    """The engine-facing injector: ``fire(kind, ...)`` at a seam returns True
    when a clause matches (and consumes one firing).  An empty plan is inert
    and free — the hot-loop polls short-circuit on ``self._clauses``."""

    def __init__(self, clauses=()):
        self._clauses = [c if isinstance(c, FaultClause) else FaultClause(**c)
                         for c in clauses]

    @classmethod
    def from_env(cls, fleet: bool = False) -> "FaultPlan":
        """Parse ``PADDLE_TPU_FAULT_INJECT`` (validated; malformed specs warn
        once and disable injection — utils/envflags.py).  ``fleet=True``
        (the FleetRouter's parse) admits the replica-scoped vocabulary —
        the ``replica_*`` kinds and the ``replica`` clause key; the default
        engine parse REJECTS those with a warning naming the fleet
        requirement, because a replica-scoped clause polled by nobody would
        make a chaos run's evidence silently incomplete."""
        from ..utils.envflags import env_fault_spec

        if fleet:
            return cls(env_fault_spec("PADDLE_TPU_FAULT_INJECT",
                                      KNOWN_KINDS | REPLICA_KINDS,
                                      KNOWN_KEYS | {"replica"}))
        return cls(env_fault_spec("PADDLE_TPU_FAULT_INJECT", KNOWN_KINDS,
                                  KNOWN_KEYS,
                                  fleet_only_kinds=REPLICA_KINDS,
                                  fleet_only_keys=frozenset({"replica"})))

    def __bool__(self) -> bool:
        return bool(self._clauses)

    def fire(self, kind: str, *, step: int | None = None,
             slot: int | None = None, rid: int | None = None,
             replica: int | None = None) -> bool:
        """Poll one seam: True exactly when a clause matches and fires.
        Polling order is the engine's deterministic scan order (the fleet's
        is replica-index order), so a clause with an omitted ``slot`` /
        ``replica`` fires on the first matching poll — the plan stays
        replayable without pinning every key."""
        if not self._clauses:
            return False
        for c in self._clauses:
            if not c.matches(kind, step, slot, rid, replica):
                continue
            if c.p < 1.0 and float(c._rng.random_sample()) >= c.p:
                continue
            if c.count > 0:
                c.count -= 1
            return True
        return False
