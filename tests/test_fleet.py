"""Fleet-serving tests (ISSUE 9, docs/fleet_serving.md).

The correctness bar: killing (or stalling) one of N replicas mid-serve
yields output streams token-identical to the same workload on an
UNINTERRUPTED fleet, for every request the fleet had accepted — greedy AND
seeded sampled, with prefix cache, speculation, chunked prefill and
graceful mode all ON — and ``PADDLE_TPU_FAULT_INJECT`` replays the exact
same failure deterministically.  Every chaos run executes under
``PADDLE_TPU_ENGINE_AUDIT=1`` (each replica audits I1–I8 after its own
steps, the router audits I9 after every fleet step) and re-audits every
surviving replica explicitly at the end.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis.engine_audit import (EngineAuditError, audit_engine,
                                              audit_fleet)
from paddle_tpu.inference.faults import FaultPlan
from paddle_tpu.inference.fleet import FleetRouter
from paddle_tpu.inference.serving import (ContinuousBatchingEngine, Request,
                                          TERMINAL_STATUSES)
from paddle_tpu.models import llama


def _tiny():
    cfg = llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                 kv_heads=2, inter=64)
    cfg.dtype = jnp.float32  # exact parity
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


#: plain paged replicas — routing/backpressure/audit tests that need no
#: cache/spec/chunked programs keep compile time down
_PLAIN = dict(max_batch=2, max_seq=64, chunk=1, paged=True, block_size=8)

#: the acceptance-criterion engine: every serving feature ON
_FULL = dict(max_batch=2, max_seq=64, chunk=1, paged=True, block_size=8,
             enable_prefix_caching=True, enable_speculation=True,
             num_draft_tokens=3, enable_chunked_prefill=True,
             prefill_chunk=8, num_blocks=16)


def _mixed_batch(seed, n=3, prompt_len=11, new=6, shared=None):
    """Half greedy, half seeded temperature+top-p sampled; with ``shared``
    the prompts extend one self-similar base (prefix-cache hits AND n-gram
    drafter proposals)."""
    rs = np.random.RandomState(seed)
    base = shared if shared is not None else None
    reqs = []
    for i in range(n):
        if base is not None:
            p = np.tile(base, 4)[:prompt_len + i].astype(np.int32)
        else:
            p = rs.randint(0, 128, (prompt_len + i,)).astype(np.int32)
        kw = (dict(temperature=0.8, top_p=0.9, seed=7 + i) if i % 2
              else {})
        reqs.append(Request(rid=i, prompt_ids=p, max_new_tokens=new, **kw))
    return reqs


def _audit_survivors(fleet):
    """Every surviving replica's I1–I8 plus the router's I9 — the
    after-each-chaos-round green bar."""
    for eng in fleet.replicas:
        if eng is not None:
            audit_engine(eng)
    audit_fleet(fleet)


def _chaos_fleet(monkeypatch, spec, n_replicas=2, **kw):
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", spec)
    fleet = FleetRouter(cfg, params, n_replicas=n_replicas, **kw)
    monkeypatch.delenv("PADDLE_TPU_FAULT_INJECT")
    return fleet


def _reference_fleet(reqs, monkeypatch=None, n_replicas=2, **kw):
    """Uninterrupted-fleet reference (chaos env must not leak in)."""
    if monkeypatch is not None:
        monkeypatch.delenv("PADDLE_TPU_FAULT_INJECT", raising=False)
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=n_replicas, **kw)
    return fleet.serve(reqs)


# ---------------- routing (pillar 1) ----------------

def test_fleet_parity_with_single_engine(monkeypatch):
    """A fault-free fleet emits exactly the single-engine streams (each
    request's stream depends only on its own (seed, position) keys, never
    on which replica computed it) and every request lands terminal with a
    fleet-level TTFT stamped."""
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, **_PLAIN)
    ref = eng.serve(_mixed_batch(0))
    fleet = FleetRouter(cfg, params, n_replicas=2, **_PLAIN)
    reqs = _mixed_batch(0)
    got = fleet.serve(reqs)
    assert got == ref
    assert all(r.status == "FINISHED" for r in reqs)
    assert all(r.ttft_s is not None for r in reqs)
    assert fleet.stats["routed_spill"] == len(reqs)  # nothing cached yet
    assert fleet._reqs == {} and fleet._owner == {}  # live registries prune
    _audit_survivors(fleet)


def test_routing_affinity_hot_prefix(monkeypatch):
    """A prompt whose prefix chain is cached on one replica routes THERE,
    even when another replica is strictly less loaded — reusing resident
    KV beats rebalancing."""
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    kw = dict(_PLAIN, enable_prefix_caching=True)
    fleet = FleetRouter(cfg, params, n_replicas=2, **kw)
    rs = np.random.RandomState(1)
    prefix = rs.randint(0, 128, (17,)).astype(np.int32)  # 2 full blocks
    warm = Request(rid=0, prompt_ids=prefix, max_new_tokens=2)
    fleet.serve([warm])
    holder = 0  # least-loaded tie broke to the lowest index
    assert fleet.replicas[holder]._pcache.resident_blocks() >= 2
    # load the chain holder with an unrelated live request: spill would
    # now prefer replica 1, affinity must still pick the holder
    filler = Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                     .astype(np.int32), max_new_tokens=30)
    fleet.add_request(filler)
    assert fleet._owner[1] == holder
    hot = Request(rid=2,
                  prompt_ids=np.concatenate([prefix, rs.randint(
                      0, 128, (6,)).astype(np.int32)]),
                  max_new_tokens=3)
    fleet.add_request(hot)
    assert fleet._owner[2] == holder
    assert fleet.stats["routed_affinity"] == 1
    while fleet.step():
        pass
    assert hot.status == "FINISHED"
    _audit_survivors(fleet)


def test_routing_spill_on_overload(monkeypatch):
    """When the chain-holding replica's queue is full, the hot request
    spills to the least-loaded routable replica instead of queueing behind
    the wall (and instead of being rejected)."""
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    kw = dict(_PLAIN, max_batch=1, max_queue=1,
              enable_prefix_caching=True)
    fleet = FleetRouter(cfg, params, n_replicas=2, **kw)
    rs = np.random.RandomState(2)
    prefix = rs.randint(0, 128, (17,)).astype(np.int32)
    fleet.serve([Request(rid=0, prompt_ids=prefix, max_new_tokens=2)])
    # fill the chain holder (replica 0): seat one filler per replica, then
    # queue a third on 0 — its queue hits max_queue while 1's stays empty
    for rid in (1, 2):
        fleet.add_request(Request(rid=rid, prompt_ids=rs.randint(
            0, 128, (9,)).astype(np.int32), max_new_tokens=30))
        fleet.step()                       # seat it (queues drain at step)
    fleet.add_request(Request(rid=3, prompt_ids=rs.randint(
        0, 128, (9,)).astype(np.int32), max_new_tokens=30))
    assert fleet._owner[1] == 0 and fleet._owner[2] == 1
    assert fleet._owner[3] == 0            # tie broke to the lowest index
    assert fleet._full(0) and not fleet._full(1)
    hot = Request(rid=4, prompt_ids=np.concatenate(
        [prefix, rs.randint(0, 128, (6,)).astype(np.int32)]),
        max_new_tokens=2)
    spills = fleet.stats["routed_spill"]
    fleet.add_request(hot)
    assert fleet._owner[4] == 1                      # spilled off the chain
    assert fleet.stats["routed_spill"] == spills + 1
    while fleet.step():
        pass
    assert hot.status == "FINISHED"
    _audit_survivors(fleet)


def test_fleet_backpressure_rejected_accounting(monkeypatch):
    """Every routable replica full -> the FLEET sheds the newcomer as
    REJECTED (with error), counted in stats — and sheds nothing that was
    already accepted."""
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=2,
                        **dict(_PLAIN, max_batch=1, max_queue=1))
    rs = np.random.RandomState(3)
    reqs = [Request(rid=i, prompt_ids=rs.randint(0, 128, (9,))
                    .astype(np.int32), max_new_tokens=3)
            for i in range(6)]
    got = fleet.serve(reqs)
    # capacity at submission (no step has drained a queue yet): one queued
    # request per replica = 2 accepted, 4 shed at the FLEET level
    shed = [r for r in reqs if r.status == "REJECTED"]
    assert len(shed) == 4
    assert all("queue is full" in r.error for r in shed)
    assert fleet.stats["fleet_rejected"] == 4
    served = [r for r in reqs if r.status == "FINISHED"]
    assert len(served) == 2 and all(len(got[r.rid]) == 3 for r in served)
    _audit_survivors(fleet)


def test_invalid_request_rejected_not_raised(monkeypatch):
    """The graceful-serve contract, fleet edition: a bad request is shed
    as REJECTED at the router, the good ones serve."""
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=2, **_PLAIN)
    rs = np.random.RandomState(4)
    good = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                   .astype(np.int32), max_new_tokens=3)
    bad = Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), temperature=float("nan"))
    got = fleet.serve([good, bad])
    assert good.status == "FINISHED" and len(got[0]) == 3
    assert bad.status == "REJECTED" and "finite" in bad.error


# ---------------- failover (pillar 2): the acceptance criterion ----------

def test_failover_token_identity_mid_decode(monkeypatch):
    """Kill one of two FULL-FEATURE replicas mid-decode: survivors keep
    streaming, the dead replica's journal replays onto the survivor, and
    EVERY accepted request's stream — greedy and seeded sampled — is
    token-identical to the uninterrupted fleet.  The same env spec replays
    the same failure deterministically."""
    shared = np.random.RandomState(5).randint(0, 128, (8,)).astype(np.int32)
    ref = _reference_fleet(_mixed_batch(5, prompt_len=17, new=8,
                                        shared=shared),
                           monkeypatch, **_FULL)
    spec = "replica_crash@step=7,replica=0"
    runs = []
    for _ in range(2):                     # determinism: replay the chaos
        fleet = _chaos_fleet(monkeypatch, spec, **_FULL)
        reqs = _mixed_batch(5, prompt_len=17, new=8, shared=shared)
        got = fleet.serve(reqs)
        assert fleet.stats["failovers"] == 1
        assert fleet.health[0] == "DEAD" and fleet.replicas[0] is None
        assert all(r.status == "FINISHED" for r in reqs)
        assert got == ref
        _audit_survivors(fleet)
        runs.append((got, dict(fleet.stats)))
    assert runs[0] == runs[1]              # exactly replayable


def test_failover_token_identity_mid_prefill_chunk(monkeypatch):
    """Kill the replica while a long prompt is mid-chunked-prefill (its
    journal carries a nonzero prefill cursor): the replay re-prefills on
    the survivor and the completed stream still matches the uninterrupted
    fleet byte-for-byte."""
    def build():
        rs = np.random.RandomState(6)
        return [Request(rid=0, prompt_ids=rs.randint(0, 128, (40,))
                        .astype(np.int32), max_new_tokens=6,
                        temperature=0.6, seed=3),
                Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                        .astype(np.int32), max_new_tokens=6)]

    ref = _reference_fleet(build(), monkeypatch, **_FULL)
    fleet = _chaos_fleet(monkeypatch, "replica_crash@step=3,replica=0",
                         **_FULL)
    reqs = build()
    for r in reqs:
        fleet.add_request(r)
    assert fleet._owner[0] == 0            # the long prompt sits on victim
    for _ in range(2):
        fleet.step()
    # genuinely mid-prefill on the victim at the crash step (40-token
    # prompt, 8-token chunks) — the journal's cursor is set
    eng0 = fleet.replicas[0]
    assert eng0._prefill_ids[0] is not None
    # (the async host runtime pulls the router's copy only when a death or
    # a hedge consumes it, so read the replica's own journal)
    assert eng0.journal()["running"][0]["prefilled"] > 0
    while fleet.step():
        pass
    assert fleet.stats["failovers"] == 1
    assert all(r.status == "FINISHED" for r in reqs)
    assert {r.rid: r.output_ids for r in reqs} == ref
    _audit_survivors(fleet)


def test_failover_replay_exempt_from_backpressure(monkeypatch):
    """Replayed journal entries are ACCEPTED work: they land on a survivor
    whose queue is full (where a fresh add_request would be rejected)."""
    kw = dict(_PLAIN, max_batch=1, max_queue=1)
    fleet = _chaos_fleet(monkeypatch, "replica_crash@step=4,replica=0",
                         **kw)
    rs = np.random.RandomState(7)
    # rid 0 -> replica 0, rid 1 -> replica 1 (seated by a step), then
    # rid 2 queues on replica 0: the crash replays TWO entries onto
    # replica 1, whose queue blows straight past max_queue=1 — legal,
    # because adopt() exempts accepted work from backpressure
    reqs = [Request(rid=i, prompt_ids=rs.randint(0, 128, (9,))
                    .astype(np.int32), max_new_tokens=6) for i in range(3)]
    fleet.add_request(reqs[0])
    fleet.add_request(reqs[1])
    fleet.step()                           # seat both; queues drain
    fleet.add_request(reqs[2])
    assert fleet._owner[2] == 0
    while fleet.step():
        pass
    got = {r.rid: r.output_ids for r in reqs}
    assert fleet.stats["failovers"] == 1
    assert all(r.status == "FINISHED" for r in reqs)
    assert all(len(got[r.rid]) == 6 for r in reqs)
    _audit_survivors(fleet)


def test_fleet_lost_fails_accepted_work(monkeypatch):
    """Every replica dead -> accepted work terminates FAILED with a
    diagnosis (never hangs, never silently vanishes) and new work is
    REJECTED."""
    fleet = _chaos_fleet(monkeypatch,
                         "replica_crash@replica=0;replica_crash@replica=1",
                         **_PLAIN)
    rs = np.random.RandomState(8)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=6)
    fleet.serve([req])
    assert req.status == "FAILED" and "no surviving replica" in req.error
    late = Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                   .astype(np.int32), max_new_tokens=2)
    fleet.add_request(late)
    assert late.status == "REJECTED" and "DEAD" in late.error
    assert fleet.stats["fleet_rejected"] == 1


# ---------------- stall + hedging (pillar 2) ----------------

def test_hedge_dedup_discards_late_answer(monkeypatch):
    """A transiently-stalled replica's work hedge-dispatches onto the
    survivor; when the primary wakes after the hedge has already won,
    first-writer-wins has cancelled the primary's copy — the late answer
    is discarded, no token is double-banked, and the streams match the
    uninterrupted fleet."""
    shared = np.random.RandomState(9).randint(0, 128, (8,)).astype(np.int32)
    ref = _reference_fleet(_mixed_batch(9, n=2, prompt_len=17, new=8,
                                        shared=shared),
                           monkeypatch, **_FULL)
    # replica 0 stalls for 8 fleet steps from the start, then wakes;
    # stall_steps=3 hedges its request well before that
    fleet = _chaos_fleet(monkeypatch, "replica_stall@replica=0,count=8",
                         stall_steps=3, **_FULL)
    reqs = _mixed_batch(9, n=2, prompt_len=17, new=8, shared=shared)
    got = fleet.serve(reqs)
    assert fleet.stats["hedges"] >= 1
    assert all(r.status == "FINISHED" for r in reqs)
    assert all(len(got[r.rid]) == 8 for r in reqs)   # nothing double-banked
    assert got == ref
    # the stalled replica's copy was cancelled at resolution: it serves
    # nothing now, and the fleet's registries are clean
    assert fleet.replicas[0]._reqs == {}
    assert fleet._hedge == {} and fleet._reqs == {}
    _audit_survivors(fleet)


def test_permanent_stall_escalates_to_dead_never_hangs(monkeypatch):
    """A stall that outlives ``stall_dead_steps`` is crash-equivalent:
    with nobody to hedge onto (a one-replica fleet), the replica is
    declared DEAD and its work terminates FAILED with a diagnosis —
    serve() ends instead of spinning forever (the never-a-hang
    contract)."""
    fleet = _chaos_fleet(monkeypatch, "replica_stall@replica=0,count=-1",
                         n_replicas=1, stall_steps=2, stall_dead_steps=5,
                         **_PLAIN)
    rs = np.random.RandomState(15)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=6)
    fleet.serve([req])                     # must TERMINATE
    assert fleet.health[0] == "DEAD"
    assert fleet.stats["failovers"] == 1
    assert req.status == "FAILED" and "no surviving replica" in req.error
    assert "stalled for" in req.error


def test_stall_dead_steps_must_exceed_stall_steps():
    cfg, params = _tiny()
    with pytest.raises(ValueError, match="stall_dead_steps"):
        FleetRouter(cfg, params, n_replicas=1, stall_steps=5,
                    stall_dead_steps=5, **_PLAIN)


def test_stall_degrades_then_heals(monkeypatch):
    """replica_slow heartbeats degrade a replica's health after a streak
    and a clean streak heals it back to HEALTHY."""
    fleet = _chaos_fleet(monkeypatch, "replica_slow@replica=0,count=3",
                         slow_after=2, heal_after=2, **_PLAIN)
    rs = np.random.RandomState(10)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=20)
    fleet.add_request(req)
    seen = set()
    while fleet.step():
        seen.add(fleet.health[0])
    assert "DEGRADED" in seen                        # the slow streak
    assert fleet.health[0] == "HEALTHY"              # healed by the end
    assert req.status == "FINISHED"
    _audit_survivors(fleet)


# ---------------- draining ----------------

def test_draining_accepts_no_new_work_finishes_inflight(monkeypatch):
    """drain(r): in-flight work on the draining replica runs to
    completion, new work routes elsewhere."""
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=2, **_PLAIN)
    rs = np.random.RandomState(11)
    inflight = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                       .astype(np.int32), max_new_tokens=12)
    fleet.add_request(inflight)
    assert fleet._owner[0] == 0
    fleet.step()
    fleet.drain(0)
    assert fleet.health[0] == "DRAINING"
    newcomers = [Request(rid=1 + i, prompt_ids=rs.randint(0, 128, (9,))
                         .astype(np.int32), max_new_tokens=4)
                 for i in range(3)]
    for r in newcomers:
        fleet.add_request(r)
    assert all(fleet._owner[r.rid] == 1 for r in newcomers)
    while fleet.step():
        pass
    assert inflight.status == "FINISHED"             # finished WHERE it was
    assert len(inflight.output_ids) == 12
    assert all(r.status == "FINISHED" for r in newcomers)
    assert fleet.health[0] == "DRAINING"             # an operator decision
    _audit_survivors(fleet)


def test_fully_drained_fleet_rejection_names_drain(monkeypatch):
    """Rejection diagnosis must name the real cause: a fully-drained
    fleet is not 'backpressure' — the operator should be pointed at their
    own drain(), not at max_queue."""
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=2, **_PLAIN)
    fleet.drain(0)
    fleet.drain(1)
    rs = np.random.RandomState(16)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=2)
    fleet.add_request(req)
    assert req.status == "REJECTED"
    assert "DRAINING" in req.error and "queue is full" not in req.error


def test_drain_dead_replica_raises(monkeypatch):
    fleet = _chaos_fleet(monkeypatch, "replica_crash@step=1,replica=0",
                         **_PLAIN)
    fleet.step()
    with pytest.raises(ValueError, match="DEAD"):
        fleet.drain(0)


# ---------------- audit I9: fleet single ownership ----------------

def _live_fleet(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_FAULT_INJECT", raising=False)
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=2, **_PLAIN)
    rs = np.random.RandomState(12)
    for i in range(2):
        fleet.add_request(Request(rid=i, prompt_ids=rs.randint(0, 128, (9,))
                                  .astype(np.int32), max_new_tokens=20))
    fleet.step()
    audit_fleet(fleet)                     # healthy mid-serve state
    return fleet


def test_audit_i9_orphan_without_owner(monkeypatch):
    fleet = _live_fleet(monkeypatch)
    del fleet._owner[0]                    # corrupt: live rid, no owner
    with pytest.raises(EngineAuditError, match="I9"):
        audit_fleet(fleet)


def test_audit_i9_double_ownership(monkeypatch):
    fleet = _live_fleet(monkeypatch)
    # corrupt: adopt rid 0's journal onto the OTHER replica with no hedge
    # record — one stream would bank twice
    other = 1 - fleet._owner[0]
    entry = fleet._journal_entry(fleet._owner[0], 0)
    copy = fleet.replicas[other].adopt(entry)
    fleet._copies[0][other] = copy
    with pytest.raises(EngineAuditError, match="I9"):
        audit_fleet(fleet)


def test_audit_i9_replica_serving_unrouted_rid(monkeypatch):
    fleet = _live_fleet(monkeypatch)
    # corrupt: the copy exists on the engine but the router forgot it
    owner = fleet._owner[0]
    del fleet._copies[0][owner]
    with pytest.raises(EngineAuditError, match="I9"):
        audit_fleet(fleet)


def test_audit_i9_terminal_zombie_in_registry(monkeypatch):
    fleet = _live_fleet(monkeypatch)
    fleet._reqs[0].status = "FAILED"       # corrupt: terminal but live
    with pytest.raises(EngineAuditError, match="I9"):
        audit_fleet(fleet)


def test_audit_i9_hedge_onto_owner(monkeypatch):
    fleet = _live_fleet(monkeypatch)
    fleet._hedge[0] = fleet._owner[0]      # corrupt: self-hedge
    with pytest.raises(EngineAuditError, match="I9"):
        audit_fleet(fleet)


def test_audit_i9_leaked_copy_of_terminal_rid(monkeypatch):
    """A replica-local copy left registered for a rid that is no longer a
    live fleet request pins its token lists forever — I9 sweeps _copies,
    not just the owner and hedge maps."""
    fleet = _live_fleet(monkeypatch)
    stale = fleet._copies[0][fleet._owner[0]]
    fleet.cancel(0)                        # terminal: registries pruned
    audit_fleet(fleet)
    fleet._copies[0] = {0: stale}          # corrupt: the copy leaks back
    with pytest.raises(EngineAuditError, match="I9"):
        audit_fleet(fleet)


# ---------------- chaos grammar scope (satellite) ----------------

def test_replica_clause_requires_fleet(monkeypatch):
    """A replica-scoped clause with NO fleet running: the engine's parse
    warns once naming the fleet requirement, injection disables entirely,
    and the engine serves normally — never a silent no-op, never a
    crash."""
    from paddle_tpu.utils import envflags
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT",
                       "replica_crash@step=2,replica=0;alloc_fail@step=3")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="FleetRouter"):
        plan = FaultPlan.from_env()
    assert not plan                        # the WHOLE plan is disabled
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, **_PLAIN)
    rs = np.random.RandomState(13)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=3)
    got = eng.serve([req])
    assert req.status == "FINISHED" and len(got[0]) == 3


def test_replica_key_requires_fleet(monkeypatch):
    """Same contract for the ``replica=`` clause key on an engine-scoped
    kind: without a fleet, the scope could never match."""
    from paddle_tpu.utils import envflags
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "alloc_fail@replica=1")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="FleetRouter"):
        assert not FaultPlan.from_env()


def test_fleet_partitions_mixed_spec(monkeypatch):
    """A mixed spec arms the router with the replica-scoped clauses and
    fans engine-scoped clauses out to the replicas — ``replica=k`` scopes
    one to a single replica's engine."""
    fleet = _chaos_fleet(
        monkeypatch,
        "replica_crash@step=99,replica=0;"
        "slot_error@rid=1,step=2,replica=1;"
        "cache_error@step=5",
        **_PLAIN)
    assert len(fleet._faults._clauses) == 1
    assert fleet._faults._clauses[0].kind == "replica_crash"
    kinds0 = [c.kind for c in fleet.replicas[0]._faults._clauses]
    kinds1 = [c.kind for c in fleet.replicas[1]._faults._clauses]
    assert kinds0 == ["cache_error"]       # unscoped clause fans out
    assert kinds1 == ["slot_error", "cache_error"]
    # the stripped replica scope must not linger on the engine clause
    assert all(c.replica is None for c in fleet.replicas[1]._faults._clauses)


def test_valid_fleet_spec_does_not_warn(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT",
                       "replica_stall@replica=1,count=4,p=0.5,seed=3")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = FaultPlan.from_env(fleet=True)
    assert bool(plan)


# ---------------- fleet-level cancel ----------------

def test_fleet_cancel_cancels_every_copy(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=2, **_PLAIN)
    rs = np.random.RandomState(14)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=50)
    fleet.add_request(req)
    for _ in range(3):
        fleet.step()
    assert fleet.cancel(0) is True
    assert req.status == "CANCELLED"
    assert len(req.output_ids) > 0                   # partial output stays
    assert fleet.cancel(0) is False                  # already terminal
    assert fleet.cancel(99) is False                 # unknown rid
    assert fleet.step() is False                     # drained, not wedged
    _audit_survivors(fleet)
