"""Async host runtime tests (ISSUE 16, docs/async_runtime.md).

The correctness bar: the incremental journal every step family keeps up
inside its ``_host_overlap()`` window equals a fresh ``snapshot()`` at every
intermediate state, and fleet failover under injected ``replica_crash``
chaos replays from that journal (the router rebuilds no snapshot),
token-identically greedy AND seeded with prefix cache + speculation +
chunked prefill all ON, under ``PADDLE_TPU_ENGINE_AUDIT=1``'s per-step
journal-vs-snapshot equivalence assert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.fleet import FleetRouter
from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.models import llama

_CFG = llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                              kv_heads=2, inter=64)
_CFG.dtype = jnp.float32  # exact parity
_PARAMS = None


def _tiny():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = llama.init_params(_CFG, jax.random.key(0))
    return _CFG, _PARAMS


#: the acceptance-criterion engine: every serving feature ON
_FULL = dict(max_batch=2, max_seq=64, chunk=1, paged=True, block_size=8,
             enable_prefix_caching=True, enable_speculation=True,
             num_draft_tokens=3, enable_chunked_prefill=True,
             prefill_chunk=8, num_blocks=16)


def _mixed_batch(seed, n=4, prompt_len=11, new=6):
    """Half greedy, half seeded temperature+top-p, prompts extending one
    self-similar base (prefix-cache hits AND n-gram drafter food)."""
    rs = np.random.RandomState(seed)
    base = np.arange(8, dtype=np.int32)
    reqs = []
    for i in range(n):
        p = np.concatenate([np.tile(base, 3)[:prompt_len],
                            rs.randint(0, 128, (i + 1,)).astype(np.int32)])
        kw = (dict(temperature=0.8, top_p=0.9, seed=7 + i) if i % 2
              else {})
        reqs.append(Request(rid=i, prompt_ids=p, max_new_tokens=new, **kw))
    return reqs


# ---------------- journal-vs-snapshot equivalence ----------------

def _norm(d):
    return {**d, "running": [dict(e, deadline_remaining_s=None)
                             for e in d["running"]],
            "queued": [dict(e, deadline_remaining_s=None)
                       for e in d["queued"]]}


#: one engine per step family: the counter says its launch path ran
_FAMILIES = {
    "decode": (dict(chunk=2, enable_chunked_prefill=False,
                    enable_speculation=False), "decode_steps"),
    "mixed": (dict(enable_speculation=False), "mixed_steps"),
    "spec": (dict(enable_chunked_prefill=False), "spec_steps"),
}


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_journal_equals_snapshot_mid_serve(family):
    """The incremental journal and a fresh full ``snapshot()`` agree at
    every intermediate state — queued, seating, mid-chunk prefill,
    tokens banked (``deadline_remaining_s`` normalized: both sides
    recompute it lazily at their own read instants) — whichever of the
    three step families (``_step``, ``_mixed_step``, ``_spec_step``) does
    the banking."""
    kw, counter = _FAMILIES[family]
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, **dict(_FULL, **kw))
    for r in _mixed_batch(2, n=4):
        eng.add_request(r)
    assert _norm(eng.journal()) == _norm(eng.snapshot())  # all queued
    while eng.step():
        assert _norm(eng.journal()) == _norm(eng.snapshot())
    assert eng.journal()["running"] == eng.journal()["queued"] == []
    assert eng.stats[counter] > 0
    assert eng.stats["host_overlap_steps"] > 0
    assert eng.stats["journal_incremental_updates"] > 0


def test_fleet_audit_catches_journal_divergence(monkeypatch):
    """The per-step equivalence audit is live: corrupt one incremental
    entry and the next audited fleet step raises EngineAuditError."""
    from paddle_tpu.analysis.engine_audit import EngineAuditError

    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    fleet = FleetRouter(cfg, params, n_replicas=2, **_FULL)
    fleet.add_request(Request(rid=0, prompt_ids=np.arange(
        11, dtype=np.int32), max_new_tokens=8))
    fleet.step()                        # audited: equivalence holds
    r = fleet._owner[0]
    eng = fleet.replicas[r]
    eng.journal()                       # flush, then corrupt the entry
    eng._jentries[0] = dict(eng._jentries[0], output_ids=[999])
    # no step in between: a step would re-mark the rid dirty and the
    # flush would lawfully rebuild the entry (the journal self-heals
    # from events; the audit exists for entries events MISSED)
    with pytest.raises(EngineAuditError, match="diverged"):
        fleet._audit_journal_equiv(r)


# ---------------- fleet: steady state + chaos failover ----------------

def test_fleet_failover_token_identity_via_incremental_journal(
        monkeypatch):
    """replica_crash mid-serve with the per-step equivalence audit:
    every accepted request's stream is token-identical to an
    uninterrupted fleet's, and the replay consumed the INCREMENTAL
    journal — one boundary pull.  The uninterrupted reference doubles as
    the steady-state assert: a fault-free fleet never has a replica
    rebuild a snapshot."""
    cfg, params = _tiny()
    ref_reqs = _mixed_batch(4, new=8)
    ref_fleet = FleetRouter(cfg, params, n_replicas=2, **_FULL)
    ref = ref_fleet.serve(ref_reqs)
    assert ref_fleet.stats["host_overlap_steps"] > 0
    assert ref_fleet.stats["journal_incremental_updates"] == 0
    assert sum(e.stats["journal_full_rebuilds"]
               for e in ref_fleet.replicas) == 0
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT",
                       "replica_crash@step=3,replica=0")
    fleet = FleetRouter(cfg, params, n_replicas=2, **_FULL)
    monkeypatch.delenv("PADDLE_TPU_FAULT_INJECT")
    reqs = _mixed_batch(4, new=8)
    got = fleet.serve(reqs)
    assert got == ref
    assert all(r.status == "FINISHED" for r in reqs)
    assert fleet.stats["failovers"] == 1
    assert fleet.stats["journal_incremental_updates"] >= 1  # death pull
    assert fleet.health.count("DEAD") == 1


def test_journal_counters_in_schemas():
    from paddle_tpu.inference.observability import (ENGINE_STAT_SCHEMA,
                                                    FLEET_STAT_SCHEMA)

    for schema in (ENGINE_STAT_SCHEMA, FLEET_STAT_SCHEMA):
        for key in ("journal_incremental_updates", "host_overlap_steps"):
            assert key in schema
    # snapshot() is the engine's; the router pulls journals and builds none
    assert "journal_full_rebuilds" in ENGINE_STAT_SCHEMA
    assert "journal_full_rebuilds" not in FLEET_STAT_SCHEMA
