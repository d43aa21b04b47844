"""Hierarchical KV host tier tests (ISSUE 13 acceptance; docs/kv_tier.md).

The correctness bar: the tier only ever changes WHO produces a block's
bytes (H2D restore vs prefill compute), never WHICH bytes — so tier-on
token streams must be identical to tier-off for greedy AND seeded
sampling with every serving feature on, the demote→re-admit transport
must be byte-exact per page (fp and quantized-with-scales payloads), the
byte budget must bound the store, invariant I10 must hold across the
suites and fail loudly under injected corruption, and a vanished tier
entry (``tier_drop`` chaos) must degrade to ordinary prefill — never a
hang, never corruption."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.inference.kv_tier import HostKVTier
from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.models import llama


def _tiny():
    cfg = llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                 kv_heads=2, inter=64)
    cfg.dtype = jnp.float32  # exact parity
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


_ALL_ON = dict(max_batch=2, max_seq=64, chunk=1, paged=True, block_size=8,
               num_blocks=8, enable_prefix_caching=True,
               enable_speculation=True, enable_chunked_prefill=True,
               prefill_chunk=5)


def _pressure_reqs(seed=7, sampled=False):
    """Two 16-token (2 full 8-blocks) prefix families over a pool far
    smaller than the working set: evictions — hence demotions — are
    guaranteed, and revisits exercise the tier match."""
    rs = np.random.RandomState(seed)
    fam = [rs.randint(0, 128, (16,)).astype(np.int32) for _ in range(2)]
    tails = [rs.randint(0, 128, (n,)).astype(np.int32)
             for n in (6, 9, 5, 8, 7, 4)]
    return [Request(rid=i, prompt_ids=np.concatenate([fam[i % 2], t]),
                    max_new_tokens=8,
                    temperature=0.9 if sampled and i % 2 else 0.0,
                    top_p=0.9 if sampled else 1.0,
                    seed=40 + i if sampled else None)
            for i, t in enumerate(tails)]


# ---------------- transport unit tests (ship_out / ship_in) ----------------

def test_ship_roundtrip_byte_equality_fp_and_quant():
    """The transport contract: demote→re-admit is byte-exact per page for
    fp payloads AND dequant-on-read pools shipping per-page scales —
    the property ROADMAP item 1's prefill/decode shipping consumes."""
    rs = np.random.RandomState(0)
    tier = HostKVTier(budget_bytes=1 << 20)
    # fp page: [L, nkv, bs, hd]
    k = rs.randn(2, 2, 8, 16).astype(np.float32)
    v = rs.randn(2, 2, 8, 16).astype(np.float32)
    assert tier.ship_out("fp", k, v) is not None
    e = tier.ship_in("fp")
    assert e is not None
    assert e.k.tobytes() == k.tobytes() and e.v.tobytes() == v.tobytes()
    assert e.k_scale is None and e.v_scale is None
    # private tier: ship_in MOVED the entry (I10 exactly-one home)
    assert "fp" not in tier and len(tier) == 0
    # int8 page with per-page scales
    k8 = rs.randint(-128, 128, (2, 2, 8, 16)).astype(np.int8)
    v8 = rs.randint(-128, 128, (2, 2, 8, 16)).astype(np.int8)
    ks = rs.rand(2, 2).astype(np.float32)
    vs = rs.rand(2, 2).astype(np.float32)
    tier.ship_out("i8", k8, v8, k_scale=ks, v_scale=vs)
    e8 = tier.ship_in("i8")
    assert e8.k.tobytes() == k8.tobytes()
    assert e8.k_scale.tobytes() == ks.tobytes()
    assert e8.v_scale.tobytes() == vs.tobytes()
    # packed-int4 page (int8 storage, half head_dim) + scales
    k4 = rs.randint(-128, 128, (2, 2, 8, 8)).astype(np.int8)
    v4 = rs.randint(-128, 128, (2, 2, 8, 8)).astype(np.int8)
    tier.ship_out("i4", k4, v4, k_scale=ks, v_scale=vs)
    e4 = tier.ship_in("i4")
    assert e4.k.tobytes() == k4.tobytes()
    assert e4.v.tobytes() == v4.tobytes()
    assert e4.v_scale.tobytes() == vs.tobytes()
    # device arrays ship too (np.asarray IS the D2H)
    kd = jnp.asarray(k)
    tier.ship_out("dev", kd, v)
    ed = tier.ship_in("dev")
    assert ed.k.tobytes() == k.tobytes()


def test_byte_budget_lru_bounds_and_pins():
    rs = np.random.RandomState(1)
    page = rs.randn(1, 1, 8, 16).astype(np.float32)     # 512 B per slab
    per_entry = 2 * page.nbytes                         # k + v
    tier = HostKVTier(budget_bytes=3 * per_entry)
    for i in range(5):
        assert tier.ship_out(f"h{i}", page, page) is not None
        assert tier.used_bytes <= tier.budget_bytes
    # LRU kept the 3 newest
    assert len(tier) == 3 and tier.evictions == 2
    assert "h0" not in tier and "h1" not in tier and "h4" in tier
    # a pinned entry survives pressure; unpinned ones around it evict
    tier.pin("h2")
    for i in range(5, 9):
        tier.ship_out(f"h{i}", page, page)
    assert "h2" in tier, "pinned entry was LRU-evicted"
    assert tier.used_bytes <= tier.budget_bytes
    # an entry bigger than the whole budget is refused (block goes dead)
    big = rs.randn(64, 1, 8, 16).astype(np.float32)
    assert tier.ship_out("huge", big, big) is None
    assert tier.drops == 1
    # pins block eviction: with the budget fully held by pinned entries,
    # inserts are refused rather than blowing the budget
    full = HostKVTier(budget_bytes=2 * per_entry)
    full.ship_out("p0", page, page)
    full.ship_out("p1", page, page)
    full.pin("p0")
    full.pin("p1")
    assert full.used_bytes == full.budget_bytes
    assert full.ship_out("nofit", page, page) is None
    assert full.used_bytes <= full.budget_bytes
    # discard ignores pins (the tier_drop seam)
    assert full.discard("p0") is True
    assert "p0" not in full


def test_ship_out_copies_slab_views():
    """The engine demotes a BATCH with one gathered D2H and hands the
    tier per-page numpy VIEWS of the slab — the tier must copy, or every
    entry would pin the whole batch slab in host RAM while nbytes counts
    only the slice (review regression: the byte budget must bound actual
    memory, not just accounting)."""
    rs = np.random.RandomState(8)
    slab = rs.randn(2, 5, 2, 8, 16).astype(np.float32)  # [L, n, nkv, bs, hd]
    tier = HostKVTier(budget_bytes=1 << 20)
    e = tier.ship_out("h", slab[:, 1], slab[:, 2])
    assert not np.shares_memory(e.k, slab)
    assert not np.shares_memory(e.v, slab)
    assert e.k.tobytes() == np.ascontiguousarray(slab[:, 1]).tobytes()
    assert e.nbytes == e.k.nbytes + e.v.nbytes


def test_restores_are_paced_by_token_budget():
    """A long demoted chain restores across steps at the token budget's
    pace (one-block floor), not as one burst — and restore-only steps
    keep the serve loop spinning until the plan drains (review
    regression)."""
    cfg, params = _tiny()
    rs = np.random.RandomState(17)
    P = rs.randint(0, 128, (30,)).astype(np.int32)   # 3 full 8-blocks + 6
    kw = dict(max_batch=1, max_seq=64, chunk=1, paged=True, block_size=8,
              num_blocks=8, enable_prefix_caching=True,
              enable_chunked_prefill=True, prefill_chunk=5,
              token_budget=9, enable_host_kv_tier=True)
    eng = ContinuousBatchingEngine(cfg, params, **kw)
    first = eng.serve([Request(rid=0, prompt_ids=P, max_new_tokens=4)])
    # demote the ENTIRE resident chain deterministically (the allocator's
    # own pressure path, just driven to exhaustion): the revisit's plan
    # then spans all 3 full prompt blocks
    eng._reclaim(eng._pcache.resident_blocks())
    assert len(eng._tier) >= 3
    revisit = Request(rid=1, prompt_ids=P, max_new_tokens=4)
    eng.add_request(revisit)
    assert eng.step()                     # admission + first restores
    per_step = [eng.stats["tier_readmits"]]
    while eng._tier_plan[0]:
        assert eng.step(), "restore-only step reported idle mid-plan"
        per_step.append(eng.stats["tier_readmits"])
    # budget 9 tokens / 8-token blocks: the floor banks one block per
    # step — readmits must never jump by the whole plan in one step
    deltas = [b - a for a, b in zip(per_step, per_step[1:])]
    assert all(d <= 1 for d in deltas), (per_step, deltas)
    assert per_step[0] <= 2, per_step     # admission step: floor + budget
    while eng.step() or eng._queue:
        pass
    assert revisit.output_ids == first[0]
    assert eng.stats["tier_readmits"] >= 2


def test_shared_tier_keeps_entries_and_counts_cross_readmits():
    rs = np.random.RandomState(2)
    page = rs.randn(1, 1, 8, 16).astype(np.float32)
    tier = HostKVTier(budget_bytes=1 << 20, shared=True)
    tier.ship_out("h", page, page, owner="0")
    assert tier.ship_in("h", owner="1") is not None
    assert "h" in tier, "shared tier must keep the entry for other replicas"
    assert tier.cross_readmits == 1
    assert tier.ship_in("h", owner="0") is not None
    assert tier.cross_readmits == 1     # same-owner readmit is not cross


# ---------------- engine integration ----------------

def test_tier_on_off_token_identity_greedy_and_seeded():
    """THE acceptance bar: with prefix cache + speculation + chunked
    prefill + graceful all on and a pool small enough to evict
    constantly, tier-on streams are identical to tier-off — greedy AND
    seeded sampled — while demotions actually happened."""
    cfg, params = _tiny()
    for sampled in (False, True):
        off = ContinuousBatchingEngine(cfg, params, **_ALL_ON)
        ref = off.serve(_pressure_reqs(sampled=sampled))
        on = ContinuousBatchingEngine(cfg, params, **_ALL_ON,
                                      enable_host_kv_tier=True)
        got = on.serve(_pressure_reqs(sampled=sampled))
        assert got == ref, f"tier changed tokens (sampled={sampled})"
        assert on.stats["tier_demotions"] > 0, "pressure never demoted"
        assert on.stats["tier_bytes"] >= 0


def test_demote_readmit_roundtrip_through_engine():
    """Deterministic demote→re-admit: serve a 3-block prompt, push its
    chain out of HBM with disjoint traffic, re-serve it — the revisit
    must extend its match through the tier (tier_hits), restore pages H2D
    (tier_readmits) and emit exactly the tokens a fresh engine would."""
    cfg, params = _tiny()
    rs = np.random.RandomState(3)
    P = rs.randint(0, 128, (30,)).astype(np.int32)   # 3 full blocks + 6

    def run(tier: bool):
        eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64,
                                       chunk=1, paged=True, block_size=8,
                                       num_blocks=8,
                                       enable_prefix_caching=True,
                                       enable_chunked_prefill=True,
                                       prefill_chunk=5,
                                       enable_host_kv_tier=tier)
        first = eng.serve([Request(rid=0, prompt_ids=P, max_new_tokens=4)])
        rs2 = np.random.RandomState(4)
        for i in range(3):      # disjoint pressure: evict P's chain
            q = rs2.randint(0, 128, (40,)).astype(np.int32)
            eng.serve([Request(rid=10 + i, prompt_ids=q, max_new_tokens=4)])
        again = eng.serve([Request(rid=1, prompt_ids=P, max_new_tokens=4)])
        return eng, first[0], again[1]

    eng_t, first_t, again_t = run(True)
    eng_o, first_o, again_o = run(False)
    assert first_t == first_o and again_t == again_o
    assert again_t == first_t        # same stream, teacher-forced-free
    assert eng_t.stats["tier_hits"] > 0, "revisit never matched the tier"
    assert eng_t.stats["tier_readmits"] > 0, "no page was restored H2D"
    assert eng_o.stats["tier_readmits"] == 0
    # restored tokens moved from the computed to the cached column
    assert (eng_t.stats["prefill_tokens_computed"]
            < eng_o.stats["prefill_tokens_computed"])
    # h2d histogram observed every restore
    expo = eng_t.metrics.expose()
    assert "paddle_tpu_serving_h2d_restore_seconds_count" in expo
    # flight recorder carries the demote/readmit events
    kinds = {e["kind"] for e in eng_t._flight.events()}
    assert "tier_demote" in kinds and "tier_readmit" in kinds


def test_tier_tp2_token_identity():
    """Tier-on TP=2 must stream the exact tier-off TP=1 tokens (the
    conftest forces an 8-device CPU mesh; the H2D pool write lands on the
    kv_heads-sharded pool through the pinned out_sharding)."""
    cfg, params = _tiny()
    ref = ContinuousBatchingEngine(cfg, params, **_ALL_ON).serve(
        _pressure_reqs(sampled=True))
    tp = ContinuousBatchingEngine(cfg, params, **_ALL_ON,
                                  tensor_parallel=2,
                                  enable_host_kv_tier=True)
    got = tp.serve(_pressure_reqs(sampled=True))
    assert got == ref
    assert tp.stats["tier_demotions"] > 0


def test_tier_drop_chaos_falls_back_to_prefill(monkeypatch):
    """``tier_drop``: every restore attempt finds its entry vanished —
    the engine must fall back to ordinary prefill, finish every request,
    and stream identical tokens (never hang, never corrupt)."""
    cfg, params = _tiny()
    off = ContinuousBatchingEngine(cfg, params, **_ALL_ON)
    ref = off.serve(_pressure_reqs())
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "tier_drop@count=-1")
    on = ContinuousBatchingEngine(cfg, params, **_ALL_ON,
                                  enable_host_kv_tier=True)
    got = on.serve(_pressure_reqs())
    assert got == ref
    assert on.stats["tier_readmits"] == 0, \
        "a dropped entry must never restore"
    assert all(r is None for r in on._slot_req)


def test_fleet_cross_replica_readmit():
    """Fleet acceptance: ONE shared tier — a chain replica 0 computed and
    demoted re-admits on replica 1 (drained affinity forces the cross
    route), with the exact single-engine token stream."""
    from paddle_tpu.inference.fleet import FleetRouter

    cfg, params = _tiny()
    rs = np.random.RandomState(5)
    P = rs.randint(0, 128, (30,)).astype(np.int32)
    kw = dict(max_batch=1, max_seq=64, chunk=1, paged=True, block_size=8,
              num_blocks=8, enable_prefix_caching=True,
              enable_chunked_prefill=True, prefill_chunk=5)
    fl = FleetRouter(cfg, params, n_replicas=2, **kw,
                     enable_host_kv_tier=True)
    first = fl.serve([Request(rid=0, prompt_ids=P, max_new_tokens=4)])
    for i in range(3):          # pressure: demote P's chain to the tier
        q = rs.randint(0, 128, (40,)).astype(np.int32)
        fl.serve([Request(rid=100 + i, prompt_ids=q, max_new_tokens=4)])
    assert fl.host_tier.demotions > 0
    fl.drain(0)                 # affinity broken: the revisit routes to 1
    again = fl.serve([Request(rid=1, prompt_ids=P, max_new_tokens=4)])
    assert again[1] == first[0]
    assert fl.host_tier.cross_readmits > 0, \
        "replica 1 never re-admitted replica 0's chain"
    assert fl.replicas[1].stats["tier_readmits"] > 0


def test_failover_via_tier_parity_vs_teacher_forced():
    """Failover acceptance: a replica crash mid-serve with the shared
    tier streams token-identical output to (a) the same chaos fleet
    WITHOUT the tier (pure teacher-forced replay) and (b) an
    uninterrupted fleet — the tier only accelerates the replay's
    re-prefill, never alters it."""
    import os

    from paddle_tpu.inference.fleet import FleetRouter

    cfg, params = _tiny()

    def run(tier: bool, chaos: bool):
        if chaos:
            os.environ["PADDLE_TPU_FAULT_INJECT"] = \
                "replica_crash@step=6,replica=0"
        try:
            fl = FleetRouter(cfg, params, n_replicas=2, max_batch=2,
                             max_seq=64, chunk=1, paged=True, block_size=8,
                             num_blocks=8, enable_prefix_caching=True,
                             enable_chunked_prefill=True, prefill_chunk=5,
                             enable_host_kv_tier=tier)
        finally:
            os.environ.pop("PADDLE_TPU_FAULT_INJECT", None)
        return fl, fl.serve(_pressure_reqs(seed=9))

    _, ref = run(tier=False, chaos=False)
    _, forced = run(tier=False, chaos=True)
    fl_t, tiered = run(tier=True, chaos=True)
    assert forced == ref, "teacher-forced failover drifted (pre-existing)"
    assert tiered == ref, "tier-assisted failover changed tokens"
    assert fl_t.stats["failovers"] == 1


# ---------------- audit invariant I10 ----------------

def _audited_engine(monkeypatch, **extra):
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, **_ALL_ON,
                                   enable_host_kv_tier=True, **extra)
    return eng


def test_audit_i10_clean_across_serving(monkeypatch):
    eng = _audited_engine(monkeypatch)
    eng.serve(_pressure_reqs())          # audits after every admit + step
    assert eng.stats["tier_demotions"] > 0


def test_audit_i10_corruption_fails_loudly(monkeypatch):
    from paddle_tpu.analysis.engine_audit import (EngineAuditError,
                                                  audit_engine)

    eng = _audited_engine(monkeypatch)
    eng.serve(_pressure_reqs())
    assert len(eng._tier) > 0
    # (a) byte accounting forged
    eng._tier.used_bytes += 1
    with pytest.raises(EngineAuditError, match="I10"):
        audit_engine(eng)
    eng._tier.used_bytes -= 1
    audit_engine(eng)                    # clean again
    # (b) content address forged: entry filed under the wrong key
    h0 = next(iter(eng._tier._by_hash))
    eng._tier._by_hash["deadbeef" * 8] = eng._tier._by_hash.pop(h0)
    with pytest.raises(EngineAuditError, match="I10"):
        audit_engine(eng)
    eng._tier._by_hash[h0] = eng._tier._by_hash.pop("deadbeef" * 8)
    audit_engine(eng)
    # (c) negative pin count (unbalanced unpin)
    eng._tier._by_hash[h0].pins = -1
    with pytest.raises(EngineAuditError, match="I10"):
        audit_engine(eng)
    eng._tier._by_hash[h0].pins = 0
    audit_engine(eng)
    # (d) private-tier exclusivity: a hash resident in BOTH the HBM
    # prefix cache and the private tier breaks move semantics
    resident = next(iter(eng._pcache._by_hash.values()))
    page = np.zeros((2, 2, 8, 8), np.float32)
    eng._tier.ship_out(resident.hash, page, page)
    with pytest.raises(EngineAuditError, match="I10"):
        audit_engine(eng)
    eng._tier.discard(resident.hash)
    audit_engine(eng)


def test_audit_i10_shared_tier_relaxes_exclusivity(monkeypatch):
    """A fleet-shared tier legally holds a hash some replica also has
    HBM-resident (another replica demoted its copy) — the exclusivity
    clause is scoped to private tiers only."""
    from paddle_tpu.analysis.engine_audit import audit_engine

    eng = _audited_engine(monkeypatch)
    eng._tier.shared = True
    eng.serve(_pressure_reqs())
    resident = next(iter(eng._pcache._by_hash.values()))
    page = np.zeros((2, 2, 8, 8), np.float32)
    eng._tier.ship_out(resident.hash, page, page, owner="other")
    audit_engine(eng)                    # no raise: shared-tier semantics


# ---------------- kill switches / env validation ----------------

def test_fleet_kill_switch_drops_explicit_tier(monkeypatch):
    """PADDLE_TPU_HOST_KV_TIER=0 neutralizes the fleet tier TOTALLY: even
    an explicitly-passed tier object is dropped (and left unmutated), so
    `router.host_tier is None` truthfully reads "tier off" (review
    regression)."""
    from paddle_tpu.inference.fleet import FleetRouter

    cfg, params = _tiny()
    mine = HostKVTier(budget_bytes=1 << 20)
    monkeypatch.setenv("PADDLE_TPU_HOST_KV_TIER", "0")
    fl = FleetRouter(cfg, params, n_replicas=2, max_batch=1, max_seq=64,
                     chunk=1, paged=True, block_size=8, num_blocks=8,
                     enable_prefix_caching=True, host_tier=mine)
    assert fl.host_tier is None
    assert mine.shared is False, "kill-switched router mutated the caller's tier"
    assert all(eng._tier is None for eng in fl.replicas)


def test_kill_switch_restores_pre_tier_engine(monkeypatch):
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_HOST_KV_TIER", "0")
    eng = ContinuousBatchingEngine(cfg, params, **_ALL_ON,
                                   enable_host_kv_tier=True)
    assert eng._tier is None            # kill switch wins over the ctor
    assert not hasattr(eng, "_tier_write")
    ref_off = eng.serve(_pressure_reqs())
    monkeypatch.delenv("PADDLE_TPU_HOST_KV_TIER")
    plain = ContinuousBatchingEngine(cfg, params, **_ALL_ON)
    assert plain._tier is None
    assert plain.serve(_pressure_reqs()) == ref_off
    # prefix-cache kill switch neutralizes the tier too (nothing to key on)
    monkeypatch.setenv("PADDLE_TPU_PREFIX_CACHE", "0")
    eng2 = ContinuousBatchingEngine(cfg, params, **_ALL_ON,
                                    enable_host_kv_tier=True)
    assert eng2._tier is None and eng2._pcache is None


def test_ctor_requirements_raise():
    cfg, params = _tiny()
    with pytest.raises(ValueError, match="enable_prefix_caching"):
        ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64,
                                 paged=True, block_size=8, num_blocks=8,
                                 enable_host_kv_tier=True)
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64,
                                 enable_host_kv_tier=True)
    with pytest.raises(ValueError, match="budget_bytes"):
        HostKVTier(budget_bytes=0)


def test_flags_registered_and_typos_warn(monkeypatch):
    from paddle_tpu.utils import envflags
    from paddle_tpu.utils.envflags import BOOL_FLAGS, env_bool, env_int

    assert BOOL_FLAGS["PADDLE_TPU_HOST_KV_TIER"] is True
    monkeypatch.setenv("PADDLE_TPU_HOST_KV_TIER", "off")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="PADDLE_TPU_HOST_KV_TIER"):
        assert env_bool("PADDLE_TPU_HOST_KV_TIER", True) is True
    # the MiB budget knob: non-integer and sub-minimum both warn once and
    # fall back to the default (a typo'd budget must not zero the tier)
    monkeypatch.setenv("PADDLE_TPU_HOST_TIER_MIB", "lots")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="PADDLE_TPU_HOST_TIER_MIB"):
        tier = HostKVTier()
    assert tier.budget_bytes == 256 << 20
    monkeypatch.setenv("PADDLE_TPU_HOST_TIER_MIB", "0")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="below the minimum"):
        tier = HostKVTier()
    assert tier.budget_bytes == 256 << 20
    monkeypatch.setenv("PADDLE_TPU_HOST_TIER_MIB", "3")
    tier = HostKVTier()
    assert tier.budget_bytes == 3 << 20


def test_evict_pairs_feed_the_tier(monkeypatch):
    """The evict() return-type fix end-to-end: every (hash, page) pair a
    pressure eviction surfaces lands in the tier under that hash."""
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64,
                                   chunk=1, paged=True, block_size=8,
                                   num_blocks=8,
                                   enable_prefix_caching=True,
                                   enable_host_kv_tier=True)
    rs = np.random.RandomState(11)
    P = rs.randint(0, 128, (20,)).astype(np.int32)
    eng.serve([Request(rid=0, prompt_ids=P, max_new_tokens=4)])
    hashes = set(eng._pcache._by_hash)
    for i in range(3):
        q = rs.randint(0, 128, (40,)).astype(np.int32)
        eng.serve([Request(rid=10 + i, prompt_ids=q, max_new_tokens=4)])
    evicted = hashes - set(eng._pcache._by_hash)
    assert evicted, "pressure never evicted the first chain"
    for h in evicted:
        assert h in eng._tier, f"evicted block {h[:8]} was not demoted"
