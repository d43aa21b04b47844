"""Fault-tolerant serving tests (ISSUE 6, docs/fault_tolerance.md).

The correctness bar: no injected fault may escape ``step()`` — the
offending request terminates (pages and cache refs released,
pool accounting closing exactly) and every SURVIVING request's token
stream is identical to a run that never contained the poison request,
for greedy AND seeded sampled requests alike (each serve below carries a
mixed batch, so every assertion covers both sampling modes at once).
The switch that used to turn this off is retired: setting it warns and
changes nothing.  The chaos runs all execute under ``PADDLE_TPU_ENGINE_AUDIT=1`` — every ladder rung
must leave the auditor's invariants (including the new I8 terminal-
ownership check) green.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.faults import FaultInjected, FaultPlan
from paddle_tpu.inference.serving import (ContinuousBatchingEngine, Request,
                                          TERMINAL_STATUSES)
from paddle_tpu.models import llama
from paddle_tpu.utils.envflags import RETIRED_FLAGS


def _tiny():
    cfg = llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                 kv_heads=2, inter=64)
    cfg.dtype = jnp.float32  # exact parity
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("chunk", 1)
    kw.setdefault("paged", True)
    kw.setdefault("block_size", 8)
    return ContinuousBatchingEngine(cfg, params, **kw)


def _mixed_batch(rs, n=4, prompt_len=11, new=6):
    """Half greedy, half seeded temperature+top-p sampled — one serve covers
    both sampling modes for every chaos assertion."""
    reqs = []
    for i in range(n):
        p = rs.randint(0, 128, (prompt_len + i,)).astype(np.int32)
        if i % 2:
            reqs.append(Request(rid=i, prompt_ids=p, max_new_tokens=new,
                                temperature=0.8, top_p=0.9, seed=7 + i))
        else:
            reqs.append(Request(rid=i, prompt_ids=p, max_new_tokens=new))
    return reqs


def _pool_closes(eng):
    """Every page is free or a zero-ref cache resident — nothing leaked."""
    cached = (list(eng._pcache.resident_pages())
              if eng._pcache is not None else [])
    assert sorted(eng._free + cached) == list(range(eng.num_blocks))
    assert all(r is None for r in eng._slot_req)


# ---------------- chaos matrix ----------------
#
# >= 5 fault kinds; every run is a mixed greedy+seeded-sampled batch under
# PADDLE_TPU_ENGINE_AUDIT=1.  Survivor token-identity is asserted against a
# reference serve that never contained the poison request.

def _chaos_serve(monkeypatch, spec, reqs, **eng_kw):
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", spec)
    eng = _engine(cfg, params, **eng_kw)
    got = eng.serve(reqs)
    _pool_closes(eng)
    assert all(r.status in TERMINAL_STATUSES for r in reqs)
    return eng, got


def _reference_serve(reqs, monkeypatch=None, **eng_kw):
    """Fault-free reference: any chaos env the test set must NOT leak into
    the reference engine's construction."""
    if monkeypatch is not None:
        monkeypatch.delenv("PADDLE_TPU_FAULT_INJECT", raising=False)
    cfg, params = _tiny()
    eng = _engine(cfg, params, **eng_kw)
    return eng.serve(reqs)


def test_chaos_alloc_fail_transient(monkeypatch):
    """A transient allocator fault (one firing) degrades via the ladder
    (preempt / retry), fails NOTHING, and every stream — greedy and seeded
    sampled — is token-identical to a fault-free serve."""
    rs = np.random.RandomState(0)
    reqs = _mixed_batch(rs)
    eng, got = _chaos_serve(monkeypatch, "alloc_fail@step=3", reqs)
    assert all(r.status == "FINISHED" for r in reqs)
    ref = _reference_serve(_mixed_batch(np.random.RandomState(0)),
                           monkeypatch)
    assert got == ref


def test_chaos_kernel_error_retry(monkeypatch):
    """A kernel-dispatch fault raises BEFORE the launch: host and device
    state are untouched, the graceful engine retries the step, and every
    stream is token-identical to a fault-free serve."""
    rs = np.random.RandomState(0)
    reqs = _mixed_batch(rs)
    eng, got = _chaos_serve(monkeypatch, "kernel_error@step=2", reqs)
    assert all(r.status == "FINISHED" for r in reqs)
    assert eng.stats["kernel_error_retries"] == 1
    ref = _reference_serve(_mixed_batch(np.random.RandomState(0)),
                           monkeypatch)
    assert got == ref


def test_chaos_kernel_error_persistent_reraises(monkeypatch):
    """A PERSISTENT dispatch failure (streak past the retry limit) means
    the program itself cannot run — graceful mode re-raises rather than
    spinning forever."""
    rs = np.random.RandomState(0)
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "kernel_error@count=-1")
    eng = _engine(cfg, params)
    eng.add_request(Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                            .astype(np.int32), max_new_tokens=2))
    with pytest.raises(FaultInjected):
        for _ in range(10):
            eng.step()
    assert eng.stats["kernel_error_retries"] == eng._kernel_err_limit + 1


def test_chaos_nan_logits_quarantines_victim(monkeypatch):
    """The in-graph NaN/inf guard flags the poisoned slot: the victim fails
    (no garbage token ever banked), its pages release, and the survivors'
    streams are token-identical to a serve that never contained it."""
    rs = np.random.RandomState(1)
    reqs = _mixed_batch(rs)
    eng, got = _chaos_serve(monkeypatch, "nan_logits@slot=0,step=3", reqs)
    failed = [r for r in reqs if r.status == "FAILED"]
    assert len(failed) == 1
    assert "non-finite logits" in failed[0].error
    assert eng.stats["nan_guard_trips"] == 1
    assert eng.stats["requests_failed"] == 1
    survivors = [r for r in reqs if r is not failed[0]]
    assert all(r.status == "FINISHED" for r in survivors)
    ref_reqs = [r for r in _mixed_batch(np.random.RandomState(1))
                if r.rid != failed[0].rid]
    ref = _reference_serve(ref_reqs, monkeypatch)
    for r in survivors:
        assert got[r.rid] == ref[r.rid]


def test_chaos_slot_error_isolates_victim(monkeypatch):
    """A host-side fault while banking ONE slot's token fails only that
    request; the other lanes' tokens (already fetched) bank normally and
    their streams match a victim-free serve."""
    rs = np.random.RandomState(2)
    reqs = _mixed_batch(rs)
    eng, got = _chaos_serve(monkeypatch, "slot_error@rid=1,step=4", reqs)
    victim = next(r for r in reqs if r.rid == 1)
    assert victim.status == "FAILED"
    assert "slot_error" in victim.error
    survivors = [r for r in reqs if r.rid != 1]
    assert all(r.status == "FINISHED" for r in survivors)
    ref_reqs = [r for r in _mixed_batch(np.random.RandomState(2))
                if r.rid != 1]
    ref = _reference_serve(ref_reqs, monkeypatch)
    for r in survivors:
        assert got[r.rid] == ref[r.rid]


def test_chaos_cache_error_degrades_without_failing(monkeypatch):
    """A prefix-cache registration fault DEGRADES (the blocks stay private;
    a future request misses where it could have hit) — no request fails and
    every stream is token-identical to a fault-free cached serve."""
    rs = np.random.RandomState(3)
    reqs = _mixed_batch(rs, prompt_len=17)   # >= 2 full blocks to register
    eng, got = _chaos_serve(monkeypatch, "cache_error@step=1", reqs,
                            enable_prefix_caching=True)
    assert all(r.status == "FINISHED" for r in reqs)
    assert eng.stats["requests_failed"] == 0
    ref = _reference_serve(_mixed_batch(np.random.RandomState(3),
                                        prompt_len=17),
                           monkeypatch, enable_prefix_caching=True)
    assert got == ref


def test_chaos_tier_drop_degrades_without_failing(monkeypatch):
    """A host-KV-tier entry vanishing between match and ship_in
    (docs/kv_tier.md) DEGRADES — the engine falls back to ordinary
    prefill for the dropped chain — with no request failed, audit green,
    and every stream token-identical to a tier-free serve.  The workload
    forces the seam: a chain is computed, demoted under pool pressure,
    then revisited while every restore attempt finds its entry gone."""
    rs = np.random.RandomState(5)
    P = rs.randint(0, 128, (20,)).astype(np.int32)   # 2 full blocks + 4

    def batches():
        rs2 = np.random.RandomState(6)
        first = [Request(rid=0, prompt_ids=P, max_new_tokens=4)]
        pressure = [Request(rid=10 + i,
                            prompt_ids=rs2.randint(0, 128, (40,))
                            .astype(np.int32), max_new_tokens=4)
                    for i in range(3)]
        revisit = [Request(rid=1, prompt_ids=P, max_new_tokens=4,
                           temperature=0.8, top_p=0.9, seed=13)]
        return first, pressure, revisit

    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "tier_drop@count=-1")
    kw = dict(max_batch=1, num_blocks=8, enable_prefix_caching=True,
              enable_chunked_prefill=True, prefill_chunk=5,
              enable_host_kv_tier=True)
    eng = _engine(cfg, params, **kw)
    got = {}
    for batch in batches():
        got.update(eng.serve(batch))
    _pool_closes(eng)
    assert eng.stats["requests_failed"] == 0
    assert eng.stats["tier_demotions"] > 0, "pressure never demoted"
    assert eng.stats["tier_readmits"] == 0, "a dropped entry restored"
    monkeypatch.delenv("PADDLE_TPU_FAULT_INJECT")
    ref_eng = _engine(cfg, params, **{**kw, "enable_host_kv_tier": False})
    ref = {}
    for batch in batches():
        ref.update(ref_eng.serve(batch))
    assert got == ref


def test_chaos_spec_and_chunked_paths(monkeypatch):
    """The speculative verify and unified mixed steps carry the same guard:
    a nan_logits fault mid-serve on the full-feature engine fails only the
    victim, audit stays green, survivors match a victim-free serve."""
    rs = np.random.RandomState(4)
    # self-similar prompts so the n-gram drafter actually proposes
    base = rs.randint(0, 128, (8,)).astype(np.int32)
    reqs = [Request(rid=i,
                    prompt_ids=np.tile(base, 3)[:20 + i].astype(np.int32),
                    max_new_tokens=8,
                    **({"temperature": 0.7, "seed": 11 + i} if i % 2
                       else {}))
            for i in range(3)]
    kw = dict(enable_prefix_caching=True, enable_speculation=True,
              num_draft_tokens=3, enable_chunked_prefill=True,
              prefill_chunk=8, num_blocks=16)
    eng, got = _chaos_serve(monkeypatch, "nan_logits@slot=1,step=5", reqs,
                            **kw)
    failed = [r for r in reqs if r.status == "FAILED"]
    assert len(failed) == 1
    survivors = [r for r in reqs if r is not failed[0]]
    assert all(r.status == "FINISHED" for r in survivors)
    ref_reqs = [Request(rid=i,
                        prompt_ids=np.tile(base, 3)[:20 + i]
                        .astype(np.int32), max_new_tokens=8,
                        **({"temperature": 0.7, "seed": 11 + i} if i % 2
                           else {}))
                for i in range(3) if i != failed[0].rid]
    ref = _reference_serve(ref_reqs, monkeypatch, **kw)
    for r in survivors:
        assert got[r.rid] == ref[r.rid]


# ---------------- the retired off-switches ----------------

@pytest.mark.parametrize("flag", RETIRED_FLAGS)
def test_retired_flag_warns_once_and_changes_nothing(monkeypatch, flag):
    """An operator who still exports one of the four removed off-switches
    is told so once, by name, where the engine is built; the engine is the
    default one all the same: registry-backed stats, a working cancel(),
    and a poisoned request quarantined without touching its neighbours."""
    from paddle_tpu.inference.observability import StatsView
    from paddle_tpu.utils import envflags

    cfg, params = _tiny()
    monkeypatch.setenv(flag, "0")
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "nan_logits@slot=0,step=3")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match=f"{flag}='0' has no effect"):
        eng = _engine(cfg, params)
    with warnings.catch_warnings(record=True) as again:
        warnings.simplefilter("always")     # told once, not at every build
        _engine(cfg, params)
    assert not [w for w in again if flag in str(w.message)]
    assert isinstance(eng.stats, StatsView)
    assert eng.metrics is not None and eng.slo is not None
    assert eng._flight is not None
    reqs = _mixed_batch(np.random.RandomState(1))
    extra = Request(rid=99, prompt_ids=np.arange(9, dtype=np.int32),
                    max_new_tokens=4)
    eng.add_request(extra)
    assert eng.cancel(99) is True and extra.status == "CANCELLED"
    got = eng.serve(reqs)
    failed = [r for r in reqs if r.status == "FAILED"]
    assert len(failed) == 1 and "non-finite logits" in failed[0].error
    assert eng.stats["nan_guard_trips"] == 1
    assert eng.stats["host_overlap_steps"] > 0
    assert len(eng._flight) > 0
    ref = _reference_serve([r for r in _mixed_batch(np.random.RandomState(1))
                            if r.rid != failed[0].rid], monkeypatch)
    for rid, toks in ref.items():
        assert got[rid] == toks
    _pool_closes(eng)


# ---------------- overload degradation ladder ----------------

def test_ladder_rung1_evicts_cache_leaves_first(monkeypatch):
    """Pool pressure with zero-ref cache residents: rung 1 evicts leaves
    ahead of the allocator (observable as degrade_evict) and NOTHING is
    preempted or failed."""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    rs = np.random.RandomState(10)
    eng = _engine(cfg, params, enable_prefix_caching=True, num_blocks=8)
    # populate the cache: a retired request donates its blocks as zero-ref
    # residents (17-token prompt -> 2 full blocks cached)
    warm = Request(rid=0, prompt_ids=rs.randint(0, 128, (17,))
                   .astype(np.int32), max_new_tokens=2)
    eng.serve([warm])
    assert eng._pcache.evictable_count() > 0
    # now a request whose decode growth needs those pages back
    req = Request(rid=1, prompt_ids=rs.randint(0, 128, (30,))
                  .astype(np.int32), max_new_tokens=30)
    got = eng.serve([req])
    assert req.status == "FINISHED" and len(got[1]) == 30
    assert eng.stats["degrade_evict"] >= 1
    assert eng.stats["preemptions"] == 0
    assert eng.stats["requests_failed"] == 0


def test_ladder_rung2_suspends_speculation_under_pressure(monkeypatch):
    """When a step's speculative appends (K+1 per slot) don't fit but one
    token per slot does, rung 2 suspends speculation for the step instead
    of preempting anyone — and the streams are unchanged (speculation only
    ever changes how many tokens a round-trip banks)."""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    rs = np.random.RandomState(11)
    base = rs.randint(0, 128, (6,)).astype(np.int32)
    prompts = [np.tile(base, 4)[:21].astype(np.int32),
               np.tile(base, 4)[:22].astype(np.int32)]

    def build():
        return [Request(rid=i, prompt_ids=p, max_new_tokens=18)
                for i, p in enumerate(prompts)]

    ref = _reference_serve(build())
    # 8 blocks: two 21/22-token prompts resident (3 pages each) leave no
    # headroom for +K+1 growth right after admission — rung 2 territory
    eng = _engine(cfg, params, enable_speculation=True, num_draft_tokens=4,
                  num_blocks=8)
    reqs = build()
    got = eng.serve(reqs)
    assert got == ref
    assert all(r.status == "FINISHED" for r in reqs)
    assert eng.stats["degrade_spec_off"] >= 1
    assert eng.stats["requests_failed"] == 0


def test_ladder_rung3_shrinks_mixed_budget(monkeypatch):
    """Chunked prefill under decode-lane pool pressure: rung 3 shrinks the
    step's prefill budget to the 1-token floor (prompts crawl, decode
    never stalls, nobody is preempted for a prompt that can wait) — and
    the streams still match the roomy reference."""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    rs = np.random.RandomState(12)
    prompts = [rs.randint(0, 128, (9,)).astype(np.int32),
               rs.randint(0, 128, (49,)).astype(np.int32)]

    def build():
        return [Request(rid=0, prompt_ids=prompts[0], max_new_tokens=7),
                Request(rid=1, prompt_ids=prompts[1], max_new_tokens=4)]

    ref = _reference_serve(build(), enable_chunked_prefill=True,
                           prefill_chunk=8, num_blocks=16)
    # 8 blocks: rid 0's two blocks + rid 1's streaming 49-token prompt
    # (7 blocks) peak at 9 > 8 mid-stream — chunk-granular allocation
    # makes the deficit land on a chunk pack, which must shrink to the
    # floor (never preempt: rid 0 finishes and frees the pages rid 1's
    # crawl then grows into)
    eng = _engine(cfg, params, enable_chunked_prefill=True, prefill_chunk=8,
                  num_blocks=8)
    reqs = build()
    got = eng.serve(reqs)
    assert got == ref
    assert all(r.status == "FINISHED" for r in reqs)
    assert eng.stats["degrade_budget_shrink"] >= 1
    assert eng.stats["preemptions"] == 0
    assert eng.stats["requests_failed"] == 0


def test_ladder_rung4_preempts_youngest(monkeypatch):
    """Pressure past rungs 1-3 preempts the YOUNGEST slot (vLLM-style
    recompute) — accepted work survives, streams exact."""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    rs = np.random.RandomState(13)
    reqs = [Request(rid=i, prompt_ids=rs.randint(0, 128, (12,))
                    .astype(np.int32), max_new_tokens=24)
            for i in range(3)]
    eng = _engine(cfg, params, num_blocks=8)
    got = eng.serve(reqs)
    assert all(r.status == "FINISHED" for r in reqs)
    assert all(len(got[r.rid]) == 24 for r in reqs)
    assert eng.stats["preemptions"] >= 1
    # graceful-mode preemption IS rung 4 — the documented per-rung counter
    # must tick, not just the legacy total
    assert eng.stats["degrade_preempt"] == eng.stats["preemptions"]
    # the journal holds live requests only: terminal entries are pruned
    # (a long-lived engine must not leak one Request per rid forever)
    assert eng._reqs == {}
    _pool_closes(eng)


def test_ladder_rung5_fails_only_the_unsatisfiable(monkeypatch):
    """When eviction, degradation and preemption are ALL unavailable — a
    single resident request, no victims, the allocator reporting the pool
    dry at its block-boundary grab — rung 5 fails ONLY that request.  Its
    pages free immediately, the queued survivor admits into them and
    finishes token-identically to a serve that never contained the hog.
    (Organically a pool always holds one full request — the ctor floors
    it — so the terminal rung is reached through the allocator fault
    seam, exactly what it exists for.)"""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    # step 9 is the hog's third-block grab (pos crosses 16): max_batch=1
    # means no victims, so the ladder is already exhausted
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "alloc_fail@step=9")
    rs = np.random.RandomState(14)
    p_hog = rs.randint(0, 128, (9,)).astype(np.int32)
    p_small = rs.randint(0, 128, (9,)).astype(np.int32)
    hog = Request(rid=0, prompt_ids=p_hog, max_new_tokens=30)
    small = Request(rid=1, prompt_ids=p_small, max_new_tokens=6)
    eng = _engine(cfg, params, max_batch=1)
    got = eng.serve([hog, small])
    assert hog.status == "FAILED"
    assert "pool exhausted" in hog.error and "rid=0" in hog.error
    assert len(hog.output_ids) > 0          # partial output stays
    assert small.status == "FINISHED" and len(got[1]) == 6
    ref = _reference_serve([Request(rid=1, prompt_ids=p_small,
                                    max_new_tokens=6)],
                           monkeypatch, max_batch=1)
    assert got[1] == ref[1]
    _pool_closes(eng)


def test_ladder_rung5_diagnosis_with_prefix_cache(monkeypatch):
    """The rung-5 diagnosis must survive prefix caching being ON: the
    pinned-cached count comes from the cache's own accounting (resident
    minus evictable), and the failure still isolates to the one
    unsatisfiable request."""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "alloc_fail@step=9")
    rs = np.random.RandomState(14)
    hog = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=30)
    eng = _engine(cfg, params, max_batch=1, enable_prefix_caching=True)
    eng.serve([hog])
    assert hog.status == "FAILED"
    assert "pool exhausted" in hog.error and "pinned cached" in hog.error
    _pool_closes(eng)


# ---------------- deadline / cancel / backpressure ----------------

def test_deadline_expires_running_with_partial_output(monkeypatch):
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    rs = np.random.RandomState(15)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=10_000,
                  deadline_s=0.15)
    eng = _engine(cfg, params)
    eng.add_request(req)
    while eng.step() or eng._queue:
        pass
    assert req.status == "EXPIRED"
    assert "deadline" in req.error
    assert len(req.output_ids) > 0          # partial output delivered
    assert eng.stats["requests_expired"] == 1
    _pool_closes(eng)


def test_deadline_expires_queued(monkeypatch):
    cfg, params = _tiny()
    rs = np.random.RandomState(16)
    eng = _engine(cfg, params)
    dead = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                   .astype(np.int32), max_new_tokens=4, deadline_s=0.0)
    live = Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                   .astype(np.int32), max_new_tokens=4)
    got = eng.serve([dead, live])
    assert dead.status == "EXPIRED" and dead.output_ids == []
    assert "queued" in dead.error
    assert live.status == "FINISHED" and len(got[1]) == 4


def test_cancel_queued_and_running(monkeypatch):
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    rs = np.random.RandomState(17)
    eng = _engine(cfg, params, max_batch=1)
    running = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                      .astype(np.int32), max_new_tokens=50)
    queued = Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                     .astype(np.int32), max_new_tokens=50)
    eng.add_request(running)
    eng.add_request(queued)
    for _ in range(3):
        eng.step()
    assert eng.cancel(1) is True            # still queued
    assert queued.status == "CANCELLED" and queued not in eng._queue
    assert eng.cancel(0) is True            # mid-decode
    assert running.status == "CANCELLED"
    assert len(running.output_ids) > 0      # partial output stays
    assert eng.cancel(0) is False           # already terminal
    assert eng.cancel(999) is False         # unknown rid
    assert eng.stats["requests_cancelled"] == 2
    _pool_closes(eng)
    assert eng.step() is False              # engine is drained, not wedged


def test_cancel_mid_prefill_frees_cursor_pages(monkeypatch):
    """Cancel during a streaming prefill: the chunked cursor's pages (a
    partially-prefilled prompt) release exactly like any preemption."""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    rs = np.random.RandomState(18)
    eng = _engine(cfg, params, enable_chunked_prefill=True, prefill_chunk=4)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (40,))
                  .astype(np.int32), max_new_tokens=8)
    eng.add_request(req)
    eng.step()                               # first chunk only (4 of 40)
    assert eng._prefill_ids[0] is not None   # genuinely mid-prefill
    assert eng.cancel(0) is True
    assert req.status == "CANCELLED"
    _pool_closes(eng)
    assert eng.step() is False


def test_bounded_queue_backpressure(monkeypatch):
    cfg, params = _tiny()
    rs = np.random.RandomState(19)
    eng = _engine(cfg, params, max_batch=1, max_queue=2)
    reqs = [Request(rid=i, prompt_ids=rs.randint(0, 128, (9,))
                    .astype(np.int32), max_new_tokens=3) for i in range(4)]
    for r in reqs:
        eng.add_request(r)
    # capacity is checked at submission (no step has drained the queue
    # yet): two queue, the other two shed immediately
    shed = [r for r in reqs if r.status == "REJECTED"]
    assert len(shed) == 2
    assert all("queue full" in r.error for r in shed)
    assert eng.stats["requests_rejected"] == 2
    while eng.step() or eng._queue:
        pass
    assert sum(1 for r in reqs if r.status == "FINISHED") == 2


# ---------------- validation satellites ----------------

def test_nonfinite_sampling_params_rejected():
    """temperature=NaN passes a bare `< 0` check — the satellite: reject
    non-finite temperature/top_p/deadline_s at validation."""
    cfg, params = _tiny()
    eng = _engine(cfg, params, paged=False)
    rs = np.random.RandomState(21)
    ids = rs.randint(0, 128, (5,)).astype(np.int32)
    for bad in (dict(temperature=float("nan")),
                dict(temperature=float("inf")),
                dict(top_p=float("nan")),
                dict(deadline_s=float("nan")),
                dict(deadline_s=-1.0)):
        with pytest.raises(ValueError):
            eng.add_request(Request(rid=0, prompt_ids=ids, **bad))


def test_serve_marks_invalid_requests_rejected():
    """serve() in graceful mode: the bad request is REJECTED with error,
    the good ones run — never the old all-or-nothing raise."""
    cfg, params = _tiny()
    eng = _engine(cfg, params)
    rs = np.random.RandomState(22)
    good = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                   .astype(np.int32), max_new_tokens=3)
    bad = Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), temperature=float("nan"))
    got = eng.serve([good, bad])
    assert good.status == "FINISHED" and len(got[0]) == 3
    assert bad.status == "REJECTED" and "finite" in bad.error
    assert got[1] == []


# ---------------- snapshot / restore ----------------

def test_snapshot_restore_token_identical(monkeypatch):
    """snapshot -> kill -> restore on a fresh engine: completion emits
    token-identical streams to an uninterrupted serve (greedy AND seeded
    sampled; the journaled tokens teacher-force, the (seed, position) keys
    redraw the continuation exactly)."""
    cfg, params = _tiny()
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")

    def build():
        rs = np.random.RandomState(23)
        return [Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                        .astype(np.int32), max_new_tokens=12),
                Request(rid=1, prompt_ids=rs.randint(0, 128, (13,))
                        .astype(np.int32), max_new_tokens=12,
                        temperature=0.9, top_p=0.85, seed=5),
                Request(rid=2, prompt_ids=rs.randint(0, 128, (33,))
                        .astype(np.int32), max_new_tokens=12)]

    ref = _reference_serve(build())
    # interrupted replica: a few steps in, rid 2 still queued (2 slots)
    eng1 = _engine(cfg, params)
    reqs1 = build()
    for r in reqs1:
        eng1.add_request(r)
    for _ in range(5):
        eng1.step()
    assert any(r.output_ids for r in reqs1)      # genuinely mid-stream
    assert any(not r.finished for r in reqs1)
    snap = eng1.snapshot()
    del eng1                                     # the replica dies
    # fresh replica resumes the journal
    eng2 = _engine(cfg, params)
    restored = eng2.restore(snap)
    while eng2.step() or eng2._queue:
        pass
    by_rid = {r.rid: r for r in restored}
    for rid, want in ref.items():
        done_early = next(r for r in build() if r.rid == rid)
        if rid in by_rid:
            assert by_rid[rid].output_ids == want
            assert by_rid[rid].status == "FINISHED"
        else:
            # finished before the snapshot: its tokens left with the dead
            # replica's caller, not the journal
            got1 = next(r for r in reqs1 if r.rid == rid)
            assert got1.output_ids == want
    _pool_closes(eng2)


def test_snapshot_restore_mid_prefill_chunked(monkeypatch):
    """A snapshot taken while a prompt is mid-stream (chunked-prefill
    cursor set) restores by recompute and still matches byte-for-byte."""
    cfg, params = _tiny()
    kw = dict(enable_chunked_prefill=True, prefill_chunk=4)

    def build():
        rs = np.random.RandomState(24)
        return [Request(rid=0, prompt_ids=rs.randint(0, 128, (37,))
                        .astype(np.int32), max_new_tokens=6,
                        temperature=0.6, seed=3)]

    ref = _reference_serve(build(), **kw)
    eng1 = _engine(cfg, params, **kw)
    req = build()[0]
    eng1.add_request(req)
    for _ in range(3):
        eng1.step()
    assert eng1._prefill_ids[0] is not None      # cursor mid-prompt
    snap = eng1.snapshot()
    assert snap["running"][0]["prefilled"] > 0   # journaled provenance
    eng2 = _engine(cfg, params, **kw)
    restored = eng2.restore(snap)
    while eng2.step() or eng2._queue:
        pass
    assert restored[0].output_ids == ref[0]


def test_snapshot_journals_remaining_deadline_and_restore_rearms(
        monkeypatch):
    """Satellite regression (ISSUE 9): ``snapshot()`` used to journal the
    ORIGINAL ``deadline_s`` only, so a restored request got its full
    budget again (~180% of the SLO when snapshotted at 80%).  The journal
    now carries ``deadline_remaining_s`` and restore re-arms with exactly
    that — expiry lands at ~100% of the original budget."""
    cfg, params = _tiny()
    rs = np.random.RandomState(28)
    eng1 = _engine(cfg, params)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=10_000, deadline_s=10.0)
    eng1.add_request(req)
    eng1.step()
    import time as _time
    req._submit_s = _time.perf_counter() - 8.0   # exactly 80% burned
    snap = eng1.snapshot()
    j = snap["running"][0]
    assert j["deadline_s"] == 10.0          # original grant: provenance
    assert 1.5 < j["deadline_remaining_s"] < 2.1    # ~20% left
    eng2 = _engine(cfg, params)
    restored = eng2.restore(snap)[0]
    # re-armed with the REMAINING budget, not the full grant
    assert restored.deadline_s < 2.5
    restored._submit_s -= restored.deadline_s + 0.1  # remaining now spent
    eng2.step()
    assert restored.status == "EXPIRED"     # ~100% of the SLO, not ~180%
    # a v1-era journal entry (no remaining field) falls back to the full
    # grant — the historical behavior, never a KeyError
    del j["deadline_remaining_s"]
    eng3 = _engine(cfg, params)
    legacy = eng3.adopt(j)
    assert legacy.deadline_s == 10.0


def test_restore_rejects_unknown_version():
    cfg, params = _tiny()
    eng = _engine(cfg, params, paged=False)
    with pytest.raises(ValueError, match="version"):
        eng.restore({"version": 99, "running": [], "queued": []})


# ---------------- audit I8: terminal ownership ----------------

def test_audit_i8_terminal_request_still_seated(monkeypatch):
    from paddle_tpu.analysis.engine_audit import EngineAuditError, \
        audit_engine

    cfg, params = _tiny()
    rs = np.random.RandomState(25)
    eng = _engine(cfg, params)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=20)
    eng.add_request(req)
    eng.step()
    audit_engine(eng)                        # healthy mid-serve state
    req.status = "FAILED"                    # corrupt: terminal but seated
    with pytest.raises(EngineAuditError, match="I8"):
        audit_engine(eng)


def test_audit_i8_zombie_in_queue(monkeypatch):
    from paddle_tpu.analysis.engine_audit import EngineAuditError, \
        audit_engine

    cfg, params = _tiny()
    rs = np.random.RandomState(26)
    eng = _engine(cfg, params, max_batch=1)
    a = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                .astype(np.int32), max_new_tokens=20)
    b = Request(rid=1, prompt_ids=rs.randint(0, 128, (9,))
                .astype(np.int32), max_new_tokens=20)
    eng.add_request(a)
    eng.add_request(b)
    eng.step()
    audit_engine(eng)
    b.status = "CANCELLED"                   # corrupt: terminal but queued
    b.finished = True
    with pytest.raises(EngineAuditError, match="I8"):
        audit_engine(eng)


# ---------------- env grammar (utils/envflags satellites) ----------------

def test_fault_spec_parses_full_grammar(monkeypatch):
    monkeypatch.setenv(
        "PADDLE_TPU_FAULT_INJECT",
        "alloc_fail@step=7;nan_logits@slot=2,step=11;"
        "kernel_error@p=0.5,seed=9,count=-1")
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # a valid spec must not warn
        plan = FaultPlan.from_env()
    assert bool(plan)
    assert plan.fire("alloc_fail", step=7) is True
    assert plan.fire("alloc_fail", step=7) is False     # count=1 exhausted
    assert plan.fire("nan_logits", step=11, slot=1) is False
    assert plan.fire("nan_logits", step=11, slot=2) is True


def test_fault_spec_typo_disables_injection_and_engine_serves(monkeypatch):
    """Unknown fault kind: warn once with a did-you-mean, injection
    disabled ENTIRELY (partial acceptance would make chaos evidence
    unreadable), engine serves normally."""
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT",
                       "aloc_fail@step=2;nan_logits@step=3")
    from paddle_tpu.utils import envflags
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="alloc_fail"):
        plan = FaultPlan.from_env()
    assert not plan
    cfg, params = _tiny()
    rs = np.random.RandomState(27)
    eng = _engine(cfg, params)
    req = Request(rid=0, prompt_ids=rs.randint(0, 128, (9,))
                  .astype(np.int32), max_new_tokens=3)
    got = eng.serve([req])
    assert req.status == "FINISHED" and len(got[0]) == 3


def test_fault_spec_bad_key_and_value(monkeypatch):
    from paddle_tpu.utils import envflags

    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "alloc_fail@stp=2")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="stp"):
        assert not FaultPlan.from_env()
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "alloc_fail@step=two")
    envflags._warned.clear()
    with pytest.warns(UserWarning, match="two"):
        assert not FaultPlan.from_env()
