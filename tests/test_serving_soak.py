"""Open-loop soak of the serving engine on the CPU: what guards a rewrite of
the step functions (ROADMAP A1).

A toy Llama behind the benchmark's engine geometry in ratio (paged, chunked
prefill, 8 slots, pages of 8, chunks of 16, the default token budget).
Requests are handed over by STEP NUMBER, never by the clock, so the queue
builds the same way in every run: a chat-like mix (geometric gaps between
arrivals, lognormal prompts, 4-32 new tokens) and a docs-like mix (the whole
backlog queued before step 1, long prompts, few new tokens); a third of the
requests sample with their own seed.  Every case runs under
``PADDLE_TPU_ENGINE_AUDIT=1`` and holds the engine to its own counters: every
admitted request is answered in full, with the tokens it gets when served
alone, and the engine ends as empty as it began.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.models import llama

_GEOMETRY = dict(paged=True, enable_chunked_prefill=True, max_batch=8,
                 max_seq=160, block_size=8, prefill_chunk=16)
#: a slot maps at most 20 pages: "roomy" holds eight whole requests, the
#: other two pools not two, so streams are preempted and resumed
_POOLS = {
    "roomy": dict(num_blocks=160),
    "tight": dict(num_blocks=28),
    "all_on": dict(num_blocks=32, enable_prefix_caching=True,
                   enable_speculation=True, num_draft_tokens=3,
                   enable_host_kv_tier=True),
}
_STEP_CAP = 4000      # a serve that needs more has lost a request


@pytest.fixture(scope="module")
def engines():
    """One engine per pool, built on first use and shared by its cases: a
    drained engine serves again, and counters are read as changes."""
    mp = pytest.MonkeyPatch()
    mp.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    # two layers, two query heads on one KV head: interpreted Pallas kernels
    # cost the CPU by the head, and scheduling is what is on trial here
    cfg = llama.LlamaConfig.tiny(hidden=32, heads=2, kv_heads=1, inter=64)
    cfg.dtype = jnp.float32     # served alone or in a crowd: the same tokens
    params = llama.init_params(cfg, jax.random.key(0))
    built: dict[str, ContinuousBatchingEngine] = {}

    def get(pool):
        if pool not in built:
            built[pool] = ContinuousBatchingEngine(
                cfg, params, **_GEOMETRY, **_POOLS[pool])
            assert built[pool]._audit_every_step
        return built[pool]

    yield get
    mp.undo()


def _schedule(mix, seed):
    """[(due step, Request)] in due order; rids are unique across cases."""
    rs = np.random.RandomState(seed)
    if mix == "chat":
        n = 14
        due = np.cumsum(rs.geometric(0.4, n))
        plen = np.clip(np.round(rs.lognormal(np.log(24), 0.8, n)), 4, 120)
        new = rs.randint(4, 33, n)
    else:
        n = 10
        due = np.zeros(n, np.int64)
        plen = rs.randint(48, 121, n)
        new = rs.randint(4, 17, n)
    system = rs.randint(0, 256, 16)     # a shared two-page prefix
    out = []
    for i in range(n):
        ids = rs.randint(0, 256, int(plen[i]))
        if i % 2 and ids.size > 16:
            ids[:16] = system
        kw = (dict(temperature=0.8, top_p=0.9, seed=1000 + i)
              if i % 3 == 2 else {})
        out.append((int(due[i]), Request(
            rid=seed * 1000 + i, prompt_ids=ids.astype(np.int32),
            max_new_tokens=int(new[i]), **kw)))
    return out


def _twin(req):
    return Request(rid=req.rid + 500, prompt_ids=req.prompt_ids.copy(),
                   max_new_tokens=req.max_new_tokens,
                   temperature=req.temperature, top_p=req.top_p,
                   seed=req.seed)


def _drive(eng, schedule):
    """Hand each request over at its step and step the engine until nothing
    is due, queued or seated."""
    pending = list(schedule)
    for step in range(1, _STEP_CAP + 1):
        while pending and pending[0][0] <= step:
            eng.add_request(pending.pop(0)[1])
        busy = eng.step()
        if not (busy or pending or eng._queue):
            return step
    raise AssertionError(
        f"{len(pending)} not yet due, {len(eng._queue)} queued, "
        f"{sum(r is not None for r in eng._slot_req)} seated after "
        f"{_STEP_CAP} steps")


def _soak(eng, mix, seed):
    schedule = _schedule(mix, seed)
    reqs = [r for _, r in schedule]
    before = dict(eng.stats)
    _drive(eng, schedule)
    d = {k: v - before[k] for k, v in dict(eng.stats).items()}

    for r in reqs:
        assert r.status == "FINISHED", (r.rid, r.status, r.error)
        assert len(r.output_ids) == r.max_new_tokens, r.rid
        assert r.ttft_s is not None, r.rid
    assert 0 < d["step_rows_live"] <= d["step_rows_computed"]
    assert d["mixed_steps"] > 0

    # the engine ends as empty as it began: every page free or a cached
    # resident nobody holds, nothing journaled
    cached = (list(eng._pcache.resident_pages())
              if eng._pcache is not None else [])
    assert sorted(eng._free + cached) == list(range(eng.num_blocks))
    if eng._pcache is not None:
        assert eng._pcache.evictable_count() == eng._pcache.resident_blocks()
    assert all(r is None for r in eng._slot_req) and not eng._reqs
    assert eng.journal() == eng.snapshot()
    assert eng.journal()["running"] == eng.journal()["queued"] == []

    # served alone on the same (drained) engine: the same tokens
    for r in reqs:
        alone = _twin(r)
        assert eng.serve([alone])[alone.rid] == r.output_ids, r.rid
    return reqs, d


_CASES = [(mix, pool, seed) for mix in ("chat", "docs")
          for pool in ("roomy", "tight", "all_on") for seed in (1, 2, 3)]


@pytest.mark.parametrize("mix,pool,seed", _CASES,
                         ids=[f"{m}-{p}-{s}" for m, p, s in _CASES])
def test_every_admitted_request_is_answered(engines, mix, pool, seed):
    eng = engines(pool)
    reqs, d = _soak(eng, mix, seed)
    prompt_rows = sum(r.prompt_ids.size for r in reqs)
    if pool == "roomy":
        # room for every slot's whole request and no cache: nobody is
        # preempted, and every prompt row is packed exactly once
        assert d["preemptions"] == 0
        assert d["prefill_rows_packed"] == prompt_rows
        return
    if pool == "tight":
        assert d["preemptions"] > 0
    else:
        assert d["prefix_hits"] > 0 and d["spec_steps"] > 0
        assert d["tier_demotions"] > 0
    # the rule the engine keeps when streams are preempted and resumed, or
    # start behind a cached prefix: an admission packs the rows its cursor
    # has to compute and one for the emit, at most once each (a victim
    # taken mid-prompt packed fewer), and whatever no cache covered is
    # packed at least once
    admissions = len(reqs) + d["preemptions"]
    assert (prompt_rows - d["prefill_tokens_cached"]
            <= d["prefill_rows_packed"]
            <= d["prefill_tokens_computed"] + admissions)


# ---- a model that brings its own step programs and per-slot state ----

@pytest.fixture(scope="module")
def hybrid_engine():
    """``models/olmo_hybrid`` (one period: three linear-attention layers
    and a full one) behind the tight pool: streams are preempted and
    re-prefilled, slots are reused, and a slot's recurrent state has to
    start from zero every time (docs/hybrid_serving.md)."""
    from paddle_tpu.models import olmo_hybrid

    mp = pytest.MonkeyPatch()
    mp.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg = olmo_hybrid.OlmoHybridConfig(
        vocab_size=256, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16, dtype=jnp.float32)
    eng = ContinuousBatchingEngine(
        cfg, olmo_hybrid.init_params(cfg, jax.random.key(0), std=0.2),
        **_GEOMETRY, **_POOLS["tight"])
    assert eng._audit_every_step and eng._program.state_bytes() > 0
    yield eng
    mp.undo()


@pytest.mark.parametrize("mix", ["chat", "docs"])
def test_every_admitted_request_is_answered_by_a_hybrid(hybrid_engine, mix):
    reqs, d = _soak(hybrid_engine, mix, 5)
    assert d["preemptions"] > 0
    # every request started a state, and again when it came back from a
    # preemption that had let it pack a first row
    assert len(reqs) <= d["state_starts"] <= len(reqs) + d["preemptions"]
    assert 0 < d["gdn_rows_live"] < d["gdn_rows_computed"]


# ---- the row bound: the mixed program's matmuls run P packed rows ----

#: token_budget -> the rows the mixed program computes (float32: sublanes
#: of 8): under max_batch the 1-row floor keeps prompts moving and max_batch
#: bounds the rows; 13 rounds up; None is the default, chunk + max_batch;
#: 128 is max_batch x prefill_chunk, every row of the staging
_ROW_BOUNDS = {4: 8, 8: 8, 13: 16, None: 24, 128: 128}
_BOUND_CASES = [(b, mix, pool) for b in _ROW_BOUNDS
                for mix, pool in (("chat", "tight"), ("docs", "roomy"))]


def _bounded_engine(monkeypatch, budget, **pool):
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    cfg = llama.LlamaConfig.tiny(hidden=32, heads=2, kv_heads=1, inter=64)
    cfg.dtype = jnp.float32
    return ContinuousBatchingEngine(
        cfg, llama.init_params(cfg, jax.random.key(0)), **_GEOMETRY,
        **pool, token_budget=budget)


@pytest.mark.parametrize(
    "budget,mix,pool", _BOUND_CASES,
    ids=[f"budget{b}-{m}-{p}" for b, m, p in _BOUND_CASES])
def test_no_step_packs_more_rows_than_the_program_computes(
        monkeypatch, budget, mix, pool):
    eng = _bounded_engine(monkeypatch, budget, **_POOLS[pool])
    rows = _ROW_BOUNDS[budget]
    assert eng._mixed_rows == rows
    step, st, seen = eng.step, eng.stats, []

    def checked():
        was = (st["step_rows_live"], st["step_rows_computed"],
               st["mixed_steps"])
        busy = step()
        live, computed, mixed = (st["step_rows_live"] - was[0],
                                 st["step_rows_computed"] - was[1],
                                 st["mixed_steps"] - was[2])
        assert 0 <= live <= computed, (live, computed)
        if mixed:
            assert computed == rows, (computed, rows)
            seen.append(live)
        return busy

    eng.step = checked
    _, d = _soak(eng, mix, 4)
    # the rows a step may pack: the budget, or one a decode slot and the
    # floor's row beside them; the docs backlog fills it
    most = min(max(budget or 24, eng.max_batch), rows)
    assert 0 < max(seen) <= most
    if mix == "docs":
        assert max(seen) >= min(budget or 24, most)
    if pool == "tight" and rows >= 16:
        assert d["preemptions"] > 0     # under pressure all the while


def test_an_overfull_packing_raises_and_drops_no_row(monkeypatch):
    """``token_budget`` raised behind the engine's back: the host packs
    more live rows than the program was built to compute.  The launch must
    not happen — the program would compute the first P rows and lose the
    rest in silence."""
    from paddle_tpu.analysis.engine_audit import EngineAuditError

    eng = _bounded_engine(monkeypatch, 8, num_blocks=160)
    assert eng._mixed_rows == 8
    eng._token_budget = 64
    rs = np.random.RandomState(0)
    for i in range(4):
        eng.add_request(Request(
            rid=i, prompt_ids=rs.randint(0, 256, 40).astype(np.int32),
            max_new_tokens=4))
    with pytest.raises(EngineAuditError, match="I11"):
        eng.step()
    assert eng.stats["mixed_steps"] == eng.stats["step_rows_computed"] == 0
