"""Split-K flash-decode + fused decode step (ISSUE 10,
docs/paged_attention.md).

Kernel level: the split-K page walk's combine pass must reproduce the
sequential kernel and the gather oracle at every raggedness extreme —
empty slot, single token, single page, full table, shard count past the
live pages — and through GQA grouping and int8/packed-int4 dequant-on-read.
The fused rope+append+attention step must match its unfused reference
composition, including dropped writes and spill-page isolation.

Engine level: flash + fused are the paged decode path's NEW DEFAULT —
token identity is asserted against the kill-switched (pre-PR) engine with
every feature on (prefix cache, speculation, chunked prefill, graceful),
greedy AND seeded sampled, and under TP=2 shard_map.  The kill switches
must rebuild the pre-fusion program shape exactly (no spill page, the two
KV-append scatters back).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops import decode_attention as da


def _rand_paged(rs, *, nb=30, nkv=2, bs=8, hd=16, nh=4, B=3, mb=8):
    kc = jnp.asarray(rs.randn(nb, nkv, bs, hd), jnp.float32)
    vc = jnp.asarray(rs.randn(nb, nkv, bs, hd), jnp.float32)
    tables = jnp.asarray(rs.permutation(nb)[:B * mb].reshape(B, mb),
                         jnp.int32)
    q = jnp.asarray(rs.randn(B, nh, hd), jnp.float32)
    return q, kc, vc, tables


# ---------------------------------------------------------------------------
# split-K kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lens", [
    [0, 0, 0],       # all-pages-dead slots (empty accumulator -> zeros)
    [1, 1, 1],       # seq_len = 1
    [8, 8, 8],       # exactly one live page per slot
    [64, 64, 64],    # seq_len = max_seq (every table page live)
    [0, 1, 64],      # the extremes mixed in one launch
    [5, 37, 23],     # ragged interior
])
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_splitk_combine_parity(lens, shards):
    """Split-K (any shard count, incl. > live pages: lens=1 at shards=8
    leaves 7 shards all-dead) matches the sequential kernel and the gather
    oracle at f32 tolerance."""
    rs = np.random.RandomState(0)
    q, kc, vc, tables = _rand_paged(rs)
    sl = jnp.asarray(lens, jnp.int32)
    ref = pa.paged_attention_reference(q, kc, vc, tables, sl)
    seq = pa.paged_attention_decode(q, kc, vc, tables, sl, num_shards=1)
    fl = pa.paged_attention_decode(q, kc, vc, tables, sl, num_shards=shards)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(seq), atol=1e-5)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=1e-5)


def test_splitk_gqa_groups():
    """Grouped query heads (nh/nkv = 4) ride one grid step per kv head in
    the split-K walk exactly as in the sequential kernel."""
    rs = np.random.RandomState(1)
    q, kc, vc, tables = _rand_paged(rs, nh=8, nkv=2)
    sl = jnp.asarray([3, 40, 61], jnp.int32)
    seq = pa.paged_attention_decode(q, kc, vc, tables, sl, num_shards=1)
    fl = pa.paged_attention_decode(q, kc, vc, tables, sl, num_shards=4)
    ref = pa.paged_attention_reference(q, kc, vc, tables, sl)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(seq), atol=1e-5)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_splitk_quantized_kv(mode):
    """Dequant-on-read (per-page scales) through the split-K walk: the
    shard boundaries must never split a page's scale from its payload."""
    rs = np.random.RandomState(2)
    q, kc, vc, tables = _rand_paged(rs)
    kq, ks = pa.quantize_kv_cache(kc, mode)
    vq, vs = pa.quantize_kv_cache(vc, mode)
    sl = jnp.asarray([1, 29, 64], jnp.int32)
    seq = pa.paged_attention_decode(q, kq, vq, tables, sl, kv_quant=mode,
                                    k_scale=ks, v_scale=vs, num_shards=1)
    fl = pa.paged_attention_decode(q, kq, vq, tables, sl, kv_quant=mode,
                                   k_scale=ks, v_scale=vs, num_shards=8)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(seq), atol=1e-5)


def test_flash_shard_heuristic_and_kill_switch(monkeypatch):
    """Auto shard count comes off the table width (the max live page
    count); PADDLE_TPU_DISABLE_PALLAS=flash_decode pins the sequential
    kernel even when num_shards asks for the fan-out."""
    assert pa.flash_decode_shards(512) == 8      # 32k ctx @ bs=64
    assert pa.flash_decode_shards(8) == 2
    assert pa.flash_decode_shards(3) == 1        # nothing to split
    assert pa.flash_decode_shards(4, num_shards=16) == 4   # clamp to pages

    rs = np.random.RandomState(3)
    q, kc, vc, tables = _rand_paged(rs)
    sl = jnp.asarray([20, 50, 7], jnp.int32)
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    pa.reset_kernel_counters()
    out_auto = pa.paged_attention_decode(q, kc, vc, tables, sl)
    assert pa.FLASH_KERNEL_CALLS == 1 and pa.KERNEL_CALLS == 0
    assert pa.LAST_FLASH_SHARDS == 2             # mb=8 -> auto 2 shards

    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "flash_decode")
    pa.reset_kernel_counters()
    out_seq = pa.paged_attention_decode(q, kc, vc, tables, sl, num_shards=8)
    assert pa.KERNEL_CALLS == 1 and pa.FLASH_KERNEL_CALLS == 0
    np.testing.assert_allclose(np.asarray(out_auto), np.asarray(out_seq),
                               atol=1e-5)


def test_reset_kernel_counters():
    """The counters are module state persisting across engines — the reset
    helper zeroes every pair (the per-rung bench hygiene; ISSUE 10)."""
    rs = np.random.RandomState(4)
    q, kc, vc, tables = _rand_paged(rs)
    sl = jnp.asarray([5, 5, 5], jnp.int32)
    pa.paged_attention_decode(q, kc, vc, tables, sl, num_shards=4)
    pa.paged_attention_decode(q, kc, vc, tables, sl, num_shards=1)
    assert pa.FLASH_KERNEL_CALLS > 0 and pa.KERNEL_CALLS > 0
    pa.reset_kernel_counters()
    for name in ("KERNEL_CALLS", "FALLBACK_CALLS", "VERIFY_KERNEL_CALLS",
                 "VERIFY_FALLBACK_CALLS", "PREFILL_KERNEL_CALLS",
                 "PREFILL_FALLBACK_CALLS", "FLASH_KERNEL_CALLS",
                 "LAST_FLASH_SHARDS", "FUSED_KERNEL_CALLS",
                 "FUSED_FALLBACK_CALLS"):
        assert getattr(pa, name) == 0, name


# ---------------------------------------------------------------------------
# fused decode step parity
# ---------------------------------------------------------------------------

def _fused_case(rs, *, lens, nbl=12, nkv=2, bs=8, hd=16, nh=4, mb=6):
    """Pools with a spill page; per-slot write pages derived from lens
    (lanes with lens None are dropped: inactive)."""
    B = len(lens)
    nbp = nbl + 1
    kc = jnp.asarray(rs.randn(nbp, nkv, bs, hd), jnp.float32)
    vc = jnp.asarray(rs.randn(nbp, nkv, bs, hd), jnp.float32)
    tables = np.full((B, mb), nbl, np.int32)
    pool = list(rs.permutation(nbl))
    wblk, wable, lens_i = [], [], []
    for b, ln in enumerate(lens):
        if ln is None:                  # inactive lane: sentinel row
            wblk.append(nbl)
            wable.append(0)
            lens_i.append(0)
            continue
        n_pages = ln // bs + 1          # live pages incl. the append page
        pages = [pool.pop() for _ in range(n_pages)]
        tables[b, :n_pages] = pages
        wblk.append(pages[ln // bs])
        wable.append(1)
        lens_i.append(ln)
    q = jnp.asarray(rs.randn(B, nh, hd), jnp.float32)
    kn = jnp.asarray(rs.randn(B, nkv, hd), jnp.float32)
    vn = jnp.asarray(rs.randn(B, nkv, hd), jnp.float32)
    cos = jnp.asarray(rs.randn(B, hd), jnp.float32)
    sin = jnp.asarray(rs.randn(B, hd), jnp.float32)
    return (q, kn, vn, cos, sin, kc, vc, jnp.asarray(tables),
            jnp.asarray(lens_i, jnp.int32), jnp.asarray(wblk, jnp.int32),
            jnp.asarray(wable, jnp.int32))


#: (num_heads, num_kv_heads) of the models that serve through the fused
#: step: Mistral's rep 4 over 8 KV heads, Olmo-Hybrid's rep 1 over 30,
#: Jamba's rep 20 over one (its q group padded to 24 rows)
_HEADS = {"rep4_nkv8": (32, 8), "rep1_nkv30": (30, 30), "rep20_nkv1": (20, 1)}

#: lane populations of one launch (lens None: an inactive lane, which
#: writes nothing and is handed length 0), at 8-position pages and a
#: 6-page table
_LANES = {
    # the longest lane ends mid-page (19: page 2 of 3), a fresh-page
    # append (8: offset 0 of page 1) walks one dead step, a dropped lane
    "mid_page": [19, 8, None],
    # a lane at the table's last position (47: page 5 of 6, the walk's
    # bound is max_blocks), a lane at position 0, and two inactive lanes
    "full_table": [47, 0, None, 3, None],
    # nothing writes: the walk is one page and the spill page takes zeros
    "all_dropped": [None, None],
}


@pytest.mark.parametrize("lanes", list(_LANES))
@pytest.mark.parametrize("heads", list(_HEADS))
def test_fused_step_matches_reference(heads, lanes):
    """Fused rope+append+attend vs the unfused reference composition:
    outputs match on writeable lanes, the appended row lands (k roped, v
    raw), untouched pages are byte-preserved, and dropped lanes write
    nothing into the allocator's range but zeros on the spill page.  The
    walk takes a page with all its KV heads a grid step and ends at the
    launch's longest lane, whatever the head count and grouping."""
    nh, nkv = _HEADS[heads]
    rs = np.random.RandomState(5)
    case = _fused_case(rs, lens=_LANES[lanes], nbl=16, nkv=nkv, nh=nh)
    (q, kn, vn, cos, sin, kc, vc, tables, lens, wblk, wable) = case
    o_ref, kc_ref, vc_ref = pa.fused_decode_step_reference(*case)
    pa.reset_kernel_counters()
    o, kc2, vc2 = da.fused_paged_decode_step(q, kn, vn, cos, sin, kc, vc,
                                             tables, lens, wblk, wable)
    assert pa.FUSED_KERNEL_CALLS == 1
    nbl = kc.shape[0] - 1
    act = np.asarray(wable).astype(bool)
    np.testing.assert_allclose(np.asarray(o)[act], np.asarray(o_ref)[act],
                               atol=1e-5)
    assert np.isfinite(np.asarray(o)).all()
    # every REAL page matches the scatter path byte-for-byte except the
    # appended rows, which match at rope-math tolerance
    np.testing.assert_allclose(np.asarray(kc2)[:nbl],
                               np.asarray(kc_ref)[:nbl], atol=1e-5)
    np.testing.assert_allclose(np.asarray(vc2)[:nbl],
                               np.asarray(vc_ref)[:nbl], atol=1e-5)
    if not act.all():
        # a dropped write lands on the spill page, as zeros
        assert not np.asarray(kc2)[nbl].any()
        assert not np.asarray(vc2)[nbl].any()
    for b in np.flatnonzero(act):
        # the appended v row is the RAW v (no rope), exactly
        page, off = int(wblk[b]), int(lens[b]) % kc.shape[2]
        np.testing.assert_allclose(np.asarray(vc2)[page, :, off],
                                   np.asarray(vn)[b], atol=1e-6)


@pytest.mark.parametrize("heads", list(_HEADS))
def test_fused_step_bf16_rope_matches_reference(heads):
    """bf16 operands (the production pool dtype): the kernel ropes in the
    INPUT dtype and rounds the appended row through the pool dtype, so the
    committed page must EXACTLY equal the reference's scatter bytes and
    the output must match at bf16 tolerance — the near-tied-argmax guard
    behind the engine-level token-identity assertion."""
    nh, nkv = _HEADS[heads]
    rs = np.random.RandomState(8)
    case = _fused_case(rs, lens=[19, 8], nkv=nkv, nh=nh)
    bf = lambda x: x.astype(jnp.bfloat16)
    q, kn, vn, cos, sin, kc, vc, tables, lens, wblk, wable = case
    case16 = (bf(q), bf(kn), bf(vn), bf(cos), bf(sin), bf(kc), bf(vc),
              tables, lens, wblk, wable)
    o_ref, kc_ref, vc_ref = pa.fused_decode_step_reference(*case16)
    o, kc2, vc2 = da.fused_paged_decode_step(*case16)
    nbl = kc.shape[0] - 1
    # the pools must agree BITWISE on every real page: same input-dtype
    # rope, same pool-dtype rounding (XLA contracts the mul+add the same
    # way on this backend; a platform that fuses differently would still
    # be 1-ulp, caught by the output tolerance below)
    assert jnp.array_equal(kc2[:nbl], kc_ref[:nbl])
    assert jnp.array_equal(vc2[:nbl], vc_ref[:nbl])
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_fused_step_kill_switch_and_fallback(monkeypatch):
    """PADDLE_TPU_DISABLE_PALLAS=fused_decode_step routes the front door to
    the unfused reference composition exactly (counter evidence both
    ways)."""
    rs = np.random.RandomState(6)
    case = _fused_case(rs, lens=[3, 15])
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    pa.reset_kernel_counters()
    da.fused_paged_decode_step(*case)
    assert pa.FUSED_KERNEL_CALLS == 1 and pa.FUSED_FALLBACK_CALLS == 0

    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "fused_decode_step")
    pa.reset_kernel_counters()
    o, kc2, vc2 = da.fused_paged_decode_step(*case)
    assert pa.FUSED_FALLBACK_CALLS == 1 and pa.FUSED_KERNEL_CALLS == 0
    o_ref, kc_ref, vc_ref = pa.fused_decode_step_reference(*case)
    assert jnp.array_equal(o, o_ref)
    assert jnp.array_equal(kc2, kc_ref)


@pytest.mark.parametrize("lens,live,counted", [
    # lanes of 20, 9, 1 positions after their append: 3, 2 and 1 pages
    # live, a lane that does not write (length 0) walks along unlive
    ([19, 8, 0, 0], [True, True, False, True], (3 + 2 + 1, 4 * 3)),
    # a lane at the table's last position bounds the walk at max_blocks
    ([47, 3], [True, True], (6 + 1, 2 * 6)),
    # nothing live: a one-page walk that holds no live page
    ([0, 0], [False, False], (0, 2)),
], ids=["mid_page", "full_table", "none_live"])
def test_decode_census_counts_what_the_walk_visits(lens, live, counted):
    """``decode_census`` counts (lane, page) steps by the kernel's own
    predicates: a lane's live pages ``cdiv(len + 1, bs)``, and every lane
    walking to the launch's longest lane — the bound the kernel's wrapper
    hands ``pallas_call`` as the page axis's extent."""
    got = pa.decode_census(lens, 8, max_blocks=6, live=live)
    assert (got["pages_live"], got["pages_walked"]) == counted
    _, bound = pa._decode_walk(jnp.asarray(lens, jnp.int32), 8, 6, jnp)
    assert int(bound) * len(lens) == got["pages_walked"]


def test_fused_step_page_axis_is_the_launchs_bound():
    """The fused kernel's grid is (slots, head blocks, pages) with the
    page extent run-time data, so one compiled launch serves every
    length; all KV heads of a page ride one grid step here."""
    from jax._src.pallas import core as pallas_core

    rs = np.random.RandomState(3)
    case = _fused_case(rs, lens=[19, 8, None], nkv=8, nh=32)
    closed = jax.make_jaxpr(da.fused_paged_decode_step)(*case)
    (eqn,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert eqn.params["name"] == "fused_decode_attn"
    grid = eqn.params["grid_mapping"].grid
    assert grid[:2] == (3, 1)
    assert grid[2] is pallas_core.dynamic_grid_dim


def test_engine_builds_one_decode_program_per_sampling_mode(monkeypatch):
    """Greedy and sampled requests of many lengths through the dense paged
    engine compile ONE decode program per sampling mode (the walk's bound
    is data, never a variant per length), the decode steps count what the
    kernel walked, and the decode program lowered for the TPU holds
    exactly one ``fused_decode_attn`` launch (the layers are one scan
    body)."""
    from paddle_tpu.analysis import n_traces
    from paddle_tpu.ops.pallas import rms_norm

    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    kw = dict(max_batch=3, max_seq=64, paged=True, block_size=8,
              enable_chunked_prefill=True, prefill_chunk=8)
    eng = ContinuousBatchingEngine(cfg, params, **kw)
    rs = np.random.RandomState(4)
    eng.serve([Request(rid=i, max_new_tokens=n_new,
                       prompt_ids=rs.randint(1, 128, n).astype(np.int32),
                       temperature=0.0 if i % 2 == 0 else 0.8, seed=7 + i)
               for i, (n, n_new) in enumerate(
                   ((3, 20), (30, 12), (12, 28), (41, 9), (5, 17)))])
    assert eng.stats["decode_steps"] > eng.stats["mixed_steps"] > 0
    assert n_traces(eng._decode_greedy) == 1
    assert n_traces(eng._decode_sampling) == 1
    st = eng.stats
    assert 0 < st["decode_attn_pages_live"] <= st["decode_attn_pages_walked"]

    # a fresh engine, so the program is traced for the chip's kernels
    fresh = ContinuousBatchingEngine(cfg, params, **kw)
    for mod in (pa, rms_norm):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    B = kw["max_batch"]
    i32 = jnp.zeros(B, jnp.int32)
    lowered = fresh._decode_greedy.trace(
        params, fresh.cache_k, fresh.cache_v, i32, i32, jnp.ones(B, bool),
        jnp.zeros(B), jnp.ones(B), i32, jnp.asarray(fresh._table),
        poison=jnp.zeros(B, bool)).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("fused_decode_attn") == 1


def test_engine_off_the_fused_kernel_counts_no_walk(monkeypatch):
    """An engine whose decode step does not run the fused fp kernel (here
    killed by its switch) adds nothing to the two walk counters."""
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "fused_decode_step")
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   paged=True, block_size=8)
    eng.serve([Request(rid=0, prompt_ids=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=6)])
    assert not eng._fused and eng.stats["decode_steps"] > 0
    assert eng.stats["decode_attn_pages_walked"] == 0
    assert eng.stats["decode_attn_pages_live"] == 0


# ---------------------------------------------------------------------------
# engine token identity (the acceptance matrix)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                  kv_heads=2, inter=64)


def _serve_tokens(cfg, params, *, disable=None, tensor_parallel=1,
                  audit=False, monkeypatch=None, **eng_kwargs):
    """Build one engine under the given kill-switch tokens and serve the
    standard all-features workload (greedy + seeded sampled, prefix-shared
    prompts so the cache hits, prompts long enough to chunk)."""
    assert monkeypatch is not None
    if disable:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", ",".join(disable))
    else:
        monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    if audit:
        monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=2, max_seq=64, chunk=2, paged=True,
        block_size=8, enable_prefix_caching=True, enable_speculation=True,
        num_draft_tokens=3, enable_chunked_prefill=True, prefill_chunk=8,
        tensor_parallel=tensor_parallel, **eng_kwargs)
    shared = np.arange(1, 17, dtype=np.int32)          # two full blocks
    rs = np.random.RandomState(9)
    prompts = [np.concatenate([shared, rs.randint(1, 128, (n,))
                               .astype(np.int32)]) for n in (3, 11, 7, 20)]
    reqs = [Request(rid=i, prompt_ids=p, max_new_tokens=8,
                    temperature=0.0 if i % 2 == 0 else 0.8, seed=41 + i)
            for i, p in enumerate(prompts)]
    out = eng.serve(reqs)
    # snapshot the launch telemetry UNDER THIS ENGINE'S env — the method
    # re-traces, and the kill switches are trace-time state
    eng._launches = eng.decode_step_launches()
    return out, eng


def test_engine_flash_fused_token_identity_all_features(monkeypatch):
    """ISSUE-10 acceptance: the flash+fused default engine is
    token-identical to the kill-switched (pre-PR) engine with prefix
    cache + speculation + chunked prefill + graceful all ON, greedy AND
    seeded sampled — and the kill-switched engine rebuilds the pre-fusion
    program shape exactly (no spill page, the two KV-append scatters
    back)."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    on, eng_on = _serve_tokens(cfg, params, disable=None,
                               monkeypatch=monkeypatch)
    off, eng_off = _serve_tokens(
        cfg, params, disable=("flash_decode", "fused_decode_step"),
        monkeypatch=monkeypatch)
    assert on == off
    # ... and both match the gather ORACLE engine (the whole kernel family
    # off), closing the three-way ISSUE-10 identity
    gather, eng_g = _serve_tokens(cfg, params, disable=("paged_attention",),
                                  monkeypatch=monkeypatch)
    assert on == gather and not eng_g._fused
    assert eng_on._fused and not eng_off._fused
    # spill-page geometry: exactly one extra physical page, fused only
    assert eng_on.cache_k.shape[1] == eng_on.num_blocks + 1
    assert eng_off.cache_k.shape[1] == eng_off.num_blocks
    # launch shape: the fused step drops BOTH per-layer append scatters
    on_l = eng_on._launches
    off_l = eng_off._launches
    assert on_l["scatters"] == 0 and off_l["scatters"] == 2
    # launch-shaped primitives, not raw equations: the fused call reshapes
    # its one-row operands to TPU-legal blocks ([b, nkv, 1, hd] /
    # [b, 1, hd] views), which are equations but not launches
    assert (on_l["pallas_calls"] + on_l["scatters"]
            < off_l["pallas_calls"] + off_l["scatters"])


def test_engine_fused_audit_green(monkeypatch):
    """The runtime auditor (I1 incl. the new spill-page geometry check,
    I2..I8) stays green through a full-feature serve on the fused
    engine."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    out, eng = _serve_tokens(cfg, params, disable=None, audit=True,
                             monkeypatch=monkeypatch)
    assert eng._fused and all(len(v) == 8 for v in out.values())


def test_engine_fused_audit_catches_spill_drift(monkeypatch):
    """Corruption injection: an engine whose pool lost its spill page (or
    grew a stray one) must fail I1 — dropped writes would corrupt a real
    page."""
    from paddle_tpu.analysis.engine_audit import EngineAuditError, \
        audit_engine

    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   chunk=1, paged=True, block_size=8)
    assert eng._fused
    audit_engine(eng)                                   # healthy
    eng.cache_k = eng.cache_k[:, :-1]                   # lose the spill page
    with pytest.raises(EngineAuditError, match="I1"):
        audit_engine(eng)


def test_engine_tp2_flash_fused_token_identity(monkeypatch):
    """TP=2 shard_map composes with the fused split-K decode: the sharded
    engine is token-identical to TP=1 (greedy AND seeded), both on the
    flash+fused default."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    tp1, eng1 = _serve_tokens(cfg, params, disable=None,
                              monkeypatch=monkeypatch)
    tp2, eng2 = _serve_tokens(cfg, params, disable=None, tensor_parallel=2,
                              monkeypatch=monkeypatch)
    assert eng1._fused and eng2._fused and eng2.tp == 2
    assert tp1 == tp2


def test_engine_dense_mode_unaffected(monkeypatch):
    """The dense (non-paged) engine never takes the fused path — no spill
    page, no fused counter ticks."""
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    pa.reset_kernel_counters()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   chunk=2)
    out = eng.serve([Request(rid=0, prompt_ids=np.arange(1, 9,
                                                         dtype=np.int32),
                             max_new_tokens=4)])
    assert not eng._fused and len(out[0]) == 4
    assert pa.FUSED_KERNEL_CALLS == 0


# ---------------------------------------------------------------------------
# lint gate: the fused step's allowlist is exact
# ---------------------------------------------------------------------------

def test_lint_gate_rejects_new_upcast_in_fused_step():
    """The serving_flash_decode_step target passes the gate with ONLY the
    reasoned combine/kernel allowlist entries (asserted by the in-process
    gate test); any OTHER upcast riding the fused step — modeled here as a
    bf16-tainted f32 dot appended after the step, the shape of a stray
    unfused epilogue — must survive the allowlist and gate."""
    from paddle_tpu.analysis import analyze, load_allowlist
    from paddle_tpu.analysis.targets import build

    t = build("serving_flash_decode_step")
    w = jnp.ones((8, 8), jnp.bfloat16)

    def leaky(*args):
        outs = t.fn(*args)
        leak = jnp.dot(w.astype(jnp.float32), w.astype(jnp.float32).T)
        return (outs[0] + leak.sum().astype(outs[0].dtype),) + outs[1:]

    r = analyze(leaky, *t.args, target="serving_flash_decode_step",
                rules=("dtype_upcast",), allowlist=load_allowlist())
    bad = [f for f in r.findings if f.rule == "dtype_upcast"]
    assert bad, "a non-allowlisted upcast in the fused step must gate"
