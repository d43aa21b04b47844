"""Decode megastep stage 2 (ISSUE 15, docs/paged_attention.md "Megastep
stage 2"): fused post-attention layer half + in-kernel requantized KV
append.

Kernel level: the fused residual+RMSNorm+SwiGLU launch must reproduce the
unfused composition byte-for-byte under jit in the single-block regime
(it reuses rms_norm's f32 math and swiglu's silu-in-f32, with the normed
activations rounded to the input dtype before the gate/up dots — same
operand bytes either way); in the multi-block weight-streaming regime the
cross-block f32 accumulation keeps f32 byte-exact and holds bf16 to the
repo's standard empirical within-ulp kernel contract.

Engine level: stage 2 is the paged decode path's NEW DEFAULT — a decode
layer is at most TWO Pallas launches (fused attention step + fused MLP
half), asserted against the static ProgramCard census, and int8/packed-
int4 pools take the fused append path (0 scatters per decode step).
Token identity is asserted three ways (default vs kill-switched vs gather
oracle) with every serving feature ON, greedy AND seeded sampled, and
under TP=2 shard_map.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.ops.pallas import paged_attention as pa


# ---------------------------------------------------------------------------
# fused MLP kernel parity
# ---------------------------------------------------------------------------

def _mlp_case(rs, *, B=3, h=32, inter=64, dtype=jnp.float32):
    x = jnp.asarray(rs.randn(B, h), dtype)
    ay = jnp.asarray(rs.randn(B, h), dtype)
    w = jnp.asarray(rs.randn(h), dtype)
    wg = jnp.asarray(rs.randn(h, inter) / np.sqrt(h), dtype)
    wu = jnp.asarray(rs.randn(h, inter) / np.sqrt(h), dtype)
    wd = jnp.asarray(rs.randn(inter, h) / np.sqrt(inter), dtype)
    return x, ay, w, wg, wu, wd


def _assert_y_close(y, y_r, dtype):
    """Two ulps of the dtype at the oracle's magnitude (bf16: 2^-8, f32:
    2^-23 with headroom for the reordered f32 sums)."""
    yf = np.asarray(y, np.float32)
    yf_r = np.asarray(y_r, np.float32)
    ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5
    tol = 2.0 * ulp * max(np.max(np.abs(yf_r)), 1.0)
    np.testing.assert_allclose(yf, yf_r, rtol=0, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,h,inter", [(3, 32, 64), (1, 16, 48), (8, 64, 256)])
def test_fused_layer_mlp_matches_reference(dtype, B, h, inter):
    """Fused launch vs the unfused composition, both jitted: h1 is
    byte-equal; the un-reduced down projection agrees within the dtype's
    rounding.  Every in-kernel dot accumulates in f32 and rounds once (the
    only accumulator the MXU has — Mosaic refuses a bf16 one), while the
    reference's dots are whatever XLA's backend makes of them, so the
    gate/up activations can differ by an ulp of the input dtype."""
    rs = np.random.RandomState(0)
    case = _mlp_case(rs, B=B, h=h, inter=inter, dtype=dtype)
    pa.reset_kernel_counters()
    h1, y = jax.jit(lambda *a: pa.fused_layer_mlp(*a, 1e-5))(*case)
    assert pa.MLP_KERNEL_CALLS == 1, "kernel path not taken"
    h1_r, y_r = jax.jit(lambda *a: pa.fused_layer_mlp_reference(*a, 1e-5))(
        *case)
    assert h1.dtype == dtype and y.dtype == dtype
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h1_r))
    _assert_y_close(y, y_r, dtype)


@pytest.mark.parametrize("inter", [512, 1024])
def test_fused_layer_mlp_multi_block_parity(inter):
    """The weight-streaming regime (grid > 1 ffn block — the kernel's
    reason to exist): the cross-block f32 accumulation reorders the
    down-projection sum relative to XLA's single dot, so ``y`` carries
    the repo's standard empirical kernel contract (within-ulp of the
    oracle, like the split-K combine) while ``h1`` stays byte-exact."""
    blocks = inter // pa.fused_mlp_block_cols(inter)
    assert blocks > 1, "case must exercise the streaming loop"
    for dtype in (jnp.float32, jnp.bfloat16):
        rs = np.random.RandomState(2)
        case = _mlp_case(rs, B=4, h=64, inter=inter, dtype=dtype)
        pa.reset_kernel_counters()
        h1, y = jax.jit(lambda *a: pa.fused_layer_mlp(*a, 1e-5))(*case)
        assert pa.MLP_KERNEL_CALLS == 1, "kernel path not taken"
        h1_r, y_r = jax.jit(
            lambda *a: pa.fused_layer_mlp_reference(*a, 1e-5))(*case)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h1_r))
        _assert_y_close(y, y_r, dtype)


def test_fused_mlp_block_cols_heuristic():
    """Weight-streaming block width: whole ffn when it fits, else the
    largest sublane-multiple divisor <= 256; indivisible widths fall back
    to one whole block."""
    assert pa.fused_mlp_block_cols(64) == 64
    assert pa.fused_mlp_block_cols(256) == 256
    assert pa.fused_mlp_block_cols(512) == 256
    assert pa.fused_mlp_block_cols(11008) == 256      # 11008 = 256 * 43
    assert 11008 % pa.fused_mlp_block_cols(11008) == 0
    assert pa.fused_mlp_block_cols(1000) == 200
    assert pa.fused_mlp_block_cols(262) == 262        # no /8 divisor fits


def test_fused_mlp_kill_switch_and_fallback(monkeypatch):
    """PADDLE_TPU_DISABLE_PALLAS=fused_layer_mlp routes to the unfused
    composition exactly (counter evidence both ways)."""
    rs = np.random.RandomState(1)
    case = _mlp_case(rs)
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    pa.reset_kernel_counters()
    pa.fused_layer_mlp(*case, 1e-5)
    assert pa.MLP_KERNEL_CALLS == 1 and pa.MLP_FALLBACK_CALLS == 0

    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "fused_layer_mlp")
    pa.reset_kernel_counters()
    h1, y = pa.fused_layer_mlp(*case, 1e-5)
    assert pa.MLP_FALLBACK_CALLS == 1 and pa.MLP_KERNEL_CALLS == 0
    h1_r, y_r = pa.fused_layer_mlp_reference(*case, 1e-5)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h1_r))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_r))


def test_envflags_did_you_mean_new_tokens(monkeypatch):
    """The stage-2 kill switches are registered vocabulary: typos get the
    did-you-mean warning naming the intended token (satellite: a switch
    reached for mid-incident must never be silently ignored)."""
    from paddle_tpu.ops.pallas import KNOWN_KERNELS, kernel_disabled

    assert {"fused_layer_mlp", "fused_quant_append"} <= KNOWN_KERNELS
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "fused_layer_mpl")
    with pytest.warns(UserWarning, match="fused_layer_mlp"):
        assert not kernel_disabled("fused_layer_mlp")
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "fused_quant_apend")
    with pytest.warns(UserWarning, match="fused_quant_append"):
        assert not kernel_disabled("fused_quant_append")
    # the real tokens parse silently and disable exactly their member
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "fused_quant_append")
    assert kernel_disabled("fused_quant_append")
    assert not kernel_disabled("fused_decode_step")


def test_reset_kernel_counters_covers_stage2_counters():
    """reset_kernel_counters zeroes the NEW stage-2 pairs too (module
    state persisting across engines — the per-rung bench hygiene)."""
    rs = np.random.RandomState(2)
    pa.fused_layer_mlp(*_mlp_case(rs), 1e-5)
    assert pa.MLP_KERNEL_CALLS > 0
    pa.reset_kernel_counters()
    for name in ("MLP_KERNEL_CALLS", "MLP_FALLBACK_CALLS",
                 "QUANT_APPEND_KERNEL_CALLS",
                 "QUANT_APPEND_FALLBACK_CALLS"):
        assert getattr(pa, name) == 0, name


# ---------------------------------------------------------------------------
# engine: stage-2 identity + launch census (the acceptance matrix)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                  kv_heads=2, inter=64)


def _serve_tokens(cfg, params, *, disable=None, tensor_parallel=1,
                  audit=False, monkeypatch=None, **eng_kwargs):
    """One engine under the given kill-switch tokens serving the standard
    all-features workload (prefix-shared prompts, chunked prefill,
    speculation, greedy + seeded sampled)."""
    assert monkeypatch is not None
    if disable:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", ",".join(disable))
    else:
        monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1" if audit else "0")
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


def test_engine_stage2_three_way_identity_and_launch_drop(monkeypatch):
    """ISSUE-15 acceptance (fp): the stage-2 default engine is
    token-identical to the fused_layer_mlp-killed stage-1 engine, the
    fully kill-switched pre-fusion engine AND the gather-oracle engine —
    all features on, greedy + seeded — and the default decode layer is at
    most TWO Pallas launches, asserted against the static ProgramCard
    census."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    s2, eng2 = _serve_tokens(cfg, params, disable=None,
                             monkeypatch=monkeypatch)
    s1, eng1 = _serve_tokens(cfg, params, disable=("fused_layer_mlp",),
                             monkeypatch=monkeypatch)
    pre, eng0 = _serve_tokens(
        cfg, params, disable=("flash_decode", "fused_decode_step"),
        monkeypatch=monkeypatch)
    gather, engg = _serve_tokens(cfg, params, disable=("paged_attention",),
                                 monkeypatch=monkeypatch)
    assert s2 == s1 == pre == gather
    assert eng2._fused and eng2._fused_mlp
    assert eng1._fused and not eng1._fused_mlp
    assert not eng0._fused

    # launch census: the scan body holds the per-layer program ONCE, the
    # final norm launches outside it — stage 2 = fused attention + fused
    # MLP per layer (2) + final norm (1); stage 1 pays the separate
    # input-norm launch back (3 + 1)
    l2, l1, l0 = eng2._launches, eng1._launches, eng0._launches
    per_layer_s2 = l2["pallas_calls"] - 1
    assert per_layer_s2 <= 2, l2
    assert l2["pallas_calls"] == 3 and l1["pallas_calls"] == 4, (l2, l1)
    assert l2["scatters"] == 0 and l1["scatters"] == 0
    assert l0["scatters"] == 2                     # pre-fusion appends back
    # (eqn counts are NOT compared: inlining the input norm and the MLP
    # call's pad/reshape plumbing trade eqns for launches — the launch
    # census above is the dispatch-tax metric)
    # static ProgramCard census == dynamic telemetry (one implementation,
    # but the card path re-derives through analysis/cost_model).  The
    # card re-traces under the AMBIENT env — restore the default arm's
    # (the last _serve_tokens call left the gather oracle's pinned)
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    card = eng2.decode_step_card()
    assert card["pallas_calls"] == l2["pallas_calls"]
    assert card["scatters"] == l2["scatters"]
    assert card["fused_mlp"] is True


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_engine_quant_fused_zero_scatters_identity(mode, monkeypatch):
    """ISSUE-15 acceptance (quantized pools): the int8/packed-int4 engine
    reports 0 scatters per decode step with the fused append ON, and is
    token-identical to the kill-switched requant-scatter arm AND the
    gather-oracle arm — all features on, greedy + seeded."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    pa.reset_kernel_counters()
    fused, engf = _serve_tokens(cfg, params, disable=None,
                                monkeypatch=monkeypatch, kv_quant=mode)
    assert engf._fused and engf._fused_mlp and engf.kv_quant == mode
    assert pa.QUANT_APPEND_KERNEL_CALLS > 0
    scat, engs = _serve_tokens(cfg, params,
                               disable=("fused_quant_append",),
                               monkeypatch=monkeypatch, kv_quant=mode)
    assert not engs._fused
    gather, engg = _serve_tokens(cfg, params, disable=("paged_attention",),
                                 monkeypatch=monkeypatch, kv_quant=mode)
    assert fused == scat == gather
    lf, ls = engf._launches, engs._launches
    assert lf["scatters"] == 0 and lf["kv_quant"] == mode
    # the unfused arm pays the requant-scatter pair per pool: codes +
    # per-page scale, k and v = 4 scatters per decode step
    assert ls["scatters"] == 4
    assert lf["pallas_calls"] < ls["pallas_calls"]


def test_engine_quant_audit_green(monkeypatch):
    """The runtime auditor (I1 pool partition incl. quant pytree pools +
    spill geometry, I2..I8) stays green through a full-feature quantized
    serve on the fused default."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    out, eng = _serve_tokens(cfg, params, disable=None, audit=True,
                             monkeypatch=monkeypatch, kv_quant="int8")
    assert eng._fused and eng._fused_mlp
    assert all(len(v) == 8 for v in out.values())


def test_engine_quant_tp2_identity(monkeypatch):
    """TP=2 shard_map composes with the quantized fused step (codes AND
    per-page scales shard along kv_heads): token-identical to TP=1,
    greedy + seeded."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    tp1, e1 = _serve_tokens(cfg, params, disable=None,
                            monkeypatch=monkeypatch, kv_quant="int8")
    tp2, e2 = _serve_tokens(cfg, params, disable=None, tensor_parallel=2,
                            monkeypatch=monkeypatch, kv_quant="int8")
    assert e1._fused and e2._fused and e2.tp == 2
    assert tp1 == tp2


def test_kv_quant_ctor_validation():
    """kv_quant is validated before any pool geometry exists: bad mode,
    dense mode, and packed-int4 over an odd head_dim all raise."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="kv_quant"):
        ContinuousBatchingEngine(cfg, params, kv_quant="int2", paged=True,
                                 block_size=8)
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingEngine(cfg, params, kv_quant="int8")
    odd = llama.LlamaConfig.tiny(vocab=64, hidden=36, layers=1, heads=4,
                                 kv_heads=4, inter=32)   # head_dim = 9
    assert odd.head_dim % 2 == 1, odd.head_dim
    params_odd = llama.init_params(odd, jax.random.key(0))
    with pytest.raises(ValueError, match="even head_dim"):
        ContinuousBatchingEngine(odd, params_odd, kv_quant="int4",
                                 paged=True, block_size=8)


def test_snapshot_kv_quant_topology_mismatch_raises(monkeypatch):
    """Pool storage changes the teacher-forced logits (requantized appends
    are lossy), so a kv_quant-mismatched restore must raise — same
    contract as every other topology field except tp degree."""
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    eq = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                  paged=True, block_size=8, kv_quant="int8")
    eq.serve([Request(rid=0, prompt_ids=np.arange(1, 9, dtype=np.int32),
                      max_new_tokens=2)])
    snap = eq.snapshot()
    assert snap["engine"]["kv_quant"] == "int8"
    efp = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   paged=True, block_size=8)
    with pytest.raises(ValueError, match="kv_quant"):
        efp.restore(snap)


def test_quant_tier_demote_readmit_roundtrip():
    """Hierarchical-KV composition (docs/kv_tier.md): an int8 engine's
    demoted pages carry codes + per-page scales through the host tier and
    restore byte-exactly — the revisit matches through the tier, restores
    H2D, and emits exactly the tokens the first serve did (and a tier-off
    engine does)."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    rs = np.random.RandomState(3)
    P = rs.randint(1, 128, (30,)).astype(np.int32)     # 3 full blocks + 6

    def run(tier: bool):
        eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64,
                                       chunk=1, paged=True, block_size=8,
                                       num_blocks=8, kv_quant="int8",
                                       enable_prefix_caching=True,
                                       enable_chunked_prefill=True,
                                       prefill_chunk=5,
                                       enable_host_kv_tier=tier)
        first = eng.serve([Request(rid=0, prompt_ids=P, max_new_tokens=4)])
        rs2 = np.random.RandomState(4)
        for i in range(3):      # disjoint pressure: evict P's chain
            q = rs2.randint(1, 128, (40,)).astype(np.int32)
            eng.serve([Request(rid=10 + i, prompt_ids=q, max_new_tokens=4)])
        again = eng.serve([Request(rid=1, prompt_ids=P, max_new_tokens=4)])
        return eng, first[0], again[1]

    eng_t, first_t, again_t = run(True)
    eng_o, first_o, again_o = run(False)
    assert first_t == first_o and again_t == again_o
    assert again_t == first_t
    assert eng_t.stats["tier_readmits"] > 0, "no quant page restored H2D"
    assert eng_o.stats["tier_readmits"] == 0


def test_tier_storage_format_mismatch_falls_back():
    """A SHARED fleet tier keys entries by token-chain hash alone, so a
    replica with different pool storage (fp vs int8) can match a chain
    another replica demoted: the restore must treat the incompatible
    entry as a miss — compute the block, emit correct tokens, never cast
    foreign bytes into the pool — and leave the entry for compatible
    replicas."""
    from paddle_tpu.inference.kv_tier import HostKVTier

    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    rs = np.random.RandomState(5)
    P = rs.randint(1, 128, (30,)).astype(np.int32)

    def engine(kvq, tier):
        return ContinuousBatchingEngine(cfg, params, max_batch=1,
                                        max_seq=64, chunk=1, paged=True,
                                        block_size=8, num_blocks=8,
                                        kv_quant=kvq,
                                        enable_prefix_caching=True,
                                        enable_chunked_prefill=True,
                                        prefill_chunk=5,
                                        enable_host_kv_tier=tier is not None,
                                        host_tier=tier)

    for demoter_q, restorer_q in ((None, "int8"), ("int8", None)):
        tier = HostKVTier(budget_bytes=1 << 20, shared=True)
        src = engine(demoter_q, tier)
        src.serve([Request(rid=0, prompt_ids=P, max_new_tokens=4)])
        src._reclaim(src._pcache.resident_blocks())    # demote P's chain
        assert len(tier) >= 3
        dst = engine(restorer_q, tier)
        got = dst.serve([Request(rid=1, prompt_ids=P, max_new_tokens=4)])
        ref = engine(restorer_q, None).serve(
            [Request(rid=2, prompt_ids=P, max_new_tokens=4)])
        assert got[1] == ref[2], (demoter_q, restorer_q)
        assert dst.stats["tier_readmits"] == 0, \
            "restored a foreign-format page"
        # shared tier keeps the entries for compatible replicas
        assert len(tier) >= 3
