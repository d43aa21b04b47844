"""Continuous-batching decode scheduler tests (VERDICT r2 #6).

The correctness bar: tokens generated by the slot-pool engine must exactly
match per-request greedy decoding with a full-forward oracle, including when
requests are admitted mid-flight into a batch whose other lanes are at
different depths (the continuous-batching case the reference's
block_multihead_attention serving stack exists for)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.models import llama


def _tiny():
    cfg = llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                                 kv_heads=2, inter=64)
    cfg.dtype = jnp.float32  # exact parity
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def _oracle(cfg, params, prompt, n):
    ids = jnp.asarray(prompt[None], jnp.int32)
    out = []
    for _ in range(n):
        logits = llama.forward(cfg, params, ids, use_flash=False, remat=False)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        ids = jnp.concatenate([ids, jnp.full((1, 1), nxt, jnp.int32)], axis=1)
    return out


def test_continuous_batching_matches_oracle():
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64)
    rs = np.random.RandomState(3)
    # 4 requests through 2 slots with different prompt lengths and budgets:
    # forces staggered admission and retire-then-admit mid-decode
    reqs = [Request(rid=i,
                    prompt_ids=rs.randint(0, 128, (ln,)).astype(np.int32),
                    max_new_tokens=nt)
            for i, (ln, nt) in enumerate([(5, 6), (9, 3), (3, 7), (7, 4)])]
    got = eng.serve(reqs)
    for r in reqs:
        want = _oracle(cfg, params, r.prompt_ids, r.max_new_tokens)
        assert got[r.rid] == want, f"request {r.rid}: {got[r.rid]} != {want}"
    assert eng.stats["prefills"] == 4
    # with 2 slots and staggered lengths, batched steps must have run lanes
    # at different depths — fewer steps than serial decoding would take
    assert eng.stats["decode_steps"] < sum(r.max_new_tokens for r in reqs)


def test_eos_retires_slot():
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64)
    prompt = np.arange(4, dtype=np.int32)
    first = _oracle(cfg, params, prompt, 1)[0]
    r = Request(rid=0, prompt_ids=prompt, max_new_tokens=10, eos_token_id=first)
    got = eng.serve([r])
    assert got[0] == [first] and r.finished


def test_chunked_decode_matches_oracle():
    """chunk>1 feeds tokens back on-device (one host RTT per chunk); greedy
    output must still exactly match the per-step oracle, including budget
    trimming when max_new_tokens is not a chunk multiple."""
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64, chunk=4)
    rs = np.random.RandomState(5)
    reqs = [Request(rid=i,
                    prompt_ids=rs.randint(0, 128, (ln,)).astype(np.int32),
                    max_new_tokens=nt)
            for i, (ln, nt) in enumerate([(5, 6), (9, 3), (3, 7)])]
    got = eng.serve(reqs)
    for r in reqs:
        want = _oracle(cfg, params, r.prompt_ids, r.max_new_tokens)
        assert got[r.rid] == want, f"request {r.rid}: {got[r.rid]} != {want}"
    # chunked: fewer host round-trips than tokens generated
    assert eng.stats["decode_steps"] % 4 == 0


def test_chunked_eos_trimming():
    cfg, params = _tiny()
    prompt = np.arange(4, dtype=np.int32)
    first = _oracle(cfg, params, prompt, 1)[0]
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64, chunk=4)
    r = Request(rid=0, prompt_ids=prompt, max_new_tokens=10, eos_token_id=first)
    got = eng.serve([r])
    assert got[0] == [first] and r.finished


def test_chunked_cache_boundary():
    cfg, params = _tiny()
    S = 16
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=S, chunk=4)
    r = Request(rid=0, prompt_ids=np.arange(1, S, dtype=np.int32), max_new_tokens=5)
    got = eng.serve([r])
    # only positions S-2, S-1 are usable; garbage chunk tail must be trimmed
    assert len(got[0]) == 2
    want = _oracle(cfg, params, r.prompt_ids, 2)
    assert got[0] == want


def test_serve_rejects_bad_request_and_serves_the_rest():
    """Graceful mode (the default): an invalid request is marked REJECTED
    (with ``error``) and the rest of the batch still serves — one bad
    request must not zero the whole batch's goodput
    (docs/fault_tolerance.md)."""
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=32)
    good = Request(rid=0, prompt_ids=np.arange(4, dtype=np.int32), max_new_tokens=2)
    bad = Request(rid=1, prompt_ids=np.arange(100, dtype=np.int32))
    got = eng.serve([good, bad])
    assert len(got[0]) == 2
    assert got[1] == []
    assert bad.status == "REJECTED" and bad.finished
    assert "exceeds" in bad.error
    assert eng.stats["requests_rejected"] == 1
    # the rejected request left nothing behind — a later serve is unaffected
    assert eng._queue == []
    other = Request(rid=2, prompt_ids=np.arange(3, dtype=np.int32), max_new_tokens=2)
    got = eng.serve([other])
    assert list(got) == [2] and len(got[2]) == 2


def test_cache_boundary_uses_last_position():
    """A prompt of max_seq-1 tokens can still generate 2 tokens: decode may
    write K/V at position max_seq-1 (the off-by-one the review caught)."""
    cfg, params = _tiny()
    S = 16
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=S)
    r = Request(rid=0, prompt_ids=np.arange(1, S, dtype=np.int32), max_new_tokens=5)
    got = eng.serve([r])
    assert len(got[0]) == 2  # positions S-2 (last prompt tok) and S-1
    want = _oracle(cfg, params, r.prompt_ids, 2)
    assert got[0] == want


def test_decode_throughput_counter():
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64)
    reqs = [Request(rid=i, prompt_ids=np.arange(1, 5, dtype=np.int32),
                    max_new_tokens=4) for i in range(2)]
    eng.serve(reqs)
    assert eng.stats["decode_tokens"] == 8
    assert eng.decode_tokens_per_s > 0


def test_decode_counter_excludes_trimmed_chunk_tail():
    """decode_tokens (the tok/s headline numerator) counts only tokens a
    caller receives: chunk steps past the token budget are trimmed host-side
    and must not inflate throughput (round-3 advisor finding)."""
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64, chunk=4)
    reqs = [Request(rid=i, prompt_ids=np.arange(1, 5, dtype=np.int32),
                    max_new_tokens=3) for i in range(2)]  # ends mid-chunk
    got = eng.serve(reqs)
    delivered = sum(len(v) for v in got.values())
    assert delivered == 6
    assert eng.stats["decode_tokens"] == delivered  # not chunk*active = 8


# ---------------- paged (block-table) KV cache ----------------

def test_paged_matches_dense():
    """Block-table cache produces exactly the dense engine's greedy tokens
    on mixed prompt lengths (round-3 verdict #6)."""
    cfg, params = _tiny()
    prompts = [np.arange(1, n, dtype=np.int32) for n in (5, 17, 9, 26)]
    dense = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64, chunk=2)
    ref = dense.serve([Request(rid=i, prompt_ids=p, max_new_tokens=6)
                       for i, p in enumerate(prompts)])
    paged = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                     chunk=2, paged=True, block_size=8)
    got = paged.serve([Request(rid=i, prompt_ids=p, max_new_tokens=6)
                       for i, p in enumerate(prompts)])
    assert got == ref


def test_paged_oversubscribed_pool_preempts_and_completes():
    """Pool holds fewer pages than the slots' worst case: long prompts force
    admission waits and mid-flight preemption, yet every request finishes
    with the dense engine's exact tokens (greedy recompute resume)."""
    cfg, params = _tiny()
    prompts = [np.arange(1, 40, dtype=np.int32),
               np.arange(2, 35, dtype=np.int32),
               np.arange(3, 30, dtype=np.int32)]
    dense = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64, chunk=1)
    ref = dense.serve([Request(rid=i, prompt_ids=p, max_new_tokens=10)
                       for i, p in enumerate(prompts)])
    # 10 pages * 8 = 80 logical tokens shared; two 40+-token slots exceed it
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   chunk=1, paged=True, block_size=8,
                                   num_blocks=10)
    got = eng.serve([Request(rid=i, prompt_ids=p, max_new_tokens=10)
                     for i, p in enumerate(prompts)])
    assert got == ref
    # the 10-page pool cannot hold two 40+-token residents through their
    # full decode: preemption must actually have fired
    assert eng.stats["preemptions"] > 0


def test_paged_blocks_released_on_retire():
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   chunk=2, paged=True, block_size=8,
                                   num_blocks=12)
    eng.serve([Request(rid=i, prompt_ids=np.arange(1, 20, dtype=np.int32),
                       max_new_tokens=5) for i in range(4)])
    assert sorted(eng._free) == list(range(12))
    assert (eng._table == eng.num_blocks).all()
    assert all(not b for b in eng._slot_blocks)


def test_paged_admission_waits_for_free_pages():
    """A request whose prompt needs more pages than are free stays queued
    (admission by free-page count), then runs once pages return."""
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=3, max_seq=64,
                                   chunk=1, paged=True, block_size=8,
                                   num_blocks=8)
    # one fat request (needs 5 pages) + two medium (3 pages each):
    # all three can never be resident together (5+3+3 > 8)
    reqs = [Request(rid=0, prompt_ids=np.arange(1, 40, dtype=np.int32),
                    max_new_tokens=4),
            Request(rid=1, prompt_ids=np.arange(1, 20, dtype=np.int32),
                    max_new_tokens=4),
            Request(rid=2, prompt_ids=np.arange(1, 20, dtype=np.int32),
                    max_new_tokens=4)]
    got = eng.serve(reqs)
    assert all(len(v) == 4 for v in got.values())


def test_paged_single_request_too_big_raises():
    cfg, params = _tiny()
    with pytest.raises(AssertionError):
        ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                 paged=True, block_size=8, num_blocks=4)


def test_paged_failed_admission_leaks_no_blocks():
    """A request too big for the current free pool must not strand pages on
    an empty slot (rollback + headroom admission; review-caught scenario:
    partial allocation on a slot with no request was invisible to every
    release path, starving the pool into RuntimeError or a serve() spin)."""
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   chunk=1, paged=True, block_size=8,
                                   num_blocks=8)
    reqs = [Request(rid=0, prompt_ids=np.arange(1, 11, dtype=np.int32),
                    max_new_tokens=20),  # 2 pages, grows across a boundary
            Request(rid=1, prompt_ids=np.arange(1, 60, dtype=np.int32),
                    max_new_tokens=2)]   # needs 8 pages: must wait for rid 0
    got = eng.serve(reqs)
    assert len(got[0]) == 20 and len(got[1]) == 2
    assert sorted(eng._free) == list(range(8))


def test_paged_kernel_on_off_token_identical(monkeypatch):
    """ISSUE-1 acceptance: the paged CB engine's token streams are identical
    with the ragged paged-attention Pallas kernel ON (the new default) and
    OFF (PADDLE_TPU_DISABLE_PALLAS=paged_attention → today's gather path),
    across ragged prompt lengths, GQA, chunked decode, and mid-flight
    admission.  The dispatch happens at trace time, so each engine is built
    AND served under its own env setting."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    cfg, params = _tiny()
    prompts = [np.arange(1, n, dtype=np.int32) for n in (5, 30, 9, 50, 17)]

    def run():
        eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                       chunk=4, paged=True, block_size=8)
        return eng.serve([Request(rid=i, prompt_ids=p, max_new_tokens=6)
                          for i, p in enumerate(prompts)])

    # the decode path may take any member of the paged kernel family:
    # sequential, split-K flash, or the fused rope+append+attend step
    # (the PR-9 default, ISSUE 10) — "kernel on" means one of them traced
    def family_calls():
        return (pa.KERNEL_CALLS + pa.FLASH_KERNEL_CALLS
                + pa.FUSED_KERNEL_CALLS)

    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    k0 = family_calls()
    with_kernel = run()
    assert family_calls() > k0, "paged kernel path not taken"

    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "paged_attention")
    k0 = family_calls()
    without = run()
    # disabled engines take their own in-graph gather attend (not even the
    # op's reference) — no member of the kernel family may have traced
    assert family_calls() == k0

    assert with_kernel == without
    # and both match the dense (non-paged) engine — the original oracle
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    dense = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                     chunk=4)
    ref = dense.serve([Request(rid=i, prompt_ids=p, max_new_tokens=6)
                       for i, p in enumerate(prompts)])
    assert with_kernel == ref


# ---------------- per-request sampling (top-p / temperature) ----------------

def test_mixed_greedy_and_sampled_batch():
    """One compiled program serves mixed greedy/sampled lanes: greedy
    requests' tokens are bit-identical to the oracle regardless of sampled
    neighbors, and a seeded sampled request is deterministic across engines
    (reference: top_p_sampling, ops.yaml:4947)."""
    cfg, params = _tiny()
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 128, (n,)).astype(np.int32) for n in (5, 7, 9)]

    def build():
        return [Request(rid=0, prompt_ids=prompts[0], max_new_tokens=6),
                Request(rid=1, prompt_ids=prompts[1], max_new_tokens=6,
                        temperature=0.9, top_p=0.8, seed=42),
                Request(rid=2, prompt_ids=prompts[2], max_new_tokens=6,
                        temperature=1.3, seed=7)]

    eng = ContinuousBatchingEngine(cfg, params, max_batch=3, max_seq=64)
    got = eng.serve(build())
    # greedy lane unchanged by sampled neighbors
    assert got[0] == _oracle(cfg, params, prompts[0], 6)
    # sampled lanes produce valid ids and are reproducible given the seed
    assert all(0 <= t < cfg.vocab_size for t in got[1] + got[2])
    eng2 = ContinuousBatchingEngine(cfg, params, max_batch=3, max_seq=64)
    assert eng2.serve(build()) == got


def test_sampling_seed_changes_stream():
    cfg, params = _tiny()
    p = np.arange(1, 8, dtype=np.int32)

    def run(seed):
        eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64)
        return eng.serve([Request(rid=0, prompt_ids=p, max_new_tokens=12,
                                  temperature=1.5, seed=seed)])[0]

    a, b = run(1), run(2)
    assert a != b  # 12 draws at temp 1.5 over 128 tokens: collision ~ 0


def test_top_p_nucleus_restricts_support():
    """With a peaked distribution and small top_p, sampling must stay inside
    the nucleus (here: the argmax token only), for every seed."""
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64)
    logits = jnp.zeros((2, cfg.vocab_size), jnp.float32)
    logits = logits.at[:, 17].set(5.0)  # softmax mass ~ 0.54 at temp 1
    for seed in range(8):
        tok = eng._sample_tokens(
            logits, jnp.asarray([3, 9], jnp.int32),
            jnp.asarray([1.0, 1.0], jnp.float32),
            jnp.asarray([0.5, 0.5], jnp.float32),
            jnp.asarray([seed, seed + 100], jnp.int32))
        assert int(tok[0]) == 17 and int(tok[1]) == 17


def test_chunked_sampling_matches_unchunked():
    """Position-derived RNG keys make the sampled stream identical whether
    tokens feed back on-device (chunk=4) or per-step (chunk=1)."""
    cfg, params = _tiny()
    p = np.arange(2, 9, dtype=np.int32)

    def run(chunk):
        eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64,
                                       chunk=chunk)
        return eng.serve([Request(rid=0, prompt_ids=p, max_new_tokens=8,
                                  temperature=0.8, top_p=0.9, seed=5)])[0]

    assert run(1) == run(4)


def test_sampled_preemption_resume_exact():
    """Preemption + resume must not fork a sampled stream: the paged engine
    under an oversubscribed pool (preemptions fire) produces exactly the
    un-oversubscribed dense engine's tokens for the same seeds."""
    cfg, params = _tiny()
    prompts = [np.arange(1, 40, dtype=np.int32),
               np.arange(2, 35, dtype=np.int32),
               np.arange(3, 30, dtype=np.int32)]

    def build():
        return [Request(rid=i, prompt_ids=p, max_new_tokens=10,
                        temperature=0.9, top_p=0.85, seed=100 + i)
                for i, p in enumerate(prompts)]

    dense = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                     chunk=1)
    ref = dense.serve(build())
    eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                   chunk=1, paged=True, block_size=8,
                                   num_blocks=10)
    got = eng.serve(build())
    assert got == ref
    assert eng.stats["preemptions"] > 0


def test_sampling_validation():
    cfg, params = _tiny()
    eng = ContinuousBatchingEngine(cfg, params, max_batch=1, max_seq=64)
    with pytest.raises(ValueError):
        eng.add_request(Request(rid=0, prompt_ids=np.arange(3, dtype=np.int32),
                                temperature=-0.1))
    with pytest.raises(ValueError):
        eng.add_request(Request(rid=1, prompt_ids=np.arange(3, dtype=np.int32),
                                top_p=0.0))
    with pytest.raises(ValueError):
        eng.add_request(Request(rid=2, prompt_ids=np.arange(3, dtype=np.int32),
                                top_p=1.5))
