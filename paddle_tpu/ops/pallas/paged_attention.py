"""Ragged paged-attention decode kernel (Pallas TPU).

Replaces the pure-XLA page-attention fallback for the continuous-batching
decode path (reference: ``block_multihead_attention_``, fused_ops.yaml:45;
kernel design: "Ragged Paged Attention" — PAPERS.md).  The gather fallback
(`ops/decode_attention.py`) reads every slot's KV out to the *maximum*
logical length (`max_blocks * block_size`) and masks the ragged tail, so
HBM bytes per decode step scale with the longest request in the batch.
This kernel walks each slot's block table and streams only the LIVE pages:

- grid ``(slots, kv_heads, logical_pages)`` with the page dim innermost
  (sequential) — one grid step = one physical KV page for one (slot, head);
- the block table and per-slot ``seq_lens`` ride in as scalar-prefetch
  operands (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index
  maps resolve the PHYSICAL page id before the DMA is issued — the gather
  never materializes in HBM;
- pages past a slot's live count are remapped to its last live page:
  Mosaic elides the copy when consecutive grid steps fetch the same block,
  so a slot at 1/8th of max_seq costs ~1/8th of the page reads (the ragged
  win), and the compute for those steps is skipped with ``pl.when``;
- online-softmax accumulation in VMEM scratch (same recurrence as
  ``flash_attention.py``), finalized on the last page;
- GQA-aware: q is viewed ``[slots, kv_heads, group, head_dim]`` and the
  whole q-head group rides one grid step (grouped K/V never repeat in HBM);
- optional dequant-on-read for int8 / packed-int4 KV pages with per
  (page, kv_head) float32 scales — the serving analog of the weight-only
  decode configs (KV streams at 1/2 or 1/4 the bytes).

Conventions shared with the other kernels here: interpret mode off-TPU so
the parity suite runs on CPU, a per-kernel ``PADDLE_TPU_DISABLE_PALLAS``
opt-out ("paged_attention"), and a pure-JAX reference
(:func:`paged_attention_reference`) that doubles as the fallback and the
test oracle.  Decode-only: one query token per slot, no backward pass
(serving never differentiates through the KV cache).

Speculative decoding (docs/speculative.md) adds a RAGGED MULTI-TOKEN variant,
:func:`paged_attention_verify`: each slot carries ``q_lens[b] <= qmax`` query
tokens (the pending token plus up to K drafted tokens) at consecutive
positions, all verified in ONE kernel launch.  The grid and page walk are
identical to the decode kernel — the q-head group simply widens to
``qmax * rep`` rows (row ``t*rep + g`` is query token t, grouped head g) and
the causal mask becomes per-row: row t sees ``seq_lens[b] - (q_lens[b]-1-t)``
KV positions, so drafted token t attends everything up to and including
itself but not the later drafts.  ``q_lens`` rides in as a third
scalar-prefetch operand; rows past a slot's live queries are fully masked
(their output is garbage the engine never reads).  The decode kernel is left
byte-for-byte untouched — spec-off serving must compile the exact same
program as before this feature existed.

Chunked prefill (docs/chunked_prefill.md) adds the RAGGED CHUNKED-PREFILL
member, :func:`paged_attention_prefill`: each slot carries a
``q_lens[b] <= T`` row slice of its prompt at consecutive positions — a
prefill chunk streaming into already-written pages, or a single pending
decode token (``q_lens == 1``) riding the same launch, which is what lets
the serving engine run ONE mixed prefill/decode step per iteration instead
of stalling decode behind a whole-prompt prefill.  The mask law is the
verify kernel's (verify is the T = K+1 special case): row t of slot b sits
at absolute position ``seq_lens[b] - q_lens[b] + t`` and sees
``seq_lens[b] - (q_lens[b]-1-t)`` KV positions — the already-written prefix
plus the chunk's own tokens up to and including itself (the causal in-chunk
mask), never the later rows.  Unlike verify it also carries the decode
kernel's dequant-on-read for int8 / packed-int4 KV pages (a KV-quantized
pool must be prefillable through the same kernel family that decodes it).
Its work follows what a lane carries (docs/chunked_prefill.md "What the
kernel skips"): a grid step holds one page with as many of its KV heads as
VMEM holds — grid ``(slots, kv_heads / heads_per_step, logical_pages)`` —
and multiplies only the aligned sub-tiles of the lane's rows that
``q_lens[b]`` says are live, at the pages those rows can see; a lane with
``q_lens[b] == 0`` works no page and leaves zeros.
:func:`prefill_census` counts the same from the host's numbers.
Separate KERNEL/FALLBACK counters; decode and verify stay byte-untouched.

Tensor-parallel serving (docs/tp_serving.md) needs NO kernel variant: the
engine shards the KV pools along kv_heads and calls the kernel family
inside a shard_map region with tp-local head counts — the grid's kv_heads
dim simply shrinks, the block-table page walk (pages address the UNSHARDED
num_blocks axis) and the per-(slot, head) online softmax are untouched, and
``kernel_supported`` evaluates on the local counts (head_dim and the GQA
ratio are tp-invariant, so support never changes with the degree).  All
three kernel bodies are byte-identical to the single-chip engine's.

Long-context flash-decode (docs/paged_attention.md "Split-K flash-decode")
adds a SPLIT-K member, :func:`_flash_decode_kernel`: the decode grid grows a
page-shard axis — ``(slots, kv_heads, shards, pages_per_shard)`` — so a
32k-context slot's page walk is processed by S independent shards instead of
one serial chain (the load-balancing core of the Ragged Paged Attention
paper, PAPERS.md).  Each shard keeps its own partial online-softmax
accumulator ``(m, l, acc)`` over its page range and emits it raw; a small
XLA combine pass (:func:`_flash_combine`, an exact log-sum-exp merge) folds
the S partials into the same softmax the sequential walk computes.  Shard
count is chosen per-launch from the table width — the MAX live page count a
slot can reach (:func:`flash_decode_shards`) — and the dispatch in
:func:`paged_attention_decode` prefers split-K whenever it is enabled and
S > 1, keeping BOTH the sequential kernel and the gather reference as
oracles.  Opt-out: ``PADDLE_TPU_DISABLE_PALLAS=flash_decode`` restores the
sequential kernel byte-for-byte (``paged_attention`` still opts the whole
family out to the gather path).

Decode megastep stage 1 (docs/paged_attention.md "Fused decode step") is
:func:`_fused_decode_kernel`: RoPE application, the KV-page append and the
paged attention of ONE decode token fused into a single Pallas launch per
layer (the MPK paper's case against per-op dispatch, PAPERS.md).  Its grid
takes a page with all its KV heads a step, as the prefill kernel's does,
and its page axis ends at the launch's longest lane, an extent read from
``seq_lens`` at run time.  The kernel takes PRE-rope q/k, rotates them
in-kernel against per-slot cos/sin rows, inserts the roped k (and raw v)
into the slot's write page in-register, so attention sees the appended
token without a separate scatter, and commits the updated page through an
ALIASED pool output whose index map targets exactly the write page (one
page write per (slot, head block), the same bytes the scatter wrote).
Lanes that
must not write (inactive, or past max_seq) direct their page flush at a
dedicated SPILL page the caller appends to the pool — Pallas output index
maps cannot drop, so the drop semantics of ``.at[].set(mode='drop')``
materialize as one trash-can page the allocator never hands out.  fp pools
only (the serving engine's KV pools are bf16/f32 — kv_quant stays an
op-level feature of the unfused kernels).  Opt-out:
``PADDLE_TPU_DISABLE_PALLAS=fused_decode_step`` (the engine then rebuilds
the unfused rope + scatter + attention decode path byte-identically,
spill page gone).

Decode megastep stage 2 (docs/paged_attention.md "Megastep stage 2") adds
two members:

- :func:`_fused_mlp_kernel` / :func:`fused_layer_mlp` — the post-attention
  half of a decoder layer (residual add, post RMSNorm, SwiGLU MLP) in ONE
  Pallas launch: the MLP weights stream HBM→VMEM per grid step as
  column/row blocks of the ffn dim (``fused_mlp_block_cols``), which the
  Pallas pipeline double-buffers, while the [B, h] activations and the
  f32 accumulator stay resident in VMEM.  With it, a decode layer is two
  launches — the fused attention step and this one — separated only by
  the TP psum boundaries (models/llama.decoder_layer_tail is the seam).
  Opt-out ``PADDLE_TPU_DISABLE_PALLAS=fused_layer_mlp`` restores the
  stage-1 per-layer program (rms_norm launch + XLA MLP) byte-identically.
- :func:`_fused_quant_decode_kernel` / :func:`fused_quant_decode_step` —
  the fused decode step for int8/packed-int4 KV pools: the append that
  used to force quantized serving onto the scatter path (a new row dirties
  the page's scale) runs IN-KERNEL — the write page is dequantized with
  its old scale, the roped row inserted, the per-page scale recomputed
  (absmax/bound, the same ``_quant_encode_page`` the XLA scatter arm
  uses), and the requantized page plus its new scale committed through
  the existing aliased-output mechanism (pool AND scale outputs aliased).
  Attention at the write step reads the requantized bytes — exactly what
  the scatter arm's dequant-on-read would see — so the fused program is
  token-identical to the kill-switched one.  Opt-out:
  ``PADDLE_TPU_DISABLE_PALLAS=fused_quant_append`` (quantized pools then
  take the requant-scatter path; ``fused_decode_step`` disables both
  fused decode members).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode, kernel_disabled
# the tile-body idiom of the flash kernels (PR 29): products in the operands'
# own dtype, row statistics kept 128 lanes wide
from .flash_attention import _LANES, _dot, _lanes

_VMEM = pltpu.VMEM

NEG_INF = -1e30

# trace-time counters, same contract as flash_attention.py (bench detail +
# the "did not fall back" assertions in tests)
KERNEL_CALLS = 0
FALLBACK_CALLS = 0
# the ragged multi-token verify variant keeps its own pair so a spec-decode
# test can assert its path without the single-token decode calls aliasing it
VERIFY_KERNEL_CALLS = 0
VERIFY_FALLBACK_CALLS = 0
# ditto the ragged chunked-prefill variant (the mixed prefill/decode step)
PREFILL_KERNEL_CALLS = 0
PREFILL_FALLBACK_CALLS = 0
# split-K flash-decode (docs/paged_attention.md): FLASH counts launches that
# took the page-sharded grid, LAST_FLASH_SHARDS records the shard count the
# most recent flash trace chose (bench rung detail: flash_combine_shards)
FLASH_KERNEL_CALLS = 0
LAST_FLASH_SHARDS = 0
# fused rope+append+attention decode step (decode megastep stage 1)
FUSED_KERNEL_CALLS = 0
FUSED_FALLBACK_CALLS = 0
# fused post-attention layer half: residual + RMSNorm + SwiGLU MLP in one
# launch (decode megastep stage 2)
MLP_KERNEL_CALLS = 0
MLP_FALLBACK_CALLS = 0
# fused decode step with IN-KERNEL requantized KV append (int8/int4 pools;
# stage 2's quantized-serving member); the fallback is the requant-scatter
# composition (quant_append_decode)
QUANT_APPEND_KERNEL_CALLS = 0
QUANT_APPEND_FALLBACK_CALLS = 0


def reset_kernel_counters() -> None:
    """Zero every module-level kernel/fallback counter.  The counters are
    trace-time telemetry that persists across engine constructions (they
    live on the module, not the engine), so per-rung bench detail and
    "did not fall back" test assertions must reset them at setup or prior
    rungs/tests contaminate the delta."""
    global KERNEL_CALLS, FALLBACK_CALLS, VERIFY_KERNEL_CALLS, \
        VERIFY_FALLBACK_CALLS, PREFILL_KERNEL_CALLS, PREFILL_FALLBACK_CALLS, \
        FLASH_KERNEL_CALLS, LAST_FLASH_SHARDS, FUSED_KERNEL_CALLS, \
        FUSED_FALLBACK_CALLS, MLP_KERNEL_CALLS, MLP_FALLBACK_CALLS, \
        QUANT_APPEND_KERNEL_CALLS, QUANT_APPEND_FALLBACK_CALLS
    KERNEL_CALLS = FALLBACK_CALLS = 0
    VERIFY_KERNEL_CALLS = VERIFY_FALLBACK_CALLS = 0
    PREFILL_KERNEL_CALLS = PREFILL_FALLBACK_CALLS = 0
    FLASH_KERNEL_CALLS = LAST_FLASH_SHARDS = 0
    FUSED_KERNEL_CALLS = FUSED_FALLBACK_CALLS = 0
    MLP_KERNEL_CALLS = MLP_FALLBACK_CALLS = 0
    QUANT_APPEND_KERNEL_CALLS = QUANT_APPEND_FALLBACK_CALLS = 0

# MXU/VPU rows: the q-head group is padded up to this many rows so the
# logits tile and the scratch accumulators keep a full sublane
_MIN_GROUP_ROWS = 8

_QUANT_BOUND = {"int8": 127.0, "int4": 7.0}


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def kernel_shape_problem(num_heads: int, num_kv_heads: int, head_dim: int,
                         block_size: int) -> str | None:
    """Why the paged kernel family cannot take these shapes (None: it can).
    The serving engine names this when it has to take the gather reference
    on a TPU."""
    if head_dim % 8:
        return f"head_dim={head_dim} is not a multiple of 8"
    if block_size % 8:
        return f"block_size={block_size} is not a multiple of 8"
    if num_heads % num_kv_heads:
        return (f"num_heads={num_heads} is not a multiple of "
                f"num_kv_heads={num_kv_heads}")
    return None


def kernel_supported(num_heads: int, num_kv_heads: int, head_dim: int,
                     block_size: int) -> bool:
    """Trace-time dispatch predicate: shapes the kernel handles AND the
    operational opt-out.  The single home of the decision — callers (the
    CB engine, the op layer) consult this once at trace time, so a hung
    Mosaic compile can be routed around via
    ``PADDLE_TPU_DISABLE_PALLAS=paged_attention`` without a redeploy."""
    return (kernel_shape_problem(num_heads, num_kv_heads, head_dim,
                                 block_size) is None
            and not kernel_disabled("paged_attention"))


# ---------------------------------------------------------------------------
# quantized-KV storage helpers
# ---------------------------------------------------------------------------

def quantize_kv_cache(cache, mode: str):
    """Quantize a [num_blocks, nkv, bs, hd] KV cache for dequant-on-read.

    Per-(page, kv_head) symmetric absmax scales (a page is the write/evict
    granularity, so its scale never needs rescaling mid-decode).  Returns
    ``(q, scale[num_blocks, nkv] f32)`` with q int8 for mode='int8', or —
    for 'int4' — adjacent head-dim pairs packed two-nibbles-per-byte into an
    int8 ``[num_blocks, nkv, bs, hd // 2]`` buffer (element 2i in the low
    nibble, 2i+1 in the high nibble; see ``_unpack_int4``)."""
    bound = _QUANT_BOUND[mode]
    x = cache.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=(2, 3))                 # [blocks, nkv]
    scale = absmax / bound
    q = jnp.round(x / jnp.maximum(scale, 1e-10)[:, :, None, None])
    q = jnp.clip(q, -bound, bound).astype(jnp.int8)
    if mode == "int8":
        return q, scale.astype(jnp.float32)
    lo = q[..., 0::2].astype(jnp.int32)
    hi = q[..., 1::2].astype(jnp.int32)
    packed = ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.int8)
    return packed, scale.astype(jnp.float32)


def _unpack_int4(packed_i32):
    """[..., hd//2] int32 nibble pairs -> [..., hd] f32 in [-7, 7].
    Arithmetic shifts sign-extend each nibble."""
    lo = (packed_i32 << 28) >> 28
    hi = (packed_i32 << 24) >> 28
    both = jnp.stack([lo, hi], axis=-1)                       # [..., hd//2, 2]
    return both.reshape(*packed_i32.shape[:-1],
                        packed_i32.shape[-1] * 2).astype(jnp.float32)


def _dequant_page(raw, scale, kv_quant):
    """One KV page tile -> f32 [bs, hd] (dequantized when kv_quant set)."""
    if kv_quant == "int8":
        return raw.astype(jnp.float32) * scale
    if kv_quant == "int4":
        return _unpack_int4(raw.astype(jnp.int32)) * scale
    return raw.astype(jnp.float32)


def dequantize_kv_cache(q, scale, mode: str, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv_cache` (reference path / tests)."""
    if mode == "int4":
        x = _unpack_int4(q.astype(jnp.int32))
    else:
        x = q.astype(jnp.float32)
    return (x * scale[:, :, None, None]).astype(dtype)


def _quant_encode_page(x, kv_quant: str):
    """f32 page content ``[..., bs, hd]`` -> (codes ``[..., bs, hd_store]``
    int8, scale ``[...]`` f32): the per-page symmetric-absmax quantization
    of :func:`quantize_kv_cache`, factored so the requantized-append
    family has exactly ONE encode implementation — the XLA scatter arm
    (:func:`quant_append_decode` / :func:`quant_append_rows`) and the
    fused kernel's in-register requantize both call it, which is what
    makes the two arms byte-identical by construction rather than by
    tolerance."""
    codes, scale = _quant_encode_page_tile(x, kv_quant)
    return codes, scale[..., 0, 0]


def _quant_encode_page_tile(x, kv_quant: str):
    """:func:`_quant_encode_page` with the scale left as a ``[..., 1, 1]``
    tile — the form a kernel body can keep in vector registers and
    broadcast back over the page."""
    bound = _QUANT_BOUND[kv_quant]
    absmax = jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True)
    scale = (absmax / bound).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / jnp.maximum(scale, 1e-10)), -bound, bound)
    if kv_quant == "int8":
        return q.astype(jnp.int8), scale
    # pack adjacent head-dim pairs two-nibbles-per-byte (element 2i low,
    # 2i+1 high — quantize_kv_cache's layout, inverted by _unpack_int4).
    # Mosaic lowers neither a lane-strided slice nor the [.., hd/2, 2]
    # reshape, so the even/odd lanes are compacted by a dot with a 0/1
    # selection matrix — exact at any matmul precision (codes are integers
    # in [-7, 7]) and the same expression in the XLA scatter arm
    hd = q.shape[-1]
    src = jax.lax.broadcasted_iota(jnp.int32, (hd, hd // 2), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (hd, hd // 2), 1)
    lo, hi = (jnp.dot(q, (src == 2 * dst + odd).astype(jnp.float32),
                      preferred_element_type=jnp.float32).astype(jnp.int32)
              for odd in (0, 1))
    packed = (lo & 0xF) | ((hi & 0xF) << 4)
    return packed.astype(jnp.int8), scale


def _dequant_page_content(codes, scale, kv_quant: str):
    """Inverse of :func:`_quant_encode_page` on page content: codes
    ``[..., bs, hd_store]`` + scale ``[...]`` -> f32 ``[..., bs, hd]``."""
    if kv_quant == "int4":
        x = _unpack_int4(codes.astype(jnp.int32))
    else:
        x = codes.astype(jnp.float32)
    return x * scale[..., None, None]


def quant_append_decode(qpool, scale, rows, blk, off, writeable,
                        kv_quant: str):
    """Requantized single-row KV append into an int8/packed-int4 pool —
    the XLA composition (gather page → dequantize with the old scale →
    insert the row → recompute the per-page scale → requantize → scatter
    page + scale back).  THE semantic the fused quant kernel reproduces
    in-register, and the engine's kill-switched decode arm: its scatter
    pair is exactly what ``fused_quant_append`` eliminates.

    qpool: [nbp, nkv, bs, hd_store]; scale: [nbp, nkv] f32; rows:
    [b, nkv, hd] (the roped k row or raw v row, any fp dtype); blk [b]
    physical write page; off [b] row offset; writeable [b] — 0 drops the
    append (page and scale untouched).  Returns (qpool, scale)."""
    nbp = qpool.shape[0]
    bs = qpool.shape[2]
    safe = jnp.clip(blk, 0, nbp - 1)
    page = jnp.take(qpool, safe, axis=0)              # [b, nkv, bs, hd_st]
    sc = jnp.take(scale, safe, axis=0)                # [b, nkv]
    deq = _dequant_page_content(page, sc, kv_quant)   # [b, nkv, bs, hd] f32
    ins = (jax.lax.broadcasted_iota(jnp.int32, deq.shape, 2)
           == off[:, None, None, None])
    new = jnp.where(ins, rows.astype(jnp.float32)[:, :, None, :], deq)
    codes, nsc = _quant_encode_page(new, kv_quant)
    drop = jnp.where(writeable.astype(bool), blk, nbp)    # oob -> drop
    return (qpool.at[drop].set(codes, mode="drop"),
            scale.at[drop].set(nsc, mode="drop"))


def quant_append_rows(qpool, scale, rows, table, row_pos, valid,
                      kv_quant: str):
    """Requantized MULTI-row KV append (one write event: a prefill bucket,
    a chunked-prefill/mixed chunk, or a verify draft window) into an
    int8/packed-int4 pool.  A slot's live rows are CONSECUTIVE positions
    (every caller writes a cursor window), so the event touches at most
    ``(T-1)//bs + 2`` logical pages; only that window of each slot's
    table row is gathered and dequantized (the window width is static —
    one trace family, and a verify/chunk event stays O(event) instead of
    O(max_seq)), the event's rows inserted at their absolute positions,
    the per-page scales recomputed, and ONLY the dirty pages (pages that
    received at least one row) are scattered back — clean pages, in
    particular shared prefix-cache pages, keep their exact bytes.
    Allocator invariant (distinct slots own disjoint writable pages;
    dirty pages are always private) guarantees scatter disjointness.

    qpool: [nbp, nkv, bs, hd_store]; scale: [nbp, nkv] f32;
    rows: [B, T, nkv, hd]; table: [B, max_blocks] physical page ids;
    row_pos: [B, T] absolute position of each row; valid: [B, T] — rows
    with 0 are dropped.  Returns (qpool, scale)."""
    nbp = qpool.shape[0]
    bs = qpool.shape[2]
    B, maxblk = table.shape
    T = rows.shape[1]
    nwin = min(maxblk, (T - 1) // bs + 2)
    safe_pos = jnp.where(valid, row_pos, 0)
    lblk = safe_pos // bs                       # [B, T] logical page
    loff = safe_pos % bs
    # window start = the slot's first live logical page (0 if none live)
    lmin = jnp.min(jnp.where(valid, lblk, maxblk), axis=1)
    p0 = jnp.where(lmin == maxblk, 0, lmin)     # [B]
    lane = jnp.arange(B)[:, None]
    win = jnp.clip(p0[:, None] + jnp.arange(nwin), 0, maxblk - 1)
    wtab = table[lane, win]                     # [B, nwin] physical ids
    safe_tab = jnp.clip(wtab, 0, nbp - 1)
    pages = jnp.take(qpool, safe_tab, axis=0)   # [B, nw, nkv, bs, hd_st]
    sc = jnp.take(scale, safe_tab, axis=0)      # [B, nw, nkv]
    deq = _dequant_page_content(pages, sc, kv_quant)  # [B,nw,nkv,bs,hd] f32
    wblk_d = jnp.where(valid, lblk - p0[:, None], nwin)  # invalid rows drop
    deq = deq.at[lane, wblk_d, :, loff].set(
        rows.astype(jnp.float32), mode="drop")
    codes, nsc = _quant_encode_page(deq, kv_quant)
    # dirty = window slots that received >= 1 live row this event (a live
    # row's wblk is always < nwin by the consecutive-positions contract,
    # so the clip above can only alias CLEAN slots, which drop here)
    dirty = (wblk_d[:, :, None]
             == jnp.arange(nwin)[None, None, :]).any(axis=1)  # [B, nw]
    phys_d = jnp.where(dirty, wtab, nbp)        # clean/sentinel -> drop
    flat = lambda a: a.reshape((B * nwin,) + a.shape[2:])
    return (qpool.at[flat(phys_d)].set(flat(codes), mode="drop"),
            scale.at[flat(phys_d)].set(flat(nsc), mode="drop"))


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                  scale, bs, kv_quant):
    """Grid: (slots, kv_heads, logical_pages); pages innermost (sequential).

    Scalar-prefetch refs: tables [b, max_blocks], lens [b].  One grid step
    attends the slot's whole q-head group over one physical KV page."""
    if kv_quant:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]

    # dead pages (the ragged tail): DMA already elided by the index map
    # (same physical block as the previous step), compute skipped here
    @pl.when(j * bs < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                   # [group, hd]
        k = _dequant_page(k_ref[0, 0],
                          _head_scale(ks_ref, h) if kv_quant else None,
                          kv_quant)                           # [bs, hd]
        v = _dequant_page(v_ref[0, 0],
                          _head_scale(vs_ref, h) if kv_quant else None,
                          kv_quant)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [group, bs]
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[:]                                     # [group, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # exp that is exactly 0 for masked entries even when the running max
        # is itself NEG_INF (avoids exp(-inf + inf) = 1)
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
        alpha = jnp.where(m_prev > 0.5 * NEG_INF,
                          jnp.exp(m_prev - m_new), 0.0)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _resolve_page(b, j, tables_ref, lens_ref, bs: int, num_blocks: int):
    """Grid position + prefetched (tables, lens) -> physical page.  Pages
    past the live count repeat the LAST live page, so the pipeline sees
    identical consecutive indices and elides the copy — that is where the
    ragged HBM saving comes from.  Single home of the remap so the KV and
    scale fetches can never diverge.  Every index is clamped — the table
    column against the table's own width (the split-K walk's j = s*P + p
    can exceed max_blocks when S*P rounds up, and a huge/negative
    ``lens`` must not widen the walk), the fetched page id against the
    pool — so NO runtime table content can take the map out of bounds:
    the contract ``analysis/kernel_contracts.py`` verifies under
    adversarial prefetch valuations (docs/analysis.md §"Kernel
    contracts")."""
    n_live = jnp.maximum((lens_ref[b] + bs - 1) // bs, 1)
    j_eff = jnp.clip(jnp.minimum(j, n_live - 1), 0,
                     tables_ref.shape[1] - 1)
    return jnp.clip(tables_ref[b, j_eff], 0, num_blocks - 1)


def _page_index_map(bs: int, num_blocks: int):
    def idx(b, h, j, tables_ref, lens_ref):
        return (_resolve_page(b, j, tables_ref, lens_ref, bs, num_blocks),
                h, 0, 0)

    return idx


def _scale_operand(scale):
    """Per-(page, head) scales ``[nb, nkv]`` as the kernels take them:
    ``[nb, 1, nkv]`` f32.  A one-scale block ``(1, 1)`` of the 2-d array
    is refused by the TPU tiling (a block's last two dims must be (8, 128)
    multiples or the array's own); a page's whole head row ``(1, 1, nkv)``
    of this view is legal, and the view is the same bytes in HBM."""
    return scale.astype(jnp.float32)[:, None, :]


def _scale_spec(nkv: int, page_index_map):
    """BlockSpec for a :func:`_scale_operand`: the head row of the page the
    payload's own index map resolves — codes and scale cannot diverge."""
    return pl.BlockSpec((1, 1, nkv),
                        lambda *a: (page_index_map(*a)[0], 0, 0))


def _head_lane(row_shape, h):
    return jax.lax.broadcasted_iota(jnp.int32, row_shape, 1) == h


def _head_scale(sc_ref, h):
    """Head ``h``'s scale out of a ``(1, 1, nkv)`` scale block, as a
    ``[1, 1]`` tile that broadcasts over the page."""
    row = sc_ref[0]                                       # [1, nkv]
    return jnp.sum(jnp.where(_head_lane(row.shape, h), row, 0.0), axis=-1,
                   keepdims=True)


def _paged_attention_kernel_call(q, key_cache, value_cache, block_tables,
                                 seq_lens, scale, kv_quant, k_scale, v_scale):
    """q: [b, nkv, group, hd] (group already padded to sublane rows);
    caches: [num_blocks, nkv, bs, hd_store].  Returns [b, nkv, group, hd]."""
    b, nkv, group, hd = q.shape
    num_blocks, _, bs, _ = key_cache.shape
    max_blocks = block_tables.shape[1]

    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs,
                               kv_quant=kv_quant)
    kv_spec = pl.BlockSpec((1, 1, bs, key_cache.shape[-1]),
                           _page_index_map(bs, num_blocks))
    in_specs = [
        pl.BlockSpec((1, 1, group, hd), lambda b, h, j, t, l: (b, h, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    args = [q, key_cache, value_cache]
    if kv_quant:
        sc_spec = _scale_spec(nkv, _page_index_map(bs, num_blocks))
        in_specs += [sc_spec, sc_spec]
        args += [_scale_operand(k_scale), _scale_operand(v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nkv, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda b, h, j, t, l: (b, h, 0, 0)),
        scratch_shapes=[
            _VMEM((group, 1), jnp.float32),
            _VMEM((group, 1), jnp.float32),
            _VMEM((group, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, group, hd), q.dtype),
        name="paged_decode_attn",
        interpret=interpret_mode(),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), *args)


# ---------------------------------------------------------------------------
# split-K flash-decode (page-sharded grid + log-sum-exp combine)
# ---------------------------------------------------------------------------

#: auto shard sizing: one shard per this many table pages, capped — a
#: 512-page (32k-context @ bs=64) table gets 8 shards of 64 pages, a tiny
#: 8-page test table gets 2; tables under 2*_FLASH_PAGES_PER_SHARD stay on
#: the sequential kernel (S == 1 has nothing to parallelize)
_FLASH_PAGES_PER_SHARD = 4
_FLASH_MAX_SHARDS = 8


def flash_decode_shards(max_blocks: int, num_shards: int | None = None) -> int:
    """Shard count for a split-K decode launch.  ``max_blocks`` (the block
    table's width) is the MAX live page count any slot can reach — the only
    static bound available at trace time, and the per-launch knob the ISSUE
    names: a long-context engine (wide table) fans out, a short one stays
    sequential.  ``num_shards`` overrides (tests force shard-count > live
    pages); always clamped to [1, max_blocks]."""
    if num_shards is None:
        num_shards = min(_FLASH_MAX_SHARDS,
                         max_blocks // _FLASH_PAGES_PER_SHARD)
    return max(1, min(int(num_shards), max_blocks))


def _online_softmax_update(q, k, v, j, bs, length, m_scr, l_scr, acc_scr,
                           scale):
    """One page's update of the split-K online-softmax state: score dot,
    column mask against ``length``, max/rescale recurrence into the
    (m, l, acc) scratch.  The ONE copy shared by the split-K flash kernel
    and the fused decode kernel, so a masking or rescaling fix can never
    make the two diverge (the byte-pinned sequential/verify/prefill
    kernels keep their own frozen copies by design).  ``q``/``k``/``v``
    are f32 tiles ([rows, hd] / [bs, hd])."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [rows, bs]
    cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < length, s, NEG_INF)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
    alpha = jnp.where(m_prev > 0.5 * NEG_INF, jnp.exp(m_prev - m_new), 0.0)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:] = m_new


def _flash_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                  scale, bs, kv_quant, pages_per_shard):
    """Grid: (slots, kv_heads, shards, pages_per_shard) — the decode
    kernel's page walk with a page-shard axis: shard s owns logical pages
    [s*P, (s+1)*P) and runs the SAME online-softmax recurrence over them,
    but instead of finalizing it emits its raw partial ``(m, l, acc)`` —
    the combine pass (:func:`_flash_combine`) merges the S partials
    exactly.  Shards wholly past a slot's live pages skip compute (their
    DMA re-fetches the last live page, which Mosaic elides) and emit the
    empty accumulator (m = NEG_INF, l = 0)."""
    if kv_quant:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    s_id = pl.program_id(2)
    p = pl.program_id(3)
    j = s_id * pages_per_shard + p                        # logical page

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]

    @pl.when(j * bs < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # [group, hd]
        k = _dequant_page(k_ref[0, 0],
                          _head_scale(ks_ref, h) if kv_quant else None,
                          kv_quant)                       # [bs, hd]
        v = _dequant_page(v_ref[0, 0],
                          _head_scale(vs_ref, h) if kv_quant else None,
                          kv_quant)
        _online_softmax_update(q, k, v, j, bs, length, m_scr, l_scr,
                               acc_scr, scale)

    @pl.when(p == pages_per_shard - 1)
    def _emit_partial():
        m_ref[0, 0, 0] = m_scr[:]
        l_ref[0, 0, 0] = l_scr[:]
        acc_ref[0, 0, 0] = acc_scr[:]


def _flash_page_index_map(bs: int, num_blocks: int, pages_per_shard: int):
    # the sequential kernel's physical-page resolution over the GLOBAL
    # logical page index j = s*P + p; shards past the live range remap to
    # the last live page (copy elided) exactly like the sequential tail
    def idx(b, h, s, p, tables_ref, lens_ref):
        j = s * pages_per_shard + p
        return (_resolve_page(b, j, tables_ref, lens_ref, bs, num_blocks),
                h, 0, 0)

    return idx


def _flash_combine(m, l, acc):
    """Log-sum-exp merge of per-shard partial accumulators — the "small
    combine pass".  m/l: [b, nkv, S, group, 1] f32, acc: [b, nkv, S, group,
    hd] f32.  Mathematically exact: each shard's softmax contribution is
    rescaled to the global max before the weighted sum, so the result
    equals the sequential walk's softmax (same f32 numerics floor).  All
    shards empty (seq_len == 0 slot) -> l_tot == 0 -> zeros, matching the
    sequential kernel's empty-accumulator finalize."""
    m_max = jnp.max(m, axis=2, keepdims=True)             # [b, nkv, 1, g, 1]
    w = jnp.where(m > 0.5 * NEG_INF, jnp.exp(m - m_max), 0.0)
    l_tot = jnp.sum(w * l, axis=2)                        # [b, nkv, g, 1]
    acc_tot = jnp.sum(w * acc, axis=2)                    # [b, nkv, g, hd]
    l_safe = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return acc_tot / l_safe


def _flash_decode_kernel_call(q, key_cache, value_cache, block_tables,
                              seq_lens, scale, kv_quant, k_scale, v_scale,
                              num_shards):
    """Split-K launch: q [b, nkv, group, hd] (group padded to sublane rows);
    caches [num_blocks, nkv, bs, hd_store].  Returns [b, nkv, group, hd]
    (partials merged by :func:`_flash_combine`)."""
    b, nkv, group, hd = q.shape
    num_blocks, _, bs, _ = key_cache.shape
    max_blocks = block_tables.shape[1]
    S = num_shards
    P = -(-max_blocks // S)                               # pages per shard

    kernel = functools.partial(_flash_kernel, scale=scale, bs=bs,
                               kv_quant=kv_quant, pages_per_shard=P)
    kv_spec = pl.BlockSpec((1, 1, bs, key_cache.shape[-1]),
                           _flash_page_index_map(bs, num_blocks, P))
    in_specs = [
        pl.BlockSpec((1, 1, group, hd),
                     lambda b, h, s, p, t, l: (b, h, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    args = [q, key_cache, value_cache]
    if kv_quant:
        sc_spec = _scale_spec(nkv, _flash_page_index_map(bs, num_blocks, P))
        in_specs += [sc_spec, sc_spec]
        args += [_scale_operand(k_scale), _scale_operand(v_scale)]

    part_spec = pl.BlockSpec((1, 1, 1, group, 1),
                             lambda b, h, s, p, t, l: (b, h, s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nkv, S, P),
        in_specs=in_specs,
        out_specs=[
            part_spec,
            part_spec,
            pl.BlockSpec((1, 1, 1, group, hd),
                         lambda b, h, s, p, t, l: (b, h, s, 0, 0)),
        ],
        scratch_shapes=[
            _VMEM((group, 1), jnp.float32),
            _VMEM((group, 1), jnp.float32),
            _VMEM((group, hd), jnp.float32),
        ],
    )
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, nkv, S, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nkv, S, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, nkv, S, group, hd), jnp.float32),
        ],
        name="paged_flash_decode_attn",
        interpret=interpret_mode(),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), *args)
    return _flash_combine(m, l, acc).astype(q.dtype)


# ---------------------------------------------------------------------------
# pure-JAX reference (fallback + test oracle)
# ---------------------------------------------------------------------------

def paged_attention_reference(q, key_cache, value_cache, block_tables,
                              seq_lens, scale=None, kv_quant=None,
                              k_scale=None, v_scale=None):
    """The gather oracle: read every slot's KV out to max_blocks * bs and
    mask the ragged tail (exactly today's serving fallback, GQA- and
    quant-aware).  O(max_seq) HBM per slot — what the kernel avoids.

    q: [b, nh, hd]; caches: [num_blocks, nkv, bs, hd] (or quantized
    storage); block_tables: [b, max_blocks]; seq_lens: [b].
    Returns [b, nh, hd]; slots with seq_len == 0 return zeros (matching the
    kernel's empty accumulator) instead of softmax-of-garbage."""
    num_blocks, nkv, bs, hd_store = key_cache.shape
    hd = hd_store * 2 if kv_quant == "int4" else hd_store
    b, nh, _ = q.shape
    rep = nh // nkv
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    safe = jnp.clip(block_tables, 0, num_blocks - 1)
    # gather the live pages FIRST, dequantize only the gathered slice —
    # dequantizing the whole pool would transiently materialize every page
    # at full precision (num_blocks >> b * max_blocks), defeating the
    # quantized cache's footprint on exactly the robustness path
    k_seq = jnp.take(key_cache, safe, axis=0)  # [b, maxblk, nkv, bs, hd_st]
    v_seq = jnp.take(value_cache, safe, axis=0)
    if kv_quant:
        ks = jnp.take(k_scale, safe, axis=0)[..., None, None]  # [b,mb,nkv,1,1]
        vs = jnp.take(v_scale, safe, axis=0)[..., None, None]
        if kv_quant == "int4":
            k_seq = _unpack_int4(k_seq.astype(jnp.int32)) * ks
            v_seq = _unpack_int4(v_seq.astype(jnp.int32)) * vs
        else:
            k_seq = k_seq.astype(jnp.float32) * ks
            v_seq = v_seq.astype(jnp.float32) * vs
    k_seq = k_seq.transpose(0, 2, 1, 3, 4).reshape(b, nkv, S, hd)
    v_seq = v_seq.transpose(0, 2, 1, 3, 4).reshape(b, nkv, S, hd)

    qg = q.reshape(b, nkv, rep, hd)
    logits = jnp.einsum("bngd,bnsd->bngs", qg.astype(jnp.float32),
                        k_seq.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, None, None, :] < seq_lens[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(seq_lens[:, None, None, None] > 0, p, 0.0)
    out = jnp.einsum("bngs,bnsd->bngd", p, v_seq.astype(jnp.float32))
    return out.reshape(b, nh, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _dispatch(q, key_cache, value_cache, block_tables, seq_lens, k_scale,
              v_scale, scale, kv_quant, num_shards=None):
    """Forward dispatch: split-K flash-decode when enabled and the shard
    heuristic fans out, the sequential Pallas kernel otherwise, gather
    oracle off-TPU-shapes (and the trace-time path counters)."""
    global KERNEL_CALLS, FALLBACK_CALLS, FLASH_KERNEL_CALLS, \
        LAST_FLASH_SHARDS
    b, nh, hd = q.shape
    num_blocks, nkv, bs, _ = key_cache.shape
    if not kernel_supported(nh, nkv, hd, bs):
        FALLBACK_CALLS += 1
        return paged_attention_reference(
            q, key_cache, value_cache, block_tables, seq_lens, scale=scale,
            kv_quant=kv_quant, k_scale=k_scale, v_scale=v_scale)

    rep = nh // nkv
    group = _round_up(rep, _MIN_GROUP_ROWS)
    qg = q.reshape(b, nkv, rep, hd)
    if group != rep:
        # pad the q-head group to a full sublane; padded rows attend over
        # the same pages (finite logits) and are sliced off below
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, group - rep), (0, 0)))

    # split-K dispatch: the kill switch wins over an explicit num_shards
    # (the operator's escape hatch must always restore the sequential walk)
    S = 1
    if not kernel_disabled("flash_decode"):
        S = flash_decode_shards(block_tables.shape[1], num_shards)
    if S > 1:
        FLASH_KERNEL_CALLS += 1
        LAST_FLASH_SHARDS = S
        out = _flash_decode_kernel_call(
            qg, key_cache, value_cache, block_tables, seq_lens, scale,
            kv_quant, k_scale, v_scale, S)
    else:
        KERNEL_CALLS += 1
        out = _paged_attention_kernel_call(
            qg, key_cache, value_cache, block_tables, seq_lens, scale,
            kv_quant, k_scale, v_scale)
    return out[:, :, :rep].reshape(b, nh, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _paged_core(q, key_cache, value_cache, block_tables, seq_lens, k_scale,
                v_scale, scale, kv_quant, num_shards):
    # custom_vjp so the eager tape / jit-grad compose (the repo's kernel
    # contract, ops/pallas/__init__.py): pallas_call has no AD rule, so the
    # backward recomputes through the pure-JAX reference instead
    return _dispatch(q, key_cache, value_cache, block_tables, seq_lens,
                     k_scale, v_scale, scale, kv_quant, num_shards)


def _paged_core_fwd(q, key_cache, value_cache, block_tables, seq_lens,
                    k_scale, v_scale, scale, kv_quant, num_shards):
    out = _dispatch(q, key_cache, value_cache, block_tables, seq_lens,
                    k_scale, v_scale, scale, kv_quant, num_shards)
    return out, (q, key_cache, value_cache, block_tables, seq_lens,
                 k_scale, v_scale)


def _paged_core_bwd(scale, kv_quant, num_shards, res, g):
    q, key_cache, value_cache, block_tables, seq_lens, k_scale, v_scale = res
    zero = lambda x: None if x is None else jnp.zeros_like(x)
    if kv_quant is None:
        _, vjp = jax.vjp(
            lambda q_, kc_, vc_: paged_attention_reference(
                q_, kc_, vc_, block_tables, seq_lens, scale=scale),
            q, key_cache, value_cache)
        dq, dkc, dvc = vjp(g)
    else:
        # quantized caches are not differentiable storage: grads flow to q
        _, vjp = jax.vjp(
            lambda q_: paged_attention_reference(
                q_, key_cache, value_cache, block_tables, seq_lens,
                scale=scale, kv_quant=kv_quant, k_scale=k_scale,
                v_scale=v_scale),
            q)
        (dq,) = vjp(g)
        dkc, dvc = zero(key_cache), zero(value_cache)
    return (dq, dkc, dvc, zero(block_tables), zero(seq_lens),
            zero(k_scale), zero(v_scale))


_paged_core.defvjp(_paged_core_fwd, _paged_core_bwd)


def paged_attention_decode(q, key_cache, value_cache, block_tables, seq_lens,
                           scale=None, kv_quant=None, k_scale=None,
                           v_scale=None, num_shards=None):
    """Ragged paged-attention decode over a block-table KV cache.

    Args:
      q: [b, num_heads, head_dim] — one query token per slot (GQA/MQA: any
        num_heads divisible by the caches' kv heads).
      key_cache/value_cache: [num_blocks, num_kv_heads, block_size, head_dim]
        pages (bf16/f32), or quantized storage per ``kv_quant``:
        'int8' → int8 same shape, 'int4' → int8 [..., head_dim // 2] with
        two nibbles per byte (:func:`quantize_kv_cache`).
      block_tables: [b, max_blocks] int32 physical page ids; entries past a
        slot's live pages may be arbitrary/sentinel (they are never read).
      seq_lens: [b] int32 valid KV length per slot (0 → output zeros).
      k_scale/v_scale: [num_blocks, num_kv_heads] f32 (quantized caches).
      num_shards: split-K override — None picks
        :func:`flash_decode_shards`' per-launch count from the table width
        (the max live page count); an explicit value forces that many page
        shards (clamped to [1, max_blocks]; 1 = the sequential walk).

    Returns [b, num_heads, head_dim] in q's dtype.  Dispatches to the
    split-K flash-decode kernel when the shard heuristic fans out (opt-out
    ``PADDLE_TPU_DISABLE_PALLAS=flash_decode`` restores the sequential
    kernel), the sequential Pallas kernel otherwise when
    :func:`kernel_supported`, and (or under
    ``PADDLE_TPU_DISABLE_PALLAS=paged_attention``) the gather reference.
    """
    assert kv_quant in (None, "int8", "int4"), kv_quant
    b, nh, hd = q.shape
    num_blocks, nkv, bs, hd_store = key_cache.shape
    if kv_quant == "int4":
        assert hd_store * 2 == hd, (hd_store, hd)
    else:
        assert hd_store == hd, (hd_store, hd)
    if kv_quant:
        assert k_scale is not None and v_scale is not None, (
            "quantized KV caches need k_scale/v_scale")
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    return _paged_core(q, key_cache, value_cache, block_tables, seq_lens,
                       k_scale, v_scale, scale, kv_quant,
                       None if num_shards is None else int(num_shards))


# ---------------------------------------------------------------------------
# ragged multi-token verification (speculative decoding)
# ---------------------------------------------------------------------------

def _verify_kernel(tables_ref, lens_ref, qlens_ref, q_ref, k_ref, v_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, scale, bs, rep):
    """Grid: (slots, kv_heads, logical_pages) — identical page walk to
    :func:`_paged_kernel`; the q tile widens to ``R = pad(qmax * rep)`` rows
    (row ``t*rep + g`` = query token t, grouped head g) and the causal mask
    becomes per-row.  Scalar-prefetch refs: tables [b, max_blocks], lens [b]
    (TOTAL written length incl. every drafted token), qlens [b] (live query
    tokens, 1..qmax)."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    qlen = qlens_ref[b]

    @pl.when(j * bs < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                   # [R, hd]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bs, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [R, bs]
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        t = rows // rep                                       # query token idx
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # query token t sits at absolute position length - qlen + t and sees
        # everything up to and including itself: length - (qlen - 1 - t)
        # columns.  Rows past the slot's live queries (incl. sublane padding)
        # see nothing — their l stays 0 and _finalize emits zeros.
        row_len = jnp.where(t < qlen, length - (qlen - 1 - t), 0)
        s = jnp.where(cols < row_len, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
        alpha = jnp.where(m_prev > 0.5 * NEG_INF,
                          jnp.exp(m_prev - m_new), 0.0)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _verify_page_index_map(bs: int, num_blocks: int):
    # same physical-page resolution as the decode kernel, arity-adjusted for
    # the third (qlens) scalar-prefetch operand
    def idx(b, h, j, tables_ref, lens_ref, qlens_ref):
        return (_resolve_page(b, j, tables_ref, lens_ref, bs, num_blocks),
                h, 0, 0)

    return idx


def _verify_kernel_call(q, key_cache, value_cache, block_tables, seq_lens,
                        q_lens, scale, rep):
    """q: [b, nkv, R, hd] (R = qmax*rep padded to sublane rows, t-major).
    Returns [b, nkv, R, hd]."""
    b, nkv, R, hd = q.shape
    num_blocks, _, bs, _ = key_cache.shape
    max_blocks = block_tables.shape[1]

    kernel = functools.partial(_verify_kernel, scale=scale, bs=bs, rep=rep)
    kv_spec = pl.BlockSpec((1, 1, bs, hd),
                           _verify_page_index_map(bs, num_blocks))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nkv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, R, hd),
                         lambda b, h, j, t, l, ql: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, R, hd),
                               lambda b, h, j, t, l, ql: (b, h, 0, 0)),
        scratch_shapes=[
            _VMEM((R, 1), jnp.float32),
            _VMEM((R, 1), jnp.float32),
            _VMEM((R, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, R, hd), q.dtype),
        name="paged_verify_attn",
        interpret=interpret_mode(),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_lens.astype(jnp.int32), q, key_cache, value_cache)


def paged_verify_reference(q, key_cache, value_cache, block_tables, seq_lens,
                           q_lens, scale=None):
    """Gather oracle for ragged multi-token verification (fallback + test
    oracle, mirroring :func:`paged_attention_reference`).

    q: [b, qmax, nh, hd]; caches [num_blocks, nkv, bs, hd];
    block_tables [b, max_blocks]; seq_lens [b] TOTAL written length (incl.
    every drafted token); q_lens [b] live query tokens per slot (<= qmax).
    Returns [b, qmax, nh, hd]; rows past q_lens (and slots with an empty
    window) return zeros."""
    num_blocks, nkv, bs, hd = key_cache.shape
    b, qmax, nh, _ = q.shape
    rep = nh // nkv
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    safe = jnp.clip(block_tables, 0, num_blocks - 1)
    k_seq = jnp.take(key_cache, safe, axis=0)   # [b, maxblk, nkv, bs, hd]
    v_seq = jnp.take(value_cache, safe, axis=0)
    k_seq = k_seq.transpose(0, 2, 1, 3, 4).reshape(b, nkv, S, hd)
    v_seq = v_seq.transpose(0, 2, 1, 3, 4).reshape(b, nkv, S, hd)

    qg = q.reshape(b, qmax, nkv, rep, hd)
    logits = jnp.einsum("btngd,bnsd->btngs", qg.astype(jnp.float32),
                        k_seq.astype(jnp.float32)) * scale
    t = jnp.arange(qmax)[None, :, None, None, None]
    ql = q_lens[:, None, None, None, None]
    row_len = jnp.where(t < ql,
                        seq_lens[:, None, None, None, None] - (ql - 1 - t), 0)
    mask = jnp.arange(S)[None, None, None, None, :] < row_len
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(row_len > 0, p, 0.0)
    out = jnp.einsum("btngs,bnsd->btngd", p, v_seq.astype(jnp.float32))
    return out.reshape(b, qmax, nh, hd).astype(q.dtype)


def paged_attention_verify(q, key_cache, value_cache, block_tables, seq_lens,
                           q_lens, scale=None):
    """Ragged multi-token verification over a block-table KV cache (the
    speculative-decoding target-model step; docs/speculative.md).

    Args:
      q: [b, qmax, num_heads, head_dim] — per slot, up to ``qmax`` query
        tokens at CONSECUTIVE positions (token t at position
        ``seq_lens[b] - q_lens[b] + t``); rows at or past ``q_lens[b]`` are
        padding whose output is unspecified.
      key_cache/value_cache: [num_blocks, num_kv_heads, block_size, head_dim]
        pages with every query token's K/V already written (incl. drafts).
      block_tables: [b, max_blocks] int32 physical page ids.
      seq_lens: [b] int32 TOTAL valid KV length per slot (incl. drafts).
      q_lens: [b] int32 live query tokens per slot (1..qmax).

    Returns [b, qmax, num_heads, head_dim] in q's dtype: row t is attention
    for query token t under the per-row causal mask (t sees everything up to
    and including its own position, never the later drafts).  Dispatches to
    the Pallas verify kernel when :func:`kernel_supported` (same predicate
    and ``PADDLE_TPU_DISABLE_PALLAS=paged_attention`` opt-out as decode —
    one launch-or-gather decision for the whole paged family); no kv_quant
    variant (the serving engine's KV pools are bf16/f32; weight-only quant
    does not touch them).  Forward-only like the decode entry — serving
    never differentiates through the KV cache, and the analysis target
    traces forward."""
    global VERIFY_KERNEL_CALLS, VERIFY_FALLBACK_CALLS
    b, qmax, nh, hd = q.shape
    num_blocks, nkv, bs, hd_store = key_cache.shape
    assert hd_store == hd, (hd_store, hd)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if not kernel_supported(nh, nkv, hd, bs):
        VERIFY_FALLBACK_CALLS += 1
        return paged_verify_reference(q, key_cache, value_cache,
                                      block_tables, seq_lens, q_lens,
                                      scale=scale)
    VERIFY_KERNEL_CALLS += 1

    rep = nh // nkv
    R = _round_up(qmax * rep, _MIN_GROUP_ROWS)
    # [b, qmax, nkv, rep, hd] -> [b, nkv, qmax*rep, hd], row = t*rep + g
    qg = q.reshape(b, qmax, nkv, rep, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, nkv, qmax * rep, hd)
    if R != qmax * rep:
        # padded rows index query token t >= qmax >= qlen: fully masked in
        # the kernel (zero output), sliced off below
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - qmax * rep), (0, 0)))
    out = _verify_kernel_call(qg, key_cache, value_cache, block_tables,
                              seq_lens, q_lens, scale, rep)
    out = out[:, :, :qmax * rep].reshape(b, nkv, qmax, rep, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, qmax, nh, hd)


# ---------------------------------------------------------------------------
# ragged chunked prefill (stall-free continuous batching)
# ---------------------------------------------------------------------------

#: what the chip's compiler lets one kernel hold in VMEM (v5e: 16 MiB
#: scoped) and the part of it the prefill kernel's blocks and scratch may
#: take — the rest is the tile bodies' own intermediates (s, p, selects)
_VMEM_LIMIT = 16 * 1024 * 1024
_PREFILL_VMEM_BUDGET = 12 * 1024 * 1024
#: rows of a chunk lane's sub-tile: one MXU pass of q rows against a page
_PREFILL_SUB_ROWS = 128


def _prefill_tiles(T: int, rep: int, dtype) -> tuple[int, int, int]:
    """(R, head_rows, sub_rows) of a launch whose lanes carry up to ``T``
    rows of ``rep`` grouped heads in ``dtype``: the q tile's padded rows,
    the FIRST sub-tile a lane of few rows works alone (``rep`` rows in
    whole sublanes of the dtype — a decode lane's one token), and the
    aligned sub-tiles a longer lane is worked in.  ``R`` is a whole number
    of either."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    head_rows = _round_up(rep, sublanes)
    R = _round_up(T * rep, head_rows)
    if R <= _PREFILL_SUB_ROWS:
        return R, head_rows, R
    sub_rows = _round_up(_PREFILL_SUB_ROWS, head_rows)
    return _round_up(R, sub_rows), head_rows, sub_rows


def _prefill_vmem_bytes(heads: int, R: int, hd: int, bs: int, hd_store: int,
                        q_dtype, kv_dtype) -> int:
    """What ``heads`` KV heads a grid step hold in VMEM: q and the output
    block double-buffered, the float32 accumulator, m and l at 128 lanes,
    and the K and the V page double-buffered."""
    qb = jnp.dtype(q_dtype).itemsize
    per_head = (R * hd * (2 * qb + 2 * qb + 4) + 2 * R * _LANES * 4
                + 2 * 2 * bs * hd_store * jnp.dtype(kv_dtype).itemsize)
    return heads * per_head


def _heads_that_fit(nkv: int, per_head: int) -> int:
    """KV heads of a page one grid step of a paged kernel takes: the
    largest divisor of ``nkv`` whose blocks and scratch, ``per_head`` bytes
    a head, fit the budget (a page with all its heads is one contiguous
    block of the pool).  The prefill and the fused decode kernel both."""
    for heads in range(nkv, 1, -1):
        if nkv % heads == 0 and heads * per_head <= _PREFILL_VMEM_BUDGET:
            return heads
    return 1


def _prefill_heads_per_step(nkv: int, R: int, hd: int, bs: int,
                            hd_store: int, q_dtype, kv_dtype) -> int:
    """KV heads of a page one prefill grid step takes."""
    return _heads_that_fit(nkv, _prefill_vmem_bytes(
        1, R, hd, bs, hd_store, q_dtype, kv_dtype))


def _sub_tile_sees(i, sub, qlen, length, rep, maximum):
    """KV positions the LAST live row of sub-tile ``i`` (rows
    ``[i*sub, (i+1)*sub)`` of a lane's tile) sees: row ``t`` sees
    ``length - (qlen-1-t)``, so a page ``j`` with ``j*bs`` at or past this
    holds nothing any row of the sub-tile may read.  One expression for
    the kernel (traced scalars, ``jnp.maximum``) and for
    :func:`prefill_census` (numpy)."""
    return length - maximum(qlen - 1 - ((i + 1) * sub - 1) // rep, 0)


def prefill_census(q_lens, seq_lens, T: int, rep: int, bs: int, *,
                   max_blocks: int, nkv: int, hd: int, dtype,
                   kv_quant: str | None = None, live=None) -> dict:
    """What one launch of the prefill kernel works, from the host's
    numbers (the like of ``flash_attention.tile_census``): counted by the
    predicates the kernel branches on, so here and not at run time.

    ``q_lens`` / ``seq_lens`` are the kernel's own operands (numpy, [b]);
    ``live`` [b] bool marks the lanes whose rows carry a token (an
    inactive lane of the mixed step is given ``q_lens == 1`` over one
    stale position: the kernel works it, nothing reads it).  Returns
    ``row_pages_live`` — sum over the live lanes' rows of the pages a row
    sees (row t: ``cdiv(seq_lens - (q_lens-1-t), bs)``), the least any
    kernel of this layout works; ``row_pages_computed`` — sum over lanes
    of the rows of the sub-tiles worked, in token rows, x the pages each
    is worked at; and ``grid_steps``."""
    q_lens = np.asarray(q_lens, np.int64)
    seq_lens = np.asarray(seq_lens, np.int64)
    live = (np.ones(q_lens.shape, bool) if live is None
            else np.asarray(live, bool))
    R, head_rows, sub_rows = _prefill_tiles(T, rep, dtype)
    heads = _prefill_heads_per_step(
        nkv, R, hd, bs, hd // 2 if kv_quant == "int4" else hd, dtype,
        jnp.int8 if kv_quant else dtype)

    def pages(visible):
        return np.clip(-(-visible // bs), 0, max_blocks)

    runs = q_lens > 0
    short = runs & (q_lens * rep <= head_rows)
    computed = np.where(short, -(-head_rows // rep) * pages(seq_lens), 0)
    for i in range(R // sub_rows):
        worked = runs & ~short & (i * sub_rows < q_lens * rep)
        computed += np.where(
            worked, -(-sub_rows // rep) * pages(_sub_tile_sees(
                i, sub_rows, q_lens, seq_lens, rep, np.maximum)), 0)

    def pages_upto(n):
        # sum of cdiv(v, bs) over v = 1..n
        k, r = np.divmod(np.maximum(n, 0), bs)
        return bs * k * (k + 1) // 2 + r * (k + 1)

    # a live row sees the pages up to its own position: rows' visibilities
    # are the consecutive integers seq_lens - q_lens + 1 .. seq_lens
    seen = pages_upto(seq_lens) - pages_upto(seq_lens - q_lens)
    return {
        "row_pages_live": int(np.where(live & runs, seen, 0).sum()),
        "row_pages_computed": int(computed.sum()),
        "grid_steps": int(q_lens.size * (nkv // heads) * max_blocks),
    }


def _prefill_kernel(tables_ref, lens_ref, qlens_ref, q_ref, k_ref, v_ref,
                    *rest, scale, bs, rep, kv_quant, head_rows, sub_rows):
    """Grid: (slots, kv_heads / heads_per_step, logical_pages), pages
    innermost (sequential).  One grid step holds one physical page with
    ``heads_per_step`` of its KV heads (a contiguous block of the pool)
    and the lane's q tile for those heads, ``R = pad(T * rep)`` rows (row
    ``t*rep + g`` = chunk row t, grouped head g), under the verify
    kernel's per-row causal law — row t sees ``lens[b] - (qlens[b]-1-t)``
    KV positions — with the decode kernel's dequant-on-read.

    What a step WORKS follows what the lane carries, read from the
    prefetched scalars.  A lane's live rows are a prefix of its tile, so:
    a lane of at most ``head_rows`` rows (a decode lane's one token: its
    ``rep`` grouped heads in whole sublanes) works the tile's first
    ``head_rows`` rows and nothing else; a longer lane is worked in
    aligned sub-tiles of ``sub_rows`` rows, ``cdiv(qlens[b]*rep,
    sub_rows)`` of them, and a sub-tile skips a page that lies wholly
    past its last row's visibility (a chunk is causal by sub-tile, not
    only by mask); a lane with ``qlens[b] <= 0`` works no page whatever
    ``lens[b]`` says.  Rows nothing worked — past ``q_lens`` — leave as
    zeros, masked rows of a worked sub-tile too.  Scalar-prefetch refs:
    tables [b, max_blocks], lens [b] (TOTAL written length incl. this
    chunk), qlens [b] (live chunk rows, 0..T)."""
    if kv_quant:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, m_scr, l_scr, acc_scr = rest
    heads, R = q_ref.shape[1], q_ref.shape[2]
    b = pl.program_id(0)
    hb = pl.program_id(1)
    j = pl.program_id(2)
    first, last = j == 0, j == pl.num_programs(2) - 1
    length = lens_ref[b]
    qlen = qlens_ref[b]
    rows_live = qlen * rep

    def sub_tile(r0, rows, visible, unroll):
        """Rows ``[r0, r0 + rows)`` of every head at this page: the
        online-softmax recurrence over the rows' slices of the scratch.
        ``visible`` is what the sub-tile's last live row sees; every
        branch is the lane's, none a head's."""
        at = pl.ds(r0, rows)

        @pl.when(first)
        def _init():
            def init(h):
                m_scr[h, at] = jnp.full((rows, _LANES), NEG_INF, jnp.float32)
                l_scr[h, at] = jnp.zeros((rows, _LANES), jnp.float32)
                acc_scr[h, at] = jnp.zeros((rows, acc_scr.shape[-1]),
                                           jnp.float32)

            _each_head(init, heads, unroll)

        @pl.when(j * bs < visible)
        def _compute():
            # chunk row t sits at absolute position length - qlen + t and
            # sees everything up to and including itself; rows past the
            # lane's live chunk (incl. sublane padding) see nothing — their
            # l stays 0 and the finalize emits zeros
            iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                                     (rows, bs))
            t = (r0 + iota(0)) // rep
            cols = j * bs + iota(1)
            seen = cols < jnp.where(t < qlen, length - (qlen - 1 - t), 0)

            def compute(h):
                q = q_ref[0, h, at]                           # [rows, hd]
                k, v = k_ref[0, h], v_ref[0, h]               # [bs, hd_store]
                if kv_quant:
                    k = _dequant_page(
                        k, _head_scale(ks_ref, hb * heads + h), kv_quant)
                    v = _dequant_page(
                        v, _head_scale(vs_ref, hb * heads + h), kv_quant)
                s = jnp.where(seen, _dot(q, k, ((1,), (1,))) * scale,
                              NEG_INF)                        # [rows, bs]
                # m and l are kept [rows, 128] with all lanes alike, as
                # they come off the lane reductions: nothing is spread
                # back over the lanes to meet s or the accumulator
                m_prev = m_scr[h, at]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                # exactly 0 for masked entries even when the running max
                # is itself NEG_INF (avoids exp(-inf + inf) = 1)
                p = jnp.where(seen, jnp.exp(s - _lanes(m_new, bs)), 0.0)
                alpha = jnp.where(m_prev > 0.5 * NEG_INF,
                                  jnp.exp(m_prev - m_new), 0.0)
                l_scr[h, at] = alpha * l_scr[h, at] + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_scr[h, at] = (
                    acc_scr[h, at] * _lanes(alpha, acc_scr.shape[-1])
                    + _dot(p.astype(v.dtype), v, ((1,), (0,))))
                m_scr[h, at] = m_new

            _each_head(compute, heads, unroll)

        @pl.when(last)
        def _finalize():
            def finalize(h):
                l = l_scr[h, at]
                l_safe = jnp.where(l == 0.0, 1.0, l)
                o_ref[0, h, at] = (acc_scr[h, at] / _lanes(
                    l_safe, acc_scr.shape[-1])).astype(o_ref.dtype)

            _each_head(finalize, heads, unroll)

    @pl.when(last)
    def _zeros():
        o_ref[:] = jnp.zeros_like(o_ref)

    # a step with nothing to do — a dead page of the ragged tail (its DMA
    # already elided by the index map), every page of an empty lane —
    # costs its branch and nothing else
    works = (qlen > 0) & ((j * bs < length) | first | last)
    short = rows_live <= head_rows
    # heads unrolled side by side in a short tile and in a sub-tile; the
    # int4 unpack (an interleave of lanes) costs Mosaic seconds of compile
    # a copy, more than in proportion (8 and 2 copies: 50 s; 2 and 1: 5 s)
    few, many = (2, 1) if kv_quant == "int4" else (8, 2)

    @pl.when(works & short)
    def _few_rows():
        sub_tile(0, head_rows, length, _unroll(heads, few))

    @pl.when(works & ~short)
    def _sub_tiles():
        def one(i, carry):
            pl.when(i * sub_rows < rows_live)(lambda: sub_tile(
                pl.multiple_of(i * sub_rows, sub_rows), sub_rows,
                _sub_tile_sees(i, sub_rows, qlen, length, rep, jnp.maximum),
                _unroll(heads, many)))
            return carry

        jax.lax.fori_loop(0, R // sub_rows, one, 0)


def _each_head(body, heads: int, unroll: int):
    """``body(h)`` for the ``heads`` KV heads of a grid step, ``unroll`` of
    them side by side: heads are independent chains of two products and
    two lane reductions each, so the scheduler overlaps an unrolled group,
    and a ``fori_loop`` over the groups keeps the body's size that of one
    group whatever the head count."""
    def group(g, carry):
        for k in range(unroll):
            body(g * unroll + k)
        return carry

    if unroll == heads:
        group(0, 0)
    else:
        jax.lax.fori_loop(0, heads // unroll, group, 0)


def _unroll(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def _prefill_page_index_map(bs: int, num_blocks: int):
    # the decode kernel's physical-page resolution over a block of heads
    def idx(b, hb, j, tables_ref, lens_ref, qlens_ref):
        return (_resolve_page(b, j, tables_ref, lens_ref, bs, num_blocks),
                hb, 0, 0)

    return idx


def _prefill_kernel_call(q, key_cache, value_cache, block_tables, seq_lens,
                         q_lens, scale, rep, kv_quant, k_scale, v_scale,
                         head_rows, sub_rows):
    """q: [b, nkv, R, hd] (R = T*rep padded to whole sub-tiles, t-major).
    Returns [b, nkv, R, hd]."""
    b, nkv, R, hd = q.shape
    num_blocks, _, bs, hd_store = key_cache.shape
    max_blocks = block_tables.shape[1]
    heads = _prefill_heads_per_step(nkv, R, hd, bs, hd_store, q.dtype,
                                    key_cache.dtype)

    kernel = functools.partial(_prefill_kernel, scale=scale, bs=bs, rep=rep,
                               kv_quant=kv_quant, head_rows=head_rows,
                               sub_rows=sub_rows)
    page_map = _prefill_page_index_map(bs, num_blocks)
    kv_spec = pl.BlockSpec((1, heads, bs, hd_store), page_map)
    q_spec = pl.BlockSpec((1, heads, R, hd),
                          lambda b, hb, j, t, l, ql: (b, hb, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, key_cache, value_cache]
    if kv_quant:
        sc_spec = _scale_spec(nkv, page_map)
        in_specs += [sc_spec, sc_spec]
        args += [_scale_operand(k_scale), _scale_operand(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nkv // heads, max_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            _VMEM((heads, R, _LANES), jnp.float32),
            _VMEM((heads, R, _LANES), jnp.float32),
            _VMEM((heads, R, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, R, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="ragged_prefill_attn",
        interpret=interpret_mode(),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_lens.astype(jnp.int32), *args)


def paged_prefill_reference(q, key_cache, value_cache, block_tables,
                            seq_lens, q_lens, scale=None, kv_quant=None,
                            k_scale=None, v_scale=None):
    """Gather oracle for ragged chunked prefill (fallback + test oracle).

    The verify oracle's per-row causal mask (verify is the T = K+1 special
    case) composed with the decode oracle's dequantize-then-gather quant
    handling.  q: [b, T, nh, hd]; caches [num_blocks, nkv, bs, hd] (or
    quantized storage per ``kv_quant``); block_tables [b, max_blocks];
    seq_lens [b] TOTAL written length incl. this chunk; q_lens [b] live
    chunk rows (<= T).  Returns [b, T, nh, hd]; rows past q_lens (and slots
    with an empty window) return zeros."""
    num_blocks, nkv, bs, hd_store = key_cache.shape
    hd = hd_store * 2 if kv_quant == "int4" else hd_store
    b, qmax, nh, _ = q.shape
    rep = nh // nkv
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    safe = jnp.clip(block_tables, 0, num_blocks - 1)
    k_seq = jnp.take(key_cache, safe, axis=0)   # [b, maxblk, nkv, bs, hd_st]
    v_seq = jnp.take(value_cache, safe, axis=0)
    if kv_quant:
        # dequantize only the gathered slice (matching the decode oracle:
        # the whole pool at full precision would defeat the quantized
        # footprint on exactly the robustness path)
        ks = jnp.take(k_scale, safe, axis=0)[..., None, None]  # [b,mb,nkv,1,1]
        vs = jnp.take(v_scale, safe, axis=0)[..., None, None]
        if kv_quant == "int4":
            k_seq = _unpack_int4(k_seq.astype(jnp.int32)) * ks
            v_seq = _unpack_int4(v_seq.astype(jnp.int32)) * vs
        else:
            k_seq = k_seq.astype(jnp.float32) * ks
            v_seq = v_seq.astype(jnp.float32) * vs
    k_seq = k_seq.transpose(0, 2, 1, 3, 4).reshape(b, nkv, S, hd)
    v_seq = v_seq.transpose(0, 2, 1, 3, 4).reshape(b, nkv, S, hd)

    qg = q.reshape(b, qmax, nkv, rep, hd)
    logits = jnp.einsum("btngd,bnsd->btngs", qg.astype(jnp.float32),
                        k_seq.astype(jnp.float32)) * scale
    t = jnp.arange(qmax)[None, :, None, None, None]
    ql = q_lens[:, None, None, None, None]
    row_len = jnp.where(t < ql,
                        seq_lens[:, None, None, None, None] - (ql - 1 - t), 0)
    mask = jnp.arange(S)[None, None, None, None, :] < row_len
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(row_len > 0, p, 0.0)
    out = jnp.einsum("btngs,bnsd->btngd", p, v_seq.astype(jnp.float32))
    return out.reshape(b, qmax, nh, hd).astype(q.dtype)


def paged_attention_prefill(q, key_cache, value_cache, block_tables,
                            seq_lens, q_lens, scale=None, kv_quant=None,
                            k_scale=None, v_scale=None):
    """Ragged chunked prefill over a block-table KV cache (the serving
    engine's unified mixed prefill/decode step; docs/chunked_prefill.md).

    Args:
      q: [b, T, num_heads, head_dim] — per slot, up to ``T`` query tokens at
        CONSECUTIVE positions (row t at position
        ``seq_lens[b] - q_lens[b] + t``): a prefill chunk of the slot's
        prompt, or a single pending decode token (``q_lens[b] == 1``) riding
        the same launch.  Rows at or past ``q_lens[b]`` are padding: never
        multiplied where a whole sub-tile of them is dead, and zeros out.
      key_cache/value_cache: [num_blocks, num_kv_heads, block_size, head_dim]
        pages with every query row's K/V already written, or quantized
        storage per ``kv_quant`` ('int8' → int8 same shape, 'int4' → int8
        [..., head_dim // 2]; :func:`quantize_kv_cache`).
      block_tables: [b, max_blocks] int32 physical page ids.
      seq_lens: [b] int32 TOTAL valid KV length per slot (incl. the chunk).
      q_lens: [b] int32 live chunk rows per slot (0..T; a lane with 0
        works no page, whatever its ``seq_lens``, and returns zeros).
      k_scale/v_scale: [num_blocks, num_kv_heads] f32 (quantized caches).

    Returns [b, T, num_heads, head_dim] in q's dtype: row t is attention
    for chunk row t under the per-row causal mask (the written prefix plus
    the chunk through itself, never the later rows — the verify kernel's
    law with T free; verify is the T = K+1 special case).  Dispatches to
    the Pallas prefill kernel when :func:`kernel_supported` (same predicate
    and ``PADDLE_TPU_DISABLE_PALLAS=paged_attention`` opt-out as the rest
    of the paged family); forward-only like decode/verify — serving never
    differentiates through the KV cache."""
    global PREFILL_KERNEL_CALLS, PREFILL_FALLBACK_CALLS
    assert kv_quant in (None, "int8", "int4"), kv_quant
    b, qmax, nh, hd_q = q.shape
    num_blocks, nkv, bs, hd_store = key_cache.shape
    if kv_quant == "int4":
        assert hd_store * 2 == hd_q, (hd_store, hd_q)
    else:
        assert hd_store == hd_q, (hd_store, hd_q)
    if kv_quant:
        assert k_scale is not None and v_scale is not None, (
            "quantized KV caches need k_scale/v_scale")
    hd = hd_q
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if not kernel_supported(nh, nkv, hd, bs):
        PREFILL_FALLBACK_CALLS += 1
        return paged_prefill_reference(q, key_cache, value_cache,
                                       block_tables, seq_lens, q_lens,
                                       scale=scale, kv_quant=kv_quant,
                                       k_scale=k_scale, v_scale=v_scale)
    PREFILL_KERNEL_CALLS += 1

    rep = nh // nkv
    R, head_rows, sub_rows = _prefill_tiles(qmax, rep, q.dtype)
    # [b, T, nkv, rep, hd] -> [b, nkv, T*rep, hd], row = t*rep + g
    qg = q.reshape(b, qmax, nkv, rep, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, nkv, qmax * rep, hd)
    if R != qmax * rep:
        # padded rows index chunk row t >= T >= qlen: never worked or fully
        # masked in the kernel (zero output), sliced off below
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - qmax * rep), (0, 0)))
    out = _prefill_kernel_call(qg, key_cache, value_cache, block_tables,
                               seq_lens, q_lens, scale, rep, kv_quant,
                               k_scale, v_scale, head_rows, sub_rows)
    out = out[:, :, :qmax * rep].reshape(b, nkv, qmax, rep, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, qmax, nh, hd)


# ---------------------------------------------------------------------------
# fused rope + KV-append + attention decode step (decode megastep stage 1)
# ---------------------------------------------------------------------------

def _rotate_half_rows(x, half: int):
    """rotate-half along the last (head_dim) axis of a tile of any rank."""
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _decode_walk(seq_lens, bs: int, max_blocks: int, xp):
    """The fused decode walk's two predicates, from its PRE-append lengths:
    each lane's live pages ``cdiv(seq_lens + 1, bs)`` (its write page is
    the last of them), and the walk's bound, the longest of those over the
    launch's lanes, at least 1 and at most the table's width.  One
    expression for the kernel's wrapper (``xp`` = jnp, traced: the bound
    is the grid's extent) and for :func:`decode_census` (numpy)."""
    pages = (seq_lens + bs) // bs
    return pages, xp.clip(xp.max(pages), 1, max_blocks)


def decode_census(seq_lens, bs: int, *, max_blocks: int, live=None) -> dict:
    """What one launch of the fused decode kernel walks, from the host's
    numbers (the like of :func:`prefill_census`), by the predicates the
    kernel's grid and branches read (:func:`_decode_walk`).

    ``seq_lens`` [b] are the kernel's own operand (the PRE-append lengths);
    ``live`` [b] bool marks the lanes whose row carries a token (the engine
    hands every other lane length 0).  Returns ``pages_live`` — sum over
    the live lanes of the pages each attends, the least any kernel of this
    layout walks — and ``pages_walked`` — the (lane, page) steps of the
    grid for one block of KV heads: every lane walks to the launch's
    bound, and a step past the lane's own last page is a branch and no
    copy."""
    seq_lens = np.asarray(seq_lens, np.int64)
    live = (np.ones(seq_lens.shape, bool) if live is None
            else np.asarray(live, bool))
    pages, bound = _decode_walk(seq_lens, bs, max_blocks, np)
    return {"pages_live": int(np.where(live, pages, 0).sum()),
            "pages_walked": int(seq_lens.size * bound)}


#: KV heads a fused decode step's grid step works side by side at most
_DECODE_UNROLL = 8


def _fused_decode_kernel(tables_ref, lens_ref, wblk_ref, wable_ref,
                         q_ref, k_ref, v_ref, cos_ref, sin_ref,
                         kp_ref, vp_ref,
                         o_ref, kp_out_ref, vp_out_ref,
                         m_scr, l_scr, acc_scr, q_scr, kn_scr, vn_scr,
                         *, scale, bs, unroll):
    """Grid: (slots, kv_heads / heads_per_step, pages), pages innermost
    (sequential), the page extent the launch's longest lane
    (:func:`_decode_walk`, run-time data).  One grid step holds one
    physical page with ``heads_per_step`` of its KV heads (a contiguous
    block of the pool) and those heads' padded q groups, with the whole
    decode-token prologue folded in:

    - RoPE: the q groups are rotated ONCE per (slot, head block) at the
      first page, in the INPUT dtype; the new k rows at the write page.
      cos/sin arrive as per-slot rows (a [b, hd] operand, not a launch).
    - append: the write page is the lane's last live page (``lens // bs``);
      there the roped k and raw v rows are inserted into the fetched page
      of every head at once and the page is committed through the ALIASED
      pool output, whose index map pins the slot's write page (``wblk``):
      one page write per (slot, head block), the bytes the XLA scatter
      wrote.  The attention of that step reads the inserted rows, so it
      sees the appended token without a separate scatter.
    - lanes with ``wable == 0`` (inactive / past max_seq) never insert;
      their pool-output flush lands on the caller's SPILL page (``wblk`` =
      spill) and commits ZEROS — the materialized form of ``mode='drop'``,
      kept deterministic so a sentinel-page gather can never read
      uninitialized (possibly NaN) bits off the spill page.
    - attention: the online-softmax recurrence of each head over the
      lane's pages, q and k in their own dtype, the softmax weights in
      float32 against v, ``m`` and ``l`` kept 128 lanes wide in float32
      scratch; the output is written once, at the lane's last page.  A
      step past the lane's last page (a shorter lane under the launch's
      bound) works nothing, and its index maps name the lane's last page
      again, so no copy is issued either.

    Scalar-prefetch refs: tables [b, max_blocks], lens [b] PRE-append
    length (the append position), wblk [b] physical write page (spill when
    dropped), wable [b] 0/1.  Attention masks columns < lens + 1."""
    heads, group = q_ref.shape[1], q_ref.shape[2]
    hd = acc_scr.shape[-1]
    b = pl.program_id(0)
    j = pl.program_id(2)
    lens = lens_ref[b]
    last = lens // bs                       # the write page, the last live
    w_on = wable_ref[b] == 1
    half = hd // 2

    def rope(x):
        # in the INPUT dtype, exactly like the unfused path's
        # apply_rotary_pos_emb (bf16 operands -> bf16 math): the fused
        # program must feed the score dot the same rounded values the
        # kill-switched program reads, or near-tied argmaxes could flip
        cos, sin = cos_ref[0], sin_ref[0]                 # [1, hd]
        return (x * cos + _rotate_half_rows(x, half) * sin).astype(x.dtype)

    @pl.when(j == 0)
    def _init():
        q_scr[:] = rope(q_ref[0]).astype(q_scr.dtype)    # [heads, group, hd]
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j == last)
    def _append():
        # the new k roped in the input dtype and rounded through the POOL
        # dtype: the page (and the score dot) holds exactly the bytes the
        # unfused path's scatter writes, not an unrounded f32 row
        kn = rope(k_ref[0]).astype(kp_ref.dtype)          # [heads, 1, hd]
        vn = v_ref[0].astype(vp_ref.dtype)
        kn_scr[:] = kn
        vn_scr[:] = vn
        at = jax.lax.broadcasted_iota(jnp.int32, (heads, bs, hd), 1)
        ins = at == lens % bs
        # dropped lanes write ZEROS: their flush lands on the spill page,
        # and the output VMEM buffer would otherwise carry uninitialized
        # bits on hardware — a NaN pattern parked on the spill page would
        # poison every later sentinel-page gather through the masked
        # softmax's 0*NaN (the guarantee jnp.take(..., fill_value=0) gave)
        kp_out_ref[0] = jnp.where(w_on, jnp.where(ins, kn, kp_ref[0]),
                                  0).astype(kp_out_ref.dtype)
        vp_out_ref[0] = jnp.where(w_on, jnp.where(ins, vn, vp_ref[0]),
                                  0).astype(vp_out_ref.dtype)

    @pl.when(j <= last)
    def _attend():
        # the appended row, at the write page of a lane that writes
        ins = ((j == last) & w_on
               & (jax.lax.broadcasted_iota(jnp.int32, (bs, hd), 0)
                  == lens % bs))
        seen = (j * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
                < lens + 1)

        def attend(h):
            k = jnp.where(ins, kn_scr[h], kp_ref[0, h])   # [bs, hd]
            v = jnp.where(ins, vn_scr[h], vp_ref[0, h])
            q = q_scr[h].astype(q_ref.dtype)              # [group, hd]
            s = jnp.where(seen, _dot(q, k, ((1,), (1,))) * scale,
                          NEG_INF)                        # [group, bs]
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # exactly 0 for masked entries even when the running max is
            # itself NEG_INF (avoids exp(-inf + inf) = 1)
            p = jnp.where(seen, jnp.exp(s - _lanes(m_new, bs)), 0.0)
            alpha = jnp.where(m_prev > 0.5 * NEG_INF,
                              jnp.exp(m_prev - m_new), 0.0)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            # p stays float32 (v is widened to meet it): the weights are
            # not rounded to the pool's dtype before they sum the values
            acc_scr[h] = acc_scr[h] * _lanes(alpha, hd) + jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

        _each_head(attend, heads, unroll)

    @pl.when(j == last)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / _lanes(l_safe, hd)).astype(o_ref.dtype)


def _decode_vmem_bytes(heads: int, group: int, hd: int, bs: int, q_dtype,
                       kv_dtype) -> int:
    """What ``heads`` KV heads a fused decode grid step hold in VMEM: the
    prefill kernel's account at ``group`` rows (q and output blocks, the
    accumulator, m and l, the K and V page) plus the aliased K and V
    output pages, double-buffered, and the roped q."""
    kvb = jnp.dtype(kv_dtype).itemsize
    return heads * (2 * 2 * bs * hd * kvb + group * hd * 4) + (
        _prefill_vmem_bytes(heads, group, hd, bs, hd, q_dtype, kv_dtype))


def _fused_walk_page(b, j, tables_ref, lens_ref, bs: int, nbp: int):
    """The fused walk's physical-page resolution of logical page ``j`` over
    length + 1 (the walk must include the append page): a page past the
    lane's live count names its last live page again (no copy is issued
    when consecutive steps fetch the same block); sentinel table entries
    clip to nbp - 1 — the caller's SPILL page in fused pools, so an
    unseated lane's reads can never alias a live slot's write page.  The
    table column is clamped to the table width like _resolve_page (the
    kernel-contract bounds rule: lens is runtime data).  ONE
    implementation shared by the fp and quant kernels' payload and scale
    index maps — a page's codes and its scale can never diverge mid-walk
    by construction, not by parallel edits."""
    n_live = jnp.maximum((lens_ref[b] + 1 + bs - 1) // bs, 1)
    j_eff = jnp.clip(jnp.minimum(j, n_live - 1), 0,
                     tables_ref.shape[1] - 1)
    return jnp.clip(tables_ref[b, j_eff], 0, nbp - 1)


def _fused_page_index_map(bs: int, nbp: int, pages_per_shard: int):
    # the quant kernel's split-K walk: logical page j = s*P + p
    def idx(b, h, s, p, tables_ref, lens_ref, wblk_ref, wable_ref):
        return (_fused_walk_page(b, s * pages_per_shard + p, tables_ref,
                                 lens_ref, bs, nbp), h, 0, 0)

    return idx


def _fused_small_in_specs(group: int, hd: int, heads: int = 1):
    """The five small per-slot operands every fused decode launch streams
    whole — q groups, new k/v rows, cos/sin (shapes: see
    :func:`_fused_small_operands`), for ``heads`` KV heads a grid step.
    ONE spec set shared by the fp and quant launch wrappers (like
    ``_fused_walk_page`` for the page maps), whatever their grids' rank
    (the slot and head block lead both): a geometry or clamp fix lands in
    both by construction."""
    row = pl.BlockSpec((1, heads, 1, hd), lambda b, h, *_: (b, h, 0, 0))
    rope = pl.BlockSpec((1, 1, hd), lambda b, *_: (b, 0, 0))
    return [
        pl.BlockSpec((1, heads, group, hd), lambda b, h, *_: (b, h, 0, 0)),
        row, row, rope, rope,
    ]


def _fused_small_operands(qg, k_new, v_new, cos, sin):
    """The operands :func:`_fused_small_in_specs` describes.  A one-row
    block is only legal on the TPU when the block's last two dims equal the
    array's (Mosaic tiles them (8, 128)), so the per-(slot, head) k/v rows
    ride as ``[b, nkv, 1, hd]`` and the per-slot cos/sin rows as
    ``[b, 1, hd]`` — trailing-singleton views of a few KB, the same trick
    flash_attention's segment ids use."""
    return [qg, k_new[:, :, None, :], v_new[:, :, None, :],
            cos[:, None, :], sin[:, None, :]]


def _fused_write_page_map(nbp: int):
    """Index map of the ALIASED pool output, pinned to the slot's write
    page, for either kernel's grid (the prefetch refs end ``wblk``,
    ``wable``).  The page id is runtime data: clamp it to the pool like
    every other data-dependent index — the engine always passes a valid
    page (own page or spill), but the kernel-contract bounds rule
    (analysis/kernel_contracts.py) requires the map itself to be safe for
    ALL prefetch values, not safe-by-caller-convention."""
    def idx(b, h, *rest):
        wblk_ref = rest[-2]
        return (jnp.clip(wblk_ref[b], 0, nbp - 1), h, 0, 0)

    return idx


def _fused_decode_kernel_call(qg, k_new, v_new, cos, sin, key_cache,
                              value_cache, block_tables, seq_lens,
                              write_blk, writeable, scale):
    """qg: [b, nkv, group, hd] PRE-rope (group padded to sublane rows);
    k_new/v_new: [b, nkv, hd]; cos/sin: [b, hd]; pools [nbp, nkv, bs, hd].
    Returns (out [b, nkv, group, hd], new key pool, new value pool)."""
    b, nkv, group, hd = qg.shape
    nbp, _, bs, _ = key_cache.shape
    heads = _heads_that_fit(nkv, _decode_vmem_bytes(
        1, group, hd, bs, qg.dtype, key_cache.dtype))
    _, pages = _decode_walk(seq_lens.astype(jnp.int32), bs,
                            block_tables.shape[1], jnp)

    kernel = functools.partial(_fused_decode_kernel, scale=scale, bs=bs,
                               unroll=_unroll(heads, _DECODE_UNROLL))

    def walk(b, hb, j, tables_ref, lens_ref, wblk_ref, wable_ref):
        return (_fused_walk_page(b, j, tables_ref, lens_ref, bs, nbp), hb,
                0, 0)

    kv_spec = pl.BlockSpec((1, heads, bs, hd), walk)
    pool_out_spec = pl.BlockSpec((1, heads, bs, hd),
                                 _fused_write_page_map(nbp))
    small = _fused_small_in_specs(group, hd, heads)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nkv // heads, pages),
        in_specs=small + [kv_spec, kv_spec],
        out_specs=[small[0], pool_out_spec, pool_out_spec],
        scratch_shapes=[
            _VMEM((heads, group, _LANES), jnp.float32),   # m
            _VMEM((heads, group, _LANES), jnp.float32),   # l
            _VMEM((heads, group, hd), jnp.float32),       # acc
            _VMEM((heads, group, hd), jnp.float32),       # roped q
            _VMEM((heads, 1, hd), key_cache.dtype),       # roped new k
            _VMEM((heads, 1, hd), value_cache.dtype),     # new v
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(qg.shape, qg.dtype),
            jax.ShapeDtypeStruct(key_cache.shape, key_cache.dtype),
            jax.ShapeDtypeStruct(value_cache.shape, value_cache.dtype),
        ],
        # pool inputs (global operand indices 9/10: four scalar-prefetch
        # refs then five small operands precede them) alias the pool
        # outputs — the append is in-place, no pool copy materializes
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="fused_decode_attn",
        interpret=interpret_mode(),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      write_blk.astype(jnp.int32), writeable.astype(jnp.int32),
      *_fused_small_operands(qg, k_new, v_new, cos, sin),
      key_cache, value_cache)


def fused_decode_step_reference(q, k_new, v_new, cos, sin, key_cache,
                                value_cache, block_tables, seq_lens,
                                write_blk, writeable, scale=None):
    """Oracle for the fused decode step: the unfused composition — rope in
    the INPUT dtype (exactly ``apply_rotary_pos_emb``'s math, which the
    kernel mirrors), one-row scatter append, gather-oracle attention over
    ``seq_lens + 1``.  Same signature and return contract as the kernel
    path; lanes with ``writeable == 0`` drop their append (scatter
    mode='drop' via an out-of-range index)."""
    from . import rope as rope_mod

    b, nh, hd = q.shape
    nbp, nkv, bs, _ = key_cache.shape
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    # the ONE rotate-half implementation (ops/pallas/rope.py) — the oracle
    # is the unfused composition by definition, so it must rope through
    # the same function the unfused engine path calls
    q_r, k_r = rope_mod.apply_rotary_pos_emb(
        q[:, None], k_new[:, None], cos[:, None, :], sin[:, None, :])
    q_r, k_r = q_r[:, 0], k_r[:, 0]                       # [b, {nh,nkv}, hd]
    off = seq_lens % bs
    drop = jnp.where(writeable.astype(bool), write_blk, nbp)  # oob -> drop
    kc = key_cache.at[drop, :, off].set(k_r.astype(key_cache.dtype),
                                        mode="drop")
    vc = value_cache.at[drop, :, off].set(
        v_new.astype(value_cache.dtype), mode="drop")
    out = paged_attention_reference(q_r, kc, vc,
                                    block_tables, seq_lens + 1, scale=scale)
    return out, kc, vc


def fused_decode_step(q, k_new, v_new, cos, sin, key_cache, value_cache,
                      block_tables, seq_lens, write_blk, writeable,
                      scale=None):
    """Fused RoPE + KV-page append + paged attention for ONE decode token
    per slot — the serving engine's decode-path megastep stage 1
    (docs/paged_attention.md "Fused decode step").

    Args:
      q: [b, num_heads, head_dim] PRE-rope query (GQA like decode).
      k_new/v_new: [b, num_kv_heads, head_dim] PRE-rope key / value of the
        token being appended.
      cos/sin: [b, head_dim] rope rows at each slot's append position.
      key_cache/value_cache: [nbp, num_kv_heads, block_size, head_dim] fp
        pools.  In the serving engine nbp = num_blocks + 1: the last page
        is the SPILL page dropped writes land on (Pallas output index maps
        cannot drop).  kv_quant pools are not supported here — appending
        would dirty the per-page scale (:func:`fused_quant_decode_step`).
      block_tables: [b, max_blocks] int32 physical page ids.
      seq_lens: [b] int32 PRE-append lengths (the append position), with
        ``seq_lens + 1 <= max_blocks * block_size``; the longest lane bounds
        every lane's walk, so a lane that does not write should be given 0
        (the engine does).
      write_blk: [b] int32 physical append page — the slot's own private
        page for writeable lanes, the spill page otherwise.
      writeable: [b] bool/int32 — 0 drops the append (inactive lane or
        position past max_seq) and masks the insert.

    Returns ``(out [b, num_heads, head_dim], key_cache, value_cache)`` —
    attention over columns < seq_lens + 1 (the appended token included)
    plus the updated pools (aliased: donated callers update in place).
    Dispatches to the fused kernel when :func:`kernel_supported`; the
    ``PADDLE_TPU_DISABLE_PALLAS=fused_decode_step`` opt-out (or an
    unsupported shape) routes to the unfused reference composition.
    Forward-only: serving never differentiates through the KV cache."""
    global FUSED_KERNEL_CALLS, FUSED_FALLBACK_CALLS
    b, nh, hd = q.shape
    nbp, nkv, bs, hd_store = key_cache.shape
    assert hd_store == hd, (hd_store, hd)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if (not kernel_supported(nh, nkv, hd, bs)
            or kernel_disabled("fused_decode_step")):
        FUSED_FALLBACK_CALLS += 1
        return fused_decode_step_reference(
            q, k_new, v_new, cos, sin, key_cache, value_cache, block_tables,
            seq_lens, write_blk, writeable, scale=scale)
    FUSED_KERNEL_CALLS += 1

    rep = nh // nkv
    group = _round_up(rep, _MIN_GROUP_ROWS)
    qg = q.reshape(b, nkv, rep, hd)
    if group != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, group - rep), (0, 0)))
    out, kc, vc = _fused_decode_kernel_call(
        qg, k_new, v_new, cos, sin, key_cache, value_cache, block_tables,
        seq_lens, write_blk, writeable, scale)
    return out[:, :, :rep].reshape(b, nh, hd), kc, vc


# ---------------------------------------------------------------------------
# fused decode step with in-kernel requantized KV append (megastep stage 2:
# int8/packed-int4 pools take the fused path instead of requant scatters)
# ---------------------------------------------------------------------------

def _fused_quant_decode_kernel(tables_ref, lens_ref, wblk_ref, wable_ref,
                               q_ref, k_ref, v_ref, cos_ref, sin_ref,
                               kp_ref, vp_ref, ks_ref, vs_ref,
                               m_ref, l_ref, acc_ref,
                               kp_out_ref, vp_out_ref, ks_out_ref,
                               vs_out_ref,
                               m_scr, l_scr, acc_scr, q_scr,
                               kw_scr, vw_scr,
                               *, scale, bs, pages_per_shard, kv_quant):
    """Grid: (slots, kv_heads, shards, pages_per_shard) — the fused decode
    walk (:func:`_fused_decode_kernel`) over int8/packed-int4 pages:

    - every walked page is dequantized with its per-(page, head) scale
      before the score dot (the decode kernel's dequant-on-read);
    - at the write step the page is dequantized with its OLD scale, the
      roped k row (raw v row) inserted, the page's scale RECOMPUTED and
      the page requantized (:func:`_quant_encode_page` — the same encode
      the XLA scatter arm uses, so the committed bytes are identical),
      then codes AND new scale commit through ALIASED outputs pinned to
      the write page.  A scale output block is the write page's whole head
      row ``(1, 1, nkv)`` (see :func:`_scale_operand`): its index depends
      on the slot only, so it stays resident in VMEM across the slot's
      heads, each head's write step fills its own lane, and the row is
      flushed complete when the walk moves to the next slot;
    - attention at the write step reads the requantize→dequantize round
      trip — exactly the bytes the scatter arm's dequant-on-read would
      see, which is what makes fused vs kill-switched token-identical;
    - dropped lanes (``wable == 0``) commit zero codes and a zero scale to
      the caller's SPILL page/scale entry (deterministic trash can, like
      the fp kernel)."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    s_id = pl.program_id(2)
    p = pl.program_id(3)
    j = s_id * pages_per_shard + p                        # logical page
    length = lens_ref[b] + 1                              # incl. appended tok
    half = q_scr.shape[-1] // 2

    @pl.when((s_id == 0) & (p == 0))
    def _rope_q():
        # rope in the INPUT dtype, exactly like the fp fused kernel (and
        # the unfused arm's apply_rotary_pos_emb)
        q = q_ref[0, 0]                                   # [group, hd]
        cos = cos_ref[0]                                  # [1, hd]
        sin = sin_ref[0]
        q_r = (q * cos + _rotate_half_rows(q, half) * sin).astype(q.dtype)
        q_scr[:] = q_r.astype(jnp.float32)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * bs < length)
    def _compute():
        w_on = wable_ref[b] == 1
        is_wpage = j == lens_ref[b] // bs
        is_wstep = w_on & is_wpage
        k_deq = _dequant_page(kp_ref[0, 0], _head_scale(ks_ref, h),
                              kv_quant)
        v_deq = _dequant_page(vp_ref[0, 0], _head_scale(vs_ref, h),
                              kv_quant)

        @pl.when(is_wpage)
        def _append_commit():
            # rope + insert + requantize ONLY at the write page: the
            # other pages of a long walk (the latency-critical bulk)
            # pay the dequant alone.  The write page is the LAST live
            # page of the length+1 walk, so exactly one compute step
            # per (slot, head) lane lands here.
            # rope the new k in the input dtype (matching the scatter
            # arm's apply_rotary_pos_emb); the f32 cast below mirrors
            # quant_append_decode's rows.astype(f32) insert
            cos = cos_ref[0]                              # [1, hd]
            sin = sin_ref[0]
            k_new = k_ref[0, 0]                           # [1, hd]
            k_roped = (k_new * cos + _rotate_half_rows(k_new, half) * sin
                       ).astype(k_new.dtype)
            rows = jax.lax.broadcasted_iota(jnp.int32, k_deq.shape, 0)
            ins = rows == lens_ref[b] % bs
            k_ins = jnp.where(ins, k_roped.astype(jnp.float32), k_deq)
            v_ins = jnp.where(ins, v_ref[0, 0].astype(jnp.float32), v_deq)
            k_q, k_nsc = _quant_encode_page_tile(k_ins, kv_quant)
            v_q, v_nsc = _quant_encode_page_tile(v_ins, kv_quant)
            # dropped lanes flush zero codes + zero scale at the spill
            # page (deterministic — uninitialized VMEM bits must never
            # park on the spill page, same contract as the fp kernel)
            zq = jnp.zeros_like(k_q)
            kp_out_ref[0, 0] = jnp.where(w_on, k_q, zq)
            vp_out_ref[0, 0] = jnp.where(w_on, v_q, zq)
            mine = _head_lane(ks_out_ref.shape[1:], h)
            ks_out_ref[0] = jnp.where(mine, jnp.where(w_on, k_nsc, 0.0),
                                      ks_out_ref[0])
            vs_out_ref[0] = jnp.where(mine, jnp.where(w_on, v_nsc, 0.0),
                                      vs_out_ref[0])
            # stage the requantize→dequantize round trip for the score
            # dot — exactly the bytes the scatter arm's dequant-on-read
            # would see (fused vs kill-switched token identity)
            kw_scr[:] = _dequant_page(k_q, k_nsc, kv_quant)
            vw_scr[:] = _dequant_page(v_q, v_nsc, kv_quant)

        # non-write steps select the plain dequant; the scratch operand
        # is only ever READ at the write step (where select — garbage in
        # the unselected branch is discarded lane-wise)
        k_eff = jnp.where(is_wstep, kw_scr[:], k_deq)
        v_eff = jnp.where(is_wstep, vw_scr[:], v_deq)
        _online_softmax_update(q_scr[:], k_eff, v_eff, j, bs, length,
                               m_scr, l_scr, acc_scr, scale)

    @pl.when(p == pages_per_shard - 1)
    def _emit_partial():
        m_ref[0, 0, 0] = m_scr[:]
        l_ref[0, 0, 0] = l_scr[:]
        acc_ref[0, 0, 0] = acc_scr[:]


def _fused_quant_decode_kernel_call(qg, k_new, v_new, cos, sin, kq, ksc,
                                    vq, vsc, block_tables, seq_lens,
                                    write_blk, writeable, scale, num_shards,
                                    kv_quant):
    """qg: [b, nkv, group, hd] PRE-rope (group padded to sublane rows);
    kq/vq: [nbp, nkv, bs, hd_store] int8 codes; ksc/vsc: [nbp, nkv] f32.
    Returns (m, l, acc partials, new key codes, new value codes, new key
    scales, new value scales)."""
    b, nkv, group, hd = qg.shape
    nbp, _, bs, hd_store = kq.shape
    max_blocks = block_tables.shape[1]
    S = num_shards
    P = -(-max_blocks // S)                               # pages per shard

    kernel = functools.partial(_fused_quant_decode_kernel, scale=scale,
                               bs=bs, pages_per_shard=P, kv_quant=kv_quant)
    kv_spec = pl.BlockSpec((1, 1, bs, hd_store),
                           _fused_page_index_map(bs, nbp, P))
    sc_spec = _scale_spec(nkv, _fused_page_index_map(bs, nbp, P))
    pool_out_spec = pl.BlockSpec((1, 1, bs, hd_store),
                                 _fused_write_page_map(nbp))
    scale_out_spec = _scale_spec(nkv, _fused_write_page_map(nbp))
    # the split-K partials (m, l, acc) the combine pass merges
    part_spec = pl.BlockSpec((1, 1, 1, group, 1),
                             lambda b, h, s, p, t, l, w, a: (b, h, s, 0, 0))
    acc_spec = pl.BlockSpec((1, 1, 1, group, hd),
                            lambda b, h, s, p, t, l, w, a: (b, h, s, 0, 0))
    part_specs = [part_spec, part_spec, acc_spec]
    part_shapes = [
        jax.ShapeDtypeStruct((b, nkv, S, group, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, nkv, S, group, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, nkv, S, group, hd), jnp.float32),
    ]
    scratch = [
        _VMEM((group, 1), jnp.float32),
        _VMEM((group, 1), jnp.float32),
        _VMEM((group, hd), jnp.float32),
        _VMEM((group, hd), jnp.float32),        # roped q
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nkv, S, P),
        in_specs=_fused_small_in_specs(group, hd) + [
            kv_spec,
            kv_spec,
            sc_spec,
            sc_spec,
        ],
        out_specs=part_specs + [
            pool_out_spec,
            pool_out_spec,
            scale_out_spec,
            scale_out_spec,
        ],
        scratch_shapes=scratch + [
            _VMEM((bs, hd), jnp.float32),       # write-page k round trip
            _VMEM((bs, hd), jnp.float32),       # write-page v round trip
        ],
    )
    ks3, vs3 = _scale_operand(ksc), _scale_operand(vsc)
    *parts, kq2, vq2, ks2, vs2 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=part_shapes + [
            jax.ShapeDtypeStruct(kq.shape, kq.dtype),
            jax.ShapeDtypeStruct(vq.shape, vq.dtype),
            jax.ShapeDtypeStruct(ks3.shape, ks3.dtype),
            jax.ShapeDtypeStruct(vs3.shape, vs3.dtype),
        ],
        # pool codes + scales (global operand indices 9-12: four scalar-
        # prefetch refs then five small operands precede them) alias their
        # outputs — the requantized append is in-place, no pool copy
        input_output_aliases={9: 3, 10: 4, 11: 5, 12: 6},
        name="fused_quant_decode_attn",
        interpret=interpret_mode(),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      write_blk.astype(jnp.int32), writeable.astype(jnp.int32),
      *_fused_small_operands(qg, k_new, v_new, cos, sin), kq, vq, ks3, vs3)
    return (*parts, kq2, vq2, ks2[:, 0, :].astype(ksc.dtype),
            vs2[:, 0, :].astype(vsc.dtype))


def fused_quant_decode_step_reference(q, k_new, v_new, cos, sin, kq, ksc,
                                      vq, vsc, block_tables, seq_lens,
                                      write_blk, writeable, kv_quant,
                                      scale=None):
    """Oracle for the quantized fused decode step: the unfused
    composition — rope in the INPUT dtype (``apply_rotary_pos_emb``), the
    requantized-append scatter pair (:func:`quant_append_decode`: the
    same ``_quant_encode_page`` the kernel calls, so the pool bytes match
    exactly), then dequant-on-read gather attention over
    ``seq_lens + 1``."""
    from . import rope as rope_mod

    b, nh, hd = q.shape
    nbp, nkv, bs, _ = kq.shape
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    q_r, k_r = rope_mod.apply_rotary_pos_emb(
        q[:, None], k_new[:, None], cos[:, None, :], sin[:, None, :])
    q_r, k_r = q_r[:, 0], k_r[:, 0]
    off = seq_lens % bs
    kq2, ks2 = quant_append_decode(kq, ksc, k_r, write_blk, off, writeable,
                                   kv_quant)
    vq2, vs2 = quant_append_decode(vq, vsc, v_new, write_blk, off,
                                   writeable, kv_quant)
    out = paged_attention_reference(q_r, kq2, vq2, block_tables,
                                    seq_lens + 1, scale=scale,
                                    kv_quant=kv_quant, k_scale=ks2,
                                    v_scale=vs2)
    return out, kq2, ks2, vq2, vs2


def fused_quant_decode_step(q, k_new, v_new, cos, sin, kq, ksc, vq, vsc,
                            block_tables, seq_lens, write_blk, writeable,
                            kv_quant, scale=None, num_shards=None):
    """Fused RoPE + requantized KV-page append + split-K dequant-on-read
    attention for ONE decode token per slot over int8/packed-int4 pools —
    the quantized-serving member of decode megastep stage 2
    (docs/paged_attention.md "Megastep stage 2").

    Args mirror :func:`fused_decode_step` with the fp pools replaced by
    quantized storage: ``kq``/``vq`` [nbp, nkv, block_size, hd_store]
    int8 codes (hd_store = head_dim, or head_dim // 2 packed int4),
    ``ksc``/``vsc`` [nbp, nkv] f32 per-(page, head) scales.  In the
    serving engine nbp = num_blocks + 1 (the spill page — dropped lanes
    commit zero codes and a zero scale there).

    Returns ``(out [b, nh, hd], kq, ksc, vq, vsc)`` — attention over
    columns < seq_lens + 1 with the pools and scales updated in place
    (aliased).  Dispatch: the fused quant kernel when
    :func:`kernel_supported`; ``PADDLE_TPU_DISABLE_PALLAS=
    fused_quant_append`` (or ``fused_decode_step``, which kills the whole
    fused decode family, or an unsupported shape) routes to the
    requant-scatter reference composition — byte-identical pool contents
    by construction (shared ``_quant_encode_page``)."""
    global QUANT_APPEND_KERNEL_CALLS, QUANT_APPEND_FALLBACK_CALLS, \
        LAST_FLASH_SHARDS
    assert kv_quant in ("int8", "int4"), kv_quant
    b, nh, hd = q.shape
    nbp, nkv, bs, hd_store = kq.shape
    if kv_quant == "int4":
        assert hd_store * 2 == hd, (hd_store, hd)
    else:
        assert hd_store == hd, (hd_store, hd)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if (not kernel_supported(nh, nkv, hd, bs)
            or kernel_disabled("fused_decode_step")
            or kernel_disabled("fused_quant_append")):
        QUANT_APPEND_FALLBACK_CALLS += 1
        return fused_quant_decode_step_reference(
            q, k_new, v_new, cos, sin, kq, ksc, vq, vsc, block_tables,
            seq_lens, write_blk, writeable, kv_quant, scale=scale)
    QUANT_APPEND_KERNEL_CALLS += 1

    S = 1
    if not kernel_disabled("flash_decode"):
        S = flash_decode_shards(block_tables.shape[1], num_shards)
    if S > 1:
        LAST_FLASH_SHARDS = S
    rep = nh // nkv
    group = _round_up(rep, _MIN_GROUP_ROWS)
    qg = q.reshape(b, nkv, rep, hd)
    if group != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, group - rep), (0, 0)))
    m, l, acc, kq2, vq2, ks2, vs2 = _fused_quant_decode_kernel_call(
        qg, k_new, v_new, cos, sin, kq, ksc, vq, vsc, block_tables,
        seq_lens, write_blk, writeable, scale, S, kv_quant)
    out = _flash_combine(m, l, acc).astype(q.dtype)
    return out[:, :, :rep].reshape(b, nh, hd), kq2, ks2, vq2, vs2


# ---------------------------------------------------------------------------
# fused post-attention layer half: residual + RMSNorm + SwiGLU MLP
# (decode megastep stage 2 — docs/paged_attention.md "Megastep stage 2")
# ---------------------------------------------------------------------------

#: ffn-column block the MLP weights stream in per grid step (HBM→VMEM,
#: double-buffered by the Pallas pipeline); 256 keeps the three weight
#: blocks of a production layer (2·h·F + F·h elements) well under the
#: 16 MiB VMEM floor with headroom for the resident activations
_MLP_BLOCK_COLS = 256


def fused_mlp_block_cols(inter: int) -> int:
    """ffn-dim block width for the fused MLP launch: the largest divisor
    of ``inter`` that is <= :data:`_MLP_BLOCK_COLS` and a sublane multiple
    (so the grid tiles the weights exactly); tiny/odd ffn widths fall back
    to a single whole block."""
    if inter <= _MLP_BLOCK_COLS:
        return inter
    for f in range(_MLP_BLOCK_COLS, 7, -8):
        if inter % f == 0:
            return f
    return inter


def fused_mlp_shape_problem(hidden: int, inter: int) -> str | None:
    """Why :func:`fused_layer_mlp` cannot take these widths (None: it
    can)."""
    if hidden % 8 or inter % 8:
        return (f"hidden={hidden} / ffn={inter} are not both multiples "
                f"of 8")
    return None


def fused_mlp_supported(hidden: int, inter: int) -> bool:
    """Dispatch predicate for :func:`fused_layer_mlp` — sublane-aligned
    dims and the operational opt-out
    (``PADDLE_TPU_DISABLE_PALLAS=fused_layer_mlp``)."""
    return (fused_mlp_shape_problem(hidden, inter) is None
            and not kernel_disabled("fused_layer_mlp"))


def _fused_mlp_kernel(x_ref, ay_ref, w_ref, wg_ref, wu_ref, wd_ref,
                      h1_ref, y_ref, xn_scr, acc_scr, *, eps):
    """Grid: (ffn_blocks,) — the post-attention half of one decoder layer
    for a decode step's [B, h] activations:

    - step 0 computes the residual add ``h1 = x + attn_y`` (input dtype,
      matching the XLA add) and the post RMSNorm in f32 (exactly
      rms_norm's kernel math), parking the rounded ``xn`` in f32 scratch;
    - every step streams one (h, F) block of w_gate/w_up and the matching
      (F, h) block of w_down from HBM (the Pallas pipeline double-buffers
      the fetches), computes the block's swiglu activation in the input
      dtype (silu in f32 — swiglu's exact math) and accumulates the down
      projection in f32 scratch;
    - ``h1`` and the running ``y`` are written every step (consecutive
      revisits of the same output block), so the final flush carries the
      completed layer half."""
    j = pl.program_id(0)
    h1 = x_ref[:] + ay_ref[:]                     # residual add, input dtype

    @pl.when(j == 0)
    def _prologue():
        xf = h1.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + eps)
        xn = (xf * inv * w_ref[:].astype(jnp.float32)).astype(h1.dtype)
        xn_scr[:] = xn.astype(jnp.float32)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # xn was rounded to the input dtype before parking in f32 scratch, so
    # this cast is an exact round trip: the gate/up dots see the same
    # operand bytes the unfused xn @ w_gate reads
    xn = xn_scr[:].astype(h1.dtype)
    # the MXU accumulates in f32 (Mosaic refuses a narrower accumulator);
    # rounding the result once to the input dtype is what XLA's own
    # bf16-output dot does on the chip
    g = jnp.dot(xn, wg_ref[:],
                preferred_element_type=jnp.float32).astype(h1.dtype)
    u = jnp.dot(xn, wu_ref[:],
                preferred_element_type=jnp.float32).astype(h1.dtype)
    act = (jax.nn.silu(g.astype(jnp.float32))
           * u.astype(jnp.float32)).astype(h1.dtype)   # swiglu's math
    acc_scr[:] += jax.lax.dot_general(
        act, wd_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h1_ref[:] = h1
    y_ref[:] = acc_scr[:].astype(y_ref.dtype)


def _fused_mlp_kernel_call(x, attn_y, norm_w, w_gate, w_up, w_down, eps):
    Bp, h = x.shape
    inter = w_gate.shape[1]
    F = fused_mlp_block_cols(inter)
    kernel = functools.partial(_fused_mlp_kernel, eps=eps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(inter // F,),
        in_specs=[
            pl.BlockSpec((Bp, h), lambda j: (0, 0)),
            pl.BlockSpec((Bp, h), lambda j: (0, 0)),
            pl.BlockSpec((h,), lambda j: (0,)),
            pl.BlockSpec((h, F), lambda j: (0, j)),
            pl.BlockSpec((h, F), lambda j: (0, j)),
            pl.BlockSpec((F, h), lambda j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Bp, h), lambda j: (0, 0)),
            pl.BlockSpec((Bp, h), lambda j: (0, 0)),
        ],
        scratch_shapes=[
            _VMEM((Bp, h), jnp.float32),
            _VMEM((Bp, h), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bp, h), x.dtype),
            jax.ShapeDtypeStruct((Bp, h), x.dtype),
        ],
        name="fused_mlp",
        interpret=interpret_mode(),
    )(x, attn_y, norm_w, w_gate, w_up, w_down)


def fused_layer_mlp_reference(x, attn_y, norm_w, w_gate, w_up, w_down, eps):
    """The unfused composition (oracle + fallback): residual add, the
    rms_norm op (which itself dispatches the rms Pallas kernel — this IS
    the pre-fusion program), swiglu MLP.  Returns ``(h1, y)`` with the
    down projection UN-reduced: the caller owns the TP psum boundary and
    the closing residual add (models/llama.decoder_layer_tail)."""
    from . import rms_norm as rms
    from . import swiglu as swiglu_mod

    h1 = x + attn_y
    xn = rms.rms_norm(h1, norm_w, eps)
    y = swiglu_mod.swiglu(xn @ w_gate, xn @ w_up) @ w_down
    return h1, y


def fused_layer_mlp(x, attn_y, norm_w, w_gate, w_up, w_down, eps):
    """Fused post-attention layer half for the decode hot path: residual
    add + post RMSNorm + SwiGLU MLP in ONE Pallas launch, MLP weights
    streamed HBM→VMEM in ffn-column blocks per grid step (double-buffered
    by the pipeline).

    Args:
      x: [B, h] residual stream entering the layer half.
      attn_y: [B, h] attention output projection AFTER the TP psum
        (``psum(attn @ wo)`` — the kernel must see the completed sum, so
        the all-reduce boundary stays outside, exactly where PR 7 put it).
      norm_w: [h] post-norm weight; w_gate/w_up: [h, inter] column blocks
        (tp-local slice under TP); w_down: [inter, h].
      eps: rms epsilon.

    Returns ``(h1 [B, h], y [B, h])``: ``h1 = x + attn_y`` (the layer's
    next residual anchor) and ``y`` the UN-reduced down projection — the
    caller closes the layer with ``h1 + psum(y)``.  Dispatches to the
    Pallas kernel when :func:`fused_mlp_supported`; the
    ``PADDLE_TPU_DISABLE_PALLAS=fused_layer_mlp`` opt-out (or an
    unsupported shape) routes to the unfused reference composition."""
    global MLP_KERNEL_CALLS, MLP_FALLBACK_CALLS
    B, h = x.shape
    inter = w_gate.shape[1]
    if not fused_mlp_supported(h, inter):
        MLP_FALLBACK_CALLS += 1
        return fused_layer_mlp_reference(x, attn_y, norm_w, w_gate, w_up,
                                         w_down, eps)
    MLP_KERNEL_CALLS += 1
    Bp = _round_up(B, _MIN_GROUP_ROWS)
    xp, ayp = x, attn_y
    if Bp != B:
        # pad the row dim to a full sublane; zero rows rms-normalize to
        # zeros (rsqrt(eps) * 0), sliced off below
        pad = ((0, Bp - B), (0, 0))
        xp = jnp.pad(x, pad)
        ayp = jnp.pad(attn_y, pad)
    h1, y = _fused_mlp_kernel_call(xp, ayp, norm_w, w_gate, w_up, w_down,
                                   float(eps))
    return h1[:B], y[:B]
