"""Decode-path attention with KV caches (the LLM serving hot ops).

Reference: the reference ships these as hand-written CUDA fused ops —
`masked_multihead_attention_` (fused_ops.yaml:~, kernels in
phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu: single-token
decode, append k/v to a dense cache, attend q over the prefix) and
`block_multihead_attention_` (fused_ops.yaml:45, blocked/paged KV cache with
per-sequence block tables, PageAttention-style).

TPU-native design: both are expressed as gather + batched matmul so XLA tiles
them onto the MXU; the block-table gather compiles to a dynamic-slice-free
`take` along the block axis (static shapes — the cache and tables are padded
to maxima, masking handles the ragged tails).  The paged decode hot path
additionally has a ragged Pallas kernel (`ops/pallas/paged_attention.py`,
docs/paged_attention.md) behind :func:`paged_decode_attention` that walks
only each slot's live pages; the gather oracle/fallback lives in
`pallas.paged_attention.paged_attention_reference` (one home —
:func:`block_multihead_attention` is a parity alias over it).  All
functions are functional:
caches are inputs AND outputs (donated under jit), matching JAX's
no-mutation model rather than the reference's in-place `_` ops.

Tensor parallelism (docs/tp_serving.md): the serving engine's
``tensor_parallel`` mode calls these front doors from INSIDE a shard_map
region over a 1-D ("tp",) mesh, with ``num_kv_heads`` (and the grouped
query heads) already tp-LOCAL slices — the KV pools shard along kv_heads,
block tables and seq_lens replicate, and since attention is independent
per kv-head group and the GQA ratio nh/nkv is tp-invariant, every function
here (and the Pallas kernels they dispatch to) runs byte-unchanged
per-shard with zero collectives.  No axis_name ever reaches this layer by
design: the only cross-shard traffic of the TP step lives at the decoder's
two psum boundaries (models/llama.decoder_attn_residual /
decoder_mlp_residual).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "masked_multihead_attention",
    "block_multihead_attention",
    "paged_decode_attention",
    "paged_verify_attention",
    "paged_prefill_attention",
    "fused_paged_decode_step",
    "fused_paged_quant_decode_step",
    "append_to_block_cache",
]


def masked_multihead_attention(qkv, cache_k, cache_v, seq_lens, scale=None):
    """Single-token decode attention over a dense KV cache.

    Args:
      qkv: [b, 3, nh, hd] current-step packed q/k/v (nh == kv heads here;
        apply GQA repeat before calling for grouped heads).
      cache_k, cache_v: [b, nh, S, hd] dense caches, valid prefix per batch
        given by seq_lens.
      seq_lens: [b] int32 — number of tokens already in the cache.

    Returns (out [b, nh, hd], new_cache_k, new_cache_v, new_seq_lens).
    """
    b, three, nh, hd = qkv.shape
    assert three == 3, f"qkv must pack q,k,v; got dim1={three}"
    S = cache_k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    q = qkv[:, 0]  # [b, nh, hd]
    k_new = qkv[:, 1]
    v_new = qkv[:, 2]

    # write k_new/v_new at position seq_lens (scatter via one-hot: static shapes)
    pos_oh = jax.nn.one_hot(seq_lens, S, dtype=cache_k.dtype)       # [b, S]
    cache_k = cache_k * (1 - pos_oh[:, None, :, None]) + \
        k_new[:, :, None, :] * pos_oh[:, None, :, None]
    cache_v = cache_v * (1 - pos_oh[:, None, :, None]) + \
        v_new[:, :, None, :] * pos_oh[:, None, :, None]

    new_lens = seq_lens + 1
    # attend q over cache[0:new_lens]
    logits = jnp.einsum("bnd,bnsd->bns", q.astype(jnp.float32),
                        cache_k.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, None, :] < new_lens[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bns,bnsd->bnd", p.astype(cache_v.dtype), cache_v)
    return out, cache_k, cache_v, new_lens


def append_to_block_cache(key_cache, value_cache, k, v, block_tables, seq_lens):
    """Append one token's k/v into a paged cache.

    key_cache/value_cache: [num_blocks, nh, block_size, hd]
    k, v: [b, nh, hd];  block_tables: [b, max_blocks] int32 (-1 = unassigned);
    seq_lens: [b] current lengths. Returns updated caches.
    """
    num_blocks, nh, bs, hd = key_cache.shape
    b = k.shape[0]
    blk_idx = seq_lens // bs                                  # logical block
    blk_off = seq_lens % bs
    phys = jnp.take_along_axis(block_tables, blk_idx[:, None], axis=1)[:, 0]
    phys = jnp.maximum(phys, 0)

    # scatter: for each batch elem, write k at [phys, :, blk_off, :]
    def write_one(cache, vec):
        def body(i, c):
            return c.at[phys[i], :, blk_off[i], :].set(vec[i].astype(c.dtype))

        return jax.lax.fori_loop(0, b, body, cache)

    return write_one(key_cache, k), write_one(value_cache, v)


def paged_decode_attention(q, key_cache, value_cache, block_tables, seq_lens,
                           scale=None, kv_quant=None, k_scale=None,
                           v_scale=None, num_shards=None):
    """Ragged paged-attention decode (the CB engine's ``paged=True`` hot op).

    GQA-aware front door over the Pallas kernel
    (`ops/pallas/paged_attention.py`): q may carry ``num_heads`` grouped
    query heads over ``num_kv_heads`` cache heads, and the caches may be
    weight-only-style quantized (``kv_quant`` in {'int8', 'int4'} with
    per-page scales).  Dispatches to the SPLIT-K flash-decode kernel when
    the per-launch shard heuristic fans out (a long slot's page walk runs
    as S parallel shards merged by an exact log-sum-exp combine —
    docs/paged_attention.md "Split-K flash-decode";
    ``PADDLE_TPU_DISABLE_PALLAS=flash_decode`` restores the sequential
    walk; ``num_shards`` overrides the heuristic), to the sequential
    kernel otherwise — both walk only each slot's LIVE block-table pages,
    so HBM bytes scale with the tokens actually resident, not with the
    longest request — and falls back to the
    :func:`block_multihead_attention`-style gather oracle off-TPU-shapes or
    under ``PADDLE_TPU_DISABLE_PALLAS=paged_attention``.

    Shapes: q [b, nh, hd]; caches [num_blocks, nkv, block_size, hd]
    (nh % nkv == 0); block_tables [b, max_blocks]; seq_lens [b].
    Returns out [b, nh, hd]."""
    from .pallas import paged_attention as _pa

    return _pa.paged_attention_decode(
        q, key_cache, value_cache, block_tables, seq_lens, scale=scale,
        kv_quant=kv_quant, k_scale=k_scale, v_scale=v_scale,
        num_shards=num_shards)


def fused_paged_decode_step(q, k_new, v_new, cos, sin, key_cache,
                            value_cache, block_tables, seq_lens, write_blk,
                            writeable, scale=None):
    """Fused RoPE + KV-append + paged attention for one decode token per
    slot — decode megastep stage 1 (docs/paged_attention.md "Fused decode
    step"; the MPK paper's answer to per-layer dispatch tax).  The unfused
    decode path runs rope (XLA), two one-row scatters and the attention
    kernel per layer; this front door runs ONE Pallas launch that rotates
    q/k in-kernel, inserts the new k/v into the slot's write page
    in-register before the score dot, and commits the page through an
    aliased pool output.  fp pools only; in the serving engine the pools
    carry one extra SPILL page (physical index num_blocks) that dropped
    writes land on.  Falls back to the rope+scatter+gather-oracle
    composition off-TPU-shapes or under
    ``PADDLE_TPU_DISABLE_PALLAS=fused_decode_step``.

    Shapes: q [b, nh, hd] PRE-rope; k_new/v_new [b, nkv, hd] pre-rope;
    cos/sin [b, hd] rope rows at each slot's append position; caches
    [num_blocks(+1), nkv, block_size, hd]; block_tables [b, max_blocks];
    seq_lens [b] PRE-append lengths; write_blk [b] physical append page
    (spill when dropped); writeable [b].  Returns
    (out [b, nh, hd], key_cache, value_cache)."""
    from .pallas import paged_attention as _pa

    return _pa.fused_decode_step(
        q, k_new, v_new, cos, sin, key_cache, value_cache, block_tables,
        seq_lens, write_blk, writeable, scale=scale)


def fused_paged_quant_decode_step(q, k_new, v_new, cos, sin, key_codes,
                                  key_scale, value_codes, value_scale,
                                  block_tables, seq_lens, write_blk,
                                  writeable, kv_quant, scale=None,
                                  num_shards=None):
    """Fused RoPE + REQUANTIZED KV-page append + dequant-on-read paged
    attention for one decode token per slot over int8/packed-int4 pools —
    decode megastep stage 2's quantized-serving member (docs/
    paged_attention.md "Megastep stage 2").  The unfused quantized decode
    path pays a requant-scatter pair per pool per layer (a new row
    dirties the page's absmax scale, so the whole page is dequantized,
    rewritten and rescaled in XLA); this front door runs ONE Pallas
    launch that recomputes the dirty page's scale in-register and commits
    codes AND scale through aliased outputs.  Falls back to the
    requant-scatter + gather-oracle composition off-TPU-shapes or under
    ``PADDLE_TPU_DISABLE_PALLAS=fused_quant_append`` (or
    ``fused_decode_step``) — pool bytes identical either way (the two
    arms share one page-encode implementation).

    Shapes: q [b, nh, hd] PRE-rope; k_new/v_new [b, nkv, hd] pre-rope;
    cos/sin [b, hd]; key_codes/value_codes [num_blocks(+1), nkv,
    block_size, hd_store] int8 (hd_store = hd, or hd // 2 packed int4)
    with key_scale/value_scale [num_blocks(+1), nkv] f32; block_tables
    [b, max_blocks]; seq_lens [b] PRE-append; write_blk/writeable [b].
    Returns (out [b, nh, hd], key_codes, key_scale, value_codes,
    value_scale)."""
    from .pallas import paged_attention as _pa

    return _pa.fused_quant_decode_step(
        q, k_new, v_new, cos, sin, key_codes, key_scale, value_codes,
        value_scale, block_tables, seq_lens, write_blk, writeable,
        kv_quant, scale=scale, num_shards=num_shards)


def paged_verify_attention(q, key_cache, value_cache, block_tables, seq_lens,
                           q_lens, scale=None):
    """Ragged multi-token verification (the speculative-decoding hot op;
    reference: the ``speculate_*`` op family in paddle/phi/ops/yaml).

    Each slot verifies ``q_lens[b]`` query tokens at consecutive positions —
    the pending token plus up to K n-gram-drafted tokens — in ONE launch of
    the paged-attention kernel family (`ops/pallas/paged_attention.
    paged_attention_verify`, docs/speculative.md), with a per-row causal
    mask: drafted token t attends everything up to and including itself,
    never the later drafts.  Falls back to the gather oracle
    (``pallas.paged_attention.paged_verify_reference``) off-TPU-shapes or
    under ``PADDLE_TPU_DISABLE_PALLAS=paged_attention``.

    Shapes: q [b, qmax, nh, hd]; caches [num_blocks, nkv, block_size, hd]
    (nh % nkv == 0, drafts' K/V already written); block_tables
    [b, max_blocks]; seq_lens [b] TOTAL written length incl. drafts;
    q_lens [b] in 1..qmax.  Returns [b, qmax, nh, hd]."""
    from .pallas import paged_attention as _pa

    return _pa.paged_attention_verify(q, key_cache, value_cache,
                                      block_tables, seq_lens, q_lens,
                                      scale=scale)


def paged_prefill_attention(q, key_cache, value_cache, block_tables,
                            seq_lens, q_lens, scale=None, kv_quant=None,
                            k_scale=None, v_scale=None):
    """Ragged chunked prefill (the continuous-batching engine's unified
    mixed prefill/decode hot op; docs/chunked_prefill.md).

    Each slot carries ``q_lens[b]`` query rows at consecutive positions —
    a ``prefill_chunk``-token slice of its prompt streaming into
    already-written pages, or a single pending decode token riding the same
    launch — all attended in ONE call of the paged-attention kernel family
    (`ops/pallas/paged_attention.paged_attention_prefill`) under the verify
    kernel's per-row causal law: chunk row t sees the written prefix plus
    the chunk through itself, never the later rows.  This is what lets the
    engine co-schedule prefill chunks with decode in a single compiled step
    (decode never stalls behind a long prompt).  Supports the decode path's
    dequant-on-read quantized KV pools (``kv_quant`` in {'int8', 'int4'}
    with per-page scales).  Falls back to the gather oracle
    (``pallas.paged_attention.paged_prefill_reference``) off-TPU-shapes or
    under ``PADDLE_TPU_DISABLE_PALLAS=paged_attention``.

    Shapes: q [b, T, nh, hd]; caches [num_blocks, nkv, block_size, hd]
    (nh % nkv == 0, the chunk's K/V already written); block_tables
    [b, max_blocks]; seq_lens [b] TOTAL written length incl. the chunk;
    q_lens [b] in 1..T.  Returns [b, T, nh, hd]."""
    from .pallas import paged_attention as _pa

    return _pa.paged_attention_prefill(q, key_cache, value_cache,
                                       block_tables, seq_lens, q_lens,
                                       scale=scale, kv_quant=kv_quant,
                                       k_scale=k_scale, v_scale=v_scale)


def block_multihead_attention(q, key_cache, value_cache, block_tables,
                              seq_lens, scale=None):
    """PageAttention-style decode: q attends over a paged KV cache.

    Args:
      q: [b, nh, hd] one query token per sequence.
      key_cache/value_cache: [num_blocks, nh, block_size, hd].
      block_tables: [b, max_blocks] physical block ids (-1 for unused slots).
      seq_lens: [b] valid KV length per sequence (incl. the just-appended token).

    Returns out [b, nh, hd].  Thin reference-parity alias over the single
    gather-oracle implementation (`ops/pallas/paged_attention.
    paged_attention_reference` — also the kernel's dispatch fallback), so
    the two can never drift.
    """
    from .pallas.paged_attention import paged_attention_reference

    return paged_attention_reference(q, key_cache, value_cache, block_tables,
                                     seq_lens, scale=scale)
