"""Flash attention (Pallas TPU kernel).

Replaces the reference's CUDA flash-attn v2/v3 integration
(`paddle/phi/kernels/gpu/flash_attn_kernel.cu`, dynload
`paddle/phi/backends/dynload/flashattn.h`, varlen entry
`flash_attn_varlen_kernel`) with a TPU-native online-softmax kernel: Q/K/V
tiles stream HBM→VMEM, logits never materialize in HBM, the MXU does the two
matmuls per tile and the VPU the online rescale.

Feature parity with the reference kernel family:
- causal and full attention; a causal sliding ``window`` (a position sees
  itself and the ``window - 1`` before it) whose calls walk only the band:
  the grid's inner dimension is the few blocks a q (kv) block's band can
  touch, so the cost is s·w and not s²/2;
- GQA/MQA natively: K/V blocks are indexed per kv head group inside the grid
  (``bh // rep`` index maps) — grouped heads are never materialized in HBM;
- arbitrary sequence lengths: inputs are padded to the block grid and the
  kernel masks out-of-range KV columns (padded Q rows are sliced off);
- packed/varlen sequences via ``segment_ids`` (the TPU-native analog of the
  reference's cu_seqlens varlen API): positions attend only within equal ids;
- dense additive/boolean ``attn_mask`` ([b|1, h|1, sq, skv]) streamed through
  the kernel block-by-block — the mask is read tile-wise, logits still never
  hit HBM.

Layout: public entry takes BSHD ([batch, seq, heads, head_dim], the paddle
convention); the kernel runs BHSD grids of (batch*heads, q_blocks, kv_blocks).

Backward: two Pallas kernels (FlashAttention-2 recurrence) — a dk/dv kernel
gridded over kv blocks with (group, q) innermost, and a dq kernel gridded over
q blocks with kv innermost.  Per-tile probabilities are recomputed exactly
from the saved log-sum-exp; delta = rowsum(dO·O) is precomputed in XLA
(O(s·d)).  Block sizes are chosen per-call from a VMEM budget.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import interpret_mode, kernel_disabled, per_shard

_VMEM = pltpu.VMEM

NEG_INF = -1e30

# trace-time counters: how often the public entry took the Pallas kernel path
# vs the composed-XLA fallback (bench.py records both in its detail output)
KERNEL_CALLS = 0
FALLBACK_CALLS = 0

# VMEM working-set budget for block-size selection (per-core VMEM is ~16 MiB;
# leave headroom for the pipeline's double buffering and the compiler)
_VMEM_BUDGET = 8 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(seq: int, cap: int) -> int:
    """Largest block in {cap, ..., 128} that divides the 128-padded length;
    sequences shorter than 128 become a single 8-aligned block."""
    if seq < 128:
        return _round_up(seq, 8)
    padded = _round_up(seq, 128)
    bs = cap
    while bs > 128 and padded % bs:
        bs //= 2
    return bs


def _pick_blocks(sq: int, skv: int, d: int, has_mask: bool) -> tuple[int, int]:
    """(bq, bkv) under the VMEM budget.  Working set per grid step (fp32,
    double-buffered inputs): q + 2·kv + optional mask tile + s/p intermediates
    + accumulators."""
    cap = 512

    def fits(bq, bkv):
        inputs = 2 * (bq * d + 2 * bkv * d) * 4          # double-buffered
        mask_b = 2 * bq * bkv * 4 if has_mask else 0
        scratch = (bq * d + 2 * bq) * 4
        inter = 3 * bq * bkv * 4                          # s, p, selects
        return inputs + mask_b + scratch + inter <= _VMEM_BUDGET

    bq, bkv = _pick_block(sq, cap), _pick_block(skv, cap)
    while not fits(bq, bkv) and bkv > 128:
        bkv //= 2
    while not fits(bq, bkv) and bq > 128:
        bq //= 2
    return bq, bkv


def _pad_seq(x, seq_axis: int, target: int):
    pad = target - x.shape[seq_axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[seq_axis] = (0, pad)
    return jnp.pad(x, widths)


def _mask_index_fn(b: int, hq: int, mb: int, mh: int):
    """Grid-dim-0 (b·hq) → mask row index for a [mb·mh, sq, skv] mask with
    broadcastable batch/head dims (mb ∈ {1,b}, mh ∈ {1,hq})."""

    def idx(bh):
        batch = bh // hq
        h = bh % hq
        return (batch if mb > 1 else 0) * mh + (h if mh > 1 else 0)

    return idx


def _tile_mask(s, mask_blk):
    """Apply one streamed mask tile to the logits tile."""
    if mask_blk.dtype == jnp.bool_:
        return jnp.where(mask_blk, s, NEG_INF)
    return s + mask_blk.astype(jnp.float32)


def _seg_mask(s, q_seg, kv_seg):
    """Packed-sequence mask: attend only within equal segment ids.
    Seg refs are [1, blk, 1] (trailing singleton keeps Mosaic's last-two-dims
    block constraint satisfiable)."""
    return jnp.where(q_seg[0, :, 0][:, None] == kv_seg[0, :, 0][None, :],
                     s, NEG_INF)


def _bounds_mask(s, kv_idx, bkv, kv_len):
    """Mask padded KV columns (seq padded up to the block grid)."""
    cols = kv_idx * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols < kv_len, s, NEG_INF)


def _causal_mask(s, q_idx, bq, kv_idx, bkv):
    rows = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kv_idx * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, NEG_INF)


def _window_mask(s, q_idx, bq, kv_idx, bkv, window):
    rows = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kv_idx * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows - cols < window, s, NEG_INF)


def _band_first_kv(q_idx, bq, bkv, window):
    """First kv block that the band of q block ``q_idx`` touches."""
    return jnp.maximum(q_idx * bq - (window - 1), 0) // bkv


def _band_first_q(kv_idx, bq, bkv):
    """First q block that sees kv block ``kv_idx`` (causal)."""
    return kv_idx * bkv // bq


def _band_widths(n_q, n_kv, bq, bkv, window):
    """(kv blocks a q block's band touches at most, q blocks a kv block's
    band touches at most): the inner grid dimensions of the banded calls."""
    kv_per_q = max(((i + 1) * bq - 1) // bkv
                   - max(i * bq - (window - 1), 0) // bkv + 1
                   for i in range(n_q))
    q_per_kv = max(min(n_q - 1, ((j + 1) * bkv - 1 + window - 1) // bq)
                   - j * bkv // bq + 1 for j in range(n_kv))
    return kv_per_q, q_per_kv


def _run_block(q_idx, kv_idx, *, causal, bq, bkv, kv_len, window):
    """Whole-block skips: padded KV blocks (fully out of range), causal
    (block fully above the diagonal) and, under a window, blocks fully
    behind the band."""
    run = kv_idx * bkv < kv_len
    if causal:
        run &= (q_idx + 1) * bq - 1 >= kv_idx * bkv
    if window is not None:
        run &= q_idx * bq - ((kv_idx + 1) * bkv - 1) < window
    return run


def _masked_logits(q, k, refs, q_idx, kv_idx, *, scale, causal, bq, bkv,
                   kv_len, skv_pad, has_mask, has_seg, window=None):
    """Shared fwd/bwd logits tile: QK^T · scale with all masks applied.
    ``refs`` holds the optional (mask, q_seg, kv_seg) refs in order."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    it = iter(refs)
    if has_mask:
        s = _tile_mask(s, next(it)[0])
    if has_seg:
        s = _seg_mask(s, next(it), next(it))
    if causal:
        s = _causal_mask(s, q_idx, bq, kv_idx, bkv)
    if window is not None:
        s = _window_mask(s, q_idx, bq, kv_idx, bkv, window)
    if kv_len != skv_pad:
        s = _bounds_mask(s, kv_idx, bkv, kv_len)
    return s


def _safe_exp(s, shift):
    """exp(s - shift) that is exactly 0 for fully-masked entries even when the
    running max / lse is itself NEG_INF (avoids exp(-inf + inf) = 1)."""
    return jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - shift), 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, bq, bkv, kv_len,
                skv_pad, has_mask, has_seg, window=None):
    """Grid: (bh, num_q_blocks, num_kv_blocks); kv innermost (sequential).
    Under ``window`` the inner dimension walks the band's kv blocks only,
    from ``_band_first_kv`` on."""
    n_opt = int(has_mask) + 2 * int(has_seg)
    opt_refs = rest[:n_opt]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[n_opt:]
    j = pl.program_id(2)
    q_idx = pl.program_id(1)
    kv_idx = j if window is None else (
        _band_first_kv(q_idx, bq, bkv, window) + j)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _run_block(q_idx, kv_idx, causal=causal, bq=bq, bkv=bkv,
                     kv_len=kv_len, window=window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bkv, d]
        v = v_ref[0].astype(jnp.float32)  # [bkv, d]
        s = _masked_logits(q, k, opt_refs, q_idx, kv_idx, scale=scale,
                           causal=causal, bq=bq, bkv=bkv, kv_len=kv_len,
                           skv_pad=skv_pad, has_mask=has_mask,
                           has_seg=has_seg, window=window)
        m_prev = m_scr[:]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = _safe_exp(s, m_new)  # [bq, bkv]
        alpha = _safe_exp(m_prev, m_new)  # [bq, 1]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)  # [bq, 1]


def _opt_specs(bq, bkv, mask, mask_idx, segs, batch_of, q_blk, kv_blk,
               head_of=None):
    """(arrays, in_specs) for the optional streamed inputs, shared by the three
    kernels.  ``q_blk``/``kv_blk``: grid position → (q block, kv block);
    ``head_of``: grid position → q-head row (defaults to grid dim 0; the dkv
    kernel resolves it from its (kv-head, group·q) walk)."""
    head_of = head_of or (lambda *g: g[0])
    arrays, specs = [], []
    if mask is not None:
        arrays.append(mask)
        specs.append(pl.BlockSpec(
            (1, bq, bkv),
            lambda *g: (mask_idx(head_of(*g)), q_blk(*g), kv_blk(*g))))
    if segs is not None:
        q_seg, kv_seg = segs
        arrays += [q_seg, kv_seg]
        specs.append(pl.BlockSpec(
            (1, bq, 1), lambda *g: (batch_of(head_of(*g)), q_blk(*g), 0)))
        specs.append(pl.BlockSpec(
            (1, bkv, 1), lambda *g: (batch_of(head_of(*g)), kv_blk(*g), 0)))
    return arrays, specs


def _flash_fwd(q, k, v, scale, causal, *, rep=1, kv_len=None, mask=None,
               mask_idx=None, segs=None, batch_of=None, blocks=None,
               window=None):
    """q: [bh, sq, d] (bh = b·hq); k,v: [bh // rep, skv, d].
    Returns (out [bh, sq, d], lse [bh, sq]).  All seq lengths already padded
    to the block grid; ``kv_len`` is the real KV length before padding;
    ``blocks`` is the (bq, bkv) the caller padded for."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    kv_len = skv if kv_len is None else kv_len
    bq_sz, bkv_sz = blocks or _pick_blocks(sq, skv, d, mask is not None)
    n_q = pl.cdiv(sq, bq_sz)
    n_kv = pl.cdiv(skv, bkv_sz)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq_sz, bkv=bkv_sz,
        kv_len=kv_len, skv_pad=skv, has_mask=mask is not None,
        has_seg=segs is not None, window=window,
    )
    if window is None:
        n_inner, kv_of = n_kv, lambda i, j: j
    else:
        # the band's kv blocks only; past the diagonal the index stays on
        # the last block (no new copy) and the kernel skips the step
        n_inner = _band_widths(n_q, n_kv, bq_sz, bkv_sz, window)[0]
        kv_of = lambda i, j: jnp.minimum(
            _band_first_kv(i, bq_sz, bkv_sz, window) + j, n_kv - 1)
    opt_arrays, opt_specs = _opt_specs(
        bq_sz, bkv_sz, mask, mask_idx, segs, batch_of,
        q_blk=lambda b, i, j: i, kv_blk=lambda b, i, j: kv_of(i, j))
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_inner),
        in_specs=[
            pl.BlockSpec((1, bq_sz, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv_sz, d),
                         lambda b, i, j: (b // rep, kv_of(i, j), 0)),
            pl.BlockSpec((1, bkv_sz, d),
                         lambda b, i, j: (b // rep, kv_of(i, j), 0)),
            *opt_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, bq_sz, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_sz, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            _VMEM((bq_sz, 1), jnp.float32),
            _VMEM((bq_sz, 1), jnp.float32),
            _VMEM((bq_sz, d), jnp.float32),
        ],
        name="flash_attn_fwd" if window is None else "flash_attn_win_fwd",
        interpret=interpret_mode(),
    )(q, k, v, *opt_arrays)
    return out, lse[..., 0]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                scale, causal, bq, bkv, kv_len, skv_pad, n_q, n_inner,
                has_mask, has_seg, window=None):
    """Grid: (bh_kv, num_kv_blocks, rep·n_inner); the innermost dim walks
    the q blocks (all ``n_inner = n_q`` of them, or under ``window`` the
    band's, from ``_band_first_q`` on) of every q head in the kv head's
    group (sequential)."""
    n_opt = int(has_mask) + 2 * int(has_seg)
    opt_refs = rest[:n_opt]
    dk_ref, dv_ref, dk_scr, dv_scr = rest[n_opt:]
    t = pl.program_id(2)
    kv_idx = pl.program_id(1)
    q_idx = t % n_inner
    if window is not None:
        q_idx += _band_first_q(kv_idx, bq, bkv)

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _run_block(q_idx, kv_idx, causal=causal, bq=bq, bkv=bkv,
                     kv_len=kv_len, window=window)
    if window is not None:
        run &= q_idx < n_q

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bkv, d]
        v = v_ref[0].astype(jnp.float32)          # [bkv, d]
        do = do_ref[0].astype(jnp.float32)        # [bq, d]
        lse = lse_ref[0]                          # [bq, 1]
        delta = delta_ref[0]                      # [bq, 1]
        s = _masked_logits(q, k, opt_refs, q_idx, kv_idx, scale=scale,
                           causal=causal, bq=bq, bkv=bkv, kv_len=kv_len,
                           skv_pad=skv_pad, has_mask=has_mask,
                           has_seg=has_seg, window=window)
        p = _safe_exp(s, lse)                      # exact probs
        # dv += p^T @ do
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # [bq, bkv]
        # dk += ds^T @ q
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, bq, bkv, kv_len, skv_pad, has_mask, has_seg,
               window=None):
    """Grid: (bh, num_q_blocks, num_kv_blocks); kv innermost (sequential);
    under ``window`` the band's kv blocks only, as in the forward."""
    n_opt = int(has_mask) + 2 * int(has_seg)
    opt_refs = rest[:n_opt]
    dq_ref, dq_scr = rest[n_opt:]
    j = pl.program_id(2)
    q_idx = pl.program_id(1)
    kv_idx = j if window is None else (
        _band_first_kv(q_idx, bq, bkv, window) + j)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = _run_block(q_idx, kv_idx, causal=causal, bq=bq, bkv=bkv,
                     kv_len=kv_len, window=window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = _masked_logits(q, k, opt_refs, q_idx, kv_idx, scale=scale,
                           causal=causal, bq=bq, bkv=bkv, kv_len=kv_len,
                           skv_pad=skv_pad, has_mask=has_mask,
                           has_seg=has_seg, window=window)
        p = _safe_exp(s, lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, scale, causal, *, rep=1, kv_len=None,
               mask=None, mask_idx=None, segs=None, batch_of=None, blocks=None,
               window=None):
    """Pallas FlashAttention-2 backward; q/out/do: [bh, sq, d], k/v:
    [bh // rep, skv, d].  Returns (dq [bh,...], dk, dv [bh//rep,...]) — the
    group sum for GQA happens inside the dkv kernel's accumulator."""
    bh, sq, d = q.shape
    bhkv, skv, _ = k.shape
    kv_len = skv if kv_len is None else kv_len
    bq_sz, bkv_sz = blocks or _pick_blocks(sq, skv, d, mask is not None)
    n_q = pl.cdiv(sq, bq_sz)
    n_kv = pl.cdiv(skv, bkv_sz)

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)          # [bh, sq, 1]
    lse3 = lse[..., None]                             # [bh, sq, 1]

    if window is None:
        kv_inner, q_inner = n_kv, n_q
        kv_of = lambda i, j: j
        q_of = lambda kv, t: t % n_q
    else:
        # the band's blocks only; an index past the band's end stays on
        # the last block (no new copy) and the kernel skips the step
        kv_inner, q_inner = _band_widths(n_q, n_kv, bq_sz, bkv_sz, window)
        kv_of = lambda i, j: jnp.minimum(
            _band_first_kv(i, bq_sz, bkv_sz, window) + j, n_kv - 1)
        q_of = lambda kv, t: jnp.minimum(
            _band_first_q(kv, bq_sz, bkv_sz) + t % q_inner, n_q - 1)

    # dkv grid → q-row index
    hq_of = lambda bh_kv, t: bh_kv * rep + t // q_inner

    common = dict(scale=scale, causal=causal, bq=bq_sz, bkv=bkv_sz,
                  kv_len=kv_len, skv_pad=skv, window=window,
                  has_mask=mask is not None, has_seg=segs is not None)

    # ---- dk/dv: grid (bh_kv, n_kv, rep·n_q), q innermost over the group ----
    # the optional-input index maps resolve the group-dependent q head first
    q_spec = pl.BlockSpec((1, bq_sz, d),
                          lambda b, kv, t: (hq_of(b, t), q_of(kv, t), 0))
    row_spec = pl.BlockSpec((1, bq_sz, 1),
                            lambda b, kv, t: (hq_of(b, t), q_of(kv, t), 0))
    kv_spec = pl.BlockSpec((1, bkv_sz, d), lambda b, kv, t: (b, kv, 0))
    opt_arrays, opt_specs = _opt_specs(
        bq_sz, bkv_sz, mask, mask_idx, segs, batch_of,
        q_blk=lambda b, kv, t: q_of(kv, t), kv_blk=lambda b, kv, t: kv,
        head_of=lambda b, kv, t: hq_of(b, t))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, n_inner=q_inner, **common),
        grid=(bhkv, n_kv, rep * q_inner),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  *opt_specs],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((bhkv, skv, d), k.dtype),
                   jax.ShapeDtypeStruct((bhkv, skv, d), v.dtype)],
        scratch_shapes=[_VMEM((bkv_sz, d), jnp.float32),
                        _VMEM((bkv_sz, d), jnp.float32)],
        name=("flash_attn_bwd_dkv" if window is None
              else "flash_attn_win_bwd_dkv"),
        interpret=interpret_mode(),
    )(q, k, v, do, lse3, delta, *opt_arrays)

    # ---- dq: grid (bh, n_q, n_kv), kv innermost ----
    q_spec_i = pl.BlockSpec((1, bq_sz, d), lambda b, i, j: (b, i, 0))
    kv_spec_j = pl.BlockSpec((1, bkv_sz, d),
                             lambda b, i, j: (b // rep, kv_of(i, j), 0))
    row_spec_i = pl.BlockSpec((1, bq_sz, 1), lambda b, i, j: (b, i, 0))
    opt_arrays_q, opt_specs_q = _opt_specs(
        bq_sz, bkv_sz, mask, mask_idx, segs, batch_of,
        q_blk=lambda b, i, j: i, kv_blk=lambda b, i, j: kv_of(i, j))

    dq, = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, n_q, kv_inner),
        in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, row_spec_i,
                  row_spec_i, *opt_specs_q],
        out_specs=[q_spec_i],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype)],
        scratch_shapes=[_VMEM((bq_sz, d), jnp.float32)],
        name=("flash_attn_bwd_dq" if window is None
              else "flash_attn_win_bwd_dq"),
        interpret=interpret_mode(),
    )(q, k, v, do, lse3, delta, *opt_arrays_q)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _flash_attention_core(q, k, v, mask, q_seg, kv_seg,
                          scale, causal, rep, kv_len, mask_idx, batch_of,
                          blocks, window=None):
    out, _ = _flash_fwd(
        q, k, v, scale, causal, rep=rep, kv_len=kv_len, mask=mask,
        mask_idx=mask_idx, segs=(q_seg, kv_seg) if q_seg is not None else None,
        batch_of=batch_of, blocks=blocks, window=window)
    return out


def _flash_core_fwd(q, k, v, mask, q_seg, kv_seg,
                    scale, causal, rep, kv_len, mask_idx, batch_of, blocks,
                    window=None):
    out, lse = _flash_fwd(
        q, k, v, scale, causal, rep=rep, kv_len=kv_len, mask=mask,
        mask_idx=mask_idx, segs=(q_seg, kv_seg) if q_seg is not None else None,
        batch_of=batch_of, blocks=blocks, window=window)
    return out, (q, k, v, mask, q_seg, kv_seg, out, lse)


def _xla_mask_grad(q, k, v, out, lse, do, mask, mask_idx, segs, scale, causal,
                   kv_len, rep):
    """Cotangent for an additive (float) attn_mask, recomputed in plain XLA:
    dmask = Σ_{broadcast group} ds with ds = p·(dp − delta)·scale.  This is
    O(s²) compute/memory — the same cost class as materializing the mask
    itself — and is dead-code-eliminated by XLA whenever the caller does not
    differentiate the mask *under jit*, so the jitted flash path stays
    O(s·d) in that case.  In eager (non-jit) grad with a float additive mask
    every backward pass does materialize the full [b·h, sq, skv] logits; run
    the step under jit if that cost matters."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    rows_idx = jnp.asarray([mask_idx(i) for i in range(bh)])
    kx = jnp.repeat(k, rep, axis=0) if rep > 1 else k
    vx = jnp.repeat(v, rep, axis=0) if rep > 1 else v
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   kx.astype(jnp.float32)) * scale
    s = s + mask[rows_idx].astype(jnp.float32)
    if segs is not None:
        q_seg, kv_seg = segs  # [b, s, 1]
        hq_n = bh // q_seg.shape[0]
        sq_ids = jnp.repeat(q_seg[:, :, 0], hq_n, axis=0)   # [bh, sq]
        sk_ids = jnp.repeat(kv_seg[:, :, 0], hq_n, axis=0)  # [bh, skv]
        s = jnp.where(sq_ids[:, :, None] == sk_ids[:, None, :], s, NEG_INF)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, skv), bool)), s, NEG_INF)
    if kv_len != skv:
        s = jnp.where(jnp.arange(skv)[None, None, :] < kv_len, s, NEG_INF)
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - lse[..., None]), 0.0)
    dp = jnp.einsum("bqd,bkd->bqk", do.astype(jnp.float32),
                    vx.astype(jnp.float32))
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    # d(loss)/d(mask): the mask adds to the POST-scale logits, so unlike the
    # dq/dk recurrence there is no ·scale factor here
    ds = p * (dp - delta)
    dmask = jax.ops.segment_sum(ds, rows_idx, num_segments=mask.shape[0])
    return dmask.astype(mask.dtype)


def _flash_core_bwd(scale, causal, rep, kv_len, mask_idx, batch_of, blocks,
                    window, res, do):
    q, k, v, mask, q_seg, kv_seg, out, lse = res
    segs = (q_seg, kv_seg) if q_seg is not None else None
    dq, dk, dv = _flash_bwd(
        q, k, v, out, lse, do, scale, causal, rep=rep, kv_len=kv_len,
        mask=mask, mask_idx=mask_idx, segs=segs,
        batch_of=batch_of, blocks=blocks, window=window)
    zero = lambda x: None if x is None else jnp.zeros_like(x)
    if mask is not None and jnp.issubdtype(mask.dtype, jnp.inexact):
        dmask = _xla_mask_grad(q, k, v, out, lse, do, mask, mask_idx, segs,
                               scale, causal, kv_len, rep)
    else:
        dmask = zero(mask)  # bool masks are not differentiable
    return dq, dk, dv, dmask, zero(q_seg), zero(kv_seg)


_flash_attention_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _normalize_mask(attn_mask, b, hq, sq, skv):
    """[b|1, h|1, sq, skv] (or 2D/3D broadcast forms) → ([mb·mh, sq, skv],
    index fn over grid dim 0)."""
    m = attn_mask
    if m.ndim == 2:
        m = m[None, None]
    elif m.ndim == 3:
        m = m[:, None]
    if m.shape[2] in (1, sq) and m.shape[3] in (1, skv):
        # broadcastable seq dims (e.g. paddle's canonical [b,1,1,skv]
        # key-padding mask from _convert_attention_mask): materialize
        if m.shape[2] != sq or m.shape[3] != skv:
            m = jnp.broadcast_to(m, m.shape[:2] + (sq, skv))
    else:
        raise ValueError(f"attn_mask seq dims {m.shape[2:]} != ({sq}, {skv})")
    mb, mh = m.shape[0], m.shape[1]
    if mb not in (1, b) or mh not in (1, hq):
        raise ValueError(f"attn_mask batch/head dims {m.shape[:2]} not "
                         f"broadcastable to ({b}, {hq})")
    return m.reshape(mb * mh, sq, skv), _mask_index_fn(b, hq, mb, mh)


def flash_attention_bshd(q, k, v, attn_mask=None, causal=False, scale=None,
                         segment_ids=None, window=None):
    """Public entry: q,k,v [batch, seq, heads, head_dim] (paddle layout).

    GQA/MQA: kv heads are indexed per group inside the kernel grid — grouped
    K/V never materialize in HBM.  ``attn_mask`` ([b|1, h|1, sq, skv], bool
    or additive) streams through the kernel tile-by-tile.  ``segment_ids``
    (a [b, s] int array, or a (q_ids, kv_ids) pair) implements packed/varlen
    attention (reference: flash_attn_varlen cu_seqlens).  Arbitrary sequence
    lengths are padded to the block grid and masked in-kernel.  ``window``
    (causal self-attention only, no mask or segments): a position sees
    itself and the ``window - 1`` before it, and the kernels walk the band's
    blocks only."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None:
        if not causal or attn_mask is not None or segment_ids is not None:
            raise ValueError("window= is causal self-attention's band: it "
                             "needs causal=True and takes no attn_mask or "
                             "segment_ids")
        if q.shape[1] != k.shape[1]:
            raise ValueError(f"window= needs equal q and kv lengths, got "
                             f"{q.shape[1]} and {k.shape[1]}")
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if attn_mask is None and segment_ids is None:
        # on a mesh of several devices (ops.pallas.spmd_kernels) every
        # (batch, head) pair is independent: each device attends its own
        # batch slice and head group
        bshd = lambda batch, heads: P(batch, None, heads, None)
        return per_shard(
            functools.partial(_flash_attention_bshd, causal=causal,
                              scale=scale, window=window),
            lambda b, h: ((bshd(b, h),) * 3, bshd(b, h)))(q, k, v)
    return _flash_attention_bshd(q, k, v, attn_mask, causal, scale,
                                 segment_ids)


def _flash_attention_bshd(q, k, v, attn_mask=None, causal=False, scale=None,
                          segment_ids=None, window=None):
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    skv = k.shape[1]
    global KERNEL_CALLS, FALLBACK_CALLS
    if d % 8 != 0 or hq % hkv != 0 or kernel_disabled("flash_attention"):
        FALLBACK_CALLS += 1
        if segment_ids is not None:
            # fold segment ids into the mask so packing semantics survive
            # the composed fallback
            if isinstance(segment_ids, (tuple, list)):
                q_ids, kv_ids = (jnp.asarray(s) for s in segment_ids)
            else:
                q_ids = kv_ids = jnp.asarray(segment_ids)
            seg_ok = q_ids[:, None, :, None] == kv_ids[:, None, None, :]
            if attn_mask is None:
                attn_mask = seg_ok
            elif attn_mask.dtype == jnp.bool_:
                attn_mask = jnp.logical_and(attn_mask, seg_ok)
            else:
                attn_mask = attn_mask + jnp.where(seg_ok, 0.0, NEG_INF)
        return _composed_attention(q, k, v, attn_mask, causal, scale, window)
    KERNEL_CALLS += 1
    rep = hq // hkv

    # BSHD -> (b*h, s, d)
    qh = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)

    bq_sz, bkv_sz = _pick_blocks(sq, skv, d, attn_mask is not None)
    sq_pad = _round_up(sq, bq_sz)
    skv_pad = _round_up(skv, bkv_sz)
    qh = _pad_seq(qh, 1, sq_pad)
    kh = _pad_seq(kh, 1, skv_pad)
    vh = _pad_seq(vh, 1, skv_pad)

    mask = mask_idx = None
    if attn_mask is not None:
        mask, mask_idx = _normalize_mask(attn_mask, b, hq, sq, skv)
        mask = _pad_seq(_pad_seq(mask, 1, sq_pad), 2, skv_pad)

    q_seg = kv_seg = batch_of = None
    if segment_ids is not None:
        if isinstance(segment_ids, (tuple, list)):
            q_ids, kv_ids = segment_ids
        else:
            q_ids = kv_ids = segment_ids
        # pad with -1/-2 so padded positions never match a real segment;
        # trailing singleton dim for the Mosaic block-shape constraint
        q_seg = jnp.pad(jnp.asarray(q_ids, jnp.int32), ((0, 0), (0, sq_pad - sq)),
                        constant_values=-1)[..., None]
        kv_seg = jnp.pad(jnp.asarray(kv_ids, jnp.int32), ((0, 0), (0, skv_pad - skv)),
                         constant_values=-2)[..., None]
        batch_of = lambda bh: bh // hq

    out = _flash_attention_core(qh, kh, vh, mask, q_seg, kv_seg,
                                scale, causal, rep, skv, mask_idx, batch_of,
                                (bq_sz, bkv_sz), window)
    out = out[:, :sq]
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


def _composed_attention(q, k, v, attn_mask, causal, scale, window=None):
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    if attn_mask is not None and attn_mask.ndim == 3:
        # [b, sq, skv] means per-batch (same as the kernel path's
        # _normalize_mask), not right-aligned broadcast over heads
        attn_mask = attn_mask[:, None]
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32), kh.astype(jnp.float32)) * scale
    if causal:
        m = jnp.tril(jnp.ones((logits.shape[-2], logits.shape[-1]), bool))
        if window is not None:
            m &= ~jnp.tril(m, -window)
        logits = jnp.where(m, logits, NEG_INF)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, NEG_INF)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    # fully-masked rows: softmax would give uniform garbage; zero them like
    # the flash kernel does
    all_masked = jnp.all(logits <= 0.5 * NEG_INF, axis=-1, keepdims=True)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(all_masked, 0.0, p)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    return out.astype(q.dtype).transpose(0, 2, 1, 3)
