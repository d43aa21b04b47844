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

Precision: the MXU products take their operands in the dtype the inputs
arrive in and accumulate in float32.  ``Q·Kᵀ`` and ``dO·Vᵀ`` multiply the
inputs themselves (a bf16 x bf16 product is exact in float32).  The four
products whose left side the kernel makes (``P·V``, ``Pᵀ·dO``, ``dSᵀ·Q``,
``dS·K``) round that side to the right side's dtype once, after all float32
arithmetic on it (``exp``, ``p·(dp − delta)·scale``) is done; ``scale``
multiplies the float32 logits, never a bf16 ``q``.  Logits, the running max
and sum, ``lse``, ``delta`` and the accumulators are float32 throughout.
With float32 inputs no cast is made and the products are float32 x float32
at the default precision.  On the TPU that is one bf16 pass as well: Mosaic
rounds a float32 operand to bf16 on its way into the MXU, so handing it bf16
gives the same bits in the same time (measured, PERF.md §6 PR 29); what the
explicit casts buy is that the jaxpr says what the chip does, on any backend.

Tiles: a block that ``_run_block`` lets run is an *edge* block (the causal
diagonal, the window's far edge or the KV length falls inside it; or the
call streams a mask or segment ids, then every block is) or an *interior*
one.  Each kernel holds a body for either: the edge body builds the masks
and guards ``exp`` against fully masked rows, the interior body is the
products and a plain ``exp(s − m)``.  ``tile_census`` counts both kinds from
the shapes; the kernel path's last call leaves its census and operand dtype
in ``LAST_CALL``.

Layout, which is where the time was (read off the TPU compiler's bundle
schedule, PERF.md §6 PR 29).  A per-row statistic kept as a column [bq, 1]
sits on one lane of each vreg and has to be spread over the lanes (an XLU
permute) every time it meets ``s`` or an accumulator, and a column block
[bq, 1] of an HBM array fills whole 128-lane tiles: 256 KB copied for 2 KB
of numbers.  So: the forward keeps its running max and sum [bq, 128] with
all lanes alike, as they come off the lane reductions; the dk/dv kernel
computes its tile by kv rows ([bkv, bq] = K·Qᵀ), where ``lse`` and ``delta``
are rows that spread over sublanes for nothing, dv and dk contract over the
tile's lanes, and neither ``P`` nor ``dS`` is transposed; the dq kernel takes
the same rows and stands them up once a q block as [bq, 128] columns with
all lanes alike; a grid step that a kernel skips keeps the block index of a
step that runs (``_walks``), so nothing is copied for it.  None of this
changes a number: the same sums in the same order.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import interpret_mode, kernel_disabled, per_shard

_VMEM = pltpu.VMEM

NEG_INF = -1e30
_LANES = 128        # a vreg's lanes: the width per-row statistics are kept at

# trace-time counters: how often the public entry took the Pallas kernel path
# vs the composed-XLA fallback
KERNEL_CALLS = 0
FALLBACK_CALLS = 0
# the kernel path's last call, written as it is traced: ``tiles``, the
# (skipped, edge, interior) blocks of one (head, pass) (``tile_census``), and
# ``operands``, the dtype its MXU products take (bfloat16: one pass each)
LAST_CALL: dict | None = None

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
    """(bq, bkv) under the VMEM budget.  Working set per grid step, reckoned
    at 4 bytes an element whatever the inputs' dtype (bf16 tiles take half
    of their part): double-buffered q + 2·kv + optional mask tile, the
    float32 s/p intermediates and the float32 accumulators."""
    cap = 512

    def fits(bq, bkv):
        inputs = 2 * (bq * d + 2 * bkv * d) * 4          # double-buffered
        mask_b = 2 * bq * bkv * 4 if has_mask else 0
        scratch = (bq * d + 2 * bq * _LANES) * 4
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


def _seg_mask(s, q_seg, kv_seg, by_kv):
    """Packed-sequence mask: attend only within equal segment ids.  One
    side's ids arrive as a column [1, blk, 1] (trailing singleton keeps
    Mosaic's last-two-dims block constraint satisfiable), the side along the
    tile's lanes (kv; q on a ``by_kv`` tile) as a row [1, 1, blk]."""
    if by_kv:
        same = kv_seg[0, :, 0][:, None] == q_seg[0, 0, :][None, :]
    else:
        same = q_seg[0, :, 0][:, None] == kv_seg[0, 0, :][None, :]
    return jnp.where(same, s, NEG_INF)


def _position_masks(s, q_idx, kv_idx, by_kv, *, causal, bq, bkv, kv_len,
                    skv_pad, window):
    """The causal diagonal, the window's far edge and the padded KV columns
    (seq padded up to the block grid) on one tile; q runs along axis 0, or
    along axis 1 on a ``by_kv`` tile."""
    if not causal and window is None and kv_len == skv_pad:
        return s
    q_ax = 1 if by_kv else 0
    rows = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_ax)
    cols = kv_idx * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   1 - q_ax)
    if causal:
        s = jnp.where(rows >= cols, s, NEG_INF)
    if window is not None:
        s = jnp.where(rows - cols < window, s, NEG_INF)
    if kv_len != skv_pad:
        s = jnp.where(cols < kv_len, s, NEG_INF)
    return s


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


def _walks(n_q, n_kv, bq, bkv, causal, window):
    """How the kernels' innermost grid dimension walks the blocks:
    (kv steps a q block, (q block, step) -> kv block, q steps a kv block,
    (kv block, step) -> q block).  Without a window a step is a block; under
    one the steps are the band's blocks only.  A step that the kernel skips
    (above the diagonal, past the band's end) is given the index of the
    nearest block that runs, which is already there: no copy is made for
    it."""
    last_kv = lambda i: ((i + 1) * bq - 1) // bkv    # the diagonal's block
    if window is None:
        kv_of = ((lambda i, j: jnp.minimum(j, last_kv(i))) if causal
                 else (lambda i, j: j))
        q_of = ((lambda kv, t: jnp.clip(t % n_q, _band_first_q(kv, bq, bkv),
                                        n_q - 1))
                if causal else (lambda kv, t: t % n_q))
        return n_kv, kv_of, n_q, q_of
    kv_inner, q_inner = _band_widths(n_q, n_kv, bq, bkv, window)
    kv_of = lambda i, j: jnp.minimum(
        _band_first_kv(i, bq, bkv, window) + j, last_kv(i))
    q_of = lambda kv, t: jnp.minimum(
        _band_first_q(kv, bq, bkv) + t % q_inner, n_q - 1)
    return kv_inner, kv_of, q_inner, q_of


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


def _edge_block(q_idx, kv_idx, *, causal, bq, bkv, kv_len, skv_pad, window,
                masked):
    """Whether a mask edge crosses a block that ``_run_block`` lets run: the
    causal diagonal, the window's far edge or ``kv_len`` falls inside it, or
    the call streams a mask or segment ids (``masked``: every block then).
    A block it clears is interior: every logit in it is kept.  A Python bool
    where the call's arguments alone decide it."""
    if masked:
        return True
    edge = False
    if causal:
        edge |= q_idx * bq < (kv_idx + 1) * bkv - 1
    if window is not None:
        edge |= (q_idx + 1) * bq - 1 - kv_idx * bkv >= window
    if kv_len != skv_pad:
        edge |= (kv_idx + 1) * bkv > kv_len
    return edge


def tile_census(sq, skv, bq, bkv, causal, window=None, kv_len=None,
                masked=False):
    """(skipped, edge, interior) blocks of one (head, pass) of a call over
    ``sq`` x ``skv`` positions in blocks of ``bq`` x ``bkv``, by the
    predicates the kernels branch on: skipped blocks do no work, edge blocks
    run the masked body, interior blocks the plain one.  A fact of the
    shapes, so it is counted here and not at run time."""
    n_q, n_kv = pl.cdiv(sq, bq), pl.cdiv(skv, bkv)
    i, j = np.indices((n_q, n_kv))
    geom = dict(causal=causal, bq=bq, bkv=bkv, window=window,
                kv_len=skv if kv_len is None else kv_len)
    run = _run_block(i, j, **geom)
    edge = run & _edge_block(i, j, skv_pad=n_kv * bkv, masked=masked, **geom)
    return tuple(int(n) for n in (run.size - run.sum(), edge.sum(),
                                  run.sum() - edge.sum()))


def _when_tile(run, edge, body):
    """``body(edge)`` on a block that runs: the masked body where an edge
    crosses it, the plain one elsewhere; one body only where ``edge`` is
    known when the kernel is traced."""
    if isinstance(edge, bool):
        pl.when(run)(functools.partial(body, edge))
    else:
        pl.when(run & edge)(functools.partial(body, True))
        pl.when(run & ~edge)(functools.partial(body, False))


def _dot(a, b, dims):
    """MXU product accumulated in float32, the operands in the dtype they
    come in (bf16 x bf16 is one pass, and exact in float32); where the two
    differ the narrower is widened, which is exact too."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(a.astype(dt), b.astype(dt), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _logits(q, k, refs, q_idx, kv_idx, *, edge, scale, causal, bq, bkv,
            kv_len, skv_pad, has_mask, has_seg, window=None, by_kv=False):
    """Shared fwd/bwd logits tile: QK^T · scale [bq, bkv] (``by_kv``: KQ^T,
    the same tile transposed, which the dk/dv kernel accumulates from
    without transposing anything), with all masks applied on an ``edge``
    block and none on an interior one.  ``refs`` holds the optional (mask,
    q_seg, kv_seg) refs in order, the mask tile in the tile's orientation."""
    s = (_dot(k, q, ((1,), (1,))) if by_kv else _dot(q, k, ((1,), (1,)))) * scale
    if not edge:
        return s
    it = iter(refs)
    if has_mask:
        s = _tile_mask(s, next(it)[0])
    if has_seg:
        s = _seg_mask(s, next(it), next(it), by_kv)
    return _position_masks(s, q_idx, kv_idx, by_kv, causal=causal, bq=bq,
                           bkv=bkv, kv_len=kv_len, skv_pad=skv_pad,
                           window=window)


def _lanes(x, n):
    """[..., rows, _LANES] whose lanes are all alike -> [..., rows, n]."""
    if n > _LANES:
        x = jnp.tile(x, (1,) * (x.ndim - 1) + (pl.cdiv(n, _LANES),))
    return x[..., :n]


def _exp(s, shift, edge):
    """exp(s - shift).  On an ``edge`` block it is exactly 0 for masked
    entries even when the running max / lse is itself NEG_INF (avoids
    exp(-inf + inf) = 1); on an interior block every logit is finite and
    NEG_INF is too, so the plain difference is safe."""
    if not edge:
        return jnp.exp(s - shift)
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

    geom = dict(causal=causal, bq=bq, bkv=bkv, kv_len=kv_len, window=window)
    run = _run_block(q_idx, kv_idx, **geom)
    edge = _edge_block(q_idx, kv_idx, skv_pad=skv_pad,
                       masked=has_mask or has_seg, **geom)

    def _compute(edge):
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bkv, d]
        v = v_ref[0]  # [bkv, d]
        s = _logits(q, k, opt_refs, q_idx, kv_idx, edge=edge, scale=scale,
                    skv_pad=skv_pad, has_mask=has_mask, has_seg=has_seg,
                    **geom)
        # m and l are kept [bq, 128] with all lanes alike: a row's max and
        # sum come off the lane reduction that way, and nothing has to be
        # spread back over the lanes to meet s or the accumulator
        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = _exp(s, _lanes(m_new, s.shape[1]), edge)  # [bq, bkv]
        alpha = _exp(m_prev, m_new, edge)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * _lanes(alpha, v.shape[1]) + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))
        m_scr[:] = m_new

    _when_tile(run, edge, _compute)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / _lanes(l_safe, acc_scr.shape[1])).astype(
            o_ref.dtype)
        lse_ref[0] = (m_scr[:] + jnp.log(l_safe))[:, :1]  # [bq, 1]


def _opt_specs(bq, bkv, mask, mask_idx, segs, batch_of, q_blk, kv_blk,
               head_of=None, by_kv=False):
    """(arrays, in_specs) for the optional streamed inputs, shared by the three
    kernels.  ``q_blk``/``kv_blk``: grid position → (q block, kv block);
    ``head_of``: grid position → q-head row (defaults to grid dim 0; the dkv
    kernel resolves it from its (kv-head, group·q) walk).  ``by_kv``: the
    kernel's tiles are [bkv, bq] (the dkv kernel's): the mask is streamed
    transposed, and q's segment ids lie along the lanes, not kv's."""
    head_of = head_of or (lambda *g: g[0])
    arrays, specs = [], []
    if mask is not None:
        (b0, of0), (b1, of1) = (((bkv, kv_blk), (bq, q_blk)) if by_kv
                                else ((bq, q_blk), (bkv, kv_blk)))
        arrays.append(mask.swapaxes(1, 2) if by_kv else mask)
        specs.append(pl.BlockSpec(
            (1, b0, b1),
            lambda *g: (mask_idx(head_of(*g)), of0(*g), of1(*g))))
    if segs is not None:
        q_seg, kv_seg = segs              # [b, s, 1] columns
        col = lambda blk, of: pl.BlockSpec(
            (1, blk, 1), lambda *g: (batch_of(head_of(*g)), of(*g), 0))
        row = lambda blk, of: pl.BlockSpec(
            (1, 1, blk), lambda *g: (batch_of(head_of(*g)), 0, of(*g)))
        if by_kv:
            arrays += [q_seg.swapaxes(1, 2), kv_seg]
            specs += [row(bq, q_blk), col(bkv, kv_blk)]
        else:
            arrays += [q_seg, kv_seg.swapaxes(1, 2)]
            specs += [col(bq, q_blk), row(bkv, kv_blk)]
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
    n_inner, kv_of, _, _ = _walks(n_q, n_kv, bq_sz, bkv_sz, causal, window)
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
            _VMEM((bq_sz, _LANES), jnp.float32),
            _VMEM((bq_sz, _LANES), jnp.float32),
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
    group (sequential).  Its tiles are [bkv, bq]: ``lse`` and ``delta``
    arrive as rows [1, 1, bq], the optional inputs as ``_opt_specs(by_kv=
    True)`` lays them."""
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

    geom = dict(causal=causal, bq=bq, bkv=bkv, kv_len=kv_len, window=window)
    run = _run_block(q_idx, kv_idx, **geom)
    if window is not None:
        run &= q_idx < n_q
    edge = _edge_block(q_idx, kv_idx, skv_pad=skv_pad,
                       masked=has_mask or has_seg, **geom)

    def _compute(edge):
        q = q_ref[0]                              # [bq, d]
        k = k_ref[0]                              # [bkv, d]
        v = v_ref[0]                              # [bkv, d]
        do = do_ref[0]                            # [bq, d]
        lse = lse_ref[0]                          # [1, bq]
        delta = delta_ref[0]                      # [1, bq]
        # the tile by kv rows, [bkv, bq]: dv and dk then contract over its
        # lanes, and neither p nor ds is transposed on the way to the MXU
        s = _logits(q, k, opt_refs, q_idx, kv_idx, edge=edge, scale=scale,
                    skv_pad=skv_pad, has_mask=has_mask, has_seg=has_seg,
                    by_kv=True, **geom)
        p = _exp(s, lse, edge)                     # exact probs
        dv_scr[:] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
        dp = _dot(v, do, ((1,), (1,)))
        ds = p * (dp - delta) * scale              # [bkv, bq]
        dk_scr[:] += _dot(ds.astype(q.dtype), q, ((1,), (0,)))

    _when_tile(run, edge, _compute)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               scale, causal, bq, bkv, kv_len, skv_pad, has_mask, has_seg,
               window=None):
    """Grid: (bh, num_q_blocks, num_kv_blocks); kv innermost (sequential);
    under ``window`` the band's kv blocks only, as in the forward.  ``lse``
    and ``delta`` arrive as rows [1, 1, bq] and are stood up once a q block
    as columns [bq, 128] with all lanes alike (``lse_scr``, ``delta_scr``)."""
    n_opt = int(has_mask) + 2 * int(has_seg)
    opt_refs = rest[:n_opt]
    dq_ref, dq_scr, lse_scr, delta_scr = rest[n_opt:]
    j = pl.program_id(2)
    q_idx = pl.program_id(1)
    kv_idx = j if window is None else (
        _band_first_kv(q_idx, bq, bkv, window) + j)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # a column with all lanes alike is the transpose of the row spread
        # over 128 sublanes
        lse_scr[:] = jnp.broadcast_to(lse_ref[0], (_LANES, bq)).T
        delta_scr[:] = jnp.broadcast_to(delta_ref[0], (_LANES, bq)).T

    geom = dict(causal=causal, bq=bq, bkv=bkv, kv_len=kv_len, window=window)
    run = _run_block(q_idx, kv_idx, **geom)
    edge = _edge_block(q_idx, kv_idx, skv_pad=skv_pad,
                       masked=has_mask or has_seg, **geom)

    def _compute(edge):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _logits(q, k, opt_refs, q_idx, kv_idx, edge=edge, scale=scale,
                    skv_pad=skv_pad, has_mask=has_mask, has_seg=has_seg,
                    **geom)
        p = _exp(s, _lanes(lse_scr[:], s.shape[1]), edge)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - _lanes(delta_scr[:], s.shape[1])) * scale
        dq_scr[:] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    _when_tile(run, edge, _compute)

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

    # lse and delta go in as rows [bh, 1, sq], 2 KB a q block: as columns
    # [bh, sq, 1] they fill 128-lane tiles in HBM, 256 KB a block, copied
    # with every step of the dk/dv kernel
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, None, :]
    lse = lse[:, None, :]

    kv_inner, kv_of, q_inner, q_of = _walks(n_q, n_kv, bq_sz, bkv_sz, causal,
                                            window)

    # dkv grid → q-row index
    hq_of = lambda bh_kv, t: bh_kv * rep + t // q_inner

    common = dict(scale=scale, causal=causal, bq=bq_sz, bkv=bkv_sz,
                  kv_len=kv_len, skv_pad=skv, window=window,
                  has_mask=mask is not None, has_seg=segs is not None)

    # ---- dk/dv: grid (bh_kv, n_kv, rep·n_q), q innermost over the group ----
    # the optional-input index maps resolve the group-dependent q head first
    q_spec = pl.BlockSpec((1, bq_sz, d),
                          lambda b, kv, t: (hq_of(b, t), q_of(kv, t), 0))
    row_spec = pl.BlockSpec((1, 1, bq_sz),
                            lambda b, kv, t: (hq_of(b, t), 0, q_of(kv, t)))
    kv_spec = pl.BlockSpec((1, bkv_sz, d), lambda b, kv, t: (b, kv, 0))
    opt_arrays, opt_specs = _opt_specs(
        bq_sz, bkv_sz, mask, mask_idx, segs, batch_of,
        q_blk=lambda b, kv, t: q_of(kv, t), kv_blk=lambda b, kv, t: kv,
        head_of=lambda b, kv, t: hq_of(b, t), by_kv=True)

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
    )(q, k, v, do, lse, delta, *opt_arrays)

    # ---- dq: grid (bh, n_q, n_kv), kv innermost ----
    q_spec_i = pl.BlockSpec((1, bq_sz, d), lambda b, i, j: (b, i, 0))
    kv_spec_j = pl.BlockSpec((1, bkv_sz, d),
                             lambda b, i, j: (b // rep, kv_of(i, j), 0))
    row_spec_i = pl.BlockSpec((1, 1, bq_sz), lambda b, i, j: (b, 0, i))
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
        scratch_shapes=[_VMEM((bq_sz, d), jnp.float32),
                        _VMEM((bq_sz, _LANES), jnp.float32),
                        _VMEM((bq_sz, _LANES), jnp.float32)],
        name=("flash_attn_bwd_dq" if window is None
              else "flash_attn_win_bwd_dq"),
        interpret=interpret_mode(),
    )(q, k, v, do, lse, delta, *opt_arrays_q)
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
    global KERNEL_CALLS, FALLBACK_CALLS, LAST_CALL
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
    LAST_CALL = {
        "tiles": tile_census(
            sq_pad, skv_pad, bq_sz, bkv_sz, causal, window, kv_len=skv,
            masked=attn_mask is not None or segment_ids is not None),
        "operands": jnp.promote_types(q.dtype, k.dtype).name}
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
