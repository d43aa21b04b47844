"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464) — the recurrence of a
linear-attention layer, as two Pallas TPU kernels.

A head keeps a state ``M`` of [d_k, d_v] float32 (the transpose of the
paper's ``S``) and, a token, with ``alpha = exp(g)`` in (0, 1], ``beta`` in
[0, 2], a unit key ``k``, a query ``q`` and a value ``v``::

    M <- alpha * M
    M <- M + k (beta * (v - k^T M))^T
    o  = q^T M

``gdn_decode_step`` applies ONE token a (slot, head): it reads ``M``,
writes ``M`` and emits ``o`` — memory-bound, the state goes through the
vector unit once and no product touches the MXU, so the arithmetic is
float32 throughout.

``gdn_chunk_prefill`` applies a block of ``T`` rows a slot in sub-chunks of
``CHUNK`` = 64 rows, carrying the state from one sub-chunk to the next in
VMEM (the chunked, or WY / UT-transform, form): with ``G`` the running sum
of ``g`` inside a sub-chunk and ``A = tril(beta * exp(G_i - G_j) * k_i.k_j,
-1)``, the rows' pseudo-values solve ``(I + A) U = beta * (V - exp(G) K
M0)``, the outputs are ``exp(G) Q M0 + tril(exp(G_i - G_j) q_i.k_j) U`` and
the state leaves as ``exp(G_C) M0 + (exp(G_C - G) K)^T U``.  ``I + A`` is
unit lower triangular, so its inverse is the finite series ``sum (-A)^n =
(I - A)(I + A^2)(I + A^4)...(I + A^32)``: log2(64) - 1 squarings and as many
products, exact in exact arithmetic.  A dead row (``beta`` = 0, ``g`` = 0)
leaves the state as it was; a sub-chunk with no live row is skipped; a
slot whose ``fresh`` flag is set starts from zeros whatever its state held
(NaNs included: a select, not a product).  The state is kept and
accumulated in float32, but the kernel's products run at the matrix unit's
default precision, which on the chip is ONE bfloat16 pass over the float32
operands: 3.5e-3 rms relative on ``o`` against the float32 reference, what
operands rounded to bfloat16 read, where ``Precision.HIGHEST`` on every
product reads 1.3e-5 for +0.8 ms a call at the serving cell's shapes
(PERF.md section 6, PR 32).  The interpreter multiplies exactly, so the
tests on the CPU do not see this.

Both are found in a device trace by the ``name=`` of their ``pallas_call``.
``*_reference`` are the token-by-token compositions in ``jax.numpy`` the
tests hold the kernels to."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

CHUNK = 64


def heads_per_step(n_heads: int, most: int) -> int:
    """The largest divisor of ``n_heads`` that is at most ``most``: the
    heads one grid step of a kernel works through."""
    return max(d for d in range(1, most + 1) if n_heads % d == 0)


# ---------------------------------------------------------------------------
# one token a (slot, head): the decode step
# ---------------------------------------------------------------------------

def _decode_kernel(layer_ref, qT_ref, kT_ref, bkT_ref, aT_ref, bv_ref,
                   m_ref, o_ref, m_out_ref, *, hb):
    for j in range(hb):
        a = aT_ref[0, 0, :, j:j + 1]              # [dk, 1] alpha, 0 = fresh
        m = m_ref[0, 0, j]                        # [dk, dv] f32
        m = jnp.where(a == 0.0, 0.0, a * m)
        kv = jnp.sum(bkT_ref[0, 0, :, j:j + 1] * m, axis=0, keepdims=True)
        u = bv_ref[0, 0, j:j + 1, :] - kv         # [1, dv] beta (v - k^T M)
        m = m + kT_ref[0, 0, :, j:j + 1] * u
        o_ref[0, 0, j:j + 1, :] = jnp.sum(qT_ref[0, 0, :, j:j + 1] * m,
                                          axis=0, keepdims=True)
        m_out_ref[0, 0, j] = m


def _stacked(state, layer):
    """(state as [L, B, H, dk, dv], layer as int32 [1], whether it came
    unstacked)."""
    if state.ndim == 4:
        return state[None], jnp.zeros((1,), jnp.int32), True
    return state, jnp.asarray(layer, jnp.int32).reshape(1), False


def gdn_decode_step(q, k, v, alpha, beta, state, fresh=None, layer=None):
    """One token a slot.  q, k [B, H, dk] (k a unit vector, q scaled), v
    [B, H, dv], alpha, beta [B, H] (a dead lane: alpha 1, beta 0), fresh [B]
    bool (start from zeros).  ``state`` is [B, H, dk, dv] float32, or the
    layers' stack [L, B, H, dk, dv] of which ``layer`` (a traced index) is
    read and written in place, the others untouched.  Returns (o [B, H, dv]
    float32, state)."""
    B, H, dk = q.shape
    state, layer, unstacked = _stacked(state, layer)
    dv = v.shape[-1]
    hb = heads_per_step(H, 10)
    nb = H // hb
    f32 = jnp.float32
    alpha = alpha.astype(f32)
    if fresh is not None:
        alpha = jnp.where(fresh[:, None], 0.0, alpha)

    def cols(x):
        # [B, H, dk] -> [B, nb, dk, hb]: a head's vector down the sublanes
        return x.astype(f32).reshape(B, nb, hb, dk).transpose(0, 1, 3, 2)

    beta = beta.astype(f32)[..., None]
    col = pl.BlockSpec((1, 1, dk, hb), lambda b, h, l: (b, h, 0, 0))
    row = pl.BlockSpec((1, 1, hb, dv), lambda b, h, l: (b, h, 0, 0))
    # the layer comes as data: clamped, so that no value of it leaves the
    # stack
    top = state.shape[0] - 1
    st = pl.BlockSpec((1, 1, hb, dk, dv),
                      lambda b, h, l: (jnp.clip(l[0], 0, top), b, h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nb),
            in_specs=[col, col, col, col, row, st], out_specs=[row, st]),
        out_shape=[jax.ShapeDtypeStruct((B, nb, hb, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 6 counts the scalar-prefetch array
        input_output_aliases={6: 1},
        name="gdn_decode_step",
        interpret=interpret_mode(),
    )(layer, cols(q), cols(k), cols(beta * k.astype(f32)),
      cols(jnp.broadcast_to(alpha[..., None], (B, H, dk))),
      (beta * v.astype(f32)).reshape(B, nb, hb, dv), state)
    return o.reshape(B, H, dv), state[0] if unstacked else state


def gdn_decode_reference(q, k, v, alpha, beta, state, fresh=None):
    """``gdn_decode_step`` in ``jax.numpy``."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    m = state
    if fresh is not None:
        m = jnp.where(fresh[:, None, None, None], 0.0, m)
    m = alpha.astype(f32)[..., None, None] * m
    u = beta.astype(f32)[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, m))
    m = m + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, m), m


# ---------------------------------------------------------------------------
# a block of rows a slot: the chunked form
# ---------------------------------------------------------------------------

def _chunk_kernel(layer_ref, live_ref, fresh_ref, q_ref, k_ref, v_ref,
                  gc_ref, gr_ref, bc_ref, m_ref, o_ref, m_out_ref, m_scr, *,
                  hb, C):
    b, c = pl.program_id(0), pl.program_id(2)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        m_scr[...] = jnp.where(fresh_ref[b] > 0, 0.0, m_ref[0, 0])

    @pl.when(live_ref[b, c] > 0)
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        colm = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        eye = (row == colm).astype(f32)
        dot = functools.partial(jnp.dot, preferred_element_type=f32)
        dot_t = lambda x, y: jax.lax.dot_general(     # x @ y^T
            x, y, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        for j in range(hb):
            q, k, v = q_ref[0, j], k_ref[0, j], v_ref[0, j]
            gc, gr, bc = gc_ref[0, j], gr_ref[0, j, 0], bc_ref[0, j]
            m0 = m_scr[j]
            # exp(G_i - G_j) where i >= j (the exponent is <= 0 there)
            decay = jnp.exp(jnp.where(row >= colm, gc - gr, 0.0))
            x = jnp.where(row > colm, -bc * decay * dot_t(k, k), 0.0)
            # (I + A)^-1 = (I + X)(I + X^2)(I + X^4)...  with X = -A
            inv = eye + x
            n = 2
            while n < C:
                x = dot(x, x)
                inv = inv + dot(inv, x)
                n *= 2
            eg = jnp.exp(gc)                                  # [C, 1]
            u = dot(inv, bc * (v - eg * dot(k, m0)))          # [C, dv]
            qk = jnp.where(row >= colm, decay * dot_t(q, k), 0.0)
            o_ref[0, j] = eg * dot(q, m0) + dot(qk, u)
            g_end = gr[:, C - 1:C]                            # [1, 1]
            m_scr[j] = jnp.exp(g_end) * m0 + jax.lax.dot_general(
                k * jnp.exp(g_end - gc), u, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)

    @pl.when(live_ref[b, c] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        m_out_ref[0, 0] = m_scr[...]


def gdn_chunk_prefill(q, k, v, g, beta, state, valid, fresh=None,
                      layer=None):
    """``T`` rows a slot from a start state.  q, k [B, T, H, dk] (k unit
    vectors, q scaled), v [B, T, H, dv], g (log alpha, <= 0), beta
    [B, T, H], valid [B, T] bool (a dead row leaves the state as it was and
    its output is not to be read), fresh [B] bool; ``state`` and ``layer``
    as in :func:`gdn_decode_step`.  Returns (o [B, T, H, dv] float32,
    state)."""
    B, T, H, dk = q.shape
    state, layer, unstacked = _stacked(state, layer)
    dv = v.shape[-1]
    C = CHUNK
    pad = (-T) % C
    if pad:
        widen = lambda x: jnp.pad(x, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (x.ndim - 2))
        q, k, v, g, beta, valid = map(widen, (q, k, v, g, beta, valid))
    Tp = T + pad
    nc = Tp // C
    hb = heads_per_step(H, 6)
    f32 = jnp.float32
    g = jnp.where(valid[..., None], g.astype(f32), 0.0)
    beta = jnp.where(valid[..., None], beta.astype(f32), 0.0)
    # the running sum of g inside each sub-chunk, head-major, once as a
    # column (down the rows) and once as a row (across them)
    G = jnp.cumsum(g.transpose(0, 2, 1).reshape(B, H, nc, C), axis=-1)
    heads = lambda x: x.astype(f32).transpose(0, 2, 1, 3)   # [B, H, Tp, d]
    live = valid.reshape(B, nc, C).any(axis=-1).astype(jnp.int32)
    fresh = (jnp.zeros((B,), jnp.int32) if fresh is None
             else fresh.astype(jnp.int32))

    rows = lambda d: pl.BlockSpec((1, hb, C, d),
                                  lambda b, h, c, *_: (b, h, c, 0))
    top = state.shape[0] - 1      # the layer comes as data: clamped
    st = pl.BlockSpec((1, 1, hb, dk, dv),
                      lambda b, h, c, l, *_: (jnp.clip(l[0], 0, top), b, h,
                                              0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H // hb, nc),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(1),
                  pl.BlockSpec((1, hb, 1, 1, C),
                               lambda b, h, c, *_: (b, h, c, 0, 0)),
                  rows(1), st],
        out_specs=[rows(dv), st],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), f32)],
    )
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, C=C),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, Tp, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 9 counts the three scalar-prefetch arrays
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gdn_chunk_prefill",
        interpret=interpret_mode(),
    )(layer, live, fresh, heads(q), heads(k), heads(v),
      G.reshape(B, H, Tp, 1), G.reshape(B, H, nc, 1, C),
      beta.transpose(0, 2, 1)[..., None], state)
    return (o.transpose(0, 2, 1, 3)[:, :T],
            state[0] if unstacked else state)


def gdn_chunk_reference(q, k, v, g, beta, state, valid, fresh=None):
    """``gdn_chunk_prefill`` token by token in ``jax.numpy``."""
    f32 = jnp.float32
    m = state
    if fresh is not None:
        m = jnp.where(fresh[:, None, None, None], 0.0, m)
    alpha = jnp.where(valid[..., None], jnp.exp(g.astype(f32)), 1.0)
    beta = jnp.where(valid[..., None], beta.astype(f32), 0.0)

    def one(m, row):
        o, m = gdn_decode_reference(*row, m)
        return m, o

    seq = lambda x: jnp.moveaxis(x, 1, 0)
    m, o = jax.lax.scan(one, m, tuple(map(seq, (q, k, v, alpha, beta))))
    return jnp.moveaxis(o, 0, 1), m
