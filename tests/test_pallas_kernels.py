"""Pallas kernel parity tests (mirrors the reference's fused-op unit tests,
e.g. test/legacy_test/test_flash_attention.py — kernel vs composed-XLA
oracle, forward and backward, causal/non-causal, GQA, multi-block)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import rms_norm as rms
from paddle_tpu.ops.pallas import rope as rope_mod
from paddle_tpu.ops.pallas import swiglu as swiglu_mod


def _rand(rs, *shape):
    return jnp.asarray(rs.randn(*shape), jnp.float32)


@pytest.mark.parametrize("b,s,h,d,causal", [
    (2, 128, 4, 64, True),
    (2, 128, 4, 64, False),
    (1, 256, 2, 32, True),   # multi-block q and kv
    (1, 256, 2, 32, False),
])
def test_flash_forward_parity(b, s, h, d, causal):
    rs = np.random.RandomState(0)
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    out = fa.flash_attention_bshd(q, k, v, causal=causal)
    ref = fa._composed_attention(q, k, v, None, causal, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("b,s,h,d,causal", [
    (2, 128, 4, 64, True),
    (1, 256, 2, 32, True),
    (1, 256, 2, 32, False),
])
def test_flash_backward_parity(b, s, h, d, causal):
    rs = np.random.RandomState(1)
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    scale = 1.0 / np.sqrt(d)

    def f_flash(q, k, v):
        return (fa.flash_attention_bshd(q, k, v, causal=causal) ** 2).sum()

    def f_ref(q, k, v):
        return (fa._composed_attention(q, k, v, None, causal, scale) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g1, g2, "qkv"):
        err = float(jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9))
        assert err < 2e-3, f"d{name} rel err {err}"


@pytest.mark.parametrize("s,window", [
    (384, 64),      # smaller than the 128 block
    (384, 128),     # one block
    (384, 200),     # wider than a block, no multiple of it
    (300, 100),     # a padded last block
    (256, 1000),    # wider than the sequence: plain causal
])
def test_flash_window_forward_and_backward_parity(s, window):
    """The banded calls (a grid over the band's blocks only) against
    composed attention under the same causal window, GQA 4 over 2: the
    forward and all three gradients."""
    rs = np.random.RandomState(0)
    q = _rand(rs, 2, s, 4, 16)
    k, v = _rand(rs, 2, s, 2, 16), _rand(rs, 2, s, 2, 16)
    do = _rand(rs, 2, s, 4, 16)
    scale = 1.0 / np.sqrt(16)
    flash = lambda q, k, v: fa.flash_attention_bshd(q, k, v, causal=True,
                                                    window=window)
    plain = lambda q, k, v: fa._composed_attention(q, k, v, None, True,
                                                   scale, window)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(plain(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * do), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_window_walks_the_band_only_and_is_named():
    """A windowed call's grids cover the band's blocks (s x w work, not
    s^2 / 2) under names of their own; a call without a window keeps its
    names and its full grid; a window sees exactly ``window`` positions."""
    from paddle_tpu.analysis.kernel_contracts import _pallas_eqns

    q = jnp.zeros((1, 1024, 2, 16), jnp.float32)
    loss = lambda w: (lambda q: jnp.sum(fa.flash_attention_bshd(
        q, q, q, causal=True, window=w)))
    grids = lambda w: {e.params["name"]: tuple(e.params["grid_mapping"].grid)
                       for e in _pallas_eqns(jax.make_jaxpr(
                           jax.grad(loss(w)))(q))}
    # 1024 positions in blocks of 512
    assert grids(None) == {"flash_attn_fwd": (2, 2, 2),
                           "flash_attn_bwd_dkv": (2, 2, 2),
                           "flash_attn_bwd_dq": (2, 2, 2)}
    full = grids(None)
    q = jnp.zeros((1, 4096, 2, 16), jnp.float32)
    assert grids(512) == {"flash_attn_win_fwd": (2, 8, 2),
                          "flash_attn_win_bwd_dkv": (2, 8, 2),
                          "flash_attn_win_bwd_dq": (2, 8, 2)}
    assert grids(None)["flash_attn_fwd"] == (2, 8, 8) and full
    # position i's output under window w depends on i-w+1 .. i only
    x = jnp.asarray(np.random.RandomState(1).randn(1, 256, 1, 8), jnp.float32)
    seen = jax.jacobian(lambda v: fa.flash_attention_bshd(
        x, x, v, causal=True, window=3)[0, 200, 0, 0])(x)[0, :, 0, 0]
    assert np.flatnonzero(np.asarray(seen)).tolist() == [198, 199, 200]
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention_bshd(x, x, x, causal=False, window=3)


# bf16 operands, float32 accumulation (the training cells' path): the made
# side of P.V, P^T.dO, dS^T.Q and dS.K is rounded to bf16 once, and so are the
# results.  One rounding is at most half of bf16's step, 2^-9 of the value.
_BF16_HALF_STEP = 2.0 ** -9


@pytest.mark.parametrize("s,hq,hkv,causal,window", [
    (1024, 2, 2, True, None),    # causal, 2 blocks of 512 each way
    (2048, 2, 2, True, None),    # causal, 4 blocks: interior tiles too
    (1024, 4, 1, True, None),    # GQA, 4 query heads a kv head
    (2048, 2, 1, True, 512),     # the band: window 512 in blocks of 512
    (1100, 2, 2, True, None),    # odd length: kv_len makes an edge tile
    (1024, 2, 2, False, None),   # non-causal: every tile interior
], ids=["causal2", "causal4", "gqa4", "window512", "odd1100", "full"])
def test_flash_bf16_operands_parity(s, hq, hkv, causal, window):
    """bf16 inputs through the kernels against composed attention on the same
    values in float32, forward and all three gradients.  The limits are
    worst cases reckoned from bf16's step, not fitted: an output row is a
    convex mix of v's rows, so rounding p (2^-9 of each weight) and the
    output (2^-9 of the value) moves an element by at most 2 x 2^-9 x max|v|;
    a gradient takes one more rounded factor (dS or P) and a rounded result
    on top of the rounded output that delta is made from, four roundings in
    all, held against its norm."""
    d = 32
    rs = np.random.RandomState(4)
    bf = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.bfloat16)
    q, k, v = bf(1, s, hq, d), bf(1, s, hkv, d), bf(1, s, hkv, d)
    do = bf(1, s, hq, d).astype(jnp.float32)
    up = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    flash = lambda q, k, v: fa.flash_attention_bshd(
        q, k, v, causal=causal, window=window)
    plain = lambda q, k, v: fa._composed_attention(
        q, k, v, None, causal, 1.0 / np.sqrt(d), window)
    out, want = flash(q, k, v), plain(*up(q, k, v))
    assert out.dtype == jnp.bfloat16 and fa.LAST_CALL["operands"] == "bfloat16"
    err = np.abs(np.asarray(out.astype(jnp.float32)) - np.asarray(want)).max()
    assert err <= 2 * _BF16_HALF_STEP * float(jnp.max(jnp.abs(
        v.astype(jnp.float32))))
    loss = lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32) * do)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    ref = jax.grad(loss(plain), (0, 1, 2))(*up(q, k, v))
    for g, r, name in zip(got, ref, "qkv"):
        assert g.dtype == jnp.bfloat16
        gap = float(jnp.linalg.norm(g.astype(jnp.float32) - r)
                    / jnp.linalg.norm(r))
        assert gap <= 4 * _BF16_HALF_STEP, f"d{name}: {gap}"


@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 512)],
                         ids=["causal", "full", "band"])
def test_flash_skipped_steps_copy_nothing(causal, window):
    """A grid step that the kernels skip is handed the block index of a step
    that runs beside it (the pipeline copies a block only when its index
    changes), and a step that runs is handed its own block."""
    n, blk = 8, 512
    kv_steps, kv_of, q_steps, q_of = fa._walks(n, n, blk, blk, causal, window)
    geom = dict(causal=causal, bq=blk, bkv=blk, kv_len=n * blk, window=window)
    for i in range(n):
        first = fa._band_first_kv(i, blk, blk, window) if window else 0
        walked = [(first + j, int(kv_of(i, j))) for j in range(kv_steps)]
        for kv, got in walked:
            ran = kv < n and fa._run_block(i, kv, **geom)
            assert got == kv if ran else fa._run_block(i, got, **geom)
    for kv in range(n):
        first = fa._band_first_q(kv, blk, blk) if window else 0
        for t in range(q_steps):
            q, got = first + t, int(q_of(kv, t))
            ran = q < n and fa._run_block(q, kv, **geom)
            assert got == q if ran else fa._run_block(got, kv, **geom)
    assert (kv_steps, q_steps) == ((2, 2) if window else (n, n))


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("inputs", ["segment_pair", "bool_mask",
                                    "additive_mask", "bool_mask+segments"])
def test_flash_streamed_inputs_backward_unlike_sides(inputs, causal):
    """The dk/dv kernel takes its tiles by kv rows, so a mask arrives
    transposed and q's segment ids along the lanes: with 300 q positions
    over 520 kv positions, unlike ids either side and a random mask, no
    symmetry can hide a swapped axis.  All three gradients against composed
    attention, float32."""
    rs = np.random.RandomState(0)
    b, sq, skv, h, hkv, d = 2, 300, 520, 4, 2, 16
    q, do = _rand(rs, b, sq, h, d), _rand(rs, b, sq, h, d)
    k, v = _rand(rs, b, skv, hkv, d), _rand(rs, b, skv, hkv, d)
    q_ids = jnp.asarray(rs.randint(0, 3, (b, sq)), jnp.int32)
    kv_ids = jnp.asarray(rs.randint(0, 3, (b, skv)), jnp.int32)
    same = q_ids[:, None, :, None] == kv_ids[:, None, None, :]
    kept = jnp.asarray(rs.rand(b, 1, sq, skv) > 0.3)
    added = _rand(rs, 1, h, sq, skv)
    kw, ref_mask = {
        "segment_pair": (dict(segment_ids=(q_ids, kv_ids)), same),
        "bool_mask": (dict(attn_mask=kept), kept),
        "additive_mask": (dict(attn_mask=added), added),
        "bool_mask+segments": (dict(attn_mask=kept,
                                    segment_ids=(q_ids, kv_ids)), kept & same),
    }[inputs]
    got = jax.grad(lambda *a: jnp.sum(fa.flash_attention_bshd(
        *a, causal=causal, **kw) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(fa._composed_attention(
        *a, ref_mask, causal, 1.0 / np.sqrt(d)) * do), (0, 1, 2))(q, k, v)
    assert fa.LAST_CALL["tiles"][2] == 0      # every block that runs is edge
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def _eqns_under(jaxpr):
    """Every eqn of a jaxpr and of the jaxprs its eqns hold (cond, ...)."""
    from paddle_tpu.analysis.rules import _sub_jaxprs

    for e in jaxpr.eqns:
        yield e
        for sub in _sub_jaxprs(e):
            yield from _eqns_under(sub)


def _flash_kernel_bodies(dtype, s=1024, **kw):
    """{kernel name: its body's jaxpr} of a forward + backward flash call."""
    from paddle_tpu.analysis.kernel_contracts import _pallas_eqns

    x = jnp.zeros((1, s, 2, 16), dtype)
    loss = lambda q, k, v: jnp.sum(fa.flash_attention_bshd(
        q, k, v, **kw).astype(jnp.float32))
    closed = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x)
    return {e.params["name"]: e.params["jaxpr"] for e in _pallas_eqns(closed)}


@pytest.mark.parametrize("window", [None, 512], ids=["full", "band"])
def test_flash_products_take_the_inputs_dtype(window):
    """The three tile bodies: with bf16 inputs every product has bf16
    operands and accumulates in float32; with float32 inputs nothing is
    rounded to bf16 anywhere (the kernels compute what they always did)."""
    bodies = _flash_kernel_bodies(jnp.bfloat16, causal=True, window=window)
    assert len(bodies) == 3
    for name, body in bodies.items():
        dots = [e for e in _eqns_under(body)
                if e.primitive.name == "dot_general"]
        # an edge and an interior body of 2 (forward), 4 (dk/dv), 3 (dq)
        assert len(dots) == 2 * {"fwd": 2, "dkv": 4, "_dq": 3}[name[-3:]], name
        for e in dots:
            assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2, name
            assert e.params["preferred_element_type"] == jnp.float32, name
            assert e.outvars[0].aval.dtype == jnp.float32
    for name, body in _flash_kernel_bodies(
            jnp.float32, causal=True, window=window).items():
        for e in _eqns_under(body):
            if e.primitive.name == "convert_element_type":
                assert e.params["new_dtype"] != jnp.bfloat16, name
            if e.primitive.name == "dot_general":
                assert [v.aval.dtype for v in e.invars] == [jnp.float32] * 2


def _brute_force_census(sq, skv, bq, bkv, causal, window, kv_len):
    """(skipped, edge, interior) counted over the mask itself: a block with
    no kept logit, with some, with all (padded q rows are not masked)."""
    n_q, n_kv = -(-sq // bq), -(-skv // bkv)
    rows = np.arange(n_q * bq)[:, None]
    cols = np.arange(n_kv * bkv)[None, :]
    keep = np.broadcast_to(cols < kv_len, (n_q * bq, n_kv * bkv)).copy()
    if causal:
        keep &= rows >= cols
    if window is not None:
        keep &= rows - cols < window
    kept = keep.reshape(n_q, bq, n_kv, bkv).sum(axis=(1, 3))
    return (int((kept == 0).sum()), int(((kept > 0) & (kept < bq * bkv)).sum()),
            int((kept == bq * bkv).sum()))


@pytest.mark.parametrize("sq,skv,bq,bkv,causal,window,kv_len,cell", [
    (2048, 2048, 512, 512, True, None, 2048, (6, 4, 6)),       # yicoder-train
    (8192, 8192, 512, 512, True, None, 8192, (120, 16, 120)),  # laguna, full
    (8192, 8192, 512, 512, True, 512, 8192, (225, 31, 0)),     # laguna, band
    (2176, 2176, 128, 128, True, 300, 2049, None),   # band over unlike edges
    (1536, 1536, 512, 512, False, None, 1100, None),  # non-causal, padded kv
    (1024, 2048, 256, 512, False, None, 2048, None),  # cross, all interior
], ids=["yicoder", "laguna-full", "laguna-band", "band-odd", "padded", "cross"])
def test_tile_census_against_the_mask(sq, skv, bq, bkv, causal, window,
                                      kv_len, cell):
    got = fa.tile_census(sq, skv, bq, bkv, causal, window, kv_len)
    assert got == _brute_force_census(sq, skv, bq, bkv, causal, window, kv_len)
    assert cell is None or got == cell
    # a streamed mask or segment ids: every block that runs is an edge block
    skipped, edge, interior = got
    assert fa.tile_census(sq, skv, bq, bkv, causal, window, kv_len,
                          masked=True) == (skipped, edge + interior, 0)


@pytest.mark.parametrize("kw,bodies", [
    (dict(causal=True), [False, True]),            # interior and edge
    (dict(causal=True, window=512), [False, True]),
    (dict(causal=False), [False]),                 # no edge anywhere
    (dict(causal=False, segment_ids=jnp.zeros((1, 1024), jnp.int32)),
     [True]),                                      # masked: edge only
], ids=["causal", "band", "full", "segments"])
def test_flash_interior_tiles_build_no_mask(kw, bodies):
    """Each kernel holds one tile body a kind of block it can meet; the
    interior one has no iota, compare or select in it, the edge one does."""
    for name, body in _flash_kernel_bodies(jnp.bfloat16, **kw).items():
        prims = [{x.primitive.name for x in _eqns_under(e.params["branches"][1].jaxpr)}
                 for e in body.eqns if e.primitive.name == "cond"]
        tiles = [p for p in prims if "dot_general" in p]
        assert sorted("select_n" in p for p in tiles) == bodies, name
        for p in tiles:
            assert ("iota" in p) == ("select_n" in p and "causal" in kw
                                     and kw["causal"]), (name, p)
            if "select_n" not in p:
                assert not p & {"iota", "gt", "ge", "lt", "eq"}, (name, p)
    assert fa.LAST_CALL["tiles"] == fa.tile_census(
        1024, 1024, 512, 512, kw["causal"], kw.get("window"),
        masked="segment_ids" in kw)


def test_flash_gqa_grouped_heads():
    rs = np.random.RandomState(2)
    q = _rand(rs, 2, 128, 8, 32)
    k = _rand(rs, 2, 128, 2, 32)   # 4x grouped
    v = _rand(rs, 2, 128, 2, 32)
    out = fa.flash_attention_bshd(q, k, v, causal=True)
    ref = fa._composed_attention(q, k, v, None, True, 1.0 / np.sqrt(32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_under_jit_and_vmapless_shapes():
    rs = np.random.RandomState(3)
    q = _rand(rs, 1, 128, 2, 64)
    k, v = _rand(rs, 1, 128, 2, 64), _rand(rs, 1, 128, 2, 64)
    jit_out = jax.jit(lambda a, b, c: fa.flash_attention_bshd(a, b, c, causal=True))(q, k, v)
    eager_out = fa.flash_attention_bshd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(jit_out), np.asarray(eager_out),
                               rtol=1e-5, atol=1e-5)


def test_rms_norm_parity_and_grad():
    rs = np.random.RandomState(4)
    x = _rand(rs, 4, 256)
    w = _rand(rs, 256)

    def ref(x, w):
        var = jnp.mean(x.astype(jnp.float32) ** 2, -1, keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6) * w).astype(x.dtype)

    np.testing.assert_allclose(np.asarray(rms.rms_norm(x, w, 1e-6)),
                               np.asarray(ref(x, w)), rtol=1e-4, atol=1e-4)
    g1 = jax.grad(lambda x, w: (rms.rms_norm(x, w, 1e-6) ** 2).sum(),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: (ref(x, w) ** 2).sum(), argnums=(0, 1))(x, w)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-3)


def test_rms_norm_ragged_rows():
    """rows % 256 != 0 must go through the padded block grid, not one giant
    block (VERDICT r2 weak #7: VMEM blowup at [8*2048+1, 4096])."""
    rs = np.random.RandomState(11)
    x = _rand(rs, 257, 128)  # 257 = 256 + 1 ragged row
    w = _rand(rs, 128)

    def ref(x, w):
        var = jnp.mean(x.astype(jnp.float32) ** 2, -1, keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6) * w).astype(x.dtype)

    np.testing.assert_allclose(np.asarray(rms.rms_norm(x, w, 1e-6)),
                               np.asarray(ref(x, w)), rtol=1e-4, atol=1e-4)


def test_swiglu_parity():
    rs = np.random.RandomState(5)
    a, b_ = _rand(rs, 4, 64), _rand(rs, 4, 64)
    np.testing.assert_allclose(
        np.asarray(swiglu_mod.swiglu(a, b_)),
        np.asarray(jax.nn.silu(a) * b_), rtol=1e-5, atol=1e-5)


def test_rope_rotation_preserves_norm():
    rs = np.random.RandomState(6)
    q = _rand(rs, 2, 16, 4, 32)
    k = _rand(rs, 2, 16, 2, 32)
    cos, sin = rope_mod.rope_cos_sin(16, 32)
    q2, k2 = rope_mod.apply_rotary_pos_emb(q, k, cos, sin)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(q2), axis=-1),
                               np.linalg.norm(np.asarray(q), axis=-1),
                               rtol=1e-4)


# ---------------- masked / varlen flash attention (flash_attn_varlen parity) ----


def _mask_oracle(q, k, v, mask, causal, d):
    return fa._composed_attention(q, k, v, mask, causal, 1.0 / np.sqrt(d))


@pytest.mark.parametrize("mshape", [(2, 4, 128, 128), (2, 1, 128, 128),
                                    (1, 1, 128, 128),
                                    # broadcastable seq dims: the canonical
                                    # [b,1,1,skv] key-padding mask and a
                                    # per-query broadcast column
                                    (2, 1, 1, 128), (2, 4, 128, 1)])
def test_flash_dense_bool_mask_parity(mshape):
    rs = np.random.RandomState(7)
    b, s, h, d = 2, 128, 4, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    mask = jnp.asarray(rs.rand(*mshape) > 0.3)
    out = fa.flash_attention_bshd(q, k, v, attn_mask=mask, causal=False)
    ref = _mask_oracle(q, k, v, mask, False, d)
    assert fa.KERNEL_CALLS > 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_additive_mask_parity_and_grad():
    rs = np.random.RandomState(8)
    b, s, h, d = 1, 256, 2, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    mask = jnp.asarray((rs.rand(b, 1, s, s) > 0.5) * -1e9, jnp.float32)

    def f_flash(q, k, v):
        return (fa.flash_attention_bshd(q, k, v, attn_mask=mask, causal=True) ** 2).sum()

    def f_ref(q, k, v):
        return (_mask_oracle(q, k, v, mask, True, d) ** 2).sum()

    np.testing.assert_allclose(
        np.asarray(fa.flash_attention_bshd(q, k, v, attn_mask=mask, causal=True)),
        np.asarray(_mask_oracle(q, k, v, mask, True, d)), rtol=2e-3, atol=2e-3)
    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g1, g2, "qkv"):
        err = float(jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9))
        assert err < 5e-3, f"d{name} rel err {err}"


@pytest.mark.parametrize("s", [129, 200, 2049])
def test_flash_odd_seq_lengths_no_fallback(s):
    """Non-128-multiple sequences run through the kernel (padded+masked), not
    the composed O(s^2) fallback (VERDICT weak #7)."""
    rs = np.random.RandomState(9)
    b, h, d = 1, 2, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    before = fa.FALLBACK_CALLS
    out = fa.flash_attention_bshd(q, k, v, causal=True)
    assert fa.FALLBACK_CALLS == before, "odd seq fell back to composed path"
    ref = fa._composed_attention(q, k, v, None, True, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_odd_seq_backward():
    rs = np.random.RandomState(10)
    b, s, h, d = 1, 200, 2, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    scale = 1.0 / np.sqrt(d)
    g1 = jax.grad(lambda q, k, v: (fa.flash_attention_bshd(q, k, v, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (fa._composed_attention(q, k, v, None, True, scale) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g1, g2, "qkv"):
        err = float(jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9))
        assert err < 5e-3, f"d{name} rel err {err}"


def test_flash_segment_ids_packing():
    """Packed sequences (varlen analog): two documents in one row must not
    attend across the boundary; oracle = bool block-diagonal mask."""
    rs = np.random.RandomState(11)
    b, s, h, d = 2, 128, 2, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    seg = np.zeros((b, s), np.int32)
    seg[:, 70:] = 1  # doc boundary at 70 (odd on purpose)
    out = fa.flash_attention_bshd(q, k, v, causal=True,
                                  segment_ids=jnp.asarray(seg))
    same = jnp.asarray(seg[:, None, :, None] == seg[:, None, None, :])
    ref = _mask_oracle(q, k, v, same, True, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_segment_ids_backward():
    rs = np.random.RandomState(12)
    b, s, h, d = 1, 128, 2, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    seg = np.zeros((b, s), np.int32)
    seg[:, 50:] = 1
    segj = jnp.asarray(seg)
    same = jnp.asarray(seg[:, None, :, None] == seg[:, None, None, :])
    g1 = jax.grad(lambda q, k, v: (fa.flash_attention_bshd(
        q, k, v, causal=True, segment_ids=segj) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (_mask_oracle(q, k, v, same, True, d) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g1, g2, "qkv"):
        err = float(jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9))
        assert err < 5e-3, f"d{name} rel err {err}"


def test_flash_gqa_backward_no_repeat():
    """GQA backward: dk/dv accumulate over the head group inside the kernel."""
    rs = np.random.RandomState(13)
    q = _rand(rs, 2, 128, 8, 32)
    k = _rand(rs, 2, 128, 2, 32)
    v = _rand(rs, 2, 128, 2, 32)
    scale = 1.0 / np.sqrt(32)
    g1 = jax.grad(lambda q, k, v: (fa.flash_attention_bshd(q, k, v, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (fa._composed_attention(q, k, v, None, True, scale) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g1, g2, "qkv"):
        err = float(jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9))
        assert err < 5e-3, f"d{name} rel err {err}"


def test_flash_padding_mask_2049():
    """Padding mask at seq 2048+1 (VERDICT item #5's named acceptance case)."""
    rs = np.random.RandomState(14)
    b, s, h, d = 1, 2049, 1, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    valid = np.ones((b, s), bool)
    valid[:, -100:] = False  # tail padding
    seg = np.where(valid, 0, np.arange(s)[None] + 1).astype(np.int32)
    out = fa.flash_attention_bshd(q, k, v, causal=True,
                                  segment_ids=jnp.asarray(seg))
    same = jnp.asarray(seg[:, None, :, None] == seg[:, None, None, :])
    ref = _mask_oracle(q, k, v, same, True, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_composed_fallback_3d_mask_per_batch():
    """3D [b, sq, skv] masks mean per-batch on BOTH paths (kernel and the
    d%8!=0 composed fallback) — not numpy right-aligned broadcast."""
    rs = np.random.RandomState(15)
    b, s, h, d = 2, 16, 2, 12  # d%8!=0 -> composed fallback
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    mask3 = jnp.asarray(rs.rand(b, s, s) > 0.3)
    out = fa.flash_attention_bshd(q, k, v, attn_mask=mask3, causal=False)
    ref = fa._composed_attention(q, k, v, mask3[:, None], False, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_additive_mask_gradient_flows():
    """Learned additive bias (ALiBi-style): grad w.r.t. the mask itself must
    match the composed oracle, not silently be zero."""
    rs = np.random.RandomState(16)
    b, s, h, d = 1, 128, 2, 32
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    bias = jnp.asarray(rs.randn(1, 1, s, s).astype(np.float32) * 0.1)

    g1 = jax.grad(lambda m: (fa.flash_attention_bshd(q, k, v, attn_mask=m,
                                                     causal=True) ** 2).sum())(bias)
    g2 = jax.grad(lambda m: (_mask_oracle(q, k, v, m, True, d) ** 2).sum())(bias)
    assert float(jnp.max(jnp.abs(g2))) > 1e-6  # oracle grad is nonzero
    err = float(jnp.max(jnp.abs(g1 - g2)) / (jnp.max(jnp.abs(g2)) + 1e-9))
    assert err < 5e-3, f"dmask rel err {err}"


# ---------------- ragged paged-attention decode kernel ----------------
# (kernel vs the gather oracle — the path the paged CB engine serves through;
# ISSUE acceptance: max abs err <= 1e-2 across ragged seq_lens / GQA / quant)


def _paged_case(rs, b, nh, nkv, hd, bs, max_blocks, lens, num_blocks=None,
                dtype=jnp.float32):
    num_blocks = num_blocks or b * max_blocks + 3
    kc = jnp.asarray(rs.randn(num_blocks, nkv, bs, hd), dtype)
    vc = jnp.asarray(rs.randn(num_blocks, nkv, bs, hd), dtype)
    q = jnp.asarray(rs.randn(b, nh, hd), dtype)
    # distinct physical pages per slot (the allocator invariant), shuffled so
    # a block-table indirection bug cannot hide behind identity layout
    tables = jnp.asarray(
        rs.permutation(num_blocks)[:b * max_blocks].reshape(b, max_blocks),
        jnp.int32)
    return q, kc, vc, tables, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("nh,nkv", [(4, 4), (8, 2), (20, 4), (6, 1)])
def test_paged_attention_gqa_parity(nh, nkv):
    """Kernel vs gather oracle across GQA head ratios (incl. the 3B bench
    config's 20q/4kv and MQA) on ragged seq_lens."""
    rs = np.random.RandomState(20)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=4, nh=nh, nkv=nkv, hd=32, bs=16, max_blocks=4,
        lens=[1, 17, 40, 64])
    before = pa.KERNEL_CALLS
    out = pa.paged_attention_decode(q, kc, vc, tables, lens)
    assert pa.KERNEL_CALLS > before, "kernel path not taken"
    ref = pa.paged_attention_reference(q, kc, vc, tables, lens)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("lens", [[1, 1, 1], [128, 5, 77], [3, 128, 64],
                                  [0, 9, 128]])
def test_paged_attention_ragged_lens(lens):
    """Skewed per-slot lengths — the regime the ragged kernel exists for
    (incl. a zero-length slot, which must return zeros, not NaN)."""
    rs = np.random.RandomState(21)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=3, nh=8, nkv=2, hd=64, bs=16, max_blocks=8, lens=lens)
    out = pa.paged_attention_decode(q, kc, vc, tables, lens)
    ref = pa.paged_attention_reference(q, kc, vc, tables, lens)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_paged_attention_quantized_kv(mode):
    """Dequant-on-read parity: the kernel over int8 / packed-int4 pages with
    per-(page, head) scales matches the dequantize-then-gather oracle."""
    rs = np.random.RandomState(22)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=3, nh=8, nkv=4, hd=32, bs=16, max_blocks=4, lens=[5, 37, 64])
    qk, ks = pa.quantize_kv_cache(kc, mode)
    qv, vs = pa.quantize_kv_cache(vc, mode)
    if mode == "int4":
        assert qk.shape[-1] == kc.shape[-1] // 2  # two nibbles per byte
    out = pa.paged_attention_decode(q, qk, qv, tables, lens, kv_quant=mode,
                                    k_scale=ks, v_scale=vs)
    ref = pa.paged_attention_reference(q, qk, qv, tables, lens, kv_quant=mode,
                                       k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    # and the quantized result tracks the fp attention within quant noise
    fp = pa.paged_attention_reference(q, kc, vc, tables, lens)
    tol = 0.05 if mode == "int8" else 0.35
    assert float(jnp.max(jnp.abs(out - fp))) < tol


def test_paged_attention_quant_roundtrip():
    rs = np.random.RandomState(23)
    kc = jnp.asarray(rs.randn(6, 2, 16, 32), jnp.float32)
    for mode, tol in (("int8", 0.03), ("int4", 0.5)):
        qk, s = pa.quantize_kv_cache(kc, mode)
        back = pa.dequantize_kv_cache(qk, s, mode)
        assert float(jnp.max(jnp.abs(back - kc))) < tol


def test_paged_attention_sentinel_pages_never_read():
    """Table entries past the live page count may be arbitrary sentinels
    (the CB engine uses num_blocks): clobbering them must not change the
    output."""
    rs = np.random.RandomState(24)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=2, nh=4, nkv=2, hd=32, bs=16, max_blocks=4, lens=[20, 33])
    out = pa.paged_attention_decode(q, kc, vc, tables, lens)
    poisoned = np.asarray(tables).copy()
    poisoned[0, 2:] = 999999   # slot 0 has 2 live pages
    poisoned[1, 3:] = -7       # slot 1 has 3
    out2 = pa.paged_attention_decode(q, kc, vc, jnp.asarray(poisoned), lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_paged_attention_disable_env_routes_to_oracle(monkeypatch):
    rs = np.random.RandomState(25)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=2, nh=4, nkv=2, hd=32, bs=16, max_blocks=2, lens=[5, 30])
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "paged_attention")
    before = pa.FALLBACK_CALLS
    out = pa.paged_attention_decode(q, kc, vc, tables, lens)
    assert pa.FALLBACK_CALLS > before
    ref = pa.paged_attention_reference(q, kc, vc, tables, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_attention_under_jit_and_bf16():
    rs = np.random.RandomState(26)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=2, nh=8, nkv=2, hd=64, bs=8, max_blocks=4, lens=[9, 25],
        dtype=jnp.bfloat16)
    out = jax.jit(pa.paged_attention_decode)(q, kc, vc, tables, lens)
    assert out.dtype == jnp.bfloat16
    ref = pa.paged_attention_reference(q, kc, vc, tables, lens)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) <= 1e-2


def test_paged_attention_grad_matches_reference():
    """The kernel path is decode-only but must still compose with grad (the
    eager tape wraps ops in jax.vjp): the custom_vjp recomputes through the
    gather reference, so d{q,kc,vc} must match differentiating the oracle."""
    rs = np.random.RandomState(28)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=2, nh=8, nkv=2, hd=32, bs=16, max_blocks=2, lens=[9, 30])
    f_k = lambda q_, kc_, vc_: (pa.paged_attention_decode(
        q_, kc_, vc_, tables, lens) ** 2).sum()
    f_r = lambda q_, kc_, vc_: (pa.paged_attention_reference(
        q_, kc_, vc_, tables, lens) ** 2).sum()
    g1 = jax.grad(f_k, argnums=(0, 1, 2))(q, kc, vc)
    g2 = jax.grad(f_r, argnums=(0, 1, 2))(q, kc, vc)
    for a, b_, name in zip(g1, g2, ("q", "kc", "vc")):
        err = float(jnp.max(jnp.abs(a - b_)) / (jnp.max(jnp.abs(b_)) + 1e-9))
        assert err < 2e-3, f"d{name} rel err {err}"
    # quantized storage: grads flow to q (caches are not differentiable)
    qk, ks = pa.quantize_kv_cache(kc, "int8")
    qv, vs = pa.quantize_kv_cache(vc, "int8")
    gq = jax.grad(lambda q_: pa.paged_attention_decode(
        q_, qk, qv, tables, lens, kv_quant="int8", k_scale=ks,
        v_scale=vs).sum())(q)
    assert bool(jnp.all(jnp.isfinite(gq))) and float(jnp.abs(gq).max()) > 0


def test_paged_attention_unsupported_shape_falls_back():
    """bs % 8 != 0 (the incubate op's small-page callers) must take the
    gather oracle, not crash in Mosaic."""
    rs = np.random.RandomState(27)
    q, kc, vc, tables, lens = _paged_case(
        rs, b=2, nh=4, nkv=2, hd=32, bs=4, max_blocks=2, lens=[3, 7])
    before = pa.FALLBACK_CALLS
    out = pa.paged_attention_decode(q, kc, vc, tables, lens)
    assert pa.FALLBACK_CALLS > before
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------- ragged multi-token verify kernel (speculative) ----------
# (row t of a verify call must equal a plain decode call whose cache stops at
# that row's position — the independent oracle that pins the per-row causal
# mask, docs/speculative.md)


def _verify_case(rs, b, nh, nkv, hd, bs, max_blocks, lens, qmax, qlens,
                 dtype=jnp.float32):
    q, kc, vc, tables, lens = _paged_case(
        rs, b=b, nh=nh, nkv=nkv, hd=hd, bs=bs, max_blocks=max_blocks,
        lens=lens, dtype=dtype)
    qm = jnp.asarray(rs.randn(b, qmax, nh, hd), dtype)
    return qm, kc, vc, tables, lens, jnp.asarray(qlens, jnp.int32)


@pytest.mark.parametrize("nh,nkv", [(4, 4), (8, 2), (20, 4), (6, 1)])
def test_paged_verify_gqa_parity(nh, nkv):
    """Verify kernel vs its gather oracle across GQA ratios with ragged
    per-slot query counts."""
    rs = np.random.RandomState(40)
    q, kc, vc, tables, lens, qlens = _verify_case(
        rs, b=4, nh=nh, nkv=nkv, hd=32, bs=16, max_blocks=4,
        lens=[5, 17, 40, 64], qmax=4, qlens=[1, 2, 4, 3])
    before = pa.VERIFY_KERNEL_CALLS
    out = pa.paged_attention_verify(q, kc, vc, tables, lens, qlens)
    assert pa.VERIFY_KERNEL_CALLS > before, "verify kernel path not taken"
    ref = pa.paged_verify_reference(q, kc, vc, tables, lens, qlens)
    # compare live rows only (padding rows are unspecified by contract)
    for b_ in range(4):
        ql = int(qlens[b_])
        np.testing.assert_allclose(np.asarray(out)[b_, :ql],
                                   np.asarray(ref)[b_, :ql],
                                   rtol=2e-3, atol=2e-3)


def test_paged_verify_rows_match_single_token_decode():
    """The defining property: row t of verify(seq_lens=L, q_lens=ql) IS the
    single-token decode of query t over the first L-(ql-1-t) cache positions
    (token t sees itself and everything before, never the later drafts)."""
    rs = np.random.RandomState(41)
    b, qmax = 3, 3
    q, kc, vc, tables, lens, qlens = _verify_case(
        rs, b=b, nh=8, nkv=2, hd=32, bs=16, max_blocks=4,
        lens=[9, 30, 50], qmax=qmax, qlens=[3, 1, 2])
    out = pa.paged_attention_verify(q, kc, vc, tables, lens, qlens)
    for b_ in range(b):
        ql = int(qlens[b_])
        for t in range(ql):
            row_len = int(lens[b_]) - (ql - 1 - t)
            one = pa.paged_attention_decode(
                q[b_:b_ + 1, t], kc, vc, tables[b_:b_ + 1],
                jnp.asarray([row_len], jnp.int32))
            np.testing.assert_allclose(np.asarray(out)[b_, t],
                                       np.asarray(one)[0],
                                       rtol=2e-3, atol=2e-3)


def test_paged_verify_qlen1_matches_decode():
    """q_lens all 1 degenerates to plain decode: the verify family must not
    drift from the single-token kernel it generalizes."""
    rs = np.random.RandomState(42)
    q, kc, vc, tables, lens, qlens = _verify_case(
        rs, b=3, nh=8, nkv=2, hd=64, bs=16, max_blocks=4,
        lens=[7, 33, 64], qmax=1, qlens=[1, 1, 1])
    out = pa.paged_attention_verify(q, kc, vc, tables, lens, qlens)
    one = pa.paged_attention_decode(q[:, 0], kc, vc, tables, lens)
    np.testing.assert_allclose(np.asarray(out)[:, 0], np.asarray(one),
                               rtol=2e-3, atol=2e-3)


def test_paged_verify_disable_env_routes_to_oracle(monkeypatch):
    rs = np.random.RandomState(43)
    q, kc, vc, tables, lens, qlens = _verify_case(
        rs, b=2, nh=4, nkv=2, hd=32, bs=16, max_blocks=2,
        lens=[5, 30], qmax=3, qlens=[3, 2])
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "paged_attention")
    before = pa.VERIFY_FALLBACK_CALLS
    out = pa.paged_attention_verify(q, kc, vc, tables, lens, qlens)
    assert pa.VERIFY_FALLBACK_CALLS > before
    ref = pa.paged_verify_reference(q, kc, vc, tables, lens, qlens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_verify_under_jit_and_bf16():
    rs = np.random.RandomState(44)
    q, kc, vc, tables, lens, qlens = _verify_case(
        rs, b=2, nh=8, nkv=2, hd=64, bs=8, max_blocks=4, lens=[9, 25],
        qmax=4, qlens=[4, 2], dtype=jnp.bfloat16)
    out = jax.jit(pa.paged_attention_verify)(q, kc, vc, tables, lens, qlens)
    assert out.dtype == jnp.bfloat16
    ref = pa.paged_verify_reference(q, kc, vc, tables, lens, qlens)
    for b_ in range(2):
        ql = int(qlens[b_])
        assert float(jnp.max(jnp.abs(
            out[b_, :ql].astype(jnp.float32)
            - ref[b_, :ql].astype(jnp.float32)))) <= 1e-2


def test_flash_fallback_respects_segment_ids():
    """d%8!=0 routes to the composed fallback, which must still honor
    segment_ids (no cross-document attention)."""
    rs = np.random.RandomState(17)
    b, s, h, d = 1, 32, 2, 12  # d%8 != 0 -> fallback
    q, k, v = (_rand(rs, b, s, h, d) for _ in range(3))
    seg = np.zeros((b, s), np.int32)
    seg[:, 16:] = 1
    out = fa.flash_attention_bshd(q, k, v, causal=True,
                                  segment_ids=jnp.asarray(seg))
    same = jnp.asarray(seg[:, None, :, None] == seg[:, None, None, :])
    ref = _mask_oracle(q, k, v, same, True, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


# ---------------- ragged chunked-prefill kernel (mixed step) ----------
# (the verify kernel's per-row causal law with T free — verify is the
# T=K+1 special case — plus the decode kernel's dequant-on-read;
# docs/chunked_prefill.md)


def _prefill_case(rs, b, nh, nkv, hd, bs, max_blocks, lens, qmax, qlens,
                  dtype=jnp.float32):
    q, kc, vc, tables, lens = _paged_case(
        rs, b=b, nh=nh, nkv=nkv, hd=hd, bs=bs, max_blocks=max_blocks,
        lens=lens, dtype=dtype)
    qm = jnp.asarray(rs.randn(b, qmax, nh, hd), dtype)
    return qm, kc, vc, tables, lens, jnp.asarray(qlens, jnp.int32)


@pytest.mark.parametrize("nh,nkv", [(4, 4), (8, 2), (20, 4), (6, 1)])
def test_paged_prefill_gqa_parity(nh, nkv):
    """Prefill kernel vs its gather oracle across GQA ratios with ragged
    per-slot chunk widths (incl. a decode-style q_len==1 lane riding the
    same launch — the mixed step's defining shape)."""
    rs = np.random.RandomState(50)
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=4, nh=nh, nkv=nkv, hd=32, bs=16, max_blocks=4,
        lens=[6, 17, 40, 64], qmax=6, qlens=[6, 1, 4, 3])
    before = pa.PREFILL_KERNEL_CALLS
    out = pa.paged_attention_prefill(q, kc, vc, tables, lens, qlens)
    assert pa.PREFILL_KERNEL_CALLS > before, "prefill kernel path not taken"
    ref = pa.paged_prefill_reference(q, kc, vc, tables, lens, qlens)
    # compare live rows only (padding rows are unspecified by contract)
    for b_ in range(4):
        ql = int(qlens[b_])
        np.testing.assert_allclose(np.asarray(out)[b_, :ql],
                                   np.asarray(ref)[b_, :ql],
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("lens,qlens", [([3, 33, 64], [3, 5, 2]),
                                        ([1, 16, 17], [1, 8, 8])])
def test_paged_prefill_ragged_tails(lens, qlens):
    """Chunk windows ending mid-page / exactly at a page boundary / in the
    first page — every phase of the ragged tail the page walk elides."""
    rs = np.random.RandomState(51)
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=3, nh=8, nkv=2, hd=64, bs=16, max_blocks=4, lens=lens,
        qmax=8, qlens=qlens)
    out = pa.paged_attention_prefill(q, kc, vc, tables, lens, qlens)
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = pa.paged_prefill_reference(q, kc, vc, tables, lens, qlens)
    for b_ in range(3):
        ql = int(qlens[b_])
        np.testing.assert_allclose(np.asarray(out)[b_, :ql],
                                   np.asarray(ref)[b_, :ql],
                                   rtol=2e-3, atol=2e-3)


def test_paged_prefill_is_verify_generalized():
    """The T = K+1 special case: on verify-sized chunks the prefill oracle
    IS the verify oracle, and the prefill kernel matches the verify kernel
    row for row — the two family members may never drift."""
    rs = np.random.RandomState(52)
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=3, nh=8, nkv=2, hd=32, bs=16, max_blocks=4,
        lens=[9, 30, 50], qmax=4, qlens=[4, 1, 3])
    ref_p = pa.paged_prefill_reference(q, kc, vc, tables, lens, qlens)
    ref_v = pa.paged_verify_reference(q, kc, vc, tables, lens, qlens)
    np.testing.assert_array_equal(np.asarray(ref_p), np.asarray(ref_v))
    out_p = pa.paged_attention_prefill(q, kc, vc, tables, lens, qlens)
    out_v = pa.paged_attention_verify(q, kc, vc, tables, lens, qlens)
    for b_ in range(3):
        ql = int(qlens[b_])
        np.testing.assert_allclose(np.asarray(out_p)[b_, :ql],
                                   np.asarray(out_v)[b_, :ql],
                                   rtol=1e-5, atol=1e-5)


def test_paged_prefill_rows_match_single_token_decode():
    """The defining property: row t of a prefill chunk IS the single-token
    decode of that query over the first lens-(qlens-1-t) cache positions
    (the written prefix plus the chunk through itself)."""
    rs = np.random.RandomState(53)
    b, qmax = 2, 5
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=b, nh=8, nkv=2, hd=32, bs=16, max_blocks=4,
        lens=[21, 40], qmax=qmax, qlens=[5, 3])
    out = pa.paged_attention_prefill(q, kc, vc, tables, lens, qlens)
    for b_ in range(b):
        ql = int(qlens[b_])
        for t in range(ql):
            row_len = int(lens[b_]) - (ql - 1 - t)
            one = pa.paged_attention_decode(
                q[b_:b_ + 1, t], kc, vc, tables[b_:b_ + 1],
                jnp.asarray([row_len], jnp.int32))
            np.testing.assert_allclose(np.asarray(out)[b_, t],
                                       np.asarray(one)[0],
                                       rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_paged_prefill_quantized_kv(mode):
    """Dequant-on-read parity over int8 / packed-int4 pages — the decode
    kernel's quant support the verify member never had, so a KV-quantized
    pool can prefill through the same kernel family that decodes it."""
    rs = np.random.RandomState(54)
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=3, nh=8, nkv=4, hd=32, bs=16, max_blocks=4,
        lens=[7, 37, 64], qmax=4, qlens=[4, 2, 3])
    qk, ks = pa.quantize_kv_cache(kc, mode)
    qv, vs = pa.quantize_kv_cache(vc, mode)
    out = pa.paged_attention_prefill(q, qk, qv, tables, lens, qlens,
                                     kv_quant=mode, k_scale=ks, v_scale=vs)
    ref = pa.paged_prefill_reference(q, qk, qv, tables, lens, qlens,
                                     kv_quant=mode, k_scale=ks, v_scale=vs)
    for b_ in range(3):
        ql = int(qlens[b_])
        np.testing.assert_allclose(np.asarray(out)[b_, :ql],
                                   np.asarray(ref)[b_, :ql],
                                   rtol=2e-3, atol=2e-3)
    # and the quantized result tracks the fp attention within quant noise
    # (int4 bound matches the roundtrip test's: ~0.5 absmax at 4 bits)
    fp = pa.paged_prefill_reference(q, kc, vc, tables, lens, qlens)
    tol = 0.05 if mode == "int8" else 0.5
    for b_ in range(3):
        ql = int(qlens[b_])
        assert float(jnp.max(jnp.abs(np.asarray(out)[b_, :ql]
                                     - np.asarray(fp)[b_, :ql]))) < tol


# launches that hold every lane class at once (ISSUE 33): q_lens 0 over
# lens > 0, a decode lane's one row, one row short of and one past a
# sub-tile edge, a full T; lens ending in the first page, on a page edge
# and mid-page.  (name, nh, nkv, T, dtype, kv_quant): at rep 4 in float32
# the first sub-tile is 8 rows = 2 tokens and the aligned ones 128 rows =
# 32 tokens; at rep 1 in bfloat16 16 rows = 16 tokens and 128 = 128
_LANE_CLASS_CASES = [
    ("rep4_nkv8", 32, 8, 40, jnp.float32, None),
    ("rep4_nkv2", 8, 2, 40, jnp.float32, None),
    ("rep1_nkv30", 30, 30, 136, jnp.bfloat16, None),
    ("rep4_int8", 8, 2, 40, jnp.float32, "int8"),
    ("rep4_int4", 8, 2, 40, jnp.float32, "int4"),
    ("verify_T5", 8, 2, 5, jnp.float32, "int8"),
    # jamba2-reasoning's grouping (PR 35): one KV head under 20 query heads,
    # rep 20 not a multiple of the sublanes — a decode lane's short tile is
    # 32 rows, a sub-tile 128 rows = 6.4 tokens, so a token's heads straddle
    # sub-tiles
    ("rep20_nkv1", 20, 1, 128, jnp.bfloat16, None),
]


def _lane_class_case(nh, nkv, T, dtype, kv_quant):
    rep = nh // nkv
    R, head_rows, sub_rows = pa._prefill_tiles(T, rep, dtype)
    edge = sub_rows // rep                     # tokens of one sub-tile
    few = head_rows // rep                     # tokens of the first one
    bs, max_blocks = 16, 12
    # (q_len, len): lens in the first page, on a page edge, mid-page
    lanes = [(0, 37), (1, 1), (1, 48), (few, few), (few + 1, 90),
             (min(edge - 1, T), 64 + min(edge - 1, T)),
             (min(edge + 1, T), 150), (T, T), (T, 176), (0, 0)]
    qlens = [q for q, _ in lanes]
    lens = [n for _, n in lanes]
    rs = np.random.RandomState(60)
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=len(lanes), nh=nh, nkv=nkv, hd=32, bs=bs,
        max_blocks=max_blocks, lens=lens, qmax=T, qlens=qlens, dtype=dtype)
    kw = {}
    if kv_quant:
        kc, ks = pa.quantize_kv_cache(kc, kv_quant)
        vc, vs = pa.quantize_kv_cache(vc, kv_quant)
        kw = dict(kv_quant=kv_quant, k_scale=ks, v_scale=vs)
    return (q, kc, vc, tables, lens, qlens), kw, (R, head_rows, sub_rows)


@pytest.mark.parametrize("name,nh,nkv,T,dtype,kv_quant", _LANE_CLASS_CASES,
                         ids=[c[0] for c in _LANE_CLASS_CASES])
def test_paged_prefill_lane_classes(name, nh, nkv, T, dtype, kv_quant):
    """The kernel's work follows what a lane carries and its numbers do
    not: every lane class in one launch against the gather oracle, live
    rows to tolerance and every row past ``q_lens`` exactly zero (a lane
    with ``q_lens == 0`` works no page, whatever ``lens`` says)."""
    args, kw, _ = _lane_class_case(nh, nkv, T, dtype, kv_quant)
    qlens = np.asarray(args[5])
    before = pa.PREFILL_KERNEL_CALLS
    out = np.asarray(pa.paged_attention_prefill(*args, **kw), np.float32)
    assert pa.PREFILL_KERNEL_CALLS > before, "prefill kernel path not taken"
    ref = np.asarray(pa.paged_prefill_reference(*args, **kw), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    for b_, ql in enumerate(qlens):
        np.testing.assert_allclose(out[b_, :ql], ref[b_, :ql], rtol=tol,
                                   atol=tol)
        assert not out[b_, ql:].any(), f"lane {b_}: rows past q_lens"


def _brute_census(q_lens, seq_lens, rep, bs, max_blocks, tiles):
    """Row by row, page by page: what the kernel's predicates let through."""
    R, head_rows, sub_rows = tiles
    live = computed = 0
    for ql, n in zip(q_lens, seq_lens):
        sees = lambda t: n - (ql - 1 - t)
        for t in range(ql):
            live += sum(1 for j in range(max_blocks) if j * bs < sees(t))
        if ql <= 0:
            continue
        if ql * rep <= head_rows:
            subs = [(0, head_rows)]
        else:
            subs = [(i * sub_rows, sub_rows) for i in range(R // sub_rows)
                    if i * sub_rows < ql * rep]
        for r0, rows in subs:
            t_last = min((r0 + rows - 1) // rep, ql - 1)
            computed += -(-rows // rep) * sum(
                1 for j in range(max_blocks) if j * bs < sees(t_last))
    return live, computed


@pytest.mark.parametrize("name,nh,nkv,T,dtype,kv_quant", _LANE_CLASS_CASES,
                         ids=[c[0] for c in _LANE_CLASS_CASES])
def test_prefill_census_matches_brute_force(name, nh, nkv, T, dtype,
                                            kv_quant):
    """``prefill_census`` (closed forms over numpy vectors, what the
    engine adds to its counters a step) against a row-by-row count on the
    lane-class launches; the live share can never pass 1."""
    args, _, tiles = _lane_class_case(nh, nkv, T, dtype, kv_quant)
    lens, qlens = np.asarray(args[4]), np.asarray(args[5])
    bs, max_blocks = args[1].shape[2], args[3].shape[1]
    got = pa.prefill_census(qlens, lens, T, nh // nkv, bs,
                            max_blocks=max_blocks, nkv=nkv, hd=32,
                            dtype=dtype, kv_quant=kv_quant)
    live, computed = _brute_census(qlens, lens, nh // nkv, bs, max_blocks,
                                   tiles)
    assert (got["row_pages_live"], got["row_pages_computed"]) == (
        live, computed)
    assert 0 < live <= computed
    assert got["grid_steps"] == len(qlens) * max_blocks   # all heads a step
    # a lane the engine marks dead still costs what the kernel works
    dead = pa.prefill_census(qlens, lens, T, nh // nkv, bs,
                             max_blocks=max_blocks, nkv=nkv, hd=32,
                             dtype=dtype, kv_quant=kv_quant,
                             live=np.arange(len(qlens)) % 2 == 0)
    assert dead["row_pages_computed"] == computed
    assert dead["row_pages_live"] < live


def test_paged_prefill_disable_env_routes_to_oracle(monkeypatch):
    rs = np.random.RandomState(55)
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=2, nh=4, nkv=2, hd=32, bs=16, max_blocks=2,
        lens=[5, 30], qmax=3, qlens=[3, 2])
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "paged_attention")
    before = pa.PREFILL_FALLBACK_CALLS
    out = pa.paged_attention_prefill(q, kc, vc, tables, lens, qlens)
    assert pa.PREFILL_FALLBACK_CALLS > before
    ref = pa.paged_prefill_reference(q, kc, vc, tables, lens, qlens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_prefill_under_jit_and_bf16():
    rs = np.random.RandomState(56)
    q, kc, vc, tables, lens, qlens = _prefill_case(
        rs, b=2, nh=8, nkv=2, hd=64, bs=8, max_blocks=4, lens=[9, 25],
        qmax=6, qlens=[6, 2], dtype=jnp.bfloat16)
    out = jax.jit(pa.paged_attention_prefill)(q, kc, vc, tables, lens, qlens)
    assert out.dtype == jnp.bfloat16
    ref = pa.paged_prefill_reference(q, kc, vc, tables, lens, qlens)
    for b_ in range(2):
        ql = int(qlens[b_])
        assert float(jnp.max(jnp.abs(
            out[b_, :ql].astype(jnp.float32)
            - ref[b_, :ql].astype(jnp.float32)))) <= 1e-2


def test_fused_decode_at_one_kv_head_and_rep_20():
    """``jamba2-reasoning``'s grouping on the decode path (PR 35): 20 query
    heads over ONE KV head, so the q group pads 20 rows to 24; identity
    rope tables as a model without a rotary embedding passes them; lanes
    mid-page, on a page edge, in the first page and dropped (spill)."""
    rs = np.random.RandomState(63)
    nh, hd, bs, mb, nbl = 20, 32, 8, 6, 14
    lens = [5, 16, 31, None]
    B = len(lens)
    kc = jnp.asarray(rs.randn(nbl + 1, 1, bs, hd), jnp.bfloat16)
    vc = jnp.asarray(rs.randn(nbl + 1, 1, bs, hd), jnp.bfloat16)
    tables = np.full((B, mb), nbl, np.int32)
    pool = list(rs.permutation(nbl))
    wblk, wable, lens_i = [], [], []
    for b, ln in enumerate(lens):
        if ln is None:
            wblk.append(nbl), wable.append(0), lens_i.append(0)
            continue
        pages = [pool.pop() for _ in range(ln // bs + 1)]
        tables[b, :len(pages)] = pages
        wblk.append(pages[ln // bs]), wable.append(1), lens_i.append(ln)
    case = (jnp.asarray(rs.randn(B, nh, hd), jnp.bfloat16),
            jnp.asarray(rs.randn(B, 1, hd), jnp.bfloat16),
            jnp.asarray(rs.randn(B, 1, hd), jnp.bfloat16),
            jnp.ones((B, hd), jnp.bfloat16), jnp.zeros((B, hd), jnp.bfloat16),
            kc, vc, jnp.asarray(tables), jnp.asarray(lens_i, jnp.int32),
            jnp.asarray(wblk, jnp.int32), jnp.asarray(wable, bool))
    pa.reset_kernel_counters()
    out, k2, v2 = jax.jit(pa.fused_decode_step)(*case)
    assert pa.FUSED_KERNEL_CALLS == 1, "kernel path not taken"
    ref, k_r, v_r = pa.fused_decode_step_reference(*case)
    live = np.asarray(wable)
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               rtol=2e-2, atol=2e-2)
    for p in range(nbl):            # the real pages: what the oracle wrote
        np.testing.assert_array_equal(np.asarray(k2)[p], np.asarray(k_r)[p])
        np.testing.assert_array_equal(np.asarray(v2)[p], np.asarray(v_r)[p])


def test_fused_decode_head_blocks_when_a_page_outgrows_vmem():
    """A page whose KV heads do not all fit one grid step's VMEM budget
    (64 heads of 128 in float32 at 64-position pages) is taken in blocks
    of heads — the grid's second axis — each block walking and appending
    its own heads; the result is the reference's on every writeable lane,
    and the pools' real pages are the reference's."""
    rs = np.random.RandomState(29)
    nkv, nh, hd, bs, mb, nbl = 64, 128, 128, 64, 3, 6
    heads = pa._heads_that_fit(nkv, pa._decode_vmem_bytes(
        1, 8, hd, bs, jnp.float32, jnp.float32))
    assert 1 < heads < nkv
    lens = [130, 5, None]               # 3 pages (mid-page), 1, dropped
    B = len(lens)
    tables = np.full((B, mb), nbl, np.int32)
    pool = list(rs.permutation(nbl))
    wblk, wable, lens_i = [], [], []
    for b, ln in enumerate(lens):
        if ln is None:
            wblk.append(nbl), wable.append(0), lens_i.append(0)
            continue
        pages = [pool.pop() for _ in range(ln // bs + 1)]
        tables[b, :len(pages)] = pages
        wblk.append(pages[-1]), wable.append(1), lens_i.append(ln)
    f32 = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)
    case = (f32(B, nh, hd), f32(B, nkv, hd), f32(B, nkv, hd), f32(B, hd),
            f32(B, hd), f32(nbl + 1, nkv, bs, hd), f32(nbl + 1, nkv, bs, hd),
            jnp.asarray(tables), jnp.asarray(lens_i, jnp.int32),
            jnp.asarray(wblk, jnp.int32), jnp.asarray(wable, jnp.int32))
    pa.reset_kernel_counters()
    out, k2, v2 = pa.fused_decode_step(*case)
    assert pa.FUSED_KERNEL_CALLS == 1
    ref, k_r, v_r = pa.fused_decode_step_reference(*case)
    live = np.asarray(wable).astype(bool)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(k2)[:nbl], np.asarray(k_r)[:nbl],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(v2)[:nbl], np.asarray(v_r)[:nbl],
                               atol=1e-5)
    assert not np.asarray(k2)[nbl].any() and not np.asarray(v2)[nbl].any()


# ---------------------------------------------------------------------------
# requantized KV append (decode megastep stage 2 — ISSUE 15,
# docs/paged_attention.md "Megastep stage 2")
# ---------------------------------------------------------------------------

def _quant_fused_case(rs, mode, *, lens, nbl=10, nkv=2, bs=8, hd=16, nh=4,
                      mb=4, dtype=jnp.float32):
    """Quantized pools WITH a spill page + per-slot write geometry derived
    from lens (None = inactive lane -> spill)."""
    B = len(lens)
    nbp = nbl + 1
    kc = jnp.asarray(rs.randn(nbp, nkv, bs, hd), jnp.float32)
    vc = jnp.asarray(rs.randn(nbp, nkv, bs, hd), jnp.float32)
    kq, ks = pa.quantize_kv_cache(kc, mode)
    vq, vs = pa.quantize_kv_cache(vc, mode)
    tables = np.full((B, mb), nbl, np.int32)
    pool = list(rs.permutation(nbl))
    wblk, wable, lens_i = [], [], []
    for b, ln in enumerate(lens):
        if ln is None:
            wblk.append(nbl)
            wable.append(0)
            lens_i.append(0)
            continue
        n_pages = ln // bs + 1
        pages = [pool.pop() for _ in range(n_pages)]
        tables[b, :n_pages] = pages
        wblk.append(pages[ln // bs])
        wable.append(1)
        lens_i.append(ln)
    q = jnp.asarray(rs.randn(B, nh, hd), dtype)
    kn = jnp.asarray(rs.randn(B, nkv, hd), dtype)
    vn = jnp.asarray(rs.randn(B, nkv, hd), dtype)
    cos = jnp.asarray(rs.randn(B, hd), dtype)
    sin = jnp.asarray(rs.randn(B, hd), dtype)
    return (q, kn, vn, cos, sin, kq, ks, vq, vs, jnp.asarray(tables),
            jnp.asarray(lens_i, jnp.int32), jnp.asarray(wblk, jnp.int32),
            jnp.asarray(wable, jnp.int32))


@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("lens", [
    [3, 15],          # mid-page appends
    [8, 16],          # PAGE BOUNDARY: seq_len % bs == 0 -> fresh page, off 0
    [7, 23],          # off == bs - 1: the append FILLS its page
])
def test_fused_quant_step_codes_and_scales_byte_vs_oracle(mode, lens):
    """The fused quant kernel's committed page bytes AND recomputed
    per-page scales match the requant-scatter oracle composition exactly
    (both arms jitted: they share _quant_encode_page, so the pool state is
    byte-identical by construction); attention output at f32 tolerance
    (the split-K combine reorders the reduction)."""
    rs = np.random.RandomState(60)
    case = _quant_fused_case(rs, mode, lens=lens)
    pa.reset_kernel_counters()
    out, kq2, ks2, vq2, vs2 = jax.jit(
        lambda *a: pa.fused_quant_decode_step(*a, mode))(*case)
    assert pa.QUANT_APPEND_KERNEL_CALLS == 1, "kernel path not taken"
    ref_o, kq_r, ks_r, vq_r, vs_r = jax.jit(
        lambda *a: pa.fused_quant_decode_step_reference(*a, mode))(*case)
    np.testing.assert_array_equal(np.asarray(kq2), np.asarray(kq_r))
    np.testing.assert_array_equal(np.asarray(vq2), np.asarray(vq_r))
    np.testing.assert_array_equal(np.asarray(ks2), np.asarray(ks_r))
    np.testing.assert_array_equal(np.asarray(vs2), np.asarray(vs_r))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_fused_quant_step_spill_page_isolation(mode):
    """Non-writeable lanes (inactive / pos >= max_seq) land on the spill
    page: every REAL page's codes and scales are byte-untouched, and the
    live lane still appends correctly."""
    rs = np.random.RandomState(61)
    case = _quant_fused_case(rs, mode, lens=[5, None])
    kq0, ks0 = np.asarray(case[5]).copy(), np.asarray(case[6]).copy()
    nbl = kq0.shape[0] - 1
    pa.reset_kernel_counters()
    out, kq2, ks2, vq2, vs2 = jax.jit(
        lambda *a: pa.fused_quant_decode_step(*a, mode))(*case)
    assert pa.QUANT_APPEND_KERNEL_CALLS == 1
    wblk = int(case[11][0])
    touched = {wblk, nbl}                       # live write page + spill
    for p in range(nbl):
        if p not in touched:
            np.testing.assert_array_equal(np.asarray(kq2)[p], kq0[p])
            np.testing.assert_array_equal(np.asarray(ks2)[p], ks0[p])
    # the dropped lane's output is still finite (masked attention)
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_fused_quant_step_disable_env_routes_to_oracle(mode, monkeypatch):
    """PADDLE_TPU_DISABLE_PALLAS=fused_quant_append routes to the
    requant-scatter reference with byte-identical pool state (counter
    evidence both ways); =fused_decode_step kills the quant member too."""
    rs = np.random.RandomState(62)
    case = _quant_fused_case(rs, mode, lens=[3, 12])
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    pa.reset_kernel_counters()
    _, kq_on, ks_on, _, _ = pa.fused_quant_decode_step(*case, mode)
    assert pa.QUANT_APPEND_KERNEL_CALLS == 1

    for token in ("fused_quant_append", "fused_decode_step"):
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", token)
        pa.reset_kernel_counters()
        o, kq2, ks2, vq2, vs2 = pa.fused_quant_decode_step(*case, mode)
        assert (pa.QUANT_APPEND_FALLBACK_CALLS == 1
                and pa.QUANT_APPEND_KERNEL_CALLS == 0), token
        _, kq_r, ks_r, _, _ = pa.fused_quant_decode_step_reference(*case,
                                                                   mode)
        np.testing.assert_array_equal(np.asarray(kq2), np.asarray(kq_r))
        np.testing.assert_array_equal(np.asarray(ks2), np.asarray(ks_r))


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quant_append_rows_rewrites_only_dirty_pages(mode):
    """The multi-row append (prefill bucket / chunk / verify window)
    recomputes scales for DIRTY pages only: pages receiving no row this
    event — shared prefix-cache pages — keep their exact bytes, and each
    dirty page matches the dequant-insert-encode oracle computed once over
    the whole event."""
    rs = np.random.RandomState(63)
    nbl, nkv, bs, hd, mb = 8, 2, 8, 16, 4
    nbp = nbl + 1
    kc = jnp.asarray(rs.randn(nbp, nkv, bs, hd), jnp.float32)
    qpool, sc = pa.quantize_kv_cache(kc, mode)
    q0, s0 = np.asarray(qpool).copy(), np.asarray(sc).copy()
    table = jnp.asarray(rs.permutation(nbl)[:2 * mb].reshape(2, mb),
                        jnp.int32)
    # slot 0: rows 13..18 (crosses the page-1/page-2 boundary); slot 1:
    # 2 valid rows + 4 masked
    T = 6
    row_pos = jnp.asarray([[13, 14, 15, 16, 17, 18],
                           [3, 4, 0, 0, 0, 0]], jnp.int32)
    valid = jnp.asarray([[1, 1, 1, 1, 1, 1],
                         [1, 1, 0, 0, 0, 0]], jnp.bool_)
    rows = jnp.asarray(rs.randn(2, T, nkv, hd), jnp.float32)
    out_q, out_s = pa.quant_append_rows(qpool, sc, rows, table, row_pos,
                                        valid, mode)
    out_q, out_s = np.asarray(out_q), np.asarray(out_s)
    dirty = {}     # phys page -> [(local off, (slot, row))]
    for b in range(2):
        for t in range(T):
            if bool(valid[b, t]):
                phys = int(table[b, int(row_pos[b, t]) // bs])
                dirty.setdefault(phys, []).append(
                    (int(row_pos[b, t]) % bs, (b, t)))
    for p in range(nbp):
        if p not in dirty:
            np.testing.assert_array_equal(out_q[p], q0[p], str(p))
            np.testing.assert_array_equal(out_s[p], s0[p], str(p))
    for p, hits in dirty.items():
        deq = np.array(pa._dequant_page_content(
            jnp.asarray(q0[p]), jnp.asarray(s0[p]), mode))
        for off, (b, t) in hits:
            deq[:, off, :] = np.asarray(rows[b, t])
        want_q, want_s = pa._quant_encode_page(jnp.asarray(deq), mode)
        np.testing.assert_array_equal(out_q[p], np.asarray(want_q), str(p))
        np.testing.assert_array_equal(out_s[p], np.asarray(want_s), str(p))


def test_quant_encode_page_matches_quantize_kv_cache():
    """_quant_encode_page (the ONE encode implementation the scatter arm
    and the fused kernel share) reproduces quantize_kv_cache's codes,
    scales and int4 nibble layout on whole-pool content."""
    rs = np.random.RandomState(64)
    kc = jnp.asarray(rs.randn(5, 3, 8, 16), jnp.float32)
    for mode in ("int8", "int4"):
        want_q, want_s = pa.quantize_kv_cache(kc, mode)
        got_q, got_s = pa._quant_encode_page(kc.astype(jnp.float32), mode)
        np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        back = pa._dequant_page_content(got_q, got_s, mode)
        tol = 0.03 if mode == "int8" else 0.5
        assert float(jnp.max(jnp.abs(back - kc))) < tol
