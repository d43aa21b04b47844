"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with this
installation compiles for a topology that is described
(``topologies.get_topology_desc``), which is enough to find what Mosaic
refuses — block shapes the tiling rejects, dots that do not accumulate in
f32, VMEM overruns — before any chip time is spent.  A compile that passes
is not a chip run and says nothing about results or speed.

Widths are ``LlamaConfig.llama3_8b()``'s (hidden 4096, ffn 14336, 32 heads /
8 KV heads, head_dim 128) at batch 8, block 64, plus the TP=4 shard-local
widths (8 heads / 2 KV heads, ffn 3584) for the decode and fused-MLP
kernels.

All of these live in ONE file and the topology is described inside a
module-scoped fixture (never at import): only one process may load the
TPU's library, so only the xdist worker that is handed this file does.
``interpret_mode`` reads ``jax.default_backend()``, which is the CPU during
such a compile, so it is steered from here by patching the name each kernel
module imported.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import gated_delta as gd
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import rms_norm as rn

# Llama-3-8B widths
H, FFN, NH, NKV, HD = 4096, 14336, 32, 8, 128
B, BS, MAXBLK = 8, 64, 32                   # 8 slots x 2048 tokens of KV
NBP = B * MAXBLK + 1                        # pool pages + the spill page
TP = 4
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache but
    # cannot be read back without a chip: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels lower for the chip (not the interpreter) inside the test, at
    the matmul precision the program runs with (conftest.py raises it to
    'highest' for the numpy oracles; Mosaic refuses that on bf16 operands)."""
    for mod in (pa, fa, rn, gd):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", prev)


def _compile(fn, one_chip, *specs):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(hd_store=HD, dtype=BF16, nkv=NKV):
    return ((NBP, nkv, BS, hd_store), dtype)


_TABLES = ((B, MAXBLK), jnp.int32)
_LENS = ((B,), jnp.int32)
_SCALES = ((NBP, NKV), jnp.float32)


def _quant_store(kv_quant):
    return _pool(HD // 2 if kv_quant == "int4" else HD, jnp.int8)


@pytest.mark.parametrize("num_shards", [1, None], ids=["sequential", "splitk"])
def test_paged_decode_fp(one_chip, compiled_kernels, num_shards):
    fn = functools.partial(pa.paged_attention_decode, num_shards=num_shards)
    _compile(fn, one_chip, ((B, NH, HD), BF16), _pool(), _pool(), _TABLES,
             _LENS)


@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
@pytest.mark.parametrize("num_shards", [1, None], ids=["sequential", "splitk"])
def test_paged_decode_quant(one_chip, compiled_kernels, num_shards, kv_quant):
    def fn(q, kc, vc, t, l, ks, vs):
        return pa.paged_attention_decode(q, kc, vc, t, l, kv_quant=kv_quant,
                                         k_scale=ks, v_scale=vs,
                                         num_shards=num_shards)

    st = _quant_store(kv_quant)
    _compile(fn, one_chip, ((B, NH, HD), BF16), st, st, _TABLES, _LENS,
             _SCALES, _SCALES)


def test_paged_verify(one_chip, compiled_kernels):
    _compile(pa.paged_attention_verify, one_chip, ((B, 5, NH, HD), BF16),
             _pool(), _pool(), _TABLES, _LENS, _LENS)


@pytest.mark.parametrize("T", [64, 256])
def test_paged_prefill_fp(one_chip, compiled_kernels, T):
    _compile(pa.paged_attention_prefill, one_chip, ((B, T, NH, HD), BF16),
             _pool(), _pool(), _TABLES, _LENS, _LENS)


@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
def test_paged_prefill_quant(one_chip, compiled_kernels, kv_quant):
    def fn(q, kc, vc, t, l, ql, ks, vs):
        return pa.paged_attention_prefill(q, kc, vc, t, l, ql,
                                          kv_quant=kv_quant, k_scale=ks,
                                          v_scale=vs)

    st = _quant_store(kv_quant)
    _compile(fn, one_chip, ((B, 64, NH, HD), BF16), st, st, _TABLES, _LENS,
             _LENS, _SCALES, _SCALES)


@pytest.mark.parametrize("nh,nkv,pages", [(32, 8, 513), (30, 30, 769),
                                          (8, 2, 513)],
                         ids=["mistral7b", "olmo_hybrid", "mistral7b_tp4"])
def test_paged_prefill_at_the_cells_shapes(one_chip, compiled_kernels, nh,
                                           nkv, pages):
    """The mixed step's launch as the serving cells make it: 32 lanes of a
    128-row chunk, bf16, 64-position pages, a 64-page table.  All of a
    page's KV heads ride one grid step (8, 30, and 2 under TP = 4), and
    the blocks and scratch that takes stay under the module's own budget
    of the chip's scoped VMEM."""
    b, T, bs, max_blocks = 32, 128, 64, 64
    rep = nh // nkv
    R, head_rows, sub_rows = pa._prefill_tiles(T, rep, BF16)
    assert (R, head_rows, sub_rows) == (T * rep, 16, 128)
    heads = pa._prefill_heads_per_step(nkv, R, HD, bs, HD, BF16, BF16)
    assert heads == nkv
    assert (pa._prefill_vmem_bytes(heads, R, HD, bs, HD, BF16, BF16)
            <= pa._PREFILL_VMEM_BUDGET < pa._VMEM_LIMIT)
    pool = ((pages, nkv, bs, HD), BF16)
    _compile(pa.paged_attention_prefill, one_chip, ((b, T, nh, HD), BF16),
             pool, pool, ((b, max_blocks), jnp.int32), ((b,), jnp.int32),
             ((b,), jnp.int32))


@pytest.mark.parametrize("tp", [1, TP], ids=["full", "tp4_local"])
def test_fused_decode_step(one_chip, compiled_kernels, tp):
    nh, nkv = NH // tp, NKV // tp
    _compile(pa.fused_decode_step, one_chip, ((B, nh, HD), BF16),
             ((B, nkv, HD), BF16), ((B, nkv, HD), BF16), ((B, HD), BF16),
             ((B, HD), BF16), _pool(nkv=nkv), _pool(nkv=nkv), _TABLES, _LENS,
             _LENS, _LENS)


@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
@pytest.mark.parametrize("tp", [1, TP], ids=["full", "tp4_local"])
def test_fused_quant_decode_step(one_chip, compiled_kernels, tp, kv_quant):
    nh, nkv = NH // tp, NKV // tp

    def fn(q, k, v, cos, sin, kq, ksc, vq, vsc, t, l, wb, wa):
        return pa.fused_quant_decode_step(q, k, v, cos, sin, kq, ksc, vq,
                                          vsc, t, l, wb, wa, kv_quant)

    st = ((NBP, nkv, BS, HD // 2 if kv_quant == "int4" else HD), jnp.int8)
    sc = ((NBP, nkv), jnp.float32)
    _compile(fn, one_chip, ((B, nh, HD), BF16), ((B, nkv, HD), BF16),
             ((B, nkv, HD), BF16), ((B, HD), BF16), ((B, HD), BF16), st, sc,
             st, sc, _TABLES, _LENS, _LENS, _LENS)


@pytest.mark.parametrize("tp", [1, TP], ids=["full", "tp4_local"])
def test_fused_layer_mlp(one_chip, compiled_kernels, tp):
    inter = FFN // tp
    fn = functools.partial(pa.fused_layer_mlp, eps=1e-5)
    _compile(fn, one_chip, ((B, H), BF16), ((B, H), BF16), ((H,), BF16),
             ((H, inter), BF16), ((H, inter), BF16), ((inter, H), BF16))


def test_rms_norm(one_chip, compiled_kernels):
    _compile(rn.rms_norm, one_chip, ((8 * 2048, H), BF16), ((H,), BF16))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_seq2048(one_chip, compiled_kernels, grad):
    def fwd(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(fn, one_chip, ((2, 2048, NH, HD), BF16),
             ((2, 2048, NKV, HD), BF16), ((2, 2048, NKV, HD), BF16))


@pytest.mark.parametrize("case", ["segments", "bool_mask", "additive_mask",
                                  "head_dim64", "float32"])
def test_flash_attention_streamed_inputs_and_widths(one_chip,
                                                    compiled_kernels, case):
    """What the two training cells do not pass: segment ids and masks (the
    dk/dv kernel takes them by kv rows), a head narrower than the 128 lanes
    the softmax statistics are kept on, float32 inputs; forward and all three
    gradients at 1,024 positions."""
    hd = 64 if case == "head_dim64" else HD
    dtype = jnp.float32 if case == "float32" else BF16

    def loss(q, k, v):
        kw = {"causal": True}
        if case == "segments":
            kw["segment_ids"] = jnp.zeros((2, 1024), jnp.int32)
        if case == "bool_mask":
            kw = {"attn_mask": jnp.ones((2, 1, 1024, 1024), bool)}
        if case == "additive_mask":
            kw = {"attn_mask": jnp.zeros((1, NH, 1024, 1024), jnp.float32)}
        return fa.flash_attention_bshd(q, k, v, **kw).astype(
            jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
             ((2, 1024, NH, hd), dtype), ((2, 1024, NKV, hd), dtype),
             ((2, 1024, NKV, hd), dtype))


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)],
                         ids=["window512x64", "full48"])
def test_flash_attention_seq8192_laguna_widths(one_chip, compiled_kernels,
                                               heads, window):
    """Laguna-XS.2's two kinds of layer at 8,192 positions: the banded
    kernels (64 query heads over 8 KV heads, window 512) and the full ones
    (48 heads), forward and all three gradients."""
    def loss(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True,
                                       window=window).astype(
                                           jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                        ((1, 8192, heads, HD), BF16),
                        ((1, 8192, NKV, HD), BF16),
                        ((1, 8192, NKV, HD), BF16))
    names = ("flash_attn_win_fwd", "flash_attn_win_bwd_dkv",
             "flash_attn_win_bwd_dq")
    assert all((n in compiled.as_text()) == (window is not None)
               for n in names)


# Olmo-Hybrid-7B's linear layers at the cell ``olmo-hybrid-chat``'s shapes:
# 30 heads of d_k 96 / d_v 192, 32 slots, the state of 12 layers stacked
GDN = dict(B=32, H=30, DK=96, DV=192, L=12)


def test_gdn_decode_step(one_chip, compiled_kernels):
    """One token a slot against the stacked float32 state, in place."""
    B, H, DK, DV, L = GDN.values()
    F32 = jnp.float32
    fn = lambda q, k, v, a, b, s, f, l: gd.gdn_decode_step(
        q, k, v, a, b, s, f, l)
    compiled = _compile(fn, one_chip, ((B, H, DK), F32), ((B, H, DK), F32),
                        ((B, H, DV), F32), ((B, H), F32), ((B, H), F32),
                        ((L, B, H, DK, DV), F32), ((B,), jnp.bool_),
                        ((), jnp.int32))
    assert "gdn_decode_step" in compiled.as_text()


@pytest.mark.parametrize("T", [128, 100], ids=["chunk128", "ragged100"])
def test_gdn_chunk_prefill(one_chip, compiled_kernels, T):
    """A mixed step's [B, T] rows in sub-chunks of 64 from the stacked
    state (T = 100: the pad to whole sub-chunks)."""
    B, H, DK, DV, L = GDN.values()
    F32 = jnp.float32
    fn = lambda q, k, v, g, b, s, ok, f, l: gd.gdn_chunk_prefill(
        q, k, v, g, b, s, ok, f, l)
    compiled = _compile(fn, one_chip, ((B, T, H, DK), F32),
                        ((B, T, H, DK), F32), ((B, T, H, DV), F32),
                        ((B, T, H), F32), ((B, T, H), F32),
                        ((L, B, H, DK, DV), F32), ((B, T), jnp.bool_),
                        ((B,), jnp.bool_), ((), jnp.int32))
    assert "gdn_chunk_prefill" in compiled.as_text()
