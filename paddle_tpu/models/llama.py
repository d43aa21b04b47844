"""Llama family (BASELINE config #3; north-star Llama-3-8B pretrain).

Reference recipe surface: PaddleNLP llm/ on top of the reference framework's
fused ops (fused_rms_norm, fused_rotary_position_embedding, swiglu — see
python/paddle/incubate/nn/functional/) and fleet hybrid parallelism.

TPU-first design:
- the eager Layer graph (LlamaForCausalLM) is the UX/debug surface;
- the *training path* is :func:`build_train_step` — a pure pjit-compiled
  function over a named mesh ("dp", "sharding"/zero, "mp"/tensor, "sep"/context)
  where every weight carries a PartitionSpec (Megatron-style column/row splits
  over "mp"), activations shard batch over "dp" and sequence over "sep", and
  GSPMD inserts the all-reduces/all-gathers the reference does with NCCL.
- attention = Pallas flash attention (ops/pallas/flash_attention.py);
  rms_norm/rope/swiglu = fused kernels from ops/pallas.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import pallas
from ..ops.pallas import flash_attention as fa
from ..ops.pallas import rms_norm as rms
from ..ops.pallas import rope as rope_mod
from ..ops.pallas import swiglu as swiglu_mod


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def llama3_8b():
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        )

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2, inter=128, seq=128):
        return LlamaConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, max_position_embeddings=seq,
        )


# ---------------------------------------------------------------------------
# pure functional core (the pjit training path)
# ---------------------------------------------------------------------------

def init_params(cfg: LlamaConfig, key=None) -> dict:
    """Parameter pytree.  Layer weights are stacked over a leading layer dim so
    the transformer stack runs as one lax.scan (single compiled block, fast
    compile, and the natural shape for pipeline stacking over 'pp')."""
    key = key if key is not None else jax.random.key(0)
    k = iter(jax.random.split(key, 16))
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    nh, nkv, hd, L = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.num_hidden_layers
    std = 0.02

    def init(kk, shape):
        return (jax.random.normal(kk, shape, jnp.float32) * std).astype(cfg.dtype)

    params = {
        "embed": init(next(k), (v, h)),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": {
            "input_norm": jnp.ones((L, h), cfg.dtype),
            "post_norm": jnp.ones((L, h), cfg.dtype),
            "wq": init(next(k), (L, h, nh * hd)),
            "wk": init(next(k), (L, h, nkv * hd)),
            "wv": init(next(k), (L, h, nkv * hd)),
            "wo": init(next(k), (L, nh * hd, h)),
            "w_gate": init(next(k), (L, h, i)),
            "w_up": init(next(k), (L, h, i)),
            "w_down": init(next(k), (L, i, h)),
        },
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(next(k), (h, v))
    return params


def param_specs(cfg: LlamaConfig, pp: bool = False, mp: int = 1) -> dict:
    """PartitionSpecs = the Megatron TP sharding map of the reference's mp_layers
    (ColumnParallelLinear splits output dim over 'mp', RowParallelLinear splits
    input dim; VocabParallelEmbedding splits vocab), plus ZeRO over 'sharding'
    on the other dim (fleet sharding stage 3 analog).  With ``pp`` the stacked
    layer dim is sharded over the 'pp' mesh axis — each device holds one
    pipeline stage's contiguous layer slice (the PipelineLayer segmentation of
    pp_layers.py:258, realized as a sharding).

    GQA under TP: when ``mp`` exceeds ``num_key_value_heads``, K/V projections
    are REPLICATED over 'mp' instead of column-sharded — a sub-head split
    makes the SPMD partitioner replicate-then-repartition every layer
    ("involuntary full rematerialization", wasted ICI bandwidth).  The
    reference's mp_layers duplicate KV heads in exactly this regime
    (fleet/layers/mpu/mp_layers.py:49,336)."""
    layer_dim = "pp" if pp else None
    # replicate unless mp divides the kv heads evenly (mp > kv_heads is the
    # common case, but any non-dividing mp sub-head-splits too)
    kv_col = None if cfg.num_key_value_heads % mp != 0 else "mp"

    def mat(name):
        # column/row assignment comes from the shared Megatron table
        # (MEGATRON_SPLIT) — the same one serving_param_specs reads
        tensor = kv_col if name in ("wk", "wv") else "mp"
        if MEGATRON_SPLIT[name] == "col":
            return P(layer_dim, "sharding", tensor)
        return P(layer_dim, tensor, "sharding")

    return {
        "embed": P("mp", "sharding"),          # vocab-parallel embedding
        "final_norm": P(None),
        "layers": {
            "input_norm": P(layer_dim, None),
            "post_norm": P(layer_dim, None),
            **{name: mat(name) for name in MEGATRON_SPLIT},
        },
        "lm_head": P("sharding", "mp"),
    }


#: the Megatron split per decoder matmul leaf — the ONE table the training
#: specs above and the serving TP specs below both read, so the two spec
#: surfaces cannot disagree about which dim a weight shards on.
#: 'col' = ColumnParallelLinear (output dim over the tensor axis),
#: 'row' = RowParallelLinear (input dim over the tensor axis).
MEGATRON_SPLIT = {"wq": "col", "wk": "col", "wv": "col",
                  "w_gate": "col", "w_up": "col",
                  "wo": "row", "w_down": "row"}


def serving_param_specs(cfg: LlamaConfig, quant: str | None = None,
                        axis: str = "tp") -> dict:
    """PartitionSpecs for the SERVING param tree over a 1-D ``(axis,)`` mesh
    (docs/tp_serving.md) — the continuous-batching engine's
    ``tensor_parallel=N`` mode.

    Unlike the training map (:func:`param_specs`), serving keeps the
    residual stream, embedding, norms and lm_head REPLICATED: every shard
    computes the full [B, V] logits row identically, so the sampler and the
    host scheduler see exactly the single-chip values and the only
    cross-shard traffic is the two per-layer psums
    (:func:`decoder_attn_residual` / :func:`decoder_mlp_residual`).
    Column-parallel leaves split heads/ffn on their OUTPUT dim, row-parallel
    ones their INPUT dim (:data:`MEGATRON_SPLIT`); K/V projections split
    along kv_heads — the same axis the paged KV pool shards on, which is
    what keeps the paged-attention kernels' page walk shard-local.

    ``quant`` (None | 'int8' | 'int4'): the engine's weight-only mode stores
    matmul leaves as ``{'qweight': [L, out, in], 'scale': [L, out]}``
    (nn/quant layout) — the split dim maps through the transpose, and a
    row-parallel leaf's per-out-channel scales replicate so dequant-on-read
    stays shard-local."""
    def leaf(name):
        split = MEGATRON_SPLIT.get(name)
        if split == "col":
            return ({"qweight": P(None, axis, None), "scale": P(None, axis)}
                    if quant else P(None, None, axis))
        if split == "row":
            return ({"qweight": P(None, None, axis), "scale": P(None, None)}
                    if quant else P(None, axis, None))
        return P()      # norms: replicated
    specs = {
        "embed": P(),
        "final_norm": P(),
        "layers": {k: leaf(k) for k in
                   ("input_norm", "post_norm", "wq", "wk", "wv", "wo",
                    "w_gate", "w_up", "w_down")},
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P()
    return specs


def _tp_psum(y, tp_axis, scope):
    """The tensor-parallel all-reduce boundary.  ``tp_axis=None`` is the
    single-chip path (no collective, byte-identical program); with an axis
    name the caller is inside a shard_map region holding a row-parallel
    partial sum.  The named scope lands in HLO op_name metadata so the
    analysis resharding rule can allowlist exactly these two collectives
    per layer and flag everything else (docs/tp_serving.md)."""
    if tp_axis is None:
        return y
    with jax.named_scope(scope):
        return jax.lax.psum(y, tp_axis)


def decoder_attn_residual(x, attn, lp, wmat=None, tp_axis=None):
    """Attention output projection + residual — ONE home for serving
    (inference.transformer_apply) and training (``_layer_forward`` here and
    in moe_llama), so the Megatron row-parallel contract cannot drift:
    ``wo`` splits its INPUT (heads) dim over tp, each shard's
    ``attn_local @ wo_local`` is a partial sum, and the psum here is TP
    boundary 1 of the layer's exactly-two.  ``wmat(leaf, dtype)`` resolves
    weight-only-quantized leaves (serving); None reads the leaf raw."""
    wo = lp["wo"] if wmat is None else wmat(lp["wo"], x.dtype)
    return x + _tp_psum(attn @ wo, tp_axis, "tp_allreduce_attn_out")


def decoder_mlp_residual(cfg, x, lp, wmat=None, tp_axis=None):
    """post-norm + swiglu MLP + residual, the layer's second half and TP
    boundary 2: w_gate/w_up are column-parallel (each shard computes a
    ffn/tp slice), w_down row-parallel, and the psum completes the down
    projection.  Shared by serving and training like
    :func:`decoder_attn_residual`."""
    w = (lambda n: lp[n] if wmat is None else wmat(lp[n], x.dtype))
    xn = rms.rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
    y = swiglu_mod.swiglu(xn @ w("w_gate"), xn @ w("w_up")) @ w("w_down")
    return x + _tp_psum(y, tp_axis, "tp_allreduce_mlp_out")


def decoder_layer_tail(cfg, x, attn, lp, wmat=None, tp_axis=None,
                       mlp_fn=None):
    """The whole post-attention half of a decoder layer — attention output
    projection, TP psum boundary 1, residual add, post-norm + swiglu MLP,
    TP psum boundary 2, residual add — in ONE seam shared by serving and
    training (the stage-2 megastep seam; docs/paged_attention.md
    "Megastep stage 2").

    ``mlp_fn=None`` composes :func:`decoder_attn_residual` +
    :func:`decoder_mlp_residual` exactly — byte-identical to calling the
    two halves directly, which is what training and every unfused serving
    program keep tracing.  With ``mlp_fn(h_res, attn_y, lp) -> (h1, y)``
    the residual add + post RMSNorm + SwiGLU MLP between the two psum
    boundaries run through the caller's fused implementation (the serving
    decode path passes ops/pallas/paged_attention.fused_layer_mlp here):
    ``h1 = h_res + attn_y`` is the layer's next residual anchor and ``y``
    the UN-reduced down projection, so the two all-reduces stay exactly
    where PR 7 put them — the only per-layer exits of the fused decode
    layer."""
    if mlp_fn is None:
        x = decoder_attn_residual(x, attn, lp, wmat=wmat, tp_axis=tp_axis)
        return decoder_mlp_residual(cfg, x, lp, wmat=wmat, tp_axis=tp_axis)
    wo = lp["wo"] if wmat is None else wmat(lp["wo"], x.dtype)
    attn_y = _tp_psum(attn @ wo, tp_axis, "tp_allreduce_attn_out")
    h1, y = mlp_fn(x, attn_y, lp)
    return h1 + _tp_psum(y, tp_axis, "tp_allreduce_mlp_out")


def _layer_forward(cfg: LlamaConfig, x, layer_params, cos, sin, use_flash=True,
                   attn_fn=None):
    """One transformer block; x: [b, s, h].  ``attn_fn(q, k, v) -> out`` (all
    BSHD) overrides the attention implementation — used by the context-parallel
    path to route through ring attention over the 'sep' axis."""
    lp = layer_params
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    # attention
    xn = rms.rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    q = (xn @ lp["wq"]).reshape(b, s, nh, hd)
    kk = (xn @ lp["wk"]).reshape(b, s, nkv, hd)
    vv = (xn @ lp["wv"]).reshape(b, s, nkv, hd)
    q, kk = rope_mod.apply_rotary_pos_emb(q, kk, cos, sin)
    if attn_fn is not None:
        attn = attn_fn(q, kk, vv)
    elif use_flash:
        attn = fa.flash_attention_bshd(q, kk, vv, causal=True)
    else:
        attn = fa._composed_attention(q, kk, vv, None, True, 1.0 / math.sqrt(hd))
    # the shared post-attention seam (mlp_fn=None: the exact two-half
    # composition serving's unfused programs and TP both pin)
    return decoder_layer_tail(cfg, x, attn.reshape(b, s, nh * hd), lp)


def _embed_rope(cfg: LlamaConfig, params, input_ids):
    """Shared prelude: token embedding + rope tables for the sequence length."""
    x = jnp.take(params["embed"], input_ids, axis=0).astype(cfg.dtype)
    cos, sin = rope_mod.rope_cos_sin(
        x.shape[1], cfg.head_dim, base=cfg.rope_theta, dtype=cfg.dtype)
    return x, cos, sin


def _norm_and_head(cfg: LlamaConfig, params, x):
    """Final rms_norm + resolved (possibly tied) lm head weight — the single
    source for head tying/dtype, shared by the dense and chunked losses."""
    xn = rms.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T.astype(cfg.dtype)
    return xn, head


def _final_head(cfg: LlamaConfig, params, x):
    """Shared tail: final rms_norm + (possibly tied) lm head."""
    xn, head = _norm_and_head(cfg, params, x)
    return xn @ head


def sep_attention(mesh: Mesh, axis: str = "sep", impl: str = "ring"):
    """Context-parallel attention over the mesh's sequence axis (the reference's
    sep axis + SegmentParallel, segment_parallel.py:26; flash-attention SPMD
    rule with sharded seq, spmd_rules/flash_attention.cc).

    Returns an ``attn_fn(q, k, v)`` (BSHD) that binds the 'sep' axis with a
    partial-manual shard_map — only 'sep' goes manual, dp/mp/sharding stay
    GSPMD-auto — and runs ring attention (K/V blocks rotating over ICI with
    ppermute) or Ulysses (all_to_all heads<->seq) on the local shards."""
    from ..ops import ring_attention as ra

    seq_spec = P(None, axis, None, None)

    def attn_fn(q, k, v):
        def local(q_, k_, v_):
            if impl == "ulysses":
                return ra.ulysses_attention(q_, k_, v_, axis_name=axis, causal=True)
            return ra.ring_attention(q_, k_, v_, axis_name=axis, causal=True)

        return jax.shard_map(
            local, mesh=mesh, in_specs=(seq_spec,) * 3, out_specs=seq_spec,
            axis_names={axis}, check_vma=False,
        )(q, k, v)

    return attn_fn


def _remat_wrap(body, remat):
    """Apply the recompute policy (reference: fleet/recompute full-block
    recompute vs selective recompute).  PADDLE_TPU_REMAT selects at trace
    time: 'full' (default — recompute everything, minimum HBM), 'dots'
    (save matmul outputs, recompute only cheap elementwise — trades HBM for
    fewer recomputed MXU FLOPs), 'none' (no recompute)."""
    import os

    if not remat:
        return body
    policy = os.environ.get("PADDLE_TPU_REMAT", "full")
    if policy == "none":
        return body
    if policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def forward(cfg: LlamaConfig, params, input_ids, use_flash=True, remat=True,
            attn_fn=None, return_hidden=False):
    """Logits for [b, s] token ids.  The layer stack is a lax.scan over the
    stacked layer weights with jax.checkpoint (activation recompute ≙ the
    reference's recompute_sequential over transformer blocks).
    ``return_hidden`` skips the final norm + lm head and returns the last
    hidden states (the chunked-xent loss fuses the head into the loss)."""
    x, cos, sin = _embed_rope(cfg, params, input_ids)

    def body(carry, lp):
        out = _layer_forward(cfg, carry, lp, cos, sin, use_flash, attn_fn)
        return out, None

    scan_body = _remat_wrap(body, remat)
    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    if return_hidden:
        return x
    return _final_head(cfg, params, x)


def forward_pp(cfg: LlamaConfig, params, input_ids, mesh, num_microbatches,
               use_flash=True, remat=True, sep_attn_impl="ring",
               return_hidden=False):
    """Pipeline-parallel forward: the stacked layer dim is sharded over 'pp'
    and executed by the in-jit GPipe engine (fleet/pipeline.py gpipe_stacked ≙
    the reference's PipelineParallel.forward_backward_pipeline at
    pipeline_parallel.py:684, as one compiled SPMD program).

    When the mesh also has 'sep' > 1, sep is bound manually in the SAME region
    (sdy cannot nest partial-manual regions): microbatches and rope tables are
    seq-sharded over 'sep' and attention runs ring/Ulysses directly."""
    from ..distributed.fleet.pipeline import gpipe_stacked
    from ..ops import ring_attention as ra

    sep = dict(mesh.shape).get("sep", 1)
    x, cos, sin = _embed_rope(cfg, params, input_ids)
    b, s, h = x.shape
    M = num_microbatches
    assert b % M == 0, f"batch {b} not divisible by num_microbatches {M}"
    xm = x.reshape(M, b // M, s, h)

    if sep > 1:
        if sep_attn_impl == "ulysses":
            attn_fn = lambda q, k, v: ra.ulysses_attention(q, k, v, axis_name="sep", causal=True)
        else:
            attn_fn = lambda q, k, v: ra.ring_attention(q, k, v, axis_name="sep", causal=True)
        gp_kw = dict(
            mb_spec=P(None, None, "sep", None),
            extra_specs=(P(None, "sep", None),) * 2,  # rope [1, s, d]: local slices
            manual_axes=("sep",),
        )
    else:
        attn_fn = None
        gp_kw = {}

    def stage_fn(stage_params, xin, cos_, sin_):
        def body(carry, lp):
            return _layer_forward(cfg, carry, lp, cos_, sin_, use_flash, attn_fn), None

        scan_body = _remat_wrap(body, remat)
        y, _ = jax.lax.scan(scan_body, xin, stage_params)
        return y

    outs = gpipe_stacked(stage_fn, params["layers"], xm, mesh, "pp",
                         extra_args=(cos, sin), **gp_kw)
    if return_hidden:
        return outs.reshape(b, s, h)
    return _final_head(cfg, params, outs.reshape(b, s, h))


def loss_and_grads_1f1b(cfg: LlamaConfig, params, input_ids, labels, mesh,
                        num_microbatches, use_flash=True, remat=True,
                        num_chunks=1, layers_stage_major=False,
                        zero_bubble=False, sep_attn_impl="ring"):
    """Pipeline train-step core on the executed 1F1B schedule
    (fleet/pipeline.py one_f_one_b_stacked ≙ pipeline_parallel.py:684 run,
    not simulated).  Stage 0 owns the embedding, the last stage owns final
    norm + lm head + loss, so loss cotangents stream backward per microbatch.
    With ``num_chunks`` C > 1 this is the interleaved/VPP schedule
    (PipelineParallelWithInterleave, pipeline_parallel.py:1308): the stacked
    layers are reordered stage-major (stage s owns virtual stages c·P+s) so
    the pp shard of each stage holds its C chunks; grads are reordered back.
    That in-step reorder reshards ~half the layer params across pp shards
    each step — callers that keep their train state stage-major permanently
    (reorder once at init) should pass ``layers_stage_major=True`` to skip
    both permutes.  Returns (mean_loss, grads) with grads matching the
    params tree (f32)."""
    from ..distributed.fleet.pipeline import one_f_one_b_stacked
    from ..ops import ring_attention as ra

    b, s = input_ids.shape
    M = num_microbatches
    assert b % M == 0, f"batch {b} not divisible by num_microbatches {M}"
    ids_m = input_ids.reshape(M, b // M, s)
    lbl_m = labels.reshape(M, b // M, s)
    cos, sin = rope_mod.rope_cos_sin(s, cfg.head_dim, base=cfg.rope_theta,
                                     dtype=cfg.dtype)
    C = num_chunks
    pp_deg = dict(mesh.shape).get("pp", 1)
    sep = dict(mesh.shape).get("sep", 1)
    L = cfg.num_hidden_layers
    assert L % (pp_deg * C) == 0, (L, pp_deg, C)
    Lv = L // (pp_deg * C)  # layers per virtual stage

    # sep > 1: the runner binds 'sep' manually in the same region (mirrors
    # the gpipe region, forward_pp) — sequence-sharded microbatches + rope
    # slices, ring/Ulysses attention inside each stage
    if sep > 1:
        if sep_attn_impl == "ulysses":
            attn_fn = lambda q, k, v: ra.ulysses_attention(
                q, k, v, axis_name="sep", causal=True)
        else:
            attn_fn = lambda q, k, v: ra.ring_attention(
                q, k, v, axis_name="sep", causal=True)
    else:
        attn_fn = None

    def embed_fn(ep, ids, cos_, sin_):
        return jnp.take(ep, ids, axis=0).astype(cfg.dtype)

    def _scan_layers(sp, x, cos_, sin_):
        def body(carry, lp):
            return _layer_forward(cfg, carry, lp, cos_, sin_, use_flash,
                                  attn_fn), None

        scan_body = _remat_wrap(body, remat)
        y, _ = jax.lax.scan(scan_body, x, sp)
        return y

    if C == 1:
        stage_fn = _scan_layers
    else:
        def stage_fn(sp, x, chunk, cos_, sin_):
            # local stacked leaves hold C chunks of Lv layers (stage-major
            # layout): slice this chunk, then scan it
            pick = lambda w: jax.lax.dynamic_index_in_dim(
                w.reshape((C, Lv) + w.shape[1:]), chunk, 0, keepdims=False)
            return _scan_layers(jax.tree_util.tree_map(pick, sp), x, cos_, sin_)

    def _to_vpp(tree):
        # natural layer order [V·Lv, ...] -> stage-major [P·(C·Lv), ...]
        return jax.tree_util.tree_map(
            lambda w: w.reshape((C, pp_deg, Lv) + w.shape[1:])
                       .swapaxes(0, 1).reshape(w.shape), tree)

    def _from_vpp(tree):
        return jax.tree_util.tree_map(
            lambda w: w.reshape((pp_deg, C, Lv) + w.shape[1:])
                       .swapaxes(0, 1).reshape(w.shape), tree)

    tied = "lm_head" not in params

    def head_loss_fn(hp, y, lbl, cos_, sin_):
        # hp carries exactly the keys _norm_and_head reads ('final_norm' +
        # 'embed' or 'lm_head'), so the head path stays single-sourced;
        # head_xent honors PADDLE_TPU_XENT_CHUNK per microbatch
        return head_xent(cfg, hp, y, lbl)

    head_params = {"final_norm": params["final_norm"]}
    head_params["embed" if tied else "lm_head"] = (
        params["embed"] if tied else params["lm_head"])

    # bind dp+sharding manually alongside pp when either is nontrivial: the
    # batch dim tuple-sharded over two auto axes CHECK-fails the partitioner
    # (the round-3 north-star blocker) — and manual ZeRO gathers make the
    # sharding-axis flow explicit (see one_f_one_b_stacked docstring)
    mesh_axes = dict(mesh.shape)
    batch_axes = tuple(a for a in ("dp", "sharding") if mesh_axes.get(a, 1) > 1)
    pipe_kw = {}
    if batch_axes:
        specs = param_specs(cfg, pp=True, mp=mesh_axes.get("mp", 1))
        head_specs = {"final_norm": specs["final_norm"]}
        head_specs["embed" if tied else "lm_head"] = (
            specs["embed"] if tied else specs["lm_head"])
        pipe_kw = dict(batch_axes=batch_axes,
                       zero_axis="sharding" if "sharding" in batch_axes else None,
                       embed_specs=specs["embed"],
                       stacked_specs=specs["layers"], head_specs=head_specs)

    if sep > 1:
        pipe_kw["seq_axis"] = "sep"
        pipe_kw["extra_specs"] = (P(None, "sep", None),) * 2  # rope [1, s, d]

    reorder = C > 1 and not layers_stage_major
    stacked = _to_vpp(params["layers"]) if reorder else params["layers"]
    loss, (dep, dsp, dhp) = one_f_one_b_stacked(
        embed_fn, stage_fn, head_loss_fn,
        params["embed"], stacked, head_params,
        ids_m, lbl_m, mesh, axis_name="pp", extra_args=(cos, sin),
        num_chunks=C, zero_bubble=zero_bubble, **pipe_kw)
    if reorder:
        dsp = _from_vpp(dsp)

    grads = {"final_norm": dhp["final_norm"], "layers": dsp}
    grads["embed"] = dep + dhp["embed"] if tied else dep
    if not tied:
        grads["lm_head"] = dhp["lm_head"]
    return loss, grads


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def _xent_chunk_env() -> int:
    """``PADDLE_TPU_XENT_CHUNK=<positions>`` (read at trace time, like
    PADDLE_TPU_REMAT): sequence-chunked cross-entropy.  0/unset = off."""
    raw = os.environ.get("PADDLE_TPU_XENT_CHUNK", "0")
    try:
        return int(raw)
    except ValueError:
        # a typo silently disabling chunking would resurface the exact OOM
        # the flag exists to prevent
        raise ValueError(
            f"PADDLE_TPU_XENT_CHUNK must be an integer, got {raw!r}") from None


def head_xent(cfg: LlamaConfig, params, x, labels, chunk=None):
    """final_norm + lm head + cross entropy, optionally WITHOUT materializing
    the full [b, s, V] f32 logits: with ``chunk`` set (or the
    PADDLE_TPU_XENT_CHUNK env), the head matmul + log_softmax run per
    sequence chunk inside a rematerialized lax.scan, so peak logits memory
    drops from b*s*V*4 bytes to b*chunk*V*4 (2.1 GB -> 0.5 GB for the
    bench's xl rung) at the cost of recomputing chunk logits in the
    backward — the standard memory/FLOPs trade for big-vocab heads.
    Numerics are identical (per-position log_softmax is independent)."""
    chunk = _xent_chunk_env() if chunk is None else int(chunk)
    b, s, h = x.shape
    if chunk <= 0 or s <= chunk or s % chunk:
        return _xent(_final_head(cfg, params, x), labels)
    xn, head = _norm_and_head(cfg, params, x)
    n = s // chunk
    xc = xn.reshape(b, n, chunk, h).swapaxes(0, 1)      # [n, b, chunk, h]
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def step(tot, xl):
        xck, lbl = xl
        logp = jax.nn.log_softmax((xck @ head).astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
        return tot + picked.sum(), None

    tot, _ = jax.lax.scan(step, jnp.float32(0), (xc, lc))
    return -tot / (b * s)


def loss_fn(cfg: LlamaConfig, params, input_ids, labels, attn_fn=None):
    if _xent_chunk_env() > 0:
        x = forward(cfg, params, input_ids, attn_fn=attn_fn,
                    return_hidden=True)
        return head_xent(cfg, params, x, labels)
    return _xent(forward(cfg, params, input_ids, attn_fn=attn_fn), labels)


def loss_fn_pp(cfg: LlamaConfig, params, input_ids, labels, mesh, num_microbatches,
               sep_attn_impl="ring"):
    if _xent_chunk_env() > 0:
        x = forward_pp(cfg, params, input_ids, mesh, num_microbatches,
                       sep_attn_impl=sep_attn_impl, return_hidden=True)
        return head_xent(cfg, params, x, labels)
    logits = forward_pp(cfg, params, input_ids, mesh, num_microbatches,
                        sep_attn_impl=sep_attn_impl)
    return _xent(logits, labels)


def make_mesh(dp=1, mp=1, sharding=1, sep=1, pp=1, devices=None):
    """Build the hybrid mesh with the reference's canonical axis set."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = dp * mp * sharding * sep * pp
    assert devices.size >= n, f"need {n} devices, have {devices.size}"
    arr = devices[:n].reshape(dp, pp, sharding, sep, mp)
    return Mesh(arr, axis_names=("dp", "pp", "sharding", "sep", "mp"))


def build_train_step(cfg: LlamaConfig, mesh: Mesh, lr=3e-4, weight_decay=0.1,
                     beta1=0.9, beta2=0.95, grad_clip=1.0, num_microbatches=None,
                     sep_attn_impl="ring", pipeline_schedule=None,
                     num_chunks=None):
    """The pjit-compiled train step: forward+backward+AdamW, all sharded.

    Data: [b, s] sharded ('dp'+'sharding' on batch, 'sep' on sequence).
    GSPMD propagates the Megatron weight specs through the scan; gradient psum
    over 'dp' and optimizer-state sharding over 'sharding' (ZeRO-1/2) come out
    of the same spec algebra — no per-op SPMD rules needed (SURVEY.md §3.4).
    When the mesh carries a 'pp' axis > 1, the layer stack is staged over it
    and the forward runs through the in-jit GPipe engine with
    ``num_microbatches`` (default: pp size) microbatches.  When 'sep' > 1,
    attention routes through ring attention over the sep axis
    (``sep_attn_impl``: 'ring' or 'ulysses') with the sequence sharded."""
    pp = dict(mesh.shape).get("pp", 1)
    sep = dict(mesh.shape).get("sep", 1)
    if pp > 1:
        assert cfg.num_hidden_layers % pp == 0, (
            f"{cfg.num_hidden_layers} layers not divisible by pp={pp}")
        num_microbatches = num_microbatches or pp
    # pp>1 binds sep inside its own manual region (forward_pp); otherwise wrap
    # attention in its own sep shard_map
    attn_fn = sep_attention(mesh, "sep", sep_attn_impl) if sep > 1 and pp == 1 else None
    specs = param_specs(cfg, pp=pp > 1, mp=dict(mesh.shape).get("mp", 1))

    # the executed-1F1B runner binds 'pp' plus any nontrivial dp/sharding
    # axes manually (loss_and_grads_1f1b), and since round 5 also a 'sep'
    # axis (seq-sharded microbatches + ring attention inside each stage —
    # the reference's 1F1B runtime composes with sep the same way,
    # pipeline_parallel.py:684 + topology.py:77).
    # 'vpp'/'interleave' runs the same executed runner with C>1 virtual
    # chunks per stage (num_chunks); '1f1b' is C=1; 'zb'/'zero_bubble' is
    # the executed ZB-H1 (deferred weight grads fill the drain bubble —
    # needs num_microbatches >= 2*(pp-1)+1)
    # None = auto (executed 1F1B when pp > 1); ANY explicit request that
    # can't run here raises — a schedule silently different from the
    # configured one is worse than an error
    schedule = "1f1b" if pipeline_schedule is None else pipeline_schedule
    # eager_1f1b runs the executed 1F1B clock: its deeper warmup exists to
    # overlap p2p sends with compute, which inside one jitted SPMD program
    # is already the XLA latency-hiding scheduler's job (see
    # schedule_eager_1f1b's spec oracle in fleet/pipeline.py)
    known = ("1f1b", "eager_1f1b", "vpp", "interleave", "zb", "zero_bubble",
             "gpipe", "fthenb")
    if schedule not in known:
        raise ValueError(f"unknown pipeline_schedule {schedule!r} "
                         f"(expected one of {known})")
    use_1f1b = pp > 1 and schedule in (
        "1f1b", "eager_1f1b", "vpp", "interleave", "zb", "zero_bubble")
    zb = schedule in ("zb", "zero_bubble")
    if pipeline_schedule is not None:
        if schedule in ("gpipe", "fthenb"):
            if pp <= 1:
                raise ValueError(
                    f"pipeline_schedule={pipeline_schedule!r} needs a mesh "
                    f"with pp > 1 (got pp={pp})")
        elif not use_1f1b:
            raise ValueError(
                f"pipeline_schedule={pipeline_schedule!r} needs a mesh with "
                f"pp > 1 (got pp={pp})")
    if num_chunks is not None and num_chunks > 1 and not (
            schedule in ("vpp", "interleave")):
        raise ValueError(
            f"num_chunks={num_chunks} requires pipeline_schedule="
            f"'vpp'/'interleave', got {schedule!r}")
    vpp_chunks = ((num_chunks or 2)
                  if schedule in ("vpp", "interleave") else 1)

    def loss_and_grads(params, input_ids, labels):
        if pp == 1 and sep == 1:
            # plain GSPMD program: Mosaic kernels cannot be partitioned
            # automatically, they run per shard (ops.pallas.spmd_kernels)
            def lfn(p):
                # the first kernel wants its rows split by batch: gather the
                # embedding's ZeRO-split hidden dim BEFORE the lookup (what
                # ZeRO does anyway) instead of leaving the partitioner to
                # move 'sharding' from hidden to batch on the activations
                embed = jax.lax.with_sharding_constraint(
                    p["embed"], NamedSharding(mesh, P("mp", None)))
                return loss_fn(cfg, dict(p, embed=embed), input_ids, labels)

            with pallas.spmd_kernels(mesh, ("dp", "sharding"), "mp"):
                return jax.value_and_grad(lfn)(params)
        if use_1f1b:
            return loss_and_grads_1f1b(cfg, params, input_ids, labels,
                                       mesh, num_microbatches,
                                       num_chunks=vpp_chunks,
                                       zero_bubble=zb,
                                       sep_attn_impl=sep_attn_impl)
        if pp > 1:
            lfn = lambda p: loss_fn_pp(cfg, p, input_ids, labels, mesh,
                                       num_microbatches, sep_attn_impl)
        else:
            lfn = lambda p: loss_fn(cfg, p, input_ids, labels, attn_fn)
        return jax.value_and_grad(lfn)(params)

    return adamw_train_step(mesh, specs, loss_and_grads, lr=lr,
                            weight_decay=weight_decay, beta1=beta1,
                            beta2=beta2, grad_clip=grad_clip)


def adamw_train_step(mesh: Mesh, specs, loss_and_grads, *, lr, weight_decay,
                     beta1, beta2, grad_clip, counters=None):
    """The one AdamW scaffold of the model zoo: the jitted, sharded,
    donating train step round a model's ``loss_and_grads(params, input_ids,
    labels) -> (loss, grads)``, its ``opt_init`` and the shardings, as
    ``(step_fn, opt_init, param_shardings, data_sharding)``.

    ``specs`` is the parameter tree of PartitionSpecs.  Global-norm clip,
    bias-corrected AdamW with decoupled weight decay on float32 master
    weights; the optimizer state carries the last step's pre-clip gradient
    norm (``gnorm``).  ``counters`` ({name: int32 shape}) adds cumulative
    counters to that state: ``loss_and_grads`` then returns ``(loss, grads,
    {name: this step's counts})`` and the step adds them on
    (docs/observability.md)."""
    counters = counters or {}
    data_spec = P(("dp", "sharding"), "sep")

    def to_named(tree_specs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree_specs,
            is_leaf=lambda s: isinstance(s, P),
        )

    param_shardings = to_named(specs)

    def opt_init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "v": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            # master fp32 weights (multi_precision AdamW semantics)
            "master": jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params),
            # last step's pre-clip grad global-norm: free to export (it is
            # already computed for clipping) and the multichip dryrun's
            # numerics fingerprint — loss ≈ ln(vocab) at init cannot
            # distinguish right from wrong backward compute
            "gnorm": jnp.zeros((), jnp.float32),
            **{name: jnp.zeros(shape, jnp.int32)
               for name, shape in counters.items()},
        }

    def train_step(params, opt_state, input_ids, labels):
        loss, grads, *counts = loss_and_grads(params, input_ids, labels)
        g32 = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        # global-norm clip (HybridParallelClipGrad semantics; psum over all axes
        # is implicit — the sharded sum-of-squares reduces globally under GSPMD)
        leaves = jax.tree_util.tree_leaves(g32)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        scale_f = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-6))
        step = opt_state["step"] + 1
        b1c = 1 - beta1**step.astype(jnp.float32)
        b2c = 1 - beta2**step.astype(jnp.float32)

        def upd(g, m, v, master):
            g = g * scale_f
            m2 = beta1 * m + (1 - beta1) * g
            v2 = beta2 * v + (1 - beta2) * g * g
            update = (m2 / b1c) / (jnp.sqrt(v2 / b2c) + 1e-8)
            master2 = master * (1 - lr * weight_decay) - lr * update
            return m2, v2, master2

        flat_g, treedef = jax.tree_util.tree_flatten(g32)
        flat_m = treedef.flatten_up_to(opt_state["m"])
        flat_v = treedef.flatten_up_to(opt_state["v"])
        flat_w = treedef.flatten_up_to(opt_state["master"])
        new_m, new_v, new_w = [], [], []
        for g, m, v, w in zip(flat_g, flat_m, flat_v, flat_w):
            m2, v2, w2 = upd(g, m, v, w)
            new_m.append(m2)
            new_v.append(v2)
            new_w.append(w2)
        unf = lambda leaves_: jax.tree_util.tree_unflatten(treedef, leaves_)
        new_params = jax.tree_util.tree_map(
            lambda w, p: w.astype(p.dtype), unf(new_w), params
        )
        new_opt = {"step": step, "m": unf(new_m), "v": unf(new_v),
                   "master": unf(new_w), "gnorm": gnorm}
        for name in counters:
            new_opt[name] = opt_state[name] + counts[0][name]
        return loss, new_params, new_opt

    opt_shardings = {
        "step": NamedSharding(mesh, P()),
        "m": param_shardings,
        "v": param_shardings,
        "master": param_shardings,
        "gnorm": NamedSharding(mesh, P()),
        **{name: NamedSharding(mesh, P()) for name in counters},
    }
    data_sharding = NamedSharding(mesh, data_spec)
    jitted = jax.jit(
        train_step,
        in_shardings=(param_shardings, opt_shardings, data_sharding, data_sharding),
        out_shardings=(NamedSharding(mesh, P()), param_shardings, opt_shardings),
        donate_argnums=(0, 1),
    )
    # fresh zeros in the opt state don't inherit param shardings — pin them so
    # opt_init output always matches the step's in_shardings
    opt_init = jax.jit(opt_init, out_shardings=opt_shardings)
    return jitted, opt_init, param_shardings, data_sharding


def flops_per_token(cfg: LlamaConfig) -> float:
    """Training FLOPs/token ≈ 6 * active params + attention quadratic term."""
    h, i, v, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_hidden_layers
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    per_layer = h * (nh * hd) + 2 * h * (nkv * hd) + (nh * hd) * h + 3 * h * i
    dense = L * per_layer + v * h  # + embed (lookup free)
    return 6.0 * dense


def attn_flops_per_token(cfg: LlamaConfig, seq: int, causal: bool = True) -> float:
    # 2 matmuls of [s, hd] x [hd, s] per head, fwd+bwd(2x) => 6 * 2 * s * hd * nh.
    # Causal attention only computes the lower triangle — the flash kernel
    # skips above-diagonal blocks — so the average effective kv length per
    # query is (s+1)/2, not s.  Counting the full square would overstate
    # achieved FLOPs (VERDICT r2 weak #5).
    eff = (seq + 1) / 2.0 if causal else float(seq)
    return 6.0 * 2.0 * eff * cfg.head_dim * cfg.num_attention_heads * cfg.num_hidden_layers


def count_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# eager Layer surface (paddle-style UX over the same functional core)
# ---------------------------------------------------------------------------

from ..core.tensor import Parameter, Tensor, apply_op, _unwrap  # noqa: E402
from ..nn.layer_base import Layer  # noqa: E402


class LlamaModel(Layer):
    """Eager wrapper: parameters are paddle Tensors; forward dispatches the
    functional core through the tape (so .backward()/optimizers work), and the
    same weights feed build_train_step for the pjit path."""

    def __init__(self, config: LlamaConfig, seed: int = 0):
        super().__init__()
        self.config = config
        raw = init_params(config, jax.random.key(seed))
        self._tree_names = []
        flat, self._treedef = jax.tree_util.tree_flatten_with_path(raw)
        for path, val in flat:
            name = "_".join(str(getattr(p, "key", p)) for p in path)
            self.add_parameter(name, Parameter(val))
            self._tree_names.append(name)

    def _params_tree(self, vals=None):
        leaves = [
            self._parameters[n]._value if vals is None else vals[i]
            for i, n in enumerate(self._tree_names)
        ]
        import jax.tree_util as jtu

        return jtu.tree_unflatten(jtu.tree_structure(init_spec_like(self.config)), leaves)

    def forward(self, input_ids):
        cfg = self.config
        tensors = [self._parameters[n] for n in self._tree_names]

        def fn(ids, *leaf_vals):
            params = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(init_spec_like(cfg)), list(leaf_vals)
            )
            return forward(cfg, params, ids, remat=False)

        return apply_op("llama_forward", fn, [input_ids] + tensors)


def init_spec_like(cfg: LlamaConfig):
    """Abstract pytree with the same structure as init_params (no allocation)."""
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    s = {
        "embed": 0,
        "final_norm": 0,
        "layers": {
            "input_norm": 0, "post_norm": 0, "wq": 0, "wk": 0, "wv": 0,
            "wo": 0, "w_gate": 0, "w_up": 0, "w_down": 0,
        },
    }
    if not cfg.tie_word_embeddings:
        s["lm_head"] = 0
    return s


class LlamaForCausalLM(LlamaModel):
    def forward(self, input_ids, labels=None):
        logits = super().forward(input_ids)
        if labels is None:
            return logits
        from ..nn import functional as F
        from ..ops.manipulation import reshape

        b, s, v = logits.shape
        loss = F.cross_entropy(reshape(logits, (b * s, v)), reshape(labels, (b * s,)))
        return logits, loss
