"""MoE transformer LM (BASELINE config #5: DeepSeekMoE / Qwen2-MoE class).

Reference surface: the reference's MoE stack is `MoELayer` + gates
(python/paddle/incubate/distributed/models/moe/moe_layer.py, moe/gate/) with
dispatch/combine over `global_scatter`/`global_gather` NCCL alltoall, plus the
semi-auto `moe_global_mesh_tensor` APIs (auto_parallel/api.py:495).

TPU-first design: experts are a stacked weight tensor [E, ...] sharded over the
"mp" mesh axis (expert parallelism); routing uses the dense GShard/Switch
formulation — one_hot dispatch/combine einsums with a static capacity — which
XLA lowers to an all-to-all over the expert axis on ICI (SURVEY.md §7 row
"EP").  DeepSeekMoE structure: `n_shared` always-on shared experts + `E`
routed experts with top-k token-choice gating, load-balance auxiliary loss
(Switch-style) and router z-loss.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.pallas import flash_attention as fa
from ..ops.pallas import rms_norm as rms
from ..ops.pallas import rope as rope_mod
from ..ops.pallas import swiglu as swiglu_mod


@dataclasses.dataclass
class MoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 4096       # shared-expert (dense) ffn width
    moe_intermediate_size: int = 1024   # per-routed-expert ffn width
    num_hidden_layers: int = 12
    num_attention_heads: int = 16
    num_key_value_heads: int = 4
    num_experts: int = 8
    num_shared_experts: int = 1
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    # "dense" = GShard one-hot einsum routing (O(tokens*E*C) FLOPs; compiles
    # to clean all-to-alls under EP sharding), "sort" = stable-argsort
    # scatter/gather routing (O(tokens*K) data movement — the winner at
    # DeepSeek-scale E), "ragged" = DROPLESS lax.ragged_dot grouped matmuls
    # (no capacity, no padding; opt-in — changes drop semantics),
    # "auto" = sort above _SORT_DISPATCH_MIN_EXPERTS
    dispatch: str = "auto"
    # router scores: "softmax" over the experts, or "sigmoid" of each logit
    # (DeepSeek-V3-class routers); the chosen K are normalised to sum to one
    # and multiplied by routed_scaling_factor
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # (lo, hi): the experts THIS program holds (one member of an
    # expert-parallel group).  Tokens are routed over all num_experts, the
    # e_* weights are [hi - lo, ...] and only those experts' terms are
    # summed; None = all.  Needs the ragged engine.
    experts_held: Any = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def deepseek_moe_16b():
        """DeepSeekMoE-16B structure (BASELINE ladder row #5): 64 routed +
        2 shared experts (shared width = intermediate_size * num_shared =
        2816), top-6 token-choice gating.  At E=64 the 'auto' dispatch
        resolves to the sort engine."""
        return MoEConfig(
            vocab_size=102400, hidden_size=2048, intermediate_size=1408,
            moe_intermediate_size=1408, num_hidden_layers=28,
            num_attention_heads=16, num_key_value_heads=16,
            num_experts=64, num_shared_experts=2, top_k=6,
        )

    @staticmethod
    def qwen2_moe_a14b():
        """Qwen2-57B-A14B structure: 64 routed + shared block of width
        8 * 2560 = 20480, top-8."""
        return MoEConfig(
            vocab_size=151936, hidden_size=3584, intermediate_size=2560,
            moe_intermediate_size=2560, num_hidden_layers=28,
            num_attention_heads=28, num_key_value_heads=4,
            num_experts=64, num_shared_experts=8, top_k=8,
        )

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, kv_heads=2,
             experts=4, top_k=2, inter=128, moe_inter=64):
        return MoEConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
            moe_intermediate_size=moe_inter, num_hidden_layers=layers,
            num_attention_heads=heads, num_key_value_heads=kv_heads,
            num_experts=experts, top_k=top_k, max_position_embeddings=256,
        )


def init_params(cfg: MoEConfig, key=None) -> dict:
    key = key if key is not None else jax.random.key(0)
    k = iter(jax.random.split(key, 24))
    h, i, mi, v = (cfg.hidden_size, cfg.intermediate_size,
                   cfg.moe_intermediate_size, cfg.vocab_size)
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    L, E = cfg.num_hidden_layers, cfg.num_experts
    std = 0.02

    def init(kk, shape):
        return (jax.random.normal(kk, shape, jnp.float32) * std).astype(cfg.dtype)

    return {
        "embed": init(next(k), (v, h)),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "lm_head": init(next(k), (h, v)),
        "layers": {
            "input_norm": jnp.ones((L, h), cfg.dtype),
            "post_norm": jnp.ones((L, h), cfg.dtype),
            "wq": init(next(k), (L, h, nh * hd)),
            "wk": init(next(k), (L, h, nkv * hd)),
            "wv": init(next(k), (L, h, nkv * hd)),
            "wo": init(next(k), (L, nh * hd, h)),
            # shared (dense) experts: swiglu ffn, width i * n_shared
            "s_gate": init(next(k), (L, h, i * cfg.num_shared_experts)),
            "s_up": init(next(k), (L, h, i * cfg.num_shared_experts)),
            "s_down": init(next(k), (L, i * cfg.num_shared_experts, h)),
            # router + routed experts (stacked on E)
            "router": init(next(k), (L, h, E)).astype(jnp.float32),
            "e_gate": init(next(k), (L, E, h, mi)),
            "e_up": init(next(k), (L, E, h, mi)),
            "e_down": init(next(k), (L, E, mi, h)),
        },
    }


def param_specs(cfg: MoEConfig, mp: int = 1) -> dict:
    """Experts shard over 'mp' (expert parallelism); attention is Megatron-TP
    over the same axis; ZeRO over 'sharding' like models/llama.py.  K/V
    projections replicate over 'mp' when it exceeds num_key_value_heads
    (sub-head splits trigger involuntary remat — see llama.param_specs)."""
    kv_col = None if cfg.num_key_value_heads % mp != 0 else "mp"
    return {
        "embed": P("mp", "sharding"),
        "final_norm": P(None),
        "lm_head": P("sharding", "mp"),
        "layers": {
            "input_norm": P(None, None),
            "post_norm": P(None, None),
            "wq": P(None, "sharding", "mp"),
            "wk": P(None, "sharding", kv_col),
            "wv": P(None, "sharding", kv_col),
            "wo": P(None, "mp", "sharding"),
            "s_gate": P(None, "sharding", "mp"),
            "s_up": P(None, "sharding", "mp"),
            "s_down": P(None, "mp", "sharding"),
            "router": P(None, None, None),
            "e_gate": P(None, "mp", "sharding", None),   # expert dim over mp
            "e_up": P(None, "mp", "sharding", None),
            "e_down": P(None, "mp", None, "sharding"),
        },
    }


def serving_param_specs(cfg: MoEConfig, axis: str = "tp") -> dict:
    """Serving-mesh TP *placement layout* for the MoE param tree: residual
    stream / embed / norms / router / lm_head replicated, attention split
    along (kv_)heads via the shared MEGATRON_SPLIT table, shared-expert ffn
    column/row-split, routed experts split on the EXPERT dim (expert
    compute shard-local, all-to-all dispatch/combine between shards).

    WEIGHT LAYOUT ONLY — no forward in this module consumes it yet: the
    continuous-batching engine's TP mode (docs/tp_serving.md) runs the
    dense llama decoder, whose shard_map bodies insert the per-layer psum
    boundaries themselves (llama.decoder_attn_residual /
    decoder_mlp_residual).  A sharded MoE serve additionally needs those
    reductions plus the expert dispatch collectives wired into
    ``_layer_forward``/``moe_ffn`` — the fleet-tier work this layout is
    staged for (ROADMAP item 2).  Sharding params with these specs and
    calling the existing single-chip forward inside a manual mesh region
    would produce unreduced partial sums."""
    from .llama import MEGATRON_SPLIT

    def mat(name):
        if MEGATRON_SPLIT[name] == "col":
            return P(None, None, axis)
        return P(None, axis, None)

    return {
        "embed": P(),
        "final_norm": P(),
        "lm_head": P(),
        "layers": {
            "input_norm": P(None, None),
            "post_norm": P(None, None),
            "wq": mat("wq"), "wk": mat("wk"), "wv": mat("wv"),
            "wo": mat("wo"),
            # shared (dense) experts: same column/row split as llama's mlp
            "s_gate": P(None, None, axis),
            "s_up": P(None, None, axis),
            "s_down": P(None, axis, None),
            "router": P(None, None, None),      # replicated: routing must
                                                # agree across shards
            "e_gate": P(None, axis, None, None),   # expert dim over tp
            "e_up": P(None, axis, None, None),
            "e_down": P(None, axis, None, None),
        },
    }


# auto dispatch switches to the sort path above this expert count: at E<=8
# the dense one-hot einsums are small and shard perfectly over EP meshes; past
# that the O(tokens*E*C) dispatch FLOPs dominate step time (round-3 verdict:
# DeepSeek-scale E=64 makes dense routing the bottleneck)
_SORT_DISPATCH_MIN_EXPERTS = 9


def moe_ffn(cfg: MoEConfig, x, lp):
    """Routed-expert FFN for x: [b, s, h] → (out, aux_loss, z_loss).

    Three dispatch engines behind one routing front-end (cfg.dispatch):

    * dense — GShard one-hot formulation: capacity-bounded dispatch tensor
      [g, E, C] → einsum into per-expert batches [E, C, h] → swiglu → combine.
      Under GSPMD with e_* sharded on 'mp' this compiles to
      all-to-all(dispatch) + expert-local matmuls + all-to-all(combine), the
      exact dataflow of the reference's global_scatter/global_gather
      (python/paddle/distributed/utils/moe_utils.py).
    * sort — stable argsort of (token, k) pairs by expert id, scatter into a
      static [E*C, h] buffer, gather back after expert compute.  O(g*K*h)
      data movement instead of O(g*E*C*h) einsum FLOPs; identical numerics
      (same within-expert ordering, same capacity drops) — the scalable path
      for DeepSeek-class expert counts (reference moe_layer.py routes through
      variable-size global_scatter for the same reason).
    * ragged — DROPLESS ``lax.ragged_dot`` grouped matmuls (no capacity,
      no padding, keeps tokens GShard would drop).  Opt-in only: drop
      semantics differ from dense/sort, and GSPMD cannot usefully shard the
      ragged group dimension, so under an expert-parallel mesh the expert
      weights are gathered to each device — prefer sort/dense for EP
      meshes, ragged for single-device or pure-dp serving/training.
    """
    b, s, h = x.shape
    E, K = cfg.num_experts, cfg.top_k
    g = b * s
    xf = x.reshape(g, h)

    topk_p, topk_i, probs, logits = route_topk(
        xf, lp["router"], K, cfg.router_scoring, cfg.routed_scaling_factor)
    # z-loss: keeps router logits small (numerics at scale)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    cap = int(np.ceil(cfg.capacity_factor * K * g / E))
    cap = max(cap, 1)

    # aux load-balance loss (Switch: E * sum_e f_e * P_e)
    frac_tokens = jnp.mean(jax.nn.one_hot(topk_i[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    mode = resolved_dispatch(cfg)
    if cfg.experts_held is not None and mode != "ragged":
        raise ValueError("experts_held needs dispatch='ragged': the "
                         "capacity engines compute every expert")
    route = {"sort": _dispatch_sort, "ragged": _dispatch_ragged,
             "dense": _dispatch_dense}[mode]
    out = route(cfg, xf, lp, topk_p, topk_i, cap)
    return out.reshape(b, s, h), aux, z_loss


def route_topk(xf, router, top_k, scoring="softmax", scale=1.0, groups=None):
    """The routing front end: xf [g, h] -> (weights [g, K] float32, experts
    [g, K], scores [g, E], logits [g, E]).  Scores are computed in float32
    over ALL experts; the K largest are normalised to sum to one and
    multiplied by ``scale``.

    ``groups`` (the rows are that many equal runs: a batch's sequences)
    chooses the K by each logit's standard score over its run (less the
    expert's mean there, over its deviation): what the run's rows share in
    their logits, and how far an expert's logits swing, move no choice, so
    rows that have run together are still spread evenly over the experts.
    The weights come from the scores as they are, and no gradient passes
    through the choice."""
    logits = xf.astype(jnp.float32) @ router                   # [g, E]
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"router scoring must be 'softmax'|'sigmoid', "
                         f"got {scoring!r}")
    if groups is None:
        topk_p, topk_i = jax.lax.top_k(scores, top_k)          # [g, K]
    else:
        runs = jax.lax.stop_gradient(logits).reshape(groups, -1,
                                                     logits.shape[-1])
        centred = runs - runs.mean(axis=1, keepdims=True)
        standard = centred * jax.lax.rsqrt(
            jnp.mean(jnp.square(centred), axis=1, keepdims=True) + 1e-12)
        _, topk_i = jax.lax.top_k(standard.reshape(logits.shape), top_k)
        topk_p = jnp.take_along_axis(scores, topk_i, axis=1)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        topk_p = topk_p * scale
    return topk_p, topk_i, scores, logits


def resolved_dispatch(cfg: MoEConfig) -> str:
    """The dispatch engine a config actually runs: 'dense'|'sort'|'ragged'."""
    mode = cfg.dispatch
    if mode == "auto":
        mode = ("sort" if cfg.num_experts >= _SORT_DISPATCH_MIN_EXPERTS
                else "dense")
    if mode not in ("dense", "sort", "ragged"):
        raise ValueError(
            f"MoEConfig.dispatch must be 'auto'|'dense'|'sort'|'ragged', "
            f"got {cfg.dispatch!r}")
    return mode


def _expert_compute(lp, expert_in):
    """Per-expert swiglu FFN on stacked batches [E, C, h] → [E, C, h]."""
    gate = jnp.einsum("ech,ehm->ecm", expert_in, lp["e_gate"])
    up = jnp.einsum("ech,ehm->ecm", expert_in, lp["e_up"])
    act = swiglu_mod.swiglu(gate, up)
    return jnp.einsum("ecm,emh->ech", act, lp["e_down"])


def _dispatch_dense(cfg, xf, lp, topk_p, topk_i, cap):
    g, h = xf.shape
    E, K = cfg.num_experts, cfg.top_k

    # position of each (token, k) within its expert queue, counted in
    # flattened (token, k) row-major order
    onehot = jax.nn.one_hot(topk_i, E, dtype=jnp.int32)        # [g, K, E]
    flat = onehot.reshape(g * K, E)
    pos = jnp.cumsum(flat, axis=0) - flat                      # slots before me
    pos = (pos * flat).sum(-1).reshape(g, K)                   # [g, K]
    keep = pos < cap                                           # drop overflow

    # dispatch/combine tensors from one-hot einsums
    oh_e = jax.nn.one_hot(topk_i, E, dtype=xf.dtype)           # [g, K, E]
    oh_c = jax.nn.one_hot(pos, cap, dtype=xf.dtype) * keep[..., None]  # [g, K, C]
    combine = jnp.einsum("gke,gkc,gk->gec", oh_e, oh_c, topk_p.astype(xf.dtype))
    dispatch = jnp.einsum("gke,gkc->gec", oh_e, oh_c)

    expert_in = jnp.einsum("gec,gh->ech", dispatch, xf)        # [E, C, h]
    expert_out = _expert_compute(lp, expert_in)
    return jnp.einsum("gec,ech->gh", combine, expert_out)


def _dispatch_ragged(cfg, xf, lp, topk_p, topk_i, cap):
    """DROPLESS dispatch over ``lax.ragged_dot`` (the TPU-native grouped
    matmul; MegaBlocks-style): (token, k) pairs stable-sorted by expert form
    contiguous groups, and the three expert matmuls run as ragged dots with
    per-expert group sizes — no capacity, no padding FLOPs, no dropped
    tokens.  ``cap`` is ignored; numerics match dense/sort exactly when no
    capacity drops occur (cap_factor >= E), and otherwise keep the tokens
    GShard would drop — a quality/perf point, not a parity point, so it is
    opt-in (cfg.dispatch='ragged'), never chosen by 'auto'."""
    out, _ = ragged_experts(xf, lp, topk_p, topk_i, cfg.num_experts,
                            cfg.experts_held)
    return out


# rows of one chunk of the grouped products over a SHARE of the experts: this
# much above the rows a uniform router sends to the held experts
_HELD_ROWS_SLACK = 1.25


def held_rows(g: int, top_k: int, num_experts: int, n_held: int) -> tuple:
    """(rows, chunks) of the grouped products over ``n_held`` of
    ``num_experts`` for ``g`` tokens: ``chunks * rows`` holds the worst case
    (every token choosing ``min(top_k, n_held)`` held experts), so nothing
    is ever dropped at static shapes; ``rows`` is the expected load plus a
    quarter.  The first chunk always runs, at static shapes (the step's
    time does not move with the router while the held experts' load is
    within it); the chunks behind it run while they hold live rows."""
    worst = g * min(top_k, n_held)
    if n_held == num_experts:
        return worst, 1
    expected = g * top_k * n_held / num_experts
    rows = -(-int(np.ceil(_HELD_ROWS_SLACK * expected)) // 256) * 256
    rows = min(rows, worst)
    return rows, -(-worst // rows)


def ragged_experts(xf, lp, topk_p, topk_i, num_experts, held=None):
    """The grouped expert FFN of the ragged engine on xf [g, h] for the
    experts ``held = (lo, hi)`` (None: all): ``lp["e_*"]`` are
    ``[hi - lo, ...]``.  Assignments to absent experts sort behind the held
    ones and are never gathered.  The sorted assignments are taken
    ``rows`` at a time (``held_rows``): the first chunk always, the rest by
    a loop that runs as many chunks as hold work, forward and backward
    (``_chunked_experts``), so the worst case costs memory for one chunk
    and time for the chunks it fills.

    Returns (out [g, h], stats): ``held`` [hi - lo] assignments to each held
    expert, ``total`` all g * K assignments, ``rows`` the rows the grouped
    products were given (padding included), ``dropped`` the held
    assignments that no chunk covered (0 by construction; counted from the
    group sizes actually handed to ``ragged_dot``)."""
    g, h = xf.shape
    K = topk_i.shape[1]
    N = g * K
    lo, hi = held or (0, num_experts)
    n_held = hi - lo
    rows, chunks = held_rows(g, K, num_experts, n_held)

    with jax.named_scope("moe/dispatch"):
        flat_e = topk_i.reshape(N)
        local = jnp.where((flat_e >= lo) & (flat_e < hi), flat_e - lo, n_held)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        counts = jnp.bincount(local, length=n_held + 1)[:n_held].astype(
            jnp.int32)
        ends = jnp.cumsum(counts)
        starts = ends - counts
        n_live = ends[-1]
        pad = chunks * rows - N
        if pad > 0:
            order = jnp.concatenate([order, jnp.zeros((pad,), order.dtype)])
        flat_w = topk_p.reshape(N).astype(jnp.float32)
    weights = (lp["e_gate"], lp["e_up"], lp["e_down"])
    if chunks == 1:
        at, tok, sizes, live = _chunk_rows(order, starts, ends, n_live, 0,
                                           rows, K)
        ys = _expert_rows(xf[tok], *weights, flat_w[at], live, sizes)
        with jax.named_scope("moe/combine"):
            y = jnp.zeros((g, h), jnp.float32).at[tok].add(ys)
        covered, ran = sizes.sum(), jnp.int32(1)
    else:
        y, covered, ran = _chunked_experts(
            xf, *weights, flat_w, order, starts, ends, n_live, rows, chunks,
            K)
    stats = {"held": counts, "total": jnp.int32(N),
             "rows": (ran * rows).astype(jnp.int32),
             "dropped": (n_live - covered).astype(jnp.int32)}
    return y.astype(xf.dtype), stats


def _chunk_rows(order, starts, ends, n_live, first, rows, K):
    """Rows [first, first + rows) of the sorted assignments: (assignment of
    each row, its token, the group sizes of this chunk, which rows are
    live)."""
    with jax.named_scope("moe/dispatch"):
        at = jax.lax.dynamic_slice(order, (first,), (rows,))
        sizes = jnp.clip(jnp.minimum(ends, first + rows)
                         - jnp.maximum(starts, first), 0, rows)
        live = first + jnp.arange(rows, dtype=jnp.int32) < n_live
        return at, at // K, sizes, live


def _expert_rows(xs, e_gate, e_up, e_down, w, live, sizes):
    """xs [rows, h] grouped by expert -> each row's expert output times its
    router weight, float32; rows past the live ones give zeros.  A grouped
    product leaves the rows past its groups unwritten, in the backward pass
    too: both ends are selected by ``live``, so that what lies there never
    reaches a token."""
    xs = jnp.where(live[:, None], xs, 0)
    with jax.named_scope("moe/experts"):
        gate = jax.lax.ragged_dot(xs, e_gate, sizes)
        up = jax.lax.ragged_dot(xs, e_up, sizes)
        act = swiglu_mod.swiglu(gate, up)
        ys = jax.lax.ragged_dot(act, e_down, sizes)
    with jax.named_scope("moe/combine"):
        ys = jnp.where(live[:, None], ys.astype(jnp.float32), 0.0)
        return ys * jnp.where(live, w, 0.0)[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _chunked_experts(xf, e_gate, e_up, e_down, flat_w, order, starts, ends,
                     n_live, rows, chunks, K):
    """(y [g, h] float32, held assignments covered, chunks run): the held
    experts' weighted outputs summed onto their tokens, ``rows`` sorted
    assignments at a time: the first chunk always, then as many chunks as
    hold live rows (a ``while_loop``: no further chunk is computed, or kept
    for the backward pass, that holds none).  Its own VJP walks the same
    chunks again and recomputes each, so neither pass holds more than one
    chunk."""
    stop = jnp.minimum(n_live, chunks * rows)

    def body(carry):
        first, y, covered = carry
        at, tok, sizes, live = _chunk_rows(order, starts, ends, n_live,
                                           first, rows, K)
        ys = _expert_rows(xf[tok], e_gate, e_up, e_down, flat_w[at], live,
                          sizes)
        with jax.named_scope("moe/combine"):
            return first + rows, y.at[tok].add(ys), covered + sizes.sum()

    first, y, covered = jax.lax.while_loop(
        lambda c: c[0] < stop, body,
        body((jnp.int32(0), jnp.zeros(xf.shape, jnp.float32), jnp.int32(0))))
    return y, covered, first // rows


def _chunked_experts_fwd(xf, e_gate, e_up, e_down, flat_w, order, starts,
                         ends, n_live, rows, chunks, K):
    out = _chunked_experts(xf, e_gate, e_up, e_down, flat_w, order, starts,
                           ends, n_live, rows, chunks, K)
    return out, (xf, e_gate, e_up, e_down, flat_w, order, starts, ends,
                 n_live)


def _chunked_experts_bwd(rows, chunks, K, res, cts):
    xf, e_gate, e_up, e_down, flat_w, order, starts, ends, n_live = res
    dy = cts[0]
    stop = jnp.minimum(n_live, chunks * rows)

    def body(carry):
        first, dxf, dws, dflat = carry
        at, tok, sizes, live = _chunk_rows(order, starts, ends, n_live,
                                           first, rows, K)
        _, vjp = jax.vjp(
            lambda xs, eg, eu, ed, w: _expert_rows(xs, eg, eu, ed, w, live,
                                                   sizes),
            xf[tok], e_gate, e_up, e_down, flat_w[at])
        dxs, *dw, dwr = vjp(dy[tok])
        with jax.named_scope("moe/dispatch"):
            dxf = dxf.at[tok].add(dxs.astype(jnp.float32))
            # dead rows repeat assignment 0: their cotangent is zero
            dflat = dflat.at[at].add(dwr)
        return (first + rows, dxf,
                tuple(a + b for a, b in zip(dws, dw)), dflat)

    _, dxf, dws, dflat = jax.lax.while_loop(
        lambda c: c[0] < stop, body,
        body((jnp.int32(0), jnp.zeros(xf.shape, jnp.float32),
              tuple(jnp.zeros_like(w) for w in (e_gate, e_up, e_down)),
              jnp.zeros_like(flat_w))))
    return (dxf.astype(xf.dtype), *dws, dflat, None, None, None, None)


_chunked_experts.defvjp(_chunked_experts_fwd, _chunked_experts_bwd)


def _dispatch_sort(cfg, xf, lp, topk_p, topk_i, cap):
    g, h = xf.shape
    E, K = cfg.num_experts, cfg.top_k
    N = g * K

    flat_e = topk_i.reshape(N)                                 # expert per (t,k)
    # stable sort groups (token, k) pairs by expert while preserving the
    # row-major (token, k) order within each expert — the same order the
    # dense path's cumsum assigns, so capacity drops are bit-identical
    order = jnp.argsort(flat_e, stable=True)                   # [N]
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)                    # [E]
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(N, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_sorted < cap
    slot = sorted_e * cap + pos_sorted                         # [N] in [0, E*cap)
    tok = order // K                                           # source token

    # scatter tokens to their expert slots (overflow routed out-of-bounds and
    # dropped); slots are unique so set() has no collision ambiguity
    buf = jnp.zeros((E * cap, h), xf.dtype)
    buf = buf.at[jnp.where(keep, slot, E * cap)].set(xf[tok], mode="drop")
    expert_out = _expert_compute(lp, buf.reshape(E, cap, h))

    out_flat = expert_out.reshape(E * cap, h)
    gathered = out_flat[jnp.where(keep, slot, 0)] * keep[:, None].astype(xf.dtype)
    w = topk_p.reshape(N)[order].astype(xf.dtype)              # [N]
    y = jnp.zeros((g, h), xf.dtype)
    return y.at[tok].add(gathered * w[:, None])


def _layer_forward(cfg: MoEConfig, x, lp, cos, sin, use_flash=True):
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    xn = rms.rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    q = (xn @ lp["wq"]).reshape(b, s, nh, hd)
    kk = (xn @ lp["wk"]).reshape(b, s, nkv, hd)
    vv = (xn @ lp["wv"]).reshape(b, s, nkv, hd)
    q, kk = rope_mod.apply_rotary_pos_emb(q, kk, cos, sin)
    if use_flash:
        attn = fa.flash_attention_bshd(q, kk, vv, causal=True)
    else:
        import math

        attn = fa._composed_attention(q, kk, vv, None, True, 1.0 / math.sqrt(hd))
    # shared sharded decoder half (models/llama.py): the attention output
    # projection + residual — and, under tensor parallelism, TP boundary 1 —
    # have one home for the dense and MoE decoders alike.  The stage-2
    # fused layer tail (llama.decoder_layer_tail's mlp_fn hook, docs/
    # paged_attention.md "Megastep stage 2") is dense-decoder-only: the
    # MoE MLP half is shared-expert + routed experts, not the single
    # swiglu block the fused MLP kernel streams, so MoE keeps the
    # explicit two-half composition until MoE serving (ROADMAP item 4)
    # grows its own fused tail
    from .llama import decoder_attn_residual

    x = decoder_attn_residual(x, attn.reshape(b, s, nh * hd), lp)

    xn = rms.rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
    shared = swiglu_mod.swiglu(xn @ lp["s_gate"], xn @ lp["s_up"]) @ lp["s_down"]
    routed, aux, z = moe_ffn(cfg, xn, lp)
    return x + shared + routed, aux, z


def forward(cfg: MoEConfig, params, input_ids, use_flash=True, remat=True,
            return_aux=False):
    x = jnp.take(params["embed"], input_ids, axis=0).astype(cfg.dtype)
    b, s, _ = x.shape
    cos, sin = rope_mod.rope_cos_sin(s, cfg.head_dim, base=cfg.rope_theta,
                                     dtype=cfg.dtype)

    def body(carry, lp):
        x, aux, z = carry
        x2, a, zz = _layer_forward(cfg, x, lp, cos, sin, use_flash)
        return (x2, aux + a, z + zz), None

    scan_body = jax.checkpoint(body) if remat else body
    (x, aux, z), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        params["layers"])
    x = rms.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = x @ params["lm_head"]
    if return_aux:
        return logits, aux / cfg.num_hidden_layers, z / cfg.num_hidden_layers
    return logits


def loss_fn(cfg: MoEConfig, params, input_ids, labels):
    logits, aux, z = forward(cfg, params, input_ids, return_aux=True)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = -jnp.mean(picked)
    return ce + cfg.aux_loss_weight * aux + cfg.z_loss_weight * z


def make_mesh(dp=1, mp=1, sharding=1, sep=1, pp=1, devices=None):
    from . import llama

    return llama.make_mesh(dp=dp, mp=mp, sharding=sharding, sep=sep, pp=pp,
                           devices=devices)


def build_train_step(cfg: MoEConfig, mesh: Mesh, lr=3e-4, weight_decay=0.1,
                     beta1=0.9, beta2=0.95, grad_clip=1.0):
    """models/llama's AdamW scaffold round the MoE loss (ce + aux + z)."""
    from .llama import adamw_train_step

    specs = param_specs(cfg, mp=dict(mesh.shape).get("mp", 1))

    def loss_and_grads(params, input_ids, labels):
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, p, input_ids, labels))(params)

    return adamw_train_step(mesh, specs, loss_and_grads, lr=lr,
                            weight_decay=weight_decay, beta1=beta1,
                            beta2=beta2, grad_clip=grad_clip)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def active_params_per_token(cfg: MoEConfig) -> int:
    """Active (per-token) parameter count — the MoE MFU denominator."""
    h, i, mi = cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    per_layer = (h * nh * hd + 2 * h * nkv * hd + nh * hd * h
                 + 3 * h * i * cfg.num_shared_experts
                 + 3 * h * mi * cfg.top_k + h * cfg.num_experts)
    return cfg.num_hidden_layers * per_layer + 2 * cfg.vocab_size * h
