"""Laguna family (poolside Laguna-XS.2 / Laguna-S-2.1): a decoder whose
layers are NOT alike.

What the published config asks of the model zoo that ``llama`` and
``moe_llama`` (one stacked ``params["layers"]`` scanned by one body) do not
have:

- ``layer_types``: full and sliding-window attention mixed, with a
  different number of query heads on each (``num_attention_heads_per_layer``)
  over the same KV heads, and a rope of its own for each kind
  (``rope_parameters``: yarn on half of the head on the full layers, plain
  rope on the whole head on the sliding ones);
- ``gating``: a sigmoid gate a query head on the attention output;
- ``mlp_layer_types``: a leading dense SwiGLU layer, then sigmoid-routed
  experts (top-k normalised, scaled by ``moe_routed_scaling_factor``) beside
  one shared expert;
- ``experts_held = (lo, hi)``: the experts THIS program holds, one member's
  share of an expert-parallel group.  Every token is routed over all
  ``num_experts``; the held experts' terms are summed and the absent ones'
  left out (``moe_llama.ragged_experts``), with no assignment dropped, and
  the routing weights carry no gradient (a share's part of it is not the
  router's gradient).

Layers are grouped so that compile time does not grow with depth
(:func:`layer_groups`): ``lead`` layers before the pattern repeats, one
``lax.scan`` over the repetitions of the ``period`` (each position of the
period stacked separately, since positions differ in shape), and the
``rest`` of a last partial repetition.  Training goes through
``llama.adamw_train_step``, the zoo's one AdamW scaffold, with the MoE
counters riding in the optimizer state (docs/observability.md)."""

from __future__ import annotations

import dataclasses
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
from . import llama, moe_llama

FULL, SLIDING = "full_attention", "sliding_attention"
MAX_PERIOD = 8

# the cumulative counters the step keeps in the optimizer state, one row an
# expert layer (docs/observability.md "Training counters"): name -> the
# statistic of ``moe_llama.ragged_experts`` it sums
COUNTERS = {"moe_assignments_held": "held", "moe_assignments_total": "total",
            "moe_rows_computed": "rows", "moe_assignments_dropped": "dropped"}


@dataclasses.dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192           # the dense layers' SwiGLU width
    num_hidden_layers: int = 40
    num_attention_heads: int = 48           # where no per-layer list is given
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_attention_heads_per_layer: Any = None
    layer_types: Any = None                 # FULL | SLIDING a layer
    mlp_layer_types: Any = None             # "dense" | "sparse" a layer
    sliding_window: int = 512
    rope_parameters: Any = None             # {layer type: its rope}
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    gating: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    experts_held: Any = None                # (lo, hi); None = all
    # "sequence_standard": the K experts of a token are chosen by its router
    # logits' standard scores over the token's sequence (training's load
    # balancing, ``moe_llama.route_topk(groups=)``); "scores": by the scores
    router_selection: str = "scores"
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.num_hidden_layers

        def per_layer(given, default, what):
            vals = tuple(given) if given is not None else (default,) * L
            if len(vals) < L:
                raise ValueError(f"{what} names {len(vals)} layers, "
                                 f"num_hidden_layers is {L}")
            return vals[:L]     # a cut in depth keeps the published lists

        self.layer_types = per_layer(self.layer_types, FULL, "layer_types")
        self.mlp_layer_types = per_layer(self.mlp_layer_types, "sparse",
                                         "mlp_layer_types")
        self.num_attention_heads_per_layer = per_layer(
            self.num_attention_heads_per_layer, self.num_attention_heads,
            "num_attention_heads_per_layer")
        if self.rope_parameters is None:
            self.rope_parameters = {
                FULL: {"rope_type": "default", "rope_theta": 10000.0},
                SLIDING: {"rope_type": "default", "rope_theta": 10000.0}}
        self.experts_held = (tuple(int(e) for e in self.experts_held)
                             if self.experts_held is not None
                             else (0, self.num_experts))
        if self.router_selection not in ("scores", "sequence_standard"):
            raise ValueError("router_selection must be 'scores'|"
                             f"'sequence_standard', got "
                             f"{self.router_selection!r}")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.num_experts} experts")
        for i, (kind, nh, mlp) in enumerate(self.signatures):
            if kind not in (FULL, SLIDING) or mlp not in ("dense", "sparse"):
                raise ValueError(f"layer {i}: unknown kind {(kind, mlp)}")
            if nh % self.num_key_value_heads:
                raise ValueError(f"layer {i}: {nh} query heads over "
                                 f"{self.num_key_value_heads} KV heads")

    @property
    def signatures(self) -> tuple:
        """(attention kind, query heads, mlp kind) of every layer: what
        makes two layers' weights and programs alike."""
        return tuple(zip(self.layer_types, self.num_attention_heads_per_layer,
                         self.mlp_layer_types))

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_expert_layers(self) -> int:
        return sum(m == "sparse" for m in self.mlp_layer_types)

    @classmethod
    def from_dict(cls, m: dict, **over):
        """The published ``config.json`` keys as they are (unknown keys are
        ignored; ``torch_dtype`` names ``dtype``), plus ``experts_held``."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in m.items() if k in names}
        if "torch_dtype" in m:
            kw["dtype"] = {"bfloat16": jnp.bfloat16,
                           "float32": jnp.float32}[m["torch_dtype"]]
        kw.update(over)
        return cls(**kw)

    @staticmethod
    def laguna_xs2(**over):
        """Laguna-XS.2 as published: layer 0 full attention + dense MLP,
        then ``S S S F`` nine times and ``S S S``, all sparse."""
        L = 40
        kinds = [FULL if i % 4 == 0 else SLIDING for i in range(L)]
        kw = dict(
            layer_types=kinds,
            num_attention_heads_per_layer=[48 if k == FULL else 64
                                           for k in kinds],
            mlp_layer_types=["dense"] + ["sparse"] * (L - 1),
            rope_parameters={
                FULL: {"rope_type": "yarn", "rope_theta": 500000.0,
                       "factor": 64, "original_max_position_embeddings": 4096,
                       "beta_fast": 64, "beta_slow": 1,
                       "attention_factor": 1.4158883083359672,
                       "partial_rotary_factor": 0.5},
                SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
                          "partial_rotary_factor": 1}})
        kw.update(over)
        return LagunaConfig(**kw)

    @staticmethod
    def tiny(layers=9, experts=16, top_k=4, held=None, vocab=256, window=32):
        """The published pattern at toy widths: hidden 64, head 16, a
        leading dense layer, then ``S S S F`` periods."""
        kinds = [FULL if i % 4 == 0 else SLIDING for i in range(layers)]
        xs2 = LagunaConfig.laguna_xs2().rope_parameters
        return LagunaConfig(
            vocab_size=vocab, hidden_size=64, intermediate_size=128,
            num_hidden_layers=layers, num_key_value_heads=2, head_dim=16,
            layer_types=kinds,
            num_attention_heads_per_layer=[4 if k == FULL else 6
                                           for k in kinds],
            mlp_layer_types=["dense"] + ["sparse"] * (layers - 1),
            sliding_window=window,
            rope_parameters={
                FULL: dict(xs2[FULL], original_max_position_embeddings=64),
                SLIDING: xs2[SLIDING]},
            num_experts=experts, num_experts_per_tok=top_k,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            max_position_embeddings=4096, experts_held=held)


def config_from_dict(m: dict) -> LagunaConfig:
    return LagunaConfig.from_dict(m)


# ---------------------------------------------------------------- the grouping

def layer_groups(cfg: LagunaConfig) -> tuple:
    """(lead, period, repeats, rest): layer numbers ``lead`` run one by
    one, then ``repeats`` repetitions of a pattern of ``period`` layers run
    as one scan, then the first ``rest`` positions of the pattern once more.
    A layer leads if no later layer is like it (the dense first layer);
    after those, the shortest pattern that the remaining layers repeat."""
    sig, L = cfg.signatures, cfg.num_hidden_layers
    first = 0
    while first < L and sig[first] not in sig[first + 1:]:
        first += 1
    for lead in range(first, L):
        for period in range(1, min(MAX_PERIOD, L - lead) + 1):
            if all(sig[i] == sig[lead + (i - lead) % period]
                   for i in range(lead, L)):
                return (lead, period) + divmod(L - lead, period)
    return L, 0, 0, 0       # no pattern within MAX_PERIOD: every layer alone


def _group_layers(cfg: LagunaConfig) -> dict:
    """{group: [layer number of each entry]}; a period entry's number is its
    first repetition's."""
    lead, period, repeats, rest = layer_groups(cfg)
    after = lead + period * repeats
    return {"lead": list(range(lead)),
            "period": list(range(lead, lead + period)),
            "rest": list(range(after, after + rest))}


def _layer_shapes(cfg: LagunaConfig, i: int) -> dict:
    h, hd = cfg.hidden_size, cfg.head_dim
    kind, nh, mlp = cfg.signatures[i]
    kv = cfg.num_key_value_heads * hd
    shapes = {"input_norm": (h,), "post_norm": (h,),
              "wq": (h, nh * hd), "wk": (h, kv), "wv": (h, kv),
              "wo": (nh * hd, h)}
    if cfg.gating:
        shapes["wg"] = (h, nh)
    if mlp == "dense":
        i_ = cfg.intermediate_size
        shapes.update(w_gate=(h, i_), w_up=(h, i_), w_down=(i_, h))
    else:
        si, mi = cfg.shared_expert_intermediate_size, cfg.moe_intermediate_size
        shapes.update(router=(h, cfg.num_experts),
                      s_gate=(h, si), s_up=(h, si), s_down=(si, h),
                      e_gate=(cfg.n_held, h, mi), e_up=(cfg.n_held, h, mi),
                      e_down=(cfg.n_held, mi, h))
    return shapes


def param_shapes(cfg: LagunaConfig) -> dict:
    """The parameter tree as shapes.  ``layers`` holds the three groups of
    :func:`layer_groups`, each entry keyed by its number as a string; a
    ``period`` entry is stacked over the repetitions."""
    repeats = layer_groups(cfg)[2]
    layers = {}
    for group, numbers in _group_layers(cfg).items():
        stack = (repeats,) if group == "period" else ()
        if numbers:
            layers[group] = {
                str(n): {k: stack + s
                         for k, s in _layer_shapes(cfg, i).items()}
                for n, i in enumerate(numbers)}
    return {"embed": (cfg.vocab_size, cfg.hidden_size),
            "final_norm": (cfg.hidden_size,),
            "lm_head": (cfg.hidden_size, cfg.vocab_size),
            "layers": layers}


def _is_shape(s) -> bool:
    return isinstance(s, tuple)


def _leaf_name(path) -> str:
    return str(path[-1].key)


def init_params(cfg: LagunaConfig, key=None) -> dict:
    """normal(0, 0.02) matrices in ``cfg.dtype`` (the router in float32, as
    ``moe_llama``'s), norm gains of one."""
    key = key if key is not None else jax.random.key(0)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    leaves = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = _leaf_name(path)
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, cfg.dtype))
        else:
            w = jax.random.normal(k, shape, jnp.float32) * 0.02
            leaves.append(w if name == "router" else w.astype(cfg.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# column-parallel leaves split their last dim over 'mp', row-parallel ones
# the dim before it; experts split on their expert dim
_COL = ("wq", "wg", "w_gate", "w_up", "s_gate", "s_up")
_ROW = ("wo", "w_down", "s_down")


def param_specs(cfg: LagunaConfig, mp: int = 1) -> dict:
    """Megatron splits over 'mp' and ZeRO over 'sharding' as models/llama's:
    K/V projections replicate over 'mp' when it does not divide the KV
    heads; the held experts split on their expert dim."""
    kv_col = "mp" if cfg.num_key_value_heads % mp == 0 else None

    def spec(path, shape):
        name = _leaf_name(path)
        lead = (None,) * _stack_dims(name, shape)
        if name == "embed":
            return P("mp", "sharding")
        if name == "lm_head":
            return P("sharding", "mp")
        if name in _COL:
            return P(*lead, "sharding", "mp")
        if name in ("wk", "wv"):
            return P(*lead, "sharding", kv_col)
        if name in _ROW:
            return P(*lead, "mp", "sharding")
        if name in ("e_gate", "e_up"):
            return P(*lead, "mp", "sharding", None)
        if name == "e_down":
            return P(*lead, "mp", None, "sharding")
        return P(*(None,) * len(shape))       # norms, router

    return jax.tree_util.tree_map_with_path(spec, param_shapes(cfg),
                                            is_leaf=_is_shape)


def _stack_dims(name: str, shape: tuple) -> int:
    """Leading dims of a leaf that are a period entry's repetitions."""
    own = 3 if name.startswith("e_") else 1 if name.endswith("norm") else 2
    return len(shape) - own


# ------------------------------------------------------------------ the layer

def rope_tables(cfg: LagunaConfig, seq: int) -> dict:
    """{layer type: (cos, sin) [1, s, r]} in float32, each by its own
    ``rope_parameters`` entry (``partial_rotary_factor`` of the head rotates,
    ``yarn`` blends the frequencies)."""
    out = {}
    for kind in set(cfg.layer_types):
        rp = cfg.rope_parameters[kind]
        rope_type = rp.get("rope_type", "default")
        if rope_type not in ("default", "yarn"):
            raise ValueError(f"unknown rope_type {rope_type!r}")
        out[kind] = rope_mod.rope_cos_sin(
            seq, cfg.head_dim, base=float(rp["rope_theta"]),
            rotary_dim=int(round(cfg.head_dim
                                 * rp.get("partial_rotary_factor", 1))),
            yarn=rp if rope_type == "yarn" else None)
    return out


def sparse_mlp(cfg: LagunaConfig, xn, lp):
    """Shared expert + the held experts' part of the routed sum on xn
    [b, s, h] -> (y, stats of ``moe_llama.ragged_experts``)."""
    b, s, h = xn.shape
    xf = xn.reshape(b * s, h)
    with jax.named_scope("moe/shared"):
        shared = swiglu_mod.swiglu(xf @ lp["s_gate"],
                                   xf @ lp["s_up"]) @ lp["s_down"]
    with jax.named_scope("moe/route"):
        w, experts, _, _ = moe_llama.route_topk(
            xf, lp["router"], cfg.num_experts_per_tok, "sigmoid",
            cfg.moe_routed_scaling_factor,
            groups=b if cfg.router_selection == "sequence_standard" else None)
        if cfg.n_held < cfg.num_experts:
            # a member has its own experts' terms of the router's gradient
            # only, a part that tells held experts from absent ones as no
            # whole gradient does: left out, as the absent experts' terms are
            w = jax.lax.stop_gradient(w)
    routed, stats = moe_llama.ragged_experts(
        xf, lp, w, experts, cfg.num_experts, cfg.experts_held)
    return (shared + routed).reshape(b, s, h), stats


def _layer_forward(cfg: LagunaConfig, sig, x, lp, tables, use_flash=True):
    """One block of signature ``sig`` on x [b, s, h] -> (x, stats | None)."""
    kind, nh, mlp = sig
    b, s, h = x.shape
    nkv, hd = cfg.num_key_value_heads, cfg.head_dim
    xn = rms.rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    q = (xn @ lp["wq"]).reshape(b, s, nh, hd)
    kk = (xn @ lp["wk"]).reshape(b, s, nkv, hd)
    vv = (xn @ lp["wv"]).reshape(b, s, nkv, hd)
    q, kk = rope_mod.apply_rotary_pos_emb(q, kk, *tables[kind])
    window = cfg.sliding_window if kind == SLIDING else None
    with jax.named_scope("attn_window" if window else "attn_full"):
        if use_flash:
            attn = fa.flash_attention_bshd(q, kk, vv, causal=True,
                                           window=window)
        else:
            attn = fa._composed_attention(q, kk, vv, None, True,
                                          1.0 / np.sqrt(hd), window)
    if cfg.gating:
        gate = jax.nn.sigmoid((xn @ lp["wg"]).astype(jnp.float32))
        attn = (attn * gate[..., None]).astype(x.dtype)
    x = llama.decoder_attn_residual(x, attn.reshape(b, s, nh * hd), lp)
    if mlp == "dense":
        return llama.decoder_mlp_residual(cfg, x, lp), None
    y, stats = sparse_mlp(
        cfg, rms.rms_norm(x, lp["post_norm"], cfg.rms_norm_eps), lp)
    return x + y, stats


def forward(cfg: LagunaConfig, params, input_ids, use_flash=True, remat=True,
            return_hidden=False, return_stats=False):
    """Logits (or the last hidden states) for [b, s] token ids.  Every layer
    is recomputed in the backward pass (``llama._remat_wrap``,
    PADDLE_TPU_REMAT).  ``return_stats`` adds the expert layers' counts, in
    layer order: {"held" [layers, n_held], "total", "rows", "dropped"
    [layers]}."""
    x = jnp.take(params["embed"], input_ids, axis=0).astype(cfg.dtype)
    tables = rope_tables(cfg, x.shape[1])
    numbers = _group_layers(cfg)
    groups = params["layers"]
    stats = []          # one {name: [n, ...]} an expert layer or stack

    def block(i):
        return llama._remat_wrap(
            lambda x, lp: _layer_forward(cfg, cfg.signatures[i], x, lp,
                                         tables, use_flash), remat)

    def run_single(x, group):
        for n, i in enumerate(numbers[group]):
            x, st = block(i)(x, groups[group][str(n)])
            if st is not None:
                stats.append({k: v[None] for k, v in st.items()})
        return x

    x = run_single(x, "lead")
    if numbers["period"]:
        blocks = [block(i) for i in numbers["period"]]

        def period(x, lps):
            out = []
            for f, lp in zip(blocks, lps):
                x, st = f(x, lp)
                if st is not None:
                    out.append(st)
            return x, out

        x, per = jax.lax.scan(
            period, x, tuple(groups["period"][str(n)]
                             for n in range(len(blocks))))
        if per:
            # [position][repeat, ...] -> repeat-major, as the layers run
            stats.append({k: jnp.stack([p[k] for p in per], axis=1).reshape(
                (-1,) + per[0][k].shape[1:]) for k in per[0]})
    x = run_single(x, "rest")
    out = x if return_hidden else llama._final_head(cfg, params, x)
    if not return_stats:
        return out
    if not stats:
        empty = jnp.zeros((0,), jnp.int32)
        return out, {"held": jnp.zeros((0, cfg.n_held), jnp.int32),
                     "total": empty, "rows": empty, "dropped": empty}
    return out, {k: jnp.concatenate([s[k] for s in stats]) for k in stats[0]}


def loss_fn(cfg: LagunaConfig, params, input_ids, labels, return_stats=False):
    """Mean next-token cross entropy through ``llama.head_xent`` (chunked
    under PADDLE_TPU_XENT_CHUNK); no auxiliary loss."""
    x, stats = forward(cfg, params, input_ids, return_hidden=True,
                       return_stats=True)
    loss = llama.head_xent(cfg, params, x, labels)
    return (loss, stats) if return_stats else loss


make_mesh = llama.make_mesh


def build_train_step(cfg: LagunaConfig, mesh: Mesh, lr=3e-4, weight_decay=0.1,
                     beta1=0.9, beta2=0.95, grad_clip=1.0):
    """``llama.adamw_train_step`` round this model's loss: (step_fn,
    opt_init, param_shardings, data_sharding) as ``llama.build_train_step``
    returns them.  The optimizer state also carries the cumulative MoE
    counters ``COUNTERS``, one row an expert layer."""
    shape = dict(mesh.shape)
    if shape.get("pp", 1) > 1 or shape.get("sep", 1) > 1:
        raise ValueError("models/laguna trains over dp / sharding / mp "
                         "meshes; it has no pipeline or context-parallel "
                         "path")
    specs = param_specs(cfg, mp=shape.get("mp", 1))
    n = cfg.n_expert_layers
    counters = {name: (n, cfg.n_held) if stat == "held" else (n,)
                for name, stat in COUNTERS.items()}

    def loss_and_grads(params, input_ids, labels):
        def lfn(p):
            embed = jax.lax.with_sharding_constraint(
                p["embed"], NamedSharding(mesh, P("mp", None)))
            return loss_fn(cfg, dict(p, embed=embed), input_ids, labels,
                           return_stats=True)

        with pallas.spmd_kernels(mesh, ("dp", "sharding"), "mp"):
            (loss, st), grads = jax.value_and_grad(lfn, has_aux=True)(params)
        return loss, grads, {name: st[stat] for name, stat in COUNTERS.items()}

    return llama.adamw_train_step(
        mesh, specs, loss_and_grads, lr=lr, weight_decay=weight_decay,
        beta1=beta1, beta2=beta2, grad_clip=grad_clip, counters=counters)
