"""Olmo-Hybrid family (allenai Olmo-Hybrid-7B): a decoder whose layers keep
two kinds of state.

``layer_types`` mixes ``linear_attention`` layers — Gated DeltaNet
(arXiv:2412.06464, with the negative-eigenvalue switch of arXiv:2411.12537):
a head keeps a recurrent state ``M`` of [d_k, d_v] float32 and the last
``linear_conv_kernel_dim - 1`` inputs of a depthwise causal conv, both of
fixed size whatever the context — with ``full_attention`` layers that keep
K/V rows a position, three of the first to one of the second as published.
With ``x`` a layer's input (Olmo 2/3 block order: the norm sits on a
sublayer's OUTPUT, ``h = x + norm(mix(x))``, ``h = h + norm(mlp(h))``):

- linear: ``q, k, v = silu(conv(W_q x | W_k x | W_v x))``; ``q =
  l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)``; ``beta = 2 sigmoid(W_b x)``
  (the 2 is ``linear_allow_neg_eigval``); ``g = -exp(A_log) softplus(W_a x
  + dt_bias)``, ``alpha = exp(g)``; ``M <- alpha M``, ``M <- M + k (beta (v
  - k^T M))^T``, ``o = q^T M``; ``y = W_o [rmsnorm_dv(o) * silu(W_g x)]``;
- full: RMS norm over the whole of q and of k before the heads are split,
  no rotary embedding (``rope_parameters.rope_theta`` is null in the
  published configuration: the recurrent layers carry position; another
  value is refused), causal softmax attention.

The recurrence has three forms that share projections, conv and gated norm
(:func:`linear_mixer`): :func:`gated_delta_rule_recurrent` (one token,
state in and out), :func:`gated_delta_rule_chunked` (a block of rows from a
start state, per-row validity) and, for tests, :func:`forward` over whole
sequences.  Layers are grouped by period (``L L L F``) and one ``lax.scan``
runs over the repetitions, so a program's size does not grow with depth.

Serving (:class:`ServingProgram`, docs/hybrid_serving.md): the
continuous-batching engine takes its decode and mixed step programs and its
cache from here — a paged K/V pool for the full layers and, for the linear
layers, a per-slot unpaged state and conv window."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..inference import lm_head_logits
from ..ops.pallas import gated_delta as gd
from ..ops.pallas import rms_norm as rms

LINEAR, FULL = "linear_attention", "full_attention"

#: why an engine option is refused for a model with recurrent state; every
#: message names the roadmap item that keeps what is left
REFUSED = {
    "enable_prefix_caching": "a cached block holds K/V rows but no "
    "recurrent state at its boundary",
    "enable_speculation": "a rejected draft's rows cannot be rolled back "
    "out of the recurrent state",
    "enable_host_kv_tier": "the host tier ships K/V pages, not the "
    "recurrent state",
    "kv_quant": "the quantized-pool programs are the dense model's",
    "tensor_parallel": "the recurrent state's heads are not sharded",
}


@dataclasses.dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: Any = None         # None: as many as query heads
    head_dim: Any = None                    # None: hidden / heads
    layer_types: Any = None                 # LINEAR | FULL a layer
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: Any = None             # {"rope_theta": None}: no rope
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        types = (tuple(self.layer_types) if self.layer_types is not None
                 else (LINEAR, LINEAR, LINEAR, FULL) * (-(-L // 4)))
        if len(types) < L:
            raise ValueError(f"layer_types names {len(types)} layers, "
                             f"num_hidden_layers is {L}")
        self.layer_types = types[:L]   # a cut in depth keeps the published list
        if set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"unknown layer types in {self.layer_types}")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("linear layers with fewer key heads than value "
                             "heads are not implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        self.rope_parameters = dict(self.rope_parameters
                                    or {"rope_theta": None})
        if self.rope_parameters.get("rope_theta") is not None:
            raise ValueError(
                "rope_parameters.rope_theta is null in the published "
                "configuration (no rotary embedding on the full layers) "
                "and no configuration in the benchmark carries another "
                "value: a rotary embedding is not implemented")

    @classmethod
    def from_dict(cls, m: dict, **over):
        """From a published ``config.json`` (further keys are ignored)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in m.items() if k in names}
        if "torch_dtype" in m and "dtype" not in over:
            kw["dtype"] = {"bfloat16": jnp.bfloat16,
                           "float32": jnp.float32}[m["torch_dtype"]]
        kw.update(over)
        return cls(**kw)

    @property
    def period(self) -> tuple:
        """The shortest pattern of layer types whose repetitions make the
        whole stack (a stack that ends inside a repetition is one period)."""
        t = self.layer_types
        for n in range(1, len(t) + 1):
            if len(t) % n == 0 and t == t[:n] * (len(t) // n):
                return t[:n]
        return t

    @property
    def n_rep(self) -> int:
        return self.num_hidden_layers // len(self.period)

    @property
    def n_linear(self) -> int:
        return sum(t == LINEAR for t in self.layer_types)

    @property
    def n_full(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def state_bytes_per_slot(self) -> int:
        """Recurrent state and conv window of one slot, every linear layer."""
        H = self.linear_num_value_heads
        s = H * self.linear_key_head_dim * self.linear_value_head_dim * 4
        w = ((self.linear_conv_kernel_dim - 1) * self.conv_channels
             * jnp.dtype(self.dtype).itemsize)
        return self.n_linear * (s + w)

    # ---- the serving engine's seam (docs/hybrid_serving.md) ----

    def check_serving_options(self, **opts) -> None:
        """Raise for every engine option a model with recurrent state
        cannot honour (``opts``: the options as the engine resolved them)."""
        why = []
        if not (opts.get("paged") and opts.get("enable_chunked_prefill")):
            why.append("paged=True and enable_chunked_prefill=True are "
                       "required: a prompt enters the recurrent state "
                       "through the mixed step's chunks only")
        for name, reason in REFUSED.items():
            v = opts.get(name)
            if v and not (name == "tensor_parallel" and int(v) <= 1):
                why.append(f"{name} is refused: {reason}")
        if why:
            raise ValueError(
                "olmo_hybrid (a model with recurrent state): "
                + "; ".join(why) + " (ROADMAP B-I.4)")

    def serving_program(self, geometry):
        return ServingProgram(self, geometry)


def config_from_dict(m: dict, **over) -> OlmoHybridConfig:
    return OlmoHybridConfig.from_dict(m, **over)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: OlmoHybridConfig) -> dict:
    """The tree the programs take: ``linear`` and ``full`` leaves stacked
    over the layers of their kind, in layer order."""
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    Ll, Lf = cfg.n_linear, cfg.n_full
    mlp = lambda L: {"attn_norm": (L, h), "mlp_norm": (L, h),
                     "w_gate": (L, h, i), "w_up": (L, h, i),
                     "w_down": (L, i, h)}
    tree = {
        "embed": (v, h), "final_norm": (h,),
        "linear": {"wq": (Ll, h, H * dk), "wk": (Ll, h, H * dk),
                   "wv": (Ll, h, H * dv), "wg": (Ll, h, H * dv),
                   "wa": (Ll, h, H), "wb": (Ll, h, H),
                   "conv_w": (Ll, cfg.linear_conv_kernel_dim,
                              cfg.conv_channels),
                   "A_log": (Ll, H), "dt_bias": (Ll, H),
                   "o_norm": (Ll, dv), "wo": (Ll, H * dv, h), **mlp(Ll)},
        "full": {"wq": (Lf, h, nh * hd), "wk": (Lf, h, nkv * hd),
                 "wv": (Lf, h, nkv * hd), "wo": (Lf, nh * hd, h),
                 "q_norm": (Lf, nh * hd), "k_norm": (Lf, nkv * hd),
                 **mlp(Lf)},
    }
    if not cfg.tie_word_embeddings:
        tree["lm_head"] = (h, v)
    return tree


def init_params(cfg: OlmoHybridConfig, key, std: float = 0.02) -> dict:
    """normal(0, ``std``) matrices in ``cfg.dtype``, norm gains of one;
    ``A_log`` = log U(1, 16) and ``dt_bias`` the inverse softplus of a step
    drawn log-uniformly from [1e-3, 1e-1] (Gated DeltaNet's ranges), both
    float32."""
    is_shape = lambda s: isinstance(s, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=is_shape)
    leaves = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = str(path[-1].key)
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, cfg.dtype))
        elif name == "A_log":
            leaves.append(jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0)))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            leaves.append(dt + jnp.log(-jnp.expm1(-dt)))
        else:
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * std).astype(cfg.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# the recurrence, by name
# ---------------------------------------------------------------------------

def gated_delta_rule_recurrent(q, k, v, g, beta, state, live=None,
                               fresh=None, layer=None):
    """One token a lane.  q, k [B, H, dk], v [B, H, dv], g (log alpha), beta
    [B, H]; ``state`` [B, H, dk, dv] float32, or the layers' stacked
    [L, B, H, dk, dv] with ``layer`` naming the one.  A lane that is not
    ``live`` leaves its state as it was (alpha 1, beta 0); a ``fresh`` lane
    starts from zeros.  Returns (o [B, H, dv] float32, state)."""
    alpha = jnp.exp(g.astype(jnp.float32))
    if live is not None:
        alpha = jnp.where(live[:, None], alpha, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    return gd.gdn_decode_step(q, k, v, alpha, beta, state, fresh, layer)


def gated_delta_rule_chunked(q, k, v, g, beta, state, valid, fresh=None,
                             layer=None):
    """A block of ``T`` rows a lane from a start state.  q, k [B, T, H, dk],
    v [B, T, H, dv], g, beta [B, T, H], valid [B, T]: a dead row leaves the
    state as it was (alpha 1, beta 0).  ``state`` as in
    :func:`gated_delta_rule_recurrent`.  Returns (o [B, T, H, dv] float32,
    state)."""
    return gd.gdn_chunk_prefill(q, k, v, g, beta, state, valid, fresh, layer)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _norm(x, w, eps):
    return rms.rms_norm_ref(x, w, eps)


def mlp_residual(cfg, lp, h):
    """``h + norm(mlp(h))``: SwiGLU, the norm on the output."""
    y = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    return h + _norm(y, lp["mlp_norm"], cfg.rms_norm_eps)


def causal_conv(cfg, lp, x, window, n_live):
    """Depthwise causal conv of width K over each lane's rows, then SiLU.
    x [B, T, C] the rows' inputs, of which the first ``n_live`` [B] are
    live; window [B, K-1, C] the last inputs before row 0.  Returns (y
    [B, T, C], the window after the lane's last live row)."""
    K = cfg.linear_conv_kernel_dim
    T = x.shape[1]
    xin = jnp.concatenate([window.astype(x.dtype), x], axis=1)
    w = lp["conv_w"].astype(jnp.float32)
    y = sum(xin[:, i:i + T].astype(jnp.float32) * w[i] for i in range(K))
    at = n_live[:, None] + jnp.arange(K - 1)[None, :]
    window = jnp.take_along_axis(xin, at[..., None], axis=1)
    return jax.nn.silu(y).astype(x.dtype), window


def linear_mixer(cfg, lp, x, state, window, valid, fresh, unpack, pack,
                 layer=None):
    """The linear-attention sublayer over rows ``x`` [R, h] -> (y [R, h],
    state, window).  Everything row-wise runs over the ``R`` rows as they
    are given; only the conv and the recurrence see lanes: ``unpack`` takes
    [R, d] rows to [B, T, d] (dead rows read as zeros) and ``pack`` takes
    [B, T, d] back.  ``valid`` [B, T] marks the live rows, a prefix of each
    lane's; ``fresh`` [B] the lanes that start from zeros.  ``state`` is
    the layer's [B, H, dk, dv] or the stack with ``layer``; ``window`` the
    layer's [B, K-1, C].  ``T`` == 1 takes the one-token recurrent form,
    anything wider the chunked one."""
    f32 = jnp.float32
    H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    with jax.named_scope("attn_linear"):
        qkv = jnp.concatenate([x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]],
                              axis=-1)
        gate = x @ lp["wg"]
        g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
            (x @ lp["wa"]).astype(f32) + lp["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid((x @ lp["wb"]).astype(f32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g, beta = unpack(g), unpack(beta)                   # [B, T, H]
        B, T = valid.shape
        with jax.named_scope("gdn/conv"):
            window = jnp.where(fresh[:, None, None], 0, window)
            y, window = causal_conv(cfg, lp, unpack(qkv), window,
                                    valid.sum(axis=1, dtype=jnp.int32))
        q, k, v = jnp.split(y.astype(f32), [H * dk, 2 * H * dk], axis=-1)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        q = unit(q.reshape(B, T, H, dk)) * dk ** -0.5
        k = unit(k.reshape(B, T, H, dk))
        v = v.reshape(B, T, H, dv)
        if T == 1:
            with jax.named_scope("gdn/decode"):
                o, state = gated_delta_rule_recurrent(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                    valid[:, 0], fresh, layer)
                o = o[:, None]
        else:
            with jax.named_scope("gdn/chunk"):
                o, state = gated_delta_rule_chunked(q, k, v, g, beta, state,
                                                    valid, fresh, layer)
        o = pack(o.reshape(B, T, H * dv))                   # [R, H dv] f32
        with jax.named_scope("gdn/gate_norm"):
            o = o.reshape(-1, H, dv)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps)
            o = (o * lp["o_norm"].astype(f32)).reshape(-1, H * dv)
            o = (o * jax.nn.silu(gate.astype(f32))).astype(x.dtype)
        return o @ lp["wo"], state, window


def full_mixer(cfg, lp, x, attend):
    """The full-attention sublayer over rows ``x`` [R, h]: RMS norm over the
    whole of q and of k, then ``attend(q [R, nh, hd], k [R, nkv, hd], v)
    -> [R, nh * hd]``."""
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    with jax.named_scope("attn_full"):
        q = _norm(x @ lp["wq"], lp["q_norm"], cfg.rms_norm_eps)
        k = _norm(x @ lp["wk"], lp["k_norm"], cfg.rms_norm_eps)
        v = x @ lp["wv"]
        o = attend(q.reshape(-1, nh, hd), k.reshape(-1, nkv, hd),
                   v.reshape(-1, nkv, hd))
        return o.astype(x.dtype) @ lp["wo"]


def identity_rope(cfg, positions):
    """(cos, sin) [..., head_dim] of no rotation, 1 and 0: what the fused
    decode kernel, which rotates inside its launch, is given for a model
    without a rotary embedding."""
    shape = positions.shape + (cfg.head_dim,)
    return jnp.ones(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def run_layers(cfg, params, x, caches, linear_fn, full_fn):
    """The layer stack over rows ``x`` [R, h]: one ``lax.scan`` over the
    repetitions of the period.  ``caches`` is the model's whole cache
    (carried); ``linear_fn(lp, x, caches, l) -> (y, caches)`` and
    ``full_fn(lp, x, caches, l) -> (y, caches)`` mix layer ``l`` of their
    kind (a traced index into the stacked leaves they own).  Returns (the
    final-normed rows, caches)."""
    period = cfg.period
    n_lin = sum(t == LINEAR for t in period)
    n_full = len(period) - n_lin
    # a layer's weights are read where they lie in the stacked leaves, by a
    # traced index: nothing is sliced out of the stack a repetition
    at = lambda tree, l: jax.tree_util.tree_map(
        lambda a: _layer(a, l), tree)

    def body(carry, r):
        x, caches = carry
        il = jf = 0
        for kind in period:
            if kind == LINEAR:
                l = r * n_lin + il
                lp = at(params["linear"], l)
                y, caches = linear_fn(lp, x, caches, l)
                il += 1
            else:
                l = r * n_full + jf
                lp = at(params["full"], l)
                y, caches = full_fn(lp, x, caches, l)
                jf += 1
            x = x + _norm(y, lp["attn_norm"], cfg.rms_norm_eps)
            x = mlp_residual(cfg, lp, x)
        return (x, caches), None

    (x, caches), _ = jax.lax.scan(body, (x, caches), jnp.arange(cfg.n_rep))
    return _norm(x, params["final_norm"], cfg.rms_norm_eps), caches


def append_rows(pool, pages, start, n_rows, rows):
    """Write each lane's ``n_rows`` [B] new K (or V) rows ``rows`` [B, T,
    nkv, hd] into a paged pool [N, nkv, block, hd], a PAGE at a time: the
    lane's rows land in consecutive positions from offset ``start`` [B] of
    its page ``pages[:, 0]`` on through ``pages[:, 1:]`` ([B, J] physical
    page ids, J = the most pages T rows can touch).  The touched pages are
    gathered, their new rows selected in, and scattered back whole — a
    scatter over the pool's leading dimension, in place — where a
    row-granular scatter at ``pool[page, :, offset]`` makes XLA transpose
    the whole pool there and back."""
    N, _, bs, _ = pool.shape
    B, T = rows.shape[:2]
    J = pages.shape[1]
    t = (jnp.arange(J * bs)[None, :] - start[:, None])          # [B, J bs]
    ok = (t >= 0) & (t < n_rows[:, None])
    new = jnp.take_along_axis(
        rows, jnp.clip(t, 0, T - 1)[:, :, None, None], axis=1)
    new = new.reshape(B, J, bs, *rows.shape[2:]).transpose(0, 1, 3, 2, 4)
    ok = ok.reshape(B, J, bs)
    old = jnp.take(pool, pages, axis=0, mode="clip")    # [B, J, nkv, bs, hd]
    merged = jnp.where(ok[:, :, None, :, None], new.astype(pool.dtype), old)
    at = jnp.where(ok.any(axis=-1), pages, N)           # untouched: dropped
    return pool.at[at.reshape(-1)].set(
        merged.reshape(B * J, *pool.shape[1:]), mode="drop")


def _layer(tree, l):
    return jax.lax.dynamic_index_in_dim(tree, l, axis=0, keepdims=False)


def _put(tree, l, value):
    return jax.lax.dynamic_update_index_in_dim(tree, value.astype(tree.dtype),
                                               l, axis=0)


def forward(cfg, params, ids):
    """Logits [B, T, V] of whole sequences ``ids`` [B, T] from empty state
    (tests): the chunked recurrence on the linear layers, dense causal
    attention on the full ones."""
    B, T = ids.shape
    H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    x = jnp.take(params["embed"], ids.reshape(-1), axis=0).astype(cfg.dtype)
    valid = jnp.ones((B, T), bool)
    fresh = jnp.ones((B,), bool)
    caches = {
        "state": jnp.zeros((cfg.n_linear, B, H, dk, dv), jnp.float32),
        "conv": jnp.zeros((cfg.n_linear, B, cfg.linear_conv_kernel_dim - 1,
                           cfg.conv_channels), cfg.dtype)}
    unpack = lambda rows: rows.reshape(B, T, *rows.shape[1:])
    pack = lambda bt: bt.reshape(B * T, *bt.shape[2:])

    def linear_fn(lp, x, caches, l):
        y, state, window = linear_mixer(
            cfg, lp, x, caches["state"], _layer(caches["conv"], l), valid,
            fresh, unpack, pack, layer=l)
        return y, {"state": state, "conv": _put(caches["conv"], l, window)}

    def attend(q, k, v):
        q, k = unpack(q), unpack(k)
        q = q.reshape(B, T, nkv, nh // nkv, hd)
        s = jnp.einsum("bsngd,btnd->bngst", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        o = jnp.einsum("bngst,btnd->bsngd", jax.nn.softmax(s, axis=-1),
                       unpack(v).astype(jnp.float32))
        return o.reshape(B * T, nh * hd)

    full_fn = lambda lp, x, caches, l: (full_mixer(cfg, lp, x, attend),
                                        caches)
    x, _ = run_layers(cfg, params, x, caches, linear_fn, full_fn)
    return lm_head_logits(cfg, params, x).reshape(B, T, -1)


# ---------------------------------------------------------------------------
# the serving engine's step programs and cache
# ---------------------------------------------------------------------------

class ServingProgram:
    """What ``ContinuousBatchingEngine`` takes from a model that brings its
    own layers (docs/hybrid_serving.md).  The cache pair the compiled steps
    carry and donate:

    - ``cache_k = {"pages": [L_full, pages, nkv, block, hd], "state":
      [L_lin, max_batch, H, dk, dv] float32}``
    - ``cache_v = {"pages": the same, "conv": [L_lin, max_batch, K-1, C]}``

    Pages belong to whoever the allocator's block table says; a slot's
    state and window belong to the slot and are started from zeros BY THE
    PROGRAM wherever a live lane's first row sits at position 0 — so
    admission, a preempted request's re-prefill and journal replay need no
    reset from the host."""

    def __init__(self, cfg: OlmoHybridConfig, geometry):
        self.cfg = cfg
        self.geo = geometry

    def init_cache(self):
        cfg, g = self.cfg, self.geo
        H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        pages = (cfg.n_full, g.pool_pages, cfg.num_key_value_heads,
                 g.block_size, cfg.head_dim)
        return ({"pages": jnp.zeros(pages, cfg.dtype),
                 "state": jnp.zeros((cfg.n_linear, g.max_batch, H, dk, dv),
                                    jnp.float32)},
                {"pages": jnp.zeros(pages, cfg.dtype),
                 "conv": jnp.zeros((cfg.n_linear, g.max_batch,
                                    cfg.linear_conv_kernel_dim - 1,
                                    cfg.conv_channels), cfg.dtype)})

    def pages(self, cache):
        """The paged pool of one of the pair (the allocator's and the
        auditor's view)."""
        return cache["pages"]

    def model_id(self) -> str:
        """What a journal's restore target has to agree on: every field
        that changes the teacher-forced recompute's logits."""
        c = self.cfg
        kinds = "".join("L" if t == LINEAR else "F" for t in c.period)
        return (f"olmo_hybrid:v{c.vocab_size}:h{c.hidden_size}"
                f":L{c.num_hidden_layers}:{kinds}"
                f":nh{c.num_attention_heads}:nkv{c.num_key_value_heads}"
                f":lin{c.linear_num_value_heads}x{c.linear_key_head_dim}"
                f"x{c.linear_value_head_dim}:conv{c.linear_conv_kernel_dim}"
                f":neg{int(bool(c.linear_allow_neg_eigval))}"
                f":i{c.intermediate_size}"
                f":tie{int(bool(c.tie_word_embeddings))}"
                f":dt{jnp.dtype(c.dtype).name}"
                f":eps{c.rms_norm_eps:g}")

    def state_bytes(self) -> int:
        return self.geo.max_batch * self.cfg.state_bytes_per_slot

    def launch_counters(self, launch: dict) -> dict:
        """What one launch adds to ``engine.stats``.  ``launch`` is what
        the engine's launch site knows: ``lanes`` (B x T a mixed step, B x
        chunk a decode step: the rows the recurrence kernel is given, live
        or dead), ``rows_live`` of them that carry a token, ``lanes_live``
        slots whose state the launch reads, ``starts`` of them from zero,
        and ``mixed``: whether the chunked kernel walks them (its part is
        counted apart, so that each kernel's roofline has its own live
        rows; the decode kernel's is the rest)."""
        out = {"gdn_rows_computed": launch["lanes"],
               "gdn_rows_live": launch["rows_live"],
               "state_slot_steps_live": launch["lanes_live"],
               "state_starts": launch["starts"]}
        if launch["mixed"]:
            out.update(gdn_chunk_rows_live=launch["rows_live"],
                       state_chunk_slot_steps_live=launch["lanes_live"])
        return out

    # ---- the two step programs ----

    def _layers(self, params, x, cache_k, cache_v, valid, fresh, unpack,
                pack, attend_pages):
        """The stack over rows ``x``.  The full layers' pools go through
        as ONE array of ``L_full * pages`` pages and ``attend_pages(q, k,
        v, pool_k, pool_v, base)`` reaches layer ``l``'s pages by adding
        ``base = l * pages`` to every page index, the block table's too: a
        kernel then reads and writes the carried pool in place, and no
        layer's pool is sliced out of the stack and written back."""
        cfg, n = self.cfg, self.geo.pool_pages
        shape = cache_k["pages"].shape
        flat = lambda pool: pool.reshape(shape[0] * n, *shape[2:])
        caches = {"k": flat(cache_k["pages"]), "v": flat(cache_v["pages"]),
                  "state": cache_k["state"], "conv": cache_v["conv"]}

        def linear_fn(lp, x, c, l):
            y, state, window = linear_mixer(
                cfg, lp, x, c["state"], _layer(c["conv"], l), valid, fresh,
                unpack, pack, layer=l)
            return y, dict(c, state=state, conv=_put(c["conv"], l, window))

        def full_fn(lp, x, c, l):
            pools = {}

            def attend(q, k, v):
                o, pools["k"], pools["v"] = attend_pages(
                    q, k, v, c["k"], c["v"], l * n)
                return o

            y = full_mixer(cfg, lp, x, attend)
            return y, dict(c, **pools)

        x, c = run_layers(cfg, params, x, caches, linear_fn, full_fn)
        return (x, {"pages": c["k"].reshape(shape), "state": c["state"]},
                {"pages": c["v"].reshape(shape), "conv": c["conv"]})

    def decode_one(self, params, cache_k, cache_v, tokens, pos, active,
                   table):
        """One token a slot: tokens, pos, active [B] -> (logits [B, V],
        cache pair).  The dense engine's ``_decode_one`` for this model."""
        from ..ops import decode_attention as _da

        cfg, g = self.cfg, self.geo
        B, S, bs = g.max_batch, g.max_seq, g.block_size
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        live = active & (pos < S)
        safe = jnp.where(live, pos, 0)
        fresh = live & (pos == 0)
        cos, sin = identity_rope(cfg, safe)     # the fused launch's tables
        blk = table[jnp.arange(B), safe // bs]
        off = safe % bs

        def attend_pages(q, k, v, ck, cv, base):
            if g.fused:
                # rope + page append + attention in one launch; a dropped
                # write lands on the layer's spill page
                spill = jnp.int32(g.num_blocks)
                wblk = jnp.where(live, jnp.minimum(blk, spill), spill)
                o, ck, cv = _da.fused_paged_decode_step(
                    q, k, v, cos, sin, ck, cv, table + base, safe,
                    wblk + base, live)
            else:
                pages, n = (blk + base)[:, None], live.astype(jnp.int32)
                ck = append_rows(ck, pages, off, n, k[:, None])
                cv = append_rows(cv, pages, off, n, v[:, None])
                o = _da.paged_decode_attention(q, ck, cv, table + base,
                                               safe + 1)
            return o.reshape(B, nh * hd), ck, cv

        x, ck, cv = self._layers(
            params, x, cache_k, cache_v, live[:, None], fresh,
            lambda rows: rows[:, None], lambda bt: bt[:, 0], attend_pages)
        return lm_head_logits(cfg, params, x), ck, cv

    def mixed_one(self, params, cache_k, cache_v, tokens, pos, active,
                  q_lens, table):
        """One unified prefill/decode forward: tokens [B, T], pos, q_lens
        [B] -> (emit-row logits [B, V], cache pair).  The row-wise work
        runs over the ``P`` packed live rows as the dense engine's
        ``_mixed_one`` packs them; the conv and the recurrence unpack to
        [B, T] beside attention."""
        from ..ops import decode_attention as _da

        cfg, g = self.cfg, self.geo
        B, S, bs, P = g.max_batch, g.max_seq, g.block_size, g.mixed_rows
        T = tokens.shape[1]
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        pos_t = pos[:, None] + jnp.arange(T)[None, :]
        valid = (active[:, None] & (jnp.arange(T)[None, :] < q_lens[:, None])
                 & (pos_t < S))
        fresh = valid[:, 0] & (pos == 0)
        # the pages a lane's rows can touch, from its first row's on
        J = (T + bs - 2) // bs + 1
        first = jnp.where(valid[:, 0], pos, 0)
        blocks = jnp.minimum(first[:, None] // bs + jnp.arange(J)[None, :],
                             table.shape[1] - 1)
        pages = jnp.take_along_axis(table, blocks, axis=1)      # [B, J]
        n_rows = valid.sum(axis=1, dtype=jnp.int32)

        live = valid.reshape(B * T)
        idx = jnp.argsort(~live, stable=True)[:P].astype(jnp.int32)
        inv = jnp.where(live, jnp.cumsum(live, dtype=jnp.int32) - 1,
                        P).reshape(B, T)
        x = jnp.take(params["embed"], tokens.reshape(B * T)[idx],
                     axis=0).astype(cfg.dtype)                  # [P, h]
        unpack = lambda rows: jnp.take(rows, inv, axis=0, mode="fill",
                                       fill_value=0)
        pack = lambda bt: bt.reshape(B * T, *bt.shape[2:])[idx]
        seq_base = jnp.where(active & (pos < S), pos, 0)
        seq_now = jnp.minimum(seq_base + jnp.where(active, q_lens, 1), S)

        def attend_pages(q, k, v, ck, cv, base):
            ck = append_rows(ck, pages + base, first % bs, n_rows, unpack(k))
            cv = append_rows(cv, pages + base, first % bs, n_rows, unpack(v))
            o = _da.paged_prefill_attention(unpack(q), ck, cv, table + base,
                                            seq_now, q_lens)
            return pack(o.reshape(B, T, nh * hd)), ck, cv

        x, ck, cv = self._layers(params, x, cache_k, cache_v, valid, fresh,
                                 unpack, pack, attend_pages)
        n_live = valid.sum(axis=1, dtype=jnp.int32)
        emit = jnp.take_along_axis(
            inv, jnp.maximum(n_live - 1, 0)[:, None], axis=1)[:, 0]
        return lm_head_logits(cfg, params,
                              x[jnp.minimum(emit, P - 1)]), ck, cv
