"""models/laguna against the plain reference (benchmarks/reference/laguna):
a decoder of unlike layers (full and windowed attention of different head
counts, partial and yarn rope, gated attention, a leading dense layer,
sigmoid-routed experts of which a program holds a share), on seeded weights
at tiny widths, in float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from paddle_tpu.models import laguna, llama, moe_llama
from paddle_tpu.ops.pallas import rope as rope_mod

HP = dict(lr=3e-4, weight_decay=0.1, beta1=0.9, beta2=0.95, grad_clip=1.0)


def model_dict(cfg) -> dict:
    """The configuration as the reference reads it (a config file's
    ``model`` group)."""
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
         if f.name != "dtype"}
    return {k: list(v) if isinstance(v, tuple) else v for k, v in m.items()}


def tiny(**kw):
    cfg = laguna.LagunaConfig.tiny(**kw)
    cfg.dtype = jnp.float32
    return cfg


def flat(tree) -> dict:
    return {"/".join(str(p.key) for p in path): x for path, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module", params=[
    ("scores", None), ("sequence_standard", (4, 8))],
    ids=["whole-by-scores", "share-by-standard-scores"])
def model(request):
    """The leading layer + two periods + a partial period (10 layers):
    every expert held and chosen by the scores (the router's gradient
    whole); and, as the benchmark's configuration runs, a quarter of the 16
    experts held (the grouped products in two chunks), chosen by the
    sequence's standard scores, no gradient into the routing weights."""
    selection, held = request.param
    cfg = tiny(layers=10, held=held)
    cfg.router_selection = selection
    params = jax.jit(lambda k: laguna.init_params(cfg, k))(jax.random.key(1))
    rows = jax.random.randint(jax.random.key(2), (2, 65), 0, cfg.vocab_size)
    return cfg, params, np.asarray(rows[:, :-1]), np.asarray(rows[:, 1:])


def test_layer_groups_express_the_published_pattern():
    xs2 = laguna.LagunaConfig.laguna_xs2()
    assert xs2.signatures[0] == (laguna.FULL, 48, "dense")
    assert xs2.signatures[1:5] == ((laguna.SLIDING, 64, "sparse"),) * 3 + (
        (laguna.FULL, 48, "sparse"),)
    # a leading layer, S S S F nine times, S S S
    assert laguna.layer_groups(xs2) == (1, 4, 9, 3)
    # the benchmark's cut: the leading layer and one whole period
    cut = laguna.LagunaConfig.laguna_xs2(num_hidden_layers=5,
                                         vocab_size=12544,
                                         experts_held=(0, 32))
    assert laguna.layer_groups(cut) == (1, 4, 1, 0)
    shapes = laguna.param_shapes(cut)["layers"]
    assert shapes["lead"]["0"]["wq"] == (2048, 48 * 128)
    assert shapes["lead"]["0"]["w_gate"] == (2048, 8192)
    assert shapes["period"]["0"]["wq"] == (1, 2048, 64 * 128)
    assert shapes["period"]["3"]["wq"] == (1, 2048, 48 * 128)
    assert shapes["period"]["0"]["wg"] == (1, 2048, 64)
    assert shapes["period"]["0"]["e_gate"] == (1, 32, 2048, 512)
    assert shapes["period"]["0"]["router"] == (1, 2048, 256)
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        laguna.param_shapes(cut), is_leaf=lambda s: isinstance(s, tuple)))
    assert 691.0e6 < n < 692.5e6
    # a model of like layers is one scan
    same = laguna.LagunaConfig(num_hidden_layers=6, num_experts=8,
                               num_experts_per_tok=2)
    assert laguna.layer_groups(same) == (0, 1, 6, 0)


def test_whole_published_depth_traces_with_three_layer_programs():
    """40 layers at toy widths: the period is traced once however deep the
    model (one scan), and the counters come out a row an expert layer."""
    cfg = tiny(layers=40)
    assert laguna.layer_groups(cfg) == (1, 4, 9, 3)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        laguna.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    ids = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, i: laguna.forward(
        cfg, p, i, return_stats=True))(shapes, ids)
    logits, stats = jaxpr.out_avals[0], jaxpr.out_avals[1:]
    assert logits.shape == (1, 64, cfg.vocab_size)
    assert sorted(a.shape for a in stats) == [(39,), (39,), (39,), (39, 16)]
    text = str(jaxpr)
    assert text.count("scan[") >= 1
    # 1 lead + 4 period + 3 rest bodies, not 40
    assert text.count("name=flash_attn_win_fwd") == 3 + 3
    assert text.count("name=flash_attn_fwd") == 1 + 1


def test_logits_loss_and_every_gradient_leaf_match_the_reference(model):
    cfg, params, ids, labels = model
    m = model_dict(cfg)
    logits = jax.jit(lambda p, i: laguna.forward(cfg, p, i))(params, ids)
    row = jax.jit(lambda p, r: ref.logits(m, p, r))
    want = jnp.stack([row(params, jnp.asarray(r)) for r in ids])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=2e-5)
    # composed attention here (the flash kernels' gradients are held to it
    # in test_pallas_kernels, and the AdamW test below runs them)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: llama._xent(
        laguna.forward(cfg, p, ids, use_flash=False), labels)))(params)
    ref_loss, ref_grads = ref.loss_and_grads(m, params, ids, labels)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    mine, theirs = flat(grads), flat(ref_grads)
    assert mine.keys() == theirs.keys() and len(mine) > 50
    for name, g in theirs.items():
        scale = float(jnp.max(jnp.abs(g))) + 1e-12
        np.testing.assert_allclose(
            np.asarray(mine[name]) / scale, np.asarray(g) / scale,
            atol=2e-4, err_msg=name)
        if name.endswith("router") and cfg.n_held < cfg.num_experts:
            assert scale < 1e-9 and not np.any(np.asarray(mine[name])), name
        else:
            assert scale > 1e-9, f"{name}: the reference's gradient is zero"


def test_two_adamw_steps_match_train_readings(model):
    cfg, params, ids, labels = model
    mesh = laguna.make_mesh(devices=jax.devices()[:1])
    step, opt_init, psh, dsh = laguna.build_train_step(cfg, mesh, **HP)
    # the step and the reference's AdamW both donate what they are given
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, params)
    p = jax.device_put(fresh(), psh)
    opt = opt_init(p)
    batches = [(ids, labels), (labels, ids)]
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))
    losses = []
    for k, (a, b) in enumerate(batches):
        loss, p, opt = step(p, opt, jax.device_put(a, dsh),
                            jax.device_put(b, dsh))
        losses.append(float(loss))
        if k == 0:
            first_m = {n: norm(x) for n, x in flat(opt["m"]).items()}
    theirs = ref.train_readings(model_dict(cfg), fresh, batches,
                                dict(HP, eps=1e-8))
    np.testing.assert_allclose(losses, theirs["loss"], rtol=1e-5)
    for name, g in theirs["grad"].items():
        assert abs(first_m[name] / (1 - HP["beta1"]) - g) \
            <= 1e-3 * g + 1e-9, name
    change = flat(jax.tree_util.tree_map(lambda a, b: a - b, opt["master"],
                                         params))
    for name, c in theirs["change"].items():
        assert abs(norm(change[name]) - c) <= 2e-3 * c + 1e-9, name
    # the counters rode along: 9 expert layers, the experts held, 2 steps
    assert opt["moe_assignments_held"].shape == (9, cfg.n_held)
    assert np.asarray(opt["moe_assignments_total"]).tolist() == [
        2 * ids.size * cfg.num_experts_per_tok] * 9
    assert int(opt["moe_assignments_dropped"].sum()) == 0
    assert int(opt["step"]) == 2
    held = np.asarray(opt["moe_assignments_held"]).sum(-1)
    assert np.all(held <= np.asarray(opt["moe_rows_computed"]))


@pytest.mark.parametrize("kind", [laguna.FULL, laguna.SLIDING])
def test_partial_and_yarn_rope_match_the_reference_tables(kind):
    cfg = laguna.LagunaConfig.laguna_xs2()
    rp = cfg.rope_parameters[kind]
    cos, sin = laguna.rope_tables(cfg, 300)[kind]
    want_cos, want_sin = ref.rope_tables(rp, cfg.head_dim, jnp.arange(300))
    r = 64 if kind == laguna.FULL else 128
    assert cos.shape == (1, 300, r)
    np.testing.assert_allclose(np.asarray(cos[0]), np.asarray(want_cos),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin[0]), np.asarray(want_sin),
                               atol=1e-5)
    if kind == laguna.FULL:
        # yarn: the attention factor scales cos at position 0, the slowest
        # frequency is the plain one over the factor, the fastest untouched
        assert abs(float(cos[0, 0, 0]) - 1.4158883083359672) < 1e-6
        plain = 1.0 / (5e5 ** (np.arange(0, 64, 2) / 64.0))
        got = np.asarray(rope_mod.yarn_inv_freq(
            jnp.asarray(plain, jnp.float32), 64, 5e5, 64, 4096, 64, 1))
        np.testing.assert_allclose(got[-1], plain[-1] / 64, rtol=1e-5)
        np.testing.assert_allclose(got[0], plain[0], rtol=1e-6)
        assert np.all(np.diff(got / plain) <= 1e-7)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(1, 300, 3, 128), jnp.float32)
    q, _ = rope_mod.apply_rotary_pos_emb(x, x, cos, sin)
    want = ref.rotate(x[0], want_cos, want_sin)
    np.testing.assert_allclose(np.asarray(q[0]), np.asarray(want), atol=1e-5)
    # the dimensions past the rotary ones pass through
    np.testing.assert_array_equal(np.asarray(q[..., r:]),
                                  np.asarray(x[..., r:]))


def _expert_layer(cfg, key):
    shapes = laguna._layer_shapes(cfg, 1)
    keys = jax.random.split(key, len(shapes))
    return {name: jax.random.normal(k, shape, jnp.float32) * 0.05
            for k, (name, shape) in zip(keys, sorted(shapes.items()))}


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the shares [0, 4) .. [12, 16) give, with the
    shared expert counted once, are the uncut reference's layer."""
    whole = tiny(layers=2)
    lp = _expert_layer(whole, jax.random.key(3))
    xn = jax.random.normal(jax.random.key(4), (2, 64, 64), jnp.float32)
    m = model_dict(whole)
    want = jnp.stack([ref.sparse_mlp(m, lp, row) for row in xn])
    shared = jnp.stack([ref.swiglu(row, lp["s_gate"], lp["s_up"],
                                   lp["s_down"], None) for row in xn])
    total, counts = shared, 0
    for lo in range(0, 16, 4):
        cfg = tiny(layers=2, held=(lo, lo + 4))
        part = dict(lp, **{k: lp[k][lo:lo + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        y, seen = jax.jit(lambda x, p, c=cfg: laguna.sparse_mlp(c, x, p))(
            xn, part)
        # what this member computes is what the reference computes for it
        mine = jnp.stack([ref.sparse_mlp(dict(m, experts_held=[lo, lo + 4]),
                                         part, row) for row in xn])
        np.testing.assert_allclose(np.asarray(y), np.asarray(mine),
                                   atol=1e-5)
        total = total + (y - shared)
        counts += int(seen["held"].sum())
        assert int(seen["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)
    assert counts == 2 * 64 * whole.num_experts_per_tok


def test_an_imbalanced_router_drops_nothing():
    """One held expert takes every token and the held experts take every
    choice of every token (the worst case of the row bound): every chunk of
    the grouped product runs, nothing is dropped, and the result is the
    reference's."""
    cfg = tiny(layers=2, held=(0, 4))
    lp = _expert_layer(cfg, jax.random.key(5))
    # the held experts' router columns far above the rest: all 4 choices of
    # every token fall on them; expert 2 first of all
    bias = jnp.zeros((64, 16)).at[:, :4].set(0.5).at[:, 2].set(1.0)
    xn = jnp.abs(jax.random.normal(jax.random.key(6), (2, 64, 64))) + 0.1
    lp = dict(lp, router=bias)
    rows, chunks = moe_llama.held_rows(128, 4, 16, 4)
    assert (rows, chunks) == (256, 2) and rows * chunks == 128 * 4
    assert moe_llama.held_rows(16384, 8, 256, 32) == (20480, 7)
    y, seen = jax.jit(lambda x, p: laguna.sparse_mlp(cfg, x, p))(xn, lp)
    assert np.asarray(seen["held"]).tolist() == [128, 128, 128, 128]
    assert int(seen["dropped"]) == 0
    assert int(seen["rows"]) == rows * chunks
    m = model_dict(cfg)
    plain = lambda x, p: jnp.stack([ref.sparse_mlp(m, p, row) for row in x])
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain(xn, lp)),
                               atol=2e-5)
    # and so are the gradients that the chunks' own backward pass gives
    do = jax.random.normal(jax.random.key(10), xn.shape)
    got = jax.jit(jax.grad(lambda x, p: jnp.sum(
        laguna.sparse_mlp(cfg, x, p)[0] * do), (0, 1)))(xn, lp)
    want = jax.grad(lambda x, p: jnp.sum(plain(x, p) * do), (0, 1))(xn, lp)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * float(jnp.abs(b).max() + 1))
    # near balance the first chunk alone runs; it runs with no live row too
    _, seen = jax.jit(lambda x, p: laguna.sparse_mlp(cfg, x, p))(
        xn, _expert_layer(cfg, jax.random.key(7)))
    assert int(seen["rows"]) == rows and int(seen["dropped"]) == 0
    away = dict(lp, router=-bias)
    y, seen = jax.jit(lambda x, p: laguna.sparse_mlp(cfg, x, p))(xn, away)
    assert int(seen["held"].sum()) == 0 and int(seen["rows"]) == rows
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain(xn, away)),
                               atol=2e-5)


def test_selection_bias_spreads_rows_that_have_run_together():
    """``route_topk(groups=)``: rows that share most of their logits all
    choose the same experts by the raw scores; by the logits' standard
    scores over the sequence they spread over the experts, the weights
    still the scores' as they are, with their gradient; the reference
    chooses alike."""
    E, K, h, b, s = 16, 4, 32, 2, 96
    k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
    router = jax.random.normal(k1, (h, E), jnp.float32) * 0.3
    shared = jax.random.normal(k2, (b, 1, h)) * 4.0     # a sequence's own
    xf = (shared + jax.random.normal(k3, (b, s, h))).reshape(b * s, h)
    raw = moe_llama.route_topk(xf, router, K, "sigmoid", 2.5)
    w, experts, scores, logits = moe_llama.route_topk(
        xf, router, K, "sigmoid", 2.5, groups=b)
    load = lambda e: np.bincount(np.asarray(e).ravel(), minlength=E)
    # by the scores a sequence's rows nearly all make its K choices
    assert np.sort(load(raw[1]))[-b * K:].sum() > 0.8 * b * s * K
    assert load(experts).max() < 2.0 * b * s * K / E
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)
    top = np.take_along_axis(np.asarray(scores), np.asarray(experts), 1)
    np.testing.assert_allclose(np.asarray(w),
                               2.5 * top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    # what a sequence's rows share does not move its choice
    moved = moe_llama.route_topk(
        xf + jnp.repeat(jax.random.normal(k1, (b, h)), s, axis=0), router, K,
        "sigmoid", 2.5, groups=b)
    assert np.array_equal(np.asarray(moved[1]), np.asarray(experts))
    m = {"num_experts_per_tok": K, "moe_routed_scaling_factor": 2.5,
         "router_selection": "sequence_standard"}
    for r in range(b):
        rw, re = ref.routing(m, xf[r * s:(r + 1) * s], router)
        assert np.array_equal(np.asarray(re), np.asarray(experts[r * s:
                                                                (r + 1) * s]))
        np.testing.assert_allclose(np.asarray(rw), np.asarray(
            w[r * s:(r + 1) * s]), rtol=1e-5)
    g = jax.grad(lambda r: moe_llama.route_topk(
        xf, r, K, "sigmoid", 2.5, groups=b)[0][:, 0].sum())(router)
    assert float(jnp.abs(g).max()) > 0


def test_all_experts_held_is_moe_ffn_whole_routing():
    """``experts_held`` = all runs the ragged engine as ``moe_ffn`` does
    (one grouped product over every assignment, no padding), and the
    sigmoid front end with its scaling is the one ``moe_ffn`` takes."""
    cfg = tiny(layers=2)
    lp = _expert_layer(cfg, jax.random.key(8))
    xn = jax.random.normal(jax.random.key(9), (2, 32, 64), jnp.float32)
    mcfg = moe_llama.MoEConfig(
        hidden_size=64, moe_intermediate_size=32, num_experts=16, top_k=4,
        dispatch="ragged", router_scoring="sigmoid",
        routed_scaling_factor=cfg.moe_routed_scaling_factor,
        dtype=jnp.float32)
    routed, _, _ = moe_llama.moe_ffn(mcfg, xn, lp)
    y, seen = laguna.sparse_mlp(cfg, xn, lp)
    xf = xn.reshape(64, 64)
    shared = (jax.nn.silu(xf @ lp["s_gate"]) * (xf @ lp["s_up"])) @ lp[
        "s_down"]
    np.testing.assert_allclose(np.asarray(y - shared.reshape(y.shape)),
                               np.asarray(routed), atol=1e-5)
    assert int(seen["rows"]) == int(seen["total"]) == 64 * 4
    assert int(seen["held"].sum()) == 64 * 4
    # with every expert held the router learns: its gradient is the
    # reference's, and not nought
    do = jax.random.normal(jax.random.key(11), xn.shape)
    m = model_dict(cfg)
    got = jax.grad(lambda r: jnp.sum(laguna.sparse_mlp(
        cfg, xn, dict(lp, router=r))[0] * do))(lp["router"])
    want = jax.grad(lambda r: jnp.sum(jnp.stack([ref.sparse_mlp(
        m, dict(lp, router=r), row) for row in xn]) * do))(lp["router"])
    assert float(jnp.abs(want).max()) > 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()) + 1e-9)
    # a share needs the engine that can skip experts
    held = dataclasses.replace(mcfg, dispatch="sort", experts_held=(0, 4))
    with pytest.raises(ValueError, match="ragged"):
        moe_llama.moe_ffn(held, xn, lp)


def test_config_takes_the_published_keys_and_cuts_depth():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "laguna-xs2-ep8-l5.json")
    m = json.load(open(path))
    cfg = laguna.LagunaConfig.from_dict(m)
    assert cfg.num_hidden_layers == 5 and len(m["layer_types"]) == 40
    assert cfg.layer_types == (laguna.FULL,) + (laguna.SLIDING,) * 3 + (
        laguna.FULL,)
    assert cfg.num_attention_heads_per_layer == (48, 64, 64, 64, 48)
    assert cfg.experts_held == (0, 32) and cfg.vocab_size == 12544
    assert cfg == laguna.LagunaConfig.laguna_xs2(
        num_hidden_layers=5, vocab_size=12544, experts_held=(0, 32),
        router_selection="sequence_standard",
        rope_parameters=cfg.rope_parameters)
    published = laguna.LagunaConfig.laguna_xs2().rope_parameters
    for kind in (laguna.FULL, laguna.SLIDING):
        assert {k: cfg.rope_parameters[kind][k] for k in published[kind]} \
            == published[kind]
