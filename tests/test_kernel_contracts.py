"""Kernel-contract verifier tests (ISSUE 14 acceptance).

One positive AND negative fixture per contract family — an out-of-bounds
index map, a racing output map (parallel axes), a non-consecutive
write-only revisit (lost write), a block-geometry-drifted alias pair, and
an aliased-buffer read/write overlap — plus the sampling semantics, the
validated ``PADDLE_TPU_KERNEL_VERIFY_SAMPLES`` knob, the live serving
kernels (the fused decode step's deliberate alias overlap is detected and
exactly allowlisted; the sequential/split-K kernels verify clean), the
KNOWN_KERNELS drift lint, and the lint-gate integration: each injected
violation must fail ``tools/lint_gate.py`` naming the kernel, operand,
and grid point.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.analysis import Severity, analyze
from paddle_tpu.analysis.kernel_contracts import (check_kernel_contracts,
                                                  check_kernel_names,
                                                  contracts_summary,
                                                  registry_drift_findings,
                                                  verify_samples_cap,
                                                  DEFAULT_SAMPLES_CAP)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _zero_kernel(x_ref, o_ref):
    # shape-agnostic body for drifted-BlockSpec fixtures (a copy would
    # fail the kernel trace before the verifier ever sees the geometry)
    o_ref[...] = jnp.zeros_like(o_ref)


def _trace_call(in_map, out_map, grid=(4,), shape=(4, 8), block=(1, 8),
                out_shape=None, out_block=None, aliased=False,
                compiler_params=None, kernel=_copy_kernel):
    """Trace (never run) a one-input pallas_call with the given index
    maps; returns the ClosedJaxpr the verifier consumes."""
    out_shape = out_shape or shape
    x = jnp.zeros(shape, jnp.float32)

    def f(x):
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[pl.BlockSpec(block, in_map)],
            out_specs=pl.BlockSpec(out_block or block, out_map),
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            input_output_aliases={0: 0} if aliased else {},
            **({"compiler_params": compiler_params} if compiler_params
               else {}),
            interpret=True)(x)

    return jax.make_jaxpr(f)(x)


# ---------------------------------------------------------------------------
# bounds
# ---------------------------------------------------------------------------

def test_bounds_positive_off_by_one_walk():
    """The off-by-one page walk: map i -> block i+1 leaves a 4-block
    operand at the last grid point — must be named exactly."""
    closed = _trace_call(lambda i: (i + 1, 0), lambda i: (i, 0))
    findings, sections = check_kernel_contracts(closed, target="t")
    hits = [f for f in findings if f.rule == "kernel_bounds"]
    assert hits and hits[0].severity == Severity.ERROR
    assert "grid point (3,)" in hits[0].message
    assert "input 0" in hits[0].message
    assert sections[0]["bounds"] == "violated"


def test_bounds_negative_identity_walk():
    closed = _trace_call(lambda i: (i, 0), lambda i: (i, 0))
    findings, sections = check_kernel_contracts(closed)
    assert [f for f in findings if f.severity != Severity.INFO] == []
    assert sections[0]["bounds"] == "ok"
    assert sections[0]["points_checked"] == sections[0]["grid_points"] == 4


def test_bounds_negative_index_is_flagged():
    closed = _trace_call(lambda i: (i - 1, 0), lambda i: (i, 0))
    findings, _ = check_kernel_contracts(closed)
    hits = [f for f in findings if f.rule == "kernel_bounds"]
    assert hits and "grid point (0,)" in hits[0].message


def test_bounds_partial_edge_block_is_legal():
    """Blocked-mode partial final blocks (pallas pads them) must not flag:
    3 blocks of 8 rows over a 20-row operand."""
    closed = _trace_call(lambda i: (i, 0), lambda i: (i, 0), grid=(3,),
                         shape=(20, 8), block=(8, 8))
    findings, _ = check_kernel_contracts(closed)
    assert [f for f in findings if f.severity != Severity.INFO] == []


def _prefetch_call(table_to_block, tbl_len=4):
    """A scalar-prefetch (block-table) kernel whose KV-fetch block index
    is runtime data — the data-dependent map regime."""
    def kern(t_ref, x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def f(tbl, x):
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), table_to_block)],
            out_specs=pl.BlockSpec((1, 8), lambda i, t: (i, 0)))
        return pl.pallas_call(
            kern, grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((4, 8), x.dtype),
            interpret=True)(tbl, x)

    return jax.make_jaxpr(f)(jnp.zeros((tbl_len,), jnp.int32),
                             jnp.zeros((4, 8), jnp.float32))


def test_bounds_unclamped_table_read_is_flagged():
    """A data-dependent map that passes table values through unclamped is
    only safe by caller convention — the adversarial valuations must
    catch it (the contract the fused kernel's write-page map now clamps
    for)."""
    closed = _prefetch_call(lambda i, t: (t[i], 0))
    findings, sections = check_kernel_contracts(closed)
    hits = [f for f in findings if f.rule == "kernel_bounds"]
    assert hits, "unclamped prefetch-driven block index must be flagged"
    assert "valuation" in hits[0].message and "data-dependent" in \
        hits[0].message
    assert sections[0]["data_dependent"]


def test_bounds_clamped_table_read_is_clean():
    closed = _prefetch_call(lambda i, t: (jnp.clip(t[i], 0, 3), 0))
    findings, sections = check_kernel_contracts(closed)
    assert [f for f in findings if f.severity != Severity.INFO] == []
    assert sections[0]["data_dependent"]


# ---------------------------------------------------------------------------
# write races / lost writes
# ---------------------------------------------------------------------------

def test_race_positive_parallel_axis_collision():
    """Two grid points separated along a parallel-declared axis writing
    one output block is a race — the megakernel failure mode."""
    closed = _trace_call(
        lambda i: (i, 0), lambda i: (0, 0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)))
    findings, sections = check_kernel_contracts(closed, target="t")
    hits = [f for f in findings if f.rule == "kernel_race"]
    assert hits and hits[0].severity == Severity.ERROR
    assert "parallel grid axis 0" in hits[0].message
    assert "block (0, 0)" in hits[0].message
    assert sections[0]["race"] == "violated"


def test_race_multiple_parallel_collisions_never_mislabel_lost_write():
    """Two distinct parallel races on one output (blocks 0 and 1, map
    i -> i % 2): after the first race is recorded, later parallel groups
    must NOT fall through to the sequential branch and surface as a
    downgraded/mislabeled kernel_lost_write warning."""
    closed = _trace_call(
        lambda i: (i, 0), lambda i: (i % 2, 0),
        shape=(4, 8), out_shape=(2, 8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)))
    findings, _ = check_kernel_contracts(closed)
    assert [f for f in findings if f.rule == "kernel_race"]
    assert [f for f in findings if f.rule == "kernel_lost_write"] == []


def test_lost_write_positive_nonconsecutive_revisit():
    """out map i -> i % 2 on a sequential grid: block 0 is written at
    grid points 0 and 2 with block 1 written in between — the first
    write's bytes are flushed and clobbered (write-only, unaliased)."""
    closed = _trace_call(lambda i: (i, 0), lambda i: (i % 2, 0),
                         shape=(4, 8), out_shape=(2, 8))
    findings, _ = check_kernel_contracts(closed)
    hits = [f for f in findings if f.rule == "kernel_lost_write"]
    assert hits and hits[0].severity == Severity.WARNING
    assert "revisited non-consecutively" in hits[0].message


def test_race_negative_consecutive_accumulation_revisit():
    """The accumulate-then-finalize pattern: revisits consecutive in
    iteration order (i // 2 with the revisit axis innermost) keep the
    block VMEM-resident — the split-K partials' shape; must not flag."""
    closed = _trace_call(lambda i: (i, 0), lambda i: (i // 2, 0),
                         shape=(4, 8), out_shape=(2, 8))
    findings, sections = check_kernel_contracts(closed)
    assert [f for f in findings if f.severity != Severity.INFO] == []
    assert sections[0]["race"] == "ok"


def test_race_negative_readable_output_revisit():
    """A non-consecutive revisit whose kernel READS the output ref is
    accumulation-through-the-block — legal, not a lost write."""
    def accum(x_ref, o_ref):
        o_ref[...] = o_ref[...] + x_ref[...]

    closed = _trace_call(lambda i: (i, 0), lambda i: (i % 2, 0),
                         shape=(4, 8), out_shape=(2, 8), kernel=accum)
    findings, _ = check_kernel_contracts(closed)
    assert [f for f in findings if f.rule == "kernel_lost_write"] == []


def test_race_negative_injective_output():
    closed = _trace_call(lambda i: (i, 0), lambda i: (i, 0))
    findings, _ = check_kernel_contracts(closed)
    assert [f for f in findings
            if f.rule in ("kernel_race", "kernel_lost_write")] == []


# ---------------------------------------------------------------------------
# alias contracts
# ---------------------------------------------------------------------------

def test_alias_block_geometry_drift_is_flagged():
    """pallas enforces aval equality on aliased pairs but NOT block
    geometry: an aliased pair whose BlockSpecs drifted writes different
    elements than the read fetched."""
    closed = _trace_call(lambda i: (i, 0), lambda i: (0, i),
                         block=(1, 8), out_block=(4, 2), aliased=True,
                         kernel=_zero_kernel)
    findings, sections = check_kernel_contracts(closed, target="t")
    hits = [f for f in findings if f.rule == "kernel_alias"
            and "block geometry drifted" in f.message]
    assert hits and hits[0].severity == Severity.ERROR
    assert "(1, 8)" in hits[0].message and "(4, 2)" in hits[0].message
    assert sections[0]["alias"] == "violated"


def test_alias_overlap_read_of_written_block_is_flagged():
    """Aliased in-place output: a grid point reading a block another grid
    point writes observes updated bytes — must be flagged with both grid
    points named."""
    closed = _trace_call(lambda i: (3 - i, 0), lambda i: (i, 0),
                         aliased=True)
    findings, _ = check_kernel_contracts(closed)
    hits = [f for f in findings if f.rule == "kernel_alias"]
    assert hits and "writes in place" in hits[0].message
    assert "grid point" in hits[0].message


def test_alias_negative_matching_read_write():
    """Read-modify-write of the SAME block at the SAME grid point (maps
    identical) is the legitimate in-place pattern — clean."""
    closed = _trace_call(lambda i: (i, 0), lambda i: (i, 0), aliased=True)
    findings, sections = check_kernel_contracts(closed)
    assert [f for f in findings if f.severity != Severity.INFO] == []
    assert sections[0]["alias"] == "ok"


# ---------------------------------------------------------------------------
# sampling + the validated env knob
# ---------------------------------------------------------------------------

def test_sampling_above_cap_still_catches_corner_oob(monkeypatch):
    """A grid bigger than the cap is sampled (corners + stratified) —
    deterministically, and the corner points still catch the classic
    last-block overread."""
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VERIFY_SAMPLES", "16")
    closed = _trace_call(lambda i, j: (i + 1, j), lambda i, j: (i, j),
                         grid=(64, 4), shape=(64, 32), block=(1, 8))
    f1, s1 = check_kernel_contracts(closed)
    f2, s2 = check_kernel_contracts(closed)
    assert s1[0]["sampled"] and s1[0]["points_checked"] < 256
    assert s1[0]["grid_points"] == 256
    hits = [f for f in f1 if f.rule == "kernel_bounds"]
    assert hits, "corner sampling must catch the last-block overread"
    # deterministic: two runs, identical findings and sections
    assert [f.message for f in f1] == [f.message for f in f2]
    assert s1 == s2


def test_unevaluable_index_map_downgrades_verdicts(monkeypatch):
    """An index map the evaluator cannot execute must surface as
    'unchecked' on the card section (with an advisory finding), never as
    a clean 'ok' — the cards-only gate and bench detail drop info
    findings, so the verdict itself carries the downgrade."""
    import paddle_tpu.analysis.kernel_contracts as kc

    def boom(bm, pts, vals):
        raise RuntimeError("unsupported index-map primitive")

    monkeypatch.setattr(kc, "_eval_index_map", boom)
    closed = _trace_call(lambda i: (i, 0), lambda i: (i, 0))
    findings, sections = check_kernel_contracts(closed)
    assert sections[0]["bounds"] == "unchecked"
    assert sections[0]["race"] == "unchecked"
    assert sections[0]["unchecked_operands"] == 2
    assert contracts_summary(sections)["unchecked_operands"] == 2
    infos = [f for f in findings if f.severity == Severity.INFO]
    assert infos and "could not be evaluated" in infos[0].message
    assert [f for f in findings if f.severity != Severity.INFO] == []


def test_verify_samples_env_knob_validated(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_KERNEL_VERIFY_SAMPLES", raising=False)
    assert verify_samples_cap() == DEFAULT_SAMPLES_CAP
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VERIFY_SAMPLES", "64")
    assert verify_samples_cap() == 64
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VERIFY_SAMPLES", "lots")
    with pytest.warns(UserWarning, match="KERNEL_VERIFY_SAMPLES"):
        assert verify_samples_cap() == DEFAULT_SAMPLES_CAP
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VERIFY_SAMPLES", "2")
    with pytest.warns(UserWarning, match="minimum"):
        assert verify_samples_cap() == DEFAULT_SAMPLES_CAP


# ---------------------------------------------------------------------------
# live kernels: the shipped programs' contracts
# ---------------------------------------------------------------------------

def _pool_args(b=2, nkv=2, group=8, hd=8, bs=8, nb=10, mb=4):
    q = jnp.zeros((b, nkv, group, hd), jnp.float32)
    kc = jnp.zeros((nb, nkv, bs, hd), jnp.float32)
    vc = jnp.zeros((nb, nkv, bs, hd), jnp.float32)
    tbl = jnp.zeros((b, mb), jnp.int32)
    lens = jnp.zeros((b,), jnp.int32)
    return q, kc, vc, tbl, lens


def test_sequential_and_splitk_kernels_verify_clean():
    from paddle_tpu.ops.pallas import paged_attention as pa

    q, kc, vc, tbl, lens = _pool_args()
    seq = jax.make_jaxpr(lambda *a: pa._paged_attention_kernel_call(
        *a, scale=1.0, kv_quant=None, k_scale=None, v_scale=None))(
            q, kc, vc, tbl, lens)
    findings, sections = check_kernel_contracts(seq)
    assert [f for f in findings if f.severity != Severity.INFO] == []
    assert sections[0]["kernel"] == "paged_decode_attn"

    flash = jax.make_jaxpr(lambda *a: pa._flash_decode_kernel_call(
        *a, scale=1.0, kv_quant=None, k_scale=None, v_scale=None,
        num_shards=2))(q, kc, vc, tbl, lens)
    findings, sections = check_kernel_contracts(flash)
    # the split-K partials: revisits along the page-walk axis are
    # CONSECUTIVE accumulate/finalize — the live negative fixture
    assert [f for f in findings if f.severity != Severity.INFO] == []
    assert sections[0]["race"] == "ok" and sections[0]["bounds"] == "ok"


@pytest.mark.parametrize("nkv,R,hd,kv_quant", [(2, 16, 8, None),
                                               (2, 16, 8, "int8"),
                                               (16, 512, 128, None)],
                         ids=["all_heads_a_step", "quant_scales",
                              "four_head_blocks"])
def test_prefill_kernel_whole_page_walk_verifies_clean(nkv, R, hd, kv_quant):
    """``ragged_prefill_attn`` takes a page with as many of its KV heads as
    VMEM holds (the third case: 16 heads of a 512-row float32 tile in four
    blocks of 4).  Its page index map reads ``tables`` and ``lens`` and
    ignores ``qlens``; under every adversarial valuation of the three — zeros, a
    ramp, +BIG, -BIG — nothing leaves the pool, the table or the scales,
    and the output's revisits along the page walk are consecutive."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    b, bs, nb, mb = 2, 8, 10, 4
    sds = jax.ShapeDtypeStruct
    pool = sds((nb, nkv, bs, hd), jnp.int8 if kv_quant else jnp.float32)
    args = [sds((b, nkv, R, hd), jnp.float32), pool, pool,
            sds((b, mb), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.int32)]
    if kv_quant:
        args += [sds((nb, nkv), jnp.float32)] * 2
    heads = pa._prefill_heads_per_step(nkv, R, hd, bs, hd, jnp.float32,
                                       pool.dtype)
    assert heads == (4 if nkv == 16 else nkv)

    def call(q, kc, vc, tbl, lens, qlens, ks=None, vs=None):
        return pa._prefill_kernel_call(q, kc, vc, tbl, lens, qlens, 1.0, 1,
                                       kv_quant, ks, vs, 8, min(R, 128))

    jaxpr = jax.make_jaxpr(call)(*args)
    findings, sections = check_kernel_contracts(jaxpr)
    assert [f for f in findings if f.severity != Severity.INFO] == []
    (sec,) = sections
    assert sec["kernel"] == "ragged_prefill_attn"
    assert sec["bounds"] == "ok" and sec["race"] == "ok"
    assert tuple(sec["grid"]) == (b, nkv // heads, mb)


def test_fused_kernel_alias_overlap_detected_and_allowlisted():
    """The fused decode step's in-register append: the pool is read AND
    written in place — the verifier must DETECT the cross-grid-point
    overlap (the megakernel failure mode it guards), and the packaged
    allowlist must cover exactly it (deliberate, masked/spill-zeroed)."""
    from paddle_tpu.analysis.report import load_allowlist
    from paddle_tpu.ops.pallas import paged_attention as pa

    q, kc, vc, tbl, lens = _pool_args()
    k_new = jnp.zeros((2, 2, 8), jnp.float32)
    cos = jnp.zeros((2, 8), jnp.float32)
    wblk = jnp.zeros((2,), jnp.int32)
    wable = jnp.ones((2,), jnp.int32)
    closed = jax.make_jaxpr(lambda *a: pa._fused_decode_kernel_call(
        *a, scale=1.0, num_shards=2))(q, k_new, k_new, cos, cos, kc, vc,
                                      tbl, lens, wblk, wable)
    findings, sections = check_kernel_contracts(closed)
    gating = [f for f in findings if f.severity != Severity.INFO]
    # exactly the two deliberate alias overlaps (k and v pool) — bounds
    # and race families are clean (the write-page map clamps)
    assert len(gating) == 2
    assert all(f.rule == "kernel_alias" for f in gating)
    assert sections[0]["bounds"] == "ok" and sections[0]["race"] == "ok"
    allow = load_allowlist()
    for f in gating:
        assert any(a.covers(f) for a in allow), f.render()
    agg = contracts_summary(sections)
    assert agg["violations"] == 2 and agg["kernels"] == 1


def test_card_carries_kernel_contract_sections():
    """build_card derives the kernel_contracts section from the same
    trace; the summary aggregate is the budgeted violation count."""
    from paddle_tpu.analysis.cost_model import build_card

    x = jnp.zeros((4, 8), jnp.float32)

    def f(x):
        return pl.pallas_call(
            _copy_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
            interpret=True)(x)

    card = build_card(f, (x,), target="t")
    assert len(card.kernel_contracts) == 1
    s = card.summary()
    assert s["kernel_contract_violations"] == 0
    assert s["kernel_contracts"]["kernels"] == 1
    assert "contracts" in card.render()


def test_analyze_folds_kernel_findings_through_allowlist():
    """kernel_contracts is a first-class rule: findings gate via
    Report.ok and pass through the allowlist like any rule's."""
    from paddle_tpu.analysis.report import AllowRule

    x = jnp.zeros((4, 8), jnp.float32)

    def bad(x):
        return pl.pallas_call(
            _copy_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i + 1, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
            interpret=True)(x)

    r = analyze(bad, x, rules=("kernel_contracts",), allowlist=[])
    assert not r.ok and r.by_rule("kernel_bounds")
    r2 = analyze(bad, x, rules=("kernel_contracts",),
                 allowlist=[AllowRule(rule="kernel_bounds", match="",
                                      reason="test fixture")])
    assert r2.ok and len(r2.allowlisted) == 1


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------

def test_every_pallas_kernel_carries_its_own_name():
    """Every pallas_call of the gated targets (and the split-K decode
    kernel, which no gated target launches) carries a name, one per kernel
    body; the names are those the package's launch sites spell out.  An
    unnamed launch and a name shared by two bodies are each a finding."""
    import ast
    import glob

    from paddle_tpu.analysis import targets
    from paddle_tpu.analysis.kernel_contracts import _pallas_eqns
    from paddle_tpu.ops.pallas import paged_attention as pa

    spelled = []
    for path in glob.glob(os.path.join(REPO, "paddle_tpu", "ops", "pallas",
                                       "*.py")):
        for node in ast.walk(ast.parse(open(path).read())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                name = {k.arg: k.value for k in node.keywords}.get("name")
                # a literal, or a choice between two (a windowed call of
                # the flash kernels goes by a name of its own)
                names = ([name.body, name.orelse]
                         if isinstance(name, ast.IfExp) else [name])
                assert all(isinstance(n, ast.Constant) for n in names), (
                    f"{path}:{node.lineno}: pallas_call without a literal "
                    f"name=")
                spelled += [n.value for n in names]
    assert len(spelled) == len(set(spelled)) == 16
    assert {"gdn_chunk_prefill", "gdn_decode_step"} <= set(spelled)
    assert all(n.isidentifier() and n == n.lower() for n in spelled)

    programs = []
    for name in targets.GATE_TARGETS:
        t = targets.build(name)
        with targets._pinned_env(t.env):
            programs.append(jax.make_jaxpr(t.fn)(*t.args))
    programs.append(jax.make_jaxpr(lambda *a: pa._flash_decode_kernel_call(
        *a, scale=1.0, kv_quant=None, k_scale=None, v_scale=None,
        num_shards=2))(*_pool_args()))
    assert check_kernel_names(programs) == []
    traced = {e.params["name"] for p in programs for e in _pallas_eqns(p)}
    assert traced == set(spelled)

    def launch(kernel, name=None):
        x = jnp.zeros((4, 8), jnp.float32)
        return jax.make_jaxpr(lambda x: pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            name=name, interpret=True)(x))(x)

    unnamed = check_kernel_names([launch(_copy_kernel)])
    assert [f.rule for f in unnamed] == ["kernel_name"]
    assert "_copy_kernel" in unnamed[0].message
    shared = check_kernel_names([launch(_copy_kernel, "twin"),
                                 launch(_zero_kernel, "twin")])
    assert len(shared) == 1 and "'twin'" in shared[0].message
    assert check_kernel_names([launch(_copy_kernel, "one"),
                               launch(_copy_kernel, "one"),
                               launch(_zero_kernel, "other")]) == []


# ---------------------------------------------------------------------------
# KNOWN_KERNELS drift
# ---------------------------------------------------------------------------

def test_registry_drift_clean_on_shipped_tree():
    assert registry_drift_findings() == []


def test_registry_drift_detects_dead_and_unregistered(tmp_path):
    """A registered token with no dispatch site is a dead kill switch; a
    dispatch site with an unregistered token loses the typo guard —
    both directions, AST-level (docstring mentions don't count)."""
    (tmp_path / "mod.py").write_text(
        '"""docstring mention: kernel_disabled("doc_only") is not a '
        'dispatch."""\n'
        "def f():\n"
        "    if kernel_disabled('brand_new_kernel'):\n"
        "        return None\n")
    findings = registry_drift_findings(root=str(tmp_path))
    msgs = [f.message for f in findings]
    assert any("brand_new_kernel" in m and "not in KNOWN_KERNELS" in m
               for m in msgs)
    # every KNOWN token (minus 'all') is dead in this tree
    assert any("dead kill switch" in m for m in msgs)
    assert not any("doc_only" in m for m in msgs)


def test_retired_rope_swiglu_tokens_now_warn(monkeypatch):
    """'rope'/'swiglu' were dead kill switches (pure-jnp ops, no Pallas
    kernel to route around) retired by the drift lint: setting them now
    warns as unknown instead of silently doing nothing."""
    from paddle_tpu.ops.pallas import KNOWN_KERNELS, kernel_disabled

    assert "rope" not in KNOWN_KERNELS and "swiglu" not in KNOWN_KERNELS
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "rope")
    import paddle_tpu.utils.envflags as ef

    monkeypatch.setattr(ef, "_warned", set())
    with pytest.warns(UserWarning, match="rope"):
        assert not kernel_disabled("rms_norm")


# ---------------------------------------------------------------------------
# lint-gate integration: injected violations must fail CI by name
# ---------------------------------------------------------------------------

def _load_lint_gate():
    spec = importlib.util.spec_from_file_location(
        "lint_gate", os.path.join(REPO, "tools", "lint_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fixture_target(name, builder):
    from paddle_tpu.analysis.targets import AnalysisTarget

    def build():
        fn, args = builder()
        return AnalysisTarget(name, fn, args)

    return build


def _oob_program():
    x = jnp.zeros((4, 8), jnp.float32)

    def f(x):
        return pl.pallas_call(
            _copy_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i + 1, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
            interpret=True)(x)

    return f, (x,)


def _race_program():
    x = jnp.zeros((4, 8), jnp.float32)

    def f(x):
        return pl.pallas_call(
            _copy_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=True)(x)

    return f, (x,)


def _alias_program():
    x = jnp.zeros((4, 8), jnp.float32)

    def f(x):
        return pl.pallas_call(
            _zero_kernel, grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 2), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
            input_output_aliases={0: 0},
            interpret=True)(x)

    return f, (x,)


@pytest.mark.parametrize("fixture,rule,kname,needle", [
    (_oob_program, "kernel_bounds", "_copy_kernel", "grid point (3,)"),
    (_race_program, "kernel_race", "_copy_kernel", "parallel grid axis 0"),
    (_alias_program, "kernel_alias", "_zero_kernel",
     "block geometry drifted"),
])
def test_injected_violation_fails_lint_gate(monkeypatch, capsys, tmp_path,
                                            fixture, rule, kname, needle):
    """Acceptance: each injected-violation fixture fails lint_gate with
    the kernel name, operand, and grid point / axis in the finding."""
    import paddle_tpu.analysis.targets as targets_mod

    name = f"fixture_{rule}"
    monkeypatch.setattr(targets_mod, "TARGETS",
                        {name: _fixture_target(name, fixture)})
    monkeypatch.setattr(targets_mod, "GATE_TARGETS", (name,))
    allow = tmp_path / "allow.toml"
    allow.write_text("# empty\n")
    budgets = tmp_path / "budgets.toml"
    budgets.write_text(f'[[budget]]\ntarget = "{name}"\n'
                       f'kernel_contract_violations = 0\n'
                       f'reason = "fixture: zero tolerated violations"\n')
    mod = _load_lint_gate()
    rc = mod.main(["--allowlist", str(allow), "--budgets", str(budgets)])
    out = capsys.readouterr().out
    assert rc == 1
    assert rule in out and kname in out and needle in out
    # the budget layer independently trips on the violation count
    assert "kernel_contract_violations" in out


def test_clean_fixture_passes_lint_gate(monkeypatch, capsys, tmp_path):
    import paddle_tpu.analysis.targets as targets_mod

    def clean():
        x = jnp.zeros((4, 8), jnp.float32)

        def f(x):
            return pl.pallas_call(
                _copy_kernel, grid=(4,),
                in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
                interpret=True)(x)

        return f, (x,)

    monkeypatch.setattr(targets_mod, "TARGETS",
                        {"fixture_clean": _fixture_target("fixture_clean",
                                                          clean)})
    monkeypatch.setattr(targets_mod, "GATE_TARGETS", ("fixture_clean",))
    allow = tmp_path / "allow.toml"
    allow.write_text("# empty\n")
    budgets = tmp_path / "budgets.toml"
    budgets.write_text('[[budget]]\ntarget = "fixture_clean"\n'
                       'kernel_contract_violations = 0\n'
                       'reason = "fixture: clean kernel"\n')
    mod = _load_lint_gate()
    rc = mod.main(["--allowlist", str(allow), "--budgets", str(budgets)])
    capsys.readouterr()
    assert rc == 0
