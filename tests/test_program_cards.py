"""Program-card subsystem tests (ISSUE 12 acceptance).

The static cost model (analysis/cost_model.py): launch census shared with
``serving.decode_step_launches()`` (parity asserted on the default AND
kill-switched decode programs), liveness-based peak-HBM with donation and
pallas-alias credits, per-pallas-call VMEM fit vs the per-generation cap,
budgets.toml loading/gating (reason required, ints, stale/missing
entries), injected budget regressions (extra scatter, inflated trace
family, undonated large buffer) failing with the offending field named,
stale-allowlist strictness in tools/lint_gate.py, the --json CLI, and the
tier-1 card gate over every registered target.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.analysis import analyze, build_card
from paddle_tpu.analysis.cost_model import (BUDGET_FIELDS, BudgetEntry,
                                            ProgramCard, check_budgets,
                                            eqn_census, load_budgets,
                                            peak_live_hbm, vmem_cap_bytes,
                                            vmem_estimates,
                                            update_budgets_file)
from paddle_tpu.analysis.report import _parse_mini_toml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint_gate():
    spec = importlib.util.spec_from_file_location(
        "lint_gate", os.path.join(REPO, "tools", "lint_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pallas_double(x, alias=False):
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    return pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0} if alias else {})(x)


# ---------------------------------------------------------------------------
# launch census (shared implementation)
# ---------------------------------------------------------------------------

def test_census_pallas_call_is_one_launch_body_not_descended():
    x = jnp.ones((64, 64))
    closed = jax.make_jaxpr(lambda x: _pallas_double(x))(x)
    c = eqn_census(closed)
    assert c["pallas_calls"] == 1
    # the kernel body's mul is NOT a dispatch: only the call itself counts
    assert c["eqns"] == len(closed.jaxpr.eqns)


def test_census_counts_scatters_and_descends_scan():
    def fn(x):
        def body(c, _):
            return c.at[0].set(c[1]), None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    c = eqn_census(jax.make_jaxpr(fn)(jnp.zeros((4,))))
    assert c["scatters"] == 1  # inside the scan body — census descends


def test_census_parity_with_decode_step_launches(monkeypatch):
    """ISSUE 12 satellite: static card launch count == dynamic
    ``decode_step_launches()`` telemetry, for the default (fused/flash)
    AND kill-switched (pre-fusion) decode programs.  The engine telemetry
    and the registered target's card now share ONE census implementation;
    eqns differ by exactly the jit wrapper's pjit eqn, launches must not
    differ at all."""
    from paddle_tpu.analysis.targets import _serving_engine, run_card

    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    eng = _serving_engine()
    dyn = eng.decode_step_launches()
    assert dyn["fused_decode"]
    card = run_card("serving_flash_decode_step")
    assert card.pallas_calls == dyn["pallas_calls"]
    assert card.scatters == dyn["scatters"] == 0  # fused append contract
    assert card.eqns == dyn["eqns"] + 1  # the target's jit-wrapping pjit

    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS",
                       "flash_decode,fused_decode_step")
    eng2 = _serving_engine(_disable_pallas=("flash_decode",
                                            "fused_decode_step"))
    dyn2 = eng2.decode_step_launches()
    assert not dyn2["fused_decode"]
    card2 = run_card("serving_decode_step")
    assert card2.pallas_calls == dyn2["pallas_calls"]
    assert card2.scatters == dyn2["scatters"] == 2  # the KV-append pair
    assert card2.eqns == dyn2["eqns"] + 1


def test_decode_step_card_summary_keys(monkeypatch):
    """The bench embed: engine.decode_step_card() carries the card summary
    plus the fused flag, trace-only."""
    from paddle_tpu.analysis.targets import _serving_engine

    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    eng = _serving_engine()
    d = eng.decode_step_card()
    for key in ("peak_hbm_bytes", "eqns", "pallas_calls", "scatters",
                "vmem_bytes_per_launch", "vmem_cap_bytes", "fused_decode"):
        assert key in d, key
    assert d["fused_decode"] and d["scatters"] == 0
    # the production jit donates the KV pools (_jit_step donate_argnums=
    # (1, 2)); the card must credit that, not double-count pool bytes
    closed, donated = eng._decode_step_trace()
    assert sum(donated) >= 2
    assert d["peak_hbm_bytes"] < peak_live_hbm(closed)  # undonated trace


# ---------------------------------------------------------------------------
# peak live HBM (liveness pass)
# ---------------------------------------------------------------------------

def _state_step(state, x):
    return {"w": state["w"] + x.sum(), "m": state["m"] * 0.9}, x.sum()


def test_peak_hbm_donation_credited():
    state = {"w": jnp.ones((256, 256)), "m": jnp.zeros((256, 256))}
    x = jnp.ones((8,))
    und = peak_live_hbm(jax.make_jaxpr(jax.jit(_state_step))(state, x))
    don = peak_live_hbm(jax.make_jaxpr(
        jax.jit(_state_step, donate_argnums=(0,)))(state, x))
    tree = 2 * 256 * 256 * 4
    # undonated: inputs AND outputs both live at the end; donated: the
    # output tree aliases the donated buffers
    assert don < und
    assert und >= 2 * tree and don < und - tree // 2


def test_peak_hbm_pallas_alias_not_double_counted():
    x = jnp.ones((256, 256))
    aliased = peak_live_hbm(jax.make_jaxpr(
        lambda x: _pallas_double(x, alias=True))(x))
    fresh = peak_live_hbm(jax.make_jaxpr(
        lambda x: _pallas_double(x, alias=False))(x))
    assert aliased == x.size * 4          # one buffer, written in place
    assert fresh == 2 * x.size * 4        # input + fresh output


def test_peak_hbm_scan_body_intermediates_ride_on_carry():
    def fn(x):
        def body(c, _):
            big = jnp.ones((128, 128)) * c.sum()   # transient per step
            return c + big[0, 0], None
        out, _ = jax.lax.scan(body, x, None, length=2)
        return out

    peak = peak_live_hbm(jax.make_jaxpr(fn)(jnp.ones((4, 4))))
    assert peak >= 128 * 128 * 4  # the body's working set counts


# ---------------------------------------------------------------------------
# VMEM fit estimate + cap
# ---------------------------------------------------------------------------

def test_vmem_estimate_blocks_and_scratch():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(x_ref, o_ref, s_ref):
        s_ref[...] = x_ref[...] * 2
        o_ref[...] = s_ref[...]

    def f(x):
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32),
            scratch_shapes=[pltpu.VMEM((64, 64), jnp.float32)])(x)

    est = vmem_estimates(jax.make_jaxpr(f)(jnp.ones((64, 64))))
    assert len(est) == 1
    blk = 64 * 64 * 4
    assert est[0]["block_bytes"] == 2 * blk       # in + out blocks
    assert est[0]["scratch_bytes"] == blk
    assert est[0]["vmem_bytes"] == 3 * blk


def test_vmem_over_cap_is_gating_finding():
    x = jnp.ones((256, 256))
    r = analyze(lambda x: _pallas_double(x), x, card=True, vmem_cap=1024,
                allowlist=[], rules=())
    assert not r.ok
    hits = r.by_rule("program_card")
    assert hits and "VMEM" in hits[0].message
    # same program under the real cap: fits
    assert analyze(lambda x: _pallas_double(x), x, card=True,
                   allowlist=[], rules=()).ok


def test_vmem_cap_env_override_and_typo(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_VMEM_CAP_MIB", "8")
    assert vmem_cap_bytes() == 8 << 20
    monkeypatch.setenv("PADDLE_TPU_VMEM_CAP_MIB", "huge")
    with pytest.warns(UserWarning, match="PADDLE_TPU_VMEM_CAP_MIB"):
        assert vmem_cap_bytes() == 16 << 20  # default (v4 floor) holds


# ---------------------------------------------------------------------------
# budgets.toml: loader + gate semantics
# ---------------------------------------------------------------------------

def test_mini_toml_parses_integers_and_strings():
    entries = _parse_mini_toml(
        '[[budget]]\ntarget = "t"\nscatters = 2\nreason = "r"\n',
        header="budget")
    assert entries == [{"target": "t", "scatters": 2, "reason": "r"}]
    with pytest.raises(ValueError, match="parse error"):
        _parse_mini_toml('[[budget]]\nscatters = 2.5\n', header="budget")


def test_budgets_loader_contract(tmp_path):
    p = tmp_path / "budgets.toml"
    p.write_text('[[budget]]\ntarget = "t"\nscatters = 1\nreason = "why"\n')
    b = load_budgets(str(p))
    assert b[0].target == "t" and b[0].ceilings == {"scatters": 1}
    p.write_text('[[budget]]\ntarget = "t"\nscatters = 1\n')
    with pytest.raises(ValueError, match="reason"):
        load_budgets(str(p))
    p.write_text('[[budget]]\ntarget = "t"\nbogus_field = 1\n'
                 'reason = "r"\n')
    with pytest.raises(ValueError, match="unknown ceiling"):
        load_budgets(str(p))
    p.write_text('[[budget]]\ntarget = "t"\nreason = "r"\n'
                 '[[budget]]\ntarget = "t"\nreason = "r"\n')
    with pytest.raises(ValueError, match="duplicate"):
        load_budgets(str(p))
    with pytest.raises(FileNotFoundError):
        load_budgets(str(tmp_path / "nope.toml"))


def test_packaged_budgets_cover_every_gate_target():
    from paddle_tpu.analysis.targets import GATE_TARGETS

    budgets = load_budgets()
    assert {b.target for b in budgets} == set(GATE_TARGETS)
    assert all(b.reason for b in budgets)
    # every entry ceilings the full budget field set (collective_bytes
    # included — the TP target's psum budget is the contract ISSUE 8 pinned)
    for b in budgets:
        assert set(b.ceilings) == set(BUDGET_FIELDS), b.target


def _mk_card(name="t", **over):
    base = dict(target=name, peak_hbm_bytes=1000, eqns=10, pallas_calls=1,
                scatters=0, collective_bytes=0, vmem_bytes_per_launch=64,
                vmem_cap_bytes=16 << 20, trace_families=1)
    base.update(over)
    return ProgramCard(**base)


def _budget_of(card, **over):
    ceil = {f: card.summary()[f] for f in BUDGET_FIELDS
            if card.summary()[f] is not None}
    ceil.update(over)
    return BudgetEntry(target=card.target, ceilings=ceil, reason="test")


def test_check_budgets_over_budget_names_field():
    card = _mk_card(scatters=3)
    findings = check_budgets({"t": card},
                             [_budget_of(card, scatters=0)])
    gating = [f for f in findings if f.severity == "error"]
    assert len(gating) == 1 and gating[0].where == "scatters"
    assert "exceeds the budgeted ceiling 0" in gating[0].message
    # at the ceiling: clean
    assert check_budgets({"t": card}, [_budget_of(card)]) == []


def test_check_budgets_missing_and_stale_entries():
    card = _mk_card("present")
    findings = check_budgets(
        {"present": card},
        [BudgetEntry("ghost_target", {"scatters": 0}, "old")],
        registered=("present",))
    msgs = [f.message for f in findings]
    assert any("no budgets.toml entry" in m for m in msgs)
    assert any("stale budgets.toml entry" in m for m in msgs)
    assert all(f.severity == "warning" for f in findings)


def test_check_budgets_unknown_field_skips_with_info():
    card = _mk_card(collective_bytes=None)  # compile unavailable
    findings = check_budgets(
        {"t": card}, [_budget_of(_mk_card(), collective_bytes=0)])
    assert [f.severity for f in findings] == ["info"]
    assert "not checked" in findings[0].message


# ---------------------------------------------------------------------------
# injected budget regressions (satellite: the gate catches each class)
# ---------------------------------------------------------------------------

def test_injected_scatter_regression_fails_gate():
    x = jnp.zeros((64,))
    clean = build_card(lambda x: x * 2, (x,), target="fix")
    budget = _budget_of(clean)
    regressed = build_card(lambda x: (x * 2).at[3].set(1.0), (x,),
                           target="fix")
    findings = check_budgets({"fix": regressed}, [budget])
    assert any(f.severity == "error" and f.where == "scatters"
               for f in findings)


def test_injected_trace_family_regression_fails_gate():
    x = jnp.ones((8,))
    clean = build_card(lambda x, s: x * s, (x, jnp.float32(2.0)),
                       target="fam")
    assert clean.trace_families == 1
    budget = _budget_of(clean)
    # python-scalar provenance: an equivalent caller would recompile
    regressed = build_card(lambda x, s: x * s, (x, 2.0), target="fam")
    assert regressed.trace_families == 2
    findings = check_budgets({"fam": regressed}, [budget])
    assert any(f.severity == "error" and f.where == "trace_families"
               for f in findings)


def test_injected_undonated_buffer_regression_fails_gate():
    state = {"w": jnp.ones((256, 256)), "m": jnp.zeros((256, 256))}
    x = jnp.ones((8,))
    clean = build_card(jax.jit(_state_step, donate_argnums=(0,)),
                       (state, x), target="hbm")
    budget = _budget_of(clean)
    regressed = build_card(jax.jit(_state_step), (state, x), target="hbm")
    assert regressed.peak_hbm_bytes > clean.peak_hbm_bytes
    findings = check_budgets({"hbm": regressed}, [budget])
    assert any(f.severity == "error" and f.where == "peak_hbm_bytes"
               for f in findings)


# ---------------------------------------------------------------------------
# --update-budgets workflow
# ---------------------------------------------------------------------------

def test_update_budgets_preserves_reasons_and_drops_stale(tmp_path):
    p = tmp_path / "budgets.toml"
    p.write_text('[[budget]]\ntarget = "keep"\nscatters = 9\n'
                 'reason = "reviewed reason"\n'
                 '[[budget]]\ntarget = "other"\nscatters = 5\n'
                 'reason = "not re-measured this run"\n'
                 '[[budget]]\ntarget = "gone"\nscatters = 1\n'
                 'reason = "stale"\n')
    cards = {"keep": _mk_card("keep", scatters=2),
             "new": _mk_card("new")}
    # a PARTIAL update (registered names "other" but not "gone"): the
    # un-selected "other" entry survives verbatim — a --target run must
    # never delete the rest of the file — while unregistered "gone" retires
    update_budgets_file(cards, str(p),
                        registered=("keep", "new", "other"))
    budgets = {b.target: b for b in load_budgets(str(p))}
    assert set(budgets) == {"keep", "new", "other"}
    assert budgets["keep"].reason == "reviewed reason"
    assert budgets["keep"].ceilings["scatters"] == 2  # re-measured
    assert budgets["other"].ceilings["scatters"] == 5  # kept verbatim
    assert "review and justify" in budgets["new"].reason
    # written file gates its own cards clean
    assert check_budgets(cards, load_budgets(str(p))) == []


def test_update_budgets_roundtrips_quoted_reasons(tmp_path):
    p = tmp_path / "budgets.toml"
    p.write_text('[[budget]]\ntarget = "q"\nscatters = 0\n'
                 'reason = "pins the \\"fused\\" contract"\n')
    update_budgets_file({"q": _mk_card("q")}, str(p))
    b = load_budgets(str(p))[0]  # must still PARSE, quotes intact
    assert b.reason == 'pins the "fused" contract'
    # a reason ENDING in a backslash must survive a write->load->write
    # cycle too (an unescaped trailing \ would swallow the closing quote
    # and the next update would then discard every reason)
    weird = 'path C:\\tmp\\'
    update_budgets_file({"q": _mk_card("q")}, str(p))
    import paddle_tpu.analysis.cost_model as cm

    p.write_text(cm.render_budgets({"q": _mk_card("q")},
                                   reasons={"q": weird}))
    assert load_budgets(str(p))[0].reason == weird


def test_update_budgets_refuses_malformed_existing_file(tmp_path):
    """A malformed budgets.toml must fail the update LOUDLY: rewriting
    from scratch would replace every reviewed reason with the auto
    placeholder."""
    p = tmp_path / "budgets.toml"
    p.write_text('[[budget]]\ntarget = "t"\nreason = unquoted\n')
    with pytest.raises(ValueError):
        update_budgets_file({"t": _mk_card("t")}, str(p))
    assert "unquoted" in p.read_text()  # file untouched


def test_lint_gate_rejects_cards_only_strict_combo():
    """--strict-allowlist needs the lint pass; silently no-opping it under
    --cards-only would report success under the wrong configuration."""
    mod = _load_lint_gate()
    assert mod.main(["--cards-only", "--strict-allowlist"]) == 2
    with pytest.raises(SystemExit):
        mod.main(["--strict_allowlist"])  # typo'd flag is a hard error


def test_update_budgets_keeps_hand_added_eqns_ceiling(tmp_path):
    p = tmp_path / "budgets.toml"
    p.write_text('[[budget]]\ntarget = "t"\nscatters = 0\neqns = 99\n'
                 'reason = "eqns deliberately ceilinged"\n')
    update_budgets_file({"t": _mk_card("t", eqns=10)}, str(p))
    b = load_budgets(str(p))[0]
    assert b.ceilings["eqns"] == 10  # re-measured, not silently dropped


def test_update_budgets_keeps_ceiling_when_field_unknowable(tmp_path):
    """A card field of None this run (collective_bytes on a host whose
    multi-device compile failed) must not silently un-gate the previous
    ceiling on rewrite."""
    p = tmp_path / "budgets.toml"
    p.write_text('[[budget]]\ntarget = "t"\ncollective_bytes = 524288\n'
                 'reason = "the two psums per layer"\n')
    update_budgets_file({"t": _mk_card("t", collective_bytes=None)}, str(p))
    b = load_budgets(str(p))[0]
    assert b.ceilings["collective_bytes"] == 524288  # preserved


def test_ambient_disable_pallas_does_not_swap_carded_program(monkeypatch):
    """The env-pin contract: an operator's ambient opt-out for an
    UNRELATED kernel must not demote the gate's traced program to the
    gather oracle (analysis is pure tracing — never executes a kernel)."""
    from paddle_tpu.analysis.targets import run_card

    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "paged_attention")
    card = run_card("serving_flash_decode_step")
    # still the fused stage-2 program: fused attention + fused MLP per
    # layer + the final norm, zero scatters (ISSUE 15)
    assert card.pallas_calls == 3 and card.scatters == 0


# ---------------------------------------------------------------------------
# the gates (tier-1) + stale allowlist strictness + --json CLI
# ---------------------------------------------------------------------------

def test_card_gate_over_registered_targets():
    """ISSUE 12 acceptance, mirroring test_lint_gate_over_registered_
    targets: every registered target gets a ProgramCard and passes its
    reasoned budgets.toml ceiling set (incl. the VMEM cap per launch)."""
    assert _load_lint_gate().main(["--cards-only"]) == 0


def test_stale_allowlist_entry_gates_under_strict(tmp_path):
    """Satellite: a suppression matching no finding anywhere is a warning
    by default and a gate failure under --strict-allowlist."""
    src = open(os.path.join(REPO, "paddle_tpu", "analysis",
                            "allowlist.toml")).read()
    p = tmp_path / "allow.toml"
    p.write_text(src + '\n[[allow]]\nrule = "dtype_upcast"\n'
                 'match = "no_such_function_anywhere"\n'
                 'reason = "stale test entry"\n')
    assert _load_lint_gate().main(
        ["--allowlist", str(p), "--strict-allowlist"]) == 1


def test_cli_json_lint_mode(capsys):
    from paddle_tpu.analysis.__main__ import main

    # a target that still carries an allowlisted finding (the f32 router);
    # llama_train_step has had none since its flash kernels take bf16 operands
    rc = main(["--target", "moe_llama_train_step", "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    data = json.loads(out)
    r = data["reports"][0]
    assert r["target"] == "moe_llama_train_step" and r["ok"]
    assert isinstance(r["findings"], list) and r["allowlisted"]


def test_cli_json_cards_mode(capsys):
    from paddle_tpu.analysis.__main__ import main

    rc = main(["--cards", "--target", "llama_train_step", "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    data = json.loads(out)
    card = data["cards"]["llama_train_step"]
    assert card["pallas_calls"] >= 1 and card["trace_families"] == 1
    assert data["ok"] and isinstance(data["findings"], list)
