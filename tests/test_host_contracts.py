"""Host-contract verifier tests (ISSUE 18 acceptance).

The four injected-violation fixtures — an overlap method writing a
launch-read field, an undeclared health transition, a resurrecting
terminal status, and a blocking fetch inside the overlap window — must
each fail ``tools/lint_gate.py`` naming the field/edge/method; plus the
effect analysis's determinism across runs, the validated
``PADDLE_TPU_HOST_VERIFY_DEPTH`` knob, the declared-table model checks,
and the pinned-clean regression over the REAL engine + fleet: zero
protocol findings and exactly the reviewed journal-overlap set
(stats/_jdirty/_jentries x 3 step methods + the journal_entry asarray),
all allowlisted by the packaged allowlist.
"""

from __future__ import annotations

import importlib.util
import json
import os
import textwrap

import pytest

from paddle_tpu.analysis import Report, Severity, load_allowlist
from paddle_tpu.analysis.host_contracts import (DEFAULT_HOST_DEPTH,
                                                MachineSpec,
                                                check_host_contracts,
                                                host_contracts_summary,
                                                host_verify_depth)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint_gate():
    spec = importlib.util.spec_from_file_location(
        "lint_gate", os.path.join(REPO, "tools", "lint_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _modules(src, name="fixture_engine"):
    return [(name, textwrap.dedent(src), f"{name}.py")]


# ---------------------------------------------------------------------------
# fixture sources: one injected violation each
# ---------------------------------------------------------------------------

SRC_RACE = """
    class FixtureEngine:
        def _host_overlap(self):
            self._table = self._rebuild()

        def step(self):
            operands = self._table
            launch = self._launch(operands)
            self._host_overlap()
            return launch
    """

SRC_BLOCKING = """
    import numpy as np

    class FixtureEngine:
        def _host_overlap(self):
            self._sync_tokens()

        def _sync_tokens(self):
            self.last = np.asarray(self._device_tokens)

        def step(self):
            launch = self._launch()
            self._host_overlap()
            return launch
    """

SRC_HEALTH = """
    class FixtureRouter:
        def _health_to(self, r, state):
            prev = self.health[r]
            if prev == state:
                return
            self.health[r] = state

        def _kill(self, r):
            self._health_to(r, "DEAD")

        def _degrade(self, r):
            if self.health[r] == "HEALTHY":
                self._health_to(r, "DEGRADED")

        def _heal(self, r):
            self._health_to(r, "HEALTHY")
    """

SRC_RESURRECT = """
    class FixtureEngine:
        def _admit(self, req):
            if req.status == "PENDING":
                req.status = "RUNNING"

        def _retire(self, req):
            req.status = "FINISHED"

        def retry(self, req):
            if req.status == "FINISHED":
                req.status = "RUNNING"
    """


def _health_machine():
    return MachineSpec(
        name="fixture_health", field="health", kind="self_index",
        states=("HEALTHY", "DEGRADED", "DEAD"),
        edges=frozenset({("HEALTHY", "DEGRADED"), ("DEGRADED", "HEALTHY"),
                         ("HEALTHY", "DEAD"), ("DEGRADED", "DEAD")}),
        terminal=frozenset({"DEAD"}), initial="HEALTHY",
        default_sources=frozenset(("HEALTHY", "DEGRADED", "DEAD")),
        ladder=("HEALTHY", "DEGRADED", "DEAD"),
        heal_edges=frozenset({("DEGRADED", "HEALTHY")}))


def _request_machine():
    return MachineSpec(
        name="fixture_lifecycle", field="status", kind="attr",
        states=("PENDING", "RUNNING", "FINISHED"),
        edges=frozenset({("PENDING", "RUNNING"), ("PENDING", "FINISHED"),
                         ("RUNNING", "FINISHED")}),
        terminal=frozenset({"FINISHED"}), initial="PENDING",
        default_sources=frozenset(("PENDING", "RUNNING")))


# ---------------------------------------------------------------------------
# unit level: each fixture produces exactly the named finding
# ---------------------------------------------------------------------------

def _run_fixture(src, machines):
    return check_host_contracts(target="t", modules=_modules(src),
                                machines=machines)


def test_overlap_race_names_field_and_method():
    findings, sections = _run_fixture(SRC_RACE, machines=())
    races = [f for f in findings if f.rule == "host_race"]
    assert len(races) == 1 and races[0].severity == Severity.ERROR
    assert "self._table" in races[0].message
    assert "FixtureEngine.step" in races[0].message
    ov = [s for s in sections if s["kind"] == "overlap"]
    assert ov[0]["races"][0]["field"] == "_table"


def test_blocking_fetch_names_call_and_function():
    findings, sections = _run_fixture(SRC_BLOCKING, machines=())
    hits = [f for f in findings if f.rule == "host_blocking"]
    assert len(hits) == 1 and hits[0].severity == Severity.ERROR
    assert "np.asarray" in hits[0].message
    assert "_sync_tokens" in hits[0].message
    assert [f for f in findings if f.rule == "host_race"] == []
    assert host_contracts_summary(sections)["blocking"] == 1


def test_undeclared_health_transition_names_edge():
    findings, sections = _run_fixture(SRC_HEALTH,
                                      machines=(_health_machine(),))
    bad = [f for f in findings if f.rule == "host_transition"]
    assert len(bad) == 1
    assert "DEAD->HEALTHY" in bad[0].message
    assert "_heal" in bad[0].where
    # the guarded/choke sites cover every declared edge despite the bug
    sec = [s for s in sections if s["kind"] == "machine"][0]
    assert sec["dead_edges"] == []
    assert [f for f in findings if f.rule == "host_dead_edge"] == []


def test_resurrecting_terminal_status_names_edge():
    findings, _ = _run_fixture(SRC_RESURRECT,
                               machines=(_request_machine(),))
    bad = [f for f in findings if f.rule == "host_transition"]
    assert len(bad) == 1
    assert "FINISHED->RUNNING" in bad[0].message
    assert "retry" in bad[0].where
    assert [f for f in findings if f.rule == "host_dead_edge"] == []


def test_dead_edge_detected_when_site_removed():
    src = SRC_HEALTH.replace("self._health_to(r, \"DEGRADED\")",
                             "pass")
    findings, _ = _run_fixture(src, machines=(_health_machine(),))
    dead = [f for f in findings if f.rule == "host_dead_edge"]
    assert any("HEALTHY->DEGRADED" in f.message for f in dead)


def test_mirror_stores_are_exempt_but_counted():
    src = """
        class FixtureRouter:
            def _finish(self, f, copy):
                f.status = copy.status
    """
    findings, sections = _run_fixture(src, machines=(_request_machine(),))
    assert [f for f in findings if f.rule == "host_transition"] == []
    sec = [s for s in sections if s["kind"] == "machine"][0]
    assert sec["mirror_sites"] == 1 and sec["sites"] == 0


def test_dynamic_store_is_unverifiable():
    src = """
        class FixtureEngine:
            def mark(self, req, flag):
                req.status = "RUN" + flag
    """
    findings, _ = _run_fixture(src, machines=(_request_machine(),))
    assert any(f.rule == "host_transition"
               and "dynamic" in f.message for f in findings)


def test_model_check_rejects_bad_declared_tables():
    base = _health_machine()
    # terminal state with an outgoing edge
    leaky = MachineSpec(**{**base.__dict__,
                           "edges": base.edges | {("DEAD", "HEALTHY")}})
    findings, _ = _run_fixture("x = 1", machines=(leaky,))
    assert any(f.rule == "host_protocol" and "absorbing" in f.message
               for f in findings)
    # ladder climb without a heal edge
    climby = MachineSpec(**{**base.__dict__, "heal_edges": frozenset()})
    findings, _ = _run_fixture("x = 1", machines=(climby,))
    assert any(f.rule == "host_protocol" and "ladder" in f.message
               for f in findings)
    # unreachable state
    island = MachineSpec(**{**base.__dict__,
                            "states": base.states + ("LIMBO",)})
    findings, _ = _run_fixture("x = 1", machines=(island,))
    assert any(f.rule == "host_protocol" and "unreachable" in f.message
               for f in findings)


# ---------------------------------------------------------------------------
# the real modules: pinned-clean regression (ISSUE 18 satellite)
# ---------------------------------------------------------------------------

def test_real_modules_pinned_clean(monkeypatch):
    """The shipped engine + fleet verify clean: zero state-machine
    findings, and the raw overlap set is EXACTLY the reviewed journal
    overlap — 3 fields x 3 step methods + the one journal_entry asarray —
    every one covered by the packaged allowlist."""
    import paddle_tpu.analysis.host_contracts as hc

    monkeypatch.setattr(hc, "_CACHE", {})
    findings, sections = check_host_contracts(target="host")
    protocol = [f for f in findings
                if f.rule in ("host_transition", "host_dead_edge",
                              "host_protocol")]
    assert protocol == []
    races = [f for f in findings if f.rule == "host_race"]
    blocking = [f for f in findings if f.rule == "host_blocking"]
    assert len(races) == 9 and len(blocking) == 1
    assert len(findings) == 10
    fields = {m for f in races for m in ("stats", "_jdirty", "_jentries")
              if f"self.{m} is read" in f.message}
    assert fields == {"stats", "_jdirty", "_jentries"}
    assert "journal_entry" in blocking[0].message
    report = Report("host", findings, allowlist=load_allowlist())
    assert report.ok and len(report.allowlisted) == 10
    # both machines fully covered, both directions
    for sec in sections:
        if sec["kind"] == "machine":
            assert sec["dead_edges"] == [] and sec["undeclared"] == []
            assert len(sec["covered_edges"]) == len(sec["declared_edges"])
    summary = host_contracts_summary(sections)
    assert summary["violations"] == 10
    assert summary["machines"] == 2 and summary["windows"] == 3


@pytest.mark.parametrize("method", ["_step", "_mixed_step", "_spec_step"])
def test_step_family_has_one_window_and_one_launch(method):
    """Each step family launches its compiled program from ONE call site and
    opens ONE ``_host_overlap()`` window behind it: a second arm of either
    (a launch written twice for an option) is what lost a request inside
    ``_mixed_step`` rewrites before."""
    _, sections = check_host_contracts(target="host")
    (sec,) = [s for s in sections if s["kind"] == "overlap"
              and s["method"] == f"ContinuousBatchingEngine.{method}"]
    assert len(sec["windows"]) == 1, sec["windows"]
    assert len(sec["launches"]) == 1, sec["launches"]
    assert sec["launches"][0] < sec["windows"][0]


def test_effect_analysis_deterministic(monkeypatch):
    import paddle_tpu.analysis.host_contracts as hc

    monkeypatch.setattr(hc, "_CACHE", {})
    f1, s1 = check_host_contracts(target="host")
    monkeypatch.setattr(hc, "_CACHE", {})   # force a true re-run
    f2, s2 = check_host_contracts(target="host")
    assert [(f.rule, f.message, f.where) for f in f1] \
        == [(f.rule, f.message, f.where) for f in f2]
    assert s1 == s2
    # cached path returns equal but not aliased sections
    f3, s3 = check_host_contracts(target="host")
    assert s3 == s2 and s3 is not s2


def test_host_verify_depth_env_knob_validated(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_HOST_VERIFY_DEPTH", raising=False)
    assert host_verify_depth() == DEFAULT_HOST_DEPTH
    monkeypatch.setenv("PADDLE_TPU_HOST_VERIFY_DEPTH", "3")
    assert host_verify_depth() == 3
    monkeypatch.setenv("PADDLE_TPU_HOST_VERIFY_DEPTH", "deep")
    with pytest.warns(UserWarning, match="HOST_VERIFY_DEPTH"):
        assert host_verify_depth() == DEFAULT_HOST_DEPTH
    monkeypatch.setenv("PADDLE_TPU_HOST_VERIFY_DEPTH", "0")
    with pytest.warns(UserWarning, match="minimum"):
        assert host_verify_depth() == DEFAULT_HOST_DEPTH


def test_depth_bounds_call_resolution():
    """depth=1 resolves _host_overlap itself but not its callee — the
    blocking fetch two hops away disappears; the default depth finds it."""
    findings_deep, _ = check_host_contracts(
        target="t", modules=_modules(SRC_BLOCKING), machines=())
    assert any(f.rule == "host_blocking" for f in findings_deep)
    findings_shallow, _ = check_host_contracts(
        target="t", modules=_modules(SRC_BLOCKING), machines=(), depth=0)
    assert not any(f.rule == "host_blocking" for f in findings_shallow)


# ---------------------------------------------------------------------------
# lint-gate integration: each injected violation fails the gate by name
# ---------------------------------------------------------------------------

def _fixture_target(name):
    """A trivially jittable gate target carrying the host pass opt-in."""
    from paddle_tpu.analysis.targets import AnalysisTarget

    def build():
        import jax.numpy as jnp

        def f(x):
            return x + 1

        return AnalysisTarget(name, f, (jnp.zeros((2, 2)),),
                              analyze_kwargs={"host": True})

    return build


def _patch_host_fixture(monkeypatch, src, machines):
    import paddle_tpu.analysis.host_contracts as hc

    monkeypatch.setattr(hc, "_CACHE", {})
    monkeypatch.setattr(hc, "_default_modules", lambda: _modules(src))
    monkeypatch.setattr(hc, "_default_machines", lambda: machines)


@pytest.mark.parametrize("src,machines,rule,needles", [
    (SRC_RACE, (), "host_race", ("self._table", "FixtureEngine.step")),
    (SRC_BLOCKING, (), "host_blocking", ("np.asarray", "_sync_tokens")),
    (SRC_HEALTH, "health", "host_transition", ("DEAD->HEALTHY", "_heal")),
    (SRC_RESURRECT, "request", "host_transition",
     ("FINISHED->RUNNING", "retry")),
])
def test_injected_violation_fails_lint_gate(monkeypatch, capsys, tmp_path,
                                            src, machines, rule, needles):
    """ISSUE 18 acceptance: all four injected-violation fixtures fail
    ``lint_gate`` naming the field/edge/method, and the budget layer
    independently trips on the raw violation count."""
    import paddle_tpu.analysis.targets as targets_mod

    machines = {"health": (_health_machine(),),
                "request": (_request_machine(),)}.get(machines, machines)
    _patch_host_fixture(monkeypatch, src, machines)
    name = f"fixture_{rule}"
    monkeypatch.setattr(targets_mod, "TARGETS",
                        {name: _fixture_target(name)})
    monkeypatch.setattr(targets_mod, "GATE_TARGETS", (name,))
    allow = tmp_path / "allow.toml"
    allow.write_text("# empty\n")
    budgets = tmp_path / "budgets.toml"
    budgets.write_text(f'[[budget]]\ntarget = "{name}"\n'
                       f'host_contract_violations = 0\n'
                       f'reason = "fixture: zero tolerated violations"\n')
    mod = _load_lint_gate()
    rc = mod.main(["--allowlist", str(allow), "--budgets", str(budgets)])
    out = capsys.readouterr().out
    assert rc == 1
    assert rule in out
    for needle in needles:
        assert needle in out
    assert "host_contract_violations" in out


def test_clean_host_fixture_passes_lint_gate(monkeypatch, capsys, tmp_path):
    import paddle_tpu.analysis.targets as targets_mod

    clean = """
        class FixtureEngine:
            def _host_overlap(self):
                self.overlap_ticks = self.overlap_ticks + 1

            def step(self):
                launch = self._launch(self.table)
                self._host_overlap()
                return launch
    """
    _patch_host_fixture(monkeypatch, clean, ())
    monkeypatch.setattr(targets_mod, "TARGETS",
                        {"fixture_clean": _fixture_target("fixture_clean")})
    monkeypatch.setattr(targets_mod, "GATE_TARGETS", ("fixture_clean",))
    allow = tmp_path / "allow.toml"
    allow.write_text("# empty\n")
    budgets = tmp_path / "budgets.toml"
    budgets.write_text('[[budget]]\ntarget = "fixture_clean"\n'
                       'host_contract_violations = 0\n'
                       'reason = "fixture: clean overlap"\n')
    mod = _load_lint_gate()
    rc = mod.main(["--allowlist", str(allow), "--budgets", str(budgets)])
    capsys.readouterr()
    assert rc == 0


def test_lint_gate_json_carries_host_section(monkeypatch, capsys, tmp_path):
    """--json: the per-target document carries the card's host_contracts
    section (ISSUE 18 satellite)."""
    import paddle_tpu.analysis.targets as targets_mod

    _patch_host_fixture(monkeypatch, SRC_RACE, ())
    name = "fixture_json"
    monkeypatch.setattr(targets_mod, "TARGETS",
                        {name: _fixture_target(name)})
    monkeypatch.setattr(targets_mod, "GATE_TARGETS", (name,))
    allow = tmp_path / "allow.toml"
    allow.write_text("# empty\n")
    budgets = tmp_path / "budgets.toml"
    budgets.write_text(f'[[budget]]\ntarget = "{name}"\n'
                       f'host_contract_violations = 0\n'
                       f'reason = "fixture: json shape"\n')
    mod = _load_lint_gate()
    rc = mod.main(["--json", "--allowlist", str(allow),
                   "--budgets", str(budgets)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["ok"] is False and doc["exit"] == 1
    tgt = doc["targets"][0]
    assert tgt["target"] == name
    hc = tgt["card"]["host_contracts"]
    assert hc["races"] == 1 and tgt["card"]["host_contract_violations"] == 1
    assert any(f["rule"] == "host_race" for f in tgt["findings"])
    assert any("host_contract_violations" in f["message"]
               for f in doc["budget_findings"])


def test_stale_host_allowlist_entry_gates_under_strict(monkeypatch, capsys,
                                                       tmp_path):
    """A host-contract allowlist entry matching nothing is caught by the
    existing stale sweep under --strict-allowlist."""
    import paddle_tpu.analysis.targets as targets_mod

    clean = """
        class FixtureEngine:
            def _host_overlap(self):
                pass

            def step(self):
                launch = self._launch()
                self._host_overlap()
                return launch
    """
    _patch_host_fixture(monkeypatch, clean, ())
    name = "fixture_stale"
    monkeypatch.setattr(targets_mod, "TARGETS",
                        {name: _fixture_target(name)})
    monkeypatch.setattr(targets_mod, "GATE_TARGETS", (name,))
    allow = tmp_path / "allow.toml"
    allow.write_text('[[allow]]\nrule = "host_race"\n'
                     'match = "self.retired_field"\n'
                     'reason = "was reviewed; the race is long fixed"\n')
    budgets = tmp_path / "budgets.toml"
    budgets.write_text(f'[[budget]]\ntarget = "{name}"\n'
                       f'host_contract_violations = 0\n'
                       f'reason = "fixture: stale sweep"\n')
    mod = _load_lint_gate()
    rc = mod.main(["--strict-allowlist", "--allowlist", str(allow),
                   "--budgets", str(budgets)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "stale_allowlist" in out and "host_race" in out


# ---------------------------------------------------------------------------
# the --host CLI mode
# ---------------------------------------------------------------------------

def test_cli_host_mode_green_and_json(monkeypatch, capsys):
    """ISSUE 18 acceptance: ``python -m paddle_tpu.analysis --host`` is
    green over the shipped engine + fleet, and --json carries the
    sections + summary."""
    from paddle_tpu.analysis.__main__ import main

    assert main(["--host"]) == 0
    capsys.readouterr()
    assert main(["--host", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is True
    assert doc["host_contracts"]["violations"] == 10
    assert doc["host_contracts"]["undeclared_transitions"] == 0
    assert len(doc["allowlisted"]) == 10 and doc["findings"] == []
    kinds = {s["kind"] for s in doc["sections"]}
    assert kinds == {"overlap", "machine"}


def test_cli_host_mode_gates_on_violation(monkeypatch, capsys):
    import paddle_tpu.analysis.host_contracts as hc
    from paddle_tpu.analysis.__main__ import main

    _patch_host_fixture(monkeypatch, SRC_RACE, ())
    assert main(["--host", "--no-allowlist"]) == 1
    out = capsys.readouterr().out
    assert "host_race" in out and "self._table" in out
