#!/usr/bin/env python
"""CI gate: jaxpr-level TPU lint + static program-card budgets over every
registered target.

Per target the gate runs the six lint rules — including the
kernel-contract verifier (``paddle_tpu/analysis/kernel_contracts.py``:
index-map bounds, output write races, alias safety for every
``pallas_call``) — AND derives the static ProgramCard (peak live HBM,
launch census, collective bytes, VMEM fit, trace families,
kernel-contract sections — ``paddle_tpu/analysis/cost_model.py``) in one
build/trace pass; serving targets additionally run the host-contract pass
(``paddle_tpu/analysis/host_contracts.py``: ``_host_overlap()`` race /
blocking-fetch analysis + fleet/request state-machine protocol
verification, memoized module-wide), whose findings gate through the same
allowlist and whose sections ride the card; cards are then checked
against the reasoned per-target ceilings in
``paddle_tpu/analysis/budgets.toml``.  The KNOWN_KERNELS
drift lint (dead / unregistered kill switches) runs once after the target
loop, gated like stale allowlist entries.  Exits 0 when every target is
clean
(or fully allowlisted) AND within budget — wired into the tier-1 suite
(tests/test_analysis.py::test_lint_gate_over_registered_targets,
tests/test_program_cards.py::test_card_gate_over_registered_targets) so a
change that knocks a hot path off the fast path (f32 upcast, dropped
donation, cache-key churn, a stray callback) OR regresses its static cost
(a scatter back on the fused decode path, peak HBM growth, a doubled trace
family, an over-VMEM launch) fails the suite instead of surfacing as bench
drift rounds later.

Usage::

    JAX_PLATFORMS=cpu python tools/lint_gate.py [--verbose]
        [--strict-allowlist] [--cards-only] [--json]
        [--allowlist PATH] [--budgets PATH]

``--strict-allowlist`` turns stale allowlist entries (suppressions that
matched NO finding across all targets — a reviewed-and-fixed leak whose
pragma lingers) from a warning into a gate failure.  ``--cards-only``
skips the lint rules and runs just the card/budget layer.  ``--json``
replaces the text output with one machine-readable document — per-target
findings/allowlisted plus the full card summary (``kernel_contracts`` and
``host_contracts`` sections included), budget findings, drift and stale
sweeps; exit codes are unchanged.  The PATH overrides exist for tests;
CI runs the packaged files.

Exit codes: 0 clean, 1 gating findings (lint, budget, or strict-stale),
2 a target failed to build/trace (a broken target is a gate failure, not a
skip — otherwise a refactor that renames a traced function silently turns
the gate off).
"""

from __future__ import annotations

import sys
import traceback


def _parse_argv(argv):
    """Strict argparse flag parsing (no abbreviations): an unrecognized
    token — a CI job typo like ``--strict_allowlist`` — exits 2 rather
    than running the gate under the wrong configuration and reporting
    success."""
    import argparse

    p = argparse.ArgumentParser(
        prog="python tools/lint_gate.py", allow_abbrev=False,
        description="CI gate: TPU lint + program-card budgets over every "
                    "registered analysis target")
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("--strict-allowlist", action="store_true",
                   help="stale allowlist entries gate instead of warning")
    p.add_argument("--cards-only", action="store_true",
                   help="skip the lint rules; run just the card/budget gate")
    p.add_argument("--json", action="store_true",
                   help="emit one machine-readable document instead of "
                        "text (exit codes unchanged)")
    p.add_argument("--allowlist", default=None, metavar="PATH")
    p.add_argument("--budgets", default=None, metavar="PATH")
    return p.parse_args(argv)


def main(argv=None) -> int:
    """Pure gate logic: assumes paddle_tpu is importable and the backend is
    already configured (the ``__main__`` block does both for script use;
    the in-process tier-1 tests run under conftest's CPU-forced config) —
    no process-global mutation here, so an in-process caller's environment
    survives the gate."""
    args = _parse_argv(sys.argv[1:] if argv is None else list(argv))
    verbose = args.verbose
    strict_allowlist = args.strict_allowlist
    cards_only = args.cards_only
    allowlist_path = args.allowlist
    budgets_path = args.budgets
    json_mode = args.json
    # --json: text output is replaced wholesale by one document printed at
    # the end; every section the text mode prints has a key here
    doc = {"targets": [], "budget_findings": [], "registry_drift": [],
           "stale_allowlist": []} if json_mode else None

    if cards_only and strict_allowlist:
        # the stale-allowlist sweep needs the lint reports the cards-only
        # path never produces — accepting the combination would be a
        # silent no-op reporting success under the wrong configuration
        print("lint gate: --strict-allowlist requires the lint pass; "
              "drop --cards-only", file=sys.stderr)
        return 2

    from paddle_tpu.analysis import load_allowlist
    from paddle_tpu.analysis.cost_model import (check_budgets, gate_cards,
                                                load_budgets)
    from paddle_tpu.analysis.targets import (GATE_TARGETS, TARGETS, run,
                                             run_card)

    # load both config files BEFORE the (minutes-long) target loop: a
    # typoed --allowlist/--budgets path or a malformed file must fail
    # immediately with the documented exit contract, not as an uncaught
    # traceback after all the work
    try:
        allowlist = load_allowlist(allowlist_path)
        budgets = load_budgets(budgets_path)
    except Exception as e:
        print(f"lint gate: cannot load allowlist/budgets: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 2
    rc = 0
    cards = {}
    reports = []
    for name in GATE_TARGETS:
        try:
            if cards_only:
                # the cards-only path IS targets.run_card (build + env
                # pins + build_card) — one implementation, two gates
                cards[name] = run_card(name)
                if json_mode:
                    doc["targets"].append(
                        {"target": name, "card": cards[name].summary()})
                continue
            # targets.run applies the target's env pins + analyze_kwargs —
            # the single implementation every gate entry point shares
            report = run(name, card=True, allowlist=allowlist)
        except Exception:
            print(f"== {name}: FAILED to build/trace ==", file=sys.stderr)
            traceback.print_exc()
            rc = max(rc, 2)
            continue
        reports.append(report)
        if report.card is not None:
            cards[name] = report.card
        if json_mode:
            import dataclasses

            doc["targets"].append({
                "target": name, "ok": report.ok,
                "card": (report.card.summary()
                         if report.card is not None else None),
                "findings": [dataclasses.asdict(f) for f in report.findings],
                "allowlisted": [{**dataclasses.asdict(f),
                                 "reason": a.reason}
                                for f, a in report.allowlisted]})
        else:
            print(report.render(verbose=verbose))
        if not report.ok:
            rc = max(rc, 1)

    # --- program-card budget gate (cost_model.py, budgets.toml) ---------
    if cards_only:
        # the ONE cards-gate policy, shared with the --cards CLI (card
        # findings pass the allowlist exactly like the full-gate path)
        budget_findings = gate_cards(cards, budgets, allowlist=allowlist,
                                     registered=TARGETS)
    else:
        # analyze() already folded card findings into each report
        budget_findings = check_budgets(cards, budgets, registered=TARGETS)
    for f in budget_findings:
        if json_mode:
            import dataclasses

            doc["budget_findings"].append(dataclasses.asdict(f))
        else:
            print("  " + f.render()
                  + (f"  <{f.target}>" if f.target else ""))
        if f.severity != "info":
            rc = max(rc, 1)

    # --- KNOWN_KERNELS drift (dead / unregistered kill switches) --------
    # cross-references the PADDLE_TPU_DISABLE_PALLAS vocabulary against
    # the kernel_disabled() dispatch sites actually in the package
    # (analysis/kernel_contracts.py); same policy as stale allowlist
    # entries — warning by default, gating under --strict-allowlist, so a
    # renamed or retired kernel cannot leave a dead kill switch behind
    if not cards_only:
        from paddle_tpu.analysis import registry_drift_findings

        for f in registry_drift_findings():
            if json_mode:
                doc["registry_drift"].append(
                    {"rule": f.rule, "message": f.message,
                     "gating": strict_allowlist})
            elif strict_allowlist:
                print(f"  ERROR   {f.rule}: {f.message}")
            else:
                print(f"  warning {f.rule}: {f.message} "
                      f"(gating under --strict-allowlist)")
            if strict_allowlist:
                rc = max(rc, 1)

    # --- stale-allowlist detection (suppressions covering nothing) ------
    if rc >= 2:
        # a target that failed to build produced no report: its live
        # allowlist entries would be falsely reported stale with
        # "delete the entry" advice — skip the sweep; the exit code
        # already signals the broken gate
        print("  (stale-allowlist sweep skipped: a target failed to "
              "build, its suppressions cannot be attributed)")
    elif not cards_only:
        used = {id(a) for r in reports for _, a in r.allowlisted}
        stale = [a for a in allowlist if id(a) not in used]
        for a in stale:
            line = (f"allowlist entry matched no finding across all "
                    f"registered targets (rule={a.rule!r} "
                    f"target={a.target!r} match={a.match!r}) — the "
                    f"suppressed finding was fixed or renamed; delete the "
                    f"entry (reason on file: {a.reason[:80]})")
            if json_mode:
                doc["stale_allowlist"].append(
                    {"rule": a.rule, "target": a.target, "match": a.match,
                     "gating": strict_allowlist})
            elif strict_allowlist:
                print(f"  ERROR   stale_allowlist: {line}")
            else:
                print(f"  warning stale_allowlist: {line} "
                      f"(gating under --strict-allowlist)")
            if strict_allowlist:
                rc = max(rc, 1)

    if json_mode:
        import json

        doc["ok"] = rc == 0
        doc["exit"] = rc
        print(json.dumps(doc, indent=2))
    if rc == 1 and not json_mode:
        print("\nlint gate FAILED: fix the findings, allowlist them in "
              "paddle_tpu/analysis/allowlist.toml (with a reason), or — "
              "for budget regressions you mean to keep — re-run "
              "`python -m paddle_tpu.analysis --cards --update-budgets` "
              "and justify the new ceilings in budgets.toml",
              file=sys.stderr)
    return rc


if __name__ == "__main__":
    # script invocation: make the repo importable and pin the CPU backend
    # (analysis is pure tracing — it never takes the TPU).  Kept out of main() so the in-process tier-1 test does not
    # leak env/config mutations into the rest of the pytest run.
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the multi-device target (serving_tp_step) needs a host mesh: force
    # the virtual CPU device count like tests/conftest.py (pre-init only)
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
    import jax

    try:
        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    except Exception:
        pass
    sys.exit(main())
