"""CLI: ``JAX_PLATFORMS=cpu python -m paddle_tpu.analysis --target <name>``.

Traces the named target (or ``--all``) and prints the findings; exit status
0 = clean or fully allowlisted, 1 = gating findings, making the module
directly usable as a pre-submit check.  ``tools/lint_gate.py`` is the CI
wrapper over the same registry.

``--cards`` switches to the program-card mode (cost_model.py): derive each
selected target's static ProgramCard and gate it against the checked-in
``analysis/budgets.toml`` ceilings (exit 1 on any over-budget field,
missing entry, stale entry, or over-VMEM-cap launch);
``--cards --update-budgets`` instead rewrites the budget file at the
measured values (preserving existing reasons) and exits 0 — the documented
workflow for a PR that legitimately moves a figure.  ``--json`` emits
machine-readable findings/cards on stdout in either mode (lint mode
additionally carries per-target ``seconds`` and the ``trace_reuse`` count
— the number of rule/card consumers sharing each target's ONE trace, the
CI evidence the gate is single-compile per target); exit codes are
unchanged.

``--host`` switches to the host-contracts mode (host_contracts.py): no
target builds, no tracing — just the AST effect/race analysis of the
serving engine's ``_host_overlap()`` windows and the exhaustive protocol
verification of the fleet health machine and request lifecycle, gated
through the same allowlist (exit 1 on any non-allowlisted finding).
This is the CI entry point ISSUE 18 names: ``python -m
paddle_tpu.analysis --host`` must stay green over engine + fleet.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    # analysis is pure tracing: never let the CLI take the TPU.  Effective
    # only when the backend is not yet
    # initialized — the canonical invocation sets JAX_PLATFORMS=cpu anyway.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # multi-device targets (serving_tp_step) need a host mesh: force the
    # virtual CPU device count like tests/conftest.py.  XLA_FLAGS is read
    # at BACKEND init, not jax import (running as ``-m`` already imported
    # the package, hence jax), so setting it here still works; it is
    # harmless if the backend is somehow already up — the target then
    # reports a build failure instead of tracing the wrong mesh.
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8")
    import jax

    try:
        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    except Exception:
        pass  # backend already up; proceed on whatever it is

    from . import load_allowlist
    from .targets import GATE_TARGETS, TARGETS
    from .targets import run as run_target
    from .targets import run_card

    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="jaxpr-level TPU lint over registered paddle_tpu targets")
    p.add_argument("--target", action="append", default=[],
                   help=f"target(s) to lint; registered: {sorted(TARGETS)}")
    p.add_argument("--all", action="store_true",
                   help="lint every gate target")
    p.add_argument("--list", action="store_true",
                   help="list registered targets and exit")
    p.add_argument("--allowlist", default=None,
                   help="allowlist TOML (default: packaged allowlist.toml)")
    p.add_argument("--no-allowlist", action="store_true",
                   help="show findings the allowlist would suppress")
    p.add_argument("--cards", action="store_true",
                   help="program-card mode: derive static cost/memory cards "
                        "and gate them against budgets.toml")
    p.add_argument("--host", action="store_true",
                   help="host-contracts mode: AST effect/race analysis of "
                        "the async host runtime + state-machine protocol "
                        "verification (no tracing)")
    p.add_argument("--update-budgets", action="store_true",
                   help="with --cards: rewrite budgets.toml at the measured "
                        "values (reasons preserved) instead of gating")
    p.add_argument("--budgets", default=None,
                   help="budgets TOML (default: packaged budgets.toml)")
    p.add_argument("--json", action="store_true",
                   help="emit machine-readable findings/cards on stdout "
                        "(exit codes unchanged)")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="also print allowlisted findings with reasons")
    args = p.parse_args(argv)

    if args.list:
        for name in sorted(TARGETS):
            gate = " [gate]" if name in GATE_TARGETS else ""
            print(f"{name}{gate}")
        return 0
    if args.update_budgets and not args.cards:
        p.error("--update-budgets requires --cards")
    if args.host:
        if args.cards or args.target or args.all:
            p.error("--host is a standalone mode (module-scoped, not "
                    "per-target); drop --cards/--target/--all")
        return _host_main(args)
    names = list(args.target) or (
        list(GATE_TARGETS) if (args.all or args.cards) else [])
    if not names:
        p.error("pass --target <name> (repeatable), --all, --host, "
                "or --list")

    if args.cards:
        return _cards_main(args, names, run_card, TARGETS)

    allowlist = [] if args.no_allowlist else load_allowlist(args.allowlist)
    rc = 0
    reports = []
    seconds = []
    for name in names:
        # per-target wall time INCLUDING the target build (the analyze
        # pass alone is report.seconds) — with trace_reuse in the JSON so
        # CI logs show each target stayed single-trace: N rule/card
        # consumers sharing the one ClosedJaxpr, not N traces
        t0 = time.perf_counter()
        report = run_target(name, allowlist=allowlist)
        seconds.append(time.perf_counter() - t0)
        reports.append(report)
        if not args.json:
            print(report.render(verbose=args.verbose))
        if not report.ok:
            rc = 1
    if args.json:
        import dataclasses
        import json

        print(json.dumps({"reports": [
            {"target": r.target, "ok": r.ok, "n_traces": r.n_traces,
             "seconds": round(secs, 3),
             "analyze_seconds": (round(r.seconds, 3)
                                 if r.seconds is not None else None),
             "trace_reuse": r.trace_reuse,
             "traces_performed": r.traces_performed,
             "findings": [dataclasses.asdict(f) for f in r.findings],
             "allowlisted": [{**dataclasses.asdict(f), "reason": a.reason}
                             for f, a in r.allowlisted]}
            for r, secs in zip(reports, seconds)]}, indent=2))
    if rc and not args.json:
        print("\nlint FAILED: fix the findings above or allowlist them in "
              "paddle_tpu/analysis/allowlist.toml with a reason",
              file=sys.stderr)
    return rc


def _host_main(args) -> int:
    """--host: the standalone host-contracts gate (host_contracts.py) —
    pure AST over the shipped engine + fleet sources and their declared
    transition tables, gated through the same allowlist as every lint
    rule.  Prints the per-window / per-machine sections (or --json with
    the raw section dicts) and exits 1 on any non-allowlisted finding."""
    from . import Report, load_allowlist
    from .host_contracts import check_host_contracts, host_contracts_summary

    allowlist = [] if args.no_allowlist else load_allowlist(args.allowlist)
    t0 = time.perf_counter()
    findings, sections = check_host_contracts(target="host")
    secs = time.perf_counter() - t0
    report = Report("host", findings, allowlist=allowlist)
    summary = host_contracts_summary(sections)
    if args.json:
        import dataclasses
        import json

        print(json.dumps(
            {"host_contracts": summary, "sections": sections,
             "seconds": round(secs, 3), "ok": report.ok,
             "findings": [dataclasses.asdict(f) for f in report.findings],
             "allowlisted": [{**dataclasses.asdict(f), "reason": a.reason}
                             for f, a in report.allowlisted]}, indent=2))
    else:
        print(f"-- host contracts: {summary['methods']} overlap method(s) "
              f"/ {summary['windows']} window(s), {summary['machines']} "
              f"state machine(s) / {summary['sites']} transition site(s); "
              f"{summary['races']} race(s), {summary['blocking']} blocking "
              f"fetch(es), {summary['undeclared_transitions']} undeclared "
              f"transition(s), {summary['dead_edges']} dead edge(s), "
              f"{summary['protocol']} protocol finding(s) --")
        for s in sections:
            if s.get("kind") == "overlap":
                print(f"   overlap {s['method']} "
                      f"windows={s['windows']} "
                      f"races={[r['field'] for r in s['races']]} "
                      f"blocking={len(s['blocking'])} [{s['where']}]")
            else:
                print(f"   machine {s['machine']} sites={s['sites']} "
                      f"edges {len(s['covered_edges'])}/"
                      f"{len(s['declared_edges'])} covered "
                      f"dead={s['dead_edges']} "
                      f"undeclared={len(s['undeclared'])} "
                      f"protocol={len(s['protocol'])}")
        print(report.render(verbose=args.verbose))
        if not report.ok:
            print("\nhost-contract gate FAILED: fix the race/transition "
                  "or allowlist it in paddle_tpu/analysis/allowlist.toml "
                  "with a reason", file=sys.stderr)
    return 0 if report.ok else 1


def _cards_main(args, names, run_card, TARGETS) -> int:
    """--cards: derive the selected targets' ProgramCards, then either
    rewrite budgets.toml (--update-budgets) or gate against it.  The stale
    check (budget entries naming no registered target) needs only the
    registry, so it runs regardless of which targets were selected.
    Gating policy lives in ONE place — ``cost_model.gate_cards`` — shared
    with ``tools/lint_gate.py --cards-only``; ``-v`` additionally prints
    the card findings the allowlist suppressed, with their reasons, like
    the lint mode."""
    from . import Report, load_allowlist
    from .cost_model import (card_findings, gate_cards, load_budgets,
                             update_budgets_file)

    card_seconds = {}
    cards = {}
    for name in names:
        t0 = time.perf_counter()
        cards[name] = run_card(name)
        card_seconds[name] = round(time.perf_counter() - t0, 3)
    if args.update_budgets:
        # registered=TARGETS: entries for targets NOT selected this run are
        # kept verbatim (a partial --target update must not delete the
        # rest); only unregistered (stale) entries retire
        path = update_budgets_file(cards, args.budgets, registered=TARGETS)
        print(f"wrote {len(cards)} budget entr"
              f"{'y' if len(cards) == 1 else 'ies'} to {path}")
        return 0
    allowlist = [] if args.no_allowlist else load_allowlist(args.allowlist)
    findings = gate_cards(cards, load_budgets(args.budgets),
                          allowlist=allowlist, registered=TARGETS)
    gating = [f for f in findings if f.severity != "info"]
    if args.json:
        import dataclasses
        import json

        print(json.dumps(
            {"cards": {n: c.summary() for n, c in cards.items()},
             "seconds": card_seconds,
             "findings": [dataclasses.asdict(f) for f in findings],
             "ok": not gating}, indent=2))
    else:
        for name in sorted(cards):
            print(cards[name].render())
            if args.verbose:
                rep = Report(name, card_findings(cards[name]),
                             allowlist=allowlist)
                for f, a in rep.allowlisted:
                    print(f"   ALLOWED {f.render().strip()}  "
                          f"(reason: {a.reason})")
        for f in findings:
            print(f.render() + (f"  <{f.target}>" if f.target else ""))
        if gating:
            print("\ncard gate FAILED: fix the regression or re-run "
                  "--cards --update-budgets and justify the new ceilings "
                  "in paddle_tpu/analysis/budgets.toml", file=sys.stderr)
    return 1 if gating else 0


if __name__ == "__main__":
    sys.exit(main())
