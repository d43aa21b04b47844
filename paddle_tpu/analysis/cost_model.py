"""Program cards: static cost & memory analysis over traced programs.

The serving stack's performance contract — launches per decode step, peak
live HBM, per-step collective bytes, VMEM fit of every Pallas launch,
compiled trace-family count — was until now enforced only dynamically
(``decode_step_launches()`` counts at runtime, bench rungs notice drift
rounds later).  This module derives all of it from the ClosedJaxpr the lint
rules already trace (zero device time, ``JAX_PLATFORMS=cpu``) and gates it
against checked-in per-target ceilings (``analysis/budgets.toml``), the
same contract the allowlist gives lint findings: every ceiling carries a
REQUIRED one-line reason, and a PR that reintroduces a scatter on the
fused decode path, doubles a step's trace families, or silently grows
peak HBM fails ``tools/lint_gate.py`` with a card diff instead of a bench
regression three rounds later (PAPERS.md: MPK makes launch count, and the
Gemma-on-TPU serving paper makes HBM residency, the quantities that decide
decode latency and cache capacity).

Card fields
-----------
``peak_hbm_bytes``          liveness pass over eqn def/use ranges: inputs
                            are caller-held for the whole step, donated
                            inputs credit their matching output (the
                            aliased buffer is not double-counted — same
                            for pallas ``input_output_aliases``), and
                            sub-jaxpr bodies (scan/pjit/remat/shard_map)
                            contribute their own internal peak at the eqn
                            that runs them.
``eqns / pallas_calls / scatters``
                            the launch census (:func:`eqn_census`): a
                            ``pallas_call`` is ONE launch however large
                            its body — the same walk
                            ``serving.decode_step_launches()`` reports at
                            runtime (a parity test pins the two together).
``collective_bytes``        per-step bytes crossing the mesh, summed from
                            the post-SPMD HLO with the resharding rule's
                            attribution (all-gather/all-to-all/all-reduce);
                            0 on single-device programs, None when the
                            compile is unavailable.
``vmem_bytes_per_launch``   max per-``pallas_call`` VMEM estimate (block
                            shapes x dtype + scratch operands) vs a
                            per-generation cap (:data:`VMEM_CAPS`,
                            ``PADDLE_TPU_VMEM_CAP_MIB`` override) —
                            over-cap is a gating finding.
``trace_families``          distinct jit cache signatures under the
                            recompile rule's equivalence perturbations
                            (``rules.signature_families``).
``kernel_contracts``        per-``pallas_call`` contract verdicts from the
                            kernel-contract verifier (kernel_contracts.py:
                            index-map bounds, output write races, alias
                            safety) on the same trace; the aggregate
                            ``kernel_contract_violations`` count is a
                            budgeted field — the reviewed set of
                            deliberate violations is a ceiling, so an
                            unsound new kernel fails the card gate too.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

from .report import Finding, Severity, _parse_mini_toml

__all__ = ["ProgramCard", "BudgetEntry", "VMEM_CAPS", "BUDGET_FIELDS",
           "DEFAULT_BUDGETS", "eqn_census", "peak_live_hbm",
           "vmem_estimates", "vmem_cap_bytes", "collective_bytes_from_hlo",
           "build_card", "card_findings", "load_budgets", "check_budgets",
           "gate_cards", "render_budgets", "update_budgets_file"]

DEFAULT_BUDGETS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "budgets.toml")

#: per-generation VMEM capacity a single Pallas launch must fit in
#: (bytes/core; the pallas guide's figure — v6e doubles it).  The fit
#: estimate is block residency only; the pipeline's double buffering and
#: compiler temporaries eat into the same budget, so a launch NEAR the cap
#: deserves scrutiny even when it passes.
VMEM_CAPS = {"v4": 16 << 20, "v5e": 16 << 20, "v5p": 16 << 20,
             "v6e": 32 << 20}

#: card fields a budgets.toml entry may (and --update-budgets does) ceiling.
#: ``eqns`` is deliberately NOT budgeted by default — it drifts with any
#: innocuous refactor; the census still reports it on the card.
#: ``kernel_contract_violations`` counts the RAW kernel-contract findings
#: (kernel_contracts.py) before the allowlist: the ceiling pins the
#: reviewed set of deliberate violations (0 for most targets; the fused
#: decode step's allowlisted in-place append overlap for the flash
#: target), so a NEW unsound kernel moves the figure even if someone
#: over-broadens an allowlist entry.
#: ``host_contract_violations`` is the host-side analog
#: (host_contracts.py): raw pre-allowlist count of _host_overlap() races,
#: blocking fetches, and state-machine protocol findings — nonzero only
#: for serving targets, where it pins the reviewed journal-overlap set.
BUDGET_FIELDS = ("peak_hbm_bytes", "pallas_calls", "scatters",
                 "collective_bytes", "vmem_bytes_per_launch",
                 "trace_families", "kernel_contract_violations",
                 "host_contract_violations")
_CEILING_KEYS = BUDGET_FIELDS + ("eqns",)


def _as_jaxpr(closed):
    return closed.jaxpr if hasattr(closed, "jaxpr") else closed


# ---------------------------------------------------------------------------
# launch census (shared with serving.decode_step_launches)
# ---------------------------------------------------------------------------

def eqn_census(closed) -> dict:
    """Count equations and launch-shaped primitives: every ``pallas_call``
    (ONE launch however large its body — in-kernel eqns are not dispatches,
    so the walk does not descend into it) and every scatter (the KV-append
    pattern).  Descends scan/pjit/remat/cond/shard_map bodies.  This is THE
    census — ``serving.decode_step_launches()`` calls it on the decode
    program, the static ProgramCard calls it on every registered target,
    and a parity test asserts the two agree."""
    from .rules import _sub_jaxprs

    counts = {"eqns": 0, "pallas_calls": 0, "scatters": 0}

    def walk(jx):
        counts["eqns"] += len(jx.eqns)
        for e in jx.eqns:
            nm = e.primitive.name
            if nm == "pallas_call":
                counts["pallas_calls"] += 1
                continue
            if nm.startswith("scatter"):
                counts["scatters"] += 1
            for sub in _sub_jaxprs(e):
                walk(sub)

    walk(_as_jaxpr(closed))
    return counts


# ---------------------------------------------------------------------------
# peak live HBM (liveness over eqn def/use ranges)
# ---------------------------------------------------------------------------

def _var_bytes(v) -> int:
    a = getattr(v, "aval", None)
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    except Exception:
        return 0


def _shape_sig(v):
    a = getattr(v, "aval", None)
    if a is None or not hasattr(a, "shape"):
        return None
    return (tuple(a.shape), str(a.dtype))


def _pallas_aliased_outvars(eqn) -> set:
    """Outvars a ``pallas_call`` writes in place over an input buffer
    (``input_output_aliases``) — the fused decode step's pool output lives
    here; its bytes are the input's, not a second allocation."""
    out = set()
    for pair in eqn.params.get("input_output_aliases") or ():
        try:
            _, o_idx = pair
            if 0 <= o_idx < len(eqn.outvars):
                out.add(eqn.outvars[o_idx])
        except Exception:
            continue
    return out


def _liveness_peak(jaxpr, boundary_counted: bool,
                   donated=(), _depth: int = 0) -> int:
    """Peak live bytes across the jaxpr's eqn timeline.

    ``boundary_counted=True`` (the top level): invars/constvars are
    caller-held HBM for the whole step; donated invars credit one matching
    (shape, dtype) output as aliased (size 0) — XLA reuses the donated
    buffer, so input and output never both cost.  ``False`` (sub-jaxpr
    bodies): boundary values are the caller's operands, already counted at
    the eqn that runs the body; only the body's OWN intermediates add, and
    the result rides on top of the caller's live set at that eqn
    (scan/pjit/remat/shard_map working sets).  ``pallas_call`` bodies never
    count — their refs are VMEM, not HBM."""
    from jax._src.core import Literal

    from .rules import _sub_jaxprs

    if _depth > 32:  # defensive: pathological nesting
        return 0
    n = len(jaxpr.eqns)
    size: dict = {}
    defat: dict = {}
    last: dict = {}

    aliased: set = set()
    real_outs = [v for v in jaxpr.outvars if not isinstance(v, Literal)]
    if boundary_counted and donated:
        claimed: set = set()
        for i, v in enumerate(jaxpr.invars):
            if i < len(donated) and donated[i]:
                sig = _shape_sig(v)
                for ov in real_outs:
                    if ov not in claimed and _shape_sig(ov) == sig:
                        claimed.add(ov)
                        break
        aliased |= claimed

    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        size[v] = _var_bytes(v) if boundary_counted else 0
        defat[v] = 0
        last[v] = n if boundary_counted else last.get(v, 0)

    inner_extra = [0] * (n + 1)
    for k, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal) and v in defat:
                last[v] = max(last[v], k)
        pal_alias = (_pallas_aliased_outvars(eqn)
                     if eqn.primitive.name == "pallas_call" else set())
        for ov in eqn.outvars:
            defat[ov] = k
            last[ov] = k
            size[ov] = 0 if (ov in pal_alias or ov in aliased) \
                else _var_bytes(ov)
        if eqn.primitive.name != "pallas_call":
            subs = _sub_jaxprs(eqn)
            if subs:
                inner_extra[k] = max(
                    _liveness_peak(s, False, _depth=_depth + 1)
                    for s in subs)
    for ov in real_outs:
        if ov in last:
            last[ov] = n  # outputs survive the step

    delta = [0] * (n + 2)
    for v, sz in size.items():
        if not sz:
            continue
        d, u = defat[v], max(last[v], defat[v])
        delta[d] += sz
        delta[u + 1] -= sz
    peak = cur = 0
    for k in range(n + 1):
        cur += delta[k]
        peak = max(peak, cur + (inner_extra[k] if k < n else 0))
    return peak


def peak_live_hbm(closed, donated=None) -> int:
    """Peak live HBM estimate (bytes) of one execution of the traced
    program.  ``donated`` overrides the donation flags read off the pjit
    eqn (a plain traced callable has none)."""
    from .rules import _unwrap_pjit

    inner, don = _unwrap_pjit(closed)
    if donated is None:
        donated = don or ()
    return _liveness_peak(_as_jaxpr(inner), True, donated=tuple(donated))


# ---------------------------------------------------------------------------
# per-pallas-call VMEM fit
# ---------------------------------------------------------------------------

def vmem_cap_bytes(generation: str = "v4") -> int:
    """The VMEM ceiling a single launch is gated against: the
    per-generation figure (:data:`VMEM_CAPS`; default the v4 16 MiB floor,
    the conservative bound every current generation satisfies), overridden
    by ``PADDLE_TPU_VMEM_CAP_MIB`` (validated integer, utils/envflags.py)."""
    from ..utils.envflags import env_int

    cap_mib = VMEM_CAPS.get(generation, VMEM_CAPS["v4"]) >> 20
    return env_int("PADDLE_TPU_VMEM_CAP_MIB", cap_mib, minimum=1) << 20


def _pallas_vmem(eqn) -> dict:
    """Block shapes x dtype + scratch operands of one ``pallas_call`` —
    the VMEM residency its grid steps pin (double buffering and compiler
    temporaries ride on top; the cap leaves that headroom)."""
    from .kernel_contracts import _block_steps, _kernel_name
    from .rules import _where

    gm = eqn.params.get("grid_mapping")
    name = _kernel_name(eqn)
    block_bytes = 0
    for bm in getattr(gm, "block_mappings", ()) or ():
        shape = _block_steps(bm)
        itemsize = bm.array_aval.dtype.itemsize
        block_bytes += int(np.prod(shape, dtype=np.int64)) * itemsize
    scratch_bytes = 0
    n_scratch = int(getattr(gm, "num_scratch_operands", 0) or 0)
    if n_scratch:
        kjx = _as_jaxpr(eqn.params.get("jaxpr"))
        if kjx is not None and len(kjx.invars) >= n_scratch:
            scratch_bytes = sum(_var_bytes(v)
                                for v in kjx.invars[-n_scratch:])
    return {"kernel": name, "where": _where(eqn),
            "grid": tuple(getattr(gm, "grid", ()) or ()),
            "block_bytes": block_bytes, "scratch_bytes": scratch_bytes,
            "vmem_bytes": block_bytes + scratch_bytes}


def vmem_estimates(closed) -> list[dict]:
    """One VMEM-fit estimate per ``pallas_call`` anywhere in the program
    (descending scan/pjit/remat/shard_map bodies, in program order — the
    shared :func:`rules.iter_pallas_eqns` walk)."""
    from .rules import iter_pallas_eqns

    return [_pallas_vmem(e) for e in iter_pallas_eqns(closed)]


# ---------------------------------------------------------------------------
# collective bytes (resharding rule's HLO attribution, summed)
# ---------------------------------------------------------------------------

def collective_bytes_from_hlo(hlo: str) -> int:
    """Total bytes per step crossing the mesh: every all-gather /
    all-to-all / all-reduce in the post-SPMD HLO, matched exactly like the
    resharding rule (incl. the combiner's tuple-result form), with NO size
    floor — a budget sums the design's deliberate boundaries (the TP
    engine's two psums per layer) so any NEW collective, however small,
    moves the figure."""
    from .rules import (_HLO_OP_RE, _HLO_TUPLE_OP_RE, _SHAPE_RE,
                        _shape_bytes)

    total = 0
    for line in hlo.splitlines():
        m = _HLO_OP_RE.search(line)
        if m is not None:
            total += _shape_bytes(m.group(1), m.group(2))
            continue
        mt = _HLO_TUPLE_OP_RE.search(line)
        if mt is not None:
            total += sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(mt.group(1)))
    return total


# ---------------------------------------------------------------------------
# the card
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramCard:
    """Static cost/memory card of one compiled program (one gate target)."""

    target: str
    peak_hbm_bytes: int
    eqns: int
    pallas_calls: int
    scatters: int
    collective_bytes: int | None      # None = multi-device, compile failed
    vmem_bytes_per_launch: int        # max across pallas_calls (0 = none)
    vmem_cap_bytes: int
    trace_families: int | None        # None = no example args to perturb
    vmem: list = dataclasses.field(default_factory=list)  # per-call detail
    #: per-pallas_call kernel-contract sections (kernel_contracts.py):
    #: bounds / race / alias verdicts, grid points checked, finding count
    kernel_contracts: list = dataclasses.field(default_factory=list)
    #: host-contract sections (host_contracts.py) when the host pass ran
    #: for this target: per-overlap-window race/blocking verdicts and
    #: per-state-machine coverage; None = host pass not applicable
    host_contracts: list | None = None

    def summary(self) -> dict:
        """Compact dict for bench rung detail / --json."""
        from .host_contracts import host_contracts_summary
        from .kernel_contracts import contracts_summary

        kc = contracts_summary(self.kernel_contracts)
        hc = (host_contracts_summary(self.host_contracts)
              if self.host_contracts is not None else None)
        return {"target": self.target,
                "peak_hbm_bytes": self.peak_hbm_bytes,
                "peak_hbm_mib": round(self.peak_hbm_bytes / 2**20, 3),
                "eqns": self.eqns,
                "pallas_calls": self.pallas_calls,
                "scatters": self.scatters,
                "collective_bytes": self.collective_bytes,
                "vmem_bytes_per_launch": self.vmem_bytes_per_launch,
                "vmem_cap_bytes": self.vmem_cap_bytes,
                "vmem_launch_sites": len(self.vmem),
                "trace_families": self.trace_families,
                "kernel_contracts": kc,
                "kernel_contract_violations": kc["violations"],
                "host_contracts": hc,
                "host_contract_violations":
                    hc["violations"] if hc is not None else 0}

    def render(self) -> str:
        s = self.summary()
        lines = [f"-- card {self.target}: "
                 f"peak_hbm {s['peak_hbm_mib']} MiB, "
                 f"{self.pallas_calls} pallas launch(es), "
                 f"{self.scatters} scatter(s), "
                 f"collective_bytes {self.collective_bytes}, "
                 f"vmem/launch {self.vmem_bytes_per_launch} "
                 f"(cap {self.vmem_cap_bytes}), "
                 f"trace_families {self.trace_families}, "
                 f"{self.eqns} eqns --"]
        for v in self.vmem:
            lines.append(f"   pallas {v['kernel'] or '<unnamed>'} "
                         f"grid={v['grid']} vmem={v['vmem_bytes']}B "
                         f"(blocks {v['block_bytes']} + scratch "
                         f"{v['scratch_bytes']}) [{v['where']}]")
        for c in self.kernel_contracts:
            lines.append(f"   contracts {c['kernel']} grid={c['grid']} "
                         f"bounds={c['bounds']} race={c['race']} "
                         f"alias={c['alias']} "
                         f"({c['points_checked']}/{c['grid_points']} grid "
                         f"point(s){', sampled' if c['sampled'] else ''})")
        for h in self.host_contracts or ():
            if h.get("kind") == "overlap":
                lines.append(
                    f"   host-overlap {h['method']} "
                    f"windows={len(h['windows'])} "
                    f"races={[r['field'] for r in h['races']]} "
                    f"blocking={len(h['blocking'])} [{h['where']}]")
            elif h.get("kind") == "machine":
                lines.append(
                    f"   host-machine {h['machine']} "
                    f"sites={h['sites']} "
                    f"edges {len(h['covered_edges'])}/"
                    f"{len(h['declared_edges'])} covered, "
                    f"dead={h['dead_edges']} "
                    f"undeclared={len(h['undeclared'])} "
                    f"protocol={len(h['protocol'])}")
        return "\n".join(lines)


def build_card(fn, args=(), *, target: str = "", closed=None, hlo=None,
               donated=None, trace_families=None, compile_collectives=True,
               vmem_cap: int | None = None,
               kernel_contracts=None,
               host_contracts=None) -> ProgramCard:
    """Derive a :class:`ProgramCard` from a traced program.

    ``closed`` reuses an existing trace (else ``fn(*args)`` is traced);
    ``hlo`` reuses a compiled-HLO text for the collective attribution
    (else, on multi-device programs, one compile is attempted when
    ``compile_collectives`` and ``fn`` allow).  ``trace_families`` reuses
    the recompile rule's signature count when the caller already ran it;
    ``kernel_contracts`` likewise reuses the verifier's per-kernel
    sections when ``analyze()`` already ran the kernel_contracts rule on
    this trace — else they are derived here (the cards-only gate and
    ``engine.decode_step_card()`` paths), still on the same trace.
    ``host_contracts`` attaches the host-contract pass's sections
    (host_contracts.py); unlike kernel contracts it is NOT derived here —
    the pass is module-scoped, not trace-scoped, so only callers that
    know the target serves from the async host runtime opt in
    (targets.HOST_TARGETS / ``analyze(host=True)``)."""
    import jax

    from .rules import _mesh_devices_of, compiled_hlo, signature_families

    if closed is None:
        closed = jax.make_jaxpr(fn)(*args)
    census = eqn_census(closed)
    vm = vmem_estimates(closed)
    if kernel_contracts is None:
        from .kernel_contracts import check_kernel_contracts

        _, kernel_contracts = check_kernel_contracts(closed, target=target)
    if trace_families is None and args:
        trace_families = signature_families(args)
    devices = _mesh_devices_of(closed, args)
    if devices <= 1:
        coll: int | None = 0
    elif hlo is not None:
        coll = collective_bytes_from_hlo(hlo)
    elif compile_collectives and fn is not None:
        text, _err = compiled_hlo(fn, args)
        coll = collective_bytes_from_hlo(text) if text is not None else None
    else:
        coll = None
    return ProgramCard(
        target=target or getattr(fn, "__name__", "anonymous"),
        peak_hbm_bytes=peak_live_hbm(closed, donated=donated),
        eqns=census["eqns"], pallas_calls=census["pallas_calls"],
        scatters=census["scatters"], collective_bytes=coll,
        vmem_bytes_per_launch=max((v["vmem_bytes"] for v in vm), default=0),
        vmem_cap_bytes=vmem_cap if vmem_cap is not None else vmem_cap_bytes(),
        trace_families=trace_families, vmem=vm,
        kernel_contracts=kernel_contracts,
        host_contracts=host_contracts)


def card_findings(card: ProgramCard) -> list[Finding]:
    """Gating findings derivable from the card alone: any single Pallas
    launch whose estimated VMEM residency exceeds the per-generation cap
    (a launch that can't fit won't compile on hardware — or will, with the
    compiler spilling blocks back to HBM and the kernel's win gone)."""
    findings = []
    for v in card.vmem:
        if v["vmem_bytes"] > card.vmem_cap_bytes:
            findings.append(Finding(
                rule="program_card", severity=Severity.WARNING,
                message=(f"pallas launch {v['kernel'] or '<unnamed>'} "
                         f"estimated VMEM {v['vmem_bytes']} B (blocks "
                         f"{v['block_bytes']} + scratch "
                         f"{v['scratch_bytes']}) exceeds the "
                         f"{card.vmem_cap_bytes} B cap "
                         f"(PADDLE_TPU_VMEM_CAP_MIB overrides) — shrink "
                         f"the block shapes or shard the grid"),
                where=v["where"], target=card.target))
    return findings


# ---------------------------------------------------------------------------
# budgets.toml (per-target ceilings, reasoned like the allowlist)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BudgetEntry:
    """One ``[[budget]]`` table: a target's ceilings + REQUIRED reason."""

    target: str
    ceilings: dict
    reason: str


def load_budgets(path: str | None = None) -> list[BudgetEntry]:
    """Load the budget file; a missing default file is an empty budget set
    (the gate then flags every card as un-budgeted), a missing EXPLICIT
    path is an error — same contract as the allowlist loader."""
    explicit = path is not None
    path = path or DEFAULT_BUDGETS
    if not os.path.exists(path):
        if explicit:
            raise FileNotFoundError(f"budgets file not found: {path}")
        return []
    with open(path) as f:
        entries = _parse_mini_toml(f.read(), header="budget")
    out: list[BudgetEntry] = []
    seen: set[str] = set()
    for i, e in enumerate(entries):
        target = e.pop("target", None)
        reason = e.pop("reason", "")
        if not isinstance(target, str) or not target:
            raise ValueError(f"budget entry {i}: missing target")
        if target in seen:
            raise ValueError(f"budget entry {i}: duplicate target "
                             f"{target!r} — one ceiling set per target")
        seen.add(target)
        if not reason or not isinstance(reason, str):
            raise ValueError(
                f"budget entry {i} ({target}): every budget needs a "
                f"one-line reason justifying its ceilings")
        unknown = set(e) - set(_CEILING_KEYS)
        if unknown:
            raise ValueError(f"budget entry {i} ({target}): unknown "
                             f"ceiling keys {sorted(unknown)}; known: "
                             f"{sorted(_CEILING_KEYS)}")
        bad = {k for k, v in e.items() if not isinstance(v, int)}
        if bad:
            raise ValueError(f"budget entry {i} ({target}): non-integer "
                             f"ceiling(s) {sorted(bad)}")
        out.append(BudgetEntry(target=target, ceilings=dict(e),
                               reason=reason))
    return out


def check_budgets(cards: dict, budgets: list[BudgetEntry],
                  registered=None) -> list[Finding]:
    """Gate cards against their ceilings.  Findings (all gating):

    * a card field EXCEEDING its ceiling (the regression the subsystem
      exists to catch — named field, measured vs budgeted value);
    * a card with NO budget entry (every registered target must carry a
      reasoned ceiling set — run ``--cards --update-budgets`` and justify);
    * a STALE budget entry naming no registered target (``registered``:
      the target registry; a renamed target must not leave its old
      ceilings lingering as if still enforced).

    A card field of None (collective bytes when the compile was
    unavailable) is skipped with an advisory info finding, never silently.
    """
    findings: list[Finding] = []
    by_target = {b.target: b for b in budgets}
    for name, card in cards.items():
        entry = by_target.get(name)
        if entry is None:
            findings.append(Finding(
                rule="budget", severity=Severity.WARNING,
                message=(f"no budgets.toml entry for target {name!r} — "
                         f"every gate target needs reasoned ceilings "
                         f"(python -m paddle_tpu.analysis --cards "
                         f"--update-budgets, then justify the entry)"),
                target=name))
            continue
        s = card.summary()
        for field, ceiling in sorted(entry.ceilings.items()):
            value = s.get(field)
            if value is None:
                findings.append(Finding(
                    rule="budget", severity=Severity.INFO,
                    message=(f"{field} unknown on this run (compile "
                             f"unavailable) — ceiling {ceiling} not "
                             f"checked"),
                    where=field, target=name))
                continue
            if value > ceiling:
                findings.append(Finding(
                    rule="budget", severity=Severity.ERROR,
                    message=(f"{field} = {value} exceeds the budgeted "
                             f"ceiling {ceiling} — a static cost "
                             f"regression; fix it, or re-run "
                             f"--update-budgets and re-justify the entry "
                             f"(reason on file: {entry.reason[:80]})"),
                    where=field, target=name))
    if registered is not None:
        names = set(registered)
        for b in budgets:
            if b.target not in names:
                findings.append(Finding(
                    rule="budget", severity=Severity.WARNING,
                    message=(f"stale budgets.toml entry: target "
                             f"{b.target!r} is not registered — a renamed/"
                             f"removed target must not keep phantom "
                             f"ceilings on file (registered: "
                             f"{sorted(names)})"),
                    target=b.target))
    return findings


def gate_cards(cards: dict, budgets: list[BudgetEntry], allowlist=None,
               registered=None) -> list[Finding]:
    """THE cards-gate policy, shared by ``tools/lint_gate.py --cards-only``
    and the ``--cards`` CLI so the two documented entry points can never
    desynchronize: card-level findings (VMEM over cap) pass through the
    allowlist exactly as ``analyze(card=True)`` folds them into a report
    on the full-gate path, then the budget ceilings are checked.  Returns
    the combined finding list (callers gate on severity != info)."""
    from .report import Report

    findings: list[Finding] = []
    for name, card in cards.items():
        findings += Report(name, card_findings(card),
                           allowlist=allowlist or []).findings
    findings += check_budgets(cards, budgets, registered=registered)
    return findings


_BUDGETS_HEADER = """\
# paddle_tpu.analysis budgets — per-target static-cost ceilings gated by
# tools/lint_gate.py (and `python -m paddle_tpu.analysis --cards`).  One
# [[budget]] table per registered target; every entry carries a REQUIRED
# one-line reason (enforced by the loader), same contract as
# allowlist.toml.  Ceilings are the card values at the last reviewed
# state: a PR that legitimately grows a figure re-runs
#   python -m paddle_tpu.analysis --cards --update-budgets
# (which preserves reasons) and re-justifies the entry in review; a PR
# that grows one silently fails the gate with the offending field named.
# Fields: peak_hbm_bytes, pallas_calls, scatters, collective_bytes,
# vmem_bytes_per_launch, trace_families, kernel_contract_violations,
# host_contract_violations (docs/analysis.md).
"""


def render_budgets(cards: dict, reasons: dict | None = None,
                   keep: list | None = None,
                   extra_fields: dict | None = None,
                   fallback: dict | None = None) -> str:
    """Serialize cards as a budgets.toml (ceilings = measured values).
    ``reasons`` maps target -> reason to preserve; new targets get a
    placeholder the reviewer must replace with a real justification.
    ``keep``: existing :class:`BudgetEntry` s to re-emit verbatim (targets
    NOT re-measured this run).  ``extra_fields`` maps target -> ceiling
    keys beyond :data:`BUDGET_FIELDS` (e.g. a hand-added ``eqns``) to
    re-emit at the measured value — a deliberate extra ceiling must not
    silently vanish on update.  ``fallback`` maps target -> the existing
    entry's ceilings, used when a card field is None this run (e.g.
    collective_bytes on a host whose multi-device compile failed): the
    previous ceiling is preserved rather than silently un-gated."""
    reasons = reasons or {}
    extra_fields = extra_fields or {}
    fallback = fallback or {}

    def quote(s: str) -> str:  # exact inverse of the parser's unescape
        return (s.replace("\n", " ").replace("\\", "\\\\")
                .replace('"', '\\"'))

    chunks = [_BUDGETS_HEADER]
    entries: dict[str, list[str]] = {}
    for b in keep or []:
        lines = ["[[budget]]", f'target = "{quote(b.target)}"']
        lines += [f"{k} = {int(v)}" for k, v in sorted(b.ceilings.items())]
        lines.append(f'reason = "{quote(b.reason)}"')
        entries[b.target] = lines
    for name in sorted(cards):
        s = cards[name].summary()
        lines = ["[[budget]]", f'target = "{quote(name)}"']
        fields = BUDGET_FIELDS + tuple(
            k for k in extra_fields.get(name, ())
            if k in _CEILING_KEYS and k not in BUDGET_FIELDS)
        for field in fields:
            value = s.get(field)
            if value is None:  # unknowable on this run — keep the
                value = (fallback.get(name) or {}).get(field)  # old ceiling
            if value is None:
                continue
            lines.append(f"{field} = {int(value)}")
        reason = reasons.get(name) or (
            "auto-added by --update-budgets at the measured card values; "
            "review and justify before merging")
        lines.append(f'reason = "{quote(reason)}"')
        entries[name] = lines
    chunks += ["\n".join(entries[n]) for n in sorted(entries)]
    return "\n\n".join(chunks) + "\n"


def update_budgets_file(cards: dict, path: str | None = None,
                        registered=None) -> str:
    """Rewrite budgets.toml: ``cards`` get their measured ceilings (reasons
    preserved from the existing file), existing entries for targets NOT
    re-measured this run are kept verbatim — a partial
    ``--update-budgets --target X`` run must never delete the other
    targets' reviewed ceilings.  Entries are dropped only when
    ``registered`` is given and the target is not in it (that is how a
    stale entry retires).  Returns the path written."""
    path = path or DEFAULT_BUDGETS
    existing: list[BudgetEntry] = []
    if os.path.exists(path):
        # a malformed existing file is a hard error, NOT a rewrite-from-
        # scratch: silently discarding it would replace every reviewed
        # reason with the auto placeholder (fail-loud contract, same as
        # the parser's own)
        existing = load_budgets(path)
    reasons = {b.target: b.reason for b in existing}
    keep = [b for b in existing if b.target not in cards
            and (registered is None or b.target in registered)]
    extra = {b.target: [k for k in b.ceilings if k not in BUDGET_FIELDS]
             for b in existing if b.target in cards}
    fallback = {b.target: b.ceilings for b in existing if b.target in cards}
    with open(path, "w") as f:
        f.write(render_budgets(cards, reasons, keep=keep,
                               extra_fields=extra, fallback=fallback))
    return path
