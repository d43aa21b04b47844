"""Kernel contracts: static verification of every ``pallas_call``.

The decode megakernel roadmap (ROADMAP item 3, MPK stage 2) collapses ever
more of the decode step into single Pallas programs — exactly the regime
where a hand-fused kernel gets correctness wrong *silently*: an index map
that walks one page past the table reads another request's KV, two grid
points writing the same output block race, and an alias pair whose shapes
drift corrupts the pool in place.  None of that is visible to the lint
rules or the program card, which treat a ``pallas_call`` as an opaque
launch.  This module opens the launch: for each ``pallas_call`` eqn in an
already-traced program (the ONE ClosedJaxpr the lint/cards pass produces —
zero extra traces, zero compiles) it extracts the grid, BlockSpec index
maps, scratch shapes, and ``input_output_aliases``, and proves three
contract families by concrete enumeration of the grid:

``kernel_bounds``
    every evaluated index map x block shape stays inside its operand for
    every sampled grid point.  Index maps that read scalar-prefetch
    operands (block tables, write pages) are data-dependent: they are
    evaluated under adversarial valuations — all-zero, a distinct ramp,
    ``+BIG`` and ``-BIG`` fills — so a map is only clean when it clamps,
    i.e. when NO runtime table content can take it out of bounds.  This
    catches the off-by-one page walk and the ragged-tail overread.

``kernel_race`` / ``kernel_lost_write``
    each output's index map must be injective across grid points.
    Revisits are legal only when they are deterministic on TPU: along
    sequential (non-``parallel``) grid axes when the revisits are
    CONSECUTIVE in iteration order (the accumulate-then-finalize pattern
    — the block stays resident in VMEM, e.g. the split-K ``_flash_kernel``
    partials), or when the output block is readable (input-aliased, or
    the kernel body reads the output ref).  Two grid points separated
    along a ``parallel``-declared axis writing one block is a race
    (``kernel_race``); a non-consecutive sequential revisit of a
    write-only, unaliased block is a lost write (``kernel_lost_write``)
    — the earlier visit's bytes are flushed and clobbered.

``kernel_alias``
    every ``input_output_aliases`` pair must agree in aval (shape/dtype —
    pallas itself enforces this at trace time; re-checked for
    defense-in-depth) AND in block geometry (pallas does NOT check that:
    an aliased pair whose BlockSpecs drifted writes different elements
    than were read), and no input spec on the aliased buffer may map
    blocks overlapping the aliased output's written blocks at a
    *different* grid point — the exact failure mode a fused
    append+attention megakernel risks (the fused decode kernel's
    deliberate masked tail re-fetch of the write page is the live,
    allowlisted instance; see ``allowlist.toml``).

Enumeration is full up to a cap (default 2048 grid points; the validated
``PADDLE_TPU_KERNEL_VERIFY_SAMPLES`` env knob overrides, utils/envflags),
and deterministic corner-plus-stratified sampling above it: every corner
of the grid plus evenly spaced linear indices — no RNG, so CI findings
are reproducible.  Findings flow through the same severity/allowlist
machinery as every lint rule; per-kernel results land as the
``kernel_contracts`` section on each ProgramCard with the
``kernel_contract_violations`` count budgeted in ``budgets.toml``
(docs/analysis.md §"Kernel contracts").

Also here: :func:`registry_drift_findings`, the KNOWN_KERNELS drift lint
— ``envflags``'s kill-switch vocabulary cross-referenced against the
``kernel_disabled("...")`` call sites actually dispatched in the package
(AST-level, so docstrings/comments don't count), in both directions: a
renamed or retired kernel must not leave a dead kill switch behind, and
a new kernel's opt-out must be registered so typos get the did-you-mean.
And :func:`check_kernel_names`: every launch carries a ``name=`` (what a
device trace calls its events), one per kernel body.
"""

from __future__ import annotations

import itertools
import os

import numpy as np

from .report import Finding, Severity

__all__ = ["check_kernel_contracts", "check_kernel_names",
           "contracts_summary", "registry_drift_findings",
           "verify_samples_cap", "DEFAULT_SAMPLES_CAP"]

#: default grid-point enumeration cap (full enumeration at or below it);
#: override with PADDLE_TPU_KERNEL_VERIFY_SAMPLES (validated env_int)
DEFAULT_SAMPLES_CAP = 2048
#: adversarial fill for data-dependent (scalar-prefetch) index maps: far
#: past any real operand extent, small enough that idx * block_size stays
#: inside int64 (and any in-map int32 arithmetic does not wrap)
_BIG = 1 << 20
#: ceiling on enumerated grid corners when sampling (2^ndim corners on a
#: high-rank grid would otherwise eat the whole sample budget)
_CORNER_CAP = 256
#: the extent a grid axis whose bound is run-time data is enumerated at:
#: index maps see grid indices, never the bound, so what the first steps
#: of such an axis fetch and write is what any longer run of it does there
_DYNAMIC_EXTENT = 8


def verify_samples_cap() -> int:
    """The grid enumeration cap: full enumeration up to this many grid
    points, deterministic corner-plus-stratified sampling above it.
    ``PADDLE_TPU_KERNEL_VERIFY_SAMPLES`` overrides (validated integer,
    minimum 16 — a sub-minimum or non-integer value warns once and keeps
    the default, utils/envflags.env_int)."""
    from ..utils.envflags import env_int

    return env_int("PADDLE_TPU_KERNEL_VERIFY_SAMPLES", DEFAULT_SAMPLES_CAP,
                   minimum=16)


# ---------------------------------------------------------------------------
# geometry extraction
# ---------------------------------------------------------------------------

def _pallas_eqns(closed):
    """Every ``pallas_call`` eqn in the program — the ONE shared walk
    (``rules.iter_pallas_eqns``) the VMEM census also uses, so the two
    can never disagree about which launches exist."""
    from .rules import iter_pallas_eqns

    return list(iter_pallas_eqns(closed))


def _kernel_name(eqn) -> str:
    """The ``name=`` given to pallas_call, else the kernel function's."""
    return (eqn.params.get("name")
            or eqn.params["jaxpr"].debug_info.func_name or "<unnamed>")


def _dim_semantics(eqn, ngrid: int) -> tuple:
    """Per-grid-axis semantics ('parallel' or 'arbitrary').  Mosaic's
    default when ``dimension_semantics`` is not declared is 'arbitrary'
    (sequential) — the conservative direction for the race check: a
    revisit on an undeclared axis is judged by the consecutive-run rule,
    not condemned as a parallel race."""
    # pallas_call(compiler_params=pltpu.CompilerParams(...)) arrives keyed
    # by backend: {'mosaic_tpu': CompilerParams}
    mosaic = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    sem = getattr(mosaic, "dimension_semantics", None)
    if sem is None:
        return ("arbitrary",) * ngrid
    sem = tuple(getattr(s, "value", None) or str(s) for s in sem)
    return sem + ("arbitrary",) * (ngrid - len(sem))


def _sample_grid(grid, cap: int):
    """Deterministic grid-point sample: every point when the grid fits the
    cap, else every corner (all-{0, dim-1} combinations, capped) plus
    evenly spaced linear indices.  Returns (points [N, ndim] int64 in
    C-order linear-index order, sampled: bool, total: int)."""
    dims = [int(d) for d in grid]
    total = 1
    for d in dims:
        total *= d
    if not dims:
        return np.zeros((1, 0), np.int64), False, 1
    if total <= 0:
        return np.zeros((0, len(dims)), np.int64), False, 0
    if total <= cap:
        lin = np.arange(total, dtype=np.int64)
        sampled = False
    else:
        corners = []
        for combo in itertools.product(*[sorted({0, d - 1}) for d in dims]):
            corners.append(int(np.ravel_multi_index(combo, dims)))
            if len(corners) >= _CORNER_CAP:
                break
        strat = np.linspace(0, total - 1,
                            max(cap - len(corners), 2)).astype(np.int64)
        lin = np.unique(np.concatenate(
            [np.asarray(corners, np.int64), strat]))
        sampled = True
    pts = np.stack(np.unravel_index(lin, dims), axis=1).astype(np.int64)
    return pts, sampled, total


def _prefetch_valuations(eqn, n_prefetch: int):
    """Adversarial value sets for the scalar-prefetch operands (the block
    tables / lengths / write pages the index maps may read).  Ordered
    least-coincidental first: the 'ramp' (all-distinct, in-plausible-range)
    valuation models healthy runtime data; 'zero' models maximal
    coincidence (every slot sharing page 0 — how shared write/spill pages
    surface); 'max'/'min' are the out-of-range extremes only a clamped map
    survives.  Empty when the kernel prefetches nothing (one 'static'
    evaluation suffices)."""
    if not n_prefetch:
        return [("static", [])]
    avals = [v.aval for v in _operands(eqn)[:n_prefetch]]

    def fill(val):
        return [np.full(a.shape, val, dtype=np.dtype(a.dtype))
                for a in avals]

    ramps = []
    for a in avals:
        size = int(np.prod(a.shape, dtype=np.int64)) if a.shape else 1
        ramps.append(np.arange(size, dtype=np.dtype(a.dtype))
                     .reshape(a.shape))
    return [("ramp", ramps), ("zero", fill(0)), ("max", fill(_BIG)),
            ("min", fill(-_BIG))]


def _eval_index_map(bm, pts: np.ndarray, prefetch_vals):
    """Evaluate one BlockSpec index map at every sampled grid point —
    vectorized: the (discharged) index-map jaxpr is vmapped over the grid
    coordinates with the prefetch values broadcast, so the whole batch is
    a handful of eager CPU ops, not one interpreter pass per point.
    Returns int64 [N, n_block_dims] block indices."""
    import jax
    import jax.numpy as jnp
    from jax import core as jcore
    from jax._src.state.discharge import discharge_state

    cj = bm.index_map_jaxpr
    ds_jaxpr, ds_consts = discharge_state(cj.jaxpr, cj.consts)
    n_idx = len(bm.block_shape)
    ngrid = pts.shape[1]
    pf = [jnp.asarray(v) for v in prefetch_vals]

    def run(gi):
        args = [gi[a] for a in range(ngrid)] + pf
        out = jcore.eval_jaxpr(ds_jaxpr, ds_consts, *args)
        # discharge appends the final ref values after the original outs
        return jnp.stack([jnp.asarray(o).astype(jnp.int32)
                          for o in out[:n_idx]])

    if ngrid == 0:
        res = run(jnp.zeros((0,), jnp.int32))[None]
    else:
        res = jax.vmap(run)(jnp.asarray(pts, jnp.int32))
    return np.asarray(res, np.int64)


def _block_steps(bm):
    """Per-dim (step, extent-valid?) multipliers: a Blocked dim's index is
    in block units (element offset = idx * size); squeezed/mapped dims
    (entries without a ``block_size``) index single elements (step 1)."""
    return tuple(int(getattr(d, "block_size", 1)) for d in bm.block_shape)


def _operands(eqn) -> list:
    """A launch's operands in the order its grid mapping and
    ``input_output_aliases`` count them: the scalar-prefetch refs first.
    The values of run-time grid bounds lead the equation's inputs."""
    nd = eqn.params["grid_mapping"].num_dynamic_grid_bounds
    return list(eqn.invars[nd:])


def _operand_label(bms, k: int, n_inputs: int) -> str:
    bm = bms[k]
    origin = getattr(bm, "origin", "") or ""
    if k < n_inputs:
        return f"input {k}" + (f" ({origin})" if origin else "")
    return f"output {k - n_inputs}" + (f" ({origin})" if origin else "")


def _outputs_read(eqn, gm) -> list[bool]:
    """Which output refs the kernel body READS (``get``, ``addupdate``, or
    a ``swap`` whose old value is used) — the 'accumulated' half of the
    revisit escape.  Tracks the output ref vars through cond bodies
    (``pl.when``) and 1:1 sub-jaxprs; an untrackable operand mapping is
    treated as read (conservative: suppresses a finding rather than
    inventing one)."""
    from jax._src import core as jcore

    from .rules import _sub_jaxprs

    kjx = eqn.params.get("jaxpr")
    jx = kjx.jaxpr if hasattr(kjx, "jaxpr") else kjx
    n0 = gm.num_index_operands + gm.num_inputs
    n_out = gm.num_outputs
    read = [False] * n_out
    if jx is None or len(jx.invars) < n0 + n_out:
        return [True] * n_out

    def walk(j, env):
        for e in j.eqns:
            prim = e.primitive.name
            hit = [env[v] for v in e.invars
                   if not isinstance(v, jcore.Literal) and v in env]
            if hit:
                if prim in ("get", "addupdate"):
                    for oi in hit:
                        read[oi] = True
                elif prim == "swap" and any(
                        not isinstance(ov, jcore.DropVar)
                        for ov in e.outvars):
                    for oi in hit:
                        read[oi] = True
            subs = _sub_jaxprs(e)
            for sub in subs:
                if prim == "cond" and len(sub.invars) == len(e.invars) - 1:
                    pairs = zip(sub.invars, e.invars[1:])
                elif len(sub.invars) == len(e.invars):
                    pairs = zip(sub.invars, e.invars)
                else:
                    for oi in hit:   # unknown mapping: assume read
                        read[oi] = True
                    continue
                walk(sub, {sv: env[v] for sv, v in pairs
                           if not isinstance(v, jcore.Literal)
                           and v in env})

    walk(jx, {v: i for i, v in enumerate(jx.invars[n0:n0 + n_out])})
    return read


# ---------------------------------------------------------------------------
# the three contract families
# ---------------------------------------------------------------------------

def _check_bounds(kname, where, target, label, bm, vname, idx, pts,
                  data_dependent) -> Finding | None:
    """First out-of-bounds sampled grid point of one (mapping, valuation),
    or None.  Blocked dims: block index b is valid iff 0 <= b and
    b * block_size < dim (partial edge blocks are legal — pallas pads)."""
    steps = _block_steps(bm)
    shape = tuple(bm.array_aval.shape)
    # rank agreement is guaranteed by the caller: _verify_eqn pre-filters
    # rank-mismatched operands into the eval_failed/'unchecked' path
    # before this runs, and _eval_index_map emits exactly
    # len(block_shape) indices per point — a silent early-return here
    # would be the clean-verdict-without-checking outcome the unchecked
    # policy forbids
    starts = idx * np.asarray(steps, np.int64)[None, :]
    bad = (idx < 0) | (starts >= np.asarray(shape, np.int64)[None, :])
    rows = np.nonzero(bad.any(axis=1))[0]
    if not rows.size:
        return None
    r = int(rows[0])
    d = int(np.nonzero(bad[r])[0][0])
    pt = tuple(int(x) for x in pts[r])
    via = (f" under scalar-prefetch valuation '{vname}' (data-dependent "
           f"map: only a clamped map is safe for all runtime data)"
           if data_dependent else "")
    return Finding(
        rule="kernel_bounds", severity=Severity.ERROR,
        message=(f"pallas kernel {kname}: index map of {label} leaves the "
                 f"operand at grid point {pt}: block index "
                 f"{tuple(int(x) for x in idx[r])} x block "
                 f"{_block_steps(bm)} exceeds operand shape "
                 f"{shape} on axis {d}{via}"),
        where=where, target=target)


def _revisit_groups(idx: np.ndarray):
    """Group sampled points by written block: yields (block_tuple,
    member_rows) for every block written by more than one sampled point."""
    _, inv, counts = np.unique(idx, axis=0, return_inverse=True,
                               return_counts=True)
    for g in np.nonzero(counts > 1)[0]:
        rows = np.nonzero(inv == g)[0]
        yield tuple(int(x) for x in idx[rows[0]]), rows


def _check_races(kname, where, target, label, vname, idx, pts, lin,
                 sem, aliased, reads_out, data_dependent):
    """kernel_race / kernel_lost_write findings for one output mapping
    under one valuation (at most one of each)."""
    race = lost = None
    for blk, rows in _revisit_groups(idx):
        sub = pts[rows]
        varying = [a for a in range(pts.shape[1])
                   if sub[:, a].max() != sub[:, a].min()]
        par = [a for a in varying if sem[a] == "parallel"]
        coinc = (f" (runtime scalar-prefetch data coinciding — valuation "
                 f"'{vname}')" if data_dependent and vname != "ramp" else "")
        if par:
            # a parallel-axis collision is ALWAYS a race — later groups
            # must not fall through to the sequential lost-write logic
            # just because an earlier group already produced the (one
            # reported) race finding for this output
            if race is None:
                # cite a pair that actually exhibits the race: the group
                # members at the extremes of the parallel axis (sub[0] vs
                # sub[-1] could coincide on it when a third axis varies)
                lo = int(np.argmin(sub[:, par[0]]))
                hi = int(np.argmax(sub[:, par[0]]))
                p0, p1 = (tuple(int(x) for x in sub[lo]),
                          tuple(int(x) for x in sub[hi]))
                race = Finding(
                    rule="kernel_race", severity=Severity.ERROR,
                    message=(f"pallas kernel {kname}: {label} block {blk} "
                             f"is written by grid points {p0} and {p1}, "
                             f"which differ along parallel grid axis "
                             f"{par[0]} — concurrent grid points racing "
                             f"on one output block{coinc}"),
                    where=where, target=target)
            continue
        if race is not None and lost is not None:
            break
        # sequential revisit: legal when consecutive in iteration order
        # (block stays VMEM-resident: accumulate/finalize), or when the
        # block is readable (input-aliased / kernel reads the out ref)
        li = lin[rows]
        inside = (lin >= li.min()) & (lin <= li.max())
        consecutive = int(inside.sum()) == rows.size
        if consecutive or aliased or reads_out or lost is not None:
            continue
        p0, p1 = (tuple(int(x) for x in sub[0]),
                  tuple(int(x) for x in sub[-1]))
        lost = Finding(
            rule="kernel_lost_write", severity=Severity.WARNING,
            message=(f"pallas kernel {kname}: {label} block {blk} is "
                     f"revisited non-consecutively (grid points {p0} and "
                     f"{p1} with other blocks written in between) and the "
                     f"block is write-only (not input-aliased, never read "
                     f"in-kernel) — the earlier visit's bytes are flushed "
                     f"then clobbered{coinc}"),
            where=where, target=target)
    return race, lost


def _check_alias_pair(kname, where, target, eqn, gm, bms, gi, oj,
                      results, valuations, pts, data_dependent):
    """Contract checks for one ``input_output_aliases`` pair: aval match,
    block-geometry match, and read/write block overlap on the shared
    buffer at distinct grid points."""
    findings = []
    npf, n_in = gm.num_index_operands, gm.num_inputs
    in_k = gi - npf
    if not (0 <= in_k < n_in) or not (0 <= oj < gm.num_outputs):
        return [Finding(
            rule="kernel_alias", severity=Severity.ERROR,
            message=(f"pallas kernel {kname}: input_output_aliases pair "
                     f"({gi}, {oj}) does not name a (non-prefetch input, "
                     f"output) operand pair"),
            where=where, target=target)]
    bm_in, bm_out = bms[in_k], bms[n_in + oj]
    in_label = _operand_label(bms, in_k, n_in)
    out_label = _operand_label(bms, n_in + oj, n_in)
    operands = _operands(eqn)
    a_in = getattr(operands[gi], "aval", None)
    a_out = getattr(eqn.outvars[oj], "aval", None)
    if (a_in is not None and a_out is not None
            and (tuple(a_in.shape) != tuple(a_out.shape)
                 or str(a_in.dtype) != str(a_out.dtype))):
        findings.append(Finding(
            rule="kernel_alias", severity=Severity.ERROR,
            message=(f"pallas kernel {kname}: alias pair {in_label} -> "
                     f"{out_label} mismatches: {a_in.str_short()} aliased "
                     f"to {a_out.str_short()} — in-place write through a "
                     f"different shape/dtype corrupts the buffer"),
            where=where, target=target))
    if _block_steps(bm_in) != _block_steps(bm_out):
        findings.append(Finding(
            rule="kernel_alias", severity=Severity.ERROR,
            message=(f"pallas kernel {kname}: alias pair {in_label} -> "
                     f"{out_label} block geometry drifted: input blocks "
                     f"{_block_steps(bm_in)} vs output blocks "
                     f"{_block_steps(bm_out)} — the in-place write "
                     f"lands on different elements than the read fetched"),
            where=where, target=target))
        return findings
    # readers of the SAME buffer: the aliased input itself, plus any other
    # input operand bound to the same traced value (the pool passed twice)
    readers = [in_k] + [k for k in range(n_in) if k != in_k
                        and operands[npf + k] is operands[gi]]
    out_key = n_in + oj
    for rk in readers:
        hit = None
        for vname, _ in valuations:
            w_idx = results.get((out_key, vname))
            r_idx = results.get((rk, vname))
            if w_idx is None or r_idx is None:
                continue
            wmap: dict = {}
            for r, blk in enumerate(map(tuple, w_idx.tolist())):
                wmap.setdefault(blk, []).append(r)
            for r, blk in enumerate(map(tuple, r_idx.tolist())):
                ws = wmap.get(blk)
                if ws is None:
                    continue
                other = next((w for w in ws if w != r), None)
                if other is not None:
                    hit = (vname, blk, r, other)
                    break
            if hit:
                break
        if hit is None:
            continue
        vname, blk, r, w = hit
        coinc = f" (valuation '{vname}')" if data_dependent else ""
        findings.append(Finding(
            rule="kernel_alias", severity=Severity.WARNING,
            message=(f"pallas kernel {kname}: {_operand_label(bms, rk, n_in)} "
                     f"at grid point {tuple(int(x) for x in pts[r])} reads "
                     f"block {blk} of the buffer aliased to {out_label}, "
                     f"which grid point {tuple(int(x) for x in pts[w])} "
                     f"writes in place — a read at a different grid point "
                     f"than the write observes updated bytes{coinc}"),
            where=where, target=target))
    return findings


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def check_kernel_contracts(closed, target: str = "", samples: int | None
                           = None) -> tuple[list[Finding], list[dict]]:
    """Verify every ``pallas_call`` in an already-traced program.

    Returns ``(findings, sections)``: the findings feed the report /
    allowlist machinery like any lint rule's; ``sections`` is the
    per-kernel ``kernel_contracts`` detail the ProgramCard embeds (one
    dict per launch site: kernel, grid, points checked, sampled flag,
    per-family verdicts, finding count).  Reuses the caller's trace —
    this function never traces or compiles the target."""
    cap = samples if samples is not None else verify_samples_cap()
    findings: list[Finding] = []
    sections: list[dict] = []
    for eqn in _pallas_eqns(closed):
        f, s = _verify_eqn(eqn, target, cap)
        findings += f
        sections.append(s)
    return findings, sections


def _verify_eqn(eqn, target: str, cap: int):
    from .rules import _where

    gm = eqn.params["grid_mapping"]
    kname = _kernel_name(eqn)
    where = _where(eqn)
    grid = tuple(int(d) if isinstance(d, int) else -1
                 for d in (gm.grid or ()))
    section = {"kernel": kname, "where": where, "grid": grid,
               "grid_points": 0, "points_checked": 0, "sampled": False,
               "data_dependent": False, "bounds": "ok", "race": "ok",
               "alias": "ok", "findings": 0}
    findings: list[Finding] = []
    if any(d < 0 for d in grid):
        # an axis whose extent is run-time data: enumerated at a fixed
        # extent, and said so (the section keeps -1 for it)
        grid = tuple(_DYNAMIC_EXTENT if d < 0 else d for d in grid)
        findings.append(Finding(
            rule="kernel_bounds", severity=Severity.INFO,
            message=(f"pallas kernel {kname}: a grid bound is run-time "
                     f"data — that axis is enumerated at its first "
                     f"{_DYNAMIC_EXTENT} steps"),
            where=where, target=target))

    pts, sampled, total = _sample_grid(grid, cap)
    lin = (np.ravel_multi_index(pts.T, grid) if grid
           else np.zeros((pts.shape[0],), np.int64))
    section.update(grid_points=total, points_checked=int(pts.shape[0]),
                   sampled=sampled)
    sem = _dim_semantics(eqn, len(grid))
    npf, n_in, n_out = (gm.num_index_operands, gm.num_inputs,
                        gm.num_outputs)
    bms = list(gm.block_mappings)
    valuations = _prefetch_valuations(eqn, npf)
    aliases = [(int(i), int(o))
               for i, o in (eqn.params.get("input_output_aliases") or ())]
    aliased_outs = {o for _, o in aliases}
    reads_out = _outputs_read(eqn, gm)

    # evaluate every mapping under every valuation once; all checks share
    # the result table
    results: dict = {}
    data_dep = [False] * len(bms)
    eval_failed: set[int] = set()
    for k, bm in enumerate(bms):
        base = None
        for vname, vals in valuations:
            try:
                idx = _eval_index_map(bm, pts, vals)
            except Exception as e:   # unexpected index-map structure:
                findings.append(Finding(   # skip VISIBLY, never silently
                    rule="kernel_bounds", severity=Severity.INFO,
                    message=(f"pallas kernel {kname}: index map of "
                             f"{_operand_label(bms, k, n_in)} could not be "
                             f"evaluated ({type(e).__name__}: "
                             f"{str(e)[:80]}) — contracts unchecked for "
                             f"this operand"),
                    where=where, target=target))
                eval_failed.add(k)
                break
            results[(k, vname)] = idx
            if base is None:
                base = idx
            elif not np.array_equal(base, idx):
                data_dep[k] = True
    # geometry the bounds check cannot interpret (BlockSpec rank differing
    # from the operand rank — unblocked/ANY-space refs a future megakernel
    # style may introduce) is UNCHECKED, not silently 'ok': same policy as
    # an evaluation failure
    for k, bm in enumerate(bms):
        if k in eval_failed:
            continue
        steps = _block_steps(bm)
        shape = tuple(bm.array_aval.shape)
        if len(steps) != len(shape):
            findings.append(Finding(
                rule="kernel_bounds", severity=Severity.INFO,
                message=(f"pallas kernel {kname}: {_operand_label(bms, k, n_in)} "
                         f"block geometry rank {len(steps)} does not match "
                         f"operand rank {len(shape)} — bounds unchecked "
                         f"for this operand"),
                where=where, target=target))
            eval_failed.add(k)
    section["data_dependent"] = any(data_dep)

    # --- bounds: every mapping, every valuation --------------------------
    for k, bm in enumerate(bms):
        if k in eval_failed:
            continue
        label = _operand_label(bms, k, n_in)
        for vname, _ in valuations:
            idx = results.get((k, vname))
            if idx is None:
                continue
            f = _check_bounds(kname, where, target, label, bm, vname, idx,
                              pts, data_dep[k])
            if f is not None:
                findings.append(f)
                section["bounds"] = "violated"
                break   # one bounds finding per operand

    # --- write races: output mappings only -------------------------------
    for j in range(n_out):
        k = n_in + j
        label = _operand_label(bms, k, n_in)
        race = lost = None
        for vname, _ in valuations:
            idx = results.get((k, vname))
            if idx is None:
                continue
            r, lw = _check_races(kname, where, target, label,
                                 vname, idx, pts, lin, sem,
                                 aliased=j in aliased_outs,
                                 reads_out=reads_out[j],
                                 data_dependent=data_dep[k])
            race = race or r
            lost = lost or lw
            if race is not None and lost is not None:
                break
        for f in (race, lost):
            if f is not None:
                findings.append(f)
                section["race"] = "violated"

    # --- alias contracts --------------------------------------------------
    for gi, oj in aliases:
        fs = _check_alias_pair(kname, where, target, eqn, gm, bms, gi, oj,
                               results, valuations, pts,
                               data_dependent=any(data_dep))
        if fs:
            findings += fs
            section["alias"] = "violated"

    # an operand whose map could not be evaluated leaves its families
    # UNCHECKED, never "ok": the cards-only gate, decode_step_card(), and
    # bench detail drop info findings, so the verdict itself must carry
    # the downgrade or an unverified kernel would present as clean
    if eval_failed:
        section["unchecked_operands"] = len(eval_failed)
        affected = {"bounds"}
        if any(k >= n_in for k in eval_failed):
            affected.add("race")
        if aliases:
            affected.add("alias")
        for fam in affected:
            if section[fam] == "ok":
                section[fam] = "unchecked"
    section["findings"] = sum(1 for f in findings
                              if f.severity != Severity.INFO)
    return findings, section


def contracts_summary(sections: list) -> dict:
    """Aggregate of the per-kernel sections for card summaries / bench
    rung detail: launch-site count, grid points checked, whether any
    kernel was sampled (vs fully enumerated), and the violation count
    (``kernel_contract_violations`` is the budgeted figure)."""
    return {"kernels": len(sections),
            "points_checked": sum(s.get("points_checked", 0)
                                  for s in sections),
            "sampled": any(s.get("sampled") for s in sections),
            "unchecked_operands": sum(s.get("unchecked_operands", 0)
                                      for s in sections),
            "violations": sum(s.get("findings", 0) for s in sections)}


# ---------------------------------------------------------------------------
# kernel names (what a device trace calls a kernel's events)
# ---------------------------------------------------------------------------

def check_kernel_names(programs) -> list[Finding]:
    """Every ``pallas_call`` of the traced ``programs`` carries a non-empty
    ``name=``, and no two kernel bodies share one.  The TPU runtime names
    a kernel's device events by it (``%<name>.N = ... custom-call(...)``);
    without one they carry the JAX transformation the call was traced
    under (``closed_call.20``, ``checkpoint.18``), which any refactor
    moves, and two bodies under one name would be summed as one kernel."""
    from .rules import _where

    bodies: dict[str, dict[str, str]] = {}   # name -> {body: where}
    findings = []
    for closed in programs:
        for eqn in _pallas_eqns(closed):
            name = eqn.params.get("name")
            di = eqn.params["jaxpr"].debug_info
            body = (f"{os.path.basename(di.func_filename or '?')}:"
                    f"{di.func_lineno}")
            if not name:
                findings.append(Finding(
                    rule="kernel_name", severity=Severity.ERROR,
                    message=(f"pallas kernel {_kernel_name(eqn)} ({body}) "
                             f"is launched without name=: a device trace "
                             f"cannot tell its events from another "
                             f"kernel's"),
                    where=_where(eqn)))
                continue
            bodies.setdefault(name, {}).setdefault(body, _where(eqn))
    for name, seen in sorted(bodies.items()):
        if len(seen) > 1:
            findings.append(Finding(
                rule="kernel_name", severity=Severity.ERROR,
                message=(f"pallas kernel name {name!r} is shared by "
                         f"{len(seen)} kernel bodies "
                         f"({', '.join(sorted(seen))}): give each its own"),
                where=sorted(seen.values())[0]))
    return findings


# ---------------------------------------------------------------------------
# KNOWN_KERNELS drift (the dead-kill-switch lint)
# ---------------------------------------------------------------------------

def _dispatched_kernel_tokens(root: str | None = None) -> dict[str, str]:
    """Kernel names actually dispatched: every ``kernel_disabled("<name>")``
    call in the package source, AST-level (a mention in a docstring or
    comment is NOT a dispatch site).  Returns {token: 'file.py:line'}."""
    import ast

    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found: dict[str, str] = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path) as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            rel = os.path.relpath(path, root)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fname = (node.func.id if isinstance(node.func, ast.Name)
                         else node.func.attr
                         if isinstance(node.func, ast.Attribute) else "")
                if fname != "kernel_disabled" or not node.args:
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                                str):
                    found.setdefault(arg.value, f"{rel}:{node.lineno}")
    return found


def registry_drift_findings(root: str | None = None) -> list[Finding]:
    """Cross-reference ``envflags``' kill-switch vocabulary
    (``ops/pallas/__init__.KNOWN_KERNELS``) against the kernel names the
    package actually guards with ``kernel_disabled(...)`` — both ways:

    * a registered token with NO dispatch site is a DEAD kill switch — a
      renamed/retired kernel left its opt-out behind, and an operator
      setting it mid-incident disables nothing (silently, since the
      token still parses as known);
    * a dispatch site whose token is NOT registered loses the typo guard
      — ``PADDLE_TPU_DISABLE_PALLAS`` values near it would warn as
      unknown even when the operator spelled the real switch correctly.

    Warnings here; ``tools/lint_gate.py --strict-allowlist`` gates on
    them exactly like stale allowlist entries."""
    from ..ops.pallas import KNOWN_KERNELS

    dispatched = _dispatched_kernel_tokens(root)
    findings = []
    for token in sorted(set(KNOWN_KERNELS) - {"all"} - set(dispatched)):
        findings.append(Finding(
            rule="kernel_registry", severity=Severity.WARNING,
            message=(f"KNOWN_KERNELS registers {token!r} but no "
                     f"kernel_disabled({token!r}) dispatch site exists — "
                     f"a dead kill switch: delete the token (or wire the "
                     f"kernel's dispatch through kernel_disabled)"),
            where="ops/pallas/__init__.py"))
    for token in sorted(set(dispatched) - set(KNOWN_KERNELS)):
        findings.append(Finding(
            rule="kernel_registry", severity=Severity.WARNING,
            message=(f"kernel_disabled({token!r}) is dispatched but the "
                     f"token is not in KNOWN_KERNELS — register it so "
                     f"PADDLE_TPU_DISABLE_PALLAS typo detection covers "
                     f"it"),
            where=dispatched[token]))
    return findings
