"""The five lint rules, each a pure function over a traced program.

All rules run on the ClosedJaxpr (plus, for the resharding rule, the
post-SPMD compiled HLO) — no TPU time is spent: tracing happens under
whatever backend is active, canonically ``JAX_PLATFORMS=cpu``.  GSPMD-style
compilation makes these properties statically visible before execution
(PAPERS.md: GSPMD; TPU-MLIR's per-stage verification argument).

Rules
-----
``dtype_upcast``   f32 dot/conv eqns whose operands derive from bf16/f16/int
                   inputs (the MXU runs bf16 ~8x faster than f32 — one silent
                   ``.astype(float32)`` before a matmul erases a kernel's win),
                   plus weak-typed float inputs (python-scalar provenance).
``donation``       undonated input buffers whose (shape, dtype) reappears in
                   the outputs — the train-step/decode-cache pattern where the
                   old buffer is bitwise-dead but still pins HBM because
                   ``donate_argnums`` missed it.
``recompile``      jit cache-key instability: re-derive the cache signature
                   under perturbed-but-equivalent inputs (python-scalar vs
                   array provenance, permuted dict insertion order) and flag
                   any signature change — each one is a silent recompile in
                   production.
``host_sync``      callback-class primitives (pure/io/debug callbacks,
                   infeed/outfeed) — host round-trips; severity escalates to
                   error inside scan/while bodies (the hot loop).
``resharding``     large collectives in the compiled HLO (multi-device meshes
                   only): all-gathers/all-to-alls the SPMD partitioner
                   inserted that the program never asked for — eqns whose
                   in/out shardings force an implicit gather — plus
                   all-reduces, so deliberate psum boundaries (the TP serving
                   engine's two per layer) stay pinned behind reasoned
                   allowlist entries and any new large reduce fails the gate.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax import tree_util as jtu

from .report import Finding, Severity

# dtypes whose values we consider "low precision by design": a program that
# holds params/caches in these and then runs an MXU op in f32 has leaked
LOW_PRECISION = {"bfloat16", "float16", "int8", "uint8", "int4", "uint4",
                 "float8_e4m3fn", "float8_e5m2"}
# MXU-bound primitives: an f32 instance of these is the expensive leak
_MXU_PRIMS = {"dot_general", "conv_general_dilated", "ragged_dot"}
# host-synchronizing primitives (callback family + infeed/outfeed)
_HOST_SYNC_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                    "debug_print",
                    "callback", "infeed", "outfeed"}
# control-flow primitives that define "inside a hot loop"
_LOOP_PRIMS = {"scan", "while", "fori"}


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    """Sub-jaxprs of an eqn (pjit/scan/while/cond/remat/custom_vjp/...)."""
    from jax._src import core as jcore

    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, jcore.Jaxpr):
                out.append(x)
    return out


def _where(eqn) -> str:
    """``file.py:line (fn)`` provenance of an eqn."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is not None:
        # jax reports the qualified name (outer.<locals>.fn); the allowlist
        # matches on the bare one
        fn = frame.function_name.rsplit(".", 1)[-1]
        return f"{frame.file_name.split('/')[-1]}:{frame.start_line} ({fn})"
    return source_info_util.summarize(eqn.source_info)


def _aval(var):
    return getattr(var, "aval", None)


def _dtype_name(var) -> str:
    a = _aval(var)
    return str(a.dtype) if a is not None and hasattr(a, "dtype") else ""


def _leaf_paths(args) -> list[str]:
    """Structural names for the flattened example args ('0/params/wq')."""
    flat, _ = jtu.tree_flatten_with_path(tuple(args))
    names = []
    for path, _leaf in flat:
        parts = []
        for p in path:
            key = getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))
            parts.append(str(key))
        names.append("/".join(parts))
    return names


def _unwrap_pjit(closed):
    """If the traced fn was itself jit-wrapped, the whole program is one jit
    eqn: descend into it and surface its donation/sharding metadata."""
    jaxpr = closed.jaxpr
    body_eqns = [e for e in jaxpr.eqns if e.primitive.name == "jit"]
    if len(jaxpr.eqns) == 1 and body_eqns:
        eqn = body_eqns[0]
        return eqn.params["jaxpr"], eqn.params.get("donated_invars")
    return closed, None


# ---------------------------------------------------------------------------
# rule 1: dtype-upcast leak
# ---------------------------------------------------------------------------

def check_dtype_upcast(closed, args=(), target: str = "") -> list[Finding]:
    """Taint-walk the jaxpr: inputs with low-precision dtypes taint every
    derived value; an MXU primitive whose f32/f64 operand is tainted means a
    low-precision value was upcast on the way to the matrix unit."""
    findings: list[Finding] = []
    seen: set[tuple] = set()   # (rule-site) dedup: fwd+bwd of one line -> one

    inner, _ = _unwrap_pjit(closed)
    jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner

    def taint_of(invars):
        return [_dtype_name(v) in LOW_PRECISION for v in invars]

    def walk(jx, taint_in: list[bool]):
        from jax._src.core import Literal

        taint: dict = {}
        for v, t in zip(jx.invars, taint_in):
            taint[v] = t
        for v in jx.constvars:
            taint[v] = _dtype_name(v) in LOW_PRECISION

        def is_tainted(v):
            if isinstance(v, Literal):
                return False
            return taint.get(v, False)

        for eqn in jx.eqns:
            in_taint = [is_tainted(v) for v in eqn.invars]
            prim = eqn.primitive.name
            if prim in _MXU_PRIMS:
                for v, t in zip(eqn.invars, in_taint):
                    dt = _dtype_name(v)
                    if t and dt in ("float32", "float64"):
                        site = (prim, _where(eqn), dt)
                        if site not in seen:
                            seen.add(site)
                            findings.append(Finding(
                                rule="dtype_upcast",
                                severity=Severity.WARNING,
                                message=(f"{prim} runs in {dt} on an operand "
                                         f"upcast from a low-precision input "
                                         f"(MXU fast path lost)"),
                                where=_where(eqn), target=target))
                        break
            subs = _sub_jaxprs(eqn)
            for sub in subs:
                if len(sub.invars) == len(eqn.invars):
                    walk(sub, in_taint)
                else:
                    # conservative: unknown operand mapping (cond branches,
                    # closed-over consts) — taint everything if anything is
                    walk(sub, [any(in_taint)] * len(sub.invars))
            out_t = any(in_taint)
            for v in eqn.outvars:
                taint[v] = out_t

    walk(jaxpr, taint_of(jaxpr.invars))

    # weak-typed float inputs: python-scalar provenance promotes silently and
    # churns the jit cache (see check_recompile); advisory here
    if args:
        names = _leaf_paths(args)
        leaves = jtu.tree_leaves(tuple(args))
        for name, leaf in zip(names, leaves):
            aval = jax.api_util.shaped_abstractify(leaf) \
                if not hasattr(leaf, "aval") else leaf.aval
            if getattr(aval, "weak_type", False) and \
                    np.issubdtype(aval.dtype, np.floating):
                findings.append(Finding(
                    rule="dtype_upcast", severity=Severity.INFO,
                    message=(f"input {name} is weak-typed (python-scalar "
                             f"provenance); promotion rules may upcast "
                             f"silently"),
                    where=name, target=target))
    return findings


# ---------------------------------------------------------------------------
# rule 2: donation miss
# ---------------------------------------------------------------------------

def check_donation(closed, args, target: str = "",
                   min_bytes: int = 1 << 20) -> list[Finding]:
    """Undonated inputs whose (shape, dtype) reappears in the outputs.

    The signature of the train-step/decode-step pattern: the caller rebinds
    ``params, opt_state = step(params, opt_state, ...)`` so the old buffers
    are bitwise-dead — but without ``donate_argnums`` XLA must keep both
    copies live across the step, doubling that tree's HBM.  Shape/dtype
    aliasing is a heuristic (hence warning + allowlist, not error); only
    buffers >= ``min_bytes`` are worth flagging."""
    inner, donated = _unwrap_pjit(closed)
    jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner
    leaves = jtu.tree_leaves(tuple(args))
    names = _leaf_paths(args)
    if donated is None:
        donated = (False,) * len(leaves)
    if len(donated) != len(leaves) or len(jaxpr.invars) != len(leaves):
        # invars don't map 1:1 onto the example-arg leaves (pruned/reordered
        # args, static closures): donation flags can't be attributed to
        # leaves reliably — misaligning would emit false "donation miss"
        # findings that push bogus allowlist entries.  The skip itself must
        # be VISIBLE (an info finding), or a refactor that breaks the
        # mapping silently turns donation coverage off while the gate
        # still reports the target clean.
        return [Finding(
            rule="donation", severity=Severity.INFO,
            message=(f"donation check skipped: traced invars "
                     f"({len(jaxpr.invars)}) do not map 1:1 onto example-"
                     f"arg leaves ({len(leaves)}) — cannot attribute "
                     f"donate_argnums"),
            target=target)]

    def sig(aval):
        return (tuple(aval.shape), str(aval.dtype))

    out_pool: dict[tuple, int] = {}
    for v in jaxpr.outvars:
        a = _aval(v)
        if a is not None and hasattr(a, "shape"):
            out_pool[sig(a)] = out_pool.get(sig(a), 0) + 1
    # donated inputs claim their matching outputs first — they are the
    # buffers XLA will actually alias
    undonated = []
    for i, v in enumerate(jaxpr.invars):
        a = _aval(v)
        if a is None or not hasattr(a, "shape"):
            continue
        if i < len(donated) and donated[i]:
            if out_pool.get(sig(a), 0) > 0:
                out_pool[sig(a)] -= 1
        else:
            undonated.append((i, v, a))

    findings = []
    # biggest first: with more lookalike inputs than outputs, report the
    # buffers whose donation would save the most HBM
    undonated.sort(key=lambda t: -int(np.prod(t[2].shape) or 0)
                   * t[2].dtype.itemsize)
    for i, v, a in undonated:
        nbytes = int(np.prod(a.shape) or 0) * a.dtype.itemsize
        if nbytes < min_bytes:
            continue
        if out_pool.get(sig(a), 0) > 0:
            out_pool[sig(a)] -= 1
            name = names[i] if i < len(names) else f"arg{i}"
            findings.append(Finding(
                rule="donation", severity=Severity.WARNING,
                message=(f"input {name} ({str(a.dtype)}{list(a.shape)}, "
                         f"{nbytes / 2**20:.1f} MiB) matches an output but "
                         f"is not donated — old buffer stays live across "
                         f"the step"),
                where=name, target=target))
    return findings


# ---------------------------------------------------------------------------
# rule 3: recompile churn
# ---------------------------------------------------------------------------

def _cache_signature(args):
    """Proxy for the jit cache key: treedef + per-leaf aval incl. weak_type.
    Two call sites producing different signatures for semantically identical
    inputs will compile (and cache) two programs."""
    leaves, treedef = jtu.tree_flatten(tuple(args))
    sig = [str(treedef)]
    for leaf in leaves:
        aval = leaf.aval if hasattr(leaf, "aval") \
            else jax.api_util.shaped_abstractify(leaf)
        sig.append(f"{aval.dtype}{list(getattr(aval, 'shape', ()))}"
                   f"w{int(getattr(aval, 'weak_type', False))}")
    return "|".join(sig)


def _strongify(args):
    """Replace python scalars with committed numpy scalars — the 'other'
    provenance an equivalent caller might use."""
    return jtu.tree_map(
        lambda x: np.asarray(x) if isinstance(x, (bool, int, float))
        and not isinstance(x, np.generic) else x, tuple(args))


def _permute_dicts(args):
    """Rebuild every mapping with reversed insertion order (key sets equal).
    Plain dicts are canonicalized by jax's pytree flatten (sorted keys), so
    for them this perturbation doubles as a regression check on that
    canonicalization; OrderedDict treedefs ENCODE insertion order, so two
    call sites building one in different orders genuinely churn the cache —
    the case this variant exists to flag."""
    import collections

    def rec(x):
        if isinstance(x, dict):  # covers OrderedDict too
            items = [(k, rec(x[k])) for k in reversed(list(x.keys()))]
            return (collections.OrderedDict(items)
                    if isinstance(x, collections.OrderedDict)
                    else dict(items))
        if isinstance(x, (list, tuple)):
            return type(x)(rec(v) for v in x)
        return x
    return rec(tuple(args))


def signature_families(args) -> int:
    """Distinct jit cache signatures across the equivalence perturbations
    (python-scalar vs array provenance, dict insertion order) — 1 means the
    program compiles exactly one trace family for these inputs.  This is
    the ``trace_families`` figure on a :class:`~.cost_model.ProgramCard`;
    :func:`check_recompile` reports the same count alongside its per-leaf
    findings."""
    base = _cache_signature(args)
    return len({base, _cache_signature(_strongify(args)),
                _cache_signature(_permute_dicts(args))})


def check_recompile(fn, args, target: str = "", trace=None,
                    baseline=None) -> tuple[list[Finding], int]:
    """Signature stability under equivalent-input perturbations, plus a
    re-trace determinism check (``baseline``: an already-traced jaxpr to
    reuse as the first determinism sample, saving one trace of the target).
    Returns (findings, n_distinct_signatures)."""
    findings: list[Finding] = []
    base = _cache_signature(args)
    variants = [("python-scalar vs array provenance", _strongify(args)),
                ("dict insertion order", _permute_dicts(args))]
    sigs = {base}
    for label, v_args in variants:
        s = _cache_signature(v_args)
        sigs.add(s)
        if s != base:
            # attribute by PATH, not position: a reordering perturbation
            # (OrderedDict) shuffles leaf order, and a positional zip would
            # name an arbitrary leaf — which then poisons allowlist `match`
            # substrings.  Same path set on both sides by construction.
            sig_a = dict(zip(_leaf_paths(args),
                             (_cache_signature((x,))
                              for x in jtu.tree_leaves(tuple(args)))))
            sig_b = dict(zip(_leaf_paths(v_args),
                             (_cache_signature((x,))
                              for x in jtu.tree_leaves(v_args))))
            culprit = next((p for p in sig_a
                            if sig_b.get(p) != sig_a[p]), "")
            findings.append(Finding(
                rule="recompile", severity=Severity.WARNING,
                message=(f"jit cache key unstable under {label}"
                         + (f" (leaf {culprit})" if culprit else "")
                         + " — equivalent callers recompile"),
                where=culprit, target=target))
    # determinism: tracing twice must produce the same program (a trace that
    # reads wall clock / RNG / mutable globals churns the cache from inside)
    if trace is not None:
        try:
            j1 = baseline if baseline is not None else trace()
            j2 = trace()
            n1 = sum(1 for _ in _iter_all_eqns(j1.jaxpr))
            n2 = sum(1 for _ in _iter_all_eqns(j2.jaxpr))
            if n1 != n2:
                findings.append(Finding(
                    rule="recompile", severity=Severity.ERROR,
                    message=(f"re-tracing produced a different program "
                             f"({n1} vs {n2} eqns) — trace-time "
                             f"nondeterminism"),
                    target=target))
        except Exception:
            pass
    return findings, len(sigs)


def _iter_all_eqns(jaxpr, path=()):
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in _sub_jaxprs(eqn):
            yield from _iter_all_eqns(sub, path + (eqn.primitive.name,))


def iter_pallas_eqns(closed):
    """Every ``pallas_call`` eqn anywhere in a (Closed)Jaxpr, in program
    order, descending scan/pjit/remat/cond/shard_map bodies but never a
    kernel body (in-kernel eqns are not launches).  THE shared walk —
    ``cost_model.vmem_estimates`` and the kernel-contract verifier both
    consume it, so a traversal fix can never make the VMEM census and the
    contract verdicts disagree about which launches exist."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed

    def walk(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                yield e
                continue
            for sub in _sub_jaxprs(e):
                yield from walk(sub)

    yield from walk(jaxpr)


# ---------------------------------------------------------------------------
# rule 4: host-sync points
# ---------------------------------------------------------------------------

def check_host_sync(closed, target: str = "") -> list[Finding]:
    inner, _ = _unwrap_pjit(closed)
    jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner
    findings = []
    for eqn, path in _iter_all_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _HOST_SYNC_PRIMS:
            in_loop = any(p in _LOOP_PRIMS for p in path)
            findings.append(Finding(
                rule="host_sync",
                severity=Severity.ERROR if in_loop else Severity.WARNING,
                message=(f"{name} forces a host round-trip"
                         + (" inside a scan/while hot loop" if in_loop
                            else "")),
                where=_where(eqn), target=target))
    return findings


# ---------------------------------------------------------------------------
# rule 5: resharding surprise (implicit all-gather)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_HLO_OP_RE = re.compile(
    r"%?[\w.-]+\s*=\s*([a-z0-9]+)\[([\d,]*)\][^=]*"
    r"\s(all-gather|all-to-all|all-reduce)(?:-start)?\(")
# combined/tuple-result form the all-gather combiner emits:
#   %ag = (f32[1024,64], bf16[512,64]) all-gather(%a, %b)
_HLO_TUPLE_OP_RE = re.compile(
    r"%?[\w.-]+\s*=\s*\(([^)]*)\)[^=]*"
    r"\s(all-gather|all-to-all|all-reduce)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_META_RE = re.compile(r'op_name="([^"]*)"')


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _mesh_devices_of(closed, args=()) -> int:
    """Device count the program will partition over: the pjit eqn's explicit
    shardings OR (the equally common pattern) the shardings committed on the
    example args — jit without in_shardings still partitions over whatever
    mesh the inputs live on.  1 when unsharded/unknown."""
    best = 1
    jaxpr = closed.jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            for sh in tuple(eqn.params.get("in_shardings") or ()) + \
                    tuple(eqn.params.get("out_shardings") or ()):
                mesh = getattr(sh, "mesh", None)
                if mesh is not None:
                    best = max(best, int(getattr(mesh, "size", 1) or 1))
    for leaf in jtu.tree_leaves(tuple(args)):
        sh = getattr(leaf, "sharding", None)
        if sh is None:
            continue
        mesh = getattr(sh, "mesh", None)
        if mesh is not None and getattr(mesh, "size", None):
            best = max(best, int(mesh.size))
        else:
            try:
                best = max(best, len(sh.device_set))
            except Exception:
                pass
    return best


def compiled_hlo(fn, args) -> tuple[str | None, Exception | None]:
    """Post-SPMD compiled HLO text of ``fn(*args)`` — (text, None) on
    success, (None, error) when the backend can't compile (e.g. device
    limits).  Shared by the resharding rule and the program card's
    collective-bytes attribution so one multi-device target pays exactly
    one compile per gate run."""
    import jax

    try:
        lowered = fn.lower(*args) if hasattr(fn, "lower") \
            else jax.jit(fn).lower(*args)
        return lowered.compile().as_text(), None
    except Exception as e:
        return None, e


def check_resharding(fn, args, closed=None, target: str = "",
                     min_bytes: int = 1 << 20, hlo: str | None = None,
                     hlo_error: Exception | None = None) -> list[Finding]:
    """Compile under the fn's own mesh and scan the post-SPMD HLO for
    all-gather/all-to-all/all-reduce ops over large tensors.
    Gathers/all-to-alls are the collectives GSPMD *inserted* — the program
    never wrote them; each one is an eqn whose in/out shardings don't
    compose, silently paying ICI bandwidth (the 'involuntary
    rematerialization' class the GQA KV replication note in
    models/llama.param_specs documents).  All-reduces are reported too so
    DELIBERATE reduction boundaries stay budgeted: a program that means to
    pay one (the TP serving engine's two per-layer psums,
    docs/tp_serving.md) carries a reasoned allowlist entry, and any other
    large reduce — a sharding change widening a psum operand, a new
    replicated reduction — fails the gate instead of shipping silently.
    Skipped on single-device meshes (nothing to reshard).  ``hlo`` /
    ``hlo_error`` carry a precomputed :func:`compiled_hlo` result (the
    card-building path in ``analyze`` shares one compile); when neither is
    given the rule compiles here."""
    if closed is not None and _mesh_devices_of(closed, args) <= 1:
        return []
    if hlo is None and hlo_error is None:
        hlo, hlo_error = compiled_hlo(fn, args)
    if hlo is None:  # compile unavailable (backend limits) — skip, visibly
        e = hlo_error
        return [Finding(rule="resharding", severity=Severity.INFO,
                        message=f"sharding check skipped: compile failed "
                                f"({type(e).__name__}: {str(e)[:120]})",
                        target=target)]
    findings = []
    for line in hlo.splitlines():
        m = _HLO_OP_RE.search(line)
        if m is not None:
            dtype, dims, op = m.group(1), m.group(2), m.group(3)
            nbytes = _shape_bytes(dtype, dims)
            shape = f"{dtype}[{dims}]"
        else:
            # combiner-fused tuple-result form: sum the tuple's shapes
            mt = _HLO_TUPLE_OP_RE.search(line)
            if mt is None:
                continue
            shapes = _SHAPE_RE.findall(mt.group(1))
            if not shapes:
                continue
            op = mt.group(2)
            nbytes = sum(_shape_bytes(d, s) for d, s in shapes)
            shape = "(" + ", ".join(f"{d}[{s}]" for d, s in shapes) + ")"
        if nbytes < min_bytes:
            continue
        meta = _META_RE.search(line)
        if op == "all-reduce":
            # reduces are often intended (psum boundaries) — the message
            # points at the allowlist instead of calling them implicit
            message = (f"{op} of {shape} ({nbytes / 2**20:.1f} MiB) "
                       f"crosses the mesh — a deliberate reduction boundary "
                       f"needs a reasoned allowlist entry, anything else is "
                       f"paying unbudgeted ICI bandwidth")
        else:
            message = (f"SPMD partitioner inserted {op} of {shape} "
                       f"({nbytes / 2**20:.1f} MiB) — in/out shardings "
                       f"force an implicit gather")
        findings.append(Finding(
            rule="resharding", severity=Severity.WARNING, message=message,
            where=(meta.group(1)[:160] if meta else ""), target=target))
    return findings
