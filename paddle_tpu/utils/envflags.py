"""Validated parsing for PADDLE_TPU_* operational env switches.

The switches are operator-facing kill/debug levers read at trace or init
time; a typo (``paged_attn`` for ``paged_attention``, ``off`` for ``0``)
used to be silently ignored — the worst failure mode for an escape hatch you
reach for mid-incident.  Every parse here warns (once per distinct value, so
trace-time re-reads don't spam) naming the offending token and the closest
valid spelling.

:data:`BOOL_FLAGS` is the registry of '0'/'1' switches and their defaults —
the single place a new kill switch gets documented (the engine reads them
through :func:`env_bool`, which enforces the '0'/'1' vocabulary):

* ``PADDLE_TPU_PREFIX_CACHE`` (default on) — automatic prefix cache
  (inference/prefix_cache.py); ``0`` forces it off even when the engine was
  constructed with ``enable_prefix_caching=True``.
* ``PADDLE_TPU_ENGINE_AUDIT`` (default off) — per-step serving-engine
  invariant auditor (analysis/engine_audit.py).
* ``PADDLE_TPU_SPECULATE`` (default on) — speculative decoding
  (inference/speculative.py, docs/speculative.md); ``0`` forces it off even
  when the engine was constructed with ``enable_speculation=True``, and the
  spec-off engine is byte-identical to one built before the feature existed.
* ``PADDLE_TPU_CHUNKED_PREFILL`` (default on) — chunked prefill + unified
  mixed prefill/decode step (docs/chunked_prefill.md); ``0`` forces it off
  even when the engine was constructed with ``enable_chunked_prefill=True``,
  reverting to the bucketed whole-prompt prefill path byte-for-byte.
* ``PADDLE_TPU_HOST_KV_TIER`` (default on) — hierarchical KV: the
  host-RAM spill tier behind the prefix cache (inference/kv_tier.py,
  docs/kv_tier.md).  ``0`` forces it off even when the engine was
  constructed with ``enable_host_kv_tier=True`` (or a FleetRouter shares
  one), restoring the pre-tier engine byte-identically: eviction frees
  pages again and admission stops at the HBM match.
  ``PADDLE_TPU_PREFIX_CACHE=0`` neutralizes the tier too — with no
  content address there is nothing to demote or match through.

(``PADDLE_TPU_DISABLE_PALLAS`` is the token-set switch; its vocabulary lives
with the kernels — ops/pallas/__init__.py ``KNOWN_KERNELS``, cross-checked
against the actual ``kernel_disabled()`` dispatch sites by the
KNOWN_KERNELS drift lint (analysis/kernel_contracts.py, run by
tools/lint_gate.py) so a retired kernel cannot leave a dead kill switch
registered.  Four of its
tokens are per-path decode kill switches rather than whole-kernel opt-outs
(docs/paged_attention.md): ``flash_decode`` pins the paged decode kernel to
the sequential page walk (split-K off), ``fused_decode_step`` rebuilds
the serving engine's unfused rope + KV-scatter + attention decode path,
``fused_layer_mlp`` restores the stage-1 per-layer program (separate
rms_norm launch + XLA-composed MLP; "Megastep stage 2" in the doc), and
``fused_quant_append`` unfuses the whole decode step for int8/packed-int4
KV pools — the requant-scatter append comes back (4 scatters/step) along
with the separate per-layer launches, exactly like ``fused_decode_step``
does for fp pools; dequant-on-read attention itself survives in the
unfused kernel (``paged_attention`` still opts the whole family out to the
gather oracle).
All four are registered in ``KNOWN_KERNELS`` so a typo gets the did-you-mean
warning instead of silently leaving the kernel it meant to disable running.
``PADDLE_TPU_FAULT_INJECT`` is the structured fault-injection plan; its
clause grammar is validated by :func:`env_fault_spec` and its fault-kind
vocabulary lives with the injector — inference/faults.py ``KNOWN_KINDS``
for the engine seams, plus ``REPLICA_KINDS`` and the ``replica`` clause key
for the fleet tier (inference/fleet.py): replica-scoped clauses are only
accepted by the FleetRouter's parse — the single-engine parse rejects them
with a warning naming the fleet requirement, because a clause nobody polls
would make a chaos run's evidence silently incomplete.
``PADDLE_TPU_TP`` is the integer tensor-parallel override for the serving
engine (docs/tp_serving.md): when set it REPLACES the
``ContinuousBatchingEngine(tensor_parallel=...)`` ctor value, the
operator's one-knob way to fan an existing deployment across a mesh.
Validated by :func:`env_tp`: a non-integer value, a degree that does not
divide the model's kv_heads, or a degree exceeding the device count warns
once — naming the valid divisors — and falls back to 1 (single chip), the
same never-silently-misconfigure contract as the switches above.
``PADDLE_TPU_VMEM_CAP_MIB`` is the integer override for the per-generation
VMEM ceiling the program-card gate checks every Pallas launch against
(analysis/cost_model.py, docs/analysis.md §"Program cards & budgets";
default: the 16 MiB v4 floor from ``VMEM_CAPS``).  Parsed by
:func:`env_int`: a non-integer or sub-minimum value warns once and keeps
the default — a typo'd cap must not silently stop gating VMEM fits.
``PADDLE_TPU_KERNEL_VERIFY_SAMPLES`` is the integer grid-enumeration cap
for the kernel-contract verifier (analysis/kernel_contracts.py,
docs/analysis.md §"Kernel contracts"; default 2048): a ``pallas_call``
grid at or under the cap is enumerated exhaustively, a larger one gets
deterministic corner-plus-stratified sampling down to the cap.  Parsed by
:func:`env_int` with minimum 16 — a typo or sub-minimum value warns once
and keeps the default, so a misconfigured cap can neither explode gate
time nor silently shrink coverage to nothing.
``PADDLE_TPU_HOST_VERIFY_DEPTH`` is the integer call-graph resolution
depth for the host-contract verifier (analysis/host_contracts.py,
docs/analysis.md §"Host contracts"; default 8): how many call edges the
effect analysis follows from each ``_host_overlap()`` window (and each
state-machine choke chain) when computing read/write closures.  Parsed
by :func:`env_int` with minimum 1 — a typo or sub-minimum value warns
once and keeps the default, so a misconfigured depth can neither hide
races behind an unresolved call nor explode the closure.
``PADDLE_TPU_HOST_TIER_MIB`` is the host-KV-tier byte budget in MiB
(inference/kv_tier.py, docs/kv_tier.md; default 256): the ceiling the
tier's own LRU evicts against.  Parsed by :func:`env_int` with minimum 1
— a typo or non-integer warns once and keeps the default, so a
misconfigured budget degrades to the documented one instead of silently
zeroing (or unbounding) the tier.)
"""

from __future__ import annotations

import difflib
import os
import warnings

__all__ = ["env_token_set", "env_bool", "env_fault_spec", "env_tp",
           "env_int", "BOOL_FLAGS", "RETIRED_FLAGS", "warn_retired_flags"]

#: '0'/'1' switches -> their library defaults (documentation + test anchor;
#: callers still pass the default explicitly at the read site so a flag read
#: can never silently drift from the registry without a test catching it)
BOOL_FLAGS = {
    "PADDLE_TPU_PREFIX_CACHE": True,
    "PADDLE_TPU_ENGINE_AUDIT": False,
    "PADDLE_TPU_SPECULATE": True,
    "PADDLE_TPU_CHUNKED_PREFILL": True,
    "PADDLE_TPU_HOST_KV_TIER": True,
}

#: off-switches of the serving engine that were removed with the arms they
#: selected: the engine always isolates faults, overlaps its host work,
#: keeps its metrics registry and records its flight ring
RETIRED_FLAGS = ("PADDLE_TPU_GRACEFUL", "PADDLE_TPU_ASYNC_HOST",
                 "PADDLE_TPU_METRICS", "PADDLE_TPU_FLIGHT_RECORDER")

_warned: set[tuple[str, str]] = set()


def _warn_once(name: str, raw: str, msg: str) -> None:
    if (name, raw) in _warned:
        return
    _warned.add((name, raw))
    warnings.warn(msg, stacklevel=3)


def warn_retired_flags() -> None:
    """Called where a serving engine or fleet is built: an operator who still
    exports a retired switch (to make faults raise out of ``step()``, say) is
    told once that it no longer changes anything."""
    for name in RETIRED_FLAGS:
        raw = os.environ.get(name, "")
        if raw:
            _warn_once(name, raw,
                       f"{name}={raw!r} has no effect: the switch is retired "
                       f"and the serving engine runs as it did with {name}=1")


def env_token_set(name: str, known: frozenset[str] | set[str]) -> set[str]:
    """Comma-separated token list (e.g. PADDLE_TPU_DISABLE_PALLAS).  Unknown
    tokens are kept (forward compatibility: an old binary must still honor a
    newer kernel name as an opt-out) but warned about with a did-you-mean."""
    raw = os.environ.get(name, "")
    if not raw:
        return set()
    tokens = {s.strip() for s in raw.split(",") if s.strip()}
    unknown = tokens - set(known)
    if unknown:
        hints = []
        for t in sorted(unknown):
            close = difflib.get_close_matches(t, known, n=1, cutoff=0.5)
            hints.append(f"{t!r}" + (f" (did you mean {close[0]!r}?)"
                                     if close else ""))
        _warn_once(name, raw,
                   f"{name}={raw!r} contains unrecognized value(s) "
                   f"{', '.join(hints)}; known: {sorted(known)}")
    return tokens


def env_bool(name: str, default: bool) -> bool:
    """Boolean switch: '' -> default, '0' -> False, '1' -> True.  Any other
    value warns and falls back to the default — a typo must not silently
    flip a kill switch either way."""
    raw = os.environ.get(name, "")
    if raw == "":
        return default
    if raw == "0":
        return False
    if raw == "1":
        return True
    _warn_once(name, raw,
               f"{name}={raw!r} is not '0' or '1'; using the default "
               f"({'1' if default else '0'})")
    return default


def env_int(name: str, default: int, minimum: int | None = None) -> int:
    """Integer knob: '' -> default; a non-integer value, or one below
    ``minimum``, warns once and falls back to the default — the same
    never-silently-misconfigure contract as :func:`env_bool` (used by the
    program-card gate's ``PADDLE_TPU_VMEM_CAP_MIB`` VMEM-cap override)."""
    raw = os.environ.get(name, "")
    if raw == "":
        return default
    try:
        value = int(raw)
    except ValueError:
        _warn_once(name, raw,
                   f"{name}={raw!r} is not an integer; using the default "
                   f"({default})")
        return default
    if minimum is not None and value < minimum:
        _warn_once(name, raw,
                   f"{name}={raw!r} is below the minimum ({minimum}); "
                   f"using the default ({default})")
        return default
    return value


def env_tp(kv_heads: int, device_count: int,
           name: str = "PADDLE_TPU_TP") -> int | None:
    """Tensor-parallel degree override for the serving engine.  Returns
    None when the variable is unset (the ctor's ``tensor_parallel`` value
    stands); otherwise the validated degree.  An invalid value — not an
    integer, < 1, not a divisor of ``kv_heads`` (the paged KV pool and the
    K/V projections shard along kv_heads, so a non-divisor would sub-head
    split), or more shards than devices — warns ONCE naming the valid
    degrees and falls back to 1: an operator typo must degrade to the
    single-chip engine, never crash the serve or silently sub-shard."""
    raw = os.environ.get(name, "")
    if raw == "":
        return None
    valid = sorted(d for d in range(1, max(kv_heads, 1) + 1)
                   if kv_heads % d == 0 and d <= device_count)

    def _fallback(msg: str) -> int:
        _warn_once(name, raw,
                   f"{name}={raw!r}: {msg}; falling back to tensor_parallel"
                   f"=1 (valid degrees for kv_heads={kv_heads} on "
                   f"{device_count} device(s): {valid})")
        return 1

    try:
        tp = int(raw)
    except ValueError:
        return _fallback("not an integer")
    if tp < 1:
        return _fallback(f"degree {tp} < 1")
    if kv_heads % tp != 0:
        return _fallback(f"degree {tp} does not divide kv_heads={kv_heads} "
                         f"(a sub-head split would break the shard-local "
                         f"paged-attention page walk)")
    if tp > device_count:
        return _fallback(f"degree {tp} exceeds the {device_count} visible "
                         f"device(s)")
    return tp


def env_fault_spec(name: str, known_kinds, known_keys,
                   fleet_only_kinds=frozenset(),
                   fleet_only_keys=frozenset()) -> list[dict]:
    """Parse a fault-injection plan: ``kind@key=val,key=val;kind@...``
    (e.g. ``alloc_fail@step=7;nan_logits@slot=2,step=11``).  Returns one dict
    per clause — ``{"kind": ..., <int-valued keys>}`` (``p`` parses as float).

    A fault plan is an operator-facing chaos lever: an unknown kind, unknown
    key, or malformed clause warns ONCE with a did-you-mean and returns []
    — injection disabled, the engine serves normally.  Partial acceptance
    would be worse than none: a typo'd clause silently skipped while its
    siblings fire would make a chaos run's evidence unreadable.

    ``fleet_only_kinds`` / ``fleet_only_keys`` name the replica-scoped
    vocabulary (inference/faults.REPLICA_KINDS, the ``replica`` key) for a
    parse where NO fleet is running: those clauses get the same
    warn-and-disable treatment, with the message naming the FleetRouter
    requirement instead of a did-you-mean — a replica-scoped clause the
    single-engine serve would never poll must not be a silent no-op (and
    must not crash the engine either)."""
    raw = os.environ.get(name, "")
    if not raw:
        return []

    def _reject(msg: str) -> list[dict]:
        _warn_once(name, raw, f"{name}={raw!r}: {msg}; fault injection "
                              f"DISABLED (the engine serves normally)")
        return []

    out: list[dict] = []
    for clause in raw.split(";"):
        clause = clause.strip()
        if not clause:
            continue
        kind, sep, tail = clause.partition("@")
        kind = kind.strip()
        if kind in fleet_only_kinds:
            return _reject(
                f"fault kind {kind!r} is replica-scoped and requires a "
                f"running FleetRouter (inference/fleet.py) to poll it — "
                f"no fleet is running, so the clause could never fire")
        if kind not in known_kinds:
            close = difflib.get_close_matches(
                kind, set(known_kinds) | set(fleet_only_kinds), n=1,
                cutoff=0.5)
            hint = f" (did you mean {close[0]!r}?)" if close else ""
            return _reject(f"unknown fault kind {kind!r}{hint}; known: "
                           f"{sorted(known_kinds)}")
        kv: dict = {"kind": kind}
        for item in tail.split(",") if sep else []:
            item = item.strip()
            if not item:
                continue
            k, eq, v = item.partition("=")
            k = k.strip()
            if eq and k in fleet_only_keys:
                return _reject(
                    f"clause key {k!r} in {clause!r} is replica-scoped and "
                    f"requires a running FleetRouter (inference/fleet.py) — "
                    f"no fleet is running, so the scope could never match")
            if not eq or k not in known_keys:
                close = difflib.get_close_matches(
                    k, set(known_keys) | set(fleet_only_keys), n=1,
                    cutoff=0.5)
                hint = f" (did you mean {close[0]!r}?)" if close else ""
                return _reject(f"bad clause key {k!r}{hint} in {clause!r}; "
                               f"known: {sorted(known_keys)}")
            try:
                kv[k] = float(v) if k == "p" else int(v)
            except ValueError:
                return _reject(f"non-numeric value {v.strip()!r} for key "
                               f"{k!r} in {clause!r}")
        out.append(kv)
    return out
