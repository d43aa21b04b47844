"""Serving-engine invariant auditor (``PADDLE_TPU_ENGINE_AUDIT=1``).

The paged continuous-batching engine + prefix cache maintain a handful of
host-side invariants the whole memory model rests on.  A single bookkeeping
bug (double-freed page, leaked refcount, COW miss) silently corrupts KV bytes
for *other* requests — the worst failure class in a multi-tenant server,
detectable only by cross-checking the allocator, the block tables, and the
cache against each other.  With the env var set, the engine calls
:func:`audit_engine` after admission and after every decode chunk; a
violation raises :class:`EngineAuditError` naming the invariant.

Invariants (paged mode):

I1  page partition — every pool page is in exactly one of {free list, a
    slot's private blocks, the prefix cache}; no duplicates, total == pool.
    Under tensor parallelism (docs/tp_serving.md) the device pools must
    shard ONLY the kv_heads axis — the page axis stays whole per shard, so
    this host-side partition is exact on every shard (one allocator,
    tp-many replicas of its accounting).  Under the fused decode step
    (docs/paged_attention.md) the device pool carries exactly ONE spill
    page past the allocator's range — dropped writes' trash can, never
    handed out, never accounted — and none otherwise.
I2  block-table rows — row[i] mirrors [shared pages..., private pages...] in
    order; every remaining entry is the unallocated sentinel.
I3  refcounts — each cached block's refcount equals the number of slot
    mappings over it; the cache's O(1) zero-ref counter matches a scan.
I4  COW — no cache-resident page is simultaneously a slot's *private*
    (writable) block: the engine never writes a shared page.
I5  chain shape — a slot's shared list is a parent-linked hash chain rooted
    at None; each cached block's ``children`` count matches a scan.
I6  position bounds — active slots have 0 <= pos <= max_seq, the KV-write
    high-water mark ``_written`` satisfies pos <= written <= max_seq (a
    speculative verify step appends up to K+1 tokens, then rolls pos back
    past rejected drafts — pos may trail written, never lead it), and the
    mapped blocks cover every written position including rejected drafts'
    (multi-token append must have allocated pages before the device wrote).
I7  chunked-prefill progress (engines with ``enable_chunked_prefill``) — a
    prefilling slot holds a seated request, its ``prefilled`` cursor stays
    within [0, prompt_len], the slot's mapped pages cover every prefilled
    position (a chunk must never have scattered K/V into unallocated
    pages), and no slot was packed as BOTH a decode lane and a prefill lane
    in the same mixed step (the unified launch's two roles are disjoint by
    construction — an overlap means the scheduler double-advanced a slot).
I8  terminal ownership (docs/fault_tolerance.md) — a request in a terminal
    status (FINISHED/FAILED/REJECTED/CANCELLED/EXPIRED) owns zero pages and
    zero cache refs: it is neither seated on a slot nor waiting in the
    queue (pages and refs are slot-keyed, so "not seated" + I1's exact pool
    partition IS the zero-ownership proof); conversely every seated request
    is RUNNING and every queued request is PENDING.  The fault paths
    (_fail_slot, expiry, cancel) release before they mark terminal — a
    violation means a failed request's pages leaked or a zombie is still
    being scheduled.

I11 mixed-step row bound (engines with ``enable_chunked_prefill``;
    checked by ``_mixed_step`` itself before EVERY launch, audit on or off)
    — the rows the host marked live number at most ``_mixed_rows``, the
    packed rows the compiled mixed program computes
    (docs/chunked_prefill.md "Packed rows").  The program keeps the first
    ``_mixed_rows`` live rows and would drop the rest without a word, so a
    packing that outgrows the bound raises instead of launching: a dropped
    row is a token some request never gets.

I10 hierarchical-KV tier (docs/kv_tier.md; engines with a host tier
    attached) — every cached block is in exactly one of {HBM pool, host
    tier, dead}: demotion MOVES a block D2H (the victim leaves the prefix
    cache as its page ships) and re-admission moves it back, so a
    **private** tier never holds a hash that is simultaneously resident
    in the engine's prefix cache (a **shared** fleet tier deliberately
    relaxes this to per-replica accounting: replica A's demoted copy may
    coexist with replica B's HBM-resident one — byte-identical by the
    content-address contract — so the exclusivity clause is skipped and
    the remaining clauses carry the invariant).  Tier accounting must
    close exactly: every entry is keyed by its own hash, byte usage sums
    to ``used_bytes`` within the budget, pins are non-negative, and every
    hash in a slot's pending match-to-restore plan is still tier-resident
    (pins protect the match-to-restore window; only a ``tier_drop``
    injection may break it, and that seam drops the plan atomically).
    "Dead" is the explicit third state: a block in neither structure —
    the tier refused it (budget) or LRU-dropped it — which is exactly the
    pre-tier eviction, never an accounting hole.

I9  fleet ownership (docs/fleet_serving.md; :func:`audit_fleet`, run by the
    FleetRouter after every fleet step) — every LIVE fleet rid is owned by
    exactly one replica: the owner is alive (not DEAD) and holds a
    replica-local copy; a hedge-pending rid counts as the primary's until
    first-writer-wins resolves, and its only extra copy lives on the
    recorded hedge target; no replica engine serves a rid the router does
    not route to it (a copy on a third replica is double ownership — the
    fleet would bank one stream twice); terminal fleet requests appear in
    no routing registry.

Dense (non-paged) engines only get I6's bounds check and I8 — there is no
allocator to corrupt.  The audit is O(pool + slots·blocks) pure-host work per step:
cheap next to a device step, but nonzero, hence opt-in (a debug validator,
not a production default).
"""

from __future__ import annotations

from ..utils.envflags import env_bool

__all__ = ["EngineAuditError", "audit_engine", "audit_fleet",
           "audit_tier", "audit_enabled"]


class EngineAuditError(AssertionError):
    """A serving-engine invariant does not hold (engine state is corrupt)."""


def audit_enabled() -> bool:
    """Parse ``PADDLE_TPU_ENGINE_AUDIT`` (validated: '', '0', '1'; anything
    else warns and falls back to off — see utils/envflags.py)."""
    return env_bool("PADDLE_TPU_ENGINE_AUDIT", False)


def _fail(invariant: str, detail: str):
    raise EngineAuditError(f"engine audit {invariant} violated: {detail}")


def audit_engine(eng) -> None:
    """Cross-check a ContinuousBatchingEngine's host state; raises
    :class:`EngineAuditError` on the first violated invariant."""
    B = eng.max_batch
    # I6 first — it applies to dense and paged alike
    for s in range(B):
        if eng._slot_req[s] is None:
            continue
        pos = int(eng._pos[s])
        if not 0 <= pos <= eng.max_seq:
            _fail("I6", f"slot {s} pos {pos} outside [0, {eng.max_seq}]")
        w = int(eng._written[s])
        if w > eng.max_seq:
            _fail("I6", f"slot {s} written high-water {w} beyond "
                        f"max_seq {eng.max_seq}")
        if pos > w:
            _fail("I6", f"slot {s} pos {pos} ahead of written high-water "
                        f"{w}: speculative rollback may trail the device's "
                        f"writes but pos must never pass them")

    # I8: terminal ownership — dense and paged alike (the journal and the
    # queue are host structures both engine shapes share)
    from ..inference.serving import TERMINAL_STATUSES

    seated = {id(r) for r in eng._slot_req if r is not None}
    queued = {id(r) for r in eng._queue}
    for req in getattr(eng, "_reqs", {}).values():
        if req.status in TERMINAL_STATUSES:
            if id(req) in seated:
                _fail("I8", f"rid {req.rid} is {req.status} (terminal) but "
                            f"still seated on a slot: its pages were never "
                            f"released")
            if id(req) in queued:
                _fail("I8", f"rid {req.rid} is {req.status} (terminal) but "
                            f"still waiting in the queue (zombie: it would "
                            f"be re-admitted)")
    for s in range(B):
        req = eng._slot_req[s]
        if req is not None and req.status != "RUNNING":
            _fail("I8", f"slot {s} seats rid {req.rid} with status "
                        f"{req.status} (seated requests must be RUNNING)")
    for req in eng._queue:
        if req.status != "PENDING":
            _fail("I8", f"queued rid {req.rid} has status {req.status} "
                        f"(queued requests must be PENDING)")
    if not getattr(eng, "paged", False):
        return

    nb = eng.num_blocks
    free = list(eng._free)
    cache = eng._pcache
    cached_pages = cache.resident_pages() if cache is not None else []
    private = [p for s in range(B) for p in eng._slot_blocks[s]]

    # I1: exact partition of the pool
    if len(free) != len(set(free)):
        _fail("I1", f"duplicate pages in the free list: {sorted(free)}")
    if len(private) != len(set(private)):
        _fail("I1", f"page owned by two slots: {sorted(private)}")
    if len(cached_pages) != len(set(cached_pages)):
        _fail("I1", f"page cached twice: {sorted(cached_pages)}")
    everything = sorted(free + private + cached_pages)
    if everything != sorted(set(everything)):
        seen, dup = set(), set()
        for p in free + private + cached_pages:
            (dup if p in seen else seen).add(p)
        _fail("I1", f"pages in two owners at once: {sorted(dup)} "
                    f"(free/slot/cache overlap)")
    if everything != list(range(nb)):
        missing = sorted(set(range(nb)) - set(everything))
        extra = sorted(set(everything) - set(range(nb)))
        _fail("I1", f"pool accounting does not close: missing={missing} "
                    f"out-of-range={extra}")
    # I1 under the fused decode step (docs/paged_attention.md "Fused decode
    # step"): the device pool carries exactly one SPILL page past the
    # allocator's range iff fused mode is on.  The spill page is dropped
    # writes' trash can — it must exist when the fused kernel targets it
    # (a missing page means dropped writes corrupt page num_blocks - 1) and
    # must NOT exist otherwise (a stray page means the pool layout drifted
    # from the compiled programs').  The partition above already proves the
    # allocator never hands it out (everything == range(num_blocks)).
    # quantized pools (kv_quant engines) are {"q": codes, "scale": ...}
    # pytrees: geometry and sharding checks read the code leaf (the scale
    # leaf shares the page axis and shards the same kv_heads axis 2)
    def _pool_leaves(pool):
        if isinstance(pool, dict):
            return [("q", pool["q"]), ("scale", pool["scale"])]
        return [("", pool)]

    pool_k = eng._program.pages(eng.cache_k)
    phys = int(pool_k.shape[1])
    want = nb + (1 if getattr(eng, "_fused", False) else 0)
    if phys != want:
        _fail("I1", f"device pool has {phys} physical pages, expected "
                    f"{want} (num_blocks={nb}, fused decode "
                    f"{'on' if getattr(eng, '_fused', False) else 'off'})")
    if getattr(eng, "tp", 1) > 1:
        # I1 under tensor parallelism (docs/tp_serving.md): the host
        # partition above is only exact PER SHARD if the device pool
        # shards kv_heads alone — a spec that touched the page axis would
        # give shards different page capacities and the single host
        # allocator would silently misaccount every one of them.
        for nm, pool in (("cache_k", eng.cache_k), ("cache_v", eng.cache_v)):
            for leaf_nm, leaf in _pool_leaves(pool):
                spec = tuple(getattr(leaf.sharding, "spec", ()) or ())
                axes = spec + (None,) * (leaf.ndim - len(spec))
                kv_ax = axes[2]
                if kv_ax not in ("tp", ("tp",)):
                    _fail("I1", f"TP pool {nm}{'.' + leaf_nm if leaf_nm else ''} "
                                f"does not shard kv_heads: spec={spec}")
                if any(a is not None for i, a in enumerate(axes) if i != 2):
                    _fail("I1", f"TP pool {nm}{'.' + leaf_nm if leaf_nm else ''} "
                                f"shards a non-kv_heads axis (per-shard "
                                f"page accounting breaks): spec={spec}")

    # I4: cached pages are read-only — never simultaneously private
    leaked = set(cached_pages) & set(private)
    if leaked:
        _fail("I4", f"cache-resident pages mapped writable: {sorted(leaked)}")

    by_hash = cache._by_hash if cache is not None else {}

    # I2: table rows mirror shared+private, sentinel elsewhere
    for s in range(B):
        shared = eng._slot_shared[s]
        owned = eng._slot_blocks[s]
        row = eng._table[s]
        expect = [by_hash[h].page if h in by_hash else None for h in shared] \
            + list(owned)
        if len(expect) > eng.max_blocks:
            # must precede the row[i] loop: an over-appended allocator list
            # would otherwise surface as a bare IndexError, not the named
            # invariant
            _fail("I2", f"slot {s} maps {len(expect)} blocks but the table "
                        f"row holds max_blocks={eng.max_blocks}")
        for i, want in enumerate(expect):
            if want is None:
                _fail("I2", f"slot {s} maps evicted cached block "
                            f"{shared[i][:8]}")
            if int(row[i]) != want:
                _fail("I2", f"slot {s} table[{i}]={int(row[i])} but "
                            f"allocator says page {want}")
        for i in range(len(expect), eng.max_blocks):
            if int(row[i]) != nb:
                _fail("I2", f"slot {s} table[{i}]={int(row[i])} past the "
                            f"mapped blocks (sentinel {nb} expected)")
        # I6 continued: mapped blocks must cover every written position —
        # including a speculative verify step's rejected drafts (the device
        # wrote their K/V before the rollback), hence the _written
        # high-water mark rather than pos
        if eng._slot_req[s] is not None and expect:
            covered = len(expect) * eng.block_size
            pos = min(int(eng._pos[s]), eng.max_seq)
            hw = min(int(eng._written[s]), eng.max_seq)
            if pos > covered:
                _fail("I6", f"slot {s} pos {pos} beyond mapped pages "
                            f"({covered} positions)")
            if hw > covered:
                _fail("I6", f"slot {s} written high-water {hw} beyond "
                            f"mapped pages ({covered} positions): "
                            f"multi-token append outran its allocation")

    # I7: chunked-prefill progress (only when the feature is live)
    if getattr(eng, "_chunked", False):
        for s in range(B):
            ids = eng._prefill_ids[s]
            if ids is None:
                continue
            if eng._slot_req[s] is None:
                _fail("I7", f"slot {s} is mid-prefill with no request "
                            f"seated")
            cur = int(eng._prefilled[s])
            if not 0 <= cur <= ids.size:
                _fail("I7", f"slot {s} prefill cursor {cur} outside "
                            f"[0, {ids.size}] (prompt length)")
            covered = (len(eng._slot_shared[s])
                       + len(eng._slot_blocks[s])) * eng.block_size
            if cur > covered:
                _fail("I7", f"slot {s} prefilled {cur} positions but its "
                            f"mapped pages cover only {covered}: a chunk "
                            f"scattered K/V into unallocated pages")
        dec, pre = getattr(eng, "_last_pack", ((), ()))
        overlap = set(dec) & set(pre)
        if overlap:
            _fail("I7", f"slot(s) {sorted(overlap)} packed as BOTH decode "
                        f"and prefill in one mixed step")

    if cache is None:
        return

    # I3: refcount == slot mappings; O(1) zero-ref counter == scan
    mapped: dict[str, int] = {}
    for s in range(B):
        for h in eng._slot_shared[s]:
            mapped[h] = mapped.get(h, 0) + 1
    for h, e in by_hash.items():
        if e.refcount != mapped.get(h, 0):
            _fail("I3", f"block {h[:8]} refcount={e.refcount} but "
                        f"{mapped.get(h, 0)} slot(s) map it")
    for h in mapped:
        if h not in by_hash:
            _fail("I3", f"slot maps block {h[:8]} that is not resident")
    n_zero = sum(1 for e in by_hash.values() if e.refcount == 0)
    if cache._n_zero_ref != n_zero:
        _fail("I3", f"zero-ref counter {cache._n_zero_ref} != scan {n_zero}")

    # I5: chain shape — parent links + children counts
    kids: dict[str, int] = {}
    for e in by_hash.values():
        if e.parent is not None:
            kids[e.parent] = kids.get(e.parent, 0) + 1
    for h, e in by_hash.items():
        if e.children != kids.get(h, 0):
            _fail("I5", f"block {h[:8]} children={e.children} but scan "
                        f"finds {kids.get(h, 0)}")
    for s in range(B):
        parent = None
        for h in eng._slot_shared[s]:
            e = by_hash.get(h)
            if e is None:
                _fail("I5", f"slot {s} chain references evicted {h[:8]}")
            if e.parent != parent:
                _fail("I5", f"slot {s} shared chain broken at {h[:8]}: "
                            f"parent {str(e.parent)[:8]} != previous "
                            f"{str(parent)[:8]}")
            parent = h

    # I10: hierarchical-KV tier (docs/kv_tier.md) — block in exactly one
    # of {HBM pool, host tier, dead}
    tier = getattr(eng, "_tier", None)
    if tier is not None:
        audit_tier(tier)
        if not tier.shared:
            # private tier: strict move semantics — demotion removes the
            # hash from the prefix cache as its page ships D2H, and
            # re-admission removes the tier entry as the page comes back.
            # (A fleet-shared tier relaxes this: another replica's
            # demotion may coexist with this replica's HBM residency.)
            both = set(by_hash) & set(tier._by_hash)
            if both:
                _fail("I10", f"block(s) {sorted(h[:8] for h in both)} "
                             f"resident in BOTH the HBM prefix cache and "
                             f"the private host tier — demote/re-admit "
                             f"must MOVE a block, never fork it")
        for s in range(B):
            plan = getattr(eng, "_tier_plan", None)
            if plan is None:
                break
            for b, h, _p in plan[s]:
                if eng._slot_req[s] is None:
                    _fail("I10", f"slot {s} holds a tier-restore plan "
                                 f"with no request seated (plan leak: "
                                 f"its pins would starve the tier LRU)")
                if h not in tier._by_hash and h not in by_hash:
                    _fail("I10", f"slot {s} plans to restore block "
                                 f"{h[:8]} which is resident in neither "
                                 f"the tier nor the HBM cache (the pin "
                                 f"window broke: only a tier_drop "
                                 f"injection may discard a pinned entry, "
                                 f"and that seam drops the plan "
                                 f"atomically)")


def audit_tier(tier) -> None:
    """I10's tier-internal half (docs/kv_tier.md): cross-check a
    :class:`~paddle_tpu.inference.kv_tier.HostKVTier`'s byte accounting
    and entry bookkeeping.  Every entry must be keyed by its own hash,
    entry bytes must sum exactly to ``used_bytes`` within the budget, and
    pins must be non-negative — a mismatch means demote/re-admit/evict
    bookkeeping corrupted the store (the failure class that silently
    serves one prompt's KV bytes to another).  Raises
    :class:`EngineAuditError` on the first violation."""
    total = 0
    for h, e in tier._by_hash.items():
        if e.hash != h:
            _fail("I10", f"tier entry keyed {h[:8]} carries hash "
                         f"{e.hash[:8]} (content address forged: ship_in "
                         f"would restore the wrong bytes)")
        if e.pins < 0:
            _fail("I10", f"tier entry {h[:8]} has negative pin count "
                         f"{e.pins} (unbalanced pin/unpin)")
        if e.nbytes <= 0:
            _fail("I10", f"tier entry {h[:8]} accounts {e.nbytes} bytes "
                         f"(empty payload)")
        total += e.nbytes
    if total != tier.used_bytes:
        _fail("I10", f"tier byte accounting does not close: entries sum "
                     f"to {total} but used_bytes={tier.used_bytes}")
    if tier.used_bytes > tier.budget_bytes:
        _fail("I10", f"tier over budget: used_bytes={tier.used_bytes} > "
                     f"budget_bytes={tier.budget_bytes} (eviction must "
                     f"run BEFORE insert, never after)")


def audit_fleet(router) -> None:
    """I9 — fleet single-ownership (docs/fleet_serving.md): cross-check a
    FleetRouter's routing registries against its replicas' live request
    journals.  Every live fleet rid is owned by EXACTLY one replica (a
    hedge-pending rid counts as the primary's until first-writer-wins
    resolves — the hedge target is the one sanctioned extra copy), owners
    are alive and actually hold the work, and no replica serves a rid the
    router does not route to it.  Raises :class:`EngineAuditError` on the
    first violation.  Note: this checks the ROUTER's invariants only —
    each replica engine audits its own I1–I8 via :func:`audit_engine`."""
    from ..inference.serving import TERMINAL_STATUSES

    for rid, req in router._reqs.items():
        if req.status in TERMINAL_STATUSES:
            _fail("I9", f"rid {rid} is {req.status} (terminal) but still "
                        f"in the fleet's live registry (zombie: it would "
                        f"keep an owner and copies)")
        owner = router._owner.get(rid)
        if owner is None:
            _fail("I9", f"live rid {rid} has no owning replica (orphaned: "
                        f"no one will ever step it)")
        if router.replicas[owner] is None or router.health[owner] == "DEAD":
            _fail("I9", f"live rid {rid} is owned by DEAD replica {owner}")
        copies = router._copies.get(rid, {})
        if owner not in copies:
            _fail("I9", f"live rid {rid}'s owner (replica {owner}) holds "
                        f"no copy of it")
        hedge = router._hedge.get(rid)
        if hedge == owner:
            _fail("I9", f"rid {rid} hedged onto its own owner (replica "
                        f"{owner}): first-writer-wins could never resolve")
        sanctioned = {owner} | ({hedge} if hedge is not None else set())
        extra = set(copies) - sanctioned
        if extra:
            _fail("I9", f"rid {rid} has copies on replica(s) "
                        f"{sorted(extra)} beyond owner {owner}"
                        + (f" and hedge {hedge}" if hedge is not None
                           else "")
                        + " — double ownership banks one stream twice")
    for rid in router._owner:
        if rid not in router._reqs:
            _fail("I9", f"owner-map entry for rid {rid} which is not a "
                        f"live fleet request")
    for rid in router._hedge:
        if rid not in router._reqs:
            _fail("I9", f"hedge-map entry for rid {rid} which is not a "
                        f"live fleet request")
    for rid, copies in router._copies.items():
        if rid not in router._reqs:
            # each leaked copy pins a Request (full prompt+output token
            # lists) for the router's lifetime — the retention class the
            # engine's rid-journal pruning fixed
            _fail("I9", f"replica-local copies (on replica(s) "
                        f"{sorted(copies)}) registered for rid {rid} "
                        f"which is not a live fleet request")
    for r, eng in enumerate(router.replicas):
        if eng is None:
            continue
        for rid in eng._reqs:
            if rid < 0:
                continue        # warmup rids (bench convention) are unrouted
            if rid not in router._reqs:
                _fail("I9", f"replica {r} serves rid {rid} unknown to the "
                            f"router (a cancelled/failed-over copy was "
                            f"never released)")
            if r not in router._copies.get(rid, {}):
                _fail("I9", f"replica {r} serves rid {rid} but the router "
                            f"records no copy there (untracked ownership)")
