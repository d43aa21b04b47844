"""Continuous-batching decode scheduler (VERDICT r2 #6).

The scheduler, the page allocator and the degradation ladder below serve
whatever model the config brings: the compiled steps take their layer
stack and their cache from a *step program* (``engine._program``;
docs/hybrid_serving.md).  A config with no ``serving_program`` gets the
dense GQA decoder of ``models/llama`` (``_DenseProgram``: this file's
``_decode_one`` / ``_mixed_one`` over ``inference.transformer_apply``, a
paged K/V pool), which everything from "TPU-first design" on describes; a
config that has one (``models/olmo_hybrid``: linear-attention layers with a
per-slot recurrent state beside the pool) brings its own, and the options
its state cannot honour are refused at construction.

Reference analog: the serving stack behind the reference's fused block
attention family — `paddle/phi/ops/yaml/fused_ops.yaml:45`
(``block_multihead_attention_``) and `:394` (``fused_multi_transformer_``) —
which backs PaddleNLP's continuous-batching servers.

TPU-first design
----------------
A TPU serving engine wants *static shapes*: one compiled decode step over a
fixed slot pool, re-run every iteration.  So instead of the reference's
dynamic batch, we keep:

  * a slot pool of ``max_batch`` lanes in one shared dense KV cache
    [L, max_batch, nkv, S, hd] — a lane is the TPU analog of a block table
    entry (HBM is pre-reserved; XLA gets a fixed layout to tile),
  * one jitted decode step with a *per-slot position vector* — slots at
    different depths decode together in a single batched program (this is
    what "continuous batching" means at the kernel level: the batch never
    drains to admit a newcomer),
  * prefill into a single lane with bucketed prompt padding (powers of two),
    bounding the number of compiled prefill variants to log2(max_seq).

``paged=True`` swaps the per-slot dense lanes for a BLOCK-TABLE cache (the
reference's ``block_multihead_attention_`` memory model, fused_ops.yaml:45):
K/V live in a fixed pool of [num_blocks, nkv, block_size, hd] pages per
layer, each slot owns a host-managed list of block ids, and the compiled
programs receive the [max_batch, max_blocks] table AS DATA — shapes stay
static (the TPU requirement) while HBM is shared by actual usage, so
admission is bounded by free blocks rather than worst-case max_seq lanes.
Decode attention dispatches to the ragged paged-attention Pallas kernel
(`ops/pallas/paged_attention.py`, docs/paged_attention.md), which walks only
each slot's LIVE block-table pages — HBM bytes per step scale with resident
tokens, not the longest request; with the kernel disabled
(``PADDLE_TPU_DISABLE_PALLAS=paged_attention``) or on unsupported shapes,
attention reads a gathered view of the slot's blocks (XLA fuses the block
gather into the attention contraction's operand read); when the pool runs
dry the youngest slot is preempted vLLM-style (blocks freed, request
requeued with prompt+generated so far; the stored tokens are teacher-forced
on resume, which makes the recompute exact for greedy AND sampled decode).

Long-context flash-decode + the fused decode step (docs/paged_attention.md)
are the paged decode path's pure-speed levers, both on by default and both
token-identical to the paths they replace: decode attention dispatches
split-K (a long slot's page walk runs as S parallel shards merged by an
exact log-sum-exp combine — ``PADDLE_TPU_DISABLE_PALLAS=flash_decode``
restores the sequential walk), and the whole per-layer decode prologue —
RoPE, the two KV-append scatters and the attention kernel — runs as ONE
fused Pallas launch (``PADDLE_TPU_DISABLE_PALLAS=fused_decode_step``
rebuilds the unfused engine byte-identically; in fused mode the pool
carries one extra SPILL page dropped writes land on, since a Pallas output
index map cannot drop).  Verify/prefill/mixed programs are byte-untouched;
TP, speculation, chunked prefill, prefix-cache COW and the graceful ladder
compose with both by construction (the fused launch runs per shard inside
shard_map exactly like the rest of the kernel family).

``enable_prefix_caching=True`` (paged mode only) layers an automatic prefix
cache over the block pool (prefix_cache.py, docs/prefix_cache.md): every full
block gets a hash-chained content id, admission maps the longest cached
prefix into the slot's block-table row read-only (refcounted), prefill starts
at the first uncached token (partial-bucket prefill), release/retire/preempt
decrement refs instead of freeing, zero-ref blocks stay resident until
allocation pressure LRU-evicts them, and a fully-matched block that decode
would write into is copy-on-write duplicated first.  The paged-attention
kernel reads shared pages unchanged — sharing is purely block-table aliasing.
Opt-out: ``PADDLE_TPU_PREFIX_CACHE=0``; with caching off (the default) the
engine is byte-identical to the PR 1 engine.

``enable_host_kv_tier=True`` (paged + prefix-cache only) layers the
hierarchical-KV host tier under the cache (kv_tier.py, docs/kv_tier.md):
LRU eviction DEMOTES zero-ref chains to a byte-budgeted host-RAM page
store (``PADDLE_TPU_HOST_TIER_MIB``) instead of freeing them, and
admission's prefix match extends through that tier — a tier hit re-admits
pages by async H2D copy driven by the chunked-prefill cursor, so
"restoring from host" is scheduled exactly like "prefilling" (one cursor,
zero new compiled step shapes, chunk-granular preemption/cancel compose
for free).  Resident-prefix capacity then scales with host RAM rather
than leftover HBM, and the same ``ship_out``/``ship_in`` page transport
is the fleet tier's shared prefix store and ROADMAP item 1's
prefill/decode shipping primitive.  Opt-out: ``PADDLE_TPU_HOST_KV_TIER=0``
restores the pre-tier engine byte-identically.

``enable_speculation=True`` (paged mode only) adds draft-model-free
speculative decoding (speculative.py, docs/speculative.md; reference: the
``speculate_*`` op family in paddle/phi/ops/yaml): a host-side prompt-lookup
n-gram drafter proposes up to K continuation tokens per slot from the
request's own prompt+generated history, and ONE compiled multi-token verify
step scores all of them — the pending token plus the drafts ride through the
ragged paged-attention verify kernel as ``[B, K+1]`` queries with per-slot
``q_lens`` as DATA (one static program, no shape-family churn) — then the
acceptance rule runs in-graph: position-derived sampling keys make the
accepted stream TOKEN-IDENTICAL to the non-speculative engine for greedy AND
seeded sampled requests, so speculation only changes how many tokens each
host round-trip banks.  Rejected drafts roll ``pos`` back (their K/V writes
beyond the accepted point are dead until overwritten, tracked by the
``_written`` high-water mark the runtime auditor checks) and are never
content-addressed into the prefix cache.  Steps where no slot drafts run the
ordinary chunked decode — a drafter miss costs nothing.  Opt-out:
``PADDLE_TPU_SPECULATE=0``; spec-off the engine is byte-identical to the
non-speculative engine.

``enable_chunked_prefill=True`` (paged mode only) removes the last
monolithic hot path: instead of one bucketed whole-prompt prefill per
admission — which stalls every running decode slot for the full prompt
length and compiles a log2(max_seq) family of prefill variants — every
prompt streams in as fixed-size ``prefill_chunk``-token chunks co-scheduled
with decode inside ONE compiled **mixed step** (docs/chunked_prefill.md;
the Sarathi-style stall-free batching the ragged paged-attention papers
argue for).  Each engine step packs up to ``token_budget`` tokens as
[decode slots | prefill chunks]: every decode-ready slot advances exactly
one token (row 0 of its lane), prefilling slots carry up to
``prefill_chunk`` prompt rows, and the whole [B, T] launch runs the ragged
chunked-prefill kernel (`ops/pallas/paged_attention.paged_attention_prefill`
— per-slot positions/q_lens are DATA, so prefill compiles O(1) variants
regardless of prompt length).  A prefill lane's final row sits at the last
prompt token's position, so its logits ARE the first decode step's — TTFT
costs no extra launch.  Prefix-cache hits start the first chunk at the
first uncached token and register pages as chunks complete them;
speculation skips slots still prefilling (mixed steps run while any prompt
streams, the spec path resumes once prefill drains).  Opt-out:
``PADDLE_TPU_CHUNKED_PREFILL=0``; chunked-off the engine is byte-identical
to the bucketed-prefill engine.

Fault tolerance (docs/fault_tolerance.md): every request ends in a terminal
``status``
(``FINISHED | FAILED | REJECTED | CANCELLED | EXPIRED``) and no per-request
fault escapes ``step()`` — the offending request is failed, its pages and
cache refs released, and every surviving request's token stream is
IDENTICAL to a run that never contained the poison request (each slot's
stream depends only on its own (seed, position) keys and its own pages, so
isolation is exact, not best-effort).  Overload walks a degradation ladder
in strict order — evict prefix-cache leaves, suspend speculation for the
step, shrink the mixed-step token budget, preempt the youngest slot, and
only then fail the one unsatisfiable request.  Requests carry an optional
``deadline_s`` (expire with partial output), ``cancel(rid)`` frees even a
mid-prefill slot via the chunked-prefill cursor, a bounded queue
(``max_queue``) applies REJECTED-on-full backpressure, and an IN-GRAPH
NaN/inf logit guard quarantines a poisoned slot instead of emitting garbage
(the guard's flags ride back with the step's tokens — no extra host sync).
``snapshot()``/``restore()`` journal accepted work (prompt, emitted tokens,
chunk cursor) and resume through the preemption path's teacher-forced
recompute — the replica-restart primitive the fleet tier needs.  Faults are
injected deterministically at the allocator / kernel-dispatch / sampler
seams via ``PADDLE_TPU_FAULT_INJECT`` (faults.py).

``tensor_parallel=N`` (docs/tp_serving.md; paged mode only, kill/override
knob ``PADDLE_TPU_TP``) fans the whole engine across N devices on a 1-D
``("tp",)`` mesh: weights take the Megatron column/row split
(models/llama.serving_param_specs), the paged KV pool and every new-page
append shard along **kv_heads** — the one axis the ragged paged-attention
kernels' page walk never crosses, so decode/verify/prefill kernel bodies
run byte-unchanged per shard inside shard_map — and each layer pays exactly
two psum boundaries (attention output, MLP output).  Block tables, the
scheduler, the prefix cache, the fault ladder and drafter state stay
replicated host-side, so prefix caching, speculation, chunked prefill,
graceful degradation and snapshot/restore all compose with TP by
construction; TP=1 builds the byte-identical single-chip engine and TP>1
is token-identical to it (every shard computes the same full-vocab logits
row after the psums, so the in-graph sampler agrees by construction).

Per-request sampling (reference: ``top_p_sampling``, ops.yaml:4947) runs
inside the jitted step: temperature/top-p/seed are per-slot DATA vectors, so
one compiled program serves mixed greedy/sampled batches, and RNG keys
derive from (slot seed, position) — deterministic, replayable streams.

Admission/retirement/allocation is plain Python around the compiled
programs — scheduling is control-plane work and costs microseconds next to
a device step, the same split the reference makes between its C++ scheduler
and CUDA kernels.
"""

from __future__ import annotations

import functools
import math
import time
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..profiler import RecordEvent
from .faults import FaultInjected

__all__ = ["Request", "ContinuousBatchingEngine", "TERMINAL_STATUSES",
           "REQUEST_EDGES"]

#: terminal request statuses (docs/fault_tolerance.md status lifecycle);
#: a request in one of these owns zero pages and zero cache refs — the
#: runtime auditor's I8 (analysis/engine_audit.py)
TERMINAL_STATUSES = frozenset({"FINISHED", "FAILED", "REJECTED", "CANCELLED",
                               "EXPIRED"})

#: declared request-lifecycle transition table, verified exhaustively
#: against every ``.status`` assignment site by the host-contract pass
#: (analysis/host_contracts.py; docs/analysis.md §"Host contracts").
#: PENDING<->RUNNING covers admission (_admit) and preemption (_preempt);
#: both live states may fall to any terminal status (rejection and expiry
#: can hit queued requests, failure/cancel/finish hit seated ones).
#: Terminal statuses are absorbing — there is deliberately no edge out.
REQUEST_EDGES = frozenset(
    {("PENDING", "RUNNING"), ("RUNNING", "PENDING")}
    | {(live, term) for live in ("PENDING", "RUNNING")
       for term in TERMINAL_STATUSES})

#: terminal status -> engine stats counter (FINISHED ticks decode counters
#: through the normal retire path instead)
_STATUS_STAT = {"FAILED": "requests_failed", "REJECTED": "requests_rejected",
                "CANCELLED": "requests_cancelled",
                "EXPIRED": "requests_expired"}


@dataclass
class Request:
    rid: int
    prompt_ids: np.ndarray  # [s0] int32
    max_new_tokens: int = 32
    eos_token_id: int | None = None
    # per-request sampling (reference: top_p_sampling,
    # paddle/phi/ops/yaml/ops.yaml:4947).  temperature == 0 -> greedy.
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int | None = None
    # wall-clock budget from submission; overdue requests expire with the
    # partial output they have (status EXPIRED) instead of holding pages
    deadline_s: float | None = None
    # filled by the engine
    output_ids: list = field(default_factory=list)
    finished: bool = False
    ttft_s: float | None = None  # submit -> first generated token (wall s)
    # lifecycle: PENDING (queued) -> RUNNING (seated) -> one of
    # TERMINAL_STATUSES; ``error`` is set for every non-FINISHED terminal
    status: str = "PENDING"
    error: str | None = None
    # request-lifecycle trace id (docs/observability.md): assigned at
    # admission when None; replica copies and failover replays carry the
    # SAME id, so one request's spans correlate across the whole fleet
    trace_id: str | None = None


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def journal_entry(req: Request, prefilled: int = 0,
                  now: float | None = None) -> dict:
    """One request's snapshot-journal entry — THE schema
    :meth:`ContinuousBatchingEngine.snapshot` emits and
    :meth:`ContinuousBatchingEngine.adopt` consumes (docs/
    fault_tolerance.md "Snapshot / restore").  Shared with the fleet
    router's journal fallback (inference/fleet.py) so the field set and
    coercions can never diverge between the two producers.

    ``deadline_remaining_s`` is the UNSPENT wall-clock budget at ``now``:
    adoption re-arms the deadline with what is actually left, so a
    restored request expires at ~100% of its original SLO, never ~180%
    (``deadline_s`` stays as provenance)."""
    if now is None:
        now = time.perf_counter()
    if req.deadline_s is None:
        remaining = None
    else:
        remaining = max(0.0, float(req.deadline_s)
                        - (now - getattr(req, "_submit_s", now)))
    return {
        "rid": int(req.rid),
        "prompt_ids": np.asarray(req.prompt_ids,
                                 np.int32).ravel().tolist(),
        "output_ids": [int(t) for t in req.output_ids],
        "max_new_tokens": int(req.max_new_tokens),
        "eos_token_id": (None if req.eos_token_id is None
                         else int(req.eos_token_id)),
        "temperature": float(req.temperature or 0.0),
        "top_p": float(1.0 if req.top_p is None else req.top_p),
        "seed": None if req.seed is None else int(req.seed),
        "deadline_s": (None if req.deadline_s is None
                       else float(req.deadline_s)),
        "deadline_remaining_s": remaining,
        # the chunk cursor: restore re-prefills from the first uncached
        # token, so this is provenance (how far the dead replica got),
        # not a resume offset into lost KV bytes
        "prefilled": int(prefilled),
    }


class _TPShardView:
    """Per-shard config view inside the ``("tp",)`` shard_map region
    (docs/tp_serving.md): the compiled-step bodies read head counts off the
    config, and inside the region every shard holds nh/tp query heads and
    nkv/tp kv heads of the SAME full head_dim — so the view pins tp-local
    counts and the true head_dim (the dataclass property would miscompute
    it from hidden_size // local_heads) and proxies everything else
    (dtype, rope_theta, layer count, ...) to the real config.  The GQA
    group ratio nh/nkv is tp-invariant, which is why the paged-attention
    kernels run byte-unchanged per shard."""

    def __init__(self, cfg, tp: int):
        self._cfg = cfg
        self.num_attention_heads = cfg.num_attention_heads // tp
        self.num_key_value_heads = cfg.num_key_value_heads // tp
        self.head_dim = cfg.head_dim

    def __getattr__(self, name):
        return getattr(self._cfg, name)


@dataclass(frozen=True)
class StepGeometry:
    """What a step program is built for: the engine's static shapes."""
    max_batch: int
    max_seq: int
    block_size: int
    num_blocks: int         # pages the allocator hands out
    pool_pages: int         # pages a layer's pool holds (+1: the spill page)
    mixed_rows: int         # packed rows the mixed step's matmuls run
    fused: bool             # rope + append + attention as one decode launch


class _DenseProgram:
    """The step program of a config that brings none: the dense GQA decoder
    (``models/llama``), whose only state is a K/V row a position.  The
    contract every step program keeps (docs/hybrid_serving.md):
    ``init_cache() -> (cache_k, cache_v)``, the pair every compiled step
    carries and donates; ``decode_one`` / ``mixed_one``, the forward passes
    the decode and mixed steps sample from; ``pages(cache)``, the paged
    pool inside one of the pair; ``model_id()``, what a journal's restore
    target has to agree on; ``state_bytes()`` and
    ``launch_counters(launch)``: the per-slot state outside the pool, and
    what a launch adds to ``engine.stats`` beyond the engine's own
    counters."""

    def __init__(self, engine, cache_shape):
        self._eng = engine
        self._shape = cache_shape
        self.decode_one = engine._decode_one
        self.mixed_one = engine._mixed_one

    def init_cache(self):
        eng, shape = self._eng, self._shape
        if eng.paged and eng.kv_quant is not None:
            # quantized pools: int8 codes + per-(page, head) f32 scales as
            # ONE pytree per pool — compiled steps, donation, the COW
            # copy and TP sharding all treat the pair as the cache
            # operand, so the scheduler/allocator plumbing is untouched
            return ({"q": jnp.zeros(shape, jnp.int8),
                     "scale": jnp.zeros(shape[:3], jnp.float32)},
                    {"q": jnp.zeros(shape, jnp.int8),
                     "scale": jnp.zeros(shape[:3], jnp.float32)})
        return (jnp.zeros(shape, eng.cfg.dtype),
                jnp.zeros(shape, eng.cfg.dtype))

    def pages(self, cache):
        return cache["q"] if isinstance(cache, dict) else cache

    def model_id(self) -> str:
        # every field that changes the teacher-forced recompute's logits
        # belongs in the id — shapes alone would let a rope_theta or dtype
        # mismatch resume silently wrong
        cfg = self._eng.cfg
        return (f"llama:v{cfg.vocab_size}:h{cfg.hidden_size}"
                f":L{cfg.num_hidden_layers}"
                f":nh{cfg.num_attention_heads}"
                f":nkv{cfg.num_key_value_heads}"
                f":i{cfg.intermediate_size}"
                f":tie{int(bool(cfg.tie_word_embeddings))}"
                f":dt{jnp.dtype(cfg.dtype).name}"
                f":rope{cfg.rope_theta:g}"
                f":eps{cfg.rms_norm_eps:g}")

    def state_bytes(self) -> int:
        return 0

    def launch_counters(self, launch: dict) -> dict:
        return {}


class ContinuousBatchingEngine:
    """Slot-pool continuous batching over a decoder model: ``models/llama``
    conventions for ``cfg``/``params`` (the same pytree the AOT
    GenerationEngine uses, inference/__init__.py:249) or, where the config
    brings a ``serving_program`` (``models/olmo_hybrid``), that model's own
    layers and cache (docs/hybrid_serving.md).
    """

    def __init__(self, cfg, params, max_batch: int = 8, max_seq: int = 512,
                 chunk: int = 1, quant: str | None = None, paged: bool = False,
                 kv_quant: str | None = None,
                 block_size: int = 64, num_blocks: int | None = None,
                 enable_prefix_caching: bool = False,
                 enable_speculation: bool = False, num_draft_tokens: int = 4,
                 spec_ngram: int = 3, enable_chunked_prefill: bool = False,
                 prefill_chunk: int = 128, token_budget: int | None = None,
                 max_queue: int | None = None, tensor_parallel: int = 1,
                 enable_host_kv_tier: bool = False, host_tier=None,
                 metrics=None, metrics_labels: dict | None = None):
        """``cfg`` / ``params``: a ``models/llama`` config and its pytree
        (the dense GQA decoder: every option below applies), or a config
        that brings its own step program and cache through
        ``cfg.serving_program`` (``models/olmo_hybrid``: per-slot recurrent
        state beside the paged pool) — such a config says through
        ``cfg.check_serving_options`` which of the options below its state
        cannot honour, and those raise here (docs/hybrid_serving.md).
        ``chunk``: decode steps per compiled call.  Tokens feed back
        on-device inside a lax.scan and the host fetches ``chunk`` tokens per
        round-trip — the lever against host-device latency (one round trip
        per token is what bounds single-step decode).  Retire
        and admission happen at chunk granularity; generated tokens past a
        request's EOS/budget inside a chunk are trimmed host-side.
        ``quant``: None | 'int8' | 'int4' — weight-only quantized matmuls
        (weights stream from HBM at 1/2 or 1/4 the bytes).
        ``kv_quant``: None | 'int8' | 'int4' — QUANTIZED KV pools (paged
        mode only; docs/paged_attention.md "Megastep stage 2"): pages
        store int8 codes (int4 packs two nibbles per byte) plus per-
        (page, kv_head) f32 scales, halving or quartering resident KV
        bytes — the production memory configuration.  Every attention
        path dequantizes on read (the kernels' ``kv_quant`` mode);
        appends REQUANTIZE the dirty page (dequantize with the old
        scale, insert, recompute the scale, rewrite) — in-kernel on the
        fused decode path (``fused_quant_append``: zero scatters per
        decode step), as a requant-scatter pair on the kill-switched
        path, page-batched in XLA on prefill/verify/mixed writes.
        Because requantization is lossy per write EVENT, the emitted
        stream depends on event grouping (chunking/speculation change
        quantization noise); the guaranteed identity is between the
        fused, kill-switched and gather-oracle ARMS of one
        configuration — each computes byte-identical pool contents.
        ``paged``: block-table KV cache (``block_size`` tokens per page,
        ``num_blocks`` pages shared by all slots; default num_blocks gives
        half the dense pool's capacity — the paged mode's point is serving
        more logical context than physically reserved HBM).
        ``enable_prefix_caching``: content-addressed reuse of full KV blocks
        across requests (paged mode only; see prefix_cache.py).  Kill switch:
        ``PADDLE_TPU_PREFIX_CACHE=0`` forces it off regardless.
        ``enable_speculation``: prompt-lookup n-gram drafting + multi-token
        verification (paged mode only; see speculative.py and
        docs/speculative.md).  ``num_draft_tokens`` (K) bounds drafts per
        step — the verify step's static query width is K+1;``spec_ngram`` is
        the longest suffix the drafter matches.  Kill switch:
        ``PADDLE_TPU_SPECULATE=0`` forces it off regardless.
        ``enable_chunked_prefill``: stream prompts in ``prefill_chunk``-token
        chunks co-scheduled with decode in one compiled mixed step per
        iteration (paged mode only; docs/chunked_prefill.md).
        ``token_budget`` caps total tokens per mixed step (decode rows pack
        first, prefill chunks fill the remainder; default
        ``prefill_chunk + max_batch``).  While any prompt streams, every
        engine step is a mixed step — ONE host round-trip per decode token
        — so a ``chunk > 1`` engine trades its scan's RTT amortization for
        stall-freedom exactly while prompts are in flight (the Sarathi
        tradeoff; the untouched chunk-length scan resumes once prefill
        drains — docs/chunked_prefill.md "token-budget semantics").  Kill
        switch: ``PADDLE_TPU_CHUNKED_PREFILL=0`` forces it off
        regardless.
        ``max_queue``: admission backpressure — when the wait queue already
        holds this many requests, ``add_request`` marks the newcomer
        ``REJECTED`` (with ``error``) instead of queueing it; None (the
        default) keeps the queue unbounded.  Preemption re-inserts are
        exempt: accepted work is never rejected.
        ``tensor_parallel``: shard the engine over N devices on a 1-D
        ``("tp",)`` mesh (docs/tp_serving.md; paged mode only).  Weights
        take the Megatron column/row split (models/llama.
        serving_param_specs), the paged KV pool and every new-page append
        shard along **kv_heads**, and each compiled step runs the
        single-chip per-shard programs inside shard_map with exactly two
        psum boundaries per layer (attention output, MLP output) — block
        tables, scheduler, prefix cache, fault ladder and drafter state
        stay replicated host-side, so every feature above composes with TP
        by construction and TP>1 is token-identical to TP=1.  N must
        divide num_key_value_heads (and intermediate_size) and not exceed
        the visible device count.  ``PADDLE_TPU_TP=<int>`` overrides this
        value (validated: an invalid degree warns once with the valid
        divisors and falls back to 1 — utils/envflags.env_tp).
        ``enable_host_kv_tier`` (docs/kv_tier.md; requires paged mode AND
        ``enable_prefix_caching``): hierarchical KV — prefix-cache
        eviction DEMOTES zero-ref chains to a byte-budgeted host-RAM page
        store (``PADDLE_TPU_HOST_TIER_MIB``) instead of freeing them, and
        admission's prefix match extends through that tier: a tier hit
        re-admits pages by async H2D copy scheduled through the
        chunked-prefill cursor exactly like prefilling (one cursor, zero
        new compiled shapes).  ``host_tier`` passes a pre-built
        :class:`~paddle_tpu.inference.kv_tier.HostKVTier` — how the
        FleetRouter shares ONE tier across replicas so any replica
        re-admits chains another replica computed.  Kill switch:
        ``PADDLE_TPU_HOST_KV_TIER=0`` forces it off regardless
        (byte-identical to the pre-tier engine), and
        ``PADDLE_TPU_PREFIX_CACHE=0`` neutralizes it too (no content
        address, nothing to demote).
        ``metrics`` / ``metrics_labels`` (docs/observability.md): an
        optional shared :class:`~paddle_tpu.inference.observability.
        MetricsRegistry` plus constant label set (e.g. ``{"replica": k}``
        — how the FleetRouter aggregates N replicas into one exposition);
        by default the engine creates its own registry."""
        from ..models import llama as _llama  # noqa: F401  (cfg type lives there)

        self.cfg = cfg
        if quant is not None:
            from . import quantize_layer_params

            params = quantize_layer_params(params, quant)
        self.quant = quant
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.chunk = int(chunk)
        self.paged = bool(paged)
        L = cfg.num_hidden_layers
        nkv, hd = cfg.num_key_value_heads, cfg.head_dim
        # quantized KV pools (docs/paged_attention.md "Megastep stage 2"):
        # validated before any pool geometry is derived
        if kv_quant is not None:
            if kv_quant not in ("int8", "int4"):
                raise ValueError(f"kv_quant must be None, 'int8' or "
                                 f"'int4', got {kv_quant!r}")
            if not paged:
                raise ValueError("kv_quant requires paged=True (per-page "
                                 "scales live on block-table pages)")
            if kv_quant == "int4" and hd % 2:
                raise ValueError(f"kv_quant='int4' needs an even head_dim "
                                 f"(got {hd}): two nibbles pack per byte")
        self.kv_quant = kv_quant
        # ---- tensor parallelism (docs/tp_serving.md) ----
        # resolve the degree FIRST: the KV pool is created already sharded
        # and every compiled program below is built per-shard.  tp == 1
        # must construct the exact pre-TP engine (no mesh, no device_put,
        # no shard_map) — every TP behavior hangs off self.tp > 1.
        from ..utils.envflags import env_tp

        tp = int(tensor_parallel)
        if tp < 1:
            # a caller's arithmetic bug (devices // n == 0) must raise,
            # not degrade to a nonsense degree (env typos degrade instead
            # — env_tp already floors those at 1 with a warning)
            raise ValueError(f"tensor_parallel must be >= 1, got {tp}")
        tp_env = env_tp(nkv, jax.device_count())
        if tp_env is not None:
            tp = tp_env     # operator override replaces the ctor value
        if tp > 1:
            problems = []
            if not paged:
                problems.append(
                    "tensor_parallel > 1 requires paged=True (TP shards "
                    "the paged KV pool along kv_heads)")
            if nkv % tp:
                divs = sorted(d for d in range(1, nkv + 1) if nkv % d == 0)
                problems.append(
                    f"tensor_parallel={tp} does not divide "
                    f"num_key_value_heads={nkv} — a sub-head split would "
                    f"break the shard-local page walk (valid divisors: "
                    f"{divs})")
            if cfg.intermediate_size % tp:
                problems.append(
                    f"tensor_parallel={tp} does not divide "
                    f"intermediate_size={cfg.intermediate_size} (the MLP "
                    f"column split needs an even ffn slice per shard)")
            if tp > jax.device_count():
                problems.append(
                    f"tensor_parallel={tp} exceeds the "
                    f"{jax.device_count()} visible device(s)")
            if problems:
                if tp_env is not None:
                    # an env override must degrade to the single-chip
                    # engine, never crash the serve (same contract as
                    # env_tp's own validation)
                    warnings.warn(f"PADDLE_TPU_TP={tp}: "
                                  + "; ".join(problems)
                                  + "; falling back to tensor_parallel=1")
                    tp = 1
                else:
                    raise ValueError("; ".join(problems))
        self.tp = tp
        # a model that brings its own step program says which of the
        # options above its state cannot honour (docs/hybrid_serving.md):
        # refused here, as resolved (env kill switches included), before
        # anything is built
        check = getattr(cfg, "check_serving_options", None)
        if check is not None:
            from ..utils.envflags import env_bool

            on = lambda asked, flag: bool(asked) and env_bool(flag, True)
            check(paged=paged, chunk=self.chunk, kv_quant=kv_quant,
                  tensor_parallel=tp,
                  enable_prefix_caching=on(enable_prefix_caching,
                                           "PADDLE_TPU_PREFIX_CACHE"),
                  enable_speculation=on(enable_speculation,
                                        "PADDLE_TPU_SPECULATE"),
                  enable_host_kv_tier=on(
                      enable_host_kv_tier or host_tier is not None,
                      "PADDLE_TPU_HOST_KV_TIER"),
                  enable_chunked_prefill=on(enable_chunked_prefill,
                                            "PADDLE_TPU_CHUNKED_PREFILL"))
        self._tp_axis = None
        self._mesh = None
        self._body_cfg = cfg       # the cfg the compiled-step bodies read
        if tp > 1:
            from jax.sharding import Mesh, NamedSharding
            from jax.sharding import PartitionSpec as _P

            self._mesh = Mesh(np.asarray(jax.devices()[:tp]), ("tp",))
            self._tp_axis = "tp"
            # inside the shard_map region every step body sees tp-local
            # head counts over the same head_dim (GQA ratio unchanged —
            # the Pallas kernels run byte-identically per shard)
            self._body_cfg = _TPShardView(cfg, tp)
            specs = _llama.serving_param_specs(cfg, quant=quant)
            if "lm_head" not in params:
                specs.pop("lm_head", None)
            self._param_specs = specs
            self._param_shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self._mesh, s), specs,
                is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
            # pool layout [L, num_blocks, nkv, bs, hd]: ONLY kv_heads
            # shards — per-shard page capacity equals num_blocks, so the
            # host allocator's accounting holds exactly on every shard
            self._cache_spec = _P(None, None, "tp")
            self._cache_sharding = NamedSharding(self._mesh,
                                                 self._cache_spec)
            self.params = jax.device_put(self.params, self._param_shardings)
        self._fused = False   # fused decode step: paged-mode only, see below
        self._fused_mlp = False   # fused MLP layer half: ditto (stage 2)
        if paged:
            assert max_seq % block_size == 0, (max_seq, block_size)
            self.block_size = block_size
            self.max_blocks = max_seq // block_size     # per-slot logical cap
            # default pool: half the worst-case footprint (continuous
            # batching oversubscribes), floored at ONE full request so a
            # max_batch=1 engine is constructible
            self.num_blocks = (num_blocks if num_blocks is not None
                               else max((max_batch * self.max_blocks) // 2,
                                        self.max_blocks))
            assert self.num_blocks >= self.max_blocks, (
                f"pool of {self.num_blocks} blocks cannot hold one full "
                f"request ({self.max_blocks} blocks)")
            # fused decode step (docs/paged_attention.md "Fused decode
            # step"): rope + KV-append + attention in ONE Pallas launch per
            # layer on the plain decode path.  Decided at ctor time because
            # the pool grows a SPILL page (physical index num_blocks) that
            # dropped writes land on — Pallas output index maps cannot
            # drop.  The allocator never hands the spill page out (its free
            # list stays range(num_blocks)), reads of sentinel table rows
            # resolve to it (finite garbage, masked), and every other
            # compiled program treats it exactly like `.at[...,
            # mode='drop']` did.  PADDLE_TPU_DISABLE_PALLAS=
            # fused_decode_step (or =paged_attention, or an unsupported
            # shape) rebuilds the pre-fusion engine byte-identically:
            # no spill page, unfused rope + scatter + attention decode.
            from ..ops.pallas import on_tpu
            from ..ops.pallas import paged_attention as _pa_mod

            if on_tpu():
                # on the chip a default kernel the shapes rule out is said
                # once, here — never a silent switch to the XLA reference
                for kname, why in (
                        ("paged_attention", _pa_mod.kernel_shape_problem(
                            cfg.num_attention_heads, nkv, hd, block_size)),
                        ("fused_layer_mlp", _pa_mod.fused_mlp_shape_problem(
                            cfg.hidden_size,
                            cfg.intermediate_size // self.tp))):
                    if why:
                        warnings.warn(
                            f"Pallas kernel {kname} does not support this "
                            f"engine's shapes ({why}); serving takes its "
                            f"XLA reference path instead")
            self._fused = (_pa_mod.kernel_supported(
                cfg.num_attention_heads, nkv, hd, block_size)
                and not _pa_mod.kernel_disabled("fused_decode_step"))
            if self.kv_quant is not None:
                # quantized pools take the fused path only with the
                # in-kernel requantized append (stage 2): killing
                # fused_quant_append restores the requant-scatter decode
                # (and drops the spill page) exactly like
                # fused_decode_step does for fp pools
                self._fused = (self._fused and not _pa_mod.kernel_disabled(
                    "fused_quant_append"))
            # decode megastep stage 2: fuse the post-attention layer half
            # (residual + post RMSNorm + SwiGLU MLP) into one per-layer
            # launch on the decode path.  Requires the fused attention
            # step (so the kill-switched serving_decode_step program
            # stays the exact pre-fusion oracle) and fp matmul leaves
            # (weight-only-quant leaves resolve through wmat's dequant;
            # streaming them dense through the kernel would defeat the
            # quantized weight footprint).
            self._fused_mlp = (self._fused and quant is None
                               and _pa_mod.fused_mlp_supported(
                                   cfg.hidden_size,
                                   cfg.intermediate_size // self.tp))
            nbp = self.num_blocks + (1 if self._fused else 0)
            if self.kv_quant is None:
                shape = (L, nbp, nkv, block_size, hd)
            else:
                hd_store = hd // 2 if self.kv_quant == "int4" else hd
                shape = (L, nbp, nkv, block_size, hd_store)
            # host allocator state
            self._free: list[int] = list(range(self.num_blocks))
            self._slot_blocks: list[list[int]] = [[] for _ in range(max_batch)]
            # shared (refcounted, read-only) cached blocks mapped at the FRONT
            # of each slot's row; private writable pages follow — row layout
            # [shared..., private...] is the allocator invariant
            self._slot_shared: list[list[str]] = [[] for _ in range(max_batch)]
            # sentinel num_blocks = unallocated (oob: writes drop, reads are
            # masked by the causal/active mask before they matter)
            self._table = np.full((max_batch, self.max_blocks),
                                  self.num_blocks, np.int32)
            self._admit_seq = 0
            self._slot_age = np.zeros(max_batch, np.int64)
        else:
            shape = (L, max_batch, nkv, max_seq, hd)
        # automatic prefix cache (content-addressed KV block reuse).  The
        # cache-off path must stay byte-identical to the plain paged engine,
        # so EVERY cache behavior hangs off self._pcache being non-None.
        self._pcache = None
        # the env kill switch is checked FIRST so =0 neutralizes the feature
        # totally — even an (invalid) paged=False request runs cache-off
        # instead of raising, honoring "forces it off regardless".
        # env_bool validates the value: a typo ('off') warns instead of
        # silently leaving the cache enabled (utils/envflags.py)
        from ..utils.envflags import env_bool, warn_retired_flags

        warn_retired_flags()
        if enable_prefix_caching and env_bool("PADDLE_TPU_PREFIX_CACHE",
                                              True):
            if not paged:
                raise ValueError("enable_prefix_caching requires paged=True "
                                 "(the cache shares block-table pages)")
            from .prefix_cache import PrefixCache

            self._pcache = PrefixCache(block_size)
            # page-granular COW: duplicate pool page src into dst across
            # all layers (donated — no full-pool copy materializes).  TP:
            # page indices address the unsharded num_blocks axis, so the
            # copy is shard-local; the output pins the pool sharding so
            # GSPMD can never decide to re-lay the donated buffer out.
            # tree_map so a quantized pool's codes AND per-page scales
            # copy together (a bare fp pool maps through unchanged —
            # identical jaxpr to the direct .at[] form)
            self._copy_page = jax.jit(
                lambda c, dst, src: jax.tree_util.tree_map(
                    lambda a: a.at[:, dst].set(a[:, src]), c),
                donate_argnums=(0,),
                **({"out_shardings": self._cache_sharding}
                   if self.tp > 1 else {}))
            # partial-bucket prefill: compiled per bucket; start/length
            # are DATA so one program serves every hit depth
            if self.tp == 1:
                self._prefill_prefix = jax.jit(
                    self._prefill_impl_paged_prefix, donate_argnums=(2, 3),
                    static_argnums=(7,))
            else:
                self._prefill_prefix = jax.jit(
                    self._tp_shard_prefill(self._prefill_impl_paged_prefix),
                    donate_argnums=(2, 3), static_argnums=(7,))
        # hierarchical KV: host-RAM spill tier behind the prefix cache
        # (ISSUE 13, docs/kv_tier.md).  EVERY tier behavior hangs off
        # self._tier being non-None, and the env kill switch is checked
        # FIRST so PADDLE_TPU_HOST_KV_TIER=0 neutralizes the feature
        # totally — tier-off the engine is byte-identical to the pre-tier
        # engine (eviction frees, admission stops at the HBM match).
        self._tier = None
        if ((enable_host_kv_tier or host_tier is not None)
                and env_bool("PADDLE_TPU_HOST_KV_TIER", True)):
            if not paged or not enable_prefix_caching:
                raise ValueError(
                    "enable_host_kv_tier requires paged=True and "
                    "enable_prefix_caching=True (the tier is keyed by the "
                    "prefix cache's chain hashes and holds its evicted "
                    "pages)")
            if self._pcache is not None:
                # PADDLE_TPU_PREFIX_CACHE=0 neutralizes the tier too:
                # with no content address there is nothing to demote to
                # or match through — the engine runs tier-off rather than
                # raising, honoring "forces it off regardless"
                from .kv_tier import HostKVTier

                self._tier = (host_tier if host_tier is not None
                              else HostKVTier())
                # donated H2D page write (ship_in's device half): upload
                # one host page into pool page dst across all layers.
                # TP: page indices address the unsharded num_blocks axis
                # and the replicated page operand shards onto the pool's
                # kv_heads spec in-graph; out_shardings pins the layout
                # so the donated buffer is never re-laid out (the same
                # contract as _copy_page).
                # tree_map like _copy_page: a quantized pool restores
                # codes + scales in one donated write
                self._tier_write = jax.jit(
                    lambda c, dst, page: jax.tree_util.tree_map(
                        lambda a, p: a.at[:, dst].set(p), c, page),
                    donate_argnums=(0,),
                    **({"out_shardings": self._cache_sharding}
                       if self.tp > 1 else {}))
                # per-slot match-to-restore plans: [(block_idx, hash,
                # parent), ...] — consumed front-first by the chunked
                # cursor at the step token budget's pace (restores bill
                # like prefill rows, one-block floor), dropped whole on
                # preempt/cancel/terminal or a tier miss (see
                # _tier_restore_step / _drop_tier_plan)
                self._tier_plan: list[list] = [[] for _ in range(max_batch)]
        # slot state (host side)
        self._slot_req: list[Request | None] = [None] * max_batch
        self._pos = np.zeros(max_batch, np.int32)      # next write position
        # KV-write high-water mark per slot: positions [0, _written) hold
        # device-written (or cache-mapped) K/V.  Equals pos everywhere except
        # after a speculative verify step with rejections, where pos rolls
        # back to the accepted point but the rejected drafts' writes remain
        # (dead until overwritten).  The engine auditor's I6 cross-checks
        # pos <= written <= mapped-page coverage.
        self._written = np.zeros(max_batch, np.int32)
        self._last_tok = np.zeros(max_batch, np.int32)
        # per-slot sampling state (temperature 0 = greedy; one compiled
        # program serves mixed greedy/sampled batches — the knobs are DATA)
        self._temp = np.zeros(max_batch, np.float32)
        self._topp = np.ones(max_batch, np.float32)
        self._seed = np.zeros(max_batch, np.int32)
        self._queue: list[Request] = []
        # fault tolerance (docs/fault_tolerance.md)
        from .faults import FaultPlan

        self._faults = FaultPlan.from_env()
        self._step_no = 0          # engine step counter (fault-plan key)
        self.max_queue = max_queue
        # rid -> Request for every request ever accepted: cancel()'s lookup,
        # snapshot()'s journal source, and the auditor's I8 witness set
        self._reqs: dict[int, Request] = {}
        # per-slot sampler-seam poison bits (nan_logits injection): DATA to
        # the compiled steps, where they become a genuinely non-finite
        # logits row the in-graph guard must catch
        self._poison = np.zeros(max_batch, bool)
        self._kernel_err_streak = 0
        # consecutive failed launches tolerated before giving up: a raise at
        # the dispatch seam leaves state untouched (retry is free), but a
        # persistent failure means the program itself cannot run
        self._kernel_err_limit = 3
        # consecutive steps where admission made no progress with nothing
        # resident (see step(): waiting cannot help — ladder rung 5 applies
        # at admission after this many stuck steps)
        self._admit_stalls = 0
        impl = self._decode_impl_paged if paged else self._decode_impl
        # two decode variants behind a STATIC sampling flag: the full-vocab
        # sort/softmax/categorical of the sampler must not run (XLA cannot
        # DCE work behind a data-dependent where) when every resident slot
        # is greedy.  n_rep: tokens + guard flags ride back replicated.
        self._decode_greedy = self._jit_step(impl, n_rep=2, sampling=False)
        self._decode_sampling = self._jit_step(impl, n_rep=2, sampling=True)
        # prefill writes its lane directly into the donated pool arrays —
        # no slice-out/scatter-back copies of the full pool per admission
        pimpl = self._prefill_impl_paged if paged else self._prefill_impl
        if self.tp == 1:
            self._prefill = jax.jit(pimpl, donate_argnums=(2, 3),
                                    static_argnums=(6,))
        else:
            self._prefill = jax.jit(self._tp_shard_prefill(pimpl),
                                    donate_argnums=(2, 3),
                                    static_argnums=(6,))
        # speculative decoding (prompt-lookup drafting + multi-token verify).
        # Like the prefix cache, EVERY spec behavior hangs off self._spec
        # being non-None, and the env kill switch is checked FIRST so
        # PADDLE_TPU_SPECULATE=0 neutralizes the feature totally (even an
        # invalid paged=False request runs spec-off instead of raising).
        self._spec = None
        self._spec_qmax = 0
        if enable_speculation and env_bool("PADDLE_TPU_SPECULATE", True):
            if not paged:
                raise ValueError(
                    "enable_speculation requires paged=True (the multi-token "
                    "verify step runs through the paged-attention kernel)")
            from .speculative import NGramDrafter

            self._spec = NGramDrafter(num_draft_tokens=num_draft_tokens,
                                      max_ngram=spec_ngram)
            # the verify step's query width is STATIC at K+1 (per-slot
            # raggedness is the q_lens data vector): one compiled variant
            # per sampling mode for the whole serve, no shape-family churn
            self._spec_qmax = int(num_draft_tokens) + 1
            self._verify_greedy = self._jit_step(
                self._verify_impl_paged, n_rep=3, sampling=False)
            self._verify_sampling = self._jit_step(
                self._verify_impl_paged, n_rep=3, sampling=True)
        # chunked prefill + unified mixed prefill/decode step (stall-free
        # continuous batching; docs/chunked_prefill.md).  Like the prefix
        # cache and speculation, EVERY chunked behavior hangs off
        # self._chunked, and the env kill switch is checked FIRST so
        # PADDLE_TPU_CHUNKED_PREFILL=0 neutralizes the feature totally —
        # chunked-off the engine is byte-identical to the bucketed engine.
        self._chunked = False
        if enable_chunked_prefill and env_bool("PADDLE_TPU_CHUNKED_PREFILL",
                                               True):
            if not paged:
                raise ValueError(
                    "enable_chunked_prefill requires paged=True (prefill "
                    "chunks stream into block-table pages)")
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            self._chunked = True
            self._prefill_chunk = int(prefill_chunk)
            # per-step token cap: decode rows pack FIRST (decode never
            # stalls), prefill chunks fill the remainder with a 1-token
            # floor so admission can never livelock on a tiny budget
            self._token_budget = (int(token_budget)
                                  if token_budget is not None
                                  else self._prefill_chunk + max_batch)
            # the rows the mixed program's matmuls run (_mixed_one): the
            # most _mixed_step can mark live — decode slots pack first, and
            # the 1-row floor adds a row only beside a slot that is still
            # prefilling — in whole sublanes of the dtype, within [B, T]
            sub = 32 // jnp.dtype(cfg.dtype).itemsize
            self._mixed_rows = min(
                -(-max(self._token_budget, max_batch) // sub) * sub,
                max_batch * self._prefill_chunk)
            # per-slot prefill progress: _prefill_ids[s] holds the FULL id
            # stream (prompt, or prompt + generated-so-far on a preemption
            # resume) while the slot is still streaming in; _prefilled[s]
            # is the cursor — the next position whose K/V must be computed.
            # A slot is "prefilling" iff _prefill_ids[s] is not None.
            self._prefill_ids: list[np.ndarray | None] = [None] * max_batch
            self._prefilled = np.zeros(max_batch, np.int32)
            # the last mixed step's packing (decode slots, prefill slots) —
            # the runtime auditor's I7 checks the two sets stay disjoint
            self._last_pack: tuple[tuple[int, ...], tuple[int, ...]] = ((),
                                                                        ())
            # ONE compiled program per sampling mode for the whole
            # serve: chunk packing / per-slot progress are q_lens/pos DATA,
            # so prefill goes from log2(max_seq) bucketed variants to O(1)
            self._mixed_greedy = self._jit_step(
                self._mixed_impl_paged, n_rep=2, sampling=False)
            self._mixed_sampling = self._jit_step(
                self._mixed_impl_paged, n_rep=2, sampling=True)
        # ---- the step program and its cache (docs/hybrid_serving.md) ----
        # the compiled steps above take their layers (decode_one /
        # mixed_one) and the cache pair they carry from the model's
        # program; a config that brings none gets the dense decoder's
        build = getattr(cfg, "serving_program", None)
        if build is None:
            self._program = _DenseProgram(self, shape)
        else:
            self._program = build(StepGeometry(
                max_batch=max_batch, max_seq=max_seq,
                block_size=self.block_size, num_blocks=self.num_blocks,
                pool_pages=nbp, mixed_rows=self._mixed_rows,
                fused=self._fused))
        self.cache_k, self.cache_v = self._program.init_cache()
        if self.tp > 1:
            # the pool lives sharded from birth; donation keeps it sharded
            # through every step, so no per-step resharding ever happens
            self.cache_k = jax.device_put(self.cache_k, self._cache_sharding)
            self.cache_v = jax.device_put(self.cache_v, self._cache_sharding)
        # ---- observability (ISSUE 11, docs/observability.md) ----
        # stats live on a typed MetricsRegistry behind a dict-compatible
        # view (keys + help strings: observability.ENGINE_STAT_SCHEMA), so
        # ``eng.stats[...]`` reads like a dict while the same counters show
        # up labelled in ``metrics.expose()``; the SLO tracker and request
        # tracer feed off the same host events.  ALL recording is
        # host-side post-step — the compiled programs above never see it.
        from .observability import (ENGINE_STAT_SCHEMA, FlightRecorder,
                                    MetricsRegistry, RequestTracer,
                                    SLOTracker, StatsView)

        self._obs_labels = dict(metrics_labels or {})
        replica = self._obs_labels.get("replica")
        obs_name = (f"replica-{replica}" if replica is not None
                    else "engine")
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.stats = StatsView(self.metrics, ENGINE_STAT_SCHEMA,
                               self._obs_labels)
        self.stats["state_bytes"] = self._program.state_bytes()
        self.slo = SLOTracker(self.metrics, self._obs_labels)
        self._h_hostgap = self.metrics.histogram(
            "paddle_tpu_serving_host_gap_seconds",
            "Host-side gap between the end of one compiled serving "
            "step and the next launch (scheduler/drafter/router time "
            "the device sits idle — ROADMAP item 5's target)"
        ).labels(**self._obs_labels)
        self._h_step = self.metrics.histogram(
            "paddle_tpu_serving_step_seconds",
            "Wall seconds per compiled serving step (launch to host "
            "fetch)").labels(**self._obs_labels)
        self._h_h2d = (self.metrics.histogram(
            "paddle_tpu_serving_h2d_restore_seconds",
            "Host->device dispatch seconds per tier page restore "
            "(kv_tier ship_in: two donated pool writes, overlapped "
            "with the next compiled step by async dispatch)")
            .labels(**self._obs_labels) if self._tier is not None
            else None)
        self._h_jupdate = self.metrics.histogram(
            "paddle_tpu_serving_journal_update_seconds",
            "Host seconds per incremental journal flush (dirty-rid "
            "entry rebuilds overlapped with the in-flight device "
            "step, docs/async_runtime.md)").labels(**self._obs_labels)
        self._tracer = RequestTracer(
            pid=int(replica) if replica is not None else 0,
            process_name=obs_name)
        self._last_step_end = None     # host-gap histogram anchor
        # the step's open phase span (serving/admit ... serving/bank), when
        # it began, and the seconds this step has spent waiting on the
        # device (the host_overlap + fetch phases): see _phase
        self._phase_ev = None
        self._phase_t0 = 0.0
        self._device_wait_s = 0.0
        # flight recorder: bounded ring of recent engine events, dumped
        # (with a metrics snapshot) on request failure / audit error —
        # chaos triage without a rerun
        self._flight = FlightRecorder(registry=self.metrics, name=obs_name)
        # opt-in runtime invariant auditor (PADDLE_TPU_ENGINE_AUDIT=1):
        # cross-checks allocator / block-table / prefix-cache bookkeeping
        # after admission and after every decode chunk, raising
        # EngineAuditError on corruption (analysis/engine_audit.py)
        from ..analysis.engine_audit import audit_enabled

        self._audit_every_step = audit_enabled()
        # ---- async host runtime (docs/async_runtime.md) ----
        # Incremental event-sourced journal: _jentries mirrors what
        # snapshot() would emit per live rid, maintained in O(changed
        # rids) — every admission / token bank / chunk-cursor advance /
        # terminal marks the rid dirty and _jflush rebuilds just those
        # entries.  The flush runs inside _host_overlap(), i.e. while
        # the device executes the already-launched step, so steady-state
        # journal upkeep costs the host-gap nothing.
        self._jentries: dict[int, dict] = {}
        self._jdirty: set[int] = set()

    # ------------- tensor-parallel wrapping (docs/tp_serving.md) -----------

    #: argnums every compiled step donates (cache_k, cache_v) — shared
    #: between _jit_step and the static-telemetry trace, which rebuilds
    #: the donation mask for an unjitted trace of the same program
    _STEP_DONATE_ARGNUMS = (1, 2)

    def _jit_step(self, impl, n_rep: int, **statics):
        """jit one ``(params, cache_k, cache_v, *data[, poison=...])``
        compiled step with the standard cache donation.  Single-chip
        (``tp == 1``): exactly the pre-TP ``jax.jit(functools.partial(...))``
        — byte-identical programs.  TP: the SAME per-shard body runs inside
        shard_map (``_tp_shard``); ``n_rep`` is the number of leading
        replicated outputs before the two cache pools."""
        body = functools.partial(impl, **statics)
        step = body if self.tp == 1 else self._tp_shard(body, n_rep)
        # the step's name in a trace: a partial has none, and its XLA
        # module would print as jit__unknown(<fingerprint>)
        step.__name__ = impl.__name__
        return jax.jit(step, donate_argnums=self._STEP_DONATE_ARGNUMS)

    def _tp_shard(self, body, n_rep: int):
        """shard_map a compiled-step body over the 1-D ``("tp",)`` mesh.

        Operand contract: ``params`` take the Megatron specs
        (models/llama.serving_param_specs — QKV/gate/up column-split,
        O/down row-split, embed/norms/lm_head replicated), the two KV pools
        shard **kv_heads** (the axis the paged-attention page walk is
        blind to), and every other operand — tokens, positions, active
        mask, sampling knobs, the block table, poison bits — replicates:
        the scheduler stays host-side and identical on every shard.
        Outputs: ``n_rep`` replicated leaves (tokens/counts/guard flags —
        every shard computes the identical full [B, V] logits row after
        the per-layer psums, so the sampler's choice agrees by
        construction) followed by the two sharded pools.  The body is the
        byte-same single-chip program over tp-local head counts; its only
        collectives are transformer_apply's two per-layer psums."""
        from jax.sharding import PartitionSpec as P

        mesh, pspec, cspec = self._mesh, self._param_specs, self._cache_spec

        def run(params, cache_k, cache_v, *data, poison=None):
            extra = (poison,) if poison is not None else ()
            if poison is None:
                fn = body
            else:
                def fn(*a):
                    return body(*a[:-1], poison=a[-1])
            in_specs = ((pspec, cspec, cspec)
                        + (P(),) * (len(data) + len(extra)))
            out_specs = (P(),) * n_rep + (cspec, cspec)
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)(
                params, cache_k, cache_v, *data, *extra)

        return run

    def _tp_shard_prefill(self, impl):
        """shard_map wrapper for the prefill-family impls
        ``(params, ids, cache_k, cache_v, *data, bucket)`` — same operand
        contract as ``_tp_shard`` (ids/table rows/lengths replicate, pools
        shard kv_heads, no replicated outputs), with the trailing static
        ``bucket`` closed over so the shard_map region is purely
        array-in/array-out."""
        from jax.sharding import PartitionSpec as P

        mesh, pspec, cspec = self._mesh, self._param_specs, self._cache_spec

        def run(params, ids, cache_k, cache_v, *rest):
            data, bucket = rest[:-1], rest[-1]

            def fn(p, i, ck, cv, *d):
                return impl(p, i, ck, cv, *d, bucket)

            in_specs = (pspec, P(), cspec, cspec) + (P(),) * len(data)
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=(cspec, cspec), check_vma=False)(
                params, ids, cache_k, cache_v, *data)

        return run

    # ---------------- compiled programs ----------------

    def _decode_one(self, params, cache_k, cache_v, tokens, pos, active,
                    table=None):
        """One batched decode step: tokens [B], pos [B], active [B] ->
        (logits [B, V], caches).  Inactive slots compute garbage that is
        masked out — the static batch is the price of a single compiled
        program, and idle lanes are cheap next to recompiling (the standard
        TPU serving trade).

        With ``table`` (paged mode) the K/V write lands in pool page
        table[b, pos//bs] at offset pos%bs and attention reads a gathered
        [B, nkv, max_seq, hd] view of each slot's pages (the reference's
        block_multihead_attention memory model; the gather fuses into the
        attention contraction).  On the fused default (``self._fused``,
        docs/paged_attention.md) rope + the page append + split-K
        attention run as ONE Pallas launch per layer instead — dropped
        writes land on the pool's spill page."""
        from .. import inference as _inf
        from ..ops.pallas import rope as rope_mod

        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        B = self.max_batch
        S = self.max_seq
        nkv, hd = cfg.num_key_value_heads, cfg.head_dim
        x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cfg.dtype)
        cos_full, sin_full = rope_mod.rope_cos_sin(S, cfg.head_dim,
                                                   base=cfg.rope_theta,
                                                   dtype=cfg.dtype)
        safe_pos = jnp.where(active & (pos < S), pos, 0)
        cos = jnp.take(cos_full[0], safe_pos, axis=0)[:, None]  # [B, 1, d]
        sin = jnp.take(sin_full[0], safe_pos, axis=0)[:, None]
        kv_pos = jnp.arange(S)[None, None, None, None, :]
        mask = ((kv_pos <= pos[:, None, None, None, None])
                & active[:, None, None, None, None])
        lane = jnp.arange(B)
        writeable = active & (pos < S)
        attend_fn = None
        fused_fn = None

        if table is None:
            def write(ck, k):
                # ck [B, nkv, S, hd]; k [B, 1, nkv, hd] — per-slot scatter at
                # each slot's own depth (drop writes from inactive/oob lanes)
                upd = jnp.where(writeable[:, None, None], k[:, 0],
                                ck[lane, :, safe_pos])
                out = ck.at[lane, :, safe_pos].set(upd)
                return out, out
        elif self.kv_quant is not None:
            # quantized KV pools (docs/paged_attention.md "Megastep
            # stage 2"): pools are {"q": codes, "scale": per-page f32}
            # pytrees.  The kill-switched arm appends via the requant-
            # scatter composition (the scatter pair the fused path
            # eliminates) and attends dequant-on-read through the paged
            # front door (which itself falls back to the quant gather
            # oracle off-TPU-shapes / under =paged_attention); the fused
            # default runs rope + requantized append + attention in ONE
            # launch with codes AND scales committed through aliased
            # outputs.
            from ..ops import decode_attention as _da
            from ..ops.pallas import paged_attention as _pa

            bs_ = self.block_size
            kvq = self.kv_quant
            nh = cfg.num_attention_heads
            blk = table[lane, safe_pos // bs_]                   # [B]
            off = safe_pos % bs_
            seq_now = safe_pos + 1  # incl. the token written this step

            def write(ck, k):
                qp, sc = _pa.quant_append_decode(ck["q"], ck["scale"],
                                                 k[:, 0], blk, off,
                                                 writeable, kvq)
                out = {"q": qp, "scale": sc}
                return out, out

            def attend_fn(q, k_pool, v_pool):
                o = _da.paged_decode_attention(
                    q[:, 0], k_pool["q"], v_pool["q"], table, seq_now,
                    kv_quant=kvq, k_scale=k_pool["scale"],
                    v_scale=v_pool["scale"])
                return o.reshape(B, 1, nh * hd)

            if self._fused:
                spill = jnp.int32(self.num_blocks)
                wblk = jnp.where(writeable, jnp.minimum(blk, spill), spill)
                lens_pre = safe_pos   # append position; inactive lanes 0

                def fused_fn(q, k, v, ck, cv):
                    # q [B, 1, nh, hd] / k, v [B, 1, nkv, hd] PRE-rope
                    o, kq, ksc, vq, vsc = _da.fused_paged_quant_decode_step(
                        q[:, 0], k[:, 0], v[:, 0], cos[:, 0], sin[:, 0],
                        ck["q"], ck["scale"], cv["q"], cv["scale"],
                        table, lens_pre, wblk, writeable, kvq)
                    return (o.reshape(B, 1, nh * hd),
                            {"q": kq, "scale": ksc},
                            {"q": vq, "scale": vsc})
        else:
            from ..ops import decode_attention as _da
            from ..ops.pallas import paged_attention as _pa

            bs_ = self.block_size
            blk = table[lane, safe_pos // bs_]                   # [B]
            off = safe_pos % bs_
            drop_blk = jnp.where(writeable, blk, self.num_blocks)  # oob -> drop
            nh = cfg.num_attention_heads
            # trace-time dispatch: the ragged Pallas kernel walks only each
            # slot's live pages (PADDLE_TPU_DISABLE_PALLAS=paged_attention
            # routes back to the gather oracle below)
            use_kernel = _pa.kernel_supported(nh, nkv, hd, bs_)

            def write(ck, k):
                # ck [num_blocks, nkv, bs, hd].  Allocator invariant:
                # distinct slots own disjoint pages — no scatter collisions.
                out = ck.at[drop_blk, :, off].set(k[:, 0], mode="drop")
                if use_kernel:
                    # attention reads the paged pool directly — no
                    # [B, nkv, S, hd] gather materializes per layer per step
                    return out, out
                # unallocated (sentinel) pages read as ZEROS — jnp.take's
                # default oob mode fills NaN, and 0*NaN through the masked
                # softmax would poison the whole row
                view = jnp.take(out, table, axis=0, mode="fill", fill_value=0)
                view = view.transpose(0, 2, 1, 3, 4).reshape(B, nkv, S, hd)
                return out, view

            if self._fused and use_kernel:
                # decode megastep stage 1: rope + page append + split-K
                # attention in ONE Pallas launch per layer (docs/
                # paged_attention.md "Fused decode step").  Dropped writes
                # (inactive lanes, pos >= max_seq) land on the pool's
                # spill page — the ctor sized the pool with it.
                spill = jnp.int32(self.num_blocks)
                wblk = jnp.where(writeable, jnp.minimum(blk, spill), spill)
                lens_pre = safe_pos   # append position; inactive lanes 0

                def fused_fn(q, k, v, ck, cv):
                    # q [B, 1, nh, hd] / k, v [B, 1, nkv, hd] PRE-rope
                    o, ck, cv = _da.fused_paged_decode_step(
                        q[:, 0], k[:, 0], v[:, 0], cos[:, 0], sin[:, 0],
                        ck, cv, table, lens_pre, wblk, writeable)
                    return o.reshape(B, 1, nh * hd), ck, cv
            elif use_kernel:
                seq_now = safe_pos + 1  # incl. the token written this step

                def attend_fn(q, k_pool, v_pool):
                    # q [B, 1, nh, hd] post-rope; sentinel table entries are
                    # clamped in-kernel and masked by seq_now; inactive
                    # lanes attend one stale position (finite, masked out
                    # downstream like the dense path's garbage lanes)
                    o = _da.paged_decode_attention(q[:, 0], k_pool, v_pool,
                                                   table, seq_now)
                    return o.reshape(B, 1, nh * hd)

        mlp_fused_fn = None
        if table is not None and self._fused_mlp:
            # decode megastep stage 2: the post-attention layer half
            # (residual + post RMSNorm + SwiGLU MLP) as ONE launch per
            # layer through the decoder_layer_tail seam — with it, a
            # decode layer is two Pallas launches separated only by the
            # TP psum boundaries.  PADDLE_TPU_DISABLE_PALLAS=
            # fused_layer_mlp restores the stage-1 program byte-
            # identically (mlp_fused_fn stays None).
            from ..ops.pallas import paged_attention as _pa_mlp

            def mlp_fused_fn(h_res, attn_y, lp):
                # [B, 1, h] <-> [B, h]: the decode step's single live row
                h1, y = _pa_mlp.fused_layer_mlp(
                    h_res[:, 0], attn_y[:, 0], lp["post_norm"],
                    lp["w_gate"], lp["w_up"], lp["w_down"],
                    cfg.rms_norm_eps)
                return h1[:, None], y[:, None]

        x, ak, av = _inf.transformer_apply(cfg, params, x, cache_k, cache_v,
                                           write, mask, cos, sin,
                                           attend_fn=attend_fn,
                                           tp_axis=self._tp_axis,
                                           fused_fn=fused_fn,
                                           mlp_fused_fn=mlp_fused_fn)
        return _inf.lm_head_logits(cfg, params, x[:, -1]), ak, av

    def _quant_rows_write(self, table, row_pos, valid, view=True):
        """write_fn factory for MULTI-row events into quantized KV pools
        (docs/paged_attention.md "Megastep stage 2"): bucketed/prefix
        prefill (``view=True`` — the dense attend reads a dequantized
        gathered view of the slot's pages, batch-1) and the verify/mixed
        steps (``view=False`` — the paged front doors read the raw pool
        pytree).  The append itself is the page-batched requantize
        (ops/pallas/paged_attention.quant_append_rows): only dirty pages
        rewrite, so shared prefix pages keep their exact bytes."""
        from ..ops.pallas import paged_attention as _pa

        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        S = self.max_seq
        nkv, hd = cfg.num_key_value_heads, cfg.head_dim
        kvq = self.kv_quant

        def write(ck, k):
            qp, sc = _pa.quant_append_rows(ck["q"], ck["scale"], k, table,
                                           row_pos, valid, kvq)
            out = {"q": qp, "scale": sc}
            if not view:
                return out, out
            # sentinel pages read as zeros (codes 0 * scale 0), matching
            # the fp path's fill_value=0 gather
            codes = jnp.take(qp, table[0], axis=0, mode="fill",
                             fill_value=0)
            scales = jnp.take(sc, table[0], axis=0, mode="fill",
                              fill_value=0.0)
            v = _pa._dequant_page_content(codes, scales, kvq)
            v = v.transpose(1, 0, 2, 3).reshape(1, nkv, S, hd)
            return out, v.astype(cfg.dtype)

        return write

    def _sample_tokens(self, logits, pos, temp, topp, seeds):
        """Per-slot next-token choice inside the compiled step: greedy where
        temperature == 0, temperature + nucleus (top-p) sampling elsewhere
        (reference: top_p_sampling, ops.yaml:4947).  The RNG key is derived
        deterministically from (slot seed, position): sampling is replayable,
        and a preempted-then-resumed request continues its stream exactly
        (resume teacher-forces the stored tokens, then position-derived keys
        make the continuation draw what it would have drawn)."""
        B = self.max_batch
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = (logits.astype(jnp.float32)
                  / jnp.maximum(temp, 1e-6)[:, None])
        # nucleus mask via sorted cumsum: keep the smallest prefix of
        # descending-prob tokens whose mass reaches top_p (top-1 always kept)
        order = jnp.argsort(-scaled, axis=-1)
        sprob = jax.nn.softmax(jnp.take_along_axis(scaled, order, axis=-1),
                               axis=-1)
        keep_sorted = (jnp.cumsum(sprob, axis=-1) - sprob) < topp[:, None]
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(B)[:, None], order].set(keep_sorted)
        masked = jnp.where(keep, scaled, -jnp.inf)
        keys = jax.vmap(lambda s, p: jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), s), p))(seeds, pos)
        sampled = jax.vmap(jax.random.categorical)(keys, masked)
        return jnp.where(temp > 0.0, sampled.astype(jnp.int32), greedy)

    def _guard_logits(self, logits, active, poison):
        """In-graph NaN/inf logit guard: flag every ACTIVE slot whose logits
        row is non-finite — numerically poisoned by the model, or by the
        ``nan_logits`` fault-injection poison bit — and replace the row with
        zeros so the sampler stays finite (the host discards a flagged
        slot's token and quarantines the request).
        Pure element-wise ops: no callback, no host sync — the flags ride
        back with the step's tokens in the same device fetch.  Inactive
        lanes are excluded: their garbage logits may be legitimately
        non-finite (fully-masked softmax rows).  The poison bit is applied
        FIRST, turning the slot's row genuinely NaN, so injection exercises
        the same finiteness check a real numerical blowup hits — never a
        parallel flag-only path."""
        row = jnp.where(poison, jnp.float32(jnp.nan), jnp.float32(0.0))
        logits = logits + row[:, None].astype(logits.dtype)
        bad = active & ~jnp.isfinite(logits).all(axis=-1)
        return jnp.where(bad[:, None], jnp.zeros_like(logits), logits), bad

    def _chunk_scan(self, params, cache_k, cache_v, tokens, pos, active,
                    temp, topp, seeds, table=None, poison=None,
                    sampling=False):
        """``chunk`` decode steps in one compiled program; the chosen token
        feeds back on-device (no host round-trip inside the chunk).
        ``sampling`` is STATIC: the greedy variant compiles without the
        sampler's full-vocab sort.  The ``poison`` operand feeds the
        in-graph NaN/inf guard, and the per-step guard flags [chunk, B]
        come back with the tokens.  Returns (tokens [chunk, B],
        bad [chunk, B], caches)."""
        if poison is None:
            # direct callers (lint targets, tests) may omit the injection
            # operand; a zeros vector traces the same guarded program
            poison = jnp.zeros_like(active)

        def one(carry, _):
            ck, cv, tok, p = carry
            logits, ck, cv = self._program.decode_one(params, ck, cv, tok, p,
                                                      active, table)
            logits, bad = self._guard_logits(logits, active, poison)
            if sampling:
                nxt = self._sample_tokens(logits, p, temp, topp, seeds)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (ck, cv, nxt, p + 1), (nxt, bad)

        (ck, cv, _, _), (toks, bad) = jax.lax.scan(
            one, (cache_k, cache_v, tokens, pos), None, length=self.chunk)
        return toks, bad, ck, cv

    def _decode_impl(self, params, cache_k, cache_v, tokens, pos, active,
                     temp, topp, seeds, poison=None, sampling=False):
        return self._chunk_scan(params, cache_k, cache_v, tokens, pos, active,
                                temp, topp, seeds, poison=poison,
                                sampling=sampling)

    def _prefill_body(self, params, ids, cache_k, cache_v, length, bucket,
                      write, start=None):
        """Shared prefill: embed/rope/mask once, write-path injected (dense
        lane vs paged block table) so mask/rope fixes cannot diverge.

        Tokens at or beyond ``length`` are padding and masked out of attention
        (they still write cache positions, which the causal mask makes
        unreachable until the slot's pos pointer passes them — it never does,
        decode overwrites).  No logits are computed: the last real prompt
        token is fed to the first decode step instead (standard split).

        ``start`` (traced scalar, prefix-cache hits only): ``ids`` holds
        tokens at ABSOLUTE positions start..start+bucket-1 — rope tables and
        the causal mask shift accordingly, and ``length`` stays the absolute
        total.  ``start=None`` keeps the original program byte-for-byte."""
        from .. import inference as _inf
        from ..ops.pallas import rope as rope_mod

        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        S = self.max_seq
        x = jnp.take(params["embed"], ids, axis=0).astype(cfg.dtype)
        cos_full, sin_full = rope_mod.rope_cos_sin(S, cfg.head_dim,
                                                   base=cfg.rope_theta,
                                                   dtype=cfg.dtype)
        if start is None:
            cos = cos_full[:, :bucket]
            sin = sin_full[:, :bucket]
            q_pos = jnp.arange(bucket)[None, None, None, :, None]
        else:
            pos_j = start + jnp.arange(bucket)      # absolute positions
            safe_j = jnp.minimum(pos_j, S - 1)      # bucket may overrun S
            cos = jnp.take(cos_full[0], safe_j, axis=0)[None]
            sin = jnp.take(sin_full[0], safe_j, axis=0)[None]
            q_pos = pos_j[None, None, None, :, None]
        kv_pos = jnp.arange(S)[None, None, None, None, :]
        mask = (kv_pos <= q_pos) & (kv_pos < length)
        _, ak, av = _inf.transformer_apply(cfg, params, x, cache_k, cache_v,
                                           write, mask, cos, sin,
                                           tp_axis=self._tp_axis)
        return ak, av

    def _prefill_impl(self, params, ids, cache_k, cache_v, slot, length, bucket):
        """Prefill one request (batch 1, prompt padded to ``bucket``) directly
        into lane ``slot`` of the (donated) cache pools."""
        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        S = self.max_seq
        nkv = cfg.num_key_value_heads

        def write(ck, k):
            # ck [B, nkv, S, hd] pool layer; commit this request's K/V
            # into lane `slot` positions [0:bucket], attend on that lane
            out = jax.lax.dynamic_update_slice(
                ck, k.transpose(0, 2, 1, 3), (slot, 0, 0, 0))
            view = jax.lax.dynamic_slice(
                out, (slot, 0, 0, 0), (1, nkv, S, cfg.head_dim))
            return out, view

        return self._prefill_body(params, ids, cache_k, cache_v, length,
                                  bucket, write)

    # ---------------- paged (block-table) compiled programs ----------------

    def _decode_impl_paged(self, params, cache_k, cache_v, tokens, pos, active,
                           temp, topp, seeds, table, poison=None,
                           sampling=False):
        return self._chunk_scan(params, cache_k, cache_v, tokens, pos, active,
                                temp, topp, seeds, table, poison=poison,
                                sampling=sampling)

    def _prefill_impl_paged(self, params, ids, cache_k, cache_v, table_row,
                            length, bucket):
        """Prefill into the slot's pages: prompt position j writes page
        table_row[j // bs] offset j % bs; padding positions whose page is
        the unallocated sentinel drop (and are masked from attention)."""
        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        S = self.max_seq
        bs_ = self.block_size
        nkv, hd = cfg.num_key_value_heads, cfg.head_dim
        j = jnp.arange(bucket)
        blk_j = table_row[j // bs_]                          # [bucket]
        off_j = j % bs_

        if self.kv_quant is not None:
            # mask PAD rows (j >= length), not just oob ones: a requant
            # write is not free like the fp scatter — a garbage pad row
            # in the prompt's tail page would inflate that page's absmax
            # scale and permanently coarsen the REAL rows' codes
            write = self._quant_rows_write(
                table_row[None], j[None, :],
                ((j < length) & (j < S))[None, :])
        else:
            def write(ck, k):
                # k [1, bucket, nkv, hd] -> scatter each prompt position
                # into its page; view = this slot's gathered pages, batch-1
                out = ck.at[blk_j, :, off_j].set(k[0], mode="drop")
                view = jnp.take(out, table_row, axis=0,  # [maxblk,nkv,bs,hd]
                                mode="fill", fill_value=0)  # sentinel -> 0
                view = view.transpose(1, 0, 2, 3).reshape(1, nkv, S, hd)
                return out, view

        return self._prefill_body(params, ids, cache_k, cache_v, length,
                                  bucket, write)

    def _prefill_impl_paged_prefix(self, params, ids, cache_k, cache_v,
                                   table_row, start, length, bucket):
        """Partial-bucket prefill for a prefix-cache hit: ``ids`` [1, bucket]
        holds the prompt's UNCACHED tail — tokens at ABSOLUTE positions
        start..start+bucket-1, padded to ``bucket`` (the only static arg, so
        compile variants stay log2-bounded; start/length are data).  Attention
        reads the full gathered view, whose leading pages are the shared
        cached prefix; writes land only at positions in [start, length), so a
        shared page is never written (COW at admission guarantees the first
        decode position's block is private too).  Embed/rope/mask come from
        the shared ``_prefill_body`` (its ``start`` mode) — only the
        position-offset page scatter lives here."""
        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        S = self.max_seq
        bs_ = self.block_size
        nkv, hd = cfg.num_key_value_heads, cfg.head_dim
        pos_j = start + jnp.arange(bucket)  # absolute positions  [bucket]
        safe_j = jnp.minimum(pos_j, S - 1)
        blk_j = table_row[safe_j // bs_]
        # padding (pos >= length) and anything past max_seq must not write
        blk_j = jnp.where((pos_j < length) & (pos_j < S), blk_j,
                          self.num_blocks)
        off_j = safe_j % bs_

        if self.kv_quant is not None:
            write = self._quant_rows_write(
                table_row[None], pos_j[None, :],
                ((pos_j < length) & (pos_j < S))[None, :])
        else:
            def write(ck, k):
                out = ck.at[blk_j, :, off_j].set(k[0], mode="drop")
                view = jnp.take(out, table_row, axis=0,  # [maxblk,nkv,bs,hd]
                                mode="fill", fill_value=0)
                view = view.transpose(1, 0, 2, 3).reshape(1, nkv, S, hd)
                return out, view

        return self._prefill_body(params, ids, cache_k, cache_v, length,
                                  bucket, write, start=start)

    # ---------------- speculative verify (compiled program) ----------------

    def _verify_one(self, params, cache_k, cache_v, tokens, pos, active,
                    q_lens, table):
        """One multi-token verify forward: tokens [B, Q] (row 0 = the pending
        last token, rows 1.. = n-gram drafts), pos [B] (row 0's write
        position), q_lens [B] live rows per slot -> (logits [B, Q, V],
        caches).  The multi-token analog of ``_decode_one``: every row's K/V
        is scattered into its page at absolute position pos+t (row t of a
        slot with t >= q_lens, an inactive lane, or a position past max_seq
        drops), and attention runs the ragged verify kernel over the paged
        pool — one weight stream from HBM serves up to Q tokens per slot,
        which is the speculative win in bandwidth-bound decode."""
        from .. import inference as _inf
        from ..ops import decode_attention as _da
        from ..ops.pallas import rope as rope_mod

        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        B = self.max_batch
        S = self.max_seq
        Q = tokens.shape[1]
        nh = cfg.num_attention_heads
        bs_ = self.block_size
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        cos_full, sin_full = rope_mod.rope_cos_sin(S, cfg.head_dim,
                                                   base=cfg.rope_theta,
                                                   dtype=cfg.dtype)
        pos_t = pos[:, None] + jnp.arange(Q)[None, :]          # [B, Q] abs
        valid_t = (active[:, None] & (jnp.arange(Q)[None, :] < q_lens[:, None])
                   & (pos_t < S))
        safe_t = jnp.where(valid_t, pos_t, 0)
        cos = jnp.take(cos_full[0], safe_t, axis=0)            # [B, Q, d]
        sin = jnp.take(sin_full[0], safe_t, axis=0)
        lane = jnp.arange(B)[:, None]
        blk = table[lane, safe_t // bs_]                       # [B, Q]
        off = safe_t % bs_
        drop_blk = jnp.where(valid_t, blk, self.num_blocks)    # oob -> drop

        if self.kv_quant is not None:
            write = self._quant_rows_write(table, pos_t, valid_t,
                                           view=False)
        else:
            def write(ck, k):
                # ck [num_blocks, nkv, bs, hd]; k [B, Q, nkv, hd].
                # Allocator invariant: distinct slots own disjoint pages,
                # distinct rows hit distinct positions — no scatter
                # collisions among live writes.
                out = ck.at[drop_blk, :, off].set(k, mode="drop")
                # the verify kernel reads the paged pool directly (no
                # gathered view materializes; its fallback oracle gathers
                # internally)
                return out, out

        # total written length per slot incl. every draft; inactive lanes
        # attend one stale position (finite, masked out downstream like the
        # dense path's garbage lanes)
        seq_base = jnp.where(active & (pos < S), pos, 0)
        seq_now = jnp.minimum(seq_base + jnp.where(active, q_lens, 1), S)

        def attend_fn(q, k_pool, v_pool):
            # q [B, Q, nh, hd] post-rope
            if self.kv_quant is not None:
                # verify is the T = K+1 special case of the chunked-
                # prefill kernel, and ONLY the prefill member carries
                # dequant-on-read (docs/chunked_prefill.md) — quantized
                # verify routes through it rather than growing a fourth
                # kernel variant (identical mask law, same page walk)
                o = _da.paged_prefill_attention(
                    q, k_pool["q"], v_pool["q"], table, seq_now, q_lens,
                    kv_quant=self.kv_quant, k_scale=k_pool["scale"],
                    v_scale=v_pool["scale"])
            else:
                o = _da.paged_verify_attention(q, k_pool, v_pool, table,
                                               seq_now, q_lens)
            return o.reshape(B, Q, nh * cfg.head_dim)

        x, ak, av = _inf.transformer_apply(cfg, params, x, cache_k, cache_v,
                                           write, None, cos, sin,
                                           attend_fn=attend_fn,
                                           tp_axis=self._tp_axis)
        return _inf.lm_head_logits(cfg, params, x), ak, av

    def _verify_impl_paged(self, params, cache_k, cache_v, tokens, pos,
                           active, q_lens, temp, topp, seeds, table,
                           poison=None, sampling=False):
        """Verify + accept in ONE compiled program.  Row t's logits condition
        on draft tokens <= t; the emitted token for position pos+t+1 is drawn
        with the SAME (seed, pos+t)-derived key ``_sample_tokens`` would use
        in the non-speculative step — so row 0's token is always what plain
        decode would have produced, and each draft is accepted exactly when
        it equals that token.  The accepted stream is therefore
        token-identical to the non-speculative engine (greedy AND seeded
        sampled), not merely distribution-preserving.  Returns
        (out [B, Q] chosen tokens per row, n_emitted [B] in 1..q_lens,
        bad [B] guard flags, caches); host code consumes
        out[:, :n_emitted]."""
        logits, ck, cv = self._verify_one(params, cache_k, cache_v, tokens,
                                          pos, active, q_lens, table)
        Q = tokens.shape[1]
        # per-slot guard over the LIVE rows only (rows past q_lens are
        # computed from garbage positions and may be legitimately
        # non-finite); a flagged slot's whole verify output is discarded
        # by the host, so one [B] flag per slot suffices
        if poison is None:
            poison = jnp.zeros_like(active)
        # poison bit FIRST, as a genuinely NaN row (same contract as
        # _guard_logits): injection exercises the finiteness check a
        # real numerical blowup hits — never a parallel flag-only path
        row = jnp.where(poison, jnp.float32(jnp.nan), jnp.float32(0.0))
        logits = logits + row[:, None, None].astype(logits.dtype)
        live = jnp.arange(Q)[None, :] < q_lens[:, None]
        rowbad = (~jnp.isfinite(logits).all(axis=-1)) & live
        bad = active & rowbad.any(axis=-1)
        logits = jnp.where(bad[:, None, None], jnp.zeros_like(logits),
                           logits)
        if sampling:
            pos_t = pos[:, None] + jnp.arange(Q)[None, :]
            out = jax.vmap(
                lambda lg, p: self._sample_tokens(lg, p, temp, topp, seeds),
                in_axes=(1, 1), out_axes=1)(logits, pos_t)
        else:
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # acceptance: draft t+1 survives iff it equals the token the target
        # chose at row t AND every earlier draft survived (leading-run via
        # cumprod); row 0 is always emitted.  t+1 < q_lens bounds n_emitted
        # by the slot's live rows, so padding rows can never count.
        ok = ((tokens[:, 1:] == out[:, :-1])
              & (jnp.arange(1, Q)[None, :] < q_lens[:, None]))
        n_emitted = 1 + jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
        # the guard flags ride back with the step's tokens — no extra
        # device fetch; the host quarantines flagged slots
        return out, n_emitted.astype(jnp.int32), bad, ck, cv

    # -------- unified mixed prefill/decode step (compiled program) --------

    def _mixed_one(self, params, cache_k, cache_v, tokens, pos, active,
                   q_lens, table):
        """One unified prefill/decode forward: tokens [B, T] (row t of slot
        b = the token at absolute position pos[b]+t), pos [B] row-0
        positions, q_lens [B] live rows -> (emit-row logits [B, V], caches).
        Decode-ready slots ride as q_lens == 1 lanes (row 0 = the pending
        token — exactly ``_decode_one``'s computation at their position);
        prefilling slots carry a prefill_chunk-row slice of their prompt.

        Everything row-wise — embedding, norms, q/k/v, rope, ``wo``, the
        residuals and the MLP — runs over the ``P = _mixed_rows`` PACKED
        live rows ([1, P, h]; docs/chunked_prefill.md "Packed rows"), not
        over [B, T]: ``idx`` [P] names the p-th live row (row-major; the
        tail names dead rows, whose results nothing reads) and ``inv``
        [B, T] the packed row of (b, t), or P — past the end, read as
        zeros — where the row is dead.  Both are derived here from
        ``valid_t`` and both are applied as gathers; the host's [B, T]
        staging and its per-slot banking never see them.  Only the [B, T]
        consumers unpack: every live row's K/V scatters into its page and
        attention runs the ragged chunked-prefill kernel (per-row
        visibility pos+t+1 — the verify kernel's causal law with T free)
        over the unpacked q, whose output is gathered back to the packed
        rows.  ONLY each slot's last live row projects through the
        lm_head: a mid-prompt chunk's emit is garbage the host ignores,
        the FINAL chunk's emit row sits at the last prompt token's
        position so its logits ARE the first decode step's (TTFT costs no
        extra launch), and a [B, V] head is T times cheaper than the
        [B, T, V] one the mixed step never needs."""
        from .. import inference as _inf
        from ..ops import decode_attention as _da
        from ..ops.pallas import rope as rope_mod

        cfg = self._body_cfg    # TP: tp-local head counts (else self.cfg)
        B = self.max_batch
        S = self.max_seq
        T = tokens.shape[1]
        P = self._mixed_rows
        nh = cfg.num_attention_heads
        bs_ = self.block_size
        cos_full, sin_full = rope_mod.rope_cos_sin(S, cfg.head_dim,
                                                   base=cfg.rope_theta,
                                                   dtype=cfg.dtype)
        pos_t = pos[:, None] + jnp.arange(T)[None, :]          # [B, T] abs
        valid_t = (active[:, None] & (jnp.arange(T)[None, :] < q_lens[:, None])
                   & (pos_t < S))
        safe_t = jnp.where(valid_t, pos_t, 0)
        lane = jnp.arange(B)[:, None]
        blk = table[lane, safe_t // bs_]                       # [B, T]
        off = safe_t % bs_
        drop_blk = jnp.where(valid_t, blk, self.num_blocks)    # oob -> drop

        # the two row maps: a stable sort brings the live rows first in
        # row-major order; a running count gives each live row its place
        live = valid_t.reshape(B * T)
        idx = jnp.argsort(~live, stable=True)[:P].astype(jnp.int32)
        inv = jnp.where(live, jnp.cumsum(live, dtype=jnp.int32) - 1,
                        P).reshape(B, T)
        x = jnp.take(params["embed"], tokens.reshape(B * T)[idx],
                     axis=0).astype(cfg.dtype)[None]           # [1, P, h]
        safe_p = safe_t.reshape(B * T)[idx]
        cos = jnp.take(cos_full[0], safe_p, axis=0)[None]      # [1, P, d]
        sin = jnp.take(sin_full[0], safe_p, axis=0)[None]

        def unpack(rows):
            # [1, P, ...] packed -> [B, T, ...]; dead rows read as zeros
            return jnp.take(rows[0], inv, axis=0, mode="fill", fill_value=0)

        if self.kv_quant is not None:
            write_bt = self._quant_rows_write(table, pos_t, valid_t,
                                              view=False)
        else:
            def write_bt(ck, k):
                # ck [num_blocks, nkv, bs, hd]; k [B, T, nkv, hd].
                # Allocator invariant: distinct slots own disjoint pages,
                # distinct rows hit distinct positions — no scatter
                # collisions among live writes; the kernel reads the paged
                # pool directly.
                out = ck.at[drop_blk, :, off].set(k, mode="drop")
                return out, out

        def write(ck, k):
            return write_bt(ck, unpack(k))

        # total written length per slot incl. this chunk; inactive lanes
        # attend one stale position (finite, masked out downstream like the
        # dense path's garbage lanes)
        seq_base = jnp.where(active & (pos < S), pos, 0)
        seq_now = jnp.minimum(seq_base + jnp.where(active, q_lens, 1), S)

        def attend_fn(q, k_pool, v_pool):
            # q [1, P, nh, hd] post-rope -> the kernel's [B, T, nh, hd]
            # (its kv_quant mode dequantizes quantized pools on read);
            # its output goes back to the packed rows
            q = unpack(q)
            if self.kv_quant is not None:
                o = _da.paged_prefill_attention(
                    q, k_pool["q"], v_pool["q"], table, seq_now, q_lens,
                    kv_quant=self.kv_quant, k_scale=k_pool["scale"],
                    v_scale=v_pool["scale"])
            else:
                o = _da.paged_prefill_attention(q, k_pool, v_pool, table,
                                                seq_now, q_lens)
            return o.reshape(B * T, nh * cfg.head_dim)[idx][None]

        x, ak, av = _inf.transformer_apply(cfg, params, x, cache_k, cache_v,
                                           write, None, cos, sin,
                                           attend_fn=attend_fn,
                                           tp_axis=self._tp_axis)
        # the emit row: a lane's live rows are a prefix of its T, so its
        # last one is row n_live - 1 (a lane with none is clamped to a row
        # the guard masks by ``active`` and the host never reads)
        n_live = valid_t.sum(axis=1, dtype=jnp.int32)
        emit = jnp.take_along_axis(
            inv, jnp.maximum(n_live - 1, 0)[:, None], axis=1)[:, 0]
        last = x[0][jnp.minimum(emit, P - 1)]                  # [B, h]
        return _inf.lm_head_logits(cfg, params, last), ak, av

    def _mixed_impl_paged(self, params, cache_k, cache_v, tokens, pos,
                          active, q_lens, temp, topp, seeds, table,
                          poison=None, sampling=False):
        """Mixed step + emit in ONE compiled program.  The emitted token for
        slot b is drawn from its emit row's logits with the SAME
        (seed, pos + q_lens - 1)-derived key ``_sample_tokens`` uses in the
        plain decode step at that position — so a decode lane's token
        (q_lens == 1, key (seed, pos)) and a completing prefill's first
        token (emit row at the last prompt token's position, the exact key
        the unchunked engine's first decode step derives) are
        token-identical to the bucketed-prefill engine, greedy AND seeded
        sampled.  Returns (next token [B], bad [B] guard flags, caches);
        the host consumes a lane's token only when it decoded or finished
        its prompt."""
        logits, ck, cv = self._program.mixed_one(
            params, cache_k, cache_v, tokens, pos, active, q_lens, table)
        # the emit row is each slot's ONLY row through the lm_head: a
        # non-finite emit (numerical blowup or the nan_logits poison
        # bit) flags the slot; the host quarantines the request instead
        # of banking garbage.  One [B] flag, fetched with the tokens.
        if poison is None:
            poison = jnp.zeros_like(active)
        logits, bad = self._guard_logits(logits, active, poison)
        if sampling:
            nxt = self._sample_tokens(logits, pos + q_lens - 1, temp, topp,
                                      seeds)
        else:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, bad, ck, cv

    # ---------------- block allocator (host control plane) ----------------

    def _blocks_needed(self, last_pos: int) -> int:
        return min(last_pos, self.max_seq - 1) // self.block_size + 1

    def _alloc_to(self, slot: int, n_blocks: int) -> bool:
        """Grow slot to n_blocks pages (shared cached prefix counts); False if
        the pool runs dry.  Under prefix caching, allocation pressure first
        LRU-evicts zero-ref cached blocks — eviction happens ONLY here, so
        resident hot prefixes are sacrificed last, never proactively."""
        owned = self._slot_blocks[slot]
        base = len(self._slot_shared[slot])
        if (base + len(owned) < n_blocks and self._faults
                and self._faults.fire("alloc_fail", step=self._step_no,
                                      slot=slot)):
            # allocator seam (faults.py): report the pool dry even though
            # pages may be free — drives the overload ladder adversarially
            # without needing a genuinely tiny pool.  Polled only when a
            # real grab would happen, so no-op calls never consume firings.
            self._flight.record("fault", fault="alloc_fail", slot=slot,
                                step=self._step_no)
            return False
        while base + len(owned) < n_blocks:
            if not self._free:
                # with the tier attached, reclaim the WHOLE remaining
                # deficit in one call: eviction demotes D2H, and one
                # batched gather per admission beats a serialized
                # per-page transfer ladder.  Tier-off keeps the one-page
                # pre-PR reclaim so page-assignment order — hence the
                # pool layout — stays byte-identical to the pre-tier
                # engine.
                want = (n_blocks - base - len(owned)
                        if self._tier is not None else 1)
                if not self._reclaim(want):
                    return False
            b = self._free.pop()
            self._table[slot, base + len(owned)] = b
            owned.append(b)
        return True

    def _reclaim(self, n: int) -> int:
        """Evict up to n zero-ref cached blocks into the free list.  With
        the host tier attached (docs/kv_tier.md), eviction DEMOTES instead
        of killing: every victim's page ships D2H under its chain hash
        before the page is recycled, so the chain stays re-admittable —
        the whole point of returning (hash, page) pairs from evict()."""
        if self._pcache is None:
            return 0
        with RecordEvent("prefix_cache/evict"):
            pairs = self._pcache.evict(n)
        if pairs:
            if self._tier is not None:
                self._demote(pairs)
            self._free.extend(page for _, page in pairs)
            self.stats["prefix_evictions"] += len(pairs)
            self._flight.record("evict", pages=len(pairs))
        return len(pairs)

    # -------- hierarchical KV: demote / re-admit (docs/kv_tier.md) --------

    def _demote(self, pairs) -> None:
        """ship_out the evicted pages: ONE gathered device read for the
        whole batch, then per-page host slices into the tier.  np.asarray
        blocks on the D2H, so a later compiled step can never overwrite a
        page mid-demotion — the pages re-enter the free list only after
        their bytes are safe on the host.  A page the tier cannot fit
        (budget exhausted by pinned entries) goes dead, exactly the
        pre-tier eviction, counted by the tier's ``drops``."""
        with RecordEvent("kv_tier/demote"):
            idx = jnp.asarray([page for _, page in pairs], jnp.int32)
            owner = self._obs_labels.get("replica")
            if self.kv_quant is not None:
                # quantized pools demote codes + per-page scales together
                # (the tier's transport has carried scales since PR 12 —
                # byte-exact roundtrip asserted there)
                k_slab = np.asarray(self.cache_k["q"][:, idx])
                v_slab = np.asarray(self.cache_v["q"][:, idx])
                ks_slab = np.asarray(self.cache_k["scale"][:, idx])
                vs_slab = np.asarray(self.cache_v["scale"][:, idx])
                for i, (h, _page) in enumerate(pairs):
                    if self._tier.ship_out(h, k_slab[:, i], v_slab[:, i],
                                           k_scale=ks_slab[:, i],
                                           v_scale=vs_slab[:, i],
                                           owner=owner) is not None:
                        self.stats["tier_demotions"] += 1
            else:
                k_slab = np.asarray(self.cache_k[:, idx])
                v_slab = np.asarray(self.cache_v[:, idx])
                for i, (h, _page) in enumerate(pairs):
                    if self._tier.ship_out(h, k_slab[:, i], v_slab[:, i],
                                           owner=owner) is not None:
                        self.stats["tier_demotions"] += 1
        self.stats["tier_bytes"] = self._tier.used_bytes
        self.stats["tier_evictions"] = self._tier.evictions
        self._flight.record("tier_demote", pages=len(pairs),
                            tier_bytes=int(self._tier.used_bytes))

    def _restore_tier_block(self, slot: int, req, ids, b: int, h: str,
                            parent: str | None) -> bool:
        """Re-admit ONE demoted block: allocate a free page, dispatch the
        async H2D pool writes (ship_in's device half), and register the
        block into the prefix cache with this slot holding a reference —
        from here on it is indistinguishable from a freshly-prefilled
        shared block.  False when the restore cannot proceed (pool dry,
        tier miss / injected ``tier_drop``, private pages ahead of the
        shared front): the caller falls back to ordinary prefill compute
        for the block — token-identical either way, the tier only ever
        changes who produces the bytes, never which bytes."""
        bs_ = self.block_size
        if self._faults and self._faults.fire("tier_drop",
                                              step=self._step_no,
                                              slot=slot, rid=req.rid):
            # chaos seam (faults.py): the entry vanishes between match
            # and ship_in — the engine must fall back to normal prefill,
            # never hang or corrupt
            self._tier.discard(h)
            self._flight.record("fault", fault="tier_drop", slot=slot,
                                step=self._step_no)
        if h in self._pcache._by_hash:
            # another slot restored or computed the same chain block since
            # this plan was made: map the HBM-resident copy instead (a
            # late HBM hit — strictly cheaper than the H2D)
            e = self._pcache._by_hash[h]
            self._pcache.acquire(e)
            self._table[slot, len(self._slot_shared[slot])] = e.page
            self._slot_shared[slot].append(h)
            return True
        if not self._free or self._slot_blocks[slot]:
            # pool pressure, or unregistered private pages ahead of the
            # shared front (a cache_error degradation left them there —
            # appending shared past them would break the [shared...,
            # private...] row layout): compute instead
            return False
        entry = self._tier.ship_in(h,
                                   owner=self._obs_labels.get("replica"))
        if entry is None:
            return False        # dropped or LRU-evicted: compute instead
        # storage-format guard (docs/paged_attention.md "Megastep
        # stage 2"): tier entries are keyed by token-chain hash alone, so
        # a SHARED fleet tier can hold pages demoted by a replica with a
        # different pool storage (fp vs int8 vs packed int4 — scales
        # present/absent, hd vs hd//2 payload, bf16 vs int8 dtype).
        # Restoring one would silently corrupt this engine's pool (the
        # donated page write casts); treat a mismatched entry as a miss
        # and compute the block instead — on a shared tier the entry
        # stays for compatible replicas
        pool = self.cache_k["q"] if self.kv_quant is not None \
            else self.cache_k
        page_shape = (pool.shape[0],) + pool.shape[2:]
        if ((entry.k_scale is not None) != (self.kv_quant is not None)
                or entry.k.shape != page_shape
                or entry.k.dtype != np.dtype(pool.dtype)):
            return False
        dst = self._free.pop()
        t0 = time.perf_counter()
        with RecordEvent("kv_tier/restore"):
            d = jnp.asarray(dst, jnp.int32)
            if self.kv_quant is not None:
                k_page = {"q": jnp.asarray(entry.k),
                          "scale": jnp.asarray(entry.k_scale)}
                v_page = {"q": jnp.asarray(entry.v),
                          "scale": jnp.asarray(entry.v_scale)}
            else:
                k_page, v_page = jnp.asarray(entry.k), jnp.asarray(entry.v)
            self.cache_k = self._tier_write(self.cache_k, d, k_page)
            self.cache_v = self._tier_write(self.cache_v, d, v_page)
        e = self._pcache.register(parent, ids[b * bs_:(b + 1) * bs_], dst,
                                  refcount=1)
        if e is None:
            # defensive: the parent left the index between plan and
            # restore — the page would be unreachable by radix descent;
            # hand it back and compute the block instead
            self._free.append(dst)
            return False
        self._table[slot, len(self._slot_shared[slot])] = dst
        self._slot_shared[slot].append(h)
        self.stats["tier_readmits"] += 1
        self.stats["tier_bytes"] = self._tier.used_bytes
        self._h_h2d.observe(time.perf_counter() - t0)
        self._flight.record("tier_readmit", rid=req.rid, slot=slot,
                            block=b, page=dst)
        return True

    def _tier_restore_step(self, s: int, ids,
                           budget: int) -> tuple[int, int, bool]:
        """Advance slot ``s``'s prefill cursor through its pending
        tier-restore plan (the chunked path's ship_in driver): plan blocks
        the cursor already passed (computed by a fallback chunk) drop;
        while the cursor sits exactly at a planned block's boundary,
        restore it by H2D page copy and advance the cursor a whole block.
        "Restoring from host" is thereby scheduled exactly like
        "prefilling" — one cursor, zero new compiled step shapes,
        chunk-granular preemption/cancel compose for free, AND restores
        are paced by the step's token budget exactly like prefill rows
        (each restored block bills ``block_size`` tokens, with a
        one-block-per-step floor so plans always drain — a long demoted
        chain must not burst hundreds of H2D uploads into one step and
        recreate the decode stall chunked prefill exists to erase).  The
        H2D dispatch is async: donation order guarantees this step's
        mixed launch reads the restored pages, while the bytes stream in
        parallel with the host's packing work.  Returns ``(cursor,
        remaining budget, pending)`` — ``pending`` means a planned block
        still sits AT the cursor (deferred by the budget), so the caller
        must idle the lane this step instead of computing the block a
        later step will restore."""
        bs_ = self.block_size
        req = self._slot_req[s]
        plan = self._tier_plan[s]
        cur = int(self._prefilled[s])
        restored = 0
        while plan:
            b, h, _parent = plan[0]
            if b * bs_ < cur:
                plan.pop(0)                 # computed by a fallback chunk
                self._tier.unpin(h)
                continue
            if b * bs_ != cur:
                break                       # mid-block cursor: compute on
            if restored > 0 and budget < bs_:
                # budget drained: defer the rest of the plan to the next
                # step (the floor above already banked one block, so the
                # plan strictly drains — no livelock on a tiny budget)
                return cur, budget, True
            if not self._restore_tier_block(s, req, ids, b, h, _parent):
                # pool dry this step, or the entry vanished (tier_drop /
                # LRU): drop the WHOLE plan and fall back to prefill
                # compute — token-identical, never a hang
                self._drop_tier_plan(s)
                break
            plan.pop(0)
            self._tier.unpin(h)
            restored += 1
            budget = max(budget - bs_, 0)
            cur += bs_
            self._prefilled[s] = cur
            self._pos[s] = cur
            self._written[s] = max(int(self._written[s]), cur)
            # admission pre-counted the whole uncovered tail as computed
            # (it could not know which blocks the cursor would restore):
            # move this block's tokens to the cached column so the
            # prefill hit-rate reads what actually happened
            self.stats["prefill_tokens_computed"] -= bs_
            self.stats["prefill_tokens_cached"] += bs_
        return cur, budget, False

    def _drop_tier_plan(self, slot: int) -> None:
        """Invalidate a slot's pending tier-restore plan (preempt, cancel,
        terminal, restore fallback): unpin every remaining entry so the
        tier's LRU may reclaim them.  The cursor keeps whatever progress
        restores already banked — the blocks it covered are ordinary
        shared cache blocks now."""
        if self._tier is None:
            return
        for _b, h, _p in self._tier_plan[slot]:
            self._tier.unpin(h)
        self._tier_plan[slot] = []

    def _evictable(self) -> int:
        return self._pcache.evictable_count() if self._pcache is not None else 0

    def _release(self, slot: int):
        self._drop_tier_plan(slot)  # no-op tier-off / plan already drained
        self._free.extend(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        if self._slot_shared[slot]:
            # shared pages are refcounted, not freed: at zero refs they stay
            # resident in the cache until eviction needs them
            for h in self._slot_shared[slot]:
                self._pcache.release(h)
            self._slot_shared[slot] = []
        self._table[slot, :] = self.num_blocks

    def _register_prefix_blocks(self, slot: int, ids: np.ndarray,
                                valid_len: int):
        """After an admission's prefill: move the newly-computed full prompt
        blocks (beyond the matched shared prefix) into the cache with this
        slot holding a reference — a request admitted later in the SAME step
        already hits.  Transfers are a contiguous front of the private list,
        preserving the [shared..., private...] row layout."""
        bs_ = self.block_size
        n_shared = len(self._slot_shared[slot])
        limit = valid_len // bs_            # blocks fully written by prefill
        if limit <= n_shared:
            return
        if self._faults and self._faults.fire("cache_error",
                                              step=self._step_no, slot=slot):
            # prefix-cache seam (faults.py): a registration fault degrades
            # — the blocks stay private (a future request misses where it
            # could have hit) and NO request fails
            return
        # continue the chain from the mapped shared prefix — each new block
        # is hashed exactly once (inside register), nothing is re-hashed
        parent = self._slot_shared[slot][-1] if n_shared else None
        for b in range(n_shared, limit):
            e = self._pcache.register(parent, ids[b * bs_:(b + 1) * bs_],
                                      self._slot_blocks[slot][0], refcount=1)
            if e is None:
                # defensive only: in the single-threaded admit flow nothing
                # can insert between match() and here, and leaf-first
                # eviction can't orphan a parent mid-chain — but if either
                # invariant ever breaks, keeping the page private (freed by
                # _release) is the safe degradation
                break
            if self._tier is not None and not self._tier.shared:
                # a freshly-computed block whose demoted twin still sits
                # in a PRIVATE tier: drop the stale host copy — demote/
                # re-admit is move semantics there (I10's exactly-one
                # home; a shared tier keeps it for the other replicas)
                self._tier.discard(e.hash)
            parent = e.hash
            self._slot_blocks[slot].pop(0)
            self._slot_shared[slot].append(e.hash)

    def _register_retired_blocks(self, slot: int):
        """Before releasing a finishing/preempted slot: donate its full,
        content-known private blocks to the cache as zero-ref residents, so
        the prefix (prompt AND generated tokens — the preempt-resume path
        re-admits exactly this stream) survives for future requests.
        Positions are trusted only up to min(pos, len(prompt+output),
        max_seq): chunk-tail writes past the delivered tokens hold post-EOS
        garbage and must never be content-addressed."""
        if self._pcache is None:
            return
        req = self._slot_req[slot]
        seq = np.concatenate([np.asarray(req.prompt_ids, np.int32).ravel(),
                              np.asarray(req.output_ids, np.int32)])
        trusted = min(int(self._pos[slot]), seq.size, self.max_seq)
        bs_ = self.block_size
        n_shared = len(self._slot_shared[slot])
        limit = trusted // bs_
        if limit <= n_shared:
            return
        # the slot's shared prefix IS the chain over seq's first n_shared
        # blocks — continue from its tip instead of re-hashing the prefix
        parent = self._slot_shared[slot][-1] if n_shared else None
        keep: list[int] = []
        for i, page in enumerate(self._slot_blocks[slot]):
            b = n_shared + i
            if b < limit:
                tokens = seq[b * bs_:(b + 1) * bs_]
                e = self._pcache.register(parent, tokens, page, refcount=0)
                if e is not None:
                    if self._tier is not None and not self._tier.shared:
                        # same private-tier dedup as _register_prefix_blocks
                        self._tier.discard(e.hash)
                    parent = e.hash
                    continue               # ownership moved to the cache
                # duplicate content (identical stream retired earlier): the
                # page stays private, but later blocks still chain through
                # the EXISTING entry's id
                parent = self._pcache.chain_hash(parent, tokens)
            keep.append(page)              # partial tail / duplicate content
        self._slot_blocks[slot] = keep

    def _preempt(self, slot: int):
        """vLLM-style recompute preemption: free the slot, requeue the
        request with prompt + generated-so-far.  Sampling-safe: resume
        teacher-forces the STORED sampled tokens (no re-decode of history),
        and the continuation's RNG keys derive from (seed, position), so the
        stream picks up exactly where it left off."""
        req = self._slot_req[slot]
        ids = np.concatenate([np.asarray(req.prompt_ids, np.int32).ravel(),
                              np.asarray(req.output_ids, np.int32)])
        req._resume_ids = ids
        # keep seniority across the round trip: a resumed request must not
        # become the youngest slot and the repeat victim (preemption thrash)
        req._resume_age = int(self._slot_age[slot])
        # donate the computed prefix to the cache first: the resume re-admits
        # prompt+generated, so its prefill restarts at the first uncached
        # token instead of recomputing the whole stream
        self._register_retired_blocks(slot)
        self._release(slot)
        self._slot_req[slot] = None
        self._written[slot] = 0
        self._temp[slot] = 0.0  # re-set on readmission
        if self._chunked:
            # a mid-prefill victim resumes as a fresh admission: the donated
            # full blocks above make its re-prefill restart at the first
            # uncached token, not the prompt's head
            self._prefill_ids[slot] = None
            self._prefilled[slot] = 0
        req.status = "PENDING"   # back in the queue; re-seated by _admit
        self._queue.insert(0, req)
        self._jmark(req.rid)
        self.stats["preemptions"] += 1
        self._flight.record("degrade", rung=4, what="preempt",
                            rid=req.rid, slot=slot)
        # every preemption is pool-pressure-driven, so it IS ladder rung 4
        # (rungs 1-3 already ran and left a deficit)
        self.stats["degrade_preempt"] += 1

    def _ensure_growth(self, k):
        """Before a decode chunk: every active slot needs pages covering
        positions up to pos+k-1 (``k`` may be a per-slot vector — the
        speculative verify step appends q_lens tokens to each slot, so a
        non-drafting slot must not be forced to allocate the drafting
        slots' pages).  Oldest slots win; when the pool is dry the youngest
        active slot is preempted and its pages recycled."""
        karr = np.broadcast_to(np.asarray(k, np.int64), (self.max_batch,))
        order = sorted((s for s in range(self.max_batch)
                        if self._slot_req[s] is not None),
                       key=lambda s: self._slot_age[s])
        for slot in order:
            if self._slot_req[slot] is None:
                continue  # preempted by an older slot this pass
            need = self._blocks_needed(int(self._pos[slot])
                                       + int(karr[slot]) - 1)
            while not self._alloc_to(slot, need):
                victims = [s for s in range(self.max_batch)
                           if s != slot and self._slot_req[s] is not None]
                if not victims:
                    req = self._slot_req[slot]
                    have = (len(self._slot_shared[slot])
                            + len(self._slot_blocks[slot]))
                    pinned = (self._pcache.resident_blocks()
                              - self._pcache.evictable_count()
                              if self._pcache is not None else 0)
                    msg = (f"KV block pool exhausted by a single request: "
                           f"rid={req.rid} needs {need} block(s) to cover "
                           f"position {int(self._pos[slot]) + int(karr[slot]) - 1} "
                           f"({have} mapped, {len(self._free)} free, "
                           f"{self._evictable()} evictable cached, {pinned} "
                           f"pinned cached, {self.num_blocks} total); "
                           f"increase num_blocks")
                    # ladder rung 5 (docs/fault_tolerance.md): eviction,
                    # degradation and preemption are all exhausted — fail
                    # ONLY the unsatisfiable request.  Its pages free
                    # immediately; survivors never see the fault.
                    self._fail_slot(slot, "FAILED", msg, donate=True)
                    break
                self._preempt(max(victims, key=lambda s: self._slot_age[s]))

    # ---------------- scheduler ----------------

    def _validate(self, req: Request):
        ids = np.asarray(req.prompt_ids, np.int32).ravel()
        if ids.size == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if ids.size > self.max_seq - 1:
            raise ValueError(
                f"request {req.rid}: prompt length {ids.size} exceeds "
                f"max_seq-1 = {self.max_seq - 1}")
        temp = req.temperature if req.temperature is not None else 0.0
        # math.isfinite, not just `< 0`: temperature=NaN satisfies neither
        # comparison and would sail into the compiled sampler as a per-slot
        # DATA value, poisoning that slot's logits scaling
        if not math.isfinite(temp) or temp < 0:
            raise ValueError(f"request {req.rid}: temperature must be "
                             f"finite and >= 0, got {temp!r}")
        topp = req.top_p if req.top_p is not None else 1.0
        if not (math.isfinite(topp) and 0 < topp <= 1):
            raise ValueError(f"request {req.rid}: top_p must be finite and "
                             f"in (0, 1], got {topp!r}")
        if (req.deadline_s is not None
                and not (math.isfinite(req.deadline_s)
                         and req.deadline_s >= 0)):
            raise ValueError(f"request {req.rid}: deadline_s must be finite "
                             f"and >= 0, got {req.deadline_s!r}")

    def add_request(self, req: Request):
        self._validate(req)
        # normalize to a host int32 array at acceptance: journal_entry
        # re-runs np.asarray on prompt_ids inside the _host_overlap()
        # window, and a device-array prompt would turn that into a blocking
        # transfer mid-pipeline (host_blocking, analysis/host_contracts.py)
        req.prompt_ids = np.asarray(req.prompt_ids, np.int32).ravel()
        req._submit_s = time.perf_counter()  # TTFT epoch
        if req.trace_id is None:
            req.trace_id = f"req-{req.rid:x}"
        self.slo.begin(req.rid, req._submit_s)
        self._reqs[req.rid] = req
        if (self.max_queue is not None
                and len(self._queue) >= self.max_queue):
            # bounded-queue backpressure: shedding load at admission keeps
            # the accepted requests' SLOs intact (preemption re-inserts
            # bypass add_request — accepted work is never rejected)
            msg = (f"queue full ({len(self._queue)} waiting, "
                   f"max_queue={self.max_queue})")
            with RecordEvent("serving/rejected"):
                self._terminal(req, "REJECTED", msg)
            return
        self._queue.append(req)
        self._jmark(req.rid)

    def _admit(self):
        """Fill free slots from the queue (prefill path).  Paged mode admits
        by free-page count: a request enters only when its prompt's pages
        are allocatable — the block-table analog of "is a lane free"."""
        for slot in range(self.max_batch):
            if self._slot_req[slot] is not None or not self._queue:
                continue
            req = self._queue[0]
            # a preempted request resumes with prompt + generated-so-far
            ids = getattr(req, "_resume_ids", None)
            if ids is None:
                ids = np.asarray(req.prompt_ids, np.int32).ravel()
            s0 = ids.size
            start = 0            # first token whose K/V must be computed
            if self.paged:
                # admit only if the prompt's pages fit AND the active slots'
                # imminent growth (next chunk — or the verify step's K+1
                # appends when speculation is on) keeps its headroom —
                # otherwise a fresh admit would be preempted by
                # _ensure_growth in the same step, wasting its full-prompt
                # prefill.  Spec-off: horizon == chunk, byte-identical.
                horizon = max(self.chunk, self._spec_qmax)
                # per-slot clamp at 0: a mid-prefill slot already owns its
                # whole prompt's pages while pos (the chunk cursor) trails
                # them — surplus must not offset other slots' real growth.
                # (No-op chunked-off: a decode slot never owns pages beyond
                # its growth horizon.)
                headroom = sum(
                    max(0, self._blocks_needed(int(self._pos[s]) + horizon
                                               - 1)
                        - len(self._slot_shared[s])
                        - len(self._slot_blocks[s]))
                    for s in range(self.max_batch)
                    if self._slot_req[s] is not None)
                need = self._blocks_needed(s0 - 1)
                # gate on the new slot's own first-chunk growth too, or
                # _ensure_growth would preempt someone in this same step
                gate = self._blocks_needed(s0 - 2 + horizon)
                # prefix-cache lookup: map the longest cached chain of full
                # blocks into this row read-only.  Acquire BEFORE any
                # allocation — a pinned (refcount > 0) block is unevictable,
                # so _alloc_to's pressure eviction cannot steal the match.
                matched = (self._pcache.match(ids)
                           if self._pcache is not None else [])
                m = len(matched)
                # a fully-matched block-aligned prompt would put the first
                # decode write (position s0-1) inside the last matched block:
                # COW — copy that page into a private one instead of sharing
                # (the engine NEVER writes a shared page)
                cow = m > 0 and m * self.block_size > s0 - 1
                n_map = m - 1 if cow else m
                for e in matched:       # pin all, incl. the COW source
                    self._pcache.acquire(e)
                for i, e in enumerate(matched[:n_map]):
                    self._table[slot, i] = e.page
                    self._slot_shared[slot].append(e.hash)
                # hierarchical KV (docs/kv_tier.md): extend the prefix
                # match THROUGH the host tier.  Walk the chain past the
                # HBM-resident blocks — every hash the tier holds is a
                # block this admission re-admits by H2D copy instead of
                # prefill compute.  The walk stops strictly below the
                # first decode write position (s0-1): a restored block
                # the decode step would write into would need COW, so
                # skipping it costs at most one block of prefill and
                # keeps the restore path write-free; COW admissions
                # (full HBM match) have no tail to extend.
                tier_plan: list[tuple[int, str, str | None]] = []
                if self._tier is not None and not cow:
                    parent = matched[-1].hash if m else None
                    b = m
                    bs_t = self.block_size
                    while (b + 1) * bs_t <= s0 - 1:
                        h = self._pcache.chain_hash(
                            parent, ids[b * bs_t:(b + 1) * bs_t])
                        if h not in self._tier:
                            break
                        tier_plan.append((b, h, parent))
                        parent = h
                        b += 1
                    if tier_plan:
                        self.stats["tier_hits"] += 1
                        for _b, h, _p in tier_plan:
                            # pinned until restored or dropped: the
                            # tier's LRU must not reclaim a matched
                            # entry mid-plan (the chunked cursor spans
                            # steps between match and restore)
                            self._tier.pin(h)
                        self._flight.record("tier_match", rid=req.rid,
                                            blocks=len(tier_plan))
                n_restored = 0
                if tier_plan and not self._chunked:
                    # bucketed engines restore at admission: each block
                    # takes a free page and registers into the prefix
                    # cache exactly like a freshly-prefilled block, then
                    # the bucketed prefill begins past the restored
                    # coverage.  A mid-walk failure (pool dry, tier_drop)
                    # falls back to prefill for the remainder — never a
                    # hang.
                    for b, h, parent in tier_plan:
                        if not self._restore_tier_block(slot, req, ids, b,
                                                        h, parent):
                            break
                        n_restored += 1
                    for _b, h, _p in tier_plan:
                        self._tier.unpin(h)
                    tier_plan = []
                if self._chunked:
                    # chunk-granular allocation (docs/fault_tolerance.md):
                    # a streaming prompt owns pages only as its cursor
                    # advances — _mixed_step's _ensure_growth allocates
                    # each chunk's pages, so ladder rung 3 can relieve
                    # pool pressure by shrinking the chunk instead of
                    # preempting.  Only the COW duplicate must exist at
                    # admission (its content is copied here).  Admission
                    # still gates on full-prompt fit (avail check below).
                    need = m if cow else n_map
                avail = len(self._free) + self._evictable()
                if (avail < gate - (n_map + n_restored) + headroom
                        or not self._alloc_to(slot, need)):
                    # roll back refs + any partial allocation on this EMPTY
                    # slot — stranded pages/refs are invisible to every
                    # release path.  Restored tier blocks stay resident in
                    # the HBM cache zero-ref (a retry hits them there);
                    # a chunked plan's pins release so the tier's LRU may
                    # reclaim the unconsumed entries.
                    if cow:
                        self._pcache.release(matched[-1].hash)
                    for _b, h, _p in tier_plan:
                        self._tier.unpin(h)
                    self._release(slot)
                    break  # pool dry: keep queue order, retry next step
                if cow:
                    # private duplicate of the matched block decode will write
                    src = matched[-1]
                    dst = self._slot_blocks[slot][0]   # row index m-1
                    with RecordEvent("prefix_cache/cow_copy"):
                        d = jnp.asarray(dst, jnp.int32)
                        s_ = jnp.asarray(src.page, jnp.int32)
                        self.cache_k = self._copy_page(self.cache_k, d, s_)
                        self.cache_v = self._copy_page(self.cache_v, d, s_)
                    self._pcache.release(src.hash)  # content copied: unpin
                    self.stats["cow_copies"] += 1
                if m:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_blocks_reused"] += m
                # cached positions: all of a shared/COW/tier-restored
                # block's K/V is already in the pool — prefill starts at
                # the first uncached token (never past s0-1, decode's
                # first position)
                start = min((m + n_restored) * self.block_size, s0 - 1)
                age = getattr(req, "_resume_age", None)
                self._slot_age[slot] = self._admit_seq if age is None else age
                self._admit_seq += 1
            self._queue.pop(0)
            if hasattr(req, "_resume_ids"):
                del req._resume_ids
            if hasattr(req, "_resume_age"):
                del req._resume_age
            plen = (s0 - 1) - start
            self.stats["prefill_tokens_cached"] += start
            self.stats["prefill_tokens_computed"] += max(plen, 0)
            # a whole-prompt prefill dispatched while other slots hold
            # requests stalls their decode for the full prompt length — the
            # TBT spike chunked prefill erases (the chunked path below never
            # ticks this: prompts stream through the mixed step instead)
            stalls = any(r is not None for r in self._slot_req)
            if self._chunked:
                # enqueue-without-prefill: the mixed step streams positions
                # [start, s0) in prefill_chunk rows; the final row (the last
                # prompt token, position s0-1) emits the first generated
                # token, so admission costs no device step here and decode
                # slots never wait on a prompt.  Same-pass identical-prefix
                # bursts each stream independently — a still-streaming
                # slot's pages are private/writable until its chunk
                # registers them, so they cannot be shared in flight
                # (docs/chunked_prefill.md "deliberate tradeoff")
                self._prefill_ids[slot] = ids
                self._prefilled[slot] = start
                if self._tier is not None:
                    # the match-to-restore plan: the mixed step's cursor
                    # consumes it one block per boundary crossing
                    # (_tier_restore_step), so "restore from host" and
                    # "prefill" share one scheduler
                    self._tier_plan[slot] = tier_plan
            elif start == 0:
                bucket = min(_bucket(s0), self.max_seq)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :s0] = ids
                # the last real token is fed to decode, not prefill, so its
                # logits come from the decode step (standard split)
                slot_arg = (jnp.asarray(self._table[slot]) if self.paged
                            else jnp.asarray(slot, jnp.int32))
                t_pf = time.perf_counter()
                self.cache_k, self.cache_v = self._prefill(
                    self.params, jnp.asarray(padded), self.cache_k,
                    self.cache_v, slot_arg, jnp.asarray(s0 - 1, jnp.int32),
                    bucket)
                self.stats["prefills"] += 1
                self.stats["prefill_rows_packed"] += plen
                self.stats["decode_stall_steps"] += int(stalls)
                self._tracer.span(req.rid, "prefill", t_pf,
                                  time.perf_counter(),
                                  args={"bucket": bucket, "tokens": s0 - 1})
            elif plen > 0:
                # partial-bucket prefill over the uncached tail only
                t_pf = time.perf_counter()
                with RecordEvent("prefix_cache/partial_prefill"):
                    bucket = min(_bucket(plen), self.max_seq)
                    padded = np.zeros((1, bucket), np.int32)
                    padded[0, :plen] = ids[start:s0 - 1]
                    self.cache_k, self.cache_v = self._prefill_prefix(
                        self.params, jnp.asarray(padded), self.cache_k,
                        self.cache_v, jnp.asarray(self._table[slot]),
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(s0 - 1, jnp.int32), bucket)
                self.stats["prefills"] += 1
                self.stats["prefill_rows_packed"] += plen
                self.stats["decode_stall_steps"] += int(stalls)
                self._tracer.span(req.rid, "prefill", t_pf,
                                  time.perf_counter(),
                                  args={"bucket": bucket, "tokens": plen,
                                        "cached": start})
            # else: full hit — nothing to compute, decode starts immediately
            if self.paged and self._pcache is not None and not self._chunked:
                # share this admission's freshly-computed full prompt blocks
                # (the chunked path registers as each chunk completes them)
                self._register_prefix_blocks(slot, ids, s0 - 1)
            self._slot_req[slot] = req
            req.status = "RUNNING"
            # lifecycle observability: queue-wait span closes at seating
            # (docs/observability.md — the decode span opens here too)
            now = time.perf_counter()
            req._admit_s = now
            self.slo.admitted(req.rid, now)
            self._tracer.span(req.rid, "queued",
                              getattr(req, "_submit_s", now), now,
                              args={"rid": req.rid, "slot": slot,
                                    "cached_tokens": int(start)})
            self._flight.record("admit", rid=req.rid, slot=slot,
                                prompt=int(s0),
                                cached_tokens=int(start))
            if self._chunked:
                # the prefill cursor IS the position state: pos/_written
                # advance with each chunk, so preemption's trusted-content
                # bound and the auditor's I6 read the same fields they do
                # for decode (cached positions below ``start`` count as
                # written — the pool already holds their K/V)
                self._pos[slot] = start
                self._written[slot] = start
            else:
                self._pos[slot] = s0 - 1
                # prefill committed (or the cache already held) K/V for
                # every position below s0-1; position s0-1 itself is
                # decode's first write
                self._written[slot] = s0 - 1
            self._last_tok[slot] = ids[-1]
            self._temp[slot] = max(float(req.temperature or 0.0), 0.0)
            self._topp[slot] = float(req.top_p if req.top_p is not None
                                     else 1.0)
            # default seed: the request id, so two concurrent sampled
            # requests never share a stream
            self._seed[slot] = np.int32(
                req.seed if req.seed is not None else req.rid)
            self._jmark(req.rid)   # seating sets the journal's cursor

    def _retire(self, slot):
        self._terminal(self._slot_req[slot], "FINISHED")
        if self.paged:
            self._register_retired_blocks(slot)  # needs the request's tokens
        self._slot_req[slot] = None
        self._written[slot] = 0
        self._temp[slot] = 0.0  # freed slot must not pin the sampling variant
        if self._chunked:
            self._prefill_ids[slot] = None
            self._prefilled[slot] = 0
        if self.paged:
            self._release(slot)

    # ---------------- fault tolerance (docs/fault_tolerance.md) ------------

    def _terminal(self, req: Request, status: str, error: str | None = None):
        """Move a request to its terminal status (status lifecycle:
        PENDING -> RUNNING -> terminal, exactly one terminal transition).
        ``finished`` stays the caller-facing "no more tokens coming" flag
        for every terminal status; ``status`` says why."""
        req.status = status
        req.finished = True
        if error is not None:
            req.error = error
        stat = _STATUS_STAT.get(status)
        if stat is not None:
            self.stats[stat] += 1
        # the journal only tracks LIVE requests: a terminal entry would
        # leak one Request per rid forever in a long-lived engine (the
        # caller keeps its own reference; cancel() on a terminal rid
        # correctly reports False via the journal miss)
        self._reqs.pop(req.rid, None)
        self._jdrop(req.rid)
        # lifecycle observability: close the SLO record, emit the decode
        # span (admission -> terminal) + terminal marker, and — for a
        # FAILED request — dump the flight recorder so triage reads the
        # engine's last seconds instead of rerunning the chaos
        now = time.perf_counter()
        self.slo.finish(req.rid, status, now)
        t_admit = getattr(req, "_admit_s", None)
        if t_admit is not None:
            self._tracer.span(req.rid, "decode", t_admit, now,
                              args={"tokens": len(req.output_ids),
                                    "status": status})
        self._tracer.instant(
            req.rid, f"terminal:{status}", now,
            args={"rid": req.rid, **({"error": error} if error else {})})
        self._flight.record("terminal", rid=req.rid, status=status,
                            tokens=len(req.output_ids),
                            **({"error": error} if error else {}))
        if status == "FAILED":
            self._flight.dump(f"request_failed rid={req.rid}")

    def _fail_slot(self, slot: int, status: str, error: str,
                   donate: bool = False):
        """Terminate the request seated on ``slot`` with a non-FINISHED
        terminal status, releasing every page and cache ref it owns (the
        auditor's I8).  ``donate=True`` (cancel / expiry / overload — the
        slot's K/V content is trusted) content-addresses full blocks into
        the prefix cache first, exactly like retirement; ``donate=False``
        (NaN quarantine and other fault paths) drops the pages without
        registering them — a fault step's K/V writes must never be served
        to a future request.  Partial output already banked stays on the
        request (EXPIRED/CANCELLED deliver what they have)."""
        req = self._slot_req[slot]
        with RecordEvent(f"serving/{status.lower()}"):
            if donate and self.paged:
                self._register_retired_blocks(slot)
            self._slot_req[slot] = None
            self._written[slot] = 0
            self._temp[slot] = 0.0
            self._poison[slot] = False
            if self._chunked:
                self._prefill_ids[slot] = None
                self._prefilled[slot] = 0
            if self.paged:
                self._release(slot)
            self._terminal(req, status, error)

    def _host_fault(self, kind: str, slot: int | None = None,
                    rid: int | None = None):
        """Poll one host-side injection seam; raises :class:`FaultInjected`
        when a plan clause fires (no-op without a plan)."""
        if self._faults and self._faults.fire(kind, step=self._step_no,
                                              slot=slot, rid=rid):
            where = "".join((f", slot {slot}" if slot is not None else "",
                             f", rid {rid}" if rid is not None else ""))
            self._flight.record("fault", fault=kind,
                                step=self._step_no,
                                **({"slot": slot}
                                   if slot is not None else {}),
                                **({"rid": rid}
                                   if rid is not None else {}))
            raise FaultInjected(
                f"injected {kind} (step {self._step_no}{where})")

    def _arm_poison(self):
        """Sampler seam: set per-slot poison bits for ``nan_logits`` clauses
        firing this step.  The bits are DATA to the compiled step, where
        they turn the slot's logits row genuinely non-finite IN-GRAPH — the
        guard proves itself against the real failure shape."""
        if not self._faults:
            return
        for s in range(self.max_batch):
            req = self._slot_req[s]
            if req is not None and self._faults.fire(
                    "nan_logits", step=self._step_no, slot=s, rid=req.rid):
                self._poison[s] = True

    def _retry_launch(self, err: FaultInjected) -> bool:
        """Handling of a kernel-dispatch fault: the raise happened
        BEFORE the compiled call, so host and device state (including the
        donated cache buffers) are untouched and the step can simply run
        again.  A persistent failure (streak past the limit) means the
        program itself cannot run — re-raise rather than spin."""
        self._kernel_err_streak += 1
        self.stats["kernel_error_retries"] += 1
        self._flight.record("fault", fault="kernel_error",
                            streak=self._kernel_err_streak,
                            step=self._step_no)
        if self._kernel_err_streak > self._kernel_err_limit:
            raise err
        with RecordEvent("serving/kernel_error_retry"):
            pass
        return True    # state untouched: the next step() retries

    def _growth_need(self, growth) -> int:
        """Block-pool pressure probe: pages the active slots' imminent
        growth needs beyond what they already own (``growth`` may be a
        per-slot vector, matching ``_ensure_growth``)."""
        karr = np.broadcast_to(np.asarray(growth, np.int64),
                               (self.max_batch,))
        need = 0
        for s in range(self.max_batch):
            if self._slot_req[s] is None or karr[s] <= 0:
                continue
            need += max(0, self._blocks_needed(int(self._pos[s])
                                               + int(karr[s]) - 1)
                        - len(self._slot_shared[s])
                        - len(self._slot_blocks[s]))
        return need

    def _degrade_reclaim(self, growth) -> int:
        """Ladder rung 1: on pool pressure, proactively evict prefix-cache
        leaves into the free list (oldest zero-ref first — the same
        LRU order allocation-pressure eviction uses, just ahead of the
        allocator instead of inside it, so the rung is observable and
        strictly ordered before rungs 2-5).  Returns the deficit that
        REMAINS after eviction; <= 0 means the step fits."""
        need = self._growth_need(growth)
        short = need - len(self._free)
        if short > 0 and self._evictable() > 0:
            with RecordEvent("serving/degrade_evict"):
                if self._reclaim(short) > 0:
                    self.stats["degrade_evict"] += 1
                    self._flight.record("degrade", rung=1, what="evict",
                                        short=int(short))
        return need - len(self._free)

    def _expire_overdue(self):
        """Deadline enforcement: a request past its
        ``deadline_s`` wall-clock budget (from submission) terminates
        EXPIRED with whatever partial output it has, freeing its pages for
        requests that can still meet their SLO.  Queued and running
        requests expire alike — a queued request that can no longer finish
        in time should not consume a slot at all."""
        now = time.perf_counter()

        def overdue(req):
            return (req.deadline_s is not None
                    and now - getattr(req, "_submit_s", now) > req.deadline_s)

        for s in range(self.max_batch):
            req = self._slot_req[s]
            if req is not None and overdue(req):
                self._fail_slot(s, "EXPIRED",
                                f"deadline_s={req.deadline_s} exceeded "
                                f"({len(req.output_ids)} token(s) delivered)",
                                donate=True)
        if any(overdue(r) for r in self._queue):
            keep = []
            for req in self._queue:
                if overdue(req):
                    with RecordEvent("serving/expired"):
                        self._terminal(req, "EXPIRED",
                                       f"deadline_s={req.deadline_s} "
                                       f"exceeded while queued")
                else:
                    keep.append(req)
            self._queue = keep

    def cancel(self, rid: int) -> bool:
        """Cancel a request by id: queued requests leave the queue, a
        running request frees its slot (even mid-prefill — the chunked
        cursor's pages release like any preemption, and full blocks donate
        to the prefix cache so a re-submission resumes cheaply).  Partial
        output stays on the request.  Returns True when the request was
        still live (False: unknown rid or already terminal)."""
        req = self._reqs.get(rid)
        if req is None or req.status in TERMINAL_STATUSES:
            return False
        for s in range(self.max_batch):
            if self._slot_req[s] is req:
                self._fail_slot(s, "CANCELLED", "cancelled by caller",
                                donate=True)
                return True
        with RecordEvent("serving/cancelled"):
            # identity scan, not `in`/`remove`: the dataclass __eq__
            # compares numpy prompt_ids and would raise on same-shape twins
            for i, q in enumerate(self._queue):
                if q is req:
                    del self._queue[i]
                    break
            self._terminal(req, "CANCELLED", "cancelled by caller")
        return True

    def _topology(self) -> dict:
        """Engine topology/config fingerprint journaled into snapshots
        (v2): everything a restore target must agree on for the journal to
        be replayable — the model identity (a mismatched model would
        teacher-force the wrong logits silently), serving geometry
        (max_seq/paged/block_size/quant) — plus the tp degree, which is
        recorded for diagnosis but deliberately NOT enforced: the KV pool
        is never captured, so teacher-forced recompute makes a
        cross-degree restore token-identical by construction
        (docs/tp_serving.md)."""
        return {
            "model": self._program.model_id(),
            "quant": self.quant,
            # pool storage changes the teacher-forced logits (requantized
            # appends are lossy), so a kv_quant mismatch must raise; old
            # v2 snapshots lack the key and src.get() -> None == the fp
            # engine's value, so pre-stage-2 journals restore unchanged
            "kv_quant": self.kv_quant,
            "paged": self.paged,
            "block_size": self.block_size if self.paged else None,
            "max_seq": int(self.max_seq),
            "tp": int(self.tp),
        }

    def snapshot(self) -> dict:
        """Serialize accepted-but-unfinished work: queue order plus a
        per-request journal (prompt, emitted tokens, sampling params,
        chunked-prefill cursor).  JSON-serializable, device-free — the
        KV pool is deliberately NOT captured: :meth:`restore` resumes by
        teacher-forced recompute (the preemption path), which is exact for
        greedy AND seeded sampling, so a snapshot costs bytes proportional
        to the token streams, not the HBM pool.  The replica-restart
        primitive the fleet tier needs (ROADMAP item 2).

        v2 adds the ``engine`` topology block (:meth:`_topology`) so
        :meth:`restore` can refuse a mismatched replica instead of
        resuming silently wrong, and ``deadline_remaining_s`` — the
        UNSPENT wall-clock budget at snapshot time — so :meth:`adopt`
        re-arms a restored deadline with what is actually left rather
        than granting the full budget again."""

        now = time.perf_counter()
        self.stats["journal_full_rebuilds"] += 1
        with RecordEvent("serving/snapshot"):
            running = [s for s in range(self.max_batch)
                       if self._slot_req[s] is not None]
            if self.paged:
                running.sort(key=lambda s: int(self._slot_age[s]))
            return {
                "version": 2,
                "engine": self._topology(),
                "running": [journal_entry(self._slot_req[s],
                                          self._prefilled[s]
                                          if self._chunked else 0, now)
                            for s in running],
                "queued": [journal_entry(r, 0, now) for r in self._queue],
            }

    # ------------- incremental journal (docs/async_runtime.md) ------------

    def _jmark(self, rid: int):
        """Mark one rid's journal entry stale (admission, token bank,
        chunk-cursor advance, adopt, preempt).  O(1) — the entry rebuild
        happens in :meth:`_jflush`, inside the host-overlap window."""
        self._jdirty.add(rid)

    def _jdrop(self, rid: int):
        """Retire one rid's journal entry (terminal states)."""
        self._jentries.pop(rid, None)
        self._jdirty.discard(rid)

    def _jflush(self, now: float | None = None):
        """Rebuild the journal entries of every dirty rid — the O(changed
        rids) incremental replacement for :meth:`snapshot`'s full scan.
        Entries freeze ``deadline_remaining_s`` at flush time;
        :meth:`journal` re-derives it at read time, so consumers always
        see the live remaining budget."""
        if not self._jdirty:
            return
        t0 = time.perf_counter()
        if now is None:
            now = t0
        slot_of = {}
        for s in range(self.max_batch):
            r = self._slot_req[s]
            if r is not None:
                slot_of[r.rid] = s
        n = 0
        for rid in self._jdirty:
            req = self._reqs.get(rid)
            if req is None:
                # terminal raced the mark (defensive; _terminal _jdrops)
                self._jentries.pop(rid, None)
                continue
            s = slot_of.get(rid)
            prefilled = (int(self._prefilled[s])
                         if self._chunked and s is not None else 0)
            self._jentries[rid] = journal_entry(req, prefilled, now)
            n += 1
        self._jdirty.clear()
        if n:
            self.stats["journal_incremental_updates"] += n
            self._h_jupdate.observe(time.perf_counter() - t0)
            self._flight.record("journal_flush", entries=n)

    def journal(self) -> dict:
        """:meth:`snapshot`-equivalent view assembled from the incremental
        journal (docs/async_runtime.md).  The fleet pulls this only at
        failover/hedge boundaries; equivalence with :meth:`snapshot` is
        asserted every fleet step under PADDLE_TPU_ENGINE_AUDIT=1
        (fleet._audit_journal_equiv)."""
        now = time.perf_counter()
        self._jflush(now)

        def _entry(req, prefilled: int) -> dict:
            e = self._jentries.get(req.rid)
            if e is None:
                # defensive: a rid that never got marked (should not
                # happen — every mutation site _jmarks) still journals
                e = journal_entry(req, prefilled, now)
                self._jentries[req.rid] = e
            if e["deadline_s"] is not None:
                e = dict(e)
                e["deadline_remaining_s"] = max(
                    0.0, e["deadline_s"]
                    - (now - getattr(req, "_submit_s", now)))
            return e

        running = [s for s in range(self.max_batch)
                   if self._slot_req[s] is not None]
        if self.paged:
            running.sort(key=lambda s: int(self._slot_age[s]))
        return {
            "version": 2,
            "engine": self._topology(),
            "running": [_entry(self._slot_req[s],
                               int(self._prefilled[s])
                               if self._chunked else 0) for s in running],
            "queued": [_entry(r, 0) for r in self._queue],
        }

    def _host_overlap(self):
        """The token-independent half of a step's host work, run between
        the compiled launch and the first token fetch — while the device
        executes the step (JAX async dispatch), so steady-state journal
        upkeep costs the host gap nothing."""
        self.stats["host_overlap_steps"] += 1
        self._jflush()

    def adopt(self, j: dict) -> Request:
        """Adopt ONE journaled request (an entry of :meth:`snapshot`'s
        ``running``/``queued`` lists) into this engine's queue — the fleet
        tier's per-request failover/hedge primitive (inference/fleet.py),
        and the loop body :meth:`restore` runs over a whole snapshot.

        The request re-enters through the preemption-resume path: prompt +
        already-emitted tokens are teacher-forced by (chunked) prefill
        recompute, then position-derived sampling keys continue the stream
        exactly.  Deliberately EXEMPT from ``max_queue`` backpressure:
        journaled work was already accepted once (by the dead or stalled
        replica), and accepted work is never rejected — the same contract
        preemption re-inserts enjoy.  The deadline re-arms with the
        journaled ``deadline_remaining_s`` (v2): the budget the original
        replica already burned stays burned.  (Journals without the field —
        v1 snapshots — fall back to the full ``deadline_s``, the historical
        behavior.)"""
        req = Request(
            rid=j["rid"],
            prompt_ids=np.asarray(j["prompt_ids"], np.int32),
            max_new_tokens=j["max_new_tokens"],
            eos_token_id=j["eos_token_id"],
            temperature=j["temperature"], top_p=j["top_p"],
            seed=j["seed"],
            deadline_s=j.get("deadline_remaining_s", j["deadline_s"]))
        req.output_ids = list(j["output_ids"])
        if req.output_ids:
            # the preempt-resume contract: stored tokens are
            # teacher-forced, the continuation redraws exactly
            req._resume_ids = np.concatenate(
                [np.asarray(req.prompt_ids, np.int32).ravel(),
                 np.asarray(req.output_ids, np.int32)])
        req._submit_s = time.perf_counter()
        if req.trace_id is None:
            req.trace_id = f"req-{req.rid:x}"
        self.slo.begin(req.rid, req._submit_s)
        self._tracer.instant(req.rid, "adopt", req._submit_s,
                             args={"rid": req.rid,
                                   "replayed_tokens": len(req.output_ids)})
        self._flight.record("adopt", rid=req.rid,
                            replayed_tokens=len(req.output_ids))
        self._reqs[req.rid] = req
        self._queue.append(req)
        self._jmark(req.rid)
        return req

    def restore(self, snap: dict) -> list[Request]:
        """Resume a :meth:`snapshot` on THIS engine (typically a fresh
        replica after a crash/restart).  Every journaled request re-enters
        the queue through the preemption-resume path: prompt + already-
        emitted tokens are teacher-forced by (chunked) prefill recompute,
        then position-derived sampling keys continue the stream exactly —
        a serve completed after restore() emits token-identical output to
        one that was never interrupted.  Deadlines re-arm from restore
        time with the journaled REMAINING budget (the dead replica's
        clock is gone, but the budget it burned stays burned —
        :meth:`adopt`).  Returns the resumed Request objects (in
        admission order: running work first).

        v2 snapshots carry the source engine's topology (:meth:`_topology`)
        and restore onto a mismatched engine raises a diagnosable
        ``ValueError`` naming every differing field — a journal replayed
        through the wrong model or serving geometry would resume silently
        wrong.  The ONE deliberate exception is the tensor-parallel
        degree: the journal holds tokens, not KV bytes, and teacher-forced
        recompute is degree-independent, so a tp=4 snapshot legally
        restores onto a tp=1 (or tp=2) replica token-identically — the
        fleet-tier elasticity primitive.  v1 snapshots (pre-topology)
        restore as before, unchecked."""
        if snap.get("version") not in (1, 2):
            raise ValueError(f"unknown snapshot version "
                             f"{snap.get('version')!r} (expected 1 or 2)")
        src = snap.get("engine")
        if snap.get("version") == 2 and src is not None:
            mine = self._topology()
            mismatch = {k: (src.get(k), mine[k]) for k in mine
                        if k != "tp" and src.get(k) != mine[k]}
            if mismatch:
                diff = "; ".join(
                    f"{k}: snapshot={a!r} vs engine={b!r}"
                    for k, (a, b) in sorted(mismatch.items()))
                raise ValueError(
                    f"snapshot topology does not match this engine "
                    f"({diff}); restoring across topologies would resume "
                    f"silently wrong — only the tensor-parallel degree "
                    f"may differ (snapshot tp={src.get('tp')!r}, engine "
                    f"tp={self.tp})")
        with RecordEvent("serving/restore"):
            return [self.adopt(j) for j in snap["running"] + snap["queued"]]

    def _maybe_audit(self):
        if self._audit_every_step:
            from ..analysis.engine_audit import (EngineAuditError,
                                                 audit_engine)

            try:
                audit_engine(self)
            except EngineAuditError:
                # triage-without-a-rerun: the flight recorder's last
                # N events + a metrics snapshot accompany the raise
                self._flight.dump("engine_audit_error")
                raise

    # ------------- per-step latency accounting (docs/observability.md) ----

    def _note_launch(self, t0: float):
        """Called at each compiled launch's dispatch time: the gap since
        the previous step's host fetch is pure host-side work (packing,
        drafting, journal upkeep) the device spent idle — the host-gap
        histogram ROADMAP item 5 will optimize against."""
        if self._last_step_end is not None:
            self._h_hostgap.observe(t0 - self._last_step_end)

    def _note_step_done(self, t0: float):
        end = time.perf_counter()
        self._h_step.observe(end - t0)
        self._last_step_end = end

    def _count_launch(self, rows_computed: int, rows_live: int,
                      slots_seated: int, prefill_rows: int = 0,
                      of_program: dict | None = None):
        """Called once by each launch path (mixed, decode, verify) when its
        step has banked: what the program computed against what was live
        and the slots seated at the launch, with the numbers packing had
        in hand; the pool as banking leaves it.  ``of_program`` is what
        the step program's own ``launch_counters`` made of the launch (the
        dense program counts nothing of its own).  Plain
        counters, so a mean over any window is a ratio of two deltas
        (docs/observability.md "Step accounting")."""
        st = self.stats
        for key, n in (of_program or {}).items():
            st[key] += n
        st["step_rows_computed"] += rows_computed
        st["step_rows_live"] += rows_live
        st["prefill_rows_packed"] += prefill_rows
        st["slot_steps_live"] += slots_seated
        st["slot_steps_total"] += self.max_batch
        if self.paged:
            st["kv_page_steps_in_use"] += self.num_blocks - len(self._free)
            st["kv_page_steps_total"] += self.num_blocks

    #: the phases in which the host waits on (or works beside) the device
    _DEVICE_PHASES = ("serving/host_overlap", "serving/fetch")

    def _phase(self, name: str | None = None, **args):
        """Close the step's open phase span and open the next (``None``
        closes only).  The phases follow one another inside
        ``serving/step`` on the profiler's clock (``RecordEvent`` ->
        ``TraceAnnotation``); the time spent in the device phases is what
        ``step_host_s`` leaves out of ``step_total_s``."""
        now = time.perf_counter()
        ev = self._phase_ev
        if ev is not None:
            ev.end()
            if ev.name in self._DEVICE_PHASES:
                self._device_wait_s += now - self._phase_t0
        self._phase_ev = None
        if name is not None:
            self._phase_t0 = now
            self._phase_ev = RecordEvent(name, **args)
            self._phase_ev.begin()

    def step(self) -> bool:
        """One admit + decode iteration (a chunked decode scan; with
        speculation on and at least one slot drafting, a single multi-token
        verify step; with chunked prefill on and at least one prompt still
        streaming, a single unified mixed prefill/decode step).  Returns
        False when idle.

        No per-request fault escapes this method — the
        offending request terminates (pages and cache refs released) and
        every survivor's token stream is identical to a run that never
        contained it (each slot's stream depends only on its own
        (seed, position) keys and its own pages)."""
        self._step_no += 1          # fault-plan step key (1-based)
        if not self._queue and all(r is None for r in self._slot_req):
            # an idle poll: nothing to expire, admit or launch — and no
            # span or clock, or a polling caller fills the span buffer
            self._admit_stalls = 0
            self._maybe_audit()
            return False
        t_in = time.perf_counter()
        self._device_wait_s = 0.0
        span = RecordEvent("serving/step", step=self._step_no)
        span.begin()
        try:
            return self._step()
        finally:
            self._phase()       # the last phase closes inside its parent
            span.end()
            total = time.perf_counter() - t_in
            self.stats["step_total_s"] += total
            self.stats["step_host_s"] += total - self._device_wait_s

    def _step(self) -> bool:
        """``step()``'s body, in phases: admit, pack, then one launch path
        (dispatch, host_overlap, fetch, bank)."""
        self._phase("serving/admit")
        self._expire_overdue()
        self._admit()
        if (self.paged and self._queue
                and all(r is None for r in self._slot_req)):
            # admission made no progress with NOTHING resident: no future
            # step can free pages (zero-ref cache leaves were already fair
            # game inside _alloc_to), so waiting is a livelock.  Tolerate a
            # few consecutive stuck steps (a transient injected alloc fault
            # clears), then fail the head request — ladder rung 5 applied
            # at admission.
            self._admit_stalls += 1
            if self._admit_stalls > self._kernel_err_limit:
                req = self._queue.pop(0)
                ids = getattr(req, "_resume_ids", None)
                s0 = (np.asarray(req.prompt_ids, np.int32).ravel().size
                      if ids is None else ids.size)
                with RecordEvent("serving/failed"):
                    self._terminal(
                        req, "FAILED",
                        f"pool exhausted at admission: rid={req.rid} needs "
                        f"{self._blocks_needed(s0 - 1)} block(s) for its "
                        f"{s0}-token stream, {len(self._free)} free + "
                        f"{self._evictable()} evictable of "
                        f"{self.num_blocks} total")
                self._admit_stalls = 0
        else:
            self._admit_stalls = 0
        self._maybe_audit()
        self._phase("serving/pack")
        if self._chunked and any(i is not None for i in self._prefill_ids):
            # at least one prompt is streaming in: ONE mixed launch advances
            # every decode slot a token AND moves the prompts forward under
            # the token budget.  Once every prompt drains, the ordinary
            # decode/speculative paths below run their untouched programs —
            # steady-state throughput is byte-identical to chunked-off.
            return self._mixed_step()
        if self._spec is not None:
            drafts = self._draft_proposals()
            if drafts is not None:
                qlens = np.ones(self.max_batch, np.int64)
                for s, d in drafts.items():
                    qlens[s] = 1 + d.size
                if self._degrade_reclaim(qlens) > 0:
                    # ladder rung 2: this step's speculative appends do not
                    # fit even after rung 1's eviction — suspend speculation
                    # for the step (growth drops to one token per slot)
                    # before anyone is preempted.  Token streams are
                    # unaffected: speculation only changes how many tokens
                    # each round-trip banks, never which ones.
                    with RecordEvent("serving/degrade_spec_off"):
                        self.stats["degrade_spec_off"] += 1
                        self._flight.record("degrade", rung=2,
                                            what="spec_off",
                                            step=self._step_no)
                    drafts = None
            if drafts is not None:
                return self._spec_step(drafts)
            # no slot drafted: fall through to the ordinary decode path —
            # a drafter miss must cost nothing (same step shape as spec-off)
        k = self.chunk
        if self.paged:
            self._degrade_reclaim(k)    # ladder rung 1 before rung 4
            self._ensure_growth(k)  # may preempt the youngest slot
        active_np = np.asarray([r is not None for r in self._slot_req])
        if not active_np.any():
            return False
        n_live = int(active_np.sum())
        # the [B, k] lanes staged, those that carry a row; no slot starts
        # in a decode step (a prompt's first row enters through a chunk)
        of_program = self._program.launch_counters(
            {"mixed": False, "lanes": self.max_batch * k,
             "rows_live": n_live * k, "lanes_live": n_live, "starts": 0})
        self._phase("serving/dispatch", program="decode",
                    decode_rows=n_live * k, prefill_rows=0,
                    rows_computed=self.max_batch * k,
                    linear_rows=of_program.get("gdn_rows_computed", 0))
        t0 = time.perf_counter()
        self._note_launch(t0)
        extra = (jnp.asarray(self._table),) if self.paged else ()
        # greedy-only resident set takes the sampler-free compiled variant
        any_sampled = bool((self._temp * active_np).max() > 0)
        decode = self._decode_sampling if any_sampled else self._decode_greedy
        self._arm_poison()
        try:
            self._host_fault("kernel_error")   # dispatch seam: pre-launch
            toks, bad, self.cache_k, self.cache_v = decode(
                self.params, self.cache_k, self.cache_v,
                jnp.asarray(self._last_tok), jnp.asarray(self._pos),
                jnp.asarray(active_np), jnp.asarray(self._temp),
                jnp.asarray(self._topp), jnp.asarray(self._seed),
                *extra, poison=jnp.asarray(self._poison))
            # async host runtime: the token-independent host half
            # (journal upkeep) runs while the device executes the
            # launch above — the guard/token fetches below block as
            # late as possible (docs/async_runtime.md)
            self._phase("serving/host_overlap")
            self._host_overlap()
            self._phase("serving/fetch")
            bad_np = np.asarray(bad)    # [k, B] guard flags
        except FaultInjected as e:
            return self._retry_launch(e)
        self._kernel_err_streak = 0
        self._poison[:] = False
        toks_np = np.asarray(toks)  # [k, B] — ONE host round-trip per chunk
        self._phase("serving/bank")
        self.stats["decode_time_s"] += time.perf_counter() - t0
        self._note_step_done(t0)
        now = self._last_step_end   # banking-event timestamp (SLO tracker)
        self.stats["decode_steps"] += k
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            old_pos = int(self._pos[slot])
            # tokens produced from positions >= max_seq are garbage (their
            # K/V writes were dropped): only the first max_seq - old_pos
            # chunk steps are trustworthy
            valid = min(k, self.max_seq - old_pos)
            done = False
            banked = 0
            fail_err = None
            try:
                self._host_fault("slot_error", slot=slot, rid=req.rid)
            except FaultInjected as e:
                fail_err = str(e)
            if fail_err is None:
                for j in range(valid):
                    if bad_np[j, slot]:
                        # quarantine: tokens from the poisoned scan step on
                        # are sampled from a zeroed row — never banked
                        self.stats["nan_guard_trips"] += 1
                        fail_err = (f"non-finite logits at position "
                                    f"{old_pos + j} (in-graph guard)")
                        break
                    tok = int(toks_np[j, slot])
                    req.output_ids.append(tok)
                    banked += 1
                    if req.ttft_s is None:
                        # time-to-first-token: the cached-prefix admission's
                        # headline win (prefill skipped, decode starts
                        # sooner)
                        req.ttft_s = (time.perf_counter()
                                      - getattr(req, "_submit_s", t0))
                    # count only tokens a caller actually receives: chunk
                    # steps past EOS / the token budget / max_seq are
                    # trimmed here, so they must not inflate
                    # decode_tokens_per_s
                    self.stats["decode_tokens"] += 1
                    if (len(req.output_ids) >= req.max_new_tokens
                            or (req.eos_token_id is not None
                                and tok == req.eos_token_id)):
                        done = True
                        break
            if fail_err is not None:
                # per-request isolation: fail THIS slot, free its pages;
                # the other lanes' tokens (already fetched) bank normally
                self._fail_slot(slot, "FAILED", fail_err, donate=False)
                continue
            if banked:
                # one banking event: the whole chunk arrives in one fetch
                self.slo.tokens(req.rid, banked, now)
            self._pos[slot] = old_pos + k  # device advanced k regardless
            # maximum, not overwrite: a prior verify step's rejected drafts
            # may have written past old_pos+k, and the high-water mark must
            # keep covering them until they are actually overwritten
            self._written[slot] = max(int(self._written[slot]),
                                      min(old_pos + k, self.max_seq))
            self._last_tok[slot] = int(toks_np[-1, slot])
            self._jmark(req.rid)   # token bank advanced the journal entry
            if done or old_pos + k >= self.max_seq:
                self._retire(slot)
        self._count_launch(self.max_batch * k, n_live * k, n_live,
                           of_program=of_program)
        self._maybe_audit()
        return True

    # ---------------- chunked-prefill scheduling (host control plane) ------

    def _mixed_step(self) -> bool:
        """One unified prefill/decode round (docs/chunked_prefill.md): pack
        up to ``token_budget`` rows as [decode slots | prefill chunks] and
        dispatch ONE compiled launch ([B, T] staged, ``_mixed_rows`` packed
        rows computed).  Decode rows pack FIRST — every
        decode-ready slot advances exactly one token, so decode never waits
        on a prompt (``decode_stall_steps`` stays 0) — then prefill chunks
        fill the remaining budget oldest-slot-first, at most
        ``prefill_chunk`` rows per slot per step, with a 1-token floor so a
        tiny budget degrades to slow prefill instead of livelock.  A lane
        whose chunk reaches the last prompt token consumes its emitted
        token (the fused first decode step); mid-prompt lanes ignore theirs.
        Freshly-completed full blocks register into the prefix cache chunk
        by chunk, so a request admitted later in the same serve already
        hits the streaming prefix."""
        B = self.max_batch
        T = self._prefill_chunk
        decode_slots = [s for s in range(B)
                        if self._slot_req[s] is not None
                        and self._prefill_ids[s] is None]
        budget = max(self._token_budget - len(decode_slots), 1)
        tokens = np.zeros((B, T), np.int32)
        q_lens = np.ones(B, np.int32)
        pos = np.asarray(self._pos, np.int32).copy()   # row-0 positions
        active = np.zeros(B, bool)
        growth = np.zeros(B, np.int64)
        chunk_rows: dict[int, int] = {}
        for s in decode_slots:
            tokens[s, 0] = self._last_tok[s]
            active[s] = True
            growth[s] = 1
        prefilling = sorted((s for s in range(B)
                             if self._prefill_ids[s] is not None),
                            key=lambda s: self._slot_age[s])
        tier_progress = False
        t_r0 = None        # first tier-restore dispatch (host-gap anchor)
        for s in prefilling:
            ids = self._prefill_ids[s]
            cur = int(self._prefilled[s])
            if self._tier is not None and self._tier_plan[s]:
                # hierarchical KV (docs/kv_tier.md): consume this slot's
                # tier-restore plan at the cursor — restored blocks
                # advance the cursor like computed chunks, billed against
                # the same token budget as prefill rows (no packed rows,
                # just the H2D); a budget-deferred plan idles the lane
                # rather than computing a block the next step restores
                cur0 = cur
                if t_r0 is None:
                    t_r0 = time.perf_counter()
                cur, budget, pending = self._tier_restore_step(s, ids,
                                                               budget)
                tier_progress = tier_progress or cur != cur0 or pending
                if cur != cur0:
                    self._jmark(self._slot_req[s].rid)  # cursor advanced
                if pending:
                    continue
            n = min(T, ids.size - cur, budget)
            if n <= 0:
                continue    # budget drained: the lane idles this step
            budget -= n
            tokens[s, :n] = ids[cur:cur + n]
            pos[s] = cur
            q_lens[s] = n
            active[s] = True
            growth[s] = n
            chunk_rows[s] = n
        if self._degrade_reclaim(growth) > 0:
            # ladder rungs 1 + 3: the step's FULL growth (decode lanes'
            # one-token appends + every packed prefill chunk) must fit —
            # _degrade_reclaim already evicted cache leaves (rung 1); if
            # still short, shrink this step's prefill rows to the 1-token
            # floor (prompts crawl, decode never stalls, nobody is
            # preempted for a prompt that could simply wait).  Only when
            # even the floor-packed step does not fit does _ensure_growth
            # below preempt (rung 4).
            shrinkable = [s for s, n in chunk_rows.items() if n > 1]
            if shrinkable:
                with RecordEvent("serving/degrade_budget_shrink"):
                    self.stats["degrade_budget_shrink"] += 1
                    self._flight.record("degrade", rung=3,
                                        what="budget_shrink",
                                        slots=len(shrinkable))
                for s in shrinkable:
                    tokens[s, 1:] = 0
                    q_lens[s] = 1
                    growth[s] = 1
                    chunk_rows[s] = 1
        # the auditor's I7 cross-checks the packing stayed disjoint
        self._last_pack = (tuple(decode_slots), tuple(sorted(chunk_rows)))
        self._ensure_growth(growth)  # may preempt the youngest slot
        for s in range(B):
            if self._slot_req[s] is None:       # preempted after packing
                active[s] = False
                chunk_rows.pop(s, None)
        if not active.any():
            if tier_progress and t_r0 is not None:
                # restore-only step: no compiled launch follows, but the
                # H2D restore dispatches above ARE this step's device work
                # — observe the host gap + step time here so the
                # tier-restore family shows up in the histograms too
                self._note_launch(t_r0)
                self._note_step_done(t_r0)
            # tier restores are progress even when every lane's ROWS were
            # deferred or drained (a restore-only step must keep the serve
            # loop spinning until the plan finishes draining)
            return bool(self._queue) or tier_progress
        n_decode = sum(1 for s in decode_slots if active[s])
        n_seated = sum(r is not None for r in self._slot_req)
        prefill_rows = int(sum(chunk_rows.values()))
        P = self._mixed_rows
        staged = int(q_lens[active].sum())
        if staged > P:
            # the program packs the first P live rows and would drop the
            # rest without a word: a lost row is a lost token
            from ..analysis.engine_audit import EngineAuditError

            self._flight.dump("engine_audit_error")
            raise EngineAuditError(
                f"engine audit I11 violated: mixed step staged {staged} "
                f"live rows, the program computes {P}")
        # the [B, T] lanes staged, the slots that carry a row, and those
        # whose first row sits at position 0
        of_program = self._program.launch_counters(
            {"mixed": True, "lanes": B * T,
             "rows_live": n_decode + prefill_rows,
             "lanes_live": int(active.sum()),
             "starts": int((active & (pos == 0)).sum())})
        self._phase("serving/dispatch", program="mixed",
                    decode_rows=n_decode, prefill_rows=prefill_rows,
                    rows_computed=P,
                    linear_rows=of_program.get("gdn_rows_computed", 0))
        t0 = time.perf_counter()
        self._note_launch(t0)
        # step-packing summary: O(1) per step, the flight recorder's
        # picture of what the scheduler chose when things went wrong
        self._flight.record("pack", step=self._step_no,
                            decode=len(decode_slots),
                            prefill=len(chunk_rows),
                            prefill_rows=prefill_rows)
        any_sampled = bool((self._temp * active).max() > 0)
        mixed = self._mixed_sampling if any_sampled else self._mixed_greedy
        self._arm_poison()
        try:
            self._host_fault("kernel_error")   # dispatch seam: pre-launch
            nxt, bad, self.cache_k, self.cache_v = mixed(
                self.params, self.cache_k, self.cache_v,
                jnp.asarray(tokens), jnp.asarray(pos),
                jnp.asarray(active), jnp.asarray(q_lens),
                jnp.asarray(self._temp), jnp.asarray(self._topp),
                jnp.asarray(self._seed), jnp.asarray(self._table),
                poison=jnp.asarray(self._poison))
            self._phase("serving/host_overlap")
            self._host_overlap()   # journal upkeep rides the launch
            # and so does the count of what its attention kernel works
            of_program.update(self._mixed_attn_census(q_lens, pos, active))
            self._phase("serving/fetch")
            bad_np = np.asarray(bad)    # [B] emit-row guard flags
        except FaultInjected as e:
            return self._retry_launch(e)
        self._kernel_err_streak = 0
        self._poison[:] = False
        nxt_np = np.asarray(nxt)   # [B] — ONE host round-trip for the step
        self._phase("serving/bank")
        self.stats["decode_time_s"] += time.perf_counter() - t0
        self._note_step_done(t0)
        self.stats["decode_steps"] += 1
        self.stats["mixed_steps"] += 1
        self.stats["prefill_chunks"] += len(chunk_rows)
        for s in decode_slots:
            req = self._slot_req[s]
            if req is None:
                continue            # preempted by _ensure_growth
            if bad_np[s]:
                self.stats["nan_guard_trips"] += 1
                self._fail_slot(s, "FAILED",
                                f"non-finite logits at position "
                                f"{int(self._pos[s])} (in-graph guard)",
                                donate=False)
                continue
            try:
                self._host_fault("slot_error", slot=s, rid=req.rid)
            except FaultInjected as e:
                self._fail_slot(s, "FAILED", str(e), donate=False)
                continue
            old_pos = int(self._pos[s])
            self._pos[s] = old_pos + 1
            self._written[s] = max(int(self._written[s]),
                                   min(old_pos + 1, self.max_seq))
            self._consume_token(s, req, int(nxt_np[s]), t0)
            if (self._slot_req[s] is not None
                    and old_pos + 1 >= self.max_seq):
                self._retire(s)
        for s, n in chunk_rows.items():
            req = self._slot_req[s]
            if req is None:
                continue            # preempted after packing
            if bad_np[s]:
                # a poisoned prefill lane: the forward pass that computed
                # this chunk's K/V is not trusted — quarantine the request
                # before any of its progress (or blocks) is banked
                self.stats["nan_guard_trips"] += 1
                self._fail_slot(s, "FAILED",
                                f"non-finite logits while prefilling "
                                f"(cursor {int(self._prefilled[s])}; "
                                f"in-graph guard)", donate=False)
                continue
            ids = self._prefill_ids[s]
            new_cur = int(self._prefilled[s]) + n
            self._prefilled[s] = new_cur
            self._jmark(req.rid)   # chunk cursor advanced
            self._tracer.span(req.rid, "prefill_chunk", t0,
                              self._last_step_end,
                              args={"rows": n, "cursor": new_cur,
                                    "prompt": int(ids.size)})
            self._pos[s] = new_cur
            self._written[s] = max(int(self._written[s]),
                                   min(new_cur, self.max_seq))
            if self._pcache is not None:
                # register full freshly-computed prompt blocks as chunks
                # complete them (all content below new_cur is prompt tokens;
                # decode's first write lands at position >= ids.size, never
                # inside a block these cover)
                self._register_prefix_blocks(s, ids, new_cur)
            if new_cur >= ids.size:
                # final chunk: its emit row sat at the last prompt token's
                # position — consume the fused first decode token
                self._prefill_ids[s] = None
                self._prefilled[s] = 0
                self._consume_token(s, req, int(nxt_np[s]), t0)
                if (self._slot_req[s] is not None
                        and new_cur >= self.max_seq):
                    self._retire(s)
        self._count_launch(P, n_decode + prefill_rows, n_seated,
                           prefill_rows, of_program)
        self._maybe_audit()
        return True

    def _mixed_attn_census(self, q_lens, pos, active) -> dict:
        """What the mixed step's attention kernel worked this launch, from
        the operands the step staged (``prefill_census``; a count of one
        layer's launch, the same for every layer): row-pages that carried
        a token against the row-pages of the sub-tiles the kernel
        multiplied."""
        from ..ops.pallas.paged_attention import prefill_census

        cfg = self._body_cfg
        # what mixed_one hands the kernel as seq_lens: an inactive lane
        # attends one stale position
        seq_base = np.where(active & (pos < self.max_seq), pos, 0)
        seq_now = np.minimum(seq_base + np.where(active, q_lens, 1),
                             self.max_seq)
        census = prefill_census(
            q_lens, seq_now, self._prefill_chunk,
            cfg.num_attention_heads // cfg.num_key_value_heads,
            self.block_size, max_blocks=self._table.shape[1],
            nkv=cfg.num_key_value_heads, hd=cfg.head_dim, dtype=cfg.dtype,
            kv_quant=self.kv_quant, live=active)
        return {"attn_row_pages_live": census["row_pages_live"],
                "attn_row_pages_computed": census["row_pages_computed"]}

    def _consume_token(self, slot: int, req: Request, tok: int, t0: float):
        """Bank one generated token on a slot (mixed-step emit): append,
        stamp TTFT, tick the throughput counter, advance the feedback token,
        and retire on EOS / budget — the single-token analog of the decode
        chunk's host trimming loop."""
        req.output_ids.append(tok)
        if req.ttft_s is None:
            req.ttft_s = time.perf_counter() - getattr(req, "_submit_s", t0)
        self.slo.tokens(req.rid, 1, self._last_step_end)
        self.stats["decode_tokens"] += 1
        self._last_tok[slot] = tok
        self._jmark(req.rid)   # token bank advanced the journal entry
        if (len(req.output_ids) >= req.max_new_tokens
                or (req.eos_token_id is not None
                    and tok == req.eos_token_id)):
            self._retire(slot)

    # ---------------- speculative scheduling (host control plane) ----------

    def _draft_proposals(self) -> dict[int, np.ndarray] | None:
        """Run the prompt-lookup drafter over every active slot's
        prompt+generated history.  Returns {slot: drafts} when at least one
        slot proposed something, else None (the caller then takes the
        ordinary decode path).  Drafts are capped so the verify step never
        writes past max_seq and never drafts past the request's remaining
        token budget (both would be pure wasted verify lanes)."""
        out: dict[int, np.ndarray] = {}
        any_draft = False
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            if self._chunked and self._prefill_ids[slot] is not None:
                # a slot still streaming its prompt has no token to draft
                # from (step() routes to the mixed path while any prompt is
                # in flight, so this is belt-and-braces for direct callers)
                out[slot] = np.zeros(0, np.int32)
                continue
            cap = min(self.max_seq - 1 - int(self._pos[slot]),
                      req.max_new_tokens - len(req.output_ids) - 1)
            if cap <= 0:
                out[slot] = np.zeros(0, np.int32)
                continue
            ctx = np.concatenate(
                [np.asarray(req.prompt_ids, np.int32).ravel(),
                 np.asarray(req.output_ids, np.int32)])
            d = self._spec.propose(ctx)[:cap]
            out[slot] = d
            if d.size:
                any_draft = True
        return out if any_draft else None

    def _spec_step(self, drafts: dict[int, np.ndarray]) -> bool:
        """One draft-verify-accept round: grow pages for every slot's
        appends, run the compiled verify step once (ONE host round-trip for
        up to K+1 tokens per slot), emit the accepted run + the target's
        correction token, and roll ``pos`` back past any rejected drafts —
        their K/V writes stay behind as dead bytes above pos (tracked by
        ``_written``, overwritten by the next step, never content-addressed
        into the prefix cache because every cache registration trusts only
        positions below pos)."""
        B = self.max_batch
        Q = self._spec_qmax
        qlens = np.ones(B, np.int64)
        for s, d in drafts.items():
            qlens[s] = 1 + d.size
        self._ensure_growth(qlens)  # may preempt the youngest slot
        active_np = np.asarray([r is not None for r in self._slot_req])
        if not active_np.any():
            return False
        tokens = np.zeros((B, Q), np.int32)
        tokens[:, 0] = self._last_tok
        q_lens = np.ones(B, np.int32)
        for s, d in drafts.items():
            if self._slot_req[s] is None or d.size == 0:
                continue  # preempted after drafting, or no proposal
            tokens[s, 1:1 + d.size] = d
            q_lens[s] = 1 + d.size
        n_live = int(active_np.sum())
        rows_live = int(q_lens[active_np].sum())
        self._phase("serving/dispatch", program="verify",
                    decode_rows=rows_live, prefill_rows=0,
                    rows_computed=B * Q)
        t0 = time.perf_counter()
        self._note_launch(t0)
        any_sampled = bool((self._temp * active_np).max() > 0)
        verify = self._verify_sampling if any_sampled else self._verify_greedy
        self._arm_poison()
        try:
            self._host_fault("kernel_error")   # dispatch seam: pre-launch
            out, n_acc, bad, self.cache_k, self.cache_v = verify(
                self.params, self.cache_k, self.cache_v,
                jnp.asarray(tokens), jnp.asarray(self._pos),
                jnp.asarray(active_np), jnp.asarray(q_lens),
                jnp.asarray(self._temp), jnp.asarray(self._topp),
                jnp.asarray(self._seed), jnp.asarray(self._table),
                poison=jnp.asarray(self._poison))
            self._phase("serving/host_overlap")
            self._host_overlap()   # journal upkeep rides the launch
            self._phase("serving/fetch")
            bad_np = np.asarray(bad)    # [B] per-slot guard flags
        except FaultInjected as e:
            return self._retry_launch(e)
        self._kernel_err_streak = 0
        self._poison[:] = False
        out_np = np.asarray(out)
        n_np = np.asarray(n_acc)
        self._phase("serving/bank")
        self.stats["decode_time_s"] += time.perf_counter() - t0
        self._note_step_done(t0)
        now = self._last_step_end   # banking-event timestamp (SLO tracker)
        self.stats["decode_steps"] += 1
        self.stats["spec_steps"] += 1
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            old_pos = int(self._pos[slot])
            if bad_np[slot]:
                # the whole verify output for this slot is discarded (its
                # correction token came from a zeroed row); quarantine it
                self.stats["nan_guard_trips"] += 1
                self._fail_slot(slot, "FAILED",
                                f"non-finite logits at position {old_pos} "
                                f"(verify step; in-graph guard)",
                                donate=False)
                continue
            try:
                self._host_fault("slot_error", slot=slot, rid=req.rid)
            except FaultInjected as e:
                self._fail_slot(slot, "FAILED", str(e), donate=False)
                continue
            n = int(n_np[slot])        # 1..q_lens: accepted run + correction
            drafted = int(q_lens[slot]) - 1
            self.stats["spec_drafted_tokens"] += drafted
            self.stats["spec_accepted_tokens"] += n - 1
            self.stats["spec_rejected_tokens"] += drafted - (n - 1)
            done = False
            banked = 0
            for j in range(n):
                tok = int(out_np[slot, j])
                req.output_ids.append(tok)
                banked += 1
                if req.ttft_s is None:
                    req.ttft_s = (time.perf_counter()
                                  - getattr(req, "_submit_s", t0))
                self.stats["decode_tokens"] += 1
                if (len(req.output_ids) >= req.max_new_tokens
                        or (req.eos_token_id is not None
                            and tok == req.eos_token_id)):
                    done = True
                    break
            if banked:
                # one banking event: the accepted run arrives in one fetch
                self.slo.tokens(req.rid, banked, now)
            # rejection rollback: pos advances only past ACCEPTED tokens;
            # the high-water mark remembers how far the device EVER wrote
            # (a shorter draft after a long rejected one must not shrink it)
            self._written[slot] = max(int(self._written[slot]),
                                      min(old_pos + int(q_lens[slot]),
                                          self.max_seq))
            self._pos[slot] = old_pos + n
            self._last_tok[slot] = int(out_np[slot, n - 1])
            self._jmark(req.rid)   # accepted run advanced the journal
            if done or old_pos + n >= self.max_seq:
                self._retire(slot)
        self._count_launch(B * Q, rows_live, n_live)
        self._maybe_audit()
        return True

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify step accepted (0.0 before
        any speculative step — also the spec-off value)."""
        d = self.stats["spec_drafted_tokens"]
        return self.stats["spec_accepted_tokens"] / d if d > 0 else 0.0

    def serve(self, requests: list[Request]) -> dict[int, list[int]]:
        """Run all requests to completion; returns {rid: generated tokens}.

        An invalid request is marked ``REJECTED`` (with ``error``) and the
        rest are served — one bad sampling param must not zero a whole
        batch's goodput."""
        for r in requests:
            try:
                self.add_request(r)
            except ValueError as e:
                self._reqs[r.rid] = r
                with RecordEvent("serving/rejected"):
                    self._terminal(r, "REJECTED", str(e))
        while self.step() or self._queue:
            pass
        return {r.rid: r.output_ids for r in requests}

    @property
    def decode_tokens_per_s(self) -> float:
        t = self.stats["decode_time_s"]
        return self.stats["decode_tokens"] / t if t > 0 else 0.0

    def n_traces(self) -> int | None:
        """Total compiled program variants across this engine's jitted
        programs (decode greedy/sampling, prefill(s), COW copy) — the
        jit-cache-churn telemetry: the expected count is small and
        static (one decode variant per sampling mode actually used + one
        prefill per warmed bucket), so growth across a serve is a silent
        recompile in the hot loop (paddle_tpu.analysis.n_traces)."""
        from ..analysis import n_traces as _n

        fns = [self._decode_greedy, self._decode_sampling, self._prefill]
        if self._pcache is not None:
            fns += [self._prefill_prefix, self._copy_page]
        if self._tier is not None:
            # the ship_in pool write: ONE variant for the whole serve
            # (page index and payload are data, shapes are static)
            fns += [self._tier_write]
        if self._spec is not None:
            # the verify step's query width is static (K+1): exactly one
            # variant per sampling mode actually used, regardless of how
            # ragged the per-step drafts were
            fns += [self._verify_greedy, self._verify_sampling]
        if self._chunked:
            # the mixed step's width is static (prefill_chunk): one variant
            # per sampling mode for every prompt length — the O(1) that
            # replaces the bucketed path's log2(max_seq) prefill family
            fns += [self._mixed_greedy, self._mixed_sampling]
        return _n(*fns)

    def _decode_step_trace(self):
        """Trace ONE greedy decode step to a ClosedJaxpr (no compile, no
        device time) under the CURRENT trace-time state (kill switches,
        fused/flash config) — the shared substrate of the static
        telemetry: :meth:`decode_step_launches` runs the launch census
        over it and :meth:`decode_step_card` the full program card.
        Returns ``(closed, donated)``: the impl is traced unjitted, so the
        production program's cache donation (``_jit_step``'s
        ``donate_argnums=(1, 2)``) is reconstructed as a per-leaf mask for
        the card's peak-HBM pass — without it the KV pools would count
        both as caller-held inputs and as fresh outputs."""
        B = self.max_batch
        zi = jnp.zeros((B,), jnp.int32)
        body = functools.partial(
            self._decode_impl_paged if self.paged else self._decode_impl,
            sampling=False)
        args = [self.params, self.cache_k, self.cache_v, zi, zi,
                jnp.ones((B,), bool), jnp.zeros((B,), jnp.float32),
                jnp.ones((B,), jnp.float32), zi]
        if self.paged:
            args.append(jnp.asarray(self._table))
        if self.tp > 1:
            body = self._tp_shard(body, n_rep=2)
        # telemetry must not contaminate the dispatch counters: the trace
        # below executes the kernels' Python dispatch, which would tick
        # KERNEL/FLASH/FUSED_*_CALLS by one launch the serve never ran.
        # Snapshot and restore around the trace.
        from ..ops.pallas import paged_attention as _pa

        counter_names = ("KERNEL_CALLS", "FALLBACK_CALLS",
                         "FLASH_KERNEL_CALLS", "LAST_FLASH_SHARDS",
                         "FUSED_KERNEL_CALLS", "FUSED_FALLBACK_CALLS",
                         "MLP_KERNEL_CALLS", "MLP_FALLBACK_CALLS",
                         "QUANT_APPEND_KERNEL_CALLS",
                         "QUANT_APPEND_FALLBACK_CALLS")
        saved = {n: getattr(_pa, n) for n in counter_names}
        try:
            closed = jax.make_jaxpr(body)(*args)
        finally:
            for n, v in saved.items():
                setattr(_pa, n, v)
        donated = tuple(i in self._STEP_DONATE_ARGNUMS
                        for i, a in enumerate(args)
                        for _ in jax.tree_util.tree_leaves(a))
        return closed, donated

    def decode_step_launches(self) -> dict:
        """Static dispatch-tax telemetry for ONE greedy decode step: trace
        the decode program and count its equations plus the per-layer
        launch-shaped primitives — every ``pallas_call`` and every scatter
        (the KV appends) — via the ONE census implementation the static
        program card uses (``analysis.cost_model.eqn_census``; a parity
        test pins static card == this telemetry).  The fused decode step's
        win is visible here before any wall clock: the unfused paged path
        traces 1 pallas_call + 2 scatters per layer (plus the rope/gather
        glue XLA must fuse around them), the fused path traces 1
        pallas_call and 0 scatters (eqns inside the chunk scan's per-step
        body count once, matching the per-layer dispatch they model)."""
        from ..analysis.cost_model import eqn_census

        closed, _ = self._decode_step_trace()
        counts = eqn_census(closed)
        counts["fused_decode"] = bool(self._fused)
        counts["fused_mlp"] = bool(self._fused_mlp)
        counts["kv_quant"] = self.kv_quant
        return counts

    def decode_step_card(self) -> dict:
        """Static ProgramCard summary of ONE greedy decode step
        (analysis/cost_model.py): peak live HBM, launch census, per-launch
        VMEM fit, and the kernel-contract aggregate (bounds / race /
        alias verdicts over every pallas launch,
        analysis/kernel_contracts.py).  The host-contract sections
        (analysis/host_contracts.py) ride along: this engine IS the async
        host runtime the pass verifies, so the card carries the
        overlap-window race/blocking verdicts and state-machine coverage
        beside the kernel ones.  Trace-only, like
        the launch telemetry; collective bytes are not compiled here (the
        TP gate target owns that figure) and trace-family accounting
        lives with ``n_traces()``."""
        from ..analysis.cost_model import build_card
        from ..analysis.host_contracts import check_host_contracts

        closed, donated = self._decode_step_trace()
        card = build_card(None, (), target="decode_step", closed=closed,
                          donated=donated, compile_collectives=False,
                          host_contracts=check_host_contracts(
                              target="decode_step")[1])
        d = card.summary()
        d["fused_decode"] = bool(self._fused)
        d["fused_mlp"] = bool(self._fused_mlp)
        d["kv_quant"] = self.kv_quant
        return d
