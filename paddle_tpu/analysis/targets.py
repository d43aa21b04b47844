"""Registered lint targets: the framework's own hot paths.

Each target builds a jittable callable + example args at a *tiny* config —
the lint is shape-generic (dtype flows, donation, cache keys, and callback
primitives are invariant to width/depth), so tracing the tiny config under
``JAX_PLATFORMS=cpu`` proves the same properties the production config has,
in seconds and with zero device time.

``build(name)`` returns an :class:`AnalysisTarget`; ``run(name)`` builds and
analyzes it.  ``tools/lint_gate.py`` iterates :data:`GATE_TARGETS` (and the
tier-1 suite runs the gate), so a change that knocks a train step or the
serving decode path off the fast path fails CI, not a later chip run.
"""

from __future__ import annotations

import contextlib
import dataclasses
import typing

import numpy as np

__all__ = ["AnalysisTarget", "TARGETS", "GATE_TARGETS", "HOST_TARGETS",
           "build", "run", "run_card"]


@dataclasses.dataclass
class AnalysisTarget:
    name: str
    fn: typing.Any
    args: tuple
    analyze_kwargs: dict = dataclasses.field(default_factory=dict)
    #: env pins to hold while ANALYZING (value None = unset).  Kill
    #: switches are trace-time state, and analysis re-traces the target
    #: AFTER its builder returned — without re-pinning here, an ambient
    #: PADDLE_TPU_DISABLE_PALLAS (or a bare environment) would silently
    #: swap which decode program the gate traces (e.g. the pre-fusion
    #: serving_decode_step picking up the flash kernel), and the program
    #: card would drift with whatever ran before it.
    env: dict = dataclasses.field(default_factory=dict)


@contextlib.contextmanager
def _pinned_env(env: dict):
    import os

    saved = {k: os.environ.get(k) for k in env}
    try:
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, p in saved.items():
            if p is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = p


def _t_llama_train_step() -> AnalysisTarget:
    import jax

    from ..models import llama

    cfg = llama.LlamaConfig.tiny()
    mesh = llama.make_mesh(devices=jax.devices()[:1])
    step_fn, opt_init, psh, dsh = llama.build_train_step(cfg, mesh)
    params = llama.init_params(cfg, jax.random.key(0))
    opt_state = opt_init(params)
    rs = np.random.RandomState(0)
    ids = jax.numpy.asarray(rs.randint(0, cfg.vocab_size, (2, 32)))
    labels = jax.numpy.asarray(rs.randint(0, cfg.vocab_size, (2, 32)))
    return AnalysisTarget("llama_train_step", step_fn,
                          (params, opt_state, ids, labels))


def _t_moe_train_step() -> AnalysisTarget:
    import jax

    from ..models import moe_llama

    cfg = moe_llama.MoEConfig.tiny()
    mesh = moe_llama.make_mesh(devices=jax.devices()[:1])
    step_fn, opt_init, psh, dsh = moe_llama.build_train_step(cfg, mesh)
    params = moe_llama.init_params(cfg, jax.random.key(0))
    opt_state = opt_init(params)
    rs = np.random.RandomState(0)
    ids = jax.numpy.asarray(rs.randint(0, cfg.vocab_size, (2, 32)))
    labels = jax.numpy.asarray(rs.randint(0, cfg.vocab_size, (2, 32)))
    return AnalysisTarget("moe_llama_train_step", step_fn,
                          (params, opt_state, ids, labels))


def _t_laguna_train_step() -> AnalysisTarget:
    import jax

    from ..models import laguna

    # the leading dense layer and one S S S F period, a quarter of the
    # experts held: full and windowed flash attention, the grouped expert
    # products in chunks, the counters in the optimizer state
    cfg = laguna.LagunaConfig.tiny(layers=5, held=(0, 4))
    mesh = laguna.make_mesh(devices=jax.devices()[:1])
    step_fn, opt_init, psh, dsh = laguna.build_train_step(cfg, mesh)
    params = laguna.init_params(cfg, jax.random.key(0))
    opt_state = opt_init(params)
    rs = np.random.RandomState(0)
    ids = jax.numpy.asarray(rs.randint(0, cfg.vocab_size, (2, 64)))
    labels = jax.numpy.asarray(rs.randint(0, cfg.vocab_size, (2, 64)))
    return AnalysisTarget("laguna_train_step", step_fn,
                          (params, opt_state, ids, labels))


def _serving_engine(_force_flags=(), _cfg_kwargs=None, _disable_pallas=(),
                    **kwargs):
    import contextlib
    import os
    import jax

    from ..models import llama
    from ..inference.serving import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny(**(_cfg_kwargs or dict(
        vocab=128, hidden=32, layers=2, heads=4, kv_heads=2, inter=64)))
    params = llama.init_params(cfg, jax.random.key(0))
    # the lint gate analyzes a feature's compiled program even when the
    # operator's kill switch (e.g. PADDLE_TPU_CHUNKED_PREFILL=0) has it off
    # at runtime — without the override the ctor would skip building the
    # program and the target builder would crash the whole gate.
    with contextlib.ExitStack() as stack:
        for flag in _force_flags:
            prev = os.environ.get(flag)
            os.environ[flag] = "1"
            stack.callback(lambda f=flag, p=prev: (
                os.environ.__setitem__(f, p) if p is not None
                else os.environ.pop(f, None)))
        # the Pallas kill switches are trace-time state like the flags
        # above: every serving target pins PADDLE_TPU_DISABLE_PALLAS to
        # EXACTLY the token set it declares — serving_decode_step
        # disables flash/fused (the pre-fusion program whose lint shape
        # is locked in), serving_flash_decode_step declares none (the
        # production default), and an operator's ambient opt-out for ANY
        # kernel is cleared rather than merged: the gate only traces
        # (never executes a kernel), so ambient paged_attention must not
        # demote a target to the gather oracle, flip the ctor's fused
        # mode, or fail the budget gate spuriously.
        prev_dp = os.environ.get("PADDLE_TPU_DISABLE_PALLAS")
        tokens = set(_disable_pallas)
        if tokens:
            os.environ["PADDLE_TPU_DISABLE_PALLAS"] = ",".join(sorted(tokens))
        else:
            os.environ.pop("PADDLE_TPU_DISABLE_PALLAS", None)
        stack.callback(lambda p=prev_dp: (
            os.environ.__setitem__("PADDLE_TPU_DISABLE_PALLAS", p)
            if p is not None
            else os.environ.pop("PADDLE_TPU_DISABLE_PALLAS", None)))
        # an ambient PADDLE_TPU_TP would OVERRIDE every builder's
        # tensor_parallel (the env wins by design) — e.g. PADDLE_TPU_TP=1
        # would collapse serving_tp_step to a single-chip program whose
        # resharding gate polices nothing, and PADDLE_TPU_TP=2 would turn
        # the single-chip targets into TP engines.  The gate must analyze
        # exactly the program each target declares: clear the override.
        prev_tp = os.environ.pop("PADDLE_TPU_TP", None)
        if prev_tp is not None:
            stack.callback(lambda: os.environ.__setitem__("PADDLE_TPU_TP",
                                                          prev_tp))
        eng = ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                       chunk=2, paged=True, block_size=8,
                                       **kwargs)
        # the pins above only cover CONSTRUCTION (this stack unwinds on
        # return) — but the kill switches are also read at TRACE time,
        # and analysis traces the target later.  Record pins on the
        # engine so the AnalysisTarget can re-apply them around
        # analyze()/build_card() (AnalysisTarget.env): otherwise an
        # ambient opt-out — or its absence — swaps which program the
        # gate traces after the builder already returned.  The pinned
        # token set is the target's DECLARED tokens only, not the
        # construction-time ambient merge: analysis is pure tracing
        # (never executes a kernel), so an operator's ambient
        # paged_attention opt-out must not demote the gate's traced
        # program to the gather oracle and fail the budget gate
        # spuriously.
        eng._lint_env = {
            **{flag: "1" for flag in _force_flags},
            "PADDLE_TPU_DISABLE_PALLAS": (",".join(sorted(_disable_pallas))
                                          if _disable_pallas else None),
            "PADDLE_TPU_TP": None,
        }
        return eng


def _t_serving_decode_step() -> AnalysisTarget:
    import jax.numpy as jnp

    # the PRE-fusion decode program (rope + KV scatters + sequential paged
    # kernel): its lint shape stays pinned even though production now
    # defaults to the fused/split-K path (serving_flash_decode_step below)
    eng = _serving_engine(_disable_pallas=("flash_decode",
                                           "fused_decode_step"))
    B = eng.max_batch
    tokens = jnp.zeros((B,), jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    active = jnp.asarray([True, False])
    temp = jnp.zeros((B,), jnp.float32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng._table)
    return AnalysisTarget(
        "serving_decode_step", eng._decode_greedy,
        (eng.params, eng.cache_k, eng.cache_v, tokens, pos, active,
         temp, topp, seeds, table), env=eng._lint_env)


def _t_serving_flash_decode_step() -> AnalysisTarget:
    import jax.numpy as jnp

    # the production-default decode program (ISSUE 10): fused rope +
    # KV-append + split-K attention with the log-sum-exp combine.  The
    # gate polices it like every hot path: the combine's f32 online-
    # softmax dots are the ONLY allowlisted upcasts (allowlist.toml), and
    # any other collective/upcast that sneaks into the fused step fails CI.
    eng = _serving_engine()
    assert eng._fused, "flash target must build the fused decode engine"
    B = eng.max_batch
    tokens = jnp.zeros((B,), jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    active = jnp.asarray([True, False])
    temp = jnp.zeros((B,), jnp.float32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng._table)
    return AnalysisTarget(
        "serving_flash_decode_step", eng._decode_greedy,
        (eng.params, eng.cache_k, eng.cache_v, tokens, pos, active,
         temp, topp, seeds, table), env=eng._lint_env)


def _t_serving_quant_decode_step() -> AnalysisTarget:
    import jax.numpy as jnp

    # the quantized-pool decode program at the stage-2 default (ISSUE 15):
    # fused rope + IN-KERNEL requantized append + dequant-on-read
    # attention, plus the fused MLP layer half — scatters = 0 IS the
    # contract (a requant scatter reappearing on this path is the
    # regression the budget gate names), and the kernel-contract rule
    # verifies the quant kernel's four aliased outputs every gate run.
    eng = _serving_engine(kv_quant="int8")
    assert eng._fused and eng._fused_mlp, (
        "quant target must build the fused stage-2 engine")
    B = eng.max_batch
    tokens = jnp.zeros((B,), jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    active = jnp.asarray([True, False])
    temp = jnp.zeros((B,), jnp.float32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng._table)
    return AnalysisTarget(
        "serving_quant_decode_step", eng._decode_greedy,
        (eng.params, eng.cache_k, eng.cache_v, tokens, pos, active,
         temp, topp, seeds, table), env=eng._lint_env)


def _t_serving_quant_scatter_step() -> AnalysisTarget:
    import jax.numpy as jnp

    # the PINNED pre-fusion quantized decode program (the kill-switch
    # oracle arm): requant-scatter append — two scatters per pool (codes
    # + per-page scale), four per step — with sequential-kernel
    # dequant-on-read attention.  This budget freezes the fallback's
    # shape exactly like serving_decode_step does for fp pools.
    eng = _serving_engine(_disable_pallas=("flash_decode",
                                           "fused_decode_step"),
                          kv_quant="int8")
    assert not eng._fused and not eng._fused_mlp
    B = eng.max_batch
    tokens = jnp.zeros((B,), jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    active = jnp.asarray([True, False])
    temp = jnp.zeros((B,), jnp.float32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng._table)
    return AnalysisTarget(
        "serving_quant_scatter_step", eng._decode_greedy,
        (eng.params, eng.cache_k, eng.cache_v, tokens, pos, active,
         temp, topp, seeds, table), env=eng._lint_env)


def _t_serving_prefill_step() -> AnalysisTarget:
    import jax.numpy as jnp

    eng = _serving_engine()
    bucket = 16
    ids = jnp.zeros((1, bucket), jnp.int32)
    table_row = jnp.asarray(eng._table[0])
    length = jnp.asarray(bucket - 1, jnp.int32)

    # bucket is a static argnum of the compiled prefill: close over it so
    # the analyzed callable is purely array-in/array-out
    def prefill(params, ids, cache_k, cache_v, table_row, length):
        return eng._prefill(params, ids, cache_k, cache_v, table_row,
                            length, bucket)

    return AnalysisTarget(
        "serving_prefill_step", prefill,
        (eng.params, ids, eng.cache_k, eng.cache_v, table_row, length),
        env=eng._lint_env)


def _t_serving_verify_step() -> AnalysisTarget:
    import jax.numpy as jnp

    eng = _serving_engine(_force_flags=("PADDLE_TPU_SPECULATE",),
                          enable_speculation=True, num_draft_tokens=3)
    B = eng.max_batch
    Q = eng._spec_qmax
    # slot 0 mid-decode carrying a full draft, slot 1 idle — the exact data
    # regime the speculative hot loop runs (q_lens/active are DATA, so this
    # one trace covers every per-step raggedness)
    tokens = jnp.zeros((B, Q), jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    active = jnp.asarray([True, False])
    q_lens = jnp.asarray([Q, 1], jnp.int32)
    temp = jnp.zeros((B,), jnp.float32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng._table)
    return AnalysisTarget(
        "serving_verify_step", eng._verify_greedy,
        (eng.params, eng.cache_k, eng.cache_v, tokens, pos, active, q_lens,
         temp, topp, seeds, table), env=eng._lint_env)


def _t_serving_mixed_step() -> AnalysisTarget:
    import jax.numpy as jnp

    eng = _serving_engine(_force_flags=("PADDLE_TPU_CHUNKED_PREFILL",),
                          enable_chunked_prefill=True, prefill_chunk=8)
    B = eng.max_batch
    T = eng._prefill_chunk
    # slot 0 decoding (one live row), slot 1 streaming a full prefill chunk
    # — the exact mixed regime the unified step compiles once for (pos /
    # q_lens / active are DATA, so this one trace covers every token-budget
    # packing the scheduler can emit)
    tokens = jnp.zeros((B, T), jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    active = jnp.asarray([True, True])
    q_lens = jnp.asarray([1, T], jnp.int32)
    temp = jnp.zeros((B,), jnp.float32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng._table)
    return AnalysisTarget(
        "serving_mixed_step", eng._mixed_greedy,
        (eng.params, eng.cache_k, eng.cache_v, tokens, pos, active, q_lens,
         temp, topp, seeds, table), env=eng._lint_env)


def _t_serving_tier_restore() -> AnalysisTarget:
    import jax.numpy as jnp

    # the host-KV-tier re-admit program (ISSUE 13, docs/kv_tier.md): the
    # donated H2D pool write ship_in dispatches per restored page.  The
    # gate pins its shape — ONE in-place dynamic-update per pool, no
    # callbacks: the H2D itself happens OUTSIDE jit (jnp.asarray on the
    # host payload), so the compiled program must stay host_sync-clean,
    # and a device-to-host sync sneaking into the restore hot path is
    # exactly the regression this target exists to catch.
    eng = _serving_engine(
        _force_flags=("PADDLE_TPU_PREFIX_CACHE", "PADDLE_TPU_HOST_KV_TIER"),
        enable_prefix_caching=True, enable_host_kv_tier=True)
    assert eng._tier is not None, "tier target must build the tier engine"
    L, _nb, nkv, bs, hd = eng.cache_k.shape
    page = jnp.zeros((L, nkv, bs, hd), eng.cfg.dtype)
    dst = jnp.asarray(0, jnp.int32)
    return AnalysisTarget(
        "serving_tier_restore", eng._tier_write,
        (eng.cache_k, dst, page), env=eng._lint_env)


def _t_serving_tp_step() -> AnalysisTarget:
    import jax
    import jax.numpy as jnp

    if jax.device_count() < 2:
        # RuntimeError, not SystemExit: lint_gate.py's per-target handler
        # must classify this as "FAILED to build/trace" (exit 2) instead
        # of the exception escaping past it — both CLI entry points force
        # an 8-device host platform pre-init, so this only fires when the
        # backend initialized single-device before the gate ran
        raise RuntimeError(
            "serving_tp_step needs >= 2 devices; run under the test "
            "harness (tests/conftest.py forces 8 CPU devices) or set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    # the TP mixed prefill/decode step over a 2-shard ("tp",) mesh — the
    # one compiled program whose collectives the resharding rule must
    # police (ISSUE 8).  Config sized so the layer's psum operand
    # [B, T, h] (2*64*512 bf16 = 128 KiB) clears the target's lowered
    # gather threshold: the production bar is >=1MiB, and a lint-sized
    # model translates it the way every target here translates shape
    # bounds — same rule, proportionally smaller floor (analyze_kwargs).
    eng = _serving_engine(
        _force_flags=("PADDLE_TPU_CHUNKED_PREFILL",),
        _cfg_kwargs=dict(vocab=128, hidden=512, layers=2, heads=4,
                         kv_heads=2, inter=256),
        enable_chunked_prefill=True, prefill_chunk=64, tensor_parallel=2)
    B = eng.max_batch
    T = eng._prefill_chunk
    tokens = jnp.zeros((B, T), jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    active = jnp.asarray([True, True])
    q_lens = jnp.asarray([1, T], jnp.int32)
    temp = jnp.zeros((B,), jnp.float32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.int32)
    table = jnp.asarray(eng._table)
    return AnalysisTarget(
        "serving_tp_step", eng._mixed_greedy,
        (eng.params, eng.cache_k, eng.cache_v, tokens, pos, active, q_lens,
         temp, topp, seeds, table),
        analyze_kwargs={"min_gather_bytes": 1 << 16}, env=eng._lint_env)


def _hybrid_engine():
    """``models/olmo_hybrid`` (one period L L L F at lint size) behind the
    serving engine's chunked geometry: the step programs and the cache are
    the model's (docs/hybrid_serving.md), the scheduler the engine's."""
    import os

    import jax

    from ..inference.serving import ContinuousBatchingEngine
    from ..models import olmo_hybrid

    cfg = olmo_hybrid.OlmoHybridConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=16)
    pins = {"PADDLE_TPU_CHUNKED_PREFILL": "1",
            "PADDLE_TPU_DISABLE_PALLAS": None, "PADDLE_TPU_TP": None}
    prev = {k: os.environ.get(k) for k in pins}
    try:
        for k, v in pins.items():
            os.environ.pop(k, None) if v is None else \
                os.environ.__setitem__(k, v)
        eng = ContinuousBatchingEngine(
            cfg, olmo_hybrid.init_params(cfg, jax.random.key(0)),
            max_batch=2, max_seq=64, paged=True, block_size=8,
            enable_chunked_prefill=True, prefill_chunk=8)
    finally:
        for k, v in prev.items():
            os.environ.pop(k, None) if v is None else \
                os.environ.__setitem__(k, v)
    eng._lint_env = pins
    return eng


def _t_serving_hybrid_decode_step() -> AnalysisTarget:
    import jax.numpy as jnp

    # one token a slot through three linear-attention layers (the
    # one-token recurrence kernel against the stacked per-slot state, in
    # place) and a full one (the fused rope + append + attention launch)
    eng = _hybrid_engine()
    B = eng.max_batch
    zi = jnp.zeros((B,), jnp.int32)
    return AnalysisTarget(
        "serving_hybrid_decode_step", eng._decode_greedy,
        (eng.params, eng.cache_k, eng.cache_v, zi,
         jnp.asarray([5, 0], jnp.int32), jnp.asarray([True, False]),
         jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32), zi,
         jnp.asarray(eng._table)), env=eng._lint_env)


def _t_serving_hybrid_mixed_step() -> AnalysisTarget:
    import jax.numpy as jnp

    # slot 0 decoding beside slot 1's first chunk (its state started from
    # zero by the program): the chunked recurrence kernel over [B, T], the
    # page-granular K/V append and the ragged prefill kernel
    eng = _hybrid_engine()
    B, T = eng.max_batch, eng._prefill_chunk
    zi = jnp.zeros((B,), jnp.int32)
    return AnalysisTarget(
        "serving_hybrid_mixed_step", eng._mixed_greedy,
        (eng.params, eng.cache_k, eng.cache_v,
         jnp.zeros((B, T), jnp.int32), jnp.asarray([5, 0], jnp.int32),
         jnp.asarray([True, True]), jnp.asarray([1, T], jnp.int32),
         jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32), zi,
         jnp.asarray(eng._table)), env=eng._lint_env)


TARGETS = {
    "llama_train_step": _t_llama_train_step,
    "moe_llama_train_step": _t_moe_train_step,
    "laguna_train_step": _t_laguna_train_step,
    "serving_decode_step": _t_serving_decode_step,
    "serving_flash_decode_step": _t_serving_flash_decode_step,
    "serving_quant_decode_step": _t_serving_quant_decode_step,
    "serving_quant_scatter_step": _t_serving_quant_scatter_step,
    "serving_prefill_step": _t_serving_prefill_step,
    "serving_verify_step": _t_serving_verify_step,
    "serving_mixed_step": _t_serving_mixed_step,
    "serving_tier_restore": _t_serving_tier_restore,
    "serving_tp_step": _t_serving_tp_step,
    "serving_hybrid_decode_step": _t_serving_hybrid_decode_step,
    "serving_hybrid_mixed_step": _t_serving_hybrid_mixed_step,
}

# the CI gate runs every registered target; kept as an explicit list so an
# expensive future target (multi-device compile) can register without
# slowing the tier-1 suite
GATE_TARGETS = ("llama_train_step", "moe_llama_train_step",
                "laguna_train_step", "serving_decode_step", "serving_flash_decode_step",
                "serving_quant_decode_step", "serving_quant_scatter_step",
                "serving_prefill_step", "serving_verify_step",
                "serving_mixed_step", "serving_tier_restore",
                "serving_tp_step", "serving_hybrid_decode_step",
                "serving_hybrid_mixed_step")

# targets that serve from the async host runtime: these additionally run
# the module-scoped host-contract pass (host_contracts.py) — overlap-window
# race/blocking analysis + state-machine protocol verification.  Train
# steps have no host runtime, so they skip it; the pass is memoized, so
# the N serving targets share one AST run per gate sweep.
HOST_TARGETS = tuple(n for n in GATE_TARGETS if n.startswith("serving_"))


def build(name: str) -> AnalysisTarget:
    try:
        builder = TARGETS[name]
    except KeyError:
        raise SystemExit(
            f"unknown target {name!r}; registered: {sorted(TARGETS)}") \
            from None
    return builder()


def run(name: str, **overrides):
    """Build and analyze one registered target (under its env pins — the
    trace must see exactly the program the target declares)."""
    from . import analyze

    t = build(name)
    kwargs = {**t.analyze_kwargs, **overrides}
    kwargs.setdefault("host", t.name in HOST_TARGETS)
    with _pinned_env(t.env):
        return analyze(t.fn, *t.args, target=t.name, **kwargs)


def run_card(name: str, **card_kwargs):
    """Build one registered target and derive just its ProgramCard —
    the cards-only path (``--cards`` CLI, the card-gate tier-1 test): no
    lint rules, no perturbation re-traces; multi-device targets still pay
    one compile for the collective-bytes attribution unless
    ``compile_collectives=False``.  Runs under the target's env pins like
    :func:`run`."""
    from .cost_model import build_card

    t = build(name)
    if name in HOST_TARGETS and "host_contracts" not in card_kwargs:
        from .host_contracts import check_host_contracts

        card_kwargs["host_contracts"] = \
            check_host_contracts(target=name)[1]
    with _pinned_env(t.env):
        return build_card(t.fn, t.args, target=t.name, **card_kwargs)
