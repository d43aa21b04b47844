"""Smoke of the main path on the TPU: the serving engine answers a few
requests and the train step takes a few steps, through the entry points a
user calls.  The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4  # four chips: ONLY the multi-chip paths
                                    # (TP=4 vs TP=1 serving, dp2 x mp2 vs
                                    # one-device training)

One process; it touches JAX once and holds the chip to the end; it starts
no child.  It fails (non-zero exit, no ``"ok": true``) when JAX finds no
TPU, when the Pallas kernels would be interpreted, or when any phase
raises or fails a check.  Earlier lines are JSON per phase (seconds to
first result with compilation included, seconds of the steady repeat,
peak device bytes, kernel counters, the depth cut, and ``failed``: the
checks that did not hold — a failing phase still prints what it saw, and
the phases after it still run); when every phase passed, the last line of
stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

Times printed here are a smoke's, not a benchmark's: one run, no warm-up
discipline, no profiler.  The phases are functions of a config so that
tests/test_chip_smoke.py drives the same control flow on the CPU at
``LlamaConfig.tiny()`` size.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback

# Llama-3-8B's published depth is 32; one 16 GB chip holds 16 layers of it
# in bf16 (9.1 GB of weights) beside the KV pool and the step's activations.
# Widths are never cut.
SERVE_LAYERS = 16
# The largest training config the repo carries for a 16 GB chip (cfg_460m).  The Llama-3-8B widths do not fit one chip with AdamW state:
# the two embedding tables alone are 1.05 B parameters = 14.7 GB of
# bf16 + f32 m/v/master.
TRAIN_CFG = dict(vocab_size=32000, hidden_size=1536, intermediate_size=4096,
                 num_hidden_layers=12, num_attention_heads=12,
                 num_key_value_heads=4)
PROMPT_LENS = (200, 260, 320, 380, 230, 290, 350, 410)


def say(**line) -> None:
    print(json.dumps(line), flush=True)


def peak_bytes(device=None):
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_params(cfg, seed: int):
    """Random weights from a seed, generated on the device already cast
    (no f32 copy of an 8B-width tensor ever materializes)."""
    import jax

    from paddle_tpu.models import llama

    return jax.jit(lambda k: llama.init_params(cfg, k))(jax.random.key(seed))


def make_prompts(cfg, seed: int, lens):
    import numpy as np

    rs = np.random.RandomState(seed)
    return [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def serve_once(engine, prompts, max_new: int):
    from paddle_tpu.inference.serving import Request

    reqs = [Request(rid=i, prompt_ids=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    out = engine.serve(reqs)
    bad = {r.rid: (r.status, r.error) for r in reqs if r.status != "FINISHED"}
    if bad:
        raise RuntimeError(f"requests did not finish: {bad}")
    return [list(map(int, out[i])) for i in range(len(prompts))]


def serve_counters() -> dict:
    from paddle_tpu.ops.pallas import paged_attention as pa

    return {k: getattr(pa, k) for k in dir(pa)
            if k.endswith(("KERNEL_CALLS", "FALLBACK_CALLS"))}


def check_serve_counters(counters: dict) -> list:
    """The kernels a paged, chunked-prefill, default-flag engine selects —
    fused decode step, fused MLP, ragged prefill — were traced, and no
    paged kernel fell back to its XLA reference.  Returns what failed."""
    failed = [f"{k} == 0: kernel path not taken"
              for k in ("FUSED_KERNEL_CALLS", "MLP_KERNEL_CALLS",
                        "PREFILL_KERNEL_CALLS") if counters[k] <= 0]
    fell = {k: v for k, v in counters.items()
            if k.endswith("FALLBACK_CALLS") and v}
    if fell:
        failed.append(f"kernels fell back to the reference: {fell}")
    return failed


def teacher_force(cfg, params, prompts, outputs) -> dict:
    """The engine's greedy tokens against a reference that shares no
    attention code with it: every request's prompt + output goes through
    ``llama.forward`` with XLA attention (no flash, no paged kernels) in
    one right-padded batch, and the engine's token must be the reference's
    argmax at every generated position — not counting near-ties, where the
    reference's logit for the engine's token is within bf16 rounding of its
    maximum: 8 ulps of bf16 (8 significant bits) at the row's largest
    magnitude.  The logits ARE bf16, so at |logit| in [4, 8) the margin
    moves in steps of 2^-5 and two 16-layer bf16 pipelines that round
    differently land a few steps apart; a token picked off wrong pages or
    a wrong mask is whole logit sigmas (>= 1 here) below the maximum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import llama

    n = len(outputs[0])
    width = -(-(max(map(len, prompts)) + n) // 64) * 64
    ids = np.zeros((len(prompts), width), np.int32)
    for row, prompt, out in zip(ids, prompts, outputs):
        row[:len(prompt) + n] = np.concatenate([prompt, out])
    # logits row len(prompt) - 1 + i predicts generated token i
    at = np.asarray([len(p) - 1 for p in prompts])[:, None] + np.arange(n)

    def reference(p, ids, at):
        logits = llama.forward(cfg, p, ids, use_flash=False, remat=False)
        return jnp.take_along_axis(logits, at[..., None], axis=1).astype(
            jnp.float32)

    rows = np.asarray(jax.jit(reference)(params, ids, at))
    rows = rows.reshape(-1, rows.shape[-1])                  # [B * n, V]
    out = np.asarray(outputs).reshape(-1)
    top = rows.max(axis=-1)
    mine = rows[np.arange(out.size), out]
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(rows).max(axis=-1),
                                               1.0))) - 7)
    tol = 8 * ulp
    agree = rows.argmax(axis=-1) == out
    near = ~agree & (top - mine <= tol)
    wrong = ~agree & ~near
    report = {"requests": len(prompts), "positions": int(out.size),
              "argmax_agree": int(agree.sum()),
              "near_tie": int(near.sum()), "wrong": int(wrong.sum()),
              "agree_share": round(float(agree.mean()), 4),
              "worst_margin": float((top - mine).max()),
              "tolerance": float(tol.min()), "failed": []}
    if not np.isfinite(rows).all():
        report["failed"].append("reference logits are not finite")
    if wrong.any():
        report["failed"].append(
            f"{int(wrong.sum())} engine tokens disagree with the XLA "
            f"reference beyond bf16 rounding")
    return report


def serve_phase(cfg, params, prompts, *, max_new=32, max_batch=8,
                max_seq=1024, block_size=64, prefill_chunk=128,
                tensor_parallel=1) -> tuple[list, dict, object]:
    """Build the engine, serve the requests twice (first call compiles,
    second is steady and must repeat the tokens), check the counters.
    Returns (tokens, report, engine)."""
    import jax

    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.ops.pallas import paged_attention as pa

    pa.reset_kernel_counters()
    engine = ContinuousBatchingEngine(
        cfg, params, max_batch=max_batch, max_seq=max_seq, paged=True,
        block_size=block_size, enable_chunked_prefill=True,
        prefill_chunk=prefill_chunk, tensor_parallel=tensor_parallel)
    t0 = time.perf_counter()
    tokens = serve_once(engine, prompts, max_new)
    jax.block_until_ready((engine.cache_k, engine.cache_v))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = serve_once(engine, prompts, max_new)
    jax.block_until_ready((engine.cache_k, engine.cache_v))
    steady_s = time.perf_counter() - t0
    counters = serve_counters()
    failed = check_serve_counters(counters)
    if again != tokens:
        failed.append("the same greedy requests gave other tokens on the "
                      "second serve")
    short = [i for i, t in enumerate(tokens) if len(t) != max_new]
    if short:
        failed.append(f"requests {short} did not produce {max_new} tokens")
    report = {"failed": failed,"requests": len(prompts), "new_tokens": max_new * len(prompts),
              "tensor_parallel": tensor_parallel,
              "first_serve_s_compile_included": round(first_s, 3),
              "steady_serve_s": round(steady_s, 3),
              "decode_steps": int(engine.stats["decode_steps"]),
              "mixed_steps": int(engine.stats["mixed_steps"]),
              "preemptions": int(engine.stats["preemptions"]),
              "counters": counters}
    return tokens, report, engine


def run_serve(cfg, *, seed=0, prompt_lens=PROMPT_LENS, **engine_kw) -> dict:
    params = make_params(cfg, seed)
    prompts = make_prompts(cfg, seed, prompt_lens)
    tokens, report, engine = serve_phase(cfg, params, prompts, **engine_kw)
    del engine
    forced = teacher_force(cfg, params, prompts, tokens)
    report["failed"] += forced.pop("failed")
    report["teacher_forced"] = forced
    report["peak_bytes_in_use"] = peak_bytes()
    return report


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_phase(cfg, mesh, *, batch, seq, steps, seed=0) -> dict:
    """``steps`` AdamW steps of ``llama.build_train_step`` on ONE fixed
    batch: the loss is finite and lower at the last step than at the
    first, and attention took the flash kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import llama
    from paddle_tpu.ops.pallas import flash_attention as fa

    k0, f0 = fa.KERNEL_CALLS, fa.FALLBACK_CALLS
    step_fn, opt_init, param_shardings, data_sharding = \
        llama.build_train_step(cfg, mesh)
    params = jax.jit(lambda k: llama.init_params(cfg, k),
                     out_shardings=param_shardings)(jax.random.key(seed))
    opt_state = opt_init(params)
    rs = np.random.RandomState(seed)
    ids, labels = (jax.device_put(
        jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32),
        data_sharding) for _ in range(2))
    t0 = time.perf_counter()
    loss, params, opt_state = step_fn(params, opt_state, ids, labels)
    jax.block_until_ready(loss)
    first_s = time.perf_counter() - t0
    losses = [loss]
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        loss, params, opt_state = step_fn(params, opt_state, ids, labels)
        losses.append(loss)
    jax.block_until_ready(loss)
    steady_s = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    counters = {"KERNEL_CALLS": fa.KERNEL_CALLS - k0,
                "FALLBACK_CALLS": fa.FALLBACK_CALLS - f0}
    failed = []
    if not all(np.isfinite(losses)):
        failed.append("loss is not finite")
    if not losses[-1] < losses[0]:
        failed.append("loss did not fall on a fixed batch")
    if counters["KERNEL_CALLS"] <= 0 or counters["FALLBACK_CALLS"]:
        failed.append("flash attention did not take its kernel")
    return {"failed": failed,
              "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
              "batch": batch, "seq": seq, "steps": steps, "losses": losses,
              "first_step_s_compile_included": round(first_s, 3),
              "steady_steps_s": round(steady_s, 3),
              "flash_attention": counters,
              "peak_bytes_in_use": peak_bytes()}


# ---------------------------------------------------------------------------
# four chips: only what exists across chips, and what it is compared with
# ---------------------------------------------------------------------------

def device_shares(tree) -> dict:
    """Bytes each device holds of a pytree of (possibly sharded) arrays."""
    import jax

    held: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    return held


def run_tp_serve(cfg, tp: int, *, seed=0, prompt_lens=PROMPT_LENS,
                 **engine_kw) -> dict:
    """The serve phase with ``tensor_parallel=tp`` against
    ``tensor_parallel=1`` in this process on the same weights and requests:
    every device holds 1/tp of the sharded weights and of the KV pool, and
    both engines' greedy tokens hold against the XLA reference.  Whether the
    two token streams are IDENTICAL is reported, not required: in bf16 the
    TP psum adds tp rounded partial products where one chip rounds once, so
    a near-tied argmax may flip (on the CPU at f32 they are identical, which
    tests/test_tp_serving.py pins); teacher forcing bounds both streams
    within bf16 rounding of the same reference row at the first position
    they part."""
    import jax

    params = make_params(cfg, seed)
    prompts = make_prompts(cfg, seed, prompt_lens)
    one, rep1, engine = serve_phase(cfg, params, prompts, **engine_kw)
    del engine
    gc.collect()
    many, rept, engine = serve_phase(cfg, params, prompts,
                                     tensor_parallel=tp, **engine_kw)
    failed = rep1.pop("failed") + rept.pop("failed")
    parted = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
              for a, b in zip(one, many)]
    # matmul leaves and the pool shard; embedding, norms and lm_head
    # replicate by design (models/llama.serving_param_specs)
    layers = {k: v for k, v in engine.params["layers"].items()
              if not k.endswith("_norm")}
    weights = device_shares(layers)
    pool = device_shares((engine.cache_k, engine.cache_v))
    for name, held in (("weights", weights), ("kv_pool", pool)):
        total = sum(held.values())
        if len(held) != tp or max(held.values()) * tp > total * 1.02:
            failed.append(f"{name} are not spread 1/{tp} per device")
    replicated = device_shares(
        [engine.params["embed"], engine.params.get("lm_head", ())]
    ).get(jax.devices()[0].id)
    del engine
    gc.collect()
    for name, tokens, rep in (("tp1", one, rep1), (f"tp{tp}", many, rept)):
        forced = teacher_force(cfg, params, prompts, tokens)
        failed += [f"{name}: {f}" for f in forced.pop("failed")]
        rep["teacher_forced"] = forced
    report = {"failed": failed, "tp1": rep1, f"tp{tp}": rept,
              "tokens_identical": one == many,
              "first_position_parted_per_request": parted,
              "sharded_weight_bytes_per_device": weights,
              "kv_pool_bytes_per_device": pool,
              "replicated_bytes_per_device": replicated,
              "peak_bytes_in_use_per_device": {
                  d.id: peak_bytes(d) for d in jax.devices()[:tp]}}
    return report


def run_mesh_train(cfg, *, dp=2, mp=2, batch, seq, steps=3, seed=0) -> dict:
    """``build_train_step`` on a dp x mp mesh against the one-device mesh,
    same seed and batch: the losses agree within bf16 tolerance."""
    import jax
    import numpy as np

    from paddle_tpu.models import llama

    one = train_phase(cfg, llama.make_mesh(devices=jax.devices()[:1]),
                      batch=batch, seq=seq, steps=steps, seed=seed)
    gc.collect()
    many = train_phase(cfg, llama.make_mesh(dp=dp, mp=mp), batch=batch,
                       seq=seq, steps=steps, seed=seed)
    failed = one.pop("failed") + many.pop("failed")
    a, b = np.asarray(one["losses"]), np.asarray(many["losses"])
    if not np.allclose(a, b, rtol=2e-2, atol=0):
        failed.append(f"dp{dp} x mp{mp} losses differ from the one-device "
                      f"losses beyond bf16 tolerance")
    return {"failed": failed, "one_device": one, f"dp{dp}_mp{mp}": many,
            "max_rel_loss_diff": float(np.max(np.abs(a - b) / np.abs(a)))}


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from paddle_tpu.models import llama
    from paddle_tpu.ops import pallas
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()                  # the one touch: takes the chip
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU here (JAX found {device})",
              file=sys.stderr)
        return 1
    if pallas.interpret_mode():
        print("chip_smoke: Pallas kernels would be interpreted",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    say(phase="start", device=device, compile_cache_dir=cache_dir,
        note="times below are a smoke's, not a benchmark's")

    full = llama.LlamaConfig.llama3_8b()
    serve_cfg = dataclasses.replace(full, num_hidden_layers=SERVE_LAYERS)
    cut = (f"llama3_8b widths (hidden {full.hidden_size}, ffn "
           f"{full.intermediate_size}, {full.num_attention_heads}/"
           f"{full.num_key_value_heads} heads, vocab {full.vocab_size}), "
           f"bf16; depth cut {full.num_hidden_layers} -> {SERVE_LAYERS} "
           f"layers: what one 16 GB chip holds (9.1 GB of weights)")
    train_cfg = llama.LlamaConfig(**TRAIN_CFG)
    train_note = ("cfg_460m, batch 8 x seq 2048: the llama3_8b "
                  "widths do not fit one chip with AdamW state (the "
                  "embedding tables alone are 1.05 B parameters)")

    if args.chips == 1:
        phases = [
            ("serve", cut, lambda: run_serve(serve_cfg, seed=args.seed)),
            ("train", train_note, lambda: train_phase(
                train_cfg, llama.make_mesh(devices=devices[:1]), batch=8,
                seq=2048, steps=5, seed=args.seed)),
        ]
    else:
        phases = [
            ("serve_tp4_vs_tp1", cut,
             lambda: run_tp_serve(serve_cfg, 4, seed=args.seed)),
            ("train_dp2_mp2_vs_one_device", train_note,
             lambda: run_mesh_train(train_cfg, batch=8, seq=2048, steps=3,
                                    seed=args.seed)),
        ]
    ok = True
    for name, config, phase in phases:
        try:
            report = phase()
        except Exception:
            traceback.print_exc()
            report = {"failed": [traceback.format_exc(limit=1)
                                 .strip().splitlines()[-1]]}
        say(phase=name, config=config, **report)
        ok = ok and not report["failed"]
        gc.collect()
    if not ok:
        print("chip_smoke: FAILED (see the phases' \"failed\" lists)",
              file=sys.stderr)
        return 1
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
