"""Runner of kind ``train``: AdamW steps through the jitted step that
``llama.build_train_step`` returns, on a one-device mesh."""

from __future__ import annotations

import gc
import importlib
import math
import os
import statistics
import time

from ..harness import stats, traffic, weights
from ..harness.cell import (Cell, CompileCount, Tracer, peak_memory_bytes,
                            program_config, say, span, timed, within)


def build(cell: Cell):
    """(step_fn, opt_init, param_shardings, data_sharding): the program's
    step for this job.  The job's ``env`` is the program's documented
    switches, set before tracing."""
    import jax

    from paddle_tpu.models import llama

    job = cell.mix
    for k, v in job.get("env", {}).items():
        os.environ[k] = str(v)
    shape = job.get("mesh", {})
    n = math.prod(shape.values()) if shape else 1
    mesh = llama.make_mesh(devices=jax.devices()[:n], **shape)
    return llama.build_train_step(program_config(cell.config["model"]), mesh,
                                  **job["optimizer"])


def n_traced(step_fn) -> int:
    """Programs traced for the jitted step (0 for a test's wrapper)."""
    return getattr(step_fn, "_cache_size", lambda: 0)()


def leaf_gap(mine: dict, ref: dict, skip=()) -> float:
    """Worst leaf: the gap between the two norms against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(ref.values())
    return max(abs(mine[k] - ref[k]) / max(ref[k], floor)
               for k in ref if k not in skip)


def compare(mine: dict, ref: dict) -> dict:
    """The numbers compared, program (``mine``) against reference: worst
    relative loss gap of the followed steps, worst-leaf gap of the first
    gradient's norm, worst-leaf gap of the norm of the parameters' change.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of the change."""
    floor = 1e-3 * statistics.median(ref["grad_raw"].values())
    still = [k for k, v in ref["grad_raw"].items() if v < floor]
    losses = [abs(a - b) / abs(b) for a, b in zip(mine["loss"], ref["loss"])]
    out = {"loss_gap": max(losses),
           "grad_gap": leaf_gap(mine["grad"], ref["grad"]),
           "change_gap": leaf_gap(mine["change"], ref["change"], still)}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def run(cell: Cell, step_wrap=None, controls=()) -> dict:
    """One run of a training cell.  ``step_wrap(step_fn) -> step_fn`` is for
    the tests that break the timed path underneath; ``controls`` names
    lower precisions ("int8", "fp8") and faults ("half_batch") planted in
    the reference, whose readings ``prove.py`` and the tests want beside
    the program's (the benchmark's own runs ask for none)."""
    import jax
    import jax.numpy as jnp

    job, m = cell.mix, cell.config["model"]
    batch, seq = job["batch"], job["seq"]
    hp = dict(job["optimizer"], eps=job["optimizer_assumed"]["eps"])
    n_check = job["check"]["steps"]
    compiles = CompileCount()
    t = {}
    with timed(t, "build_s"):
        step_fn, opt_init, p_shard, d_shard = build(cell)
        if step_wrap is not None:
            step_fn = step_wrap(step_fn)
    with timed(t, "weights_s"):
        params = weights.make_params(m, cell.seed, out_shardings=p_shard)
        opt = opt_init(params)
        jax.block_until_ready(opt)
    norms = jax.jit(lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree))
    moved = jax.jit(lambda master, p0: jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a - b.astype(jnp.float32)))), master, p0))

    def feed(k):
        with span("bench/feed"):
            ids, labels = traffic.train_batch(cell.seed, k, batch, seq,
                                              m["vocab_size"])
            return (jax.device_put(ids, d_shard),
                    jax.device_put(labels, d_shard))

    def call(k, params, opt):
        ids, labels = feed(k)
        with span("bench/train_step.dispatch"):
            return step_fn(params, opt, ids, labels)

    # the first steps, through the window's own call and feed; they compile
    # the step and leave the readings the reference is compared with
    mine = {"loss": []}
    with timed(t, "first_steps_s"):
        for k in range(n_check):
            loss, params, opt = call(k, params, opt)
            mine["loss"].append(loss)
            if k == 0:
                first_m = norms(opt["m"])
        start = weights.make_params(m, cell.seed, out_shardings=p_shard)
        change = moved(opt["master"], start)
        del start
        flat = lambda tree: {
            "/".join(str(p.key) for p in path): float(x) for path, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
        mine["loss"] = [float(x) for x in mine["loss"]]
        mine["grad"] = {k: v / (1.0 - hp["beta1"])
                        for k, v in flat(first_m).items()}
        mine["change"] = flat(change)
    compiled_before = compiles.n
    traced_before = n_traced(step_fn)

    tracer = Tracer(cell)
    ends: list = []
    pending = None
    k = n_check
    t_open = time.perf_counter()
    clock = lambda: time.perf_counter() - t_open
    setup_s = t_open - cell.t0
    while True:
        tracer.maybe_start(clock(), clock)
        loss, params, opt = call(k, params, opt)
        k += 1
        if pending is not None:
            with span("bench/train_step.wait"):
                jax.block_until_ready(pending)
            ends.append(clock())
        pending = loss
        if clock() >= cell.seconds:
            break
    jax.block_until_ready(pending)
    ends.append(clock())
    tracer.stop(clock)
    window_s = ends[-1]
    last_loss = float(pending)
    retraced = n_traced(step_fn) - traced_before
    in_window_compiles = compiles.n - compiled_before
    say(phase="window", setup=t, steps=len(ends), window_s=window_s,
        retraced=retraced, compiles=in_window_compiles)
    if retraced or in_window_compiles:
        raise RuntimeError(
            f"{retraced} program(s) traced anew and {in_window_compiles} "
            f"compiled inside the window")
    memory_peak = peak_memory_bytes(cell.chips)
    tokens = len(ends) * batch * seq
    obs = {"kind": "train", "step_ends": ends, "window_s": window_s,
           "tokens": tokens, "batch": batch, "seq": seq, "model": m,
           "peak": cell.peak, "seconds": cell.seconds}
    del params, opt, pending, loss
    gc.collect()
    obs["trace"] = tracer.reduce()

    t_check = time.perf_counter()
    ref = importlib.import_module(cell.config["reference"])
    batches = [traffic.train_batch(cell.seed, k, batch, seq, m["vocab_size"])
               for k in range(n_check)]
    start = lambda: weights.make_params(m, cell.seed)
    theirs = ref.train_readings(m, start, batches, hp)
    got = compare(mine, theirs)
    say(phase="check", seconds=time.perf_counter() - t_check,
        compiles=compiles.n - compiled_before,
        cache_hits=compiles.cache_hits)
    check_s = time.perf_counter() - t_check
    limits = job["check"]["limits"] or {}

    def beside_limits(got: dict) -> dict:
        out = {k: {"value": v, "limit": limits.get(k)}
               for k, v in got.items()}
        out["last_loss_not_finite"] = {
            "value": int(not math.isfinite(last_loss)), "limit": 0}
        return out

    control = {}
    for name in controls:
        kw = ({"rows": range(batch // 2)} if name == "half_batch"
              else {"lower": name})
        numbers = beside_limits(compare(
            ref.train_readings(m, start, batches, hp, **kw), theirs))
        control[name] = {"compared": numbers, "correct": within(numbers)}
    compared = beside_limits(got)
    return {
        "control": control,
        "attempted": len(ends), "failed": 0,
        "end_to_end": {"train_tokens_per_s": stats.rate(tokens, window_s),
                       "setup_s": setup_s},
        "obs": obs, "memory_peak_bytes": memory_peak,
        "check_s": check_s,
        "compared": compared,
        "readings": {"program": mine, "reference": theirs},
        "correct": within(compared),
    }
