"""Runner of kind ``train_model``: the training window of ``runners/train``
for a configuration that names its own program.

The configuration's file holds the model's keys in a ``model`` group or, as
the catalog's check reads them, at its own top level beside the harness's
keys (``model_of``).  It carries ``program`` (a module with
``config_from_dict``, ``make_mesh``, ``build_train_step`` and, for
cumulative counters in the optimizer state, ``COUNTERS``), ``weights``
(``make_params(m, seed, out_shardings)``), ``work`` (the operations and
bytes its readers count) and ``reference`` (``train_readings``).  The job's
``check`` may name, beside the limits: ``zero_counters``, counters of the
program whose total over the run has to be 0, each under the name it is
compared by; ``from_work``, numbers the ``work`` module computes from the
window's ``obs`` (``{compared name: function}``); ``unrouted_grad_gap``, the
leaf names left out of a second gradient gap (the leaves a token's choice of
experts decides, where the program's precision and the reference's part on
ties); ``second_start``, a start of the weights module's own making
(``weights``: its keyword arguments) that the compiled step takes one step
from after the window, compared with the reference as the first steps are,
whose counters have to reach ``at_least``."""

from __future__ import annotations

import gc
import importlib
import math
import os
import time

from ..harness import stats, traffic
from ..harness.cell import (Cell, CompileCount, Tracer, peak_memory_bytes,
                            say, span, timed, within)
from .train import compare, leaf_gap, n_traced


def model_of(config: dict) -> dict:
    """The model's keys: the file's ``model`` group, or the file itself."""
    return config.get("model", config)


def build(cell: Cell, program):
    """(step_fn, opt_init, param_shardings, data_sharding) of the
    configuration's program for this job; the job's ``env`` is the program's
    documented switches, set before tracing."""
    import jax

    job = cell.mix
    for k, v in job.get("env", {}).items():
        os.environ[k] = str(v)
    shape = job.get("mesh", {})
    n = math.prod(shape.values()) if shape else 1
    mesh = program.make_mesh(devices=jax.devices()[:n], **shape)
    return program.build_train_step(
        program.config_from_dict(model_of(cell.config)), mesh,
        **job["optimizer"])


def counters_of(opt: dict, names) -> dict:
    """The cumulative counters in the optimizer state, on the host."""
    import jax
    import numpy as np

    return {k: np.asarray(v, np.int64)
            for k, v in jax.device_get({k: opt[k] for k in names}).items()}


def run(cell: Cell, step_wrap=None, controls=()) -> dict:
    """One run of a training cell: ``runners.train.run`` with the program,
    its weights and its reference taken from the configuration."""
    # the program first: a checkout without it fails here, at once
    program = importlib.import_module(cell.config["program"])
    weights = importlib.import_module(cell.config["weights"])
    import jax
    import jax.numpy as jnp

    job, m = cell.mix, model_of(cell.config)
    batch, seq = job["batch"], job["seq"]
    hp = dict(job["optimizer"], eps=job["optimizer_assumed"]["eps"])
    n_check = job["check"]["steps"]
    names = tuple(getattr(program, "COUNTERS", ()))
    compiles = CompileCount()
    t = {}
    with timed(t, "build_s"):
        step_fn, opt_init, p_shard, d_shard = build(cell, program)
        if step_wrap is not None:
            step_fn = step_wrap(step_fn)
    norms = jax.jit(lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree))
    moved = jax.jit(lambda master, p0: jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a - b.astype(jnp.float32)))), master, p0))
    flat = lambda tree: {
        "/".join(str(p.key) for p in path): float(x) for path, x in
        jax.tree_util.tree_flatten_with_path(tree)[0]}

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

    def follow(n, **start):
        """The first ``n`` steps from the seed's weights (``start``: the
        weights module's keyword arguments), through the window's own call
        and feed -> (the readings the reference is compared with, params,
        opt)."""
        with timed(t, "weights_s"):
            params = weights.make_params(m, cell.seed, out_shardings=p_shard,
                                         **start)
            opt = opt_init(params)
            jax.block_until_ready(opt)
        mine = {"loss": []}
        for k in range(n):
            loss, params, opt = call(k, params, opt)
            mine["loss"].append(loss)
            if k == 0:
                first_m = norms(opt["m"])
        began = weights.make_params(m, cell.seed, out_shardings=p_shard,
                                    **start)
        change = moved(opt["master"], began)
        del began
        mine["loss"] = [float(x) for x in mine["loss"]]
        mine["grad"] = {k: v / (1.0 - hp["beta1"])
                        for k, v in flat(first_m).items()}
        mine["change"] = flat(change)
        return mine, params, opt

    # the first steps compile the step (and the copy of its counters that a
    # traced window takes) and leave the readings the reference is compared
    # with
    snap = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))
    with timed(t, "first_steps_s"):
        mine, params, opt = follow(n_check)
        counted_before = counters_of(snap({k: opt[k] for k in names}), names)
    compiled_before = compiles.n
    traced_before = n_traced(step_fn)

    tracer = Tracer(cell)
    ends: list = []
    pending = None
    at_trace = None
    k = n_check
    t_open = time.perf_counter()
    clock = lambda: time.perf_counter() - t_open
    setup_s = t_open - cell.t0
    while True:
        tracer.maybe_start(clock(), clock)
        if tracer.started is not None and at_trace is None:
            # the counters as the traced steps find them: a copy queued
            # behind the step in flight, nothing waited for
            at_trace = k, snap({name: opt[name] for name in names})
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
    counted = counters_of(opt, names)
    tokens = len(ends) * batch * seq
    obs = {"kind": "train", "step_ends": ends, "window_s": window_s,
           "tokens": tokens, "batch": batch, "seq": seq, "model": m,
           "peak": cell.peak, "seconds": cell.seconds,
           "counters": {name: counted[name] - counted_before[name]
                        for name in names}}
    if at_trace is not None:
        began = counters_of(at_trace[1], names)
        obs["traced"] = {"steps": k - at_trace[0],
                         "counters": {name: counted[name] - began[name]
                                      for name in names}}
    del params, opt, pending, loss, at_trace
    gc.collect()
    obs["trace"] = tracer.reduce()

    check = job["check"]
    limits = check["limits"] or {}
    t_check = time.perf_counter()
    second = check.get("second_start")
    if second:
        # the compiled step once more, from a start that the first steps do
        # not reach (a router that fills several chunks of the grouped
        # products), before the reference takes the chip
        mine2, params2, opt2 = follow(1, **second["weights"])
        counted2 = counters_of(opt2, names)
        del params2, opt2
        gc.collect()
    ref = importlib.import_module(cell.config["reference"])
    batches = [traffic.train_batch(cell.seed, k, batch, seq, m["vocab_size"])
               for k in range(n_check)]
    start = lambda: weights.make_params(m, cell.seed)
    theirs = ref.train_readings(m, start, batches, hp)
    unrouted = check.get("unrouted_grad_gap")

    def gaps(mine, theirs):
        got = compare(mine, theirs)
        if unrouted:
            got["unrouted_grad_gap"] = leaf_gap(
                mine["grad"], theirs["grad"],
                [k for k in theirs["grad"] if k.split("/")[-1] in unrouted])
        return got

    got = gaps(mine, theirs)
    if second:
        theirs2 = ref.train_readings(
            m, lambda: weights.make_params(m, cell.seed, **second["weights"]),
            batches[:1], hp)
        got.update({"second_start_" + k: v
                    for k, v in gaps(mine2, theirs2).items()})
    say(phase="check", seconds=time.perf_counter() - t_check,
        compiles=compiles.n - compiled_before,
        cache_hits=compiles.cache_hits)
    check_s = time.perf_counter() - t_check

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
        numbers = beside_limits(gaps(
            ref.train_readings(m, start, batches, hp, **kw), theirs))
        control[name] = {"compared": numbers, "correct": within(numbers)}
    compared = beside_limits(got)
    # the whole run's total of each counter that has to stay at nought
    for name, counter in check.get("zero_counters", {}).items():
        total = int(counted[counter].sum())
        if second:
            total += int(counted2[counter].sum())
        compared[name] = {"value": total, "limit": 0}
    for name, fn in check.get("from_work", {}).items():
        work = importlib.import_module(cell.config["work"])
        compared[name] = {"value": getattr(work, fn)(obs),
                          "limit": limits.get(name)}
    if second:
        # each counter the second start has to drive that far, at the least
        compared["second_start_short"] = {
            "value": sum(int(counted2[c].min() < least)
                         for c, least in second["at_least"].items()),
            "limit": 0}
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
