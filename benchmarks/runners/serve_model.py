"""Runner of kind ``serve_model``: the serving window of ``runners/serve``
for a configuration that names its own program.

The configuration's file holds the model's keys at its own top level, as
the catalog's check reads them, or in a ``model`` group
(``train_model.model_of``).  It carries ``program`` (a module with
``config_from_dict``, whose config the serving engine takes its step
programs and cache from), ``weights`` (``make_params(m, seed)``), ``work``
(``window_work`` and the kernels' operations and bytes) and ``reference``
(``served_gap``).  Window, drive, end-to-end metrics and the check are
``runners/serve``'s own, imported; what is added is the engine's counters
over the traced steps themselves (``obs["traced"]``, as ``train_model``
keeps the optimizer state's).

    python3 -m benchmarks.runners.serve_model --workload <cell> --seed 1 \\
        --seconds 60 --rates 0.3,0.4,0.5 [--out chiprun_out/sweep.jsonl]

finds the mix's knee as ``prove.py --rates`` does for a dense cell (whose
sweep builds ``models/llama``): one engine, an open-loop window a rate."""

from __future__ import annotations

import copy
import dataclasses
import gc
import importlib
import math
import time

import numpy as np

from ..harness import stats, traffic
from ..harness.cell import (Cell, CompileCount, Tracer, peak_memory_bytes,
                            say, timed, within)
from . import serve
from .train_model import model_of


def build(cell: Cell, seed: int):
    """(engine, params, model keys) of the configuration's program."""
    # the program first: a checkout without it fails here, at once
    program = importlib.import_module(cell.config["program"])
    weights = importlib.import_module(cell.config["weights"])
    import jax

    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    m = model_of(cell.config)
    t = {}
    with timed(t, "weights_s"):
        params = weights.make_params(m, seed)
        jax.block_until_ready(params)
    with timed(t, "engine_s"):
        engine = ContinuousBatchingEngine(program.config_from_dict(m),
                                          params, **cell.mix["engine"])
    return engine, params, m, t


def as_serve_sees(cell: Cell, m: dict) -> Cell:
    """The cell with the model's keys under ``model`` too, where
    ``runners/serve``'s warm-up and check look for them."""
    return dataclasses.replace(cell, config=dict(cell.config, model=m))


def control_outputs(cell: Cell, params, sample, lower) -> dict:
    """``serve.check_outputs`` for the control ``lower``, and beside the
    gap the widest change the control made to a logit it was compared on:
    a control that moved none was no control (the reference's
    ``control_gap``)."""
    ref = importlib.import_module(cell.config["reference"])
    read = [ref.control_gap(cell.config["model"], params,
                            tr.plan.prompt_ids,
                            np.asarray(tr.req.output_ids, np.int32), lower)
            for tr in sample]
    gaps = np.concatenate([g for g, _ in read] or [[math.inf]])
    return {"logit_gap": float(gaps.max()) if np.isfinite(gaps).all()
            else math.inf,
            "logit_moved": max([d for _, d in read] or [0.0]),
            "tokens": sum(g.size for g, _ in read)}


class TracedCounters:
    """``engine.stats`` before the first traced step and after the last, by
    a step hook: the counters' change over exactly the steps whose device
    events the trace holds."""

    def __init__(self, tracer: Tracer, inner=None):
        self.tracer, self.inner = tracer, inner
        self.last = self.at_start = self.at_stop = None
        self.steps = 0

    def __call__(self, engine, tracks):
        now = serve.numbers(engine.stats)
        if self.tracer.started is not None:
            if self.at_start is None:
                self.at_start = self.last or {}
            if self.tracer.stopped is None:
                self.steps += 1
            elif self.at_stop is None:
                self.at_stop = self.last
        self.last = now
        if self.inner is not None:
            self.inner(engine, tracks)

    def delta(self):
        if self.at_start is None:
            return None
        end = self.at_stop or self.last
        return {"steps": self.steps,
                "counters": {k: v - self.at_start.get(k, 0)
                             for k, v in end.items()}}


def run(cell: Cell, step_hook=None, controls=()) -> dict:
    """One run of a serving cell whose program the configuration names.
    ``step_hook(engine, tracks)`` and ``controls`` as ``runners/serve.run``
    has them."""
    mix = cell.mix
    backlog = serve.is_backlog(mix)
    compiles = CompileCount()
    engine, params, m, t = build(cell, cell.seed)
    work = importlib.import_module(cell.config["work"])
    seen = as_serve_sees(cell, m)
    plan = traffic.requests(mix, cell.seconds, cell.seed, m["vocab_size"])
    with timed(t, "warm_up_s"):
        serve.warm_up(engine, seen)
    tracer = Tracer(cell)
    traced = TracedCounters(tracer, step_hook)
    preroll = float(mix.get("preroll_s", 0.0))
    traces_before = engine.n_traces() or 0
    t_loop = time.perf_counter()
    tracks, steps, late, compiled_at_open, counters = serve.drive(
        engine, plan, cell, tracer, compiles, traced)
    setup_s = t_loop + preroll - cell.t0    # the pre-roll is set-up
    in_window_compiles = compiles.n - compiled_at_open
    retraced = (engine.n_traces() or 0) - traces_before
    say(phase="window", setup=t, generator_lateness_ms={
        "p50": stats.percentile(late, 0.5) * 1e3,
        "max": max(late) * 1e3} if late else None,
        steps=len(steps), retraced=retraced, compiles=in_window_compiles)
    if retraced or in_window_compiles:
        raise RuntimeError(
            f"{retraced} program(s) traced anew and {in_window_compiles} "
            f"compiled after warm-up: a shape was not warmed")
    memory_peak = peak_memory_bytes(cell.chips)
    mine = [tr for tr in tracks if serve.in_window(tr, backlog, cell.seconds)]
    never = [tr.req.rid for tr in mine if serve.failed(tr, backlog)]
    sample = serve.sample_for_check(mine, mix["check"]["requests"],
                                    cell.seed)
    obs = {"kind": "serve", "tracks": tracks, "steps": steps,
           "counters": counters, "seconds": cell.seconds,
           "in_window": mine, "backlog": backlog,
           "work": work.window_work(tracks, steps, m, cell.seconds),
           "model": m, "peak": cell.peak, "engine_args": mix["engine"]}
    if traced.delta() is not None:
        obs["traced"] = traced.delta()
    del engine
    gc.collect()
    obs["trace"] = tracer.reduce()
    t_check = time.perf_counter()
    limit = mix["check"]["logit_gap_limit"]

    def compare(lower=None) -> dict:
        got = (serve.check_outputs(seen, params, sample) if lower is None
               else control_outputs(seen, params, sample, lower))
        compared = {
            "logit_gap": {"value": got["logit_gap"], "limit": limit},
            "never_answered": {"value": len(never), "limit": 0},
            "tokens_compared": {"value": got["tokens"], "limit": None},
        }
        if lower is not None:
            compared["logit_moved"] = {"value": got["logit_moved"],
                                       "limit": None}
        return compared

    compared = compare()
    check_s = time.perf_counter() - t_check
    say(phase="check", seconds=check_s, cache_hits=compiles.cache_hits)
    control = {}
    for lower in controls:
        beside = compare(lower)
        control[lower] = {"compared": beside, "correct": within(beside)}
    return {
        "control": control,
        "attempted": len(mine), "failed": len(never),
        "end_to_end": dict(serve.end_to_end(tracks, backlog, cell.seconds),
                           setup_s=setup_s),
        "obs": obs, "memory_peak_bytes": memory_peak,
        "check_s": check_s,
        "compared": compared,
        "correct": within(compared),
    }


def sweep(cell_args: dict, seed: int, rates, emit) -> None:
    """Open-loop windows at ``rates`` requests/s on one engine; a line a
    rate through ``emit(**line)``, with the fields of ``prove.py``'s
    sweep."""
    cell = Cell(seed=seed, t0=time.perf_counter(), **cell_args)
    engine, _, m, _ = build(cell, seed)
    serve.warm_up(engine, as_serve_sees(cell, m))
    for rate in rates:
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"] = {"process": "poisson", "requests_per_second": rate}
        phase = Cell(**dict(cell_args, mix=mix), seed=seed, t0=cell.t0)
        plan = traffic.requests(mix, phase.seconds, seed, m["vocab_size"])
        tracks, steps, *_ = serve.drive(engine, plan, phase, Tracer(phase))
        e2e = serve.end_to_end(tracks, False, phase.seconds)
        mine = [t for t in tracks if serve.in_window(t, False, phase.seconds)]
        ttft = [(t.token_s[0] - t.plan.due_s) * 1e3 for t in mine
                if t.token_s]
        marks = [phase.seconds * f for f in (0.25, 0.5, 0.75, 1.0)]
        emit(sweep=rate, offered_per_s=rate,
             completed_per_s=(e2e["requests_completed_in_window"]
                              / phase.seconds),
             requests=len(mine), end_to_end=e2e,
             ttft_p50_ms=stats.percentile(ttft, 0.5) if ttft else None,
             in_system=[sum(1 for t in tracks if t.plan.due_s <= at
                            and (t.done_s is None or t.done_s > at))
                        for at in marks],
             waiting=[sum(1 for t in tracks if t.plan.due_s <= at
                          and (t.running_s is None or t.running_s > at))
                      for at in marks],
             steps=len(steps),
             failed=sum(1 for t in mine if serve.failed(t, False)),
             cut=sum(1 for t in mine if t.cut))


def main(argv=None) -> int:
    import argparse
    import functools

    from .. import prove, run as bench
    from ..harness.cell import ROOT, load_json

    ap = argparse.ArgumentParser(description="the rate sweep of a "
                                 "serve_model cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    w, config, mix = bench.find_cell(load_json(ROOT, "BENCHMARK.json"),
                                     args.workload)
    _, peak = bench.open_chip(w["chips"])
    sweep(dict(name=w["name"], config=config, mix=mix, chips=w["chips"],
               seconds=args.seconds, trace=False, peak=peak), args.seed,
          [float(x) for x in args.rates.split(",")],
          functools.partial(prove.emit, args.out))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
