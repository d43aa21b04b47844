"""Runner of kind ``serve``: one ``ContinuousBatchingEngine`` on one chip,
driven through ``add_request()`` / ``step()`` on the harness's own clock."""

from __future__ import annotations

import gc
import importlib
import math
import time
from typing import NamedTuple

import numpy as np

from ..harness import stats, traffic, weights, work
from ..harness.cell import (Cell, CompileCount, Tracer, peak_memory_bytes,
                            program_config, say, span, timed, within)

TERMINAL = ("FINISHED", "FAILED", "REJECTED", "CANCELLED", "EXPIRED")


class Track:
    """What the harness saw of one request."""

    def __init__(self, plan, req):
        self.plan, self.req = plan, req
        self.handed_s = None        # when add_request() was called
        self.running_s = None       # end of the first step after which it ran
        self.token_s: list = []     # when each output token was seen
        self.done_s = None
        self.cut = False            # withdrawn by the harness at the close


class Driven(NamedTuple):
    """What ``drive`` saw.  ``steps`` rows are (t0, t1, pages in use, rows
    that gave a token), times relative to the window's opening."""
    tracks: list
    steps: list
    late: list              # hand-over lateness of each request, seconds
    compiled_at_open: int   # CompileCount.n when the window opened
    counters: dict          # engine.stats, close minus opening


def is_backlog(mix: dict) -> bool:
    return mix["arrivals"]["process"] == "backlog"


def build_engine(cell: Cell, params):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(program_config(cell.config["model"]),
                                    params, **cell.mix["engine"])


def warm_up(engine, cell: Cell) -> None:
    """Compile (or read from the cache) exactly the programs the window
    uses, the mixed step and the decode step, by serving a short burst."""
    from paddle_tpu.inference.serving import Request

    w = cell.mix["warmup"]
    rng = np.random.default_rng([cell.seed, 0x3A93])
    vocab = cell.config["model"]["vocab_size"]
    for i in range(w["requests"]):
        engine.add_request(Request(
            rid=-1 - i, max_new_tokens=w["max_new_tokens"],
            prompt_ids=rng.integers(1, vocab, w["prompt_tokens"] + 16 * i,
                                    dtype=np.int32)))
    while engine.step():
        pass


def pages_in_use(engine) -> int:
    # a private read: engine.stats has no gauge of the pool (PERF.md, Open
    # questions: the tracing issue replaces it with a public one)
    return engine.num_blocks - len(engine._free)


def drive(engine, plan, cell: Cell, tracer: Tracer, compiles=None,
          step_hook=None):
    """Pre-roll, window, drain: hand each request over when it is due,
    step the engine, note what came of it."""
    from paddle_tpu.inference.serving import Request

    mix = cell.mix
    backlog = is_backlog(mix)
    preroll, seconds = float(mix.get("preroll_s", 0.0)), cell.seconds
    tracks = [Track(p, Request(rid=p.rid, prompt_ids=p.prompt_ids,
                               max_new_tokens=p.max_new_tokens))
              for p in plan]
    live: list = []
    steps: list = []
    nxt = 0
    t_open = time.perf_counter() + preroll
    clock = lambda: time.perf_counter() - t_open
    closed = False
    firsts = 0              # requests that have said a first token
    compiled_at_open = None
    while True:
        now = clock()
        if compiled_at_open is None and now >= 0.0:
            compiled_at_open = compiles.n if compiles is not None else 0
            stats_open = numbers(engine.stats)
        if not closed and now >= seconds:
            closed = True
            counters = {k: v - stats_open.get(k, 0)
                        for k, v in numbers(engine.stats).items()}
            tracer.stop(clock)
            firsts_at_close = firsts
        if closed and (not live or now >= seconds + mix.get("drain_s", 60.0)
                       or (backlog and firsts > firsts_at_close)):
            # open loop: answers still streaming a drain after the close are
            # cut: late is not wrong, and their tokens so far count.  A
            # backlog goes on after the window: it is stepped until the
            # next prompt says its first token (which dates the rows that
            # prompt was given before the close), then withdrawn, not failed
            withdraw(engine, live)
            break
        if not closed:
            tracer.maybe_start(now, clock)
            with span("bench/hand_over"):
                while nxt < len(tracks) and tracks[nxt].plan.due_s <= now:
                    tr = tracks[nxt]
                    engine.add_request(tr.req)
                    tr.handed_s = clock()
                    live.append(tr)
                    nxt += 1
        with span("bench/engine.step"):
            t0 = clock()
            busy = engine.step()
            t1 = clock()
        if not busy:
            with span("bench/wait_for_arrival"):
                time.sleep(0.0005)
            continue
        with span("bench/bookkeeping"):
            rows = 0
            for tr in live:
                n = len(tr.req.output_ids)
                if n > len(tr.token_s):
                    rows += 1
                    firsts += not tr.token_s
                    tr.token_s.extend([t1] * (n - len(tr.token_s)))
                if tr.running_s is None and (n or tr.req.status != "PENDING"):
                    tr.running_s = t1
                if tr.req.status in TERMINAL:
                    tr.done_s = t1
            live = [tr for tr in live if tr.done_s is None]
            steps.append((t0, t1, pages_in_use(engine), rows))
            if step_hook is not None:
                step_hook(engine, tracks)
    late = [tr.handed_s - max(tr.plan.due_s, -preroll)
            for tr in tracks if tr.handed_s is not None]
    return Driven(tracks, steps, late, compiled_at_open, counters)


def numbers(stats) -> dict:
    return {k: v for k, v in dict(stats).items()
            if isinstance(v, (int, float))}


def withdraw(engine, tracks) -> None:
    for tr in tracks:
        if tr.handed_s is not None and tr.req.status not in TERMINAL:
            tr.cut = True
            engine.cancel(tr.req.rid)


def in_window(tr: Track, backlog: bool, seconds: float) -> bool:
    """Open loop: due inside the window.  Backlog: in service at some
    instant of the window."""
    if not backlog:
        return 0.0 <= tr.plan.due_s < seconds
    return (tr.running_s is not None and tr.running_s < seconds
            and (tr.done_s is None or tr.done_s >= 0.0))


def finished_ok(tr: Track) -> bool:
    return (tr.req.status == "FINISHED"
            and len(tr.req.output_ids) == tr.plan.max_new_tokens)


def failed(tr: Track, backlog: bool) -> bool:
    """A request the system ended itself short of its answer, or (open
    loop) one that had not said a first token when it was cut."""
    if finished_ok(tr):
        return False
    if tr.cut:
        return not backlog and not tr.token_s
    return True


def end_to_end(tracks, backlog: bool, seconds: float) -> dict:
    mine = [tr for tr in tracks if in_window(tr, backlog, seconds)]
    ttft = [(tr.token_s[0] - tr.plan.due_s) * 1e3 if tr.token_s else math.inf
            for tr in mine]
    gaps = [g * 1e3 for tr in mine for g in stats.token_gaps(tr.token_s)]
    done = [tr for tr in tracks if finished_ok(tr)
            and 0.0 <= tr.done_s < seconds]
    # every token served inside [0, seconds): a generated token at the
    # instant the harness saw it, a prompt's rows spread back from its
    # first token (stats.prefill_spans), so that a prompt astride an edge
    # of the window counts by its part inside
    generated = sum(1 for tr in tracks for t in tr.token_s
                    if 0.0 <= t < seconds)
    prefilled = stats.spread_between(stats.prefill_spans(
        [(tr.handed_s, tr.token_s[0], tr.plan.prompt_ids.size)
         for tr in tracks if tr.token_s]), 0.0, seconds)
    out = {"requests_completed_in_window": len(done),
           "generated_tokens_in_window": generated,
           "prompt_tokens_in_window": prefilled,
           "serve_tokens_per_s": stats.rate(generated + prefilled, seconds)}
    if ttft:
        out["ttft_p95_ms"] = stats.percentile(ttft, 0.95)
    if gaps:
        out["itl_p95_ms"] = stats.percentile(gaps, 0.95)
    return out


def window_work(tracks, steps, m: dict, seconds: float) -> dict:
    """Operations and least bytes of what the window served, from what the
    harness saw: a prompt's rows are credited when its first token comes,
    a decode row when its token comes."""
    rows = pairs = logits = 0
    kv_read_tokens = kv_written_tokens = 0
    for tr in tracks:
        p = tr.plan.prompt_ids.size
        for i, t in enumerate(tr.token_s):
            if not 0.0 <= t < seconds:
                continue
            logits += 1
            if i == 0:
                rows += p
                pairs += work.causal_pairs(p)
                kv_written_tokens += p
            else:
                rows += 1
                pairs += p + i
                kv_read_tokens += p + i
                kv_written_tokens += 1
    n_steps = sum(1 for t0, t1, *_ in steps if 0.0 <= t1 < seconds)
    kvb = work.kv_bytes_per_token(m)
    return {"flops": work.serve_row_flops(m, rows, pairs, logits),
            "bytes": (n_steps * work.weight_bytes_per_step(m)
                      + (kv_read_tokens + kv_written_tokens) * kvb),
            "steps": n_steps, "rows": rows}


def sample_for_check(tracks, k: int, seed: int) -> list:
    """``k`` finished requests of the window drawn from the seed, the
    longest among them."""
    done = [tr for tr in tracks if finished_ok(tr)]
    if len(done) < k:       # too few finished: answers cut while streaming
        done += [tr for tr in tracks if tr.cut and len(tr.token_s) >= 8]
    if not done:
        return []
    size = lambda tr: tr.plan.prompt_ids.size + tr.plan.max_new_tokens
    longest = max(done, key=size)
    rest = [tr for tr in done if tr is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    picks = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]


def check_outputs(cell: Cell, params, sample, lower=None) -> dict:
    """The widest gap, over the sample's served tokens, by which a served
    token's reference logit lies below the reference's best."""
    ref = importlib.import_module(cell.config["reference"])
    m = cell.config["model"]
    worst, n = (0.0 if sample else math.inf), 0     # nothing served: no gap
    for tr in sample:
        gaps = ref.served_gap(m, params, tr.plan.prompt_ids,
                              np.asarray(tr.req.output_ids, np.int32), lower)
        if not np.isfinite(gaps).all():
            worst = math.inf
        worst = max(worst, float(gaps.max()))
        n += gaps.size
    return {"logit_gap": worst, "tokens": n, "requests": len(sample)}


def run(cell: Cell, step_hook=None, controls=()) -> dict:
    """One run of a serving cell.  ``step_hook(engine, tracks)`` is for the
    tests that break the timed path underneath; ``controls`` names lower
    precisions whose reading ``prove.py`` and the tests want beside the
    program's (the benchmark's own runs ask for none)."""
    import jax

    mix, m = cell.mix, cell.config["model"]
    backlog = is_backlog(mix)
    compiles = CompileCount()
    t = {}
    with timed(t, "weights_s"):
        params = weights.make_params(m, cell.seed)
        jax.block_until_ready(params)
    with timed(t, "engine_s"):
        engine = build_engine(cell, params)
    plan = traffic.requests(mix, cell.seconds, cell.seed, m["vocab_size"])
    with timed(t, "warm_up_s"):
        warm_up(engine, cell)
    tracer = Tracer(cell)
    preroll = float(mix.get("preroll_s", 0.0))
    traces_before = engine.n_traces() or 0
    t_loop = time.perf_counter()
    tracks, steps, late, compiled_at_open, counters = drive(
        engine, plan, cell, tracer, compiles, step_hook)
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
    mine = [tr for tr in tracks if in_window(tr, backlog, cell.seconds)]
    never = [tr.req.rid for tr in mine if failed(tr, backlog)]
    sample = sample_for_check(mine, mix["check"]["requests"], cell.seed)
    obs = {"kind": "serve", "tracks": tracks, "steps": steps,
           "counters": counters, "seconds": cell.seconds,
           "in_window": mine, "backlog": backlog,
           "work": window_work(tracks, steps, m, cell.seconds),
           "model": m, "peak": cell.peak, "engine_args": mix["engine"]}
    del engine
    gc.collect()
    obs["trace"] = tracer.reduce()
    t_check = time.perf_counter()
    limit = mix["check"]["logit_gap_limit"]

    def compare(lower=None) -> dict:
        got = check_outputs(cell, params, sample, lower)
        return {
            "logit_gap": {"value": got["logit_gap"], "limit": limit},
            "never_answered": {"value": len(never), "limit": 0},
            "tokens_compared": {"value": got["tokens"], "limit": None},
        }

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
        "end_to_end": dict(end_to_end(tracks, backlog, cell.seconds),
                           setup_s=setup_s),
        "obs": obs, "memory_peak_bytes": memory_peak,
        "check_s": check_s,
        "compared": compared,
        "correct": within(compared),
    }
