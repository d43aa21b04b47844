"""What a limit is set from, read on the chip in one process:

    python3 benchmarks/prove.py --workload <cell> --seeds 1,2,3 --seconds 8 \\
        --controls int8,fp8 [--out chiprun_out/prove.jsonl]
    python3 benchmarks/prove.py --workload <cell> --seeds 1 --seconds 30 \\
        --sweep 0.6,0.8,1.0

The first form makes a short run of the cell on each seed through the
benchmark's own runner (same set-up, window, check) and prints, a line a
seed, every number compared and the same numbers of the controls: the
reference computed in a lower precision, or with a fault planted.  The
second finds a serving mix's knee: one engine, first the mix as a backlog
(completion rate C), then open-loop windows at the given shares of C.
``--rates 0.4,0.55`` skips the backlog and offers those rates.
Neither is part of a benchmark run; the limits and the rate they give are
written into the traffic file by hand, with the readings in PERF.md."""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse     # noqa: E402
import copy         # noqa: E402
import gc           # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench             # noqa: E402
from benchmarks.harness import stats            # noqa: E402
from benchmarks.harness.cell import Cell, load_json     # noqa: E402


def emit(out, **line):
    text = json.dumps(line)
    print(text, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(text + "\n")


def values(compared: dict) -> dict:
    return {k: v["value"] for k, v in compared.items()}


def by_request(obs: dict, seconds: float) -> dict:
    """A serving run's requests one by one, to see whether the window is
    in a steady state: (due, prompt, new, TTFT ms) of each, and how many
    were in the system at the quarters of the window."""
    if obs["kind"] != "serve":
        return {}
    tracks = obs["tracks"]
    return {
        "requests": [[round(t.plan.due_s, 3), int(t.plan.prompt_ids.size),
                      t.plan.max_new_tokens,
                      round((t.token_s[0] - t.plan.due_s) * 1e3, 1)
                      if t.token_s else None] for t in obs["in_window"]],
        "in_system": [sum(1 for t in tracks if t.handed_s is not None
                          and t.handed_s <= at
                          and (t.done_s is None or t.done_s > at))
                      for at in (0.0, seconds / 4, seconds / 2,
                                 3 * seconds / 4, seconds)]}


def seeds_of(cell_args, seeds, controls, out, schedule_seeds=(),
             preroll=None):
    """One run of the cell a seed.  Each control goes through the decision
    that sets ``correct`` (``harness.cell.within``, the cell's own limits):
    ``control_correct`` has to read false.  ``schedule_seeds`` (one a seed)
    and ``preroll`` override the mix's, to show how far the schedule's draw
    and the ramp before the window move the metrics."""
    for i, seed in enumerate(seeds):
        mix = copy.deepcopy(cell_args["mix"])
        if schedule_seeds:
            mix["schedule_seed"] = schedule_seeds[i]
        if preroll is not None:
            mix["preroll_s"] = preroll
        cell = Cell(seed=seed, t0=time.perf_counter(),
                    **dict(cell_args, mix=mix))
        res = bench.execute(cell, controls=controls)
        emit(out, **by_request(res["obs"], cell.seconds))
        emit(out, workload=cell.name, seed=seed, seconds=cell.seconds,
             schedule_seed=mix.get("schedule_seed"),
             preroll_s=mix.get("preroll_s"),
             correct=res["correct"], attempted=res["attempted"],
             failed=res["failed"], end_to_end=res["end_to_end"],
             compared=values(res["compared"]),
             limits={k: v["limit"] for k, v in res["compared"].items()},
             control={k: values(c["compared"])
                      for k, c in res["control"].items()},
             control_correct={k: c["correct"]
                              for k, c in res["control"].items()},
             check_s=res["check_s"])
        del res
        gc.collect()


def sweep(cell_args, seed, shares, out, rates=False):
    """Backlog first, then open-loop windows at ``shares`` of its
    completion rate, on one engine."""
    import jax

    from benchmarks.harness import traffic, weights
    from benchmarks.harness.cell import Tracer
    from benchmarks.runners import serve

    cell = Cell(seed=seed, t0=T0, **cell_args)
    m = cell.config["model"]
    params = weights.make_params(m, seed)
    jax.block_until_ready(params)
    engine = serve.build_engine(cell, params)
    serve.warm_up(engine, cell)
    rate_c = None
    phases = list(shares) if rates else ["backlog"] + list(shares)
    for share in phases:
        mix = copy.deepcopy(cell.mix)
        if rates:
            mix["arrivals"] = {"process": "poisson",
                               "requests_per_second": share}
        elif share == "backlog":
            mix["arrivals"] = {"process": "backlog",
                               "requests_per_second": 12.0}
        else:
            mix["arrivals"] = {"process": "poisson",
                               "requests_per_second": share * rate_c}
        phase = Cell(**dict(cell_args, mix=mix), seed=seed, t0=T0)
        plan = traffic.requests(mix, phase.seconds, seed, m["vocab_size"])
        tracks, steps, *_ = serve.drive(engine, plan, phase, Tracer(phase))
        backlog = serve.is_backlog(mix)
        e2e = serve.end_to_end(tracks, backlog, phase.seconds)
        done = e2e["requests_completed_in_window"]
        if backlog:
            rate_c = done / phase.seconds
        mine = [t for t in tracks if serve.in_window(t, backlog,
                                                     phase.seconds)]
        ttft = [(t.token_s[0] - t.plan.due_s) * 1e3 for t in mine
                if t.token_s]

        def in_system(at):
            return sum(1 for t in tracks if t.plan.due_s <= at
                       and (t.done_s is None or t.done_s > at))

        def waiting(at):
            return sum(1 for t in tracks if t.plan.due_s <= at
                       and (t.running_s is None or t.running_s > at))

        marks = [phase.seconds * f for f in (0.25, 0.5, 0.75, 1.0)]
        emit(out, sweep=share, offered_per_s=mix["arrivals"][
            "requests_per_second"], completed_per_s=done / phase.seconds,
            requests=len(mine), end_to_end=e2e,
            ttft_p50_ms=stats.percentile(ttft, 0.5) if ttft else None,
            in_system=[in_system(a) for a in marks],
            waiting=[waiting(a) for a in marks],
            steps=len(steps),
            failed=sum(1 for t in mine if serve.failed(t, backlog)),
            cut=sum(1 for t in mine if t.cut))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--rates", default="",
                    help="open-loop windows at these requests/s, no backlog")
    ap.add_argument("--schedule-seeds", default="",
                    help="one a seed: overrides the mix's schedule_seed")
    ap.add_argument("--preroll", type=float, default=None,
                    help="overrides the mix's preroll_s")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    w, config, mix = bench.find_cell(manifest, args.workload)
    _, peak = bench.open_chip(w["chips"])
    cell_args = dict(name=w["name"], config=config, mix=mix,
                     chips=w["chips"], seconds=args.seconds, trace=False,
                     peak=peak)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.sweep or args.rates:
        sweep(cell_args, seeds[0],
              [float(x) for x in (args.rates or args.sweep).split(",")],
              args.out, rates=bool(args.rates))
    else:
        seeds_of(cell_args, seeds,
                 tuple(c for c in args.controls.split(",") if c), args.out,
                 [int(s) for s in args.schedule_seeds.split(",") if s],
                 args.preroll)
    return 0


if __name__ == "__main__":
    sys.exit(main())
