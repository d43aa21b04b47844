"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip to the end and starts no child.  It
finds the cell in ``BENCHMARK.json``, its configuration and traffic mix by
name under ``benchmarks/``, the runner by the mix's ``kind``
(``benchmarks/runners/<kind>.py``) and, in a traced run, each per-layer
metric's reader by the metric's own file
(``benchmarks/layer_metrics/<name>.json``).  The last line of standard
output is the result; the numbers compared for ``correct`` stand beside
their limits as the last lines of standard error and last in that line.

It exits non-zero and prints no result where JAX finds no TPU or fewer
chips than the cell asks for, where the Pallas kernels would be
interpreted, where the device is not in ``peaks.json``, where a program
compiles inside the window, and where the program under test is absent."""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import math         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.cell import HERE, Cell, load_json, resolve  # noqa: E402


def find_cell(manifest: dict, name: str) -> tuple:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    w = cells[name]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    config = load_json(ROOT, files[w["config"]])
    mix = load_json(HERE, "traffic", w["traffic"] + ".json")
    return w, config, mix


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def device_or_exit(chips: int) -> tuple:
    """(device description, this device's peaks) or a non-zero exit."""
    import jax

    from paddle_tpu.ops import pallas

    devices = jax.devices()                 # the one touch: takes the chip
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise SystemExit(f"run.py: no TPU here (JAX found {device})")
    if pallas.interpret_mode():
        raise SystemExit("run.py: the Pallas kernels would be interpreted")
    if len(devices) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chip(s), JAX "
                         f"found {len(devices)}")
    peaks = load_json(HERE, "peaks.json")
    if device["kind"] not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind "
                         f"{device['kind']!r} in benchmarks/peaks.json")
    return device, peaks[device["kind"]]


def open_chip(chips: int) -> tuple:
    """Point JAX's persistent compilation cache at the checkout, then take
    the chip: (device description, its peaks)."""
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # every program of a run goes to the cache, the small ones too: the
    # second run of a cell in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return device_or_exit(chips)


def layer_metrics(manifest: dict, cell_name: str, obs: dict) -> dict:
    """Each per-layer metric of this cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in manifest["per_layer"]:
        if not applies(metric, cell_name):
            continue
        spec = load_json(HERE, "layer_metrics", metric["name"] + ".json")
        value = resolve(spec["reader"])(obs, spec)
        if value is not None and math.isfinite(value):
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def result_line(manifest: dict, cell: Cell, device: dict, res: dict) -> dict:
    """The result as the driver reads it."""
    if cell.trace:
        metrics = layer_metrics(manifest, cell.name, res["obs"])
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in manifest["end_to_end"]
                   if applies(m, cell.name) and m["name"] in res["end_to_end"]}
    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    trace = res["obs"].get("trace")
    if cell.trace and trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["check_s"] = res["check_s"]
    line["compared"] = res["compared"]
    return line


def execute(cell: Cell, **runner_args) -> dict:
    """Drive one run through the runner of the mix's kind."""
    runner = importlib.import_module(
        f"benchmarks.runners.{cell.mix['kind']}")
    return runner.run(cell, **runner_args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    w, config, mix = find_cell(manifest, args.workload)
    device, peak = open_chip(w["chips"])
    cell = Cell(name=w["name"], config=config, mix=mix, chips=w["chips"],
                seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                peak=peak, t0=T0)
    res = execute(cell)
    line = result_line(manifest, cell, device, res)
    for name, c in res["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
