"""From a profiler trace to numbers: device busy time, the operations that
took most of it, the idle gaps by what the host was doing, and the summed
time of the operations a pattern names.

``reduce`` is a pure function of event lists, so a test hands it a trace
made by hand; ``reduce_dir`` reads the ``.xplane.pb`` that
``jax.profiler`` wrote (``jax.profiler.ProfileData``, nothing but JAX).
Times are seconds on the trace's own clock."""

from __future__ import annotations

import glob
import os
import re

import numpy as np

from . import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
NO_SPAN = "(no host span)"


OP_TEXT = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9\-]*)\(")
CONTAINERS = ("while", "call", "conditional")


def short_name(text: str) -> str:
    """An HLO instruction's text (what the TPU runtime names a device
    event by) cut to ``name opcode result-type``, layouts dropped."""
    m = OP_TEXT.match(text)
    if not m:
        return text[:120]
    name, typ, opcode = m.groups()
    typ = re.sub(r"\{[^}]*\}", "", typ)
    return f"{name} {opcode} {typ}"[:120]


def contains_others(text: str) -> bool:
    """A loop or a call: its time is its body's, which is listed too."""
    m = OP_TEXT.match(text)
    return bool(m) and m.group(3) in CONTAINERS


def reduce(device_ops: dict, host_spans: list, top: int = 10) -> dict:
    """``device_ops``: {device name: [(op name, start, end)]};
    ``host_spans``: [(name, start, end)] of the thread that drives the
    device.  The window is the extent of the harness's own spans
    (``bench/...``), or of the device operations where there is none.

    busy_s: length of the union of a device's operation intervals inside
    the window, averaged over the devices.  device_ops: summed seconds by
    operation name on the first device (loops and calls, whose time is
    their bodies', are left out of the ranking).  idle_gaps: idle seconds of the
    first device by the innermost host span that covers each gap's
    middle."""
    mine = [(s, e) for n, s, e in host_spans if n.startswith(SPAN_PREFIX)]
    every = [(s, e) for ops in device_ops.values() for _, s, e in ops]
    if not every:
        return {"window_s": 0.0, "busy_s": 0.0, "device_ops": [],
                "idle_gaps": [], "op_seconds": {}, "op_counts": {}}
    extent = mine or every
    lo, hi = min(s for s, _ in extent), max(e for _, e in extent)
    busy = []
    for ops in device_ops.values():
        clipped = [(max(s, lo), min(e, hi)) for _, s, e in ops
                   if min(e, hi) > max(s, lo)]
        busy.append(stats.union_length(clipped))
    first = device_ops[sorted(device_ops)[0]]
    seconds: dict = {}
    counts: dict = {}
    for name, s, e in first:
        seconds[name] = seconds.get(name, 0.0) + (e - s)
        counts[name] = counts.get(name, 0) + 1
    idle = stats.gaps([(s, e) for _, s, e in first], lo, hi)
    by_span: dict = {}
    if idle:
        idle.sort(key=lambda g: g[0] - g[1])
        named = [(n, s, e) for n, s, e in host_spans if e > s]
        starts = np.asarray([s for _, s, _ in named])
        ends = np.asarray([e for _, _, e in named])
        for s, e in idle[:4000]:
            who = NO_SPAN
            if named:
                mid = 0.5 * (s + e)
                over = np.flatnonzero((starts <= mid) & (ends >= mid))
                if over.size:
                    who = named[over[np.argmin(ends[over]
                                               - starts[over])]][0]
            by_span[who] = by_span.get(who, 0.0) + (e - s)
        rest = sum(e - s for s, e in idle[4000:])
        if rest:
            by_span["(shorter gaps)"] = rest
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    leaves: dict = {}
    for name, v in seconds.items():
        if not contains_others(name):
            label = short_name(name)
            leaves[label] = leaves.get(label, 0.0) + v
    return {"window_s": hi - lo, "busy_s": float(np.mean(busy)),
            "device_ops": rank(leaves), "idle_gaps": rank(by_span),
            "op_seconds": seconds, "op_counts": counts}


def matched_seconds(reduction: dict, patterns) -> tuple:
    """(summed seconds, number of events) of the first device's operations
    whose name matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    names = [n for n in reduction["op_seconds"]
             if any(r.search(n) for r in rx)]
    return (sum(reduction["op_seconds"][n] for n in names),
            sum(reduction["op_counts"][n] for n in names))


def read_xplane(path: str) -> tuple:
    """(device_ops, host_spans) of one ``.xplane.pb``.  Host spans are the
    events of the host thread that carries the harness's spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device_ops: dict = {}
    host_lines: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_lines.append([
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events])
    driver = max(host_lines, default=[], key=lambda evs: sum(
        1 for n, _, _ in evs if n.startswith(SPAN_PREFIX)))
    return device_ops, driver


def describe(path: str, per_line: int = 12) -> list:
    """Planes, lines and their commonest event names: what to look at by
    hand before trusting ``read_xplane`` on a new runtime."""
    import collections

    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            names = collections.Counter()
            kernels: dict = {}
            for ev in line.events:
                names[ev.name] += 1
                if "custom-call(" in ev.name:
                    n, t = kernels.get(ev.name, (0, 0.0))
                    kernels[ev.name] = (n + 1, t + ev.duration_ns * 1e-9)
            out.append({"plane": plane.name, "line": line.name,
                        "events": sum(names.values()),
                        "names": names.most_common(per_line),
                        "custom_calls": sorted(
                            ([k, n, t] for k, (n, t) in kernels.items()),
                            key=lambda r: -r[2])})
    return out


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_dir(trace_dir: str):
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce(*read_xplane(path))
