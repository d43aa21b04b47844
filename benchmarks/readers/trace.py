"""Readers of the device trace's reduction (``harness/trace_reduce.py``)."""

from __future__ import annotations

from ..harness import trace_reduce, work


def idle_pct(obs, spec):
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def flash_attn_roofline(obs, spec):
    """Least time by the roofline for the flash-attention calls of the
    traced steps over the kernels' summed device time.  The calls counted
    are the events seen: ``per_step`` events make one step's work."""
    tr = obs.get("trace")
    if not tr:
        return None
    seconds, events = trace_reduce.matched_seconds(tr, spec["params"]["match"])
    if not events or seconds <= 0:
        return None
    steps = events / float(spec["params"]["events_per_step"])
    m, b, s = obs["model"], obs["batch"], obs["seq"]
    least, _ = work.roofline_s(work.flash_attn_train_flops(m, b, s),
                               work.flash_attn_train_bytes(m, b, s),
                               obs["peak"])
    return 100.0 * least * steps / seconds
