"""Readers of the program's own counters (``engine.stats``) that are not
shares: a ratio of two counters' deltas over the window, in the unit of the
one over the other."""

from __future__ import annotations


def ratio(obs, spec):
    """Counter ``over`` / counter ``under`` (a mean per launch, per step,
    per request: whatever ``under`` counts)."""
    c, p = obs["counters"], spec["params"]
    if p["over"] not in c or not c.get(p["under"]):
        return None     # a program without the counter, or nothing counted
    return c[p["over"]] / c[p["under"]]
