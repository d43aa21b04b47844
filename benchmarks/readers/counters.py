"""Readers of the program's own counters (``engine.stats``), as deltas
from the window's opening to its close."""

from __future__ import annotations


def ratio_pct(obs, spec):
    """100 * counter ``over`` / counter ``under``."""
    c, p = obs["counters"], spec["params"]
    if not c.get(p["under"]):
        return None
    return 100.0 * c.get(p["over"], 0) / c[p["under"]]

