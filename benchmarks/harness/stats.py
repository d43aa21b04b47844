"""The metric arithmetic: percentiles, rates, union of intervals.

Plain functions of plain lists, so that tests can hand them timelines made
by hand.  No JAX here."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-quantile (0 < q <= 1) by nearest rank: the smallest value with
    at least a share q of the sample at or below it.  ``math.inf`` is a
    value like any other (a request that never answered is beyond every
    percentile it falls in), so nothing is interpolated."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    return vals[max(math.ceil(q * len(vals)), 1) - 1]


def token_gaps(arrivals) -> list:
    """Gaps between consecutive output tokens of one request, from the
    instants at which the harness saw each token."""
    return [b - a for a, b in zip(arrivals, arrivals[1:])]


def rate(amount: float, window_s: float) -> float:
    """All the work over all the time of the window."""
    if window_s <= 0:
        raise ValueError("window has no length")
    return amount / window_s


def spread_between(spans, lo: float, hi: float) -> float:
    """The part of the amounts in ``spans`` that falls inside [lo, hi).
    A row is (start, end, amount): the amount is spread evenly over
    (start, end]; a row with no length lies whole at ``end``."""
    total = 0.0
    for start, end, amount in spans:
        if end <= start:
            total += amount if lo <= end < hi else 0.0
        else:
            overlap = min(end, hi) - max(start, lo)
            if overlap > 0:
                total += amount * overlap / (end - start)
    return total


def prefill_spans(prompts) -> list:
    """When each prompt's rows were computed, as far as a client can tell.
    ``prompts`` rows are (handed_s, first_token_s, prompt_tokens).  A
    prompt is known to be prefilled at its first token; its rows are
    spread evenly back from there to the later of its hand-over and the
    last first-token instant of another prompt before its own (until then
    the prefill channel was that prompt's).  Returns (start, end, tokens)
    rows for ``spread_between``: the whole of every prompt, once."""
    out, before, at = [], -math.inf, None
    for handed, first, tokens in sorted(prompts, key=lambda r: r[1]):
        if at is not None and first > at:
            before = at
        at = first
        out.append((max(handed, before), first, tokens))
    return out


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals) -> list:
    """Sorted, disjoint (start, end) covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(intervals, lo: float, hi: float) -> list:
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in merge(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out
