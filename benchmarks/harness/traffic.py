"""One generator for every traffic mix: it reads a mix's data file
(``benchmarks/traffic/<name>.json``) and returns the requests of a run.

The *schedule* (how many requests, each one's prompt length, output length
and due time) is a function of the file and of the window's length alone:
every length is a stratified quantile of the file's distribution, shuffled
by the file's ``schedule_seed``.  So every run of a cell does the same work
at the same instants, and runs differ by the chip's timing only.  ``--seed``
makes the *contents*: token ids here, weights elsewhere, and the sample the
output check draws.  Greedy decoding with no EOS means contents cannot
change the work."""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of a run, before it is handed to the system."""
    rid: int
    due_s: float            # relative to the window's opening; < 0 = pre-roll
    prompt_ids: np.ndarray
    max_new_tokens: int


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` whole numbers that are the stratified quantiles of ``spec``'s
    distribution, in rising order."""
    q = quantiles(n)
    dist = spec["dist"]
    if dist == "constant":
        x = np.full(n, float(spec["value"]))
    elif dist == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    x = np.clip(x, spec.get("min", 1), spec.get("max", math.inf))
    return np.maximum(np.rint(x).astype(np.int64), 1)


def due_times(spec: dict, span_s: float, rng) -> np.ndarray:
    """Due instants in [0, span_s), rising.  ``poisson``: exponential gaps,
    the stratified quantiles of their distribution in shuffled order;
    ``backlog``: every request is due at 0."""
    n = max(int(round(spec["requests_per_second"] * span_s)), 1)
    process = spec["process"]
    if process == "backlog":
        return np.zeros(n)
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    gaps = -np.log1p(-quantiles(n)) / spec["requests_per_second"]
    rng.shuffle(gaps)
    due = np.cumsum(gaps)
    return due[due < span_s]


def schedule(mix: dict, seconds: float) -> list:
    """[(due_s, prompt_len, max_new)], rising in due_s: the work of a run,
    a function of the mix and the window's length only."""
    rng = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    preroll = float(mix.get("preroll_s", 0.0))
    due = due_times(mix["arrivals"], seconds + preroll, rng) - preroll
    n = due.size
    prompts = lengths(mix["prompt_tokens"], n)
    news = lengths(mix["max_new_tokens"], n)
    rng.shuffle(prompts)
    rng.shuffle(news)
    return [(float(d), int(p), int(m)) for d, p, m in zip(due, prompts, news)]


def requests(mix: dict, seconds: float, seed: int, vocab_size: int) -> list:
    """The run's requests: the schedule of the mix, token ids from ``seed``."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    return [Planned(rid, due, rng.integers(1, vocab_size, plen,
                                           dtype=np.int32), new)
            for rid, (due, plen, new) in enumerate(schedule(mix, seconds))]


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab_size: int) -> tuple:
    """(input ids, labels) of one training step: rows of ``seq + 1`` fresh
    token ids, all different, shifted by one for the labels."""
    rng = np.random.default_rng([int(seed), 0x7BA7C4, int(step)])
    rows = rng.integers(0, vocab_size, (batch, seq + 1), dtype=np.int32)
    return rows[:, :-1], rows[:, 1:]
