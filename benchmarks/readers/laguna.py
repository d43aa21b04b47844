"""Readers of the ``laguna`` family's training cell: the step's share of
the peak by ``harness/work_laguna``, the flash-attention kernels' (windowed
and full) and the grouped expert products' shares of their rooflines from
the device trace, and the expert layers' counters (``paddle_tpu.models.laguna``
``COUNTERS``, read from the optimizer state by ``runners/train_model``).
Each returns None where there is nothing to read."""

from __future__ import annotations

import numpy as np

from ..harness import trace_reduce, work_laguna
from . import kernels


def moe_train_mfu(obs, spec):
    """tokens/s x operations a token (the held experts a token reached by
    the window's counters, by expectation where a program keeps none;
    in-window attention pairs; recomputation not counted) over the peak."""
    if not obs.get("tokens"):
        return None
    m, held = obs["model"], _held(obs.get("counters"))
    per_token = work_laguna.train_flops_per_token(
        m, obs["seq"], None if held is None else
        float(held.sum()) / held.shape[0] / obs["tokens"])
    return (100.0 * per_token * obs["tokens"]
            / (obs["window_s"] * obs["peak"]["flops_per_s_bf16"]))


def _share(obs, patterns, steps_of, flops, nbytes):
    """Least time by the roofline for ``flops`` and ``nbytes`` a step over
    the device time of the events ``patterns`` match, for as many steps as
    ``steps_of``'s events make (``match`` patterns, ``events_per_step``)."""
    tr = obs.get("trace")
    if not tr:
        return None
    seconds, events = trace_reduce.matched_seconds(tr, patterns)
    _, marks = trace_reduce.matched_seconds(tr, steps_of["match"])
    if not events or not marks or seconds <= 0:
        return None
    steps = marks / float(steps_of["events_per_step"])
    least, _ = work_laguna.roofline_s(flops, nbytes, obs["peak"])
    return 100.0 * least * steps / seconds


def flash_roofline(obs, spec):
    """The flash attention of the layers of ``params.layer_type``, forward
    + backward, over the in-mask pairs, against the device time of the
    kernels ``params.kernels`` found by name (the recomputed forward's time
    counts, its operations do not)."""
    m, b, s = obs["model"], obs["batch"], obs["seq"]
    p = spec["params"]
    patterns = [kernels.named(k) for k in p["kernels"]]
    return _share(obs, patterns,
                  {"match": patterns,
                   "events_per_step": p["events_per_step"]},
                  work_laguna.flash_train_flops(m, p["layer_type"], b, s),
                  work_laguna.flash_train_bytes(m, p["layer_type"], b, s))


def moe_experts_roofline(obs, spec):
    """The held experts' three grouped products, forward + backward, for
    the assignments a step that the counters read over the traced steps
    themselves (``obs["traced"]``: the counters' change from the trace's
    start, and the steps that made it), against the device time of the
    events that compute them (``params.match``).  How many of those a step
    runs depends on the router, so the traced steps are counted by a kernel
    that runs a fixed number of times a step (``params.steps_of``)."""
    traced = obs.get("traced") or {}
    held = _held(traced.get("counters"))
    if held is None or not traced.get("steps"):
        return None
    m = obs["model"]
    a_step = float(held.sum()) / traced["steps"]
    p = spec["params"]
    steps_of = {"match": [kernels.named(k) for k in p["steps_of"]["kernels"]],
                "events_per_step": p["steps_of"]["events_per_step"]}
    return _share(obs, p["match"], steps_of,
                  work_laguna.moe_experts_train_flops(m, a_step),
                  work_laguna.moe_experts_train_bytes(m, a_step))


def _held(counters):
    """[expert layers, held experts] assignments of ``counters``, or None
    where a program keeps none or none was made."""
    held = (counters or {}).get("moe_assignments_held")
    if held is None:
        return None
    held = np.asarray(held, np.float64)
    return held if held.size and held.sum() > 0 else None


def expert_load_max_over_mean(obs, spec):
    """Over the window, the busiest held expert's assignments (of any
    expert layer) over the mean held expert's."""
    held = _held(obs.get("counters"))
    return None if held is None else float(held.max() / held.mean())


def live_row_pct(obs, spec):
    """Assignments to held experts over the rows the grouped products were
    given, padding included."""
    held = _held(obs.get("counters"))
    rows = (obs.get("counters") or {}).get("moe_rows_computed")
    if held is None or rows is None or np.sum(rows) <= 0:
        return None
    return 100.0 * float(held.sum()) / float(np.sum(rows))
