"""Readers of the ``olmo_hybrid`` family's serving cell: the two recurrence
kernels' shares of their rooflines, from the device trace (the kernels'
events found by name) and the engine's counters over the traced steps
themselves (``obs["traced"]``: ``runners/serve_model`` reads
``engine.stats`` before the first traced step and after the last).  Each
returns None where there is nothing to read: a program without the
counters, a run without a trace, a kernel that did not run."""

from __future__ import annotations

from ..harness import trace_reduce, work_olmo_hybrid
from . import kernels


def _share(obs, kernel: str, rows_live, lanes_live):
    tr = obs.get("trace")
    if not tr or rows_live is None or lanes_live is None or lanes_live <= 0:
        return None
    seconds, events = trace_reduce.matched_seconds(tr,
                                                   [kernels.named(kernel)])
    if not events or seconds <= 0:
        return None
    least, _ = work_olmo_hybrid.roofline_s(
        *work_olmo_hybrid.gdn_work(obs["model"], rows_live, lanes_live),
        obs["peak"])
    return 100.0 * least / seconds


def _traced(obs) -> dict:
    return (obs.get("traced") or {}).get("counters") or {}


def gdn_chunk_roofline(obs, spec):
    """Least time by the roofline for the chunked recurrence of the traced
    mixed steps' live rows (their q, k, v, o and each live lane's state
    once each way, every linear layer) over the device time of
    ``gdn_chunk_prefill``'s events."""
    c = _traced(obs)
    return _share(obs, spec["params"]["kernel"],
                  c.get("gdn_chunk_rows_live"),
                  c.get("state_chunk_slot_steps_live"))


def gdn_decode_roofline(obs, spec):
    """The same for the traced decode steps: a live slot's state read and
    written once and its q, k, v, o rows, over the device time of
    ``gdn_decode_step``'s events."""
    c = _traced(obs)
    if "gdn_chunk_rows_live" not in c or "gdn_rows_live" not in c:
        return None
    lanes = (c.get("state_slot_steps_live", 0)
             - c.get("state_chunk_slot_steps_live", 0))
    rows = c["gdn_rows_live"] - c["gdn_chunk_rows_live"]
    return _share(obs, spec["params"]["kernel"], rows, lanes)
