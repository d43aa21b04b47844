"""Readers of one Pallas kernel's device events, found by the kernel's
name: the ``name=`` of its ``pallas_call``, which the TPU runtime puts at
the head of the event's text (``%<name>.<n> = ... custom-call(...)``)."""

from __future__ import annotations

import re

from ..harness import trace_reduce


def named(kernel: str) -> str:
    """The pattern of the events of the kernel called exactly ``kernel``:
    XLA numbers the instruction (``.20``, ``.20.clone``), and a longer name
    that starts the same way is another kernel."""
    return rf"^%?{re.escape(kernel)}(\.[\w.]+)? = .*custom-call\("


def ms_per_event(obs, spec):
    """Mean device milliseconds of one call of the kernel over the traced
    window."""
    tr = obs.get("trace")
    if not tr:
        return None
    seconds, events = trace_reduce.matched_seconds(
        tr, [named(spec["params"]["kernel"])])
    if not events or seconds <= 0:
        return None
    return 1e3 * seconds / events
