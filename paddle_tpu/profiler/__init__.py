"""Profiler (reference: python/paddle/profiler/profiler.py:358 + C++ host/CUPTI
tracers merged into chrome://tracing JSON, chrometracing_logger.h:32).

TPU-native realization (SURVEY.md §5): device-side tracing is jax.profiler
(XPlane → TensorBoard/Perfetto); this module keeps the reference's *API surface*
— ``RecordEvent`` spans, a ``Profiler`` with scheduler states, and chrome-trace
JSON export of the host-side spans."""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from enum import Enum

import jax

from .. import native as _native

__all__ = [
    "Profiler",
    "RecordEvent",
    "ProfilerState",
    "ProfilerTarget",
    "make_scheduler",
    "export_chrome_tracing",
    "load_profiler_result",
    "add_trace_event",
    "host_events_len",
    "host_events_dropped",
    "set_host_event_capacity",
    "clear_host_events",
]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


_events_lock = threading.Lock()
_events: list[dict] = []
_recording = threading.local()

# host-span buffer bound (ISSUE 11): a long-lived serving engine emits
# spans forever, and an unbounded list is a slow memory leak.  At capacity
# new events are DROPPED (and counted) rather than evicting old ones —
# chrome traces render contiguous history better than one with holes, and
# export drains the buffer anyway, so steady-state exporters never hit the
# cap.  ``set_host_event_capacity`` exists for tests; the drop counter is
# surfaced by ``host_events_dropped`` and in every export's metadata.
_MAX_HOST_EVENTS_DEFAULT = 65536
_capacity = _MAX_HOST_EVENTS_DEFAULT
_dropped = 0
# bumped on every drain (export/clear): emitters holding one-shot metadata
# (e.g. the request tracer's process_name lane labels) watch this to know
# their metadata left with a previous export and must be re-emitted
_generation = 0
# spans handed to the native tracer since its last drain: its buffers are
# plain vectors, so the cap above is kept for them here
_native_spans = 0


def host_events_generation() -> int:
    return _generation


def add_trace_event(ev: dict) -> bool:
    """Append one raw chrome-trace event dict to the host buffer,
    honoring the capacity cap.  Returns False when the event was dropped.
    The request-lifecycle tracer (inference/observability.py) writes
    through here so its spans ride the same export path RecordEvent spans
    always did."""
    global _dropped
    with _events_lock:
        if len(_events) >= _capacity:
            _dropped += 1
            return False
        _events.append(ev)
    return True


def host_events_len() -> int:
    with _events_lock:
        return len(_events) + _native_spans


def host_events_dropped() -> int:
    return _dropped


def set_host_event_capacity(n: int) -> int:
    """Set the host-span buffer cap (>= 1); returns the previous value."""
    global _capacity
    if int(n) < 1:
        raise ValueError(f"capacity must be >= 1, got {n}")
    prev = _capacity
    _capacity = int(n)
    return prev


def clear_host_events() -> None:
    """Drop buffered host events and reset the drop counter (tests and
    rung isolation; export drains implicitly)."""
    global _dropped, _generation, _native_spans
    with _events_lock:
        _events.clear()
    if _native_spans:
        _native_lib().pt_trace_clear()
        _native_spans = 0
    _dropped = 0
    _generation += 1

# Native host tracer (paddle_tpu/native/src/tracer.cc — the analog of the
# reference's C++ host_tracer).  When the library is available, spans are
# timestamped in C++ (no GIL-held dict append per span); export/summary merge
# the native buffers back in.
_nlib = None
_intern_cache: dict[str, int] = {}


def _native_lib():
    global _nlib
    if _nlib is None:
        lib = _native.load()
        if lib is not None:
            lib.pt_trace_enable()
        _nlib = lib if lib is not None else False
    return _nlib or None


def _intern(name: str) -> int:
    nid = _intern_cache.get(name)
    if nid is None:
        nid = _intern_cache[name] = _native_lib().pt_trace_intern(name.encode())
    return nid


def _native_events(clear: bool = False) -> list[dict]:
    lib = _native_lib()
    if lib is None:
        return []
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        path = tf.name
    try:
        n = lib.pt_trace_dump(path.encode(), 1 if clear else 0)
        if n <= 0:
            return []
        with open(path) as f:
            return json.load(f).get("traceEvents", [])
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


def _now_us():
    return time.perf_counter_ns() / 1000.0


class RecordEvent:
    """Span marker (reference: paddle.profiler.RecordEvent ≙ C++ RecordEvent,
    platform/profiler/host_tracer.cc).  Also forwards to jax.profiler traces
    so spans show up inside XPlane timelines, on the device trace's clock.

    Keyword arguments annotate the span: they go to
    ``jax.profiler.TraceAnnotation(name, **args)`` and onto the host-buffer
    event as ``args``.  The native tracer stores a name and two timestamps,
    so a span with arguments is buffered in Python."""

    def __init__(self, name: str, event_type=None, **args):
        self.name = name
        self.args = args
        self._t0 = None
        self._jax_ctx = None

    def begin(self):
        global _native_spans, _dropped
        lib = None if self.args else _native_lib()
        if lib is None:
            self._t0 = _now_us()
        elif _native_spans + len(_events) < _capacity:
            lib.pt_trace_begin(_intern(self.name))
            _native_spans += 1
            self._t0 = True  # marks an open native span
        else:
            _dropped += 1   # host buffer full: the jax annotation still goes
        try:
            self._jax_ctx = jax.profiler.TraceAnnotation(self.name,
                                                         **self.args)
            self._jax_ctx.__enter__()
        except Exception:
            self._jax_ctx = None

    def end(self):
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(None, None, None)
            self._jax_ctx = None
        if self._t0 is None:
            return
        if self._t0 is True:
            _native_lib().pt_trace_end()
            self._t0 = None
            return
        t1 = _now_us()
        ev = {
            "name": self.name,
            "ph": "X",
            "ts": self._t0,
            "dur": t1 - self._t0,
            "pid": os.getpid(),
            "tid": threading.get_ident() % 100000,
            "cat": "host",
        }
        if self.args:
            ev["args"] = self.args
        add_trace_event(ev)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0, skip_first: int = 0):
    """Mirror of paddle.profiler.make_scheduler (scheduler states profiler.py:89)."""

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        period = closed + ready + record
        if repeat and s >= period * repeat:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: str | None = None):
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        fname = f"{worker_name or 'worker'}_{os.getpid()}.json"
        prof.export(os.path.join(dir_name, fname))

    return handler


class Profiler:
    def __init__(
        self,
        *,
        targets=None,
        scheduler=None,
        on_trace_ready=None,
        record_shapes=False,
        profile_memory=False,
        with_flops=False,
        timer_only=False,
    ):
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._jax_dir = None
        self._started = False

    def start(self):
        self._update_state()
        if self.state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_device_trace()

    def _start_device_trace(self):
        if not self._started:
            self._jax_dir = os.environ.get("PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
            try:
                jax.profiler.start_trace(self._jax_dir)
                self._started = True
            except Exception:
                self._started = False

    def _stop_device_trace(self):
        if self._started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._started = False

    def _update_state(self):
        if self.scheduler is None:
            self.state = ProfilerState.RECORD
        else:
            self.state = (
                self.scheduler(self.step_num)
                if callable(self.scheduler)
                else ProfilerState.RECORD
            )

    def step(self, num_samples=None):
        self.step_num += 1
        prev = self.state
        self._update_state()
        if prev != ProfilerState.RECORD and self.state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_device_trace()
        if prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) and self.state == ProfilerState.CLOSED:
            self._stop_device_trace()
        if prev == ProfilerState.RECORD_AND_RETURN and self.on_trace_ready:
            self.on_trace_ready(self)

    def stop(self):
        self._stop_device_trace()
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def export(self, path: str, format: str = "json"):
        """Write the buffered host spans (python + native tracer) as one
        chrome trace and DRAIN them: export is the buffer's consumer, so a
        long-lived engine that exports periodically never hits the span
        cap.  The drop counter (spans lost while the buffer was full) is
        written as a metadata event and reset."""
        global _dropped, _generation, _native_spans
        with _events_lock:
            events = list(_events)
            _events.clear()
            dropped, _dropped = _dropped, 0
            _generation += 1
        events += _native_events(clear=True)
        _native_spans = 0
        if dropped:
            events.append({"name": "host_events_dropped", "ph": "M",
                           "pid": os.getpid(),
                           "args": {"dropped": dropped}})
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False, time_unit="ms"):
        with _events_lock:
            events = list(_events)
        events += _native_events()
        agg: dict[str, list[float]] = {}
        for e in events:
            agg.setdefault(e["name"], []).append(e["dur"])
        lines = [f"{'name':<50} {'calls':>8} {'total(ms)':>12} {'avg(ms)':>12}"]
        for name, durs in sorted(agg.items(), key=lambda kv: -sum(kv[1])):
            lines.append(
                f"{name[:50]:<50} {len(durs):>8} {sum(durs)/1000:>12.3f} {sum(durs)/len(durs)/1000:>12.3f}"
            )
        if _dropped:
            # the buffer is bounded (see add_trace_event): a summary over a
            # buffer that overflowed must say so, not read as complete
            lines.append(f"[{_dropped} span(s) dropped at the "
                         f"{_capacity}-event buffer cap; export() drains]")
        return "\n".join(lines)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def load_profiler_result(filename: str):
    with open(filename) as f:
        return json.load(f)
