"""What a runner is handed, and the helpers both runners share."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")


@dataclasses.dataclass
class Cell:
    name: str
    config: dict            # benchmarks/configs/<config>.json
    mix: dict               # benchmarks/traffic/<traffic>.json
    chips: int
    seed: int
    seconds: float
    trace: bool
    peak: dict              # this device's row of peaks.json
    t0: float               # time.perf_counter() at process start
    trace_dir: str = os.path.join(ROOT, ".bench_trace")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(spec: str):
    """``module:function`` -> the function."""
    module, _, name = spec.partition(":")
    return getattr(importlib.import_module(module), name)


def program_config(m: dict):
    """The program's own config object for a configuration's ``model``
    group; refuses what ``paddle_tpu.models.llama`` cannot express."""
    import jax.numpy as jnp

    from paddle_tpu.models import llama

    if m.get("sliding_window") is not None:
        raise ValueError("models/llama has no sliding window")
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError("models/llama is SwiGLU only")
    hd = m["hidden_size"] // m["num_attention_heads"]
    if m.get("head_dim", hd) != hd:
        raise ValueError("models/llama derives head_dim = hidden / heads")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        m.get("torch_dtype", "bfloat16")]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    return llama.LlamaConfig(dtype=dtype, **{k: m[k] for k in keys})


class CompileCount:
    """Counts backend compilations (cache hits included: a hit still stalls
    the window) through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


class Tracer:
    """The profiler round the last ``trace_s`` seconds of a window."""

    def __init__(self, cell: Cell):
        self.on = cell.trace
        self.dir = os.path.join(cell.trace_dir, cell.name)
        self.span_s = min(float(cell.mix.get("trace_s", 3.0)), cell.seconds)
        self.start_at = cell.seconds - self.span_s
        self.started = self.stopped = None

    def maybe_start(self, now_s: float, clock) -> None:
        if self.on and self.started is None and now_s >= self.start_at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.started = clock()

    def stop(self, clock) -> None:
        if self.started is not None and self.stopped is None:
            import jax

            self.stopped = clock()
            jax.profiler.stop_trace()

    def reduce(self):
        """The trace's reduction (``trace_reduce.reduce``), or None; the
        files are deleted once read."""
        if self.stopped is None:
            return None
        from . import trace_reduce

        try:
            return trace_reduce.reduce_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def peak_memory_bytes(n_devices: int):
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n_devices]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def within(compared: dict) -> bool:
    """What decides ``correct``, for the program and for a control put in
    its place alike: ``compared`` maps a name to its ``value`` and its
    ``limit``; every number that has a limit lies within it, and one does."""
    held = [c for c in compared.values() if c["limit"] is not None]
    return bool(held) and all(c["value"] <= c["limit"] for c in held)


def say(**line) -> None:
    """An earlier line of standard output (never the last)."""
    print(json.dumps(line), flush=True)


@contextlib.contextmanager
def timed(store: dict, key: str):
    t = time.perf_counter()
    yield
    store[key] = time.perf_counter() - t
