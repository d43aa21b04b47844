"""Device management.

Analog of the reference's DeviceManager / place system
(`paddle/phi/backends/device_manager.h:134`, ``paddle.device.set_device``).
On TPU the runtime (streams, contexts, allocators) is owned by PJRT/XLA — this
module keeps the *API surface*: device discovery, a current-device setting that
controls where eager ops place their outputs, and memory stats
(analog of `paddle/phi/core/memory/stats.h`).
"""

from __future__ import annotations

import threading

import jax
import numpy as np


class Place:
    """A device identity, e.g. ``tpu:0`` / ``cpu:0`` (analog of phi::Place)."""

    __slots__ = ("device",)

    def __init__(self, device: jax.Device):
        self.device = device

    @property
    def platform(self) -> str:
        return self.device.platform

    @property
    def index(self) -> int:
        return self.device.id

    def __repr__(self):
        return f"Place({self.device.platform}:{self.device.id})"

    def __eq__(self, other):
        return isinstance(other, Place) and self.device == other.device

    def __hash__(self):
        return hash(self.device)


_current_device: jax.Device | None = None


def _parse(device: str) -> jax.Device:
    device = device.lower()
    if ":" in device:
        platform, _, idx = device.partition(":")
        idx = int(idx)
    else:
        platform, idx = device, 0
    if platform == "gpu":  # accepted for script compatibility
        platform = "tpu"
    devs = [d for d in jax.devices() if d.platform == platform]
    if not 0 <= idx < len(devs):
        have = sorted({f"{d.platform}:{d.id}" for d in jax.devices()})
        raise ValueError(f"no device {device!r} here (visible: {have})")
    return devs[idx]


def set_device(device: str) -> Place:
    """``paddle.device.set_device`` analog: 'tpu', 'tpu:1', 'cpu'."""
    global _current_device
    _current_device = _parse(device)
    return Place(_current_device)


def get_device() -> str:
    d = current_device()
    return f"{d.platform}:{d.id}"


def current_device() -> jax.Device:
    return _current_device if _current_device is not None else jax.devices()[0]


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def is_compiled_with_cuda() -> bool:  # API parity helper
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


# ---- memory stats (reference: paddle/phi/core/memory/stats.h; API surface of
# paddle.device.cuda.max_memory_allocated etc., served by PJRT stats on TPU) ----

def memory_stats(device: jax.Device | None = None) -> dict:
    d = device or current_device()
    return dict(d.memory_stats() or {})     # the CPU backend reports None


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("peak_bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    """Current reserved bytes (falls back to current bytes_in_use — PJRT
    reports no separate live reserved-pool counter)."""
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def empty_cache() -> None:
    """Drop jax's compiled-program caches (XLA owns the allocator itself)."""
    jax.clear_caches()


def synchronize(device=None) -> None:
    """Block until all pending work on the device is complete."""
    (jax.device_put(np.zeros((), np.int32), device or current_device())).block_until_ready()


# ---- host-side stat registry (native C++ when built: paddle_tpu/native/src/
# stats.cc — the analog of the reference's STAT_ADD/STAT_GET counter macros in
# paddle/phi/core/memory/stats.h, applied to host quantities: IPC queue depth,
# checkpoint bytes in flight, pinned batches) ----

_host_stats: dict = {}
_host_stats_lock = threading.Lock()


def _stat_lib():
    from .. import native

    return native.load()


def host_stat_update(name: str, delta: int) -> int:
    lib = _stat_lib()
    if lib is not None:
        return int(lib.pt_stat_update(name.encode(), int(delta)))
    with _host_stats_lock:
        cur, peak = _host_stats.get(name, (0, 0))
        cur += int(delta)
        _host_stats[name] = (cur, max(peak, cur))
        return cur


def host_stat_current(name: str) -> int:
    lib = _stat_lib()
    if lib is not None:
        return int(lib.pt_stat_current(name.encode()))
    return _host_stats.get(name, (0, 0))[0]


def host_stat_peak(name: str) -> int:
    lib = _stat_lib()
    if lib is not None:
        return int(lib.pt_stat_peak(name.encode()))
    return _host_stats.get(name, (0, 0))[1]
