"""Native C++ runtime tests: TCPStore (native + python interop), shm queue,
tracer, stats, and the multiprocess DataLoader built on them.

Mirrors the reference's C++ runtime test surface (test/cpp/phi store/socket
tests, io/dataloader worker tests in test/legacy_test/test_dataloader_*)."""

import json
import os
import pickle
import time

import numpy as np
import pytest

import paddle_tpu.native as native
from paddle_tpu.distributed import store as store_mod
from paddle_tpu.distributed.store import TCPStore, MasterDaemon


requires_native = pytest.mark.skipif(not native.available(),
                                     reason="native lib not built")


@requires_native
def test_native_store_roundtrip():
    master = TCPStore("127.0.0.1", 0, is_master=True)
    assert master.is_native
    client = TCPStore("127.0.0.1", master.port)
    client.set("a", b"1")
    assert master.get("a") == b"1"
    assert client.add("cnt", 5) == 5
    assert master.add("cnt", -2) == 3
    assert client.wait("a", timeout=5) == b"1"
    with pytest.raises(TimeoutError):
        client.wait("nope", timeout=0.2)
    assert client.delete_key("a")
    assert client.get("a") is None
    assert master.keys() == ["cnt"]
    client.close()
    master.close()


@requires_native
def test_python_client_native_server_interop():
    """The pure-Python client speaks the same wire protocol as the C++ server."""
    master = TCPStore("127.0.0.1", 0, is_master=True)
    assert master.is_native

    # hand-rolled python-protocol connection against the native server
    import socket

    sock = socket.create_connection(("127.0.0.1", master.port), timeout=10)
    store_mod._send_frame(sock, store_mod.CMD_SET, b"k", b"vv")
    status, _, _ = store_mod._recv_frame(sock)
    assert status == store_mod.ST_OK
    store_mod._send_frame(sock, store_mod.CMD_GET_NOWAIT, b"k", b"")
    status, _, val = store_mod._recv_frame(sock)
    assert status == store_mod.ST_OK and val == b"vv"
    store_mod._send_frame(sock, store_mod.CMD_ADD, b"n", b"7")
    status, _, val = store_mod._recv_frame(sock)
    assert status == store_mod.ST_OK and val == b"7"
    sock.close()
    master.close()


@requires_native
def test_native_client_python_server_interop():
    """Native client against the pure-Python MasterDaemon."""
    daemon = MasterDaemon(0)
    client = TCPStore("127.0.0.1", daemon.port)
    assert client.is_native
    client.set("x", b"y")
    assert client.get("x") == b"y"
    assert client.add("c", 4) == 4
    assert client.keys() == ["c", "x"]
    client.close()
    daemon.stop()


@requires_native
def test_shm_queue_roundtrip_and_wrap():
    q = native.ShmQueue("/pt_test_wrap", capacity=1 << 12)
    w = native.ShmQueue("/pt_test_wrap", create=False)
    # many pushes/pops forcing ring wrap-around
    for i in range(200):
        msg = bytes([i % 256]) * (17 + i % 700)
        w.push(msg)
        assert q.pop() == msg
    # oversized message rejected
    with pytest.raises(ValueError):
        w.push(b"x" * (1 << 13))
    w.close()
    assert q.pop() is None  # closed and drained
    w.destroy()
    q.destroy()


@requires_native
def test_shm_queue_cross_process():
    import multiprocessing as mp

    name = f"/pt_test_xp_{os.getpid()}"
    q = native.ShmQueue(name, capacity=1 << 20)

    def producer(name):
        import paddle_tpu.native as native

        w = native.ShmQueue(name, create=False)
        for i in range(50):
            w.push(pickle.dumps(np.full((100,), i)))
        w.close()
        w.destroy()

    p = mp.get_context("fork").Process(target=producer, args=(name,))
    p.start()
    for i in range(50):
        arr = pickle.loads(q.pop(timeout=30))
        assert arr[0] == i
    assert q.pop(timeout=30) is None
    p.join()
    q.destroy()


@requires_native
def test_native_tracer_chrome_dump(tmp_path):
    lib = native.load()
    lib.pt_trace_enable()
    lib.pt_trace_clear()
    from paddle_tpu import profiler
    from paddle_tpu.profiler import RecordEvent

    # RecordEvent drops a span once this PROCESS has buffered its capacity
    # of them, and an xdist worker may come here from files that step
    # engines by the thousand: start from an empty buffer
    profiler.clear_host_events()

    with RecordEvent("outer"):
        with RecordEvent("inner"):
            time.sleep(0.002)
    path = tmp_path / "trace.json"
    n = lib.pt_trace_dump(str(path).encode(), 0)
    assert n >= 2
    data = json.loads(path.read_text())
    names = {e["name"] for e in data["traceEvents"]}
    assert {"outer", "inner"} <= names
    inner = next(e for e in data["traceEvents"] if e["name"] == "inner")
    assert inner["ph"] == "X" and inner["dur"] >= 1000  # >= 1ms in us


@requires_native
def test_host_stats():
    from paddle_tpu.core import device as dev

    name = f"test_stat_{os.getpid()}"
    assert dev.host_stat_update(name, 10) == 10
    assert dev.host_stat_update(name, -4) == 6
    assert dev.host_stat_current(name) == 6
    assert dev.host_stat_peak(name) == 10


class _SquareDataset:
    def __len__(self):
        return 37

    def __getitem__(self, i):
        return np.full((4,), i * i, np.float32), np.int64(i)


def _check_loader_output(loader, n_items=37, batch_size=5):
    seen = []
    for xb, yb in loader:
        x, y = np.asarray(xb.numpy()), np.asarray(yb.numpy())
        assert x.shape[1:] == (4,)
        np.testing.assert_array_equal(x[:, 0], (y.astype(np.float32)) ** 2)
        seen.extend(y.tolist())
    assert seen == list(range(n_items))


def test_dataloader_process_workers_shm():
    from paddle_tpu.io import DataLoader

    loader = DataLoader(_SquareDataset(), batch_size=5, num_workers=3,
                        worker_mode="process")
    _check_loader_output(loader)
    # second epoch re-spawns workers
    _check_loader_output(loader)


def test_dataloader_process_workers_mpq_fallback(monkeypatch):
    from paddle_tpu.io import DataLoader

    loader = DataLoader(_SquareDataset(), batch_size=5, num_workers=2,
                        worker_mode="process", use_shared_memory=False)
    _check_loader_output(loader)


def test_dataloader_process_worker_error():
    from paddle_tpu.io import DataLoader

    class Boom:
        def __len__(self):
            return 10

        def __getitem__(self, i):
            if i == 7:
                raise ValueError("bad sample")
            return np.zeros(2, np.float32)

    loader = DataLoader(Boom(), batch_size=2, num_workers=2, worker_mode="process")
    with pytest.raises(RuntimeError, match="worker"):
        list(loader)
