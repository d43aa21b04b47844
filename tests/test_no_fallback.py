"""The machinery that could make a run look healthy without the chip is
gone (ISSUE 21): these pin the replacements — errors, not quiet defaults."""

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from paddle_tpu.core import device
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ["tpu", "gpu", "tpu:0", "cpu:64", "cpu:-1"])
def test_set_device_raises_for_a_device_that_is_not_there(name):
    with pytest.raises(ValueError, match="no device"):
        device.set_device(name)


def test_set_device_takes_an_existing_device():
    assert device.set_device("cpu:3").index == 3
    device.set_device("cpu")


def test_on_tpu_does_not_swallow_backend_errors(monkeypatch):
    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        pallas.interpret_mode()


def test_chip_peak_knows_v5e_and_nothing_it_was_not_told():
    bench = _bench()

    class Dev:
        device_kind = "TPU v5 lite"

    assert bench.chip_peak(Dev()) == 197e12
    Dev.device_kind = "cpu"
    with pytest.raises(ValueError, match="no published peak"):
        bench.chip_peak(Dev())


def test_bench_attempt_records_a_rung_that_raised():
    bench = _bench()

    def run_boom(name):
        raise MemoryError("RESOURCE_EXHAUSTED")

    assert bench.attempt(run_boom, "xl") is False
    assert bench.FAILED_RUNGS == ["run_boom:xl"]


def test_bench_exits_nonzero_without_a_tpu_and_parent_stays_off_jax():
    """`python bench.py` here (no TPU): no result line, exit code 1 — and
    the parent process never imports jax (one process per chip)."""
    probe = ("import runpy, sys\n"
             "try:\n"
             "    runpy.run_path('bench.py', run_name='__main__')\n"
             "except SystemExit as e:\n"
             "    print('RC', e.code, 'JAX_IN_PARENT', 'jax' in sys.modules)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_TPU_TIMEOUT="120",
               BENCH_MODE_TIMEOUT="120")
    out = subprocess.run([sys.executable, "-c", probe], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert "RC 1 JAX_IN_PARENT False" in out.stdout, out.stdout + out.stderr
    assert '"metric"' not in out.stdout


@pytest.mark.parametrize("shape,problem", [
    ((32, 8, 128, 64), None),
    ((32, 8, 100, 64), "head_dim=100"),
    ((32, 8, 128, 60), "block_size=60"),
    ((30, 8, 128, 64), "num_heads=30"),
])
def test_kernel_shape_problem_names_the_reason(shape, problem):
    why = pa.kernel_shape_problem(*shape)
    assert (why is None) if problem is None else (problem in why)
    assert pa.kernel_supported(*shape) == (problem is None)


def test_engine_on_tpu_warns_when_a_default_kernel_is_ruled_out(monkeypatch):
    """An engine built on a TPU whose shapes the kernels cannot take says so
    at construction instead of quietly serving from the reference."""
    from paddle_tpu.inference import serving
    from paddle_tpu.models import llama

    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    with pytest.warns(UserWarning, match="paged_attention.*block_size=12"):
        eng = serving.ContinuousBatchingEngine(
            cfg, params, max_batch=2, max_seq=48, paged=True, block_size=12)
    assert not eng._fused
