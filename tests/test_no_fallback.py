"""The machinery that could make a run look healthy without the chip is
gone (ISSUE 21): these pin the replacements — errors, not quiet defaults."""

import jax
import pytest

from paddle_tpu.core import device
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import paged_attention as pa


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
