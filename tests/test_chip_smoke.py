"""chip_smoke.py's control flow on the CPU at LlamaConfig.tiny() size (the
script's phases are functions of a config), and the compile-cache helper.
The chip run itself is `python chip_smoke.py` through the chip tool."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from paddle_tpu.models import llama
from paddle_tpu.utils import compile_cache

TINY = dict(prompt_lens=(20, 33, 47), max_new=8, max_batch=4, max_seq=128,
            block_size=16, prefill_chunk=16)


def test_serve_phase_tiny():
    rep = chip_smoke.run_serve(llama.LlamaConfig.tiny(), **TINY)
    assert rep["failed"] == []
    assert rep["requests"] == 3 and rep["new_tokens"] == 24
    assert rep["mixed_steps"] > 0
    assert rep["counters"]["FUSED_KERNEL_CALLS"] > 0
    assert rep["counters"]["MLP_KERNEL_CALLS"] > 0
    assert rep["counters"]["PREFILL_KERNEL_CALLS"] > 0
    tf = rep["teacher_forced"]
    assert tf["wrong"] == 0
    assert tf["argmax_agree"] + tf["near_tie"] == tf["positions"] == 24


@pytest.mark.parametrize("counters,msg", [
    ({"FUSED_KERNEL_CALLS": 2, "MLP_KERNEL_CALLS": 2,
      "PREFILL_KERNEL_CALLS": 0, "PREFILL_FALLBACK_CALLS": 0}, "not taken"),
    ({"FUSED_KERNEL_CALLS": 2, "MLP_KERNEL_CALLS": 2,
      "PREFILL_KERNEL_CALLS": 1, "MLP_FALLBACK_CALLS": 1}, "fell back"),
])
def test_serve_counters_check_fails(counters, msg):
    (failed,) = chip_smoke.check_serve_counters(counters)
    assert msg in failed


def test_teacher_force_catches_wrong_tokens():
    cfg = llama.LlamaConfig.tiny()
    params = chip_smoke.make_params(cfg, 0)
    (prompt,) = chip_smoke.make_prompts(cfg, 0, (24,))
    # the reference's own greedy continuation passes ...
    out = []
    for _ in range(4):
        ids = np.concatenate([prompt, np.asarray(out, np.int32)])[None]
        logits = llama.forward(cfg, params, ids, use_flash=False, remat=False)
        out.append(int(np.asarray(logits[0, -1]).argmax()))
    assert chip_smoke.teacher_force(cfg, params, [prompt], [out])["failed"] == []
    # ... and its least likely token does not
    out[2] = int(np.asarray(logits[0, -2].astype(np.float32)).argmin())
    rep = chip_smoke.teacher_force(cfg, params, [prompt], [out])
    assert rep["wrong"] >= 1 and "disagree" in rep["failed"][0]


def test_train_phase_tiny():
    rep = chip_smoke.train_phase(
        llama.LlamaConfig.tiny(),
        llama.make_mesh(devices=jax.devices()[:1]), batch=2, seq=64, steps=3)
    assert rep["failed"] == []
    assert len(rep["losses"]) == 3 and rep["losses"][-1] < rep["losses"][0]
    assert rep["flash_attention"]["KERNEL_CALLS"] > 0
    assert rep["flash_attention"]["FALLBACK_CALLS"] == 0


def test_four_chip_phases_on_virtual_devices():
    """The --chips 4 paths on the CPU's virtual devices: TP=2 (tiny has 2
    KV heads) against TP=1, and dp2 x mp2 against one device."""
    cfg = llama.LlamaConfig.tiny()
    rep = chip_smoke.run_tp_serve(cfg, 2, **TINY)
    assert rep["failed"] == [] and rep["tokens_identical"]
    assert rep["tp2"]["teacher_forced"]["wrong"] == 0
    assert len(rep["kv_pool_bytes_per_device"]) == 2
    assert len(set(rep["sharded_weight_bytes_per_device"].values())) == 1
    rep = chip_smoke.run_mesh_train(cfg, batch=4, seq=64, steps=3)
    assert rep["failed"] == [] and rep["max_rel_loss_diff"] < 2e-2
    assert rep["dp2_mp2"]["mesh"] == {"dp": 2, "mp": 2}


def test_main_fails_without_a_tpu(capsys, monkeypatch):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


# ---------------------------------------------------------------------------
# compile-cache helper
# ---------------------------------------------------------------------------

_CACHE_SNIPPET = ("import jax; from paddle_tpu.utils.compile_cache import "
                  "enable_compile_cache as e; a = e(); b = e(); "
                  "print(a == b, a, jax.config.jax_compilation_cache_dir)")


def _cache_child(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_SNIPPET], env=env,
                         capture_output=True, text=True, check=True,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    return out.stdout.split()


def test_compile_cache_fixed_path_when_env_unset():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == want
    # identical across two calls (inside the child) and two processes
    assert _cache_child(None) == ["True", want, want]
    assert _cache_child(None) == ["True", want, want]


def test_compile_cache_env_var_wins(tmp_path):
    # the variable is set: the helper sets NO directory in code — what JAX
    # holds is what JAX itself read from the environment
    placed = str(tmp_path / "elsewhere")
    assert _cache_child(placed) == ["True", placed, placed]
    src = open(compile_cache.__file__).read()
    assert src.count("jax.config.update(") == 1

