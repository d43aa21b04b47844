"""The ``train_model`` runner end to end on the CPU at a tiny size of the
``laguna`` family (float32, so that the program and the reference differ
by float32 rounding alone), with faults planted underneath; ``work_laguna``
against numbers worked by hand; the family's readers on a hand-made
``obs``."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import work_laguna as work
from benchmarks.harness.cell import HERE, ROOT, Cell
from benchmarks.readers import laguna as readers
from benchmarks.tests import tiny

FULL, SLIDING = "full_attention", "sliding_attention"
KINDS = [FULL, SLIDING, SLIDING, SLIDING, FULL]
MODEL = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "moe_routed_scaling_factor": 2.5, "gating": True, "sliding_window": 16,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 64,
               "original_max_position_embeddings": 64, "beta_fast": 64,
               "beta_slow": 1, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 64},
    "layer_types": KINDS * 2,       # longer than the depth, as published
    "mlp_layer_types": ["dense"] + ["sparse"] * 9,
    "num_attention_heads_per_layer": [4 if k == FULL else 6
                                      for k in KINDS * 2],
    "experts_held": [0, 4], "num_experts_held": 4,
    "router_selection": "sequence_standard", "torch_dtype": "float32"}
CONFIG = {"name": "tiny-laguna", "family": "laguna",
          "reference": "benchmarks.reference.laguna",
          "program": "paddle_tpu.models.laguna",
          "weights": "benchmarks.harness.weights_laguna",
          "work": "benchmarks.harness.work_laguna", "model": MODEL}
LIMITS = {"grad_gap": 5e-4, "change_gap": 7e-4, "unrouted_grad_gap": 5e-4,
          "held_load_off": 0.5, "second_start_grad_gap": 5e-4,
          "second_start_change_gap": 7e-4}


def job(limits=LIMITS):
    return {"name": "tiny-moe-train", "kind": "train_model", "batch": 2,
            "seq": 256, "mesh": {"dp": 1, "mp": 1},
            "optimizer": {"lr": 3e-4, "weight_decay": 0.1, "beta1": 0.9,
                          "beta2": 0.95, "grad_clip": 1.0},
            "optimizer_assumed": {"eps": 1e-8},
            "env": {"PADDLE_TPU_XENT_CHUNK": "16"},
            "check": {"steps": 2, "limits": limits,
                      "zero_counters": {
                          "dropped_assignments": "moe_assignments_dropped"},
                      "unrouted_grad_gap": ["e_gate", "e_up", "e_down",
                                            "router"],
                      "from_work": {"held_load_off": "held_load_off"},
                      # 512 tokens x 4 choices, 4 of 16 experts held: a
                      # chunk is 768 rows; no absent expert kept, so a token
                      # chooses the held experts whose standard score is
                      # above nought, ~2 of 4: ~1,024 rows, two chunks
                      "second_start": {
                          "weights": {"absent_router_kept": 0.0},
                          "at_least": {"moe_rows_computed": 1536}}},
            "trace_s": 0.2}


def cell(seconds=0.3, trace=False, tmp="/tmp", limits=LIMITS):
    mix = job(limits)
    return Cell(name="tiny." + mix["name"], config=CONFIG, mix=mix, chips=1,
                seed=2**31 + 5, seconds=seconds, trace=trace, peak=tiny.PEAK,
                t0=time.perf_counter(), trace_dir=str(tmp))


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    c = cell(seconds=0.5, trace=True, tmp=tmp_path_factory.mktemp("trace"))
    return c, bench.execute(c, controls=("fp8", "half_batch"))


def test_train_model_runner_end_to_end(trained):
    c, res = trained
    assert res["correct"], res["compared"]
    firsts = {"loss_gap", "grad_gap", "change_gap", "unrouted_grad_gap"}
    assert set(res["compared"]) == firsts | {
        "second_start_" + k for k in firsts} | {
        "last_loss_not_finite", "dropped_assignments", "held_load_off",
        "second_start_short"}
    assert res["compared"]["dropped_assignments"] == {"value": 0, "limit": 0}
    assert res["compared"]["second_start_short"] == {"value": 0, "limit": 0}
    # a seeded router chosen by the sequence's standard scores: near its
    # share
    assert res["compared"]["held_load_off"]["value"] < 0.3
    assert (res["compared"]["unrouted_grad_gap"]["value"]
            <= res["compared"]["grad_gap"]["value"])
    assert res["attempted"] >= 2
    assert res["end_to_end"]["train_tokens_per_s"] > 0
    obs = res["obs"]
    # the window's counter deltas: 4 expert layers, 4 experts held
    steps = res["attempted"]
    assert obs["counters"]["moe_assignments_held"].shape == (4, 4)
    assert obs["counters"]["moe_assignments_total"].tolist() == [
        steps * 2 * 256 * 4] * 4
    assert obs["counters"]["moe_assignments_dropped"].sum() == 0
    # the traced steps' own counters: the last steps of the window
    traced = obs["traced"]
    assert 1 <= traced["steps"] <= steps
    assert traced["counters"]["moe_assignments_total"].tolist() == [
        traced["steps"] * 2 * 256 * 4] * 4
    manifest = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                               {"name": "train_tokens_per_s",
                                "unit": "tokens/s"}], "per_layer": []}
    line = bench.result_line(manifest, dataclasses.replace(c, trace=False),
                             {"platform": "cpu"}, res)
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert readers.moe_train_mfu(obs, {}) > 0
    assert 0 < readers.live_row_pct(obs, {}) <= 100
    assert readers.expert_load_max_over_mean(obs, {}) >= 1.0
    # the CPU's trace has no TPU plane: the kernels' readers return nothing
    assert readers.flash_roofline(obs, {"params": {
        "kernels": ["flash_attn_win_fwd"], "events_per_step": 12,
        "layer_type": SLIDING}}) is None
    assert readers.moe_experts_roofline(obs, {"params": {
        "match": ["^%?ragged-dot-none"], "steps_of": {
            "kernels": ["flash_attn_win_bwd_dq"],
            "events_per_step": 3}}}) is None


def test_train_model_controls_are_not_correct(trained):
    _, res = trained
    for name in ("fp8", "half_batch"):
        control = res["control"][name]
        assert control["correct"] is False, (name, control)
        got = control["compared"]
        assert any(got[k]["value"] > 3 * LIMITS[k] for k in LIMITS), (name,
                                                                      got)


def test_a_step_that_drops_an_assignment_is_not_correct(monkeypatch):
    """The grouped product handed fewer rows than the held experts were
    assigned: the step's own counter reads it and ``correct`` is false."""
    from paddle_tpu.models import moe_llama

    monkeypatch.setattr(moe_llama, "held_rows",
                        lambda g, k, e, held: (64, 1))
    res = bench.execute(cell())
    assert res["compared"]["dropped_assignments"]["value"] > 0
    assert not res["correct"], res["compared"]


def test_a_held_load_far_from_its_share_is_not_correct():
    """The window's counters against the deployment's load: a limit the
    seeded router's scatter cannot meet reads ``correct`` false by that
    number alone; so does a second start that fills less than it has to."""
    mix_limits = dict(LIMITS, held_load_off=1e-4)
    res = bench.execute(cell(limits=mix_limits))
    off = {k for k, v in res["compared"].items()
           if v["limit"] is not None and v["value"] > v["limit"]}
    assert off == {"held_load_off"} and not res["correct"]
    c = cell()
    c.mix["check"]["second_start"]["at_least"] = {"moe_rows_computed": 2304}
    res = bench.execute(c)
    assert res["compared"]["second_start_short"]["value"] == 1
    assert not res["correct"]


def test_half_of_the_batch_left_out_is_not_correct():
    def half(step_fn):
        def step(params, opt, ids, labels):
            n = ids.shape[0] // 2
            return step_fn(params, opt, ids[:n], labels[:n])
        return step

    res = bench.execute(cell(), step_wrap=half)
    assert not res["correct"], res["compared"]
    assert res["compared"]["dropped_assignments"]["value"] == 0


def test_the_new_cell_fails_at_once_without_its_program(tmp_path):
    """The benchmark's files over a checkout that lacks the program (the
    parent commit): the runner's first act is to import it."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys, time; sys.path.insert(0, '.');"
            "from benchmarks import run as bench;"
            "from benchmarks.tests import test_laguna_cell as t;"
            "bench.execute(t.cell())")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=dict(env, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "No module named 'paddle_tpu'" in out.stderr


def model():
    with open(os.path.join(HERE, "configs", "laguna-xs2-ep8-l5.json")) as f:
        return json.load(f)


def test_work_laguna_by_hand():
    m = model()
    h, hd = 2048, 128
    # layer 0: full attention of 48 heads, 8 KV heads, a gate a head
    full = 2 * h * 48 * hd + 2 * h * 8 * hd + h * 48
    assert full == 29_458_432 == work.attn_matmul_params(m, 0)
    assert work.attn_matmul_params(m, 4) == full
    sliding = 2 * h * 64 * hd + 2 * h * 8 * hd + h * 64
    assert sliding == 37_879_808 == work.attn_matmul_params(m, 1)
    assert work.mlp_stored_params(m, 0) == 3 * h * 8192 == 50_331_648
    expert = 3 * h * 512
    assert work.mlp_stored_params(m, 1) == h * 256 + expert + 32 * expert
    # 8 choices over 256 experts, 32 held: one held expert a token expected
    assert work.held_assignments_per_token(m) == 1.0
    assert work.mlp_active_params(m, 1) == h * 256 + expert + 1.0 * expert
    total = (full + 50_331_648 + 3 * (sliding + 33 * expert + h * 256)
             + (full + 33 * expert + h * 256) + 5 * 2 * h
             + 2 * 12544 * h + h)
    assert work.total_params(m) == total == 691_623_936
    active = (full + 50_331_648 + 3 * (sliding + 2 * expert + h * 256)
              + (full + 2 * expert + h * 256) + 12544 * h)
    assert work.active_matmul_params(m) == active == 275_841_024
    # window 512 at 8192 positions: 512 * 513 / 2 + 7680 * 512
    assert work.window_pairs(8192, 512) == 131_328 + 3_932_160
    assert work.window_pairs(100, 512) == work.causal_pairs(100) == 5050
    assert work.layer_pairs(m, 1, 8192) == 4_063_488
    assert work.layer_pairs(m, 0, 8192) == 8192 * 8193 / 2
    attn = (2 * 4 * hd * 48 * 33_558_528 + 3 * 4 * hd * 64 * 4_063_488)
    assert work.train_flops_per_token(m, 8192) == pytest.approx(
        6 * active + 3 * attn / 8192)
    assert work.train_flops_per_token(m, 8192) == pytest.approx(2.405e9,
                                                                rel=1e-3)
    # the three sliding layers of a step of 2 x 8192
    assert work.flash_train_flops(m, SLIDING, 2, 8192) == pytest.approx(
        3 * 3 * 4 * hd * 64 * 2 * 4_063_488)
    q, kv = 2 * 8192 * 64 * hd * 2, 2 * 8192 * 8 * hd * 2
    assert work.flash_train_bytes(m, SLIDING, 2, 8192) == 3 * (6 * q + 6 * kv)
    # the two full layers: 48 heads over every causal pair
    assert work.flash_train_flops(m, FULL, 2, 8192) == pytest.approx(
        2 * 3 * 4 * hd * 48 * 2 * 33_558_528)
    q = 2 * 8192 * 48 * hd * 2
    assert work.flash_train_bytes(m, FULL, 2, 8192) == 2 * (6 * q + 6 * kv)
    # 4 expert layers x 16,384 expected assignments, 18 x 2048 x 512 each
    assert work.expected_assignments(m, 16384) == 4 * 16384
    assert work.moe_experts_train_flops(m, 65536) == pytest.approx(
        65536 * 18 * h * 512)
    # rows in and out each way + 4 layers' 32 experts' weights each way
    assert work.moe_experts_train_bytes(m, 65536) == (
        4 * 65536 * h * 2 + 2 * 4 * 32 * expert * 2)
    # a token that reached half a held expert a layer, counted
    assert work.active_matmul_params(m, 0.5) == active - 4 * 0.5 * expert
    # the worst layer's load against total x 32 / 256
    counters = {"moe_assignments_held": np.array([[10] * 32, [12] * 32]),
                "moe_assignments_total": np.array([2560, 2560])}
    assert work.held_load_off({"model": m, "counters": counters}) == (
        pytest.approx(0.2))


def test_laguna_readers_on_a_hand_made_obs():
    m = model()
    peak = {"flops_per_s_bf16": 100e12, "hbm_bytes_per_s": 1e12}
    held = np.zeros((4, 32))
    held[:] = 100
    held[2, 7] = 400
    obs = {"model": m, "batch": 2, "seq": 8192, "tokens": 10 * 16384,
           "window_s": 8.0, "peak": peak, "trace": None,
           "counters": {"moe_assignments_held": held,
                        "moe_rows_computed": np.full(4, 5000.0)}}
    # 13,100 held assignments over 4 layers and 163,840 tokens, counted
    assert readers.moe_train_mfu(obs, {}) == pytest.approx(
        100 * work.train_flops_per_token(m, 8192, 13100 / 4 / 163840)
        * 163840 / (8 * 100e12))
    assert readers.expert_load_max_over_mean(obs, {}) == pytest.approx(
        400 / (13100 / 128))
    assert readers.live_row_pct(obs, {}) == pytest.approx(
        100 * 13100 / 20000)
    win = {"params": {"kernels": ["flash_attn_win_fwd",
                                  "flash_attn_win_bwd_dkv",
                                  "flash_attn_win_bwd_dq"],
                      "events_per_step": 12, "layer_type": SLIDING}}
    full = {"params": {"kernels": ["flash_attn_fwd", "flash_attn_bwd_dkv",
                                   "flash_attn_bwd_dq"],
                       "events_per_step": 8, "layer_type": FULL}}
    moe = {"params": {"match": ["^%?ragged-dot-none[\\w.]* = "],
                      "steps_of": {"kernels": ["flash_attn_win_bwd_dq"],
                                   "events_per_step": 3}}}
    # the traced steps' own counters: 2 steps of 1,500 held assignments
    obs["traced"] = {"steps": 2, "counters": {
        "moe_assignments_held": np.full((4, 32), 3000 / 128)}}
    assert readers.flash_roofline(obs, win) is None
    assert readers.moe_experts_roofline(obs, moe) is None
    call = ("%{} = bf16[128,8192,128]{{2,1,0}} custom-call(bf16[1]{{0}} %a), "
            "custom_call_target=\"tpu_custom_call\"")
    names = {call.format("flash_attn_win_fwd.3"): (0.010, 6),
             call.format("flash_attn_win_bwd_dkv.5"): (0.010, 3),
             call.format("flash_attn_win_bwd_dq.7.clone"): (0.004, 3),
             call.format("flash_attn_fwd.1"): (0.5, 4),
             call.format("flash_attn_bwd_dkv.2"): (0.3, 2),
             call.format("flash_attn_bwd_dq.2"): (0.2, 2),
             "%ragged-dot-none.4 = bf16[20480,512]{1,0} custom-call(s32[1] "
             "%n), custom_call_target=\"tpu_custom_call\"": (0.020, 8),
             "%fusion.9 = f32[8] fusion(f32[8] %x)": (1.0, 5)}
    obs["trace"] = {"window_s": 2.0, "busy_s": 1.9,
                    "op_seconds": {k: v[0] for k, v in names.items()},
                    "op_counts": {k: v[1] for k, v in names.items()}}
    least, bound = work.roofline_s(
        work.flash_train_flops(m, SLIDING, 2, 8192),
        work.flash_train_bytes(m, SLIDING, 2, 8192), peak)
    assert bound == "compute"
    # 12 events = one step's three sliding layers, in 24 ms of kernels
    assert readers.flash_roofline(obs, win) == pytest.approx(
        100 * least / 0.024)
    # 8 events = one step's two full layers, in 1 s of kernels; the
    # windowed kernels' names are other kernels
    least, _ = work.roofline_s(work.flash_train_flops(m, FULL, 2, 8192),
                               work.flash_train_bytes(m, FULL, 2, 8192), peak)
    assert readers.flash_roofline(obs, full) == pytest.approx(
        100 * least / 1.0)
    # 1,500 assignments a traced step; 3 bwd_dq events = 1 step
    least, _ = work.roofline_s(work.moe_experts_train_flops(m, 1500.0),
                               work.moe_experts_train_bytes(m, 1500.0), peak)
    assert readers.moe_experts_roofline(obs, moe) == pytest.approx(
        100 * least * 1 / 0.020)
    # an untraced run, and counters the parent's program does not keep:
    # nothing, and no error
    del obs["traced"]
    assert readers.moe_experts_roofline(obs, moe) is None
    obs["counters"] = {}
    assert readers.live_row_pct(obs, {}) is None
    assert readers.expert_load_max_over_mean(obs, {}) is None
    assert readers.moe_train_mfu(obs, {}) == pytest.approx(
        100 * work.train_flops_per_token(m, 8192) * 163840 / (8 * 100e12))
    del obs["counters"]
    assert readers.live_row_pct(obs, {}) is None
