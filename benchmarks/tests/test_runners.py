"""Both runners end to end at ``LlamaConfig.tiny()`` size on the CPU, by
calling what ``run.py`` calls after its look for a chip; then the same
with the timed path broken underneath, and with the reference computed in
a lower precision put in the program's place: ``correct`` must come out
false each time."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness.cell import ROOT
from benchmarks.tests import tiny

# float32 on the CPU: the program and the reference differ by float32
# rounding alone, so the limits here are those of float32, not the cells'
SERVE_LIMIT = 1e-4
TRAIN_LIMITS = {"loss_gap": 3e-5, "grad_gap": 5e-4, "change_gap": 7e-4}


@pytest.fixture(scope="module")
def served():
    cell = tiny.cell(tiny.serve_mix(limit=SERVE_LIMIT), seconds=1.0)
    return cell, bench.execute(cell, controls=("fp8",))


def test_serve_runner_end_to_end(served):
    cell, res = served
    assert res["correct"], res["compared"]
    assert res["attempted"] > 10 and res["failed"] == 0
    e2e = res["end_to_end"]
    assert {"setup_s", "ttft_p95_ms", "itl_p95_ms",
            "serve_tokens_per_s"} <= set(e2e)
    assert all(np.isfinite(v) and v > 0 for v in e2e.values())
    assert res["compared"]["tokens_compared"]["value"] >= 9


def test_serve_result_line_has_the_contracts_keys(served):
    cell, res = served
    manifest = {"end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "ttft_p95_ms", "unit": "ms", "workloads": [cell.name]},
        {"name": "serve_tokens_per_s", "unit": "tokens/s",
         "workloads": ["another"]}], "per_layer": []}
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    line = bench.result_line(manifest, cell, device, res)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"setup_s", "ttft_p95_ms"}
    assert line["metrics"]["setup_s"]["unit"] == "s"
    json.dumps(line)


def test_serve_control_in_lower_precision_is_not_correct(served):
    cell, res = served
    control = res["control"]["fp8"]
    assert control["correct"] is False
    assert control["compared"]["logit_gap"]["value"] > 3 * SERVE_LIMIT
    assert res["compared"]["logit_gap"]["value"] <= SERVE_LIMIT / 3


def test_serve_token_altered_where_it_is_produced_is_not_correct():
    def alter(engine, tracks):
        for tr in tracks:
            out = tr.req.output_ids
            if len(out) >= 2 and not getattr(tr, "altered", False):
                out[-1] = (out[-1] + 1) % 256
                tr.altered = True

    cell = tiny.cell(tiny.serve_mix(limit=SERVE_LIMIT), seconds=0.6)
    res = bench.execute(cell, step_hook=alter)
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > SERVE_LIMIT


def test_serve_request_that_never_answers_is_not_correct():
    def starve(engine, tracks):
        for tr in tracks:
            if tr.plan.due_s > 0.1 and tr.req.status == "RUNNING":
                engine.cancel(tr.req.rid)
                return

    cell = tiny.cell(tiny.serve_mix(limit=SERVE_LIMIT), seconds=0.6)
    res = bench.execute(cell, step_hook=starve)
    assert not res["correct"] and res["failed"] >= 1
    assert res["compared"]["never_answered"]["value"] == res["failed"]


def test_backlog_mix_and_traced_run(tmp_path):
    mix = tiny.serve_mix("backlog", rate=200.0, limit=SERVE_LIMIT)
    cell = tiny.cell(mix, seconds=0.8, trace=True, tmp=tmp_path)
    res = bench.execute(cell)
    assert res["correct"], res["compared"]
    obs = res["obs"]
    # the CPU's trace has no TPU plane: the reducer reads no device time,
    # and the readers of the trace then return nothing, never 0
    from benchmarks.readers import trace as trace_readers
    assert trace_readers.idle_pct(obs, {}) is None
    assert obs["counters"]["mixed_steps"] > 0
    # the rate is over the whole window, and the backlog was stepped past
    # the close only as far as the next first token
    e2e = res["end_to_end"]
    assert e2e["serve_tokens_per_s"] * cell.seconds == pytest.approx(
        e2e["generated_tokens_in_window"] + e2e["prompt_tokens_in_window"])
    past = [t for tr in obs["tracks"] for t in tr.token_s[:1]
            if t >= cell.seconds]
    assert len(set(past)) <= 1
    assert obs["work"]["rows"] > 0 and obs["work"]["bytes"] > 0
    assert not os.path.exists(os.path.join(str(tmp_path), cell.name))


@pytest.fixture(scope="module")
def trained():
    cell = tiny.cell(tiny.train_job(TRAIN_LIMITS), seconds=0.5)
    return cell, bench.execute(cell, controls=("fp8", "half_batch"))


def test_train_runner_end_to_end(trained):
    cell, res = trained
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 2
    assert res["end_to_end"]["train_tokens_per_s"] > 0
    line = bench.result_line({"end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "train_tokens_per_s", "unit": "tokens/s"}],
        "per_layer": []}, cell, {"platform": "cpu"}, res)
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}


def test_train_controls_are_not_correct(trained):
    cell, res = trained
    for name in ("fp8", "half_batch"):
        control = res["control"][name]
        assert control["correct"] is False, (name, control)
        got = control["compared"]
        assert any(got[k]["value"] > 3 * TRAIN_LIMITS[k]
                   for k in TRAIN_LIMITS), (name, got)


def test_train_step_that_returns_its_state_unchanged_is_not_correct():
    import jax
    import jax.numpy as jnp

    def frozen(step_fn):
        def step(params, opt, ids, labels):
            keep = jax.tree_util.tree_map(jnp.copy, (params, opt))
            loss, _, _ = step_fn(params, opt, ids, labels)
            return (loss,) + keep
        return step

    res = bench.execute(tiny.cell(tiny.train_job(TRAIN_LIMITS), seconds=0.3),
                        step_wrap=frozen)
    assert not res["correct"]
    # nothing moved: the change's norm is nought against the reference's
    assert res["compared"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out_is_not_correct():
    def half(step_fn):
        def step(params, opt, ids, labels):
            n = ids.shape[0] // 2
            return step_fn(params, opt, ids[:n], labels[:n])
        return step

    res = bench.execute(tiny.cell(tiny.train_job(TRAIN_LIMITS), seconds=0.3),
                        step_wrap=half)
    assert not res["correct"], res["compared"]


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b-docs",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_the_command_refuses_to_run_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b-docs",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
