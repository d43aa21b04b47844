"""The readers of what the program counts and names itself: a ratio of two
``engine.stats`` counters, and a Pallas kernel's device time by the
kernel's name; then a tiny run whose counters hold every key the
manifest's metrics read."""

import glob
import json
import os
import re

import pytest

from benchmarks import run as bench
from benchmarks.harness import trace_reduce
from benchmarks.harness.cell import HERE, ROOT, resolve
from benchmarks.readers import counters, kernels, program
from benchmarks.tests import tiny

TAIL = ('custom-call(bf16[32,128,4096]{2,1,0} %x), '
        'custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={}}')
PREFILL = "%ragged_prefill_attn.20 = bf16[32,8,512,128]{3,2,1,0} " + TAIL
DECODE = ("%fused_decode_attn.39 = (f32[32,8,1,4,1]{4,3,2,1,0}, "
          "bf16[513,8,64,128]{3,2,1,0}) " + TAIL)
QUANT = "%fused_decode_attn_q.41.clone = s8[513,8,64,128]{3,2,1,0} " + TAIL
UNNAMED = "%closed_call.20 = bf16[32,8,512,128]{3,2,1,0} " + TAIL


def spec_of(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_ratio_of_two_counters():
    spec = {"params": {"over": "prefill_rows_packed",
                       "under": "mixed_steps"}}
    obs = {"counters": {"prefill_rows_packed": 1240, "mixed_steps": 8}}
    assert program.ratio(obs, spec) == 155.0
    # nothing launched, or a program without the counter: nothing read
    assert program.ratio({"counters": {"prefill_rows_packed": 5,
                                       "mixed_steps": 0}}, spec) is None
    assert program.ratio({"counters": {}}, spec) is None
    # the parent of the PR that brought the counter counts mixed_steps alone
    assert program.ratio({"counters": {"mixed_steps": 4}}, spec) is None
    assert program.ratio({"counters": {"prefill_rows_packed": 0,
                                       "mixed_steps": 4}}, spec) == 0.0


def test_ms_per_event_tells_kernels_apart_by_name():
    ops = [(PREFILL, 0.01 * i, 0.01 * i + 0.005) for i in range(40)]
    ops += [(DECODE, 1.0 + 0.01 * i, 1.0 + 0.01 * i + 0.003)
            for i in range(20)]
    ops += [(QUANT, 2.0 + 0.01 * i, 2.0 + 0.01 * i + 0.009)
            for i in range(10)]
    obs = {"trace": trace_reduce.reduce({"/device:TPU:0": ops}, [])}
    read = lambda k: kernels.ms_per_event(obs, {"params": {"kernel": k}})
    assert read("ragged_prefill_attn") == pytest.approx(5.0)
    # a name that another starts with is not that other kernel
    assert read("fused_decode_attn") == pytest.approx(3.0)
    assert read("fused_decode_attn_q") == pytest.approx(9.0)
    assert read("fused_decode") is None and read("prefill_attn") is None
    # no trace, no device events, or kernels without names: nothing read
    assert kernels.ms_per_event({"trace": None},
                                {"params": {"kernel": "fused_mlp"}}) is None
    bare = {"trace": trace_reduce.reduce(
        {"/device:TPU:0": [(UNNAMED, 0.0, 0.005)]}, [])}
    assert kernels.ms_per_event(
        bare, {"params": {"kernel": "ragged_prefill_attn"}}) is None


def test_the_kernels_the_metrics_name_are_launched_under_that_name():
    """A metric's ``kernel`` is the ``name=`` of a pallas_call in the
    program, letter for letter."""
    spelled = set()
    for path in glob.glob(os.path.join(ROOT, "paddle_tpu", "ops", "pallas",
                                       "*.py")):
        with open(path) as f:
            spelled |= set(re.findall(r'name="(\w+)"', f.read()))
    for metric in ("ragged_prefill_attn.ms_per_call",
                   "fused_decode_attn.ms_per_call"):
        spec = spec_of(metric)
        assert spec["params"]["kernel"] in spelled
        assert resolve(spec["reader"]) is kernels.ms_per_event


def test_a_tiny_run_counts_every_key_the_new_metrics_read(tmp_path):
    mix = tiny.serve_mix("backlog", rate=200.0, limit=1e-4)
    cell = tiny.cell(mix, seconds=0.8, trace=True, tmp=tmp_path)
    res = bench.execute(cell)
    assert res["correct"], res["compared"]
    obs, c = res["obs"], res["obs"]["counters"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [spec_of(m["name"]) for m in manifest["per_layer"]
            if spec_of(m["name"])["reader"].startswith(
                ("benchmarks.readers.counters:", "benchmarks.readers.program:"))]
    assert len(mine) >= 6
    for spec in mine:
        assert {spec["params"]["over"], spec["params"]["under"]} <= set(c)
        value = resolve(spec["reader"])(obs, spec)
        assert value is not None and value > 0, spec["name"]
        assert spec["unit"] != "%" or value <= 100.0
    # the counters' own arithmetic, on what the harness saw
    launches = c["slot_steps_total"] // mix["engine"]["max_batch"]
    assert c["mixed_steps"] <= launches <= len(obs["steps"])
    assert c["step_rows_live"] <= c["step_rows_computed"]
    assert c["step_host_s"] <= c["step_total_s"]
    assert c["kv_page_steps_total"] == launches * mix["engine"]["num_blocks"]
    # the public count reads what the private read of engine._free reads
    from benchmarks.readers import host
    private = host.pool_in_use_pct(obs, {})
    public = counters.ratio_pct(obs, spec_of("kv.pool_occupancy_pct"))
    assert public == pytest.approx(private, abs=3.0)
    # the CPU's trace has no TPU plane: the kernel readers read nothing
    assert kernels.ms_per_event(
        obs, spec_of("ragged_prefill_attn.ms_per_call")) is None
