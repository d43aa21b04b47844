"""The ``serve_model`` runner end to end at a tiny Olmo-Hybrid on the CPU
(one period of three linear-attention layers and a full one, float32), by
calling what ``run.py`` calls after its look for a chip; then with a fault
planted in the program (a slot's recurrent state not started from zero when
the slot takes a new request) and with the reference's controls in the
program's place: ``correct`` must come out false each time, and each
control must have moved the logits it was compared on."""

import dataclasses
import json
import time

import pytest

from benchmarks import run as bench
from benchmarks.harness import work_olmo_hybrid as work
from benchmarks.harness.cell import ROOT, Cell, load_json
from benchmarks.readers import olmo_hybrid as readers
from benchmarks.tests import tiny

# float32 on the CPU: program and reference differ by float32 rounding
# through four layers.  The weights are drawn ten times as wide as the
# cell's (normal(0, 0.2): ``wide_weights``): at 64 wide, 0.02 leaves the
# logits so flat that neither a control nor a fault moves an argmax; at
# 0.2 rounding reads up to 7e-4 on logits of magnitude 6, a bf16 state 0.1
# and more (tests/test_olmo_hybrid.py).  At the cell's size, on the chip,
# the same fault reads 4.77 and 3.36 against the cell's 2.7 (PERF.md
# section 6)
LIMIT = 2e-3
LINEAR, FULL = "linear_attention", "full_attention"
CONFIG = {
    "name": "tiny-hybrid", "program": "paddle_tpu.models.olmo_hybrid",
    "weights": "benchmarks.harness.weights_olmo_hybrid",
    "work": "benchmarks.harness.work_olmo_hybrid",
    "reference": "benchmarks.reference.olmo_hybrid",
    "model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "torch_dtype": "float32"}


def cell(seconds=0.8, trace=False, tmp="/tmp", limit=LIMIT):
    mix = dict(tiny.serve_mix(limit=limit), kind="serve_model")
    # answers long enough, and all of them compared, that a control which
    # moves the logits by a hundredth moves a token's rank somewhere
    mix["max_new_tokens"] = {"dist": "lognormal", "median": 14,
                             "sigma": 0.4, "min": 6, "max": 28}
    mix["check"] = dict(mix["check"], requests=40)
    return Cell(name="tiny." + mix["name"], config=CONFIG, mix=mix, chips=1,
                seed=7, seconds=seconds, trace=trace, peak=tiny.PEAK,
                t0=time.perf_counter(), trace_dir=str(tmp))


@pytest.fixture(scope="module", autouse=True)
def wide_weights():
    from benchmarks.harness import weights_olmo_hybrid

    mp = pytest.MonkeyPatch()
    mp.setattr(weights_olmo_hybrid, "STD", 0.2)
    yield
    mp.undo()


@pytest.fixture(scope="module")
def served():
    c = cell()
    return c, bench.execute(c, controls=("fp8", "state_bf16", "bf16",
                                         "bf16_all"))


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    c = cell(seconds=0.5, trace=True, tmp=tmp_path_factory.mktemp("trace"))
    return c, bench.execute(c)


def test_runner_end_to_end(served):
    c, res = served
    assert res["correct"], res["compared"]
    assert res["attempted"] > 10 and res["failed"] == 0
    e2e = res["end_to_end"]
    assert {"setup_s", "ttft_p95_ms", "itl_p95_ms"} <= set(e2e)
    assert res["compared"]["logit_gap"]["value"] <= LIMIT / 3
    obs = res["obs"]
    assert obs["counters"]["state_starts"] >= res["attempted"]
    assert 0 < obs["counters"]["gdn_rows_live"] \
        < obs["counters"]["gdn_rows_computed"]
    assert obs["work"]["flops"] > 0 and obs["work"]["rows"] > 0


def test_the_traced_steps_counters_and_the_readers(traced_run):
    c, res = traced_run
    obs = res["obs"]
    traced = obs["traced"]
    assert 0 < traced["steps"] <= len(obs["steps"])
    assert 0 < traced["counters"]["decode_steps"] <= traced["steps"]
    assert traced["counters"]["gdn_rows_live"] \
        <= obs["counters"]["gdn_rows_live"] + 64
    # the CPU's trace has no TPU plane: the readers of the trace return
    # nothing, never 0 — as they do on a program without the counters
    spec = {"params": {"kernel": "gdn_chunk_prefill"}}
    assert readers.gdn_chunk_roofline(obs, spec) is None
    assert readers.gdn_decode_roofline(dict(obs, traced=None), spec) is None
    # with a device time put in the kernel's place, the share is the work
    # module's least time over it
    name = "%gdn_chunk_prefill.3 = f32[1]{0} custom-call(f32[1]{0} %p)"
    fake = dict(obs, trace={"op_seconds": {name: 2.0},
                            "op_counts": {name: 6}})
    tc = traced["counters"]
    least, _ = work.roofline_s(*work.gdn_work(
        CONFIG, tc["gdn_chunk_rows_live"],
        tc["state_chunk_slot_steps_live"]), tiny.PEAK)
    assert least > 0
    assert readers.gdn_chunk_roofline(fake, spec) == pytest.approx(
        100.0 * least / 2.0)
    assert readers.gdn_decode_roofline(
        fake, {"params": {"kernel": "gdn_decode_step"}}) is None


def test_result_line_has_the_new_metrics_where_they_read(traced_run):
    c, res = traced_run
    manifest = load_json(ROOT, "BENCHMARK.json")
    named = dataclasses.replace(c, name="olmo-hybrid-chat")
    line = bench.result_line(manifest, named, {"platform": "cpu"}, res)
    assert "gdn.live_row_pct" in line["metrics"]
    assert 0 < line["metrics"]["gdn.live_row_pct"]["value"] < 100
    assert "serve_step.mfu" in line["metrics"]
    json.dumps(line)


def test_controls_are_not_correct(served):
    c, res = served
    for lower, control in res["control"].items():
        assert control["correct"] is False, lower
        assert control["compared"]["logit_gap"]["value"] > LIMIT
        # a control that moved no logit would be no control at all
        assert control["compared"]["logit_moved"]["value"] > LIMIT
    assert res["control"]["fp8"]["compared"]["logit_gap"]["value"] \
        > 3 * LIMIT
    # the program's own comparison carries no such entry
    assert "logit_moved" not in res["compared"]


def test_a_state_not_started_from_zero_is_not_correct(monkeypatch):
    """The planted fault: the chunked recurrence never hears that a lane is
    fresh, so a slot's second request starts from its first one's state."""
    from paddle_tpu.models import olmo_hybrid

    chunked = olmo_hybrid.gated_delta_rule_chunked

    def stale(q, k, v, g, beta, state, valid, fresh=None, layer=None):
        return chunked(q, k, v, g, beta, state, valid, None, layer)

    monkeypatch.setattr(olmo_hybrid, "gated_delta_rule_chunked", stale)
    res = bench.execute(cell(seconds=0.6))
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > LIMIT


def test_the_cells_files_agree_with_the_manifest():
    manifest = load_json(ROOT, "BENCHMARK.json")
    w, config, mix = bench.find_cell(manifest, "olmo-hybrid-chat")
    assert (w["chips"], mix["kind"]) == (1, "serve_model")
    assert config["num_hidden_layers"] == 16 and len(config["layer_types"]) \
        == 32 and config["reduced"] == ["num_hidden_layers"]
    assert work.total_params(config) == 4_100_788_944
    assert work.kv_bytes_per_token(config) == 61_440
    assert work.state_bytes_per_slot(config) == 12 * (2_211_840 + 69_120)
    for entry in manifest["configs"] + manifest["workloads"]:
        assert len(entry["why"]) <= 200
    new = [m for m in manifest["per_layer"]
           if m.get("workloads") == ["olmo-hybrid-chat"]]
    assert [m["name"] for m in new] == ["gdn_chunk.roofline",
                                        "gdn_decode.roofline",
                                        "gdn.live_row_pct"]
    for m in new:
        spec = load_json(ROOT, "benchmarks", "layer_metrics",
                         m["name"] + ".json")
        assert {k: spec[k] for k in m} == m
