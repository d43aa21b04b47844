import pytest

from benchmarks.harness import trace_reduce


def hand_made():
    ops = {"/device:TPU:0": [
        ("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 2.5),     # overlap: 1.5 s
        ("flash_fwd", 4.0, 5.0), ("flash_fwd", 5.0, 5.5),
        ("fusion.1", 9.0, 10.0)]}
    host = [("bench/engine.step", 0.0, 6.0), ("bench/bookkeeping", 2.4, 4.1),
            ("np.asarray", 2.6, 3.9), ("bench/feed", 6.0, 10.0)]
    return ops, host


def test_overlapping_ops_count_once_and_the_window_is_the_spans():
    r = trace_reduce.reduce(*hand_made())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(1.5 + 1.5 + 1.0)
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == pytest.approx(2.0)
    assert r["op_counts"]["flash_fwd"] == 2


def test_a_gap_goes_to_the_innermost_host_span_that_covers_it():
    r = trace_reduce.reduce(*hand_made())
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 2.5..4.0 (middle 3.25) lies in engine.step > bookkeeping > np.asarray
    assert gaps["np.asarray"] == pytest.approx(1.5)
    # 0..1 lies in engine.step alone; 5.5..9 (middle 7.25) in bench/feed
    assert gaps["bench/engine.step"] == pytest.approx(1.0)
    assert gaps["bench/feed"] == pytest.approx(3.5)
    assert sum(gaps.values()) == pytest.approx(10.0 - r["busy_s"])


def test_two_devices_average_and_patterns_match_names():
    ops, host = hand_made()
    ops["/device:TPU:1"] = [("fusion.1", 0.0, 10.0)]
    r = trace_reduce.reduce(ops, host)
    assert r["busy_s"] == pytest.approx((4.0 + 10.0) / 2)
    assert trace_reduce.matched_seconds(r, ["^flash_"]) == \
        (pytest.approx(1.5), 2)
    assert trace_reduce.matched_seconds(r, ["nothing"]) == (0, 0)


def test_no_device_events_reads_nothing():
    r = trace_reduce.reduce({}, [("bench/engine.step", 0.0, 1.0)])
    assert r["busy_s"] == 0.0 and r["device_ops"] == []


FLASH = ('%checkpoint.18 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, '
         'bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[128,2048,'
         '128]{2,1,0} %bitcast.582), custom_call_target="tpu_custom_call"')
NORM = ('%closed_call.25 = bf16[16384,2048]{1,0:T(8,128)(2,1)} custom-call('
        'bf16[16384,2048]{1,0} %bitcast.575), '
        'custom_call_target="tpu_custom_call"')
LOOP = ('%while.2 = (s32[]{:T(128)}, bf16[32,128,4096]{2,1,0:T(8,128)(2,1)})'
        ' while((s32[]{:T(128)}, bf16[32,128,4096]{2,1,0}) %tuple.1), '
        'condition=%cond, body=%body')


def test_device_event_names_are_cut_to_name_opcode_type():
    assert trace_reduce.short_name(FLASH) == \
        "checkpoint.18 custom-call (bf16[128,2048,128], bf16[128,2048,128])"
    assert trace_reduce.short_name(NORM) == \
        "closed_call.25 custom-call bf16[16384,2048]"
    assert trace_reduce.contains_others(LOOP)
    assert not trace_reduce.contains_others(FLASH)
    r = trace_reduce.reduce({"/device:TPU:0": [
        (LOOP, 0.0, 10.0), (FLASH, 1.0, 2.0), (NORM, 2.0, 2.5)]}, [])
    assert [n for n, _ in r["device_ops"]] == [
        trace_reduce.short_name(FLASH), trace_reduce.short_name(NORM)]
    assert r["busy_s"] == pytest.approx(10.0)


def test_flash_attention_roofline_reads_the_metrics_own_pattern():
    import json
    import os

    from benchmarks.harness import work
    from benchmarks.harness.cell import HERE
    from benchmarks.readers import trace as readers

    with open(os.path.join(HERE, "layer_metrics",
                           "flash_attn_roofline.json")) as f:
        spec = json.load(f)
    with open(os.path.join(HERE, "configs", "yicoder1.5b-l8.json")) as f:
        m = json.load(f)["model"]
    peak = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # two steps' worth of events (32 a step), 3 ms each; rms_norm's beside
    ops = [(FLASH, 0.01 * i, 0.01 * i + 0.003) for i in range(64)]
    ops += [(NORM, 1.0 + 0.01 * i, 1.0 + 0.01 * i + 0.001) for i in range(9)]
    obs = {"trace": trace_reduce.reduce({"/device:TPU:0": ops}, []),
           "model": m, "batch": 8, "seq": 2048, "peak": peak}
    least = work.flash_attn_train_flops(m, 8, 2048) / 197e12
    assert least == pytest.approx(0.01675, rel=1e-2)     # compute-bound
    assert readers.flash_attn_roofline(obs, spec) == pytest.approx(
        100 * least * 2 / (64 * 0.003))
    # nothing matched: nothing read, never 0
    obs["trace"] = trace_reduce.reduce({"/device:TPU:0": ops[64:]}, [])
    assert readers.flash_attn_roofline(obs, spec) is None
