"""Serving observability tests (ISSUE 11, docs/observability.md).

Covers the four pillars and their contracts:

* MetricsRegistry — typed counters/gauges/log2-bucket histograms, labels,
  Prometheus exposition, and the dict-compatible StatsView the engines'
  ``stats`` migrated onto (every counter key read anywhere in tests/
  must be registered with a help string — enforced by a source scan);
* request-lifecycle tracing — queued/prefill/decode spans + terminal
  markers per request, cross-replica failover/hedge flow links, one chrome
  trace per fleet chaos run, and the profiler host-buffer cap (bounded,
  drop-counted, drained on export);
* SLOTracker — streaming TTFT/TBT/queue-wait accounting whose
  ``goodput_at`` matches a hand-rolled poll-loop computation exactly;
* FlightRecorder — bounded ring, dumps (with metrics snapshot) on request
  FAILURE, EngineAuditError, and replica death.

THE overriding contract: recording is host-side post-step — a metric
recorded via callback from INSIDE a jitted step fails the host_sync lint
gate.
"""

from __future__ import annotations

import json
import pathlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.inference.observability import (ENGINE_STAT_SCHEMA,
                                                FLEET_STAT_SCHEMA,
                                                FlightRecorder,
                                                MetricsRegistry, SLOTracker,
                                                StatsView)
from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.models import llama

_CFG = llama.LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=8,
                              kv_heads=4, inter=128)
_CFG.dtype = jnp.float32
_PARAMS = None


def _tiny():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = llama.init_params(_CFG, jax.random.key(0))
    return _CFG, _PARAMS


def _engine(**kw):
    cfg, params = _tiny()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("paged", True)
    return ContinuousBatchingEngine(cfg, params, **kw)


def _requests(n=3, new=5, seed=0):
    rs = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt_ids=rs.randint(0, 128, (10 + i,)).astype(np.int32),
                    max_new_tokens=new) for i in range(n)]


@pytest.fixture(autouse=True)
def _clean_host_events():
    profiler.clear_host_events()
    yield
    profiler.set_host_event_capacity(65536)
    profiler.clear_host_events()


# ---------------- MetricsRegistry units ----------------

def test_counter_gauge_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("t_requests", "requests served").labels(replica="0")
    c.inc()
    c.inc(2)
    g = reg.gauge("t_time_s", "wall seconds").labels()
    g.set(1.5)
    text = reg.expose()
    assert "# HELP t_requests requests served" in text
    assert "# TYPE t_requests counter" in text
    assert 't_requests{replica="0"} 3' in text
    assert "# TYPE t_time_s gauge" in text
    assert "t_time_s 1.5" in text


def test_histogram_log2_buckets_and_cumulative_counts():
    reg = MetricsRegistry()
    h = reg.histogram("t_lat_seconds", "latency", lo=-2, hi=2).labels()
    # bounds: 0.25, 0.5, 1, 2, 4, +Inf
    for v in (0.1, 0.25, 0.26, 1.0, 3.0, 100.0):
        h.observe(v)
    assert h.count == 6 and h.sum == pytest.approx(104.61)
    pairs = dict(h.buckets(-2))
    assert pairs["0.25"] == 2          # 0.1 and 0.25 (boundary inclusive)
    assert pairs["0.5"] == 3           # + 0.26
    assert pairs["1"] == 4             # + 1.0 (boundary inclusive)
    assert pairs["4"] == 5             # + 3.0
    assert pairs["+Inf"] == 6          # + 100.0 (past the top bound)
    text = reg.expose()
    assert 't_lat_seconds_bucket{le="+Inf"} 6' in text
    assert "t_lat_seconds_count 6" in text


def test_histogram_nonpositive_and_nan_land_in_first_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("t_h", "h", lo=-2, hi=2).labels()
    h.observe(0.0)
    h.observe(-1.0)
    h.observe(float("nan"))
    assert dict(h.buckets(-2))["0.25"] == 3


def test_registry_reregistration_same_family_and_mismatch_raises():
    reg = MetricsRegistry()
    a = reg.counter("t_x", "help")
    b = reg.counter("t_x", "help")
    assert a is b
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t_x", "help")
    with pytest.raises(ValueError, match="help"):
        reg.counter("t_y", "")


# ---------------- StatsView dict compatibility ----------------

def test_stats_view_behaves_like_the_old_dict():
    reg = MetricsRegistry()
    view = StatsView(reg, ENGINE_STAT_SCHEMA, {"replica": "1"})
    view["decode_tokens"] += 3
    view["decode_time_s"] += 0.5
    assert view["decode_tokens"] == 3 and isinstance(view["decode_tokens"],
                                                     int)
    view.update(decode_steps=0, decode_tokens=0, decode_time_s=0.0)
    assert view["decode_tokens"] == 0
    d = dict(view)
    assert set(d) == set(ENGINE_STAT_SCHEMA)
    assert d["decode_time_s"] == 0.0
    # the same number is visible in the exposition, labelled
    view["prefix_hits"] += 2
    assert ('paddle_tpu_serving_prefix_hits{replica="1"} 2'
            in reg.expose())
    with pytest.raises(TypeError):
        del view["decode_tokens"]
    with pytest.raises(KeyError):
        view["no_such_stat"]
    # dynamic keys register on the fly (dict compatibility never raises)
    view["adhoc_counter"] = 7
    assert view["adhoc_counter"] == 7


def test_every_stats_key_read_in_tests_and_bench_is_registered():
    """Introspection satellite: scan tests/ for stats["..."] reads and
    require each key in a schema, with a non-empty help."""
    root = pathlib.Path(__file__).resolve().parent.parent
    pat = re.compile(r"stats\[[\"']([a-z_]+)[\"']\]")
    keys: set[str] = set()
    for path in sorted((root / "tests").glob("test_*.py")):
        keys |= set(pat.findall(path.read_text()))
    known = set(ENGINE_STAT_SCHEMA) | set(FLEET_STAT_SCHEMA)
    assert keys <= known, f"unregistered stat keys: {sorted(keys - known)}"
    for schema in (ENGINE_STAT_SCHEMA, FLEET_STAT_SCHEMA):
        for key, (kind, help) in schema.items():
            assert kind in ("counter", "gauge"), (key, kind)
            assert help.strip(), f"{key} needs a help string"


def test_engine_stats_keys_match_schema_exactly():
    eng = _engine()
    assert set(eng.stats) == set(ENGINE_STAT_SCHEMA)
    helps = eng.metrics.describe()
    for key in ENGINE_STAT_SCHEMA:
        assert helps[f"paddle_tpu_serving_{key}"].strip()


# ---------------- lifecycle tracing ----------------

def test_request_spans_emitted_and_export_drains(tmp_path):
    eng = _engine(enable_chunked_prefill=True, prefill_chunk=4)
    eng.serve(_requests(2, new=4))
    path = tmp_path / "trace.json"
    profiler.Profiler().export(str(path))
    events = json.load(open(path))["traceEvents"]
    names = {e["name"] for e in events}
    assert {"queued", "prefill_chunk", "decode"} <= names
    assert any(e["name"].startswith("terminal:FINISHED") for e in events)
    # spans carry the request id as their thread lane
    decode_tids = {e["tid"] for e in events if e["name"] == "decode"}
    assert decode_tids == {0, 1}
    # drain-on-export: the buffer is the export's, not a leak
    assert profiler.host_events_len() == 0
    # span counts are mirrored on the tracer
    assert eng._tracer.counts["decode"] == 2
    assert eng._tracer.counts["queued"] == 2


def test_trace_ids_assigned_and_stable():
    eng = _engine()
    reqs = _requests(2)
    eng.serve(reqs)
    assert reqs[0].trace_id == "req-0" and reqs[1].trace_id == "req-1"


def test_profiler_buffer_cap_drops_and_counts(tmp_path):
    prev = profiler.set_host_event_capacity(8)
    try:
        for i in range(20):
            with profiler.RecordEvent(f"span{i}"):
                pass
        native = profiler._native_lib() is not None
        if not native:
            # pure-python buffer: capped exactly, overflow counted
            assert profiler.host_events_len() == 8
            assert profiler.host_events_dropped() == 12
        path = tmp_path / "t.json"
        profiler.Profiler().export(str(path))
        events = json.load(open(path))["traceEvents"]
        if not native:
            assert any(e.get("name") == "host_events_dropped"
                       and e["args"]["dropped"] == 12 for e in events)
        # export drained and reset the drop counter
        assert profiler.host_events_len() == 0
        assert profiler.host_events_dropped() == 0
        profiler.add_trace_event({"name": "after", "ph": "i", "ts": 0})
        assert profiler.host_events_len() == 1
    finally:
        profiler.set_host_event_capacity(prev)


# ---------------- SLOTracker ----------------

def test_slo_tracker_streaming_accounting():
    t = SLOTracker()
    t.begin(1, 100.0)
    t.admitted(1, 100.5)
    t.tokens(1, 1, 101.0)       # ttft = 1.0
    t.tokens(1, 2, 101.2)       # gap 0.2
    t.tokens(1, 1, 103.0)       # gap 1.8 (the max)
    t.finish(1, "FINISHED", 103.1)
    t.begin(2, 100.0)
    t.tokens(2, 1, 109.0)       # ttft 9.0: blows a 5s TTFT SLO
    t.finish(2, "FINISHED", 109.1)
    t.begin(3, 100.0)
    t.finish(3, "FAILED", 101.0)    # non-FINISHED never counts
    rec = {r["rid"]: r for r in t.records}
    assert rec[1]["ttft_s"] == pytest.approx(1.0)
    assert rec[1]["max_gap_s"] == pytest.approx(1.8)
    assert rec[1]["tokens"] == 4
    assert rec[3]["ttft_s"] is None
    g = t.goodput_at(ttft_slo_s=5.0, tbt_slo_s=2.0)
    assert g == {"requests": 1, "tokens": 4, "rids": (1,)}
    # tighter TBT SLO kills request 1's 1.8s gap
    assert t.goodput_at(5.0, 1.0)["requests"] == 0
    # looser TTFT admits request 2 (single arrival -> no gap to judge)
    assert t.goodput_at(10.0, 2.0)["tokens"] == 5


def test_engine_slo_histograms_and_records():
    eng = _engine()
    eng.serve(_requests(3, new=4))
    assert len(eng.slo.records) == 3
    assert all(r["status"] == "FINISHED" and r["tokens"] == 4
               for r in eng.slo.records)
    g = eng.slo.goodput_at(60.0, 60.0)
    assert g["requests"] == 3 and g["tokens"] == 12
    text = eng.metrics.expose()
    assert "paddle_tpu_serving_ttft_seconds_count 3" in text
    assert "paddle_tpu_serving_queue_wait_seconds_count 3" in text
    # host-gap + step-time histograms observed at least one step
    assert re.search(r"paddle_tpu_serving_step_seconds_count [1-9]", text)


# ---------------- flight recorder ----------------

def test_flight_recorder_ring_bounds_and_drop_counter():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("e", i=i)
    assert len(fr) == 4 and fr.dropped == 6
    assert [e["i"] for e in fr.events()] == [6, 7, 8, 9]
    d = fr.dump("why")
    assert d["events_dropped"] == 6 and len(d["events"]) == 4
    assert fr.dumps[-1] is d
    json.loads(fr.dump_json("again"))       # serializable


def test_flight_dump_on_request_failure(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULT_INJECT", "slot_error@step=3")
    eng = _engine()
    reqs = _requests(2, new=6)
    eng.serve(reqs)
    assert sum(r.status == "FAILED" for r in reqs) == 1
    assert len(eng._flight.dumps) == 1
    d = eng._flight.dumps[0]
    assert d["reason"].startswith("request_failed")
    assert "paddle_tpu_serving_requests_failed" in d["metrics"]
    kinds = {e["kind"] for e in d["events"]}
    assert {"admit", "fault", "terminal"} <= kinds


def test_flight_dump_on_engine_audit_error(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_ENGINE_AUDIT", "1")
    from paddle_tpu.analysis import EngineAuditError

    eng = _engine(num_blocks=16, enable_prefix_caching=True)
    eng.serve([Request(rid=0, prompt_ids=np.arange(1, 20, dtype=np.int32),
                       max_new_tokens=4)])
    assert eng._pcache.resident_blocks() > 0
    victim = next(iter(eng._pcache._by_hash.values()))
    victim.refcount += 1        # inject: a ref no slot holds
    eng.add_request(Request(rid=1,
                            prompt_ids=np.arange(1, 9, dtype=np.int32),
                            max_new_tokens=2))
    with pytest.raises(EngineAuditError):
        while eng.step() or eng._queue:
            pass
    assert [d["reason"] for d in eng._flight.dumps] == ["engine_audit_error"]


# ---------------- fleet: links, dumps, SLO parity ----------------

def _fleet(n=3, fault=None, **kw):
    import os

    from paddle_tpu.inference.fleet import FleetRouter

    cfg, params = _tiny()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("paged", True)
    if fault is not None:
        os.environ["PADDLE_TPU_FAULT_INJECT"] = fault
    try:
        return FleetRouter(cfg, params, n_replicas=n, **kw)
    finally:
        os.environ.pop("PADDLE_TPU_FAULT_INJECT", None)


def _poll_serve(fleet, reqs):
    """Bench-style poll loop: per-request arrival timestamps recorded after
    every fleet step — the hand-rolled TTFT/TBT evidence the SLOTracker
    must reproduce."""
    import time as _time

    for r in reqs:
        fleet.add_request(r)
    seen = {r.rid: 0 for r in reqs}
    arrivals = {r.rid: [] for r in reqs}
    while fleet.step():
        now = _time.perf_counter()
        for r in reqs:
            if len(r.output_ids) > seen[r.rid]:
                seen[r.rid] = len(r.output_ids)
                arrivals[r.rid].append(now)
    return arrivals


def test_fleet_chaos_produces_all_four_artifacts(tmp_path):
    """Acceptance criterion: a fleet chaos run yields (1) one chrome trace
    with cross-replica failover links, (2) a Prometheus snapshot, (3) a
    flight-recorder dump on the injected replica death, (4) an SLOTracker
    goodput figure matching the hand-rolled poll-loop computation."""
    fleet = _fleet(fault="replica_crash@step=6,replica=1",
                   enable_prefix_caching=True, enable_chunked_prefill=True,
                   prefill_chunk=8)
    reqs = _requests(5, new=6, seed=3)
    arrivals = _poll_serve(fleet, reqs)
    assert all(r.status == "FINISHED" for r in reqs)
    assert fleet.stats["failovers"] == 1

    # (4) SLOTracker goodput == hand-rolled figure (generous SLOs: every
    # FINISHED request qualifies on both arms, so the sets must be equal)
    ttft_slo, tbt_slo = 120.0, 120.0

    def met(r):
        if r.status != "FINISHED" or r.ttft_s is None or r.ttft_s > ttft_slo:
            return False
        gaps = [b - a for a, b in zip(arrivals[r.rid], arrivals[r.rid][1:])]
        return not gaps or max(gaps) <= tbt_slo

    hand_ok = [r for r in reqs if met(r)]
    g = fleet.slo.goodput_at(ttft_slo, tbt_slo)
    assert set(g["rids"]) == {r.rid for r in hand_ok}
    assert g["tokens"] == sum(len(r.output_ids) for r in hand_ok)
    # tracker TTFT is byte-equal to the caller-visible Request.ttft_s
    recs = {r["rid"]: r for r in fleet.slo.records}
    for r in reqs:
        assert recs[r.rid]["ttft_s"] == r.ttft_s

    # (2) Prometheus snapshot over the shared registry: fleet + per-replica
    text = fleet.metrics.expose()
    assert "paddle_tpu_fleet_failovers 1" in text
    assert 'paddle_tpu_serving_decode_tokens{replica="0"}' in text

    # (3) flight-recorder dump on the replica death, with the dead
    # engine's own ring attached
    assert len(fleet._flight.dumps) == 1
    d = fleet._flight.dumps[0]
    assert "replica 1 DEAD" in d["reason"]
    assert d["replica"] == 1 and d["engine_events"]
    kinds = {e["kind"] for e in d["events"]}
    assert {"route", "health", "failover"} <= kinds

    # (1) one chrome trace with cross-replica failover links
    path = tmp_path / "fleet.json"
    fleet.export_trace(str(path))
    events = json.load(open(path))["traceEvents"]
    outs = [e for e in events if e["ph"] == "s" and e["name"] == "failover"]
    ins = {e["id"]: e for e in events
           if e["ph"] == "f" and e["name"] == "failover"}
    assert outs and all(o["id"] in ins for o in outs)
    for o in outs:
        assert o["pid"] == 1                      # from the dead replica
        assert ins[o["id"]]["pid"] != 1           # onto a survivor
    # replica process lanes are named for the timeline
    pnames = {e["args"]["name"] for e in events
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert {"replica-0", "replica-1", "replica-2"} <= pnames


def test_fleet_hedge_emits_linked_spans():
    fleet = _fleet(n=2, fault="replica_stall@replica=0,count=8",
                   stall_steps=2, stall_dead_steps=50)
    reqs = _requests(2, new=4, seed=5)
    _poll_serve(fleet, reqs)
    assert fleet.stats["hedges"] >= 1
    assert all(r.status == "FINISHED" for r in reqs)
    # hedge flow links: out on the stalled replica, in on the survivor
    outs = [c for t in fleet._tracers for c in [t.counts.get("hedge", 0)]]
    assert outs[0] >= 1 and outs[1] >= 1
    kinds = {e["kind"] for e in fleet._flight.events()}
    assert "hedge" in kinds and "health" in kinds


def test_process_names_survive_drain_on_export(tmp_path):
    """Periodic-export regression: the replica lane-name metadata must
    re-emit after export() drains the buffer, or every trace after the
    first renders bare pids."""
    eng = _engine()
    eng.serve(_requests(1, new=2))
    profiler.Profiler().export(str(tmp_path / "t1.json"))
    eng.serve(_requests(1, new=2, seed=1))
    path2 = tmp_path / "t2.json"
    profiler.Profiler().export(str(path2))
    events = json.load(open(path2))["traceEvents"]
    assert any(e.get("ph") == "M" and e.get("name") == "process_name"
               for e in events)


# ---------------- the step measured from inside ----------------

_PHASES = ["serving/admit", "serving/pack", "serving/dispatch",
           "serving/host_overlap", "serving/fetch", "serving/bank"]


def _drive(eng, reqs):
    """add_request + step() to the end; the pool's fill after each step
    that launched, as a harness outside the engine would sample it."""
    for r in reqs:
        eng.add_request(r)
    fills = []
    while eng.step():
        fills.append((eng.num_blocks - len(eng._free)) / eng.num_blocks)
    return fills


class _Recorder:
    """A recording stand-in for ``jax.profiler.TraceAnnotation``."""

    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _Recorder.log.append(("begin", self.name, self.kw))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("end", self.name))


@pytest.fixture
def recorded(monkeypatch):
    _Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    return _Recorder.log


def _step_trees(log):
    """[(step args, [(child name, child args)])] of a recorded log, with
    the nesting checked: children open and close inside their
    ``serving/step``, one after another."""
    trees, stack = [], []
    for ev in log:
        if not ev[1].startswith("serving/"):
            continue
        if ev[0] == "begin":
            if ev[1] == "serving/step":
                assert not stack
                trees.append((ev[2], []))
            elif ev[1] in _PHASES:
                assert stack == ["serving/step"], stack
                trees[-1][1].append((ev[1], ev[2]))
            stack.append(ev[1])
        else:
            assert stack.pop() == ev[1]
    assert not stack
    return trees


@pytest.mark.parametrize("chunk,budget,rows", [(4, None, 8), (8, 8, 8),
                                               (8, None, 16)])
def test_step_counters_after_chunked_serve(chunk, budget, rows):
    """Chunked prefill, no prefix cache, no preemption: every prompt row is
    packed exactly once, and each mean over the serve is a ratio of two
    counters."""
    eng = _engine(enable_chunked_prefill=True, prefill_chunk=chunk,
                  token_budget=budget, num_blocks=24)
    assert eng._mixed_rows == rows
    reqs = _requests(3, new=5)
    fills = _drive(eng, reqs)
    st = dict(eng.stats)
    assert set(st) == set(ENGINE_STAT_SCHEMA)
    assert st["preemptions"] == 0 and len(fills) > st["mixed_steps"] > 0
    assert st["prefill_rows_packed"] == sum(r.prompt_ids.size for r in reqs)
    assert 0 < st["step_rows_live"] <= st["step_rows_computed"]
    assert st["slot_steps_total"] == eng.max_batch * len(fills)
    assert 0 < st["slot_steps_live"] <= st["slot_steps_total"]
    assert st["kv_page_steps_total"] == eng.num_blocks * len(fills)
    assert (st["kv_page_steps_in_use"] / st["kv_page_steps_total"]
            == pytest.approx(np.mean(fills), rel=1e-12))
    assert 0.0 <= st["step_host_s"] <= st["step_total_s"]
    # a mixed step computes the packed rows its matmuls run (the most a
    # step can mark live, in whole sublanes, within max_batch x
    # prefill_chunk), a decode chunk max_batch x chunk, whatever is live
    decode_launches = len(fills) - st["mixed_steps"]
    assert st["step_rows_computed"] == (
        rows * st["mixed_steps"]
        + eng.max_batch * eng.chunk * decode_launches)


def test_prefill_rows_of_a_whole_prompt_engine():
    """Without chunked prefill the prompt goes in one ``_prefill`` launch:
    its rows (all but the last token, which decode feeds) count there."""
    eng = _engine()
    reqs = _requests(3, new=4)
    _drive(eng, reqs)
    assert eng.stats["prefills"] == 3 and eng.stats["mixed_steps"] == 0
    assert eng.stats["prefill_rows_packed"] == sum(
        r.prompt_ids.size - 1 for r in reqs)
    assert eng.stats["prefill_rows_packed"] == \
        eng.stats["prefill_tokens_computed"]


def test_step_counters_on_the_verify_path():
    """A verify launch computes max_batch x (1 + draft tokens) rows; live
    are each seated slot's token plus its drafts."""
    eng = _engine(enable_speculation=True, num_draft_tokens=3)
    rep = np.tile(np.arange(6, dtype=np.int32), 4)     # drafter food
    fills = _drive(eng, [Request(rid=i, prompt_ids=rep[:20 + i],
                                 max_new_tokens=12) for i in range(2)])
    st = eng.stats
    assert st["spec_steps"] > 0
    assert st["slot_steps_total"] == eng.max_batch * len(fills)
    assert st["step_rows_live"] <= st["step_rows_computed"]
    decode_launches = len(fills) - st["spec_steps"]
    assert st["step_rows_computed"] == eng.max_batch * (
        4 * st["spec_steps"] + eng.chunk * decode_launches)
    assert st["step_rows_live"] >= (st["spec_drafted_tokens"]
                                    + st["slot_steps_live"]
                                    - eng.max_batch * decode_launches)


def test_idle_poll_opens_no_span_and_no_clock(recorded):
    eng = _engine()
    buffered = profiler.host_events_len()   # the tracer's lane name
    assert eng.step() is False and eng.step() is False
    assert recorded == []
    assert eng.stats["step_total_s"] == 0 and eng._step_no == 2
    assert profiler.host_events_len() == buffered


@pytest.mark.parametrize("chunk,budget,rows,first", [(4, None, 8, 6),
                                                     (8, 8, 8, 8)])
def test_step_spans_nest_in_order_with_arguments(recorded, chunk, budget,
                                                 rows, first):
    """One mixed step and one decode step each yield ``serving/step`` with
    its six children in order, nested, carrying their arguments: a mixed
    step's ``rows_computed`` is the packed rows, not max_batch x
    prefill_chunk."""
    eng = _engine(enable_chunked_prefill=True, prefill_chunk=chunk,
                  token_budget=budget)
    _drive(eng, _requests(2, new=6))
    trees = _step_trees(recorded)
    assert [t[0]["step"] for t in trees] == list(range(1, len(trees) + 1))
    by_program = {}
    for args, children in trees:
        assert [n for n, _ in children] == _PHASES
        dispatch = dict(children)["serving/dispatch"]
        by_program.setdefault(dispatch["program"], dispatch)
        for name, kw in children:
            assert kw == {} or name == "serving/dispatch"
    mixed, decode = by_program["mixed"], by_program["decode"]
    # linear_rows: the rows a recurrence walks; a dense model has none
    assert mixed == {"program": "mixed", "decode_rows": 0,
                     "prefill_rows": first, "rows_computed": rows,
                     "linear_rows": 0}
    assert rows == eng._mixed_rows <= eng.max_batch * chunk
    assert decode == {"program": "decode", "prefill_rows": 0,
                      "decode_rows": 2 * eng.chunk,
                      "rows_computed": 2 * eng.chunk, "linear_rows": 0}
    # the dispatch arguments are the counters' increments
    assert sum(dict(c)["serving/dispatch"]["rows_computed"]
               for _, c in trees) == eng.stats["step_rows_computed"]
    assert sum(dict(c)["serving/dispatch"]["prefill_rows"]
               for _, c in trees) == eng.stats["prefill_rows_packed"]


def test_verify_step_spans(recorded):
    eng = _engine(enable_speculation=True, num_draft_tokens=3)
    rep = np.tile(np.arange(6, dtype=np.int32), 4)
    _drive(eng, [Request(rid=0, prompt_ids=rep[:20], max_new_tokens=12)])
    verify = [dict(c)["serving/dispatch"] for _, c in _step_trees(recorded)
              if [n for n, _ in c] == _PHASES
              and dict(c)["serving/dispatch"]["program"] == "verify"]
    assert verify and all(v["rows_computed"] == eng.max_batch * 4
                          and 1 <= v["decode_rows"] <= 4 for v in verify)


def test_token_streams_identical_with_spans_recording(monkeypatch):
    def serve():
        eng = _engine(enable_chunked_prefill=True, prefill_chunk=4,
                      enable_speculation=True, num_draft_tokens=3)
        reqs = _requests(3, new=6)
        reqs[1].temperature, reqs[1].seed = 0.8, 5
        return eng.serve(reqs)

    plain = serve()
    _Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    assert serve() == plain
    assert any(ev[1] == "serving/step" for ev in _Recorder.log)


def test_step_spans_reach_the_profiler_trace(tmp_path):
    """The real path: a CPU ``start_trace`` read back with ``ProfileData``
    holds ``serving/step`` and its children on one host line, children
    inside their parent, the dispatch arguments as event stats."""
    import glob

    eng = _engine(enable_chunked_prefill=True, prefill_chunk=4)
    eng.serve(_requests(2, new=3, seed=1))      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve(_requests(2, new=3, seed=2))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = [[e for e in line.events if e.name.startswith("serving/")]
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines]
    lines = [evs for evs in lines if evs]
    assert len(lines) == 1
    evs = sorted(lines[0], key=lambda e: (e.start_ns, -e.duration_ns))
    steps = [e for e in evs if e.name == "serving/step"]
    assert steps
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for st in steps:
            lo, hi = st.start_ns, st.start_ns + st.duration_ns
            inside = [e for e in evs if e is not st and lo <= e.start_ns
                      and e.start_ns + e.duration_ns <= hi]
            assert [e.name for e in inside] == _PHASES
            assert all(a.start_ns + a.duration_ns <= b.start_ns
                       for a, b in zip(inside, inside[1:]))
            assert "step" in dict(st.stats)
            assert dict(inside[2].stats)["program"] in ("mixed", "decode")


def test_record_event_arguments_ride_the_host_buffer(tmp_path):
    with profiler.RecordEvent("with_args", rows=3, program="mixed"):
        pass
    with profiler.RecordEvent("without_args"):
        pass
    assert profiler.host_events_len() == 2
    path = tmp_path / "t.json"
    profiler.Profiler().export(str(path))
    events = {e["name"]: e for e in json.load(open(path))["traceEvents"]}
    assert events["with_args"]["args"] == {"rows": 3, "program": "mixed"}
    assert "args" not in events["without_args"]
    assert profiler.host_events_len() == 0


# ---------------- lint gate ----------------

def test_serving_target_host_sync_clean_with_metrics_on():
    """The gate's serving programs stay callback-free with the engine's
    metrics registry, tracer and flight recorder recording."""
    from paddle_tpu.analysis import targets

    t = targets.build("serving_decode_step")
    from paddle_tpu.analysis import analyze

    r = analyze(t.fn, *t.args, target=t.name, rules=("host_sync",),
                allowlist=[])
    assert r.by_rule("host_sync") == []


def test_metric_recorded_via_callback_inside_jit_fails_gate():
    """Positive control: recording a metric through a callback from INSIDE
    a compiled step is exactly the host-sync regression the gate exists to
    catch."""
    from paddle_tpu.analysis import analyze

    reg = MetricsRegistry()
    c = reg.counter("t_bad_inline", "recorded from inside jit").labels()

    def bad_step(x):
        def body(carry, _):
            jax.debug.callback(lambda: c.inc())
            return carry * 2.0, None
        y, _ = jax.lax.scan(body, x, None, length=4)
        return y

    r = analyze(bad_step, jnp.float32(1.0), rules=("host_sync",),
                allowlist=[])
    hits = r.by_rule("host_sync")
    assert hits and any(f in ("warning", "error")
                        for f in {h.severity for h in hits})
    assert r.gating(), "a callback inside a jitted step must gate"
