"""Olmo-Hybrid on the CPU at a small size, seeded random weights, float32:
``models/olmo_hybrid`` and the serving engine that takes its step programs
and cache from it, against ``benchmarks/reference/olmo_hybrid.py`` — a
forward pass that computes the recurrence token by token and imports
nothing of ``paddle_tpu``.

Tolerances, and why: activations and weights are float32 here and the
interpreted kernels multiply exactly, so program and reference differ by
float32 rounding through 8 layers of normal(0, 0.2) weights — 2e-5 to 7e-4
on logits of magnitude 6 at these lengths.  ``LOGIT_TOL`` = 5e-3 leaves
that seven times of room and is FAILED by a recurrent state kept in
bfloat16 (the reference's ``state_bf16`` control reads 0.1 to 3.9 on the
same sequences: ``test_a_bf16_state_fails_the_tolerance``)."""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request
from paddle_tpu.models import olmo_hybrid as oh
from paddle_tpu.ops.pallas import gated_delta as gd

ref = importlib.import_module("benchmarks.reference.olmo_hybrid")

LOGIT_TOL = 5e-3
VOCAB = 97


@pytest.fixture(scope="module")
def model():
    cfg = oh.OlmoHybridConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        num_hidden_layers=8, num_attention_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=32, max_position_embeddings=256,
        dtype=jnp.float32)
    params = oh.init_params(cfg, jax.random.key(0), std=0.2)
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
         if f.name != "dtype"}
    m["layer_types"] = list(m["layer_types"])
    return cfg, params, m


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in lengths]


def reference_logits(m, params, ids, lower=None):
    return np.asarray(ref.logits_at(m, params, ids, lower=lower, pad_to=16))


class Served:
    """An engine whose step programs also hand every launch's emit-row
    logits to the host, each with the request and the position it stands
    for: what a lane emitted, served or not (a mid-prompt chunk's last row
    is a position's logits like any other)."""

    def __init__(self, cfg, params, **engine):
        geometry = dict(max_batch=4, max_seq=128, paged=True, block_size=8,
                        num_blocks=64, enable_chunked_prefill=True,
                        prefill_chunk=16, token_budget=24)
        geometry.update(engine)
        self.eng = eng = ContinuousBatchingEngine(cfg, params, **geometry)
        self.rows: dict = {}        # rid -> {position: logits row}
        self.launches: list = []    # (pos, q_lens, active) a launch
        prog = eng._program
        decode_one, mixed_one = prog.decode_one, prog.mixed_one

        def record(logits, at, q_lens, active):
            self.launches.append((np.array(at), np.array(q_lens),
                                  np.array(active)))
            for s in np.flatnonzero(active):
                req = eng._slot_req[s]
                if req is not None:
                    self.rows.setdefault(req.rid, {})[
                        int(at[s])] = np.array(logits[s])

        def decode(params, ck, cv, tokens, pos, active, table):
            logits, ck, cv = decode_one(params, ck, cv, tokens, pos, active,
                                        table)
            jax.debug.callback(record, logits, pos, jnp.ones_like(pos),
                               active, ordered=True)
            return logits, ck, cv

        def mixed(params, ck, cv, tokens, pos, active, q_lens, table):
            logits, ck, cv = mixed_one(params, ck, cv, tokens, pos, active,
                                       q_lens, table)
            jax.debug.callback(record, logits, pos + q_lens - 1, q_lens,
                               active, ordered=True)
            return logits, ck, cv

        prog.decode_one, prog.mixed_one = decode, mixed

    def serve(self, requests, cap=2000):
        for r in requests:
            self.eng.add_request(r)
        for _ in range(cap):
            if not self.eng.step():
                return
        raise AssertionError("the engine did not drain")

    def gap(self, m, params, req) -> float:
        """Widest distance between a logits row this request's lane emitted
        and the reference's row at that position."""
        ids = np.concatenate([req.prompt_ids,
                              np.asarray(req.output_ids, np.int32)])
        want = reference_logits(m, params, ids)
        got = self.rows[req.rid]
        served = range(req.prompt_ids.size - 1,
                       req.prompt_ids.size - 1 + len(req.output_ids))
        assert set(served) <= set(got), "a served position left no logits"
        return max(float(np.abs(row - want[at]).max())
                   for at, row in got.items() if at < ids.size)


def requests(ps, new=6, first_rid=0):
    return [Request(rid=first_rid + i, prompt_ids=p, max_new_tokens=new)
            for i, p in enumerate(ps)]


# ------------------------------------------------------------ the model

def test_forward_matches_the_reference(model):
    cfg, params, m = model
    for ids in prompts((9, 131), seed=1):
        mine = np.asarray(oh.forward(cfg, params, jnp.asarray(ids[None]))[0])
        assert np.abs(mine - reference_logits(m, params, ids)).max() \
            < LOGIT_TOL


def test_a_rope_theta_is_refused_by_model_and_reference(model):
    """The row's ``rope_theta`` is null and no cell runs another value:
    neither the model nor the reference has a rotary embedding, and a
    config that asks for one is refused, not served without it."""
    cfg, params, m = model
    with pytest.raises(ValueError, match="rope_theta"):
        dataclasses.replace(cfg, rope_parameters={"rope_theta": 500000.0})
    with pytest.raises(ValueError, match="rope_theta"):
        reference_logits(dict(m, rope_parameters={"rope_theta": 500000.0}),
                         params, prompts((9,), seed=1)[0])


def test_a_bf16_state_fails_the_tolerance(model):
    _, params, m = model
    for ids in prompts((40, 131), seed=1):
        off = np.abs(reference_logits(m, params, ids, "state_bf16")
                     - reference_logits(m, params, ids)).max()
        assert off > 10 * LOGIT_TOL


def test_the_config_reads_the_published_keys(model):
    row = {"model_type": "olmo_hybrid", "num_hidden_layers": 8,
           "hidden_size": 64, "num_attention_heads": 2, "hidden_act": "silu",
           "layer_types": [oh.LINEAR, oh.LINEAR, oh.LINEAR, oh.FULL] * 8,
           "rope_parameters": {"rope_theta": None}, "torch_dtype": "float32"}
    cfg = oh.config_from_dict(row)
    assert cfg.layer_types == (oh.LINEAR,) * 3 + (oh.FULL,) + \
        (oh.LINEAR,) * 3 + (oh.FULL,)          # the list is read up to depth
    assert cfg.period == (oh.LINEAR,) * 3 + (oh.FULL,) and cfg.n_rep == 2
    assert (cfg.head_dim, cfg.num_key_value_heads) == (32, 2)
    assert cfg.rope_parameters == {"rope_theta": None}
    assert cfg.dtype == jnp.float32
    cut = oh.config_from_dict(dict(row, num_hidden_layers=6))
    assert len(cut.period) == 6 and cut.n_rep == 1      # ends mid-period
    shapes = oh.param_shapes(model[0])
    assert shapes["linear"]["wq"] == (6, 64, 32)
    assert shapes["full"]["q_norm"] == (2, 64)


# ------------------------------------------- chunked against recurrent form

def _rows(case, B=3, T=100, H=2, dk=16, dv=32):
    ks = jax.random.split(jax.random.key(7), 8)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.random.uniform(ks[3], (B, T, H)) * 0.5
    beta = jax.random.uniform(ks[4], (B, T, H)) * 2.0
    state = jnp.zeros((B, H, dk, dv))
    valid = jnp.ones((B, T), bool)
    fresh = jnp.zeros((B,), bool)
    if case == "start_state":
        state = jax.random.normal(ks[5], (B, H, dk, dv))
    elif case == "beta_near_2":
        beta = 2.0 - jax.random.uniform(ks[4], (B, T, H)) * 1e-3
        state = jax.random.normal(ks[5], (B, H, dk, dv))
    elif case == "dead_rows":
        state = jax.random.normal(ks[5], (B, H, dk, dv))
        valid = jax.random.uniform(ks[6], (B, T)) > 0.3     # in the middle
        valid = valid.at[1].set(False)                      # a whole lane
    elif case == "fresh_lane":
        state = jnp.full((B, H, dk, dv), jnp.nan).at[0].set(1.0)
        fresh = jnp.array([False, True, True])
    elif case == "whole_chunks":
        q, k, v, g, beta, valid = (x[:, :64] for x in
                                   (q, k, v, g, beta, valid))
    return q, k, v, g, beta, state, valid, fresh


@pytest.mark.parametrize("case", ["start_state", "beta_near_2", "dead_rows",
                                  "fresh_lane", "whole_chunks"])
def test_chunked_form_matches_the_recurrent_form(case):
    """T = 100 is no multiple of the sub-chunk's 64.  The recurrent form is
    the decode kernel a row at a time; both are held to the token-by-token
    composition in ``jax.numpy`` too."""
    q, k, v, g, beta, state, valid, fresh = _rows(case)
    o, end = oh.gated_delta_rule_chunked(q, k, v, g, beta, state, valid,
                                         fresh)
    rows = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta, valid))
    first = lambda x: x[0]
    o_0, s = oh.gated_delta_rule_recurrent(*map(first, rows[:5]), state,
                                           rows[5][0], fresh)

    def one(s, row):
        o_t, s = oh.gated_delta_rule_recurrent(*row[:5], s, row[5])
        return s, o_t

    s, outs = jax.lax.scan(one, s, tuple(x[1:] for x in rows))
    outs = jnp.concatenate([o_0[None], outs])
    if case == "fresh_lane":    # a lane with no live row keeps its NaNs
        assert np.isfinite(np.asarray(end)).all()
    live = np.asarray(valid)[..., None, None]
    for other, other_end in ((jnp.moveaxis(outs, 0, 1), s),
                             gd.gdn_chunk_reference(q, k, v, g, beta, state,
                                                    valid, fresh)):
        assert np.abs(np.where(live, o - other, 0.0)).max() < 2e-5
        assert np.abs(np.asarray(end - other_end)).max() < 2e-5


def test_stacked_state_touches_one_layer():
    q, k, v, g, beta, state, valid, fresh = _rows("start_state")
    stack = jnp.stack([state, 2 * state, 3 * state])
    o, out = gd.gdn_chunk_prefill(q, k, v, g, beta, stack, valid, fresh, 1)
    o1, end = gd.gdn_chunk_prefill(q, k, v, g, beta, 2 * state, valid, fresh)
    assert np.array_equal(np.asarray(out[1]), np.asarray(end))
    assert np.array_equal(np.asarray(out[0]), np.asarray(state))
    assert np.array_equal(np.asarray(out[2]), np.asarray(3 * state))
    assert np.array_equal(np.asarray(o), np.asarray(o1))


# ------------------------------------------------------------ the engine

def test_engine_logits_match_the_reference(model):
    """Prompts of unlike lengths through chunked prefill and then decode,
    through ``add_request`` / ``step``: every emitted logits row against
    the reference's full forward pass."""
    cfg, params, m = model
    served = Served(cfg, params)
    reqs = requests(prompts((5, 37, 70)), new=8)
    served.serve(reqs)
    for r in reqs:
        assert r.status == "FINISHED" and len(r.output_ids) == 8
        assert served.gap(m, params, r) < LOGIT_TOL
    st = served.eng.stats
    assert st["state_starts"] == 3 and st["state_bytes"] == \
        4 * cfg.state_bytes_per_slot
    assert 0 < st["gdn_rows_live"] < st["gdn_rows_computed"]
    assert 0 < st["state_slot_steps_live"] <= st["slot_steps_live"]


def test_decode_lanes_ride_beside_a_chunk(model):
    """A long prompt streams in while an earlier request decodes: at least
    one mixed launch carries both kinds of lane, and neither is disturbed."""
    cfg, params, m = model
    served = Served(cfg, params)
    first, late = requests(prompts((6, 90), seed=2), new=12)
    served.eng.add_request(first)
    for _ in range(3):
        served.eng.step()
    served.serve([late])
    both = [1 for at, q_lens, active in served.launches
            if (active & (q_lens == 1) & (at > 0)).any()
            and (active & (q_lens > 1)).any()]
    assert both
    for r in (first, late):
        assert served.gap(m, params, r) < LOGIT_TOL


def test_a_reused_slot_gives_what_it_gives_alone(model):
    cfg, params, m = model
    crowd = Served(cfg, params, max_batch=1)
    before, again = requests(prompts((50, 21), seed=3), new=5)
    crowd.serve([before, again])        # one slot: the second inherits it
    alone = Served(cfg, params, max_batch=1)
    twin = Request(rid=9, prompt_ids=again.prompt_ids, max_new_tokens=5)
    alone.serve([twin])
    assert again.output_ids == twin.output_ids
    for at, row in alone.rows[9].items():
        assert np.array_equal(row, crowd.rows[again.rid][at])
    assert crowd.gap(m, params, again) < LOGIT_TOL
    assert crowd.eng.stats["state_starts"] == 2


def test_a_preempted_request_resumes_to_the_same_logits(model):
    """A pool of 8 pages under two streams that need 10: the younger is
    preempted, re-prefilled from position 0 (its state started from zero
    by the program) and ends on the logits it has when served alone."""
    cfg, params, m = model
    tight = Served(cfg, params, max_batch=2, max_seq=64, num_blocks=8)
    reqs = requests(prompts((30, 28), seed=4), new=10)
    tight.serve(reqs)
    assert tight.eng.stats["preemptions"] > 0
    assert tight.eng.stats["state_starts"] > 2
    alone = Served(cfg, params, max_batch=2, max_seq=64, num_blocks=8)
    for r in reqs:
        assert r.status == "FINISHED" and len(r.output_ids) == 10
        assert tight.gap(m, params, r) < LOGIT_TOL
        twin = Request(rid=r.rid, prompt_ids=r.prompt_ids,
                       max_new_tokens=10)
        alone.serve([twin])             # one at a time: nobody is preempted
        assert twin.output_ids == r.output_ids
    assert alone.eng.stats["preemptions"] == 0


def test_a_decode_scan_carries_the_state(model):
    cfg, params, _ = model
    outs = []
    for chunk in (1, 3):
        reqs = requests(prompts((12, 33), seed=5), new=9)
        Served(cfg, params, chunk=chunk).serve(reqs)
        outs.append([r.output_ids for r in reqs])
    assert outs[0] == outs[1]


_REFUSED = [dict(enable_prefix_caching=True), dict(enable_speculation=True),
            dict(enable_prefix_caching=True, enable_host_kv_tier=True),
            dict(kv_quant="int8"), dict(tensor_parallel=2),
            dict(enable_chunked_prefill=False), dict(paged=False)]


@pytest.mark.parametrize("option", _REFUSED,
                         ids=["+".join(o) for o in _REFUSED])
def test_options_the_state_cannot_honour_are_refused(model, option):
    cfg, params, _ = model
    geometry = dict(max_batch=2, max_seq=64, paged=True, block_size=8,
                    enable_chunked_prefill=True, prefill_chunk=16)
    geometry.update(option)
    with pytest.raises(ValueError, match=r"ROADMAP B-I\.4"):
        ContinuousBatchingEngine(cfg, params, **geometry)


def test_the_chunked_kill_switch_is_refused_too(model, monkeypatch):
    cfg, params, _ = model
    monkeypatch.setenv("PADDLE_TPU_CHUNKED_PREFILL", "0")
    with pytest.raises(ValueError, match=r"ROADMAP B-I\.4"):
        ContinuousBatchingEngine(cfg, params, max_batch=2, max_seq=64,
                                 paged=True, block_size=8,
                                 enable_chunked_prefill=True)
