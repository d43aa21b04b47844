"""``mixed_attn.live_row_pct`` (PR 33): the metric's file against the
manifest's entry, and what its reader makes of counters with and without
the two keys ``_mixed_step`` adds."""

import json
import os

import pytest

from benchmarks.harness.cell import HERE, ROOT, resolve
from benchmarks.readers import counters

NAME = "mixed_attn.live_row_pct"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(HERE, "layer_metrics", NAME + ".json")) as f:
        return json.load(f)


def test_the_file_agrees_with_the_manifests_entry(spec):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == NAME]
    assert entry == {k: spec[k] for k in entry}
    assert entry["workloads"] == ["mistral7b-docs", "mistral7b-chat",
                                  "olmo-hybrid-chat"]
    assert (entry["layer"], entry["moves"]) == ("kernels", "itl_p95_ms")
    assert resolve(spec["reader"]) is counters.ratio_pct


@pytest.mark.parametrize("window,share", [
    # the parent of the PR that brought the keys counts neither
    ({"mixed_steps": 290, "step_rows_live": 46400}, None),
    # a window without a mixed step
    ({"attn_row_pages_live": 0, "attn_row_pages_computed": 0}, None),
    # a docs-like window: 290 mixed steps of ~2,660 live row-pages each
    ({"attn_row_pages_live": 771400, "attn_row_pages_computed": 1011200},
     76.29),
], ids=["parent", "no_mixed_step", "recorded"])
def test_the_reader_on_counters(spec, window, share):
    got = counters.ratio_pct({"counters": window}, spec)
    assert got is None if share is None else got == pytest.approx(
        share, abs=0.01)


def test_the_engine_counts_both_keys_and_a_share_under_100():
    """A toy engine through mixed steps: both counters move, the live
    share is a share, and the census is a count of one layer's launch a
    step (not of every layer's)."""
    import jax
    import numpy as np

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request)
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(cfg, params, max_batch=4, max_seq=64,
                                   paged=True, block_size=8,
                                   enable_chunked_prefill=True,
                                   prefill_chunk=8, token_budget=12)
    rs = np.random.RandomState(0)
    eng.serve([Request(rid=i, max_new_tokens=4, prompt_ids=rs.randint(
        1, cfg.vocab_size, n).astype(np.int32))
        for i, n in enumerate((20, 9, 14))])
    st = eng.stats
    assert st["mixed_steps"] > 0
    assert 0 < st["attn_row_pages_live"] <= st["attn_row_pages_computed"]
    share = counters.ratio_pct({"counters": dict(st)}, {
        "params": {"over": "attn_row_pages_live",
                   "under": "attn_row_pages_computed"}})
    assert 0 < share <= 100
