"""``decode_attn.live_page_pct``: the metric's file against the manifest's
entry, and what its reader makes of counters with and without the two keys
the decode step adds."""

import json
import os

import pytest

from benchmarks.harness.cell import HERE, ROOT, resolve
from benchmarks.readers import counters

NAME = "decode_attn.live_page_pct"


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(HERE, "layer_metrics", NAME + ".json")) as f:
        return json.load(f)


def test_the_file_agrees_with_the_manifests_entry(spec):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == NAME]
    assert entry == {k: spec[k] for k in entry}
    assert entry["workloads"] == ["mistral7b-chat", "olmo-hybrid-chat",
                                  "jamba2-reasoning"]
    assert (entry["layer"], entry["moves"]) == ("kernels", "itl_p95_ms")
    assert resolve(spec["reader"]) is counters.ratio_pct


@pytest.mark.parametrize("window,share", [
    # a program without the two keys (the parent of the kernel's walk)
    ({"decode_steps": 400, "step_rows_live": 5200}, None),
    # a window without a decode step on the fused kernel
    ({"decode_attn_pages_live": 0, "decode_attn_pages_walked": 0}, None),
    # a chat-like window: 300 decode steps, 13 live lanes of ~12 pages
    # each under a 32-lane walk to a 20-page lane
    ({"decode_attn_pages_live": 46800, "decode_attn_pages_walked": 192000},
     24.375),
], ids=["parent", "no_decode_step", "recorded"])
def test_the_reader_on_counters(spec, window, share):
    got = counters.ratio_pct({"counters": window}, spec)
    assert got is None if share is None else got == pytest.approx(
        share, abs=0.01)


def test_the_engine_counts_both_keys_and_a_share_under_100(spec):
    """A toy paged engine through decode steps: both counters move, and
    the live share is a share."""
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
                                   prefill_chunk=8)
    rs = np.random.RandomState(0)
    eng.serve([Request(rid=i, max_new_tokens=m, prompt_ids=rs.randint(
        1, cfg.vocab_size, n).astype(np.int32))
        for i, (n, m) in enumerate(((20, 6), (9, 14), (30, 3)))])
    st = dict(eng.stats)
    assert st["decode_steps"] > st["mixed_steps"]
    assert 0 < st["decode_attn_pages_live"] <= st["decode_attn_pages_walked"]
    share = counters.ratio_pct({"counters": st}, spec)
    assert 0 < share <= 100
