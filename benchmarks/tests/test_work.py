"""The FLOP and byte functions against numbers worked by hand."""

import json
import os

import pytest

from benchmarks.harness import work
from benchmarks.harness.cell import HERE


def model(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_mistral7b_l20():
    m = model("mistral7b-l20")
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each; MLP 3 x 4096 x 14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert work.layer_matmul_params(m) == layer
    assert work.head_params(m) == 32768 * 4096 == 134_217_728
    assert work.total_params(m) == 20 * (layer + 8192) + 2 * 134_217_728 + 4096
    assert work.total_params(m) * 2 == pytest.approx(9.26e9, rel=2e-3)
    assert work.kv_bytes_per_token(m) == 2 * 8 * 128 * 2 * 20 == 81_920
    assert work.weight_bytes_per_step(m) == (20 * layer + 134_217_728) * 2
    # one decode row at context 1000: 2 FLOP a layer parameter, 4 * 128 * 32
    # a pair a layer, one logits row
    got = work.serve_row_flops(m, 1, 1000, 1)
    assert got == 2 * 20 * layer + 4 * 128 * 32 * 20 * 1000 + 2 * 134_217_728


def test_yicoder_l8():
    m = model("yicoder1.5b-l8")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5504
    assert layer == 50_593_792
    assert work.layer_matmul_params(m) == layer
    assert work.total_params(m) == 8 * (layer + 4096) + 2 * 64000 * 2048 + 2048
    assert work.total_params(m) == pytest.approx(667e6, rel=2e-3)
    dense = 8 * layer + 64000 * 2048
    attn = 3 * 4 * 128 * 16 * 8 * (2048 * 2049 / 2) / 2048
    assert work.train_flops_per_token(m, 2048) == pytest.approx(
        6 * dense + attn)
    assert work.train_flops_per_token(m, 2048) == pytest.approx(3.42e9,
                                                                rel=5e-3)
    # the head's share of the matmul parameters: 24 % here
    assert 64000 * 2048 / dense == pytest.approx(0.245, abs=0.005)
    step = work.flash_attn_train_flops(m, 8, 2048)
    assert step == pytest.approx(3 * 4 * 128 * 16 * 8 * 8 * 2048 * 2049 / 2)
    qkv = 8 * 2048 * 16 * 128 * 2
    assert work.flash_attn_train_bytes(m, 8, 2048) == (4 * qkv + 8 * qkv) * 8


def test_roofline_names_its_bound():
    peak = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_s(197e12, 1.0, peak) == (pytest.approx(1.0),
                                                  "compute")
    assert work.roofline_s(1.0, 819e9, peak) == (pytest.approx(1.0),
                                                 "memory")
