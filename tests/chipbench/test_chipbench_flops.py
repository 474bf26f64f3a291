"""Operation and byte counts, checked by hand at qwen2.5-7b widths."""
from __future__ import annotations

import json

import pytest

from chipbench_tiny import ROOT
from chipbench import reference as R


@pytest.fixture(scope="module")
def dims():
    conf = json.loads((ROOT / "chipbench/configs/qwen2.5-7b-8l.json")
                      .read_text())
    return R.dims_of(conf)


def test_dims_at_published_widths(dims):
    assert (dims.d, dims.nq, dims.nkv, dims.dh, dims.ff, dims.vocab,
            dims.layers) == (3584, 28, 4, 128, 18944, 152064, 8)


def test_decode_attention_one_request(dims):
    # 128 resident pages (2048 tokens) plus a 70-token tail: 133 pages
    flops, byts = R.decode_attention_cost(
        dims, [2118], page=16, resident_pages=128, table_width=148)
    assert flops == 4 * 28 * 128 * 2118 == 30_363_648
    kv = 2 * 133 * 16 * 4 * 128 * 2  # K and V pages, bf16
    q_out = 2 * 28 * 128 * 2
    mass = 28 * 148 * 4
    assert kv == 4_358_144
    assert byts == kv + q_out + mass == 4_389_056


def test_decode_attention_batch_is_the_sum(dims):
    one = [R.decode_attention_cost(dims, [n], page=16, resident_pages=128,
                                   table_width=148) for n in (2049, 2176)]
    both = R.decode_attention_cost(dims, [2049, 2176], page=16,
                                   resident_pages=128, table_width=148)
    assert both == pytest.approx(tuple(map(sum, zip(*one))))
    # a partly filled tail page is read whole
    assert one[0][1] - one[1][1] == pytest.approx(-7 * 2 * 16 * 4 * 128 * 2)


def test_layer_and_decode_token_flops(dims):
    proj = 3584 * (3584 + 2 * 512) + 3584 * 3584
    ffn = 3 * 3584 * 18944
    assert R.layer_matmul_flops(dims, 1) == 2 * (proj + ffn)
    per_layer = 2 * (proj + ffn) + 4 * 28 * 128 * 2200
    assert R.decode_token_flops(dims, 2200) == \
        8 * per_layer + 2 * 3584 * 152064


def test_reprefill_flops(dims):
    s, sel, n, periods = 64, 2048, 8192, 2
    pairs = s * sel + s * (s + 1) / 2
    want = (8 * (R.layer_matmul_flops(dims, s) + 4 * 28 * 128 * pairs)
            + periods * 2 * s * 28 * 128 * n + 2 * 3584 * 152064)
    assert R.reprefill_flops(dims, s, sel, n, periods) == pytest.approx(want)
