"""Operation and byte counts against hand counts at both cells' widths."""

import json
import pathlib

import pytest

from bench.harness.peaks import PEAKS, peaks
from bench.harness.spec import model_dims
from bench.roofline import model, paged_attention

BENCH = pathlib.Path(__file__).resolve().parents[1]


def dims(name):
    return model_dims(json.loads((BENCH / "configs" / f"{name}.json")
                                 .read_text()))


YI, QWEN = dims("yi-6b"), dims("qwen2.5-14b-d12")


def test_widths():
    assert (YI["d_model"], YI["n_heads"], YI["n_kv_heads"], YI["head_dim"],
            YI["d_ff"], YI["vocab"], YI["n_layers"]) == \
        (4096, 32, 4, 128, 11008, 64000, 32)
    assert (QWEN["d_model"], QWEN["n_heads"], QWEN["n_kv_heads"],
            QWEN["head_dim"], QWEN["d_ff"], QWEN["vocab"],
            QWEN["n_layers"], QWEN["qkv_bias"]) == \
        (5120, 40, 8, 128, 13824, 152064, 12, True)


def test_paged_attention_yi_one_row_by_hand():
    # 1000 tokens: 999 stored = 62 pages (992 tokens) + 7 tail, +1 new
    flops, nbytes = paged_attention.call_cost([1000], YI, 16)
    assert flops == 4 * 1000 * 32 * 128
    pages = 2 * 992 * 4 * (128 + 8)         # int8 K,V + f32 base, scale
    tail = 2 * 8 * 4 * 128 * 4              # f32 K,V of 8 tail tokens
    qo = 2 * 32 * 128 * 4
    assert nbytes == pages + tail + qo == 1079296 + 32768 + 32768


def test_paged_attention_qwen_rows_add_and_page_boundaries():
    f1, b1 = paged_attention.call_cost([17], QWEN, 16)
    # 16 stored tokens fill one page; the new token is the tail's first
    assert b1 == 2 * 16 * 8 * 136 + 2 * 1 * 8 * 128 * 4 + 2 * 40 * 128 * 4
    f2, b2 = paged_attention.call_cost([17, 4096], QWEN, 16)
    f3, b3 = paged_attention.call_cost([4096], QWEN, 16)
    assert (f2, b2) == (f1 + f3, b1 + b3)


def test_bound_is_bandwidth_for_decode_attention():
    pk = peaks("TPU v5 lite")
    ctx = [3000] * 16
    flops, nbytes = paged_attention.call_cost(ctx, QWEN, 16)
    assert nbytes / pk["hbm_bytes_per_s"] > flops / pk["bf16_flops"]
    assert paged_attention.bound_s(ctx, QWEN, 16, pk) == \
        pytest.approx(nbytes / 819e9)


def test_model_flops_by_hand():
    lp = 4096 * 4096 + 2 * 4096 * 512 + 4096 * 4096 + 3 * 4096 * 11008
    assert model.layer_params(YI) == lp
    assert model.decode_flops(100, YI) == \
        2 * (32 * lp + 4096 * 64000) + 32 * 4 * 100 * 32 * 128
    # prefill of positions 0..9: no LM head, attention over 1..10
    assert model.prefill_flops(10, 45, YI) == \
        2 * 10 * 32 * lp + 32 * 4 * 55 * 32 * 128
    q = model.layer_params(QWEN)
    assert q == 5120 * 5120 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 13824


def test_peaks_table_refuses_unknown_devices():
    assert PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
