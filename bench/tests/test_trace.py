"""The reduction from trace rows to device time, kernel time and idle
gaps, on a hand-made trace and on rows recorded on a TPU v5e."""

import json
import pathlib

import pytest

from bench.harness import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
RECORDED = pathlib.Path(__file__).resolve().parent / "data" / \
    "v5e_trace_rows.json"


def _rows():
    return [
        [HOST, "python", "bench.traced", 100.0, 1000.0],
        [HOST, "python", "bench.step", 100.0, 400.0],
        [HOST, "python", "bench.wait", 600.0, 300.0],
        [DEV, trace.OPS, "fusion.1", 50.0, 100.0],          # clipped: 50
        [DEV, trace.OPS, "_paged_attention_tail_kernel", 200.0, 100.0],
        [DEV, trace.OPS, "fusion.2", 250.0, 100.0],         # overlaps
        [DEV, trace.OPS, "fusion.2", 1000.0, 200.0],        # clipped: 100
        [DEV, trace.MODULES, "jit__decode_step(1)", 150.0, 300.0],
        [DEV, trace.MODULES, "jit__decode_step(1)", 1000.0, 50.0],
        [DEV, "Steps", "ignored", 0.0, 5000.0],
    ]


def test_busy_is_the_union_inside_the_slice():
    r = trace.reduce(_rows(), {"paged_attention": "_paged_attention_tail"})
    assert r["window_s"] == pytest.approx(1000e-9)
    # [100,150) + [200,350) + [1000,1100)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["kernel_s"]["paged_attention"] == pytest.approx(100e-9)
    assert r["modules"]["jit__decode_step(1)"] == pytest.approx([300e-9, 50e-9])
    ops = dict((n, v) for n, v in r["device_ops"])
    assert ops["fusion.2"] == pytest.approx(200e-9)
    assert ops["fusion.1"] == pytest.approx(50e-9)


def test_idle_gaps_name_what_the_host_did():
    r = trace.reduce(_rows(), {})
    gaps = r["idle_gaps"]
    # [350,1000) is mostly waiting; [150,200) falls in bench.step
    assert gaps[0][0] == "bench.wait"
    assert gaps[0][1] == pytest.approx(650e-9)
    assert ["bench.step", pytest.approx(50e-9)] in gaps
    assert sum(g for _, g in gaps) == pytest.approx(700e-9)


def test_no_slice_or_no_device_op_reads_nothing():
    assert trace.reduce([r for r in _rows() if r[2] != trace.SLICE],
                        {}) is None
    assert trace.reduce([r for r in _rows() if r[1] != trace.OPS], {}) \
        is None


def test_recorded_v5e_trace():
    rows = json.loads(RECORDED.read_text())
    r = trace.reduce(rows, {"paged_attention": "_paged_attention_tail"})
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["kernel_s"]["paged_attention"] < r["busy_s"]
    assert any("_decode_step" in n for n in r["modules"])
    assert len(r["device_ops"]) == 10
