"""The trace reduction: busy union, idle share, kernel time by name and gap
attribution, on a synthetic trace with known answers and on a trace
recorded on a TPU v5e (``data/*.trace.json``, the first 20 ms of a
traced window, op names shortened)."""
from __future__ import annotations

import glob
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def _synthetic():
    ms = 1_000_000
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.window", 0, 100 * ms],
            ["bench.step", 0, 60 * ms],
            ["bench.wait", 60 * ms, 40 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["matmul.1", 10 * ms, 20 * ms],
                ["matmul.2", 20 * ms, 20 * ms],      # overlaps the first
                ["decode", 50 * ms, 5 * ms],
                ["late", 95 * ms, 10 * ms]]},        # runs past the window
            {"name": "XLA Modules", "events": [["jit_run", 0, 100 * ms]]}]},
    ]}


def test_busy_is_the_union_clipped_to_the_window():
    r = tr.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030 + 0.005 + 0.005)
    assert r["op_s"]["late"] == pytest.approx(0.005)


def test_kernel_time_by_name():
    r = tr.reduce(_synthetic())
    assert tr.kernel_seconds(r, lambda n: "matmul" in n) == pytest.approx(0.040)
    assert tr.kernel_seconds(r, lambda n: "nothing" in n) == 0.0


def test_loop_events_do_not_count_twice():
    ms = 1_000_000
    t = _synthetic()
    t["planes"][1]["lines"][0]["events"].append(["%while.1", 5 * ms, 60 * ms])
    r = tr.reduce(t)
    assert "%while.1" not in r["op_s"]
    assert r["busy_s"] == pytest.approx(0.060 + 0.005)


def test_short_names_drop_layouts_and_attributes():
    name = ('%call.92 = f32[128,67584]{1,0:T(8,128)S(1)} custom-call(bf16[128,12288]'
            '{1,0:T(8,128)(2,1)S(1)} %copy-done.4, bf16[12288,67584]{1,0:T(8,128)(2,1)}'
            ' %dynamic-slice_bitcast_fusion.16), custom_call_target="tpu_custom_call"')
    assert tr.short(name) == ("%call.92 = f32[128,67584] custom-call("
                              "bf16[128,12288], bf16[12288,67584])")


def test_gaps_are_labelled_by_the_host_span():
    r = tr.reduce(_synthetic())
    labels = dict((k, v) for k, v in r["gap_s_by_label"].items())
    # [55, 95] ms overlaps the wait most; [0, 10] and [40, 50] the step
    assert labels["bench.wait"] == pytest.approx(0.040)
    assert labels["bench.step"] == pytest.approx(0.020)
    assert r["longest_gaps"][0][0] == "bench.wait"
    b = tr.breakdown(r)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_extra_spans_label_compiles():
    r = tr.reduce(_synthetic(), [("compile", 56_000_000, 95_000_000)])
    assert r["longest_gaps"][0][0] == "compile"


def test_trim_keeps_the_start_of_the_window():
    t = tr.trim(_synthetic(), 0.05)
    assert tr.window(t) == (0, 50_000_000)
    assert tr.reduce(t)["busy_s"] == pytest.approx(0.030)


def test_no_window_or_device_raises():
    with pytest.raises(ValueError):
        tr.reduce({"planes": []})


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.trace.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace(path):
    t = tr.load_json(path)
    r = tr.reduce(t)
    assert 0 < r["busy_s"] <= r["window_s"] * (1 + 1e-9)
    # every leaf op's time is counted; a loop's own time between its body
    # ops is busy but belongs to no leaf
    lo, hi = tr.window(t)
    (ops,) = tr.device_ops(t).values()
    leaf_busy = tr.busy_ns([(s, e) for _, s, e in tr.leaves(ops)], lo, hi) / 1e9
    assert leaf_busy <= r["busy_s"] * (1 + 1e-9)
    assert sum(r["op_s"].values()) >= leaf_busy * (1 - 1e-9)
    assert r["longest_gaps"]
    assert {g[0] for g in r["longest_gaps"]} <= {
        "host", "bench.step", "bench.submit", "bench.wait", "compile"}


def test_recorded_kernels_are_found():
    """The Mosaic kernels of a Mamba-2 decode trace (the GEMMs of every
    per-slot launch) are found by their custom-call name and take part of
    the busy time."""
    r = tr.reduce(tr.load_json(os.path.join(DATA, "mamba2.decode.trace.json")))
    mosaic = tr.kernel_seconds(r, lambda n: "custom-call(" in n)
    assert 0 < mosaic < r["busy_s"]
