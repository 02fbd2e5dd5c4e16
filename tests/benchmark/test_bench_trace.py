"""Reductions from a profiler trace to the per-layer numbers."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

import devtrace

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_serve_trace.json"


def _trace():
    """A hand-made trace: two pushes, the device busy in part of each."""
    host = [
        ["bench:window", 0, 1000],
        ["bench:push", 100, 400],
        ["bench:task", 110, 300],
        ["bench:prefill", 110, 150],
        ["bench:decode", 150, 280],
        ["bench:wait", 280, 300],
        ["bench:push", 600, 900],
        ["bench:task", 610, 800],
        ["bench:decode", 620, 790],
    ]
    ops = [
        ["fusion.1", 120, 160], ["fusion.2", 150, 200],  # overlapping
        ["dot.3", 210, 250], ["fusion.1", 620, 700], ["fusion.1", 720, 780],
    ]
    modules = [["jit_prefill_fn(1)", 120, 200], ["jit_decode_fn(2)", 210, 250],
               ["jit_decode_fn(2)", 620, 780]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": host}


def test_union_and_busy():
    t = _trace()
    assert devtrace.union(t["devices"]["/device:TPU:0"]["ops"]) == [
        [120, 200], [210, 250], [620, 700], [720, 780]]
    assert devtrace.busy_ns(t, 0, 1000) == 80 + 40 + 80 + 60
    assert devtrace.busy_ns(t, 150, 230) == 50 + 20
    assert devtrace.busy_ns(t, 0, 100) == 0


def test_idle_in_pushes():
    t = _trace()
    pushes = devtrace.spans(t, "push")
    assert pushes == [[100, 400], [600, 900]]
    assert devtrace.idle_share_in(t, pushes) == pytest.approx(1 - 260 / 600)
    assert devtrace.idle_share_in({"devices": {}, "host": []}, pushes) is None


def test_module_time():
    t = _trace()
    assert devtrace.module_ns(t, r"decode_fn", 0, 1000) == 40 + 160
    assert devtrace.module_ns(t, r"prefill_fn|decode_fn", 0, 1000) == 80 + 200
    assert devtrace.module_ns(t, r"decode_fn", 700, 1000) == 80


def test_breakdown():
    t = _trace()
    assert devtrace.top_ops(t, 0, 1000)[0] == ["fusion.1", pytest.approx(180e-9)]
    idle = dict(devtrace.idle_by_host(t, 0, 1000))
    # idle stretches [0,120) [200,210) [250,620) [700,720) [780,1000), split
    # at the host spans' edges and named by what the host did in each piece
    assert idle == {
        "between_pushes": pytest.approx((100 + 200 + 100) * 1e-9),
        "circuit": pytest.approx((10 + 100 + 10 + 100) * 1e-9),
        "prefill": pytest.approx(10e-9),
        "task": pytest.approx((10 + 10) * 1e-9),
        "decode": pytest.approx((10 + 30 + 20 + 10) * 1e-9),
        "wait": pytest.approx(20e-9),
    }
    assert devtrace.host_label(t, 290) == "wait"
    assert devtrace.host_label(t, 615) == "task"
    assert devtrace.host_label(t, 305) == "circuit"
    assert devtrace.host_label(t, 350) == "circuit"
    assert devtrace.host_label(t, 500) == "between_pushes"


def test_recorded_v5e_trace():
    """The reductions on a window recorded on one TPU v5e chip."""
    t = json.loads(RECORDED.read_text())
    lo, hi = devtrace.window(t)
    busy = devtrace.busy_ns(t, lo, hi)
    assert 0 < busy < hi - lo
    pushes = devtrace.spans(t, "push")
    share = devtrace.idle_share_in(t, pushes)
    assert 0 < share < 1
    decode = devtrace.module_ns(t, r"decode_fn", lo, hi)
    prefill = devtrace.module_ns(t, r"prefill_fn", lo, hi)
    assert decode > 0 and prefill > 0
    assert decode + prefill <= busy * 1.001
    ops = devtrace.top_ops(t, lo, hi)
    assert 0 < len(ops) <= 10 and ops[0][1] >= ops[-1][1]
    gaps = devtrace.idle_by_host(t, lo, hi)
    assert sum(s for _, s in gaps) == pytest.approx((hi - lo - busy) / 1e9)
