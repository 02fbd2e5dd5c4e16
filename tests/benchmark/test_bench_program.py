"""Reductions from the program's own spans (``progtrace``) to the circuit's
per-layer numbers, and the readers of the six metrics that use them."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

import bench
import devtrace
import progtrace

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "v5e_serve_program_trace.json"
METRICS = {m: bench.load_module(bench.HERE / "metrics" / f"{m}.py") for m in progtrace.METRICS}


def _trace():
    """Two benchmark pushes, the second a memo hit, with the program's spans
    inside: a GC inside the output hash, one inside the task, one between
    pushes, and a hash on another thread."""
    host = [
        ["bench:window", 0, 1000],
        ["bench:push", 100, 500],
        ["bench:task", 200, 300],
        ["bench:push", 600, 900],
    ]
    T = "0/0"
    program = [
        ["koalja:push", 110, 490, T, {"push": 1, "task": "generate"}],
        ["koalja:store.put", 120, 160, T, {"nbytes": 16, "tier": "local"}],
        ["koalja:hash", 125, 150, T, {"payloads": 1, "nbytes": 16, "d2h_bytes": 0}],
        ["koalja:journal.append", 165, 185, T, {"records": 1}],
        ["koalja:journal.fsync", 170, 180, T, {}],
        ["koalja:store.get", 190, 195, T, {"nbytes": 16, "tier": "local"}],
        ["koalja:task", 200, 300, T, {"task": "generate", "push": 1}],
        ["koalja:gc", 250, 260, T, {"generation": 0, "collected": 3}],
        ["koalja:hash", 310, 400, T, {"payloads": 2, "nbytes": 6000, "d2h_bytes": 6000}],
        ["koalja:gc", 350, 370, T, {"generation": 2, "collected": 5}],
        ["koalja:store.put", 410, 420, T, {"nbytes": 6000, "tier": "local"}],
        ["koalja:journal.append", 430, 470, T, {"records": 8}],
        ["koalja:push", 610, 890, T, {"push": 2, "task": "generate"}],
        ["koalja:hash", 620, 640, T, {"payloads": 1, "nbytes": 16, "d2h_bytes": 0}],
        ["koalja:journal.append", 650, 660, T, {"records": 2}],
        ["koalja:hash", 700, 800, "0/1", {"payloads": 1, "nbytes": 100, "d2h_bytes": 100}],
        ["koalja:gc", 950, 960, T, {"generation": 1, "collected": 0}],
    ]
    ops = [["fusion.1", 0, 120], ["fusion.2", 210, 290], ["fusion.3", 380, 390],
           ["fusion.4", 700, 720]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}}, "host": host,
            "program": program}


def _read(trace) -> dict:
    run = types.SimpleNamespace(trace=trace)
    return {m: mod.read(run) for m, mod in METRICS.items()}


def test_self_time_and_nesting():
    t = _trace()
    own, outer = progtrace.nest(t)
    by = {(e[0], e[1]): i for i, e in enumerate(t["program"])}
    assert own[by["koalja:push", 110]] == 380 - (40 + 20 + 5 + 100 + 90 + 10 + 40)
    assert own[by["koalja:hash", 310]] == 90 - 20  # its GC is charged to gc
    assert own[by["koalja:journal.append", 165]] == 10
    assert outer[by["koalja:gc", 350]] == ("koalja:push", "koalja:hash")
    assert outer[by["koalja:hash", 700]] == ()  # another thread
    assert progtrace.stage_ns(t, "scheduler", 0, 1000) == 75 + 250
    assert progtrace.stage_ns(t, "hash", 0, 1000) == 25 + 70 + 20
    assert progtrace.stage_ns(t, "store", 0, 1000) == 15 + 5 + 10
    assert progtrace.stage_ns(t, "journal", 0, 1000) == 10 + 10 + 40 + 10
    assert progtrace.gc_ns(t, 0, 1000) == 40 and progtrace.gc_ns(t, 0, 1000, circuit=True) == 20
    assert progtrace.circuit_ns(t, 0, 1000) == (380 - 100) + 280
    stages = sum(progtrace.stage_ns(t, s, 0, 1000) for s in progtrace.STAGES)
    assert stages + progtrace.gc_ns(t, 0, 1000, circuit=True) == progtrace.circuit_ns(t, 0, 1000)
    assert progtrace.d2h_bytes(t, 0, 1000) == 6100 and progtrace.pushes(t, 0, 1000) == 2
    assert progtrace.pushes(t, 500, 1000) == 1


def test_metric_readers():
    got = _read(_trace())
    assert got == pytest.approx({
        "scheduler_ms_per_push": 325 / 2 / 1e6,
        "hash_ms_per_push": 115 / 2 / 1e6,
        "store_ms_per_push": 30 / 2 / 1e6,
        "journal_ms_per_push": 70 / 2 / 1e6,
        "d2h_mb_per_push": 6100 / 2 / 1e6,
        "gc_ms_per_push": 40 / 2 / 1e6,
    })


def test_readers_find_nothing_without_program_spans():
    """The parent's trace has no ``program`` key: every reader gives None."""
    t = _trace()
    del t["program"]
    assert set(_read(t).values()) == {None}
    assert set(_read(None).values()) == {None}
    assert set(_read({**_trace(), "program": []}).values()) == {None}


def test_idle_by_program():
    t = _trace()
    stretches = progtrace.circuit_stretches(t, 0, 1000)
    assert stretches == [[100, 200], [300, 500], [600, 900]]
    gaps = dict(progtrace.circuit_gaps(t, 0, 1000))
    assert gaps == pytest.approx({
        "scheduler": 215e-9, "hash": 185e-9, "journal.append": 60e-9, "outside": 30e-9,
        "store.put": 25e-9, "gc": 20e-9, "journal.fsync": 10e-9, "store.get": 5e-9,
    })
    # the same stretches that idle_by_host calls the circuit
    circuit = dict(devtrace.idle_by_host(t, 0, 1000))["circuit"]
    assert sum(gaps.values()) == pytest.approx(circuit)
    whole = progtrace.idle_by_program(t, [[0, 1000]])
    assert sum(s for _, s in whole) == pytest.approx((1000 - devtrace.busy_ns(t, 0, 1000)) / 1e9)
    assert progtrace.program_label(t, 955) == "gc"
    assert progtrace.program_label(t, 920) == "outside"
    assert progtrace.program_label(t, 760) == "hash"


def test_cut_keeps_whole_pushes():
    t = _trace()
    c = progtrace.cut(t, 1)
    assert c["host"][0] == ["bench:window", 0, 300]  # the memo hit: fewest device ops
    assert [h[0] for h in c["host"][1:]] == ["bench:push"]
    assert c["program"][0][:3] == ["koalja:push", 10, 290]
    assert c["devices"]["/device:TPU:0"]["ops"] == [["fusion.4", 100, 120]]


def test_existing_reductions_unchanged_on_recorded_trace():
    """The reductions the accepted metrics read give what they gave before
    the program's spans existed, on the recorded window."""
    t = json.loads((DATA / "v5e_serve_trace.json").read_text())
    lo, hi = devtrace.window(t)
    assert (lo, hi) == (0, 336935415)
    assert devtrace.busy_ns(t, lo, hi) == 300298970.0
    assert devtrace.idle_share_in(t, devtrace.spans(t, "push")) == pytest.approx(0.013149692483274023)
    assert devtrace.module_ns(t, r"decode_fn", lo, hi) == 283941752
    assert devtrace.module_ns(t, r"prefill_fn|decode_fn", lo, hi) == 299853716
    assert devtrace.top_ops(t, lo, hi)[:3] == [
        ["while.6", 0.237087197], ["fusion.48", 0.036156238], ["copy.40", 0.033250061]]
    assert devtrace.idle_by_host(t, lo, hi) == [
        ["between_pushes", 0.032634991], ["wait", 0.002325327], ["circuit", 0.001374051],
        ["decode", 0.000299576], ["task", 2.5e-06]]
    assert set(_read(t).values()) == {None}


def test_traced_tiny_cell_with_program_spans(bench_copy, on_cpu, capsys, monkeypatch, tmp_path):
    """The script on a tiny cell on the CPU: the six metrics, stages that
    sum to the program's circuit, and the device→host bytes of the served
    tokens and logits, exactly."""
    from conftest import TINY_CONFIG, TINY_MIX, add_tiny_cell

    monkeypatch.setattr(bench, "peaks", lambda kind, here=None: {"bf16_flops_per_s": 1e12,
                                                                 "hbm_bytes_per_s": 1e11})
    cell = add_tiny_cell(bench_copy)
    keep = tmp_path / "cut.json"
    rc = progtrace.main(["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1",
                         "--keep", str(keep), "--keep-pushes", "2"], root=bench_copy)
    assert rc == 0
    result, extra = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()[-2:]]
    assert result["correct"] is True and "circuit_ms_per_push" in result["metrics"]
    got = extra["program"]
    assert set(got) == set(progtrace.METRICS) and None not in got.values()
    logits = TINY_MIX["gen_max"] * TINY_CONFIG["vocab_size"] * 2  # bf16
    assert got["d2h_mb_per_push"] == pytest.approx((logits + TINY_MIX["gen_max"] * 4) / 1e6)
    assert extra["pushes"] == result["attempted"]
    assert extra["stages_and_their_gc_ms"] == pytest.approx(extra["circuit_in_program_ms"])
    assert 0 < extra["span_cost_ns"] < 1e5
    cut = json.loads(keep.read_text())
    assert sum(h[0] == "bench:push" for h in cut["host"]) == 2
    assert sum(p[0] == "koalja:push" for p in cut["program"]) == 2


def test_recorded_v5e_program_trace():
    """The reductions on three pushes cut from a traced window of
    stablelm-serve-code on one TPU v5e chip."""
    t = json.loads(RECORDED.read_text())
    lo, hi = devtrace.window(t)
    n = progtrace.pushes(t, lo, hi)
    assert n == 3 == len(devtrace.spans(t, "push"))
    got = _read(t)
    assert None not in got.values()
    # the logits AV (32 x 100352 bf16) and 32 int32 tokens, per push
    assert got["d2h_mb_per_push"] == pytest.approx((32 * 100352 * 2 + 32 * 4) / 1e6)
    stages = sum(got[f"{s}_ms_per_push"] for s in progtrace.STAGES)
    nested_gc = progtrace.gc_ns(t, lo, hi, circuit=True) / n / 1e6
    circuit = progtrace.circuit_ns(t, lo, hi) / n / 1e6
    assert stages + nested_gc == pytest.approx(circuit, rel=0.01)
    assert got["hash_ms_per_push"] > got["scheduler_ms_per_push"] > got["store_ms_per_push"]
    # circuit_gaps splits exactly the idle time idle_by_host calls the circuit
    gaps = progtrace.circuit_gaps(t, lo, hi)
    assert gaps[0][0] == "hash"
    assert sum(s for _, s in gaps) == pytest.approx(dict(devtrace.idle_by_host(t, lo, hi))["circuit"])
    # the accepted reductions read this trace as they read one without program spans
    plain = {k: v for k, v in t.items() if k != "program"}
    assert devtrace.idle_by_host(plain, lo, hi) == devtrace.idle_by_host(t, lo, hi)
    assert devtrace.busy_ns(plain, lo, hi) == devtrace.busy_ns(t, lo, hi)
