"""The harness end to end on the CPU, at a tiny size: its refusal without a
chip, a correct run, a run whose timed path is broken underneath, the
control, and a mix and a metric added by files and entries alone."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, TINY_CONFIG, TINY_MIX, add_tiny_cell


def _cli(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload", "stablelm-serve-code",
           "--seed", "3", "--seconds", "1", "--trace", "0", *args]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_tpu_exits_nonzero_and_names_the_platform():
    out = _cli(ROOT)
    assert out.returncode == 2
    assert "needs a TPU, but JAX found platform 'cpu'" in out.stderr
    assert out.stdout == ""


def test_benchmark_files_alone_do_not_run(bench_copy):
    out = _cli(bench_copy)
    assert out.returncode != 0 and "{" not in out.stdout


def _run(root, capsys, cell, *, trace=0, seed=2**31 + 11):
    import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace)], root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_tiny_cell_is_correct(bench_copy, on_cpu, capsys):
    result = _run(bench_copy, capsys, add_tiny_cell(bench_copy))
    assert result["correct"] is True
    assert result["attempted"] == round(TINY_MIX["rate_per_s"]) and result["failed"] == 0
    assert set(result["metrics"]) == {
        "push_to_result_p50_s", "push_to_result_p90_s", "served_tokens_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    assert list(result)[-1] == "checks"
    assert result["checks"]["logit_err"]["limit"] == TINY_CONFIG["limits"]["logit_err"]
    assert result["checks"]["compared_tokens"]["value"] > 0


def test_added_mix_and_metric_are_found_by_name(bench_copy, on_cpu, capsys, monkeypatch):
    import bench

    chip = bench_copy / "benchmarks" / "chip"
    (chip / "metrics" / "pushes_per_s.py").write_text(
        "def read(run):\n    return len(run.served) / run.seconds\n")
    cell = add_tiny_cell(bench_copy, {**TINY_MIX, "repeat_share": 0.5, "popular": 4, "zipf_s": 1.0},
                         name="tiny-repeat")
    man = json.loads((bench_copy / "BENCHMARK.json").read_text())
    man["per_layer"].append({"name": "pushes_per_s", "unit": "1/s", "better": "higher",
                             "source": "host_clock", "layer": "circuit",
                             "moves": "served_tokens_per_s", "workloads": [cell]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(man))
    monkeypatch.setattr(bench, "peaks", lambda kind, here=None: {"bf16_flops_per_s": 1e12,
                                                                 "hbm_bytes_per_s": 1e11})
    result = _run(bench_copy, capsys, cell, trace=1)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["pushes_per_s"]["value"] == pytest.approx(16.0)
    assert metrics["memo_hit_share"]["value"] > 0
    assert "circuit_ms_per_push" in metrics
    # no device plane in a CPU trace: the device readers find nothing to read
    assert "decode_roofline.serve" not in metrics and "busy_s" not in result["device"]


def _broken(monkeypatch, fault):
    """Break the timed path underneath the harness."""
    import jax
    import jax.numpy as jnp

    import serve

    build = serve.build_program

    def faulty(c, mix, device):
        prog = build(c, mix, device)
        if fault == "token":  # every token altered where it is produced
            pick = prog.pick
            prog.pick = lambda logits: (pick(logits) + 1) % c["vocab_size"]
        elif fault == "state":  # decode returns the cache it was given
            decode = prog.decode
            prog.decode = lambda p, tok, st: (decode(p, tok, jax.tree.map(jnp.copy, st))[0], st)
        return prog

    monkeypatch.setattr(serve, "build_program", faulty)


@pytest.mark.parametrize("fault, caught_by", [("token", "not_greedy"), ("state", "logit_err")])
def test_a_broken_timed_path_is_not_correct(bench_copy, on_cpu, capsys, monkeypatch, fault,
                                            caught_by):
    cell = add_tiny_cell(bench_copy)
    _broken(monkeypatch, fault)
    result = _run(bench_copy, capsys, cell)
    check = result["checks"][caught_by]
    assert result["correct"] is False and check["value"] > check["limit"]


def test_control_is_not_correct(bench_copy, on_cpu, capsys):
    """The configuration's control, in the program's place on a tiny cell's
    served requests, comes out not correct through the run's own check, on
    each of three seeds, where the program comes out correct."""
    import bench
    import calibrate
    import serve

    cell = add_tiny_cell(bench_copy)
    mix = bench.traffic_file(cell, bench_copy / "benchmarks" / "chip")
    prog = serve.build_program(TINY_CONFIG, mix, __import__("jax").devices()[0])
    control = TINY_CONFIG["control"]
    args = argparse.Namespace(seeds=[1, 2, 3], control_seeds=[1, 2, 3], controls=[control],
                              seconds=1.0)
    calibrate.limits(args, prog, TINY_CONFIG, mix, str(bench_copy))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 3
    limit = TINY_CONFIG["limits"]["logit_err"]
    for x in lines:
        assert x["program"]["correct"] is True and x["program"]["logit_err"] < limit
        assert x[control]["correct"] is False and limit < x[control]["logit_err"] < np.inf
