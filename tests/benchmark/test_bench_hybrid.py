"""The hybrid serving kind (``serve_hybrid``) on the CPU at a tiny size, its
work counts against hand counts at the published widths, and the readers
of its four per-layer metrics on a small trace with its scope map."""

from __future__ import annotations

import argparse
import json
import types

import numpy as np
import pytest

import bench
import scopes
import work_hybrid
from conftest import ROOT

CONFIG = bench.load_json(ROOT / "benchmarks" / "chip" / "configs" / "jamba2-mini-period8.json")
METRICS = ("mfu.hybrid", "decode_roofline.hybrid", "mamba_scan_roofline.hybrid",
           "moe_roofline.hybrid")
READERS = {m: bench.load_module(bench.HERE / "metrics" / f"{m}.py") for m in METRICS}

# One period at width 64 (dt rank 4, d_state 8), 16 router outputs with 8
# held. Its limits lie between the CPU's readings at this size on seeds 1, 2,
# 3 and 2**31 + 11: logit_err of the program 0.0196-0.0230, of the int8
# control 0.0512-0.1355; the program's experts differed from the
# reference's own at router margins up to 0.027 (6-10 positions a run), the
# control's up to 0.060-0.110.
TINY = {**CONFIG, "source": "tests only", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
        "mamba_dt_rank": 4, "mamba_d_state": 8,
        "limits": {"logit_err": 0.035, "route_margin_tau": 0.05}}
TINY_MIX = {"base": "code-open", "kind": "serve_hybrid", "rate_per_s": 8.0,
            "prompt_lengths": [16, 32], "prompt_weights": [0.5, 0.5], "gen_min": 4,
            "gen_max": 8, "max_len": 40, "check_requests": 4}


def add_tiny_hybrid_cell(root, name="tiny-hybrid") -> str:
    """A tiny hybrid configuration, mix and cell, added to the benchmark at
    ``root`` by files and manifest entries only."""
    chip = root / "benchmarks" / "chip"
    (chip / "configs" / "tiny-hybrid.json").write_text(json.dumps(TINY))
    (chip / "traffic" / f"{name}.json").write_text(json.dumps(TINY_MIX))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-hybrid", "source": "tests only", "reduced": [],
                           "file": "benchmarks/chip/configs/tiny-hybrid.json", "why": "tests"})
    man["workloads"].append({"name": name, "config": "tiny-hybrid", "traffic": name,
                             "chips": 1, "why": "tests"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "jamba2-mini-serve-code" in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return name


# ---------------------------------------------------------------------------
# work, by hand
# ---------------------------------------------------------------------------


def test_parameter_hand_count():
    # Mamba: in_proj 4096x16384, conv 4x8192 + bias, x_proj 8192x(256+32) and
    # the dt/B/C norms, dt_proj 256x8192 + bias, A 8192x16, D, out_proj
    mamba = (4096 * 16384 + 4 * 8192 + 8192 + 8192 * 288 + 288 + 256 * 8192 + 8192
             + 8192 * 16 + 8192 + 8192 * 4096)
    assert work_hybrid.mamba_params(CONFIG) == mamba == 105_308_448
    assert work_hybrid.attention_params(CONFIG) == 4096 * 48 * 128 + 32 * 128 * 4096 == 41_943_040
    expert = 3 * 4096 * 14336
    layers = 7 * mamba + 41_943_040 + 4 * expert + 4 * (4096 * 16 + 8 * expert) + 8 * 2 * 4096
    # with an eighth of the vocabulary in the embedding and the head, the
    # period holds 7,188,326,368 parameters, and the final norm's 4,096
    assert layers + 2 * 8192 * 4096 == 7_188_326_368
    assert work_hybrid.model_params(CONFIG) == 7_188_326_368 + 4096
    assert work_hybrid.counts(CONFIG) == {"mamba": 7, "attention": 1, "moe": 4, "dense": 4}


@pytest.mark.parametrize("per_layer, gb", [(8, 14.3237), (1, 4.4586)])
def test_decode_bytes_by_held_experts_reached(per_layer, gb):
    """A decode step reading every held expert of its 4 MoE layers moves
    14.3 GB (17.5 ms at 819 GB/s); reading one a layer, 4.46 GB (5.4 ms)."""
    expert = 3 * 4096 * 14336
    base = work_hybrid.decode_bytes(CONFIG, 999, 0)
    assert work_hybrid.decode_bytes(CONFIG, 999, 4 * per_layer) == base + 2 * 4 * per_layer * expert
    # every weight but the experts and the embedding table, one embedding
    # row, 1,000 cached positions of one attention layer's 8 KV heads of 128,
    # the logits, the Mamba states read and written, A and dt_bias in float32
    other = work_hybrid.model_params(CONFIG) - 4 * 8 * expert - 8192 * 4096 + 4096
    kv = 2 * 8 * 128 * 1000
    state = 7 * 2 * (8192 * 16 * 4 + 3 * 8192 * 2)
    assert base == 2 * (other + kv + 8192) + state + 7 * (8192 * 16 + 8192) * 2
    assert work_hybrid.decode_bytes(CONFIG, 999, 4 * per_layer) / 1e9 == pytest.approx(gb, abs=1e-4)


def test_request_work_reads_the_counters():
    route = [[[300, 212, 0, 8], [20, 18, 0, 17]]] * 4  # per MoE layer: prefill, decode
    w = work_hybrid.request_work(CONFIG, 256, 20, route)
    expert = 3 * 4096 * 14336
    assert w["decode_bytes"] == (sum(work_hybrid.decode_bytes(CONFIG, p, 0) for p in range(256, 275))
                                 + 2 * 4 * 17 * expert)
    assert w["prefill_flops"] == work_hybrid.prefill_flops(CONFIG, 256, 4 * 300)
    assert w["prefill_flops"] - work_hybrid.prefill_flops(CONFIG, 256, 0) == 4 * 300 * 2 * expert
    assert w["moe_prefill"][0] == (2 * 256 * 4096 * 16 + 300 * 2 * expert,
                                   2 * (4096 * 16 + 8 * expert + 2 * 256 * 4096))
    assert w["moe_decode"][1] == 19 * 4 * 2 * (4096 * 16 + 2 * 4096) + 2 * 4 * 17 * expert


# ---------------------------------------------------------------------------
# the cell on the CPU
# ---------------------------------------------------------------------------


def test_tiny_hybrid_cell_is_correct(bench_copy, on_cpu, capsys):
    import run

    cell = add_tiny_hybrid_cell(bench_copy)
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1",
                   "--trace", "0"], root=bench_copy)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == round(TINY_MIX["rate_per_s"])
    assert set(result["metrics"]) == {
        "push_to_result_p50_s", "push_to_result_p90_s", "served_tokens_per_s", "setup_s"}
    checks = result["checks"]
    assert checks["dropped_tokens"]["value"] == 0 and checks["route_mismatch_wide"]["value"] == 0
    assert checks["compared_tokens"]["value"] > 0
    assert checks["logit_err"]["limit"] == TINY["limits"]["logit_err"]


def test_tiny_hybrid_control_is_not_correct(bench_copy, on_cpu, capsys):
    """The int8 control, routed by itself, in the program's place on the
    served requests comes out not correct through the cell's own check on
    each of three seeds, where the program comes out correct."""
    import calibrate_hybrid
    import jax
    import serve_hybrid

    cell = add_tiny_hybrid_cell(bench_copy)
    mix = bench.traffic_file(cell, bench_copy / "benchmarks" / "chip")
    prog = serve_hybrid.build_program(TINY, mix, jax.devices()[0])
    args = argparse.Namespace(seeds=[1, 2, 3], control_seeds=[1, 2, 3], seconds=1.0)
    calibrate_hybrid.limits(args, prog, TINY, mix, str(bench_copy))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 3
    limit = TINY["limits"]["logit_err"]
    for x in lines:
        assert x["program"]["correct"] is True and x["program"]["logit_err"] < limit
        assert x["program"]["route_flip_margin_max"] < TINY["limits"]["route_margin_tau"]
        assert x["int8"]["correct"] is False and limit < x["int8"]["logit_err"] < np.inf


def test_parent_program_fails_before_any_weight(monkeypatch):
    """A program without the architecture fails in ``build_program``, before
    weights are drawn or a step is compiled."""
    import repro.configs
    import serve_hybrid

    monkeypatch.setattr(repro.configs, "_MODULES", {
        k: v for k, v in repro.configs._MODULES.items() if k != "jamba2-mini"})
    with pytest.raises(KeyError, match="unknown arch 'jamba2-mini'"):
        serve_hybrid.build_program(CONFIG, TINY_MIX, None)


# ---------------------------------------------------------------------------
# the readers, on a small trace
# ---------------------------------------------------------------------------


def _traced_run():
    """Two pushes, a 256-token request then a 512-token one, each a prefill
    and decode steps; op ``fusion.1`` is the scan in the 256-token prefill
    but the MoE layer in the 512-token one, ``while.2`` a loop of the scan
    with its body op ``fusion.3`` inside it."""
    host = [["bench:window", 0, 100_000_000], ["bench:push", 0, 40_000_000],
            ["bench:push", 50_000_000, 100_000_000]]
    modules = [["jit_prefill_fn(11)", 1_000_000, 9_000_000],
               ["jit_decode_fn(33)", 10_000_000, 30_000_000],
               ["jit_prefill_fn(22)", 51_000_000, 69_000_000],
               ["jit_decode_fn(33)", 70_000_000, 90_000_000]]
    ops = [["fusion.1", 1_000_000, 3_000_000], ["while.2", 3_000_000, 7_000_000],
           ["fusion.3", 4_000_000, 6_000_000], ["fusion.9", 10_000_000, 30_000_000],
           ["fusion.1", 51_000_000, 60_000_000], ["while.2", 60_000_000, 68_000_000],
           ["fusion.9", 70_000_000, 80_000_000], ["fusion.3", 80_000_000, 81_000_000]]
    hlo = {
        "prefill_fn:256": '  %fusion.1 = f32[8] fusion(), metadata={op_name="jit(prefill_fn)/jnp_mamba_scan/mul"}\n'
                          '  %while.2 = f32[8] while(), metadata={op_name="jit(prefill_fn)/jnp_mamba_scan/while"}\n'
                          '  %fusion.3 = f32[8] fusion(), metadata={op_name="jit(prefill_fn)/jnp_mamba_scan/while/body/add"}',
        "prefill_fn:512": '  %fusion.1 = f32[8] fusion(), metadata={op_name="jit(prefill_fn)/moe_layer/jnp_moe_gmm/ragged_dot"}\n'
                          '  %while.2 = f32[8] while(), metadata={op_name="jit(prefill_fn)/jnp_mamba_scan/while"}',
        "decode_fn": '  ROOT %fusion.9 = bf16[8] fusion(), metadata={op_name="jit(decode_fn)/moe_layer/dot_general"}\n'
                     '  %fusion.3 = f32[8] fusion(), metadata={op_name="jit(decode_fn)/attention/add"}',
    }
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": host,
             "scopes": scopes.op_scopes(hlo)}
    served = [types.SimpleNamespace(req=types.SimpleNamespace(prompt_len=n, gen=3), hit=False)
              for n in (256, 512)]
    route = [[[[300, 212, 0, 8], [3, 1, 0, 2]]] * 4, [[[600, 424, 0, 8], [2, 2, 0, 2]]] * 4]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return types.SimpleNamespace(trace=trace, served=served, route=route, config=CONFIG, peak=peak)


def test_scope_map_and_time():
    run = _traced_run()
    t = run.trace
    assert t["scopes"]["prefill_fn:256"] == {"fusion.1": "mamba_scan", "while.2": "mamba_scan",
                                             "fusion.3": "mamba_scan"}
    assert t["scopes"]["decode_fn"] == {"fusion.9": "moe"}
    lens = [256, 512]
    # the scan: 2 + 4 ms in the first prefill (fusion.3 inside while.2), 8 in the second
    assert scopes.scope_ns(t, "mamba_scan", 0, 100_000_000, lens) == 14_000_000
    # the MoE layer: fusion.1 of the 512-token prefill, both decodes' fusion.9
    assert scopes.scope_ns(t, "moe", 0, 100_000_000, lens) == 9_000_000 + 20_000_000 + 10_000_000
    assert scopes.scope_ns(t, "moe", 0, 75_000_000, lens) == 9_000_000 + 20_000_000 + 5_000_000
    assert scopes.scope_ns({**t, "scopes": None}, "moe", 0, 100_000_000, lens) is None


def test_metric_readers():
    run = _traced_run()
    peak = run.peak
    w = [work_hybrid.request_work(CONFIG, s.req.prompt_len, s.req.gen, r)
         for s, r in zip(run.served, run.route)]
    flops = sum(x["prefill_flops"] + x["decode_flops"] for x in w)
    got = {m: r.read(run) for m, r in READERS.items()}
    assert got["mfu.hybrid"] == pytest.approx(100 * flops / (0.066 * 197e12))
    decode = sum(work_hybrid.floor_s(x["decode_flops"], x["decode_bytes"], peak) for x in w)
    assert got["decode_roofline.hybrid"] == pytest.approx(100 * decode / 0.040)
    scan = 7 * sum(work_hybrid.floor_s(*x["scan_prefill"], peak) for x in w)
    assert got["mamba_scan_roofline.hybrid"] == pytest.approx(100 * scan / 0.014)
    moe = sum(sum(work_hybrid.floor_s(f, b, peak) for f, b in x["moe_prefill"])
              + work_hybrid.floor_s(*x["moe_decode"], peak) for x in w)
    assert got["moe_roofline.hybrid"] == pytest.approx(100 * moe / 0.039)


def test_readers_find_nothing_where_the_parent_leaves_nothing():
    """Without counters (a dense cell's run, the parent's) or a trace, every
    reader gives None and raises nothing."""
    run = _traced_run()
    for r in READERS.values():
        assert r.read(types.SimpleNamespace(**{**vars(run), "route": []})) is None
        assert r.read(types.SimpleNamespace(**{k: v for k, v in vars(run).items() if k != "route"})) is None
        assert r.read(types.SimpleNamespace(**{**vars(run), "trace": None})) is None


def test_readers_on_a_recorded_v5e_trace():
    """Two pushes of 256-token prompts (11 and 15 tokens out) cut from a
    traced window of ``jamba2-mini-serve-code`` on one TPU v5e chip, with
    their counters and the scope maps of the two compiled steps: every
    share lies inside (0, 100], the MoE layer's time includes the grouped
    matmul's kernel and masked convolutions, which carry no scope of their
    own, and a decode step takes 5.5-7.5 ms."""
    t = json.loads((ROOT / "tests" / "benchmark" / "data" / "v5e_hybrid_trace.json").read_text())
    served = [types.SimpleNamespace(req=types.SimpleNamespace(prompt_len=n, gen=g), hit=False)
              for n, g in t["served"]]
    run = types.SimpleNamespace(trace=t, served=served, route=t["route"], config=CONFIG,
                                peak=t["peak"])
    got = {m: r.read(run) for m, r in READERS.items()}
    assert all(0 < v <= 100 for v in got.values()), got
    assert got["decode_roofline.hybrid"] == pytest.approx(83.899, abs=1e-3)
    assert got["moe_roofline.hybrid"] == pytest.approx(56.210, abs=1e-3)
    assert got["mamba_scan_roofline.hybrid"] == pytest.approx(0.48707, abs=1e-5)
    assert got["mfu.hybrid"] == pytest.approx(3.6956, abs=1e-4)
    lo, hi = t["host"][0][1], t["host"][0][2]
    kernels = [n for p in t["devices"].values() for n, _, _ in p["ops"] if n.startswith("ragged-dot")]
    assert kernels and all(t["scopes"]["prefill_fn:256"][n] == "moe" for n in kernels)
    steps = [(e - s) / 1e6 for p in t["devices"].values() for n, s, e in p["modules"] if "decode_fn" in n]
    assert len(steps) == 10 + 14 and 5.5 < min(steps) and max(steps) < 7.5
    assert scopes.scope_ns(t, "moe", lo, hi, [256, 256]) > scopes.scope_ns(t, "mamba_scan", lo, hi, [256, 256])
