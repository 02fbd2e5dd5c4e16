"""BENCHMARK.json against the rules a manifest has to keep."""

from __future__ import annotations

import json
import math
import re

import pytest

import bench

MAN = bench.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
WIDTH = re.compile(
    r"(_dim|_rank|hidden_size|intermediate_size|latent_size|state_size|projection_size"
    r"|expand|expansion|num_experts_per_tok)$|^head_"
)
METRICS = MAN["end_to_end"] + MAN["per_layer"]
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def test_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and not p.startswith("/") and ".." not in p
    assert any(MAN["command"][1].startswith(p + "/") for p in MAN["paths"])
    assert len(json.dumps(MAN)) < 64 * 1024


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in MAN[group]]
        assert len(set(names)) == len(names), group
        assert all(NAME.match(n) for n in names), names
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert LINE.match(c["why"]) and LINE.match(c["source"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and LINE.match(w["why"]) and w["chips"] in (1, 4)


def test_metrics_sources_bounds_and_moves():
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert LINE.match(m["layer"])
        assert m["moves"] in E2E
        for cell in m.get("workloads", [w["name"] for w in MAN["workloads"]]):
            assert reports(E2E[m["moves"]], cell), (m["name"], cell)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_cells_configs_and_chips():
    cells = MAN["workloads"]
    names = {c["name"] for c in MAN["configs"]}
    assert {w["config"] for w in cells} == names  # every configuration has a cell
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        e2e = [m for m in MAN["end_to_end"] if reports(m, w["name"])]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert any(reports(m, w["name"]) for m in MAN["per_layer"])
    for m in METRICS:
        assert set(m.get("workloads", [])) <= {w["name"] for w in cells}


def test_every_name_has_its_file():
    chip = bench.HERE
    for w in MAN["workloads"]:
        mix = bench.traffic_file(w["traffic"])
        assert (chip / f"{mix['kind']}.py").is_file()
    for m in METRICS:
        assert (chip / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    files = [c["file"] for c in MAN["configs"]]
    assert len(set(files)) == len(files)
    for c in MAN["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
        body = bench.config_file(c)
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not WIDTH.search(key), key
            assert key in body and key in body["published"] or key == "rms_norm_eps"


def test_run_length_fits_a_full_check():
    s = MAN["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    cells = 24
    assert (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200 <= 43200


def test_peaks_table():
    row = bench.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert math.isclose(row["ici_bits_per_s"], 1.6e12) and row["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no row"):
        bench.peaks("TPU v4")
