"""Shared set-up of the chip benchmark's tests: the harness's own modules on
the path, and a copy of the benchmark with a tiny cell added."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# A configuration and a mix small enough for the CPU: a 2-layer GQA decoder
# at width 64, prompts of 16 and 32 tokens. Its logit_err limit lies between
# the CPU's readings at this size on seeds 1, 2, 3 and 2**31 + 5: the program
# 0.0080-0.0089, the int8 control 0.023-0.029 (fp8 reads 0.084-0.096).
TINY_CONFIG = {
    "arch": "stablelm-1.6b",
    "source": "tests only",
    "num_hidden_layers": 2,
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "vocab_size": 256,
    "rope_theta": 10000,
    "partial_rotary_factor": 1.0,
    "use_qkv_bias": False,
    "rms_norm_eps": 1e-05,
    "torch_dtype": "bfloat16",
    "reduced": [],
    "control": "int8",
    "limits": {"logit_err": 0.016},
}
TINY_MIX = {
    "base": "code-open",
    "rate_per_s": 16.0,
    "prompt_lengths": [16, 32],
    "prompt_weights": [0.5, 0.5],
    "gen_min": 4,
    "gen_max": 8,
    "max_len": 40,
    "check_requests": 6,
}


def add_tiny_cell(root: Path, traffic: dict = TINY_MIX, name: str = "tiny-serve") -> str:
    """Add a tiny configuration, mix and cell to the benchmark at ``root`` by
    adding files and manifest entries only. Returns the cell's name."""
    chip = root / "benchmarks" / "chip"
    (chip / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (chip / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny", "source": "tests only",
                           "file": "benchmarks/chip/configs/tiny.json", "reduced": [], "why": "tests"})
    man["workloads"].append({"name": name, "config": "tiny", "traffic": name, "chips": 1, "why": "tests"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and "stablelm-serve-repeat" in m["workloads"]:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return name


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of BENCHMARK.json and the files under its paths."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.fixture
def on_cpu(monkeypatch):
    """Let a run go ahead on the CPU: skip the harness's look for a chip."""
    import jax

    import bench

    monkeypatch.setattr(bench, "require_chip", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: "")
