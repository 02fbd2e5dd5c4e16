"""Integration: the multi-pod dry-run machinery end to end, in a subprocess
(it needs the 512-fake-device XLA flag, which must not leak into this
process). One cheap cell per mesh proves lower+compile+record."""

import json
import os
import subprocess
import sys

import pytest


# multi-minute model/kernel path: runs in the full CI job only
pytestmark = pytest.mark.slow


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dryrun(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args],
        capture_output=True,
        text=True,
        timeout=540,
        env=env,
        cwd=REPO,
    )


@pytest.mark.parametrize("multipod", [False, True])
def test_dryrun_cell_compiles_and_records(multipod, tmp_path):
    args = [
        "--arch", "stablelm-1.6b", "--shape", "decode_32k", "--tag", "citest",
    ] + (["--multipod"] if multipod else [])
    r = _run_dryrun(args)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[OK] stablelm-1.6b x decode_32k" in r.stdout

    mesh = "pod2x16x16" if multipod else "pod16x16"
    rec_path = os.path.join(
        REPO, "benchmarks", "results", "dryrun", mesh,
        "stablelm-1.6b__decode_32k__citest.json",
    )
    rec = json.load(open(rec_path))
    assert rec["n_devices"] == (512 if multipod else 256)
    assert rec["memory_analysis"] is not None
    assert rec["state_gb_per_device"] < 16.0
    assert rec["compile_seconds"] > 0


def test_dryrun_skip_row_recorded():
    r = _run_dryrun(["--arch", "internlm2-20b", "--shape", "long_500k", "--tag", "citest"])
    assert r.returncode == 0
    assert "[SKIP]" in r.stdout
    rec = json.load(
        open(
            os.path.join(
                REPO, "benchmarks", "results", "dryrun", "pod16x16",
                "internlm2-20b__long_500k__citest.json",
            )
        )
    )
    assert "skip" in rec


def test_dryrun_lever_overrides():
    r = _run_dryrun(
        [
            "--arch", "stablelm-1.6b", "--shape", "decode_32k",
            "--set", "block_kv=1024", "--tag", "citest2",
        ]
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[OK]" in r.stdout
