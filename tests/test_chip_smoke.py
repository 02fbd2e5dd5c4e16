"""The bring-up script off the chip: it refuses the CPU, fails where the repo
is missing, and its phases pass at a tiny size (CPU, kernels interpreted;
the train phase on four virtual devices). The compile cache lands where
``JAX_COMPILATION_CACHE_DIR`` says, or at one fixed path in the checkout."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(args, cwd, **env):
    full = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full.update(env)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=full,
        capture_output=True, text=True, timeout=300,
    )


def test_refuses_cpu_and_names_it():
    r = _run([os.path.join(REPO, "chip_smoke.py")], REPO, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    r = _run([str(tmp_path / "chip_smoke.py")], str(tmp_path), **env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_one_chip_phases_at_tiny_size(capsys):
    chip_smoke.serving_phase(reduced=True, prompt_len=16, gen=4)
    chip_smoke.serve_driver_phase(reduced=True, prompt_len=16, gen=4)
    modes = chip_smoke.kernel_phase(reduced=True)
    assert set(modes) == {"flash_attention", "flash_decode", "moe_gmm", "mamba_scan", "hash_tree"}
    assert not any(modes.values())  # interpreted: no chip here
    out = capsys.readouterr().out
    for phase in ("[serve]", "[serve-driver]", "[kernel:hash_tree:core.hashing]"):
        assert phase in out


def test_four_chip_train_phase_on_virtual_devices():
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "chip_smoke.train_phase(reduced=True, batch=4, seq=32, steps=8, lr=1e-3)" % REPO
    )
    r = _run(
        ["-c", code], REPO, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.path.join(REPO, "src"),
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "[train:cut-1v4]" in r.stdout and "[train:full]" in r.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(from_env, monkeypatch, tmp_path):
    from repro.launch import device

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert device.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before  # nothing set in code
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = device.enable_compile_cache()
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
