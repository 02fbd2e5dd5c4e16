"""What every cell shares: the manifest, the files a cell is made of, the
device a run holds, seeds, the compile cache and the result line.

Everything is found by name. A cell names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``); a
metric is read by ``metrics/<name>.py``; the chip's peaks are a row of
``peaks.json`` keyed by ``device_kind``. Adding a cell, a mix or a metric is
adding files and manifest entries; no code here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
# A fixed path inside the checkout: the path is part of the cache key, so a
# directory that moves would never hit. Listed in .gitignore.
CACHE_DIR = CHECKOUT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind, or not as many as, a cell needs."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = CHECKOUT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def config_file(cfg_entry: dict, root: Path = CHECKOUT) -> dict:
    return load_json(root / cfg_entry["file"])


def traffic_file(name: str, here: Path = HERE) -> dict:
    """A mix's parameters. A file may name a ``base`` mix whose parameters
    it starts from; its own keys win."""
    own = load_json(here / "traffic" / f"{name}.json")
    base = own.pop("base", None)
    if base is None:
        return {"name": name, **own}
    return {**traffic_file(base, here), **own, "name": name}


def peaks(kind: str, here: Path = HERE) -> dict:
    table = load_json(here / "peaks.json")
    if kind not in table:
        raise KeyError(
            f"device kind {kind!r} has no row in peaks.json (rows: {sorted(table)})"
        )
    return table[kind]


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(f"_bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(man: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that this
    cell reports: those that list it, and those that list no cells."""
    out = []
    for m in man[section]:
        cells = m.get("workloads")
        if cells is None or cell in cells:
            out.append(m)
    return out


def seeds(seed: int, n: int = 4) -> list:
    """``n`` 32-bit words from a seed of any size, 2**31 and beyond included."""
    import numpy as np

    return [int(x) for x in np.random.SeedSequence(int(seed)).generate_state(n)]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at ``JAX_COMPILATION_CACHE_DIR``
    where that is set (JAX reads it itself), else at :data:`CACHE_DIR`.
    Every program is cached, however quickly it compiled, so that a second
    run of a cell compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def require_chip(chips: int) -> list:
    """The devices a cell runs on. Anything but a TPU, or fewer chips than
    the cell asks for, raises :class:`NoChip` naming what JAX found."""
    import jax

    devs = jax.devices()
    found = f"platform {devs[0].platform!r} ({devs[0].device_kind}) x{len(devs)}"
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found {found}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, but JAX found {found}")
    return devs[:chips]


def device_block(devices) -> dict:
    """The device as JAX reports it, with the peak of the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def print_result(result: dict, checks: dict) -> None:
    """Each compared number beside its limit as the last lines on stderr,
    then the result as the last line on stdout, with ``checks`` last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({**result, "checks": checks}), flush=True)
