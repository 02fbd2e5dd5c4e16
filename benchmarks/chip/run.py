#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are looked up
by name in ``BENCHMARK.json`` at the root of the checkout. The run makes its
weights and inputs from ``--seed``, warms up every shape the mix uses, then
measures for ``--seconds``. With ``--trace 0`` it reports the cell's
end-to-end metrics; with ``--trace 1`` it records a profiler trace of the
window and reports the cell's per-layer metrics, the device's busy time and
a breakdown. Either way it checks the served answers against the plain
reference and prints each compared number beside its limit.

The last line on stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (platform, kind, count, peak memory of
the fullest chip), with ``--trace 1`` also ``breakdown``, and last
``checks``. Without a TPU, or with fewer chips than the cell asks for, the
run names what JAX found on stderr and exits 2 with no result.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import bench  # noqa: E402


def breakdown(trace: dict) -> tuple:
    """(busy_s, window_s, breakdown) of a traced window."""
    import devtrace

    lo, hi = devtrace.window(trace)
    return (
        devtrace.busy_ns(trace, lo, hi) / 1e9,
        (hi - lo) / 1e9,
        {"device_ops": devtrace.top_ops(trace, lo, hi), "idle_gaps": devtrace.idle_by_host(trace, lo, hi)},
    )


def main(argv=None, root: Path = bench.CHECKOUT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = bench.manifest(root)
    chip = root / bench.HERE.relative_to(bench.CHECKOUT)
    cell = bench.find(man["workloads"], args.workload, "workload")
    c = bench.config_file(bench.find(man["configs"], cell["config"], "configuration"), root)
    mix = bench.traffic_file(cell["traffic"], chip)
    bench.enable_compile_cache()
    try:
        devices = bench.require_chip(cell["chips"])
    except bench.NoChip as exc:
        print(f"run.py: {exc}; not running on it", file=sys.stderr)
        return 2
    peak = bench.peaks(devices[0].device_kind, chip) if args.trace else None
    kind = importlib.import_module(mix["kind"])
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        run, checks, device = kind.run(
            c, mix, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            devices=devices, started=STARTED, tmp=tmp,
        )
    run.peak = peak
    section = "per_layer" if args.trace else "end_to_end"
    result = {
        "correct": kind.correct(checks),
        "attempted": len(run.served) + len(run.failed),
        "failed": len(run.failed),
        "metrics": {},
        "device": device,
    }
    if run.trace and run.trace["devices"]:
        device["busy_s"], device["window_s"], result["breakdown"] = breakdown(run.trace)
    for m in bench.cell_metrics(man, cell["name"], section):
        value = bench.load_module(chip / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    bench.print_result(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
