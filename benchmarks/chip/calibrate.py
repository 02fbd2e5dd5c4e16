#!/usr/bin/env python3
"""Readings a cell's fixed numbers are set from, many in one process.

  python3 benchmarks/chip/calibrate.py limits --workload <cell> --seeds 1,2,... \\
      --control-seeds 1,2,3 --seconds <s>
  python3 benchmarks/chip/calibrate.py knee --workload <cell> --rates 2,3,4 --seconds <s>

``limits``: for each seed, a run of the cell's timed path (its weights, its
mix at its rate for ``--seconds``) and the run's check on its seeded sample:
the program's readings. For each control seed, the same check on the same
sample with a control's logits in the program's place (``--controls``, by
default the configuration's ``control``): the control's readings, and
whether it came out correct. A limit lies between the
program's largest reading and the control's smallest.

``knee``: one window per rate on one set of weights, with a fresh
workspace each, and for each the latencies, tokens per second and how far
the queue grew: the highest rate at which the last requests wait no longer
than the first is the knee.

Each reading is one JSON line on stdout. Like ``run.py``, it runs only on a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import numpy as np  # noqa: E402

import bench  # noqa: E402
import serve  # noqa: E402


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def _session(prog, c: dict, mix: dict, seed: int, seconds: float, tmp: str):
    """A timed window of the cell on fresh weights and a fresh workspace.
    Returns (Run, Session); the session's task has let go of its weights."""
    ses = serve.setup(prog, c, mix, seed, seconds, tempfile.mkdtemp(dir=tmp))
    opened, served, failed, ended = serve.window(
        ses.ws, ses.task, ses.reqs, seconds, ses.calls, ses.keep
    )
    run = serve.Run(c, mix, seconds, opened, served, failed, 0.0, ses.ws.stats(), ended=ended)
    ses.release()
    return run, ses


def limits(args, prog, c, mix, tmp) -> None:
    for seed in args.seeds:
        run, ses = _session(prog, c, mix, seed, args.seconds, tmp)
        line = {"seed": seed, "served": len(run.served)}
        sides = ["program", *args.controls] if seed in args.control_seeds else ["program"]
        for side in sides:
            try:
                checks = serve.check(run, ses.weights, control=None if side == "program" else side)
            except Exception as exc:  # a control that crashes has failed, and sets no upper end
                line[side] = {"error": f"{type(exc).__name__}: {exc}"}
                continue
            line[side] = {k: v["value"] for k, v in checks.items()}
            line[side]["correct"] = serve.correct(checks)
        print(json.dumps(line), flush=True)
        del run, ses


def knee(args, prog, c, mix, tmp) -> None:
    for rate in args.rates:
        run, ses = _session(prog, c, {**mix, "rate_per_s": rate}, args.seed, args.seconds, tmp)
        del ses
        lat = np.asarray(run.latencies())
        quarter = max(1, len(lat) // 4)
        push = {}
        for s in run.served:
            push.setdefault(s.req.prompt_len, []).append(s.end - s.start)
        print(json.dumps({
            "rate_per_s": rate,
            "requests": len(lat),
            "p50_s": float(np.quantile(lat, 0.5)),
            "p90_s": float(np.quantile(lat, 0.9)),
            "first_quarter_mean_s": float(lat[:quarter].mean()),
            "last_quarter_mean_s": float(lat[-quarter:].mean()),
            "tokens_per_s": sum(s.req.gen for s in run.served if s.end <= run.closes) / args.seconds,
            "busy_push_share": sum(s.end - s.start for s in run.served) / (run.ended - run.opened),
            "push_s_by_prompt": {n: [float(np.median(v)), float(np.max(v))] for n, v in sorted(push.items())},
        }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("limits", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_ints, default=[1])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--controls", type=lambda t: t.split(","), default=None,
                    help="control precisions to read (default: the configuration's)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=lambda t: [float(x) for x in t.split(",")], default=[])
    args = ap.parse_args(argv)

    man = bench.manifest()
    cell = bench.find(man["workloads"], args.workload, "workload")
    c = bench.config_file(bench.find(man["configs"], cell["config"], "configuration"))
    mix = bench.traffic_file(cell["traffic"])
    bench.enable_compile_cache()
    try:
        devices = bench.require_chip(cell["chips"])
    except bench.NoChip as exc:
        print(f"calibrate.py: {exc}; not running on it", file=sys.stderr)
        return 2
    args.controls = args.controls or [c["control"]]
    prog = serve.build_program(c, mix, devices[0])
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        (limits if args.mode == "limits" else knee)(args, prog, c, mix, tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
