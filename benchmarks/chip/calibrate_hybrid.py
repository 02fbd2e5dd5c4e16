#!/usr/bin/env python3
"""``calibrate.py`` for cells of the ``serve_hybrid`` kind: the readings its
limits and its rate are set from, many in one process.

  python3 benchmarks/chip/calibrate_hybrid.py limits --workload <cell> --seeds 1,2,... \\
      --control-seeds 1,2,3 --seconds <s>
  python3 benchmarks/chip/calibrate_hybrid.py knee --workload <cell> --rates 1,1.5,2 \\
      --schedule-seeds 1,2,3 --seconds <s>

``limits``: for each seed a timed window of the cell and its check, the
program's readings; for each control seed the same check with the control
(``int8``), routed by itself, in the program's place. ``logit_err``'s limit
lies between the program's largest reading and the control's smallest;
``route_flip_margin_max``, the widest router margin at which the program's
experts differed from the reference's, is the error ``route_margin_tau``
is set above. ``knee``: as ``calibrate.py knee``, one window per rate and
arrival schedule (``--schedule-seeds``, the mix's ``schedule_seed`` by
default): a run's seed draws only the prompts, so the schedules are what
tells a queue that grows with the load from one a single burst of long
prompts builds.

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
import serve_hybrid  # noqa: E402
from calibrate import _ints  # noqa: E402


def _session(prog, c: dict, mix: dict, seed: int, seconds: float, tmp: str):
    """A timed window of the cell on fresh weights and a fresh workspace.
    Returns (Run, Session, the sampled requests' expert choices)."""
    ses = serve_hybrid.setup(prog, c, mix, seed, seconds, tempfile.mkdtemp(dir=tmp))
    opened, served, failed, ended = serve.window(
        ses.ws, ses.task, ses.reqs, seconds, ses.calls, ses.keep
    )
    run = serve_hybrid.Run(c, mix, seconds, opened, served, failed, 0.0, ses.ws.stats(),
                           ended=ended)
    choices = serve_hybrid.read_routes(run, ses)
    ses.release()
    return run, ses, choices


def limits(args, prog, c, mix, tmp) -> None:
    for seed in args.seeds:
        run, ses, choices = _session(prog, c, mix, seed, args.seconds, tmp)
        line = {"seed": seed, "served": len(run.served)}
        sides = ["program", "int8"] if seed in args.control_seeds else ["program"]
        for side in sides:
            control = None if side == "program" else side
            checks = serve_hybrid.check(run, ses.weights, choices, control=control)
            line[side] = {k: v["value"] for k, v in checks.items()}
            line[side]["correct"] = serve.correct(checks)
        print(json.dumps(line), flush=True)
        del run, ses, choices


def knee(args, prog, c, mix, tmp) -> None:
    for rate in args.rates:
        for order in args.schedule_seeds or [mix["schedule_seed"]]:
            _knee_window(args, prog, c, {**mix, "rate_per_s": rate, "schedule_seed": order}, tmp)


def _knee_window(args, prog, c, mix, tmp) -> None:
    rate = mix["rate_per_s"]
    run, _, _ = _session(prog, c, mix, args.seed, args.seconds, tmp)
    lat = np.asarray(run.latencies())
    quarter = max(1, len(lat) // 4)
    push = {}
    for s in run.served:
        push.setdefault(s.req.prompt_len, []).append(s.end - s.start)
    print(json.dumps({
        "rate_per_s": rate,
        "schedule_seed": mix["schedule_seed"],
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
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=lambda t: [float(x) for x in t.split(",")], default=[])
    ap.add_argument("--schedule-seeds", type=_ints, default=[])
    args = ap.parse_args(argv)

    man = bench.manifest()
    cell = bench.find(man["workloads"], args.workload, "workload")
    c = bench.config_file(bench.find(man["configs"], cell["config"], "configuration"))
    mix = bench.traffic_file(cell["traffic"])
    bench.enable_compile_cache()
    try:
        devices = bench.require_chip(cell["chips"])
    except bench.NoChip as exc:
        print(f"calibrate_hybrid.py: {exc}; not running on it", file=sys.stderr)
        return 2
    prog = serve_hybrid.build_program(c, mix, devices[0])
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        (limits if args.mode == "limits" else knee)(args, prog, c, mix, tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
