#!/usr/bin/env python3
"""From the program's own spans in a profiler trace to the circuit's
per-layer numbers.

The program writes ``koalja:*`` trace annotations (``repro.core.spans``) on
the clock of the device planes. ``collect`` keeps them as a list of
``[name, start_ns, end_ns, thread, args]``, sorted by start, for a trace's
``program`` key; ``thread`` tells the host threads apart and ``args`` holds
the span's arguments. The reductions below read that key beside the
trace's ``host`` and ``devices`` (``devtrace``); a trace without it gives
None.

A span's self time is its duration less the part its child spans on the
same thread cover. The circuit's stages, per push:

  scheduler  self time of ``koalja:push``: wave formation, snapshot key,
             memo lookup and insert, AV minting, registry, emit
  hash       self time of ``koalja:hash``, its device→host copy included
  store      self time of ``koalja:store.put`` and ``koalja:store.get``
  journal    ``koalja:journal.append`` with its ``koalja:journal.fsync``,
             less any GC inside

each counted inside a push and outside its task, so that the four stages
and the GC nested in them sum to the push less its task. A stage that runs
on a pooled worker thread, away from its push, is not counted: the push's
self time then holds the wait for it.

Run as a script, it runs one cell once with ``--trace 1`` as ``run.py``
does, keeps the program's spans in the trace, and prints after the run's
own result line one more JSON line: the six metrics that read them, the
device idle time of the circuit by program span, and the sums that check
them::

  python3 benchmarks/chip/progtrace.py --workload <cell> --seed <n> --seconds <s> \\
      [--keep <file.json> --keep-pushes <k>] [--spans <file.json>]

``--keep`` writes ``k`` consecutive pushes of the window, the device's
operations among them, as a recorded trace for the tests; ``--spans``
writes every host and program span of the window, without the device's.
"""

from __future__ import annotations

import bisect
import glob
import os

import devtrace

PREFIX = "koalja:"
STAGES = {
    "scheduler": ("koalja:push",),
    "hash": ("koalja:hash",),
    "store": ("koalja:store.put", "koalja:store.get"),
    "journal": ("koalja:journal.append", "koalja:journal.fsync"),
}


def events(data) -> list:
    """The ``koalja:*`` events of a ``jax.profiler.ProfileData``."""
    out: list = []
    for p, plane in enumerate(data.planes):
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append([e.name, e.start_ns, e.end_ns, f"{p}/{i}", dict(e.stats)])
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def collect(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return events(ProfileData.from_file(paths[-1])) if paths else []


# ---------------------------------------------------------------------------
# nesting
# ---------------------------------------------------------------------------


def nest(trace: dict) -> tuple:
    """(self_ns, outer) per program event: its self time, and the names of
    the spans on its thread that it lies in, outermost first. Built once
    per trace."""
    if "_nest" not in trace:
        prog = trace["program"]
        self_ns = [e - s for _, s, e, _, _ in prog]
        outer: list = [()] * len(prog)
        stacks: dict = {}
        for i, (name, s, e, thread, _) in enumerate(prog):
            stack = stacks.setdefault(thread, [])
            while stack and prog[stack[-1]][2] <= s:
                stack.pop()
            if stack:
                p = stack[-1]
                self_ns[p] -= min(e, prog[p][2]) - s
                outer[i] = outer[p] + (prog[p][0],)
            stack.append(i)
        trace["_nest"] = (self_ns, outer)
    return trace["_nest"]


def _starts(trace: dict) -> list:
    if "_pstarts" not in trace:
        trace["_pstarts"] = [s for _, s, _, _, _ in trace["program"]]
    return trace["_pstarts"]


def _inside(trace: dict, lo: float, hi: float) -> range:
    """Indices of the program events that start in [lo, hi)."""
    starts = _starts(trace)
    return range(bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi))


def _in_circuit(name: str, outer: tuple) -> bool:
    """Inside a push and outside its task (the push span itself counts)."""
    return (name == "koalja:push" or "koalja:push" in outer) and "koalja:task" not in outer


def self_ns(trace: dict, names, lo: float, hi: float) -> float:
    """Self time of the circuit's spans named ``names`` that start in
    [lo, hi)."""
    own, outer = nest(trace)
    prog = trace["program"]
    return float(sum(
        own[i] for i in _inside(trace, lo, hi)
        if prog[i][0] in names and _in_circuit(prog[i][0], outer[i])
    ))


def stage_ns(trace: dict, stage: str, lo: float, hi: float) -> float:
    return self_ns(trace, STAGES[stage], lo, hi)


def gc_ns(trace: dict, lo: float, hi: float, circuit: bool = False) -> float:
    """Time of the collections that start in [lo, hi); with ``circuit``
    those inside a push and outside its task only."""
    _, outer = nest(trace)
    prog = trace["program"]
    return float(sum(
        prog[i][2] - prog[i][1] for i in _inside(trace, lo, hi)
        if prog[i][0] == "koalja:gc" and (not circuit or _in_circuit("koalja:gc", outer[i]))
    ))


def circuit_ns(trace: dict, lo: float, hi: float) -> float:
    """The pushes that start in [lo, hi) less their tasks."""
    _, outer = nest(trace)
    prog = trace["program"]
    total = 0.0
    for i in _inside(trace, lo, hi):
        name, s, e = prog[i][:3]
        if name == "koalja:push":
            total += e - s
        elif name == "koalja:task" and outer[i] and outer[i][-1] == "koalja:push":
            total -= e - s
    return total


def d2h_bytes(trace: dict, lo: float, hi: float) -> int:
    """Bytes the hashes that start in [lo, hi) copied from a device."""
    prog = trace["program"]
    return sum(
        int(prog[i][4].get("d2h_bytes", 0)) for i in _inside(trace, lo, hi)
        if prog[i][0] == "koalja:hash"
    )


def pushes(trace: dict, lo: float, hi: float) -> int:
    prog = trace["program"]
    return sum(prog[i][0] == "koalja:push" for i in _inside(trace, lo, hi))


def per_push(run, value):
    """``value(trace, lo, hi)`` over the window, divided by the window's
    pushes; None where the trace holds no program spans or no push."""
    trace = run.trace
    if trace is None or not trace.get("program") or devtrace.window(trace) is None:
        return None
    lo, hi = devtrace.window(trace)
    n = pushes(trace, lo, hi)
    return value(trace, lo, hi) / n if n else None


# ---------------------------------------------------------------------------
# device idle time by program span
# ---------------------------------------------------------------------------


def program_label(trace: dict, t: float) -> str:
    """The innermost program span covering ``t``, without its prefix, the
    push's own time named ``scheduler``; ``outside`` where none covers it.
    The covering span that started last is the innermost; pushes do not
    overlap, so the search stops at a push that ended before ``t``."""
    prog = trace["program"]
    i = bisect.bisect_right(_starts(trace), t) - 1
    while i >= 0:
        name, s, e = prog[i][:3]
        if s <= t < e:
            label = name[len(PREFIX):]
            return "scheduler" if label == "push" else label
        if name == "koalja:push":
            break
        i -= 1
    return "outside"


def idle_by_program(trace: dict, stretches: list, n: int = 10) -> list:
    """Idle time of the first device inside ``stretches`` ([start, end]
    pairs) by program span, as [label, seconds], largest first. A gap
    that spans several program spans is split at their edges."""
    planes = devtrace.busy(trace)
    if not planes or "program" not in trace:
        return []
    edges = sorted({t for _, s, e, _, _ in trace["program"] for t in (s, e)})
    totals: dict = {}
    for lo, hi in stretches:
        for s, e in planes[0].gaps(lo, hi):
            inner = edges[bisect.bisect_right(edges, s): bisect.bisect_left(edges, e)]
            for a, b in zip([s, *inner], [*inner, e]):
                label = program_label(trace, (a + b) / 2)
                totals[label] = totals.get(label, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[label, ns / 1e9] for label, ns in ranked]


def circuit_stretches(trace: dict, lo: float, hi: float) -> list:
    """The ``circuit`` stretches of ``devtrace.idle_by_host`` in [lo, hi]:
    each benchmark push less its task."""
    pushes_ = [[max(s, lo), min(e, hi)] for s, e in devtrace.spans(trace, "push") if e > lo and s < hi]
    tasks = devtrace.spans(trace, "task")
    out = []
    for s, e in pushes_:
        at = s
        for ts, te in tasks:
            if s <= ts and te <= e:
                out.append([at, ts])
                at = te
        out.append([at, e])
    return [[a, b] for a, b in out if b > a]


def circuit_gaps(trace: dict, lo: float, hi: float) -> list:
    """Device idle time inside the circuit stretches, by program span."""
    return idle_by_program(trace, circuit_stretches(trace, lo, hi))


# ---------------------------------------------------------------------------
# the script
# ---------------------------------------------------------------------------

METRICS = (
    "scheduler_ms_per_push", "hash_ms_per_push", "store_ms_per_push",
    "journal_ms_per_push", "d2h_mb_per_push", "gc_ms_per_push",
)


def report(trace: dict, chip) -> dict:
    """The six metrics, the circuit's idle time by program span, and the
    sums that check them, for a traced window with program spans."""
    import types

    import bench

    run = types.SimpleNamespace(trace=trace)
    lo, hi = devtrace.window(trace)
    n = pushes(trace, lo, hi)
    metrics = {m: bench.load_module(chip / "metrics" / f"{m}.py").read(run) for m in METRICS}
    stages = sum(stage_ns(trace, s, lo, hi) for s in STAGES) + gc_ns(trace, lo, hi, circuit=True)
    return {
        "program": metrics,
        "pushes": n,
        "spans_per_push": len(_inside(trace, lo, hi)) / n if n else None,
        "circuit_in_program_ms": circuit_ns(trace, lo, hi) / n / 1e6 if n else None,
        "stages_and_their_gc_ms": stages / n / 1e6 if n else None,
        "circuit_gaps": circuit_gaps(trace, lo, hi),
    }


def cut(trace: dict, k: int) -> dict:
    """``k`` consecutive benchmark pushes of the window, those with the
    fewest device operations, with every event inside them, on a clock that
    starts at the first: a small recorded trace for the tests."""
    pushes_ = devtrace.spans(trace, "push")
    starts = sorted(o[1] for p in trace["devices"].values() for o in p["ops"])
    count = [bisect.bisect_left(starts, e) - bisect.bisect_left(starts, s) for s, e in pushes_]
    first = min(range(max(1, len(pushes_) - k + 1)), key=lambda i: sum(count[i:i + k]))
    lo, hi = pushes_[first][0], pushes_[min(first + k, len(pushes_)) - 1][1]

    def keep(rows):
        return [[r[0], r[1] - lo, r[2] - lo, *r[3:]] for r in rows if lo <= r[1] and r[2] <= hi]

    return {
        "devices": {
            name: {"ops": keep(p["ops"]), "modules": keep(p["modules"])}
            for name, p in trace["devices"].items()
        },
        "host": [["bench:window", 0, hi - lo]] + keep([h for h in trace["host"] if h[0] != "bench:window"]),
        "program": keep(trace["program"]),
    }


def span_cost_ns(n: int = 200_000) -> float:
    """What one span with two arguments costs with no profiler running."""
    import time

    from repro.core.spans import span

    t0 = time.perf_counter()
    for _ in range(n):
        with span("hash", payloads=2, push=1):
            pass
    return (time.perf_counter() - t0) / n * 1e9


def main(argv=None, root=None) -> int:
    import argparse
    import json

    import run

    root = root or run.bench.CHECKOUT

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keep", help="write a cut of the window's trace here")
    ap.add_argument("--keep-pushes", type=int, default=3)
    ap.add_argument("--spans", help="write the window's host and program spans here")
    args, rest = ap.parse_known_args(argv)
    traces: list = []
    collect_devices = devtrace.collect

    def with_program(trace_dir: str) -> dict:
        trace = collect_devices(trace_dir)
        trace["program"] = collect(trace_dir)
        traces.append(trace)
        return trace

    devtrace.collect = with_program  # devtrace.collect keeps no program spans
    try:
        rc = run.main([*rest, "--trace", "1"], root=root)
    finally:
        devtrace.collect = collect_devices
    if rc or not traces or devtrace.window(traces[0]) is None:
        return rc or 1
    out = report(traces[0], root / run.bench.HERE.relative_to(run.bench.CHECKOUT))
    out["span_cost_ns"] = span_cost_ns()
    print(json.dumps(out), flush=True)
    if args.keep:
        with open(args.keep, "w") as f:
            json.dump(cut(traces[0], args.keep_pushes), f, separators=(",", ":"))
    if args.spans:
        with open(args.spans, "w") as f:
            json.dump({k: traces[0][k] for k in ("host", "program")}, f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
