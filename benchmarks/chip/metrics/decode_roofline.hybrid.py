"""The hybrid cell's decode steps' share of their roofline, in %: the least
time the chip could take for the window's decode steps (the larger of their
operations over peak FLOP/s and their bytes over peak bandwidth, with only
the held experts each step's token reaches, from ``work_hybrid.py`` and the
requests' counters) over the device time of the decode program's
executions in the trace."""

import devtrace
import work_hybrid

PROGRAM = r"decode_fn"


def read(run):
    route = getattr(run, "route", None)
    if run.trace is None or not route or devtrace.window(run.trace) is None:
        return None
    lo, hi = devtrace.window(run.trace)
    device_s = devtrace.module_ns(run.trace, PROGRAM, lo, hi) / 1e9
    if device_s <= 0:
        return None
    floor = 0.0
    for s, r in zip(run.served, route):
        if r is not None:
            w = work_hybrid.request_work(run.config, s.req.prompt_len, s.req.gen, r)
            floor += work_hybrid.floor_s(w["decode_flops"], w["decode_bytes"], run.peak)
    return 100.0 * floor / device_s if floor > 0 else None
