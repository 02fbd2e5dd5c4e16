"""Decode steps' share of their roofline, in %: the least time the chip
could take for the window's decode steps (per step the larger of operations
over peak FLOP/s and bytes over peak bandwidth, from shapes in ``work.py``)
over the device time of the decode program's executions in the trace."""

import devtrace
import work

PROGRAM = r"decode_fn"


def read(run):
    if run.trace is None or devtrace.window(run.trace) is None:
        return None
    lo, hi = devtrace.window(run.trace)
    device_s = devtrace.module_ns(run.trace, PROGRAM, lo, hi) / 1e9
    if device_s <= 0:
        return None
    floor = sum(
        work.decode_floor_s(work.request_work(run.config, s.req.prompt_len, s.req.gen)["decode_steps"], run.peak)
        for s in run.served
        if not s.hit
    )
    return 100.0 * floor / device_s if floor > 0 else None
