"""Model FLOP utilization of the hybrid cell's serving steps, in %: the
operations the window's prefill and decode steps need (``work_hybrid.py``,
routed expert rows read from each request's counters) over the device time
of those programs' executions in the trace times the chip's peak bf16
FLOP/s."""

import devtrace
import work_hybrid

PROGRAMS = r"prefill_fn|decode_fn"


def read(run):
    route = getattr(run, "route", None)
    if run.trace is None or not route or devtrace.window(run.trace) is None:
        return None
    lo, hi = devtrace.window(run.trace)
    device_s = devtrace.module_ns(run.trace, PROGRAMS, lo, hi) / 1e9
    if device_s <= 0:
        return None
    flops = 0
    for s, r in zip(run.served, route):
        if r is not None:
            w = work_hybrid.request_work(run.config, s.req.prompt_len, s.req.gen, r)
            flops += w["prefill_flops"] + w["decode_flops"]
    return 100.0 * flops / (device_s * run.peak["bf16_flops_per_s"]) if flops else None
