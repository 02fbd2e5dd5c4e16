"""Model FLOP utilization of the serving steps, in %: the operations the
window's prefill and decode steps need (from shapes in ``work.py``) over
the device time of those programs' executions in the trace times the
chip's peak bf16 FLOP/s."""

import devtrace
import work

PROGRAMS = r"prefill_fn|decode_fn"


def read(run):
    if run.trace is None or devtrace.window(run.trace) is None:
        return None
    lo, hi = devtrace.window(run.trace)
    device_s = devtrace.module_ns(run.trace, PROGRAMS, lo, hi) / 1e9
    if device_s <= 0:
        return None
    flops = 0
    for s in run.served:
        if not s.hit:
            w = work.request_work(run.config, s.req.prompt_len, s.req.gen)
            flops += w["prefill_flops"] + w["decode_flops"]
    return 100.0 * flops / (device_s * run.peak["bf16_flops_per_s"]) if flops else None
