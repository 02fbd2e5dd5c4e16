"""The selective scans' share of their roofline, in %: the least time of
the window's scans (one per Mamba layer and prefill; per scan the larger of
its operations over peak FLOP/s and its bytes over peak bandwidth, from
``work_hybrid.py``) over the device time of the operations in the scan's
named scope (``jnp_mamba_scan`` or ``pallas_mamba_scan``), from the trace
and the run's map of operations to scopes (``scopes.py``)."""

import devtrace
import scopes
import work_hybrid


def read(run):
    route = getattr(run, "route", None)
    if run.trace is None or not route or devtrace.window(run.trace) is None:
        return None
    lo, hi = devtrace.window(run.trace)
    ns = scopes.scope_ns(run.trace, "mamba_scan", lo, hi, [s.req.prompt_len for s in run.served])
    if not ns:
        return None
    layers = work_hybrid.counts(run.config)["mamba"]
    floor = 0.0
    for s, r in zip(run.served, route):
        if r is not None:
            f, b = work_hybrid.request_work(run.config, s.req.prompt_len, s.req.gen, r)["scan_prefill"]
            floor += layers * work_hybrid.floor_s(f, b, run.peak)
    return 100.0 * floor / (ns / 1e9) if floor > 0 else None
