"""The MoE layers' share of their roofline, in %: the least time of the
window's routed expert work (per layer and prefill, and over the decode
steps, the larger of its operations over peak FLOP/s and its bytes over
peak bandwidth: the router, the routed rows on held experts and the held
experts they reach, from ``work_hybrid.py`` and the requests' counters) over
the device time of the operations in the ``moe_layer`` named scope (router,
dispatch, grouped matmul, combine), from the trace and the run's map of
operations to scopes (``scopes.py``)."""

import devtrace
import scopes
import work_hybrid


def read(run):
    route = getattr(run, "route", None)
    if run.trace is None or not route or devtrace.window(run.trace) is None:
        return None
    lo, hi = devtrace.window(run.trace)
    ns = scopes.scope_ns(run.trace, "moe", lo, hi, [s.req.prompt_len for s in run.served])
    if not ns:
        return None
    floor = 0.0
    for s, r in zip(run.served, route):
        if r is not None:
            w = work_hybrid.request_work(run.config, s.req.prompt_len, s.req.gen, r)
            floor += sum(work_hybrid.floor_s(f, b, run.peak) for f, b in w["moe_prefill"])
            floor += work_hybrid.floor_s(*w["moe_decode"], run.peak)
    return 100.0 * floor / (ns / 1e9) if floor > 0 else None
