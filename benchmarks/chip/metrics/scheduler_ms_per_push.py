"""Scheduler time per push, ms: the self time of the program's
``koalja:push`` spans (wave formation, snapshot key, memo lookup and insert,
AV minting, registry, emit), mean over the window's pushes (trace)."""

import progtrace


def read(run):
    ns = progtrace.per_push(run, lambda t, lo, hi: progtrace.stage_ns(t, "scheduler", lo, hi))
    return None if ns is None else ns / 1e6
