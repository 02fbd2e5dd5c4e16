"""Store time per push, ms: the self time of the program's
``koalja:store.put`` and ``koalja:store.get`` spans inside its pushes, mean
over the window's pushes (trace)."""

import progtrace


def read(run):
    ns = progtrace.per_push(run, lambda t, lo, hi: progtrace.stage_ns(t, "store", lo, hi))
    return None if ns is None else ns / 1e6
