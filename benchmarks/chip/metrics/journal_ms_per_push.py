"""Journal time per push, ms: the program's ``koalja:journal.append`` spans
inside its pushes, their ``koalja:journal.fsync`` included and any GC inside
left out, mean over the window's pushes (trace)."""

import progtrace


def read(run):
    ns = progtrace.per_push(run, lambda t, lo, hi: progtrace.stage_ns(t, "journal", lo, hi))
    return None if ns is None else ns / 1e6
