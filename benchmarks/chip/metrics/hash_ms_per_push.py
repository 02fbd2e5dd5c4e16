"""Hashing time per push, ms: the self time of the program's ``koalja:hash``
spans inside its pushes, the device→host copy of what they hash included,
mean over the window's pushes (trace)."""

import progtrace


def read(run):
    ns = progtrace.per_push(run, lambda t, lo, hi: progtrace.stage_ns(t, "hash", lo, hi))
    return None if ns is None else ns / 1e6
