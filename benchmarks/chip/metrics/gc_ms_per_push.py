"""Garbage-collection time per push, ms: the program's ``koalja:gc`` spans
in the window, wherever they fall, over the window's pushes (trace)."""

import progtrace


def read(run):
    ns = progtrace.per_push(run, progtrace.gc_ns)
    return None if ns is None else ns / 1e6
