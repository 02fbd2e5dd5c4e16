"""Bytes copied from the device to the host to be hashed, per push, in
10**6 B: the ``d2h_bytes`` of the program's ``koalja:hash`` spans in the
window over the window's pushes (trace)."""

import progtrace


def read(run):
    b = progtrace.per_push(run, progtrace.d2h_bytes)
    return None if b is None else b / 1e6
