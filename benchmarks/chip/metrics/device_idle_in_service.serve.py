"""Share of the time inside push spans in which no operation ran on the
device, in %, from the profiler trace."""

import devtrace


def read(run):
    if run.trace is None:
        return None
    share = devtrace.idle_share_in(run.trace, devtrace.spans(run.trace, "push"))
    return None if share is None else 100.0 * share
