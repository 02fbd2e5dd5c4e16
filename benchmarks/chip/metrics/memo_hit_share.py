"""Share of the window's pushes that the memo answered without executing
the task, in %, from the workspace's own counter (``ws.stats()``)."""


def read(run):
    hits = run.stats["sustainability"]["cache_hits"]
    return 100.0 * hits / len(run.served) if run.served else None
