"""Output tokens of the requests whose result returned inside the window,
over the window's length (host clock)."""


def read(run):
    done = sum(s.req.gen for s in run.served if s.end <= run.closes)
    return done / run.seconds
