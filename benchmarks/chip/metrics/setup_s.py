"""Process start to the opening of the measured window: weights, compiles
(or compile-cache loads) and warm-up (host clock)."""


def read(run):
    return run.setup_s
