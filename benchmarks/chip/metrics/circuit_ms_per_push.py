"""Circuit time per push: the push's wall time minus the task function's
own (zero for a memo hit), mean over the pushes of the window (host clock).
What remains is wave formation, hashing, memo lookup, store and journal."""

import numpy as np


def read(run):
    if not run.served:
        return None
    return 1e3 * float(np.mean([(s.end - s.start) - s.task_s for s in run.served]))
