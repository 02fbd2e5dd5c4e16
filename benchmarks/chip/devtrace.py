"""From a profiler trace to the numbers the per-layer metrics read.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things, as plain lists of ``[name, start_ns, end_ns]``:

  devices  per device plane (``/device:...``), its ``ops`` (the "XLA Ops"
           line: each operation the chip ran) and its ``modules`` (the "XLA
           Modules" line: each execution of a compiled program);
  host     the benchmark's own spans (``bench:*`` trace annotations).

Device and host events of one trace share one clock. The rest are
reductions over those lists, kept apart from reading so that tests can run
them on a small recorded trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

HOST_PREFIX = "bench:"
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"


def short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.48 = (...) ...``
    becomes ``fusion.48``."""
    return name.split(" = ", 1)[0].lstrip("%")


def collect(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return {"devices": {}, "host": []}
    data = ProfileData.from_file(paths[-1])
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OP_LINE: "ops", MODULE_LINE: "modules"}.get(line.name)
                if key:
                    lines[key] = [[short(e.name), e.start_ns, e.end_ns] for e in line.events]
            if lines["ops"] or lines["modules"]:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.end_ns])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------


def union(events) -> list:
    """Merged, sorted [start, end] intervals covered by ``events``."""
    out: list = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Busy:
    """The merged busy intervals of one device, with prefix sums so that the
    busy time inside any stretch is two binary searches."""

    def __init__(self, events):
        merged = union(events)
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0.0]
        for s, e in merged:
            self.before.append(self.before[-1] + (e - s))

    def _upto(self, t: float) -> float:
        """Busy time before ``t``."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(self.ends[i - 1], t) - self.starts[i - 1]

    def covered(self, lo: float, hi: float) -> float:
        return max(0.0, self._upto(hi) - self._upto(lo))

    def gaps(self, lo: float, hi: float) -> list:
        """The [start, end] stretches of [lo, hi] with no operation running."""
        out, at = [], lo
        for i in range(max(0, bisect.bisect_right(self.ends, lo)), len(self.starts)):
            s, e = self.starts[i], self.ends[i]
            if s >= hi:
                break
            if s > at:
                out.append([at, s])
            at = max(at, e)
        if at < hi:
            out.append([at, hi])
        return out


def busy(trace: dict) -> list:
    """One :class:`Busy` per device plane (its ops, else its modules),
    built once per trace."""
    if "_busy" not in trace:
        trace["_busy"] = [Busy(p["ops"] or p["modules"]) for p in trace["devices"].values()]
    return trace["_busy"]


def spans(trace: dict, name: str) -> list:
    return [[s, e] for n, s, e in trace["host"] if n == HOST_PREFIX + name]


def window(trace: dict):
    """(start, end) in ns of the benchmark's measured window."""
    w = spans(trace, "window")
    return (w[0][0], w[-1][1]) if w else None


def busy_ns(trace: dict, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran on the device,
    averaged over the device planes."""
    planes = busy(trace)
    return sum(b.covered(lo, hi) for b in planes) / len(planes) if planes else 0.0


def idle_share_in(trace: dict, within: list):
    """Share of the time inside the ``within`` spans with no device op, or
    None where the trace holds no device or no such span."""
    total = sum(e - s for s, e in within)
    if not trace["devices"] or total <= 0:
        return None
    return 1.0 - sum(busy_ns(trace, s, e) for s, e in within) / total


def module_ns(trace: dict, pattern: str, lo: float, hi: float) -> float:
    """Device time of the executions of compiled programs whose name matches
    ``pattern``, inside [lo, hi], summed over the device planes."""
    rx = re.compile(pattern)
    return sum(
        max(0.0, min(e, hi) - max(s, lo))
        for p in trace["devices"].values()
        for n, s, e in p["modules"]
        if rx.search(n)
    )


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------


def top_ops(trace: dict, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` device operations that took most time in [lo, hi], as
    [name, seconds] summed over their executions (averaged over planes)."""
    totals: dict = {}
    planes = list(trace["devices"].values())
    for p in planes:
        for name, s, e in p["ops"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                totals[name] = totals.get(name, 0.0) + d
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / max(1, len(planes))] for name, ns in ranked]


def host_label(trace: dict, t: float) -> str:
    """What the host was doing at ``t``: the innermost benchmark span that
    covers it, with push time outside the task named ``circuit``, and time
    outside every push ``between_pushes``. Spans nest, so the covering span
    that started last is the innermost."""
    host = trace["host"]
    if "_starts" not in trace:
        trace["_starts"] = [s for _, s, _ in host]
    i = bisect.bisect_right(trace["_starts"], t) - 1
    while i >= 0:
        name, s, e = host[i]
        if name != HOST_PREFIX + "window":
            if s <= t < e:
                label = name[len(HOST_PREFIX):]
                return "circuit" if label == "push" else label
            if name == HOST_PREFIX + "push":
                break  # pushes do not overlap: t fell after this one ended
        i -= 1
    return "between_pushes"


def idle_by_host(trace: dict, lo: float, hi: float, n: int = 10) -> list:
    """Idle time of the first device in [lo, hi] by what the host was doing,
    as [label, seconds], largest first. A gap that spans several host spans
    is split at their edges."""
    planes = busy(trace)
    if not planes:
        return []
    edges = sorted({t for name, s, e in trace["host"] if name != HOST_PREFIX + "window" for t in (s, e)})
    totals: dict = {}
    for s, e in planes[0].gaps(lo, hi):
        inner = edges[bisect.bisect_right(edges, s): bisect.bisect_left(edges, e)]
        for a, b in zip([s, *inner], [*inner, e]):
            label = host_label(trace, (a + b) / 2)
            totals[label] = totals.get(label, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[label, ns / 1e9] for label, ns in ranked]
