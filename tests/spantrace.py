"""Record the program's ``koalja:*`` spans around a call, for tests.

Runs the call under ``jax.profiler`` and reads the spans back with the
benchmark's own reader (``benchmarks/chip/progtrace.py``), with their
arguments and the host thread each ran on.
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax

CHIP = str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import progtrace  # noqa: E402


@dataclasses.dataclass
class Span:
    name: str
    start: int  # ns, the profiler's clock
    end: int
    thread: str  # one per host thread
    args: dict

    def within(self, other: "Span") -> bool:
        return (
            self.thread == other.thread
            and other.start <= self.start
            and self.end <= other.end
            and self is not other
        )


def record(fn, trace_dir) -> tuple:
    """(``fn()``, the ``koalja:*`` spans recorded while it ran, by start)."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    return result, [Span(*row) for row in progtrace.collect(str(trace_dir))]
