"""Spans of the circuit's push path, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``: it lands in the same
trace, on the same clock, as the device's operations, so a gap in which the
device sat idle can be put down to what the host was doing in it. With no
profiler running a span costs about a microsecond and records nothing.

Names (each carries the ``koalja:`` prefix) and their arguments:

=====================  ==========================================  ==================================
span                   where                                       arguments
=====================  ==========================================  ==================================
``push``               ``Workspace.push``, the whole push          ``push`` (the workspace's count), ``task``
``task``               ``SmartTask.run_user_fn``, the user code    ``task``, ``push``
``hash``               ``content_hash_batch``                      ``payloads``, ``nbytes``, ``d2h_bytes``
``store.put``          ``ArtifactStore.put_batch``                 ``nbytes``, ``tier``
``store.get``          ``ArtifactStore.get``                       ``nbytes``, ``tier``
``journal.append``     ``Journal.append_batch`` / ``_append_locked``  ``records``
``journal.fsync``      ``Journal._flush_locked``                   none
``gc``                 the interpreter's collector, start to stop  ``generation``, ``collected``
=====================  ==========================================  ==================================

Arguments come from values already at hand (``len``, ``.nbytes``); none
syncs or copies to compute. Arguments known only at a span's end are set
with ``set_metadata`` on the annotation ``span`` returns, under
:func:`enabled`.
"""

from __future__ import annotations

import contextvars
import gc

from jax.profiler import TraceAnnotation

__all__ = ["span", "enabled", "PUSH", "install_gc_hook"]

PREFIX = "koalja:"

# The push a span belongs to: set by ``Workspace.push`` for its duration and
# carried to pooled workers with the context, so spans on another thread
# join their request. -1 outside any push.
PUSH: contextvars.ContextVar = contextvars.ContextVar("koalja_push", default=-1)

enabled = TraceAnnotation.is_enabled  # True while a profiler records


def span(name: str, **args) -> TraceAnnotation:
    """A context manager recording ``koalja:<name>`` with ``args``."""
    return TraceAnnotation(PREFIX + name, **args)


# The collection in progress. The collector does not run twice at once, and
# its start and stop callbacks run on the thread that triggered it.
_open_gc = None


def _on_gc(phase: str, info: dict) -> None:
    global _open_gc
    if phase == "start":
        if enabled():
            _open_gc = span("gc", generation=info["generation"])
            _open_gc.__enter__()
    elif _open_gc is not None:
        _open_gc.set_metadata(collected=info["collected"])
        _open_gc.__exit__(None, None, None)
        _open_gc = None


def install_gc_hook() -> None:
    """Record each garbage collection as a ``koalja:gc`` span. Process-wide
    and idempotent: a second call adds nothing."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
