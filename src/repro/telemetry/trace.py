"""Trace and profiler hooks.

Three layers, all safe to leave in hot code:

* :func:`scope` — ``jax.named_scope``: names the ops a traced region
  emits, so HLO dumps and profiler timelines show ``panel.mix/float32``
  instead of ``dot_general.127``. Zero runtime cost (trace-time only).
* :func:`annotate` — ``jax.profiler.TraceAnnotation``: a HOST-side span
  on the profiler timeline (scheduler work: admit, step, checkpoint).
* :func:`profile_trace` — capture a jax profiler trace into a logdir
  (``--profile`` in the launchers). A profiler that cannot start or stop
  raises: a run asked to trace never finishes without its trace.
"""
from __future__ import annotations

import jax


def scope(name: str):
    """Trace-time op-name scope (see module docstring)."""
    return jax.named_scope(name)


def annotate(name: str, **kwargs):
    """Host-side profiler span."""
    return jax.profiler.TraceAnnotation(name, **kwargs)


class profile_trace:
    """Context manager capturing a jax profiler trace into ``logdir``.

    ``enabled=False`` makes it a no-op (so call sites can pass the CLI
    flag straight through). ``bool(ctx)`` inside the block reports
    whether a trace is being captured."""

    def __init__(self, logdir: str, enabled: bool = True):
        self.logdir = logdir
        self.enabled = enabled
        self.active = False

    def __bool__(self):
        return self.active

    def start(self):
        if not self.enabled or self.active:
            return self
        jax.profiler.start_trace(self.logdir)
        self.active = True
        return self

    def stop(self):
        if not self.active:
            return
        self.active = False
        jax.profiler.stop_trace()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
