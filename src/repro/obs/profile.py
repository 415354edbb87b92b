"""Profiler hooks: phase scopes inside compiled code, trace spans around it
(DESIGN.md Sec. 14).

Two tools with different scopes of validity:

  * :func:`scope` -- ``jax.named_scope`` labels for code being TRACED
    (sampler step phases, retrain branches): the label lands on the HLO ops
    so profiler timelines and compiled-module dumps attribute device time to
    loop phases. Zero runtime cost (purely a trace-time name-stack push),
    which is why the hot paths keep their scopes unconditionally.
  * :func:`annotation` -- ``jax.profiler.TraceAnnotation`` for HOST-side
    phases (per-tick drivers, checkpoint writes): shows up as host events in
    a captured trace.
  * :func:`profile_span` -- bracket a region with
    ``jax.profiler.start_trace/stop_trace`` writing a TensorBoard-loadable
    trace under ``dir`` (what ``launch/train.py --profile-dir`` wraps
    around its first ``--profile-ticks`` ticks).
"""
from __future__ import annotations

import contextlib

import jax


def scope(name: str):
    """Named scope for jitted phase attribution (trace-time only)."""
    return jax.named_scope(name)


def annotation(name: str):
    """Host-side profiler annotation for un-jitted phases."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def profile_span(dir: str, *, create_perfetto_link: bool = False):
    """Capture a profiler trace of the enclosed region into ``dir``.

    Exceptions inside the region still stop the trace. A failure to START
    the profiler (e.g. another trace already active) propagates: a run
    that was asked for a trace does not carry on without one."""
    jax.profiler.start_trace(dir, create_perfetto_link=create_perfetto_link)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
