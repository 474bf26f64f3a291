"""Program spans on the profiler's clock.

One switch, one span constructor, one exporter:

- :func:`span` opens a ``jax.profiler.TraceAnnotation`` named
  ``ckv/<name>`` (arguments become the event's metadata) while tracing is
  enabled, and otherwise returns one shared null context, so a span site
  with tracing off costs a global read and builds no annotation.
- :func:`enable` flips tracing for the process.
- :func:`trace_to` enables spans and records a profiler trace of a block
  (``python -m repro.launch.serve --trace-dir DIR``), without the Python
  tracer (:func:`profile_options`).

Spans land in the profiler's host planes, on the same clock as the device
planes, so a trace reduction can put each idle gap of the device down to
the innermost span of the thread that drove it.  Counters are not kept
here: they are plain attributes of the objects that do the work
(``RealCompute.d2h_syncs``/``h2d_bytes``; ``ReprefillTrace.io_stall_s``,
``d2h_syncs`` and ``h2d_bytes`` per request).

Span names, by layer:

  scheduler   sched.run, sched.admit, sched.idle, sched.io_wait,
              sched.decode_batch, sched.chunk_batch
  engine      eng.select, eng.submit, eng.gather, eng.cache (arg rid)
  storage     store.read, on the I/O thread (args rid, layer, bytes)
  model step  step.<RealCompute method>, and inside them step.d2h around
              each blocking device-to-host read, step.h2d around each
              upload and step.decode_token around each one-program decode
              step

A span never stays open across a ``yield`` of a step-plan generator (other
plans run in between), and none is opened per op, per wait or per driver
pass.
"""
from __future__ import annotations

import contextlib

import jax

PREFIX = "ckv/"

_NULL = contextlib.nullcontext()
_enabled = False


def enable(flag: bool = True) -> None:
    """Turn the program's spans on or off for the whole process."""
    global _enabled
    _enabled = bool(flag)


def span(name: str, **args):
    """A ``ckv/<name>`` profiler annotation while tracing is enabled, else a
    shared null context."""
    if not _enabled:
        return _NULL
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def profile_options():
    """The profiler's options for a trace of the serving loop: host
    annotations and device operations, without the Python tracer, which
    records every Python call and slows the host it is measuring (on one
    TPU v5e, qwen2.5-7b at 8 layers: re-prefill median TTFT 1.66-1.74x its
    untraced value with the Python tracer, 1.06-1.09x without it)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def trace_to(trace_dir: str):
    """Record a profiler trace of the block into `trace_dir`, with the
    program's spans enabled; restores the switch afterwards."""
    was = _enabled
    enable(True)
    jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        enable(was)
