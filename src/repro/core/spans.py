"""The program's profiler spans: every name in one place, and the one
helper that also counts a span's seconds.

A span is a ``jax.profiler.TraceAnnotation``.  With no trace being taken
it costs about a microsecond; under ``jax.profiler.start_trace`` it lands
on the profiler's host timeline, on the same clock as the device's
executions, with its keyword arguments as event stats.  Arguments are
passed as raw values so that nothing is formatted unless a trace is on.

Serving front end (``launch.spectral_serve.SpectralServer``):

  ``serve.submit`` (``rid``) around ``submit``;
  ``serve.tick`` (``rid`` of the batch's first request, ``n``,
  ``bucket``, ``rung``: the backend that served it) around ``tick``,
  holding in order ``serve.take`` (ladder update and batch pop),
  ``serve.upload`` (``staged_hit``), ``serve.plan`` (plan fetch),
  ``serve.forward`` (dispatch of the forward walk, not awaited),
  ``serve.stage_next`` (the next batch's upload), ``serve.readback``
  (wait for the logits and copy them to the host) and ``serve.finish``.

Forward walk (``models.cnn.forward_spectral``): one ``forward.node`` per
graph node (``node``, ``kind``; conv nodes also ``hadamard``, ``flow``,
``input_mode``, ``residual``, ``backend`` and Alg 1's ``predicted_us``)
and ``forward.fc_head``.  Every executable a node dispatches is enqueued
inside its span, so a trace ties each device execution (by its run id)
to its node, even where nodes share one compiled executable.

Plan build (``core.plan.build_network_plan``): ``plan.build`` (``batch``)
holding per layer ``plan.<phase>`` (``layer``) for each of
``PLAN_PHASES``.  These are also counted: ``NetworkPlan.phase_s`` holds
each phase's seconds and ``PlanCache.stats()["phase_s"]`` their sum over
builds.
"""

from __future__ import annotations

import contextlib
import time

import jax

span = jax.profiler.TraceAnnotation

SERVE_SUBMIT = "serve.submit"
SERVE_TICK = "serve.tick"
SERVE_TAKE = "serve.take"
SERVE_UPLOAD = "serve.upload"
SERVE_PLAN = "serve.plan"
SERVE_FORWARD = "serve.forward"
SERVE_STAGE_NEXT = "serve.stage_next"
SERVE_READBACK = "serve.readback"
SERVE_FINISH = "serve.finish"

FORWARD_NODE = "forward.node"
FORWARD_FC_HEAD = "forward.fc_head"

PLAN_BUILD = "plan.build"
PLAN_PHASES = ("prune", "operators", "schedule_stats", "autotune",
               "tables", "validate")


@contextlib.contextmanager
def counted(phase_s: dict, phase: str, **args):
    """Span ``plan.<phase>`` whose wall seconds are added to
    ``phase_s[phase]``."""
    t0 = time.perf_counter()
    try:
        with span("plan." + phase, **args):
            yield
    finally:
        phase_s[phase] = phase_s.get(phase, 0.0) + (time.perf_counter()
                                                    - t0)
