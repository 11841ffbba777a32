"""Host spans of the coded round loop, on the profiler's clock.

Every phase of a serving round is timed once, by ``timed``: the interval
is a ``jax.profiler.TraceAnnotation`` (free when no trace is running; in a
trace it sits on the same clock as the device's operations) and its
``perf_counter`` seconds feed the counters in ``serving.metrics``.  The
engine's spans are flat, none opened around another, so a trace's
coverage of an idle gap names one phase; only worker spans that run on
the engine thread (the device pool's share placement and undelayed
dispatches, the simulated clock) sit inside ``coded.submit``.  Spans link
to the round that caused them through metadata (``round``, ``layer``,
``bucket``, ``worker``), which a trace keeps as event stats beside the
bare span name.
"""
from __future__ import annotations

import time

import jax

# engine thread
ADMIT = "coded.admit"            # admit + coalesce at a layer boundary
IDLE = "coded.idle"              # no work: waiting for requests
ENCODE = "coded.encode"          # APCP encode of a round's input
SUBMIT = "coded.submit"          # dispatch of the n coded subtasks
REAP_WAIT = "coded.reap_wait"    # polling the in-flight rounds
GATHER = "coded.gather"          # fastest-delta collect + gather
INVERSE = "coded.inverse"        # host decode inverse + its upload
DECODE = "coded.decode"          # decode + relu + pool
TRANSITION = "coded.transition"  # fused partition-resident transition
COMPLETE = "coded.complete"      # a finished batch back to its requests
# worker threads (the engine or a timer thread on the device pool)
WORKER_PREP = "coded.worker.prep"          # share placement, program pick
WORKER_RUN = "coded.worker.run"            # the worker program
WORKER_STRAGGLE = "coded.worker.straggle"  # an injected straggler delay


class timed:
    """``with timed(name, **meta) as t:`` -- a flat profiler span that
    leaves its elapsed ``perf_counter`` seconds in ``t.s`` on exit."""

    __slots__ = ("name", "s", "_ann", "_t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self.s = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "timed":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)

