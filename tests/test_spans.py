"""Spans and counters inside the coded round loop and the worker pools.

Covers: every ``coded.*`` span of the engine and of the workers landing on
the profiler's host planes with bare names and a ``round`` stat, the
worker spans tied to an engine round; the per-round subtask counters under
the simulated clock and under real straggler threads; the device pool's
one share placement a round; ``reset`` clearing them; and ``GET
/v1/stats`` exporting them.
"""
import concurrent.futures
import glob
import json
import os
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import build_cnn_pipeline
from repro.models.cnn import init_cnn
from repro.runtime import (DeviceWorkerPool, StragglerModel,
                           ThreadWorkerPool, spans)
from repro.serving import CodedServer, ServingFrontend

N = 8
ENGINE_SPANS = {spans.ADMIT, spans.IDLE, spans.ENCODE, spans.SUBMIT,
                spans.REAP_WAIT, spans.GATHER, spans.INVERSE, spans.DECODE,
                spans.TRANSITION, spans.COMPLETE}
WORKER_SPANS = {spans.WORKER_PREP, spans.WORKER_RUN, spans.WORKER_STRAGGLE}


def _pipeline(fused=False):
    params = init_cnn("lenet5", jax.random.PRNGKey(0))
    return build_cnn_pipeline("lenet5", params, N, default_kab=(2, 4),
                              bucket_sizes=(1, 2), fuse_transitions=fused)


def _serve(server, count):
    pipe = server.pipeline
    xs = [jnp.full(pipe.input_shape, 0.1 * k, jnp.float32)
          for k in range(count)]
    for h in server.submit_many(xs):
        h.result(timeout=60.0)


def _host_spans(log_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, dict(e.stats)) for e in line.events
                    if e.name.startswith("coded.")]
    return out


def test_round_loop_spans_on_the_profiler_clock(tmp_path):
    """A fused pipeline with one straggler thread runs every span; each
    lands on a host plane under its bare name with its round, and every
    worker span belongs to a round the engine submitted."""
    delays = np.zeros(N)
    delays[N - 1] = 0.01
    server = CodedServer(_pipeline(fused=True), StragglerModel(delays),
                         mode="threads", pool="threads")
    server.warmup()
    with server:
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve(server, 3)
            time.sleep(0.05)  # the engine idles
        finally:
            jax.profiler.stop_trace()
    found = _host_spans(str(tmp_path))
    names = {name for name, _ in found}
    assert ENGINE_SPANS | WORKER_SPANS <= names, names
    assert all(isinstance(stats.get("round"), int) for _, stats in found)
    submitted = {stats["round"] for name, stats in found
                 if name == spans.SUBMIT}
    worker_rounds = {stats["round"] for name, stats in found
                     if name in WORKER_SPANS}
    assert worker_rounds and worker_rounds <= submitted
    assert all({"layer", "bucket"} <= set(stats) for name, stats in found
               if name == spans.SUBMIT)


def test_subtask_counters_simulated_clock():
    """Without stragglers every live subtask starts and delta of them are
    decoded from, each round."""
    pipe = _pipeline()
    server = CodedServer(pipe, StragglerModel.none(N), mode="simulated")
    with server:
        _serve(server, 4)
    o = server.overlap_stats()
    delta = pipe.specs[0].plan.delta
    assert o.rounds > 0
    assert o.subtasks_used == delta * o.rounds
    assert o.subtasks_started == N * o.rounds
    assert o.subtasks_cancelled == 0
    assert o.prep_s > 0
    assert o.longest_phase in ENGINE_SPANS and o.longest_phase_s > 0


def test_subtask_counters_with_straggler_threads():
    """Two stragglers: delta subtasks a round are decoded from, the
    workers' share preparation is counted, and the delta-th finish comes
    no later than the master saw the round ready."""
    straggler = StragglerModel.fixed(N, 2, 0.02, seed=3)
    pipe = _pipeline()
    server = CodedServer(pipe, straggler, mode="threads", pool="threads")
    with server:
        _serve(server, 6)
    o = server.overlap_stats()
    assert o.rounds > 0
    assert o.subtasks_used == pipe.specs[0].plan.delta * o.rounds
    assert o.prep_s > 0
    assert 0 < o.delta_ready_s <= o.worker_s
    assert o.share_placements == 0  # the workers read the shares in place


class _SlowPrograms:
    """Worker programs whose picking takes ``secs`` and is logged (the
    thread pool's preparation: its program selects the share itself)."""

    def __init__(self, secs):
        self.secs = secs
        self.log = []  # list.append: one atomic step per worker thread

    def __call__(self, i):
        time.sleep(self.secs)
        self.log.append(i)
        return lambda xe, ke, index: xe[index] + ke[index]


@pytest.mark.parametrize("kind", ["threads", "device"])
def test_every_started_subtask_is_counted(kind):
    """Rounds back to back with two workers 50 ms late, reaped at the
    fastest delta.  The thread pool cancels the late workers' queued
    subtasks, the device pool fires every deferred dispatch; either way
    each subtask that started picked its program exactly once, and its
    preparation seconds reach the pool's tally even when it finished after
    its round was collected.  The device pool's tally also holds the
    round's one grouped share placement."""
    rounds, delta, prep_each_s = 6, 2, 0.005
    straggler = StragglerModel.fixed(N, 2, 0.05, seed=3)
    prepared = fn = _SlowPrograms(prep_each_s)
    shares, ke = jnp.zeros((N, 4)), jnp.zeros((N, 4))
    if kind == "threads":
        pool = ThreadWorkerPool(N, straggler, mode="threads")
    else:
        pool = DeviceWorkerPool(N, straggler)
        ke = pool.resident_filters("layer", ke)
    prep_s, started, cancelled, placements, futures = 0.0, 0, 0, 0, []
    for _ in range(rounds):
        pending = pool.submit(fn, shares, ke)
        results, _, _ = pool.collect(pending, delta)
        assert len(results) >= delta
        prep_s += pool.prep.take()
        started += pending.started
        cancelled += pending.cancelled
        placements += pending.placements
        futures += pending.futures.values()
    concurrent.futures.wait(futures)
    time.sleep(0.2)  # the device pool's last deferred dispatches
    prep_s += pool.prep.take()
    pool.shutdown()
    if kind == "threads":
        assert cancelled > 0
        assert placements == 0
    else:
        assert (cancelled, started) == (0, N * rounds)
        assert placements == rounds
    assert len(prepared.log) == started
    assert prep_s >= started * prep_each_s


def test_reset_zeroes_round_counters():
    server = CodedServer(_pipeline(), StragglerModel.none(N),
                         mode="simulated")
    with server:
        _serve(server, 2)
    assert server.overlap_stats().subtasks_started > 0
    server.metrics.reset()
    o = server.overlap_stats()
    assert (o.rounds, o.subtasks_started, o.subtasks_used,
            o.subtasks_cancelled, o.share_placements) == (0, 0, 0, 0, 0)
    assert (o.prep_s, o.delta_ready_s, o.longest_phase_s) == (0.0, 0.0, 0.0)
    assert o.longest_phase == ""


def test_stats_endpoint_carries_round_counters():
    server = CodedServer(_pipeline(), StragglerModel.none(N),
                         mode="simulated")
    with ServingFrontend(server, port=0) as frontend:
        _serve(server, 2)
        with urllib.request.urlopen(f"{frontend.url}/v1/stats",
                                    timeout=30.0) as resp:
            overlap = json.loads(resp.read())["aggregate"]["overlap"]
        o = server.overlap_stats()
    # the idle engine keeps timing spans; rounds have all been counted
    for field in ("subtasks_started", "subtasks_used", "subtasks_cancelled",
                  "prep_s", "delta_ready_s", "share_placements"):
        assert overlap[field] == getattr(o, field), field
    assert overlap["subtasks_started"] > 0
    assert overlap["longest_phase"] in ENGINE_SPANS
    assert overlap["longest_phase_s"] > 0


def test_device_pool_places_shares_once_a_round():
    """Serving on the device pool counts one share placement per round;
    ``GET /v1/stats`` exports the count and ``reset`` clears it."""
    server = CodedServer(_pipeline(), StragglerModel.none(N),
                         mode="threads", pool="device")
    with ServingFrontend(server, port=0) as frontend:
        _serve(server, 3)
        with urllib.request.urlopen(f"{frontend.url}/v1/stats",
                                    timeout=30.0) as resp:
            overlap = json.loads(resp.read())["aggregate"]["overlap"]
        o = server.overlap_stats()
        assert o.rounds > 0
        assert o.share_placements == o.rounds
        assert overlap["share_placements"] == o.share_placements
        server.metrics.reset()
        assert server.overlap_stats().share_placements == 0
