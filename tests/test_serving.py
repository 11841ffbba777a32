"""The coded serving engine: CodedServer + scheduler + metrics + frontend.

Covers: served results match the pipeline's own output; bucketed batch
assembly keeps the jit program count bounded by the *bucket* count while
request batch sizes vary; continuous admission at layer boundaries;
``run_prepared`` equivalence with ``run``; the cluster's ``submit``/
``collect`` split (persistent per-worker pool, worker_times snapshot);
straggler resilience end-to-end through the server; metrics math; and the
multi-model engine — shared-pool isolation, namespaced filter caches,
fair-share scheduling, equal-depth coalescing, and the HTTP front-end
round trip.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CodedPipeline, FcdccPlan
from repro.core.pipeline import plan_layers
from repro.models.cnn import ConvL
from repro.runtime import ClusterDegraded, FcdccCluster, StragglerModel
from repro.serving import (
    CodedServer,
    MetricsCollector,
    RequestRecord,
    ServingFrontend,
    percentile,
)

RNG = np.random.default_rng(0)

STACK = [
    ConvL("s1", 2, 8, 3, stride=1, padding=1, pool=2),
    ConvL("s2", 8, 8, 3, padding=1),
]

# a second model: SAME layer names as STACK, different channels — the
# shared-cluster namespacing must keep the two models' filters apart
STACK_B = [
    ConvL("s1", 3, 8, 3, stride=1, padding=1, pool=2),
    ConvL("s2", 8, 4, 3, padding=1),
]


def _params(layers, seed=0):
    rng = np.random.default_rng(seed)
    return {
        l.name: jnp.asarray(
            rng.standard_normal((l.out_ch, l.in_ch, l.kernel, l.kernel))
            * (l.in_ch * l.kernel**2) ** -0.5,
            jnp.float32,
        )
        for l in layers
    }


def _pipeline(bucket_sizes=(1, 2, 4), n=6, hw=12):
    params = _params(STACK)
    specs = plan_layers(STACK, hw, n, default_kab=(2, 4))
    return CodedPipeline(specs, params, bucket_sizes=bucket_sizes), params


def _images(count, hw=12):
    return [jnp.asarray(RNG.standard_normal((2, hw, hw)), jnp.float32)
            for _ in range(count)]


# -- bucketing ------------------------------------------------------------
def test_bucketize_and_pad():
    pipe, _ = _pipeline(bucket_sizes=(1, 2, 4))
    assert pipe.bucket_sizes == (1, 2, 4)
    assert pipe.max_batch == 4
    assert [pipe.bucketize(b) for b in (1, 2, 3, 4)] == [1, 2, 4, 4]
    with pytest.raises(ValueError, match="exceeds"):
        pipe.bucketize(5)
    x = jnp.ones((3, 2, 12, 12))
    padded, real = pipe.pad_to_bucket(x)
    assert padded.shape[0] == 4 and real == 3
    np.testing.assert_array_equal(np.asarray(padded[3]), 0.0)
    # exact bucket size: no copy, no padding
    x2 = jnp.ones((2, 2, 12, 12))
    padded2, real2 = pipe.pad_to_bucket(x2)
    assert padded2 is x2 and real2 == 2


def test_bounded_jit_programs_bucket_count_not_batch_size_count():
    """The acceptance-criteria contract: after serving many distinct
    request-batch sizes, the number of jitted program traces is bounded by
    (layer geometries) x (buckets), NOT by the number of batch sizes."""
    pipe, _ = _pipeline(bucket_sizes=(1, 2, 4))
    n_geos = len({(s.program_key, s.geo) for s in pipe.specs})
    seen_sizes = set()
    for b in (1, 2, 3, 4, 3, 2, 1):  # 4 distinct sizes, only 3 buckets
        x = jnp.asarray(RNG.standard_normal((b, 2, 12, 12)), jnp.float32)
        padded, real = pipe.pad_to_bucket(x)
        pipe.run(padded)
        seen_sizes.add(b)
    assert len(seen_sizes) > len(pipe.bucket_sizes)
    assert pipe.worker_program_traces <= n_geos * len(pipe.bucket_sizes)


# -- run_prepared ---------------------------------------------------------
def test_run_prepared_matches_run():
    pipe, _ = _pipeline()
    x = jnp.asarray(RNG.standard_normal((2, 2, 12, 12)), jnp.float32)
    ref = np.asarray(pipe.run(x))
    # shared availability list, any order / superset of delta
    y1 = np.asarray(pipe.run_prepared(x, worker_ids=[5, 2, 4, 0]))
    np.testing.assert_allclose(y1, ref, rtol=1e-4, atol=1e-4)
    # explicit per-layer survivor subsets
    ids = [(1, 3), (5, 0)]
    y2 = np.asarray(pipe.run_prepared(x, pipe.prepare(ids)))
    np.testing.assert_allclose(y2, ref, rtol=1e-4, atol=1e-4)
    # one prepare plan reused across batches (the serving fast path)
    plan = pipe.prepare()
    for _ in range(2):
        np.testing.assert_allclose(
            np.asarray(pipe.run_prepared(x, plan)), ref, rtol=1e-4, atol=1e-4
        )
    with pytest.raises(ValueError, match="covers"):
        pipe.run_prepared(x, plan[:1])


# -- cluster submit/collect ----------------------------------------------
def test_submit_collect_split_and_persistent_pool():
    pipe, _ = _pipeline()
    cluster = FcdccCluster(pipe.specs[0].plan, StragglerModel.none(6),
                           mode="threads")
    cluster.load_pipeline(pipe)
    x = jnp.asarray(RNG.standard_normal((1, 2, 12, 12)), jnp.float32)
    y0, _ = cluster.run_pipeline(x)
    pools = cluster._pools
    assert pools is not None and len(pools) == 6
    y1, _ = cluster.run_pipeline(x)
    assert cluster._pools is pools  # same executors, not per-call ones
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)
    cluster.shutdown()
    assert cluster._pools is None
    y2, _ = cluster.run_pipeline(x)  # pools re-created lazily
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y0), atol=1e-4)
    cluster.shutdown()


def test_collect_snapshots_worker_times():
    """A straggler finishing after collect() must not mutate the returned
    timing list (the old _collect leaked its live list).  The discarded
    straggler's slot is nan — NOT 0.0, which would be indistinguishable
    from the fastest node."""
    delays = np.zeros(6)
    delays[0] = 0.3
    cluster = FcdccCluster(FcdccPlan(n=6, k_a=2, k_b=4),
                           StragglerModel(delays), mode="threads")
    pipe, _ = _pipeline()
    cluster.load_pipeline(pipe)
    x = jnp.asarray(RNG.standard_normal((1, 2, 12, 12)), jnp.float32)
    _, timing = cluster.run_pipeline_layer(0, x)
    assert np.isnan(timing.worker_compute_s[0])  # unfinished at collect
    time.sleep(0.5)  # straggler thread writes its time into the live list
    assert np.isnan(timing.worker_compute_s[0])  # snapshot unchanged
    assert 0 not in timing.used_workers
    cluster.shutdown()


# -- the server -----------------------------------------------------------
def test_server_serves_correct_results():
    pipe, _ = _pipeline()
    ref_pipe, _ = _pipeline()
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated")
    xs = _images(5)
    with server:
        handles = server.submit_many(xs)
        outs = [h.result(timeout=60.0) for h in handles]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_pipe.run(x)), rtol=1e-4, atol=1e-4
        )
    stats = server.stats()
    assert stats.completed == 5
    assert stats.e2e_p50_s > 0 and stats.images_per_s > 0
    assert stats.e2e_p99_s >= stats.e2e_p95_s >= stats.e2e_p50_s


def test_server_bounded_programs_after_warmup():
    pipe, _ = _pipeline(bucket_sizes=(1, 2, 4))
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated")
    server.warmup()
    traces = pipe.worker_program_traces
    with server:
        for burst in (1, 3, 2, 4, 1):
            handles = server.submit_many(_images(burst))
            for h in handles:
                h.result(timeout=60.0)
    # every request-batch size mapped onto a warmed bucket: zero new traces
    assert pipe.worker_program_traces == traces


def test_server_casts_request_dtype():
    """A uint8/float16 request is cast to the pipeline dtype at submit —
    a stray client dtype must not re-trace every (layer, bucket) program."""
    pipe, _ = _pipeline(bucket_sizes=(1, 2))
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated")
    server.warmup()
    traces = pipe.worker_program_traces
    with server:
        y8 = server.submit(np.zeros((2, 12, 12), np.uint8)).result(timeout=60.0)
        y16 = server.submit(
            np.ones((2, 12, 12), np.float16)).result(timeout=60.0)
    assert y8.shape == y16.shape
    assert pipe.worker_program_traces == traces


def test_server_under_stragglers_and_dead_worker():
    pipe, _ = _pipeline()
    ref_pipe, _ = _pipeline()
    delays = np.zeros(6)
    delays[1] = 5.0
    delays[4] = np.inf
    server = CodedServer(pipe, StragglerModel(delays), mode="simulated")
    xs = _images(3)
    with server:
        outs = [h.result(timeout=60.0) for h in server.submit_many(xs)]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_pipe.run(x)), rtol=1e-4, atol=1e-4
        )


def test_server_threads_mode_returns_before_straggler():
    pipe, _ = _pipeline()
    delays = np.zeros(6)
    delays[2] = 1.0
    server = CodedServer(pipe, StragglerModel(delays), mode="threads")
    server.warmup()
    t0 = time.perf_counter()
    with server:
        outs = [h.result(timeout=60.0) for h in server.submit_many(_images(2))]
    assert len(outs) == 2
    # fastest-delta collection: both layers finish well before the 1s sleep
    assert time.perf_counter() - t0 < 1.0


def test_server_direct_execution_matches_cluster():
    pipe, _ = _pipeline()
    ref_pipe, _ = _pipeline()
    delays = np.zeros(6)
    delays[0] = 2.0
    delays[3] = np.inf
    server = CodedServer(pipe, StragglerModel(delays), execution="direct")
    xs = _images(4)
    with server:
        outs = [h.result(timeout=60.0) for h in server.submit_many(xs)]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_pipe.run(x)), rtol=1e-4, atol=1e-4
        )


def test_server_late_arrivals_join_new_batch():
    """Requests arriving while a batch is mid-stack are admitted as a new
    batch at the next layer boundary, not appended to the running one."""
    pipe, _ = _pipeline()
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated",
                         max_inflight=2)
    with server:
        first = server.submit_many(_images(2))
        time.sleep(0.01)  # let the first batch start
        second = server.submit_many(_images(2))
        for h in (*first, *second):
            h.result(timeout=60.0)
    recs = {r.request_id: r for r in server.metrics.records()}
    assert len(recs) == 4
    # the late pair rode a different batch start than the early pair
    starts = {round(recs[h.request_id].start_t, 6) for h in second}
    early_starts = {round(recs[h.request_id].start_t, 6) for h in first}
    assert starts.isdisjoint(early_starts)


def test_server_degraded_cluster_fails_requests_not_engine():
    pipe, _ = _pipeline()
    delays = np.full(6, np.inf)
    delays[0] = 0.0  # one survivor < delta=2
    server = CodedServer(pipe, StragglerModel(delays), mode="simulated")
    with server:
        h = server.submit(_images(1)[0])
        with pytest.raises(ClusterDegraded):
            h.result(timeout=60.0)
        # the engine survived the failed batch and still rejects bad shapes
        with pytest.raises(ValueError, match="request shape"):
            server.submit(jnp.zeros((3, 5, 5)))


@pytest.mark.parametrize("fault", ["device", "injected"])
def test_server_surfaces_worker_faults_tolerates_dead_workers(fault):
    """Under the thread pool, a worker program that raises a
    ``JaxRuntimeError`` (a device fault, a kernel the compiler refuses)
    fails the request with that error instead of passing for a dead
    worker; an injected dead worker (``delay = inf``) is still decoded
    around."""
    pipe, _ = _pipeline()
    ref_pipe, _ = _pipeline()
    delays = np.zeros(6)
    armed = threading.Event()
    if fault == "injected":
        delays[3] = np.inf
    else:
        # the worker program both layers share (one program key): a device
        # run that fails once armed, as an XLA runtime error would
        compiled = pipe.worker_program(0, over_workers=False)

        def program(xe, ke, i):
            if armed.is_set():
                raise jax.errors.JaxRuntimeError(
                    "INTERNAL: injected device fault")
            return compiled(xe, ke, i)

        pipe._cluster_programs[pipe.specs[0].program_key] = program
    server = CodedServer(pipe, StragglerModel(delays), mode="threads",
                         pool="threads")
    server.warmup()
    armed.set()
    x = _images(1)[0]
    with server:
        h = server.submit(x)
        if fault == "device":
            with pytest.raises(jax.errors.JaxRuntimeError,
                               match="injected device fault"):
                h.result(timeout=60.0)
        else:
            np.testing.assert_allclose(
                np.asarray(h.result(timeout=60.0)),
                np.asarray(ref_pipe.run(x)), rtol=1e-4, atol=1e-4)


def test_server_shutdown_without_drain_cancels():
    pipe, _ = _pipeline()
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated")
    server.start()
    handles = server.submit_many(_images(2))
    server.shutdown(drain=False)
    for h in handles:
        if not h.done():
            continue  # may have completed before the stop landed
        try:
            h.result(timeout=1.0)
        except RuntimeError:
            pass
    with pytest.raises(RuntimeError, match="not running"):
        server.submit(_images(1)[0])


def test_server_shutdown_timeout_keeps_thread_and_cancels():
    """A join timeout must leave ``_thread`` set (so a retry joins again
    instead of silently skipping) and fail outstanding requests fast."""
    pipe, _ = _pipeline()
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated")
    gate = threading.Event()
    orig = server.cluster.dispatch_pipeline_layer

    def wedged_layer(idx, x, model=None):
        gate.wait(30.0)  # engine blocks here until the test releases it
        return orig(idx, x, model)

    server.cluster.dispatch_pipeline_layer = wedged_layer
    server.start()
    h = server.submit(_images(1)[0])
    time.sleep(0.05)  # let the engine pick up the batch and block
    with pytest.raises(TimeoutError):
        server.shutdown(timeout=0.2)
    assert server._thread is not None  # a retry will re-join, not skip
    with pytest.raises(TimeoutError):  # request cancelled, caller not hung
        h.result(timeout=5.0)
    # the gate is closed: no new request may enqueue onto the wedged engine
    with pytest.raises(RuntimeError, match="not running"):
        server.submit(_images(1)[0])
    # the cancelled request must not be counted as served
    assert server.stats().completed == 0
    gate.set()  # un-wedge; the retry drains and joins cleanly
    server.shutdown(timeout=30.0)
    assert server._thread is None


def test_engine_admits_up_to_capacity_per_boundary():
    """With free inflight slots and a deep queue, the engine fills ALL
    slots at one layer boundary — the seed admitted one batch per
    iteration, filling capacity one layer-round late."""
    pipe, _ = _pipeline(bucket_sizes=(1,))
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated",
                         max_inflight=2)
    inflight_at_advance = []
    orig = server.cluster.dispatch_pipeline_layer

    def spy(idx, x, model=None):
        inflight_at_advance.append(len(server.scheduler["default"].inflight))
        return orig(idx, x, model)

    server.cluster.dispatch_pipeline_layer = spy
    # queue two single-image batches BEFORE the engine starts: the first
    # boundary sees both waiting with both slots free
    handles = [server.scheduler["default"].queue.submit(x)
               for x in _images(2)]
    with server:
        for h in handles:
            h.result(timeout=60.0)
    assert inflight_at_advance[0] == 2  # both admitted before any advance


def test_request_finish_first_writer_wins():
    """A shutdown-timeout cancel_all races the still-running engine; a
    result delivered first must survive the late cancellation (and a
    cancellation delivered first must survive a late result)."""
    from repro.serving.scheduler import Scheduler

    sched = Scheduler(lambda x: (x, x.shape[0]), max_batch=1)
    h1 = sched.submit(jnp.zeros((2, 12, 12)))
    h2 = sched.submit(jnp.zeros((2, 12, 12)))
    b1, b2 = sched.admit(), sched.admit()
    b1.requests[0].finish(result="done")     # engine completed b1 ...
    assert sched.cancel_all(TimeoutError("wedged")) == 2  # ... then cancel
    assert h1.result(timeout=1.0) == "done"  # result not clobbered
    with pytest.raises(TimeoutError):
        h2.result(timeout=1.0)
    b2.requests[0].finish(result="late")     # engine finishes b2 after all
    with pytest.raises(TimeoutError):        # cancellation not clobbered
        h2.result(timeout=1.0)
    assert not sched.has_work()


def test_server_pallas_backend_serves_matching_results():
    """End-to-end serving over the fused pallas worker kernel: the engine's
    bucketed batch programs run the custom MXU path and decode to the same
    outputs as the lax pipeline."""
    params = _params(STACK)
    specs = plan_layers(STACK, 12, 6, default_kab=(2, 4))
    pal = CodedPipeline(specs, params, backend="pallas", bucket_sizes=(1, 2))
    ref_pipe, _ = _pipeline(bucket_sizes=(1, 2))
    server = CodedServer(pal, StragglerModel.none(6), mode="simulated")
    assert server.cluster.backend == "pallas"
    xs = _images(3)
    with server:
        outs = [h.result(timeout=120.0) for h in server.submit_many(xs)]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_pipe.run(x)), rtol=1e-3, atol=1e-3
        )


def test_server_concurrent_clients():
    pipe, _ = _pipeline()
    ref_pipe, _ = _pipeline()
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated")
    xs = _images(6)
    outs = [None] * len(xs)
    errs = []

    def client(i):
        try:
            outs[i] = server.submit(xs[i]).result(timeout=60.0)
        except BaseException as e:  # surfaced below
            errs.append(e)

    with server:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    assert not errs
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_pipe.run(x)), rtol=1e-4, atol=1e-4
        )


# -- multi-model serving ---------------------------------------------------
def _pipeline_b(bucket_sizes=(1, 2, 4), n=6, hw=12, kab=(4, 2)):
    params = _params(STACK_B, seed=3)
    specs = plan_layers(STACK_B, hw, n, default_kab=kab)
    return CodedPipeline(specs, params, bucket_sizes=bucket_sizes), params


def _images_b(count, hw=12):
    return [jnp.asarray(RNG.standard_normal((3, hw, hw)), jnp.float32)
            for _ in range(count)]


def _prequeue(server, model, xs):
    """Enqueue requests before ``start()`` (dtype pre-cast like submit)."""
    pipe = server.models[model].pipeline
    return [server.scheduler[model].queue.submit(
        jnp.asarray(x, pipe.input_dtype)) for x in xs]


def test_multimodel_bitexact_vs_single_model_servers():
    """The acceptance contract: two models with different (k_a, k_b) plans
    served concurrently from ONE shared worker pool produce bit-exact
    per-model outputs vs their own single-model servers, with the jit
    trace count bounded by geometries x buckets summed over models.

    Distinct finite delays make the simulated fastest-delta subset
    deterministic, so identical programs see identical inputs."""
    delays = np.arange(6, dtype=float)  # worker 0 fastest, strict order
    pipe_a, _ = _pipeline()
    pipe_b, _ = _pipeline_b()
    xs_a, xs_b = _images(4), _images_b(3)

    def serve_single(pipe, xs):
        server = CodedServer(pipe, StragglerModel(delays), mode="simulated")
        handles = _prequeue(server, "default", xs)
        with server:
            return [np.asarray(h.result(timeout=60.0)) for h in handles]

    ref_a = serve_single(pipe_a, xs_a)
    ref_b = serve_single(pipe_b, xs_b)

    shared = CodedServer(straggler=StragglerModel(delays), mode="simulated")
    shared.register_model("a", pipe_a)
    shared.register_model("b", pipe_b)
    ha = _prequeue(shared, "a", xs_a)
    hb = _prequeue(shared, "b", xs_b)
    with shared:
        out_a = [np.asarray(h.result(timeout=60.0)) for h in ha]
        out_b = [np.asarray(h.result(timeout=60.0)) for h in hb]
    for got, ref in zip(out_a + out_b, ref_a + ref_b):
        np.testing.assert_array_equal(got, ref)
    traces = sum(s.pipeline.worker_program_traces
                 for s in shared.models.values())
    bound = sum(s.pipeline.num_geometries * len(s.pipeline.bucket_sizes)
                for s in shared.models.values())
    assert traces <= bound
    # per-model metrics break out; the aggregate covers both
    per = shared.per_model_stats()
    assert per["a"].completed == 4 and per["b"].completed == 3
    assert shared.stats().completed == 7
    assert shared.stats("a").completed == 4


def test_multimodel_straggler_isolation_threads_mode():
    """Model A's straggler-heavy wall-clock rounds must not corrupt model
    B's results on the shared pool (threads mode, real sleeps)."""
    delays = np.zeros(6)
    delays[0] = 0.3
    delays[5] = np.inf  # and one dead worker
    pipe_a, _ = _pipeline()
    pipe_b, _ = _pipeline_b()
    ref_a, _ = _pipeline()
    ref_b, _ = _pipeline_b()
    server = CodedServer(straggler=StragglerModel(delays), mode="threads")
    server.register_model("a", pipe_a)
    server.register_model("b", pipe_b)
    server.warmup()
    xs_a, xs_b = _images(3), _images_b(3)
    with server:
        ha = server.submit_many(xs_a, "a")
        hb = server.submit_many(xs_b, "b")
        out_a = [h.result(timeout=60.0) for h in ha]
        out_b = [h.result(timeout=60.0) for h in hb]
    for x, y in zip(xs_a, out_a):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_a.run(x)), rtol=1e-4, atol=1e-4)
    for x, y in zip(xs_b, out_b):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_b.run(x)), rtol=1e-4, atol=1e-4)


def test_cluster_filter_cache_no_collision_across_pipelines():
    """Two pipelines with the SAME layer names but different plans stay
    resident on one cluster at once — namespaced entries, no clobbering,
    and each model decodes against its own filters."""
    pipe1, _ = _pipeline()                      # plan (2, 4)
    specs2 = plan_layers(STACK, 12, 6, default_kab=(4, 2))
    pipe2 = CodedPipeline(specs2, _params(STACK, seed=9))  # plan (4, 2)
    cluster = FcdccCluster(pipe1.specs[0].plan, StragglerModel.none(6),
                           mode="simulated")
    cluster.load_pipeline(pipe1, "m1")
    cluster.load_pipeline(pipe2, "m2")
    assert {"m1/s1", "m1/s2", "m2/s1", "m2/s2"} <= set(cluster._resident)
    x = jnp.asarray(RNG.standard_normal((2, 2, 12, 12)), jnp.float32)
    y1, _ = cluster.run_pipeline(x, model="m1")
    y2, _ = cluster.run_pipeline(x, model="m2")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(pipe1.run(x)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(pipe2.run(x)),
                               rtol=1e-4, atol=1e-4)
    # model selector is mandatory once ambiguous, and must exist
    with pytest.raises(ValueError, match="pass model="):
        cluster.run_pipeline(x)
    with pytest.raises(ValueError, match="unknown model"):
        cluster.run_pipeline(x, model="nope")
    # an explicitly passed pipeline is never ambiguous (default namespace)
    y3, _ = cluster.run_pipeline(x, pipe1)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y1),
                               rtol=1e-4, atol=1e-4)
    # re-registering a name purges ALL of its old resident entries (a v2
    # with fewer layers must not leave v1 filters reachable)
    short = CodedPipeline(plan_layers(STACK[:1], 12, 6, default_kab=(2, 4)),
                          _params(STACK))
    cluster.load_pipeline(short, "m1")
    assert "m1/s1" in cluster._resident and "m1/s2" not in cluster._resident
    cluster.shutdown()


def test_fair_share_interleaves_models():
    """The starvation bound: with both models holding work, layer rounds
    alternate (least-served first) — at every prefix of the advance
    sequence the per-model round counts differ by at most 1."""
    pipe_a, _ = _pipeline(bucket_sizes=(1,))
    pipe_b, _ = _pipeline_b(bucket_sizes=(1,))
    server = CodedServer(mode="simulated")
    server.register_model("a", pipe_a)
    server.register_model("b", pipe_b)
    advanced = []
    orig = server.cluster.dispatch_pipeline_layer

    def spy(idx, x, model=None):
        advanced.append(model)
        return orig(idx, x, model)

    server.cluster.dispatch_pipeline_layer = spy
    ha = _prequeue(server, "a", _images(3))
    hb = _prequeue(server, "b", _images_b(3))
    with server:
        for h in ha + hb:
            h.result(timeout=60.0)
    # 3 requests x 2 layers each = 6 rounds per model, interleaved fairly
    assert advanced.count("a") == 6 and advanced.count("b") == 6
    for i in range(1, len(advanced) + 1):
        prefix = advanced[:i]
        assert abs(prefix.count("a") - prefix.count("b")) <= 1, prefix


def test_weighted_fair_share_round_ratio_and_starvation_bound():
    """register_model(..., weight=w): the rotating sweep grants up to w
    consecutive rounds per sweep position.  With weights (2, 1) and both
    models backlogged, rounds follow a,a,b,... and the starvation bound
    holds: a backlogged model never waits more than the sum of the OTHER
    models' weights between consecutive rounds of its own."""
    pipe_a, _ = _pipeline(bucket_sizes=(1,))
    pipe_b, _ = _pipeline_b(bucket_sizes=(1,))
    server = CodedServer(mode="simulated")
    server.register_model("a", pipe_a, weight=2)
    server.register_model("b", pipe_b, weight=1)
    advanced = []
    orig = server.cluster.dispatch_pipeline_layer

    def spy(idx, x, model=None):
        advanced.append(model)
        return orig(idx, x, model)

    server.cluster.dispatch_pipeline_layer = spy
    ha = _prequeue(server, "a", _images(4))
    hb = _prequeue(server, "b", _images_b(4))
    with server:
        for h in ha + hb:
            h.result(timeout=60.0)
    # 4 requests x 2 layers per model
    assert advanced.count("a") == 8 and advanced.count("b") == 8
    # while both are backlogged the prefix ratio honors the weights: in any
    # prefix of the contended phase, a's rounds stay within weight_a of
    # 2x b's rounds (a,a,b repeating)
    contended = advanced[: 3 * 4]  # both models have work for >= 4 sweeps
    for i in range(1, len(contended) + 1):
        na, nb = contended[:i].count("a"), contended[:i].count("b")
        assert abs(na - 2 * nb) <= 2, contended[:i]
    # starvation bound: gaps between consecutive 'b' rounds <= weight_a + 1
    b_rounds = [i for i, m in enumerate(contended) if m == "b"]
    assert all(j - i <= 3 for i, j in zip(b_rounds, b_rounds[1:]))


def test_weighted_fair_share_validation():
    server = CodedServer(mode="simulated")
    with pytest.raises(ValueError, match="weight"):
        server.register_model("a", _pipeline()[0], weight=0)
    with pytest.raises(ValueError, match="weight"):
        server.register_model("a", _pipeline()[0], weight=1.5)
    # the failed registrations left no partial state behind
    assert not server.models and server.cluster is None


def test_models_registry_single_source_of_truth():
    """The name -> pipeline registry lives only in the cluster;
    CodedServer.models holds per-model serving state whose ``pipeline`` is
    a live view of ``cluster.pipelines`` — the two can never disagree."""
    pipe_a, _ = _pipeline()
    pipe_b, _ = _pipeline_b()
    server = CodedServer(mode="simulated")
    server.register_model("a", pipe_a)
    server.register_model("b", pipe_b, weight=3)
    assert set(server.models) == set(server.cluster.pipelines) == {"a", "b"}
    assert server.models["a"].pipeline is server.cluster.pipelines["a"]
    assert server.models["b"].pipeline is pipe_b
    # the fair-share weight likewise has one home: the scheduler
    assert server.scheduler.weights["b"] == 3
    # a cluster-side replace is immediately visible through the view
    pipe_a2, _ = _pipeline()
    server.cluster.load_pipeline(pipe_a2, "a")
    assert server.models["a"].pipeline is pipe_a2


def test_fair_share_idle_model_builds_no_deficit():
    """A model that idled while another served must NOT bank a least-served
    deficit it can later spend monopolizing the engine: the sweep is
    positional, so once both have work the picks alternate immediately."""
    from repro.serving.scheduler import MultiScheduler

    multi = MultiScheduler()
    for name in ("a", "b"):
        multi.add_model(name, lambda x: (x, x.shape[0]), max_batch=1,
                        max_inflight=8)
    # phase 1: only 'a' has work — it serves 50 rounds unopposed
    multi.submit("a", jnp.zeros((2, 12, 12)))
    assert multi.admit() is not None
    for _ in range(50):
        name, _batch = multi.next_batch()
        assert name == "a"
    # phase 2: 'b' arrives — picks must alternate from the very next round
    multi.submit("b", jnp.zeros((3, 12, 12)))
    assert multi.admit() is not None
    picks = [multi.next_batch()[0] for _ in range(6)]
    assert picks == ["b", "a", "b", "a", "b", "a"]


def test_coalescing_merges_equal_depth_batches():
    """Two in-flight fragments of one model at the same layer boundary are
    merged into one bucketed batch (counted in stats) and still decode to
    exactly the per-request reference results."""
    pipe, _ = _pipeline(bucket_sizes=(1, 2, 4))
    ref_pipe, _ = _pipeline()
    server = CodedServer(pipe, StragglerModel.none(6), mode="simulated")
    xs = _images(2)
    sched = server.scheduler["default"]
    # force two fragment batches at layer 0: admit each request alone
    handles = []
    for x in xs:
        handles.append(sched.queue.submit(jnp.asarray(x, pipe.input_dtype)))
        assert sched.admit() is not None
    assert [b.real for b in sched.inflight] == [1, 1]
    with server:
        outs = [h.result(timeout=60.0) for h in handles]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref_pipe.run(x)), rtol=1e-4, atol=1e-4)
    assert server.stats().completed == 2
    assert server.stats().coalesced == 1
    assert server.stats("default").coalesced == 1
    recs = sorted(server.metrics.records(), key=lambda r: r.request_id)
    assert [r.batch_real for r in recs] == [2, 2]  # both rode one batch


def test_coalesce_respects_max_batch():
    """Fragments whose combined real size exceeds the largest bucket stay
    separate (a merge must never overflow the jit program buckets)."""
    from repro.serving.scheduler import Scheduler

    pipe, _ = _pipeline(bucket_sizes=(1, 2))
    sched = Scheduler(pipe.pad_to_bucket, max_batch=2, max_inflight=4)
    for _ in range(3):
        sched.queue.submit(_images(1)[0])
        sched.admit(limit=1)
    assert len(sched.inflight) == 3
    assert sched.coalesce() == 1
    assert sorted(b.real for b in sched.inflight) == [1, 2]
    assert sched.coalesce() == 0  # nothing else fits


def test_register_model_validation():
    pipe_a, _ = _pipeline()
    server = CodedServer(pipe_a, StragglerModel.none(6), mode="simulated")
    with pytest.raises(ValueError, match="already registered"):
        server.register_model("default", _pipeline()[0])
    unbucketed = CodedPipeline(plan_layers(STACK, 12, 8, default_kab=(2, 4)),
                               _params(STACK))
    with pytest.raises(ValueError, match="n=8"):
        server.register_model("bigger", unbucketed)
    # a failed registration must not have re-bucketed the caller's pipeline
    assert unbucketed.bucket_sizes is None
    pal = CodedPipeline(plan_layers(STACK_B, 12, 6, default_kab=(2, 4)),
                        _params(STACK_B), backend="pallas",
                        bucket_sizes=(1, 2))
    with pytest.raises(ValueError, match="backend"):
        server.register_model("pallas", pal)
    with pytest.raises(ValueError, match="unknown model"):
        server.submit(_images(1)[0], "nope")
    server.start()
    try:
        # live registration: a model added while the engine loop is running
        # serves without a restart (scheduler is published last, so the
        # loop never sees a half-registered model)
        server.register_model("late", _pipeline_b()[0])
        y = server.submit(_images_b(1)[0], "late").result(timeout=60.0)
        assert y.shape == _pipeline_b()[0].run(_images_b(1)[0]).shape
    finally:
        server.shutdown()
    # a server with no model registered refuses to start
    with pytest.raises(RuntimeError, match="no model"):
        CodedServer(mode="simulated").start()


def test_multimodel_submit_requires_model_name():
    server = CodedServer(mode="simulated")
    server.register_model("a", _pipeline()[0])
    server.register_model("b", _pipeline_b()[0])
    with server:
        with pytest.raises(ValueError, match="pass model="):
            server.submit(_images(1)[0])
        y = server.submit(_images(1)[0], "a").result(timeout=60.0)
    assert y is not None
    with pytest.raises(ValueError, match="use models"):
        server.pipeline  # single-model back-compat view is now ambiguous


# -- HTTP front-end --------------------------------------------------------
def _http(method, url, payload=None, timeout=30.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def test_http_frontend_roundtrip_and_drain():
    """POST /v1/infer for two models on an ephemeral port, stats/models
    introspection, error codes, then a graceful drain: no leaked engine
    thread, no leaked worker executors, socket closed."""
    pipe_a, _ = _pipeline()
    pipe_b, _ = _pipeline_b()
    ref_a, _ = _pipeline()
    server = CodedServer(mode="simulated")
    server.register_model("a", pipe_a)
    server.register_model("b", pipe_b)
    frontend = ServingFrontend(server, port=0)
    frontend.start()
    url = frontend.url
    try:
        status, models = _http("GET", f"{url}/v1/models")
        assert status == 200
        assert {m["name"] for m in models["models"]} == {"a", "b"}
        shapes = {m["name"]: tuple(m["input_shape"]) for m in models["models"]}
        assert shapes == {"a": (2, 12, 12), "b": (3, 12, 12)}

        x = np.asarray(_images(1)[0])
        status, out = _http("POST", f"{url}/v1/infer",
                            {"model": "a", "input": x.tolist()})
        assert status == 200 and out["model"] == "a"
        np.testing.assert_allclose(
            np.asarray(out["output"], np.float32), np.asarray(ref_a.run(x)),
            rtol=1e-4, atol=1e-4)
        xb = np.asarray(_images_b(1)[0])
        status, out_b = _http("POST", f"{url}/v1/infer",
                              {"model": "b", "input": xb.tolist()})
        assert status == 200 and out_b["shape"][0] == 4  # STACK_B out_ch

        status, stats = _http("GET", f"{url}/v1/stats")
        assert status == 200
        assert stats["aggregate"]["completed"] == 2
        assert stats["per_model"]["a"]["completed"] == 1
        assert stats["per_model"]["b"]["completed"] == 1

        with pytest.raises(urllib.error.HTTPError) as err:
            _http("POST", f"{url}/v1/infer",
                  {"model": "nope", "input": x.tolist()})
        assert err.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as err:
            _http("POST", f"{url}/v1/infer",
                  {"model": "a", "input": [[1.0]]})
        assert err.value.code == 400
        # ambiguous model on a multi-model server is a client error ...
        with pytest.raises(urllib.error.HTTPError) as err:
            _http("POST", f"{url}/v1/infer", {"input": x.tolist()})
        assert err.value.code == 400
        # ... and so is a valid-JSON body that is not an object
        with pytest.raises(urllib.error.HTTPError) as err:
            _http("POST", f"{url}/v1/infer", 42)
        assert err.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as err:
            _http("GET", f"{url}/v1/nothing")
        assert err.value.code == 404
    finally:
        frontend.shutdown()
    # graceful drain: engine thread joined, worker pools released, port dead
    assert server._thread is None
    assert server.cluster._pools is None
    assert not any(t.name == "coded-server-engine" and t.is_alive()
                   for t in threading.enumerate())
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _http("GET", f"{url}/v1/models", timeout=2.0)
    # idempotent
    frontend.shutdown()


def test_http_batched_infer_per_item_errors():
    """POST /v1/infer with "inputs": one HTTP round trip fans out every
    image to the engine (in-order results), and a bad item yields a
    per-item error without failing its siblings."""
    pipe_a, _ = _pipeline()
    ref_a, _ = _pipeline()
    server = CodedServer(pipe_a, mode="simulated", model="a")
    frontend = ServingFrontend(server, port=0)
    frontend.start()
    url = frontend.url
    try:
        xs = [np.asarray(x) for x in _images(3)]
        status, out = _http("POST", f"{url}/v1/infer",
                            {"model": "a", "inputs": [x.tolist() for x in xs]})
        assert status == 200 and out["model"] == "a" and out["count"] == 3
        assert len(out["results"]) == 3
        for x, item in zip(xs, out["results"]):
            assert "error" not in item
            np.testing.assert_allclose(
                np.asarray(item["output"], np.float32),
                np.asarray(ref_a.run(x)), rtol=1e-4, atol=1e-4)
        # in-order: request ids ascend with list position
        ids = [r["request_id"] for r in out["results"]]
        assert ids == sorted(ids)

        # middle item has the wrong shape: that item errors, siblings serve
        bad = [xs[0].tolist(), np.zeros((1, 2, 2)).tolist(), xs[2].tolist()]
        status, out = _http("POST", f"{url}/v1/infer",
                            {"model": "a", "inputs": bad})
        assert status == 200 and out["count"] == 3
        assert "error" not in out["results"][0]
        assert "request shape" in out["results"][1]["error"]
        assert "error" not in out["results"][2]

        # malformed batches are request-level 400s
        for body in ({"model": "a", "inputs": []},
                     {"model": "a", "inputs": 5},
                     {"model": "a", "input": xs[0].tolist(),
                      "inputs": [xs[0].tolist()]}):
            with pytest.raises(urllib.error.HTTPError) as err:
                _http("POST", f"{url}/v1/infer", body)
            assert err.value.code == 400
    finally:
        frontend.shutdown()


def test_http_infer_no_model_registered_is_503_not_crash():
    """An infer against an engine with zero models must answer 503 (both
    single and batched forms), not kill the handler with an IndexError."""
    server = CodedServer(mode="simulated")
    frontend = ServingFrontend(server, port=0, manage_server=False)
    frontend.start()
    try:
        x = np.zeros((2, 12, 12)).tolist()
        for body in ({"input": x}, {"inputs": [x]}):
            with pytest.raises(urllib.error.HTTPError) as err:
                _http("POST", f"{frontend.url}/v1/infer", body)
            assert err.value.code == 503
    finally:
        frontend.shutdown()


def test_http_batched_infer_requires_model_when_ambiguous():
    server = CodedServer(mode="simulated")
    server.register_model("a", _pipeline()[0])
    server.register_model("b", _pipeline_b()[0])
    frontend = ServingFrontend(server, port=0)
    frontend.start()
    try:
        x = np.asarray(_images(1)[0])
        with pytest.raises(urllib.error.HTTPError) as err:
            _http("POST", f"{frontend.url}/v1/infer",
                  {"inputs": [x.tolist()]})
        assert err.value.code == 400
        # with the model named, the batch serves
        status, out = _http("POST", f"{frontend.url}/v1/infer",
                            {"model": "a", "inputs": [x.tolist()]})
        assert status == 200 and out["count"] == 1
    finally:
        frontend.shutdown()


# -- metrics --------------------------------------------------------------
def test_percentile_and_stats_math():
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert np.isnan(percentile([], 50))
    mc = MetricsCollector()
    for i in range(4):
        mc.record(RequestRecord(
            request_id=i, arrival_t=float(i), start_t=i + 1.0,
            finish_t=i + 3.0, bucket=4, batch_real=2,
        ))
    s = mc.stats()
    assert s.completed == 4
    assert s.queue_wait_p50_s == pytest.approx(1.0)
    assert s.execute_p50_s == pytest.approx(2.0)
    assert s.e2e_p50_s == pytest.approx(3.0)
    assert s.wall_s == pytest.approx(6.0)  # arrival 0 -> finish 6
    assert s.images_per_s == pytest.approx(4 / 6.0)
    assert s.mean_batch_real == pytest.approx(2.0)
    mc.reset()
    assert mc.stats().completed == 0


# -- live (de)registration ------------------------------------------------
def test_unregister_model_drains_then_removes():
    """Two-phase removal on a live engine: close (no new submits) -> drain
    queued work -> fence -> remove.  In-flight requests of the removed
    model complete; the co-resident model keeps serving; every cluster
    namespace (pipelines, resident filters) is reclaimed."""
    server = CodedServer(mode="simulated")
    server.register_model("a", _pipeline()[0])
    server.register_model("b", _pipeline_b()[0])
    with server:
        last = server.submit(_images_b(1)[0], "b")
        server.unregister_model("b", drain=True, timeout=60.0)
        assert last.result(timeout=1.0) is not None  # drained, not dropped
        with pytest.raises(ValueError, match="unknown model"):
            server.submit(_images_b(1)[0], "b")
        y = server.submit(_images(1)[0], "a").result(timeout=60.0)
        assert y is not None
        assert "b" not in server.models
        assert "b" not in server.cluster.pipelines
        assert not any(k.startswith("b/") for k in server.cluster._resident)
        # re-registration under the freed name works on the live engine
        server.register_model("b", _pipeline_b()[0])
        assert server.submit(_images_b(1)[0], "b").result(timeout=60.0) \
            is not None


def test_unregister_model_no_drain_cancels_queued():
    server = CodedServer(mode="simulated")
    server.register_model("a", _pipeline()[0])
    server.register_model("b", _pipeline_b()[0])
    # engine not started: queued work cannot drain, so drain=False cancels
    h = server.scheduler["b"].submit(_images_b(1)[0])
    server.unregister_model("b", drain=False)
    with pytest.raises(RuntimeError, match="unregistered"):
        h.result(timeout=1.0)
    with pytest.raises(ValueError, match="unknown model"):
        server.submit(_images_b(1)[0], "b")
    with pytest.raises(ValueError, match="unknown model"):
        server.unregister_model("b")


def test_scheduler_fence_blocks_bucket_bindings():
    """A fenced scheduler must never consult pad_to_bucket again: admit
    refuses new batches and coalesce refuses merges *before* touching the
    bucket bindings (they may already be unloaded mid-removal)."""
    from repro.serving.scheduler import Scheduler

    pipe, _ = _pipeline(bucket_sizes=(1, 2))
    live = {"ok": True}

    def pad(x):
        assert live["ok"], "pad_to_bucket consulted after fence"
        return pipe.pad_to_bucket(x)

    sched = Scheduler(pad, max_batch=2, max_inflight=4)
    for _ in range(2):
        sched.queue.submit(_images(1)[0])
        sched.admit(limit=1)
    assert len(sched.inflight) == 2
    sched.close()
    with pytest.raises(RuntimeError, match="unregistered"):
        sched.submit(_images(1)[0])
    assert sched.has_work()  # queued/in-flight work survives close
    sched.fence()
    live["ok"] = False  # bindings gone: any pad call from here is a bug
    sched.queue.submit(_images(1)[0])  # raced in before close... simulate
    assert sched.admit() is None
    assert sched.coalesce() == 0


def test_multischeduler_remove_is_safe_mid_iteration():
    """The engine loop iterates a snapshot: removing a model between
    next_batch calls must neither KeyError nor starve the survivor."""
    from repro.serving.scheduler import MultiScheduler

    pipe, _ = _pipeline(bucket_sizes=(1, 2))
    multi = MultiScheduler()
    multi.add_model("a", pipe.pad_to_bucket, max_batch=2, max_inflight=4)
    multi.add_model("b", pipe.pad_to_bucket, max_batch=2, max_inflight=4)
    multi.submit("a", _images(1)[0])
    multi.submit("b", _images(1)[0])
    assert multi.admit() is not None
    assert multi.admit() is not None
    removed = multi.remove_model("b")
    assert removed.cancel_all(RuntimeError("gone")) >= 0
    picked = multi.next_batch()
    assert picked is not None and picked[0] == "a"
    with pytest.raises(KeyError):
        multi.remove_model("b")
    with pytest.raises(ValueError, match="already registered"):
        multi.add_model("a", pipe.pad_to_bucket, max_batch=2, max_inflight=4)
