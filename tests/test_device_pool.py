"""The device-resident worker pool (``pool="device"``).

Each coded worker pinned to its own ``jax.Device``: coded filters resident
per device, per-device jitted programs, async dispatch, fastest-delta
reaped via per-array readiness.  These tests need a multi-device host —
on CPU boxes run them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (scripts/ci.sh
does); on a single-device host the whole module skips, keeping the tier-1
suite's behavior identical to the thread-pool-only seed.

Covers: threads-vs-device bit-parity (forced fastest-delta subsets) across
the CNN archs x {lax, pallas}; fastest-delta discard under a slowed
device; dead-device elastic re-plan; the per-device bounded-program
contract; resident filter blocks; pool resolution rules; and serving
through the device pool.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FcdccPlan
from repro.core.fcdcc import ConvGeometry
from repro.core.pipeline import build_cnn_pipeline
from repro.models.cnn import CNN_SPECS, init_cnn, input_hw
from repro.runtime import (
    FcdccCluster,
    StragglerModel,
    run_layer_elastic,
)
from repro.runtime.devicepool import resolve_pool

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="device pool needs a multi-device host (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)",
)

RNG = np.random.default_rng(0)
N = 6


def _pipe(arch, backend="lax", n=N, kab=(2, 4)):
    params = init_cnn(arch, jax.random.PRNGKey(0))
    return build_cnn_pipeline(arch, params, n, default_kab=kab,
                              input_hw=input_hw(arch, smoke=True),
                              backend=backend)


def _in_shape(pipe, batch):
    geo = pipe.specs[0].geo
    return (batch, geo.in_channels, geo.height, geo.width)


def _forced_subset_straggler(pipe, n=N):
    """Finite delays on workers delta..n-1: both pools must keep exactly
    the undelayed subset, making their decodes bit-identical."""
    dm = max(spec.plan.delta for spec in pipe.specs)
    delays = np.zeros(n)
    delays[dm:] = 0.3
    return StragglerModel(delays), dm


def _run_pool(pipe, pool, x, straggler, arch):
    cluster = FcdccCluster(pipe.specs[0].plan, straggler=straggler,
                           mode="threads", backend=pipe.backend, pool=pool)
    try:
        cluster.load_pipeline(pipe, arch)
        y, timings = cluster.run_pipeline(x, model=arch)
        return np.asarray(y), timings, cluster
    finally:
        cluster.shutdown()


# -- bit-parity across pools ----------------------------------------------
@pytest.mark.parametrize("arch", sorted(CNN_SPECS))
@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_pools_bit_identical_forced_subset(arch, backend):
    """With the fastest-delta subset pinned, the device pool's gather +
    decode is bitwise the thread pool's: same shards, same fp32 GEMMs."""
    if backend == "pallas" and arch == "vgg16":
        pytest.skip("interpret-mode vgg16 is minutes-slow; lax covers the "
                    "pool seam, pallas parity is covered by the small archs")
    pipe_t, pipe_d = _pipe(arch, backend), _pipe(arch, backend)
    straggler, dm = _forced_subset_straggler(pipe_t)
    c0 = pipe_t.specs[0].geo.in_channels
    hw0 = input_hw(arch, smoke=True)
    x = np.asarray(RNG.standard_normal((1, c0, hw0, hw0)), np.float32)
    yt, tt, _ = _run_pool(pipe_t, "threads", x, straggler, arch)
    yd, td, _ = _run_pool(pipe_d, "device", x, straggler, arch)
    assert np.array_equal(yt, yd)
    delayed = set(range(dm, N))
    for t in tt + td:
        assert not (set(t.used_workers) & delayed), (
            f"{t.name}: decode consumed a delayed shard {t.used_workers}")


# -- fastest-delta discard ------------------------------------------------
def test_slowed_device_discarded():
    """A delayed device's shard must be excluded from the decode subset and
    its worker slot marked nan (discarded) — never silently gathered."""
    delays = np.zeros(N)
    delays[0] = 3.0
    pipe = _pipe("lenet5")
    cluster = FcdccCluster(pipe.specs[0].plan, StragglerModel(delays),
                           mode="threads", pool="device")
    try:
        cluster.load_pipeline(pipe)
        x = np.asarray(RNG.standard_normal(_in_shape(pipe, 1)), np.float32)
        y, timing = cluster.run_pipeline_layer(0, x)
        assert 0 not in timing.used_workers
        assert np.isnan(timing.worker_compute_s[0])
        assert len(timing.used_workers) == pipe.specs[0].plan.delta
        assert all(np.isfinite(timing.worker_compute_s[i])
                   for i in timing.used_workers)
    finally:
        cluster.shutdown()


def test_dead_device_elastic_replan():
    """inf-delay devices never dispatch; when fewer than delta survive the
    elastic driver shrinks the subtask grid and retries on the device
    pool."""
    plan = FcdccPlan(n=N, k_a=2, k_b=4)
    geo = ConvGeometry(in_channels=2, height=12, width=12, out_channels=8,
                       kernel_h=3, kernel_w=3, stride=1, padding=1)
    x = jnp.asarray(RNG.standard_normal((2, 12, 12)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((8, 2, 3, 3)), jnp.float32)
    ref = FcdccCluster(plan, None, mode="threads").run_layer(geo, x, k)[0]
    d = np.zeros(N)
    d[:5] = np.inf  # 5 dead of 6: delta=8's plan cannot survive
    y, _, plan2 = run_layer_elastic(
        plan, geo, x, k, StragglerModel(d), mode="threads", pool="device")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-3)
    assert plan2.delta < plan.delta


# -- bounded programs per device ------------------------------------------
def test_bounded_programs_per_device():
    """After serving several buckets, every device's worker-program trace
    count stays <= (layer geometries) x (buckets) — compiles are per cell,
    never per round or per request."""
    pipe = _pipe("lenet5")
    buckets = (1, 2)
    cluster = FcdccCluster(pipe.specs[0].plan, None, mode="threads",
                           pool="device")
    try:
        cluster.load_pipeline(pipe)
        for b in buckets:
            x = np.asarray(RNG.standard_normal(_in_shape(pipe, b)), np.float32)
            for _ in range(3):  # repeats must not re-trace
                cluster.run_pipeline(x)
        traces = cluster._pool_impl().program_traces()
        assert len(traces) == min(N, len(jax.devices()))
        bound = len(pipe.specs) * len(buckets)
        assert all(c <= bound for c in traces.values()), (traces, bound)
    finally:
        cluster.shutdown()


# -- residency + placement ------------------------------------------------
def test_filters_resident_on_worker_devices():
    pipe = _pipe("lenet5")
    cluster = FcdccCluster(pipe.specs[0].plan, None, mode="threads",
                           pool="device")
    try:
        cluster.load_pipeline(pipe, "m")
        impl = cluster._pool_impl()
        devs = cluster.worker_devices
        assert devs is not None and len(devs) == N
        m = min(N, len(jax.devices()))
        for spec, ke in zip(pipe.specs, pipe.coded_filters):
            _, blocks = impl._filters[f"m/{spec.name}"]
            assert len(blocks) == m
            for i in range(N):  # worker i: block i % m, slot i // m
                block = blocks[i % m]
                assert block.devices() == {devs[i]}
                np.testing.assert_array_equal(np.asarray(block[i // m]),
                                              np.asarray(ke[i]))
        # unload reclaims every per-device block of the namespace
        cluster.unload_pipeline("m")
        assert not any(key.startswith("m/") for key in impl._filters)
    finally:
        cluster.shutdown()


def test_worker_devices_round_robin_when_fewer_devices():
    n_big = len(jax.devices()) + 3  # more workers than devices
    pipe = build_cnn_pipeline(
        "lenet5", init_cnn("lenet5", jax.random.PRNGKey(0)), n_big,
        default_kab=(2, 4), input_hw=12)
    cluster = FcdccCluster(pipe.specs[0].plan, None, mode="threads",
                           pool="device")
    try:
        cluster.load_pipeline(pipe)
        devs = cluster.worker_devices
        assert len(devs) == n_big
        assert devs[0] == devs[len(jax.devices())]  # wraps round-robin
        x = np.asarray(RNG.standard_normal(_in_shape(pipe, 1)), np.float32)
        y, _ = cluster.run_pipeline(x)
        ref, _ = FcdccCluster(pipe.specs[0].plan, None,
                              mode="threads").run_pipeline(x, pipe)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
    finally:
        cluster.shutdown()


# -- pool resolution ------------------------------------------------------
def test_resolve_pool_rules():
    assert resolve_pool(None, "threads") == "device"  # multi-device host
    assert resolve_pool(None, "threads", devices=jax.devices()[:2]) == "device"
    assert resolve_pool(None, "simulated") == "threads"
    assert resolve_pool("threads", "threads") == "threads"
    assert resolve_pool("device", "threads") == "device"
    with pytest.raises(ValueError, match="simulated"):
        resolve_pool("device", "simulated")
    with pytest.raises(ValueError, match="unknown pool"):
        resolve_pool("gpu", "threads")
    with pytest.raises(ValueError, match="simulated"):
        FcdccCluster(FcdccPlan(n=N, k_a=2, k_b=4), None, mode="simulated",
                     pool="device")


# -- serving through the device pool --------------------------------------
def test_serving_on_device_pool():
    from repro.serving import CodedServer

    pipe, ref = _pipe("lenet5"), _pipe("lenet5")
    server = CodedServer(pipe, StragglerModel.none(N), mode="threads",
                         pool="device")
    xs = [jnp.asarray(RNG.standard_normal(_in_shape(pipe, 1)[1:]),
                      jnp.float32) for _ in range(3)]
    with server:
        assert server.cluster.pool == "device"
        outs = [h.result(timeout=120.0) for h in server.submit_many(xs)]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.run(x)), rtol=1e-4, atol=1e-4)


def test_fused_transitions_on_device_pool():
    """Partition-resident transitions on the device pool: the coded shares
    carried between rounds re-dispatch to the worker devices, and with a
    forced fastest-delta subset the end result is bitwise the thread
    pool's."""
    def mk():
        return build_cnn_pipeline(
            "lenet5", init_cnn("lenet5", jax.random.PRNGKey(0)), N,
            default_kab=(2, 4), input_hw=input_hw("lenet5", smoke=True),
            fuse_transitions=True)

    pipe_t, pipe_d = mk(), mk()
    straggler, dm = _forced_subset_straggler(pipe_t)
    x = np.asarray(RNG.standard_normal(_in_shape(pipe_t, 1)), np.float32)
    yt, tt, _ = _run_pool(pipe_t, "threads", x, straggler, "m")
    yd, td, _ = _run_pool(pipe_d, "device", x, straggler, "m")
    assert np.array_equal(yt, yd)
    delayed = set(range(dm, N))
    for t in tt + td:
        assert not (set(t.used_workers) & delayed)


# -- non-blocking readiness + adaptive collect backoff ---------------------
def test_device_pool_round_ready_nonblocking():
    """The dispatch/collect split on the device pool: ``round_ready`` is
    False while the delta-th shard's deferred dispatch has not landed,
    flips True without blocking, and ``collect(block=False)`` mirrors it."""
    pipe = _pipe("lenet5")
    dm = max(spec.plan.delta for spec in pipe.specs)
    delays = np.full(N, 0.4)  # every dispatch deferred: nothing ready early
    cluster = FcdccCluster(pipe.specs[0].plan, StragglerModel(delays),
                           mode="threads", pool="device")
    try:
        cluster.load_pipeline(pipe)
        x = np.asarray(RNG.standard_normal(_in_shape(pipe, 1)), np.float32)
        rnd = cluster.dispatch_pipeline_layer(0, x)
        assert not cluster.round_ready(rnd)
        assert cluster.collect(rnd.pending, dm, block=False) is None
        deadline = time.perf_counter() + 30.0
        while not cluster.round_ready(rnd):
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        y, timing = cluster.collect_pipeline_layer(rnd)
        assert len(timing.used_workers) == dm
        refc = FcdccCluster(pipe.specs[0].plan, None, mode="threads")
        try:
            refc.load_pipeline(pipe)
            ref, _ = refc.run_pipeline_layer(0, x)
        finally:
            refc.shutdown()
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-4)
    finally:
        cluster.shutdown()


def test_device_pool_adaptive_poll_default_and_override():
    """``poll_interval_s=None`` (the default) collects with the adaptive
    5us..1ms backoff; an explicit value is kept verbatim as a fixed period
    (the test override).  Both produce identical results."""
    from repro.runtime.devicepool import DeviceWorkerPool

    assert DeviceWorkerPool._POLL_MIN == pytest.approx(5e-6)
    assert DeviceWorkerPool._POLL_MAX == pytest.approx(1e-3)
    outs = {}
    # forced fastest-delta subset: without it the reap race would pick
    # different (all-correct) shard subsets per run and bits would differ
    straggler, _ = _forced_subset_straggler(_pipe("lenet5"))
    x = np.asarray(RNG.standard_normal(_in_shape(_pipe("lenet5"), 1)),
                   np.float32)
    for label, pool_kwargs in (("adaptive", {}),
                               ("fixed", {"poll_interval_s": 5e-5})):
        pipe = _pipe("lenet5")
        impl = DeviceWorkerPool(N, straggler, **pool_kwargs)
        try:
            assert impl._poll_interval_s == pool_kwargs.get("poll_interval_s")
            cluster = FcdccCluster(pipe.specs[0].plan, None, mode="threads",
                                   pool="device")
            cluster._pool_obj = impl  # inject before the lazy default build
            cluster.load_pipeline(pipe)
            outs[label] = np.asarray(cluster.run_pipeline(x)[0])
        finally:
            cluster.shutdown()
    np.testing.assert_array_equal(outs["adaptive"], outs["fixed"])
