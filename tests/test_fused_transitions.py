"""Partition-resident layer transitions (``fuse_transitions=True``).

Covers: the partition-space helpers (channel rejoin, per-partition
relu/pool with halo exchange, APCP re-slicing) against the merged
reference — bit-exact, since everything is relu/max/slicing; fused
pipeline vs round-trip parity across all CNN_SPECS archs x {lax,
pallas-interpret}; odd/even pool boundaries and degenerate ``k_a=1`` /
``k_b=1`` grids; the bounded-trace contract under ``fuse_transitions``;
the cluster carrying partition-space state across layer rounds under
stragglers; and serving end-to-end with fused transitions under the
dead-worker straggler model, including partition-state coalescing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CodedConv2d, CodedPipeline, ConvGeometry, FcdccPlan
from repro.core.partition import (
    apcp_partition,
    gather_partition_rows,
    merge_output,
    partition_apcp_slices,
    partition_channel_merge,
    partition_relu_pool,
    partition_transition,
)
from repro.core.pipeline import plan_layers, relu_pool
from repro.models.cnn import CNN_SPECS, ConvL, init_cnn
from repro.runtime import FcdccCluster, StragglerModel
from repro.serving import CodedServer

RNG = np.random.default_rng(0)


# -- partition-space helpers ----------------------------------------------
# (geo of layer i, pool, geo of layer i+1): odd out_h with even pool
# (floor-crop), pool windows straddling partition boundaries (hb % pool
# != 0), a pool window spanning >2 partitions (hb=1, pool=3), degenerate
# k_a=1 / k_b=1 axes, stride-2 + padded next layers, and a last partition
# made entirely of adaptive zero-pad rows (out_h=5 on k_a=4 -> hb=2).
TRANSITION_CASES = [
    (ConvGeometry(1, 6, 32, 32, 5, 5, 1, 0, 2, 2), 1,
     ConvGeometry(6, 16, 28, 28, 5, 5, 1, 0, 2, 2)),
    (ConvGeometry(1, 6, 32, 32, 5, 5, 1, 0, 4, 2), 2,
     ConvGeometry(6, 16, 14, 14, 5, 5, 1, 2, 2, 2)),
    (ConvGeometry(3, 8, 13, 13, 3, 3, 1, 0, 4, 2), 2,
     ConvGeometry(8, 8, 5, 5, 3, 3, 2, 1, 2, 1)),
    (ConvGeometry(2, 4, 9, 9, 3, 3, 1, 0, 8, 1), 3,
     ConvGeometry(4, 4, 2, 2, 1, 1, 1, 0, 2, 2)),
    (ConvGeometry(2, 8, 12, 12, 3, 3, 1, 1, 1, 8), 2,
     ConvGeometry(8, 8, 6, 6, 3, 3, 1, 1, 4, 1)),
    (ConvGeometry(2, 4, 7, 7, 3, 3, 1, 0, 4, 2), 1,
     ConvGeometry(4, 4, 5, 5, 3, 3, 1, 1, 4, 1)),
]


@pytest.mark.parametrize("geo,pool,geo_next", TRANSITION_CASES)
@pytest.mark.parametrize("batched", [True, False])
def test_partition_transition_matches_merged_reference(geo, pool, geo_next,
                                                       batched):
    """partition_transition == apcp_partition(relu_pool(merge_output(.)))
    bit-exactly (relu/max/slicing only, no float arithmetic reordered),
    and the two-stage relu_pool + apcp_slices decomposition agrees."""
    q = geo.k_a * geo.k_b
    block = (geo.out_c_block, geo.out_h_block, geo.out_w)
    shape = (q, 3) + block if batched else (q,) + block
    blocks = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    ref = apcp_partition(relu_pool(merge_output(blocks, geo), pool), geo_next)
    got = partition_transition(blocks, geo, pool, geo_next, relu=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the documented two-stage decomposition is the same map
    spatial = jax.nn.relu(partition_channel_merge(blocks, geo))
    pooled, bounds = partition_relu_pool(
        [spatial[a] for a in range(geo.k_a)], geo, pool, relu=False)
    assert sum(hi - lo for lo, hi in bounds) == geo.out_h // pool
    two = partition_apcp_slices(pooled, geo_next)
    np.testing.assert_array_equal(np.asarray(two), np.asarray(ref))


def test_gather_partition_rows_halo_exchange():
    """The halo primitive: any [r0, r1) window of the virtual row stack,
    including windows spanning several ragged partitions."""
    parts = [jnp.arange(6).reshape(1, 3, 2) * (i + 1) for i in range(3)]
    virtual = np.concatenate([np.asarray(p) for p in parts], axis=-2)
    for r0, r1 in [(0, 2), (2, 5), (1, 9), (4, 4), (8, 9)]:
        got = np.asarray(gather_partition_rows(parts, r0, r1))
        np.testing.assert_array_equal(got, virtual[..., r0:r1, :])
    with pytest.raises(AssertionError, match="exceed"):
        gather_partition_rows(parts, 5, 10)


def test_decode_to_partitions_and_encode_from_partitions():
    """The fcdcc entry points: decode-to-grid + merge == decode, and
    encoding pre-sliced parts == encode_inputs on the assembled tensor."""
    plan = FcdccPlan(n=6, k_a=2, k_b=4)
    geo = ConvGeometry(3, 8, 12, 10, 3, 3, 1, 1, 2, 4)
    layer = CodedConv2d(plan, geo)
    x = jnp.asarray(RNG.standard_normal((2, 3, 12, 10)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((8, 3, 3, 3)), jnp.float32)
    xe, ke = layer.encode_inputs(x), layer.encode_filters(k)
    ids = [5, 1]
    outs = jax.vmap(layer.worker_compute)(xe[jnp.asarray(ids)],
                                          ke[jnp.asarray(ids)])
    blocks = layer.decode_to_partitions(ids, outs)
    np.testing.assert_allclose(
        np.asarray(merge_output(blocks, geo)),
        np.asarray(layer.decode(ids, outs)), atol=0)
    parts = apcp_partition(x, geo)
    np.testing.assert_allclose(
        np.asarray(layer.encode_from_partitions(parts)),
        np.asarray(layer.encode_inputs(x)), atol=0)


# -- fused pipeline vs round trip -----------------------------------------
STACK = [
    ConvL("t1", 2, 8, 3, stride=1, padding=1, pool=2),
    ConvL("t2", 8, 8, 3, padding=1),
    ConvL("t3", 8, 8, 3, padding=1, pool=2),
]


def _stack_params(layers, seed=0):
    rng = np.random.default_rng(seed)
    return {
        l.name: jnp.asarray(
            rng.standard_normal((l.out_ch, l.in_ch, l.kernel, l.kernel))
            * (l.in_ch * l.kernel**2) ** -0.5,
            jnp.float32,
        )
        for l in layers
    }


@pytest.mark.parametrize("arch,hw,backend", [
    ("lenet5", 20, "lax"),
    ("lenet5", 20, "pallas"),
    pytest.param("alexnet", 51, "lax", marks=pytest.mark.slow),
    pytest.param("alexnet", 51, "pallas", marks=pytest.mark.slow),
    pytest.param("vgg16", 32, "lax", marks=pytest.mark.slow),
    pytest.param("vgg16", 32, "pallas", marks=pytest.mark.slow),
])
def test_fused_pipeline_matches_roundtrip(arch, hw, backend):
    """The acceptance contract: fuse_transitions=True is allclose (fp32)
    with the round-trip path on every CNN_SPECS arch, on both backends,
    with worker + transition traces bounded by (geometries + transitions)
    x buckets."""
    params = init_cnn(arch, jax.random.PRNGKey(0))
    specs = plan_layers(CNN_SPECS[arch][1], hw, 6, default_kab=(2, 4))
    c0 = CNN_SPECS[arch][1][0].in_ch
    x = jnp.asarray(RNG.standard_normal((2, c0, hw, hw)), jnp.float32)
    ref = np.asarray(CodedPipeline(specs, params).run(x))
    fused = CodedPipeline(specs, params, backend=backend, bucket_sizes=(2,),
                          fuse_transitions=True)
    y = np.asarray(fused.run(x))
    assert y.shape == ref.shape
    tol = 5e-3 if backend == "pallas" else 1e-4
    np.testing.assert_allclose(y, ref, rtol=tol, atol=tol)
    # the serving fast path threads partition-space state the same way
    yp = np.asarray(fused.run_prepared(x, worker_ids=[5, 1, 3, 0]))
    np.testing.assert_allclose(yp, ref, rtol=tol, atol=tol)
    traces = fused.worker_program_traces + fused.transition_program_traces
    assert traces <= fused.program_trace_bound
    # repeated transition geometries (e.g. VGG conv blocks) share programs
    assert 1 <= fused.num_transitions <= len(specs) - 1
    assert len(fused._transitions) == fused.num_transitions
    assert fused.filter_encode_calls == len(specs)  # encode-once held


def test_fused_degenerate_grids_and_survivor_invariance():
    """k_a=1 (channel-only) and k_b=1 (spatial-only) layers mixed in one
    fused stack; every survivor subset decodes to the same output."""
    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 4,
                        per_layer_kab={"t1": (1, 8), "t2": (8, 1)},
                        default_kab=(2, 2))
    x = jnp.asarray(RNG.standard_normal((3, 2, 16, 16)), jnp.float32)
    ref = np.asarray(CodedPipeline(specs, params).run(x))
    fused = CodedPipeline(specs, params, fuse_transitions=True)
    np.testing.assert_allclose(np.asarray(fused.run(x)), ref,
                               rtol=1e-4, atol=1e-4)
    for ids in ([3, 2, 1, 0], [1, 3, 0, 2]):
        np.testing.assert_allclose(
            np.asarray(fused.run_prepared(x, worker_ids=ids)), ref,
            rtol=1e-4, atol=1e-4)


def test_fused_bounded_traces_across_buckets():
    """Serving many distinct request-batch sizes leaves worker + transition
    traces bounded by (geometries + transitions) x buckets."""
    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 6, default_kab=(2, 4))
    fused = CodedPipeline(specs, params, bucket_sizes=(1, 2, 4),
                          fuse_transitions=True)
    for b in (1, 2, 3, 4, 3, 2, 1):
        x = jnp.asarray(RNG.standard_normal((b, 2, 16, 16)), jnp.float32)
        padded, real = fused.pad_to_bucket(x)
        fused.run(padded)
    traces = fused.worker_program_traces + fused.transition_program_traces
    assert traces <= fused.program_trace_bound
    assert fused.transition_program_traces <= \
        fused.num_transitions * len(fused.bucket_sizes)


def test_pad_to_bucket_partition_axis():
    """Mid-stack coded-share state pads on its batch axis (2) — zero
    shares, identical to encoding a zero activation."""
    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 6, default_kab=(2, 4))
    pipe = CodedPipeline(specs, params, bucket_sizes=(4,))
    xe = jnp.asarray(RNG.standard_normal((6, 2, 3, 2, 9, 18)), jnp.float32)
    padded, real = pipe.pad_to_bucket(xe, axis=2)
    assert padded.shape == (6, 2, 4, 2, 9, 18) and real == 3
    np.testing.assert_array_equal(np.asarray(padded[:, :, 3]), 0.0)
    np.testing.assert_array_equal(np.asarray(padded[:, :, :3]),
                                  np.asarray(xe))


# -- cluster: partition-space state across layer rounds --------------------
def test_cluster_fused_run_pipeline_under_stragglers():
    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 6, default_kab=(2, 4))
    ref = CodedPipeline(specs, params)
    fused = CodedPipeline(specs, params, fuse_transitions=True)
    delays = np.zeros(6)
    delays[1] = 5.0          # straggler
    delays[4] = np.inf       # dead worker
    cluster = FcdccCluster(FcdccPlan(n=6, k_a=2, k_b=4),
                           StragglerModel(delays), mode="simulated")
    cluster.load_pipeline(fused)
    x = jnp.asarray(RNG.standard_normal((2, 2, 16, 16)), jnp.float32)
    y, timings = cluster.run_pipeline(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.run(x)),
                               rtol=1e-4, atol=1e-4)
    assert len(timings) == len(STACK)
    for t in timings:
        assert 1 not in t.used_workers and 4 not in t.used_workers
    # mid-stack rounds never ran a separate encode: the transition fused it
    assert [t.encode_s == 0.0 for t in timings] == [False, True, True]


def test_cluster_fused_threads_mode_partition_state():
    """Threads mode: the coded-share state produced by round i feeds round
    i+1's per-worker dispatch, and the fastest-delta subset may differ per
    round."""
    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 6, default_kab=(2, 4))
    ref = CodedPipeline(specs, params)
    fused = CodedPipeline(specs, params, fuse_transitions=True)
    with FcdccCluster(FcdccPlan(n=6, k_a=2, k_b=4), StragglerModel.none(6),
                      mode="threads") as cluster:
        cluster.load_pipeline(fused)
        x = jnp.asarray(RNG.standard_normal((2, 2, 16, 16)), jnp.float32)
        y, _ = cluster.run_pipeline(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.run(x)),
                               rtol=1e-4, atol=1e-4)


# -- serving: fused end-to-end ---------------------------------------------
def _images(count, hw=16, c=2):
    return [jnp.asarray(RNG.standard_normal((c, hw, hw)), jnp.float32)
            for _ in range(count)]


@pytest.mark.parametrize("mode", ["simulated", "threads"])
def test_serving_fused_dead_worker(mode):
    """End-to-end serving over fused transitions under the dead-worker
    straggler model, on the simulated clock and on the thread pool:
    results match the round-trip pipeline, traces stay bounded."""
    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 6, default_kab=(2, 4))
    ref = CodedPipeline(specs, params)
    fused = CodedPipeline(specs, params, bucket_sizes=(1, 2, 4),
                          fuse_transitions=True)
    delays = np.zeros(6)
    delays[2] = np.inf  # dead worker
    server = CodedServer(fused, StragglerModel(delays), mode=mode)
    server.warmup()
    xs = _images(5)
    with server:
        outs = [h.result(timeout=60.0) for h in server.submit_many(xs)]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref.run(x)),
                                   rtol=1e-4, atol=1e-4)
    traces = fused.worker_program_traces + fused.transition_program_traces
    assert traces <= fused.program_trace_bound


def test_serving_fused_coalesces_partition_state():
    """Two fragment batches admitted separately at layer 0 coalesce while
    mid-stack batches carry partition-space state — merged results still
    match per-request references."""
    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 6, default_kab=(2, 4))
    ref = CodedPipeline(specs, params)
    fused = CodedPipeline(specs, params, bucket_sizes=(1, 2, 4),
                          fuse_transitions=True)
    server = CodedServer(fused, StragglerModel.none(6), mode="simulated")
    xs = _images(2)
    sched = server.scheduler["default"]
    handles = []
    for x in xs:
        handles.append(sched.queue.submit(jnp.asarray(x, fused.input_dtype)))
        assert sched.admit() is not None
    assert [b.real for b in sched.inflight] == [1, 1]
    with server:
        outs = [h.result(timeout=60.0) for h in handles]
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref.run(x)),
                                   rtol=1e-4, atol=1e-4)
    assert server.stats().coalesced == 1


def test_scheduler_coalesce_on_partition_axis():
    """Unit-level: equal-depth batches whose state is coded shares (batch
    axis 2) merge by slicing/concatenating that axis and re-padding with
    zero shares."""
    from repro.serving.scheduler import ScheduledBatch, Scheduler

    params = _stack_params(STACK)
    specs = plan_layers(STACK, 16, 6, default_kab=(2, 4))
    pipe = CodedPipeline(specs, params, bucket_sizes=(1, 2, 4))
    sched = Scheduler(pipe.pad_to_bucket, max_batch=4, max_inflight=4)

    def share_batch(reqs, real):
        x = jnp.asarray(RNG.standard_normal((6, 2, real, 2, 9, 18)),
                        jnp.float32)
        return ScheduledBatch(requests=list(reqs), x=x, bucket=real,
                              layer_idx=1, batch_axis=2)

    b1, b2 = share_batch(["r0"], 1), share_batch(["r1", "r2"], 2)
    x1, x2 = np.asarray(b1.x), np.asarray(b2.x)
    sched.inflight.extend([b1, b2])
    assert sched.coalesce() == 1
    (merged,) = sched.inflight
    assert merged.batch_axis == 2
    assert merged.bucket == 4 and merged.real == 3  # 3 -> bucket 4
    assert merged.requests == ["r0", "r1", "r2"]
    got = np.asarray(merged.x)
    np.testing.assert_array_equal(got[:, :, 0], x1[:, :, 0])
    np.testing.assert_array_equal(got[:, :, 1:3], x2[:, :, :2])
    np.testing.assert_array_equal(got[:, :, 3], 0.0)