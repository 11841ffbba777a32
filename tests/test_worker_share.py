"""The thread pool's worker program selects each worker's coded share on
the device.

Covers: every worker's output, in every bucket and layer, equal bit for bit
to the vmapped single-process worker program on the same shares; a round
making no eager ``jax.Array.__getitem__`` call on a worker thread; the
traced worker index adding no traces; and ``program_space`` describing the
cluster worker program by the signature the pool serves.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import build_cnn_pipeline
from repro.models.cnn import init_cnn
from repro.runtime import FcdccCluster, StragglerModel, ThreadWorkerPool

N = 8
BUCKETS = (1, 2, 4)
WORKER_THREAD = "fcdcc-worker-"


def _pipeline():
    params = init_cnn("lenet5", jax.random.PRNGKey(0))
    return build_cnn_pipeline("lenet5", params, N, default_kab=(2, 4),
                              bucket_sizes=BUCKETS)


def _layer_inputs(pipe, bucket, seed=0):
    """A random batch at each layer's input shape."""
    rng = np.random.default_rng(seed)
    shapes = [pipe.input_shape] + [
        (s.geo.out_channels, s.out_hw, s.out_hw) for s in pipe.specs[:-1]]
    return [jnp.asarray(rng.standard_normal((bucket,) + shape), jnp.float32)
            for shape in shapes]


def _cluster(pipe):
    cluster = FcdccCluster(pipe.specs[0].plan, StragglerModel.none(N),
                           mode="threads", pool="threads")
    cluster.load_pipeline(pipe)
    return cluster


@pytest.mark.parametrize("bucket", BUCKETS)
def test_every_worker_matches_the_vmapped_program(bucket):
    """All n outputs of a thread-pool round, each worker's share selected
    inside its program, equal the vmapped worker program's on the same
    stacked shares, bit for bit, in every layer."""
    pipe = _pipeline()
    pool = ThreadWorkerPool(N, StragglerModel.none(N), mode="threads")
    try:
        for idx, x in enumerate(_layer_inputs(pipe, bucket)):
            xe, ke = pipe.encoder(idx)(x), pipe.coded_filters[idx]
            key = pipe.specs[idx].program_key
            raw = pipe.layers[idx].worker_compute

            def fn(i, key=key, raw=raw):
                return pool.program(key, raw, i, pipe._cluster_programs)

            results, _, _ = pool.collect(pool.submit(fn, xe, ke), N)
            want = np.asarray(pipe.worker_program(idx)(xe, ke))
            assert sorted(results) == list(range(N))
            for i in range(N):
                np.testing.assert_array_equal(np.asarray(results[i]),
                                              want[i], err_msg=f"worker {i}")
    finally:
        pool.shutdown()


def test_round_makes_no_eager_indexing_on_worker_threads(monkeypatch):
    """Rounds through the cluster (every layer, every bucket, warmed) index
    no ``jax.Array`` eagerly on a worker thread: the share is selected in
    the compiled program."""
    pipe = _pipeline()
    calls: dict[str, int] = {}
    array_type = type(jnp.zeros(1))
    getitem = array_type.__getitem__

    def counted(self, idx):
        name = threading.current_thread().name
        calls[name] = calls.get(name, 0) + 1
        return getitem(self, idx)

    with _cluster(pipe) as cluster:
        for bucket in BUCKETS:  # warm every program outside the count
            cluster.run_pipeline(_layer_inputs(pipe, bucket)[0])
        monkeypatch.setattr(array_type, "__getitem__", counted)
        jnp.zeros(2)[0]  # the patch sees eager indexing
        assert calls == {threading.current_thread().name: 1}
        for bucket in BUCKETS:
            y, timings = cluster.run_pipeline(
                _layer_inputs(pipe, bucket, seed=1)[0])
            jax.block_until_ready(y)
    assert len(timings) == len(pipe.specs)
    on_workers = {k: v for k, v in calls.items()
                  if k.startswith(WORKER_THREAD)}
    assert on_workers == {}


def test_worker_index_adds_no_traces():
    """Serving all n workers over every bucket compiles no more worker
    programs than serving worker 0 alone: the index is traced, never
    static, so the bound stays geometries x buckets."""
    pipe = _pipeline()
    with _cluster(pipe) as cluster:
        pool = cluster._pool_impl()
        for bucket in BUCKETS:
            for idx, x in enumerate(_layer_inputs(pipe, bucket)):
                xe, ke = pipe.encoder(idx)(x), pipe.coded_filters[idx]
                program = pipe.worker_program(idx, over_workers=False)
                jax.block_until_ready(program(xe, ke, pool._index[0]))
        one_worker = pipe.worker_program_traces
        for bucket in BUCKETS:
            cluster.run_pipeline(_layer_inputs(pipe, bucket)[0])
            for idx, x in enumerate(_layer_inputs(pipe, bucket)):
                xe, ke = pipe.encoder(idx)(x), pipe.coded_filters[idx]
                pending = pool.submit(
                    lambda i, idx=idx: pipe.worker_program(
                        idx, over_workers=False), xe, ke)
                results, _, _ = pool.collect(pending, N)
                assert sorted(results) == list(range(N))
    assert pipe.worker_program_traces == one_worker
    assert one_worker <= pipe.num_geometries * len(BUCKETS)


def test_program_space_cluster_worker_is_the_served_program():
    """The jit-contract gate traces the program the thread pool serves: the
    pool's cache entry, on the stacked shares and an int32 index."""
    pipe = _pipeline()
    pool = ThreadWorkerPool(N, StragglerModel.none(N), mode="simulated")
    cells = [c for c in pipe.program_space() if c.kind == "worker"
             and c.mode == "cluster"]
    assert {c.bucket for c in cells} == set(BUCKETS)
    for cell in cells:
        served = pool.program(cell.cache_key,
                              pipe.layers[cell.layer].worker_compute, 0,
                              pipe._cluster_programs)
        assert cell.fn is served
        xe, ke, index = cell.args
        assert (xe.shape[0], ke.shape[0]) == (N, N)
        assert ke.shape == pipe.coded_filters[cell.layer].shape
        assert (index.shape, index.dtype) == ((), jnp.int32)
        out = jax.eval_shape(cell.fn, *cell.args)
        direct = jax.eval_shape(
            pipe.worker_program(cell.layer),
            jax.ShapeDtypeStruct((1,) + xe.shape[1:], xe.dtype),
            jax.ShapeDtypeStruct((1,) + ke.shape[1:], ke.dtype))
        assert (1,) + out.shape == direct.shape
