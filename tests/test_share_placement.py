"""The device pool's grouped share placement, on four host devices.

Each round the device pool places every worker's coded share on its
device in one grouped transfer (one block per device, the shares of the
workers pinned to it), and each worker's program selects its share from
its device's block by its slot.  A one-device host puts every worker on
the same device, so this module runs one subprocess on four CPU host
devices (``--xla_force_host_platform_device_count=4``), which writes its
readings as JSON; the tests assert on them.

Covers: every worker's output, in every bucket and layer, equal bit for
bit to the per-worker path (the share sliced on the master device and
``device_put`` to its worker's device), for n = 8 and n = 6 on four
devices; a warmed round making no eager ``jax.Array.__getitem__`` call
and one placement call, and ``share_placements`` counting one a round;
worker traces per device within geometries x buckets; a late worker's
program dispatched no earlier than its delay after submit; and a dead
worker never dispatched.

Run as a script, ``python tests/test_share_placement.py OUT.json`` under
the four-device flag, it writes the readings.
"""
import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICES = 4
BUCKETS = (1, 2)
LATE_S = 0.05


def _layer_inputs(pipe, bucket, seed=0):
    """A random batch at each layer's input shape."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    shapes = [pipe.input_shape] + [
        (s.geo.out_channels, s.out_hw, s.out_hw) for s in pipe.specs[:-1]]
    return [jnp.asarray(rng.standard_normal((bucket,) + shape), jnp.float32)
            for shape in shapes]


def _pipeline(n):
    import jax

    from repro.core.pipeline import build_cnn_pipeline
    from repro.models.cnn import init_cnn

    params = init_cnn("lenet5", jax.random.PRNGKey(0))
    return build_cnn_pipeline("lenet5", params, n, default_kab=(2, 4),
                              bucket_sizes=BUCKETS)


def _parity(n):
    """Each worker's output through the pool against the per-worker path:
    the share sliced on the master device, ``device_put`` to the worker's
    device, and run by a program that takes the share itself."""
    import jax
    import numpy as np

    from repro.core.programs import named
    from repro.runtime import DeviceWorkerPool, StragglerModel

    pipe = _pipeline(n)
    pool = DeviceWorkerPool(n, StragglerModel.none(n),
                            devices=jax.devices()[:DEVICES])
    per_worker, rows = {}, []
    for bucket in BUCKETS:
        for idx, x in enumerate(_layer_inputs(pipe, bucket)):
            xe, ke = pipe.encoder(idx)(x), pipe.coded_filters[idx]
            key = pipe.specs[idx].program_key
            raw = pipe.layers[idx].worker_compute
            blocks = pool.resident_filters(f"layer{idx}", ke)

            def fn(i, key=key, raw=raw):
                return pool.program(key, raw, i)

            results, _, _ = pool.collect(pool.submit(fn, xe, blocks), n)
            equal, placed = 0, 0
            for i in range(n):
                dev = pool.devices[i]
                old = per_worker.setdefault(
                    (key, dev), jax.jit(named(raw, "worker")))
                want = old(jax.device_put(xe[i], dev),
                           jax.device_put(ke[i], dev))
                got = results[i]
                placed += got.devices() == {dev}
                equal += np.array_equal(np.asarray(got), np.asarray(want))
            rows.append(dict(n=n, bucket=bucket, layer=idx, workers=n,
                             equal=equal, on_their_devices=placed))
    pool.shutdown()
    return rows


def _round_dispatches():
    """Warmed rounds through the cluster: eager indexing anywhere, and the
    ``jax.device_put`` calls made inside the pool's ``submit``."""
    import jax
    import jax.numpy as jnp

    from repro.runtime import FcdccCluster, StragglerModel

    n = 8
    pipe = _pipeline(n)
    cluster = FcdccCluster(pipe.specs[0].plan, StragglerModel.none(n),
                           mode="threads", pool="device",
                           devices=jax.devices()[:DEVICES])
    cluster.load_pipeline(pipe)
    impl = cluster._pool_impl()
    for bucket in BUCKETS:  # warm every program outside the count
        cluster.run_pipeline(_layer_inputs(pipe, bucket)[0])

    counts = dict(getitem=0, puts_in_submit=0, submits=0)
    in_submit = []
    array_type = type(jnp.zeros(1))
    getitem, device_put, submit = (array_type.__getitem__, jax.device_put,
                                   impl.submit)

    def counted_getitem(self, idx):
        counts["getitem"] += 1
        return getitem(self, idx)

    def counted_put(*args, **kwargs):
        counts["puts_in_submit"] += bool(in_submit)
        return device_put(*args, **kwargs)

    def counted_submit(*args, **kwargs):
        counts["submits"] += 1
        in_submit.append(True)
        try:
            return submit(*args, **kwargs)
        finally:
            in_submit.pop()

    placements = 0
    array_type.__getitem__ = counted_getitem
    jax.device_put = counted_put
    impl.submit = counted_submit
    try:
        for bucket in BUCKETS:
            y, timings = cluster.run_pipeline(
                _layer_inputs(pipe, bucket, seed=1)[0])
            jax.block_until_ready(y)
            placements += sum(getattr(t, "placements", 0) for t in timings)
    finally:
        array_type.__getitem__ = getitem
        jax.device_put = device_put
        del impl.submit
    traces = impl.program_traces()
    cluster.shutdown()
    return dict(counts, layer_placements=placements,
                traces=sorted(traces.values()), trace_devices=len(traces),
                trace_bound=pipe.num_geometries * len(BUCKETS))


def _served_placements():
    """``share_placements`` and rounds of requests served on the pool."""
    import jax.numpy as jnp

    from repro.runtime import StragglerModel
    from repro.serving import CodedServer

    n = 8
    pipe = _pipeline(n)
    server = CodedServer(pipe, StragglerModel.none(n), mode="threads",
                         pool="device")
    xs = [jnp.full(pipe.input_shape, 0.1 * k, jnp.float32) for k in range(4)]
    with server:
        for h in server.submit_many(xs):
            h.result(timeout=120.0)
    o = server.overlap_stats()
    return dict(rounds=o.rounds,
                share_placements=getattr(o, "share_placements", None))


def _late_and_dead():
    """Worker 3 late by ``LATE_S``, worker 5 dead: when each worker's
    program was picked (its dispatch), after submit."""
    import jax
    import numpy as np

    from repro.runtime import DeviceWorkerPool, StragglerModel

    n, late, dead = 8, 3, 5
    pipe = _pipeline(n)
    delays = np.zeros(n)
    delays[late], delays[dead] = LATE_S, np.inf
    pool = DeviceWorkerPool(n, StragglerModel(delays),
                            devices=jax.devices()[:DEVICES])
    xe, ke = pipe.encoder(0)(_layer_inputs(pipe, 1)[0]), pipe.coded_filters[0]
    blocks = pool.resident_filters("layer0", ke)
    key, raw = pipe.specs[0].program_key, pipe.layers[0].worker_compute
    pool.warm(lambda i: pool.program(key, raw, i), xe, blocks)
    late_after, dead_dispatches, late_results = [], 0, 0
    for _ in range(3):
        picked = {}

        def fn(i):
            picked[i] = time.perf_counter()
            return pool.program(key, raw, i)

        t0 = time.perf_counter()
        pending = pool.submit(fn, xe, blocks)
        pool.collect(pending, pipe.specs[0].plan.delta)
        time.sleep(4 * LATE_S)  # the late worker's deferred dispatch
        with pending.lock:
            late_results += late in pending.results
        late_after.append(picked[late] - t0)
        dead_dispatches += dead in picked
    pool.shutdown()
    return dict(late_after_s=late_after, dead_dispatches=dead_dispatches,
                late_results=late_results)


def _probe(path):
    import jax

    readings = dict(devices=len(jax.devices()),
                    parity=_parity(8) + _parity(6),
                    rounds=_round_dispatches(),
                    served=_served_placements(),
                    timing=_late_and_dead())
    with open(path, "w") as f:
        json.dump(readings, f)


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    out = tmp_path_factory.mktemp("share_placement") / "readings.json"
    flag = f" --xla_force_host_platform_device_count={DEVICES}"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "") + flag,
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, os.path.abspath(__file__), str(out)],
                       capture_output=True, text=True, env=env, timeout=240,
                       cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    with open(out) as f:
        r = json.load(f)
    assert r["devices"] == DEVICES
    return r


@pytest.mark.parametrize("n", [8, 6])
def test_every_worker_matches_the_per_worker_path(readings, n):
    """n = 8 fills two slots on each of four devices; n = 6 pads the
    second slot of two devices with shares no worker computes."""
    rows = [r for r in readings["parity"] if r["n"] == n]
    assert len(rows) == len(BUCKETS) * 2  # lenet5's two coded ConvLs
    for r in rows:
        assert r["equal"] == r["workers"] == n, r
        assert r["on_their_devices"] == n, r


def test_warmed_round_places_shares_once(readings):
    r = readings["rounds"]
    assert r["submits"] == 2 * len(BUCKETS)
    assert r["getitem"] == 0
    assert r["puts_in_submit"] == r["submits"]
    assert r["layer_placements"] == r["submits"]


def test_share_placements_count_rounds(readings):
    s = readings["served"]
    assert s["rounds"] > 0
    assert s["share_placements"] == s["rounds"]


def test_worker_traces_per_device_bounded(readings):
    r = readings["rounds"]
    assert r["trace_devices"] == DEVICES
    assert all(0 < t <= r["trace_bound"] for t in r["traces"]), r


def test_late_worker_dispatched_after_its_delay(readings):
    t = readings["timing"]
    assert t["late_results"] == len(t["late_after_s"])
    assert min(t["late_after_s"]) >= LATE_S


def test_dead_worker_never_dispatched(readings):
    assert readings["timing"]["dead_dispatches"] == 0


if __name__ == "__main__":
    _probe(sys.argv[1])
