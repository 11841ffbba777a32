"""A whole run on the CPU at a tiny size, with the timed path sound and
broken; the correctness control; and the refusal to run without a TPU."""
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from harness import cell as cells  # noqa: E402
from harness import reference, runner, spec  # noqa: E402

LENET = [dict(name="conv1", in_ch=1, out_ch=6, kernel=5),
         dict(name="conv2", in_ch=6, out_ch=16, kernel=5, pool=2)]


def _tiny_cell(loop: str) -> spec.Cell:
    """The alexnet-n8 deployment (n=8, (2, 4), buckets, f32, limit) on the
    program's lenet5 ConvLs at 32x32, which a test run can hold."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "alexnet-n8.json")) as f:
        cfg = json.load(f)
    cfg.update(name="lenet5-test", arch="lenet5", input_hw=32, layers=LENET)
    if loop == "open":
        tr = dict(loop="open", rate_per_s=40.0, image_pool=6, lead_in_s=0.3,
                  stragglers=dict(count=2, delay_s=0.005))
        wl = "alexnet-n8.poisson"
    else:
        tr = dict(loop="closed", clients=4, image_pool=6, lead_in_s=0.3,
                  stragglers=dict(count=0, delay_s=0.0))
        wl = "vgg16-n8.closed16"
    real = spec.resolve(wl, ROOT)
    return spec.Cell("tiny", 1, cfg, tr, real.end_to_end, real.per_layer)


def _run(loop: str, trace: bool = False) -> dict:
    return runner.run(_tiny_cell(loop), 2 ** 33 + 1, 1.0, trace,
                      process_start=time.perf_counter(), root=ROOT)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_bench_run_tiny_cell_is_correct(loop):
    r = _run(loop)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    names = set(r["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert all(v["value"] is not None and v["value"] > 0
               for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["max_rel_err"]["value"] <= \
        r["checks"]["max_rel_err"]["limit"]


def test_bench_open_loop_window_compiles_nothing():
    """Set-up warms every batch shape an open loop brings, so even a window
    with no lead-in compiles no program."""
    c = _tiny_cell("open")
    served = cells.ServedCell(c.config, dict(c.traffic, lead_in_s=0.0), 3)
    try:
        served.setup()
        w = served.window(1.5)
    finally:
        served.close()
    assert len(w.due()) > 20
    assert w.compiles == []


def test_bench_run_tiny_cell_traced_reads_host_layers():
    r = _run("closed", trace=True)
    assert r["correct"] is True
    # no device plane on the CPU: device metrics stay silent, host ones read
    assert set(r["metrics"]) == {"mean_batch", "round_host_ms.tput"}
    assert r["device"]["window_s"] > 0


def test_bench_window_compile_is_not_correct(monkeypatch):
    """Without set-up's warm-up of the batch-assembly shapes an open loop
    compiles inside the window, and the run reads incorrect for that alone
    (36x36 images: shapes no other test here has compiled)."""
    monkeypatch.setattr(cells.ServedCell, "_warm_batch_shapes",
                        lambda self: None)
    c = _tiny_cell("open")
    c = dataclasses.replace(c, config=dict(c.config, input_hw=36),
                            traffic=dict(c.traffic, lead_in_s=0.0))
    r = runner.run(c, 11, 1.0, False, process_start=time.perf_counter(),
                   root=ROOT)
    checks = r["checks"]
    assert checks["compiles_in_window"]["value"] > 0
    assert checks["max_rel_err"]["value"] <= checks["max_rel_err"]["limit"]
    assert r["correct"] is False


@pytest.mark.parametrize("errors,missing,compiles,correct", [
    ([1e-6, 2e-6], 0, 0, True),
    ([1e-6, 9e-6], 0, 0, False),
    ([1e-6, np.inf], 0, 0, False),
    ([], 0, 0, False),
    ([1e-6], 1, 0, False),
    ([1e-6], 0, 1, False),
])
def test_bench_judge(errors, missing, compiles, correct):
    ok, checks = runner.judge(np.asarray(errors), missing, compiles, 8e-6)
    assert ok is correct
    assert list(checks) == ["max_rel_err", "unanswered", "compiles_in_window"]
    assert all(set(c) == {"value", "limit"} for c in checks.values())


def _altered_answer(monkeypatch):
    """The last layer's decode returns its answer scaled by 1 + 1e-3."""
    from repro.core.pipeline import CodedPipeline

    orig = CodedPipeline.decoder_fn

    def decoder_fn(self, idx):
        fn = orig(self, idx)
        if idx < len(self.specs) - 1:
            return fn
        return lambda outs, d: fn(outs, d) * (1 + 1e-3)

    monkeypatch.setattr(CodedPipeline, "decoder_fn", decoder_fn)


def _wrong_survivors(monkeypatch):
    """Decode with the inverse of another subset than the one collected."""
    from repro.core.pipeline import CodedPipeline

    orig = CodedPipeline.decode_matrix

    def decode_matrix(self, idx, worker_ids):
        others = tuple((i + 1) % self.n for i in worker_ids)
        return orig(self, idx, others)

    monkeypatch.setattr(CodedPipeline, "decode_matrix", decode_matrix)


def _lost_answer(monkeypatch):
    """The engine never delivers one request of each completed batch."""
    from repro.serving.engine import CodedServer

    orig = CodedServer._complete

    def _complete(self, state, batch):
        batch.requests.pop()
        return orig(self, state, batch)

    monkeypatch.setattr(CodedServer, "_complete", _complete)
    monkeypatch.setattr(cells, "ANSWER_GRACE_S", 1.0)


@pytest.mark.parametrize("fault", [_altered_answer, _wrong_survivors,
                                   _lost_answer])
def test_bench_run_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = _run("open")
    assert r["correct"] is False


class _Answered:
    """A finished request's handle, holding its answer."""

    latency_s = 1e-3

    def __init__(self, out):
        self._out = out

    def done(self):
        return True

    def result(self, timeout=None):
        return self._out


class _ControlServer:
    """The control in the program's place: the plain reference one
    precision lower (three bf16 passes, spelled out, since a CPU computes
    ``Precision.HIGH`` in full float32), behind the server's surface."""

    scheduler = None

    def __init__(self, config):
        self.config = config

    def register_model(self, model, params):
        self.params = params

    def _answer(self, image):
        return reference.forward(self.config, self.params,
                                 np.asarray(image)[None],
                                 precision="high_emulated", block=1)[0]

    def warmup(self):
        cfg = self.config
        self._answer(np.zeros((cfg["layers"][0]["in_ch"], cfg["input_hw"],
                               cfg["input_hw"]), np.float32))

    def start(self):
        pass

    def submit(self, image, model):
        return _Answered(self._answer(image))

    def shutdown(self, drain):
        pass


@pytest.mark.parametrize("workload", ["alexnet-n8.poisson",
                                      "vgg16-n8.closed16"])
def test_bench_control_fails_the_limit(monkeypatch, workload):
    """With the control's answers in place of the served ones, at the
    configuration's own sizes, the run's own comparison finds it not
    correct, and for its error alone.  The traffic is thinned (the control
    answers on the CPU) and the pool is the two images of seed 5."""
    import repro.core.pipeline
    import repro.serving

    real = spec.resolve(workload, ROOT)
    tr = dict(real.traffic, image_pool=2, lead_in_s=0.0)
    tr.update(rate_per_s=4.0) if tr["loop"] == "open" else tr.update(
        clients=1)
    monkeypatch.setattr(repro.serving, "CodedServer",
                        lambda **kw: _ControlServer(real.config))
    monkeypatch.setattr(repro.core.pipeline, "build_cnn_pipeline",
                        lambda arch, params, n, **kw: params)
    cell = spec.Cell(workload, 1, real.config, tr, real.end_to_end,
                     real.per_layer)
    r = runner.run(cell, 5, 0.5, False, process_start=time.perf_counter(),
                   root=ROOT)
    checks = r["checks"]
    assert r["correct"] is False
    assert checks["max_rel_err"]["value"] > checks["max_rel_err"]["limit"]
    assert checks["unanswered"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0


def test_bench_failed_setup_closes_cleanly():
    """A set-up that fails before the server exists (no program to import,
    a bad layer table) leaves close() nothing to undo."""
    c = _tiny_cell("open")
    served = cells.ServedCell(dict(c.config, layers=LENET[:1]), c.traffic, 1)
    with pytest.raises(ValueError, match="differ"):
        served.setup()
    served.close()


def test_bench_run_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "alexnet-n8.poisson", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=240, cwd=ROOT)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr


_DEVICE_POOL_RUNS = """
import json
import sys

sys.path.insert(0, sys.argv[1])
import jax
import jax.numpy as jnp
import test_bench_run as t
from repro.runtime.devicepool import DeviceWorkerPool

submits = []
submit = DeviceWorkerPool.submit


def counted(self, *args, **kwargs):
    submits.append(1)
    return submit(self, *args, **kwargs)


DeviceWorkerPool.submit = counted
sound = t._run("open")
# the exchange left out: no surviving shard reaches the master device,
# which decodes from buffers that nothing filled
DeviceWorkerPool.gather = lambda self, arr: jax.device_put(
    jnp.zeros(arr.shape, arr.dtype), self.master)
broken = t._run("open")
print(json.dumps(dict(devices=len(jax.devices()), submits=len(submits),
                      sound=sound["correct"], broken=broken["correct"],
                      checks=broken["checks"])))
"""


def test_bench_run_device_pool_without_the_exchange_is_not_correct():
    """On four host devices the program picks the device pool, as it does
    on four chips: the tiny cell is correct there, and is not once the
    fastest shards are no longer moved from their devices to the master."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"),
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, "-c", _DEVICE_POOL_RUNS,
         os.path.dirname(os.path.abspath(__file__))],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.splitlines()[-1])
    assert r["devices"] == 4 and r["submits"] > 0
    assert r["sound"] is True
    checks = r["checks"]
    assert r["broken"] is False
    assert checks["max_rel_err"]["value"] > checks["max_rel_err"]["limit"]
    assert checks["unanswered"]["value"] == 0
