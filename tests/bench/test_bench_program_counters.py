"""The per-layer metrics that read the program's own round counters, and
the jit names the trace reduction looks for."""
import dataclasses
import math
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import runner, spec, trace  # noqa: E402
from test_bench_run import _tiny_cell  # noqa: E402

COUNTER_METRICS = ["subtask_yield.lat", "worker_prep_ms.lat",
                   "worker_wait_ms.lat", "engine_phase_max_ms.lat"]


def test_bench_traced_open_cell_reads_round_counters():
    r = runner.run(_tiny_cell("open"), 2 ** 33 + 7, 1.0, True,
                   process_start=time.perf_counter(), root=ROOT)
    assert r["correct"] is True, r["checks"]
    for name in COUNTER_METRICS:
        v = r["metrics"][name]["value"]
        assert math.isfinite(v) and v > 0, (name, v)
    # delta of n = 8 subtasks used; straggler subtasks may never start
    assert 25.0 <= r["metrics"]["subtask_yield.lat"]["value"] <= 100.0


@dataclasses.dataclass(frozen=True)
class _CountersBefore:
    """``OverlapStats`` as it was before the round counters."""

    rounds: int = 40
    dispatch_s: float = 0.1
    worker_s: float = 0.2
    collect_s: float = 0.1
    transition_s: float = 0.1
    busy_wall_s: float = 1.0
    max_depth: int = 2


@pytest.mark.parametrize("name", COUNTER_METRICS)
@pytest.mark.parametrize("overlap", [_CountersBefore(), None])
def test_bench_counter_readers_without_the_counters(name, overlap):
    read = spec.metric_reader(name, ROOT)
    assert read({"overlap": overlap, "stats": None, "trace": None}) is None


def test_bench_program_names_are_the_trace_reductions():
    """The jit names the trace reduction counts are the program's, and the
    program's jit sites compile under them."""
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import build_cnn_pipeline
    from repro.core.programs import PROGRAMS
    from repro.models.cnn import init_cnn
    from repro.runtime import StragglerModel, ThreadWorkerPool

    assert (trace.WORKER, trace.ENCODE, trace.DECODE, trace.TRANSITION) == \
        tuple("jit_" + PROGRAMS[k]
              for k in ("worker", "encode", "decode", "transition"))
    pipe = build_cnn_pipeline("lenet5", init_cnn("lenet5",
                                                 jax.random.PRNGKey(0)),
                              8, default_kab=(2, 4), fuse_transitions=True)
    x = jnp.zeros((1,) + pipe.input_shape, jnp.float32)
    xe = pipe.encoder(0)(x)
    worker = ThreadWorkerPool(8, StragglerModel.none(8)).program(
        pipe.specs[0].program_key, pipe.layers[0].worker_compute, 0, {})
    outs = jax.eval_shape(worker, xe[0], pipe.coded_filters[0][0])
    q = pipe.specs[0].plan.k_a * pipe.specs[0].plan.k_b
    outs = jnp.zeros((pipe.specs[0].plan.delta,) + outs.shape)
    d = jnp.eye(q)
    lowered = {
        trace.ENCODE: pipe.encoder(0).lower(x),
        trace.WORKER: worker.lower(xe[0], pipe.coded_filters[0][0]),
        trace.TRANSITION: pipe.transition_fn(0).lower(
            outs, d, pipe.encode_columns_all(1)),
        trace.DECODE: pipe.decoder_fn(0).lower(outs, d),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @{name} "), name
