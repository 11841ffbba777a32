"""The trace reduction on a synthesized trace with known answers."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "bench"))

from harness import spec  # noqa: E402
from harness.trace import Event, Line, Plane, reduce  # noqa: E402


def _trace():
    host = Plane("/host:CPU", [
        Line("main", [Event("bench_map/0/1", 0, 100),
                      Event("bench_window", 1000, 1000),
                      Event("bench_map/1/1", 2200, 100)]),
        Line("engine", [Event("collect", 1500, 300),
                        Event("decode_matrix", 1500, 100)]),
    ])
    w = "jit_worker_compute"
    modules = Line("XLA Modules", [
        Event(f"{w}(1)", 10, 20, {"program_id": 7}),      # map: layer 0
        Event("jit_encode_inputs(3)", 1000, 100, {"program_id": 3}),
        Event(f"{w}(1)", 1100, 200, {"program_id": 7}),
        Event(f"{w}(1)", 1300, 100, {"program_id": 8}),
        Event(f"{w}(1)", 1400, 50, {"program_id": 9}),    # never mapped
        Event("jit_dec(4)", 1450, 50, {"program_id": 4}),
        Event("jit_dec(4)", 1900, 200, {"program_id": 4}),  # past the end
        Event(f"{w}(1)", 2210, 40, {"program_id": 8}),    # map: layer 1
    ])
    ops = Line("XLA Ops", [
        Event("fusion.1", 1000, 100), Event("convolution.2", 1100, 300),
        Event("convolution.3", 1400, 50), Event("dot.4", 1450, 50),
        Event("dot.4", 1900, 200), Event("copy.5", 500, 100)])
    return [host, Plane("/device:TPU:0", [modules, ops])]


def test_bench_trace_busy_idle_and_programs():
    r = reduce(_trace())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["devices"] == 1
    # [1000, 1500] and [1900, 2000] (the last op clipped at the window)
    assert r["busy_s"] == pytest.approx(600e-9)
    assert r["programs"]["jit_worker_compute"] == pytest.approx(350e-9)
    assert r["programs"]["jit_encode_inputs"] == pytest.approx(100e-9)
    assert r["programs"]["jit_dec"] == pytest.approx(150e-9)
    assert r["program_runs"]["jit_dec"] == 2
    assert dict(r["top_ops"])["convolution.2"] == pytest.approx(300e-9)
    assert "copy.5" not in dict(r["top_ops"])  # before the window


def test_bench_trace_worker_programs_mapped_to_geometry():
    r = reduce(_trace())
    assert r["worker_runs"] == {(0, 1): 1, (1, 1): 1}
    assert r["worker_s"] == pytest.approx(300e-9)
    assert r["worker_unmapped"] == 1


def test_bench_trace_idle_gaps_labelled_by_host_events():
    r = reduce(_trace())
    (label, secs), = r["idle_gaps"]
    assert secs == pytest.approx(400e-9)
    # host threads on average over the gap, per event name
    assert label == "collect x0.75; decode_matrix x0.25"


def test_bench_trace_readers():
    t = reduce(_trace())
    rec = {"trace": t, "peaks": None}

    def read(name, record=rec):
        return spec.metric_reader(name)(record)

    assert read("device_idle.lat") == pytest.approx(40.0)
    assert read("device_idle.tput") == pytest.approx(40.0)
    assert read("codec_device_share.tput") == pytest.approx(100 * 250 / 600)
    assert read("worker_roofline.tput") is None  # no peaks: nothing
    assert read("device_idle.lat", {"trace": None}) is None
