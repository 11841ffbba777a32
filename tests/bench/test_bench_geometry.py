"""The benchmark's FLOP and byte counts against hand counts."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from harness import geometry  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,gmac", [("alexnet-n8", 1.08),
                                       ("vgg16-n8", 15.3)])
def test_bench_uncoded_macs_per_image(name, gmac):
    cfg = _config(name)
    macs = geometry.model_flops_per_image(cfg) / 2
    assert macs / 1e9 == pytest.approx(gmac, rel=0.01)


def test_bench_alexnet_layer_by_hand():
    g = geometry.layers(_config("alexnet-n8"))
    assert [l.in_hw for l in g] == [227, 27, 13, 13, 13]
    # conv1: 96 filters of 3x11x11 over a 55x55 output
    assert geometry.uncoded_macs(g[0]) == 96 * 55 * 55 * 3 * 121


@pytest.mark.parametrize("name", ["alexnet-n8", "vgg16-n8"])
def test_bench_coded_work_is_redundancy_times_uncoded(name):
    cfg = _config(name)
    n, k_a, k_b = cfg["n"], cfg["k_a"], cfg["k_b"]
    redundancy = n * geometry.ell(k_a) * geometry.ell(k_b) / (k_a * k_b)
    assert redundancy == 4
    for i, g in enumerate(geometry.layers(cfg)):
        flops, nbytes = geometry.subtask_work(cfg, i, 1)
        h_block = -(-g.out_hw // k_a)
        n_block = -(-g.out_ch // k_b)
        assert flops == 2 * 4 * n_block * h_block * g.out_hw * g.in_ch \
            * g.kernel ** 2
        # APCP's bottom pad rounds H' up to a multiple of k_a: never less
        # than redundancy x the uncoded work, and equal when it divides
        ratio = n * flops / (2 * geometry.uncoded_macs(g))
        exact = g.out_hw % k_a == 0 and g.out_ch % k_b == 0
        assert ratio == pytest.approx(redundancy) if exact \
            else ratio > redundancy
        assert nbytes > 0
    assert geometry.coded_flops_per_image(cfg) >= \
        redundancy * geometry.model_flops_per_image(cfg)


def test_bench_subtask_bytes_by_hand():
    cfg = _config("alexnet-n8")
    g = geometry.layers(cfg)[2]  # conv3: 256 -> 384, 3x3, pad 1, 13x13
    s = geometry.subtask_shape(g, 2, 4)
    assert (s["out_h_block"], s["n_block"], s["h_hat"], s["w_pad"]) == \
        (7, 96, 9, 15)
    _, nbytes = geometry.subtask_work(cfg, 2, 4)
    assert nbytes == 4 * (2 * 4 * 256 * 9 * 15 + 2 * 96 * 256 * 9
                          + 4 * 4 * 96 * 7 * 13)
