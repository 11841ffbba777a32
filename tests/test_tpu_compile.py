"""The served path's Pallas kernels compile for a TPU v5e at real widths.

Each case lowers one kernel through Mosaic for a chip that is described,
not attached (``jax.experimental.topologies``), with ``interpret=False``,
and checks that the compiled program holds the kernel
(``tpu_custom_call``).  Widths are alexnet at its published 227x227 input
on n=8 coded workers with (k_a, k_b) = (2, 4), and smollm-135m's decoder
GEMMs on n=4 workers with k_b=4 — the shapes ``chip_smoke.py`` serves.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and only the worker given this file
loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fcdcc import CodedConv2d
from repro.core.partition import partition_transition
from repro.core.pipeline import plan_layers
from repro.kernels.coded_gemm.kernel import coded_gemm_pallas
from repro.kernels.conv2d.kernel import (coded_transition_pallas,
                                         coded_worker_pallas)
from repro.kernels.matmul.kernel import matmul_pallas
from repro.models.cnn import CNN_SPECS

ALEXNET_HW, ALEXNET_LAYERS = CNN_SPECS["alexnet"]
# smollm-135m: d_model 576, 9 + 2*3 heads of 64, d_ff 1536; a worker's
# coded columns are ell_b=2 blocks of d_out / k_b
SMOLLM_QKV = (576, 2 * (9 + 2 * 3) * 64 // 4)
SMOLLM_DOWN = (1536, 2 * 576 // 4)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _alexnet_worker(layer: int, bucket: int):
    """(spec, one worker's coded-share shape, its filter-group shape, the
    fastest-delta outputs' shape) for an alexnet layer, in shape space."""
    spec = plan_layers(ALEXNET_LAYERS, ALEXNET_HW, 8,
                       default_kab=(2, 4))[layer]
    conv = CodedConv2d(spec.plan, spec.geo)
    geo = spec.geo
    xe = jax.eval_shape(conv.encode_inputs, jax.ShapeDtypeStruct(
        (bucket, geo.in_channels, geo.height, geo.width), jnp.float32))
    ke = jax.eval_shape(conv.encode_filters, jax.ShapeDtypeStruct(
        (geo.out_channels, geo.in_channels, geo.kernel_h, geo.kernel_w),
        jnp.float32))
    delta = spec.plan.delta
    outs = jax.eval_shape(
        jax.vmap(conv.worker_compute),
        jax.ShapeDtypeStruct((delta,) + xe.shape[1:], jnp.float32),
        jax.ShapeDtypeStruct((delta,) + ke.shape[1:], jnp.float32))
    return spec, xe.shape[1:], ke.shape[1:], outs.shape


@pytest.mark.parametrize("layer,bucket", [(0, 1), (0, 8), (1, 1), (1, 8)])
def test_coded_worker_compiles(one_chip, layer, bucket):
    """One worker's coded subtask of alexnet conv1 (11x11, stride 4) or
    conv2 (5x5 over 96 channels), on the path the kernel selects itself."""
    spec, share, filters, _ = _alexnet_worker(layer, bucket)
    _compile(lambda xe, ke: coded_worker_pallas(xe, ke, spec.geo.stride,
                                                interpret=False),
             one_chip, share, filters)


def test_coded_transition_compiles(one_chip):
    """The fused conv1 -> conv2 transition: decode GEMM with ReLU, pool and
    halo re-slice, re-encode GEMM for all n workers."""
    spec, _, _, outs = _alexnet_worker(0, 1)
    nxt = _alexnet_worker(1, 1)[0]
    q = spec.plan.k_a * spec.plan.k_b

    def trans(outs, d, m_next):
        return coded_transition_pallas(
            outs, d, m_next,
            lambda blocks: partition_transition(blocks, spec.geo, spec.pool,
                                                nxt.geo, relu=False),
            interpret=False)

    _compile(trans, one_chip, outs, (q, q),
             (nxt.plan.k_a, nxt.plan.ell_a * nxt.plan.n))


@pytest.mark.parametrize("d_in,d_out", [SMOLLM_QKV, SMOLLM_DOWN],
                         ids=["qkv", "down"])
@pytest.mark.parametrize("num_buffers", [1, 2])
def test_matmul_compiles(one_chip, d_in, d_out, num_buffers):
    """A decoder worker GEMM: a 4-row decode batch against one worker's
    coded weight columns."""
    _compile(lambda a, b: matmul_pallas(a, b, num_buffers=num_buffers,
                                        interpret=False),
             one_chip, (4, d_in), (d_in, d_out))


def test_coded_gemm_compiles(one_chip):
    """The CRME decode GEMM of a coded qkv round: (Q, Q) inverse against
    the Q coded row blocks of a 4-row batch."""
    q = 4
    _compile(lambda c, t: coded_gemm_pallas(c, t, interpret=False),
             one_chip, (q, q), (q, 4 * SMOLLM_QKV[1] // 2))
