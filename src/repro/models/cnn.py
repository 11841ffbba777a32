"""The paper's CNN workloads: LeNet-5, AlexNet, VGG-16 ConvL stacks.

Each network is a list of conv-layer geometries (the paper's experiments
time only the ConvLs).  ``run_convls`` executes the stack either
single-node ("naive") or — when given a plan — as a thin wrapper over the
``repro.core.pipeline.CodedPipeline`` engine (every ConvL coded, filters
encoded once, batched inputs) — this drives the paper reproductions
``benchmarks/exp1..exp5`` and the coded-inference example.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.fcdcc import FcdccPlan
from repro.core.partition import ConvGeometry
from repro.core.pipeline import CodedPipeline, plan_layers, relu_pool


@dataclasses.dataclass(frozen=True)
class ConvL:
    name: str
    in_ch: int
    out_ch: int
    kernel: int
    stride: int = 1
    padding: int = 0
    pool: int = 1  # max-pool factor applied after relu


# (input spatial size, conv layer list) — classic configs
LENET5 = (
    32,
    [
        ConvL("conv1", 1, 6, 5),
        ConvL("conv2", 6, 16, 5, pool=2),
    ],
)

ALEXNET = (
    227,
    [
        ConvL("conv1", 3, 96, 11, stride=4, pool=2),
        ConvL("conv2", 96, 256, 5, padding=2, pool=2),
        ConvL("conv3", 256, 384, 3, padding=1),
        ConvL("conv4", 384, 384, 3, padding=1),
        ConvL("conv5", 384, 256, 3, padding=1, pool=2),
    ],
)

VGG16 = (
    224,
    [
        ConvL("conv1_1", 3, 64, 3, padding=1),
        ConvL("conv1_2", 64, 64, 3, padding=1, pool=2),
        ConvL("conv2_1", 64, 128, 3, padding=1),
        ConvL("conv2_2", 128, 128, 3, padding=1, pool=2),
        ConvL("conv3_1", 128, 256, 3, padding=1),
        ConvL("conv3_2", 256, 256, 3, padding=1),
        ConvL("conv3_3", 256, 256, 3, padding=1, pool=2),
        ConvL("conv4_1", 256, 512, 3, padding=1),
        ConvL("conv4_2", 512, 512, 3, padding=1),
        ConvL("conv4_3", 512, 512, 3, padding=1, pool=2),
        ConvL("conv5_1", 512, 512, 3, padding=1),
        ConvL("conv5_2", 512, 512, 3, padding=1),
        ConvL("conv5_3", 512, 512, 3, padding=1, pool=2),
    ],
)

CNN_SPECS = {"lenet5": LENET5, "alexnet": ALEXNET, "vgg16": VGG16}

# reduced spatial sizes for CPU smoke runs (serve CLI, examples)
SMOKE_HW = {"lenet5": 32, "alexnet": 113, "vgg16": 56}


def input_hw(name: str, smoke: bool = False) -> int:
    """Canonical input resolution of a named CNN (``smoke`` shrinks it)."""
    return SMOKE_HW[name] if smoke else CNN_SPECS[name][0]


def layer_geometry(layer: ConvL, hw: int, k_a: int = 1, k_b: int = 1) -> ConvGeometry:
    return ConvGeometry(
        in_channels=layer.in_ch,
        out_channels=layer.out_ch,
        height=hw,
        width=hw,
        kernel_h=layer.kernel,
        kernel_w=layer.kernel,
        stride=layer.stride,
        padding=layer.padding,
        k_a=k_a,
        k_b=k_b,
    )


def init_cnn(name: str, key, dtype=jnp.float32):
    _, layers = CNN_SPECS[name]
    keys = jax.random.split(key, len(layers))
    return {
        l.name: jax.random.normal(k, (l.out_ch, l.in_ch, l.kernel, l.kernel), dtype)
        * (1.0 / (l.in_ch * l.kernel**2) ** 0.5)
        for k, l in zip(keys, layers)
    }


def run_convls(name: str, params, x, *, plan: FcdccPlan | None = None,
               per_layer_kab: dict | None = None, worker_ids=None, backend="lax"):
    """Run the ConvL stack on one image (C,H,W) or a batch (B,C,H,W).

    ``plan=None`` -> single-node naive execution; otherwise the stack is
    compiled into a ``CodedPipeline`` (filters encoded once, one jitted
    worker program per distinct geometry) with (k_a, k_b) from
    ``per_layer_kab`` (falls back to the plan's defaults).  ``worker_ids``
    are the available workers; each layer decodes from the first delta.
    """
    _, layers = CNN_SPECS[name]
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
    if plan is None:
        for layer in layers:
            y = jax.lax.conv_general_dilated(
                x, params[layer.name],
                window_strides=(layer.stride, layer.stride),
                padding=((layer.padding, layer.padding),) * 2,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
            )
            x = relu_pool(y, layer.pool)
    else:
        specs = plan_layers(
            layers, x.shape[-1], plan.n,
            default_kab=(plan.k_a, plan.k_b), per_layer_kab=per_layer_kab,
        )
        pipe = CodedPipeline(specs, params, backend=backend)
        x = pipe.run(x, worker_ids)
    return x[0] if squeeze else x
