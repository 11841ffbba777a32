"""Operations and bytes of a coded ConvL stack, from its geometry alone.

The yardstick for every roofline and utilization the benchmark reports.
It reads only the configuration file (layer graph, input size, n, k_a,
k_b), never the program, so the work counted stays the same whatever
computes it.  Conventions (FCDCC, arXiv 2411.01579, Sec. IV):

  * uncoded ConvL: ``N * H' * W' * C * K^2`` multiply-adds per image;
  * coded worker subtask: APCP slices the padded input into ``k_a`` row
    blocks of output height ``ceil(H'/k_a)`` (the bottom zero-pad and the
    halo rows are part of the work), KCCP splits the ``N`` filters into
    ``k_b`` groups of ``ceil(N/k_b)``; worker i convolves its ``ell_a``
    coded inputs with its ``ell_b`` coded filter groups, where
    ``ell = 1 if k == 1 else 2``;
  * one round of a layer dispatches ``n`` such subtasks.

A FLOP is a multiply or an add (2 per multiply-add); only the convs count
(no shift, residual add, activation or pool).  Bytes are the least
traffic of one subtask: read its coded inputs and coded filters once,
write its outputs once.
"""
from __future__ import annotations

import dataclasses

from . import spec


@dataclasses.dataclass(frozen=True)
class LayerGeometry:
    name: str
    in_ch: int
    out_ch: int
    kernel: int
    stride: int
    padding: int
    pool: int | spec.Pool
    in_hw: int  # square input side this layer sees

    @property
    def out_hw(self) -> int:
        return (self.in_hw + 2 * self.padding - self.kernel) // self.stride + 1

    @property
    def next_hw(self) -> int:
        """Side of this layer's output, after its pool (floor)."""
        p = self.pool
        if isinstance(p, int):
            return self.out_hw // p if p > 1 else self.out_hw
        if p.op == "avg":
            return 1
        return (self.out_hw + 2 * p.padding - p.size) // p.stride + 1


def ell(k: int) -> int:
    return 1 if k == 1 else 2


def layers(config: dict) -> list[LayerGeometry]:
    """The configuration's ConvLs in pipeline order, each with the input
    side of what its ``from`` names: that entry's output after its pool,
    or the image.  ``ValueError`` where channels or sides do not meet."""
    graph = spec.nodes(config)
    shape = {spec.INPUT: (graph[0].in_ch, int(config["input_hw"]))}
    out = []
    for n in graph:
        ch, hw = shape[n.src]
        g = LayerGeometry(n.name, n.in_ch, n.out_ch, n.kernel, n.stride,
                          n.padding, n.pool, hw)
        if n.in_ch != ch:
            raise ValueError(f"layer {n.name!r}: in_ch {n.in_ch}, but "
                             f"{n.src!r} gives {ch} channels")
        if n.add is not None and shape[n.add] != (n.out_ch, g.out_hw):
            raise ValueError(f"layer {n.name!r}: adds {n.add!r}, (channels, "
                             f"side) {shape[n.add]}, to its own "
                             f"{(n.out_ch, g.out_hw)}")
        out.append(g)
        shape[n.name] = (n.out_ch, g.next_hw)
    return out


def uncoded_macs(g: LayerGeometry) -> int:
    """Multiply-adds of one image through one ConvL, uncoded."""
    return g.out_ch * g.out_hw * g.out_hw * g.in_ch * g.kernel ** 2


def model_flops_per_image(config: dict) -> int:
    """Useful (uncoded) FLOPs of one image through the whole stack."""
    return 2 * sum(uncoded_macs(g) for g in layers(config))


def subtask_shape(g: LayerGeometry, k_a: int, k_b: int) -> dict:
    """Sizes of one worker's coded subtask for one image."""
    out_h_block = -(-g.out_hw // k_a)
    n_block = -(-g.out_ch // k_b)
    return {
        "ell_a": ell(k_a), "ell_b": ell(k_b),
        "out_h_block": out_h_block, "out_w": g.out_hw, "n_block": n_block,
        "h_hat": (out_h_block - 1) * g.stride + g.kernel,
        "w_pad": g.in_hw + 2 * g.padding,
    }


def subtask_work(config: dict, layer: int, batch: int,
                 itemsize: int = 4) -> tuple[int, int]:
    """``(flops, bytes)`` of ONE worker subtask of ``layer`` at ``batch``."""
    g = layers(config)[layer]
    s = subtask_shape(g, config["k_a"], config["k_b"])
    ea, eb = s["ell_a"], s["ell_b"]
    macs = (ea * eb * batch * s["n_block"] * s["out_h_block"] * s["out_w"]
            * g.in_ch * g.kernel ** 2)
    elems = (ea * batch * g.in_ch * s["h_hat"] * s["w_pad"]       # inputs
             + eb * s["n_block"] * g.in_ch * g.kernel ** 2        # filters
             + ea * eb * batch * s["n_block"] * s["out_h_block"]  # outputs
             * s["out_w"])
    return 2 * macs, itemsize * elems


def coded_flops_per_image(config: dict) -> int:
    """FLOPs of the ``n`` worker subtasks per image over the stack."""
    n = config["n"]
    return sum(n * subtask_work(config, i, 1)[0]
               for i in range(len(config["layers"])))
