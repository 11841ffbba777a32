"""FCDCC: the end-to-end coded distributed convolution layer (Sec. IV).

Pipeline (Fig. 1):
  APCP(X) -> encode with A      KCCP(K) -> encode with B   (master)
  worker i: 4 pairwise convs of its 2 coded inputs x 2 coded filters
  master: pick any delta workers, invert E, decode, merge.

Two execution paths share the same math:
  * ``run_simulated`` — vmap over the worker axis on one device; straggler
    subsets selected explicitly (used by tests, the paper reproductions
    and the master/worker runtime in ``repro.runtime``).
  * ``run_sharded`` — ``shard_map`` over a mesh "workers" axis: each device
    computes its coded subtask, coded outputs are all-gathered (they are
    Q/n-sized each, so this is the paper's "download" phase as an ICI
    collective) and decoded identically on every shard.

Both paths are batch-native: ``x`` may be ``(C, H, W)`` or ``(B, C, H, W)``;
a whole batch flows through one coded program (the batch rides inside each
worker's subtask, so the code/decode algebra is unchanged).  This is what
``repro.core.pipeline.CodedPipeline`` builds on to stream multi-layer CNNs
through a persistent coded cluster.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import full_f32

from .crme import CrmeAxisCode, make_axis_codes, next_odd, recovery_matrix
from .nsctc import decode_blocks, encode_tensor_list, group_by_worker
from .partition import (
    ConvGeometry,
    apcp_partition,
    block_output_shape,
    kccp_partition,
    merge_output,
)

__all__ = ["FcdccPlan", "CodedConv2d"]


@dataclasses.dataclass(frozen=True)
class FcdccPlan:
    """Static plan: worker count, partition factors, derived code params."""

    n: int
    k_a: int
    k_b: int
    q: int | None = None

    def __post_init__(self):
        make_axis_codes(self.k_a, self.k_b, self.n, self.q)  # validate

    @property
    def codes(self) -> tuple[CrmeAxisCode, CrmeAxisCode]:
        return make_axis_codes(self.k_a, self.k_b, self.n, self.q)

    @property
    def ell_a(self) -> int:
        return 1 if self.k_a == 1 else 2

    @property
    def ell_b(self) -> int:
        return 1 if self.k_b == 1 else 2

    @property
    def delta(self) -> int:
        """Recovery threshold (eq. of Sec. II-A, with degenerate-axis rule)."""
        return (self.k_a * self.k_b) // (self.ell_a * self.ell_b)

    @property
    def gamma(self) -> int:
        return self.n - self.delta


def _conv_valid(x, k, stride, backend="lax"):
    """VALID conv of one coded block pair: x ([B,]C,H,W) * k (N,C,KH,KW)."""
    batched = x.ndim == 4
    if backend == "pallas":
        from repro.kernels.conv2d.ops import conv2d_im2col

        if batched:
            return jax.vmap(lambda xi: conv2d_im2col(xi, k, stride))(x)
        return conv2d_im2col(x, k, stride)
    y = jax.lax.conv_general_dilated(
        x if batched else x[None],
        k,
        window_strides=(stride, stride),
        padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return y if batched else y[0]


class CodedConv2d:
    """One FCDCC-coded convolution layer.

    ``plan`` fixes (n, k_a, k_b); ``geo`` fixes the conv geometry. The filter
    is encoded once (``encode_filters``) and cached — matching the paper's
    deployment where coded filters are pre-stored on workers.
    """

    def __init__(self, plan: FcdccPlan, geo: ConvGeometry, backend: str = "lax",
                 fused_worker: bool = True):
        if geo.k_a != plan.k_a or geo.k_b != plan.k_b:
            geo = dataclasses.replace(geo, k_a=plan.k_a, k_b=plan.k_b)
        self.plan = plan
        self.geo = geo
        self.backend = backend
        self.fused_worker = fused_worker
        self.a_code, self.b_code = plan.codes
        # instrumentation: CodedPipeline/tests assert encode-once semantics
        self.filter_encode_calls = 0

    # -- master side: encode ---------------------------------------------
    @full_f32
    def encode_inputs(self, x: jnp.ndarray, matrix=None) -> jnp.ndarray:
        """([B,]C,H,W) -> coded inputs (n, ell_a, [B,] C, h_hat, W+2p).

        ``matrix`` overrides the A-code encoding matrix — pass a column
        subset (``(k_a, ell_a*m)``, possibly a traced array) to encode only
        m selected workers' shares instead of all n.
        """
        parts = apcp_partition(x, self.geo)
        coded = encode_tensor_list(
            parts, self.a_code.matrix if matrix is None else matrix
        )
        return group_by_worker(coded, self.a_code.ell)

    @full_f32
    def encode_from_partitions(self, parts: jnp.ndarray, matrix=None) -> jnp.ndarray:
        """Encode pre-sliced APCP parts ``(k_a, [B,] C, h_hat, W+2p)``.

        The partition-resident transition path: layer *i+1*'s parts are
        assembled directly from layer *i*'s decoded partitions
        (``repro.core.partition.partition_transition``), so the
        ``apcp_partition`` step of ``encode_inputs`` is skipped.  ``matrix``
        as in ``encode_inputs``.
        """
        assert parts.shape[0] == self.plan.k_a, (parts.shape, self.plan)
        coded = encode_tensor_list(
            parts, self.a_code.matrix if matrix is None else matrix
        )
        return group_by_worker(coded, self.a_code.ell)

    @full_f32
    def encode_filters(self, k: jnp.ndarray) -> jnp.ndarray:
        """(N,C,KH,KW) -> coded filters (n, ell_b, N/k_b, C, KH, KW)."""
        self.filter_encode_calls += 1
        parts = kccp_partition(k, self.geo)
        coded = encode_tensor_list(parts, self.b_code.matrix)
        return group_by_worker(coded, self.b_code.ell)

    # -- worker side -------------------------------------------------------
    @full_f32
    def worker_compute(self, xe_i: jnp.ndarray, ke_i: jnp.ndarray) -> jnp.ndarray:
        """Coded subtask of one worker (Algorithm 4 lines 6-11).

        ``xe_i``: (ell_a, [B,] C, h_hat, Wp); ``ke_i``: (ell_b, N/k_b, C, KH, KW).
        Returns (ell_a*ell_b, [B,] N/k_b, H'/k_a, W'), slot ``ell_b*b1 + b2``.

        §Perf (beyond paper): the ell_a*ell_b pairwise convolutions are
        fused into ONE batched conv — coded inputs (x the request batch) as
        the batch dim, coded filters concatenated along output channels — a
        single bigger GEMM instead of 4 small ones (set ``fused_worker=False``
        for the paper-literal loop).  Both backends take the fused path:
        ``lax`` as one ``conv_general_dilated``, ``pallas`` as one im2col +
        one MXU-tiled GEMM (``coded_worker_pallas``).
        """
        if not self.fused_worker:
            outs = []
            for b1 in range(self.plan.ell_a):
                for b2 in range(self.plan.ell_b):
                    outs.append(
                        _conv_valid(xe_i[b1], ke_i[b2], self.geo.stride,
                                    self.backend)
                    )
            return jnp.stack(outs, axis=0)
        if self.backend == "pallas":
            from repro.kernels.conv2d.ops import coded_worker

            return coded_worker(xe_i, ke_i, self.geo.stride)
        ea, eb = self.plan.ell_a, self.plan.ell_b
        nb = ke_i.shape[1]
        k_cat = ke_i.reshape((eb * nb,) + ke_i.shape[2:])
        batched = xe_i.ndim == 5
        b = xe_i.shape[1] if batched else 1
        xin = xe_i.reshape((ea * b,) + xe_i.shape[-3:]) if batched else xe_i
        y = jax.lax.conv_general_dilated(
            xin,
            k_cat,
            window_strides=(self.geo.stride, self.geo.stride),
            padding="VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )  # (ell_a[*B], ell_b*nb, H', W')
        if not batched:
            return y.reshape((ea * eb, nb) + y.shape[2:])
        y = y.reshape((ea, b, eb, nb) + y.shape[2:])
        return jnp.transpose(y, (0, 2, 1, 3, 4, 5)).reshape(
            (ea * eb, b, nb) + y.shape[4:]
        )

    # -- master side: decode ------------------------------------------------
    @full_f32
    def decode_to_partitions(self, worker_ids, outputs: jnp.ndarray) -> jnp.ndarray:
        """Any-delta decode to the partition grid — merge deliberately
        skipped.

        ``outputs``: (delta, ell2, *block) with block
        ``([B,] N/k_b, H'/k_a, W')``.  Returns the A-major
        ``(k_a*k_b, *block)`` grid — the partition-resident transition path
        (``CodedPipeline`` with ``fuse_transitions=True``) threads this
        straight into the next layer's re-encode without ever assembling
        the full ``([B,] N, H', W')`` tensor.
        """
        blocks = decode_blocks(
            self.a_code,
            self.b_code,
            worker_ids,
            outputs,
            outputs.shape[2:],
        )
        assert blocks.shape[-3:] == block_output_shape(self.geo)
        return blocks

    def decode(self, worker_ids, outputs: jnp.ndarray) -> jnp.ndarray:
        """Any-delta decode + merge.

        ``outputs``: (delta, ell2, *block) where block is
        ``([B,] N/k_b, H'/k_a, W')`` — the batch dim (if any) just rides
        inside the decoded rows.
        """
        blocks = self.decode_to_partitions(worker_ids, outputs)
        return merge_output(blocks, self.geo)

    # -- end-to-end paths ----------------------------------------------------
    def run_simulated(self, x, k, worker_ids=None):
        """Single-device end-to-end run; ``worker_ids`` are the survivors."""
        ids = list(range(self.plan.delta)) if worker_ids is None else list(worker_ids)
        xe = self.encode_inputs(x)
        ke = self.encode_filters(k)
        idx = jnp.asarray(ids)
        outs = jax.vmap(self.worker_compute)(xe[idx], ke[idx])
        return self.decode(ids, outs)

    def run_sharded(self, mesh, axis: str, x, k, worker_ids=None):
        """SPMD path: workers = mesh axis ``axis`` (size must equal plan.n).

        Every shard computes its coded subtask; the coded outputs (each
        ``1/delta`` of Y) are all-gathered and decoded redundantly. Straggler
        resilience on a pod maps to *any-delta-of-n slices suffice*: the
        decode uses the statically chosen ``worker_ids`` subset, so losing
        up to gamma shards' results still reconstructs Y exactly.
        """
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        n = self.plan.n
        assert mesh.shape[axis] == n, (mesh.shape, axis, n)
        ids = list(range(self.plan.delta)) if worker_ids is None else list(worker_ids)
        e = recovery_matrix(self.a_code, self.b_code, ids)
        d = jnp.asarray(np.linalg.inv(e.T))
        sel = jnp.asarray(ids)

        xe = self.encode_inputs(x)  # (n, ell_a, ...)
        ke = self.encode_filters(k)  # (n, ell_b, ...)

        def shard_fn(xe_s, ke_s):
            # xe_s: (1, ell_a, ...) local slice
            out = self.worker_compute(xe_s[0], ke_s[0])[None]  # (1, ell2, ...)
            allout = jax.lax.all_gather(out, axis, axis=0, tiled=True)
            coded = allout[sel]  # (delta, ell2, *block) — block may be batched
            rows = coded.reshape(self.plan.k_a * self.plan.k_b, -1)
            true_rows = d.astype(rows.dtype) @ rows
            blocks = true_rows.reshape(
                (self.plan.k_a * self.plan.k_b,) + coded.shape[2:]
            )
            return merge_output(blocks, self.geo)

        fn = shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(),  # decoded output replicated
            check_rep=False,
        )
        return fn(xe, ke)
