"""Batched multi-layer coded inference engine: the ``CodedPipeline``.

The paper's deployment model (Sec. IV, Fig. 1) pre-stores coded filters on
the workers and streams a whole CNN's ConvL stack through the coded cluster.
This module is that *system* view, versus the per-layer kernel view of
``fcdcc.py``:

  * ``plan_layers``        — compile a ConvL stack (LeNet-5 / AlexNet /
    VGG-16 descriptors from ``repro.models.cnn``) into ``CodedLayerSpec``s,
    choosing per-layer ``(k_a, k_b)`` via the Sec. IV-E cost model
    (``cost.optimal_partition``) unless pinned by the caller.
  * ``CodedPipeline``      — encodes **every** layer's filters exactly once
    at construction (the resident-coded-filter store), caches one jitted
    worker program per distinct worker-program signature, and executes
    decode -> relu -> pool -> re-encode between layers for batched
    ``(B, C, H, W)`` inputs.

Amortization is the point: the seed path rebuilt ``CodedConv2d`` — and
re-encoded filters and re-jitted the worker program — for every layer of
every image.  A ``CodedPipeline`` pays encode+jit once and serves batches at
steady state; ``repro.runtime.FcdccCluster.run_pipeline`` drives the same
specs through the straggler-simulating master/worker runtime.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import full_f32

from .cost import CostWeights, optimal_partition
from .crme import recovery_matrix
from .fcdcc import CodedConv2d, FcdccPlan
from .nsctc import encode_tensor_list, group_by_worker
from .partition import ConvGeometry, merge_output, partition_transition
from .programs import named, worker_share_program

__all__ = [
    "CodedLayerSpec",
    "CodedPipeline",
    "ProgramCell",
    "plan_layers",
    "build_cnn_pipeline",
    "relu_pool",
]


@dataclasses.dataclass(frozen=True)
class ProgramCell:
    """One (program, argument-shape) cell of a pipeline's shape space.

    ``CodedPipeline.program_space`` enumerates every cell the pipeline can
    ever launch — per execution mode, layer, and batch bucket — as
    ``ShapeDtypeStruct`` arguments plus the jitted callable, so static
    analyzers (``repro.analysis``) can trace/lower each program without
    running data.

    ``kind``: ``encoder`` / ``worker`` / ``transition`` / ``decoder``.
    ``mode``: ``direct`` (single-process vmapped path) or ``cluster``
    (per-worker threaded-runtime path).
    ``cache_key``: the pipeline-side program-cache key; cells sharing
    (kind, mode, cache_key) and an argument signature share one jit trace,
    which is what the bounded-trace proof counts.
    ``allowed_const_shapes``: shapes a traced constant may legitimately
    take in this cell (e.g. the cluster encoder bakes the full-n A-code
    matrix — subset-independent, so it cannot cause retraces).
    ``donate_argnums``: argument indices the program donates.
    """

    cell_id: str
    kind: str
    mode: str
    layer: int
    bucket: int
    cache_key: tuple
    fn: callable
    args: tuple
    allowed_const_shapes: tuple = ()
    donate_argnums: tuple = ()

    @property
    def trace_signature(self) -> tuple:
        """What jit specializes on: program identity + argument avals."""
        return (
            self.kind,
            self.mode,
            self.cache_key,
            tuple((a.shape, str(a.dtype)) for a in self.args),
        )


@dataclasses.dataclass(frozen=True)
class CodedLayerSpec:
    """One compiled ConvL of a coded pipeline (static plan + geometry)."""

    name: str
    plan: FcdccPlan
    geo: ConvGeometry
    pool: int = 1  # max-pool factor applied after relu

    @property
    def out_hw(self) -> int:
        """Spatial size seen by the next layer (after pooling)."""
        return self.geo.out_h // self.pool if self.pool > 1 else self.geo.out_h

    @property
    def program_key(self) -> tuple:
        """Worker-program signature: layers sharing it share one jitted
        program (shape specialization is jit's job)."""
        return (
            self.plan.ell_a,
            self.plan.ell_b,
            self.geo.stride,
        )


def relu_pool(y: jnp.ndarray, pool: int) -> jnp.ndarray:
    """ReLU then ``pool x pool`` max-pool on the trailing (H, W) dims."""
    y = jax.nn.relu(y)
    if pool == 1:
        return y
    h, w = y.shape[-2:]
    h2, w2 = h - h % pool, w - w % pool
    y = y[..., :h2, :w2]
    return jnp.max(
        y.reshape(y.shape[:-2] + (h2 // pool, pool, w2 // pool, pool)),
        axis=(-3, -1),
    )


def _choose_kab(geo0: ConvGeometry, q: int, n: int, weights: CostWeights):
    """Cost-optimal feasible (k_a, k_b) with k_a*k_b = q and delta <= n."""
    _, _, landscape = optimal_partition(geo0, q, weights)
    for kab, _cost in sorted(landscape.items(), key=lambda kv: kv[1]):
        try:
            FcdccPlan(n=n, k_a=kab[0], k_b=kab[1])
        except ValueError:
            continue
        return kab
    raise ValueError(f"no feasible (k_a, k_b) for q={q} on n={n} workers")


def plan_layers(
    layers: Iterable,
    input_hw: int,
    n: int,
    *,
    q: int | None = None,
    default_kab: tuple[int, int] | None = None,
    per_layer_kab: dict | None = None,
    weights: CostWeights = CostWeights(),
) -> list[CodedLayerSpec]:
    """Compile a ConvL stack into per-layer coded specs.

    ``layers``: descriptors with ``name/in_ch/out_ch/kernel/stride/padding/
    pool`` attributes (``repro.models.cnn.ConvL`` or compatible).  The
    (k_a, k_b) of each layer comes from, in priority order:
    ``per_layer_kab[name]``, then ``default_kab``, then the cost-optimal
    feasible split of the ``q``-subtask budget (Sec. IV-E) — at least one of
    ``q`` / ``default_kab`` must be given.
    """
    if q is None and default_kab is None:
        raise ValueError("need q (subtask budget) or default_kab")
    specs = []
    hw = input_hw
    for layer in layers:
        geo0 = ConvGeometry(
            in_channels=layer.in_ch,
            out_channels=layer.out_ch,
            height=hw,
            width=hw,
            kernel_h=layer.kernel,
            kernel_w=layer.kernel,
            stride=layer.stride,
            padding=layer.padding,
        )
        kab = (per_layer_kab or {}).get(layer.name, default_kab)
        if kab is None:
            kab = _choose_kab(geo0, q, n, weights)
        k_a, k_b = kab
        plan = FcdccPlan(n=n, k_a=k_a, k_b=k_b)
        geo = dataclasses.replace(geo0, k_a=k_a, k_b=k_b)
        spec = CodedLayerSpec(layer.name, plan, geo, getattr(layer, "pool", 1))
        specs.append(spec)
        hw = spec.out_hw
    return specs


class CodedPipeline:
    """A whole CNN ConvL stack compiled against one coded cluster.

    Construction encodes every layer's filters exactly once (asserted by
    ``filter_encode_calls``); running feeds a ``(B, C, H, W)`` batch through
    encode -> coded worker convs -> decode -> relu -> pool per layer.  The
    per-worker view of the same specs/filters is consumed by
    ``repro.runtime.FcdccCluster`` (resident coded filters + straggler
    simulation); this class is the single-process mathematical engine.
    """

    def __init__(self, specs: Sequence[CodedLayerSpec], params: dict, *,
                 backend: str = "lax", fused_worker: bool = True,
                 bucket_sizes: Sequence[int] | None = None,
                 fuse_transitions: bool = False,
                 donate_transitions: bool | None = None,
                 pool: str | None = None, devices=None):
        specs = list(specs)
        if not specs:
            raise ValueError("empty pipeline")
        ns = {s.plan.n for s in specs}
        if len(ns) != 1:
            raise ValueError(f"all layers must target the same cluster, got n={ns}")
        self.specs = specs
        self.n = ns.pop()
        self.backend = backend
        # worker-pool preference carried to whichever FcdccCluster /
        # CodedServer adopts this pipeline (None = auto-select there);
        # the pipeline's own math never consults it
        self.pool = pool
        self.devices = devices
        # partition-resident transitions: between ConvLs the activation is
        # decoded only to the (k_a, k_b) partition grid, relu+pool run per
        # spatial partition with halo exchange, and the partitions re-encode
        # directly — one jitted transition program per (layer, bucket), no
        # merged (B, C, H, W) round trip.  The final layer always merges.
        self.fuse_transitions = fuse_transitions
        # donate the fastest-delta worker-output buffer into the fused
        # transition program: between ConvL rounds the decode consumes
        # ``outs`` exactly once, so XLA can reuse its pages for the coded
        # next-layer shares instead of holding both live (allocator
        # pressure scales with delta x block x bucket otherwise).  None =
        # donate wherever XLA honors donation (CPU does not — it warns and
        # copies, so the CPU default keeps donation off).  Callers that
        # re-feed the same ``outs`` array into a transition twice (paired
        # benchmarks) must pass False.
        if donate_transitions is None:
            donate_transitions = jax.default_backend() != "cpu"
        self.donate_transitions = donate_transitions
        # batch-size buckets: callers pad request batches up to one of these
        # sizes (``pad_to_bucket``) so jit compiles a *bounded* set of batch
        # programs — one per (program, bucket), never one per batch size
        self.bucket_sizes: tuple[int, ...] | None = (
            self.normalize_buckets(bucket_sizes) if bucket_sizes else None
        )
        self.layers = [
            CodedConv2d(s.plan, s.geo, backend=backend,
                        fused_worker=fused_worker)
            for s in specs
        ]
        # resident coded filters: encoded exactly once, reused every run
        self.coded_filters = [
            layer.encode_filters(jnp.asarray(params[s.name]))
            for s, layer in zip(specs, self.layers)
        ]
        # program caches -------------------------------------------------
        self._encoders: dict[int, callable] = {}
        self._cluster_programs: dict[tuple, callable] = {}  # per-worker call
        self._batch_programs: dict[tuple, callable] = {}  # vmapped over workers
        self._decoders: dict[int, callable] = {}  # one per layer, any subset
        self._transitions: dict[tuple, callable] = {}  # by transition key
        self._all_encode_columns: dict[int, jnp.ndarray] = {}  # full-n, resident

    @staticmethod
    def normalize_buckets(bucket_sizes: Sequence[int]) -> tuple[int, ...]:
        """Sorted, deduplicated, validated bucket tuple (assign this — never
        a raw sequence — to ``bucket_sizes``)."""
        buckets = tuple(sorted(set(int(b) for b in bucket_sizes)))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {bucket_sizes}")
        return buckets

    # -- introspection -----------------------------------------------------
    @property
    def input_shape(self) -> tuple[int, int, int]:
        """Per-image ``(C, H, W)`` the first layer expects."""
        spec0 = self.specs[0]
        return (spec0.geo.in_channels, spec0.geo.height, spec0.geo.width)

    @property
    def input_dtype(self):
        """Request dtype: everything is cast to the coded-filter dtype so a
        stray client dtype can never grow the jit program cache."""
        return self.coded_filters[0].dtype

    @property
    def num_geometries(self) -> int:
        """Distinct (program key, geometry) pairs — with bucketing, the jit
        trace count is bounded by ``num_geometries * len(bucket_sizes)``."""
        return len({(s.program_key, s.geo) for s in self.specs})

    @staticmethod
    def _transition_key(spec: CodedLayerSpec, nxt: CodedLayerSpec) -> tuple:
        """Transition-program signature: everything the traced program
        closes over.  Adjacent layer pairs sharing it share one jitted
        program (e.g. VGG-16's repeated same-shape conv blocks), exactly
        as ``worker_program`` shares by ``program_key``."""
        return (spec.geo, spec.pool, nxt.geo, nxt.plan.ell_a)

    @property
    def num_transitions(self) -> int:
        """Distinct fused transition-program signatures across adjacent
        ConvL pairs when ``fuse_transitions`` (repeated transition
        geometries share one program), else zero."""
        if not self.fuse_transitions:
            return 0
        return len({
            self._transition_key(s, n)
            for s, n in zip(self.specs, self.specs[1:])
        })

    @property
    def transition_program_traces(self) -> int:
        """Shape-specialized compilations across the jitted transition
        programs — bounded by ``num_transitions * len(bucket_sizes)``."""
        return sum(
            fn._cache_size() if hasattr(fn, "_cache_size") else 1
            for fn in self._transitions.values()
        )

    @property
    def program_trace_bound(self) -> int:
        """The bounded-program contract under bucketing: worker-program plus
        transition-program traces never exceed (worker geometries + fused
        transition geometries) x buckets, no matter how many distinct batch
        sizes or survivor subsets the server has seen."""
        buckets = len(self.bucket_sizes) if self.bucket_sizes else 1
        return (self.num_geometries + self.num_transitions) * buckets

    @property
    def filter_encode_calls(self) -> int:
        """Total ``encode_filters`` invocations across layers (== number of
        layers when the encode-once contract holds)."""
        return sum(layer.filter_encode_calls for layer in self.layers)

    @property
    def num_worker_programs(self) -> int:
        """Distinct jitted worker programs in use.  The vmapped
        single-process cache and the per-worker cluster cache hold distinct
        compiled programs even for the same program key, so both count."""
        return len(self._batch_programs) + len(self._cluster_programs)

    @property
    def worker_program_traces(self) -> int:
        """Total shape-specialized compilations across all jitted worker
        programs.  With bucketed batches this is bounded by
        ``len(layer geometries) * len(bucket_sizes)`` regardless of how many
        distinct request-batch sizes the server has seen."""
        return sum(
            fn._cache_size() if hasattr(fn, "_cache_size") else 1
            for cache in (self._batch_programs, self._cluster_programs)
            for fn in cache.values()
        )

    def layer_delta(self, idx: int) -> int:
        return self.specs[idx].plan.delta

    # -- batch-size bucketing ----------------------------------------------
    @property
    def max_batch(self) -> int | None:
        """Largest admissible request batch (None = unbucketed/unbounded)."""
        return self.bucket_sizes[-1] if self.bucket_sizes else None

    def bucketize(self, batch: int) -> int:
        """Smallest bucket >= ``batch`` (identity when unbucketed)."""
        if self.bucket_sizes is None:
            return batch
        for b in self.bucket_sizes:
            if b >= batch:
                return b
        raise ValueError(
            f"batch {batch} exceeds the largest bucket {self.bucket_sizes[-1]}"
        )

    def pad_to_bucket(self, x: jnp.ndarray, axis: int = 0) -> tuple[jnp.ndarray, int]:
        """Zero-pad a batch up to its bucket size along ``axis``.

        ``axis=0`` is the plain ``(B, C, H, W)`` batch; partition-resident
        serving also pads mid-stack coded-share state (batch on axis 2 of
        ``(n, ell_a, B, C, h_hat, Wp)``).  Returns ``(padded, real_batch)``;
        the caller keeps the first ``real_batch`` rows along ``axis``.
        Padding rows are zeros — a zero activation encodes to zero shares,
        convolves to zero, and stays zero through relu/pool/halo, so they
        ride the whole coded stack as dead weight and are dropped at the
        end."""
        b = x.shape[axis]
        bucket = self.bucketize(b)
        if bucket == b:
            return x, b
        pad_shape = x.shape[:axis] + (bucket - b,) + x.shape[axis + 1:]
        return jnp.concatenate([x, jnp.zeros(pad_shape, x.dtype)], axis=axis), b

    # -- program caches ----------------------------------------------------
    def encoder(self, idx: int):
        """Jitted APCP+encode program for layer ``idx`` (the layer's own
        ``encode_inputs``)."""
        fn = self._encoders.get(idx)
        if fn is None:
            fn = self._encoders[idx] = jax.jit(
                named(self.layers[idx].encode_inputs, "encode"))
        return fn

    def worker_program(self, idx: int, *, over_workers: bool = True):
        """The jitted coded worker program for layer ``idx``.

        ``over_workers=True`` gives the vmapped-over-the-worker-axis program
        (the single-process path); ``False`` gives the one-worker program the
        threaded cluster serves, ``(xe, ke, i)`` on the stacked shares with
        the worker index traced (``worker_share_program``; the thread pool
        fills the same cache entry with it).  Layers with the same
        ``program_key`` share one program — jit's shape cache handles the
        per-geometry specialization, so e.g. VGG-16's thirteen ConvLs run on
        a handful of compiled programs.
        """
        cache = self._batch_programs if over_workers else self._cluster_programs
        key = self.specs[idx].program_key
        fn = cache.get(key)
        if fn is None:
            compute = self.layers[idx].worker_compute
            fn = cache[key] = (jax.jit(jax.vmap(compute)) if over_workers
                               else worker_share_program(compute))
        return fn

    def encode_columns(self, idx: int, worker_ids: tuple[int, ...]) -> np.ndarray:
        """The A-code encoding columns of the selected workers — encoding
        with this slice produces only those workers' coded input shares
        ((n - delta)/n of the encode GEMM skipped versus full-n).

        Computed per call: the slice is a cheap host-side concat, and the
        threads-mode cluster picks timing-dependent subsets, so a per-subset
        cache would grow without bound on a persistent pipeline."""
        code = self.layers[idx].a_code
        return np.concatenate(
            [code.worker_columns(i) for i in worker_ids], axis=1
        )

    def encode_columns_all(self, idx: int) -> jnp.ndarray:
        """The full-n A-code encode columns of layer ``idx`` as a resident
        device array.  Unlike the timing-dependent subsets of
        ``encode_columns``, the all-workers matrix is one fixed constant
        per layer, so it is cached (one entry per layer, bounded) — the
        cluster's fused transition rounds re-encode for all n workers
        every round and must not rebuild + re-upload it each time."""
        m = self._all_encode_columns.get(idx)
        if m is None:
            m = self._all_encode_columns[idx] = jnp.asarray(
                self.layers[idx].a_code.matrix
            )
        return m

    def decode_matrix(self, idx: int, worker_ids: tuple[int, ...]) -> np.ndarray:
        """The QxQ decode inverse for layer ``idx`` under the given
        surviving-worker subset (host-side float64).  Computed per call —
        inverting a QxQ (e.g. 16x16) matrix costs microseconds, while a
        per-subset cache would grow up to C(n, delta) entries under the
        threads-mode cluster's timing-dependent subsets."""
        layer = self.layers[idx]
        e = recovery_matrix(layer.a_code, layer.b_code, list(worker_ids))
        return np.linalg.inv(e.T)

    def decoder_fn(self, idx: int):
        """The jitted decode+merge+relu+pool program for layer ``idx``,
        taking ``(outs, decode_matrix)``.

        One jitted program per layer: the decode inverse is a *runtime
        argument* (constant (Q, Q) shape), so the timing-dependent
        fastest-delta subsets chosen by the cluster never trigger a
        recompile or grow the program cache.
        """
        spec = self.specs[idx]
        fn = self._decoders.get(idx)
        if fn is None:
            q = spec.plan.k_a * spec.plan.k_b

            @full_f32
            def dec(outs, d, _q=q, _geo=spec.geo, _pool=spec.pool):
                rows = outs.reshape(outs.shape[0] * outs.shape[1], -1)
                true_rows = d.astype(rows.dtype) @ rows
                blocks = true_rows.reshape((_q,) + outs.shape[2:])
                return relu_pool(merge_output(blocks, _geo), _pool)

            fn = self._decoders[idx] = jax.jit(named(dec, "decode"))
        return fn

    def decoder(self, idx: int, worker_ids: tuple[int, ...]):
        """``decoder_fn`` with the subset's decode inverse bound; returns
        ``fn(outs)``."""
        fn = self.decoder_fn(idx)
        d = jnp.asarray(self.decode_matrix(idx, worker_ids))
        return lambda outs: fn(outs, d)

    def transition_fn(self, idx: int):
        """The jitted partition-resident transition program between ConvL
        ``idx`` and ``idx + 1``, taking ``(outs, decode_matrix,
        next_encode_columns)``.

        One program fuses the whole inter-layer round trip: decode layer
        ``idx``'s fastest-delta outputs only to the ``(k_a, k_b)`` grid,
        ReLU (in the decode epilogue), per-partition max-pool with halo
        exchange, re-slice into layer ``idx + 1``'s adaptive-padded APCP
        parts, and re-encode — the merged ``(B, C, H, W)`` tensor is never
        materialized.  The decode inverse and the next layer's encode
        columns are *runtime arguments* (constant shapes), so any
        timing-dependent survivor subset and any next-round worker
        selection reuse the one program per (transition geometry, bucket)
        — the bounded-program contract extends to transitions, and
        adjacent pairs with the same transition signature (repeated conv
        blocks) share one program.
        """
        if not 0 <= idx < len(self.specs) - 1:
            raise ValueError(f"no transition after layer {idx} "
                             f"({len(self.specs)} layers)")
        key = self._transition_key(self.specs[idx], self.specs[idx + 1])
        fn = self._transitions.get(key)
        if fn is None:
            spec, nxt = self.specs[idx], self.specs[idx + 1]
            q = spec.plan.k_a * spec.plan.k_b
            ell_next = nxt.plan.ell_a
            geo, pool, geo_next = spec.geo, spec.pool, nxt.geo

            def assemble(blocks):
                # relu already applied by the decode epilogue
                return partition_transition(blocks, geo, pool, geo_next,
                                            relu=False)

            if self.backend == "pallas":
                from repro.kernels.conv2d.ops import coded_transition

                def trans(outs, d, m_next):
                    coded = coded_transition(outs, d, m_next, assemble)
                    return group_by_worker(coded, ell_next)
            else:
                def trans(outs, d, m_next):
                    rows = outs.reshape(outs.shape[0] * outs.shape[1], -1)
                    blocks = jax.nn.relu(
                        (d.astype(rows.dtype) @ rows)
                        .reshape((q,) + outs.shape[2:])
                    )
                    parts = assemble(blocks)
                    coded = encode_tensor_list(parts, m_next)
                    return group_by_worker(coded, ell_next)

            fn = self._transitions[key] = jax.jit(
                named(full_f32(trans), "transition"),
                donate_argnums=(0,) if self.donate_transitions else (),
            )
        return fn

    # -- kernel autotuning ---------------------------------------------------
    def autotune_kernels(self, bucket_sizes: Sequence[int] | None = None, *,
                         repeat: int = 3, force: bool = False,
                         path: str | None = None) -> dict:
        """Sweep every Pallas kernel cell this pipeline will launch and
        persist the winners in the autotune ledger (``repro.kernels
        .autotune``), then drop the compiled-program caches so rebuilt
        programs pick the tuned tiles up at their next trace.

        Cells are enumerated in *shape space* (``jax.eval_shape`` walks the
        encode -> worker -> transition chain without running it), one per
        (layer geometry, bucket): the worker's implicit-GEMM conv, and —
        under ``fuse_transitions`` — the transition's decode GEMM plus both
        re-encode GEMM widths (the fastest-delta subset the single-process
        path feeds it, and the all-n re-encode the cluster runtime uses).
        Already-cached cells return instantly (``force`` re-sweeps), so
        calling this at server startup costs sweeps only on a cold ledger.
        Returns ``{ledger key: winning params}`` for the cells visited.
        """
        if self.backend != "pallas":
            return {}
        from repro.kernels import autotune

        buckets = (self.normalize_buckets(bucket_sizes) if bucket_sizes
                   else (self.bucket_sizes or (1,)))
        last = len(self.specs) - 1
        tuned: dict[str, dict] = {}
        for bucket in buckets:
            x = jax.ShapeDtypeStruct((bucket,) + self.input_shape,
                                     self.input_dtype)
            for idx, (spec, layer) in enumerate(zip(self.specs, self.layers)):
                ids = self.layer_worker_ids(idx)
                m_sel = jax.ShapeDtypeStruct(
                    self.encode_columns(idx, ids).shape, self.input_dtype)
                xe = jax.eval_shape(layer.encode_inputs, x, m_sel)
                ke_shape = self.coded_filters[idx].shape[1:]
                wkey = autotune.worker_key(
                    xe.shape[1:], ke_shape, spec.geo.stride)
                tuned[wkey] = autotune.tune_worker(
                    xe.shape[1:], ke_shape, spec.geo.stride,
                    dtype=self.input_dtype,
                    repeat=repeat, force=force, path=path)
                outs = jax.eval_shape(
                    jax.vmap(layer.worker_compute),
                    jax.ShapeDtypeStruct((len(ids),) + xe.shape[1:],
                                         xe.dtype),
                    jax.ShapeDtypeStruct((len(ids),) + ke_shape,
                                         self.coded_filters[idx].dtype),
                )
                if self.fuse_transitions and idx < last:
                    q = outs.shape[0] * outs.shape[1]
                    f = int(np.prod(outs.shape[2:]))
                    dkey = autotune.matmul_key(q, q, f, relu=True)
                    tuned[dkey] = autotune.tune_matmul(
                        q, q, f, relu=True, dtype=self.input_dtype,
                        repeat=repeat, force=force, path=path)
                    nxt = self.specs[idx + 1]
                    geo, pool, geo_next = spec.geo, spec.pool, nxt.geo

                    def probe(outs_, d_):
                        rows = outs_.reshape(
                            outs_.shape[0] * outs_.shape[1], -1)
                        blocks = (d_.astype(rows.dtype) @ rows).reshape(
                            (q,) + outs_.shape[2:])
                        return partition_transition(blocks, geo, pool,
                                                    geo_next, relu=False)

                    parts = jax.eval_shape(
                        probe, outs,
                        jax.ShapeDtypeStruct((q, q), outs.dtype))
                    k2 = parts.shape[0]
                    fp = int(np.prod(parts.shape[1:]))
                    ids_next = self.layer_worker_ids(idx + 1)
                    # both re-encode widths: the fastest-delta' subset and
                    # the all-n round the cluster runtime re-encodes for
                    widths = {
                        self.encode_columns(idx + 1, ids_next).shape[1],
                        self.encode_columns_all(idx + 1).shape[1],
                    }
                    for width in sorted(widths):
                        ekey = autotune.matmul_key(width, k2, fp)
                        tuned[ekey] = autotune.tune_matmul(
                            width, k2, fp, dtype=self.input_dtype,
                            repeat=repeat, force=force, path=path)
                # next layer sees this layer's pooled output
                x = jax.ShapeDtypeStruct(
                    (bucket, spec.geo.out_channels, spec.out_hw,
                     spec.out_hw), self.input_dtype)
        # rebuilt programs consult the fresh winners at their next trace
        self._batch_programs.clear()
        self._cluster_programs.clear()
        self._transitions.clear()
        return tuned

    # -- shape-space enumeration -------------------------------------------
    def program_space(self, bucket_sizes: Sequence[int] | None = None, *,
                      modes: Sequence[str] = ("direct", "cluster")):
        """Enumerate every program cell this pipeline can launch, in shape
        space — no data is executed.

        Yields one ``ProgramCell`` per (mode, layer, bucket, program kind),
        walking the encode -> worker -> transition/decode chain with
        ``jax.eval_shape`` exactly as execution would (the same walk
        ``autotune_kernels`` performs).  ``direct`` is the single-process
        path (vmapped worker over the fastest-delta axis, subset-width
        re-encodes); ``cluster`` is the threaded-runtime path (per-worker
        programs, full-n re-encodes, full-matrix encoder).  Survivor
        subsets never appear in the signatures — only ``delta`` (the subset
        *size*) does — which is the shape-space half of the no-retrace
        contract; ``repro.analysis`` checks the other half (matrices enter
        as runtime arguments, not baked constants) on the traced jaxprs.
        """
        buckets = (self.normalize_buckets(bucket_sizes) if bucket_sizes
                   else (self.bucket_sizes or (1,)))
        last = len(self.specs) - 1
        dtype = self.input_dtype
        for mode in modes:
            if mode not in ("direct", "cluster"):
                raise ValueError(f"unknown mode {mode!r}")
            for bucket in buckets:
                x = jax.ShapeDtypeStruct((bucket,) + self.input_shape, dtype)
                for idx, (spec, layer) in enumerate(
                        zip(self.specs, self.layers)):
                    def cid(kind):
                        return f"{spec.name}[b={bucket}]/{kind}:{mode}"

                    ids = self.layer_worker_ids(idx)
                    delta = len(ids)
                    m_sel = jax.ShapeDtypeStruct(
                        self.encode_columns(idx, ids).shape, dtype)
                    ke_shape = self.coded_filters[idx].shape[1:]
                    # the encoder runs on every layer when unfused, and only
                    # on layer 0 when transitions re-encode in coded space
                    if not self.fuse_transitions or idx == 0:
                        if mode == "direct":
                            yield ProgramCell(
                                cid("encoder"), "encoder", mode, idx, bucket,
                                (idx,), self.encoder(idx), (x, m_sel))
                        else:
                            # the cluster encodes all n workers' shares with
                            # the resident full matrix (one-arg call bakes
                            # it — subset-independent, hence allowed)
                            yield ProgramCell(
                                cid("encoder"), "encoder", mode, idx, bucket,
                                (idx,), self.encoder(idx), (x,),
                                allowed_const_shapes=(
                                    tuple(layer.a_code.matrix.shape),))
                    xe = jax.eval_shape(layer.encode_inputs, x, m_sel)
                    if mode == "direct":
                        yield ProgramCell(
                            cid("worker"), "worker", mode, idx, bucket,
                            spec.program_key, self.worker_program(idx),
                            (jax.ShapeDtypeStruct(
                                (delta,) + xe.shape[1:], xe.dtype),
                             jax.ShapeDtypeStruct(
                                (delta,) + ke_shape, dtype)))
                    else:
                        # the served signature: every worker's shares and
                        # the worker index, selected inside the program
                        yield ProgramCell(
                            cid("worker"), "worker", mode, idx, bucket,
                            spec.program_key,
                            self.worker_program(idx, over_workers=False),
                            (jax.ShapeDtypeStruct(
                                (self.n,) + xe.shape[1:], xe.dtype),
                             jax.ShapeDtypeStruct(
                                (self.n,) + ke_shape, dtype),
                             jax.ShapeDtypeStruct((), jnp.int32)))
                    outs = jax.eval_shape(
                        jax.vmap(layer.worker_compute),
                        jax.ShapeDtypeStruct((delta,) + xe.shape[1:],
                                             xe.dtype),
                        jax.ShapeDtypeStruct((delta,) + ke_shape, dtype),
                    )
                    q = spec.plan.k_a * spec.plan.k_b
                    d = jax.ShapeDtypeStruct((q, q), dtype)
                    if self.fuse_transitions and idx < last:
                        if mode == "direct":
                            m_next = jax.ShapeDtypeStruct(
                                self.encode_columns(
                                    idx + 1,
                                    self.layer_worker_ids(idx + 1)).shape,
                                dtype)
                        else:
                            m_next = jax.ShapeDtypeStruct(
                                self.encode_columns_all(idx + 1).shape,
                                dtype)
                        yield ProgramCell(
                            cid("transition"), "transition", mode, idx,
                            bucket,
                            self._transition_key(spec, self.specs[idx + 1]),
                            self.transition_fn(idx), (outs, d, m_next),
                            donate_argnums=(
                                (0,) if self.donate_transitions else ()))
                    if not self.fuse_transitions or idx == last:
                        yield ProgramCell(
                            cid("decoder"), "decoder", mode, idx, bucket,
                            (idx,), self.decoder_fn(idx), (outs, d))
                    x = jax.ShapeDtypeStruct(
                        (bucket, spec.geo.out_channels, spec.out_hw,
                         spec.out_hw), dtype)

    # -- execution ---------------------------------------------------------
    def layer_worker_ids(self, idx: int, worker_ids=None) -> tuple[int, ...]:
        """The survivors layer ``idx`` decodes from: the first delta of the
        available workers (all n when ``worker_ids`` is None)."""
        delta = self.layer_delta(idx)
        avail = list(range(self.n)) if worker_ids is None else list(worker_ids)
        if len(avail) < delta:
            raise ValueError(
                f"layer {self.specs[idx].name} needs delta={delta} workers, "
                f"got {len(avail)}"
            )
        return tuple(avail[:delta])

    def run(self, x: jnp.ndarray, worker_ids=None) -> jnp.ndarray:
        """Coded inference of the whole ConvL stack.

        ``x``: ``(B, C, H, W)`` batch or a single ``(C, H, W)`` image.
        ``worker_ids``: the available workers (any >= delta subset of n per
        layer decodes to the same output); default all n.

        With ``fuse_transitions`` the stack runs on the partition-resident
        path: survivor subsets are pre-picked per layer (same first-delta
        rule) and the inter-layer rounds stay in partition space.
        """
        if self.fuse_transitions:
            return self.run_prepared(x, self.prepare(worker_ids))
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        for idx, layer in enumerate(self.layers):
            ids = self.layer_worker_ids(idx, worker_ids)
            # encode only the selected workers' shares (matrix is a runtime
            # argument, so any subset reuses the one per-layer program)
            m_sel = jnp.asarray(self.encode_columns(idx, ids))
            xe = self.encoder(idx)(x, m_sel)
            sel = jnp.asarray(ids)
            outs = self.worker_program(idx)(xe, self.coded_filters[idx][sel])
            x = self.decoder(idx, ids)(outs)
        return x[0] if squeeze else x

    def prepare(self, worker_ids=None) -> list[tuple]:
        """Pre-pick every layer's survivor subset and build all host-side
        code artifacts up front: per-layer ``(encode_columns, selector,
        decode_matrix)`` as device arrays.

        ``worker_ids`` is either one available-worker list shared by all
        layers (each layer decodes from its first delta) or a per-layer
        sequence of subsets.  The returned plan is what ``run_prepared``
        executes without any host work between layers."""
        per_layer = (
            worker_ids is not None
            and len(worker_ids) == len(self.specs)
            and all(isinstance(w, (list, tuple)) for w in worker_ids)
        )
        prepped = []
        for idx in range(len(self.specs)):
            avail = worker_ids[idx] if per_layer else worker_ids
            ids = self.layer_worker_ids(idx, avail)
            prepped.append((
                jnp.asarray(self.encode_columns(idx, ids)),
                jnp.asarray(ids),
                jnp.asarray(self.decode_matrix(idx, ids)),
            ))
        return prepped

    def run_prepared(self, x: jnp.ndarray, prepared=None, *, worker_ids=None) -> jnp.ndarray:
        """Coded inference over pre-picked survivor subsets — the serving
        fast path.

        ``run`` interleaves host-side code prep (encode-column slices,
        decode-inverse solves) between device launches, forcing a sync at
        every layer boundary.  Here all of that comes from ``prepare``
        (or is built once up front), so the whole stack is dispatched
        asynchronously: decode of layer *i* overlaps encode of layer *i+1*
        on the device queue.  The serving engine reuses one ``prepare``
        plan across every batch that sees the same survivor set."""
        if prepared is None:
            prepared = self.prepare(worker_ids)
        if len(prepared) != len(self.specs):
            raise ValueError(
                f"prepared plan covers {len(prepared)} layers, "
                f"pipeline has {len(self.specs)}"
            )
        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        if self.fuse_transitions:
            # partition-resident path: encode once into layer 0's shares,
            # then thread coded partition-space state between layers — the
            # transition of layer i re-encodes directly for layer i+1's
            # selected workers; only the final layer merges to a tensor.
            last = len(self.specs) - 1
            xe = self.encoder(0)(x, prepared[0][0])
            for idx, (m_sel, sel, d) in enumerate(prepared):
                outs = self.worker_program(idx)(
                    xe, self.coded_filters[idx][sel]
                )
                if idx < last:
                    xe = self.transition_fn(idx)(outs, d, prepared[idx + 1][0])
                else:
                    x = self.decoder_fn(idx)(outs, d)
            return x[0] if squeeze else x
        for idx, (m_sel, sel, d) in enumerate(prepared):
            xe = self.encoder(idx)(x, m_sel)
            outs = self.worker_program(idx)(xe, self.coded_filters[idx][sel])
            x = self.decoder_fn(idx)(outs, d)
        return x[0] if squeeze else x


def build_cnn_pipeline(
    name: str,
    params: dict,
    n: int,
    *,
    q: int | None = None,
    default_kab: tuple[int, int] | None = None,
    per_layer_kab: dict | None = None,
    input_hw: int | None = None,
    weights: CostWeights = CostWeights(),
    backend: str = "lax",
    bucket_sizes: Sequence[int] | None = None,
    fuse_transitions: bool = False,
    donate_transitions: bool | None = None,
    pool: str | None = None,
    devices=None,
) -> CodedPipeline:
    """Compile one of the named CNNs (``lenet5``/``alexnet``/``vgg16``) into
    a ``CodedPipeline`` (lazy model import keeps core free of model deps)."""
    from repro.models.cnn import CNN_SPECS

    hw0, layers = CNN_SPECS[name]
    specs = plan_layers(
        layers,
        input_hw if input_hw is not None else hw0,
        n,
        q=q,
        default_kab=default_kab,
        per_layer_kab=per_layer_kab,
        weights=weights,
    )
    return CodedPipeline(specs, params, backend=backend,
                         bucket_sizes=bucket_sizes,
                         fuse_transitions=fuse_transitions,
                         donate_transitions=donate_transitions,
                         pool=pool, devices=devices)
