"""Master/worker cluster for FCDCC.

Mirrors the paper's mpi4py methodology on one host: n coded workers,
per-worker injected delays (``sleep()``-style stragglers, as in
Experiment 4), random unavailability, and hard failures.  The master
collects the *fastest delta* results and decodes immediately — later
arrivals are discarded, exactly like the paper's asynchronous collection.

Workers execute behind a pool seam (``repro.runtime.devicepool``):

  * ``pool="threads"`` — one persistent single-thread executor per worker
    on the default device (the deterministic injected-straggler mode, and
    the only executor for ``mode="simulated"``);
  * ``pool="device"`` — each worker pinned to its own ``jax.Device`` from a
    1-D worker mesh (``launch.mesh.make_worker_mesh``): coded filters
    placed once as per-device blocks and resident, worker programs jitted
    per device, ``submit`` = one grouped placement of the round's shares
    and async dispatch onto the device queues,
    ``collect`` = a per-array-readiness reaper keeping the fastest delta.
    Default whenever real parallelism is available (``mode="threads"`` on a
    multi-device host — e.g. ``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` — or real TPU/GPU devices).

The cluster is **persistent**: jitted worker programs and encoded filters
are cached across calls, so repeated ``run_layer``s (and every layer of a
``run_pipeline``) pay encode+jit once — the paper's deployment model where
coded filters are pre-stored on the workers.  The worker pool is persistent
too (``shutdown()`` releases it), so a straggler still busy with a
discarded subtask naturally backpressures *its own* node's next subtask —
exactly the behaviour of a real busy worker — while fast workers are never
blocked.

Entry points:
  * ``run_layer`` — one FCDCC ConvL end-to-end with timing breakdown
    (encode / upload / compute / download / decode), simulated-clock mode
    for deterministic tests and real-thread mode for wall-clock numbers.
  * ``dispatch_pipeline_layer`` / ``collect_pipeline_layer`` — the
    asynchronous master: dispatch a layer's n coded subtasks without
    blocking, then reap the fastest delta later.  The serving engine
    (``repro.serving``) uses this split to interleave layers of different
    in-flight request batches on one executor.
  * ``load_pipeline`` / ``run_pipeline`` / ``run_pipeline_layer`` — stream
    a whole CNN ConvL stack (a ``repro.core.pipeline.CodedPipeline`` with
    resident coded filters) through the cluster for batched
    ``(B, C, H, W)`` inputs, returning the output plus per-layer
    ``LayerTiming``.  Pipelines are *namespaced*: several models (e.g.
    lenet5 + alexnet under different ``(k_a, k_b)`` plans) stay resident
    on one shared worker pool at once — ``load_pipeline(pipe, name)`` to
    register, ``unload_pipeline(name)`` to evict, ``model=`` on the run
    entry points to select.  Resident filters and jit program caches are
    keyed per namespace, so two pipelines with colliding layer names can
    never serve each other's filters or programs.
  * elastic recovery: if more than gamma workers fail outright, the master
    re-plans with a smaller (k_a, k_b) grid (fewer subtasks) and re-runs —
    the framework-level restart path.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fcdcc import CodedConv2d, FcdccPlan
from repro.core.partition import ConvGeometry
from repro.core.pipeline import CodedPipeline

from .devicepool import (  # re-exported for back-compat  # noqa: F401
    ClusterDegraded,
    DeviceWorkerPool,
    PendingBatch,
    StragglerModel,
    ThreadWorkerPool,
    make_pool,
    resolve_pool,
)
from .spans import DECODE, ENCODE, GATHER, INVERSE, SUBMIT, TRANSITION, timed


@dataclasses.dataclass
class LayerTiming:
    encode_s: float
    compute_s: float  # master-visible completion time of the delta-th result
    decode_s: float
    # per-worker seconds: finite = measured, inf = dead worker, nan =
    # discarded before finishing (aggregate with ``finished_worker_s``)
    worker_compute_s: list
    used_workers: list
    name: str = ""
    # the round's subtasks: how many reached the device, were cancelled
    # before starting, and were decoded from (delta), and seconds from
    # submit to the delta-th finish; ``prep_s`` is the workers' share
    # preparation since the previous collected round (a straggler's lands
    # in a later round, so each started subtask counts once); ``placements``
    # the transfers that placed the round's shares on their devices
    prep_s: float = 0.0
    started: int = 0
    cancelled: int = 0
    used: int = 0
    delta_ready_s: float = 0.0
    placements: int = 0
    # master-side span seconds of the round, by span name
    phases: dict = dataclasses.field(default_factory=dict)

    @property
    def total_s(self):
        return self.encode_s + self.compute_s + self.decode_s

    @property
    def finished_worker_s(self) -> list:
        """Times of workers that actually finished — the only ones safe to
        average (dead = inf and discarded = nan slots are excluded)."""
        return [t for t in self.worker_compute_s if np.isfinite(t)]


@dataclasses.dataclass
class PendingRound:
    """One dispatched pipeline-layer round awaiting its collect half.

    Returned by ``dispatch_pipeline_layer`` and consumed by
    ``collect_pipeline_layer``/``round_ready``.  It captures everything the
    collect half needs — the pipeline object itself, not its registry name
    — so finishing an in-flight round stays safe even if the model is
    unregistered (``unload_pipeline``) between dispatch and collect."""

    idx: int
    pipe: CodedPipeline
    spec: object  # the layer's LayerProgramSpec
    pending: PendingBatch
    t_encode: float
    fused_mid: bool  # fused pipeline, non-final layer: transition, no decode
    round: int = -1  # the cluster's round id, carried by every span
    bucket: int = 0
    phases: dict = dataclasses.field(default_factory=dict)  # span seconds


class FcdccCluster:
    """n workers executing coded conv subtasks behind the pool seam.

    Persistent state across calls: jitted worker programs (keyed by the
    worker-program signature — per device under ``pool="device"``),
    per-layer ``CodedConv2d`` instances, and resident coded filters (from
    ``preload_filters`` or ``load_pipeline``; per-device blocks under the
    device pool).
    """

    def __init__(self, plan: FcdccPlan, straggler: StragglerModel | None = None,
                 mode: str = "threads", backend: str = "lax",
                 pool: str | None = None,
                 devices=None):
        assert mode in ("threads", "simulated")
        self.plan = plan
        self.straggler = straggler or StragglerModel.none(plan.n)
        self.mode = mode
        self.backend = backend
        # worker pool selection (see devicepool.resolve_pool): None picks
        # the device pool whenever real parallelism is available
        self.pool = resolve_pool(pool, mode, devices)
        self._devices = devices
        # one reentrant lock over pool creation and every persistent cache:
        # the engine thread and caller threads (load/unload/preload) hit
        # these concurrently, and the lazy pool build must not run twice
        self._registry_lock = threading.RLock()
        # built lazily on first dispatch/placement
        self._pool_obj = None  # guarded-by: self._registry_lock
        # persistent caches ------------------------------------------------
        self._coded_layers: dict[tuple, CodedConv2d] = {}  # guarded-by: self._registry_lock
        self._programs: dict[tuple, object] = {}  # guarded-by: self._registry_lock
        # resident coded filters: one entry per layer name (re-planning a
        # layer replaces its entry rather than accumulating), guarded by the
        # filter-code key so filters encoded under one code never serve a
        # different plan's decode.  Entry: (code_key, coded_filters, src).
        # Pipeline layers live under "model/layer" namespaced keys so two
        # models with the same layer names never collide.
        self._resident: dict[str, tuple] = {}  # guarded-by: self._registry_lock
        # registered pipelines by model name (insertion-ordered: the first
        # one is the default for single-model callers)
        self.pipelines: dict[str, CodedPipeline] = {}  # guarded-by: self._registry_lock
        # worker-program signatures already run once (compile happened
        # outside a timed collect); keyed by (program key, operand shapes)
        self._warmed: set[tuple] = set()  # guarded-by: self._registry_lock
        # ids of dispatched pipeline rounds, for their spans
        self._round_ids = itertools.count()

    @property
    def n(self) -> int:
        return self.plan.n

    # -- persistent worker pool --------------------------------------------
    def _pool_impl(self):
        with self._registry_lock:
            if self._pool_obj is None:
                self._pool_obj = make_pool(
                    self.pool, self.n, self.straggler, mode=self.mode,
                    devices=self._devices,
                )
            return self._pool_obj

    @property
    def worker_devices(self) -> list | None:
        """Per-worker device pinning (device pool), else None."""
        impl = self._pool_impl()
        return list(impl.devices) if impl.kind == "device" else None

    @property
    def _pools(self):
        """The threads pool's executors (None for the device pool or before
        first dispatch / after shutdown) — kept for callers that assert
        pool lifecycle."""
        impl = self._pool_obj
        return impl._pools if impl is not None and impl.kind == "threads" \
            else None

    def _ensure_pools(self):
        """Back-compat: materialize the threads pool's executors."""
        impl = self._pool_impl()
        if impl.kind != "threads":
            raise RuntimeError("cluster runs the device pool; no thread "
                               "executors to materialize")
        return impl._ensure_pools()

    def shutdown(self) -> None:
        """Release the worker pool (idempotent; the cluster can be used
        again afterwards — executors and device-resident state are
        re-created lazily)."""
        with self._registry_lock:
            pool = self._pool_obj
        if pool is not None:
            pool.shutdown()

    def __del__(self):  # best-effort: interpreter teardown may race us
        try:
            self.shutdown()
        except Exception:
            pass

    def __enter__(self) -> "FcdccCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- persistent program/filter caches ---------------------------------
    def coded_layer(self, geo: ConvGeometry, plan: FcdccPlan | None = None) -> CodedConv2d:
        plan = plan or self.plan
        key = (plan, geo)
        with self._registry_lock:
            layer = self._coded_layers.get(key)
            if layer is None:
                layer = self._coded_layers[key] = CodedConv2d(
                    plan, geo, backend=self.backend
                )
            return layer

    @staticmethod
    def _filter_code_key(plan: FcdccPlan, geo: ConvGeometry) -> tuple:
        """The parts of (plan, geo) that determine ``encode_filters`` output.
        Coded filters are input-resolution independent, so H/W/stride/padding
        are deliberately excluded — one preload serves any input size."""
        return (plan, geo.in_channels, geo.out_channels,
                geo.kernel_h, geo.kernel_w)

    def preload_filters(self, name: str, geo: ConvGeometry, k,
                        plan: FcdccPlan | None = None):
        """Encode ``k`` once and keep the coded filters resident under
        ``name`` (the deployment case: filters pre-stored on workers)."""
        plan = plan or self.plan
        layer = self.coded_layer(geo, plan)
        ke = jax.block_until_ready(layer.encode_filters(k))
        with self._registry_lock:
            self._resident[name] = (self._filter_code_key(plan, geo), ke, k)
        return ke

    def load_pipeline(self, pipeline: CodedPipeline,
                      name: str = "default") -> None:
        """Adopt a compiled ``CodedPipeline`` under the model namespace
        ``name``: its (already encoded, exactly once) coded filters become
        resident on this cluster's workers as ``"{name}/{layer}"`` entries —
        under the device pool, placed once as one block per worker
        device.  Several pipelines coexist on the one shared pool;
        re-registering a name replaces its pipeline and resident filters."""
        if pipeline.n != self.n:
            raise ValueError(f"pipeline targets n={pipeline.n}, cluster has n={self.n}")
        # replacing a model drops ALL of its old entries first: a v2 with
        # fewer layers must not leave v1 filters reachable under the name.
        # The whole swap runs under the registry lock so the engine never
        # observes a model with v1 filters gone but v2 not yet resident.
        prefix = f"{name}/"
        with self._registry_lock:
            for stale in [k for k in self._resident if k.startswith(prefix)]:
                del self._resident[stale]
            impl = self._pool_impl()
            impl.drop_filters(prefix)
            self.pipelines[name] = pipeline
            for spec, ke in zip(pipeline.specs, pipeline.coded_filters):
                key = self._filter_code_key(spec.plan, spec.geo)
                self._resident[f"{name}/{spec.name}"] = (key, ke, pipeline)
                # device pool: place the filter blocks on their devices
                # now, at load time — the paper's pre-stored deployment — so
                # the serving hot path never pays the placement
                impl.resident_filters(f"{name}/{spec.name}", ke)

    def unload_pipeline(self, name: str) -> None:
        """Evict model ``name``: its pipeline registration, resident
        filters, and (device pool) per-device filter blocks.  Jitted worker
        programs stay cached — they are keyed by program signature, shared
        across models, and a re-registration would re-trace them anyway."""
        with self._registry_lock:
            if name not in self.pipelines:
                raise ValueError(
                    f"unknown model {name!r}; loaded: {sorted(self.pipelines)}"
                )
            del self.pipelines[name]
            prefix = f"{name}/"
            for stale in [k for k in self._resident if k.startswith(prefix)]:
                del self._resident[stale]
            self._pool_impl().drop_filters(prefix)

    @property
    def pipeline(self) -> CodedPipeline | None:
        """The default (first-registered) pipeline, for single-model
        callers; None when nothing is loaded."""
        return next(iter(self.pipelines.values()), None)

    def get_pipeline(self, model: str | None = None) -> CodedPipeline:
        """Resolve a registered pipeline.  ``model=None`` means "the only
        one" — ambiguous (and an error) once several models are loaded."""
        if not self.pipelines:
            raise ValueError("no pipeline loaded; call load_pipeline() first")
        if model is None:
            if len(self.pipelines) > 1:
                raise ValueError(
                    f"{len(self.pipelines)} pipelines loaded "
                    f"({sorted(self.pipelines)}); pass model="
                )
            return next(iter(self.pipelines.values()))
        try:
            return self.pipelines[model]
        except KeyError:
            raise ValueError(
                f"unknown model {model!r}; loaded: {sorted(self.pipelines)}"
            ) from None

    def _model_name(self, model: str | None, pipe: CodedPipeline) -> str:
        if model is not None:
            return model
        for nm, p in self.pipelines.items():
            if p is pipe:
                return nm
        return "default"

    # -- fastest-delta collection ------------------------------------------
    def collect(self, pending: PendingBatch, delta: int, *,
                block: bool = True):
        """Reap the fastest ``delta`` results of a pool's ``submit``; returns
        ``(results, worker_times, t_compute)``.  Later arrivals are
        discarded, exactly like the paper's asynchronous collection —
        straggler subtasks are never joined (their own node stays busy
        finishing them, nobody waits).  ``worker_times`` is a snapshot:
        stragglers finishing after return write into the live list, not
        the one handed back.

        ``block=False`` is the reaper form: return ``None`` immediately
        when the round is not ready yet (the serving engine uses this to
        reap whichever of several in-flight rounds finishes first)."""
        impl = self._pool_impl()
        if not block and not impl.ready(pending, delta):
            return None
        results, worker_times, t_compute = impl.collect(pending, delta)
        if len(results) < delta:
            raise ClusterDegraded(
                f"only {len(results)} of delta={delta} results; "
                f"gamma={self.n - delta} exceeded"
            )
        return results, worker_times, t_compute

    def _gather_outs(self, results: dict, delta: int):
        """The surviving-shard gather feeding decode: the fastest delta
        worker outputs (sorted by worker id — any delta-subset decodes
        exactly, and a canonical order keeps the decode bit-stable across
        pools and completion orders), stacked on the master device.  Under
        the device pool each surviving shard is ``device_put`` from its
        worker device (discarded shards never move); the thread pool's
        results already live there."""
        impl = self._pool_impl()
        ids = sorted(results)[:delta]
        outs = jnp.stack([impl.gather(results[i]) for i in ids], axis=0)
        return ids, outs

    # -- one ConvL ----------------------------------------------------------
    def run_layer(self, geo: ConvGeometry, x, k=None, *, coded_filters=None,
                  layer_name: str | None = None,
                  plan: FcdccPlan | None = None) -> tuple:
        """Returns (y, LayerTiming).  ``x`` may be ``(C, H, W)`` or a
        ``(B, C, H, W)`` batch.  Filters come from, in priority order:
        ``coded_filters`` (pre-encoded), the resident store under
        ``layer_name``, or ``k`` (encoded now and — when ``layer_name`` is
        given — cached resident for next time)."""
        plan = plan or self.plan
        layer = self.coded_layer(geo, plan)
        n, delta = plan.n, plan.delta

        with timed(ENCODE) as enc:
            xe = jax.block_until_ready(layer.encode_inputs(x))
            ke = coded_filters
            code_key = self._filter_code_key(plan, geo)
            if ke is None and layer_name is not None:
                # resident hit only under the same filter-code key AND when
                # the caller passed no weights or the *same* weights object
                # the cache was built from — a plan change or new weights
                # under an old name re-encode rather than silently decoding
                # against filters coded with the wrong matrices
                ent = self._resident.get(layer_name)
                if ent is not None and ent[0] == code_key and (
                    k is None or ent[2] is k
                ):
                    ke = ent[1]
            if ke is None:
                if k is None:
                    raise ValueError(
                        "need k, coded_filters, or resident layer_name")
                ke = jax.block_until_ready(layer.encode_filters(k))
                if layer_name is not None:
                    with self._registry_lock:
                        self._resident[layer_name] = (code_key, ke, k)

        impl = self._pool_impl()
        pkey = (layer.plan.ell_a, layer.plan.ell_b, layer.geo.stride)
        fn = lambda i: impl.program(pkey, layer.worker_compute, i,  # noqa: E731
                                    self._programs)
        if impl.kind == "device":
            # filter blocks live on the worker devices (identity-cached)
            ke = impl.resident_filters(layer_name or "__layer", ke)
        # warm the kernel on first sight of these shapes so per-worker
        # timings measure steady state (skipped once warmed — re-running
        # would execute a whole discarded subtask, not a cache no-op)
        wkey = (self.pool,) + pkey + (tuple(xe.shape), _share_shape(ke))
        if wkey not in self._warmed:
            impl.warm(fn, xe, ke)  # outside the lock: warm may compile
            with self._registry_lock:
                self._warmed.add(wkey)

        pending = impl.submit(fn, xe, ke)
        results, worker_times, t_compute = self.collect(pending, delta)

        ids, outs = self._gather_outs(results, delta)
        with timed(DECODE) as dec:
            y = jax.block_until_ready(layer.decode(ids, outs))
        return y, LayerTiming(enc.s, t_compute, dec.s, worker_times, ids,
                              layer_name or "",
                              **_counts(impl, pending, ids))

    # -- whole network ------------------------------------------------------
    def dispatch_pipeline_layer(self, idx: int, x,
                                model: str | None = None) -> PendingRound:
        """The send half of one pipeline-layer round: encode the batched
        input (or adopt the previous fused round's coded shares), warm the
        worker program on first sight of these shapes, and async-dispatch
        the n coded subtasks.  Returns a ``PendingRound`` for
        ``round_ready``/``collect_pipeline_layer``.

        The serving engine calls this for batch B *before* collecting
        batch A, so A's master-side collect/decode/transition overlaps B's
        worker compute (round pipelining).  Dispatch order is the only
        thing pipelining changes — each round's arithmetic (and therefore
        its fp32 bits, for a given survivor subset) is untouched.

        Each round takes the next of the cluster's round ids, which tags
        its spans, its workers' included."""
        pipe = self.get_pipeline(model)
        spec = pipe.specs[idx]
        fused = pipe.fuse_transitions
        last = idx == len(pipe.specs) - 1
        # the pipeline's own filters, not the name-keyed store: a later
        # preload/run_layer under a colliding layer name must not swap
        # in foreign filters under this pipeline's decode
        ke = pipe.coded_filters[idx]
        carried = fused and idx > 0  # x: the previous transition's shares
        bucket = int(x.shape[2] if carried else x.shape[0])
        round_id = next(self._round_ids)
        meta = dict(round=round_id, layer=idx, bucket=bucket)
        phases = {}
        if carried:
            xe, t_encode = x, 0.0
        else:
            with timed(ENCODE, **meta) as enc:
                xe = jax.block_until_ready(pipe.encoder(idx)(x))
            t_encode = phases[ENCODE] = enc.s

        with timed(SUBMIT, **meta) as sub:
            impl = self._pool_impl()
            fn = lambda i: impl.program(  # noqa: E731
                spec.program_key, pipe.layers[idx].worker_compute, i,
                pipe._cluster_programs,
            )
            if impl.kind == "device":
                name = self._model_name(model, pipe)
                ke = impl.resident_filters(f"{name}/{spec.name}", ke)
            # first sight of these shapes: compile outside the timed collect
            # so per-worker timings measure steady state.  Once warmed it's
            # skipped — the serving hot path must not pay a discarded
            # subtask per layer.
            wkey = (self.pool, spec.program_key, tuple(xe.shape),
                    _share_shape(ke))
            if wkey not in self._warmed:
                impl.warm(fn, xe, ke)  # outside the lock: warm may compile
                with self._registry_lock:
                    self._warmed.add(wkey)
            pending = impl.submit(fn, xe, ke, round_id=round_id)
        phases[SUBMIT] = sub.s
        return PendingRound(idx, pipe, spec, pending, t_encode,
                            fused_mid=fused and not last, round=round_id,
                            bucket=bucket, phases=phases)

    def round_ready(self, rnd: PendingRound) -> bool:
        """Non-blocking: would ``collect_pipeline_layer(rnd)`` return
        without waiting on the pool?"""
        return self._pool_impl().ready(rnd.pending, rnd.spec.plan.delta)

    def collect_pipeline_layer(self, rnd: PendingRound) -> tuple:
        """The reap half: keep the fastest delta of the dispatched round,
        then decode + relu + pool (or the fused partition-resident
        transition).  Returns ``(y, LayerTiming)``."""
        pipe, spec, idx = rnd.pipe, rnd.spec, rnd.idx
        delta = spec.plan.delta
        meta = dict(round=rnd.round, layer=idx, bucket=rnd.bucket)
        with timed(GATHER, **meta) as gather:
            results, worker_times, t_compute = self.collect(rnd.pending,
                                                            delta)
            ids, outs = self._gather_outs(results, delta)
        with timed(INVERSE, **meta) as inverse:
            d = jnp.asarray(pipe.decode_matrix(idx, tuple(ids)))
        if rnd.fused_mid:
            # partition-resident transition straight into the next layer's
            # coded shares for ALL n workers (the next collect again keeps
            # whichever delta finish first); the all-n encode columns are a
            # per-layer constant resident on device
            with timed(TRANSITION, **meta) as dec:
                y = jax.block_until_ready(pipe.transition_fn(idx)(
                    outs, d, pipe.encode_columns_all(idx + 1)))
        else:
            with timed(DECODE, **meta) as dec:
                y = jax.block_until_ready(pipe.decoder_fn(idx)(outs, d))
        phases = {**rnd.phases, GATHER: gather.s, INVERSE: inverse.s,
                  dec.name: dec.s}
        return y, LayerTiming(rnd.t_encode, t_compute, inverse.s + dec.s,
                              worker_times, ids, spec.name, phases=phases,
                              **_counts(self._pool_impl(), rnd.pending, ids))

    def run_pipeline_layer(self, idx: int, x, model: str | None = None) -> tuple:
        """One ConvL of a loaded pipeline as a full master/worker round:
        encode inputs, dispatch n coded subtasks against the *resident*
        coded filters, keep the fastest delta, decode + relu + pool.
        Returns ``(y, LayerTiming)`` for the batched ``(B, C, H, W)`` input.

        This is the layer-granular step the serving engine interleaves
        across concurrent request batches — of all registered models —
        (``repro.serving.CodedServer`` admits new arrivals exactly at these
        layer boundaries, and with ``pipeline_depth > 1`` keeps several
        such rounds in flight via the dispatch/collect halves above).
        ``model`` selects the pipeline namespace.

        With a ``fuse_transitions`` pipeline the state carried between
        rounds is *partition-resident*: layer 0 takes the raw
        ``(B, C, H, W)`` batch and encodes it; every non-final round
        returns the next layer's coded input shares
        ``(n, ell_a, B, C, h_hat, Wp)`` (the fastest-delta outputs are
        decoded only to the partition grid, relu/pool run per partition
        with halo exchange, and the re-encode targets all n workers so the
        next round again keeps the fastest delta); only the final round
        merges to the full tensor.  ``x`` for ``idx > 0`` must then be the
        shares returned by the previous round.  The transition replaces the
        separate encode step, so ``encode_s`` is folded into ``decode_s``
        for those rounds.
        """
        return self.collect_pipeline_layer(
            self.dispatch_pipeline_layer(idx, x, model)
        )

    def run_pipeline(self, x, pipeline: CodedPipeline | None = None,
                     model: str | None = None) -> tuple:
        """Stream a batched ``(B, C, H, W)`` input (or one ``(C, H, W)``
        image) through every ConvL of a loaded pipeline (``model`` selects
        the namespace; passing ``pipeline`` registers it first).

        Each layer is one ``run_pipeline_layer`` master/worker round and
        contributes one ``LayerTiming``.  Returns ``(y, [LayerTiming])``.
        """
        if pipeline is not None:
            # an explicitly passed pipeline is never ambiguous: it runs
            # under its own (or the default) namespace even when other
            # models are already resident
            model = model if model is not None else "default"
            self.load_pipeline(pipeline, model)
        pipe = self.get_pipeline(model)

        squeeze = x.ndim == 3
        if squeeze:
            x = x[None]
        timings = []
        for idx in range(len(pipe.specs)):
            x, timing = self.run_pipeline_layer(idx, x, model)
            timings.append(timing)
        return (x[0] if squeeze else x), timings


def _share_shape(ke) -> tuple:
    """One worker's filter-share shape (list = per-device blocks, array =
    the stacked master copy), read without slicing on the device."""
    return tuple(ke[0].shape[1:] if isinstance(ke, list) else ke.shape[1:])


def _counts(impl, pending: PendingBatch, ids: list) -> dict:
    """``LayerTiming``'s subtask counters of a collected round; its
    ``prep_s`` is what the pool's workers prepared since the last round
    was collected."""
    return dict(prep_s=impl.prep.take(), started=pending.started,
                cancelled=pending.cancelled, used=len(ids),
                delta_ready_s=pending.delta_ready_s,
                placements=pending.placements)


def run_layer_elastic(plan: FcdccPlan, geo: ConvGeometry, x, k,
                      straggler: StragglerModel, mode="simulated",
                      max_retries=2, pool: str | None = None, devices=None):
    """Elastic recovery: on ClusterDegraded, shrink the subtask grid
    (halve k_a or k_b -> smaller delta) and retry on the surviving workers.
    ``pool``/``devices`` select the worker pool for every attempt (the
    re-plan keeps running on the surviving devices)."""
    attempt_plan = plan
    for attempt in range(max_retries + 1):
        # context-managed: each attempt's n single-thread executors are
        # released on exit instead of leaking until interpreter teardown
        with FcdccCluster(attempt_plan, straggler, mode=mode, pool=pool,
                          devices=devices) as cluster:
            try:
                y, timing = cluster.run_layer(geo, x, k)
                return y, timing, attempt_plan
            except ClusterDegraded:
                k_a, k_b = attempt_plan.k_a, attempt_plan.k_b
                if k_a >= k_b and k_a > 1:
                    k_a = max(k_a // 2, 1)
                elif k_b > 1:
                    k_b = max(k_b // 2, 1)
                else:
                    raise
                attempt_plan = FcdccPlan(n=plan.n, k_a=k_a, k_b=k_b)
    raise ClusterDegraded("elastic retries exhausted")
