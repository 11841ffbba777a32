"""Worker pools behind ``FcdccCluster``'s submit/collect seam.

Two interchangeable executors for the n coded subtasks of one FCDCC
master/worker round:

  * ``ThreadWorkerPool`` (``pool="threads"``) — the original simulated
    cluster: one persistent single-thread executor per worker, every
    subtask computed on the *default* JAX device, stragglers injected as
    ``sleep()``s after the compute.  Each worker hands its program the
    whole stacked coded inputs and filters and its own index, and the
    program selects the worker's share on the device
    (``core.programs.worker_share_program``).  Deterministic, runs
    anywhere, and the only choice for ``mode="simulated"`` — but the n
    subtasks serialize on one device queue, so the paper's parallel
    decomposition never actually runs in parallel.
  * ``DeviceWorkerPool`` (``pool="device"``) — each worker pinned to a
    ``jax.Device`` from a 1-D worker mesh (``launch.mesh.make_worker_mesh``
    / ``sharding.worker_devices``): real TPU/GPU devices, or CPU host
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` so
    CI exercises it.  Worker ``i`` runs on device ``i % m`` of the
    ``m``-device mesh, at slot ``i // m`` of that device's *block*, the
    stacked shares of the workers pinned to it.  Coded filters are placed
    once as per-device blocks and stay resident; ``submit`` places the
    round's coded inputs the same way, every device's block in one grouped
    transfer, and each worker's program selects its share from its
    device's blocks by its slot (``core.programs.worker_share_program``,
    jitted *per device*: its own bounded trace cache, so the
    bounded-program contract is per-device).  The n subtasks then enqueue
    on their own device queues with no per-call thread hop, and
    ``collect`` reaps the fastest delta via per-array readiness
    (``jax.Array.is_ready``), discarding late arrivals exactly like the
    thread pool.  Injected straggler delays are honored as *delayed
    dispatch* (a timer defers the enqueue by ``delays[i]`` — a simulated
    network/queueing delay ahead of the subtask, whose share is already on
    its device), so the deterministic straggler tests and experiments run
    unchanged on the device pool; with zero delays the variance you measure
    is the real per-device one.

Both pools expose a non-blocking ``ready(pending, delta)`` next to the
blocking ``collect``: the serving engine keeps several master/worker
rounds in flight (round pipelining) and reaps whichever finishes first
instead of FIFO-blocking on the oldest.  ``ready`` never mutates the
pending batch — a True just means the immediately following ``collect``
will return without waiting.  The device pool's collect polls with
exponential backoff (``_POLL_MIN`` up to ``_POLL_MAX``, reset on
progress) so a master blocked on a long worker round stops burning a
core; pass an explicit ``poll_interval_s`` for a fixed period (tests).

Both pools share the ``PendingBatch`` in-flight handle and the
inf = dead / nan = discarded / finite = measured ``worker_times``
convention, so ``LayerTiming`` semantics are pool-independent.  Only an
*injected* dead worker (``InjectedWorkerFailure``) is tolerated as a lost
subtask; any other exception a worker raises — a kernel the compiler
refuses, device memory exhausted, a device fault — is a real fault and
propagates out of ``collect`` to the round's caller.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.programs import worker_share_program

from .spans import WORKER_PREP, WORKER_RUN, WORKER_STRAGGLE, timed

__all__ = [
    "ClusterDegraded", "DeviceWorkerPool", "InjectedWorkerFailure",
    "PendingBatch", "StragglerModel", "ThreadWorkerPool", "make_pool",
    "resolve_pool",
]


class ClusterDegraded(RuntimeError):
    pass


class InjectedWorkerFailure(Exception):
    """A worker the ``StragglerModel`` declares dead (``delay = inf``) — the
    one failure a round tolerates by decoding from the survivors."""


@dataclasses.dataclass
class StragglerModel:
    """Per-worker latency injection (seconds added to compute time)."""

    delays: np.ndarray  # (n,) extra seconds; np.inf = dead worker

    @staticmethod
    def none(n: int) -> "StragglerModel":
        return StragglerModel(np.zeros(n))

    @staticmethod
    def fixed(n: int, stragglers: int, delay: float, seed: int = 0) -> "StragglerModel":
        rng = np.random.default_rng(seed)
        d = np.zeros(n)
        idx = rng.choice(n, size=stragglers, replace=False)
        d[idx] = delay
        return StragglerModel(d)

    @staticmethod
    def random_uniform(n: int, p: float, delay: float, seed: int = 0) -> "StragglerModel":
        rng = np.random.default_rng(seed)
        return StragglerModel(np.where(rng.random(n) < p, delay, 0.0))


@dataclasses.dataclass
class PendingBatch:
    """In-flight coded dispatch: n submitted subtasks awaiting ``collect``.

    ``futures`` holds the per-worker futures (threads mode); ``results``
    holds the precomputed outputs (simulated mode) or the asynchronously
    dispatched device arrays (device pool — filled in under ``lock`` as
    timer-deferred stragglers dispatch).  ``worker_times`` is live — workers
    write into it as they finish — so ``collect`` snapshots it before
    returning; ``finish_t`` (the ``perf_counter`` time a worker finished;
    on the device pool, when the reaper first saw its result) is live the
    same way.
    ``expected`` is the set of live workers; ``errors`` (device pool) holds
    what a dispatch raised, for ``collect`` to re-raise; ``placements``
    counts the transfers that placed the round's shares on their devices
    (the device pool's one grouped placement; 0 where the workers read the
    shares in place).  ``collect`` fills
    in ``cancelled`` (subtasks it cancelled before they started) and
    ``delta_ready_s`` (submit to the delta-th finish)."""

    futures: dict
    results: dict  # guarded-by: self.lock
    worker_times: list  # guarded-by: single-writer-slots
    t_start: float
    expected: set | None = None
    lock: threading.Lock | None = None
    errors: list = dataclasses.field(default_factory=list)  # guarded-by: self.lock
    placements: int = 0  # guarded-by: submit-thread
    finish_t: list = dataclasses.field(default_factory=list)  # guarded-by: single-writer-slots
    cancelled: int = 0  # guarded-by: collect-thread
    delta_ready_s: float = float("nan")  # guarded-by: collect-thread

    @property
    def started(self) -> int:
        """Subtasks that reached the device: live workers minus those
        ``collect`` cancelled before they started."""
        return len(self.expected) - self.cancelled

    @staticmethod
    def open(delays, lock: threading.Lock | None = None) -> "PendingBatch":
        """An empty batch of ``len(delays)`` subtasks submitted now: dead
        (infinite-delay) workers read inf, live ones nan until they
        finish."""
        n = len(delays)
        live = {i for i in range(n) if np.isfinite(delays[i])}
        return PendingBatch(
            {}, {}, [float("nan") if i in live else float("inf")
                     for i in range(n)],
            time.perf_counter(), expected=live, lock=lock,
            finish_t=[float("nan")] * n)


class PrepTally:
    """The workers' share-preparation seconds, added by whichever thread
    prepared a share and drained by the collecting one.  A subtask that
    prepares after its round was collected (a straggler, a deferred
    dispatch) lands in a later round's count instead of in none."""

    def __init__(self):
        self._lock = threading.Lock()
        self._s = 0.0  # guarded-by: self._lock

    def add(self, s: float) -> None:
        with self._lock:
            self._s += s

    def take(self) -> float:
        """The seconds added since the last ``take``."""
        with self._lock:
            s, self._s = self._s, 0.0
        return s


def resolve_pool(pool: str | None, mode: str, devices=None) -> str:
    """The pool-selection rule shared by every entry point.

    Explicit ``"threads"``/``"device"`` is honored (``"device"`` requires
    ``mode="threads"`` — the simulated clock has no device queues to race).
    ``None`` auto-selects: the device pool whenever real parallelism is
    available (``mode="threads"`` and more than one addressable device, or
    an explicit device list), else the thread pool — so a plain 1-device
    host keeps today's behavior and an ``XLA_FLAGS`` multi-device host (or
    a real accelerator slice) gets device parallelism without a flag."""
    if pool is None:
        if mode == "threads" and (
            devices is not None or len(jax.devices()) > 1
        ):
            return "device"
        return "threads"
    if pool not in ("threads", "device"):
        raise ValueError(f"unknown pool {pool!r}; use 'threads' or 'device'")
    if pool == "device" and mode != "threads":
        raise ValueError(
            f"pool='device' requires mode='threads', got mode={mode!r}"
        )
    return pool


def make_pool(pool: str, n: int, straggler: StragglerModel, *,
              mode: str = "threads", devices=None):
    if pool == "device":
        return DeviceWorkerPool(n, straggler, devices=devices)
    return ThreadWorkerPool(n, straggler, mode=mode)


class ThreadWorkerPool:
    """Persistent per-worker single-thread executors (and the simulated
    clock), computing on the default device.  One executor per worker: a
    straggler still sleeping on an abandoned subtask keeps *its own* node
    busy (its next subtask queues behind, like a real overloaded worker)
    without ever blocking the fast workers."""

    kind = "threads"

    def __init__(self, n: int, straggler: StragglerModel, *,
                 mode: str = "threads"):
        assert mode in ("threads", "simulated")
        self.n = n
        self.straggler = straggler
        self.mode = mode
        self.prep = PrepTally()
        # each worker's index as a device scalar, made once: the program
        # selects the worker's share with it, and a Python int would cost
        # a host-to-device copy every subtask
        self._index = [jax.device_put(np.int32(i)) for i in range(n)]
        # lazy create (first submit) vs shutdown swap race from another
        # thread: both transitions go through the lock
        self._lifecycle_lock = threading.Lock()
        self._pools: list[ThreadPoolExecutor] | None = None  # guarded-by: self._lifecycle_lock

    # -- lifecycle ---------------------------------------------------------
    def _ensure_pools(self) -> list[ThreadPoolExecutor]:
        with self._lifecycle_lock:
            if self._pools is None:
                self._pools = [
                    ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix=f"fcdcc-worker-{i}"
                    )
                    for i in range(self.n)
                ]
            return self._pools

    def shutdown(self) -> None:
        with self._lifecycle_lock:
            pools, self._pools = self._pools, None
        if pools:
            for ex in pools:
                ex.shutdown(wait=False, cancel_futures=True)

    # -- program/filter placement ------------------------------------------
    def program(self, key: tuple, raw, i: int, jit_cache: dict):
        """All workers share ONE jitted program on the default device (the
        cluster's cache), which selects each worker's share from the
        stacked arrays by a traced index (``worker_share_program``);
        per-worker specialization is a device-pool thing."""
        fn = jit_cache.get(key)
        if fn is None:
            fn = jit_cache[key] = worker_share_program(raw)
        return fn

    def resident_filters(self, name: str, ke):
        return ke  # single device: the master copy IS the resident copy

    def drop_filters(self, prefix: str) -> None:
        pass

    def gather(self, arr):
        return arr

    def warm(self, fn, xe, ke) -> None:
        """Compile outside the timed collect: one worker-0 call suffices —
        every worker runs the same program on the same device, its index
        a traced argument."""
        jax.block_until_ready(fn(0)(xe, ke, self._index[0]))

    # -- dispatch / reap ---------------------------------------------------
    def submit(self, fn, xe, ke, round_id: int = -1) -> PendingBatch:
        delays = self.straggler.delays
        pending = PendingBatch.open(delays)

        def work(i):
            if i not in pending.expected:
                raise InjectedWorkerFailure(f"worker {i} failed")
            with timed(WORKER_PREP, round=round_id, worker=i) as prep:
                program, index = fn(i), self._index[i]
            self.prep.add(prep.s)
            with timed(WORKER_RUN, round=round_id, worker=i) as run:
                out = jax.block_until_ready(program(xe, ke, index))
            if self.mode == "threads" and delays[i] > 0:
                with timed(WORKER_STRAGGLE, round=round_id, worker=i):
                    time.sleep(delays[i])
            pending.finish_t[i] = time.perf_counter()
            pending.worker_times[i] = prep.s + run.s + delays[i]
            return i, out

        if self.mode == "threads":
            pools = self._ensure_pools()
            pending.futures.update(
                (i, pools[i].submit(work, i)) for i in range(self.n))
        else:  # simulated clock: compute all live workers synchronously
            for i in sorted(pending.expected):
                _, pending.results[i] = work(i)
        return pending

    def ready(self, pending: PendingBatch, delta: int) -> bool:
        """Non-blocking: would ``collect`` return without waiting?  True
        once delta subtasks finished cleanly, once a subtask hit a real
        fault (``collect`` raises it), or once *every* future is done, so
        a degraded round reports ready and lets ``collect`` raise
        ``ClusterDegraded`` instead of the engine polling it forever."""
        if self.mode != "threads":
            return True  # simulated: results were computed at submit time
        done = [f for f in pending.futures.values() if f.done()]
        errs = [f.exception() for f in done]
        ok = sum(1 for e in errs if e is None)
        faulted = any(e is not None
                      and not isinstance(e, InjectedWorkerFailure)
                      for e in errs)
        return ok >= delta or faulted or len(done) == len(pending.futures)

    def collect(self, pending: PendingBatch, delta: int):
        results = dict(pending.results)
        if self.mode == "threads":
            results = {}
            outstanding = set(pending.futures.values())
            while len(results) < delta and outstanding:
                done, outstanding = wait(outstanding, return_when=FIRST_COMPLETED)
                for f in done:
                    try:
                        i, out = f.result()
                    except InjectedWorkerFailure:
                        continue
                    except Exception:
                        for g in outstanding:  # abandon the rest, re-raise
                            g.cancel()
                        raise
                    results[i] = out
            t_compute = time.perf_counter() - pending.t_start
            # abandon stragglers, don't join them; a subtask still queued
            # behind its worker's previous one never starts
            pending.cancelled = sum(f.cancel() for f in outstanding)
            finished = sorted(pending.finish_t[i] for i in results)
            pending.delta_ready_s = (
                finished[min(delta, len(finished)) - 1] - pending.t_start
                if finished else t_compute)
        else:  # completion time = max simulated clock over the chosen delta
            order = sorted(results, key=lambda i: pending.worker_times[i])
            results = {i: results[i] for i in order[:delta]}
            t_compute = (
                max(pending.worker_times[i] for i in results)
                if results else float("inf")
            )
            pending.delta_ready_s = t_compute
        return results, list(pending.worker_times), t_compute


class DeviceWorkerPool:
    """n coded workers pinned one-per-``jax.Device`` (round-robin when the
    mesh is smaller), with per-device resident filter blocks and
    per-device jit caches.  See the module docstring for the dispatch/reap
    model."""

    kind = "device"

    # adaptive collect-poll bounds: start near the old fixed 50µs period
    # (well under one subtask), back off exponentially toward 1ms while
    # nothing lands so a master parked on a long worker round stops
    # burning a core, reset on every reaped result
    _POLL_MIN = 5e-6
    _POLL_MAX = 1e-3

    def __init__(self, n: int, straggler: StragglerModel, *, devices=None,
                 mesh=None, poll_interval_s: float | None = None):
        from repro.launch.mesh import make_worker_mesh
        from repro.sharding import worker_devices

        self.n = n
        self.straggler = straggler
        self.mesh = mesh if mesh is not None else make_worker_mesh(n, devices)
        self.devices = worker_devices(self.mesh, n)  # len n (round-robin)
        # worker i: block i % m, slot i // m (``_place``)
        self._block_devices = list(self.mesh.devices.flat)
        m = len(self._block_devices)
        self._block_sharding = NamedSharding(
            self.mesh, PartitionSpec(None, self.mesh.axis_names))
        # each worker's slot as a scalar on its device, made once: the
        # program selects the worker's share with it, and a Python int
        # would cost a host-to-device copy every subtask
        self._slot = [jax.device_put(np.int32(i // m), self.devices[i])
                      for i in range(n)]
        # decode runs on the master device: where the default jit places it
        self.master = jax.devices()[0]
        self.prep = PrepTally()
        # None = adaptive exponential backoff; a number = fixed period
        # (kept as the deterministic override for tests)
        self._poll_interval_s = poll_interval_s
        # per-(program key, device) jit cache: a separate jax.jit object per
        # device keeps trace accounting per device (one shared jit would
        # pool every device's specializations in one opaque cache), so the
        # engine thread (hot path get-or-create) and caller threads
        # (load/unload placement) share these registries
        self._state_lock = threading.RLock()
        # bounded-program contract can be asserted device by device
        self._programs: dict[tuple, object] = {}  # guarded-by: self._state_lock
        # resident filter blocks: name -> (master ke ref, [per-device block])
        # — keyed by the cluster's namespaced layer name, invalidated by
        # master-array identity so re-encoded filters are re-placed
        self._filters: dict[str, tuple] = {}  # guarded-by: self._state_lock
        self._timers: set[threading.Timer] = set()  # guarded-by: self._timer_lock
        self._timer_lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------------
    def shutdown(self) -> None:
        """Cancel undelivered delayed dispatches and drop device-resident
        state (programs and filter shards re-materialize lazily on reuse)."""
        with self._timer_lock:
            timers, self._timers = set(self._timers), set()
        for t in timers:
            t.cancel()
        with self._state_lock:
            self._programs.clear()
            self._filters.clear()

    # -- program/filter placement ------------------------------------------
    def program(self, key: tuple, raw, i: int, jit_cache: dict = None):
        """Worker ``i``'s device's own jitted ``worker_share_program``,
        called as ``(xe_block, ke_block, slot)``."""
        dev = self.devices[i]
        with self._state_lock:
            fn = self._programs.get((key, dev))
            if fn is None:
                fn = self._programs[(key, dev)] = worker_share_program(raw)
            return fn

    def program_traces(self) -> dict:
        """Per-device jit-trace counts ``{device: traces}`` — the device
        pool's half of the bounded-program contract."""
        out: dict = {}
        with self._state_lock:
            programs = dict(self._programs)
        for (_, dev), fn in programs.items():
            out[dev] = out.get(dev, 0) + fn._cache_size()
        return out

    def _place(self, stacked) -> list:
        """The n stacked shares ``(n, ...)`` as one block per mesh device,
        ``(slots, ...)`` with slot ``j`` of device ``d`` worker
        ``j * m + d``'s share, placed in one grouped transfer."""
        placed = jax.device_put(
            _share_blocks(stacked, len(self._block_devices)),
            self._block_sharding)
        by_device = {s.device: s.data for s in placed.addressable_shards}
        return [by_device[d] for d in self._block_devices]

    def _pick(self, blocks: list, i: int):
        return blocks[i % len(self._block_devices)]

    def resident_filters(self, name: str, ke) -> list:
        """The per-device filter blocks of coded filters ``ke`` under the
        namespaced layer ``name`` — placed once (the paper's pre-stored
        filters), reused until ``ke`` is a different array."""
        with self._state_lock:
            ent = self._filters.get(name)
            if ent is None or ent[0] is not ke:
                blocks = jax.block_until_ready(self._place(ke))
                ent = self._filters[name] = (ke, blocks)
            return ent[1]

    def drop_filters(self, prefix: str) -> None:
        with self._state_lock:
            for name in [k for k in self._filters if k.startswith(prefix)]:
                del self._filters[name]

    def gather(self, arr):
        """One surviving shard to the master device (decode gathers only
        the fastest delta — discarded shards never move)."""
        return jax.device_put(arr, self.master)

    def warm(self, fn, xe, ke) -> None:
        """Compile the placement and, on every device with a live worker,
        the worker program on that device's blocks (per-device jit caches)
        outside the timed collect.  The devices compile at once, each on a
        thread of its own."""
        blocks, first = self._place(xe), {}
        for i in range(self.n):
            if np.isfinite(self.straggler.delays[i]):
                first.setdefault(self.devices[i], i)

        def run(i):
            return fn(i)(self._pick(blocks, i), self._pick(ke, i),
                         self._slot[i])

        with ThreadPoolExecutor(max_workers=max(len(first), 1)) as ex:
            jax.block_until_ready(list(ex.map(run, first.values())))

    # -- dispatch / reap ---------------------------------------------------
    def submit(self, fn, xe, ke, round_id: int = -1) -> PendingBatch:
        """Place the round's shares on their devices in one grouped
        transfer, then dispatch every live worker: now, or ``delays[i]``
        later on a timer thread (the share is already on its device)."""
        delays = self.straggler.delays
        pending = PendingBatch.open(delays, lock=threading.Lock())
        with timed(WORKER_PREP, round=round_id) as place:
            blocks = self._place(xe)
        self.prep.add(place.s)
        pending.placements = 1

        def dispatch(i):
            # async: enqueues on device i's queue and returns immediately;
            # a refused compile is kept for collect to raise (a deferred
            # dispatch runs on a timer thread, where it would otherwise
            # vanish and leave collect waiting forever)
            try:
                with timed(WORKER_PREP, round=round_id, worker=i) as prep:
                    program = fn(i)
                    args = (self._pick(blocks, i), self._pick(ke, i),
                            self._slot[i])
                self.prep.add(prep.s)
                with timed(WORKER_RUN, round=round_id, worker=i):
                    out = program(*args)
            except Exception as err:
                with pending.lock:
                    pending.errors.append(err)
                return
            with pending.lock:
                pending.results[i] = out

        for i in sorted(pending.expected):  # dead workers: never dispatched
            if delays[i] > 0:
                # injected straggler = delayed dispatch (simulated network/
                # queueing delay ahead of the subtask)
                self._defer(float(delays[i]), i, dispatch)
            else:
                dispatch(i)
        return pending

    def _defer(self, delay: float, i: int, dispatch) -> None:
        def run():
            try:
                dispatch(i)
            finally:
                with self._timer_lock:
                    self._timers.discard(timer)

        timer = threading.Timer(delay, run)
        timer.daemon = True
        with self._timer_lock:
            self._timers.add(timer)
        timer.start()

    def ready(self, pending: PendingBatch, delta: int) -> bool:
        """Non-blocking: are ``delta`` (or all expected, for degraded
        rounds) results resident and ready to reap right now?"""
        need = min(delta, len(pending.expected))
        with pending.lock:
            avail = list(pending.results.values())
            if pending.errors:
                return True
        return sum(1 for a in avail if a.is_ready()) >= need

    def collect(self, pending: PendingBatch, delta: int):
        """Poll per-array readiness until the fastest ``delta`` devices have
        delivered; later arrivals are discarded (their device finishes the
        subtask, naturally backpressuring its own next dispatch, but the
        array is never gathered).  The poll period backs off exponentially
        while no result lands and resets on progress (or stays fixed when
        an explicit ``poll_interval_s`` was given)."""
        need = min(delta, len(pending.expected))
        reaped: dict[int, object] = {}
        sleep_s = self._POLL_MIN
        while len(reaped) < need:
            with pending.lock:
                if pending.errors:
                    raise pending.errors[0]
                avail = {i: a for i, a in pending.results.items()
                         if i not in reaped}
            progressed = False
            for i, a in avail.items():
                if a.is_ready():
                    reaped[i] = a
                    pending.finish_t[i] = time.perf_counter()
                    pending.worker_times[i] = \
                        pending.finish_t[i] - pending.t_start
                    progressed = True
                    if len(reaped) >= delta:
                        break
            if len(reaped) >= need:
                break
            if progressed:
                sleep_s = self._POLL_MIN
            elif self._poll_interval_s is not None:
                time.sleep(self._poll_interval_s)
            else:
                time.sleep(sleep_s)
                sleep_s = min(sleep_s * 2, self._POLL_MAX)
        t_compute = time.perf_counter() - pending.t_start
        # the reaper's first sight of the delta-th result
        pending.delta_ready_s = max(
            (pending.worker_times[i] for i in reaped), default=t_compute)
        return reaped, list(pending.worker_times), t_compute


@functools.partial(jax.jit, static_argnums=1)
def _share_blocks(stacked, m: int):
    """``(n, s0, ...)`` stacked shares laid out as ``(slots, m * s0, ...)``
    with ``slots = ceil(n / m)``, so splitting axis 1 into ``m`` blocks
    gives device ``d`` the shares of workers ``d, d + m, ...``.  Where ``m``
    does not divide ``n`` the last slots repeat the first shares; no worker
    computes them."""
    n = stacked.shape[0]
    slots = -(-n // m)
    if slots * m > n:
        stacked = jnp.concatenate([stacked, stacked[:slots * m - n]])
    return stacked.reshape((slots, m * stacked.shape[1]) + stacked.shape[2:])
