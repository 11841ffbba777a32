"""Serve one cell through the program's public entry and measure it.

The system under test is built the way ``launch.serve.build_cnn_server``
builds it: ``CodedServer(straggler=..., mode="threads", bucket_sizes=...)``
plus ``register_model(arch, build_cnn_pipeline(arch, params, n,
default_kab=(k_a, k_b)))``.  Only the deployment's parameters come from
the configuration file; every implementation choice (backend, worker
pool, pipeline depth, poll intervals, transition fusion) is left to the
program's defaults, so a later change to a default is measured.

A run: weights and images from the seed, build, warm up every bucket,
start the server, send the unmeasured lead-in, then the window.  Every
served answer, lead-in included, is compared with the plain reference
once the window has closed and the server is shut down.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time

import numpy as np

from . import geometry, reference, spec, traffic
from . import trace as tracing

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# a minute past the window's close for every answer still due
ANSWER_GRACE_S = 60.0
# a --trace 1 run traces the window's last seconds (a trace of a whole
# window would be large and slow to read)
TRACE_S = 10.0


class Stalls:
    """What froze the process during the window, for the log: the main
    thread's longest oversleep of a 10 ms nap (the whole process stalled)
    and the longest and total Python garbage-collection pauses."""

    def __init__(self):
        self.oversleep_s, self.oversleep_at = 0.0, 0.0
        self.gc_max_s, self.gc_total_s, self._gc_t = 0.0, 0.0, None

    def nap(self, until: float, t0: float) -> None:
        while True:
            now = time.perf_counter()
            left = until - now
            if left <= 0:
                return
            nap = min(left, 0.01)
            time.sleep(nap)
            late = time.perf_counter() - now - nap
            if late > self.oversleep_s:
                self.oversleep_s, self.oversleep_at = late, now - t0

    def on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            d = time.perf_counter() - self._gc_t
            self.gc_max_s = max(self.gc_max_s, d)
            self.gc_total_s += d

    def line(self) -> str:
        return (f"longest main-thread oversleep {self.oversleep_s * 1e3:.1f} "
                f"ms at {self.oversleep_at:.1f} s; Python GC pauses: longest "
                f"{self.gc_max_s * 1e3:.1f} ms, total "
                f"{self.gc_total_s * 1e3:.1f} ms")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _json_pool(pool):
    """A program's pool in the configuration's JSON form: an int as it is,
    a mapping, dataclass or named tuple as a dict (a key with a trailing
    ``_``, as a keyword such as ``global`` has to be spelled, loses it)."""
    if dataclasses.is_dataclass(pool) and not isinstance(pool, type):
        pool = dataclasses.asdict(pool)
    elif hasattr(pool, "_asdict"):
        pool = pool._asdict()
    if isinstance(pool, dict):
        return {k.rstrip("_"): v for k, v in pool.items()}
    return pool


def _program_entry(layer) -> dict:
    """A program's layer descriptor as a configuration entry: every
    vocabulary key it carries, read by attribute (``from_`` for the keyword
    ``from``); a key it lacks takes the vocabulary's default."""
    entry = {}
    for key in spec.LAYER_KEYS:
        for attr in (key, key + "_"):
            if hasattr(layer, attr):
                entry[key] = getattr(layer, attr)
                break
    if "pool" in entry:
        entry["pool"] = _json_pool(entry["pool"])
    return entry


def check_program_layers(config: dict) -> None:
    """The program's own layer table for ``arch`` must be the config's
    layer graph, every vocabulary key with its default filled in (the
    reference runs the config's; this keeps the two the same)."""
    from repro.models.cnn import CNN_SPECS

    arch = config["arch"]
    if arch not in CNN_SPECS:
        raise ValueError(f"the program has no arch {arch!r} (it has "
                         f"{sorted(CNN_SPECS)})")
    _, layers = CNN_SPECS[arch]
    mine = spec.nodes(config)
    theirs = spec.nodes({"layers": [_program_entry(l) for l in layers]})
    if mine != theirs:
        raise ValueError(f"{arch}: the program's layers {theirs} differ "
                         f"from the configuration's {mine}")


@dataclasses.dataclass
class Window:
    """What one measured window left behind."""

    t0: float
    t1: float
    sent: list            # every traffic.Sent, lead-in included
    stats: object         # ServingStats over requests finished in the window
    overlap: object       # OverlapStats over rounds collected in the window
    compiles: list        # programs compiled (or loaded) inside the window
    trace: dict | None    # trace.reduce() of the window, --trace 1 only
    lateness_p95_s: float  # how late the open-loop generator submitted

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def due(self):
        """Requests due (open) or sent (closed) inside the window."""
        return [s for s in self.sent if self.t0 <= s.due_t < self.t1]


class ServedCell:
    """One configuration served under one traffic mix, in this process."""

    def __init__(self, config: dict, traffic_spec: dict, seed: int):
        self.config = config
        self.traffic = traffic_spec
        self.seed = seed
        self.server = None
        self.compiled: list[str] = []  # every backend compile, by name
        self._listening = False

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.core.pipeline import build_cnn_pipeline
        from repro.runtime import StragglerModel
        from repro.serving import CodedServer

        cfg, tr = self.config, self.traffic
        check_program_layers(cfg)
        self.model = cfg["arch"]
        self.params = reference.make_weights(cfg, self.seed)
        pool = reference.make_images(cfg, int(tr["image_pool"]), self.seed)
        self.pool = pool
        self.images = [pool[i] for i in range(pool.shape[0])]
        delays = traffic.straggler_delays(cfg["n"], tr, self.seed)
        self.stragglers = [int(i) for i in np.flatnonzero(delays)]
        self.server = CodedServer(straggler=StragglerModel(delays),
                                  mode="threads",
                                  bucket_sizes=tuple(cfg["buckets"]))
        self.server.register_model(self.model, build_cnn_pipeline(
            cfg["arch"], self.params, cfg["n"],
            default_kab=(cfg["k_a"], cfg["k_b"]),
            input_hw=cfg["input_hw"]))
        self.server.warmup()
        if tr["loop"] == "open":
            self._warm_batch_shapes()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._listening = True
        self.server.start()

    def _warm_batch_shapes(self) -> None:
        """Compile the small eager programs that batch assembly runs on the
        host, for every batch size an open loop can bring: stacking k
        images, padding k rows to their bucket, and the equal-depth merge
        of two fragments (slice, concatenate, pad) at every distinct input
        shape of the layer graph's entries.
        ``CodedServer.warmup`` covers only the full-bucket programs; these
        would otherwise compile inside the window the first time a batch
        of that size appears.  Only shapes are shared with the program:
        JAX caches an eager operation by its operands' shapes.  Should the
        program assemble its batches otherwise, the compilations return to
        the window and the run's ``compiles_in_window`` check fails it."""
        import jax
        import jax.numpy as jnp

        buckets = sorted(self.config["buckets"])
        top = buckets[-1]

        def bucket_of(k):
            return next(b for b in buckets if b >= k)

        def pad(x):
            k = x.shape[0]
            if bucket_of(k) > k:
                x = jnp.concatenate([x, jnp.zeros(
                    (bucket_of(k) - k,) + x.shape[1:], x.dtype)], axis=0)
            return x

        img = self.images[0]
        outs = [pad(jnp.stack([img] * k, axis=0)) for k in range(1, top + 1)]
        shapes = dict.fromkeys((g.in_ch, g.in_hw, g.in_hw)
                               for g in geometry.layers(self.config))
        for shape in shapes:
            full = {b: jnp.zeros((b,) + shape, img.dtype) for b in buckets}
            rows = {k: full[bucket_of(k)][tuple(
                [slice(0, k)] + [slice(None)] * len(shape))]
                for k in range(1, top + 1)}
            for a in range(1, top):
                for b in range(a, top - a + 1):
                    outs.append(pad(jnp.concatenate([rows[a], rows[b]],
                                                    axis=0)))
        jax.block_until_ready(outs)

    def _on_event(self, name: str, _secs: float, **kw) -> None:
        if name == BACKEND_COMPILE:
            self.compiled.append(kw.get("fun_name", "?"))

    def submit(self, image: int):
        return self.server.submit(self.images[image], self.model)

    # -- the window ----------------------------------------------------------
    def _snapshot(self, marks: dict) -> None:
        """The program's own counters, where it still offers them (a reader
        whose input is missing reports nothing)."""
        try:
            marks["stats"] = self.server.stats()
            marks["overlap"] = self.server.overlap_stats()
        except AttributeError as err:
            log(f"bench: the server's counters are unavailable: {err}")

    def window(self, seconds: float, *, rate_per_s: float | None = None,
               trace_dir: str | None = None) -> Window:
        """Lead-in, then ``seconds`` of measured traffic (``rate_per_s``
        overrides the traffic file's rate, for the knee sweep).  With
        ``trace_dir`` the last ``TRACE_S`` seconds of the window are traced
        and, once every answer is in, the map pass."""
        tr = self.traffic
        lead = float(tr.get("lead_in_s", 0.0))
        start = time.perf_counter() + 0.05
        t0_due = start + lead
        t1_due = t0_due + seconds
        if tr["loop"] == "open":
            rate = float(rate_per_s if rate_per_s is not None
                         else tr["rate_per_s"])
            offs, imgs = [], []
            for k, (length, at) in enumerate(((lead, 0.0),
                                              (seconds, lead))):
                if length <= 0:
                    continue
                gaps = traffic.poisson_gaps(rate, length, self.seed, 3 + k)
                offs.append(at + traffic.arrival_offsets(gaps))
                imgs.append(traffic.rng(self.seed, 5 + k).integers(
                    len(self.images), size=len(gaps)))
            gen = traffic.OpenLoop(self.submit, np.concatenate(offs),
                                   np.concatenate(imgs), start)
        else:
            gen = traffic.ClosedLoop(
                self.submit, int(tr["clients"]), len(self.images), self.seed,
                getattr(self.server.scheduler, "completion", None), start,
                t1_due)
        gen.start()
        marks: dict = {}
        Stalls().nap(t0_due, t0_due)
        stalls = Stalls()
        gc.callbacks.append(stalls.on_gc)
        try:
            self.server.metrics.reset()
        except AttributeError as err:
            log(f"bench: the server's counters cannot be reset: {err}")
        compiles0 = len(self.compiled)
        t0 = time.perf_counter()
        stop_trace, ann, tt0 = None, None, t0
        if trace_dir:
            stalls.nap(max(t0, t1_due - TRACE_S), t0)
            stop_trace = tracing.capture(trace_dir)
            ann = tracing.window_annotation()
            ann.__enter__()
            tt0 = time.perf_counter()
        stalls.nap(t1_due, t0)
        t1 = time.perf_counter()
        gc.callbacks.remove(stalls.on_gc)
        log(f"bench: window stalls: {stalls.line()}")
        if ann is not None:
            ann.__exit__(None, None, None)
        self._snapshot(marks)
        compiles = self.compiled[compiles0:]
        gen.join(timeout=ANSWER_GRACE_S)
        if gen.errors:
            raise gen.errors[0]
        sent = gen.sent
        late = [s.sent_t - s.due_t for s in sent] \
            if tr["loop"] == "open" else []
        # every answer, due or late, before the map pass and the trace stop
        deadline = t1 + ANSWER_GRACE_S
        for s in sent:
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            if not s.done():
                s.wait(left)
        reduced = None
        if stop_trace is not None:
            self._map_pass()
            t = time.perf_counter()
            stop_trace()
            t_stop = time.perf_counter() - t
            reduced = tracing.reduce(tracing.load(
                tracing.xplane_path(trace_dir)))
            reduced["t0"] = tt0
            log(f"bench: trace written in {t_stop:.3f} s, read and reduced "
                f"in {time.perf_counter() - t - t_stop:.3f} s")
        return Window(t0, t1, sent, marks.get("stats"), marks.get("overlap"),
                      compiles, reduced,
                      float(np.percentile(late, 95)) if late else float("nan"))

    def _map_pass(self) -> None:
        """Under the trace, before the window: one round of every (layer,
        bucket) in its own ``bench_map/<layer>/<bucket>`` host span, every
        subtask waited for, so the trace ties each compiled worker program
        to its geometry (``trace.reduce``).  Each entry is fed the
        collected output its ``from`` names; an entry with an ``add`` is
        also given, as the keyword ``add`` of
        ``dispatch_pipeline_layer``, the whole collected ``(B, C, H, W)``
        output of the entry its ``add`` names, which the program adds after
        the shift and before the activation in that round's collect.  Every
        other entry is dispatched without the keyword, so a program that
        lacks it still serves every chain, and fails a residual graph here
        with a ``TypeError`` naming ``add``.  An output is kept until its
        last reader, by ``from`` or by ``add``, and dropped after it."""
        import concurrent.futures

        import jax
        import jax.numpy as jnp

        try:
            cluster = self.server.cluster
            pipe = cluster.pipelines[self.model]
            dispatch = cluster.dispatch_pipeline_layer
            collect = cluster.collect_pipeline_layer
        except (AttributeError, KeyError) as err:
            log(f"bench: map pass skipped, the cluster lacks {err}")
            return
        graph = spec.nodes(self.config)
        reads = [[r for r in dict.fromkeys((n.src, n.add)) if r is not None]
                 for n in graph]
        last_read = {r: i for i, rs in enumerate(reads) for r in rs}
        for bucket in pipe.bucket_sizes:
            outs = {spec.INPUT: jnp.zeros((bucket,) + pipe.input_shape,
                                          pipe.input_dtype)}
            for idx, node in enumerate(graph):
                with jax.profiler.TraceAnnotation(
                        f"{tracing.MAP_SPAN}{idx}/{bucket}"):
                    if node.add is None:
                        rnd = dispatch(idx, outs[node.src], self.model)
                    else:
                        rnd = dispatch(idx, outs[node.src], self.model,
                                       add=outs[node.add])
                    concurrent.futures.wait(
                        list(rnd.pending.futures.values()))
                    jax.block_until_ready(list(rnd.pending.results.values()))
                    outs[node.name], _ = collect(rnd)
                for r in reads[idx]:
                    if last_read[r] == idx:
                        del outs[r]

    # -- after the window ----------------------------------------------------
    def close(self) -> None:
        import jax

        if self._listening:
            jax.monitoring.unregister_event_duration_listener(self._on_event)
            self._listening = False
        if self.server is not None:
            self.server.shutdown(drain=False)
            self.server = None
        gc.collect()

    def check(self, sent, *, precision: str = "highest") -> dict:
        """Compare every answer with the reference of its image.  Returns
        the per-request relative errors (inf for a wrong shape or a
        non-finite answer) and the requests that never answered."""
        ref = reference.forward(self.config, self.params, self.pool,
                                precision=precision)
        errs, missing = [], 0
        for s in sent:
            if not s.ok():
                missing += 1
                continue
            out = np.asarray(s.handle.result(timeout=0))
            if out.shape != ref.shape[1:]:
                errs.append(np.inf)
                continue
            errs.append(float(reference.relative_errors(
                out[None], ref[s.image][None])[0]))
        return {"errors": np.asarray(errs), "missing": missing,
                "reference": ref}


def device_info(jax_devices, chips: int) -> dict:
    used = jax_devices[:chips]
    peaks = []
    for d in used:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # a backend without memory statistics
            stats = {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def window_record(cell: ServedCell, w: Window, peaks: dict | None) -> dict:
    """What the per-layer readers in ``bench/metrics/`` read."""
    done = [s.finish_t for s in w.sent if s.ok()]
    tt0 = w.trace["t0"] if w.trace else w.t0
    return {
        "config": cell.config,
        "traffic": cell.traffic,
        "window_s": w.seconds,
        "completed": sum(w.t0 <= t <= w.t1 for t in done),
        "traced_s": w.t1 - tt0,
        "completed_traced": sum(tt0 <= t <= w.t1 for t in done),
        "stats": w.stats,
        "overlap": w.overlap,
        "trace": w.trace,
        "peaks": peaks,
        "geometry": geometry,
    }


def latencies_ms(w: Window) -> np.ndarray:
    """From due time to completion, for every request due in the window;
    one that failed or never answered counts as missing (inf)."""
    return np.asarray([(s.finish_t - s.due_t) * 1e3 if s.ok() else np.inf
                       for s in w.due()])


def end_to_end(name: str, w: Window, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "images_per_s":
        done = [s for s in w.sent if s.ok() and w.t0 <= s.finish_t <= w.t1]
        return len(done) / w.seconds
    if name == "latency_p50_ms":
        return float(np.percentile(latencies_ms(w), 50))
    raise KeyError(f"no end-to-end metric {name!r}")


def out_dir(root: str, *parts: str) -> str:
    path = os.path.join(root, "bench", "out", *parts)
    os.makedirs(path, exist_ok=True)
    return path
