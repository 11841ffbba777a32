"""Capture a profiler trace of the window and reduce it to numbers.

``capture`` wraps ``jax.profiler`` (Python tracer off, so the host code
under test runs at its own speed; JAX's and the runtime's own host events
stay on).  The harness marks the window with a ``bench_window`` host
annotation; everything is clipped to it.

``load`` turns the ``.xplane.pb`` into plain ``Plane``/``Line``/``Event``
records and ``reduce`` computes, from those alone:

  * ``busy_s``: the union of the intervals in which a device operation ran
    (the ``XLA Ops`` line of each device plane), averaged over devices;
  * ``programs``: device seconds per XLA module, by its jit name
    (``jit_worker_compute``, ``jit_encode_inputs``, ``jit_dec``, ...),
    and ``program_runs``: how many times each ran;
  * ``worker_runs``: runs of the worker program in the window per
    ``(layer, batch)``, and ``worker_s`` their device seconds.  Each
    compiled worker program is tied to its geometry by a map pass run
    under the trace after the window, with no traffic: one round per
    (layer, batch), each inside a ``bench_map/<layer>/<batch>`` host span;
  * ``top_ops``: the device operations that took most time;
  * ``idle_gaps``: the longest spans with nothing on the device, each
    labelled with the host events that overlap it most.

The tests build ``Plane`` records by hand and check these numbers.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench_window"
# host spans of the map pass: worker programs run for one (layer, batch)
MAP_SPAN = "bench_map/"
# the program's device programs, by jit name
WORKER = "jit_worker_compute"     # core/fcdcc.py CodedConv2d.worker_compute
ENCODE = "jit_encode_inputs"      # core/fcdcc.py CodedConv2d.encode_inputs
DECODE = "jit_dec"                # CodedPipeline.decoder_fn
TRANSITION = "jit_trans"          # CodedPipeline.transition_fn
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def capture(log_dir: str):
    """Start tracing into ``log_dir``; returns ``stop()``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    return jax.profiler.stop_trace


def window_annotation():
    import jax

    return jax.profiler.TraceAnnotation(WINDOW_SPAN)


def xplane_path(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "TPU" in name \
        and "SYSTEM" not in name


def load(path: str) -> list[Plane]:
    """Device planes with their events' stats; host planes without."""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        device = is_device_plane(p.name)
        if not device and not p.name.startswith("/host:"):
            continue
        lines = []
        for ln in p.lines:
            if device and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.append(Line(ln.name, [
                Event(e.name, e.start_ns, e.duration_ns,
                      dict(e.stats) if device else {})
                for e in ln.events]))
        planes.append(Plane(p.name, lines))
    return planes


def program_name(module_event: str) -> str:
    """``jit_worker_compute(123)`` -> ``jit_worker_compute``."""
    return re.sub(r"\(.*\)$", "", module_event).strip()


def module_key(ev: Event) -> str:
    """One compiled program: its event name and, where the trace has it,
    its program id."""
    pid = ev.stats.get("program_id", ev.stats.get("run_id_program", ""))
    return f"{ev.name}|{pid}"


def _map_spans(planes):
    spans = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith(MAP_SPAN):
                    layer, batch = ev.name[len(MAP_SPAN):].split("/")
                    spans.append((ev.start_ns, ev.end_ns,
                                  (int(layer), int(batch))))
    return spans


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, t0, t1):
    return max(s, t0), min(e, t1)


def find_window(planes) -> tuple[float, float]:
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name == WINDOW_SPAN:
                    return ev.start_ns, ev.end_ns
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def reduce(planes, top: int = 10) -> dict:
    t0, t1 = find_window(planes)
    spans = _map_spans(planes)
    geometry_of: dict[str, tuple] = {}
    worker_runs: dict[tuple, int] = {}
    worker_s = 0.0
    unmapped = 0
    devices = [p for p in planes if is_device_plane(p.name)]
    host_events = [ev for p in planes if p.name.startswith("/host:")
                   for ln in p.lines for ev in ln.events
                   if ev.name != WINDOW_SPAN and ev.dur_ns > 0
                   and ev.end_ns > t0 and ev.start_ns < t1]
    busy_total = 0.0
    programs: dict[str, float] = {}
    runs: dict[str, int] = {}
    ops: dict[str, float] = {}
    gaps = []
    for p in devices:
        lines = {ln.name: ln for ln in p.lines}
        op_iv = []
        for ev in lines.get(OPS_LINE, Line("", [])).events:
            s, e = _clip(ev.start_ns, ev.end_ns, t0, t1)
            if e > s:
                op_iv.append((s, e))
                ops[ev.name] = ops.get(ev.name, 0.0) + (e - s) * 1e-9
        modules = lines.get(MODULES_LINE, Line("", [])).events
        for s, e, cell in spans:
            for ev in modules:
                if s <= ev.start_ns < e and program_name(ev.name) == WORKER:
                    geometry_of[module_key(ev)] = cell
        for ev in modules:
            s, e = _clip(ev.start_ns, ev.end_ns, t0, t1)
            if e > s:
                name = program_name(ev.name)
                programs[name] = programs.get(name, 0.0) + (e - s) * 1e-9
                runs[name] = runs.get(name, 0) + 1
                if name != WORKER:
                    continue
                cell = geometry_of.get(module_key(ev))
                if cell is None:
                    unmapped += 1
                else:
                    worker_runs[cell] = worker_runs.get(cell, 0) + 1
                    worker_s += (e - s) * 1e-9
        merged = _union(op_iv)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e, p.name))
    window_s = (t1 - t0) * 1e-9
    gaps.sort(reverse=True)
    return {
        "window_s": window_s,
        "devices": len(devices),
        "busy_s": busy_total / len(devices) if devices else 0.0,
        "programs": programs,
        "program_runs": runs,
        "worker_runs": worker_runs,
        "worker_s": worker_s,
        "worker_unmapped": unmapped,
        "top_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_label(s, e, host_events), d * 1e-9]
                      for d, s, e, _ in gaps[:top]],
    }


def _label(s: float, e: float, host_events) -> str:
    """What the host was doing in [s, e]: the events covering most of it,
    each with how many host threads ran it on average over the gap."""
    cover: dict[str, float] = {}
    for ev in host_events:
        o = min(e, ev.end_ns) - max(s, ev.start_ns)
        if o > 0:
            cover[ev.name] = cover.get(ev.name, 0.0) + o
    names = sorted(cover, key=lambda n: -cover[n])[:3]
    return "; ".join(f"{n} x{cover[n] / (e - s):.2f}" for n in names) \
        or "no host event"
