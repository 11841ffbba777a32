"""Resolve a workload name to its files, as ``BENCHMARK.json`` lists them.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own; this module finds them by
name and nothing else in the harness knows a cell by name:

  * ``configs[].file``          -> the configuration (sizes, code, layers)
  * ``bench/traffic/<mix>.json`` -> the traffic mix's parameters
  * ``bench/metrics/<name>.py``  -> one reader per per-layer metric

A configuration's ``layers`` describe its network as a small layer graph.
Each entry is one coded ConvL, in the program's pipeline order, with the
keys ``name``, ``in_ch``, ``out_ch``, ``kernel``, ``stride`` (default 1),
``padding`` (default 0) and five more whose defaults make a chain:

  * ``from``: the entry whose output this conv reads, or ``"input"`` for
    the image; default the previous entry (the image for the first);
  * ``bias``: ``true`` adds a per-output-channel shift after the conv
    (batch-norm folded for inference); default ``false``;
  * ``add``: an earlier entry whose output is added after the shift and
    before the activation (a residual); default none.  The program takes
    it as the keyword ``add`` of ``FcdccCluster.dispatch_pipeline_layer``:
    the whole ``(B, C, H, W)`` output of the entry it names, added in that
    round's collect.  An entry without ``add`` is dispatched without the
    keyword;
  * ``relu``: default ``true``; ``false`` for a projection shortcut, whose
    output is only added;
  * ``pool``: an int k, a k x k non-overlapping floor max-pool (default 1,
    none); or ``{"op": "max", "size": s, "stride": t, "padding": p}``,
    padded with -inf (``stride`` defaults to ``size``, ``padding`` to 0);
    or ``{"op": "avg", "global": true}``, the mean over the whole map.

An entry's output is ``pool(act(conv(x) + bias + out[add]))``; the
network's output is the last entry's.  No other key is taken.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


INPUT = "input"
LAYER_KEYS = ("name", "in_ch", "out_ch", "kernel", "stride", "padding",
              "pool", "from", "bias", "add", "relu")


@dataclasses.dataclass(frozen=True)
class Pool:
    """A pool that is no k x k non-overlapping one: ``"max"``, padded and
    possibly overlapping, or ``"avg"``, the global average."""

    op: str
    size: int = 0
    stride: int = 0
    padding: int = 0


@dataclasses.dataclass(frozen=True)
class Node:
    """One layer-graph entry with every default filled in."""

    name: str
    in_ch: int
    out_ch: int
    kernel: int
    stride: int
    padding: int
    pool: int | Pool
    src: str            # the ``from`` key
    bias: bool
    add: str | None
    relu: bool


def pool_of(value) -> int | Pool:
    """A ``pool`` value in its one canonical form: an int for a k x k
    non-overlapping floor max-pool (a structured max-pool of stride
    ``size`` and no padding is one), else a ``Pool``."""
    if isinstance(value, int) and not isinstance(value, bool):
        if value < 1:
            raise ValueError(f"pool {value!r}: at least 1")
        return value
    if not isinstance(value, dict):
        raise ValueError(f"pool {value!r}: an int or an object")
    v = dict(value)
    if v == {"op": "avg", "global": True}:
        return Pool("avg")
    if v.get("op") != "max" or not set(v) <= {"op", "size", "stride",
                                              "padding"}:
        raise ValueError(f"pool {value!r}: neither {{'op': 'max', 'size', "
                         f"'stride', 'padding'}} nor {{'op': 'avg', "
                         f"'global': true}}")
    size = int(v["size"])
    stride, padding = int(v.get("stride", size)), int(v.get("padding", 0))
    if size < 1 or stride < 1 or not 0 <= padding < size:
        raise ValueError(f"pool {value!r}: size, stride >= 1 and "
                         f"0 <= padding < size")
    if stride == size and padding == 0:
        return size
    return Pool("max", size, stride, padding)


def nodes(config: dict) -> tuple[Node, ...]:
    """The configuration's layer graph (``ValueError`` for a key outside
    the vocabulary, or a ``from`` or ``add`` that names no earlier entry).
    A ``from`` or ``add`` of ``None`` takes the default."""
    out, names = [], set()
    for i, entry in enumerate(config["layers"]):
        extra = set(entry) - set(LAYER_KEYS)
        if extra:
            raise ValueError(f"layer {i}: keys {sorted(extra)} are not in "
                             f"the vocabulary {LAYER_KEYS}")
        name = entry["name"]
        if name == INPUT or name in names:
            raise ValueError(f"layer {i}: name {name!r} is taken")
        src = entry.get("from")
        if src is None:
            src = out[-1].name if out else INPUT
        add = entry.get("add")
        if src != INPUT and src not in names:
            raise ValueError(f"layer {name!r}: from {src!r} names no "
                             f"earlier entry")
        if add is not None and add not in names:
            raise ValueError(f"layer {name!r}: add {add!r} names no earlier "
                             f"entry")
        bias, relu = entry.get("bias", False), entry.get("relu", True)
        if not isinstance(bias, bool) or not isinstance(relu, bool):
            raise ValueError(f"layer {name!r}: bias and relu are true or "
                             f"false")
        out.append(Node(name, int(entry["in_ch"]), int(entry["out_ch"]),
                        int(entry["kernel"]), int(entry.get("stride", 1)),
                        int(entry.get("padding", 0)),
                        pool_of(entry.get("pool", 1)), src, bias, add, relu))
        names.add(name)
    if not out:
        raise ValueError("a configuration needs at least one layer")
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list   # metric entries this cell reports with --trace 1


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reported_in(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``; ``KeyError`` for an unknown name."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"]
                    if _reported_in(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if _reported_in(m, workload)],
    )


def metric_reader(name: str, root: str = ROOT):
    """The ``read(record) -> float | None`` of ``bench/metrics/<name>.py``
    (metric names may hold dots, so the file is loaded by path)."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def enable_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache at the checkout's fixed path
    (``<root>/.jax_cache``, whatever the environment says: two checkouts
    never share one), for programs of every size.  The environment
    variable is set too, for the program's own ``enable_compile_cache``."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
