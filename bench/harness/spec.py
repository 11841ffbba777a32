"""Resolve a workload name to its files, as ``BENCHMARK.json`` lists them.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own; this module finds them by
name and nothing else in the harness knows a cell by name:

  * ``configs[].file``          -> the configuration (sizes, code, layers)
  * ``bench/traffic/<mix>.json`` -> the traffic mix's parameters
  * ``bench/metrics/<name>.py``  -> one reader per per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


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
