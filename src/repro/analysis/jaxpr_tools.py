"""Jaxpr introspection helpers for the contract analyzer.

Everything here operates on traced jaxprs only — no data is executed.
The helpers recurse through nested closed jaxprs (pjit bodies, scan/cond
branches, custom_jvp calls, ...) because the interesting facts about a
jitted closure — e.g. a constant captured by the jitted function — live
on the *inner* pjit ClosedJaxpr, not the outer trace.
"""

from __future__ import annotations

from typing import Any, Iterator

import jax
import numpy as np
from jax.extend import core as jax_core

ClosedJaxpr = jax_core.ClosedJaxpr
Jaxpr = jax_core.Jaxpr


def _nested_jaxprs(params: dict) -> Iterator[ClosedJaxpr | Jaxpr]:
    for value in params.values():
        if isinstance(value, (ClosedJaxpr, Jaxpr)):
            yield value
        elif isinstance(value, (list, tuple)):
            for item in value:
                if isinstance(item, (ClosedJaxpr, Jaxpr)):
                    yield item


def iter_eqns(jaxpr: ClosedJaxpr | Jaxpr) -> Iterator[Any]:
    """Yield every equation in ``jaxpr`` and all nested jaxprs."""
    inner = jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _nested_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def collect_consts(jaxpr: ClosedJaxpr | Jaxpr) -> list[tuple[Any, Any]]:
    """All (constvar, const_value) pairs, including nested closed jaxprs.

    A jitted closure's captured arrays appear as consts of the inner pjit
    ClosedJaxpr, so a top-level-only scan would miss them.
    """
    out: list[tuple[Any, Any]] = []
    if isinstance(jaxpr, ClosedJaxpr):
        out.extend(zip(jaxpr.jaxpr.constvars, jaxpr.consts))
        inner = jaxpr.jaxpr
    else:
        inner = jaxpr
    for eqn in inner.eqns:
        for sub in _nested_jaxprs(eqn.params):
            out.extend(collect_consts(sub))
    return out


def iter_avals(jaxpr: ClosedJaxpr | Jaxpr) -> Iterator[Any]:
    """Yield the aval of every var (inputs, outputs, intermediates)."""
    inner = jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr
    for var in list(inner.invars) + list(inner.constvars):
        yield var.aval
    for eqn in inner.eqns:
        for var in eqn.outvars:
            yield var.aval
        for sub in _nested_jaxprs(eqn.params):
            yield from iter_avals(sub)


def primitive_names(jaxpr: ClosedJaxpr | Jaxpr) -> set[str]:
    return {eqn.primitive.name for eqn in iter_eqns(jaxpr)}


def const_arrays(jaxpr: ClosedJaxpr | Jaxpr) -> list[np.ndarray]:
    """Baked constants as concrete arrays (skips non-array consts)."""
    arrays = []
    for _, value in collect_consts(jaxpr):
        if hasattr(value, "shape") and hasattr(value, "dtype"):
            arrays.append(np.asarray(value))
    return arrays


def make_jaxpr_abstract(fn, *arg_shapes) -> ClosedJaxpr:
    """Trace ``fn`` on ShapeDtypeStructs without touching data."""
    return jax.make_jaxpr(fn)(*arg_shapes)
