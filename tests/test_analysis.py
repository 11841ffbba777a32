"""Static-analysis layer: every rule has a seeded violation (positive)
and the repo itself stays clean under ``--strict`` (negative).

The contract-rule positives run on tiny synthetic jitted programs (cheap
to trace); one real pipeline config covers the repo-clean direction so
the whole file stays fast — the full 12-config matrix is the CI gate's
job (``python -m repro.analysis --strict`` in scripts/ci.sh), not the
unit suite's.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import concurrency, contracts
from repro.analysis.findings import Report, Severity

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")
VIOLATIONS = os.path.join(FIXTURES, "conc_violations.py")
CLEAN = os.path.join(FIXTURES, "conc_clean.py")


# -- concurrency lint: seeded violations ------------------------------------

@pytest.fixture(scope="module")
def seeded():
    return concurrency.run(paths=[VIOLATIONS])


def _rules(report: Report, rule: str):
    return [f for f in report.findings if f.rule == rule]


def test_conc_guard_fires_on_unlocked_mutations(seeded):
    found = _rules(seeded, "CONC-GUARD")
    msgs = " | ".join(f.message for f in found)
    assert "GuardViolation.bad" in msgs
    assert "bad_global_write" in msgs
    # two field mutations in bad() plus the module-global write
    assert len(found) == 3


def test_conc_guard_respects_lock_and_interproc_entry(seeded):
    msgs = " | ".join(f.message for f in _rules(seeded, "CONC-GUARD"))
    assert "GuardViolation.ok" not in msgs
    # _apply mutates state but every call site holds the lock
    assert "InterprocHeld" not in msgs


def test_conc_guard_suppression(seeded):
    assert not any(
        "suppressed" in f.message for f in _rules(seeded, "CONC-GUARD")
    )


def test_conc_guard_unknown(seeded):
    found = _rules(seeded, "CONC-GUARD-UNKNOWN")
    assert len(found) == 1
    assert "_no_such_lock" in found[0].message


def test_conc_self_deadlock_lexical_and_interproc(seeded):
    found = _rules(seeded, "CONC-SELF-DEADLOCK")
    msgs = " | ".join(f.message for f in found)
    assert "SelfDeadlock" in msgs
    assert "_acquires" in msgs  # the held-across-call variant
    assert "ReentrantOk" not in msgs


def test_conc_order_cycle(seeded):
    found = _rules(seeded, "CONC-ORDER")
    assert found, "lock-order cycle _a/_b not detected"
    assert any("OrderCycle._a" in f.message and "OrderCycle._b" in f.message
               for f in found)


def test_conc_wait_loop(seeded):
    found = _rules(seeded, "CONC-WAIT-LOOP")
    assert len(found) == 1  # bad_wait only; good_wait + Event.wait pass
    assert "WaitWithoutLoop.cv" in found[0].message


def test_conc_thread_lifecycle(seeded):
    found = _rules(seeded, "CONC-THREAD-LIFECYCLE")
    assert len(found) == 1
    assert "LeakedThreads" in found[0].message


def test_conc_clean_fixture_is_clean():
    report = concurrency.run(paths=[CLEAN])
    assert report.findings == []


def test_repo_concurrency_strict_clean():
    """The serving/runtime stack itself must pass the lint in strict mode."""
    report = concurrency.run(root=os.path.join(os.path.dirname(__file__), ".."))
    assert not report.failed(strict=True), report.render_text(show_info=True)
    # the annotations are live, not decorative: guards bound and checked
    assert report.stats["guarded_fields_checked"] >= 20
    assert report.stats["locks_discovered"] >= 8


# -- contract rules: synthetic seeded violations ----------------------------

def _cell(fn, args, *, allowed=(), donate=()):
    return types.SimpleNamespace(
        fn=fn, args=tuple(args), cell_id="synthetic",
        allowed_const_shapes=tuple(allowed), donate_argnums=tuple(donate),
    )


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_baked_const_positive():
    baked = jnp.asarray(np.ones((8, 8), np.float32))
    cell = _cell(jax.jit(lambda x: x @ baked), [_f32(4, 8)])
    found = contracts.check_jaxpr_contracts(cell)
    assert any(f.rule == "JIT-BAKED-CONST" and f.severity == Severity.ERROR
               for f in found)


def test_baked_const_allowed_shape_and_small_consts_pass():
    baked = jnp.asarray(np.ones((8, 8), np.float32))
    cell = _cell(jax.jit(lambda x: x @ baked), [_f32(4, 8)],
                 allowed=[(8, 8)])
    assert not contracts.check_jaxpr_contracts(cell)
    eps = jnp.asarray(np.float32(1e-6))
    cell = _cell(jax.jit(lambda x: x + eps), [_f32(4, 8)])
    assert not contracts.check_jaxpr_contracts(cell)


def test_f64_positive():
    with jax.enable_x64():
        cell = _cell(
            lambda x: x.astype(jnp.float64).sum(),
            [jax.ShapeDtypeStruct((4,), jnp.float32)],
        )
        found = contracts.check_jaxpr_contracts(cell)
    assert any(f.rule == "JIT-F64" and f.severity == Severity.ERROR
               for f in found)


def test_weak_type_positive():
    cell = _cell(lambda x: jnp.asarray(2.0), [_f32(2)])
    found = contracts.check_jaxpr_contracts(cell)
    assert any(f.rule == "JIT-WEAK-TYPE" and f.severity == Severity.WARNING
               for f in found)


def test_host_callback_positive():
    def fn(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    found = contracts.check_jaxpr_contracts(_cell(fn, [_f32(3)]))
    assert any(f.rule == "JIT-HOST-CALLBACK" and f.severity == Severity.ERROR
               for f in found)


def test_donation_missing_positive():
    # the cell CLAIMS argument 0 is donated, but the jitted fn never was
    cell = _cell(jax.jit(lambda x: x + 1.0), [_f32(8, 8)], donate=[0])
    found = contracts.check_donation(cell)
    assert any(f.rule == "JIT-DONATION" and f.severity == Severity.ERROR
               for f in found)


def test_donation_wired_through_passes():
    cell = _cell(jax.jit(lambda x: x + 1.0, donate_argnums=(0,)),
                 [_f32(8, 8)], donate=[0])
    found = contracts.check_donation(cell)
    assert not [f for f in found if f.severity == Severity.ERROR]


def test_donation_no_matching_output_is_info():
    cell = _cell(jax.jit(lambda x: x.sum(), donate_argnums=(0,)),
                 [_f32(8, 8)], donate=[0])
    found = contracts.check_donation(cell)
    assert [f for f in found if f.severity == Severity.INFO]
    assert not [f for f in found if f.severity == Severity.ERROR]


# -- trace bound + repo-clean on one real config ----------------------------

@pytest.fixture(scope="module")
def lenet_cfg():
    return contracts.ContractConfig("lenet5", "lax", fused=True)


@pytest.fixture(scope="module")
def lenet_pipe_cells(lenet_cfg):
    pipe = contracts.build_pipeline(lenet_cfg)
    return pipe, list(pipe.program_space())


def test_trace_bound_holds_on_real_pipeline(lenet_pipe_cells):
    pipe, cells = lenet_pipe_cells
    report = contracts.check_trace_bound(pipe, cells, "lenet5")
    assert not report.findings, report.render_text()
    # exhaustive enumeration actually exercised the bound, not vacuous
    assert report.stats["lenet5/direct/traces"] > 0
    assert report.stats["lenet5/cluster/traces"] > 0


def test_trace_bound_positive(lenet_pipe_cells):
    import dataclasses

    pipe, cells = lenet_pipe_cells
    # mint bound+1 impostor signatures in one mode: must trip the proof
    workers = [c for c in cells if c.kind == "worker"]
    extra = [
        dataclasses.replace(workers[0], cache_key=("impostor", i))
        for i in range(pipe.program_trace_bound + 1)
    ]
    report = contracts.check_trace_bound(pipe, list(cells) + extra, "seeded")
    assert any(f.rule == "TRACE-BOUND" and f.severity == Severity.ERROR
               for f in report.findings)


def test_repo_contracts_clean_one_config(lenet_cfg):
    """One real config end-to-end: no errors, no warnings (info allowed —
    CPU donation geometry notes)."""
    report = contracts.analyze_config(lenet_cfg)
    hard = [f for f in report.findings
            if f.severity in (Severity.ERROR, Severity.WARNING)]
    assert not hard, "\n".join(f.render() for f in hard)
    assert report.stats["lenet5/lax/fused/programs_checked"] > 0


# -- CLI --------------------------------------------------------------------

def test_cli_json_and_exit_code(tmp_path, capsys):
    from repro.analysis.__main__ import main

    out = tmp_path / "findings.json"
    code = main(["--only", "concurrency", "--strict", "--format", "json",
                 "--json-out", str(out)])
    assert code == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"]["error"] == 0
    assert json.loads(out.read_text())["counts"] == payload["counts"]


def test_cli_strict_fails_on_findings(monkeypatch, capsys):
    from repro.analysis import __main__ as cli

    monkeypatch.setattr(
        concurrency, "DEFAULT_SCOPE", (VIOLATIONS,), raising=True
    )
    code = cli.main(["--only", "concurrency", "--strict"])
    capsys.readouterr()
    assert code == 1
