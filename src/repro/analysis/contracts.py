"""Program-contract analyzer: jit contracts proven on the shape space.

For every program the pipeline family can build — CNN archs x batch
buckets x {lax, pallas} backends x {fused, unfused} transitions, in both
the single-process (``direct``) and threaded-runtime (``cluster``)
execution modes, plus the coded LM decoder's decode-step program space
({coded, uncoded} plans x backends, worker GEMM rounds and master-side
glue alike) — this module traces the program on ``ShapeDtypeStruct``
arguments (``CodedPipeline.program_space`` /
``CodedDecoderPipeline.program_space``; no data runs) and checks:

- ``JIT-BAKED-CONST`` (error): decode-inverse / encode-column matrices
  must enter traced programs as *runtime arguments*, never baked
  constants — a baked survivor-subset matrix would mean a fresh trace per
  subset, breaking the no-retrace contract.  Any floating-point constant
  of >= ``CONST_SIZE_LIMIT`` elements is flagged unless the cell
  explicitly allows its shape (the cluster encoder legitimately bakes the
  full-n A-code matrix: it is subset-independent).
- ``JIT-F64`` (error): no float64/complex128 aval anywhere in a traced
  program — the stack is float32-resident; silent x64 promotion doubles
  memory and halves throughput.
- ``JIT-WEAK-TYPE`` (warning): program outputs must not be weakly typed —
  a weak output means a Python-scalar promotion leaked through and the
  next program's trace signature becomes input-history-dependent.
- ``JIT-HOST-CALLBACK`` (error): no ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` primitives inside jitted programs — host round trips
  serialize the async dispatch chain (``device_get``-style syncs cannot
  even be expressed in a jaxpr; the callback primitives are the residue
  this rule can see).
- ``JIT-DONATION`` (error/info): transition programs built with donation
  must actually mark argument 0 donated in the lowered module
  (``args_info``); when an output aval matches the donated input, the
  compiled HLO must carry the ``tf.aliasing_output`` attribute (when no
  output matches, aliasing is impossible and an info note records it).
- ``TRACE-BOUND`` (error): a static proof of the bounded-trace contract —
  for each execution mode, the number of *distinct trace signatures* the
  full shape space induces must not exceed
  ``(num_geometries + num_transitions) x len(buckets)``
  (``CodedPipeline.program_trace_bound``).  Together with
  ``JIT-BAKED-CONST`` (subsets enter as runtime args, so they cannot
  create signatures) this bounds compilations for the pipeline's
  lifetime.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Iterable, Sequence

import numpy as np

from repro.analysis import jaxpr_tools
from repro.analysis.findings import Report, Severity

# Floating constants smaller than this are tolerated everywhere (eps
# scalars, small index-free masks); coding matrices are always bigger.
CONST_SIZE_LIMIT = 16

# Host-callback primitive names across jax versions.
HOST_CALLBACK_PRIMITIVES = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "outside_call",
    "host_callback_call",
}

F64_DTYPES = {"float64", "complex128"}


@dataclasses.dataclass(frozen=True)
class ContractConfig:
    """One pipeline family member to analyze."""

    arch: str
    backend: str  # "lax" | "pallas"
    fused: bool
    n: int = 4
    kab: tuple = (2, 2)
    buckets: tuple = (1, 2)

    @property
    def label(self) -> str:
        fused = "fused" if self.fused else "unfused"
        return f"{self.arch}/{self.backend}/{fused}"


def iter_configs(
    archs: Sequence[str] | None = None,
    backends: Sequence[str] = ("lax", "pallas"),
) -> list[ContractConfig]:
    """The default analysis matrix: every arch x backend x transition mode."""
    if archs is None:
        from repro.models.cnn import CNN_SPECS

        archs = sorted(CNN_SPECS)
    return [
        ContractConfig(arch, backend, fused)
        for arch in archs
        for backend in backends
        for fused in (False, True)
    ]


def build_pipeline(cfg: ContractConfig):
    """Construct the config's pipeline with zero weights (shapes are all
    that matter; filter encoding of zeros is cheap) at smoke resolution.

    Donation is forced on so the donation contract is checked even on CPU
    hosts where the pipeline's own default keeps it off.
    """
    from repro.core.pipeline import build_cnn_pipeline
    from repro.models.cnn import CNN_SPECS, input_hw

    _, layers = CNN_SPECS[cfg.arch]
    params = {
        l.name: np.zeros((l.out_ch, l.in_ch, l.kernel, l.kernel), np.float32)
        for l in layers
    }
    return build_cnn_pipeline(
        cfg.arch,
        params,
        n=cfg.n,
        default_kab=cfg.kab,
        input_hw=input_hw(cfg.arch, smoke=True),
        backend=cfg.backend,
        bucket_sizes=cfg.buckets,
        fuse_transitions=cfg.fused,
        donate_transitions=True,
    )


# -- per-cell checks (unit-testable on any cell-shaped object) --------------

def check_jaxpr_contracts(cell, jaxpr=None) -> list:
    """JIT-BAKED-CONST / JIT-F64 / JIT-WEAK-TYPE / JIT-HOST-CALLBACK on one
    traced cell.  ``cell`` needs ``fn``, ``args``, ``cell_id`` and
    ``allowed_const_shapes``; ``jaxpr`` may be pre-traced."""
    import jax

    report = Report()
    if jaxpr is None:
        jaxpr = jax.make_jaxpr(cell.fn)(*cell.args)
    loc = cell.cell_id
    allowed = {tuple(s) for s in getattr(cell, "allowed_const_shapes", ())}

    for arr in jaxpr_tools.const_arrays(jaxpr):
        if not np.issubdtype(arr.dtype, np.floating) and not np.issubdtype(
            arr.dtype, np.complexfloating
        ):
            continue
        if arr.size < CONST_SIZE_LIMIT:
            continue
        if tuple(arr.shape) in allowed:
            continue
        report.add(
            "JIT-BAKED-CONST",
            Severity.ERROR,
            loc,
            f"traced program bakes a float constant of shape {arr.shape} "
            f"({arr.dtype}); coding matrices must be runtime arguments so "
            f"survivor subsets never retrace",
        )

    bad_dtypes = sorted(
        {
            str(aval.dtype)
            for aval in jaxpr_tools.iter_avals(jaxpr)
            if hasattr(aval, "dtype") and str(aval.dtype) in F64_DTYPES
        }
    )
    if bad_dtypes:
        report.add(
            "JIT-F64",
            Severity.ERROR,
            loc,
            f"traced program contains {'/'.join(bad_dtypes)} avals; the "
            f"stack is float32-resident",
        )

    weak = [
        i
        for i, aval in enumerate(jaxpr.out_avals)
        if getattr(aval, "weak_type", False)
    ]
    if weak:
        report.add(
            "JIT-WEAK-TYPE",
            Severity.WARNING,
            loc,
            f"program outputs {weak} are weakly typed; a Python-scalar "
            f"promotion leaked into the traced program",
        )

    callbacks = sorted(
        jaxpr_tools.primitive_names(jaxpr) & HOST_CALLBACK_PRIMITIVES
    )
    if callbacks:
        report.add(
            "JIT-HOST-CALLBACK",
            Severity.ERROR,
            loc,
            f"host callback primitive(s) {callbacks} inside a jitted "
            f"program; host round trips serialize async dispatch",
        )
    return report.findings


def check_donation(cell) -> list:
    """JIT-DONATION on one cell that declares ``donate_argnums``."""
    report = Report()
    donate = tuple(getattr(cell, "donate_argnums", ()) or ())
    if not donate:
        return report.findings
    loc = cell.cell_id
    with warnings.catch_warnings():
        # CPU backends warn that donated buffers are unusable — the
        # platform copies; the *contract* (donation requested and wired
        # through) is what we verify, via args_info.
        warnings.filterwarnings(
            "ignore", message=".*donated.*", category=UserWarning
        )
        lowered = cell.fn.lower(*cell.args)
    # args_info is ((per-positional-arg pytrees...), kwargs-dict)
    positional = lowered.args_info[0]
    for argnum in donate:
        if argnum >= len(positional):
            report.add(
                "JIT-DONATION",
                Severity.ERROR,
                loc,
                f"donate_argnums includes {argnum} but the program has "
                f"{len(positional)} arguments",
            )
            continue
        leaves = _tree_leaves(positional[argnum])
        if not all(getattr(leaf, "donated", False) for leaf in leaves):
            report.add(
                "JIT-DONATION",
                Severity.ERROR,
                loc,
                f"argument {argnum} is declared donated but the lowered "
                f"module does not mark it donated",
            )
            continue
        # aliasing is only possible when some output matches the donated
        # input's aval; otherwise the platform must copy regardless
        donated_avals = {
            (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves
        }
        out_avals = {
            (tuple(a.shape), str(a.dtype)) for a in _out_avals(lowered)
        }
        if donated_avals & out_avals:
            if "tf.aliasing_output" not in lowered.as_text():
                report.add(
                    "JIT-DONATION",
                    Severity.ERROR,
                    loc,
                    f"argument {argnum} is donated and an output shares its "
                    f"aval, but the lowered module carries no "
                    f"tf.aliasing_output attribute — donation is not "
                    f"aliasing the buffer",
                )
        else:
            report.add(
                "JIT-DONATION",
                Severity.INFO,
                loc,
                f"argument {argnum} donated; no output matches its aval, so "
                f"buffer aliasing is impossible for this geometry (platform "
                f"will copy)",
            )
    return report.findings


def _tree_leaves(arg_info):
    import jax

    return jax.tree_util.tree_leaves(
        arg_info, is_leaf=lambda x: hasattr(x, "donated")
    )


def _out_avals(lowered):
    out = lowered.out_info
    import jax

    return [
        leaf
        for leaf in jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: hasattr(x, "shape")
        )
        if hasattr(leaf, "shape")
    ]


def check_trace_bound(pipe, cells: Iterable, label: str) -> Report:
    """TRACE-BOUND: distinct trace signatures per execution mode must fit
    ``pipe.program_trace_bound``.  Static proof by exhaustive enumeration:
    ``program_space`` covers every (layer, bucket, mode) the pipeline can
    launch, and JIT-BAKED-CONST separately proves survivor subsets cannot
    mint new signatures."""
    report = Report()
    per_mode: dict[str, set] = {}
    for cell in cells:
        if cell.kind in ("worker", "transition"):
            per_mode.setdefault(cell.mode, set()).add(cell.trace_signature)
    bound = pipe.program_trace_bound
    for mode, sigs in sorted(per_mode.items()):
        report.stats[f"{label}/{mode}/traces"] = len(sigs)
        if len(sigs) > bound:
            report.add(
                "TRACE-BOUND",
                Severity.ERROR,
                f"{label}:{mode}",
                f"shape space induces {len(sigs)} worker+transition trace "
                f"signatures in {mode} mode, exceeding the bounded-trace "
                f"contract of {bound} "
                f"((geometries={pipe.num_geometries} + "
                f"transitions={pipe.num_transitions}) x "
                f"buckets={len(pipe.bucket_sizes or (1,))})",
            )
    report.stats[f"{label}/bound"] = bound
    return report


# -- LM decoder program space ------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecoderContractConfig:
    """One coded-LM-decoder family member: the decoder program space gets
    the same jit contracts as the ConvL pipelines — coding matrices as
    runtime args, no f64, no host callbacks, decode-step worker traces
    bounded by (GEMM geometry x bucket)."""

    plan_kind: str  # "coded" | "uncoded"
    backend: str  # "lax" | "pallas"
    n: int = 4
    k_b: int = 4
    buckets: tuple = (1, 2)

    @property
    def label(self) -> str:
        return f"lm-decoder/{self.backend}/{self.plan_kind}"


def iter_decoder_configs(
    backends: Sequence[str] = ("lax", "pallas"),
) -> list[DecoderContractConfig]:
    return [
        DecoderContractConfig(kind, backend)
        for backend in backends
        for kind in ("coded", "uncoded")
    ]


def build_decoder_pipeline(cfg: DecoderContractConfig):
    """The smoke LM config with zero weights (shape space only)."""
    import jax

    from repro.configs import smollm_135m
    from repro.core.decoder_pipeline import (UncodedPlan,
                                             build_lm_decoder_pipeline)

    bundle = smollm_135m.smoke()
    params = jax.tree.map(
        lambda s: np.zeros(s.shape, np.float32),
        bundle.param_shapes(np.float32),
    )
    plan = UncodedPlan(cfg.n) if cfg.plan_kind == "uncoded" else None
    return build_lm_decoder_pipeline(
        bundle.cfg, params, cfg.n,
        k_b=None if plan else cfg.k_b, plan=plan,
        backend=cfg.backend,
        bucket_sizes=cfg.buckets, max_len=32,
    )


# -- driver -----------------------------------------------------------------

def _analyze(pipe, label: str) -> Report:
    """Trace and check every program cell of one pipeline's shape space."""
    import jax

    report = Report()
    cells = list(pipe.program_space())
    report.extend(check_trace_bound(pipe, cells, label))
    seen: set = set()
    checked = 0
    for cell in cells:
        # decoder/encoder cells can repeat identical (fn, args) across
        # modes — checking one representative per program is enough
        key = (id(cell.fn), tuple(
            (a.shape, str(a.dtype)) for a in cell.args))
        if key in seen:
            continue
        seen.add(key)
        jaxpr = jax.make_jaxpr(cell.fn)(*cell.args)
        for f in check_jaxpr_contracts(cell, jaxpr):
            report.findings.append(
                dataclasses.replace(f, location=f"{label}/{f.location}")
            )
        if cell.donate_argnums:
            for f in check_donation(cell):
                report.findings.append(
                    dataclasses.replace(
                        f, location=f"{label}/{f.location}")
                )
        checked += 1
    report.stats[f"{label}/programs_checked"] = checked
    return report


def analyze_config(cfg: ContractConfig) -> Report:
    """Trace and check every program cell of one CNN pipeline config."""
    return _analyze(build_pipeline(cfg), cfg.label)


def analyze_decoder_config(cfg: DecoderContractConfig) -> Report:
    """Trace and check every program cell of one LM decoder config."""
    return _analyze(build_decoder_pipeline(cfg), cfg.label)


def run(
    archs: Sequence[str] | None = None,
    backends: Sequence[str] = ("lax", "pallas"),
) -> Report:
    """Run the contract analyzer over the full pipeline family: every CNN
    config plus the coded-LM-decoder program space."""
    report = Report()
    configs = iter_configs(archs, backends)
    for cfg in configs:
        report.extend(analyze_config(cfg))
    decoder_configs = iter_decoder_configs(backends)
    for dcfg in decoder_configs:
        report.extend(analyze_decoder_config(dcfg))
    report.stats["contract_configs"] = len(configs) + len(decoder_configs)
    return report
