import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("EXTRA_XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we:
  1. build the bundle + ShapeDtypeStruct inputs (no allocation),
  2. jit the right step (train/prefill/serve) with full shardings,
  3. ``.lower().compile()`` on the production mesh,
  4. record memory_analysis / cost_analysis / parsed-HLO roofline terms
     into results/dryrun/<cell>.json (resumable cache).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, get_bundle  # noqa: E402
from repro.configs.shapes import SHAPES, batch_structs  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../results/dryrun")

# Cells skipped by design (DESIGN.md §4): long_500k needs sub-quadratic
# attention; pure full-attention archs skip it.
def cell_skip_reason(bundle, shape: str) -> str | None:
    if shape == "long_500k" and not bundle.sub_quadratic:
        return "long_500k skipped: full-attention arch (quadratic); see DESIGN.md"
    return None


def _named(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(arch: str, shape: str, mesh, *, smoke_scale=None, extra=None):
    """Returns (lowered, compiled, meta).  Raises on sharding bugs."""
    kw = {}
    if arch.startswith("deepseek") and shape != "long_500k":
        # align MoE dispatch groups with the data-parallel degree
        dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        b = SHAPES[shape]["global_batch"]
        if smoke_scale:
            b = max(b // smoke_scale, 2)
        kw["dispatch_groups"] = dp if b % dp == 0 else 1
    bundle = get_bundle(arch, **kw) if kw else get_bundle(arch)
    if extra:
        bundle = extra(bundle)
    kind = SHAPES[shape]["kind"]
    batch, cache = batch_structs(bundle, shape, smoke_scale=smoke_scale)
    params = bundle.param_shapes(jnp.bfloat16)

    with jax.set_mesh(mesh):
        if kind == "train":
            from repro.models.common import count_params

            baseline = os.environ.get("REPRO_BASELINE") == "1"
            n_params = count_params(bundle.schema)
            micro = 1 if baseline else (8 if n_params > 1e11 else
                                        4 if n_params > 5e9 else 1)
            # FSDP pays off (and is needed for capacity) only at scale;
            # on <5B models the weight all-gathers regress the roofline
            # (measured on paligemma train_4k: 3.7x flops) -- see §Perf.
            use_fsdp = (not baseline) and n_params > 5e9
            tcfg = steps_mod.TrainConfig(microbatches=micro, fsdp=use_fsdp)
            fn, param_ps, opt_ps = steps_mod.build_train_step(bundle, mesh, tcfg)
            opt_shapes = steps_mod.make_opt_shapes(bundle)
            batch_ps = steps_mod.batch_pspecs(bundle, batch, mesh)
            jitted = jax.jit(
                fn,
                in_shardings=(
                    _named(mesh, param_ps),
                    _named(mesh, opt_ps),
                    _named(mesh, batch_ps),
                ),
                out_shardings=(
                    _named(mesh, param_ps),
                    _named(mesh, opt_ps),
                    None,
                ),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params, opt_shapes, batch)
        elif kind == "prefill":
            fn, param_ps = steps_mod.build_prefill_step(bundle, mesh)
            batch_ps = steps_mod.batch_pspecs(bundle, batch, mesh)
            jitted = jax.jit(
                fn,
                in_shardings=(_named(mesh, param_ps), _named(mesh, batch_ps)),
            )
            lowered = jitted.lower(params, batch)
        else:  # decode
            fn, param_ps = steps_mod.build_serve_step(bundle, mesh)
            batch_ps = steps_mod.batch_pspecs(bundle, batch, mesh)
            cache_ps = steps_mod.cache_pspecs(bundle, cache, mesh)
            jitted = jax.jit(
                fn,
                in_shardings=(
                    _named(mesh, param_ps),
                    _named(mesh, cache_ps),
                    _named(mesh, batch_ps),
                ),
                out_shardings=(None, _named(mesh, cache_ps)),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params, cache, batch)
        compiled = lowered.compile()
    return lowered, compiled, {"bundle": bundle, "kind": kind}


def run_cell(arch: str, shape: str, *, multi_pod: bool, force=False, smoke_scale=None):
    tag = f"{arch}__{shape}__{'2x16x16' if multi_pod else '16x16'}"
    if smoke_scale:
        # Smoke runs get their own cache file: a scaled-down record must
        # never be resumed (or roofline-reported) as a production cell.
        tag += f"__smoke{smoke_scale}"
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(RESULTS_DIR, tag + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            cached = json.load(f)
            # Records written before smoke tagging lack the key entirely and
            # may be smoke-poisoned production cells -- recompute those.
            if "smoke_scale" in cached and cached["smoke_scale"] == smoke_scale:
                return cached

    bundle = get_bundle(arch)
    skip = cell_skip_reason(bundle, shape)
    rec = {"arch": arch, "shape": shape, "multi_pod": multi_pod, "tag": tag,
           "smoke_scale": smoke_scale}
    if skip:
        rec.update(status="skipped", reason=skip)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.size
        t0 = time.time()
        try:
            lowered, compiled, meta = lower_cell(
                arch, shape, mesh, smoke_scale=smoke_scale
            )
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo_cost = analyze_hlo(compiled.as_text(), n_dev)
            rec.update(
                status="ok",
                compile_s=round(time.time() - t0, 1),
                devices=n_dev,
                memory={
                    k: int(getattr(mem, k, 0))
                    for k in (
                        "argument_size_in_bytes",
                        "output_size_in_bytes",
                        "temp_size_in_bytes",
                        "generated_code_size_in_bytes",
                    )
                },
                xla_cost={
                    "flops": float(cost.get("flops", -1)),
                    "bytes_accessed": float(cost.get("bytes accessed", -1)),
                },
                hlo_cost=hlo_cost.as_dict(),
            )
        except Exception as e:  # sharding bug -> fail loudly but record
            rec.update(
                status="error",
                error=f"{type(e).__name__}: {e}",
                trace=traceback.format_exc()[-2000:],
            )
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    print(f"[{status:7s}] {tag} " + (
        f"compile={rec.get('compile_s')}s temp={rec.get('memory',{}).get('temp_size_in_bytes',0)/2**30:.2f}GiB"
        if status == "ok" else rec.get("reason", rec.get("error", ""))[:120]
    ), flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke-scale", type=int, default=None,
                    help="divide batch/seq for quick validation")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(
                    arch, shape, multi_pod=mp, force=args.force,
                    smoke_scale=args.smoke_scale,
                )
                failures += rec["status"] == "error"
    print(f"\ndone; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
