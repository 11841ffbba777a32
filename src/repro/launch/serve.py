"""Serving driver for both model families.

  * LM archs (``qwen3-4b``, ...): batched prefill + greedy decode loop with
    KV cache.
  * CNN archs (``lenet5``/``alexnet``/``vgg16``): routed through the coded
    serving engine — a ``repro.serving.CodedServer`` with one or several
    resident ``CodedPipeline``s sharing a straggler-simulating
    ``FcdccCluster`` worker pool, continuous batching across the models'
    concurrent requests.  ``--arch`` may repeat to co-serve several CNNs
    from the one pool, and ``--http-port`` raises the JSON front-end
    (``repro.serving.ServingFrontend``) in front of the engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
      --batch 4 --prompt-len 32 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch lenet5 --requests 16 \
      --workers 8 --stragglers 2
  PYTHONPATH=src python -m repro.launch.serve --arch lenet5 --arch alexnet \
      --smoke --http-port 8080
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import enable_compile_cache
from repro.configs import get_bundle
from repro.launch.mesh import make_host_mesh


def serve_lm(arch: str, *, batch: int, prompt_len: int, gen: int, smoke: bool,
             mesh=None, param_dtype=jnp.float32):
    bundle = get_bundle(arch, smoke=smoke)
    mesh = mesh or make_host_mesh()
    max_len = prompt_len + gen

    with jax.set_mesh(mesh):
        params = bundle.init(jax.random.PRNGKey(0), param_dtype)
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (batch, prompt_len), 0, bundle.cfg.vocab
        )
        cache = bundle.make_cache(batch, max_len, param_dtype)
        decode = jax.jit(bundle.decode_fn, donate_argnums=(1,))

        # prefill: one batched jitted pass fills the whole prompt's cache
        # (families without a cache-filling prefill — recurrent state that
        # only advances one token at a time — fall back to stepping the
        # decoder over the prompt).
        t0 = time.time()
        if prompt_len > 0 and bundle.prefill_cache_fn is not None:
            pf = jax.jit(bundle.prefill_cache_fn, donate_argnums=(1,))
            logits, cache = pf(params, cache, {"tokens": prompts})
            jax.block_until_ready(logits)
        else:
            for t in range(prompt_len):
                logits, cache = decode(
                    params, cache,
                    {"tokens": prompts[:, t : t + 1], "pos": jnp.int32(t)},
                )
        prefill_s = time.time() - t0

        out_tokens = []
        if prompt_len > 0:
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        else:  # empty prompt: no logits yet, start from BOS-like token 0
            tok = jnp.zeros((batch, 1), jnp.int32)
        t0 = time.time()
        for t in range(prompt_len, max_len):
            out_tokens.append(tok)
            logits, cache = decode(params, cache, {"tokens": tok, "pos": jnp.int32(t)})
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        decode_s = time.time() - t0

    seq = jnp.concatenate(out_tokens, axis=1)
    tps = batch * gen / decode_s
    print(
        f"{arch}: prefill {prompt_len} toks in {prefill_s:.2f}s; "
        f"generated {gen} x {batch} in {decode_s:.2f}s ({tps:.1f} tok/s)"
    )
    return seq


def _check_cnn_archs(archs) -> None:
    from repro.models.cnn import CNN_SPECS

    unknown = [a for a in archs if a not in CNN_SPECS]
    if unknown:
        raise SystemExit(
            f"unknown CNN arch(s) {unknown}; valid: {sorted(CNN_SPECS)}"
        )
    dupes = sorted({a for a in archs if archs.count(a) > 1})
    if dupes:
        raise SystemExit(f"duplicate --arch value(s) {dupes}; each model "
                         f"registers once on the shared pool")


def build_cnn_server(archs, *, workers: int, stragglers: int,
                     straggler_delay: float, smoke: bool, kab=(2, 4),
                     mode: str = "threads", seed: int = 0,
                     fuse_transitions: bool = False,
                     pool: str | None = None, pipeline_depth: int = 2):
    """One multi-model ``CodedServer``: every arch's pipeline resident on
    the same n-worker pool (its own scheduler/buckets per model).
    ``fuse_transitions`` serves on the partition-resident path (batches
    advance between ConvLs as coded partition shares, no full-activation
    round trip).  ``pool`` selects the worker executor: ``"device"`` pins
    each coded worker to its own ``jax.Device`` (real accelerators, or CPU
    host devices under ``XLA_FLAGS=--xla_force_host_platform_device_
    count=N``), ``"threads"`` keeps the per-worker thread executors, and
    None auto-selects the device pool on multi-device hosts.
    ``pipeline_depth`` is the round-pipelining window: how many dispatched
    worker rounds may be in flight at once (1 = serial dispatch->collect)."""
    from repro.core.pipeline import build_cnn_pipeline
    from repro.models.cnn import init_cnn, input_hw
    from repro.runtime import StragglerModel
    from repro.serving import CodedServer

    _check_cnn_archs(archs)
    straggler = StragglerModel.fixed(workers, stragglers, straggler_delay,
                                     seed=seed)
    server = CodedServer(straggler=straggler, mode=mode,
                         bucket_sizes=(1, 2, 4, 8), pool=pool,
                         pipeline_depth=pipeline_depth)
    for arch in archs:
        params = init_cnn(arch, jax.random.PRNGKey(0))
        server.register_model(arch, build_cnn_pipeline(
            arch, params, workers, default_kab=kab,
            input_hw=input_hw(arch, smoke=smoke),
            fuse_transitions=fuse_transitions,
        ))
    return server


def serve_cnn(archs, *, requests: int, workers: int, stragglers: int,
              straggler_delay: float, smoke: bool, kab=(2, 4),
              mode: str = "threads", seed: int = 0,
              http_port: int | None = None,
              fuse_transitions: bool = False,
              pool: str | None = None, pipeline_depth: int = 2):
    """Serve one or several CNN archs from one shared coded worker pool.

    Without ``--http-port``: fire ``requests`` concurrent single-image
    requests per model and print latency/throughput stats.  With it: raise
    the JSON front-end and serve until interrupted (graceful drain).

    Default ``mode="threads"``: the printed percentiles are wall-clock, so
    injected straggler delays must really elapse (``simulated`` only shifts
    the subset-selection clock and would make the knobs cosmetic)."""
    from repro.models.cnn import CNN_SPECS, input_hw

    archs = [archs] if isinstance(archs, str) else list(archs)
    server = build_cnn_server(
        archs, workers=workers, stragglers=stragglers,
        straggler_delay=straggler_delay, smoke=smoke, kab=kab, mode=mode,
        seed=seed, fuse_transitions=fuse_transitions, pool=pool,
        pipeline_depth=pipeline_depth,
    )
    server.warmup()

    if http_port is not None:
        from repro.serving import ServingFrontend

        frontend = ServingFrontend(server, port=http_port)
        with frontend:
            print(f"serving {archs} on {frontend.url} "
                  f"(POST /v1/infer, GET /v1/models, GET /v1/stats); "
                  f"Ctrl-C drains and exits")
            try:
                frontend._thread.join()
            except KeyboardInterrupt:
                print("\ndraining ...")
        for m, s in server.per_model_stats().items():
            print(f"{m}: {s.summary_line()}")
        return None, server.stats()

    rng = np.random.default_rng(seed)
    handles = []
    with server:
        for arch in archs:
            hw0 = input_hw(arch, smoke=smoke)
            c0 = CNN_SPECS[arch][1][0].in_ch
            xs = rng.standard_normal((requests, c0, hw0, hw0)) \
                .astype(np.float32)
            handles.append(server.submit_many(xs, arch))
        outs = [[h.result(timeout=300.0) for h in hs] for hs in handles]
    for arch in archs:
        stats = server.stats(arch) if len(archs) > 1 else server.stats()
        print(f"{arch}: coded serving on n={workers} shared workers "
              f"({stragglers} stragglers +{straggler_delay}s): "
              f"{stats.summary_line()}")
    agg = server.stats()
    if len(archs) > 1:
        print(f"aggregate: {agg.summary_line()} "
              f"(coalesced merges: {agg.coalesced})")
    return outs, agg


def serve(arch: str, *, batch: int, prompt_len: int, gen: int, smoke: bool,
          mesh=None, param_dtype=jnp.float32, workers: int = 8,
          stragglers: int = 1, straggler_delay: float = 0.1):
    """Route by family: CNN archs hit the coded serving engine (``batch``
    becomes the number of concurrent requests, the cluster shape comes
    from ``workers``/``stragglers``), LM archs the decode loop."""
    from repro.models.cnn import CNN_SPECS

    if arch in CNN_SPECS:
        outs, _ = serve_cnn(arch, requests=batch, workers=workers,
                            stragglers=stragglers,
                            straggler_delay=straggler_delay, smoke=smoke)
        return outs[0]
    return serve_lm(arch, batch=batch, prompt_len=prompt_len, gen=gen,
                    smoke=smoke, mesh=mesh, param_dtype=param_dtype)


def main():
    from repro.configs import ARCH_IDS
    from repro.models.cnn import CNN_SPECS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help=f"LM arch ({ARCH_IDS}) or CNN ({sorted(CNN_SPECS)});"
                         " repeat to co-serve several CNNs on one pool")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--smoke", action="store_true")
    # CNN serving knobs
    ap.add_argument("--requests", type=int, default=16,
                    help="concurrent single-image requests per CNN model")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--stragglers", type=int, default=2)
    ap.add_argument("--straggler-delay", type=float, default=0.1)
    ap.add_argument("--mode", default="threads",
                    choices=("threads", "simulated"),
                    help="threads = wall-clock straggler sleeps (CNN only)")
    ap.add_argument("--pool", default="auto",
                    choices=("auto", "threads", "device"),
                    help="worker executor: device = one jax.Device per "
                         "coded worker (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 for CPU "
                         "host devices); auto picks device on multi-device "
                         "hosts (CNN only)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve the JSON front-end on this port (CNN only; "
                         "0 = ephemeral)")
    ap.add_argument("--fuse-transitions", action="store_true",
                    help="partition-resident layer transitions: batches "
                         "advance between ConvLs as coded partition shares "
                         "(CNN only)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="round-pipelining window: dispatched worker rounds "
                         "in flight at once (1 = serial dispatch->collect; "
                         "CNN only)")
    args = ap.parse_args()
    enable_compile_cache()
    archs = args.arch or ["qwen3-4b"]
    if all(a in CNN_SPECS for a in archs):
        serve_cnn(archs, requests=args.requests, workers=args.workers,
                  stragglers=args.stragglers,
                  straggler_delay=args.straggler_delay, smoke=args.smoke,
                  mode=args.mode, http_port=args.http_port,
                  fuse_transitions=args.fuse_transitions,
                  pool=None if args.pool == "auto" else args.pool,
                  pipeline_depth=args.pipeline_depth)
        return
    if len(archs) > 1 or args.http_port is not None or args.fuse_transitions:
        raise SystemExit(
            f"multi-model / --http-port / --fuse-transitions serving is "
            f"CNN-only (valid CNN archs: {sorted(CNN_SPECS)}); got {archs}"
        )
    if archs[0] not in ARCH_IDS:
        raise SystemExit(
            f"unknown arch {archs[0]!r}; LM archs: {ARCH_IDS}, "
            f"CNN archs: {sorted(CNN_SPECS)}"
        )
    seq = serve_lm(
        archs[0], batch=args.batch, prompt_len=args.prompt_len,
        gen=args.gen, smoke=args.smoke,
    )
    print("sample tokens:", seq[0, :16].tolist())


if __name__ == "__main__":
    main()
