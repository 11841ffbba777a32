"""Bring-up check: the coded serving path end to end on a TPU chip.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # the device pool across four chips

One process; every model is built through the entry points users call and
checked against a plain, uncoded f32 reference computed at ``HIGHEST``
matmul precision.  Phases, in order (any failure exits non-zero and prints
no result line):

  1. device      — the default backend must be a TPU; there is no fallback.
  2. cnn-lax     — alexnet at its published 227x227 input through
                   ``launch.serve.build_cnn_server``: n=8 coded workers,
                   (k_a, k_b) = (2, 4), thread pool, one fixed straggler.
  3. cnn-pallas  — the same model and requests on ``backend="pallas"`` with
                   partition-resident transitions, registered through
                   ``CodedServer.register_model`` (Mosaic-compiled worker,
                   transition and matmul kernels).
  4. lm          — smollm-135m at full width (30 layers, d_model 576, vocab
                   49152, seeded random weights) through ``CodedLMServer``:
                   n=4 workers, k_b=4, greedy decode.

``--chips 4`` runs only the four-chip path: alexnet on ``pool="device"``
(n=8 workers, two per chip) against the same reference on one device, and
checks that every chip ran worker programs and holds resident filters.

The last line of standard output is the result, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite",
"count": 1}}``.  Setup times printed on earlier lines include compilation
and are not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CNN_ARCH = "alexnet"
CNN_WORKERS = 8
CNN_KAB = (2, 4)
CNN_REQUESTS = 8
STRAGGLER_DELAY_S = 0.05
# Max |coded - reference| / max |reference| over a request's output.  Both
# sides compute in f32 at full precision, so the gap is f32 rounding scaled
# by the CRME decode inverse (1e-6 .. 1e-5 here); an f32 matmul left at the
# TPU's default bf16 operand rounding lands near 1e-2 and fails.
CNN_REL_TOL = 1e-3

LM_WORKERS = 4
LM_KB = 4
LM_BUCKETS = (1, 2, 4)
LM_REQUESTS = 4
LM_PROMPT = 16
LM_NEW_TOKENS = 8
LM_MAX_LEN = 64  # prompt + generated tokens fit; the KV cache is sized to it
# Max |coded - reference| over the first decode step's logits.  The logits
# are O(1) (tied embeddings at init scale), so f32 rounding through 30
# layers and the decode inverse stays near 1e-5; bf16 operand rounding
# moves them by ~1e-2.
LM_LOGIT_TOL = 2e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def _relu_pool(y, pool: int):
    """ReLU, then non-overlapping ``pool x pool`` max-pool (floor), as in
    ``models.cnn``'s ConvL stacks."""
    import jax.numpy as jnp

    y = jnp.maximum(y, 0.0)
    if pool == 1:
        return y
    h, w = y.shape[-2:]
    h2, w2 = h - h % pool, w - w % pool
    y = y[..., :h2, :w2]
    return y.reshape(y.shape[:-2] + (h2 // pool, pool, w2 // pool,
                                     pool)).max(axis=(-3, -1))


def cnn_reference(params, xs):
    """The uncoded ConvL stack on one device: f32 convs at HIGHEST."""
    import jax

    from repro.models.cnn import CNN_SPECS

    _, layers = CNN_SPECS[CNN_ARCH]

    @jax.jit
    def run(p, x):
        for layer in layers:
            y = jax.lax.conv_general_dilated(
                x, p[layer.name], (layer.stride, layer.stride),
                ((layer.padding, layer.padding),) * 2,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                precision=jax.lax.Precision.HIGHEST)
            x = _relu_pool(y, layer.pool)
        return x

    dev = jax.devices()[0]
    return np.asarray(run(jax.device_put(params, dev),
                          jax.device_put(xs, dev)))


def cnn_inputs(seed: int = 0):
    from repro.models.cnn import CNN_SPECS, input_hw

    hw = input_hw(CNN_ARCH)
    c0 = CNN_SPECS[CNN_ARCH][1][0].in_ch
    rng = np.random.default_rng(seed)
    return rng.standard_normal((CNN_REQUESTS, c0, hw, hw)).astype(np.float32)


def serve_cnn(server, xs, probe=None, warm=True):
    """Warm every bucket (``warm``), then serve one single-image request per
    row of ``xs``; returns the outputs, the warm-up (compile) seconds, and
    what ``probe(server)`` returns while the server still holds its pool."""
    t0 = time.perf_counter()
    if warm:
        server.warmup()
    setup_s = time.perf_counter() - t0
    with server:
        handles = server.submit_many(list(xs), CNN_ARCH)
        outs = np.stack([np.asarray(h.result(timeout=600.0))
                         for h in handles])
        probed = probe(server) if probe is not None else None
    return outs, setup_s, probed


def check_cnn(name: str, outs, ref) -> None:
    scale = np.abs(ref).reshape(len(ref), -1).max(axis=1)
    err = (np.abs(outs - ref).reshape(len(ref), -1).max(axis=1)
           / np.maximum(scale, 1e-30))
    log(f"{name}: output {outs.shape[1:]}, max relative error "
        f"{float(err.max()):.3e} (tolerance {CNN_REL_TOL:.0e}), "
        f"reference max |y| {float(scale.min()):.3e}..{float(scale.max()):.3e}")
    if not np.all(np.isfinite(outs)):
        raise AssertionError(f"{name}: non-finite outputs")
    if not err.max() <= CNN_REL_TOL:
        raise AssertionError(
            f"{name}: max relative error {float(err.max()):.3e} exceeds "
            f"{CNN_REL_TOL:.0e}")


def phase_cnn_lax(params, xs, ref) -> None:
    from repro.launch.serve import build_cnn_server

    server = build_cnn_server(
        [CNN_ARCH], workers=CNN_WORKERS, stragglers=1,
        straggler_delay=STRAGGLER_DELAY_S, smoke=False, kab=CNN_KAB,
        mode="threads")
    outs, setup_s, _ = serve_cnn(server, xs)
    log(f"cnn-lax: setup (warm-up incl. compile) {setup_s:.1f} s, "
        f"pool={server.cluster.pool}")
    check_cnn("cnn-lax", outs, ref)


def phase_cnn_pallas(params, xs, ref) -> None:
    from repro.core.pipeline import build_cnn_pipeline
    from repro.runtime import StragglerModel
    from repro.serving import CodedServer

    straggler = StragglerModel.fixed(CNN_WORKERS, 1, STRAGGLER_DELAY_S)
    server = CodedServer(straggler=straggler, mode="threads",
                         bucket_sizes=(1, 2, 4, 8))
    server.register_model(CNN_ARCH, build_cnn_pipeline(
        CNN_ARCH, params, CNN_WORKERS, default_kab=CNN_KAB,
        backend="pallas", fuse_transitions=True))
    outs, setup_s, _ = serve_cnn(server, xs)
    log(f"cnn-pallas: setup (warm-up incl. compile) {setup_s:.1f} s")
    check_cnn("cnn-pallas", outs, ref)


def phase_cnn_devices(params, xs, ref, chips: int) -> None:
    """The coded workers spread over ``chips`` devices (device pool)."""
    import jax

    from repro.launch.serve import build_cnn_server

    server = build_cnn_server(
        [CNN_ARCH], workers=CNN_WORKERS, stragglers=1,
        straggler_delay=STRAGGLER_DELAY_S, smoke=False, kab=CNN_KAB,
        mode="threads", pool="device")

    def probe(server):
        impl = server.cluster._pool_impl()
        with impl._state_lock:
            placed = {d for _, shards in impl._filters.values()
                      for s in shards for d in s.devices()}
        return impl, impl.program_traces(), placed

    # no warm-up of every bucket: each of the 4 chips compiles its own
    # worker programs, so only the buckets the requests use are compiled
    t0 = time.perf_counter()
    outs, _, (impl, traces, placed) = serve_cnn(server, xs, probe,
                                                warm=False)
    devices = set(jax.devices()[:chips])
    log(f"cnn-devices: serving incl. compile {time.perf_counter() - t0:.1f}"
        f" s, worker devices {[str(d) for d in impl.devices]}")
    log(f"cnn-devices: worker-program traces per device "
        f"{ {str(d): n for d, n in traces.items()} }")
    missing_prog = devices - {d for d, n in traces.items() if n > 0}
    missing_filt = devices - placed
    if missing_prog or missing_filt:
        raise AssertionError(
            f"devices without worker programs {sorted(map(str, missing_prog))}"
            f", without resident filters {sorted(map(str, missing_filt))}")
    check_cnn("cnn-devices", outs, ref)


def lm_reference(cfg, params, prompts, steps: int):
    """Undistributed greedy decode at HIGHEST precision: the first decode
    step's logits and every generated token."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as lm

    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(lambda p, c, t: lm.prefill(p, cfg, c, t))
        step = jax.jit(lambda p, c, t, pos: lm.decode_step(p, cfg, c, t,
                                                           pos))
        cache = lm.init_cache(cfg, len(prompts), LM_MAX_LEN, jnp.float32)
        logits, cache = prefill(params, cache, jnp.asarray(prompts))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens, step1 = [tok], None
        for t in range(steps - 1):
            logits, cache = step(params, cache, tok[:, None],
                                 jnp.int32(prompts.shape[1] + t))
            if step1 is None:
                step1 = np.asarray(logits[:, 0])
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            tokens.append(tok)
    return step1, np.stack([np.asarray(t) for t in tokens], axis=1)


def coded_step1_logits(pipe, cluster, prompts, first_tokens):
    """The first coded decode step, through the cluster seam the server
    drives, from the master-side prefill of ``prompts``."""
    import jax.numpy as jnp

    b = len(prompts)
    _, ks, vs = pipe.prefill_prompt(jnp.asarray(prompts))
    cache = pipe.init_slot_cache(b)
    for c, lk, lv in zip(cache, ks, vs):
        c["k"] = pipe.slot_write(c["k"], lk, 0)
        c["v"] = pipe.slot_write(c["v"], lv, 0)
    pos = jnp.full((b,), prompts.shape[1], jnp.int32)
    logits, _, _ = pipe.run_decode_step_cluster(
        cluster, jnp.asarray(first_tokens, jnp.int32), cache, pos, model="lm")
    return np.asarray(logits)


def phase_lm() -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import smollm_135m
    from repro.core.decoder_pipeline import build_lm_decoder_pipeline
    from repro.runtime import StragglerModel
    from repro.serving import CodedLMServer

    bundle = smollm_135m.full()
    cfg = bundle.cfg
    params = bundle.init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(1)
    prompts = rng.integers(1, cfg.vocab, size=(LM_REQUESTS, LM_PROMPT),
                           dtype=np.int32)
    t0 = time.perf_counter()
    pipe = build_lm_decoder_pipeline(cfg, params, LM_WORKERS, k_b=LM_KB,
                                     bucket_sizes=LM_BUCKETS,
                                     max_len=LM_MAX_LEN)
    build_s = time.perf_counter() - t0
    srv = CodedLMServer(pipe, StragglerModel.none(LM_WORKERS),
                        mode="threads", max_prompt=LM_PROMPT)
    t0 = time.perf_counter()
    with srv:
        handles = [srv.submit(p, LM_NEW_TOKENS) for p in prompts]
        served = np.stack([np.asarray(h.result(timeout=900.0))
                           for h in handles])
    serve_s = time.perf_counter() - t0
    ref_step1, ref_tokens = lm_reference(cfg, params, prompts, LM_NEW_TOKENS)
    step1 = coded_step1_logits(pipe, srv.cluster, prompts, ref_tokens[:, 0])
    srv.cluster.shutdown()
    gap = float(np.abs(step1 - ref_step1).max())
    agree = float((served == ref_tokens).mean())
    log(f"lm: {cfg.name} layers={cfg.layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab}; setup: build {build_s:.1f} s, serving "
        f"{LM_REQUESTS} requests incl. compile {serve_s:.1f} s")
    log(f"lm: step-1 max |logit gap| {gap:.3e} (tolerance {LM_LOGIT_TOL:.0e})"
        f", reference max |logit| {float(np.abs(ref_step1).max()):.3e}; "
        f"tokens agreeing with the reference {agree:.3f} "
        f"({int((served == ref_tokens).sum())}/{served.size})")
    if not np.all(np.isfinite(step1)):
        raise AssertionError("lm: non-finite step-1 logits")
    if not gap <= LM_LOGIT_TOL:
        raise AssertionError(
            f"lm: step-1 logit gap {gap:.3e} exceeds {LM_LOGIT_TOL:.0e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the device-pool path across four chips")
    args = ap.parse_args()
    try:
        import jax

        from repro.backend import enable_compile_cache
        from repro.models.cnn import init_cnn
    except ImportError as err:
        print(f"chip_smoke: cannot import the program: {err}",
              file=sys.stderr)
        return 2

    phase = "device"
    try:
        cache_dir = enable_compile_cache()
        devices = jax.devices()
        dev = devices[0]
        log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
            f"count={len(devices)}; compile cache {cache_dir}")
        if dev.platform != "tpu":
            raise RuntimeError(
                f"needs a TPU; the default backend is {dev.platform!r}")
        if len(devices) < args.chips:
            raise RuntimeError(
                f"--chips {args.chips} needs {args.chips} devices, found "
                f"{len(devices)}")
        # the same seeded weights build_cnn_server initializes
        params = init_cnn(CNN_ARCH, jax.random.PRNGKey(0))
        xs = cnn_inputs()
        ref = cnn_reference(params, xs)
        if args.chips == 4:
            phase = "cnn-devices"
            phase_cnn_devices(params, xs, ref, args.chips)
        else:
            phase = "cnn-lax"
            phase_cnn_lax(params, xs, ref)
            phase = "cnn-pallas"
            phase_cnn_pallas(params, xs, ref)
            phase = "lm"
            phase_lm()
    except Exception:
        traceback.print_exc()
        print(f"chip_smoke: phase {phase!r} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
