"""The coded LM decoder pipeline (``core/decoder_pipeline.py``).

Covers: once-only weight encoding; coded-vs-uncoded transformer decode
fp32 parity across forced survivor subsets x {lax, pallas}; bit-exact
replication-vs-uncoded equality (the fp32 bit-exactness claim: identical
worker/glue programs, decode by an exact one/identity); straggler and
dead-worker decode through the threaded cluster and the device pool;
batched-prefill-vs-step-loop parity; and the bounded-trace contract over
the decode-step program space.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smollm_135m
from repro.core.decoder_pipeline import (
    CodedDecoderPipeline,
    UncodedPlan,
    build_lm_decoder_pipeline,
)
from repro.models import transformer as lm
from repro.runtime import ClusterDegraded, FcdccCluster, StragglerModel

N = 4
MAX_LEN = 32
PROMPT = [5, 9, 2, 7, 1]
PROMPT2 = [7, 1, 4, 2, 6]
ATOL = 3e-4


@pytest.fixture(scope="module")
def smoke():
    bundle = smollm_135m.smoke()
    params = bundle.init(jax.random.PRNGKey(0), jnp.float32)
    return bundle.cfg, params


def _pipe(smoke, *, backend="lax", k_b=4, n=N, plan=None, buckets=(2, 4)):
    cfg, params = smoke
    return build_lm_decoder_pipeline(
        cfg, params, n, k_b=None if plan else k_b, plan=plan,
        backend=backend, bucket_sizes=buckets, max_len=MAX_LEN,
    )


def _prefilled(pipe, cfg, params, prompts):
    """Slot cache + first decode inputs from one batched prefill."""
    toks = jnp.asarray(prompts)
    logits, ks, vs = pipe.prefill_prompt(toks)
    cache = pipe.init_slot_cache(max(N, toks.shape[0]))
    for l in range(cfg.layers):
        cache[l]["k"] = pipe.slot_write(cache[l]["k"], ks[l], 0)
        cache[l]["v"] = pipe.slot_write(cache[l]["v"], vs[l], 0)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    pos = jnp.full((toks.shape[0],), toks.shape[1], jnp.int32)
    return cache, nxt, pos


def _ref_step(cfg, params, prompts):
    """Reference logits for the first post-prompt decode step."""
    toks = jnp.asarray(prompts)
    cache = lm.init_cache(cfg, toks.shape[0], MAX_LEN, jnp.float32)
    logits, cache = lm.prefill(params, cfg, cache, toks)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    ref, _ = lm.decode_step(params, cfg, cache, nxt[:, None],
                            jnp.int32(toks.shape[1]))
    return ref[:, 0]


def _subsets(n, delta):
    import itertools

    return list(itertools.combinations(range(n), delta))


def test_weights_encoded_once(smoke):
    cfg, params = smoke
    pipe = _pipe(smoke)
    assert pipe.weight_encode_calls == 4 * cfg.layers
    prompts = [PROMPT, PROMPT]
    cache, nxt, pos = _prefilled(pipe, cfg, params, prompts)
    for _ in range(3):
        _, nxt_, cache = pipe.run_decode_step_direct(nxt, cache, pos)
        nxt = nxt_[: len(prompts)]
        pos = pos + 1
    # serving N steps re-encodes nothing: weights are resident
    assert pipe.weight_encode_calls == 4 * cfg.layers


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_decode_parity_forced_subsets(smoke, backend):
    """Coded decode == uncoded decoder output for EVERY survivor subset."""
    cfg, params = smoke
    pipe = _pipe(smoke, backend=backend)
    prompts = [PROMPT, [3, 3, 4, 8, 2]]
    ref = _ref_step(cfg, params, prompts)
    cache, nxt, pos = _prefilled(pipe, cfg, params, prompts)
    delta = pipe.specs[0].plan.delta
    for ids in _subsets(N, delta):
        logits, toks, _ = pipe.run_decode_step_direct(
            nxt, cache, pos, worker_ids=ids
        )
        b = len(prompts)
        np.testing.assert_allclose(np.asarray(logits[:b]), np.asarray(ref),
                                   atol=ATOL, rtol=0)
        assert jnp.array_equal(
            toks[:b], jnp.argmax(ref, axis=-1).astype(jnp.int32)
        ), f"greedy token mismatch for subset {ids} ({backend})"


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_replication_bit_exact_vs_uncoded(smoke, backend):
    """k_b=1 replication decodes by multiplying with an exact 1.0, the
    uncoded plan by the identity — same worker program, same glue, so the
    fp32 outputs are bit-identical for every forced survivor."""
    cfg, params = smoke
    rep = _pipe(smoke, backend=backend, k_b=1, n=3)
    unc = _pipe(smoke, backend=backend, plan=UncodedPlan(N))
    prompts = [PROMPT, PROMPT2]
    cache_r, nxt, pos = _prefilled(rep, cfg, params, prompts)
    cache_u, _, _ = _prefilled(unc, cfg, params, prompts)
    lu, tu, _ = unc.run_decode_step_direct(nxt, cache_u, pos)
    for wid in range(3):
        lr, tr, _ = rep.run_decode_step_direct(
            nxt, cache_r, pos, worker_ids=(wid,)
        )
        assert jnp.array_equal(lr, lu), f"survivor {wid} not bit-equal"
        assert jnp.array_equal(tr, tu)


def test_uncoded_plan_needs_all_workers(smoke):
    unc = _pipe(smoke, plan=UncodedPlan(N))
    with pytest.raises(ValueError, match="needs delta"):
        unc.run_decode_step_direct(
            jnp.zeros(2, jnp.int32), unc.init_slot_cache(N),
            jnp.zeros(2, jnp.int32), worker_ids=(0, 1, 2),
        )


def test_prefill_matches_step_loop(smoke):
    """One jitted batched prefill == stepping the decoder over the prompt."""
    cfg, params = smoke
    toks = jnp.asarray([PROMPT, [3, 3, 4, 8, 2]])
    b, p = toks.shape
    cache = lm.init_cache(cfg, b, MAX_LEN, jnp.float32)
    logits_pf, cache_pf = lm.prefill(params, cfg, cache, toks)
    cache_st = lm.init_cache(cfg, b, MAX_LEN, jnp.float32)
    steps = []
    for t in range(p):
        lg, cache_st = lm.decode_step(params, cfg, cache_st, toks[:, t:t + 1],
                                      jnp.int32(t))
        steps.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(logits_pf),
                               np.stack([np.asarray(s) for s in steps], 1),
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(
        np.asarray(cache_pf["dense"]["k"][:, :, :p]),
        np.asarray(cache_st["dense"]["k"][:, :, :p]), atol=ATOL, rtol=0)


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_cluster_straggler_skipped(smoke, backend):
    """1 of n straggling: every round decodes from the fastest delta, the
    straggler's results are never waited on, outputs match reference."""
    cfg, params = smoke
    pipe = _pipe(smoke, backend=backend)
    st = StragglerModel(np.array([0.0, 0.0, 0.05, 0.0]))  # worker 2 straggles
    cluster = FcdccCluster(pipe.specs[0].plan, st, mode="simulated",
                           backend=backend)
    try:
        cluster.load_pipeline(pipe, "lm")
        prompts = [PROMPT, PROMPT2]
        ref = _ref_step(cfg, params, prompts)
        cache, nxt, pos = _prefilled(pipe, cfg, params, prompts)
        timings = []
        logits, toks, _ = pipe.run_decode_step_cluster(
            cluster, nxt, cache, pos, model="lm", timings=timings
        )
        np.testing.assert_allclose(np.asarray(logits[:2]), np.asarray(ref),
                                   atol=ATOL, rtol=0)
        assert len(timings) == 4 * cfg.layers
        assert all(2 not in t.used_workers for t in timings)
    finally:
        cluster.shutdown()


def test_cluster_dead_worker(smoke):
    """delay=inf worker: coded rounds decode from the survivors; the
    uncoded plan (delta=n) degrades instead."""
    cfg, params = smoke
    st = StragglerModel(np.array([0.0, float("inf"), 0.0, 0.0]))  # worker 1 dead
    pipe = _pipe(smoke)
    cluster = FcdccCluster(pipe.specs[0].plan, st, mode="simulated",
                           backend="lax")
    try:
        cluster.load_pipeline(pipe, "lm")
        prompts = [PROMPT]
        ref = _ref_step(cfg, params, prompts)
        cache, nxt, pos = _prefilled(pipe, cfg, params, prompts)
        logits, _, _ = pipe.run_decode_step_cluster(
            cluster, nxt, cache, pos, model="lm"
        )
        np.testing.assert_allclose(np.asarray(logits[:1]), np.asarray(ref),
                                   atol=ATOL, rtol=0)
        unc = _pipe(smoke, plan=UncodedPlan(N))
        cluster.load_pipeline(unc, "lm-uncoded")
        cache_u, nxt_u, pos_u = _prefilled(unc, cfg, params, prompts)
        with pytest.raises(ClusterDegraded):
            unc.run_decode_step_cluster(
                cluster, nxt_u, cache_u, pos_u, model="lm-uncoded"
            )
    finally:
        cluster.shutdown()


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="device pool needs a multi-device host (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)",
)
@pytest.mark.parametrize("backend", ["lax"])
def test_device_pool_decode(smoke, backend):
    """Thread-vs-device pool bit-parity on a forced fastest-delta subset,
    plus straggling-device decode correctness."""
    cfg, params = smoke
    prompts = [PROMPT, PROMPT2]
    # finite delays on workers delta..n-1 force both pools to keep exactly
    # the undelayed subset -> decodes must be bit-identical
    pipe = _pipe(smoke, backend=backend)
    delta = pipe.specs[0].plan.delta
    delays = [0.0] * N
    for w in range(delta, N):
        delays[w] = 0.25
    st = StragglerModel(np.asarray(delays))
    outs = {}
    for pool in ("threads", "device"):
        p = _pipe(smoke, backend=backend)
        cluster = FcdccCluster(p.specs[0].plan, st, mode="threads",
                               backend=backend, pool=pool)
        try:
            cluster.load_pipeline(p, "lm")
            cache, nxt, pos = _prefilled(p, cfg, params, prompts)
            timings = []
            logits, toks, _ = p.run_decode_step_cluster(
                cluster, nxt, cache, pos, model="lm", timings=timings
            )
            assert all(t.used_workers == list(range(delta)) for t in timings)
            outs[pool] = (np.asarray(logits), np.asarray(toks))
        finally:
            cluster.shutdown()
    np.testing.assert_array_equal(outs["threads"][0], outs["device"][0])
    np.testing.assert_array_equal(outs["threads"][1], outs["device"][1])


def test_trace_bound_over_program_space(smoke):
    """Distinct worker trace signatures stay bounded by geometry x bucket
    per mode — timing-dependent survivor subsets and the decode inverse
    are runtime values, never trace keys."""
    pipe = _pipe(smoke, buckets=(1, 2, 4))
    assert pipe.num_geometries == 4  # qkv / wo / gateup / down
    assert pipe.program_trace_bound == 4 * 3
    per_mode = {}
    for cell in pipe.program_space():
        if cell.kind != "worker":
            continue
        per_mode.setdefault(cell.mode, set()).add(cell.trace_signature)
    assert set(per_mode) == {"direct", "cluster"}
    for mode, sigs in per_mode.items():
        assert len(sigs) <= pipe.program_trace_bound, (
            f"{mode}: {len(sigs)} worker signatures > bound "
            f"{pipe.program_trace_bound}"
        )


def test_decode_inverse_is_runtime_arg(smoke):
    """Same jitted decoder object serves every survivor subset: only the
    (Q, Q) inverse argument changes."""
    pipe = _pipe(smoke)
    assert pipe.decoder_fn(0) is pipe.decoder_fn(7)
    dms = [pipe.decode_matrix(0, ids) for ids in _subsets(N, 2)]
    assert len({dm.tobytes() for dm in dms}) > 1  # genuinely different
