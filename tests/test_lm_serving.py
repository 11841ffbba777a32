"""The continuous-batching coded LM server (``serving/lm_engine.py``).

Covers: token-stream continuous batching with late admission per decode
step (greedy outputs match the uncoded reference decoder for every
request, whatever admission order interleaved them); single-token
requests completing at admission; straggler-tolerant serving; request
packing; lifecycle guards; and CNN + LM co-serving on ONE shared coded
worker pool (the same cluster runs ConvL rounds and decoder GEMM rounds
concurrently).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smollm_135m
from repro.core.decoder_pipeline import build_lm_decoder_pipeline
from repro.core.pipeline import build_cnn_pipeline
from repro.models import transformer as lm
from repro.models.cnn import init_cnn, input_hw
from repro.runtime import FcdccCluster, StragglerModel
from repro.serving import CodedLMServer, pack_request, unpack_request

N = 4
MAX_LEN = 32
MAX_PROMPT = 8
PROMPTS = [[5, 9, 2], [7, 1], [3, 3, 4, 8, 2], [11], [6, 2, 9, 1]]
GENS = [6, 4, 3, 1, 5]


@pytest.fixture(scope="module")
def smoke():
    bundle = smollm_135m.smoke()
    params = bundle.init(jax.random.PRNGKey(0), jnp.float32)
    return bundle.cfg, params


@pytest.fixture(scope="module")
def refs(smoke):
    cfg, params = smoke
    return [_ref_generate(cfg, params, p, g) for p, g in zip(PROMPTS, GENS)]


def _ref_generate(cfg, params, prompt, gen):
    """Uncoded greedy reference: batched prefill + decode_step loop."""
    toks = jnp.asarray([prompt])
    cache = lm.init_cache(cfg, 1, MAX_LEN, jnp.float32)
    logits, cache = lm.prefill(params, cfg, cache, toks)
    out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
    pos = len(prompt)
    for _ in range(gen - 1):
        logits, cache = lm.decode_step(
            params, cfg, cache, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


def _pipe(smoke, **kw):
    cfg, params = smoke
    kw.setdefault("bucket_sizes", (1, 2, 4))
    kw.setdefault("max_len", MAX_LEN)
    return build_lm_decoder_pipeline(cfg, params, N, k_b=4, **kw)


def test_pack_unpack_roundtrip():
    row = pack_request([4, 5, 6], 7, MAX_PROMPT)
    prompt, gen = unpack_request(row)
    assert prompt.tolist() == [4, 5, 6] and gen == 7
    with pytest.raises(ValueError, match="exceeds"):
        pack_request(list(range(MAX_PROMPT + 1)), 1, MAX_PROMPT)
    with pytest.raises(ValueError, match="at least one"):
        pack_request([], 1, MAX_PROMPT)
    with pytest.raises(ValueError, match="max_new_tokens"):
        pack_request([1], 0, MAX_PROMPT)


def test_continuous_batching_matches_reference(smoke, refs):
    """Mixed prompt/generation lengths served concurrently, plus a request
    submitted mid-flight (admitted at a decode-step boundary), all match
    the uncoded reference decoder exactly."""
    cfg, params = smoke
    srv = CodedLMServer(_pipe(smoke), max_prompt=MAX_PROMPT,
                        poll_interval_s=0.002)
    with srv:
        handles = [srv.submit(p, g) for p, g in zip(PROMPTS, GENS)]
        time.sleep(0.05)  # engine mid-stream: this one admits late
        late = srv.submit([2, 4, 6], 4)
        results = [h.result(timeout=120) for h in handles]
        late_result = late.result(timeout=120)
    for got, want in zip(results, refs):
        assert list(got) == want
    assert list(late_result) == _ref_generate(cfg, params, [2, 4, 6], 4)
    assert srv.requests_served == len(PROMPTS) + 1
    assert srv.tokens_generated >= sum(GENS) + 4
    assert srv.tokens_per_second() > 0


def test_single_token_request(smoke, refs):
    """gen=1 resolves from the prefill logits alone — no decode round."""
    srv = CodedLMServer(_pipe(smoke), max_prompt=MAX_PROMPT)
    with srv:
        out = srv.generate(PROMPTS[3], 1)
    assert list(out) == refs[3]


def test_straggler_serving(smoke, refs):
    """1 of n straggling every round: served tokens are unchanged."""
    st = StragglerModel(np.array([0.0, 0.0, 0.02, 0.0]))  # worker 2 straggles
    srv = CodedLMServer(_pipe(smoke), st, max_prompt=MAX_PROMPT)
    with srv:
        handles = [srv.submit(p, g) for p, g in zip(PROMPTS, GENS)]
        results = [h.result(timeout=120) for h in handles]
    for got, want in zip(results, refs):
        assert list(got) == want


def test_direct_execution_forced_subset(smoke, refs):
    """execution='direct' with a forced survivor subset: no cluster spun
    up, same tokens."""
    srv = CodedLMServer(_pipe(smoke), execution="direct",
                        worker_ids=(1, 3), max_prompt=MAX_PROMPT)
    assert srv.cluster is None
    with srv:
        out = srv.generate(PROMPTS[0], GENS[0])
    assert list(out) == refs[0]


def test_lifecycle_guards(smoke):
    srv = CodedLMServer(_pipe(smoke), max_prompt=MAX_PROMPT)
    with pytest.raises(RuntimeError, match="not running"):
        srv.submit([1, 2], 2)
    with srv:
        with pytest.raises(ValueError, match="exceeds"):
            srv.submit(list(range(MAX_PROMPT + 1)), 2)
    # idempotent shutdown
    srv.shutdown()


def test_cnn_lm_co_serving_one_pool(smoke, refs):
    """One FcdccCluster serves a CNN's ConvL rounds and the LM's decoder
    GEMM rounds concurrently: the LM engine thread streams decode steps
    while the main thread pushes CNN inferences through the same worker
    pool, and both outputs are unchanged from solo runs."""
    cfg, params = smoke
    cnn_params = init_cnn("lenet5", jax.random.PRNGKey(1))
    cnn_pipe = build_cnn_pipeline(
        "lenet5", cnn_params, N, default_kab=(1, 2),
        input_hw=input_hw("lenet5", smoke=True), bucket_sizes=(1, 2),
    )
    lm_pipe = _pipe(smoke)
    cluster = FcdccCluster(cnn_pipe.specs[0].plan, None, mode="simulated",
                           backend="lax")
    try:
        cluster.load_pipeline(cnn_pipe, "cnn")
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2,) + cnn_pipe.input_shape),
                        jnp.float32)
        y_solo, _ = cluster.run_pipeline(x, model="cnn")
        srv = CodedLMServer(lm_pipe, cluster=cluster, model="lm",
                            max_prompt=MAX_PROMPT)
        cnn_out, cnn_err = [], []

        def cnn_client():
            try:
                for _ in range(4):
                    y, _ = cluster.run_pipeline(x, model="cnn")
                    cnn_out.append(np.asarray(y))
            except Exception as err:  # surfaces in the main thread below
                cnn_err.append(err)

        with srv:
            t = threading.Thread(target=cnn_client)
            t.start()
            handles = [srv.submit(p, g) for p, g in zip(PROMPTS, GENS)]
            results = [h.result(timeout=120) for h in handles]
            t.join(timeout=120)
        assert not t.is_alive() and not cnn_err, f"CNN client failed: {cnn_err}"
        for got, want in zip(results, refs):
            assert list(got) == want
        for y in cnn_out:
            np.testing.assert_array_equal(y, np.asarray(y_solo))
    finally:
        cluster.shutdown()


def test_shutdown_drain_finishes_requests(smoke, refs):
    """shutdown(drain=True) completes queued work before stopping."""
    srv = CodedLMServer(_pipe(smoke), max_prompt=MAX_PROMPT)
    srv.start()
    h = srv.submit(PROMPTS[0], GENS[0])
    srv.shutdown(drain=True)
    assert list(h.result(timeout=1)) == refs[0]
