"""What the program derives from the backend: interpret mode and the
persistent compile cache's directory."""
import os

import jax
import pytest

from repro import backend


def test_kernels_interpret_only_on_cpu():
    assert backend.interpret_kernels() is (jax.default_backend() == "cpu")


@pytest.mark.parametrize("env", [None, "given"])
def test_compile_cache_dir(env, tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: left to JAX, no directory set in
    code.  Unset: the checkout's fixed, git-ignored ``.jax_cache``."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        got = backend.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    if env is None:
        assert got == after == backend.COMPILE_CACHE_DIR
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        assert got == str(tmp_path) and after == before


def _precisions(jaxpr):
    from repro.analysis.jaxpr_tools import iter_eqns

    return [eqn.params["precision"] for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in ("dot_general", "conv_general_dilated")]


@pytest.mark.parametrize("backend", ["lax", "pallas"])
def test_coded_programs_trace_full_f32(backend):
    """Every matmul and conv the coded path traces — CNN encode, worker,
    transition and decode; the LM's weight encode, worker round, decode,
    prefill and glue — runs at HIGHEST precision: on a TPU anything less
    rounds the f32 operands to bf16."""
    import jax.numpy as jnp

    from repro.configs import smollm_135m
    from repro.core.decoder_pipeline import build_lm_decoder_pipeline
    from repro.core.pipeline import build_cnn_pipeline
    from repro.models.cnn import init_cnn, input_hw

    highest = (jax.lax.Precision.HIGHEST,) * 2
    cnn = build_cnn_pipeline(
        "lenet5", init_cnn("lenet5", jax.random.PRNGKey(0)), 4,
        default_kab=(2, 2), input_hw=input_hw("lenet5", smoke=True),
        backend=backend, bucket_sizes=(1,), fuse_transitions=True)
    bundle = smollm_135m.smoke()
    params = bundle.init(jax.random.PRNGKey(0), jnp.float32)
    lm = build_lm_decoder_pipeline(bundle.cfg, params, 4, k_b=4,
                                   backend=backend, bucket_sizes=(1,),
                                   max_len=16)
    tokens = jnp.zeros((1, 4), jnp.int32)
    lm.prefill_prompt(tokens)
    jaxprs = [jax.make_jaxpr(c.fn)(*c.args)
              for pipe in (cnn, lm) for c in pipe.program_space()]
    w = params["dense_layers"]["w_down"][0]
    k = init_cnn("lenet5", jax.random.PRNGKey(0))["conv1"]
    jaxprs += [jax.make_jaxpr(lm._encode_weights)(w),
               jax.make_jaxpr(cnn.layers[0].encode_filters)(k),
               jax.make_jaxpr(lm._prefill_fn)(lm.params, tokens)]
    found = [p for j in jaxprs for p in _precisions(j)]
    assert found and all(p == highest for p in found), set(found)
