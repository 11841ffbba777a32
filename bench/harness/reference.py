"""The plain reference: weights, images and the uncoded layer graph.

Imports nothing of the program.  The weights and the request images are
made here from the seed, on the device, in one jitted call each, and the
program under test is handed the same arrays: so the reference takes
nothing that the program made.  Random streams (``prng_key``): 0 the
filters, 1 the images, 1000 the shifts; ``traffic.rng`` takes 2, 3+, 5+
and 10+ of its own generator.  Filters are N(0, 1) / sqrt(fan-in), one
split key per entry in the configuration's order; shifts, only for
entries with ``"bias": true`` and under ``"<name>.bias"``, are
N(0, 1) x ``SHIFT_STD`` (0.1) per output channel.

``forward`` walks the configuration's layer graph (``spec.py`` gives the
vocabulary and its defaults) in float32 at ``HIGHEST`` precision, the
precision the configuration states (``"precision": "highest"``).  Each
entry computes ``pool(act(conv(x) + bias + out[add]))``: conv (NCHW /
OIHW) of the output its ``from`` names, its shift, the residual, ReLU
unless ``"relu": false``, then its pool: a floor ``k x k`` max-pool for
an int, ``lax.reduce_window`` max over -inf padding, or the mean over the
whole map.  The last entry's output is the answer; a chain runs exactly
conv, ReLU, pool per layer.  It runs in blocks of a fixed number of
images, so one compiled program covers any number of them and its memory
stays small.

``forward(..., precision="high")`` is the control: the same stack with
every conv at three bf16 passes, the nearest precision below the stated
one.  ``"high_emulated"`` spells those three passes out (hi*hi + hi*lo +
lo*hi of the bf16 split of each operand) so a CPU, which ignores
``Precision.HIGH``, computes the same control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import spec

SHIFT_STREAM = 1000
SHIFT_STD = 0.1


def prng_key(seed: int, stream: int):
    """A threefry key from all the bits of ``seed`` (``PRNGKey`` keeps only
    the low 32), one independent stream per ``stream`` number."""
    words = np.random.SeedSequence([seed % 2 ** 64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _filter_shapes(config: dict) -> dict:
    return {l["name"]: (l["out_ch"], l["in_ch"], l["kernel"], l["kernel"])
            for l in config["layers"]}


def make_weights(config: dict, seed: int) -> dict:
    """Per-layer OIHW filters, N(0, 1) / sqrt(fan-in), and the shifts of
    the entries with ``bias``, on the device in one jitted call."""
    shapes = _filter_shapes(config)
    shifts = {n.name + ".bias": n.out_ch for n in spec.nodes(config)
              if n.bias}
    dtype = jnp.dtype(config["dtype"])

    def init(key, shift_key=None):
        keys = jax.random.split(key, len(shapes))
        params = {name: jax.random.normal(k, shape, dtype)
                  / np.sqrt(shape[1] * shape[2] * shape[3])
                  for k, (name, shape) in zip(keys, shapes.items())}
        if shift_key is not None:
            keys = jax.random.split(shift_key, len(shifts))
            params.update({name: SHIFT_STD * jax.random.normal(k, (ch,),
                                                               dtype)
                           for k, (name, ch) in zip(keys, shifts.items())})
        return params

    keys = [prng_key(seed, 0)]
    if shifts:
        keys.append(prng_key(seed, SHIFT_STREAM))
    return jax.block_until_ready(jax.jit(init)(*keys))


def make_images(config: dict, count: int, seed: int):
    """``count`` standard-normal (C, H, W) images, stacked, on the device."""
    shape = (count, config["layers"][0]["in_ch"], config["input_hw"],
             config["input_hw"])
    dtype = jnp.dtype(config["dtype"])
    make = jax.jit(lambda key: jax.random.normal(key, shape, dtype))
    return jax.block_until_ready(make(prng_key(seed, 1)))


def _conv(x, w, stride, padding, precision):
    def conv(a, b, prec):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), ((padding, padding),) * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=prec)

    if precision == "high_emulated":
        def split(a):
            hi = a.astype(jnp.bfloat16).astype(a.dtype)
            return hi, (a - hi).astype(jnp.bfloat16).astype(a.dtype)

        (xh, xl), (wh, wl) = split(x), split(w)
        exact = jax.lax.Precision.HIGHEST
        return conv(xh, wh, exact) + conv(xh, wl, exact) + conv(xl, wh, exact)
    return conv(x, w, {"highest": jax.lax.Precision.HIGHEST,
                       "high": jax.lax.Precision.HIGH,
                       "default": jax.lax.Precision.DEFAULT}[precision])


def _pool(y, pool):
    if isinstance(pool, int):
        if pool == 1:
            return y
        h, w = y.shape[-2:]
        h2, w2 = h - h % pool, w - w % pool
        y = y[..., :h2, :w2]
        return y.reshape(y.shape[:-2] + (h2 // pool, pool, w2 // pool,
                                         pool)).max(axis=(-3, -1))
    if pool.op == "avg":
        return jnp.mean(y, axis=(-2, -1), keepdims=True)
    pad = ((0, 0), (0, 0)) + ((pool.padding, pool.padding),) * 2
    return jax.lax.reduce_window(
        y, jnp.asarray(-jnp.inf, y.dtype), jax.lax.max,
        (1, 1, pool.size, pool.size), (1, 1, pool.stride, pool.stride), pad)


@functools.lru_cache(maxsize=8)
def _graph_fn(graph: tuple, precision: str):
    def run(params, x):
        outs = {spec.INPUT: x}
        for n in graph:
            y = _conv(outs[n.src], params[n.name], n.stride, n.padding,
                      precision)
            if n.bias:
                y = y + params[n.name + ".bias"][:, None, None]
            if n.add is not None:
                y = y + outs[n.add]
            if n.relu:
                y = jnp.maximum(y, 0.0)
            outs[n.name] = _pool(y, n.pool)
        return outs[graph[-1].name]

    return jax.jit(run)


def forward(config: dict, params: dict, images, *, precision: str = "highest",
            block: int = 8) -> np.ndarray:
    """The uncoded layer graph over ``images`` (N, C, H, W), ``block``
    images per call (the last block is zero-padded); returns host f32."""
    fn = _graph_fn(spec.nodes(config), precision)
    outs = []
    for s in range(0, images.shape[0], block):
        x = images[s:s + block]
        real = x.shape[0]
        if real < block:
            x = jnp.concatenate(
                [x, jnp.zeros((block - real,) + x.shape[1:], x.dtype)])
        outs.append(np.asarray(fn(params, x))[:real])
    return np.concatenate(outs)


def relative_errors(outs: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per request: max |out - ref| / max |ref| (inf where not finite)."""
    outs = outs.reshape(len(outs), -1).astype(np.float64)
    ref = ref.reshape(len(ref), -1).astype(np.float64)
    err = np.abs(outs - ref).max(axis=1) / np.maximum(
        np.abs(ref).max(axis=1), 1e-30)
    return np.where(np.isfinite(err), err, np.inf)
