"""The configuration's layer graph: a residual network is data alone, and a
chain reads exactly what it read before the graph vocabulary."""
import dataclasses
import functools
import json
import os
import sys
import types
import typing
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from harness import cell as cells  # noqa: E402
from harness import geometry, reference, spec  # noqa: E402

MAXPOOL_3S2 = {"op": "max", "size": 3, "stride": 2, "padding": 1}
GLOBAL_AVG = {"op": "avg", "global": True}

# A stem and two bottlenecks (v1.5: the stride on the 3x3), the first with
# a projection shortcut, every conv after the stem with a folded shift.
RESIDUAL_LAYERS = [
    dict(name="stem", in_ch=3, out_ch=8, kernel=3, padding=1,
         pool=MAXPOOL_3S2),
    dict(name="b1proj", in_ch=8, out_ch=16, kernel=1, stride=2, bias=True,
         relu=False),
    {"name": "b1a", "in_ch": 8, "out_ch": 4, "kernel": 1, "from": "stem",
     "bias": True},
    dict(name="b1b", in_ch=4, out_ch=4, kernel=3, stride=2, padding=1,
         bias=True),
    dict(name="b1c", in_ch=4, out_ch=16, kernel=1, bias=True, add="b1proj"),
    dict(name="b2a", in_ch=16, out_ch=4, kernel=1, bias=True),
    dict(name="b2b", in_ch=4, out_ch=4, kernel=3, padding=1, bias=True),
    dict(name="b2c", in_ch=4, out_ch=16, kernel=1, bias=True, add="b1c",
         pool=GLOBAL_AVG),
]


def _residual_config():
    return {"name": "tinyres", "arch": "tinyres", "input_hw": 32,
            "dtype": "float32", "layers": RESIDUAL_LAYERS}


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


# -- the chain reference as it stood before the graph vocabulary ------------

def _prng_key_chain(seed, stream):
    words = np.random.SeedSequence([seed % 2 ** 64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _make_weights_chain(config, seed):
    shapes = {l["name"]: (l["out_ch"], l["in_ch"], l["kernel"], l["kernel"])
              for l in config["layers"]}
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(shapes))
        return {name: jax.random.normal(k, shape, dtype)
                / np.sqrt(shape[1] * shape[2] * shape[3])
                for k, (name, shape) in zip(keys, shapes.items())}

    return jax.block_until_ready(init(_prng_key_chain(seed, 0)))


def _conv_chain(x, w, stride, padding, precision):
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
                       "high": jax.lax.Precision.HIGH}[precision])


def _relu_pool_chain(y, pool):
    y = jnp.maximum(y, 0.0)
    if pool == 1:
        return y
    h, w = y.shape[-2:]
    h2, w2 = h - h % pool, w - w % pool
    y = y[..., :h2, :w2]
    return y.reshape(y.shape[:-2] + (h2 // pool, pool, w2 // pool,
                                     pool)).max(axis=(-3, -1))


@functools.lru_cache(maxsize=4)
def _stack_fn_chain(layers, precision):
    def run(params, x):
        for name, stride, padding, pool in layers:
            x = _relu_pool_chain(_conv_chain(x, params[name], stride, padding,
                                             precision), pool)
        return x

    return jax.jit(run)


def _forward_chain(config, params, images, precision, block):
    layers = tuple((l["name"], l.get("stride", 1), l.get("padding", 0),
                    l.get("pool", 1)) for l in config["layers"])
    fn = _stack_fn_chain(layers, precision)
    outs = []
    for s in range(0, images.shape[0], block):
        x = images[s:s + block]
        real = x.shape[0]
        if real < block:
            x = jnp.concatenate(
                [x, jnp.zeros((block - real,) + x.shape[1:], x.dtype)])
        outs.append(np.asarray(fn(params, x))[:real])
    return np.concatenate(outs)


@pytest.mark.parametrize("precision", ["highest", "high_emulated"])
@pytest.mark.parametrize("name,hw", [("alexnet-n8", 67), ("vgg16-n8", 32)])
def test_bench_graph_chain_forward_is_bit_identical(name, hw, precision):
    cfg = dict(_config(name), input_hw=hw)
    params = reference.make_weights(cfg, 2 ** 33 + 7)
    images = reference.make_images(cfg, 3, 2 ** 33 + 7)
    got = reference.forward(cfg, params, images, precision=precision,
                            block=2)
    want = _forward_chain(cfg, params, images, precision, 2)
    assert got.shape == want.shape and got.shape[0] == 3
    assert np.array_equal(got, want)


@pytest.mark.parametrize("name", ["alexnet-n8", "vgg16-n8", "tinyres"])
def test_bench_graph_weights_keep_the_filters_and_add_shifts(name):
    cfg = _residual_config() if name == "tinyres" else _config(name)
    seed = 4000000000 + 17
    got = reference.make_weights(cfg, seed)
    want = _make_weights_chain(cfg, seed)
    biased = {l["name"] + ".bias": l["out_ch"] for l in cfg["layers"]
              if l.get("bias")}
    assert set(got) == set(want) | set(biased)
    for k, w in want.items():
        assert np.array_equal(np.asarray(got[k]), np.asarray(w)), k
    for k, ch in biased.items():
        shift = np.asarray(got[k])
        assert shift.shape == (ch,) and shift.dtype == np.float32
        assert 0 < np.abs(shift).max() < 1.0
    if biased:
        # the shifts come from their own stream: another seed, other shifts
        other = reference.make_weights(cfg, seed + 1)
        assert not np.array_equal(np.asarray(other["b1a.bias"]),
                                  np.asarray(got["b1a.bias"]))


def _hand_forward(p, x, precision):
    """The tiny residual network written out layer by layer."""
    def conv(x, name, stride=1, padding=0):
        def c(a, b):
            return jax.lax.conv_general_dilated(
                a, b, (stride, stride), ((padding, padding),) * 2,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                precision=jax.lax.Precision.HIGHEST)

        w = p[name]
        if precision == "highest":
            return c(x, w)
        xh = x.astype(jnp.bfloat16).astype(jnp.float32)
        wh = w.astype(jnp.bfloat16).astype(jnp.float32)
        xl = (x - xh).astype(jnp.bfloat16).astype(jnp.float32)
        wl = (w - wh).astype(jnp.bfloat16).astype(jnp.float32)
        return c(xh, wh) + c(xh, wl) + c(xl, wh)

    def shift(name):
        return p[name + ".bias"].reshape(1, -1, 1, 1)

    def relu(y):
        return jnp.maximum(y, 0.0)

    stem = relu(conv(x, "stem", 1, 1))
    stem = jax.lax.reduce_window(stem, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                                 (1, 1, 2, 2),
                                 ((0, 0), (0, 0), (1, 1), (1, 1)))
    proj = conv(stem, "b1proj", 2) + shift("b1proj")
    h = relu(conv(stem, "b1a") + shift("b1a"))
    h = relu(conv(h, "b1b", 2, 1) + shift("b1b"))
    b1 = relu(conv(h, "b1c") + shift("b1c") + proj)
    h = relu(conv(b1, "b2a") + shift("b2a"))
    h = relu(conv(h, "b2b", 1, 1) + shift("b2b"))
    b2 = relu(conv(h, "b2c") + shift("b2c") + b1)
    return jax.lax.reduce_window(b2, 0.0, jax.lax.add, (1, 1, 8, 8),
                                 (1, 1, 1, 1), "VALID") / 64.0


@pytest.mark.parametrize("precision", ["highest", "high_emulated"])
def test_bench_graph_residual_forward_by_hand(precision):
    cfg = _residual_config()
    params = reference.make_weights(cfg, 2 ** 40 + 3)
    images = reference.make_images(cfg, 5, 2 ** 40 + 3)
    got = reference.forward(cfg, params, images, precision=precision,
                            block=4)
    want = np.asarray(_hand_forward(params, images, precision))
    assert got.shape == want.shape == (5, 16, 1, 1)
    assert reference.relative_errors(got, want).max() <= 1e-6
    if precision == "high_emulated":
        full = reference.forward(cfg, params, images, block=4)
        assert reference.relative_errors(got, full).max() > 1e-6


def test_bench_graph_residual_geometry_by_hand():
    g = geometry.layers(_residual_config())
    assert [l.name for l in g] == [l["name"] for l in RESIDUAL_LAYERS]
    # stem 32 -> 32, pooled (32 + 2 - 3) // 2 + 1 = 16; b1proj and b1a
    # read the stem's 16; b1b's stride 2 gives 8; the rest stay at 8
    assert [l.in_hw for l in g] == [32, 16, 16, 16, 8, 8, 8, 8]
    assert [l.out_hw for l in g] == [32, 8, 16, 8, 8, 8, 8, 8]
    assert [l.next_hw for l in g] == [16, 8, 16, 8, 8, 8, 8, 1]
    macs = [8 * 32 * 32 * 3 * 9,   # stem
            16 * 8 * 8 * 8,        # b1proj, 1x1 stride 2
            4 * 16 * 16 * 8,       # b1a
            4 * 8 * 8 * 4 * 9,     # b1b
            16 * 8 * 8 * 4,        # b1c
            4 * 8 * 8 * 16,        # b2a
            4 * 8 * 8 * 4 * 9,     # b2b
            16 * 8 * 8 * 4]        # b2c
    assert [geometry.uncoded_macs(l) for l in g] == macs
    cfg = dict(_residual_config(), n=8, k_a=2, k_b=4)
    assert geometry.model_flops_per_image(cfg) == 2 * sum(macs)
    assert geometry.coded_flops_per_image(cfg) >= 4 * 2 * sum(macs)


@pytest.mark.parametrize("name", ["alexnet-n8", "vgg16-n8"])
def test_bench_graph_chain_input_shapes_are_the_old_ones(name):
    """The distinct entry inputs set-up warms are the chain's old list:
    the image, then every layer's pooled output but the last."""
    cfg = _config(name)
    g = geometry.layers(cfg)
    old = [(g[0].in_ch, cfg["input_hw"], cfg["input_hw"])] + [
        (l.out_ch, l.next_hw, l.next_hw) for l in g[:-1]]
    assert [(l.in_ch, l.in_hw, l.in_hw) for l in g] == old


@pytest.mark.parametrize("layers,match", [
    ([dict(name="a", in_ch=3, out_ch=4, kernel=3, groups=2)], "vocabulary"),
    ([dict(name="a", in_ch=3, out_ch=4, kernel=3),
      {"name": "b", "in_ch": 4, "out_ch": 4, "kernel": 1, "from": "c"},
      dict(name="c", in_ch=4, out_ch=4, kernel=1)], "earlier"),
    ([dict(name="a", in_ch=3, out_ch=4, kernel=3, add="a")], "earlier"),
    ([dict(name="input", in_ch=3, out_ch=4, kernel=3)], "taken"),
    ([dict(name="a", in_ch=3, out_ch=4, kernel=3, pool={"op": "avg"})],
     "pool"),
    ([dict(name="a", in_ch=3, out_ch=4, kernel=3, bias=1)], "bias"),
])
def test_bench_graph_refuses_what_the_vocabulary_lacks(layers, match):
    with pytest.raises(ValueError, match=match):
        spec.nodes({"layers": layers})


@pytest.mark.parametrize("layers,match", [
    ([dict(name="a", in_ch=3, out_ch=4, kernel=3),
      dict(name="b", in_ch=8, out_ch=4, kernel=3)], "channels"),
    ([dict(name="a", in_ch=3, out_ch=4, kernel=3),
      dict(name="b", in_ch=4, out_ch=4, kernel=3, stride=2, add="a")],
     "adds"),
])
def test_bench_graph_geometry_refuses_shapes_that_do_not_meet(layers, match):
    with pytest.raises(ValueError, match=match):
        geometry.layers({"input_hw": 16, "layers": layers})


def test_bench_graph_structured_pool_has_one_form():
    assert spec.pool_of({"op": "max", "size": 2}) == 2
    assert spec.pool_of({"op": "max", "size": 2, "stride": 2,
                         "padding": 0}) == 2
    assert spec.pool_of(MAXPOOL_3S2) == spec.Pool("max", 3, 2, 1)
    assert spec.pool_of(GLOBAL_AVG) == spec.Pool("avg")


# -- the program's layer table ------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _MaxPool:
    op: str
    size: int
    stride: int
    padding: int


class _AvgPool(typing.NamedTuple):
    op: str = "avg"
    global_: bool = True


@dataclasses.dataclass(frozen=True)
class _GraphConvL:
    """A program layer descriptor that has grown the vocabulary's fields."""

    name: str
    in_ch: int
    out_ch: int
    kernel: int
    stride: int = 1
    padding: int = 0
    pool: object = 1
    from_: str | None = None
    bias: bool = False
    add: str | None = None
    relu: bool = True


def _stub_table():
    layers = []
    for entry in RESIDUAL_LAYERS:
        kw = {("from_" if k == "from" else k): v for k, v in entry.items()}
        if kw.get("pool") == MAXPOOL_3S2:
            kw["pool"] = _MaxPool("max", 3, 2, 1)
        elif kw.get("pool") == GLOBAL_AVG:
            kw["pool"] = _AvgPool()
        layers.append(_GraphConvL(**kw))
    return 32, layers


@pytest.mark.parametrize("name", ["alexnet-n8", "vgg16-n8"])
def test_bench_graph_program_layers_accept_the_shipped_configs(name):
    cells.check_program_layers(_config(name))


def test_bench_graph_program_layers_accept_a_matching_graph(monkeypatch):
    from repro.models.cnn import CNN_SPECS

    monkeypatch.setitem(CNN_SPECS, "tinyres", _stub_table())
    cells.check_program_layers(_residual_config())


@pytest.mark.parametrize("index,field,value", [
    (2, "from_", "b1proj"),
    (4, "add", None),
    (5, "bias", False),
    (1, "relu", True),
    (0, "pool", _MaxPool("max", 3, 2, 0)),
    (7, "pool", 1),
])
def test_bench_graph_program_layers_differ(monkeypatch, index, field, value):
    from repro.models.cnn import CNN_SPECS

    hw, layers = _stub_table()
    layers[index] = dataclasses.replace(layers[index], **{field: value})
    monkeypatch.setitem(CNN_SPECS, "tinyres", (hw, layers))
    with pytest.raises(ValueError, match="differ"):
        cells.check_program_layers(_residual_config())


def test_bench_graph_program_lacks_the_arch():
    with pytest.raises(ValueError, match="no arch"):
        cells.check_program_layers(dict(_residual_config(),
                                        arch="no-such-net"))


# -- the map pass ---------------------------------------------------------------

class _RecordingCluster:
    """Dispatches nothing: records what each layer is fed, and collects an
    output of the layer's geometry filled with the layer's index + 1."""

    def __init__(self, config, buckets):
        self.geo = geometry.layers(config)
        self.fed = []
        self.added = []
        c = config["layers"][0]["in_ch"]
        pipe = types.SimpleNamespace(
            bucket_sizes=buckets, specs=self.geo,
            input_shape=(c, config["input_hw"], config["input_hw"]),
            input_dtype=jnp.float32)
        self.pipelines = {"m": pipe}

    def dispatch_pipeline_layer(self, idx, x, model, add=None):
        self.fed.append((idx, x.shape, float(x.reshape(-1)[0])))
        self.added.append(None if add is None else
                          (idx, add.shape, float(add.reshape(-1)[0])))
        return types.SimpleNamespace(
            idx=idx, batch=x.shape[0],
            pending=types.SimpleNamespace(futures={}, results={}))

    def collect_pipeline_layer(self, rnd):
        g = self.geo[rnd.idx]
        return jnp.full((rnd.batch, g.out_ch, g.next_hw, g.next_hw),
                        rnd.idx + 1.0), None


def _map(cfg, cluster):
    served = cells.ServedCell(cfg, {}, 1)
    served.model = "m"
    served.server = types.SimpleNamespace(cluster=cluster)
    served._map_pass()


@pytest.mark.parametrize("name", ["tinyres", "alexnet-n8", "vgg16-n8"])
def test_bench_graph_map_pass_feeds_what_from_names(name):
    cfg = _residual_config() if name == "tinyres" else _config(name)
    cluster = _RecordingCluster(cfg, (1, 4))
    _map(cfg, cluster)
    graph = spec.nodes(cfg)
    index = {n.name: i for i, n in enumerate(graph)}
    want, added = [], []
    for bucket in (1, 4):
        for i, (n, g) in enumerate(zip(graph, cluster.geo)):
            fill = 0.0 if n.src == spec.INPUT else index[n.src] + 1.0
            want.append((i, (bucket, g.in_ch, g.in_hw, g.in_hw), fill))
            if n.add is not None:
                a = cluster.geo[index[n.add]]
                added.append((i, (bucket, a.out_ch, a.next_hw, a.next_hw),
                              index[n.add] + 1.0))
    assert cluster.fed == want
    assert [a for a in cluster.added if a is not None] == added
    if name == "tinyres":
        # b1c adds the projection's output, b2c the first block's
        assert [a[0] for a in added] == [4, 7, 4, 7]
    else:
        assert cluster.added == [None] * len(want)


class _Tracked(np.ndarray):
    """A collected output that can be watched for being dropped."""


class _WatchingCluster(_RecordingCluster):
    """Records, at each dispatch, which collected outputs are still held."""

    def __init__(self, config, buckets):
        super().__init__(config, buckets)
        self.names = [n.name for n in spec.nodes(config)]
        self.refs = {}
        self.held = []

    def dispatch_pipeline_layer(self, idx, x, model, add=None):
        self.held.append({n for n, r in self.refs.items()
                          if r() is not None})
        return super().dispatch_pipeline_layer(idx, x, model, add=add)

    def collect_pipeline_layer(self, rnd):
        y, t = super().collect_pipeline_layer(rnd)
        y = np.asarray(y).view(_Tracked)
        self.refs[self.names[rnd.idx]] = weakref.ref(y)
        return y, t


def test_bench_graph_map_pass_keeps_an_add_input_to_its_add_reader():
    """b1c is read last by b2a's ``from`` and then by b2c's ``add``: it is
    held through b2c's dispatch and gone at the next entry's; b1proj, read
    by b1c's ``add`` alone, is held until b1c and dropped right after."""
    head = dict(name="head", in_ch=16, out_ch=16, kernel=1)
    cfg = dict(_residual_config(), layers=RESIDUAL_LAYERS + [head])
    cluster = _WatchingCluster(cfg, (2,))
    _map(cfg, cluster)
    assert cluster.held == [
        set(),                   # stem
        {"stem"},                # b1proj
        {"stem", "b1proj"},      # b1a: the stem's last reader
        {"b1proj", "b1a"},       # b1b
        {"b1proj", "b1b"},       # b1c: adds b1proj, its last reader
        {"b1c"},                 # b2a: b1c's last ``from`` reader
        {"b1c", "b2a"},          # b2b
        {"b1c", "b2b"},          # b2c: adds b1c, its last reader
        {"b2c"},                 # head
    ]


class _ChainOnlyCluster(_RecordingCluster):
    """A program whose dispatch has no ``add`` keyword."""

    def dispatch_pipeline_layer(self, idx, x, model):
        return super().dispatch_pipeline_layer(idx, x, model)


@pytest.mark.parametrize("name", ["tinyres", "alexnet-n8"])
def test_bench_graph_map_pass_needs_add_only_for_a_residual(name):
    cfg = _residual_config() if name == "tinyres" else _config(name)
    cluster = _ChainOnlyCluster(cfg, (1,))
    if name == "tinyres":
        with pytest.raises(TypeError, match="add"):
            _map(cfg, cluster)
        # the entries before the first residual were served
        assert [f[0] for f in cluster.fed] == [0, 1, 2, 3]
    else:
        _map(cfg, cluster)
        assert len(cluster.fed) == len(cfg["layers"])
