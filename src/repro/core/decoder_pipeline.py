"""Coded LM decode serving: the ``CodedDecoderPipeline``.

The FCDCC machinery treats a ConvL as ``coded inputs x resident coded
filters``; a transformer decode step is the same shape of problem four
times per layer — the qkv / attention-output / gate-up / down projections
are GEMMs ``x (B, d_in) @ W (d_in, d_out)`` whose weights are static for
the lifetime of the model.  This module compiles a GQA decoder stack into
per-layer coded GEMM *rounds* against the same cluster seam CNNs use
(``FcdccCluster.load_pipeline`` / ``dispatch_pipeline_layer`` /
``collect_pipeline_layer``), so one coded worker pool serves CNN ConvL
rounds and LM decode rounds concurrently:

  * weights are column-partitioned (``k_b`` parts of the output axis) and
    CRME-encoded **once** at construction — the resident-coded-filter
    store, exactly like ConvL filters;
  * the token activation is broadcast to every worker (``k_a = 1``: the
    degenerate replication axis — decode batches are small and the master
    keeps the KV cache, so input partitioning buys nothing);
  * every worker computes ``ell_b`` skinny GEMMs per round; the master
    decodes the fastest ``delta`` workers' outputs with a ``(Q, Q)``
    inverse passed as a *runtime argument*, so timing-dependent survivor
    subsets never retrace (the same contract as ``CodedPipeline``);
  * everything between the GEMM rounds — embedding, RMS norms, RoPE +
    causal attention over the master-resident KV slot cache, SiLU gating,
    residual adds, unembed/argmax — runs master-side as small jitted glue
    programs with weights as runtime arguments.

``UncodedPlan`` is the straggler-bound baseline: the same worker pool and
worker program, weights split ``n`` ways with no redundancy, identity
decode — every round must wait for ALL ``n`` workers, so one straggler
bounds the token rate.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import full_f32

from .crme import recovery_matrix
from .fcdcc import FcdccPlan
from .nsctc import encode_tensor_list, group_by_worker
from .pipeline import ProgramCell
from .programs import worker_share_program

__all__ = [
    "GemmGeometry",
    "GemmRoundSpec",
    "UncodedPlan",
    "CodedDecoderPipeline",
    "build_lm_decoder_pipeline",
]


@dataclasses.dataclass(frozen=True)
class UncodedPlan:
    """Uncoded column-split baseline: worker ``i`` holds the ``i``-th of
    ``n`` weight column blocks, decode is the identity gather — so the
    recovery threshold is all ``n`` workers (``gamma = 0``).  Duck-types
    the ``FcdccPlan`` attributes the cluster/pipeline seams consult."""

    n: int

    @property
    def k_a(self) -> int:
        return 1

    @property
    def k_b(self) -> int:
        return self.n

    @property
    def ell_a(self) -> int:
        return 1

    @property
    def ell_b(self) -> int:
        return 1

    @property
    def delta(self) -> int:
        return self.n

    @property
    def gamma(self) -> int:
        return 0


@dataclasses.dataclass(frozen=True)
class GemmGeometry:
    """Geometry of one decoder GEMM round, shaped like the ``ConvGeometry``
    attributes ``FcdccCluster._filter_code_key`` consults (a 1x1 "conv"
    of ``in_channels -> out_channels``), so coded GEMM weights live in the
    same resident-filter registry as ConvL filters."""

    in_channels: int
    out_channels: int
    kernel_h: int = 1
    kernel_w: int = 1


@dataclasses.dataclass(frozen=True)
class GemmRoundSpec:
    """One coded GEMM round of a decoder layer (static plan + geometry).

    ``kind``: ``qkv`` / ``wo`` / ``gateup`` / ``down``.  ``program_key``
    carries the backend so an LM pipeline never collides with a ConvL
    program (ConvL keys are int tuples) in a shared device pool."""

    name: str
    kind: str
    layer: int
    plan: object  # FcdccPlan | UncodedPlan
    geo: GemmGeometry
    backend: str = "lax"

    @property
    def program_key(self) -> tuple:
        return ("gemm", self.backend, self.plan.ell_a, self.plan.ell_b)


class _GemmRound:
    """Per-round holder mirroring ``CodedPipeline.layers[idx]`` — the
    cluster seam reads ``.worker_compute`` off it."""

    def __init__(self, worker_compute):
        self.worker_compute = worker_compute


def _make_worker_compute(backend: str):
    """The ONE plan-agnostic coded GEMM worker program.

    ``xe_i``: (ell_a=1, B, d_in) — the broadcast activation share;
    ``ke_i``: (ell_b, d_in, ob) — the worker's resident coded weight
    columns.  Returns (ell_a*ell_b, B, ob), slot ``ell_b*b1 + b2``.

    Every round of every layer shares this function under one
    ``program_key``: the thread pool caches ONE ``jax.jit`` per key, so
    the callable must be plan-agnostic — jit's shape cache handles the
    per-geometry/per-bucket specialization (the bounded-trace contract).
    """
    if backend == "pallas":
        from repro.kernels.matmul.ops import matmul

        @full_f32
        def worker_compute(xe_i, ke_i):
            eb, d_in, ob = ke_i.shape
            # one MXU GEMM for all ell_b coded column blocks
            kcat = jnp.transpose(ke_i, (1, 0, 2)).reshape(d_in, eb * ob)
            y = matmul(xe_i[0], kcat)
            return jnp.transpose(y.reshape(y.shape[0], eb, ob), (1, 0, 2))

        return worker_compute

    @full_f32
    def worker_compute(xe_i, ke_i):
        y = jnp.einsum("abd,cdo->acbo", xe_i, ke_i)
        return y.reshape((-1,) + y.shape[2:])

    return worker_compute


class CodedDecoderPipeline:
    """A GQA decoder stack compiled into coded GEMM rounds on one cluster.

    Construction encodes every round's weights exactly once (asserted by
    ``weight_encode_calls``).  A decode step runs ``4 * layers`` worker
    rounds through ``run_round`` — either the threaded/device cluster
    (``run_decode_step_cluster``) or the single-process vmapped path with
    forced survivor subsets (``run_decode_step_direct``) — with the KV
    cache, norms, RoPE/attention, activations, and unembed kept
    master-side.  Per-request state lives in *slot caches*: row ``i`` of
    every layer's (slots, max_len, hkv, hd) K/V cache belongs to request
    slot ``i``, written at its own position each step (continuous
    batching advances every active slot by one token per step).
    """

    def __init__(self, cfg, params, plan, *, backend: str = "lax",
                 bucket_sizes: Sequence[int] | None = None,
                 max_len: int | None = None):
        if cfg.attn != "gqa":
            raise ValueError(f"coded decode supports attn='gqa', got {cfg.attn!r}")
        if cfg.moe is not None:
            raise ValueError("coded decode does not support MoE layers")
        if plan.k_a != 1:
            raise ValueError(
                f"decoder rounds broadcast the activation: need k_a=1, got "
                f"k_a={plan.k_a}"
            )
        self.cfg = cfg
        self.plan = plan
        self.n = plan.n
        self.backend = backend
        self.pool = None
        self.devices = None
        self.fuse_transitions = False  # GEMM rounds have no fused transitions
        self.max_len = int(max_len if max_len is not None else cfg.max_seq)
        self.bucket_sizes: tuple[int, ...] | None = (
            self.normalize_buckets(bucket_sizes) if bucket_sizes else None
        )

        # master-side params: full tree (prefill) + per-layer glue weights
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        self.params = params
        h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        self.qkv_dim = (h + 2 * hkv) * hd
        lp = params["dense_layers"]
        self.glue_w: list[dict] = []
        for l in range(cfg.layers):
            g = {"ln_attn": lp["ln_attn"][l], "ln_ffn": lp["ln_ffn"][l]}
            if cfg.qk_norm:
                g["q_ln"], g["k_ln"] = lp["q_ln"][l], lp["k_ln"][l]
            if cfg.sandwich_norms:
                g["ln_attn_post"] = lp["ln_attn_post"][l]
                g["ln_ffn_post"] = lp["ln_ffn_post"][l]
            self.glue_w.append(g)
        self.embed_table = params["embed"]
        self.ln_f = params["ln_f"]
        self.head = (params["embed"].T if cfg.tie_embeddings
                     else params["lm_head"])

        # compile the round specs and encode weights exactly once ---------
        self.weight_encode_calls = 0
        compute = _make_worker_compute(backend)
        self.specs: list[GemmRoundSpec] = []
        self.layers: list[_GemmRound] = []
        self.coded_filters: list[jnp.ndarray] = []
        self._windows = _decoder_windows(cfg)
        for l in range(cfg.layers):
            rounds = [
                ("qkv", jnp.concatenate(
                    [lp["wq"][l], lp["wk"][l], lp["wv"][l]], axis=1)),
                ("wo", lp["wo"][l]),
                ("gateup", jnp.concatenate(
                    [lp["w_gate"][l], lp["w_up"][l]], axis=1)),
                ("down", lp["w_down"][l]),
            ]
            for kind, w in rounds:
                d_in, d_out = int(w.shape[0]), int(w.shape[1])
                if d_out % plan.k_b:
                    raise ValueError(
                        f"round L{l:02d}.{kind}: d_out={d_out} not divisible "
                        f"by k_b={plan.k_b}"
                    )
                spec = GemmRoundSpec(
                    f"L{l:02d}.{kind}", kind, l, plan,
                    GemmGeometry(d_in, d_out), backend,
                )
                self.specs.append(spec)
                self.layers.append(_GemmRound(compute))
                self.coded_filters.append(self._encode_weights(w))

        # program caches --------------------------------------------------
        self._encoder_fn = None
        self._decoder = None
        self._cluster_programs: dict[tuple, callable] = {}  # per-worker call
        self._batch_programs: dict[tuple, callable] = {}  # vmapped over workers
        self._glue: dict = {}
        self._attn_fns: dict = {}
        self._prefill_fn = None

    # -- weight encoding (once, at construction) ---------------------------
    @full_f32
    def _encode_weights(self, w: jnp.ndarray) -> jnp.ndarray:
        """(d_in, d_out) -> resident coded columns (n, ell_b, d_in, ob)."""
        self.weight_encode_calls += 1
        plan = self.plan
        d_in, d_out = w.shape
        ob = d_out // plan.k_b
        parts = w.reshape(d_in, plan.k_b, ob).swapaxes(0, 1)  # (k_b, d_in, ob)
        if isinstance(plan, UncodedPlan):
            matrix = np.eye(plan.n)  # worker i holds column block i
        else:
            matrix = plan.codes[1].matrix  # B-code, (k_b, ell_b*n)
        coded = encode_tensor_list(parts, matrix)
        return group_by_worker(coded, plan.ell_b)

    # -- bucketing (same contract as CodedPipeline) ------------------------
    @staticmethod
    def normalize_buckets(bucket_sizes: Sequence[int]) -> tuple[int, ...]:
        buckets = tuple(sorted(set(int(b) for b in bucket_sizes)))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {bucket_sizes}")
        return buckets

    @property
    def max_batch(self) -> int | None:
        return self.bucket_sizes[-1] if self.bucket_sizes else None

    def bucketize(self, batch: int) -> int:
        if self.bucket_sizes is None:
            return batch
        for b in self.bucket_sizes:
            if b >= batch:
                return b
        raise ValueError(
            f"batch {batch} exceeds the largest bucket {self.bucket_sizes[-1]}"
        )

    def pad_to_bucket(self, x: jnp.ndarray, axis: int = 0) -> tuple[jnp.ndarray, int]:
        b = x.shape[axis]
        bucket = self.bucketize(b)
        if bucket == b:
            return x, b
        pad_shape = x.shape[:axis] + (bucket - b,) + x.shape[axis + 1:]
        return jnp.concatenate([x, jnp.zeros(pad_shape, x.dtype)], axis=axis), b

    # -- introspection -----------------------------------------------------
    @property
    def num_geometries(self) -> int:
        """Distinct (program key, GEMM geometry) pairs: 4 for a homogeneous
        decoder stack no matter how many layers."""
        return len({(s.program_key, s.geo) for s in self.specs})

    @property
    def num_transitions(self) -> int:
        return 0

    @property
    def program_trace_bound(self) -> int:
        buckets = len(self.bucket_sizes) if self.bucket_sizes else 1
        return self.num_geometries * buckets

    @property
    def num_rounds_per_step(self) -> int:
        return len(self.specs)

    def layer_delta(self, idx: int) -> int:
        return self.specs[idx].plan.delta

    def layer_worker_ids(self, idx: int, worker_ids=None) -> tuple[int, ...]:
        delta = self.layer_delta(idx)
        avail = list(range(self.n)) if worker_ids is None else list(worker_ids)
        if len(avail) < delta:
            raise ValueError(
                f"round {self.specs[idx].name} needs delta={delta} workers, "
                f"got {len(avail)}"
            )
        return tuple(avail[:delta])

    # -- coded program caches (the CodedPipeline duck-type surface) --------
    def encoder(self, idx: int):
        """k_a=1 'encoding' is a broadcast: every worker receives the whole
        (B, d_in) activation as its single coded share.  One jitted program
        serves every round (shape specialization is jit's job); nothing is
        baked but the worker count."""
        if self._encoder_fn is None:
            n = self.n
            self._encoder_fn = jax.jit(
                lambda x: jnp.broadcast_to(x[None, None], (n, 1) + x.shape)
            )
        return self._encoder_fn

    def worker_program(self, idx: int, *, over_workers: bool = True):
        cache = self._batch_programs if over_workers else self._cluster_programs
        key = self.specs[idx].program_key
        fn = cache.get(key)
        if fn is None:
            compute = self.layers[idx].worker_compute
            fn = cache[key] = (jax.jit(jax.vmap(compute)) if over_workers
                               else worker_share_program(compute))
        return fn

    def decode_matrix(self, idx: int, worker_ids: tuple[int, ...]) -> np.ndarray:
        """The (Q, Q) decode inverse for the given survivor subset (host
        side).  Uncoded rounds accept only the full worker set and decode
        with the identity — sorted-id gather order IS column-block order."""
        plan = self.specs[idx].plan
        if isinstance(plan, UncodedPlan):
            ids = tuple(sorted(worker_ids))
            if ids != tuple(range(plan.n)):
                raise ValueError(
                    f"uncoded round needs all {plan.n} workers, got {ids}"
                )
            return np.eye(plan.n)
        a_code, b_code = plan.codes
        e = recovery_matrix(a_code, b_code, list(worker_ids))
        return np.linalg.inv(e.T)

    def decoder_fn(self, idx: int):
        """One jitted decode program for EVERY round: the (Q, Q) inverse is
        a runtime argument, and with k_a=1 the decoded blocks are plain
        column blocks, so decode+concat is round-geometry-agnostic."""
        if self._decoder is None:
            def dec(outs, d):
                # outs (delta, ell2, B, ob) sorted by worker id
                q = outs.shape[0] * outs.shape[1]
                rows = outs.reshape(q, -1)
                true_rows = d.astype(rows.dtype) @ rows
                blocks = true_rows.reshape((q,) + outs.shape[2:])
                return jnp.transpose(blocks, (1, 0, 2)).reshape(
                    outs.shape[2], q * outs.shape[3]
                )

            self._decoder = jax.jit(full_f32(dec))
        return self._decoder

    def decoder(self, idx: int, worker_ids: tuple[int, ...]):
        fn = self.decoder_fn(idx)
        d = jnp.asarray(self.decode_matrix(idx, worker_ids))
        return lambda outs: fn(outs, d)

    # -- master-side glue programs -----------------------------------------
    def _glue_fn(self, name: str):
        fn = self._glue.get(name)
        if fn is not None:
            return fn
        cfg = self.cfg
        if name == "embed":
            scale = math.sqrt(cfg.d_model)

            def raw(table, tokens):
                x = table[tokens]
                if cfg.embed_scale:
                    x = x * jnp.asarray(scale, x.dtype)
                return x
        elif name == "norm":
            from repro.models.common import rms_norm

            def raw(x, gamma):
                return rms_norm(x, gamma)
        elif name == "add":
            def raw(x, y):
                return x + y
        elif name == "act":
            act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu

            def raw(gu):
                g, u = jnp.split(gu, 2, axis=-1)
                return act(g.astype(jnp.float32)).astype(u.dtype) * u
        elif name == "finish":
            from repro.models.common import rms_norm, softcap

            def raw(x, gamma, head):
                logits = (rms_norm(x, gamma) @ head).astype(jnp.float32)
                if cfg.logit_softcap is not None:
                    logits = softcap(logits, cfg.logit_softcap)
                return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)
        elif name == "slot_write":
            def raw(c, new, row):
                return jax.lax.dynamic_update_slice_in_dim(c, new, row, axis=0)
        elif name == "slot_take":
            def raw(c, row):
                return jax.lax.dynamic_slice_in_dim(c, row, 1, axis=0)
        else:
            raise KeyError(name)
        fn = self._glue[name] = jax.jit(full_f32(raw))
        return fn

    def attn_fn(self, layer: int):
        """The jitted decode-attention glue for ``layer`` (programs shared
        across layers with the same sliding window): split the coded qkv
        round's output, RoPE at each row's own position, write K/V into
        row ``i``'s cache slot at position ``pos[i]`` (per-row iota
        select), attend causally over the slot cache, return the merged
        head context plus the updated full slot caches."""
        window = self._windows[layer]
        fn = self._attn_fns.get(window)
        if fn is not None:
            return fn
        cfg = self.cfg
        from repro.models.common import rms_norm
        from repro.models.transformer import _attend

        h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

        def raw(qkv, ck, cv, pos, *ln):
            b = qkv.shape[0]
            q, k, v = jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1)
            q = q.reshape(b, 1, h, hd)
            k = k.reshape(b, 1, hkv, hd)
            v = v.reshape(b, 1, hkv, hd)
            if cfg.qk_norm:
                q = rms_norm(q, ln[0])
                k = rms_norm(k, ln[1])
            from repro.models.common import apply_rope, rope_inv_freq

            rope = rope_inv_freq(hd, cfg.rope_base)
            q = apply_rope(q, rope, pos[:, None])
            k = apply_rope(k, rope, pos[:, None])
            max_len = ck.shape[1]
            idx = jnp.arange(max_len, dtype=jnp.int32)
            sel = (idx[None, :] == pos[:, None])[:, :, None, None]
            ckb = jnp.where(sel, k, ck[:b])
            cvb = jnp.where(sel, v, cv[:b])
            ck = jax.lax.dynamic_update_slice_in_dim(ck, ckb, 0, axis=0)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, cvb, 0, axis=0)
            k_pos = jnp.broadcast_to(idx[None, :], (b, max_len))
            # causal mask k_pos <= pos hides not-yet-written slots
            ctx = _attend(q, ckb, cvb, pos[:, None], k_pos, cfg, window)
            return ctx.reshape(b, h * hd), ck, cv

        fn = self._attn_fns[window] = jax.jit(full_f32(raw))
        return fn

    # -- KV slot cache ------------------------------------------------------
    def init_slot_cache(self, slots: int) -> list[dict]:
        """Per-layer K/V slot caches: row ``i`` belongs to request slot
        ``i`` for its whole lifetime (prefill-scattered in, advanced one
        position per decode step, recycled on completion)."""
        cfg = self.cfg
        shape = (slots, self.max_len, cfg.n_kv_heads, cfg.head_dim)
        return [
            {"k": jnp.zeros(shape, jnp.float32),
             "v": jnp.zeros(shape, jnp.float32)}
            for _ in range(cfg.layers)
        ]

    def slot_write(self, cache_leaf, new, row: int):
        """Write ``new`` (G, max_len, hkv, hd) into rows [row, row+G)."""
        return self._glue_fn("slot_write")(cache_leaf, new, jnp.int32(row))

    def slot_take(self, cache_leaf, row: int):
        """Read one slot row (1, max_len, hkv, hd) at ``row``."""
        return self._glue_fn("slot_take")(cache_leaf, jnp.int32(row))

    def prefill_prompt(self, prompts: jnp.ndarray):
        """Batched cache-filling prefill for a group of admitted prompts:
        ONE jitted full-stack pass (``models.transformer.prefill``) on the
        master — prompt positions never go through worker rounds.  Returns
        ``(logits (G, P, V), ks, vs)`` with ks/vs ``(L, G, max_len, hkv,
        hd)`` ready to scatter into the slot caches."""
        if self._prefill_fn is None:
            from repro.models import transformer as lm

            cfg, max_len = self.cfg, self.max_len

            def raw(params, tokens):
                cache = lm.init_cache(cfg, tokens.shape[0], max_len,
                                      jnp.float32)
                logits, filled = lm.prefill(params, cfg, cache, tokens)
                return logits, filled["dense"]["k"], filled["dense"]["v"]

            self._prefill_fn = jax.jit(full_f32(raw))
        return self._prefill_fn(self.params, prompts)

    # -- decode-step drivers -------------------------------------------------
    def _decode_step(self, tokens, cache, pos, run_round):
        """One decode step over the first ``B = len(tokens)`` cache slots.

        ``tokens`` (B,) int32, ``pos`` (B,) int32 (each row's next
        position), ``cache`` the full slot-cache list (slots >= B).  Every
        projection GEMM goes through ``run_round(idx, x)``; everything
        else is master-side glue.  Returns (logits (B, V), next_tokens
        (B,), new_cache)."""
        cfg = self.cfg
        norm = self._glue_fn("norm")
        add = self._glue_fn("add")
        x = self._glue_fn("embed")(self.embed_table, tokens)
        new_cache = []
        for l in range(cfg.layers):
            g = self.glue_w[l]
            base = 4 * l
            qkv = run_round(base + 0, norm(x, g["ln_attn"]))
            ln = (g["q_ln"], g["k_ln"]) if cfg.qk_norm else ()
            ctx, ck, cv = self.attn_fn(l)(
                qkv, cache[l]["k"], cache[l]["v"], pos, *ln
            )
            new_cache.append({"k": ck, "v": cv})
            attn_out = run_round(base + 1, ctx)
            if cfg.sandwich_norms:
                attn_out = norm(attn_out, g["ln_attn_post"])
            x = add(x, attn_out)
            gu = run_round(base + 2, norm(x, g["ln_ffn"]))
            ffn_out = run_round(base + 3, self._glue_fn("act")(gu))
            if cfg.sandwich_norms:
                ffn_out = norm(ffn_out, g["ln_ffn_post"])
            x = add(x, ffn_out)
        logits, next_tokens = self._glue_fn("finish")(x, self.ln_f, self.head)
        return logits, next_tokens, new_cache

    def run_round_direct(self, idx: int, x, worker_ids=None):
        """One coded GEMM round on the single-process vmapped path with an
        explicitly forced survivor subset (the tests' reference)."""
        ids = tuple(sorted(self.layer_worker_ids(idx, worker_ids)))
        xe = self.encoder(idx)(x)
        sel = jnp.asarray(ids)
        outs = self.worker_program(idx)(xe[sel], self.coded_filters[idx][sel])
        return self.decoder(idx, ids)(outs)

    def run_decode_step_direct(self, tokens, cache, pos, worker_ids=None):
        """Full decode step, every round decoded from the forced subset."""
        return self._decode_step(
            tokens, cache, pos,
            lambda idx, x: self.run_round_direct(idx, x, worker_ids),
        )

    def run_decode_step_cluster(self, cluster, tokens, cache, pos, *,
                                model: str = "lm", timings: list | None = None):
        """Full decode step through the master/worker runtime: each round
        dispatches n coded subtasks via ``dispatch_pipeline_layer`` and
        reaps the fastest delta via ``collect_pipeline_layer`` (stragglers
        beyond gamma are simply never waited for)."""
        def run_round(idx, x):
            rnd = cluster.dispatch_pipeline_layer(idx, x, model)
            y, timing = cluster.collect_pipeline_layer(rnd)
            if timings is not None:
                timings.append(timing)
            return y

        return self._decode_step(tokens, cache, pos, run_round)

    # -- shape-space enumeration -------------------------------------------
    def program_space(self, bucket_sizes: Sequence[int] | None = None, *,
                      modes: Sequence[str] = ("direct", "cluster")):
        """Enumerate every program cell a decode step can launch, in shape
        space.  Coded-round cells mirror ``CodedPipeline.program_space``
        (worker cells are what the bounded-trace proof counts); the
        master-side glue programs are yielded as ``glue`` cells under the
        ``master`` pseudo-mode so the jaxpr contracts (no baked coding
        matrices, no f64, no host callbacks) cover them too."""
        buckets = (self.normalize_buckets(bucket_sizes) if bucket_sizes
                   else (self.bucket_sizes or (1,)))
        cfg = self.cfg
        f32 = jnp.float32
        sds = jax.ShapeDtypeStruct
        geoms = set()
        for mode in modes:
            if mode not in ("direct", "cluster"):
                raise ValueError(f"unknown mode {mode!r}")
            for bucket in buckets:
                for idx, spec in enumerate(self.specs):
                    key = (mode, bucket, spec.program_key, spec.geo)
                    if key in geoms:
                        continue  # repeated layer geometry: same programs
                    geoms.add(key)
                    plan = spec.plan
                    d_in = spec.geo.in_channels
                    ob = spec.geo.out_channels // plan.k_b
                    delta, ea, eb = plan.delta, plan.ell_a, plan.ell_b
                    q = plan.k_a * plan.k_b

                    def cid(kind):
                        return f"{spec.name}[b={bucket}]/{kind}:{mode}"

                    x = sds((bucket, d_in), f32)
                    yield ProgramCell(
                        cid("encoder"), "encoder", mode, idx, bucket,
                        ("bcast",), self.encoder(idx), (x,))
                    if mode == "direct":
                        yield ProgramCell(
                            cid("worker"), "worker", mode, idx, bucket,
                            spec.program_key, self.worker_program(idx),
                            (sds((delta, ea, bucket, d_in), f32),
                             sds((delta, eb, d_in, ob), f32)))
                    else:
                        yield ProgramCell(
                            cid("worker"), "worker", mode, idx, bucket,
                            spec.program_key,
                            self.worker_program(idx, over_workers=False),
                            (sds((self.n, ea, bucket, d_in), f32),
                             sds((self.n, eb, d_in, ob), f32),
                             sds((), jnp.int32)))
                    yield ProgramCell(
                        cid("decoder"), "decoder", mode, idx, bucket,
                        ("dec",), self.decoder_fn(idx),
                        (sds((delta, ea * eb, bucket, ob), f32),
                         sds((q, q), f32)))
        # master-side glue (mode-independent; checked, never trace-counted)
        d, v = cfg.d_model, cfg.vocab
        h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        i32 = jnp.int32
        for bucket in buckets:
            def gid(kind):
                return f"glue.{kind}[b={bucket}]:master"

            cells = [
                ("embed", (sds((v, d), f32), sds((bucket,), i32))),
                ("norm", (sds((bucket, d), f32), sds((d,), f32))),
                ("add", (sds((bucket, d), f32), sds((bucket, d), f32))),
                ("act", (sds((bucket, 2 * cfg.d_ff), f32),)),
                ("finish", (sds((bucket, d), f32), sds((d,), f32),
                            sds((d, v), f32))),
            ]
            for kind, args in cells:
                yield ProgramCell(
                    gid(kind), "glue", "master", 0, bucket, (kind,),
                    self._glue_fn(kind), args)
            cache_sds = sds((bucket, self.max_len, hkv, hd), f32)
            ln = ((sds((hd,), f32), sds((hd,), f32)) if cfg.qk_norm else ())
            for window in sorted(set(self._windows), key=repr):
                layer = self._windows.index(window)
                yield ProgramCell(
                    f"glue.attn[w={window},b={bucket}]:master", "glue",
                    "master", layer, bucket, ("attn", window),
                    self.attn_fn(layer),
                    (sds((bucket, self.qkv_dim), f32), cache_sds, cache_sds,
                     sds((bucket,), i32)) + ln)


def _decoder_windows(cfg) -> list:
    from repro.models.transformer import _layer_windows

    return list(_layer_windows(cfg, cfg.layers))


def build_lm_decoder_pipeline(
    cfg,
    params,
    n: int,
    *,
    k_b: int | None = None,
    plan=None,
    backend: str = "lax",
    bucket_sizes: Sequence[int] | None = None,
    max_len: int | None = None,
) -> CodedDecoderPipeline:
    """Compile a GQA ``LMConfig`` + f32 params into a coded decoder
    pipeline.  Pass ``k_b`` (even) for a CRME-coded plan with recovery
    threshold ``k_b/2``, or ``plan=UncodedPlan(n)`` for the straggler-bound
    uncoded baseline; ``plan`` wins when both are given."""
    if plan is None:
        if k_b is None:
            raise ValueError("need k_b or plan")
        plan = FcdccPlan(n=n, k_a=1, k_b=k_b)
    if plan.n != n:
        raise ValueError(f"plan targets n={plan.n}, requested n={n}")
    return CodedDecoderPipeline(
        cfg, params, plan, backend=backend,
        bucket_sizes=bucket_sizes, max_len=max_len,
    )
