"""End-to-end training driver.

Runs on whatever devices exist (1 CPU here; a pod via the production mesh)
with the full production substrate: sharded params/opt-state, deterministic
resumable data pipeline, checkpoint/restart (async), straggler-aware
logging, optional cross-pod gradient compression.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 8 --seq 256 --smoke
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs import get_bundle
from repro.data import DataConfig, SyntheticTokens
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamWConfig, init_state


def train(arch: str, *, steps: int, batch: int, seq: int, smoke: bool,
          ckpt_dir: str | None = None, ckpt_every: int = 50,
          grad_compression: str | None = None, lr: float = 3e-4,
          mesh=None, log_every: int = 10, param_dtype=jnp.float32):
    bundle = get_bundle(arch, smoke=smoke)
    mesh = mesh or make_host_mesh()
    tcfg = steps_mod.TrainConfig(
        opt=AdamWConfig(lr=lr), warmup=min(20, steps // 10 + 1),
        total_steps=steps, grad_compression=grad_compression,
    )
    step_fn, param_ps, opt_ps = steps_mod.build_train_step(bundle, mesh, tcfg)

    with jax.set_mesh(mesh):
        params = bundle.init(jax.random.PRNGKey(0), param_dtype)
        opt_state = init_state(params)
        start = 0
        ckpt = None
        if ckpt_dir:
            ckpt = AsyncCheckpointer(ckpt_dir)
            last = latest_step(ckpt_dir)
            if last is not None:
                state = restore(ckpt_dir, last, {"params": params, "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                start = last
                print(f"restored step {start} from {ckpt_dir}")

        data = SyntheticTokens(
            DataConfig(vocab=bundle.cfg.vocab, seq_len=seq, global_batch=batch)
        )
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        losses = []
        t0 = time.time()
        for step in range(start, steps):
            hb = data.batch(step)
            b = {k: jnp.asarray(v) for k, v in hb.items()}
            if bundle.family == "encdec":
                b["frames"] = jax.random.normal(
                    jax.random.PRNGKey(step), (batch, bundle.cfg.enc_len, bundle.cfg.d_model),
                    param_dtype,
                )
            if bundle.family == "vlm":
                b["prefix"] = jax.random.normal(
                    jax.random.PRNGKey(step), (batch, 8, bundle.cfg.d_model), param_dtype
                )
            params, opt_state, metrics = jitted(params, opt_state, b)
            losses.append(float(metrics["loss"]))
            if (step + 1) % log_every == 0:
                dt = (time.time() - t0) / log_every
                print(
                    f"step {step+1:5d} loss {losses[-1]:.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f} ms/step",
                    flush=True,
                )
                t0 = time.time()
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.submit(step + 1, {"params": params, "opt": opt_state})
        if ckpt:
            ckpt.submit(steps, {"params": params, "opt": opt_state})
            ckpt.wait()
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    losses = train(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        smoke=args.smoke, ckpt_dir=args.ckpt_dir,
        grad_compression=args.grad_compression, lr=args.lr,
    )
    print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
