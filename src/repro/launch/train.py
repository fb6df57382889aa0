"""Training driver: data pipeline -> jit train_step -> checkpoints,
with fault-tolerant restart and elastic re-mesh.

End-to-end example (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch minitron-4b \
        --smoke --steps 30 --ckpt-dir ckpt

``--full`` trains the arch at its published widths instead.

On a real pod the same driver runs under `jax.distributed.initialize()`
with the production mesh; here the mesh defaults to every local device.
The loop demonstrates the full production posture: deterministic
per-step data, async checkpointing every K steps, restart-from-latest,
heartbeat + straggler telemetry, and (optionally) microbatched gradient
accumulation with cross-pod int8 gradient compression.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import replace
from functools import partial
from typing import Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, Pipeline
from repro.models import lm
from repro.models.registry import get_arch, state_specs
from repro.models.train import (TrainOptions, init_train_state,
                                make_train_step)
from repro.runtime.fault import FaultMonitor
from .compile_cache import enable_compile_cache
from .mesh import make_mesh, named_shardings


def train_loop(arch: str, steps: int = 30, smoke: bool = True,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 10,
               seq_len: int = 128, global_batch: int = 8,
               n_micro: int = 1, compress: bool = False,
               n_data: Optional[int] = None, n_model: Optional[int] = None,
               log_every: int = 5, seed: int = 0):
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    nd = jax.device_count()
    n_model = n_model or 1
    n_data = n_data or (nd // n_model)
    mesh = make_mesh(n_data, n_model)

    opts = TrainOptions(n_micro=n_micro, compress_grads=compress,
                        total_steps=max(steps, 2))
    step_fn = make_train_step(cfg, opts=opts)

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                      global_batch=global_batch, seed=seed)
    pipe = Pipeline(dcfg)
    monitor = FaultMonitor(n_hosts=1)

    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0

    with jax.set_mesh(mesh):
        state = init_train_state(cfg, jax.random.PRNGKey(seed), opts=opts)
        if ckpt is not None and ckpt.latest_step() is not None:
            state, start_step, meta = ckpt.restore(state)
            import jax.numpy as jnp
            state = jax.tree_util.tree_map(jnp.asarray, state)
            print(f"[restore] resumed from step {start_step}")
            # fast-forward the data pipeline deterministically
            pipe.close()
            pipe = Pipeline(dcfg, start_step=start_step)

        sspec = named_shardings(mesh, state_specs(cfg, state,
                                                  n_model=n_model))
        repl = named_shardings(mesh, None)
        jitted = jax.jit(step_fn, in_shardings=(sspec, repl),
                         out_shardings=(sspec, repl),
                         donate_argnums=(0,))
        losses = []
        for i in range(start_step, steps):
            t0 = time.monotonic()
            batch = next(pipe)
            state, metrics = jitted(state, batch)
            loss = float(metrics["loss"])
            dt = time.monotonic() - t0
            monitor.beat(0, i, dt)
            losses.append(loss)
            if i % log_every == 0 or i == steps - 1:
                print(f"step {i:5d}  loss {loss:8.4f}  "
                      f"gnorm {float(metrics['grad_norm']):8.3f}  "
                      f"{dt*1e3:7.1f} ms", flush=True)
            if ckpt is not None and (i + 1) % ckpt_every == 0:
                ckpt.save_async(i + 1, state, meta={"loss": loss})
        if ckpt is not None and losses:
            ckpt.wait()
            ckpt.save(steps, state, meta={"loss": losses[-1]})
    pipe.close()
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    losses = train_loop(args.arch, steps=args.steps, smoke=args.smoke,
                        ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every,
                        seq_len=args.seq_len,
                        global_batch=args.global_batch,
                        n_micro=args.n_micro, compress=args.compress,
                        seed=args.seed)
    if losses:
        print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    else:
        print("nothing to do (checkpoint already at target step)")


if __name__ == "__main__":
    main()
