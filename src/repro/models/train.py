"""Training step: loss -> grads -> AdamW, with microbatching and optional
cross-pod int8 gradient compression.

`make_train_step(cfg, ...)` returns a pure function
    train_step(state, batch) -> (state, metrics)
suitable for `jax.jit` with in/out shardings from
:func:`repro.models.registry.shardings_for`.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import optim
from repro.kernels import ops
from repro.runtime.overlap import accumulate_grads
from .config import ArchConfig
from . import lm


class TrainState(NamedTuple):
    params: Any
    opt: optim.AdamWState
    error_fb: Any = None          # int8-compression error feedback


@dataclass(frozen=True)
class TrainOptions:
    n_micro: int = 1
    compress_grads: bool = False  # cross-pod int8 EF compression
    lr_schedule: str = "cosine"
    warmup: int = 100
    total_steps: int = 10000


def init_train_state(cfg: ArchConfig, key,
                     opt_cfg: Optional[optim.AdamWConfig] = None,
                     opts: Optional[TrainOptions] = None) -> TrainState:
    opt_cfg = opt_cfg or default_opt_config(cfg)
    opts = opts or TrainOptions()
    params = lm.init_params(cfg, key)
    state = optim.init_state(opt_cfg, params)
    err = optim.init_error(params) if opts.compress_grads else None
    return TrainState(params, state, err)


def default_opt_config(cfg: ArchConfig) -> optim.AdamWConfig:
    # bf16 moments for >=100B-parameter configs (fit the dry-run HBM)
    big = cfg.n_params() > 50e9
    return optim.AdamWConfig(
        moment_dtype="bfloat16" if big else "float32")


def make_train_step(cfg: ArchConfig,
                    opt_cfg: Optional[optim.AdamWConfig] = None,
                    opts: Optional[TrainOptions] = None) -> Callable:
    opt_cfg = opt_cfg or default_opt_config(cfg)
    opts = opts or TrainOptions()

    def lsf(params, batch):
        return lm.loss_fn(cfg, params, batch)

    def train_step(state: TrainState, batch: Dict
                   ) -> Tuple[TrainState, Dict]:
        # differentiated: the Pallas attention/SSD kernels have no VJP
        with ops.use_jnp():
            loss, grads = accumulate_grads(lsf, state.params, batch,
                                           opts.n_micro)
        err = state.error_fb
        if opts.compress_grads and err is not None:
            grads, err = optim.compress_grads(grads, err)
        if opts.lr_schedule == "cosine":
            lr_scale = optim.warmup_cosine(state.opt.step + 1,
                                           opts.warmup, opts.total_steps)
        else:
            lr_scale = 1.0
        gnorm = optim.global_norm(grads)
        params, opt_state = optim.apply_updates(
            opt_cfg, state.params, grads, state.opt, lr_scale)
        metrics = {"loss": loss.astype(jnp.float32),
                   "grad_norm": gnorm,
                   "lr_scale": jnp.asarray(lr_scale, jnp.float32),
                   "step": opt_state.step}
        return TrainState(params, opt_state, err), metrics

    return train_step
