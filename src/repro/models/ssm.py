"""Mamba2 block (SSD) — chunked-scan training, O(1)-state decode.

Block layout follows arXiv:2405.21060: a single input projection yields
(z, x, B, C, dt); x/B/C pass through a short causal depthwise conv; the
SSD scan mixes sequence information; a gated RMSNorm and output
projection close the block.  Decode carries (conv_state, ssd_state) —
constant in sequence length, which is why the SSM/hybrid archs run the
500k-token cell.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .config import ArchConfig
from .layers import dense_init, init_rms_norm, rms_norm


class SSMState(NamedTuple):
    conv: jnp.ndarray        # (B, conv_w - 1, d_conv_in)
    ssd: jnp.ndarray         # (B, H, P, N)


def init_ssm(key, cfg: ArchConfig, dtype) -> Dict:
    d = cfg.d_model
    di = cfg.d_inner
    N = cfg.ssm_state
    H = cfg.ssm_heads
    ks = jax.random.split(key, 4)
    d_in_proj = 2 * di + 2 * N + H           # z, x, B, C, dt
    d_conv_in = di + 2 * N                   # conv over x, B, C
    return {
        "ssm_in": dense_init(ks[0], (d, d_in_proj), dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, d_conv_in), dtype,
                             scale=0.5),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "gnorm": init_rms_norm(di, dtype),
        "ssm_out": dense_init(ks[2], (di, d), dtype),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv.  x (B, S, C); w (K, C).  Returns (y, new
    state of the last K-1 inputs)."""
    K = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = jnp.zeros_like(x, shape=x.shape)
    for i in range(K):
        y = y + xp[:, i:i + x.shape[1], :] * w[i]
    new_state = xp[:, -(K - 1):, :] if K > 1 else xp[:, :0, :]
    return jax.nn.silu(y), new_state


def _split_proj(cfg: ArchConfig, proj: jnp.ndarray):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xBC = proj[..., di:di + di + 2 * N]
    dt = proj[..., di + di + 2 * N:]
    return z, xBC, dt


def ssm_block(p: Dict, h: jnp.ndarray, cfg: ArchConfig,
              state: Optional[SSMState] = None
              ) -> Tuple[jnp.ndarray, Optional[SSMState]]:
    """h (B, S, d) full-sequence (state=None) or (B, 1, d) decode."""
    B, S, d = h.shape
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, \
        cfg.ssm_head_dim
    proj = h @ p["ssm_in"]
    z, xBC, dt_raw = _split_proj(cfg, proj)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"])                    # (B, S, H)
    A = -jnp.exp(p["A_log"])                                # (H,)

    if state is None:
        xBC, _ = _causal_conv(xBC, p["conv_w"])
        xs = xBC[..., :di].reshape(B, S, H, P)
        Bm = xBC[..., di:di + N]
        Cm = xBC[..., di + N:]
        y, _ = ops.ssd_scan(xs, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
        y = (y + xs * p["D"][None, None, :, None]).astype(h.dtype)
        y = y.reshape(B, S, di)
        y = rms_norm(p["gnorm"], y * jax.nn.silu(z), cfg.norm_eps)
        return (y @ p["ssm_out"]).astype(h.dtype), None

    # ---- decode step ----
    xBC_t, conv_state = _causal_conv(xBC, p["conv_w"], state.conv)
    xs = xBC_t[:, 0, :di].reshape(B, H, P)
    Bm = xBC_t[:, 0, di:di + N]
    Cm = xBC_t[:, 0, di + N:]
    y, ssd_state = ops.ssd_step(state.ssd, xs, dt[:, 0], A, Bm, Cm)
    y = (y + xs * p["D"][None, :, None]).astype(h.dtype)
    y = y.reshape(B, 1, di)
    y = rms_norm(p["gnorm"], y * jax.nn.silu(z), cfg.norm_eps)
    return (y @ p["ssm_out"]).astype(h.dtype), \
        SSMState(conv_state.astype(state.conv.dtype),
                 ssd_state.astype(state.ssd.dtype))


def init_ssm_state(cfg: ArchConfig, batch: int, dtype) -> SSMState:
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, \
        cfg.ssm_head_dim
    d_conv_in = di + 2 * N
    return SSMState(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, d_conv_in), dtype),
        ssd=jnp.zeros((batch, H, P, N), dtype),
    )
