"""Mamba-2 SSD layers: a pre-norm block with a depthwise causal
convolution, softplus step sizes, A = -exp(A_log), a D skip and a gated
RMSNorm.

Everything about this family that the benchmark needs, found by the
configuration's ``"family": "ssm"``: the sizes a configuration file
states and the program must match, the canonical weight leaves of one
layer with the rules that draw the SSD parameters, the reference layer,
and the work of the layer stack in one decode step.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmarks.tpu import counts, reference as ref, weights
from benchmarks.tpu.weights import Leaf

SIZES = ("ssm_state", "ssm_head_dim", "ssm_expand", "ssm_conv")


def conv(key, shape):
    return weights.normalish(key, shape) * (0.5 / weights.SUM4_STD)


def a_log(key, shape):
    """A = exp(A_log) in [1, 16]."""
    b = weights.random_bytes(key, shape)
    return (b[0] * 256 + b[1]).astype(jnp.float32) \
        * (math.log(16.0) / 65536)


def d_skip(key, shape):
    """1 +- 0.25."""
    return 1.0 + (weights.random_bytes(key, shape)[0] - 128).astype(
        jnp.float32) / 512


def dt_bias(key, shape):
    """softplus(dt_bias) in [1e-3, 0.1]."""
    return -7.0 + weights.random_bytes(key, shape)[0].astype(
        jnp.float32) * (19 / 1024)


def _dims(c):
    di = c["ssm_expand"] * c["d_model"]
    N, P = c["ssm_state"], c["ssm_head_dim"]
    return di, N, P, di // P, c["ssm_conv"]


def leaves(c: Dict[str, Any]) -> List[Leaf]:
    d, dt = c["d_model"], c["dtype"]
    di, N, P, H, K = _dims(c)
    dense, norm = weights.dense, weights.norm
    return [Leaf("layers/norm", (d,), norm, dt, True),
            Leaf("layers/ssm/ssm_in", (d, 2 * di + 2 * N + H), dense, dt,
                 True),
            Leaf("layers/ssm/conv_w", (K, di + 2 * N), conv, dt, True),
            Leaf("layers/ssm/A_log", (H,), a_log, "float32", True),
            Leaf("layers/ssm/D", (H,), d_skip, "float32", True),
            Leaf("layers/ssm/dt_bias", (H,), dt_bias, "float32", True),
            Leaf("layers/ssm/gnorm", (di,), norm, dt, True),
            Leaf("layers/ssm/ssm_out", (di, d), dense, dt, True)]


def layer(c, quant, h, w):
    """One layer of the reference over whole sequences h (K, T, d): the
    recurrence stepped one position at a time from a zero state."""
    K, T, d = h.shape
    di, N, P, H, _ = _dims(c)
    x = ref.rms(h, w["norm"], c["norm_eps"])
    proj = ref.mm(x, w["ssm/ssm_in"], quant)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], \
        proj[..., 2 * di + 2 * N:]
    dt = jax.nn.softplus(dt + w["ssm/dt_bias"])              # (K, T, H)
    cw = w["ssm/conv_w"].astype(jnp.float32)                  # (Kc, C)
    Kc = cw.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (Kc - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + T] * cw[i] for i in range(Kc))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(K, T, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    A = -jnp.exp(w["ssm/A_log"])

    def tick(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = state * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None, None] * x_t[..., None]) * b_t[:, None, None]
        return state, jnp.einsum("khpn,kn->khp", state, c_t,
                                 precision=ref.HIGHEST)

    _, y = jax.lax.scan(tick, jnp.zeros((K, H, P, N), jnp.float32),
                        (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                         Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)))
    y = y.swapaxes(0, 1) + xs * w["ssm/D"][:, None]
    y = ref.rms(y.reshape(K, T, di) * jax.nn.silu(z), w["ssm/gnorm"],
                c["norm_eps"])
    return h + ref.mm(y, w["ssm/ssm_out"], quant)


def step_work(c: Dict[str, Any], batch: int, pos: int) -> counts.Work:
    """The layer stack's operations in one decode step (projections,
    convolution, and 6 an element of the SSD state: decay, input outer
    product and add, read-out), and the bytes it moves besides its
    weights: the convolution and SSD state read and written.  The same
    at every position."""
    d, L, b = c["d_model"], c["n_layers"], counts.BYTES[c["dtype"]]
    di, N, P, H, K = _dims(c)
    mats = d * (2 * di + 2 * N + H) + di * d
    flops = L * batch * (2.0 * mats + 2.0 * K * (di + 2 * N)
                         + 6.0 * H * P * N)
    state = H * P * N + (K - 1) * (di + 2 * N)
    return counts.Work(flops=flops, bytes=L * 2.0 * batch * state * b)
