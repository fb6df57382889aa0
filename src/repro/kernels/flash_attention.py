"""Flash attention (prefill) Pallas kernel — causal / sliding-window / GQA.

The TPU-native instance of the paper's data-movement thesis for the
attention hot-spot: softmax statistics (m, l) and the output accumulator
stay *output-stationary* in VMEM while KV blocks stream through the grid
pipeline; no (S x S) score matrix ever exists in HBM.

GQA is handled in the BlockSpec index maps (q head h reads kv head
h // group) — the shared-operand trick of the Neutron bus (one KV operand
feeds `group` query heads).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  sm_scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, n_k: int, kv_len: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    iq = pl.program_id(2)
    q0 = iq * block_q
    k0 = ik * block_k

    run = jnp.asarray(True)
    if causal:
        # skip fully-masked blocks (upper triangle)
        run = jnp.logical_and(run, k0 <= q0 + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, q0 - (k0 + block_k - 1) < window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        qi = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kj = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kj < kv_len
        if causal:
            mask &= kj <= qi
        if window is not None:
            mask &= qi - kj < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_q", "block_k",
                     "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q (B,H,S,D); k (B,Hkv,Sk,D); v (B,Hkv,Sk,Dv); H % Hkv == 0."""
    B, H, S, D = q.shape
    _, Hkv, Sk, _ = k.shape
    Dv = v.shape[-1]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    sm_scale = sm_scale or 1.0 / math.sqrt(D)

    bq = min(block_q, S)
    bk = min(block_k, Sk)
    Sp = math.ceil(S / bq) * bq
    Skp = math.ceil(Sk / bk) * bk
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if Skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Skp - Sk), (0, 0)))
    n_q = Sp // bq
    n_k = Skp // bk
    grid = (B, H, n_q, n_k)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=bq, block_k=bk, n_k=n_k, kv_len=Sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running sum
            pltpu.VMEM((bq, Dv), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :, :S]
