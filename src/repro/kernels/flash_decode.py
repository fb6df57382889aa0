"""Flash-decode Pallas kernel — one new token against a long KV cache.

Decode attention is *memory-roofline* work (arithmetic intensity ~2
ops/byte over the KV cache); the kernel's only job is to stream the cache
through VMEM exactly once at full bandwidth with streaming softmax — the
Neutron "one operand stays stationary (q), the other streams (KV)"
pattern.  Optionally emits the per-(batch, head) log-sum-exp so that
partial results computed on different devices (KV sharded along sequence
for 500k-token contexts) combine exactly.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_ref, l_ref, acc_ref, *,
                   sm_scale: float, block_k: int, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = pl.program_id(0)
    kv_len = len_ref[b]
    k0 = ik * block_k

    @pl.when(k0 < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kj = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kj < kv_len, s * sm_scale, NEG_INF)
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
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "block_k", "return_lse", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 kv_len: Optional[jnp.ndarray] = None,
                 sm_scale: Optional[float] = None,
                 block_k: int = 256, return_lse: bool = False,
                 interpret: bool = False):
    """q (B,H,D); k (B,Hkv,S,D); v (B,Hkv,S,Dv); kv_len (B,)."""
    B, H, D = q.shape
    _, Hkv, S, _ = k.shape
    Dv = v.shape[-1]
    assert H % Hkv == 0
    group = H // Hkv
    sm_scale = sm_scale or 1.0 / math.sqrt(D)
    if kv_len is None:
        kv_len = jnp.full((B,), S, dtype=jnp.int32)

    bk = min(block_k, S)
    Sp = math.ceil(S / bk) * bk
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    n_k = Sp // bk
    grid = (B, H, n_k)

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_k=bk, n_k=n_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # kv_len (B,)
            pl.BlockSpec((1, 1, 1, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Dv), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, 1, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(kv_len.astype(jnp.int32), q.reshape(B, H, 1, D), k, v)
    o = out.reshape(B, H, Dv)
    if return_lse:
        return o, lse.reshape(B, H)
    return o
