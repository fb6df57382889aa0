"""Output-stationary fused matmul — the Neutron dot-product engine on TPU.

TPU-native adaptation of paper §III-B:

  * the engine's wide 32-bit accumulators -> a VMEM f32/i32 accumulator
    scratch that never leaves the core while K streams through
    (*output-stationary*, "completely avoid outside memory accesses for
    wide 32-bit accumulator values");
  * the A-deep accumulator pool / operand sharing -> (block_m x block_n)
    MXU-aligned output blocks reusing both operand blocks block_k times;
  * the fused rescale -> activation epilogue ("activation engine") runs on
    the accumulator before the single result write-back, including the
    int8 requantization path of the INT8 deployment;
  * the data-engine prefetcher -> the Pallas grid pipeline double-buffers
    HBM->VMEM block copies automatically.

Block shapes are multiples of (8, 128) sublane/lane tiles; defaults
(128, 128, 512) keep the working set (x-blk + w-blk + acc ≈ 192 KiB bf16)
far under the ~16 MiB VMEM while saturating the 128x128 MXU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import apply_activation


def _matmul_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
                   act: str, n_k: int, requant: bool,
                   out_scale: Optional[float]):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    if x.dtype == jnp.int8:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        if scale_ref is not None:
            acc = acc * scale_ref[...].astype(jnp.float32)
        if bias_ref is not None:
            acc = acc + bias_ref[...].astype(jnp.float32)
        acc = apply_activation(acc, act)
        if requant:
            q = jnp.round(acc / out_scale)
            o_ref[...] = jnp.clip(q, -128, 127).astype(o_ref.dtype)
        else:
            o_ref[...] = acc.astype(o_ref.dtype)


def _pad_to(a: jnp.ndarray, mults) -> jnp.ndarray:
    pads = [(0, (-d) % m) for d, m in zip(a.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(a, pads)
    return a


@functools.partial(
    jax.jit,
    static_argnames=("act", "out_dtype", "out_scale", "block_m", "block_n",
                     "block_k", "interpret"))
def neutron_matmul(x: jnp.ndarray, w: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None,
                   scale: Optional[jnp.ndarray] = None,
                   act: str = "none",
                   out_dtype: Optional[jnp.dtype] = None,
                   out_scale: Optional[float] = None,
                   block_m: int = 128, block_n: int = 128,
                   block_k: int = 512,
                   interpret: bool = False) -> jnp.ndarray:
    """y[M,N] = requant(act(scale * (x[M,K] @ w[K,N]) + bias))."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    requant = out_scale is not None
    if out_dtype is None:
        out_dtype = jnp.int8 if requant else (
            jnp.float32 if x.dtype == jnp.int8 else x.dtype)

    bm = min(block_m, max(8, M))
    bn = min(block_n, max(128, N))
    bk = min(block_k, max(128, K))
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    n_k = Kp // bk
    grid = (Mp // bm, Np // bn, n_k)

    acc_dtype = jnp.int32 if x.dtype == jnp.int8 else jnp.float32
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [xp, wp]
    if scale is not None:
        sc = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (N,))
        args.append(_pad_to(sc.reshape(1, N), (1, bn)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
    if bias is not None:
        args.append(_pad_to(bias.reshape(1, N), (1, bn)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))

    def kernel(*refs):
        x_ref, w_ref = refs[0], refs[1]
        idx = 2
        scale_ref = bias_ref = None
        if scale is not None:
            scale_ref = refs[idx]
            idx += 1
        if bias is not None:
            bias_ref = refs[idx]
            idx += 1
        o_ref, acc_ref = refs[-2], refs[-1]
        _matmul_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref,
                       act=act, n_k=n_k, requant=requant,
                       out_scale=out_scale)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="neutron_matmul",
    )(*args)
    return out[:M, :N]
