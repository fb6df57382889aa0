"""Mamba2 SSD chunk kernel (state-space duality, arXiv:2405.21060 §6).

The SSD algorithm splits the sequence into chunks: within a chunk the
recurrence is computed as a (masked, decay-weighted) attention-like
quadratic form — MXU-friendly matmuls — while an O(S/L) recurrence
carries state across chunks.  This kernel computes the *intra-chunk*
quadratic part plus each chunk's state contribution and total decay; the
cheap cross-chunk scan runs in jnp (``ops.ssd_scan``).

The mapping to the paper's architecture: the (L x L) decay-gated score
block and the (P x N) state contribution live in VMEM for the duration of
a chunk (output-stationary), while x/dt/B/C chunk operands stream in —
exactly the operand-bandwidth-vs-accumulator-locality trade the Neutron
dot-product engine makes with its A-deep accumulator pool.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, contrib_ref, total_ref, seg_ref, *,
                      chunk: int):
    """Grid cell = (batch, chunk, head).  Blocks:
    x (L,P), dt (L,1), b (L,N), c (L,N), and A (H,) whole in SMEM ->
    y_intra (L,P), contrib (P,N), total (1,1), seg (L,1)."""
    x = x_ref[0, 0].astype(jnp.float32)           # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)         # (L, 1)
    A = a_ref[pl.program_id(1)]                   # scalar decay rate (<0)
    Bm = b_ref[0, 0].astype(jnp.float32)          # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (L, N)

    da = dt * A                                   # (L, 1)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = si <= ti
    # inclusive cumsum as a lower-triangular matmul (Mosaic has no
    # cumsum), as a column seg (L, 1) and as a row seg_row (1, L)
    tril = causal.astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST
    seg = jax.lax.dot_general(tril, da, (((1,), (0,)), ((), ())),
                              precision=exact,
                              preferred_element_type=jnp.float32)
    seg_row = jax.lax.dot_general(da, tril, (((0,), (1,)), ((), ())),
                                  precision=exact,
                                  preferred_element_type=jnp.float32)
    # decay-gated scores: G[t,s] = exp(seg[t]-seg[s]) * (C[t]·B[s]) * dt[s]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    decay = seg - seg_row                         # seg[t] - seg[s]
    gate = jnp.where(causal, jnp.exp(decay), 0.0)
    scores = cb * gate * dt.reshape(1, chunk)     # (L, L)
    y_ref[0, 0] = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)
    # chunk state contribution: sum_s exp(seg[-1]-seg[s]) dt[s] x[s]⊗B[s]
    tail = jnp.exp(seg[chunk - 1] - seg) * dt     # (L, 1)
    xw = x * tail                                 # (L, P)
    contrib_ref[0, 0] = jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(contrib_ref.dtype)
    total_ref[0, 0] = jnp.exp(seg[chunk - 1:chunk]).astype(total_ref.dtype)
    seg_ref[0, 0] = seg.astype(seg_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
              Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int = 64,
              interpret: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                         jnp.ndarray]:
    """Intra-chunk SSD.  x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N).
    S must be a multiple of `chunk` (ops.py pads).

    Returns (y_intra (B,S,H,P), contrib (B,nc,H,P,N), total (B,nc,H),
    seg (B,S,H))."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    L = chunk

    # layout: (B, nc, H, L, ...) so each grid cell reads one (L, ...) block
    xr = x.reshape(Bsz, nc, L, H, P).transpose(0, 1, 3, 2, 4)
    dtr = dt.reshape(Bsz, nc, L, H).transpose(0, 1, 3, 2)[..., None]
    br = jnp.broadcast_to(Bm.reshape(Bsz, nc, 1, L, N),
                          (Bsz, nc, H, L, N))
    cr = jnp.broadcast_to(Cm.reshape(Bsz, nc, 1, L, N),
                          (Bsz, nc, H, L, N))
    ar = A.astype(jnp.float32)

    grid = (Bsz * nc, H)
    kernel = functools.partial(_ssd_chunk_kernel, chunk=L)
    y, contrib, total, seg = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),         # A (H,)
            pl.BlockSpec((1, 1, L, N), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda bc, h: (bc, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda bc, h: (bc, h, 0, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda bc, h: (bc, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz * nc, H, L, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz * nc, H, P, N), jnp.float32),
            jax.ShapeDtypeStruct((Bsz * nc, H, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bsz * nc, H, L, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xr.reshape(Bsz * nc, H, L, P), dtr.reshape(Bsz * nc, H, L, 1),
      ar, br.reshape(Bsz * nc, H, L, N), cr.reshape(Bsz * nc, H, L, N))

    y = y.reshape(Bsz, nc, H, L, P).transpose(0, 1, 3, 2, 4) \
         .reshape(Bsz, S, H, P)
    contrib = contrib.reshape(Bsz, nc, H, P, N)
    total = total.reshape(Bsz, nc, H)
    seg = seg.reshape(Bsz, nc, H, L).transpose(0, 1, 3, 2) \
             .reshape(Bsz, S, H)
    return y, contrib, total, seg


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int = 64,
             init_state=None, interpret: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full chunked SSD: the intra-chunk kernel + the cross-chunk jnp
    recurrence.  x (B,S,H,P); dt (B,S,H); A (H,); Bm/Cm (B,S,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = math.ceil(S / chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y_in, contrib, total, seg = ssd_chunk(
        x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)

    def scan_state(s_prev, inp):
        contrib_c, total_c = inp
        return s_prev * total_c[..., None, None] + contrib_c, s_prev

    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((Bsz, H, P, N), dtype=jnp.float32))
    s_final, s_prevs = jax.lax.scan(
        scan_state, s0,
        (contrib.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)))
    s_prevs = s_prevs.transpose(1, 0, 2, 3, 4)            # (B,nc,H,P,N)
    L = chunk
    segc = seg.reshape(Bsz, nc, L, H)
    Cc = Cm.reshape(Bsz, nc, L, N).astype(jnp.float32)
    y_out = jnp.einsum("bcln,bclh,bchpn->bclhp", Cc, jnp.exp(segc),
                       s_prevs)
    y = (y_in.reshape(Bsz, nc, L, H, P) +
         y_out).reshape(Bsz, nc * L, H, P)[:, :S]
    return y.astype(x.dtype), s_final.astype(x.dtype)
