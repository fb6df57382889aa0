"""Flash-decode Pallas kernel — one new token against a long KV cache.

Decode attention is *memory-roofline* work (arithmetic intensity ~2
ops/byte over the KV cache); the kernel's only job is to stream the cache
through VMEM exactly once at full bandwidth with streaming softmax — the
Neutron "one operand stays stationary (q), the other streams (KV)"
pattern.  Optionally emits the per-(batch, head) log-sum-exp so that
partial results computed on different devices (KV sharded along sequence
for 500k-token contexts) combine exactly.

One grid cell takes a tile of ``bt`` batch rows and one KV head with all
the query heads of its group, so each KV block is fetched once for the
whole group and a cell does enough work to hide its fixed cost.  The
tiles come from the operand shapes alone (:func:`decode_tiles`).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
MAX_BATCH_TILE = 8          # batch rows a cell takes at most
VMEM_BUDGET = 8 << 20       # half of a v5e core's 16 MiB default scoped VMEM


class DecodeTiles(NamedTuple):
    bt: int          # batch rows a grid cell takes (divides B)
    bk: int          # KV rows a grid cell takes
    s_pad: int       # sequence length after padding (S unless no bk divides S)
    grid: tuple      # (B / bt, Hkv, s_pad / bk)
    vmem_bytes: int  # VMEM a cell's buffers and f32 temporaries take


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a (rows, cols) array in VMEM's (sublane, 128-lane) tiles."""
    sub = 8 * max(1, 4 // itemsize)
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def _cell_bytes(bt: int, group: int, bk: int, D: int, Dv: int,
                itemsize: int) -> int:
    """The double-buffered q/K/V/out/LSE blocks, the m/l/acc scratch and
    the f32 temporaries of the tile's rows (s, p and V in f32)."""
    blocks = (_tile_bytes(group, D, itemsize) + _tile_bytes(bk, D, itemsize)
              + _tile_bytes(bk, Dv, itemsize)
              + _tile_bytes(group, Dv, itemsize) + _tile_bytes(group, 1, 4))
    scratch = 2 * _tile_bytes(group, 1, 4) + _tile_bytes(group, Dv, 4)
    temps = 2 * _tile_bytes(group, bk, 4) + _tile_bytes(bk, Dv, 4)
    return bt * (2 * blocks + scratch + temps)


def _kv_block(S: int, block_k: int) -> int:
    """S whole if it is at most ``block_k``, else the largest multiple of 8
    up to ``block_k`` that divides S, else the smallest multiple of 8 that
    covers S in as few blocks of at most ``block_k`` as possible."""
    if S <= block_k:
        return S
    for bk in range(block_k - block_k % 8, 7, -8):
        if S % bk == 0:
            return bk
    rows = -(-S // -(-S // block_k))           # S over the fewest blocks
    return -(-rows // 8) * 8


def decode_tiles(B: int, H: int, Hkv: int, S: int, D: int, Dv: int,
                 dtype, block_k: int = 256) -> DecodeTiles:
    """The decode grid for q (B,H,D) against K (B,Hkv,S,D), V (B,Hkv,S,Dv):
    ``bt`` is the largest divisor of B up to :data:`MAX_BATCH_TILE` whose
    cell fits :data:`VMEM_BUDGET` (1 if none does); ``bk`` is
    :func:`_kv_block`'s."""
    group = H // Hkv
    itemsize = jnp.dtype(dtype).itemsize
    bk = _kv_block(S, block_k)
    s_pad = -(-S // bk) * bk
    bt = next((t for t in range(min(B, MAX_BATCH_TILE), 0, -1)
               if B % t == 0
               and _cell_bytes(t, group, bk, D, Dv, itemsize) <= VMEM_BUDGET),
              1)
    return DecodeTiles(bt=bt, bk=bk, s_pad=s_pad,
                       grid=(B // bt, Hkv, s_pad // bk),
                       vmem_bytes=_cell_bytes(bt, group, bk, D, Dv, itemsize))


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_ref, l_ref, acc_ref, *,
                   sm_scale: float, bt: int, block_k: int, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b0 = pl.program_id(0) * bt
    k0 = ik * block_k
    lens = [len_ref[b0 + i] for i in range(bt)]
    kv_len = jnp.zeros((bt, 1, 1), jnp.int32)      # each row's kv_len
    row = jax.lax.broadcasted_iota(jnp.int32, kv_len.shape, 0)
    for i, n in enumerate(lens):
        kv_len = jnp.where(row == i, n, kv_len)

    @pl.when(k0 < functools.reduce(jnp.maximum, lens))
    def _body():
        q = q_ref[:, 0]                            # (bt, group, D)
        k = k_ref[:, 0]                            # (bt, bk, D)
        v = v_ref[:, 0].astype(jnp.float32)        # (bt, bk, Dv)
        s = jnp.einsum("bgd,bkd->bgk", q, k,
                       preferred_element_type=jnp.float32)
        kj = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = kj < kv_len
        s = jnp.where(valid, s * sm_scale, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # masked p keeps a row with no valid key at l = 0 (output 0)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "bgk,bkd->bgd", p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[:, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[:, 0] = (m_ref[...] + jnp.log(l)).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "block_k", "return_lse", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 kv_len: Optional[jnp.ndarray] = None,
                 sm_scale: Optional[float] = None,
                 block_k: int = 256, return_lse: bool = False,
                 interpret: bool = False):
    """q (B,H,D); k (B,Hkv,S,D); v (B,Hkv,S,Dv); kv_len (B,).

    A row whose ``kv_len`` is 0 reads output 0 and log-sum-exp about
    ``NEG_INF``, so :func:`~repro.kernels.ref.combine_decode_shards` gives
    it no weight."""
    B, H, D = q.shape
    _, Hkv, S, _ = k.shape
    Dv = v.shape[-1]
    assert H % Hkv == 0
    group = H // Hkv
    sm_scale = sm_scale or 1.0 / math.sqrt(D)
    if kv_len is None:
        kv_len = jnp.full((B,), S, dtype=jnp.int32)

    t = decode_tiles(B, H, Hkv, S, D, Dv, k.dtype, block_k)
    if t.s_pad != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, t.s_pad - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, t.s_pad - S), (0, 0)))

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale, bt=t.bt,
                               block_k=t.bk, n_k=t.grid[2])
    out, lse = pl.pallas_call(
        kernel,
        grid=t.grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # kv_len (B,)
            pl.BlockSpec((t.bt, 1, group, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((t.bt, 1, t.bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((t.bt, 1, t.bk, Dv), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t.bt, 1, group, Dv), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((t.bt, 1, group, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, group, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, group, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.bt, group, 1), jnp.float32),
            pltpu.VMEM((t.bt, group, 1), jnp.float32),
            pltpu.VMEM((t.bt, group, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(kv_len.astype(jnp.int32), q.reshape(B, Hkv, group, D), k, v)
    o = out.reshape(B, H, Dv)
    if return_lse:
        return o, lse.reshape(B, H)
    return o
