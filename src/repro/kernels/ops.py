"""Public kernel ops — implementation dispatch.

Every op has two implementations with identical semantics:

  * ``pallas``  — the TPU kernel, compiled by Mosaic;
  * ``ref``     — the pure-jnp oracle.

``impl="auto"`` picks the Pallas kernel on a TPU and the jnp path on any
other backend.  It also picks the jnp path in two places where a kernel
cannot run: under a mesh whose axes GSPMD partitions (a ``pallas_call``
cannot be partitioned automatically; inside ``shard_map`` every device
holds whole operands and the kernel runs), and inside :func:`use_jnp`.

Off the TPU a Pallas kernel runs only in interpret mode, and only when
the caller asks for it with ``interpret=True``: ``impl="pallas"`` on
another backend without it is an error, never a silent interpretation.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from . import ref as _ref
from .flash_attention import flash_attention as _flash_attention_pallas
from .flash_decode import flash_decode as _flash_decode_pallas
from .neutron_matmul import neutron_matmul as _neutron_matmul_pallas
from .ssd_scan import ssd_scan as _ssd_scan_pallas

_USE_JNP = contextvars.ContextVar("use_jnp", default=False)


@contextlib.contextmanager
def use_jnp():
    """Trace the ``auto`` ops inside with their jnp implementations.

    The Pallas ``flash_attention`` and ``ssd_scan`` have no backward
    pass, so code that differentiates through them (the train step)
    selects the jnp path here, at its call site."""
    token = _USE_JNP.set(True)
    try:
        yield
    finally:
        _USE_JNP.reset(token)


def _gspmd_partitioned() -> bool:
    """True when the active mesh has an Auto axis of size > 1."""
    mesh = jax.sharding.get_abstract_mesh()
    return any(mesh.shape[name] > 1 and kind == AxisType.Auto
               for name, kind in zip(mesh.axis_names, mesh.axis_types))


def _resolve(impl: str, interpret: bool) -> str:
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        pallas = on_tpu and not _USE_JNP.get() and not _gspmd_partitioned()
        return "pallas" if pallas else "ref"
    if impl == "pallas" and not (on_tpu or interpret):
        raise RuntimeError(
            f"impl='pallas' needs a TPU (backend is "
            f"{jax.default_backend()!r}); pass interpret=True to run the "
            f"kernel body in the Pallas interpreter")
    return impl


# --------------------------------------------------------------------------
# neutron_matmul
# --------------------------------------------------------------------------


def neutron_matmul(x, w, bias=None, scale=None, act: str = "none",
                   out_dtype=None, out_scale: Optional[float] = None,
                   impl: str = "auto", interpret: bool = False,
                   **block_kw):
    if _resolve(impl, interpret) == "ref":
        return _ref.neutron_matmul_ref(x, w, bias=bias, scale=scale,
                                       act=act, out_dtype=out_dtype,
                                       out_scale=out_scale)
    return _neutron_matmul_pallas(x, w, bias=bias, scale=scale, act=act,
                                  out_dtype=out_dtype, out_scale=out_scale,
                                  interpret=interpret, **block_kw)


# --------------------------------------------------------------------------
# flash attention (prefill / train)
# --------------------------------------------------------------------------


def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    impl: str = "auto", interpret: bool = False,
                    fused_vjp: bool = True, **block_kw):
    """q (B,H,S,D); k/v (B,Hkv,Sk,D).

    ``fused_vjp`` uses the FlashAttention-2-style custom backward
    (O(S·D) residuals).  ``fused_vjp=False`` differentiates through the
    forward scan — the naive baseline that stacks O(S²) residuals,
    kept selectable for the §Perf before/after measurement."""
    if _resolve(impl, interpret) == "ref":
        H, Hkv = q.shape[1], k.shape[1]
        if H != Hkv:
            g = H // Hkv
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
        if fused_vjp:
            return _ref.flash_attention_fused(
                q, k, v, causal, window, sm_scale,
                block_kw.get("block_k", 512))
        return _ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window, sm_scale=sm_scale)
    return _flash_attention_pallas(q, k, v, causal=causal, window=window,
                                   sm_scale=sm_scale, interpret=interpret,
                                   **block_kw)


# --------------------------------------------------------------------------
# flash decode
# --------------------------------------------------------------------------


def flash_decode(q, k, v, kv_len=None, sm_scale: Optional[float] = None,
                 return_lse: bool = False, impl: str = "auto",
                 interpret: bool = False, **block_kw):
    """q (B,H,D); k/v (B,Hkv,S,D)."""
    if _resolve(impl, interpret) == "ref":
        H, Hkv = q.shape[1], k.shape[1]
        if H != Hkv:
            g = H // Hkv
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
        return _ref.flash_decode_ref(q, k, v, kv_len=kv_len,
                                     sm_scale=sm_scale,
                                     return_lse=return_lse)
    return _flash_decode_pallas(q, k, v, kv_len=kv_len, sm_scale=sm_scale,
                                return_lse=return_lse, interpret=interpret,
                                **block_kw)


combine_decode_shards = _ref.combine_decode_shards


# --------------------------------------------------------------------------
# Mamba2 SSD scan
# --------------------------------------------------------------------------


def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 64, init_state=None,
             impl: str = "auto", interpret: bool = False
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full chunked SSD: intra-chunk kernel + cross-chunk jnp recurrence.

    x (B,S,H,P); dt (B,S,H); A (H,); Bm/Cm (B,S,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    if _resolve(impl, interpret) == "ref":
        return _ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk,
                                 init_state=init_state)
    return _ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                            init_state=init_state, interpret=interpret)


ssd_step = _ref.ssd_step_ref          # O(1) decode step (pure jnp)
apply_activation = _ref.apply_activation
