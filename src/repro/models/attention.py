"""Attention variants: GQA / MQA, sliding-window, MLA, with KV caches.

Three entry modes per variant:
  * ``full``   — training / prefill over a whole sequence (flash kernel);
  * ``decode`` — one new token against a cached KV prefix (flash-decode);
the cache layout is (B, Hkv, S, D) so the sequence axis can be sharded
across the ``data`` mesh axis for 500k-token decode (the per-shard
partials are exact thanks to the kernel's log-sum-exp output).

MLA (DeepSeek-V3) caches only the compressed KV latent + decoupled RoPE
key — the paper's "operand that stays resident" applied to the KV cache:
per token 512+64 floats instead of 128 heads x 2 x 128.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .config import ArchConfig
from .layers import apply_mrope, apply_rope, dense_init, init_rms_norm, \
    rms_norm
from .sharding import maybe_shard, mesh_axis_size


# --------------------------------------------------------------------------
# GQA / MQA
# --------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, dtype) -> Dict:
    """wq/wo are allocated at `padded_heads` (a tp_pad multiple) so the
    head axis reshapes cleanly under 16-way tensor parallelism; the
    padded head outputs are zero-masked in the forward so the math is
    exactly the nominal-head model (padded weights receive zero grad)."""
    d, Hkv, hd = cfg.d_model, cfg.n_kv_heads, cfg.head_dim
    Hp = cfg.padded_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, Hp * hd), dtype),
        "wk": dense_init(ks[1], (d, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, Hkv * hd), dtype),
        "wo": dense_init(ks[3], (Hp * hd, d), dtype),
    }


def _split_heads(x: jnp.ndarray, n: int) -> jnp.ndarray:
    B, S, _ = x.shape
    return x.reshape(B, S, n, -1)


def _expand_kv(k: jnp.ndarray, H: int, Hkv: int, Hp: int) -> jnp.ndarray:
    """(B,S,Hkv,hd) -> (B,S,Hp,hd) with the ORIGINAL H//Hkv group map
    (padded q heads clamp to the last kv head; their outputs are masked
    away).  Used when flash's uniform Hp//Hkv grouping would misroute."""
    group = max(H // max(Hkv, 1), 1)
    idx = jnp.minimum(jnp.arange(Hp) // group, Hkv - 1)
    return jnp.take(k, idx, axis=2)


def _mask_padded(o2d: jnp.ndarray, H: int, Hp: int, hd: int
                 ) -> jnp.ndarray:
    """Zero the padded-head columns of the flattened attention output
    (B, S, Hp*hd) so wo's padded rows contribute (and learn) nothing."""
    if Hp == H:
        return o2d
    keep = (jnp.arange(Hp * hd) < H * hd).astype(o2d.dtype)
    return o2d * keep


def attention(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
              positions: jnp.ndarray,
              window: Optional[jnp.ndarray] = None,
              mrope_positions: Optional[jnp.ndarray] = None,
              kv_cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              cache_pos: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray,
                         Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """x (B, S, d).  Full mode when kv_cache is None; decode mode (S == 1)
    updates the cache at `cache_pos` and attends to the valid prefix.
    `window` is a traced per-layer scalar (0 => full attention)."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hp = cfg.padded_heads
    q = _split_heads(x @ p["wq"], Hp)
    k = _split_heads(x @ p["wk"], Hkv)
    v = _split_heads(x @ p["wv"], Hkv)
    if cfg.mrope and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections,
                        cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections,
                        cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if kv_cache is None:
        # window: None -> arch default; 0 -> explicitly full; int -> window
        if window is None:
            w = cfg.sliding_window or None
        elif isinstance(window, int) and window <= 0:
            w = None
        else:
            w = window
        q = maybe_shard(q, "data", None, "model", None)
        # KV format selection: head-sharded when the kv heads divide the
        # model axis; otherwise computed sharded (flat) and ALL-GATHERED
        # here to replicated — the broadcast-operand format.  Gathering
        # the small KV beats replicating its projection FLOPs.
        kv_ok = Hkv % max(mesh_axis_size("model"), 1) == 0
        k = maybe_shard(k, "data", None, "model" if kv_ok else None, None)
        v = maybe_shard(v, "data", None, "model" if kv_ok else None, None)
        if Hp != H:
            # padded TP: expand kv to the padded layout (original group
            # map); the expansion of replicated kv is a free local slice
            k = _expand_kv(k, H, Hkv, Hp)
            v = _expand_kv(v, H, Hkv, Hp)
            k = maybe_shard(k, "data", None, "model", None)
            v = maybe_shard(v, "data", None, "model", None)
        o = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=w,
            fused_vjp=cfg.fused_attn_vjp,
            block_k=cfg.attn_block_k)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, Hp * hd)
        o = _mask_padded(o, H, Hp, hd)
        return o @ p["wo"], None

    # ---- decode: S == 1 (cache stays at the nominal Hkv heads) ----
    ck, cv = kv_cache                           # (B, Hkv, Smax, hd)
    qd = q[:, 0][:, :H].reshape(B, H, hd)        # drop padded heads
    if _use_seq_sharded_decode(cfg, B, ck.shape[2]):
        o, ck, cv = _decode_seq_sharded(
            qd, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            ck, cv, cache_pos, cfg)
    else:
        ck = jax.lax.dynamic_update_slice(
            ck, k.transpose(0, 2, 1, 3).astype(ck.dtype),
            (0, 0, cache_pos, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, v.transpose(0, 2, 1, 3).astype(cv.dtype),
            (0, 0, cache_pos, 0))
        kv_len = jnp.full((B,), cache_pos + 1, dtype=jnp.int32)
        o = ops.flash_decode(qd, ck, cv, kv_len=kv_len)
    o = o.reshape(B, H * hd)
    if Hp != H:
        o = jnp.pad(o, ((0, 0), (0, (Hp - H) * hd)))
    return (o @ p["wo"])[:, None, :], (ck, cv)


def _decode_seq_sharded(q3, k_new, v_new, ck, cv, pos, cfg: ArchConfig):
    """Decode against a KV cache whose SEQUENCE axis is sharded over the
    `model` mesh axis (broadcast-operand archs: kv heads don't divide the
    axis).  Each shard updates only the slice owning `pos`, computes a
    partial flash-decode over its local positions, and the shards merge
    exactly via the log-sum-exp identity.  Avoids GSPMD's involuntary
    full rematerialization of the cache on the dynamic-position write
    (nemotron-340b decode: 368 GB/step of all-gather otherwise).

    q3 (B,H,hd); k_new/v_new (B,Hkv,1,hd); ck/cv (B,Hkv,S,hd)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.kernels.ref import combine_decode_shards

    def local(q3, kn, vn, ck, cv):
        i = jax.lax.axis_index("model")
        S_loc = ck.shape[2]
        start = (i * S_loc).astype(jnp.int32)
        off = jnp.clip(pos - start, 0, S_loc - 1)
        write = jnp.logical_and(pos >= start, pos < start + S_loc)

        def upd(c, n):
            return jax.lax.cond(
                write,
                lambda: jax.lax.dynamic_update_slice(
                    c, n.astype(c.dtype), (0, 0, off, 0)),
                lambda: c)

        ck2 = upd(ck, kn)
        cv2 = upd(cv, vn)
        kv_len = jnp.clip(pos + 1 - start, 0, S_loc)
        o, lse = ops.flash_decode(
            q3, ck2, cv2,
            kv_len=jnp.full((q3.shape[0],), kv_len, jnp.int32),
            return_lse=True)
        outs = jax.lax.all_gather(o, "model")
        lses = jax.lax.all_gather(lse, "model")
        return combine_decode_shards(outs, lses), ck2, cv2

    fn = jax.shard_map(
        local,
        in_specs=(P("data", None, None), P("data", None, None, None),
                  P("data", None, None, None),
                  P("data", None, "model", None),
                  P("data", None, "model", None)),
        out_specs=(P("data", None, None),
                   P("data", None, "model", None),
                   P("data", None, "model", None)),
        check_vma=False)
    return fn(q3, k_new, v_new, ck, cv)


def _use_seq_sharded_decode(cfg: ArchConfig, B: int, S: int) -> bool:
    nm = mesh_axis_size("model")
    nd = mesh_axis_size("data")
    return (nm > 1 and cfg.n_kv_heads and cfg.n_kv_heads % nm != 0
            and S % nm == 0 and B % max(nd, 1) == 0 and B >= nd)


# --------------------------------------------------------------------------
# Sliding-window KV cache decode (ring buffer)
# --------------------------------------------------------------------------


def decode_windowed(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
                    kv_cache: Tuple[jnp.ndarray, jnp.ndarray],
                    cache_pos: jnp.ndarray, window: int
                    ) -> Tuple[jnp.ndarray,
                               Tuple[jnp.ndarray, jnp.ndarray]]:
    """Decode against a ring-buffer cache of size `window` (local layers
    of gemma3 at 500k context: KV stays O(window), not O(S))."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hp = cfg.padded_heads
    q = _split_heads(x @ p["wq"], Hp)
    k = _split_heads(x @ p["wk"], Hkv)
    v = _split_heads(x @ p["wv"], Hkv)
    pos = jnp.full((B, 1), cache_pos, dtype=jnp.int32)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    ck, cv = kv_cache                            # (B, Hkv, window, hd)
    slot = jnp.mod(cache_pos, window)
    ck = jax.lax.dynamic_update_slice(
        ck, k.transpose(0, 2, 1, 3).astype(ck.dtype), (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(
        cv, v.transpose(0, 2, 1, 3).astype(cv.dtype), (0, 0, slot, 0))
    kv_len = jnp.full((B,), jnp.minimum(cache_pos + 1, window),
                      dtype=jnp.int32)
    o = ops.flash_decode(q[:, 0][:, :H].reshape(B, H, hd), ck, cv,
                         kv_len=kv_len)
    o = o.reshape(B, H * hd)
    if Hp != H:
        o = jnp.pad(o, ((0, 0), (0, (Hp - H) * hd)))
    return (o @ p["wo"])[:, None, :], (ck, cv)


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# --------------------------------------------------------------------------


def init_mla(key, cfg: ArchConfig, dtype) -> Dict:
    d, H = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.d_nope, cfg.d_rope, cfg.d_v
    ks = jax.random.split(key, 8)
    return {
        "w_dq": dense_init(ks[0], (d, qr), dtype),
        "q_norm": init_rms_norm(qr, dtype),
        "w_uq": dense_init(ks[1], (qr, H * (dn + dr)), dtype),
        "w_dkv": dense_init(ks[2], (d, kvr + dr), dtype),
        "kv_norm": init_rms_norm(kvr, dtype),
        "w_uk": dense_init(ks[3], (kvr, H * dn), dtype),
        "w_uv": dense_init(ks[4], (kvr, H * dv), dtype),
        "wo": dense_init(ks[5], (H * dv, d), dtype),
    }


def mla_attention(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
                  positions: jnp.ndarray,
                  kv_cache: Optional[jnp.ndarray] = None,
                  cache_pos: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """MLA.  Cache holds only (latent || rope-key): (B, Smax, kvr + dr)."""
    B, S, d = x.shape
    H = cfg.n_heads
    dn, dr, dv, kvr = cfg.d_nope, cfg.d_rope, cfg.d_v, cfg.kv_lora_rank
    cq = rms_norm(p["q_norm"], x @ p["w_dq"], cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = x @ p["w_dkv"]                    # (B, S, kvr + dr)
    latent, k_rope = ckv_full[..., :kvr], ckv_full[..., kvr:]
    latent = rms_norm(p["kv_norm"], latent, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    packed = jnp.concatenate([latent, k_rope], axis=-1)

    if kv_cache is not None:
        kv_cache = jax.lax.dynamic_update_slice(
            kv_cache, packed.astype(kv_cache.dtype), (0, cache_pos, 0))
        packed_all = kv_cache
        S_kv = kv_cache.shape[1]
        kv_len = cache_pos + 1
    else:
        packed_all = packed
        S_kv = S
        kv_len = None

    latent_all = packed_all[..., :kvr].astype(x.dtype)
    k_rope_all = packed_all[..., kvr:].astype(x.dtype)
    k_nope = (latent_all @ p["w_uk"]).reshape(B, S_kv, H, dn)
    v_all = (latent_all @ p["w_uv"]).reshape(B, S_kv, H, dv)
    k_all = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_all[:, :, None, :],
                                  (B, S_kv, H, dr))], axis=-1)
    q_all = jnp.concatenate([q_nope, q_rope], axis=-1)
    sm = 1.0 / math.sqrt(dn + dr)

    if kv_cache is None:
        o = ops.flash_attention(q_all.transpose(0, 2, 1, 3),
                                k_all.transpose(0, 2, 1, 3),
                                v_all.transpose(0, 2, 1, 3),
                                causal=True, sm_scale=sm,
                                fused_vjp=cfg.fused_attn_vjp,
                                block_k=cfg.attn_block_k)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
        return o @ p["wo"], None
    o = ops.flash_decode(q_all[:, 0].reshape(B, H, dn + dr),
                         k_all.transpose(0, 2, 1, 3),
                         v_all.transpose(0, 2, 1, 3),
                         kv_len=jnp.full((B,), kv_len, dtype=jnp.int32),
                         sm_scale=sm)
    return (o.reshape(B, H * dv) @ p["wo"])[:, None, :], kv_cache
