"""Unified language-model stack for the assigned architectures.

One functional API across families:

    init_params(cfg, key)                 -> params pytree
    forward(cfg, params, batch)           -> logits (B, S, V)
    loss_fn(cfg, params, batch)           -> scalar CE (+ MTP aux)
    init_cache(cfg, batch, max_len)       -> decode cache pytree
    prefill(cfg, params, batch, cache)    -> (last logits, cache)
    decode_step(cfg, params, cache, tok, pos) -> (logits, cache)

Backbones:
  * ``decoder``  — dense / MoE / VLM / enc-free archs; layers stacked and
    scanned (`jax.lax.scan`), per-layer window pattern traced (gemma3 runs
    through the grouped variant below);
  * ``grouped``  — gemma3-style 5-local:1-global blocks: scan over groups
    with an inner scan over the local layers (local layers keep O(window)
    ring caches at decode — the reason gemma3 runs the 500k cell);
  * ``ssm``      — mamba2: scan over SSD blocks, O(1) decode state;
  * ``hybrid``   — zamba2: groups of SSD blocks + one *shared* attention
    block (shared weights, per-group LoRA deltas).

All parameter trees are layer-stacked so 96-layer models compile as one
rolled loop; ``remat`` wraps the per-layer body.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .attention import attention, init_attention, init_mla, mla_attention
from .config import ArchConfig
from .layers import (cross_entropy, dense_init, dtype_of, embed, embed_init,
                     fused_ce, init_embed, init_mlp, init_rms_norm,
                     lm_logits, mlp, rms_norm)
from .moe import init_moe, moe
from .sharding import maybe_shard
from .ssm import SSMState, init_ssm, init_ssm_state, ssm_block


# ==========================================================================
# Per-layer init / apply
# ==========================================================================


def _init_decoder_layer(key, cfg: ArchConfig, dtype, use_moe: bool) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "norm1": init_rms_norm(cfg.d_model, dtype),
        "norm2": init_rms_norm(cfg.d_model, dtype),
    }
    if cfg.mla:
        p["attn"] = init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = init_attention(ks[0], cfg, dtype)
    if use_moe:
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype,
                            gated=cfg.gated_mlp)
    return p


def _decoder_layer(p: Dict, h: jnp.ndarray, cfg: ArchConfig,
                   positions: jnp.ndarray,
                   window: Optional[Any] = None,
                   mrope_positions: Optional[jnp.ndarray] = None,
                   kv_cache=None, cache_pos=None, use_moe: bool = False,
                   mesh=None, moe_impl: str = "auto"):
    hn = rms_norm(p["norm1"], h, cfg.norm_eps)
    if cfg.mla:
        a, new_cache = mla_attention(p["attn"], hn, cfg, positions,
                                     kv_cache=kv_cache,
                                     cache_pos=cache_pos)
    else:
        a, new_cache = attention(p["attn"], hn, cfg, positions,
                                 window=window,
                                 mrope_positions=mrope_positions,
                                 kv_cache=kv_cache, cache_pos=cache_pos)
    h = h + a
    hn = rms_norm(p["norm2"], h, cfg.norm_eps)
    h = _residual_shard(h, cfg)
    if use_moe:
        f = moe(p["moe"], hn, cfg, mesh=mesh, impl=moe_impl)
    else:
        f = mlp(p["mlp"], hn, act=cfg.act, gated=cfg.gated_mlp)
    return h + f, new_cache


def _residual_shard(h: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Residual-stream sharding between blocks.  With sequence
    parallelism the stream lives sequence-sharded over `model` (norms,
    residual adds and the remat-saved layer stack all shrink n_model x;
    GSPMD turns the block-boundary all-reduces into reduce-scatter +
    all-gather pairs).  Falls back to replicated-over-model when the
    sequence doesn't divide the axis (decode)."""
    from .sharding import mesh_axis_size
    nm = mesh_axis_size("model")
    if cfg.seq_parallel and h.ndim == 3 and nm > 1 \
            and h.shape[1] % nm == 0:
        return maybe_shard(h, "data", "model", None)
    return maybe_shard(h, "data", None, None)


# ==========================================================================
# Pattern helpers
# ==========================================================================


def _layer_windows(cfg: ArchConfig) -> Optional[jnp.ndarray]:
    """Per-layer window (0 = full attention) for plain-decoder archs that
    mix windowed and full layers without the grouped structure."""
    if cfg.sliding_window and not cfg.local_global_ratio:
        return jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
    return None


def _moe_flags(cfg: ArchConfig) -> Tuple[int, int]:
    """(n dense prefix layers, n moe layers)."""
    if not cfg.n_experts:
        return cfg.n_layers, 0
    return cfg.moe_layer_start, cfg.n_layers - cfg.moe_layer_start


def _grouped_dims(cfg: ArchConfig) -> Tuple[int, int, int]:
    """(groups, locals-per-group, tail local layers) for gemma pattern."""
    R = cfg.local_global_ratio
    G = cfg.n_layers // (R + 1)
    tail = cfg.n_layers - G * (R + 1)
    return G, R, tail


# ==========================================================================
# Init
# ==========================================================================


def _stack_init(key, n: int, init_fn):
    """vmap an init over a leading layer axis (n may be 0 -> None)."""
    if n == 0:
        return None
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def init_params(cfg: ArchConfig, key) -> Dict:
    dtype = dtype_of(cfg.dtype)
    ks = jax.random.split(key, 12)
    p: Dict[str, Any] = {
        "embed": init_embed(ks[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": init_rms_norm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab), dtype)

    fam = cfg.family
    if fam == "ssm":
        p["layers"] = _stack_init(
            ks[2], cfg.n_layers,
            lambda k: {"norm": init_rms_norm(cfg.d_model, dtype),
                       "ssm": init_ssm(k, cfg, dtype)})
    elif fam == "hybrid":
        R = cfg.shared_attn_every
        G = cfg.n_layers // R
        p["groups"] = {
            "ssm": _stack_init(
                ks[2], G, lambda k: _stack_init(
                    k, R, lambda k2: {
                        "norm": init_rms_norm(cfg.d_model, dtype),
                        "ssm": init_ssm(k2, cfg, dtype)})),
            "lora": _stack_init(
                ks[3], G, lambda k: _init_lora(k, cfg, dtype)),
        }
        p["shared"] = _init_decoder_layer(ks[4], cfg, dtype, use_moe=False)
    elif cfg.local_global_ratio:
        G, R, tail = _grouped_dims(cfg)
        p["groups"] = {
            "local": _stack_init(
                ks[2], G, lambda k: _stack_init(
                    k, R, lambda k2: _init_decoder_layer(
                        k2, cfg, dtype, use_moe=False))),
            "global": _stack_init(
                ks[3], G, lambda k: _init_decoder_layer(
                    k, cfg, dtype, use_moe=False)),
        }
        if tail:
            p["tail"] = _stack_init(
                ks[5], tail, lambda k: _init_decoder_layer(
                    k, cfg, dtype, use_moe=False))
    elif cfg.enc_dec:
        p["enc_pos"] = embed_init(ks[6], (cfg.n_audio_frames, cfg.d_model),
                                  dtype)
        p["enc_layers"] = _stack_init(
            ks[2], cfg.n_enc_layers,
            lambda k: _init_decoder_layer(k, cfg, dtype, use_moe=False))
        p["enc_norm"] = init_rms_norm(cfg.d_model, dtype)
        p["dec_layers"] = _stack_init(
            ks[3], cfg.n_layers,
            lambda k: _init_encdec_dec_layer(k, cfg, dtype))
    else:
        n_dense, n_moe = _moe_flags(cfg)
        if n_dense:
            p["dense_layers"] = _stack_init(
                ks[2], n_dense, lambda k: _init_decoder_layer(
                    k, cfg, dtype, use_moe=False))
        if n_moe:
            p["layers"] = _stack_init(
                ks[3], n_moe, lambda k: _init_decoder_layer(
                    k, cfg, dtype, use_moe=True))
        else:
            p["layers"] = p.pop("dense_layers")
        if cfg.mtp:
            p["mtp"] = {
                "proj": dense_init(ks[7], (2 * cfg.d_model, cfg.d_model),
                                   dtype),
                "layer": _init_decoder_layer(ks[8], cfg, dtype,
                                             use_moe=bool(cfg.n_experts)),
                "norm": init_rms_norm(cfg.d_model, dtype),
            }
    return p


def _init_lora(key, cfg: ArchConfig, dtype) -> Dict:
    """Per-group LoRA deltas for the zamba2 shared block (q and mlp-in)."""
    d, r = cfg.d_model, cfg.lora_rank
    hd = cfg.padded_heads * cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "q_a": dense_init(ks[0], (d, r), dtype),
        "q_b": jnp.zeros((r, hd), dtype),
        "in_a": dense_init(ks[1], (d, r), dtype),
        "in_b": jnp.zeros((r, cfg.d_ff), dtype),
    }


def _init_encdec_dec_layer(key, cfg: ArchConfig, dtype) -> Dict:
    ks = jax.random.split(key, 3)
    p = _init_decoder_layer(ks[0], cfg, dtype, use_moe=False)
    p["xattn"] = init_attention(ks[1], cfg, dtype)
    p["norm3"] = init_rms_norm(cfg.d_model, dtype)
    return p


# ==========================================================================
# Forward (full sequence: training / prefill body)
# ==========================================================================


def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _embed_inputs(cfg: ArchConfig, params: Dict, batch: Dict
                  ) -> jnp.ndarray:
    h = embed(params["embed"], batch["tokens"])
    if cfg.family == "vlm" and "vision_embed" in batch:
        ve = batch["vision_embed"].astype(h.dtype)
        h = jax.lax.dynamic_update_slice(h, ve, (0, 0, 0))
    return h


def _mrope_pos(cfg: ArchConfig, positions: jnp.ndarray
               ) -> Optional[jnp.ndarray]:
    if not cfg.mrope:
        return None
    return jnp.broadcast_to(positions[None], (3,) + positions.shape)


def forward_hidden(cfg: ArchConfig, params: Dict, batch: Dict
                   ) -> jnp.ndarray:
    """Full-sequence forward -> final-norm hidden states (B, S, d)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = _embed_inputs(cfg, params, batch)
    h = maybe_shard(h, ("pod", "data"), None, None)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mropep = _mrope_pos(cfg, positions)
    fam = cfg.family

    if fam == "ssm":
        def body(hc, lp):
            y, _ = ssm_block(lp["ssm"],
                             rms_norm(lp["norm"], hc, cfg.norm_eps), cfg)
            return hc + y, None
        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["layers"])
    elif fam == "hybrid":
        h = _zamba_forward(cfg, params, h, positions)
    elif cfg.local_global_ratio:
        h = _gemma_forward(cfg, params, h, positions)
    elif cfg.enc_dec:
        h = _encdec_forward(cfg, params, h, positions, batch)
    else:
        n_dense, n_moe = _moe_flags(cfg)
        if "dense_layers" in params and n_moe:
            def body_d(hc, lp):
                hn, _ = _decoder_layer(lp, hc, cfg, positions,
                                       mrope_positions=mropep,
                                       use_moe=False)
                return hn, None
            h, _ = jax.lax.scan(_maybe_remat(body_d, cfg), h,
                                params["dense_layers"])

        def body(hc, lp):
            hn, _ = _decoder_layer(lp, hc, cfg, positions,
                                   mrope_positions=mropep,
                                   use_moe=bool(n_moe))
            return hn, None
        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["layers"])

    return rms_norm(params["final_norm"], h, cfg.norm_eps)


def forward(cfg: ArchConfig, params: Dict, batch: Dict) -> jnp.ndarray:
    """Full-sequence forward -> logits (B, S, V)."""
    h = forward_hidden(cfg, params, batch)
    head = params.get("lm_head", params["embed"])
    logits = lm_logits(head, h)
    return maybe_shard(logits, ("pod", "data"), None, "model")


def _gemma_forward(cfg: ArchConfig, params: Dict, h, positions):
    W = cfg.sliding_window

    def local_body(hc, lp):
        hn, _ = _decoder_layer(lp, hc, cfg, positions, window=W)
        return hn, None

    def group_body(hc, gp):
        hc, _ = jax.lax.scan(_maybe_remat(local_body, cfg), hc,
                             gp["local"])
        hn, _ = _decoder_layer(gp["global"], hc, cfg, positions,
                               window=0)      # 0 sentinel: full attention
        return hn, None

    h, _ = jax.lax.scan(group_body, h, params["groups"])
    if "tail" in params:
        def tail_body(hc, lp):
            hn, _ = _decoder_layer(lp, hc, cfg, positions, window=W)
            return hn, None
        h, _ = jax.lax.scan(_maybe_remat(tail_body, cfg), h,
                            params["tail"])
    return h


def _lora_apply(shared: Dict, lora: Dict) -> Dict:
    """Shared block weights + this group's LoRA deltas."""
    p = dict(shared)
    attn = dict(shared["attn"])
    attn["wq"] = attn["wq"] + lora["q_a"] @ lora["q_b"]
    p["attn"] = attn
    mlpp = dict(shared["mlp"])
    mlpp["w_in"] = mlpp["w_in"] + lora["in_a"] @ lora["in_b"]
    p["mlp"] = mlpp
    return p


def _zamba_forward(cfg: ArchConfig, params: Dict, h, positions):
    h0 = h  # original embeddings feed the shared block (zamba concat ~ add)

    def ssm_body(hc, lp):
        y, _ = ssm_block(lp["ssm"],
                         rms_norm(lp["norm"], hc, cfg.norm_eps), cfg)
        return hc + y, None

    def group_body(hc, gp):
        hc, _ = jax.lax.scan(_maybe_remat(ssm_body, cfg), hc, gp["ssm"])
        sp = _lora_apply(params["shared"], gp["lora"])
        hn, _ = _decoder_layer(sp, hc + h0, cfg, positions)
        return hn, None

    h, _ = jax.lax.scan(group_body, h, params["groups"])
    return h


def _encdec_forward(cfg: ArchConfig, params: Dict, h, positions, batch):
    enc = batch["audio_embed"].astype(h.dtype) + params["enc_pos"]
    Be, Se = enc.shape[:2]
    enc_pos = jnp.broadcast_to(jnp.arange(Se)[None], (Be, Se))

    def enc_body(hc, lp):
        hn = rms_norm(lp["norm1"], hc, cfg.norm_eps)
        a = _bidir_attention(lp["attn"], hn, cfg, enc_pos)
        hc = hc + a
        hn = rms_norm(lp["norm2"], hc, cfg.norm_eps)
        return hc + mlp(lp["mlp"], hn, act=cfg.act,
                        gated=cfg.gated_mlp), None

    enc, _ = jax.lax.scan(_maybe_remat(enc_body, cfg), enc,
                          params["enc_layers"])
    enc = rms_norm(params["enc_norm"], enc, cfg.norm_eps)

    def dec_body(hc, lp):
        hn = rms_norm(lp["norm1"], hc, cfg.norm_eps)
        a, _ = attention(lp["attn"], hn, cfg, positions)
        hc = hc + a
        hn = rms_norm(lp["norm3"], hc, cfg.norm_eps)
        x = _cross_attention(lp["xattn"], hn, enc, cfg)
        hc = hc + x
        hn = rms_norm(lp["norm2"], hc, cfg.norm_eps)
        return hc + mlp(lp["mlp"], hn, act=cfg.act,
                        gated=cfg.gated_mlp), None

    h, _ = jax.lax.scan(_maybe_remat(dec_body, cfg), h,
                        params["dec_layers"])
    return h


def _bidir_attention(p: Dict, x, cfg: ArchConfig, positions):
    from .attention import _expand_kv, _mask_padded
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hp = cfg.padded_heads
    q = (x @ p["wq"]).reshape(B, S, Hp, hd)
    k = (x @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (x @ p["wv"]).reshape(B, S, Hkv, hd)
    if Hp != H:
        k = _expand_kv(k, H, Hkv, Hp)
        v = _expand_kv(v, H, Hkv, Hp)
    o = ops.flash_attention(q.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3),
                            causal=False, fused_vjp=cfg.fused_attn_vjp,
                            block_k=cfg.attn_block_k)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, Hp * hd)
    return _mask_padded(o, H, Hp, hd) @ p["wo"]


def _cross_attention(p: Dict, x, enc, cfg: ArchConfig,
                     kv: Optional[Tuple] = None):
    from .attention import _expand_kv, _mask_padded
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hp = cfg.padded_heads
    q = (x @ p["wq"]).reshape(B, S, Hp, hd).transpose(0, 2, 1, 3)
    if kv is None:
        Se = enc.shape[1]
        k = (enc @ p["wk"]).reshape(B, Se, Hkv, hd).transpose(0, 2, 1, 3)
        v = (enc @ p["wv"]).reshape(B, Se, Hkv, hd).transpose(0, 2, 1, 3)
    else:
        k, v = kv
    if Hp != H:
        kx = _expand_kv(k.transpose(0, 2, 1, 3), H, Hkv, Hp)
        vx = _expand_kv(v.transpose(0, 2, 1, 3), H, Hkv, Hp)
        k, v = kx.transpose(0, 2, 1, 3), vx.transpose(0, 2, 1, 3)
    o = ops.flash_attention(q, k, v, causal=False,
                            fused_vjp=cfg.fused_attn_vjp,
                            block_k=cfg.attn_block_k)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, Hp * hd)
    return _mask_padded(o, H, Hp, hd) @ p["wo"]


# ==========================================================================
# Loss
# ==========================================================================


def _head_matrix(cfg: ArchConfig, params: Dict) -> jnp.ndarray:
    head = params.get("lm_head", params["embed"])
    return head if head.shape[0] == cfg.d_model else head.T


def loss_fn(cfg: ArchConfig, params: Dict, batch: Dict) -> jnp.ndarray:
    if cfg.fused_ce_loss:
        h = forward_hidden(cfg, params, batch)
        w = _head_matrix(cfg, params)
        loss = fused_ce(h[:, :-1], w, batch["labels"][:, 1:],
                        cfg.ce_chunk)
    else:
        logits = forward(cfg, params, batch)
        loss = cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
    if cfg.mtp and "mtp" in params:
        loss = loss + 0.3 * _mtp_loss(cfg, params, batch)
    return loss


def _mtp_loss(cfg: ArchConfig, params: Dict, batch: Dict
              ) -> jnp.ndarray:
    """DeepSeek-V3 multi-token prediction: one extra block predicting
    token t+2 from [h_t ; emb(tok_{t+1})]."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = embed(params["embed"], tokens)
    nxt = embed(params["embed"], jnp.roll(tokens, -1, axis=1))
    hh = jnp.concatenate([h, nxt], axis=-1) @ params["mtp"]["proj"]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    hh, _ = _decoder_layer(params["mtp"]["layer"], hh, cfg, positions,
                           use_moe=bool(cfg.n_experts))
    hh = rms_norm(params["mtp"]["norm"], hh, cfg.norm_eps)
    if cfg.fused_ce_loss:
        w = _head_matrix(cfg, params)
        return fused_ce(hh[:, :-2], w, batch["labels"][:, 2:],
                        cfg.ce_chunk)
    lg = lm_logits(params.get("lm_head", params["embed"]), hh)
    return cross_entropy(lg[:, :-2], batch["labels"][:, 2:])


# ==========================================================================
# Decode caches
# ==========================================================================


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Dict:
    dtype = dtype_of(cfg.dtype)
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    fam = cfg.family

    def kv(n, S):
        return {"k": jnp.zeros((n, batch, Hkv, S, hd), dtype),
                "v": jnp.zeros((n, batch, Hkv, S, hd), dtype)}

    if fam == "ssm":
        st = init_ssm_state(cfg, batch, dtype)
        return {"ssm": jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x[None], (cfg.n_layers,) + x.shape), st)}
    if fam == "hybrid":
        R = cfg.shared_attn_every
        G = cfg.n_layers // R
        st = init_ssm_state(cfg, batch, dtype)
        return {
            "ssm": jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None, None],
                                           (G, R) + x.shape), st),
            "shared": kv(G, max_len),
        }
    if cfg.local_global_ratio:
        G, R, tail = _grouped_dims(cfg)
        W = min(cfg.sliding_window, max_len)
        c = {"local": kv(G * R, W), "global": kv(G, max_len)}
        c["local"] = jax.tree_util.tree_map(
            lambda x: x.reshape((G, R) + x.shape[1:]), c["local"])
        if tail:
            c["tail"] = kv(tail, W)
        return c
    if cfg.enc_dec:
        return {"self": kv(cfg.n_layers, max_len), "cross": None}
    if cfg.mla:
        width = cfg.kv_lora_rank + cfg.d_rope
        n_dense, n_moe = _moe_flags(cfg)
        c = {"latent": jnp.zeros((n_moe or cfg.n_layers, batch, max_len,
                                  width), dtype)}
        if n_dense and n_moe:
            c["latent_dense"] = jnp.zeros((n_dense, batch, max_len, width),
                                          dtype)
        return c
    n_dense, n_moe = _moe_flags(cfg)
    c = {"kv": kv(n_moe or cfg.n_layers, max_len)}
    if n_dense and n_moe:
        c["kv_dense"] = kv(n_dense, max_len)
    return c


# ==========================================================================
# Decode step
# ==========================================================================


def decode_step(cfg: ArchConfig, params: Dict, cache: Dict,
                token: jnp.ndarray, pos: jnp.ndarray,
                aux: Optional[Dict] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """token (B,) int32; pos scalar int32.  Returns (logits (B,V), cache).
    `aux` carries encoder states (whisper) / vision embeds when needed."""
    B = token.shape[0]
    h = embed(params["embed"], token[:, None])
    if cfg.family == "vlm" and aux is not None and \
            "vision_embed" in aux:
        ve = aux["vision_embed"]                  # (B, Nv, d)
        idx = jnp.minimum(pos, ve.shape[1] - 1)
        vis = jax.lax.dynamic_slice_in_dim(ve, idx, 1, axis=1)
        h = jnp.where(pos < ve.shape[1], vis.astype(h.dtype), h)
    positions = jnp.broadcast_to(pos[None, None], (B, 1))
    fam = cfg.family
    new_cache = dict(cache)

    if fam == "ssm":
        def body(hc, xs):
            lp, st = xs
            y, st2 = ssm_block(lp["ssm"],
                               rms_norm(lp["norm"], hc, cfg.norm_eps),
                               cfg, state=st)
            return hc + y, st2
        h, new_ssm = jax.lax.scan(body, h, (params["layers"],
                                            cache["ssm"]))
        new_cache["ssm"] = new_ssm
    elif fam == "hybrid":
        h, new_cache = _zamba_decode(cfg, params, cache, h, positions,
                                     pos, token)
    elif cfg.local_global_ratio:
        h, new_cache = _gemma_decode(cfg, params, cache, h, positions,
                                     pos)
    elif cfg.enc_dec:
        h, new_cache = _encdec_decode(cfg, params, cache, h, positions,
                                      pos, aux)
    elif cfg.mla:
        def body(hc, xs):
            lp, lat = xs
            hn, lat2 = _decoder_layer(lp, hc, cfg, positions,
                                      kv_cache=lat, cache_pos=pos,
                                      use_moe=bool(cfg.n_experts))
            return hn, lat2
        if "latent_dense" in cache:
            def body_d(hc, xs):
                lp, lat = xs
                hn, lat2 = _decoder_layer(lp, hc, cfg, positions,
                                          kv_cache=lat, cache_pos=pos,
                                          use_moe=False)
                return hn, lat2
            h, nd = jax.lax.scan(body_d, h, (params["dense_layers"],
                                             cache["latent_dense"]))
            new_cache["latent_dense"] = nd
        h, nl = jax.lax.scan(body, h, (params["layers"], cache["latent"]))
        new_cache["latent"] = nl
    else:
        n_dense, n_moe = _moe_flags(cfg)

        if "kv_dense" in cache:
            def body_d(hc, xs):
                lp, ck, cv = xs
                hn, kv2 = _decoder_layer(lp, hc, cfg, positions,
                                         kv_cache=(ck, cv),
                                         cache_pos=pos, use_moe=False)
                return hn, kv2
            h, (nk, nv) = jax.lax.scan(
                body_d, h, (params["dense_layers"],
                            cache["kv_dense"]["k"],
                            cache["kv_dense"]["v"]))
            new_cache["kv_dense"] = {"k": nk, "v": nv}

        def body(hc, xs):
            lp, ck, cv = xs
            hn, kv2 = _decoder_layer(lp, hc, cfg, positions,
                                     kv_cache=(ck, cv), cache_pos=pos,
                                     use_moe=bool(n_moe))
            return hn, kv2
        h, (nk, nv) = jax.lax.scan(body, h, (params["layers"],
                                             cache["kv"]["k"],
                                             cache["kv"]["v"]))
        new_cache["kv"] = {"k": nk, "v": nv}

    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    head = params.get("lm_head", params["embed"])
    logits = lm_logits(head, h)[:, 0]
    return logits, new_cache


def _gemma_decode(cfg: ArchConfig, params, cache, h, positions, pos):
    from .attention import decode_windowed
    W = cfg.sliding_window
    new_cache = dict(cache)

    def local_body(hc, xs):
        lp, ck, cv = xs
        hn, kv2 = decode_windowed(lp["attn"],
                                  rms_norm(lp["norm1"], hc, cfg.norm_eps),
                                  cfg, (ck, cv), pos, W)
        hc = hc + hn
        hn2 = rms_norm(lp["norm2"], hc, cfg.norm_eps)
        return hc + mlp(lp["mlp"], hn2, act=cfg.act,
                        gated=cfg.gated_mlp), kv2

    def group_body(hc, xs):
        gp, lk, lv, gk, gv = xs
        hc, lkv = jax.lax.scan(local_body, hc, (gp["local"], lk, lv))
        hn, gkv = _decoder_layer(gp["global"], hc, cfg, positions,
                                 kv_cache=(gk, gv), cache_pos=pos)
        return hn, (lkv, gkv)

    h, (lkv, gkv) = jax.lax.scan(
        group_body, h,
        (params["groups"], cache["local"]["k"], cache["local"]["v"],
         cache["global"]["k"], cache["global"]["v"]))
    new_cache["local"] = {"k": lkv[0], "v": lkv[1]}
    new_cache["global"] = {"k": gkv[0], "v": gkv[1]}
    if "tail" in params:
        def tail_body(hc, xs):
            return local_body(hc, xs)
        h, tkv = jax.lax.scan(tail_body, h,
                              (params["tail"], cache["tail"]["k"],
                               cache["tail"]["v"]))
        new_cache["tail"] = {"k": tkv[0], "v": tkv[1]}
    return h, new_cache


def _zamba_decode(cfg: ArchConfig, params, cache, h, positions, pos,
                  token):
    h0 = h
    new_cache = dict(cache)

    def ssm_body(hc, xs):
        lp, st = xs
        y, st2 = ssm_block(lp["ssm"],
                           rms_norm(lp["norm"], hc, cfg.norm_eps),
                           cfg, state=st)
        return hc + y, st2

    def group_body(hc, xs):
        gp, st, ck, cv = xs
        hc, st2 = jax.lax.scan(ssm_body, hc, (gp["ssm"], st))
        sp = _lora_apply(params["shared"], gp["lora"])
        hn, kv2 = _decoder_layer(sp, hc + h0, cfg, positions,
                                 kv_cache=(ck, cv), cache_pos=pos)
        return hn, (st2, kv2)

    h, (st2, kv2) = jax.lax.scan(
        group_body, h,
        (params["groups"], cache["ssm"], cache["shared"]["k"],
         cache["shared"]["v"]))
    new_cache["ssm"] = st2
    new_cache["shared"] = {"k": kv2[0], "v": kv2[1]}
    return h, new_cache


def _encdec_decode(cfg: ArchConfig, params, cache, h, positions, pos,
                   aux):
    enc = aux["enc_states"]
    cross_kv = aux.get("cross_kv")
    new_cache = dict(cache)

    def body(hc, xs):
        lp, ck, cv, xk, xv = xs
        hn = rms_norm(lp["norm1"], hc, cfg.norm_eps)
        a, kv2 = attention(lp["attn"], hn, cfg, positions,
                           kv_cache=(ck, cv), cache_pos=pos)
        hc = hc + a
        hn = rms_norm(lp["norm3"], hc, cfg.norm_eps)
        x = _cross_attention(lp["xattn"], hn, enc, cfg, kv=(xk, xv))
        hc = hc + x
        hn = rms_norm(lp["norm2"], hc, cfg.norm_eps)
        return hc + mlp(lp["mlp"], hn, act=cfg.act,
                        gated=cfg.gated_mlp), kv2

    h, kv2 = jax.lax.scan(body, h, (params["dec_layers"],
                                    cache["self"]["k"],
                                    cache["self"]["v"],
                                    cross_kv["k"], cross_kv["v"]))
    new_cache["self"] = {"k": kv2[0], "v": kv2[1]}
    return h, new_cache


# ==========================================================================
# Prefill (fill the cache from a full prompt; returns last-token logits)
# ==========================================================================


def prefill(cfg: ArchConfig, params: Dict, batch: Dict
            ) -> jnp.ndarray:
    """Prompt processing: full-sequence forward returning last-position
    logits.  (Cache population on TPU reuses the same compute — the
    roofline of the prefill cell is this lowering.)"""
    logits = forward(cfg, params, batch)
    return logits[:, -1]


def encode_audio(cfg: ArchConfig, params: Dict, audio_embed: jnp.ndarray
                 ) -> jnp.ndarray:
    """Whisper encoder only (for decode aux)."""
    enc = audio_embed.astype(dtype_of(cfg.dtype)) + params["enc_pos"]
    Be, Se = enc.shape[:2]
    enc_pos = jnp.broadcast_to(jnp.arange(Se)[None], (Be, Se))

    def enc_body(hc, lp):
        hn = rms_norm(lp["norm1"], hc, cfg.norm_eps)
        a = _bidir_attention(lp["attn"], hn, cfg, enc_pos)
        hc = hc + a
        hn = rms_norm(lp["norm2"], hc, cfg.norm_eps)
        return hc + mlp(lp["mlp"], hn, act=cfg.act,
                        gated=cfg.gated_mlp), None

    enc, _ = jax.lax.scan(enc_body, enc, params["enc_layers"])
    return rms_norm(params["enc_norm"], enc, cfg.norm_eps)


def cross_kv(cfg: ArchConfig, params: Dict, enc: jnp.ndarray) -> Dict:
    """Per-decoder-layer cross-attention K/V from encoder states."""
    B, Se, _ = enc.shape
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim

    def one(lp):
        k = (enc @ lp["xattn"]["wk"]).reshape(B, Se, Hkv, hd)
        v = (enc @ lp["xattn"]["wv"]).reshape(B, Se, Hkv, hd)
        return {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}

    return jax.vmap(one)(params["dec_layers"])
