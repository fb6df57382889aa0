"""Mixture-of-Experts with expert parallelism.

Two functionally-identical dispatch implementations:

  * ``dense`` (default) — Switch/Mesh-TF style one-hot dispatch einsums
    with capacity bounding.  Under pjit + the expert-parallel parameter
    specs (experts sharded over ``model``), GSPMD slices the expert
    einsums per shard; tokens stay replicated across the model axis and
    the combine is a single cross-shard reduction.  Robust everywhere
    (CPU single-device tests included).
  * ``a2a`` — shard_map all_to_all dispatch (tokens re-shuffled to the
    devices owning their experts and back) — the production EP schedule;
    selected by the perf pass where it wins on collective bytes.

Router: softmax top-k with normalized gates (DeepSeek-V3 style sigmoid
gating optional), plus optional shared experts always active.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .config import ArchConfig
from .layers import dense_init
from .sharding import maybe_shard


def init_moe(key, cfg: ArchConfig, dtype) -> Dict:
    d = cfg.d_model
    fe = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32, scale=0.02),
        "experts": {
            "w_in": dense_init(ks[1], (E, d, fe), dtype),
            "w_gate": dense_init(ks[2], (E, d, fe), dtype),
            "w_out": dense_init(ks[3], (E, fe, d), dtype),
        },
    }
    if cfg.n_shared_experts:
        fs = fe * cfg.n_shared_experts
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_in": dense_init(ks2[0], (d, fs), dtype),
            "w_gate": dense_init(ks2[1], (d, fs), dtype),
            "w_out": dense_init(ks2[2], (fs, d), dtype),
        }
    return p


def _router_probs(p: Dict, x2d: jnp.ndarray, cfg: ArchConfig
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k gates (T, k) and expert ids (T, k)."""
    logits = x2d.astype(jnp.float32) @ p["router"]          # (T, E)
    gates, idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(gates, axis=-1)
    return gates, idx


def _dispatch_onehot(x2d, gates, idx, E: int, cap: int, dtype):
    """Mesh-TF one-hot dispatch/combine einsums.  O(T·E·cap·d) FLOPs —
    quadratic in tokens; kept as the recorded §Perf baseline."""
    T, k = idx.shape
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)        # (T, k, E)
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = (pos.reshape(T, k, E) * onehot).sum(-1)           # (T, k)
    keep = pos < cap
    gates = gates * keep
    disp = (jax.nn.one_hot(idx, E, dtype=dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=dtype)[..., None, :])[..., :cap] \
        .sum(axis=1)                                        # (T, E, cap)
    comb = (jax.nn.one_hot(idx, E, dtype=jnp.float32)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=jnp.float32)[..., None, :]
            * gates[..., None, None])[..., :cap].sum(axis=1)
    xe = jnp.einsum("td,tec->ecd", x2d, disp)

    def combine(ye):
        return jnp.einsum("ecd,tec->td", ye.astype(jnp.float32), comb)

    return xe, combine


def _dispatch_sort(x2d, gates, idx, E: int, cap: int, dtype):
    """Sort-based dispatch: stable-sort assignments by expert, derive the
    within-expert slot from segment offsets, scatter tokens into the
    (E, cap, d) buffers and gather back — O(T·k·d) data movement instead
    of O(T·E·cap·d) FLOPs.  Token-drop semantics identical to the
    one-hot path (token-major order within each expert)."""
    T, k = idx.shape
    Tk = T * k
    flat_e = idx.reshape(Tk)
    order = jnp.argsort(flat_e, stable=True)                # (Tk,)
    sorted_e = flat_e[order]
    seg_first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    slot = jnp.arange(Tk) - seg_first                       # pos in expert
    keep = slot < cap
    token = order // k
    addr = jnp.where(keep, sorted_e * cap + slot, E * cap)  # OOB drops
    xe = jnp.zeros((E * cap, x2d.shape[1]), x2d.dtype)
    xe = xe.at[addr].set(x2d[token], mode="drop",
                         unique_indices=True)
    xe = xe.reshape(E, cap, x2d.shape[1])
    gate_sorted = gates.reshape(Tk)[order]

    def combine(ye):
        ye_flat = ye.reshape(E * cap, -1).astype(jnp.float32)
        picked = ye_flat[jnp.minimum(addr, E * cap - 1)]
        picked = picked * (keep * gate_sorted)[:, None]
        y = jnp.zeros((T, ye_flat.shape[1]), jnp.float32)
        return y.at[token].add(picked)

    return xe, combine


def _dispatch(x2d, gates, idx, E, cap, dtype, method: str):
    if method == "sort":
        return _dispatch_sort(x2d, gates, idx, E, cap, dtype)
    return _dispatch_onehot(x2d, gates, idx, E, cap, dtype)


def moe_dense(p: Dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Capacity-bounded dispatch (method per cfg.moe_dispatch).
    x (B, S, d)."""
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    x2d = x.reshape(T, d)
    gates, idx = _router_probs(p, x2d, cfg)
    cap = max(1, int(math.ceil(T * k / E * cfg.capacity_factor)))
    xe, combine = _dispatch(x2d, gates, idx, E, cap, x.dtype,
                            cfg.moe_dispatch)
    xe = maybe_shard(xe, "model", None, None)
    we = p["experts"]
    h = jnp.einsum("ecd,edf->ecf", xe, we["w_in"])
    if cfg.gated_mlp:
        g = jnp.einsum("ecd,edf->ecf", xe, we["w_gate"])
        h = ops.apply_activation(g, cfg.act) * h
    else:
        h = ops.apply_activation(h, cfg.act)
    ye = jnp.einsum("ecf,efd->ecd", h, we["w_out"])
    ye = maybe_shard(ye, "model", None, None)
    y = combine(ye).astype(x.dtype)

    if cfg.n_shared_experts:
        sh = p["shared"]
        hs = x2d @ sh["w_in"]
        hs = ops.apply_activation(x2d @ sh["w_gate"], cfg.act) * hs
        y = y + hs @ sh["w_out"]
    return y.reshape(B, S, d)


def moe_a2a(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
            mesh: Optional[jax.sharding.Mesh] = None,
            model_axis: str = "model",
            data_axis: str = "data") -> jnp.ndarray:
    """shard_map EP: per-shard local dispatch (scatter/gather stay local,
    avoiding GSPMD's sharded-scatter collectives) + all_to_all of the
    (E, cap, d) buffers to the shards owning each expert and back.
    Requires E % n_model == 0.  Uses the ambient mesh when `mesh` is
    None (inside pjit/dry-run)."""
    from jax.sharding import PartitionSpec as P
    from .sharding import active_mesh_axes, mesh_axis_size

    E = cfg.n_experts
    n_model = (mesh.shape[model_axis] if mesh is not None
               else mesh_axis_size(model_axis))
    assert E % n_model == 0, (E, n_model)
    e_loc = E // n_model
    B, S, d = x.shape
    axes = active_mesh_axes() or ((data_axis, model_axis)
                                  if mesh is None else tuple(
                                      mesh.axis_names))
    data_spec = tuple(a for a in ("pod", data_axis) if a in axes) \
        or data_axis

    def local(x_blk, router, w_in, w_gate, w_out):
        # x_blk: (B_loc, S_loc, d) — tokens split over BOTH axes (the
        # sequence slice over `model` is the line format: every token is
        # dispatched exactly once fleet-wide)
        Bl, Sl = x_blk.shape[:2]
        T = Bl * Sl
        x2d = x_blk.reshape(T, d)
        logits = x2d.astype(jnp.float32) @ router
        gates, idx = jax.lax.top_k(logits, cfg.top_k)
        gates = jax.nn.softmax(gates, axis=-1)
        cap = max(1, int(math.ceil(T * cfg.top_k / E
                                   * cfg.capacity_factor)))
        xe, combine = _dispatch(x2d, gates, idx, E, cap, x.dtype,
                                cfg.moe_dispatch)
        # re-shuffle: each shard keeps its e_loc experts' buffers from all
        # shards -> (e_loc, n_model * cap, d)
        xe = xe.reshape(n_model, e_loc, cap, d)
        xe = jax.lax.all_to_all(xe, model_axis, 0, 0, tiled=False)
        xe = xe.transpose(1, 0, 2, 3).reshape(e_loc, n_model * cap, d)
        h = jnp.einsum("ecd,edf->ecf", xe, w_in)
        g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        h = ops.apply_activation(g, cfg.act) * h
        ye = jnp.einsum("ecf,efd->ecd", h, w_out)
        ye = ye.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3)
        ye = jax.lax.all_to_all(ye, model_axis, 0, 0, tiled=False)
        ye = ye.reshape(E, cap, d)
        y = combine(ye)
        return y.reshape(Bl, Sl, d).astype(x.dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(data_spec, model_axis, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=P(data_spec, model_axis, None), check_vma=False)
    # (output replication over `model` is by math — round-trip
    # all_to_all — hence replication checking is disabled)
    y = fn(x, p["router"], p["experts"]["w_in"], p["experts"]["w_gate"],
           p["experts"]["w_out"])
    if cfg.n_shared_experts:
        sh = p["shared"]
        x2d = x.reshape(-1, x.shape[-1])
        hs = x2d @ sh["w_in"]
        hs = ops.apply_activation(x2d @ sh["w_gate"], cfg.act) * hs
        y = y + (hs @ sh["w_out"]).reshape(x.shape)
    return y


def moe(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        impl: str = "auto") -> jnp.ndarray:
    """auto: shard_map a2a EP whenever a model axis is active and the
    expert count divides it (local dispatch, explicit collectives);
    dense GSPMD dispatch otherwise (single-device tests, odd counts)."""
    from .sharding import mesh_axis_size
    if impl == "a2a" and mesh is not None:
        return moe_a2a(p, x, cfg, mesh)
    if impl in ("auto", "a2a"):
        n_model = mesh_axis_size("model")
        if n_model > 1 and cfg.n_experts % n_model == 0 \
                and x.shape[1] % n_model == 0:
            return moe_a2a(p, x, cfg)
        return moe_dense(p, x, cfg)
    return moe_dense(p, x, cfg)
