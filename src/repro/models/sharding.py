"""Sharding rules — the paper's *format selection* at pod scale.

The Neutron compiler picks per-layer between depth parallelism (split
output channels; share activations) and line parallelism (split lines;
share parameters) by estimated latency (§IV-A).  On a TPU mesh the same
two formats are tensor parallelism over the ``model`` axis (split
heads/features; activations broadcast) and data/sequence parallelism over
the ``data`` axis (split batch/tokens; parameters broadcast).  This module
holds

  * the partitioning rule set mapping every parameter in the tree to a
    PartitionSpec (depth-format on features, Megatron col/row pairing so
    consecutive matmuls need no reshard — the paper's "rotating fragment
    addressing avoids rearrangement"),
  * activation constraint helpers safe on un-meshed CPU,
  * :class:`FormatPlanner` — the latency-model-driven chooser used by the
    perf pass (depth vs line per block, switch cost = collective bytes).
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def active_mesh_axes() -> Tuple[str, ...]:
    """Axis names of the mesh active in the current jit/pjit context."""
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is None or not m.axis_names:
            return ()
        return tuple(m.axis_names)
    except Exception:  # pragma: no cover
        return ()


def mesh_axis_size(name: str) -> int:
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is None or not m.axis_names or name not in m.axis_names:
            return 1
        return int(m.shape[name])
    except Exception:  # pragma: no cover
        return 1


def maybe_shard(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint that degrades to identity when no mesh is
    active or when a referenced axis is absent (CPU unit tests)."""
    axes = active_mesh_axes()
    if not axes:
        return x

    def keep(s):
        if s is None:
            return None
        if isinstance(s, tuple):
            kept = tuple(a for a in s if a in axes)
            return kept if kept else None
        return s if s in axes else None

    clean = tuple(keep(s) for s in spec)
    try:
        return jax.lax.with_sharding_constraint(x, P(*clean))
    except Exception:  # pragma: no cover
        return x


# --------------------------------------------------------------------------
# Parameter partition rules
# --------------------------------------------------------------------------

#: rule table: regex on the param path -> spec builder(shape) -> tuple.
#: 'M' = model axis, 'F' = fsdp (data) axis, None = replicated.
_RULES = [
    # MoE experts: expert-parallel over model axis (must precede the
    # generic w_in/w_gate/w_out rules)
    (r"experts/w_(in|gate|out)$", lambda sh: ("M", "F", None)),
    (r"router$", lambda sh: (None, None)),
    # embeddings / lm head: vocab on model axis
    (r"embed$", lambda sh: ("M", "F")),
    (r"lm_head$", lambda sh: ("F", "M")),
    (r"mtp_head$", lambda sh: ("F", "M")),
    # attention: column-parallel qkv, row-parallel out
    (r"wq$|wk$|wv$|w_uq$|w_uk$|w_uv$", lambda sh: ("F", "M")),
    (r"wo$", lambda sh: ("M", "F")),
    (r"w_dq$|w_dkv$", lambda sh: ("F", None)),
    # mlp: column-parallel in/gate, row-parallel out
    (r"w_in$|w_gate$", lambda sh: ("F", "M")),
    (r"w_out$", lambda sh: ("M", "F")),
    # mamba: split the inner dim (heads) over model
    (r"ssm_in$", lambda sh: ("F", "M")),
    (r"ssm_out$", lambda sh: ("M", "F")),
    (r"conv_w$", lambda sh: (None, "M")),
    (r"(A_log|D|dt_bias)$", lambda sh: ("M",)),
    # norms / small vectors replicated
    (r".*", lambda sh: tuple(None for _ in sh)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_spec(path: str, shape: Tuple[int, ...],
               model_axis: str = "model",
               fsdp_axis: Optional[str] = None,
               stacked: bool = False) -> P:
    """Spec for one parameter.  The rule's spec is RIGHT-aligned onto the
    shape so any number of leading stack axes (layer scans, grouped
    G x R stacks) are replicated automatically."""
    base: Tuple = ()
    for pat, fn in _RULES:
        if re.search(pat, path):
            base = fn(shape)
            break
    subst = {"M": model_axis, "F": fsdp_axis, None: None}
    spec = tuple(subst.get(s, None) for s in base)
    rank = len(shape)
    if len(spec) > rank:
        spec = spec[len(spec) - rank:]
    spec = tuple(None for _ in range(rank - len(spec))) + spec
    return P(*spec)


#: default mesh axis sizes for divisibility checks (the production mesh)
DEFAULT_AXIS_SIZES = {"model": 16, "data": 16, "pod": 2}


def enforce_divisible(spec: P, shape: Tuple[int, ...],
                      axis_sizes: Optional[Dict[str, int]] = None) -> P:
    """Drop axis names from dims the mesh axis doesn't divide — pjit
    rejects explicit arg shardings with uneven dims (odd vocab sizes
    like 50280 stay replicated; head/vocab padding is the opt-in fix)."""
    sizes = axis_sizes or DEFAULT_AXIS_SIZES
    out = []
    for dim, s in zip(shape, tuple(spec) + (None,) * len(shape)):
        if s is None:
            out.append(None)
            continue
        names = s if isinstance(s, tuple) else (s,)
        total = 1
        for nm in names:
            total *= sizes.get(nm, 1)
        out.append(s if dim % total == 0 else None)
    return P(*out)


def tree_partition_specs(params: Any, model_axis: str = "model",
                         fsdp_axis: Optional[str] = None,
                         replicate_kv: bool = False,
                         replicate_q: bool = False) -> Any:
    """PartitionSpec pytree matching `params` (a pytree of arrays or
    ShapeDtypeStructs).  Anything under a 'layers'/'groups' subtree is
    treated as layer-stacked (leading scan axis).

    ``replicate_kv`` keeps wk/wv (and MQA/GQA KV caches) replicated over
    the model axis — the Neutron *broadcast-operand* format, required
    when n_kv_heads doesn't divide the TP degree (fractional-head
    sharding otherwise costs an all-reduce per attention block).
    ``replicate_q`` does the same for wq/wo when n_heads doesn't divide
    the TP degree."""

    def spec_of(path, leaf):
        ps = _path_str(path)
        stacked = bool(re.search(r"(layers|groups|tail|enc_layers|"
                                 r"dec_layers)/", ps))
        if replicate_kv and re.search(r"(wk|wv)$", ps):
            n = len(leaf.shape)
            return P(*((None,) * n))
        if replicate_q and re.search(r"(wq|wo)$", ps):
            n = len(leaf.shape)
            return P(*((None,) * n))
        spec = param_spec(ps, tuple(leaf.shape), model_axis, fsdp_axis,
                          stacked)
        return enforce_divisible(spec, tuple(leaf.shape))

    return jax.tree_util.tree_map_with_path(spec_of, params)


# --------------------------------------------------------------------------
# Format planner (depth vs line) — TPU analogue of §IV-A
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshSpec:
    n_data: int
    n_model: int
    device_kind: str                    # key of analysis.roofline.PEAKS
    n_pod: int = 1

    @property
    def peaks(self):
        from repro.analysis.roofline import peaks
        return peaks(self.device_kind)


@dataclass
class LayerShape:
    """One matmul-ish block: (tokens, d_in, d_out), bytes/elt."""
    name: str
    tokens: int
    d_in: int
    d_out: int
    bytes_per_elt: int = 2


@dataclass
class FormatChoice:
    name: str
    fmt: str                            # "depth" (TP) | "line" (SP/DP)
    t_depth: float
    t_line: float


class FormatPlanner:
    """Pick per-block depth (shard d_out over model, all-reduce partials)
    vs line (shard tokens, all-gather params) by modeled latency —
    the paper's format-selection criterion with collective bytes playing
    the role of the TCM-copy bytes."""

    def __init__(self, mesh: MeshSpec):
        self.mesh = mesh

    def block_latency(self, ls: LayerShape, fmt: str) -> float:
        m = self.mesh
        flops = 2.0 * ls.tokens * ls.d_in * ls.d_out
        if fmt == "depth":
            # TP: weights split n_model ways; activations replicated;
            # row-parallel partner needs one all-reduce of the output.
            t_compute = flops / m.n_model / m.peaks.bf16_flops
            coll = 2.0 * ls.tokens * ls.d_out * ls.bytes_per_elt \
                * (m.n_model - 1) / m.n_model
            t_coll = coll / m.peaks.ici_link_bw
        else:
            # line/SP: tokens split; params broadcast (all-gather weights)
            t_compute = flops / m.n_model / m.peaks.bf16_flops
            coll = ls.d_in * ls.d_out * ls.bytes_per_elt \
                * (m.n_model - 1) / m.n_model
            t_coll = coll / m.peaks.ici_link_bw
        w_bytes = ls.d_in * ls.d_out * ls.bytes_per_elt / m.n_model
        a_bytes = ls.tokens * (ls.d_in + ls.d_out) * ls.bytes_per_elt
        if fmt == "line":
            a_bytes /= m.n_model
        t_mem = (w_bytes + a_bytes) / m.peaks.hbm_bw
        return max(t_compute, t_mem) + t_coll

    def choose(self, ls: LayerShape) -> FormatChoice:
        td = self.block_latency(ls, "depth")
        tl = self.block_latency(ls, "line")
        return FormatChoice(ls.name, "depth" if td <= tl else "line",
                            td, tl)

    def plan(self, blocks) -> Dict[str, FormatChoice]:
        return {b.name: self.choose(b) for b in blocks}
