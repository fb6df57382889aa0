"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so
importing this module never touches jax device state — only the dry-run
entrypoint forces the 512-device host platform.

Every mesh here has ``AxisType.Auto`` axes: the model code states its
layouts as GSPMD constraints (``maybe_shard``, ``named_shardings``) and
leaves propagation to the partitioner.  ``jax.make_mesh`` would default
to ``Explicit`` axes, under which ops such as gather and repeat demand
an ``out_sharding`` at every call site.  Activate a mesh with
``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def named_shardings(mesh, tree):
    """Convert a pytree of PartitionSpec / None into NamedShardings.

    None maps to the replicated sharding, so a spec tree can mark whole
    subtrees (a missing cache kind, an absent aux input) as replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    def conv(s):
        if s is None:
            return NamedSharding(mesh, PartitionSpec())
        if isinstance(s, PartitionSpec):
            return NamedSharding(mesh, s)
        return s

    return jax.tree_util.tree_map(
        conv, tree,
        is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(n_data: int, n_model: int, n_pod: int = 1, devices=None):
    """(data, model) mesh — or (pod, data, model) — over the first
    ``n_pod * n_data * n_model`` of ``devices`` (default: all devices)."""
    if n_pod > 1:
        return _auto_mesh((n_pod, n_data, n_model),
                          ("pod", "data", "model"), devices)
    return _auto_mesh((n_data, n_model), ("data", "model"), devices)
