"""Seeded weights, drawn by the benchmark and not by the program.

Every leaf of a model has a canonical shape, taken from the sizes in the
configuration file alone, and a rule that draws it from the seed.  The
program's step takes its parameters in its own layout; :func:`place`
draws each canonical leaf in one jitted call, straight into the
program's shardings, rounds it to the type it is served in, and pads it
with zeros where the program's layout is larger (attention heads padded
for tensor parallelism).  The reference draws the same values with
:func:`layer` and :func:`globals_`, one layer at a time, from the same
seed: it takes nothing that the program made.

A layer-stacked leaf of layer ``l`` is drawn from
``fold_in(fold_in(root, crc32(path)), l)``, so one layer can be drawn
alone and gives the same bits as the stacked draw.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .spec import family

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


@dataclass(frozen=True)
class Leaf:
    path: str                  # "layers/attn/wq": the program's tree path
    shape: Tuple[int, ...]     # canonical shape of one layer's leaf
    rule: Callable             # (key, shape) -> float32 values, see draw
    dtype: str                 # the type it is served in
    stacked: bool              # one per layer, under "layers/"


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as 31-bit words, least significant first."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = [seed & 0x7FFFFFFF]
    seed >>= 31
    while seed:
        words.append(seed & 0x7FFFFFFF)
        seed >>= 31
    return np.asarray(words + [0] * (3 - len(words)), np.uint32)


def root_key(words) -> jax.Array:
    key = jax.random.PRNGKey(0)
    for i in range(len(words)):
        key = jax.random.fold_in(key, words[i])
    return key


#: standard deviation of the sum of four independent bytes
SUM4_STD = math.sqrt(4 * (256 ** 2 - 1) / 12)


def random_bytes(key, shape):
    bits = jax.random.bits(key, shape, jnp.uint32)
    return [((bits >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4)]


def normalish(key, shape):
    """Sum of four random bytes, centred: integers of mean 0 and standard
    deviation ``SUM4_STD`` (about 147.8), exact in float32."""
    b = random_bytes(key, shape)
    return (b[0] + b[1] + b[2] + b[3] - 510).astype(jnp.float32)


# Rules shared by every family.  A family's own rules live in its file.

def dense(key, shape):
    """std 1/sqrt(fan_in)."""
    return normalish(key, shape) * (1.0 / (SUM4_STD * math.sqrt(shape[0])))


def embed(key, shape):
    """std 1."""
    return normalish(key, shape) * (1.0 / SUM4_STD)


def norm(key, shape):
    """An offset from 1, as the model applies it."""
    return normalish(key, shape) * (0.1 / SUM4_STD)


def leaves(config: Dict[str, Any]) -> List[Leaf]:
    """The canonical leaves of a configuration, from its sizes: the
    embedding, final norm and head, then the family's layer leaves
    (``families/<family>.py``)."""
    d, V = config["d_model"], config["vocab"]
    dt = config["dtype"]
    return [Leaf("embed", (V, d), embed, dt, False),
            Leaf("final_norm", (d,), norm, dt, False),
            Leaf("lm_head", (d, V), dense, dt, False),
            *family(config["family"]).leaves(config)]


def draw(leaf: Leaf, key) -> jax.Array:
    """One layer's leaf, rounded to its served type.

    Every rule gives exact integers times one constant, so any program
    that draws it (the stacked draw under ``vmap``, one layer alone, any
    fusion or sharding) gets the same bits."""
    return leaf.rule(key, leaf.shape).astype(DTYPES[leaf.dtype])


def _leaf_key(root, leaf: Leaf):
    return jax.random.fold_in(root, zlib.crc32(leaf.path.encode())
                              & 0x7FFFFFFF)


def layer(config, words, l) -> Dict[str, jax.Array]:
    """Layer ``l``'s leaves as served, keyed by path (no "layers/")."""
    root = root_key(words)
    return {lf.path[len("layers/"):]:
            draw(lf, jax.random.fold_in(_leaf_key(root, lf), l))
            for lf in leaves(config) if lf.stacked}


def globals_(config, words) -> Dict[str, jax.Array]:
    """The leaves outside the layer stack, as served."""
    root = root_key(words)
    return {lf.path: draw(lf, _leaf_key(root, lf))
            for lf in leaves(config) if not lf.stacked}


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _pad_to(x: jax.Array, shape) -> jax.Array:
    if x.shape == tuple(shape):
        return x
    if len(x.shape) != len(shape) or any(a > b for a, b in
                                         zip(x.shape, shape)):
        raise ValueError(f"canonical shape {x.shape} does not fit the "
                         f"program's {tuple(shape)}")
    return jnp.pad(x, [(0, b - a) for a, b in zip(x.shape, shape)])


def place(config, abstract_params, shardings, seed: int):
    """The program's parameter tree, drawn from ``seed`` in one jitted
    call into ``shardings``."""
    return placer(config, abstract_params, shardings)(
        jnp.asarray(seed_words(seed)))


def placer(config, abstract_params, shardings):
    """The jitted draw of the program's parameter tree from the seed's
    words.  A leaf that the program has and the configuration does not,
    or the other way round, is an error."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)
    by_path = {lf.path: lf for lf in leaves(config)}
    prog = [(_path(p), a) for p, a in flat]
    missing = set(by_path) - {p for p, _ in prog}
    extra = [p for p, _ in prog if p not in by_path]
    if missing or extra:
        raise ValueError(f"parameter layout differs from the "
                         f"configuration: missing {sorted(missing)}, "
                         f"unknown {extra}")
    for p, a in prog:
        if jnp.dtype(a.dtype) != jnp.dtype(DTYPES[by_path[p].dtype]):
            raise ValueError(f"{p}: program serves {a.dtype}, the "
                             f"configuration states {by_path[p].dtype}")

    def build(words):
        root = root_key(words)
        out = []
        for p, a in prog:
            lf = by_path[p]
            k = _leaf_key(root, lf)
            if lf.stacked:
                x = jax.vmap(lambda l: draw(lf, jax.random.fold_in(k, l)))(
                    jnp.arange(a.shape[0]))
            else:
                x = draw(lf, k)
            out.append(_pad_to(x, a.shape))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)
