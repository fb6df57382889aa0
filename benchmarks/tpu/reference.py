"""Plain reference of each configuration: float32 ``jax.numpy``, no
kernels, no cache, no batching of steps, every matmul at ``HIGHEST``.

It follows the layer equations of the configuration as the program runs
them: each family's layer is in ``families/<family>.py`` and uses the
pieces here (pre-norm RMSNorm with the weight stored as an offset from
1, rotate-half RoPE over the whole head, squared-ReLU, tanh-GELU or SiLU
activations).  ``PERF.md`` lists where these depart from the published
models.  It imports nothing of the program: weights come from
:mod:`weights`, drawn again from the seed, one layer at a time, so that
a 20-billion-parameter model fits on one chip in float32.

``quant="fp8"`` is the control: every weight matmul takes its inputs
rounded to float8 (e4m3, scaled per output column and per row), the
nearest precision below the bfloat16 that the configurations state.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import weights
from .spec import family

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w.astype(jnp.float32))


def rope(x, theta):
    """x (K, T, heads, hd), positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs   # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def act(x, act):
    if act == "sqrelu":
        return jnp.square(jnp.maximum(x, 0.0))
    if act == "gelu":                    # tanh form (GPT-BigCode, jax)
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                         * (x + 0.044715 * x ** 3)))
    if act == "silu":
        return x * jax.nn.sigmoid(x)
    raise ValueError(f"unknown activation {act!r}")


class Reference:
    """Logits of the configuration for whole sequences.

    ``logits(tokens, first)`` runs tokens (K, T) and returns float32
    logits (K, T - first, V) for positions ``first..T-1``; it runs one
    layer at a time in ``weights.layer_order``, drawing that layer's
    weights on the device and dropping them before the next.  A family
    that declares ``stacks`` gets the layer's stack as ``layer``'s last
    argument, ``stack``."""

    def __init__(self, config: Dict[str, Any], seed: int,
                 quant: Optional[str] = None):
        self.c = config
        self.words = jnp.asarray(weights.seed_words(seed))
        self.quant = quant
        fam = family(config["family"])
        self._layer_w, self._layer = {}, {}
        for s in weights.stack_counts(config):
            own = {"stack": s} if hasattr(fam, "stacks") else {}
            self._layer_w[s] = jax.jit(partial(weights.layer, config,
                                               stack=s))
            self._layer[s] = jax.jit(partial(fam.layer, config, quant,
                                             **own))

    def logits(self, tokens: np.ndarray, first: int) -> jax.Array:
        c = self.c
        g = jax.jit(partial(weights.globals_, c))(self.words)
        h = jnp.take(g["embed"], jnp.asarray(tokens),
                     axis=0).astype(jnp.float32)
        head, norm = g["lm_head"], g["final_norm"]
        del g
        for s, i in weights.layer_order(c):
            w = self._layer_w[s](self.words, jnp.int32(i))
            h = self._layer[s](h, w)
            del w
        return jax.jit(self._head)(h[:, first:], norm, head)

    def _head(self, h, norm, head):
        return mm(rms(h, norm, self.c["norm_eps"]), head, self.quant)


def gaps(ref_logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """How far each token's reference logit lies below the reference's
    best at its position: (K, G)."""
    best = ref_logits.max(axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return best - got
