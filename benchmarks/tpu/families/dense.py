"""Dense decoder layers: pre-norm attention (GQA or MQA, RoPE) and MLP.

Everything about this family that the benchmark needs, found by the
configuration's ``"family": "dense"``: the sizes a configuration file
states and the program must match, the canonical weight leaves of one
layer, the reference layer, and the work of the layer stack in one
decode step.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmarks.tpu import counts, reference as ref, weights
from benchmarks.tpu.weights import Leaf

SIZES = ("n_heads", "n_kv_heads", "head_dim", "d_ff", "act", "gated_mlp",
         "rope_theta")


def leaves(c: Dict[str, Any]) -> List[Leaf]:
    d, dt = c["d_model"], c["dtype"]
    H, Hkv, hd, f = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    dense, norm = weights.dense, weights.norm
    out = [Leaf("layers/norm1", (d,), norm, dt, True),
           Leaf("layers/norm2", (d,), norm, dt, True),
           Leaf("layers/attn/wq", (d, H * hd), dense, dt, True),
           Leaf("layers/attn/wk", (d, Hkv * hd), dense, dt, True),
           Leaf("layers/attn/wv", (d, Hkv * hd), dense, dt, True),
           Leaf("layers/attn/wo", (H * hd, d), dense, dt, True),
           Leaf("layers/mlp/w_in", (d, f), dense, dt, True),
           Leaf("layers/mlp/w_out", (f, d), dense, dt, True)]
    if c["gated_mlp"]:
        out.append(Leaf("layers/mlp/w_gate", (d, f), dense, dt, True))
    return out


def layer(c, quant, h, w):
    """One layer of the reference over whole sequences h (K, T, d)."""
    K, T, _ = h.shape
    H, Hkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    x = ref.rms(h, w["norm1"], c["norm_eps"])
    q = ref.mm(x, w["attn/wq"], quant).reshape(K, T, H, hd)
    k = ref.mm(x, w["attn/wk"], quant).reshape(K, T, Hkv, hd)
    v = ref.mm(x, w["attn/wv"], quant).reshape(K, T, Hkv, hd)
    q, k = ref.rope(q, c["rope_theta"]), ref.rope(k, c["rope_theta"])
    g = H // Hkv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("kthd,kshd->khts", q, k, precision=ref.HIGHEST)
    s = s / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("khts,kshd->kthd", p, v, precision=ref.HIGHEST)
    h = h + ref.mm(o.reshape(K, T, H * hd), w["attn/wo"], quant)
    x = ref.rms(h, w["norm2"], c["norm_eps"])
    a = ref.mm(x, w["mlp/w_in"], quant)
    if c["gated_mlp"]:
        a = ref.act(ref.mm(x, w["mlp/w_gate"], quant), c["act"]) * a
    else:
        a = ref.act(a, c["act"])
    return h + ref.mm(a, w["mlp/w_out"], quant)


def step_work(c: Dict[str, Any], batch: int, pos: int) -> counts.Work:
    """The layer stack's operations in one decode step at ``pos``, and
    the bytes it moves besides its weights: the cache rows read (0..pos)
    and the new row written, K and V."""
    d, L, b = c["d_model"], c["n_layers"], counts.BYTES[c["dtype"]]
    H, Hkv, hd, f = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    mats = d * H * hd * 2 + 2 * d * Hkv * hd \
        + (3 if c["gated_mlp"] else 2) * d * f
    attn = counts.attention_kernel(c, batch, pos + 1)
    return counts.Work(flops=L * (2.0 * batch * mats + attn.flops),
                       bytes=L * 2.0 * batch * Hkv * (pos + 2) * hd * b)
