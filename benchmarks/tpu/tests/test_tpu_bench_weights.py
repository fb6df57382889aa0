"""The benchmark's weights: one layer drawn alone equals its slice of the
stacked, padded draw the program gets, on one device and sharded; and
every configuration draws the bits it always drew."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import weights


@pytest.mark.parametrize("name", h.CELLS[:2])
def test_layer_draw_equals_program_slice(name):
    from repro.models.registry import abstract_params
    cell, cfg = h.tiny(name)
    ap = abstract_params(cfg.__class__(**{**cfg.__dict__, "tp_pad": 8}))
    sh = jax.tree_util.tree_map(
        lambda a: jax.sharding.SingleDeviceSharding(jax.devices()[0]), ap)
    seed = 2 ** 33 + 12345                  # more than 32 bits
    p = weights.place(cell.config, ap, sh, seed)
    words = jnp.asarray(weights.seed_words(seed))
    g = weights.globals_(cell.config, words)
    for k in g:
        np.testing.assert_array_equal(np.asarray(g[k], np.float32),
                                      np.asarray(p[k], np.float32))
    flat = {weights._path(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(p["layers"])[0]}
    for l in range(cell.config["n_layers"]):
        lw = jax.jit(lambda w, l: weights.layer(cell.config, w, l))(
            words, jnp.int32(l))
        for k, v in lw.items():
            got = np.asarray(flat[k][l], np.float32)
            want = np.asarray(v, np.float32)
            sl = tuple(slice(0, s) for s in want.shape)
            np.testing.assert_array_equal(got[sl], want)
            pad = got.copy()
            pad[sl] = 0
            assert not pad.any()           # padded heads are zero


def test_seeds_differ_and_repeat():
    a = weights.seed_words(2 ** 31 + 5)
    b = weights.seed_words(5)
    assert a.tolist() != b.tolist()
    assert weights.seed_words(2 ** 31 + 5).tolist() == a.tolist()
    with pytest.raises(ValueError):
        weights.seed_words(-1)


def test_layout_that_differs_is_refused():
    cell, _ = h.tiny("minitron-4b.chat-b64")
    ap = {"embed": jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)}
    with pytest.raises(ValueError, match="layout"):
        weights.placer(cell.config, ap, {"embed": None})


#: the first 16 hex digits of the sha256 of each draw (``_digest``) at
#: the registry's reduced sizes from seed 2**33 + 12345, as the benchmark
#: drew them before a family could declare its layer stacks
PINNED = {
    ("minitron-4b.chat-b64", "globals"): "d848745719eeb099",
    ("minitron-4b.chat-b64", "first layer"): "4709fe08fa59d0c3",
    ("minitron-4b.chat-b64", "last layer"): "0c49499d059904db",
    ("minitron-4b.chat-b64", "placed"): "37e2b9974b3c5c8e",
    ("mamba2-370m.chat-b256", "globals"): "d848745719eeb099",
    ("mamba2-370m.chat-b256", "first layer"): "1606b62b300f1131",
    ("mamba2-370m.chat-b256", "last layer"): "8b78856ab0daceaa",
    ("mamba2-370m.chat-b256", "placed"): "d00fe2b40e018728",
    ("granite-20b.tp4.chat-b32", "globals"): "d848745719eeb099",
    ("granite-20b.tp4.chat-b32", "first layer"): "ff173ebbc0e0e69e",
    ("granite-20b.tp4.chat-b32", "last layer"): "4fa5dc60d20d6a7c",
    ("granite-20b.tp4.chat-b32", "placed"): "480f2695646ed57c",
}


def _digest(tree) -> str:
    m = hashlib.sha256()
    for k in sorted(tree):
        a = np.asarray(tree[k])
        m.update(f"{k}|{a.dtype}|{a.shape}|".encode())
        m.update(a.tobytes())
    return m.hexdigest()[:16]


@pytest.mark.parametrize("name,part", sorted(PINNED))
def test_draws_are_pinned(name, part):
    from repro.models.registry import abstract_params
    cell, cfg = h.tiny(name)
    c = cell.config
    seed = 2 ** 33 + 12345
    words = jnp.asarray(weights.seed_words(seed))
    if part == "globals":
        tree = weights.globals_(c, words)
    elif part == "placed":
        ap = abstract_params(cfg)
        sh = jax.tree_util.tree_map(
            lambda a: jax.sharding.SingleDeviceSharding(jax.devices()[0]),
            ap)
        p = weights.place(c, ap, sh, seed)
        tree = {weights._path(k): v for k, v in
                jax.tree_util.tree_flatten_with_path(p)[0]}
    else:
        assert c["n_layers"] > 1
        l = 0 if part == "first layer" else c["n_layers"] - 1
        tree = jax.jit(lambda w, l: weights.layer(c, w, l))(
            words, jnp.int32(l))
    assert _digest(tree) == PINNED[(name, part)]
