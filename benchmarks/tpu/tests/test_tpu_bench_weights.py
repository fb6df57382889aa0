"""The benchmark's weights: one layer drawn alone equals its slice of the
stacked, padded draw the program gets, on one device and sharded."""
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
