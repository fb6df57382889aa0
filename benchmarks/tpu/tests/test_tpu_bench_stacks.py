"""A family whose layers lie in two stacks: a toy model with a dense
prefix (``dense_layers``) and a second stack (``layers``), each with its
own MLP width and activation, registered as ``families/toy2.py`` would
be.  The seeded weights, the reference and the counts follow its
``stacks``; the placement and a run refuse stacks that do not fit."""
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_bench_helpers as h
from benchmarks.tpu import counts, reference as ref, weights
from benchmarks.tpu.weights import Leaf

D, V, F_DENSE, F = 8, 16, 12, 20
ACTS = {"dense_layers": "sqrelu", "layers": "silu"}
CONFIG = {"family": "toy2", "n_layers": 5, "d_model": D, "vocab": V,
          "norm_eps": 1e-5, "dtype": "bfloat16"}
#: segments in layer order; "layers" in two segments continues its index
ORDERS = {"prefix": [("dense_layers", 2), ("layers", 3)],
          "split": [("layers", 1), ("dense_layers", 2), ("layers", 2)]}
SEED = 2 ** 33 + 4321


def _toy(segments):
    mod = types.ModuleType("bench_family_toy2")
    mod.SIZES = ()

    def leaves(c):
        out = []
        for stack, f in (("dense_layers", F_DENSE), ("layers", F)):
            out += [Leaf(f"{stack}/norm", (D,), weights.norm, "bfloat16",
                         True),
                    Leaf(f"{stack}/mlp/w_in", (D, f), weights.dense,
                         "bfloat16", True),
                    Leaf(f"{stack}/mlp/w_out", (f, D), weights.dense,
                         "bfloat16", True)]
        return out

    def layer(c, quant, h, w, stack):
        x = ref.rms(h, w["norm"], c["norm_eps"])
        a = ref.act(ref.mm(x, w["mlp/w_in"], quant), ACTS[stack])
        return h + ref.mm(a, w["mlp/w_out"], quant)

    mod.leaves, mod.layer = leaves, layer
    mod.stacks = lambda c: list(segments)
    return mod


@pytest.fixture
def toy(monkeypatch):
    def register(segments):
        monkeypatch.setitem(sys.modules, "bench_family_toy2",
                            _toy(segments))
    return register


def _abstract(n_dense=2, n_layers=3):
    bf = jnp.bfloat16

    def stack(n, f):
        return {"norm": jax.ShapeDtypeStruct((n, D), bf),
                "mlp": {"w_in": jax.ShapeDtypeStruct((n, D, f), bf),
                        "w_out": jax.ShapeDtypeStruct((n, f, D), bf)}}
    return {"embed": jax.ShapeDtypeStruct((V, D), bf),
            "final_norm": jax.ShapeDtypeStruct((D,), bf),
            "lm_head": jax.ShapeDtypeStruct((D, V), bf),
            "dense_layers": stack(n_dense, F_DENSE),
            "layers": stack(n_layers, F)}


def _place(ap):
    sh = jax.tree_util.tree_map(
        lambda a: jax.sharding.SingleDeviceSharding(jax.devices()[0]), ap)
    return weights.place(CONFIG, ap, sh, SEED)


def _forward(p, tokens, first, segments):
    """Plain float32 forward over the placed tree, in ``segments``'
    order."""
    def f(a):
        return np.asarray(a, np.float32)

    def rms(x, w):
        x = x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True)
                        + CONFIG["norm_eps"])
        return x * (1.0 + w)

    act = {"sqrelu": lambda x: np.square(np.maximum(x, 0.0)),
           "silu": lambda x: x / (1.0 + np.exp(-x))}
    x = f(p["embed"])[tokens]
    nxt = {"dense_layers": 0, "layers": 0}
    for stack, n in segments:
        t = p[stack]
        for i in range(nxt[stack], nxt[stack] + n):
            a = rms(x, f(t["norm"][i])) @ f(t["mlp"]["w_in"][i])
            x = x + act[ACTS[stack]](a) @ f(t["mlp"]["w_out"][i])
        nxt[stack] += n
    return (rms(x, f(p["final_norm"])) @ f(p["lm_head"]))[:, first:]


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_layer_draw_equals_the_placed_slice(toy, order):
    toy(ORDERS[order])
    p = _place(_abstract())
    words = jnp.asarray(weights.seed_words(SEED))
    draw = {s: jax.jit(lambda w, i, s=s: weights.layer(CONFIG, w, i, s))
            for s in ACTS}
    seen = 0
    for s, i in weights.layer_order(CONFIG):
        lw = draw[s](words, jnp.int32(i))
        assert sorted(lw) == ["mlp/w_in", "mlp/w_out", "norm"]
        np.testing.assert_array_equal(np.asarray(lw["norm"], np.float32),
                                      np.asarray(p[s]["norm"][i],
                                                 np.float32))
        for k in ("w_in", "w_out"):
            np.testing.assert_array_equal(
                np.asarray(lw[f"mlp/{k}"], np.float32),
                np.asarray(p[s]["mlp"][k][i], np.float32))
        seen += 1
    assert seen == CONFIG["n_layers"]


def test_layer_order_continues_a_stack_across_segments(toy):
    toy(ORDERS["split"])
    assert weights.layer_order(CONFIG) == [
        ("layers", 0), ("dense_layers", 0), ("dense_layers", 1),
        ("layers", 1), ("layers", 2)]
    assert weights.stack_counts(CONFIG) == {"layers": 3, "dense_layers": 2}


def test_a_family_without_stacks_has_one():
    cell, _ = h.tiny("minitron-4b.chat-b64")
    n = cell.config["n_layers"]
    assert weights.layer_order(cell.config) == [("layers", i)
                                                for i in range(n)]


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_reference_equals_a_plain_forward(toy, order):
    toy(ORDERS[order])
    p = _place(_abstract())
    tokens = np.random.default_rng(7).integers(0, V, size=(3, 6))
    got = np.asarray(ref.Reference(CONFIG, SEED).logits(tokens, 2))
    want = _forward(p, tokens, 2, ORDERS[order])
    assert got.shape == (3, 4, V)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_weight_bytes_count_each_stack_by_its_own_length(toy):
    toy(ORDERS["prefix"])
    # batch 4, bfloat16: embedding rows 4*8, final norm 8, head 8*16;
    # dense_layers 2 * (8 + 8*12 + 12*8), layers 3 * (8 + 8*20 + 20*8)
    hand = 2 * (4 * 8 + 8 + 8 * 16 + 2 * 200 + 3 * 328)
    assert hand == 3104
    assert counts.weight_bytes(CONFIG, 4) == hand


def test_placement_refuses_a_stack_of_another_length(toy):
    toy(ORDERS["prefix"])
    ap = _abstract(n_layers=4)
    with pytest.raises(ValueError, match="stack 'layers'"):
        weights.placer(CONFIG, ap, None)


def test_stacks_that_do_not_sum_to_n_layers_are_refused(toy):
    toy([("dense_layers", 2), ("layers", 4)])
    with pytest.raises(ValueError, match="n_layers"):
        weights.placer(CONFIG, _abstract(n_layers=4), None)
    with pytest.raises(ValueError, match="n_layers"):
        ref.Reference(CONFIG, SEED)
    with pytest.raises(ValueError, match="n_layers"):
        counts.weight_bytes(CONFIG, 4)


def test_a_run_refuses_stacks_that_do_not_sum_to_n_layers(monkeypatch):
    from benchmarks.tpu.spec import family
    cell, _ = h.tiny("minitron-4b.chat-b64")
    n = cell.config["n_layers"]
    monkeypatch.setattr(family("dense"), "stacks",
                        lambda c: [("layers", n - 1)], raising=False)
    with pytest.raises(ValueError, match="n_layers"):
        h.run_tiny("minitron-4b.chat-b64")
