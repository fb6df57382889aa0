"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (the kernel body executes on CPU; each call asks for the
interpreter explicitly)."""
import jax
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("op,args", [
    ("neutron_matmul", ((8, 16), (16, 8))),
    ("flash_attention", ((1, 2, 8, 16), (1, 2, 8, 16), (1, 2, 8, 16))),
    ("flash_decode", ((1, 2, 16), (1, 2, 8, 16), (1, 2, 8, 16))),
    ("ssd_scan", ((1, 8, 2, 4), (1, 8, 2), (2,), (1, 8, 4), (1, 8, 4))),
])
def test_pallas_off_tpu_needs_explicit_interpret(op, args):
    """Off the TPU, ``auto`` takes the jnp path and ``pallas`` without
    ``interpret=True`` is refused, never interpreted in silence."""
    arrays = [np.ones(s, np.float32) for s in args]
    fn = getattr(ops, op)
    with pytest.raises(RuntimeError, match="interpret=True"):
        fn(*arrays, impl="pallas")
    want = fn(*arrays, impl="ref")
    for g, w in zip(*(jax.tree_util.tree_leaves(o)
                      for o in (fn(*arrays), want))):
        np.testing.assert_array_equal(g, w)


# --------------------------------------------------------------------------
# neutron_matmul
# --------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (100, 300, 70),
                                   (128, 512, 128), (33, 65, 129)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_neutron_matmul_shapes(m, k, n, dtype):
    x = RNG.normal(size=(m, k)).astype(dtype)
    w = RNG.normal(size=(k, n)).astype(dtype)
    got = ops.neutron_matmul(x, w, impl="pallas", interpret=True)
    want = ops.neutron_matmul(x, w, impl="ref")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2 if dtype == "bfloat16" else 2e-3,
                               rtol=3e-2 if dtype == "bfloat16" else 1e-3)


@pytest.mark.parametrize("act", ["none", "relu", "relu6", "silu", "gelu",
                                 "sqrelu", "mish", "sigmoid"])
def test_neutron_matmul_activations(act):
    x = RNG.normal(size=(32, 64)).astype(np.float32)
    w = RNG.normal(size=(64, 48)).astype(np.float32)
    b = RNG.normal(size=(48,)).astype(np.float32)
    got = ops.neutron_matmul(x, w, bias=b, act=act, impl="pallas",
                             interpret=True)
    want = ops.neutron_matmul(x, w, bias=b, act=act, impl="ref")
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


def test_neutron_matmul_int8_requant_bit_exact():
    x = RNG.integers(-128, 128, size=(64, 256)).astype(np.int8)
    w = RNG.integers(-128, 128, size=(256, 96)).astype(np.int8)
    got = ops.neutron_matmul(x, w, scale=np.float32(0.02), act="relu",
                             out_scale=0.7, impl="pallas", interpret=True)
    want = ops.neutron_matmul(x, w, scale=np.float32(0.02), act="relu",
                              out_scale=0.7, impl="ref")
    assert got.dtype == np.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_neutron_matmul_per_channel_scale():
    x = RNG.integers(-64, 64, size=(16, 128)).astype(np.int8)
    w = RNG.integers(-64, 64, size=(128, 32)).astype(np.int8)
    sc = RNG.uniform(0.001, 0.1, size=(32,)).astype(np.float32)
    got = ops.neutron_matmul(x, w, scale=sc, impl="pallas", interpret=True)
    want = ops.neutron_matmul(x, w, scale=sc, impl="ref")
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-4)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Hkv,S,D", [
    (1, 1, 1, 16, 8), (2, 4, 2, 100, 32), (2, 8, 1, 64, 16),
    (1, 6, 3, 77, 64),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, Hkv, S, D, causal):
    q = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    k = RNG.normal(size=(B, Hkv, S, D)).astype(np.float32)
    v = RNG.normal(size=(B, Hkv, S, D)).astype(np.float32)
    got = ops.flash_attention(q, k, v, causal=causal, impl="pallas",
                              interpret=True, block_q=32, block_k=32)
    want = ops.flash_attention(q, k, v, causal=causal, impl="ref")
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("window", [1, 7, 64])
def test_flash_attention_sliding_window(window):
    B, H, S, D = 2, 2, 90, 16
    q = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    k = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    v = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    got = ops.flash_attention(q, k, v, window=window, impl="pallas",
                              interpret=True, block_q=32, block_k=32)
    want = ref.attention_naive(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


def test_flash_attention_mla_head_dims():
    # MLA: value head dim differs from qk head dim
    B, H, S, Dqk, Dv = 2, 4, 48, 24, 16
    q = RNG.normal(size=(B, H, S, Dqk)).astype(np.float32)
    k = RNG.normal(size=(B, H, S, Dqk)).astype(np.float32)
    v = RNG.normal(size=(B, H, S, Dv)).astype(np.float32)
    got = ops.flash_attention(q, k, v, impl="pallas", interpret=True,
                              block_q=16, block_k=16)
    want = ops.flash_attention(q, k, v, impl="ref")
    assert got.shape == (B, H, S, Dv)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)


def test_flash_attention_bf16():
    B, H, S, D = 1, 2, 64, 32
    q = RNG.normal(size=(B, H, S, D)).astype("bfloat16")
    k = RNG.normal(size=(B, H, S, D)).astype("bfloat16")
    v = RNG.normal(size=(B, H, S, D)).astype("bfloat16")
    got = ops.flash_attention(q, k, v, impl="pallas", interpret=True,
                              block_q=32, block_k=32)
    want = ops.flash_attention(q, k, v, impl="ref")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_flash_fused_vjp_grads():
    import jax
    import jax.numpy as jnp
    B, H, S, D = 2, 2, 40, 16
    q = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    k = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    v = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    do = RNG.normal(size=(B, H, S, D)).astype(np.float32)

    def f_fused(q, k, v):
        return (ops.flash_attention(q, k, v, impl="ref", fused_vjp=True,
                                    block_k=16) * do).sum()

    def f_exact(q, k, v):
        return (ref.attention_naive(q, k, v) * do).sum()

    g1 = jax.grad(f_fused, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_exact, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=1e-3)


# --------------------------------------------------------------------------
# flash decode
# --------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Hkv,S,D", [
    (1, 1, 1, 32, 8), (3, 4, 2, 200, 32), (2, 8, 8, 128, 64),
])
def test_flash_decode_sweep(B, H, Hkv, S, D):
    q = RNG.normal(size=(B, H, D)).astype(np.float32)
    k = RNG.normal(size=(B, Hkv, S, D)).astype(np.float32)
    v = RNG.normal(size=(B, Hkv, S, D)).astype(np.float32)
    kvl = RNG.integers(1, S + 1, size=(B,)).astype(np.int32)
    got, lg = ops.flash_decode(q, k, v, kv_len=kvl, return_lse=True,
                               impl="pallas", interpret=True, block_k=64)
    want, lw = ops.flash_decode(q, k, v, kv_len=kvl, return_lse=True,
                                impl="ref")
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(lg, lw, atol=2e-3, rtol=1e-3)


def test_decode_shard_combine_exact():
    """Sequence-sharded decode: combining per-shard partials via LSE must
    equal the unsharded result (the long_500k mechanism)."""
    B, H, S, D = 2, 4, 96, 16
    q = RNG.normal(size=(B, H, D)).astype(np.float32)
    k = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    v = RNG.normal(size=(B, H, S, D)).astype(np.float32)
    full = ops.flash_decode(q, k, v, impl="ref")
    n_shards = 4
    outs, lses = [], []
    for i in range(n_shards):
        ks = k[:, :, i * S // n_shards:(i + 1) * S // n_shards]
        vs = v[:, :, i * S // n_shards:(i + 1) * S // n_shards]
        o, l = ops.flash_decode(q, ks, vs, return_lse=True, impl="ref")
        outs.append(o)
        lses.append(l)
    combined = ops.combine_decode_shards(np.stack(outs), np.stack(lses))
    np.testing.assert_allclose(combined, full, atol=2e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 32, 1, 8, 4, 8), (2, 128, 3, 16, 8, 32), (2, 100, 2, 32, 16, 32),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    x = RNG.normal(size=(B, S, H, P)).astype(np.float32)
    dt = RNG.uniform(0.001, 0.1, size=(B, S, H)).astype(np.float32)
    A = -RNG.uniform(0.5, 2.0, size=(H,)).astype(np.float32)
    Bm = RNG.normal(size=(B, S, N)).astype(np.float32)
    Cm = RNG.normal(size=(B, S, N)).astype(np.float32)
    yg, sg = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, impl="pallas",
                          interpret=True)
    yw, sw = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, impl="ref")
    np.testing.assert_allclose(yg, yw, atol=2e-3, rtol=1e-3)
    np.testing.assert_allclose(sg, sw, atol=2e-3, rtol=1e-3)


def test_ssd_chunked_equals_stepwise():
    """Chunked scan == token-by-token recurrence (train/decode parity)."""
    B, S, H, P, N = 2, 48, 2, 8, 8
    x = RNG.normal(size=(B, S, H, P)).astype(np.float32)
    dt = RNG.uniform(0.001, 0.2, size=(B, S, H)).astype(np.float32)
    A = -RNG.uniform(0.2, 1.5, size=(H,)).astype(np.float32)
    Bm = RNG.normal(size=(B, S, N)).astype(np.float32)
    Cm = RNG.normal(size=(B, S, N)).astype(np.float32)
    y, s_final = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=16, impl="ref")
    state = np.zeros((B, H, P, N), np.float32)
    ys = []
    for t in range(S):
        yt, state = ops.ssd_step(state, x[:, t], dt[:, t], A,
                                 Bm[:, t], Cm[:, t])
        ys.append(np.asarray(yt))
    np.testing.assert_allclose(np.stack(ys, 1), y, atol=5e-3, rtol=1e-2)
    np.testing.assert_allclose(state, s_final, atol=5e-3, rtol=1e-2)


def test_ssd_chunk_invariance():
    """Result must not depend on the chunk size (property)."""
    B, S, H, P, N = 1, 64, 2, 8, 4
    x = RNG.normal(size=(B, S, H, P)).astype(np.float32)
    dt = RNG.uniform(0.001, 0.1, size=(B, S, H)).astype(np.float32)
    A = -RNG.uniform(0.5, 1.0, size=(H,)).astype(np.float32)
    Bm = RNG.normal(size=(B, S, N)).astype(np.float32)
    Cm = RNG.normal(size=(B, S, N)).astype(np.float32)
    y8, _ = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=8, impl="ref")
    y32, _ = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32, impl="ref")
    np.testing.assert_allclose(y8, y32, atol=2e-3, rtol=1e-3)
