"""End-to-end training integration: loss decreases, checkpoints restart
deterministically, serve generates."""
import os
import shutil
import tempfile

import numpy as np
import pytest

from repro.launch.serve import serve
from repro.launch.train import train_loop


def test_train_loss_decreases():
    losses = train_loop("qwen2-vl-2b", steps=25, smoke=True,
                        seq_len=64, global_batch=8, log_every=100)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_restart_is_bit_deterministic():
    d = tempfile.mkdtemp()
    try:
        a = train_loop("mamba2-370m", steps=8, smoke=True, ckpt_dir=d,
                       ckpt_every=4, seq_len=32, global_batch=4,
                       log_every=100)
        b = train_loop("mamba2-370m", steps=12, smoke=True, ckpt_dir=d,
                       ckpt_every=4, seq_len=32, global_batch=4,
                       log_every=100)
        c = train_loop("mamba2-370m", steps=12, smoke=True,
                       ckpt_dir=None, seq_len=32, global_batch=4,
                       log_every=100)
        # resumed steps 8..11 must match the uninterrupted run
        np.testing.assert_allclose(b[-4:], c[-4:], atol=1e-4)
    finally:
        shutil.rmtree(d)


def test_microbatch_and_compression_train():
    losses = train_loop("granite-moe-1b-a400m", steps=6, smoke=True,
                        seq_len=32, global_batch=8, n_micro=2,
                        compress=True, log_every=100)
    assert np.isfinite(losses).all()


def test_serve_generates():
    toks = serve("minitron-4b", batch=2, prompt_len=8, gen=4,
                 smoke=True).tokens
    assert toks.shape == (2, 4)
    assert (toks >= 0).all()


def test_serve_warmup_leaves_recurrent_state_alone():
    """serve's warm-up step must not touch the cache it then serves: an
    SSM's state is recurrent, so a replayed token would change it.  The
    generations equal a plain greedy replay on a fresh cache."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.launch.serve import make_step, place_cache, place_params
    from repro.models.registry import get_arch

    arch, batch, plen, gen = "mamba2-370m", 2, 8, 6
    got = serve(arch, smoke=True, batch=batch, prompt_len=plen,
                gen=gen).tokens
    cfg = get_arch(arch).reduced()
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(batch, plen)).astype(np.int32)
    mesh = make_mesh(1, 1)
    with jax.set_mesh(mesh):
        params = place_params(cfg, mesh, 0)
        cache = place_cache(cfg, mesh, batch, plen + gen)
        step = make_step(cfg, mesh, cache)
        tok = jnp.asarray(prompts[:, 0])
        want = []
        for t in range(plen + gen):
            if t >= plen:
                want.append(np.asarray(tok))
            inp = prompts[:, t] if t < plen else tok
            _, tok, cache = step(params, cache, inp, jnp.int32(t))
    np.testing.assert_array_equal(got, np.stack(want, axis=1))


def test_compile_cache_dir(monkeypatch):
    """The entry points' compile cache: the environment's directory when
    ``JAX_COMPILATION_CACHE_DIR`` is set, else the fixed ``.jax_cache``
    at the repository root."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "elsewhere")
        assert enable_compile_cache() == "elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        d = enable_compile_cache()
        assert d == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
