"""Serving driver: batched prefill + decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
        --full --batch 4 --prompt-len 64 --gen 32

``--smoke`` (the default) serves the arch's reduced config; ``--full``
serves it at its published widths.

Demonstrates the inference path every decode cell of the dry-run lowers:
jit'd ``serve_step`` (one token for the whole batch against the cache,
which it donates), greedy sampling, and per-arch cache handling (KV /
MLA latent / SSD state / ring buffers).  Prefill here replays tokens
through decode steps (identical math; the dry-run's prefill cell lowers
the fused full-sequence path).  The step is compiled before the clock
starts, and every timed phase ends in ``block_until_ready``.
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.registry import (abstract_cache, abstract_params,
                                   cache_specs, get_arch, state_specs)
from .compile_cache import enable_compile_cache
from .mesh import make_mesh, named_shardings


class ServeResult(NamedTuple):
    tokens: np.ndarray            # (batch, gen) greedy generations
    compile_s: float              # set-up: compiling the decode step
    prefill_s: float              # prompt replay, synced
    decode_s: float               # generation, synced


def place_params(cfg, mesh, seed: int):
    """Seeded parameters, initialised under ``jit`` straight into their
    ``state_specs`` shardings: each device draws only its own shard, and
    no float32 copy of a whole leaf is ever resident."""
    shardings = named_shardings(mesh, state_specs(cfg, abstract_params(cfg)))
    init = jax.jit(partial(lm.init_params, cfg), out_shardings=shardings)
    return init(jax.random.PRNGKey(seed))


def place_cache(cfg, mesh, batch: int, max_len: int):
    """Empty decode cache in the batch-over-data layout of ``cache_specs``
    (the decode_32k cell's; KV heads that do not divide the model axis
    shard the sequence over it instead)."""
    specs = cache_specs(cfg, abstract_cache(cfg, batch, max_len),
                        "decode_32k", n_model=mesh.shape["model"])
    init = jax.jit(partial(lm.init_cache, cfg, batch, max_len),
                   out_shardings=named_shardings(mesh, specs))
    return init()


def make_step(cfg, mesh, cache, aux=None):
    """Jitted decode step ``(params, cache, token, pos) -> (logits,
    greedy token, cache)``; the cache is donated and keeps its layout,
    logits and tokens come back replicated."""
    cache_sh = jax.tree_util.tree_map(lambda a: a.sharding, cache)
    repl = named_shardings(mesh, None)

    def step(params, cache, token, pos):
        logits, cache = lm.decode_step(cfg, params, cache, token, pos,
                                       aux=aux)
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return jax.jit(step, donate_argnums=(1,),
                   out_shardings=(repl, repl, cache_sh))


def serve(arch: str, *, smoke: bool, batch: int = 4, prompt_len: int = 32,
          gen: int = 16, seed: int = 0, max_len: Optional[int] = None
          ) -> ServeResult:
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    max_len = max_len or (prompt_len + gen)
    mesh = make_mesh(1, 1)

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, size=(batch, prompt_len),
                           ).astype(np.int32)

    aux = None
    with jax.set_mesh(mesh):
        params = place_params(cfg, mesh, seed)
        if cfg.enc_dec:
            audio = rng.normal(size=(batch, cfg.n_audio_frames,
                                     cfg.d_model)).astype(np.float32)
            enc = lm.encode_audio(cfg, params, audio)
            aux = {"enc_states": enc,
                   "cross_kv": lm.cross_kv(cfg, params, enc)}
        cache = place_cache(cfg, mesh, batch, max_len)

        t0 = time.monotonic()
        step = make_step(cfg, mesh, cache, aux).lower(
            params, cache, prompts[:, 0], jnp.int32(0)).compile()
        t_compile = time.monotonic() - t0
        # warm-up on a throwaway cache: SSM and conv states are recurrent,
        # so a step replayed on the served cache would change its answers
        _, tok, _ = step(params, place_cache(cfg, mesh, batch, max_len),
                         prompts[:, 0], jnp.int32(0))
        tok.block_until_ready()

        # prefill by replaying the prompt (teacher-forced decode)
        t0 = time.monotonic()
        for t in range(prompt_len):
            _, tok, cache = step(params, cache, prompts[:, t],
                                 jnp.int32(t))
        tok.block_until_ready()
        t_prefill = time.monotonic() - t0

        out = []
        t0 = time.monotonic()
        for t in range(prompt_len, prompt_len + gen):
            out.append(tok)
            _, tok, cache = step(params, cache, tok, jnp.int32(t))
        tok.block_until_ready()
        t_decode = time.monotonic() - t0

    gen_tokens = np.stack([np.asarray(t) for t in out], axis=1)
    print(f"compile decode step: {t_compile:.2f} s (set-up)")
    print(f"prefill {prompt_len} toks x {batch} streams: "
          f"{t_prefill*1e3:.1f} ms")
    print(f"decode  {gen} toks x {batch} streams: {t_decode*1e3:.1f} ms "
          f"({gen*batch/max(t_decode,1e-9):.1f} tok/s)")
    print("sample generations (first stream):", gen_tokens[0][:12])
    return ServeResult(gen_tokens, t_compile, t_prefill, t_decode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
          gen=args.gen, smoke=args.smoke, seed=args.seed)


if __name__ == "__main__":
    main()
