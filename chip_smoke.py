#!/usr/bin/env python3
"""Bring-up check: the JAX serving and training paths on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded path only

One chip runs, in order:

  device       JAX must report TPU devices; there is no CPU fallback.
  kernels      flash_decode, flash_attention, ssd_scan and neutron_matmul
               compiled by Mosaic (``tpu_custom_call`` in the HLO) at
               minitron-4b's (SSD: mamba2-370m's) shapes, each against
               its jnp reference on the chip.
  serve        ``launch.serve.serve`` on minitron-4b at full width: 4
               streams, a 64-token prompt, 32 generated tokens.
  correctness  teacher-forced decode logits against ``lm.forward`` on
               the same seeded weights.
  train        ``launch.train.train_loop`` on mamba2-370m at full width,
               3 steps; the loss must be finite.

``--chips 4`` runs granite-20b at full width (40.6 GB of bf16 weights:
more than one chip holds) on a 1x4 (data, model) mesh.  Parameters are
initialised sharded; decode goes through the sequence-sharded
flash-decode path.  It compares the decode logits with ``lm.forward`` on
the four chips, and a 2-layer cut of the model run on one device with
the same weights sharded over four.

Weights and inputs are drawn from a seed.  Timings are a bring-up
reading, not a benchmark.  Any failed phase exits non-zero; without a
TPU, or away from the repository, the script exits non-zero before
printing any result.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# Tolerances.  Weights, activations, caches and logits are bf16: one
# rounding moves a value by up to 2^-8 of its magnitude.
#
# KERNEL_TOL: a kernel and its reference read the same bf16 inputs and
# accumulate in f32, then round the output to bf16 (2^-8).  Inside the
# kernel the f32 softmax weights or SSD gates may enter the MXU rounded
# to bf16 (another 2^-8).  The two roundings and 2x headroom give 2^-6,
# as a share of the reference's largest magnitude.
KERNEL_TOL = 2.0 ** -6
# LOGIT_TOL: decode and the whole-sequence forward run the same math but
# round different intermediates to bf16 (one token against the cache
# versus all positions at once; split matmuls and all-reduces on four
# chips).  Each layer adds a bf16-rounded update to the residual, and
# over 32-52 layers the differences compound: at minitron-4b's depth and
# vocabulary (d_model cut to 768, on the CPU) they reach 2% of the
# largest logit.  2^-4 (6.25%) bounds that with 3x headroom; a decode
# that reads a wrong cache row misses by the size of the logits.
LOGIT_TOL = 2.0 ** -4
# TOP1_MIN: random weights leave the reference's best two logits close
# (a median 2.7% of the largest logit apart in the same CPU probe, where
# 96.5% of picks agreed), so a few picks flip at near-ties; each flip
# must be one (reference gap within LOGIT_TOL, checked separately), and
# at least 90% of positions must pick the same token.
TOP1_MIN = 0.90


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def versions() -> str:
    from importlib.metadata import PackageNotFoundError, version
    out = []
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out.append(f"{pkg} {version(pkg)}")
        except PackageNotFoundError:
            out.append(f"{pkg} (not installed)")
    return ", ".join(out)


# --------------------------------------------------------------------------
# comparisons
# --------------------------------------------------------------------------


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in float32."""
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    return float(np.abs(g - w).max() / max(float(np.abs(w).max()), 1e-30))


def check_logits(name: str, got, want) -> None:
    """Decode logits (..., V) against reference logits (..., V)."""
    import numpy as np
    g = np.asarray(got, np.float32).reshape(-1, np.shape(got)[-1])
    w = np.asarray(want, np.float32).reshape(-1, np.shape(want)[-1])
    scale = float(np.abs(w).max())
    err = float(np.abs(g - w).max())
    pick = g.argmax(-1)
    agree = float((pick == w.argmax(-1)).mean())
    # where the picks differ, the decode pick must be a reference near-tie
    gap = float((w.max(-1) - w[np.arange(len(w)), pick]).max())
    log(f"[correctness] {name}: max|dlogit| {err:.4g} = "
        f"{err / scale:.4g} of max|logit| {scale:.4g} "
        f"(bound {LOGIT_TOL:.4g}); top-1 agreement {agree:.4f} "
        f"(min {TOP1_MIN}); largest reference gap at a flipped pick "
        f"{gap:.4g} (bound {LOGIT_TOL * scale:.4g})")
    require(np.isfinite(g).all(), f"{name}: non-finite decode logits")
    require(err <= LOGIT_TOL * scale, f"{name}: logits differ by {err}")
    require(agree >= TOP1_MIN, f"{name}: top-1 agreement {agree}")
    require(gap <= LOGIT_TOL * scale, f"{name}: top-1 flip gap {gap}")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 32))

    def normal(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    # minitron-4b: 24 query heads over 8 KV heads (32 after tp padding),
    # head_dim 128, d_model 3072, d_ff 9216; 4 streams, 96-token cache
    B, H, Hkv, hd, S = 4, 24, 8, 128, 96
    kv_len = jnp.array([96, 65, 17, 1], jnp.int32)
    # mamba2-370m: 32 SSD heads of 64, state 128, chunk 128
    Sm, Hs, P, N = 512, 32, 64, 128
    A = -jnp.exp(jax.random.uniform(next(keys), (Hs,), jnp.float32,
                                    -1.0, 1.0))
    dt = jax.nn.softplus(normal((B, Sm, Hs), jnp.float32) - 3.0)
    cases = {
        "flash_decode": (
            lambda q, k, v, n, impl: ops.flash_decode(q, k, v, kv_len=n,
                                                      impl=impl),
            (normal((B, H, hd)), normal((B, Hkv, S, hd)),
             normal((B, Hkv, S, hd)), kv_len)),
        "flash_attention": (
            lambda q, k, v, impl: ops.flash_attention(
                q, k, v, causal=True, impl=impl, fused_vjp=False,
                block_k=512),
            (normal((B, 32, 64, hd)), normal((B, Hkv, 64, hd)),
             normal((B, Hkv, 64, hd)))),
        "ssd_scan": (
            lambda x, dt, A, b, c, impl: ops.ssd_scan(
                x, dt, A, b, c, chunk=128, impl=impl),
            (normal((B, Sm, Hs, P)), dt, A, normal((B, Sm, N)),
             normal((B, Sm, N)))),
        "neutron_matmul": (
            lambda x, w, impl: ops.neutron_matmul(x, w, act="sqrelu",
                                                  impl=impl),
            (normal((256, 3072)), normal((3072, 9216)) / 3072 ** 0.5)),
    }
    for name, (fn, args) in cases.items():
        t0 = time.monotonic()
        compiled = jax.jit(lambda *a: fn(*a, impl="pallas")).lower(
            *args).compile()
        t_compile = time.monotonic() - t0
        require("tpu_custom_call" in compiled.as_text(),
                f"{name}: no Mosaic kernel in the compiled HLO")
        got = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: fn(*a, impl="ref"))(*args)
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        errs = [rel_err(g, w) for g, w in zip(got, want)]
        log(f"[kernels] {name}: compiled {t_compile:.2f} s, "
            f"tpu_custom_call present, max rel err "
            f"{', '.join(f'{e:.3g}' for e in errs)} (bound {KERNEL_TOL:.4g})")
        require(all(e <= KERNEL_TOL for e in errs), f"{name}: {errs}")


def teacher_forced(cfg, mesh, params, prompts, max_len):
    """Decode the prompt one position at a time through the serving step;
    returns (logits (B, P, V), seconds per step after the first)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import make_step, place_cache

    cache = place_cache(cfg, mesh, prompts.shape[0], max_len)
    step = make_step(cfg, mesh, cache)
    out = []
    t0 = None
    for t in range(prompts.shape[1]):
        logits, _, cache = step(params, cache, prompts[:, t], jnp.int32(t))
        if t == 0:
            logits.block_until_ready()
            t0 = time.monotonic()
        out.append(logits)
    logits = jax.block_until_ready(jnp.stack(out, axis=1))
    per_step = (time.monotonic() - t0) / max(prompts.shape[1] - 1, 1)
    return logits, per_step


def forward_logits(cfg, params, prompts):
    import jax

    from repro.models import lm
    return jax.jit(lambda p, t: lm.forward(cfg, p, {"tokens": t}))(
        params, prompts)


def describe(cfg) -> str:
    return (f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, "
            f"vocab {cfg.vocab}, {cfg.n_params() / 1e9:.2f} B params")


def phase_serve(dev) -> None:
    from repro.launch.serve import serve
    from repro.models.registry import get_arch

    cfg = get_arch("minitron-4b")
    batch, prompt_len, gen = 4, 64, 32
    log(f"[serve] {describe(cfg)}; {batch} streams x {prompt_len} prompt "
        f"+ {gen} generated tokens")
    res = serve("minitron-4b", smoke=False, batch=batch,
                prompt_len=prompt_len, gen=gen, seed=SEED)
    require(res.tokens.shape == (batch, gen), f"tokens {res.tokens.shape}")
    require(((res.tokens >= 0) & (res.tokens < cfg.vocab)).all(),
            "generated token ids inside the vocabulary")
    peak = dev.memory_stats().get("peak_bytes_in_use", 0)
    log(f"[serve] bring-up reading (not a benchmark): compile "
        f"{res.compile_s:.2f} s (set-up), prefill {prompt_len} tok x "
        f"{batch}: {res.prefill_s * 1e3:.1f} ms, decode {gen} tok x "
        f"{batch}: {res.decode_s * 1e3:.1f} ms = "
        f"{gen * batch / res.decode_s:.1f} tok/s, peak HBM "
        f"{peak / 2 ** 30:.2f} GiB")


def phase_correctness() -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.launch.serve import place_params
    from repro.models.registry import get_arch

    cfg = get_arch("minitron-4b")
    batch, prompt_len = 4, 64
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab, size=(batch, prompt_len)).astype(np.int32)
    mesh = make_mesh(1, 1)
    with jax.set_mesh(mesh):
        params = place_params(cfg, mesh, SEED)
        dec, _ = teacher_forced(cfg, mesh, params, prompts, prompt_len)
        ref = forward_logits(cfg, params, prompts)
    check_logits("minitron-4b decode vs forward", dec, ref)


def phase_train() -> None:
    import numpy as np

    from repro.launch.train import train_loop

    log("[train] mamba2-370m at full width: 3 steps, seq 512, batch 4")
    losses = train_loop("mamba2-370m", smoke=False, steps=3, seq_len=512,
                        global_batch=4, log_every=1, seed=SEED)
    log(f"[train] losses {[round(x, 4) for x in losses]}")
    require(len(losses) == 3 and np.isfinite(losses).all(),
            f"3 finite losses, got {losses}")


def phase_four_chips() -> None:
    """granite-20b sharded over a 1x4 (data, model) mesh."""
    import dataclasses

    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh, named_shardings
    from repro.launch.serve import place_params
    from repro.models.registry import abstract_params, get_arch, state_specs

    devs = jax.devices()
    require(len(devs) >= 4, f"--chips 4 needs four devices, found "
            f"{len(devs)}")
    mesh4 = make_mesh(1, 4, devices=devs[:4])
    batch, prompt_len = 4, 32
    max_len = 64                       # divides the 4-way sequence split

    cfg = get_arch("granite-20b")
    log(f"[4 chips] {describe(cfg)}; mesh (data 1, model 4)")
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab, size=(batch, prompt_len)).astype(np.int32)
    with jax.set_mesh(mesh4):
        t0 = time.monotonic()
        params = place_params(cfg, mesh4, SEED)
        jax.block_until_ready(params)
        per_dev = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in devs[:4]]
        log(f"[4 chips] sharded init {time.monotonic() - t0:.1f} s; "
            f"GiB in use per chip "
            f"{[round(b / 2 ** 30, 2) for b in per_dev]}")
        dec, per_step = teacher_forced(cfg, mesh4, params, prompts,
                                       max_len)
        log(f"[4 chips] bring-up reading (not a benchmark): "
            f"{per_step * 1e3:.2f} ms per decode step of {batch} streams")
        ref = forward_logits(cfg, params, prompts)
        check_logits("granite-20b 4-chip decode vs 4-chip forward", dec,
                     ref)
    del params, dec, ref

    cut = dataclasses.replace(cfg, n_layers=2)
    mesh1 = make_mesh(1, 1, devices=devs[:1])
    with jax.set_mesh(mesh1):
        p1 = place_params(cut, mesh1, SEED)
        one, _ = teacher_forced(cut, mesh1, p1, prompts, max_len)
    with jax.set_mesh(mesh4):
        p4 = jax.device_put(p1, named_shardings(
            mesh4, state_specs(cut, abstract_params(cut))))
        four, _ = teacher_forced(cut, mesh4, p4, prompts, max_len)
    check_logits("granite-20b 2-layer cut: 4 chips vs 1 device", four, one)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repository ({e})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    warm = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"[setup] compile cache at {cache_dir} ({warm} entries at start)")

    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"[device] {dev.platform} {dev.device_kind} x {len(devs)}; "
        f"{versions()}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.chips == 4:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("kernels", phase_kernels),
                  ("serve", lambda: phase_serve(dev)),
                  ("correctness", phase_correctness),
                  ("train", phase_train)]
    for name, fn in phases:
        t0 = time.monotonic()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: phase {name} failed", file=sys.stderr)
            return 1
        log(f"[{name}] done in {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
