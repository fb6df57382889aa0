"""One run of one cell: set-up, the measured window, the check.

Set-up draws the weights from the seed straight into the program's
shardings (:mod:`weights`), places the program's decode cache
(``launch.serve.place_cache``), compiles the program's step
(``launch.serve.make_step``) ahead of time, compiles the program's
cache initialiser (``models.lm.init_cache``), places every batch's
prompt on the device, and runs the step once on the served cache before
it is swapped for a fresh one.  Then the cell's
driver runs the window.  After it, the peak memory is read, the
program's state is freed, and :mod:`reference` checks a sample of the
served requests drawn from the seed.
"""
from __future__ import annotations

import gc
import math
import sys
import tempfile
import time
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List

import numpy as np

from . import peaks as peaks_mod, reference, xplane
from .spec import Cell, family

#: ArchConfig fields that every configuration file states and the
#: program's configuration must match; a family adds its own ``SIZES``.
SIZES = ("family", "n_layers", "d_model", "vocab", "norm_eps",
         "tie_embeddings", "dtype")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def program_config(config: Dict[str, Any]):
    """The program's ArchConfig for ``config["arch"]``: its published
    entry, with each key that the configuration file cuts
    (``spec.check_cut``) set to the file's value.  Each cut key must be
    a field of the entry and its published value the entry's, and every
    size the file states, cut or not, the ArchConfig's."""
    from repro.models.registry import get_arch
    pub = get_arch(config["arch"])
    cut = config["reduced"]
    lacks = [k for k in cut if not hasattr(pub, k)]
    if lacks:
        raise ValueError(f"the program's {config['arch']!r} has no field "
                         f"for the cut keys {lacks}")
    differ = {k: (getattr(pub, k), config["published"][k]) for k in cut
              if getattr(pub, k) != config["published"][k]}
    if differ:
        raise ValueError(f"published values differ from the program's "
                         f"(program, file): {differ}")
    cfg = replace(pub, **{k: config[k] for k in cut}) if cut else pub
    check_sizes(cfg, config)
    return cfg


def sizes(config: Dict[str, Any]) -> tuple:
    """The fields of ``config`` that the program must match."""
    return SIZES + tuple(family(config["family"]).SIZES)


def check_sizes(cfg, config: Dict[str, Any]) -> None:
    missing = [k for k in sizes(config) if k not in config]
    if missing:
        raise ValueError(f"the configuration file states no {missing}")
    diff = {k: (getattr(cfg, k), config[k]) for k in sizes(config)
            if getattr(cfg, k) != config[k]}
    if diff:
        raise ValueError(f"program config differs from the configuration "
                         f"file (program, file): {diff}")


def prompts(traffic: Dict[str, Any], vocab: int, seed: int, i: int
            ) -> np.ndarray:
    """Batch ``i``'s prompts (batch, prompt_len): token ids drawn
    uniformly from the seed."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, vocab, size=(traffic["batch"],
                                        traffic["prompt_len"]),
                        dtype=np.int64).astype(np.int32)


class CompileCounter:
    """Counts traces and backend compiles while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1


@dataclass
class Served:
    """What the driver needs: the compiled step, its inputs placed."""
    step: Any
    params: Any
    init_cache: Any            # compiled: () -> an empty cache
    pos: List[Any]
    prompts: List[List[Any]]
    cache: Any = None

    def fresh_cache(self):
        """An empty cache, made after the one held is freed: two never
        live at once."""
        self.free_cache()
        return self.init_cache()

    def free_cache(self) -> None:
        import jax
        if self.cache is not None:
            for a in jax.tree_util.tree_leaves(self.cache):
                a.delete()
        self.cache = None

    def put_cache(self, c) -> None:
        self.cache = c

    def release(self) -> None:
        """Frees the program's state before the reference runs."""
        self.free_cache()
        self.params = None
        self.prompts = []


#: the decode step's program in the trace's ``XLA Modules`` line
STEP_MODULE = "jit_step"


@dataclass
class TraceContext:
    """What a per-layer metric reader gets."""
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    peaks: Any
    trace: xplane.Trace
    window: tuple
    positions: List[int]

    def devices(self):
        return [self.trace.devices[i]
                for i in sorted(self.trace.devices)][:self.chips]

    def in_window(self, op) -> bool:
        return op.end > self.window[0] and op.start < self.window[1]

    def module_seconds(self) -> float:
        return sum(m.dur for d in self.devices() for m in d.modules
                   if m.name.startswith(STEP_MODULE)
                   and self.in_window(m))


def quantile_p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile of raw samples."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def set_up(cell: Cell, cfg, devices, seed: int) -> Served:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch import serve
    from repro.launch.mesh import make_mesh, named_shardings
    from repro.models import lm
    from repro.models.registry import abstract_params, state_specs

    from . import weights

    c, t = cell.config, cell.traffic
    B, P, G = t["batch"], t["prompt_len"], t["gen_len"]
    max_len = P + G
    mesh = make_mesh(c["mesh"]["data"], c["mesh"]["model"],
                     devices=devices)
    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        shardings = named_shardings(mesh, state_specs(
            cfg, abstract_params(cfg)))
        params = weights.place(c, abstract_params(cfg), shardings, seed)
        cache = serve.place_cache(cfg, mesh, B, max_len)
        jax.block_until_ready((params, cache))
        t1 = time.monotonic()
        tok0 = jnp.zeros((B,), jnp.int32)
        step = serve.make_step(cfg, mesh, cache).lower(
            params, cache, tok0, jnp.int32(0)).compile()
        cache_sh = jax.tree_util.tree_map(lambda a: a.sharding, cache)
        init_cache = jax.jit(partial(lm.init_cache, cfg, B, max_len),
                             out_shardings=cache_sh).lower().compile()
        t2 = time.monotonic()
        tok_sh = NamedSharding(mesh, PartitionSpec())
        pos = [jax.device_put(np.int32(p), tok_sh) for p in range(max_len)]

        def split(a):
            return tuple(a[i] for i in range(P))

        split = jax.jit(split, out_shardings=tok_sh)
        placed = []
        for i in range(t["max_batches"]):
            cols = np.ascontiguousarray(prompts(t, c["vocab"], seed, i).T)
            placed.append(list(split(jax.device_put(cols, tok_sh))))
        # warm-up on the served cache, then a fresh one: every program
        # the window runs has now run once
        _, tok, cache = step(params, cache, placed[0][0], pos[0])
        served = Served(step, params, init_cache, pos, placed, cache)
        served.put_cache(served.fresh_cache())
        jax.block_until_ready((tok, served.cache, placed))
    log(f"[setup] weights and cache {t1 - t0:.3f} s, compile {t2 - t1:.3f}"
        f" s, prompts and warm-up {time.monotonic() - t2:.3f} s")
    return served


def end_to_end(cell: Cell, win, setup_s: float) -> Dict[str, float]:
    t = cell.traffic
    B, G = t["batch"], t["gen_len"]
    n = len(win.batches)
    ttft = [b.t_first - b.t_start for b in win.batches for _ in range(B)]
    tpot = [(b.t_end - b.t_first) / (G - 1)
            for b in win.batches for _ in range(B)]
    wall = win.batches[-1].t_end - win.t_open
    out = {"output_tok_s": n * B * G / wall,
           "ttft_p95_ms": 1e3 * quantile_p95(ttft),
           "tpot_p95_ms": 1e3 * quantile_p95(tpot),
           "setup_s": setup_s}
    log(f"[window] {n} batches x {B} requests = {n * B} requests, "
        f"{n * B * G} generated tokens in {wall:.4f} s")
    log(f"[window] ttft median {1e3 * float(np.median(ttft)):.3f} ms, "
        f"tpot median {1e3 * float(np.median(tpot)):.4f} ms "
        f"({len(ttft)} samples each)")
    return out


def sample(cell: Cell, win, seed: int) -> List[tuple]:
    """(batch index, row) of the requests the check compares: drawn from
    the seed among the window's requests, all of one length."""
    k = cell.traffic["check_requests"]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    B = cell.traffic["batch"]
    pairs = [(b.index, r) for b in win.batches for r in range(B)]
    pick = rng.choice(len(pairs), size=min(k, len(pairs)), replace=False)
    return [pairs[j] for j in sorted(pick)]


def served_tokens(win, picks) -> Dict[tuple, np.ndarray]:
    import jax.numpy as jnp
    by_batch = {b.index: b for b in win.batches}
    out = {}
    for i in sorted({p[0] for p in picks}):
        toks = np.asarray(jnp.stack(by_batch[i].tokens, axis=1))  # (B, G)
        for (bi, r) in picks:
            if bi == i:
                out[(bi, r)] = toks[r]
    return out


def sequences(cell: Cell, seed: int, picks, tokens):
    """The sampled requests as the reference reads them: (prompt + served
    tokens but the last) of shape (K, P + G - 1), and the served tokens
    (K, G)."""
    c, t = cell.config, cell.traffic
    seqs = np.stack([np.concatenate([prompts(t, c["vocab"], seed, i)[r],
                                     tokens[(i, r)][:-1]])
                     for i, r in picks])
    return seqs, np.stack([tokens[p] for p in picks])


def check(cell: Cell, seed: int, picks, tokens, control: bool = False
          ) -> Dict[str, Any]:
    """Decides ``correct``: the widest gap by which a served token's
    reference logit lies below the reference's best, over the sampled
    requests, against the cell's limit; and whether every served token
    lies in the vocabulary.

    With ``control``, the control takes the program's place: at each
    position of the same sequences the token compared is the one that
    the reference computed with float8 matmul inputs (the precision
    below the configurations' bfloat16) puts first.  Its result must
    come out not correct."""
    import jax.numpy as jnp
    V = cell.config["vocab"]
    first = cell.traffic["prompt_len"] - 1
    seqs, served = sequences(cell, seed, picks, tokens)
    logits = reference.Reference(cell.config, seed).logits(seqs, first)
    if control:
        ctl = reference.Reference(cell.config, seed, quant="fp8").logits(
            seqs, first)
        served = np.asarray(jnp.argmax(ctl, axis=-1))
        del ctl
    ok_vocab = ((served >= 0) & (served < V)).all(axis=1)
    gap = np.asarray(reference.gaps(
        logits, jnp.asarray(np.clip(served, 0, V - 1))))
    lim = cell.limits["max_logit_gap"]
    bad = ~ok_vocab | (gap.max(1) > lim)
    numbers = {"tokens_in_vocab": {"value": int(ok_vocab.all()),
                                   "limit": 1},
               "max_logit_gap": {"value": float(gap.max()), "limit": lim}}
    return {"correct": not bad.any(), "failed": int(bad.sum()),
            "median_gap": float(np.median(gap)), "numbers": numbers}


#: ops that only hold others (a layer loop): left out of the breakdown,
#: whose entries would otherwise count the same time twice
CONTAINERS = ("while", "conditional", "call")


def breakdown(ctx: TraceContext) -> Dict[str, List]:
    devs = ctx.devices()
    lo, hi = ctx.window
    per_op: Dict[str, float] = {}
    for d in devs:
        for o in d.ops:
            name = xplane.op_name(o.name)
            if ctx.in_window(o) and not name.startswith(CONTAINERS):
                per_op[name] = per_op.get(name, 0.0) + o.dur
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    ops = [[n, s / len(devs)] for n, s in top]
    idle = []
    if devs:
        g = xplane.gaps([(o.start, o.end) for o in devs[0].ops], lo, hi)
        g = sorted(g, key=lambda iv: iv[0] - iv[1])[:10]
        for s, e in g:
            mid = 0.5 * (s + e)
            around = [h for h in ctx.trace.host
                      if h.start <= mid <= h.end and h.dur > 0]
            name = min(around, key=lambda h: h.dur).name if around \
                else "no host event"
            idle.append([name, e - s])
    return {"device_ops": ops, "idle_gaps": idle}


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_setup: float) -> Dict[str, Any]:
    """One run; returns the result line's object.  ``setup_s`` counts
    from ``t_setup`` (``time.monotonic``), when JAX has found the chips.
    """
    import jax

    cfg = program_config(cell.config)
    counter = CompileCounter()
    served = set_up(cell, cfg, devices, seed)
    driver = cell.driver()
    t = cell.traffic
    tmp = None
    if trace:
        driver.run_window(served, t, 0.0, first_batch=0, max_batches=1)
        tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        jax.profiler.start_trace(tmp.name)
    setup_s = time.monotonic() - t_setup
    counter.armed = True
    win = driver.run_window(served, t, seconds,
                            first_batch=1 if trace else 0,
                            max_batches=1 if trace else 1 << 30)
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    log(f"[setup] {setup_s:.4f} s to the first timed batch")
    log(f"[window] compilations inside the window: {counter.count}")
    if not trace and win.t_close - win.t_open < seconds:
        log(f"[window] ran out of prompts after {len(win.batches)} batches")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    picks = sample(cell, win, seed)
    attempted = len(win.batches) * t["batch"]
    tokens = served_tokens(win, picks)
    metrics: Dict[str, Any] = {}
    extra: Dict[str, Any] = {}
    if trace:
        tr = xplane.read(xplane.find(tmp.name))
        tmp.cleanup()
        ctx = TraceContext(cell.config, t, cell.chips,
                           peaks_mod.peaks(dev.device_kind), tr,
                           tr.window(), [p for _ in win.batches for p in
                                         range(t["prompt_len"]
                                               + t["gen_len"] - 1)])
        for m in cell.per_layer:
            v = cell.reader(m.name).read(ctx)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        lo, hi = ctx.window
        device["busy_s"] = sum(
            xplane.union([(o.start, o.end) for o in d.ops], lo, hi)
            for d in ctx.devices()) / max(len(ctx.devices()), 1)
        device["window_s"] = hi - lo
        extra["breakdown"] = breakdown(ctx)
    else:
        e2e = end_to_end(cell, win, setup_s)
        for m in cell.end_to_end:
            metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}
    served.release()
    del served, win
    gc.collect()
    t_check = time.monotonic()
    comp = check(cell, seed, picks, tokens)
    log(f"[check] reference took {time.monotonic() - t_check:.3f} s")
    nums = comp["numbers"]
    log(f"[check] {len(picks)} sampled requests, "
        f"{len(picks) * t['gen_len']} served tokens, "
        f"{comp['failed']} failed")
    for name, v in nums.items():
        log(f"check {name} {v['value']} limit {v['limit']}")
    return {"correct": comp["correct"], "attempted": attempted,
            "failed": comp["failed"], "metrics": metrics,
            "device": device, **extra, "check": nums}
