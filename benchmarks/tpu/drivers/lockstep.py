"""Closed loop of back-to-back lockstep batches through the decode step.

The program takes one scalar position for the whole batch, so every
stream of a batch moves together.  A batch replays its prompt through
the step one position at a time, syncs on the first generated token
(time to first token), then decodes greedily until each stream holds
``gen_len`` tokens and syncs again.  The window takes a new batch while
fewer than ``seconds`` have passed since it opened; its rates count all
work up to the last sync.  This is ``launch.serve.serve``'s loop, with
a fresh cache for each batch, made after the last one is freed.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List

import jax


@dataclass
class Batch:
    index: int                 # which of the traffic's batches
    t_start: float
    t_first: float             # first generated token ready (synced)
    t_end: float               # last generated token ready (synced)
    tokens: List[Any]          # gen_len device arrays of (batch,)


@dataclass
class Window:
    t_open: float
    t_close: float = 0.0
    batches: List[Batch] = field(default_factory=list)


def steps_per_batch(traffic: Dict[str, Any]) -> int:
    return traffic["prompt_len"] + traffic["gen_len"] - 1


def run_window(served, traffic: Dict[str, Any], seconds: float,
               first_batch: int = 0, max_batches: int = 1 << 30,
               clock: Callable[[], float] = time.monotonic) -> Window:
    """Drive ``served`` (see ``harness.Served``) for one window.

    ``served.prompts[i]`` holds batch i's prompt as ``prompt_len``
    device arrays of (batch,); ``served.pos[t]`` the position t."""
    P, G = traffic["prompt_len"], traffic["gen_len"]
    step, params = served.step, served.params
    win = Window(clock())
    i = first_batch
    while (len(win.batches) < max_batches
           and i < len(served.prompts)
           and (not win.batches or clock() - win.t_open < seconds)):
        prompt = served.prompts[i]
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.prompt"):
            cache = served.fresh_cache()
            for t in range(P):
                _, tok, cache = step(params, cache, prompt[t],
                                     served.pos[t])
            tok.block_until_ready()
        t1 = clock()
        out = [tok]
        with jax.profiler.TraceAnnotation("bench.decode"):
            for t in range(P, P + G - 1):
                _, tok, cache = step(params, cache, tok, served.pos[t])
                out.append(tok)
            tok.block_until_ready()
        t2 = clock()
        served.put_cache(cache)
        win.batches.append(Batch(i, t0, t1, t2, out))
        i += 1
    win.t_close = clock()
    return win
