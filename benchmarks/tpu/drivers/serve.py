"""Closed loop of back-to-back lockstep batches through the program's own
loop, ``launch.serve.prefill`` and ``launch.serve.decode``.

The window and its batches are the lockstep driver's (``Batch``,
``Window``, and its rule: a new batch while fewer than ``seconds`` have
passed since the window opened).  A batch makes a fresh cache and
replays its prompt inside ``bench.prompt``, and decodes inside
``bench.decode``; the program names its own phases, steps and syncs
inside those spans (``serve.*``).  Each batch writes one line to
standard error with both phases' host counters, ``dispatch_s`` and
``sync_s``: a host stall shows in the first, a pause of the chip or the
runtime in the second.
"""
from __future__ import annotations

import sys
import time
from typing import Any, Callable, Dict

import jax

from benchmarks.tpu.drivers.lockstep import Batch, Window
from repro.launch.serve import decode, prefill


def run_window(served, traffic: Dict[str, Any], seconds: float,
               first_batch: int = 0, max_batches: int = 1 << 30,
               clock: Callable[[], float] = time.monotonic) -> Window:
    """Drive ``served`` (see ``harness.Served``) for one window."""
    P, G = traffic["prompt_len"], traffic["gen_len"]
    step, params = served.step, served.params
    win = Window(clock())
    i = first_batch
    while (len(win.batches) < max_batches
           and i < len(served.prompts)
           and (not win.batches or clock() - win.t_open < seconds)):
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.prompt"):
            cache = served.fresh_cache()
            tok, cache, pre = prefill(step, params, cache,
                                      served.prompts[i], served.pos)
        t1 = clock()
        with jax.profiler.TraceAnnotation("bench.decode"):
            out, cache, dec = decode(step, params, cache, tok, served.pos,
                                     P, G - 1)
        t2 = clock()
        served.put_cache(cache)
        win.batches.append(Batch(i, t0, t1, t2, [tok, *out]))
        print(f"[batch {i}] prompt dispatch_s {pre.dispatch_s:.6f} sync_s "
              f"{pre.sync_s:.6f}, decode dispatch_s {dec.dispatch_s:.6f} "
              f"sync_s {dec.sync_s:.6f}", file=sys.stderr, flush=True)
        i += 1
    win.t_close = clock()
    return win
