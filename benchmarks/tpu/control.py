#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from; not part of a
benchmark run.

    python3 benchmarks/tpu/control.py --workload minitron-4b.chat-b64 \
        --seeds 11 12 13

For each seed, in one process: set-up as a run makes it, one batch of
the cell's traffic through the program's timed path, the sample of
requests a run compares, then the run's own check (``harness.check``)
twice on the same sequences, against the cell's committed limit:

  program   as a run decides it, on the tokens the program served;
  control   with the control in the program's place: the tokens that
            the reference computed with float8 matmul inputs puts first
            at each position, the precision below the bfloat16 that the
            configuration states.  Its ``correct`` must be false.

Prints one JSON line per seed with both widest gaps, their medians and
both ``correct``.  The lower reading of a limit is the largest
``program`` gap over a dozen seeds or more, the upper the smallest
``control`` gap; the limit in ``cells/<cell>.json`` lies between them.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time


def readings(cell, seeds, devices):
    from benchmarks.tpu import harness

    cfg = harness.program_config(cell.config)
    for seed in seeds:
        t0 = time.monotonic()
        served = harness.set_up(cell, cfg, devices, seed)
        win = cell.driver().run_window(served, cell.traffic, 0.0,
                                       max_batches=1)
        picks = harness.sample(cell, win, seed)
        tokens = harness.served_tokens(win, picks)
        served.release()
        del served, win
        gc.collect()
        prog = harness.check(cell, seed, picks, tokens)
        ctl = harness.check(cell, seed, picks, tokens, control=True)
        yield {"seed": seed,
               "program": prog["numbers"]["max_logit_gap"]["value"],
               "control": ctl["numbers"]["max_logit_gap"]["value"],
               "limit": prog["numbers"]["max_logit_gap"]["limit"],
               "program_correct": prog["correct"],
               "control_correct": ctl["correct"],
               "program_median": prog["median_gap"],
               "control_median": ctl["median_gap"],
               "tokens": len(picks) * cell.traffic["gen_len"],
               "seconds": time.monotonic() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for p in (repo, os.path.join(repo, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.tpu import spec
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    cell = spec.cell(args.workload)
    for r in readings(cell, args.seeds, devices[:cell.chips]):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
