#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once, on the TPU it is started on.

    python3 benchmarks/tpu/run.py --workload minitron-4b.chat-b64 \
        --seed 7 --seconds 30 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of one batch.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``: each number compared with its limit).  The run
exits non-zero and prints no result where JAX finds no TPU or fewer
chips than the cell asks for, or where the program is not beside the
benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    t_process = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for p in (repo, os.path.join(repo, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.tpu import harness, spec
    try:
        cell = spec.cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark ({e})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    t_setup = time.monotonic()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 1
    harness.log(f"[setup] {t_setup - t_process:.3f} s to JAX's devices "
                f"(the runtime's start, left out of setup_s)")
    harness.log(f"[setup] {cell.name}, seed {args.seed}, compile cache "
                f"{cache_dir}, {devices[0].device_kind} x {len(devices)}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices[:cell.chips], t_setup)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
