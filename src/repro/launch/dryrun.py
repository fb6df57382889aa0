"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces the *compiled* SPMD artifact for the
production mesh — 16x16 = 256 chips per pod, and 2x16x16 = 512 chips
across two pods — proving the distribution config is coherent:
shardings consistent, collectives lowerable, memory per chip reported.
No arrays are allocated: inputs are ShapeDtypeStruct and parameters are
``jax.eval_shape`` trees.

Artifacts (memory analysis, cost analysis, collective-byte breakdown,
roofline terms) are cached as JSON under ``experiments/dryrun/`` so the
benchmarks and EXPERIMENTS.md tables re-read them without recompiling.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-4b \
        --shape train_4k [--multi-pod] [--all] [--out experiments/dryrun]
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any jax import (even transitively
# via repro modules): jax locks the device count at first backend init.

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.analysis import roofline as rl
from repro.analysis.hlo import analyze_hlo
from repro.models.registry import (ARCH_IDS, SHAPES, build_step, cells,
                                   get_arch)
from .mesh import make_production_mesh, named_shardings

#: The chip of the production pods the dry-run plans for (TPU v5e).  The
#: compile runs on host devices, so the kind is named, not queried.
TARGET_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             out_dir: str = "experiments/dryrun",
             overrides: Optional[Dict] = None,
             tag: str = "") -> Dict:
    """Lower+compile one cell; returns (and caches) the artifact dict."""
    import dataclasses
    cfg = get_arch(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    ss = SHAPES[shape]

    t0 = time.monotonic()
    bundle = build_step(cfg, shape, with_pod=multi_pod)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            bundle.fn,
            in_shardings=named_shardings(mesh, bundle.in_specs),
            out_shardings=named_shardings(mesh, bundle.out_specs),
            donate_argnums=bundle.donate or (),
        )
        lowered = jitted.lower(*bundle.args)
        t_lower = time.monotonic() - t0
        t0 = time.monotonic()
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    cost_list = compiled.cost_analysis()
    cost = cost_list[0] if isinstance(cost_list, (list, tuple)) \
        else (cost_list or {})
    hlo = compiled.as_text()
    # trip-count-corrected flops/bytes/collectives (XLA's cost_analysis
    # counts while bodies once — see analysis/hlo.py)
    hc = analyze_hlo(hlo)

    mem_d = {}
    per_chip_bytes = 0.0
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_d[k] = int(v)
        per_chip_bytes = (mem_d.get("argument_size_in_bytes", 0)
                          - mem_d.get("alias_size_in_bytes", 0)
                          + mem_d.get("output_size_in_bytes", 0)
                          + mem_d.get("temp_size_in_bytes", 0))

    roof = rl.build_roofline(
        arch=arch, shape=shape, mesh_name=mesh_name, chips=chips,
        device_kind=TARGET_DEVICE_KIND,
        flops_per_chip=hc.flops, bytes_per_chip=hc.bytes,
        wire_bytes_per_chip=hc.wire_bytes,
        model_flops=rl.model_flops_for(cfg, ss),
        collectives=hc.collective_bytes,
        memory_per_chip=per_chip_bytes,
    )

    art = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips,
        "multi_pod": multi_pod, "tag": tag,
        "lower_s": t_lower, "compile_s": t_compile,
        "memory_analysis": mem_d,
        "per_chip_bytes": per_chip_bytes,
        "xla_cost_analysis": {k: float(v) for k, v in dict(cost).items()
                              if isinstance(v, (int, float))
                              and k in ("flops", "bytes accessed",
                                        "transcendentals",
                                        "optimal_seconds")},
        "roofline": roof.to_json(),
        "collective_ops": roof.collectives,
        "collective_counts": dict(hc.collective_count),
        "max_trip": hc.max_trip,
        "hlo_bytes": len(hlo),
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(out_dir,
                        f"{arch}_{shape}_{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    return art


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) for this mesh")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    todo = []
    if args.all:
        for a in ARCH_IDS:
            for s in cells(get_arch(a)):
                todo.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    failures = []
    for a, s in todo:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        path = os.path.join(args.out, f"{a}_{s}_{mesh_name}.json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip] {a} x {s} ({mesh_name})")
            continue
        try:
            t0 = time.monotonic()
            art = run_cell(a, s, multi_pod=args.multi_pod,
                           out_dir=args.out)
            r = art["roofline"]
            print(f"[ok]   {a:22s} {s:12s} {mesh_name:8s} "
                  f"compile={art['compile_s']:6.1f}s "
                  f"hbm={art['per_chip_bytes']/1e9:7.2f}GB "
                  f"bound={r['bottleneck']:10s} "
                  f"roofline={r['peak_fraction']*100:5.1f}%",
                  flush=True)
            print("  memory_analysis:", art["memory_analysis"])
            print("  cost_analysis: flops/chip=%.3e bytes/chip=%.3e"
                  % (r["flops_per_chip"], r["bytes_per_chip"]))
        except Exception as e:
            failures.append((a, s, repr(e)))
            print(f"[FAIL] {a} x {s}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} x {s}: {e}")
        return 1
    print("\nall cells compiled clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
