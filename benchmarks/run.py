"""Benchmark entrypoint: `PYTHONPATH=src python -m benchmarks.run`.

Runs the paper-table reproductions on the simulated-NPU backend and then
prints the roofline table from any cached dry-run artifacts.  Pass
``--fast`` to restrict Table III to the four small classification models
(full suite ~6 min single-core).
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def write_summary(entries, out="BENCH_summary.json"):
    """Aggregate every bench artifact of this run into one
    machine-readable summary: per bench, its exit code, its artifact's
    top-level boolean gates, and a pass verdict (rc == 0 AND every gate
    true AND the artifact exists).  Returns 0 when every bench passed,
    1 otherwise — ``main`` folds this into its exit code so a red gate
    fails the run even if the bench's own main() was lenient."""
    benches = []
    ok = True
    for name, path, rc in entries:
        gates = {}
        exists = os.path.exists(path)
        if exists:
            try:
                with open(path) as f:
                    doc = json.load(f)
                gates = {k: v for k, v in doc.items()
                         if isinstance(v, bool)}
            except (OSError, ValueError) as e:
                exists = False
                gates = {"parse_error": False}
                print(f"[summary] {name}: unreadable artifact {path}: "
                      f"{e}")
        passed = bool(exists and rc == 0 and all(gates.values()))
        ok &= passed
        benches.append({"bench": name, "artifact": path, "rc": int(rc),
                        "artifact_exists": exists, "gates": gates,
                        "passed": passed})
    doc = {"ok": ok, "benches": benches}
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"[summary] {out}: "
          + ", ".join(f"{b['bench']}={'PASS' if b['passed'] else 'FAIL'}"
                      for b in benches)
          + f" -> {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="sub-minute smoke: fast-marked tier-1 tests + "
                         "compile_bench --quick; skips tables/roofline")
    ap.add_argument("--skip-tables", action="store_true")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--skip-quant", action="store_true")
    ap.add_argument("--skip-fusion", action="store_true")
    ap.add_argument("--skip-serve", action="store_true")
    ap.add_argument("--skip-robust", action="store_true")
    ap.add_argument("--skip-fleet", action="store_true")
    ap.add_argument("--skip-decode", action="store_true")
    ap.add_argument("--cache-dir", default=None,
                    help="enable the on-disk program-cache tier at this "
                         "directory (CI keys its cache on it; a warm dir "
                         "turns every repeat compile into an artifact "
                         "load)")
    args = ap.parse_args(argv)

    if args.cache_dir:
        from repro.core import program_cache_configure
        program_cache_configure(disk_dir=args.cache_dir)

    def _cache_summary():
        if not args.cache_dir:
            return
        from repro.core import program_cache_info
        info = program_cache_info()
        print(f"[program-cache] disk tier at {info['disk_dir']}: "
              f"{info['disk_entries']} artifacts, "
              f"{info['disk_hits']} hits / {info['disk_misses']} misses "
              f"/ {info['disk_rejects']} rejects this run")

    if args.quick:
        import subprocess
        import sys as _sys
        print("=" * 72)
        print("QUICK SMOKE (pytest -m fast + compile/quant/fusion/serve/"
              "robust/fleet/decode benches --quick)")
        print("=" * 72)
        # nothing above imports JAX, so an accelerator stays free for
        # the child (a chip belongs to one process)
        rc = subprocess.call(
            [_sys.executable, "-m", "pytest", "-q", "-m", "fast"])
        entries = []
        from . import compile_bench
        r = compile_bench.main(["--quick",
                                "--out", "BENCH_compile_quick.json"])
        entries.append(("compile", "BENCH_compile_quick.json", r))
        from . import quant_bench
        r = quant_bench.main(["--quick",
                              "--out", "BENCH_quant_quick.json"])
        entries.append(("quant", "BENCH_quant_quick.json", r))
        from . import fusion_bench
        r = fusion_bench.main(["--quick",
                               "--out", "BENCH_fusion_quick.json"])
        entries.append(("fusion", "BENCH_fusion_quick.json", r))
        from . import serve_bench
        r = serve_bench.main(["--quick",
                              "--out", "BENCH_serve_quick.json"])
        entries.append(("serve", "BENCH_serve_quick.json", r))
        from . import robust_bench
        r = robust_bench.main(["--quick",
                               "--out", "BENCH_robust_quick.json"])
        entries.append(("robust", "BENCH_robust_quick.json", r))
        from . import fleet_bench
        r = fleet_bench.main(["--quick",
                              "--out", "BENCH_fleet_quick.json"])
        entries.append(("fleet", "BENCH_fleet_quick.json", r))
        from . import decode_bench
        r = decode_bench.main(["--quick",
                               "--out", "BENCH_decode_quick.json"])
        entries.append(("decode", "BENCH_decode_quick.json", r))
        rc |= max(e[2] for e in entries)
        rc |= write_summary(entries)
        if args.cache_dir:
            # exercise the disk tier with real programs: cold CI solves
            # and writes artifacts; a restored cache dir serves them in
            # milliseconds (the cross-process warm-start path)
            import time as _time
            import repro.api as api_mod
            from repro.core import program_cache_clear
            program_cache_clear(stats=False)   # force past the LRU tier
            for name in ("mobilenet_v1", "mobilenet_v2"):
                t0 = _time.monotonic()
                m = api_mod.compile(name, res_scale=0.25)
                print(f"[program-cache] {name}: "
                      f"tier={m.cache_tier or 'solved'} "
                      f"{_time.monotonic() - t0:.3f}s")
        _cache_summary()
        return rc

    if not args.skip_tables:
        from . import paper_tables as pt
        print("=" * 72)
        print("PAPER-TABLE REPRODUCTIONS (simulated Neutron NPU)")
        print("=" * 72)
        print("[Table I] effective TOPS")
        pt.bench_table1()
        print("[Table III] latency + LTP")
        models = None
        if args.fast:
            models = [("mobilenet_v1", 1.0), ("mobilenet_v2", 1.0),
                      ("mobilenet_v3_min", 1.0),
                      ("efficientnet_lite0", 1.0)]
        pt.bench_table3(models=models)
        print("[Table II] CP partitioning")
        pt.bench_table2()
        print("[Fig 6] fusion memory profile")
        pt.bench_fig6()
        print("[§VI] GenAI GEMM speedup")
        pt.bench_genai()

    rc = 0
    entries = []
    if not args.skip_fusion:
        print("=" * 72)
        print("FUSION WINDOWING (greedy vs capped vs windowed CP, "
              "BENCH_fusion.json)")
        print("=" * 72)
        from . import fusion_bench
        path = "BENCH_fusion_quick.json" if args.fast \
            else "BENCH_fusion.json"
        r = fusion_bench.main(["--quick", "--out", path]
                              if args.fast else [])
        entries.append(("fusion", path, r))
        rc |= r

    if not args.skip_quant:
        print("=" * 72)
        print("QUANTIZATION (int8/int4 PTQ vs float32, BENCH_quant.json)")
        print("=" * 72)
        from . import quant_bench
        # --fast smoke must not clobber the canonical full-run artifact
        path = "BENCH_quant_quick.json" if args.fast \
            else "BENCH_quant.json"
        r = quant_bench.main(["--quick", "--out", path]
                             if args.fast else [])
        entries.append(("quant", path, r))
        rc |= r

    if not args.skip_serve:
        print("=" * 72)
        print("SERVING (compiled replay plans vs interpretive executor, "
              "BENCH_serve.json)")
        print("=" * 72)
        from . import serve_bench
        path = "BENCH_serve_quick.json" if args.fast \
            else "BENCH_serve.json"
        r = serve_bench.main(["--quick", "--out", path]
                             if args.fast else [])
        entries.append(("serve", path, r))
        rc |= r

    if not args.skip_robust:
        print("=" * 72)
        print("SERVING ROBUSTNESS (fault injection: stalls/poison/"
              "corrupt/skew, BENCH_robust.json)")
        print("=" * 72)
        from . import robust_bench
        path = "BENCH_robust_quick.json" if args.fast \
            else "BENCH_robust.json"
        r = robust_bench.main(["--quick", "--out", path]
                              if args.fast else [])
        entries.append(("robust", path, r))
        rc |= r

    if not args.skip_fleet:
        print("=" * 72)
        print("FLEET SERVING (replicated pools: hedging, failover, "
              "audit, BENCH_fleet.json)")
        print("=" * 72)
        from . import fleet_bench
        path = "BENCH_fleet_quick.json" if args.fast \
            else "BENCH_fleet.json"
        r = fleet_bench.main(["--quick", "--out", path]
                             if args.fast else [])
        entries.append(("fleet", path, r))
        rc |= r

    if not args.skip_decode:
        print("=" * 72)
        print("LM DECODE (prefill + streaming tokens/s on the NPU "
              "path, BENCH_decode.json)")
        print("=" * 72)
        from . import decode_bench
        path = "BENCH_decode_quick.json" if args.fast \
            else "BENCH_decode.json"
        r = decode_bench.main(["--quick", "--out", path]
                              if args.fast else [])
        entries.append(("decode", path, r))
        rc |= r

    if entries:
        rc |= write_summary(entries)

    if not args.skip_roofline:
        print("=" * 72)
        print("ROOFLINE (from cached dry-run artifacts)")
        print("=" * 72)
        from . import roofline as rf
        rf.main()
    _cache_summary()
    return rc


if __name__ == "__main__":
    sys.exit(main())
