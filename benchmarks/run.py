"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus section markers).
``python -m benchmarks.run [--full] [--only SECTION] [--json PATH]``

Sections:
  latency    — paper Tables 15/16/24/27 (analytic, exact reproduction;
               ``--latency-tiny`` shrinks GA populations for CI)
  ga         — GA cut search: host numpy loop vs fused device-resident
               search at population 1000, plus the per-round
               re-optimization microbench (``--ga-tiny`` for CI)
  kernels    — Pallas kernel micro-benches
  federation — fused vs legacy Eq.-16 federation round (32 clients)
               plus the chunk-streamed population-scale round at 1k/8k
               clients (``--fed-tiny`` keeps a 256-client chunked-only
               variant for CI)
  cluster    — stage-3/4 clustered round: host numpy vs device-resident
               jitted/kernel path at 32/128 clients (``--cluster-tiny``
               keeps only the 32-client scale for CI)
  train      — scan-fused device-resident epochs vs per-step loop
               (``--train-tiny`` shrinks to the 2-client CI config)
  serve      — split-serving engine: measured U-shaped cohort
               wall-clock vs the analytic Eq. 7/9 prediction per
               profile mix, plus the Pallas-kernel LM decode tail
               (``--serve-tiny`` for CI)
  quality    — paper Tables 6-13 analogue on synthetic multi-domain data
  kld        — paper Table 17 (activation vs label KLD)
  ablation   — paper Table 23 (component ablation)
  roofline   — derived roofline terms from results/dryrun.jsonl (if present)

``--json PATH`` additionally records the report rows as one snapshot
``{"meta": {...}, "results": {name: {"us_per_call": float, "derived":
str}}}`` *appended* to a ``{"history": [snapshot, ...]}`` trajectory
at PATH — repeat runs accumulate instead of overwriting, so
``results/bench_federation.json`` et al. carry the perf trajectory
across PRs. A pre-trajectory single-snapshot file is absorbed as the
first history entry.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all scenarios/algorithms (slow on CPU)")
    ap.add_argument("--only", default=None, help="run a single section")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a BENCH_*.json dict")
    ap.add_argument("--latency-tiny", action="store_true",
                    help="latency tables with shrunken GA populations "
                         "(CI smoke)")
    ap.add_argument("--ga-tiny", action="store_true",
                    help="ga section at population 64 x 20 clients "
                         "(CI smoke)")
    ap.add_argument("--train-tiny", action="store_true",
                    help="train section at 2 clients x 2 steps (CI smoke)")
    ap.add_argument("--serve-tiny", action="store_true",
                    help="serve section with a small cohort and short "
                         "generation (CI smoke)")
    ap.add_argument("--cluster-tiny", action="store_true",
                    help="cluster section at 32 clients only (CI smoke)")
    ap.add_argument("--fed-tiny", action="store_true",
                    help="federation section: chunk-streamed round only, "
                         "at 256 clients (CI smoke)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []

    def _report(name: str, value: float, derived: str = "") -> None:
        rows.append({"name": name, "us_per_call": float(value),
                     "derived": derived})
        print(f"{name},{value:.3f},{derived}", flush=True)

    sections = ["latency", "ga", "kernels", "federation", "cluster",
                "train", "serve", "quality", "kld", "ablation", "roofline"]
    if args.only:
        sections = [args.only]

    t_start = time.time()
    print("name,us_per_call,derived")
    if "latency" in sections:
        from benchmarks import latency_table
        latency_table.run(_report, tiny=args.latency_tiny)
    if "ga" in sections:
        from benchmarks import ga_bench
        ga_bench.run(_report, tiny=args.ga_tiny)
    if "kernels" in sections:
        from benchmarks import kernel_bench
        kernel_bench.run(_report)
    if "federation" in sections:
        from benchmarks import federation_bench
        federation_bench.run(_report, tiny=args.fed_tiny)
    if "cluster" in sections:
        from benchmarks import cluster_bench
        cluster_bench.run(_report, tiny=args.cluster_tiny)
    if "train" in sections:
        from benchmarks import train_bench
        train_bench.run(_report, tiny=args.train_tiny)
    if "serve" in sections:
        from benchmarks import serve_bench
        serve_bench.run(_report, tiny=args.serve_tiny)
    if "quality" in sections:
        from benchmarks import quality_scenarios
        quality_scenarios.run(_report, fast=not args.full)
    if "kld" in sections:
        from benchmarks import kld_comparison
        kld_comparison.run(_report)
    if "ablation" in sections:
        from benchmarks import ablation_components
        ablation_components.run(_report)
    if "roofline" in sections:
        path = os.path.join(os.path.dirname(__file__), "..", "results",
                            "dryrun.jsonl")
        if os.path.exists(path):
            from repro.launch.roofline import analyze_record, load
            for rec in sorted(load(path),
                              key=lambda r: (r["arch"], r["shape"])):
                a = analyze_record(rec)
                if a is None:
                    continue
                mesh = "2pod" if rec["multi_pod"] else "1pod"
                _report(f"roofline/{a['arch']}/{a['shape']}/{mesh}",
                        a["bound_s"] * 1e6,
                        f"dom={a['dominant']} useful={a['useful_ratio']:.2f}")
        else:
            print("# roofline: results/dryrun.jsonl missing — run "
                  "python -m repro.launch.dryrun --all first",
                  file=sys.stderr)
    wall = time.time() - t_start
    print(f"# total wall: {wall:.1f}s", file=sys.stderr)

    if args.json:
        snapshot = {
            "meta": {
                "argv": sys.argv[1:],
                "sections": sections,
                "unix_time": int(t_start),
                "total_wall_s": round(wall, 3),
            },
            "results": {r["name"]: {"us_per_call": r["us_per_call"],
                                    "derived": r["derived"]}
                        for r in rows},
        }
        history = []
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    prev = json.load(f)
                if isinstance(prev, dict) and \
                        isinstance(prev.get("history"), list):
                    history = prev["history"]
                elif isinstance(prev, dict) and "results" in prev:
                    # pre-trajectory files were a bare snapshot dict
                    history = [prev]
                else:
                    raise TypeError("not a snapshot/trajectory")
            except (OSError, json.JSONDecodeError, TypeError):
                print(f"# {args.json} unreadable, starting a fresh "
                      "trajectory", file=sys.stderr)
        history.append(snapshot)
        out = {"history": history}
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# json report: {args.json} ({len(history)} snapshots)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
