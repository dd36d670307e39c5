"""Run the full benchmark suite (one module per paper table/figure).

  PYTHONPATH=src python -m benchmarks.run [--scale small|medium|large] [--only NAME]

Per-suite results land in experiments/bench/<name>.json; the perf-trajectory
roll-up (per-suite wall time, pipeline phase breakdown, tuned dispatch
decisions, graph scale) is written to the repo-root BENCH_pipeline.json
(schema: benchmarks/common.validate_rollup; docs/BENCHMARKS.md). The default
scale is `small` — the CI-sized run (common.py). Roofline terms come from
the dry-run (launch/dryrun.py), not here.

`dispatch_policy` runs first on purpose: it tunes and installs the dispatch
policy cache, so every later suite (and the recorded phase breakdown) runs
under measured routing rather than the untuned fallback.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import common

SUITES = [
    ("dispatch_policy", "beyond-paper: autotune packed/unpacked + kernel modes"),
    ("strong_scaling", "Fig 5: phase breakdown + per-shard balance"),
    ("edge_elimination", "Fig 6a: edge elimination ablation"),
    ("work_aggregation", "Fig 6b: TDS token dedup ablation"),
    ("load_balance", "Fig 7: reshuffle + smaller deployments"),
    ("incremental", "Fig 9: naive vs PJI-X vs PJI-Y"),
    ("exploratory", "Fig 10: progressive relaxation"),
    ("enumeration_compare", "Tables 4/5: vs tree-search enumeration"),
    ("distributed_join", "beyond-paper: replicated vs distributed-rows join"),
    ("multi_tenant", "beyond-paper: template-batched B-query execution"),
    ("query_plan", "plan-level optimizer: planned vs heuristic order"),
    ("template_sensitivity", "Table 6: template topology family"),
    ("rmat_distributions", "Table 10: R-MAT skew sweep"),
    ("frontier_edge_prune", "beyond-paper: CC edge-exactness, TDS skipped"),
    ("precision_tradeoff", "Reza'18 §5E: effort vs precision (recall 100%)"),
    ("resilience", "beyond-paper: phase checkpoints + elastic fault recovery"),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small",
                    choices=["small", "medium", "large"])
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names to run")
    ap.add_argument("--no-rollup", action="store_true",
                    help="skip writing the repo-root BENCH_pipeline.json")
    args = ap.parse_args(argv)
    from repro.kernels import compat

    compat.enable_compile_cache()
    known = [name for name, _ in SUITES]
    only = args.only.split(",") if args.only else None
    if only:
        for sel in only:
            if sel not in known:
                ap.error(f"--only {sel!r} matches no suite; known: {known}")

    suites = {}
    payloads = {}
    failures = []
    for name, desc in SUITES:
        if only and name not in only:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.perf_counter()
        try:
            payloads[name] = mod.run(args.scale)
            secs = time.perf_counter() - t0
            suites[name] = {"seconds": secs, "ok": True, "description": desc}
            print(f"[ok]   {name:24s} {desc} ({secs:.1f}s)")
        except Exception as e:
            secs = time.perf_counter() - t0
            suites[name] = {"seconds": secs, "ok": False, "description": desc,
                            "error": repr(e)}
            failures.append((name, repr(e)))
            print(f"[FAIL] {name:24s} {e}")
            traceback.print_exc()

    if suites and not args.no_rollup:
        dp = payloads.get("dispatch_policy", {})
        carried = {}
        if only:
            # a partial (--only) run refreshes only its own suites: merge into
            # the existing same-scale roll-up so the other recorded suite
            # timings (the PR-over-PR trajectory) are not silently dropped
            try:
                with open(common.rollup_path()) as f:
                    prev = json.load(f)
            except (OSError, ValueError):
                prev = {}
            if prev.get("scale") == args.scale:
                suites = {**prev.get("suites", {}), **suites}
                carried = {k: prev.get(k)
                           for k in ("graph", "phases", "nlcc_wave",
                                     "sharded_prune", "enumeration",
                                     "distributed_join", "load_balance",
                                     "multi_tenant", "query_plan",
                                     "resilience", "policy")}
        path = common.write_rollup(
            suites, args.scale,
            graph=dp.get("graph") or carried.get("graph"),
            phases=dp.get("phase_breakdown") or carried.get("phases"),
            nlcc_wave=dp.get("nlcc_wave") or carried.get("nlcc_wave"),
            sharded_prune=(payloads.get("strong_scaling", {}).get("sharded_prune")
                           or carried.get("sharded_prune")),
            enumeration=dp.get("enumeration") or carried.get("enumeration"),
            distributed_join=(
                payloads.get("distributed_join", {}).get("rollup")
                or carried.get("distributed_join")),
            load_balance=(payloads.get("load_balance", {}).get("rollup")
                          or carried.get("load_balance")),
            multi_tenant=(payloads.get("multi_tenant", {}).get("rollup")
                          or carried.get("multi_tenant")),
            query_plan=(payloads.get("query_plan", {}).get("rollup")
                        or carried.get("query_plan")),
            resilience=(payloads.get("resilience", {}).get("rollup")
                        or carried.get("resilience")),
            policy_fallback=carried.get("policy"),
        )
        print(f"roll-up -> {path}")

    print(f"\n{len(failures)} benchmark failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
