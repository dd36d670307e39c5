"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name
from BENCHMARK.json at the root of the checkout. The last line of standard
output is one JSON object: correct, attempted, failed, metrics, device
(and breakdown with --trace 1), then the numbers the answers were checked
on, each beside its limit. The run needs a TPU with at least as many chips
as the cell asks for; elsewhere it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the compile cache sits at a fixed path inside the checkout, so that only
# the first run of a cell there compiles
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")


def configure_jax():
    """JAX with the persistent compile cache at CACHE_DIR, keeping every
    program, however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    jax = configure_jax()
    import harness
    import work

    bench = harness.load_benchmark(ROOT)
    w_entry, _ = harness.cell_entries(bench, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(w_entry["chips"]):
        print(f"run.py: cell {args.workload} needs {w_entry['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    work.load_peak(BENCH_DIR, devs[0].device_kind)  # an unknown chip is an error
    used = devs[: int(w_entry["chips"])]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(used)}
    out = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                      root=ROOT, device=device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
