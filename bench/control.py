"""Runs of a cell with the timed path broken underneath, to show that the
check against the reference fails them. Not part of a benchmark run.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30 \
        [--fault control]

Faults (`FAULTS`):

    control         the program with its non-local constraint checking
                    switched off (`prune(..., constraints=[])`): local
                    constraint checking alone, which the paper shows keeps
                    false positives (unrolled cycles), so the exactness
                    guarantee of the configuration is broken
    stale_state     the pruning loop returns its state unchanged: the
                    answer is the initial candidacy (every vertex of a
                    template label, every arc)
    altered_answer  one vertex of every answer flipped where it is produced
    half_batch      a served batch answers only its first half of lanes;
                    the rest come back empty

Each seed prints its checks; the last line is a JSON list of the runs'
results. On a TPU only, as run.py.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _control(driver):
    driver.prune_kw = dict(driver.prune_kw, constraints=[])


def _stale_state(driver):
    from repro.core import pipeline

    pipeline._Driver.run = lambda self: None


def _altered_answer(driver):
    import numpy as np

    def alter(ans):
        ans.vertices = np.setxor1d(ans.vertices, [0])
        return ans

    if hasattr(driver, "answer"):
        inner = driver.answer
        driver.answer = lambda q: alter(inner(q))
    else:
        inner_pump = driver.pump
        driver.pump = lambda force=False: [(t, alter(a)) for t, a in inner_pump(force=force)]


def _half_batch(driver):
    import numpy as np

    inner = driver.pump

    def pump(force=False):
        got = inner(force=force)
        keep = (len(got) + 1) // 2
        for _, ans in got[keep:]:
            ans.vertices = np.zeros(0, np.int64)
            ans.arcs = np.zeros(0, np.int64)
        return got

    driver.pump = pump


FAULTS = {"control": _control, "stale_state": _stale_state,
          "altered_answer": _altered_answer, "half_batch": _half_batch}


@contextlib.contextmanager
def faulted(name: str):
    """The patch for `harness.run`; restores what it replaced on exit."""
    from repro.core import pipeline

    saved = pipeline._Driver.run
    try:
        yield FAULTS[name]
    finally:
        pipeline._Driver.run = saved


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default="control")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    import run as run_mod

    jax = run_mod.configure_jax()
    import harness

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control.py: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": 1}
    outs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        with faulted(args.fault) as patch:
            out = harness.run(args.workload, seed, args.seconds, False, root=ROOT,
                              device=device, patch=patch)
        print(json.dumps({"seed": seed, "fault": args.fault, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
        outs.append(out)
    print(json.dumps(outs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
