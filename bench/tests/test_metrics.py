"""The per-layer readers on a synthetic run record: phase trajectories per
answered query, batch seconds and the time around them, queue waits."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import pytest

import drivers
import harness
from benchtest import BENCH


def _ans(phases=None, wait=None, batch=None):
    import numpy as np

    return drivers.Answer(np.zeros(0, np.int64), np.zeros(0, np.int64),
                          phases=phases, wait_s=wait, batch=batch)


def test_phase_readers_average_over_answered_queries():
    recs = [
        {"status": "ok", "answer": _ans([("LCC", 2.0), ("NLCC-cycle", 1.0), ("LCC", 0.5),
                                         ("NLCC-tds", 0.25)])},
        {"status": "ok", "answer": _ans([("LCC", 1.5), ("NLCC-path", 3.0)])},
        {"status": "error", "answer": None},
    ]
    rec = {"records": recs}
    read = lambda name: harness.load_reader(BENCH, name)(rec)  # noqa: E731
    assert read("lcc.s_per_query") == pytest.approx(2.0)
    assert read("nlcc.s_per_query") == pytest.approx(2.0)
    assert read("tds.s_per_query") == pytest.approx(0.125)
    assert harness.load_reader(BENCH, "lcc.s_per_query")({"records": recs[2:]}) is None


def test_served_readers():
    recs = [{"status": "ok", "answer": _ans(wait=w, batch=b)}
            for w, b in ((0.1, 0), (0.3, 0), (5.0, 1), (0.2, 1))]
    pumps = [{"seconds": 10.0, "batches": [0], "batch_seconds": {0: 7.0}},
             {"seconds": 13.0, "batches": [1, 2], "batch_seconds": {1: 4.0, 2: 5.0}},
             {"seconds": 0.5, "batches": [], "batch_seconds": {}}]
    rec = {"records": recs, "pumps": pumps}
    read = lambda name: harness.load_reader(BENCH, name)(rec)  # noqa: E731
    assert read("serve.queue_wait_s") == pytest.approx(0.25)
    assert read("batch.prune_s") == pytest.approx(16.0 / 3)
    assert read("batch.outside_prune_s") == pytest.approx((3.0 + 4.0) / 3)
    assert harness.load_reader(BENCH, "batch.prune_s")({"records": [], "pumps": []}) is None
