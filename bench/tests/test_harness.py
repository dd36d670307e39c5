"""The harness: cells, configurations, traffic and metrics are found by
name; the window's arithmetic; a whole run on the CPU at a tiny size."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import json
import os

import numpy as np
import pytest

import drivers
import harness
from benchtest import CPU_DEVICE, make_tiny_root, tiny_root  # noqa: F401


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeDriver:
    """Answers query i after `costs[i]` seconds of the fake clock."""

    def __init__(self, clock, costs):
        self.clock, self.costs, self.n = clock, costs, 0

    def answer(self, q):
        self.clock.t += self.costs[self.n % len(self.costs)]
        self.n += 1
        return drivers.Answer(np.zeros(0, np.int64), np.zeros(0, np.int64))


class FakeQuery:
    shape, labels, edges = "cycle3", (1, 2, 3), ((0, 1), (1, 2), (2, 0))


def test_window_closes_after_the_query_in_flight():
    clock = FakeClock()
    costs = [3.0, 4.0, 5.0, 30.0]
    records = []
    w0, w1 = harness.run_closed_loop_single(FakeDriver(clock, costs), [FakeQuery()],
                                            10.0, records, clock=clock)
    # submitted at 0, 3, 7 and 12 > 10 is not submitted: 3 queries, 12 s
    assert len(records) == 3 and w1 - w0 == pytest.approx(12.0)
    m = harness.window_metrics(records, w1 - w0)
    assert m["queries_per_s"] == pytest.approx(3 / 12.0)
    assert m["query_p50_s"] == pytest.approx(4.0)
    assert m["query_p95_s"] == pytest.approx(4.9)


def test_a_long_query_is_counted_whole():
    clock = FakeClock()
    records = []
    w0, w1 = harness.run_closed_loop_single(FakeDriver(clock, [2.0, 2.0, 2.0, 2.0, 50.0]),
                                            [FakeQuery()], 9.0, records, clock=clock)
    assert len(records) == 5 and w1 - w0 == pytest.approx(58.0)
    assert harness.window_metrics(records, w1 - w0)["queries_per_s"] == pytest.approx(5 / 58.0)


def test_p95_over_all_queries():
    recs = [{"status": "ok", "submit": 0.0, "done": float(x)} for x in range(1, 101)]
    recs.append({"status": "error", "submit": 0.0, "done": 1000.0})
    m = harness.window_metrics(recs, 200.0)
    assert m["query_p95_s"] == pytest.approx(95.05)
    assert m["query_p50_s"] == pytest.approx(50.5)
    assert m["queries_per_s"] == pytest.approx(0.5)


class FakeBatchDriver:
    """A batch of everything pending is answered per pump, after `cost` s."""

    def __init__(self, clock, cost):
        self.clock, self.cost, self.pending, self.batch = clock, cost, [], 0

    def submit(self, q, tag):
        self.pending.append(tag)

    def pump(self, force=False):
        if not self.pending:
            return []
        self.clock.t += self.cost
        out = [(t, drivers.Answer(np.zeros(0, np.int64), np.zeros(0, np.int64),
                                  batch=self.batch, counters={"batch_seconds": self.cost - 1}))
               for t in self.pending]
        self.pending, self.batch = [], self.batch + 1
        return out


def test_batched_window_counts_every_submitted_query():
    clock = FakeClock()
    records, pumps = [], []
    w0, w1 = harness.run_closed_loop_batched(FakeBatchDriver(clock, 4.0), [FakeQuery()],
                                             8, 10.0, records, pumps, clock=clock)
    # batches of 8 submitted at 0, 4 and 8; the third ends at 12
    assert len(records) == 24 and w1 - w0 == pytest.approx(12.0)
    assert [p["seconds"] for p in pumps] == [4.0, 4.0, 4.0]
    assert all(r["done"] - r["submit"] == pytest.approx(4.0) for r in records)


def test_throwaway_cell_config_and_metric_found_by_name(tmp_path):
    root = make_tiny_root(str(tmp_path))
    data = os.path.join(root, "bench")
    with open(os.path.join(data, "metrics", "throwaway.answers.py"), "w") as f:
        f.write("def read(record):\n    return len(record['records'])\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["per_layer"].append({"name": "throwaway.answers", "unit": "queries",
                               "better": "higher", "source": "host_clock",
                               "layer": "benchmark", "moves": "queries_per_s"})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    bench = harness.load_benchmark(root)
    w, c = harness.cell_entries(bench, "tiny.cell")
    assert c["file"] == "bench/configs/tiny.json"
    names = [m["name"] for m in harness.metrics_for(bench, "tiny.cell", "per_layer")]
    assert "throwaway.answers" in names
    assert harness.load_reader(data, "throwaway.answers")({"records": [1, 2]}) == 2
    with pytest.raises(KeyError):
        harness.cell_entries(bench, "no.such.cell")


def test_whole_run_on_cpu(tiny_root):
    out = harness.run("tiny.cell", 2**31 + 12345, 2.0, False, root=tiny_root,
                      device=CPU_DEVICE)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"query_p50_s", "query_p95_s", "queries_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["wrong_vertices"] == {"value": 0, "limit": 0}
