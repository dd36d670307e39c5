"""The least-work count behind `bitset_spmm_roofline`: it reads the graph,
the query and the state, never a block structure, and the reader turns it
into a share only where the trace has the kernel."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import inspect

import numpy as np
import pytest

import harness
import work
from benchtest import BENCH
from repro.graph.blocked import build_blocked_structure

PEAK = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def test_sweep_work_counts_rows_sources_and_arcs():
    assert work.sweep_work(n=1000, words=1, active_arcs=80, sources=300) == (
        4 * (1000 + 300) + 10, 80)
    assert work.sweep_work(n=10, words=2, active_arcs=0, sources=0) == (80, 0)
    assert work.words_per_vertex(4) == 1 and work.words_per_vertex(33) == 2


def test_count_does_not_depend_on_block_size():
    rng = np.random.default_rng(0)
    n, m = 4096, 60000
    src = rng.integers(0, n, m)
    dst = np.sort(rng.integers(0, n, m))
    layouts = {bn: build_blocked_structure(src, dst, n, bn=bn).nnzb for bn in (32, 64, 128)}
    assert len(set(layouts.values())) == 3            # the layouts differ ...
    sources = np.count_nonzero(np.bincount(src, minlength=n))
    counts = {bn: work.sweep_work(n, 1, m, sources) for bn in layouts}
    assert len(set(counts.values())) == 1             # ... the needed work does not
    params = set(inspect.signature(work.sweep_work).parameters)
    assert params == {"n", "words", "active_arcs", "sources"}


def test_least_seconds_names_its_bound():
    t, bound = work.least_seconds(819e9, 1.0, PEAK)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(1.0, 393e12 * 2, PEAK)
    assert bound == "ops" and t == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    assert work.load_peak(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peak(BENCH, "TPU v9 imaginary")


class _Ans:
    def __init__(self, sweeps):
        self.counters = {"lcc_iterations": sweeps}
        self.phases = []


def _record(kernel_s):
    trace = {"op_seconds": {"fusion.1": 3.0, "bitset_spmm_call": kernel_s},
             "op_text": {}, "busy_s": 4.0, "window_s": 10.0}
    recs = [{"status": "ok", "labels": [1, 2, 3], "answer": _Ans(3)},
            {"status": "ok", "labels": [1, 2, 3, 4], "answer": _Ans(1)},
            {"status": "error", "labels": [1, 2, 3], "answer": None}]
    return {"trace": trace, "peak": PEAK, "records": recs,
            "graph": {"n": 1000, "m": 16000, "sources": 900}}


def test_roofline_reader():
    read = harness.load_reader(BENCH, "bitset_spmm_roofline")
    first, _ = work.sweep_work(1000, 1, 16000, 900)
    rest, _ = work.sweep_work(1000, 1, 0, 0)
    least = (2 * first + 2 * rest) / PEAK["hbm_bytes_per_s"]
    assert read(_record(0.5)) == pytest.approx(100 * least / 0.5)
    assert read(_record(0.0)) is None                  # no kernel in the trace
    assert read(dict(_record(0.5), trace=None)) is None
    idle = harness.load_reader(BENCH, "device.idle_pct")
    assert idle(_record(0.5)) == pytest.approx(60.0)


@pytest.mark.parametrize("marked", ["metadata", "name"])
def test_roofline_reader_finds_mosaic_calls_when_only_the_kernel_ran(marked):
    """The Mosaic call is named in its metadata, or, as a TPU's trace has it,
    in the operation's own name (its HLO text)."""
    read = harness.load_reader(BENCH, "bitset_spmm_roofline")
    rec = _record(0.0)
    del rec["trace"]["op_seconds"]["bitset_spmm_call"]
    call = 'custom_call_target="tpu_custom_call"'
    op = f"%body.3 = custom-call(), {call}" if marked == "name" else "custom-call.7"
    rec["trace"]["op_seconds"][op] = 0.5
    rec["trace"]["op_text"][op] = call if marked == "metadata" else "device_duration_ps=5"
    for r in rec["records"][:2]:
        r["answer"].counters["kernel_dispatches"] = {"bitset_spmm:pallas": 1,
                                                      "bitset_wave:ref": 9}
    assert read(rec) == pytest.approx(read(_record(0.5)))
    rec["records"][0]["answer"].counters["kernel_dispatches"]["bitset_wave:pallas"] = 1
    assert read(rec) is None                       # two kernels: no attribution
