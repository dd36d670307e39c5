"""The trace reduction: busy time as the union of device operations inside
the window span, each operation's summed time, and the idle gaps named by
the host span open during them, on synthetic planes laid out as the JAX
profiler lays out a TPU trace."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

from types import SimpleNamespace as NS

import pytest

import xplane

MS = 1_000_000


def _ev(name, start_ms, dur_ms, stats=()):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS, stats=list(stats))


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 100), _ev("bench.prune", 0, 60),
        _ev("bench.readback", 60, 40), _ev("other", 0, 100)])])
    ops = [_ev("fusion.1", 5, 10), _ev("fusion.1", 10, 10),   # overlap: busy 5..20
           _ev("bitset_spmm", 30, 20, [("long_name", "custom-call bitset_spmm")]),
           _ev("copy.2", 90, 20)]                               # cut at 100
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="Steps", events=[_ev("step", 0, 100)])])
    return [host, dev]


def test_reduce_synthetic_planes():
    s = xplane.reduce_planes(_planes())
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.045)      # 15 + 20 + 10 ms
    assert s["op_seconds"]["fusion.1"] == pytest.approx(0.02)
    assert s["op_seconds"]["copy.2"] == pytest.approx(0.01)
    assert "bitset_spmm" in s["op_text"]["bitset_spmm"]
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.readback", pytest.approx(0.04)]     # 50..90
    assert ["bench.prune", pytest.approx(0.01)] in gaps          # 20..30
    assert s["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.02)]


def test_no_window_or_no_device_is_an_error():
    host, dev = _planes()
    with pytest.raises(ValueError):
        xplane.reduce_planes([dev])
    with pytest.raises(ValueError):
        xplane.reduce_planes([host])
