"""The check against the reference fails runs whose timed path is broken:
the control (local constraint checking alone) and each fault this cell can
have, driven through the rest of a real run at a tiny size on the CPU."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts the benchmark and the program on the path)

import pytest

import control
import harness
from benchtest import CPU_DEVICE, LOCAL, SERVED, make_tiny_root


def _ids(value):
    return value[0] if isinstance(value, tuple) else value


@pytest.mark.parametrize("cell,fault", [
    (LOCAL, "control"), (LOCAL, "stale_state"), (LOCAL, "altered_answer"),
    (SERVED, "half_batch"), (SERVED, "altered_answer")], ids=_ids)
def test_broken_timed_path_is_not_correct(tmp_path, cell, fault):
    root = make_tiny_root(str(tmp_path), cell=cell)
    with control.faulted(fault) as patch:
        out = harness.run("tiny.cell", 77, 1.0, False, root=root,
                          device=CPU_DEVICE, patch=patch)
    assert out["correct"] is False
    assert out["checks"]["wrong_vertices"]["value"] > 0


@pytest.mark.parametrize("cell", [LOCAL, SERVED], ids=_ids)
def test_sound_run_is_correct(tmp_path, cell):
    root = make_tiny_root(str(tmp_path), cell=cell)
    out = harness.run("tiny.cell", 78, 1.0, False, root=root, device=CPU_DEVICE)
    assert out["correct"] is True, out["checks"]
