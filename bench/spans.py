"""Host spans of the benchmark's own calls into each layer.

Each span is a `jax.profiler.TraceAnnotation`, so a traced run shows what
the host was doing during every gap on the device; `parts`, where given,
adds the span's host-clock seconds to its name.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

import jax


@contextlib.contextmanager
def span(name: str, parts: Optional[Dict[str, float]] = None):
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    if parts is not None:
        parts[name] = parts.get(name, 0.0) + time.perf_counter() - t0
