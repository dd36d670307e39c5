"""Spans and counters of the prune path, on the profiler's clock.

`span(name, **attrs)` times a block on `time.perf_counter` and opens a
`jax.profiler.TraceAnnotation` of the same name, so a profiler trace shows
the span on the host plane, on the device trace's clock. Finished spans go
into a bounded ring that `spans()` returns in the order they finished. Each
record carries its `parent` span and its `query`: the id of the outermost
span open on its thread when it started (the root `prune` span of a
request).

`count(key, n)` adds to the innermost open span of the calling thread.
`to_host(x, what)` is the one device-to-host read of the prune path: a
`host.readback` span counting `readback_bytes` for every device array whose
bytes it moves. JAX's tracing, lowering and compile events (a program
loaded from the persistent compile cache among them) add the union of
their intervals, as they nest, to the innermost open span as `trace_s`.

A span of kind host (`span(..., kind="host")`) marks numpy or Python work
over arrays of the graph's size; it holds no fence.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Dict, List, Optional

import numpy as np
import jax

RING = 65536
# JAX's tracing, lowering and compile spans; a program loaded from the
# persistent cache is one backend compile with the retrieval inside it
TRACE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})


@dataclasses.dataclass
class Span:
    name: str
    id: int
    parent: Optional[int]
    query: int
    t0: float
    t1: float
    attrs: Dict
    counters: Dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_ring: "collections.deque[Span]" = collections.deque(maxlen=RING)
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_dropped = 0
_dropped_t1 = float("-inf")


def _stack() -> List[Span]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the block as one span; yields its record (t1 set on exit)."""
    global _dropped, _dropped_t1
    stack = _stack()
    sid = next(_ids)
    top = stack[-1] if stack else None
    rec = Span(name, sid, top.id if top else None, top.query if top else sid,
               0.0, 0.0, attrs, {})
    stack.append(rec)
    with jax.profiler.TraceAnnotation(name, **attrs):
        rec.t0 = time.perf_counter()
        try:
            yield rec
        finally:
            rec.t1 = time.perf_counter()
            stack.pop()
            with _lock:
                if len(_ring) == _ring.maxlen:
                    _dropped += 1
                    _dropped_t1 = max(_dropped_t1, _ring[0].t1)
                _ring.append(rec)


def count(key: str, n=1) -> None:
    """Add `n` to counter `key` of the innermost open span, if any."""
    stack = getattr(_local, "stack", None)
    if stack:
        c = stack[-1].counters
        c[key] = c.get(key, 0) + n


def to_host(x, what: str):
    """`np.asarray(x)` under a `host.readback` span. A tuple of arrays moves
    in the same one read and comes back as a tuple of numpy arrays. An array
    whose host copy JAX already holds moves nothing and counts nothing."""
    xs = x if isinstance(x, tuple) else (x,)
    if not any(isinstance(a, jax.Array) for a in xs):
        out = tuple(np.asarray(a) for a in xs)
    else:
        with span("host.readback", what=what):
            moved = [isinstance(a, jax.Array) and getattr(a, "_npy_value", None) is None
                     for a in xs]
            for a, mv in zip(xs, moved):
                if mv:
                    a.copy_to_host_async()
            out = tuple(np.asarray(a) for a in xs)
            if any(moved):
                count("readback_bytes", sum(int(o.nbytes) for o, mv in zip(out, moved) if mv))
    return out if isinstance(x, tuple) else out[0]


def spans() -> List[Span]:
    """The finished spans still in the ring, in the order they finished."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """How many finished spans the ring has dropped to make room."""
    return _dropped


def intact_since(t: float) -> bool:
    """True when no span that ended at or after `t` has left the ring. The
    ring drops the spans that finished first, so a span that started at or
    after `t` is then still there."""
    return _dropped_t1 < t


def reset() -> None:
    """Empty the ring (tests)."""
    global _dropped, _dropped_t1
    with _lock:
        _ring.clear()
        _dropped, _dropped_t1 = 0, float("-inf")


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    if event not in TRACE_EVENTS:
        return
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    # JAX's spans nest (a jit traced inside another's trace) and each
    # reports when it ends, inner first: count an outer one past its inner
    seen = getattr(_local, "seen", None)
    if seen is None:
        seen = _local.seen = []
    inner = [iv for iv in seen if iv[0] >= start and iv[1] <= end]
    new = (end - start) - sum(b - a for a, b in inner)
    seen[:] = [iv for iv in seen if not (iv[0] >= start and iv[1] <= end)][-63:]
    seen.append((start, end))
    count("trace_s", max(new, 0.0))


jax.monitoring.register_event_time_span_listener(_on_time_span)
