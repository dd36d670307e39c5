"""Shared arithmetic of the program-span readers: the spans the program's
recorder (`repro.obs`) kept for the window's answered queries.

A query's spans are its root `prune` span, which lies inside the query's
[submit, done] on the same clock, and every span that shares the root's id
as `query`. Nothing is read where the program records no spans, where no
query was answered, where the roots are not one per answered query, or
where the recorder's ring dropped a span of the window.
"""
from __future__ import annotations


def window_spans(record: dict):
    """(the window's spans, number of answered queries), or None."""
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    answered = [r for r in record["records"] if r["status"] == "ok"]
    if not answered or not obs.intact_since(min(r["submit"] for r in answered)):
        return None
    spans = obs.spans()
    roots = {s.id for s in spans if s.name == "prune" and s.parent is None and any(
        r["submit"] <= s.t0 and s.t1 <= r["done"] for r in answered)}
    if len(roots) != len(answered):
        return None
    return [s for s in spans if s.query in roots], len(answered)


def per_query(record: dict, value):
    """Sum of `value(span)` over the window's spans, per answered query."""
    got = window_spans(record)
    if got is None:
        return None
    spans, n = got
    return sum(value(s) for s in spans) / n


def host_seconds(spans) -> float:
    """Summed seconds of the spans of kind host that no other span of kind
    host encloses, less the device reads (`host.readback`) inside them."""
    by_id = {s.id: s for s in spans}

    def host_above(s):
        p = by_id.get(s.parent)
        while p is not None and p.attrs.get("kind") != "host":
            p = by_id.get(p.parent)
        return p

    total = 0.0
    for s in spans:
        if s.attrs.get("kind") == "host" and host_above(s) is None:
            total += s.seconds
        elif s.name == "host.readback" and host_above(s) is not None:
            total -= s.seconds
    return total
