"""Shared arithmetic of the phase-trajectory readers."""
from __future__ import annotations


def mean_per_query(record: dict, match) -> float | None:
    """Sum of the seconds of the matching phase entries over every answered
    query of the window, divided by the number of those queries; None where
    no query carries a phase trajectory."""
    answered = [r for r in record["records"] if r["status"] == "ok"
                and r["answer"].phases is not None]
    if not answered:
        return None
    total = sum(s for r in answered for name, s in r["answer"].phases if match(name))
    return total / len(answered)
