"""Bytes read back from the device per query inside `prune`, in MB (1e6
bytes): the `readback_bytes` counters of the program's `host.readback`
spans (`repro.obs.to_host`), over the window's answered queries."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import per_query  # noqa: E402


def read(record):
    v = per_query(record, lambda s: s.counters.get("readback_bytes", 0))
    return None if v is None else v / 1e6
