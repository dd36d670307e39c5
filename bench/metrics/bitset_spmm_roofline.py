"""`bitset_spmm`'s share of its roofline over the window: the least time
the sweeps' needed work takes at the chip's peaks (bench/work.py), over the
summed device time of the kernel's operations in the trace.

Sweeps counted: each answered query's LCC runs `lcc_iterations` sweeps
(the program's counter). The first sweep of a query's first LCC call
starts from the initial state, in which every arc is active and every
vertex with an arc is a source; it is counted at that work. Every other
sweep is counted at the least work any sweep has (writing its n rows), as
its active arcs are not read here. So the share is a lower bound of the
true one. The kernel's operations are the device operations whose name
or metadata names `bitset_spmm`; where none does and `bitset_spmm` is the
only kernel the window's queries dispatched compiled (the program's
dispatch counts), they are the Mosaic custom calls: the operations whose
HLO text (the event's name on a TPU) or metadata names `tpu_custom_call`.
Where the trace holds none, nothing is read.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import work  # noqa: E402

KERNEL = "bitset_spmm"


def kernel_seconds(trace: dict, records) -> float:
    text = trace.get("op_text", {})
    named = [n for n in trace["op_seconds"] if KERNEL in n or KERNEL in text.get(n, "")]
    if not named:
        compiled = {k for r in records if r["status"] == "ok" and r["answer"].counters
                    for k in r["answer"].counters.get("kernel_dispatches", {})
                    if k.endswith(":pallas")}
        if compiled == {f"{KERNEL}:pallas"}:
            named = [n for n in trace["op_seconds"]
                     if "tpu_custom_call" in n or "tpu_custom_call" in text.get(n, "")]
    return sum(trace["op_seconds"][n] for n in named)


def read(record):
    tr, peak = record.get("trace"), record.get("peak")
    if not tr or peak is None:
        return None
    t_kernel = kernel_seconds(tr, record["records"])
    if t_kernel <= 0:
        return None
    g = record["graph"]
    total_bytes = total_ops = 0
    for r in record["records"]:
        if r["status"] != "ok" or not r["answer"].counters:
            continue
        sweeps = int(r["answer"].counters.get("lcc_iterations", 0))
        if sweeps <= 0:
            continue
        w = work.words_per_vertex(len(r["labels"]))
        b, o = work.sweep_work(g["n"], w, g["m"], g["sources"])
        b_rest, _ = work.sweep_work(g["n"], w, 0, 0)
        total_bytes += b + (sweeps - 1) * b_rest
        total_ops += o
    if total_bytes == 0:
        return None
    least, _ = work.least_seconds(total_bytes, total_ops, peak)
    return 100.0 * least / t_kernel
