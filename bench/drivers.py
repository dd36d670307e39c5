"""The system under test, one driver per engine a configuration names.

A driver builds the engine from a generated graph (set-up), then answers
queries the way its users call it. An answer is the pruned solution
subgraph on the host: the indices of the matched vertices and of the
matched arcs (arcs in the (dst, src) order the graph was built in).

    local_blocked  `repro.core.pipeline.prune` on the local backend with
                   the blocked structure (the bitset kernels' path), one
                   query at a time
    served_batch   `repro.serve.graph_query.GraphQueryEngine` in prune
                   mode: queries are submitted, `pump` launches due
                   batches through `repro.core.batch.prune_batch`
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Dict, List, Optional

import numpy as np
import jax

import graph500
from spans import span
from traffic import Query

# the system under test, from the checkout's src/
SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
from repro.core import pipeline  # noqa: E402
from repro.core.template import Template  # noqa: E402
from repro.graph import blocked, structs  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.serve import graph_query  # noqa: E402


@dataclasses.dataclass
class Answer:
    vertices: np.ndarray       # int64 indices of matched vertices
    arcs: np.ndarray           # int64 indices of matched arcs
    status: str = "ok"
    phases: Optional[List] = None     # [(phase, seconds)] of the prune
    counters: Optional[Dict] = None   # program counters of the prune
    wait_s: Optional[float] = None    # served: queue wait
    batch: Optional[int] = None       # served: batch id


def load_policy(bench_dir: str, name: Optional[str]):
    if name is None:
        return None
    return registry.DispatchPolicy.load(os.path.join(bench_dir, "policies", f"{name}.json"))


class LocalBlocked:
    """`prune()` on the local backend with `blocked=`, one query at a time."""

    def __init__(self, cfg: dict, seed: int, bench_dir: str, parts: Dict[str, float]):
        with span("bench.generate", parts):
            self.graph = graph500.generate(seed, **cfg["graph"])
            jax.block_until_ready(self.graph.src)
        with span("bench.host_copy", parts):
            self.dg = structs.DeviceGraph(
                n=self.graph.n, src=self.graph.src, dst=self.graph.dst,
                labels=self.graph.labels)
            self.src_host = np.asarray(self.graph.src)
            self.dst_host = np.asarray(self.graph.dst)
        pk = dict(cfg["prune"])
        with span("bench.blocked_build", parts):
            self.blocked = blocked.build_blocked_structure(
                self.src_host, self.dst_host, self.graph.n, bn=pk.pop("bn"))
            jax.block_until_ready(self.blocked.device_arrays)
        self.info = {"blocks": self.blocked.nnzb,
                     "mask_bytes": self.blocked.nnzb * self.blocked.words_per_block * 4}
        self.prune_kw = pk
        registry.set_policy(load_policy(bench_dir, cfg.get("policy")))

    def answer(self, q: Query) -> Answer:
        t = Template(list(q.labels), list(q.edges))
        with span("bench.prune"):
            res = pipeline.prune(
                self.dg, t, label_freq=self.graph.label_freq,
                blocked=self.blocked, **self.prune_kw)
        with span("bench.readback"):
            vm = np.asarray(res.state.omega).any(axis=1)
            ea = np.asarray(res.state.edge_active)
        arcs = np.flatnonzero(ea & vm[self.src_host] & vm[self.dst_host])
        st = res.stats
        counters = {k: st[k] for k in ("lcc_iterations", "lcc_calls", "kernel_dispatches")
                    if k in st}
        return Answer(np.flatnonzero(vm), arcs,
                      phases=[(p.phase, p.seconds) for p in res.phases],
                      counters=counters)

    def close(self) -> None:
        registry.set_policy(None)


class ServedBatch:
    """`GraphQueryEngine` in prune mode; `submit` queues, `pump` runs due
    batches and returns their answers."""

    def __init__(self, cfg: dict, seed: int, bench_dir: str, parts: Dict[str, float]):
        with span("bench.generate", parts):
            self.graph = graph500.generate(seed, **cfg["graph"])
            jax.block_until_ready(self.graph.src)
        with span("bench.host_copy", parts):
            self.src_host = np.asarray(self.graph.src)
            self.dst_host = np.asarray(self.graph.dst)
            host = structs.Graph(
                n=self.graph.n, src=self.src_host, dst=self.dst_host,
                labels=np.asarray(self.graph.labels))
        with span("bench.engine_start", parts):
            self.engine = graph_query.GraphQueryEngine(
                host, policy=load_policy(bench_dir, cfg.get("policy")),
                **cfg["engine_kw"])
        self.info = {}
        self._query_of: Dict[int, int] = {}

    def submit(self, q: Query, tag: int) -> None:
        t = Template(list(q.labels), list(q.edges))
        with span("bench.submit"):
            qid = self.engine.submit(t, mode=graph_query.MODE_PRUNE)
        self._query_of[qid] = tag

    def pump(self, force: bool = False):
        """[(tag, Answer)] of every query a launched batch answered."""
        with span("bench.pump"):
            results = self.engine.pump(force=force)
        out = []
        with span("bench.readback"):
            for r in results:
                tag = self._query_of.pop(r.query_id)
                if r.status != "ok" or r.result is None:
                    out.append((tag, Answer(np.zeros(0, np.int64), np.zeros(0, np.int64),
                                            status=r.status, wait_s=r.wait_s,
                                            batch=r.batch_id)))
                    continue
                st = r.result.state
                vm = np.asarray(st.omega).any(axis=1)
                ea = np.asarray(st.edge_active)
                arcs = np.flatnonzero(ea & vm[self.src_host] & vm[self.dst_host])
                out.append((tag, Answer(np.flatnonzero(vm), arcs, wait_s=r.wait_s,
                                        batch=r.batch_id,
                                        counters={"batch_seconds": r.seconds})))
        return out

    def close(self) -> None:
        registry.set_policy(None)


DRIVERS = {"local_blocked": LocalBlocked, "served_batch": ServedBatch}
