"""Local Constraint Checking (paper §3/§4, Alg. 3 + 4).

One iteration, expressed as a dense edge sweep (the TPU adaptation of the
HavoqGT `alive` visitor wave):

  1. messages:   each active arc (u -> v) carries omega(u) — packed words on
                 the distributed path, boolean planes here,
  2. aggregate:  M[v, q'] = OR over active in-arcs of omega(u)[q']
                 C[v, q'] = #   over active in-arcs of omega(u)[q']   (counts,
                 only materialized for templates with same-label multiplicity),
  3. vertex elim: keep q in omega(v) iff every template neighbor q' of q is
                 covered by M[v] and per-label distinct-neighbor counts meet
                 the template's multiplicity (Alg. 3 line 16),
  4. edge elim:  arc stays iff endpoints stay and some template edge (qi, qj)
                 has qi in omega(u), qj in omega(v) (Alg. 3 line 9).

Iterated to fixpoint by `lcc_fixpoint` (Alg. 3's do-while). All shapes static;
jitted once per (graph, template) pair.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.graph.structs import DeviceGraph
from repro.graph import segment_ops
from repro.core.template import Template
from repro.core.state import PruneState


class TemplateDev:
    """Template constants staged to device once (static per pipeline run)."""

    def __init__(self, template: Template):
        self.n0 = template.n0
        self.adj0 = jnp.asarray(template.adjacency_matrix())  # bool[n0, n0]
        # multiplicity: req[q, l_idx] over the template's distinct neighbor labels
        mult = template.multiplicity_requirements()
        counted = sorted({l for q, c in mult.items() for l, k in c.items() if k >= 1})
        self.counted_labels = jnp.asarray(counted, dtype=jnp.int32) if counted else None
        req = np.zeros((template.n0, max(len(counted), 1)), dtype=np.int32)
        for q, c in mult.items():
            for li, l in enumerate(counted):
                req[q, li] = c.get(l, 0)
        self.req = jnp.asarray(req)  # int32[n0, C]
        # label_of_counted[q] -> bool[n0, C]: template vertex q' has counted label c
        has = np.zeros((template.n0, max(len(counted), 1)), dtype=bool)
        for q in range(template.n0):
            for li, l in enumerate(counted):
                has[q, li] = int(template.labels[q]) == l
        self.vertex_has_counted_label = jnp.asarray(has)  # bool[n0, C]
        self.needs_counts = bool(
            any(k >= 2 for c in mult.values() for k in c.values())
        )


def lcc_iteration(
    dg: DeviceGraph,
    tdev: TemplateDev,
    state: PruneState,
) -> Tuple[PruneState, jnp.ndarray]:
    """One LCC sweep. Returns (new_state, changed)."""
    n, n0 = state.omega.shape
    src, dst = dg.src, dg.dst

    # 1. messages over active arcs
    msgs = jnp.take(state.omega, src, axis=0) & state.edge_active[:, None]

    # 2a. OR aggregation: which template vertices are covered among v's neighbors
    M = segment_ops.segment_or_bool(msgs, dst, n)  # bool[n, n0]

    # 3. neighborhood requirement per candidate q: adj0[q] subseteq M[v]
    #    missing[v, q] = exists q' with adj0[q, q'] and not M[v, q']
    missing = (~M).astype(jnp.float32) @ tdev.adj0.T.astype(jnp.float32)  # [n, n0]
    ok = missing < 0.5

    if tdev.needs_counts:
        # 2b. distinct active neighbors per counted label:
        # neighbor u contributes to counted label c iff omega(u) intersects the
        # template vertices carrying label c.
        ind = (
            msgs.astype(jnp.float32) @ tdev.vertex_has_counted_label.astype(jnp.float32)
            > 0.5
        )  # bool[m, C]
        cnt = segment_ops.segment_sum(ind.astype(jnp.int32), dst, n)  # [n, C]
        meets = jnp.all(cnt[:, None, :] >= tdev.req[None, :, :], axis=-1)  # [n, n0]
        ok = ok & meets

    omega = state.omega & ok

    # 4. edge elimination: some template arc (qi -> qj) with qi in omega(u), qj in omega(v)
    side = omega.astype(jnp.float32) @ tdev.adj0.astype(jnp.float32)  # [n, n0]
    compat = jnp.sum(jnp.take(side, src, axis=0) * jnp.take(omega, dst, axis=0).astype(jnp.float32), axis=-1) > 0.5
    edge_active = state.edge_active & compat

    # a vertex with no active in-arc cannot match any q with degree >= 1
    has_edge = segment_ops.segment_or_bool(
        edge_active[:, None], dst, n
    )[:, 0]
    deg_pos = jnp.asarray(jnp.any(tdev.adj0, axis=1))  # [n0] template degree >= 1
    omega = omega & (~deg_pos[None, :] | has_edge[:, None])

    changed = jnp.logical_or(
        jnp.any(omega != state.omega), jnp.any(edge_active != state.edge_active)
    )
    return PruneState(omega=omega, edge_active=edge_active), changed


def lcc_iteration_packed(
    dg: DeviceGraph,
    tdev: TemplateDev,
    state: PruneState,
    blocked,
    force_pallas: bool = False,
) -> Tuple[PruneState, jnp.ndarray]:
    """One LCC sweep through the packed-word path (the bitset_spmm kernel on
    TPU; 8x fewer aggregation bytes than the boolean-plane reference).

    Falls back to the reference for templates needing same-label multiplicity
    counts (the OR kernel carries no counts)."""
    if tdev.needs_counts:
        return lcc_iteration(dg, tdev, state)
    from repro.core.state import pack_bits, unpack_bits
    from repro.kernels import ops as kops

    n, n0 = state.omega.shape
    packed = pack_bits(state.omega)
    m_packed = kops.bitset_or_aggregate(
        packed, dg.src, dg.dst, n, state.edge_active,
        blocked=blocked, force_pallas=force_pallas)
    M = unpack_bits(m_packed, n0)

    missing = (~M).astype(jnp.float32) @ tdev.adj0.T.astype(jnp.float32)
    omega = state.omega & (missing < 0.5)
    side = omega.astype(jnp.float32) @ tdev.adj0.astype(jnp.float32)
    compat = jnp.sum(
        jnp.take(side, dg.src, axis=0)
        * jnp.take(omega, dg.dst, axis=0).astype(jnp.float32), axis=-1) > 0.5
    edge_active = state.edge_active & compat
    has_edge = segment_ops.segment_or_bool(edge_active[:, None], dg.dst, n)[:, 0]
    deg_pos = jnp.asarray(jnp.any(tdev.adj0, axis=1))
    omega = omega & (~deg_pos[None, :] | has_edge[:, None])
    changed = jnp.logical_or(
        jnp.any(omega != state.omega), jnp.any(edge_active != state.edge_active))
    return PruneState(omega=omega, edge_active=edge_active), changed


def _fixpoint(iter_fn, state: PruneState, max_iters: int,
              stats: Optional[dict], extra_stat: Optional[str] = None
              ) -> PruneState:
    """Shared do-while driver: device while_loop so the whole fixpoint is a
    single XLA computation (one dispatch). `iter_fn(state) -> (state, changed)`.

    With `stats`, the loop also sums the active arcs at the start of each
    sweep (as a uint32 pair, low word and carries: the sum passes 2**32
    within a few hundred sweeps at tens of millions of arcs). The sum comes
    back in the same read as the sweep count, as the open span's
    `active_arcs`, beside `stepped_arcs`: the arcs the sweeps' per-arc
    passes go over, every arc in every sweep."""
    count = stats is not None

    def cond(carry):
        st, changed, n = carry
        return jnp.logical_and(changed, n[0] < max_iters)

    def body(carry):
        st, _, n = carry  # n = [sweeps, active arcs low word, carries]
        if count:
            lo = n[1] + jnp.sum(st.edge_active, dtype=jnp.uint32)
            n = jnp.stack([n[0], lo, n[2] + (lo < n[1]).astype(jnp.uint32)])
        st2, changed = iter_fn(st)
        return st2, changed, n.at[0].add(1)

    init = (state, jnp.asarray(True), jnp.zeros(3, jnp.uint32))
    final_state, _, n = jax.lax.while_loop(cond, body, init)
    if count:
        it, lo, hi = (int(v) for v in obs.to_host(n, "lcc.sweeps"))
        stats["lcc_iterations"] = stats.get("lcc_iterations", 0) + it
        obs.count("active_arcs", (hi << 32) + lo)
        obs.count("stepped_arcs", it * state.edge_active.shape[0])
        stats["lcc_calls"] = stats.get("lcc_calls", 0) + 1
        if extra_stat is not None:
            stats[extra_stat] = stats.get(extra_stat, 0) + 1
    return final_state


def lcc_fixpoint(
    dg: DeviceGraph,
    tdev: TemplateDev,
    state: PruneState,
    max_iters: int = 1000,
    stats: Optional[dict] = None,
) -> PruneState:
    """Iterate LCC to fixpoint (Alg. 3 do-while)."""
    return _fixpoint(
        lambda st: lcc_iteration(dg, tdev, st), state, max_iters, stats)


LCC_ROUTE = "prune.lcc"


def lcc_route_bucket(state: PruneState, dg: DeviceGraph):
    """Shape bucket for the packed-vs-unpacked LCC routing decision: vertex
    count and arc count dominate the sweep cost (the packed width is ~1 word
    for every template since n0 <= 64)."""
    from repro.kernels import registry
    return registry.shape_bucket(state.omega.shape[0], dg.m)


def lcc_resolved_route(
    state: PruneState,
    dg: DeviceGraph,
    tdev: TemplateDev,
    blocked,
    *,
    collect_stats: bool = False,
    force_pallas: bool = False,
) -> str:
    """The packed-vs-unpacked route the LCC fixpoint will actually take — the
    single source of truth for both execution (`lcc_fixpoint_packed`) and
    reporting (`prune`'s stats["dispatch_routes"]). Capability gates come
    first (no blocked structure, per-iteration message counting, or
    multiplicity counts force the boolean planes); within the packed-capable
    envelope force_pallas pins packed (parity tests) and otherwise the tuned
    policy decides, defaulting to packed — a caller passing `blocked` opted
    in, matching the pre-policy behavior."""
    from repro.kernels import registry

    if blocked is None or collect_stats or tdev.needs_counts:
        return registry.ROUTE_UNPACKED
    if force_pallas:
        return registry.ROUTE_PACKED
    return registry.resolve_route(
        LCC_ROUTE, lcc_route_bucket(state, dg),
        default=registry.ROUTE_PACKED,
        allowed=(registry.ROUTE_PACKED, registry.ROUTE_UNPACKED))


def lcc_fixpoint_packed(
    dg: DeviceGraph,
    tdev: TemplateDev,
    state: PruneState,
    blocked,
    max_iters: int = 1000,
    stats: Optional[dict] = None,
    force_pallas: bool = False,
) -> PruneState:
    """LCC fixpoint through the packed-word sweep (the bitset_spmm kernel via
    the registry dispatch on TPU, its oracle elsewhere).

    Degrades to the boolean-plane `lcc_fixpoint` when `lcc_resolved_route`
    says so: no blocked structure, same-label multiplicity counts (the OR
    kernel carries no counts), or the tuned dispatch policy routing this
    shape bucket to the unpacked sweep. `force_pallas` pins the packed
    kernel path for parity tests."""
    from repro.kernels import registry

    route = lcc_resolved_route(
        state, dg, tdev, blocked, force_pallas=force_pallas)
    if route == registry.ROUTE_UNPACKED:
        if stats is not None and blocked is not None and not tdev.needs_counts:
            stats["lcc_routed_unpacked"] = stats.get(
                "lcc_routed_unpacked", 0) + 1
        return lcc_fixpoint(dg, tdev, state, max_iters, stats)
    return _fixpoint(
        lambda st: lcc_iteration_packed(
            dg, tdev, st, blocked, force_pallas=force_pallas),
        state, max_iters, stats, extra_stat="lcc_packed_calls")
