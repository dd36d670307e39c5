"""Non-local Constraint Checking for cycle and path constraints (Alg. 5 + 6).

TPU adaptation of token passing: a *multi-source boolean frontier*
F_r[v, s] = "a token that originated at source s sits at v after r hops".
One hop is the same edge sweep as LCC (gather over arcs, OR by destination),
masked per hop by the candidacy of the walk's r-th template vertex.

Work aggregation (paper Alg. 6 line 14) is implicit and *maximal* here: the
boolean frontier can represent a (vertex, source, hop) at most once, so a
duplicate token can never be forwarded — the OR absorbs it. This is strictly
stronger aggregation than the unordered-set dedup in the paper.

Memory-pressure control (the paper's "ability to control processing rate"):
sources are processed in fixed-size waves (`wave` bits), bounding frontier
state at n x wave booleans per hop.

Cycle constraints: token must return to its source after |C0| hops
  -> survivor s iff F_L[source_s, s].
Path constraints: token must reach a *different* vertex with the same label
  -> survivor s iff exists v != source_s with F_L[v, s] (the paper's `ack`).

Wave execution (`verify_constraint`) is batched: every walk of a constraint
(all rotations of a cycle, both directions of a path) shares one candidacy
stack built from the constraint-entry omega, per-wave survivors accumulate
into a device-side `keep` plane, and the head-column eliminations are applied
on device — the wave loop's only host round-trips per constraint are the
head-candidacy read that sizes it and (under `count_messages`) one
message-count readback; the edge-prune pass that may run first reads back
more (see `verify_constraint`). Three tunable routes execute a wave:
`unpacked` boolean planes (scan-based hops), `packed` per-hop bitset_spmm
launches, and the `fused` multi-hop bitset_wave kernel (pack/unpack once per
wave, frontier resident across hops).

Where a constraint's waves run as XLA programs — the `unpacked` route, and
the `fused` route when `bitset_wave` resolves to its oracle (its VMEM gate
refuses the graph, or off-TPU) — every hop steps over each arc it is given.
So once per constraint the active arcs and the vertices they touch are
compacted into a graph of their own (`compact_active`, capacities padded to
powers of two) and all the constraint's waves run on it; `edge_active` does
not change inside the wave loop, and inactive arcs add nothing to an OR, so
the survivors are the same. The whole graph is kept where the capacity
would not be under m, and on the kernel routes (`packed`, and `fused` where
the kernel runs), which walk the whole graph's blocked structure.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.graph.structs import DeviceGraph
from repro.graph import segment_ops
from repro.core.template import NonLocalConstraint
from repro.core.state import PruneState


def _frontier_hop(
    dg: DeviceGraph,
    frontier: jnp.ndarray,  # bool[n, S]
    edge_active: jnp.ndarray,  # bool[m]
    cand_next: jnp.ndarray,  # bool[n] candidacy for the next walk vertex
    count_messages: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    msgs = jnp.take(frontier, dg.src, axis=0) & edge_active[:, None]
    agg = segment_ops.segment_or_bool(msgs, dg.dst, frontier.shape[0])
    nxt = agg & cand_next[:, None]
    n_msgs = jnp.sum(msgs) if count_messages else jnp.asarray(0)
    return nxt, n_msgs


def wave_batches(sources: np.ndarray, wave: int):
    """Pad wave-source ids into fixed-width batches (-1 = pad) — the one
    batching rule shared by the local and sharded wave executors, so every
    route sees identical static shapes and identical pad semantics."""
    for off in range(0, sources.size, wave):
        ids = sources[off: off + wave]
        pad = wave - ids.size
        idsp = (np.concatenate([ids, np.full(pad, -1, np.int64)])
                if pad else ids)
        yield idsp.astype(np.int32), int(ids.size)


NLCC_ROUTE = "prune.nlcc"

# Walk-direction choices a query plan may pin per constraint (core/planner.py).
# "default" is the paper's expansion — every rotation of a cycle, both
# directions of a path — and is what every untuned run executes. The others
# run a SUBSET of those walks: strictly cheaper, strictly weaker, and still
# sound (a true match certifies every walk, so skipping checks never prunes
# one). The planner only emits non-default directions when a complete-walk
# TDS phase runs last and restores exactness.
PLAN_DIRECTIONS = ("default", "fwd", "rev", "head")


def expand_walks(constraint: NonLocalConstraint, direction: str = "default"):
    """The walk set a direction choice executes — the ONE expansion rule
    shared by the local wave executor, the sharded backends, and the batched
    lane driver, so a plan means the same thing everywhere."""
    if constraint.is_cyclic:
        base = constraint.walk[:-1]
        if direction == "default":
            # a cycle constraint prunes the head only; verify every rotation
            return [
                tuple(base[i:] + base[:i]) + (base[i],)
                for i in range(len(base))
            ]
        if direction == "rev":
            rb = tuple(reversed(base))
            return [rb + (rb[0],)]
        return [tuple(base) + (base[0],)]  # "head"/"fwd": stored rotation only
    if direction in ("fwd", "head"):
        return [constraint.walk]
    if direction == "rev":
        return [tuple(reversed(constraint.walk))]
    return [constraint.walk, tuple(reversed(constraint.walk))]


def nlcc_route_bucket(state: PruneState, wave: int):
    """Shape bucket for packed-vs-unpacked NLCC wave routing: vertex count and
    wave width drive the per-hop cost (each hop moves n x wave frontier bits —
    wave/32 packed words per vertex)."""
    from repro.kernels import registry
    return registry.shape_bucket(state.omega.shape[0], wave)


def nlcc_resolved_route(
    state: PruneState,
    wave: int,
    blocked,
    *,
    count_messages: bool = False,
    force_pallas: bool = False,
) -> str:
    """The route CC/PC waves will actually take (packed / unpacked / fused) —
    the single source of truth for both execution (`verify_constraint`) and
    reporting (`prune`'s stats["dispatch_routes"]). Packed and fused waves
    need a blocked structure, a word-aligned wave, and no message counting
    (the packed OR absorbs duplicates before they can be counted); within
    that envelope force_pallas pins packed (parity tests) and otherwise the
    tuned policy picks the measured-fastest of the three, defaulting to the
    old hardcoded choice — packed on TPU where the kernel compiles, boolean
    planes elsewhere (off-TPU the per-hop packed route is the same survivors
    with extra pack/unpack per hop; the fused route pays that once per
    wave)."""
    from repro.kernels import compat, registry

    if blocked is None or count_messages or wave % 32 != 0:
        return registry.ROUTE_UNPACKED
    if force_pallas:
        return registry.ROUTE_PACKED
    untuned = (
        registry.ROUTE_PACKED if compat.on_tpu() else registry.ROUTE_UNPACKED
    )
    return registry.resolve_route(
        NLCC_ROUTE, nlcc_route_bucket(state, wave), default=untuned,
        allowed=(registry.ROUTE_PACKED, registry.ROUTE_UNPACKED,
                 registry.ROUTE_FUSED))


def _initial_frontier(
    n: int,
    cand0: jnp.ndarray,       # bool[n] candidacy of the walk head
    source_ids: jnp.ndarray,  # int32[S], -1 = pad
    safe_src: jnp.ndarray,    # int32[S] = clip(source_ids, 0, n-1)
) -> jnp.ndarray:
    """F_0: one token plane per wave source, seeded at candidate sources."""
    S = source_ids.shape[0]
    frontier = jnp.zeros((n, S), dtype=bool)
    return frontier.at[safe_src, jnp.arange(S)].set(
        (source_ids >= 0) & jnp.take(cand0, safe_src)
    )


def _wave_survivors(
    frontier: jnp.ndarray,    # bool[n, S] hop-L frontier
    source_ids: jnp.ndarray,  # int32[S], -1 = pad
    safe_src: jnp.ndarray,
    is_cyclic: bool,
) -> jnp.ndarray:
    """CC: token returned to its source. PC: the paper's `ack` — token reached
    some vertex other than its source."""
    S = source_ids.shape[0]
    if is_cyclic:
        survived = frontier[safe_src, jnp.arange(S)]
    else:
        arrived_any = jnp.any(frontier, axis=0)
        arrived_self = frontier[safe_src, jnp.arange(S)]
        arrived_elsewhere = (
            jnp.sum(frontier, axis=0) > arrived_self.astype(jnp.int32))
        survived = arrived_any & arrived_elsewhere
    return survived & (source_ids >= 0)


def check_walk_constraint_fused(
    dg: DeviceGraph,
    state: PruneState,
    walk_candidacy: jnp.ndarray,  # bool[L+1, n] candidacy per walk position
    is_cyclic: bool,
    source_ids: jnp.ndarray,  # int32[S] wave source ids, -1 = pad; S % 32 == 0
    blocked,
    force_pallas: bool = False,
) -> jnp.ndarray:
    """One CC/PC wave through the fused multi-hop wave engine: the packed
    frontier is built ONCE, all L hops run inside a single `bitset_wave`
    dispatch (Pallas kernel on TPU with the frontier VMEM-resident across
    hops, the scan-based packed-word oracle elsewhere), and the result is
    unpacked ONCE. Returns survived bool[S]."""
    from repro.core.state import pack_bits, unpack_bits
    from repro.kernels import ops as kops

    n = state.omega.shape[0]
    S = source_ids.shape[0]
    assert S % 32 == 0, "packed frontier needs a word-aligned wave size"
    safe_src = jnp.clip(source_ids, 0, n - 1)

    packed = pack_bits(
        _initial_frontier(n, walk_candidacy[0], source_ids, safe_src))
    cand = jnp.where(
        walk_candidacy[1:], jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    packed = kops.bitset_wave(
        packed, dg.src, dg.dst, n, state.edge_active, cand,
        blocked=blocked, force_pallas=force_pallas,
    )
    frontier = unpack_bits(packed, S)
    return _wave_survivors(frontier, source_ids, safe_src, is_cyclic)


def check_walk_constraint_packed(
    dg: DeviceGraph,
    state: PruneState,
    walk_candidacy: jnp.ndarray,  # bool[L+1, n] candidacy per walk position
    is_cyclic: bool,
    source_ids: jnp.ndarray,  # int32[S] wave source ids, -1 = pad; S % 32 == 0
    blocked,
    force_pallas: bool = False,
) -> jnp.ndarray:
    """One CC/PC wave with the S token planes bit-packed into uint32 words:
    each hop is a single bitset OR-SpMM through the kernel registry — the
    same blocked kernel as the LCC sweep, 32x fewer aggregation bytes than
    the boolean-plane hop. Returns survived bool[S] (no message counting —
    the packed OR absorbs duplicates before they can be counted)."""
    from repro.core.state import pack_bits, unpack_bits
    from repro.kernels import ops as kops

    n = state.omega.shape[0]
    S = source_ids.shape[0]
    assert S % 32 == 0, "packed frontier needs a word-aligned wave size"
    L = walk_candidacy.shape[0] - 1
    safe_src = jnp.clip(source_ids, 0, n - 1)

    packed = pack_bits(
        _initial_frontier(n, walk_candidacy[0], source_ids, safe_src))
    for r in range(1, L + 1):
        agg = kops.bitset_or_aggregate(
            packed, dg.src, dg.dst, n, state.edge_active,
            blocked=blocked, force_pallas=force_pallas,
        )
        packed = jnp.where(walk_candidacy[r][:, None], agg, jnp.uint32(0))
    frontier = unpack_bits(packed, S)
    return _wave_survivors(frontier, source_ids, safe_src, is_cyclic)


@functools.partial(jax.jit, static_argnames=("is_cyclic", "count_messages"))
def check_walk_constraint(
    dg: DeviceGraph,
    state: PruneState,
    walk_candidacy: jnp.ndarray,  # bool[L+1, n] candidacy per walk position
    is_cyclic: bool,
    source_ids: jnp.ndarray,  # int32[S] background vertex ids (wave), -1 = pad
    count_messages: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Verify one CC/PC wave. Returns (survived bool[S], message_count).

    The hop loop is a `lax.scan` over the hop-indexed candidacy stack — one
    XLA while-loop instead of L unrolled sweeps, so waves of any walk length
    share a compiled body and trace time stays O(1) in L."""
    n = state.omega.shape[0]
    safe_src = jnp.clip(source_ids, 0, n - 1)
    frontier = _initial_frontier(n, walk_candidacy[0], source_ids, safe_src)

    def hop(carry, cand_r):
        f, total = carry
        f, nm = _frontier_hop(dg, f, state.edge_active, cand_r, count_messages)
        return (f, total + nm), None

    (frontier, total_msgs), _ = jax.lax.scan(
        hop, (frontier, jnp.asarray(0)), walk_candidacy[1:])
    return _wave_survivors(frontier, source_ids, safe_src, is_cyclic), total_msgs


# Compacted wave graphs: arcs and vertices are padded to powers of two, at
# least this many, so that each capacity compiles once.
COMPACT_MIN = 1024
# (arc, vertex) capacities built so far, per whole-graph shape (n, m)
_compact_buckets: Dict[Tuple[int, int], list] = {}


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def compact_bucket(n: int, m: int, arcs: int, verts: int):
    """The (arc, vertex) capacity a constraint's waves run at, or None to
    run them on the whole graph. A capacity holds the active arcs, and the
    vertices they touch plus one sink. The smallest capacity already built
    for graphs of this shape that holds them is taken, so the set of wave
    programs a graph needs stops growing once its largest active subgraphs
    have run; else the next powers of two, when the arcs' is under m."""
    built = _compact_buckets.setdefault((n, m), [])
    fits = [b for b in built if b[0] >= arcs and b[1] > verts]
    if fits:
        return min(fits)
    b = (_pow2(max(arcs, COMPACT_MIN)), _pow2(max(verts + 1, COMPACT_MIN)))
    if b[0] >= m:
        return None
    built.append(b)
    return b


@functools.partial(jax.jit, static_argnames=("n",))
def _active_extent(src, dst, edge_active, n: int):
    """(touched bool[n]: the vertices an active arc starts or ends at,
    int32[2]: the active arcs and the touched vertices)."""
    ea = edge_active.astype(jnp.int32)
    touched = ((segment_ops.segment_max(ea, dst, n) > 0)
               | (segment_ops.segment_max(ea, src, n, sorted=False) > 0))
    return touched, jnp.stack([jnp.sum(ea), jnp.sum(touched, dtype=jnp.int32)])


@functools.partial(jax.jit, static_argnames=("m_c", "n_c"))
def compact_active(dg: DeviceGraph, state: PruneState, touched, m_c: int, n_c: int):
    """The active subgraph with its touched vertices renumbered from 0 in
    their old order: (DeviceGraph with n_c vertices and m_c arcs, its
    PruneState, int32[n] compact id of each vertex, -1 where no active arc
    touches it).

    The active arcs keep their dst-sorted order and the renumbering keeps
    vertex order, so the compact dst stays sorted. Pad arcs are inactive
    and end at the sink, the last compact id; pad vertices have no
    candidacy. Inactive arcs add nothing to an OR, so a wave over the
    compact graph reaches the same vertices as over the whole graph."""
    (arc,) = jnp.nonzero(state.edge_active, size=m_c, fill_value=0)
    live = jnp.arange(m_c) < jnp.sum(state.edge_active, dtype=jnp.int32)
    (vid,) = jnp.nonzero(touched, size=n_c, fill_value=0)
    real = jnp.arange(n_c) < jnp.sum(touched, dtype=jnp.int32)
    to_c = jnp.cumsum(touched, dtype=jnp.int32) - 1
    sink = n_c - 1
    g = DeviceGraph(
        n=n_c,
        src=jnp.where(live, jnp.take(to_c, jnp.take(dg.src, arc)), sink),
        dst=jnp.where(live, jnp.take(to_c, jnp.take(dg.dst, arc)), sink),
        labels=jnp.where(real, jnp.take(dg.labels, vid), -1),
    )
    st = PruneState(omega=jnp.take(state.omega, vid, axis=0) & real[:, None],
                    edge_active=live)
    return g, st, jnp.where(touched, to_c, -1)


@jax.jit
def _compact_ids(to_c, source_ids):
    """Wave source ids in compact ids; pads and sources no active arc
    touches become -1 (a token there cannot move, so it never survives)."""
    n = to_c.shape[0]
    return jnp.where(source_ids >= 0,
                     jnp.take(to_c, jnp.clip(source_ids, 0, n - 1)), -1)


def _waves_on_xla(route, n, wave, hops, dg, edge_active, blocked, force_pallas):
    """True where the constraint's waves run as XLA programs (the boolean
    planes, or the fused route when `bitset_wave` resolves to its oracle),
    whose hops step over every arc they are given; the kernels walk the
    whole graph's blocked structure."""
    from repro.kernels import ops as kops  # noqa: F401  (registers bitset_wave)
    from repro.kernels import registry

    if route == registry.ROUTE_UNPACKED:
        return True
    if route != registry.ROUTE_FUSED:
        return False
    vals = jax.ShapeDtypeStruct((n, wave // 32), jnp.uint32)
    cand = jax.ShapeDtypeStruct((hops, n), jnp.uint32)
    return registry.resolve_mode(
        "bitset_wave", vals, dg.src, dg.dst, n, edge_active, cand, blocked,
        force_pallas=force_pallas) == registry.MODE_REF


@functools.partial(jax.jit, static_argnames=("is_cyclic",))
def walk_frontiers_and_edges(
    dg: DeviceGraph,
    state: PruneState,
    walk_candidacy: jnp.ndarray,  # bool[L+1, n]
    is_cyclic: bool,
    source_ids: jnp.ndarray,      # int32[S], -1 = pad
):
    """Forward + backward frontiers for one wave (beyond-paper edge pruning).

    F_r[v, s] = a token from source s sits at v after r hops (prefix exists).
    B_r[v, s] = from v a valid suffix of length L-r completes for a SURVIVING
                source s (computed by sweeping the reversed arcs, intersected
                with F_r so only realizable states remain).

    Returns (survived bool[S],
             fwd_live bool[L, m]  — arc used at hop r lies on a full walk,
             rev_live bool[L, m]  — the twin-direction usage of the same arc).
    """
    n = state.omega.shape[0]
    S = source_ids.shape[0]
    L = walk_candidacy.shape[0] - 1
    safe_src = jnp.clip(source_ids, 0, n - 1)

    frontier = jnp.zeros((n, S), dtype=bool)
    frontier = frontier.at[safe_src, jnp.arange(S)].set(
        (source_ids >= 0) & jnp.take(walk_candidacy[0], safe_src))
    fwd = [frontier]
    for r in range(1, L + 1):
        frontier, _ = _frontier_hop(
            dg, frontier, state.edge_active, walk_candidacy[r])
        fwd.append(frontier)

    if is_cyclic:
        survived = fwd[L][safe_src, jnp.arange(S)] & (source_ids >= 0)
        # walk must terminate at its own source
        B = jnp.zeros((n, S), dtype=bool)
        B = B.at[safe_src, jnp.arange(S)].set(survived)
    else:
        arrived_self = fwd[L][safe_src, jnp.arange(S)]
        arrived_elsewhere = jnp.sum(fwd[L], axis=0) > arrived_self.astype(jnp.int32)
        survived = jnp.any(fwd[L], axis=0) & arrived_elsewhere & (source_ids >= 0)
        B = fwd[L] & survived[None, :]
        B = B.at[safe_src, jnp.arange(S)].set(False)  # end vertex != source

    fwd_live = []
    rev_live = []
    for r in range(L, 0, -1):
        # arc (u -> v) used at hop r: prefix at u, suffix from v
        fu = jnp.take(fwd[r - 1], dg.src, axis=0)
        bv = jnp.take(B, dg.dst, axis=0)
        live = jnp.any(fu & bv, axis=1) & state.edge_active
        fwd_live.append(live)
        # the twin arc (v -> u) realizes the same matched pair reversed
        fu_t = jnp.take(fwd[r - 1], dg.dst, axis=0)
        bv_t = jnp.take(B, dg.src, axis=0)
        rev_live.append(jnp.any(fu_t & bv_t, axis=1) & state.edge_active)
        # backward hop: B_{r-1}[u] = OR over out-arcs (u->v) of B_r[v], & F_{r-1}
        # (src is NOT sorted in the dst-sorted arc order)
        msgs = jnp.take(B, dg.dst, axis=0) & state.edge_active[:, None]
        agg = segment_ops.segment_or_bool(msgs, dg.src, n, sorted=False)
        B = agg & fwd[r - 1]
    fwd_live = jnp.stack(fwd_live[::-1])   # [L, m], index r-1 = hop r
    rev_live = jnp.stack(rev_live[::-1])
    return survived, fwd_live, rev_live


def verify_constraint(
    dg: DeviceGraph,
    state: PruneState,
    constraint: NonLocalConstraint,
    template_labels: np.ndarray,
    wave: int = 1024,
    stats: Optional[Dict] = None,
    count_messages: bool = False,
    edge_prune: bool = False,
    template=None,
    blocked=None,
    force_pallas: bool = False,
    direction: str = "default",
) -> PruneState:
    """Alg. 5 for CC/PC (+ each rotation for cycles): eliminate the head
    template vertex from omega of every failing token source.

    Batched wave executor: every walk of the constraint (all rotations of a
    cycle, both directions of a path) is a row of one candidacy stack built
    from the constraint-entry omega; the walks' waves all run against that
    shared state, per-wave survivors accumulate into a device-side `keep`
    plane, and the head-column eliminations (Alg. 5 line 8 — the heads are
    distinct template vertices across a constraint's walks) are applied on
    device at the end. The wave loop's host round-trips per constraint: one
    head-candidacy read to size it (which also brings the active subgraph's
    arc and vertex counts where the waves may run on it), plus one
    message-count readback under `count_messages` — never a per-wave
    `survived` transfer; these are what `stats["nlcc_host_syncs"]` counts.
    The `nlcc.wave_loop` span counts the arcs the hops stepped over
    (`wave_arcs`) against m for the same hops (`graph_arcs`). Always sound (a
    token only survives by certifying a full walk, so no true match is ever
    pruned). For cycle rotations it is also exactly as strong as the old
    sequential per-rotation pass: a token completing rotation j through a
    vertex rotation i eliminated would itself certify that vertex's cycle
    candidacy, contradicting the elimination — so the narrowing the batch
    skips could only have killed tokens that cannot complete anyway. For the
    two directions of a path constraint on a *directed* graph that argument
    does not apply (a reversed-walk arrival does not certify a forward walk)
    and one batched pass may prune marginally less than the old sequential
    pass; on this repo's undirected both-arc graphs the passes coincide, and
    either way exactness is restored downstream (complete-TDS annotation /
    enumeration).

    With `blocked` set (and message counting off), the tuned policy routes
    waves onto the `fused` multi-hop wave engine (`check_walk_constraint_fused`
    — one bitset_wave dispatch per wave, pack/unpack once) or the per-hop
    `packed` bitset_spmm route; the boolean-plane scan is the unpacked
    fallback. The XLA-program waves run on the compacted active subgraph
    (module docstring, `compact_bucket`).

    edge_prune=True (requires template) additionally eliminates arcs that lie
    on NO completing walk for the template arcs this constraint covers — a
    sound beyond-paper refinement (see walk_frontiers_and_edges): a true
    match realizes every hop of the walk, so an arc that is never
    (prefix-live, suffix-live) at any covering hop supports no match via
    those template arcs. The pass reads back, per constraint: the head
    column of omega; two bool[L, m] live planes per wave of `wave` heads;
    omega, the arcs' endpoints and edge_active once (the endpoints move only
    where JAX holds no host copy yet). `nlcc_host_syncs` does not count
    these."""
    if edge_prune and template is not None:
        state = _edge_prune_pass(dg, state, constraint, template, wave, stats)
    walks = expand_walks(constraint, direction)

    from repro.kernels import registry as _registry

    route = nlcc_resolved_route(
        state, wave, blocked,
        count_messages=count_messages, force_pallas=force_pallas,
    )
    wave_stat = {
        _registry.ROUTE_FUSED: "nlcc_fused_waves",
        _registry.ROUTE_PACKED: "nlcc_packed_waves",
        _registry.ROUTE_UNPACKED: "nlcc_plane_waves",
    }[route]
    omega = state.omega
    n, m = omega.shape[0], dg.m
    heads = [w[0] for w in walks]
    hops = len(walks[0]) - 1
    compactable = _waves_on_xla(route, n, wave, hops, dg, state.edge_active,
                                blocked, force_pallas)
    with obs.span("nlcc.wave_loop"):
        head_idx = jnp.asarray(heads, jnp.int32)
        # the wave loop's ONE host sync per constraint: the head-candidacy
        # columns size it, with the active subgraph's extent where the waves
        # may run on it (everything downstream stays on device)
        if compactable:
            touched, extent = _active_extent(dg.src, dg.dst, state.edge_active, n)
            head_cols, extent = obs.to_host((omega[:, head_idx], extent), "nlcc.heads")
        else:
            head_cols = obs.to_host(omega[:, head_idx], "nlcc.heads")
        host_syncs = 1
        with obs.span("nlcc.sources", kind="host"):
            walk_sources = [np.flatnonzero(head_cols[:, wi]) for wi in range(len(walks))]
        bucket = None
        if compactable and any(s.size for s in walk_sources):
            bucket = compact_bucket(n, m, int(extent[0]), int(extent[1]))
        if bucket is not None:
            # every wave of the constraint runs on the active subgraph
            wave_dg, wave_state, to_c = compact_active(
                dg, state, touched, m_c=bucket[0], n_c=bucket[1])
        else:
            wave_dg, wave_state, to_c = dg, state, None
        keep = jnp.zeros((len(walks), n), dtype=bool)
        total_msgs = jnp.asarray(0)
        n_waves = 0
        for wi, walk in enumerate(walks):
            if walk_sources[wi].size == 0:
                continue
            # bool[L+1, n_w], n_w the wave graph's vertex count
            cand = jnp.stack([wave_state.omega[:, q] for q in walk], axis=0)
            for ids_padded, n_real in wave_batches(walk_sources[wi], wave):
                with obs.span("nlcc.wave"):
                    ids_dev = jnp.asarray(ids_padded, jnp.int32)
                    ids_w = ids_dev if to_c is None else _compact_ids(to_c, ids_dev)
                    if route == _registry.ROUTE_FUSED:
                        survived = check_walk_constraint_fused(
                            wave_dg, wave_state, cand, walk[0] == walk[-1], ids_w,
                            None if to_c is not None else blocked,
                            force_pallas=force_pallas,
                        )
                    elif route == _registry.ROUTE_PACKED:
                        survived = check_walk_constraint_packed(
                            wave_dg, wave_state, cand, walk[0] == walk[-1], ids_w,
                            blocked, force_pallas=force_pallas,
                        )
                    else:
                        survived, n_msgs = check_walk_constraint(
                            wave_dg, wave_state, cand, walk[0] == walk[-1], ids_w,
                            count_messages=count_messages,
                        )
                        total_msgs = total_msgs + n_msgs
                    # survivors land at the sources' own ids; pads clip to
                    # vertex 0 with survived=False — max() cannot unset
                    keep = keep.at[wi, jnp.clip(ids_dev, 0, n - 1)].max(survived)
                n_waves += 1
                if stats is not None:
                    stats["nlcc_tokens"] = stats.get("nlcc_tokens", 0) + n_real
                    stats[wave_stat] = stats.get(wave_stat, 0) + 1
        obs.count("wave_arcs", (m if bucket is None else bucket[0]) * hops * n_waves)
        obs.count("graph_arcs", m * hops * n_waves)
        # remove head candidacy from failing sources (Alg. 5 line 8), on device
        for wi, q0 in enumerate(heads):
            omega = omega.at[:, q0].set(omega[:, q0] & keep[wi])
    if stats is not None:
        if count_messages:
            stats["nlcc_messages"] = stats.get("nlcc_messages", 0) + int(
                obs.to_host(total_msgs, "nlcc_messages"))
            host_syncs += 1
        stats["nlcc_constraints"] = stats.get("nlcc_constraints", 0) + 1
        stats["nlcc_waves"] = stats.get("nlcc_waves", 0) + n_waves
        # the acceptance contract: survivors never cross to the host per wave
        stats["nlcc_host_syncs"] = stats.get("nlcc_host_syncs", 0) + host_syncs
    return PruneState(omega=omega, edge_active=state.edge_active)


def _edge_prune_pass(
    dg: DeviceGraph,
    state: PruneState,
    constraint: NonLocalConstraint,
    template,
    wave: int,
    stats: Optional[Dict],
) -> PruneState:
    """Forward-backward frontier edge elimination for one CC/PC constraint."""
    with obs.span("nlcc.edge_prune"):
        walk = list(constraint.walk)
        l = len(walk) - 1
        omega = state.omega
        cand = jnp.stack([omega[:, q] for q in walk], axis=0)
        head = obs.to_host(omega[:, walk[0]], "edge_prune.heads")
        with obs.span("nlcc.sources", kind="host"):
            sources = np.flatnonzero(head)
        if sources.size == 0:
            return state
        m = dg.m
        live_f = np.zeros((l, m), dtype=bool)
        live_r = np.zeros((l, m), dtype=bool)
        for idsp, _ in wave_batches(sources, wave):
            _, fl, rl = walk_frontiers_and_edges(
                dg, state, cand, constraint.is_cyclic, jnp.asarray(idsp, jnp.int32))
            fl = obs.to_host(fl, "edge_prune.fwd_live")
            rl = obs.to_host(rl, "edge_prune.rev_live")
            with obs.span("nlcc.edge_prune.support", kind="host"):
                live_f |= fl
                live_r |= rl

        pairs = list(zip(walk[:-1], walk[1:]))
        covered: Dict[tuple, list] = {}
        for i, (qa, qb) in enumerate(pairs):
            covered.setdefault((qa, qb), []).append(("f", i))
            covered.setdefault((qb, qa), []).append(("r", i))

        om = obs.to_host(omega, "omega")
        src = obs.to_host(dg.src, "src")
        dst = obs.to_host(dg.dst, "dst")
        ea = obs.to_host(state.edge_active, "edge_active")
        with obs.span("nlcc.edge_prune.support", kind="host"):
            support = np.zeros(m, dtype=bool)
            for qa in range(template.n0):
                for qb in template.adj[qa]:
                    lcc_rule = om[src, qa] & om[dst, qb]
                    if (qa, qb) in covered:
                        live = np.zeros(m, dtype=bool)
                        for kind, i in covered[(qa, qb)]:
                            live |= live_f[i] if kind == "f" else live_r[i]
                        support |= lcc_rule & live
                    else:
                        support |= lcc_rule
            new_ea = ea & support
            if stats is not None:
                stats["nlcc_edges_pruned"] = stats.get("nlcc_edges_pruned", 0) + int(
                    np.sum(ea) - np.sum(new_ea))
        return PruneState(omega=omega, edge_active=jnp.asarray(new_ea))
