"""Unified execution-backend layer: the full constraint-checking pipeline on
sharded meshes.

One set of LCC-sweep / NLCC-wave / edge-elimination primitives is written
against a tiny collective interface (`Prims`: ``exchange`` = the bucketed
all_to_all, ``all_reduce_or`` / ``psum`` = the convergence and survivor
reductions, ``axis_index`` = which shard am I). Three backends execute them:

  local   today's single-device path — the identity exchange. Delegates to the
          optimized core/{lcc,nlcc,tds} routes (packed kernels, fused wave,
          dispatch-policy routing) since with P=1 every message is local.
  spmd    shard_map + ``jax.lax.all_to_all`` over an `EdgePartition` on a real
          mesh (or a host-platform-forced multi-device CPU). The whole LCC
          fixpoint and every NLCC wave run where the partitioned state lives;
          convergence flags are psum-reduced on device.
  sim     the SAME per-shard programs under ``jax.vmap(..., axis_name=...)``
          — vmap's collective rules turn the all_to_all into a transpose, so
          single-process tests prove the distributed math equals the
          single-device engine bit-for-bit on any shard count.

The spmd and sim backends share every line of program code; only the wrapper
differs (shard_map vs vmap). This file absorbs what used to be
core/distributed.py (a stranded second implementation of the LCC math with no
NLCC verification, no TDS, and no wave executor).

Sharded NLCC waves are routed per shard-local shape by the tuned dispatch
policy (`registry.resolve_route` with `registry.shard_bucket` keys):

  fused     one program dispatch per wave — the hop loop is a lax.scan over
            the candidacy stack, packed uint32 frontier words throughout
            (the sharded analogue of the bitset_wave kernel). Gated by the
            same resident-bytes eligibility rule as the kernel, evaluated on
            SHARD-LOCAL shapes (`sharded_fused_eligible`).
  packed    one program dispatch per hop, packed words on the wire.
  unpacked  one dispatch per hop, boolean token planes (32x the exchange
            bytes; the parity/debug route).

All three compute identical survivors; the parity suite
(tests/test_sharded_engine.py) pins prune() on 1/2/4/8 shards bit-for-bit
against the local engine across cyclic, path, and TDS-bearing templates.

TDS constraints (and the beyond-paper frontier edge-prune pass) are host-side
row-table joins over the *already heavily pruned* G in every backend; on the
sharded backends they run through an explicit gather -> verify -> scatter
bridge (`gather_state`/`scatter_state`), which keeps them bit-identical to the
local engine by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.graph.structs import Graph, DeviceGraph
from repro.graph.partition import EdgePartition, partition_graph
from repro.graph.segment_ops import SegmentMeta, segment_or
from repro.core.state import PruneState, init_state, pack_bits, unpack_bits, packed_words
from repro.core.lcc import TemplateDev
from repro.core.template import Template, NonLocalConstraint

SHARD_AXIS = "shards"


# ---------------------------------------------------------------------------
# The collective interface every sharded program is written against
# ---------------------------------------------------------------------------
class Prims(NamedTuple):
    """The collective primitives of one execution backend."""

    exchange: Callable  # [P*B, W] per-shard send buckets -> received buckets
    all_reduce_or: Callable  # bool scalar -> OR over shards (convergence)
    psum: Callable  # int array -> sum over shards (wave survivors)
    axis_index: Callable  # () -> this shard's index
    # [P, Br, C] keyed row buckets (leading axis = destination shard) ->
    # received buckets (slice q = what shard q sent here). The distributed-
    # rows join routes pow2-padded row blocks by frontier-vertex owner
    # through this instead of psum-combining full-width slot tensors.
    exchange_rows: Callable
    # overlap(step, carry, max_iters) -> (carry, iters): the software-
    # pipelined fixpoint. `step: carry -> (carry, changed)`. On the sharded
    # backends convergence is checked on a LAGGED all_reduce_or — iteration
    # i's flag gates iteration i+2, so the reduction is in flight while the
    # next iteration computes. Sound for monotone sweeps: the (at most one)
    # extra iteration past the fixpoint is a no-op by definition of the
    # change flag.
    overlap: Callable


def _exchange_rows_over(axis_name: str) -> Callable:
    """Keyed row exchange over a named axis: the same bucketed all_to_all as
    `exchange`, shaped for [P, Br, C] row blocks (bucket q -> shard q)."""

    def xr(x: jnp.ndarray) -> jnp.ndarray:
        flat = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
        out = jax.lax.all_to_all(flat, axis_name, 0, 0, tiled=True)
        return out.reshape(x.shape)

    return xr


def _overlap_lagged(all_reduce_or: Callable) -> Callable:
    """The lagged-convergence pipelined fixpoint: each iteration issues the
    reduction of the PREVIOUS iteration's change flag before computing, so
    the collective overlaps the sweep instead of fencing it. Converges one
    (idempotent) iteration later than the eager schedule."""

    def overlap(step: Callable, carry, max_iters: int = 1000):
        def cond(c):
            _, pending, _, it = c
            return jnp.logical_and(pending, it < max_iters)

        def body(c):
            carry, _pending, ch_prev, it = c
            pending = all_reduce_or(ch_prev)  # lagged: flag of iteration i-1
            carry2, ch = step(carry)
            return carry2, pending, ch, it + 1

        carry, _, _, it = jax.lax.while_loop(
            cond, body,
            (carry, jnp.asarray(True), jnp.asarray(True), jnp.asarray(0)))
        return carry, it

    return overlap


def _overlap_eager(step: Callable, carry, max_iters: int = 1000):
    """P=1 pipelining degenerates to the eager do-while (reductions are
    identities, there is nothing to overlap — and nothing to lag)."""

    def cond(c):
        _, ch, it = c
        return jnp.logical_and(ch, it < max_iters)

    def body(c):
        carry, _, it = c
        carry2, ch = step(carry)
        return carry2, ch, it + 1

    carry, _, it = jax.lax.while_loop(
        cond, body, (carry, jnp.asarray(True), jnp.asarray(0)))
    return carry, it


def axis_prims(axis_name: str = SHARD_AXIS) -> Prims:
    """Prims over a named axis — valid under BOTH shard_map (spmd) and
    vmap-with-axis-name (sim); jax lowers the same collectives either way."""
    all_reduce_or = lambda f: jax.lax.psum(f.astype(jnp.int32), axis_name) > 0
    return Prims(
        exchange=lambda x: jax.lax.all_to_all(x, axis_name, 0, 0, tiled=True),
        all_reduce_or=all_reduce_or,
        psum=lambda x: jax.lax.psum(x, axis_name),
        axis_index=lambda: jax.lax.axis_index(axis_name),
        exchange_rows=_exchange_rows_over(axis_name),
        overlap=_overlap_lagged(all_reduce_or),
    )


def local_prims() -> Prims:
    """The identity exchange (P=1): every bucket is local, reductions are
    no-ops. The degenerate case the local backend embodies."""
    return Prims(
        exchange=lambda x: x,
        all_reduce_or=lambda f: f,
        psum=lambda x: x,
        axis_index=lambda: jnp.asarray(0, jnp.int32),
        exchange_rows=lambda x: x,
        overlap=_overlap_eager,
    )


# ---------------------------------------------------------------------------
# Shared partition-sweep math (absorbed from core/distributed.py)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ShardArrays:
    """Per-shard static partition arrays (local views, leading shard axis removed)."""

    send_src_local: jnp.ndarray  # int32[P, B]
    send_pad: jnp.ndarray  # bool[P, B]
    twin_recv_flat: jnp.ndarray  # int32[P, B]
    recv_perm: jnp.ndarray  # int32[P*B]
    recv_sorted_dst_local: jnp.ndarray  # int32[P*B]
    recv_is_start: jnp.ndarray  # bool[P*B]
    recv_last_edge: jnp.ndarray  # int32[n_local]
    labels_local: jnp.ndarray  # int32[n_local]
    vertex_valid: jnp.ndarray  # bool[n_local]


jax.tree_util.register_dataclass(ShardArrays)


class TemplateMasks:
    """Packed template constants for the sharded sweep."""

    def __init__(self, tdev: TemplateDev):
        self.n0 = tdev.n0
        self.adj0 = tdev.adj0.astype(jnp.float32)  # [n0, n0]
        self.needs_counts = tdev.needs_counts
        self.req = tdev.req
        self.vertex_has_counted_label = tdev.vertex_has_counted_label.astype(jnp.float32)


def _aggregate_or(recv: jnp.ndarray, sa: ShardArrays, n_local: int) -> jnp.ndarray:
    sortedv = jnp.take(recv, sa.recv_perm, axis=0)
    meta = SegmentMeta(is_start=sa.recv_is_start, last_edge_of_vertex=sa.recv_last_edge)
    return segment_or(sortedv, meta, n_local)  # [n_local, W]


def lcc_shard_iteration(
    omega: jnp.ndarray,  # uint32[n_local+1, W]
    edge_active: jnp.ndarray,  # bool[P, B]
    sa: ShardArrays,
    tm: TemplateMasks,
    prims: Prims,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One LCC sweep: gather local omega over the static send buckets, mask by
    per-arc active bits, ONE exchange (the only collective), then the static
    dst-sorted permutation + segmented OR on the receive side."""
    n_local = omega.shape[0] - 1
    W = omega.shape[1]
    send_mask = edge_active & ~sa.send_pad
    msgs = jnp.take(omega, sa.send_src_local, axis=0)  # [P, B, W]
    msgs = jnp.where(send_mask[..., None], msgs, jnp.uint32(0))
    recv = prims.exchange(msgs.reshape(-1, W))  # [P*B, W]
    return _lcc_from_recv(omega, edge_active, recv, sa, tm)


def lcc_shard_fixpoint(
    omega: jnp.ndarray,
    edge_active: jnp.ndarray,
    sa: ShardArrays,
    tm: TemplateMasks,
    prims: Prims,
    max_iters: int = 1000,
):
    """The LCC do-while as one on-device while_loop, scheduled by the
    backend's `overlap` combinator: on the sharded backends the convergence
    psum is LAGGED one iteration behind the sweep it gates, so the reduction
    is in flight while the next sweep computes instead of fencing it. The
    sweep is monotone (omega / edge bits only clear), so the one extra
    iteration past the fixpoint recomputes the fixpoint — a no-op."""

    def step(c):
        om, ea = c
        om2, ea2, ch = lcc_shard_iteration(om, ea, sa, tm, prims)
        return (om2, ea2), ch

    (om, ea), it = prims.overlap(step, (omega, edge_active), max_iters)
    return om, ea, it


def _lcc_from_recv(omega, edge_active, recv, sa: ShardArrays, tm: TemplateMasks):
    """lcc_shard_iteration with the exchange already performed (shared math).

    Edge elimination reads the twin arc's omega out of the *same* receive
    buffer (`twin_recv_flat`) — no extra collective."""
    n_local = omega.shape[0] - 1
    W = omega.shape[1]
    send_mask = edge_active & ~sa.send_pad

    M_packed = _aggregate_or(recv, sa, n_local)
    M = unpack_bits(M_packed, tm.n0)
    omega_bits = unpack_bits(omega[:n_local], tm.n0)
    missing = (~M).astype(jnp.float32) @ tm.adj0.T
    ok = missing < 0.5
    if tm.needs_counts:
        rbits = unpack_bits(jnp.take(recv, sa.recv_perm, axis=0), tm.n0)
        ind = (rbits.astype(jnp.float32) @ tm.vertex_has_counted_label) > 0.5
        cnt = jax.ops.segment_sum(
            ind.astype(jnp.int32),
            jnp.minimum(sa.recv_sorted_dst_local, n_local),
            num_segments=n_local + 1, indices_are_sorted=True,
        )[:n_local]
        ok = ok & jnp.all(cnt[:, None, :] >= tm.req[None, :, :], axis=-1)
    new_bits = omega_bits & ok & sa.vertex_valid[:, None]
    deg_pos = jnp.any(tm.adj0 > 0.5, axis=1)
    new_bits = new_bits & (~deg_pos[None, :] | jnp.any(M, axis=1)[:, None])

    recv_sink = jnp.concatenate([recv, jnp.zeros((1, W), jnp.uint32)], axis=0)
    dst_words = jnp.take(recv_sink, sa.twin_recv_flat, axis=0)
    src_bits = unpack_bits(jnp.take(omega, sa.send_src_local, axis=0), tm.n0)
    dst_bits = unpack_bits(dst_words, tm.n0)
    side = src_bits.astype(jnp.float32) @ tm.adj0
    compat_ = jnp.sum(side * dst_bits.astype(jnp.float32), axis=-1) > 0.5
    ea_new = send_mask & compat_
    omega_new = jnp.concatenate([pack_bits(new_bits), jnp.zeros((1, W), jnp.uint32)], axis=0)
    changed = jnp.any(omega_new != omega) | jnp.any(ea_new != edge_active)
    return omega_new, ea_new, changed


def frontier_shard_hop(
    frontier: jnp.ndarray,  # uint32[n_local+1, Wf] packed token words
    edge_active: jnp.ndarray,  # bool[P, B]
    sa: ShardArrays,
    cand_next: jnp.ndarray,  # bool[n_local] candidacy of the next walk vertex
    prims: Prims,
) -> jnp.ndarray:
    """One NLCC token hop (paper Alg. 6 forward) on packed multi-source words."""
    n_local = frontier.shape[0] - 1
    Wf = frontier.shape[1]
    send_mask = edge_active & ~sa.send_pad
    msgs = jnp.take(frontier, sa.send_src_local, axis=0)
    msgs = jnp.where(send_mask[..., None], msgs, jnp.uint32(0))
    recv = prims.exchange(msgs.reshape(-1, Wf))
    agg = _aggregate_or(recv, sa, n_local)
    nxt = jnp.where(cand_next[:, None], agg, jnp.uint32(0))
    return jnp.concatenate([nxt, jnp.zeros((1, Wf), jnp.uint32)], axis=0)


def frontier_shard_hop_unpacked(
    frontier: jnp.ndarray,  # bool[n_local+1, S] token planes
    edge_active: jnp.ndarray,  # bool[P, B]
    sa: ShardArrays,
    cand_next: jnp.ndarray,  # bool[n_local]
    prims: Prims,
) -> jnp.ndarray:
    """The boolean-plane hop: same sweep, 32x the exchange bytes (uint8 on the
    wire — collectives do not carry packed semantics for bools)."""
    n_local = frontier.shape[0] - 1
    S = frontier.shape[1]
    send_mask = edge_active & ~sa.send_pad
    msgs = jnp.take(frontier, sa.send_src_local, axis=0) & send_mask[..., None]
    recv = prims.exchange(msgs.reshape(-1, S).astype(jnp.uint8)).astype(bool)
    agg = _aggregate_or(recv, sa, n_local)
    nxt = agg & cand_next[:, None]
    return jnp.concatenate([nxt, jnp.zeros((1, S), bool)], axis=0)


def init_sharded_state(part: EdgePartition, template) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """omega_all uint32[P, n_local+1, W] from labels (last row = padding sink);
    edge_active_all bool[P, P, B] (real arcs active)."""
    n0 = template.n0
    W = packed_words(n0)
    n_labels = int(max(template.labels.max() + 1, part.labels_local.max() + 1))
    lm = template.label_matrix(n_labels)  # [n0, L]
    bits = lm.T[np.asarray(part.labels_local)]  # [P, n_local, n0]
    bits &= np.asarray(part.vertex_valid)[..., None]
    omega = np.asarray(pack_bits(jnp.asarray(bits)))
    omega = np.concatenate([omega, np.zeros((part.P, 1, W), np.uint32)], axis=1)
    return jnp.asarray(omega), jnp.asarray(~part.send_pad)


# ---------------------------------------------------------------------------
# Sharded NLCC wave programs (per-shard bodies; wrapped by the backends)
# ---------------------------------------------------------------------------
def _owner_local(source_ids: jnp.ndarray, n_local: int, p) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Map global wave-source ids to this shard's local rows; non-owned and
    padded (-1) sources land on the padding-sink row n_local."""
    valid = source_ids >= 0
    owner = jnp.where(valid, source_ids // n_local, -1)
    local = jnp.where(owner == p, source_ids % n_local, n_local)
    return local, valid


def _seed_frontier_planes(cand0, source_ids, n_local: int, p) -> jnp.ndarray:
    """F_0 token planes bool[n_local+1, S]: one plane per wave source, seeded
    at candidate sources on their owner shard."""
    S = source_ids.shape[0]
    local, valid = _owner_local(source_ids, n_local, p)
    cand0x = jnp.concatenate([cand0, jnp.zeros((1,), bool)])
    seed = valid & jnp.take(cand0x, local)
    f = jnp.zeros((n_local + 1, S), bool)
    return f.at[local, jnp.arange(S)].set(seed)


def _sharded_wave_survivors(
    planes: jnp.ndarray,  # bool[n_local+1, S] hop-L token planes
    source_ids: jnp.ndarray,  # int32[S], -1 = pad
    n_local: int,
    is_cyclic: bool,
    prims: Prims,
) -> jnp.ndarray:
    """CC: token returned to its source. PC: the paper's `ack` — token reached
    some vertex other than its source. Per-shard partials are psum-combined so
    the decision is replicated without leaving the device."""
    S = source_ids.shape[0]
    p = prims.axis_index()
    local, valid = _owner_local(source_ids, n_local, p)
    self_bits = planes[local, jnp.arange(S)].astype(jnp.int32)  # pad row -> 0
    self_tot = prims.psum(self_bits)
    if is_cyclic:
        return (self_tot > 0) & valid
    cnt_tot = prims.psum(jnp.sum(planes[:n_local].astype(jnp.int32), axis=0))
    return (cnt_tot > 0) & (cnt_tot > self_tot) & valid


def _scatter_keep(keep_col, survived, source_ids, n_local: int, p):
    """OR the replicated survivor bits into this shard's keep column; pads and
    non-owned sources hit the padding-sink row (max cannot unset)."""
    local, _ = _owner_local(source_ids, n_local, p)
    return keep_col.at[local].max(survived)


def sharded_fused_resident_bytes(n_local: int, Pn: int, B: int, wave: int, L: int) -> int:
    """Per-shard resident working set of the fused (single-dispatch) wave: the
    ping/pong frontier + aggregate words, the exchange receive buffer, and the
    candidacy stack — the shard-local analogue of the bitset_wave kernel's
    VMEM accounting."""
    Wf = max(wave // 32, 1)
    return (
        3 * (n_local + 1) * Wf * 4  # frontier in/out + aggregate
        + Pn * B * Wf * 4           # exchange receive buffer
        + (L + 1) * n_local         # candidacy stack (bool)
    )


def sharded_fused_eligible(n_local: int, Pn: int, B: int, wave: int, L: int) -> bool:
    """The bitset_wave eligibility gate composed with shard-local shapes: the
    fused route only runs where its resident state fits the same budget the
    kernel enforces (`ops.BITSET_WAVE_VMEM_BUDGET`). The sharded fused wave
    is a jnp program, not the VMEM kernel, so its state is counted as
    dense words here; the kernel's own count, with Mosaic's lane padding and
    double buffers, is `ops.bitset_wave_vmem_bytes`."""
    from repro.kernels import ops as kops

    return sharded_fused_resident_bytes(n_local, Pn, B, wave, L) <= kops.BITSET_WAVE_VMEM_BUDGET


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------
class LocalBackend:
    """Today's single-device path: the identity exchange. Delegates to the
    optimized core/{lcc,nlcc,tds} implementations — packed kernels, the fused
    bitset_wave engine, and dispatch-policy routing all compose here."""

    name = "local"

    def __init__(
        self,
        dg: DeviceGraph,
        template: Template,
        *,
        wave: int = 1024,
        blocked=None,
        force_pallas: bool = False,
        edge_elimination: bool = True,
        collect_stats: bool = False,
        nlcc_edge_prune: bool = False,
        tds_chunk: int = 4096,
        tds_max_rows: int = 2_000_000,
        work_aggregation: bool = True,
        guarantee_precision: bool = True,
        injector=None,
    ):
        self.dg = dg
        self.template = template
        self.tdev = TemplateDev(template)
        self.wave = wave
        self.blocked = blocked
        self.injector = injector
        self.force_pallas = force_pallas
        self.edge_elimination = edge_elimination
        self.collect_stats = collect_stats
        self.nlcc_edge_prune = nlcc_edge_prune
        self.tds_chunk = tds_chunk
        self.tds_max_rows = tds_max_rows
        self.work_aggregation = work_aggregation
        self.guarantee_precision = guarantee_precision
        self.state: Optional[PruneState] = None

    # -- state
    def init(self, initial_state: Optional[PruneState]) -> None:
        self.state = initial_state if initial_state is not None else init_state(
            self.dg, self.template)

    def final_state(self) -> PruneState:
        return self.state

    def snapshot(self):
        """In-memory device snapshot for the degradation ladder's retry rung
        (jnp arrays are immutable — holding the references is enough)."""
        return self.state

    def restore_snapshot(self, snap) -> None:
        self.state = snap

    def _fire(self, site: str, **ctx) -> None:
        if self.injector is not None:
            self.injector.event(site, **ctx)

    # -- reporting
    def record_routes(self, stats: Dict) -> None:
        if self.blocked is None:
            return
        from repro.kernels import registry as _registry
        from repro.core.lcc import LCC_ROUTE, lcc_resolved_route
        from repro.core.nlcc import NLCC_ROUTE, nlcc_resolved_route

        stats["dispatch_routes"] = {
            # the Fig-6a ablation (_lcc_no_edge_elim) never reaches the
            # packed path, whatever the policy says
            LCC_ROUTE: (_registry.ROUTE_UNPACKED if not self.edge_elimination
                        else lcc_resolved_route(
                self.state, self.dg, self.tdev, self.blocked,
                collect_stats=self.collect_stats,
                force_pallas=self.force_pallas)),
            NLCC_ROUTE: nlcc_resolved_route(
                self.state, self.wave, self.blocked,
                count_messages=self.collect_stats,
                force_pallas=self.force_pallas),
        }
        stats["dispatch_policy_active"] = _registry.get_policy() is not None

    def counts_dev(self) -> jnp.ndarray:
        """[active_vertices, active_edges, omega_bits] as one device vector —
        phase snapshots accumulate these lazily (no per-phase host sync)."""
        om, ea = self.state.omega, self.state.edge_active
        return jnp.stack([
            jnp.sum(jnp.any(om, axis=1), dtype=jnp.int32),
            jnp.sum(ea, dtype=jnp.int32),
            jnp.sum(om, dtype=jnp.int32),
        ])

    def counts_host(self) -> Dict[str, int]:
        return self.state.counts()

    def sync(self) -> None:
        """Fence the device stream (no transfer): phase wall-times must
        include the phase's own device work even though snapshot counts stay
        lazy."""
        jax.block_until_ready((self.state.omega, self.state.edge_active))

    def finalize_stats(self, stats: Dict) -> None:
        """Local routes are resolved once up front (`record_routes` is the
        single source of truth shared with execution) — nothing to amend."""

    # -- phases
    def lcc(self, stats: Dict) -> None:
        from repro.core.lcc import lcc_fixpoint, lcc_fixpoint_packed, lcc_iteration

        self._fire("lcc")
        dg, tdev, state = self.dg, self.tdev, self.state
        with obs.span("lcc.fixpoint"):
            if not self.edge_elimination:
                self.state = self._lcc_no_edge_elim(stats)
                return
            if (self.blocked is not None and not self.collect_stats
                    and not tdev.needs_counts):
                self.state = lcc_fixpoint_packed(
                    dg, tdev, state, self.blocked, stats=stats,
                    force_pallas=self.force_pallas)
                return
            if self.collect_stats:
                # python loop to count per-iteration messages (active arcs at
                # send time)
                it = 0
                while True:
                    active = int(obs.to_host(jnp.sum(state.edge_active),
                                             "active_arcs"))
                    stats["lcc_messages"] = stats.get("lcc_messages", 0) + active
                    obs.count("active_arcs", active)
                    new_state, changed = lcc_iteration(dg, tdev, state)
                    it += 1
                    state = new_state
                    if not bool(obs.to_host(changed, "changed")) or it > 1000:
                        break
                stats["lcc_iterations"] = stats.get("lcc_iterations", 0) + it
                obs.count("stepped_arcs", it * dg.m)
                self.state = state
                return
            self.state = lcc_fixpoint(dg, tdev, state, stats=stats)

    def _lcc_no_edge_elim(self, stats: Dict) -> PruneState:
        """Vertex-elimination-only LCC (Fig. 6a baseline): edges stay active
        while both endpoints are active, regardless of label compatibility."""
        from repro.core.lcc import lcc_iteration

        dg, tdev, state = self.dg, self.tdev, self.state
        it = 0
        while True:
            new_state, changed = lcc_iteration(dg, tdev, state)
            vact = jnp.any(new_state.omega, axis=1)
            ea = jnp.take(vact, dg.src) & jnp.take(vact, dg.dst)
            new_state = PruneState(omega=new_state.omega, edge_active=ea)
            changed = jnp.any(new_state.omega != state.omega) | jnp.any(
                new_state.edge_active != state.edge_active
            )
            state = new_state
            it += 1
            stats["lcc_messages"] = stats.get("lcc_messages", 0) + int(
                obs.to_host(jnp.sum(ea), "lcc_messages"))
            if not bool(obs.to_host(changed, "changed")) or it > 1000:
                break
        stats["lcc_iterations"] = stats.get("lcc_iterations", 0) + it
        return state

    def nlcc(self, c: NonLocalConstraint, cstats: Dict,
             direction: str = "default"):
        from repro.core import nlcc as nlcc_mod

        self._fire("nlcc")
        before = self.state
        self.state = nlcc_mod.verify_constraint(
            self.dg, before, c, self.template.labels, wave=self.wave,
            stats=cstats, count_messages=self.collect_stats,
            edge_prune=self.nlcc_edge_prune, template=self.template,
            blocked=self.blocked, force_pallas=self.force_pallas,
            direction=direction,
        )
        return _state_changed(before, self.state)

    def tds(self, c: NonLocalConstraint, cstats: Dict):
        from repro.core import tds as tds_mod

        self._fire("tds")
        before = self.state
        self.state = tds_mod.verify_tds_constraint(
            self.dg, before, c, chunk=self.tds_chunk,
            max_rows=self.tds_max_rows, stats=cstats,
            annotate=(c.complete and self.guarantee_precision),
            dedup=self.work_aggregation,
        )
        return _state_changed(before, self.state)


def _state_changed(before: PruneState, after: PruneState) -> jnp.ndarray:
    """Device-side change flag: omega/edge bits are monotone decreasing, so a
    bitwise compare is exactly the old counts-based `after != before` check —
    one device bool instead of six blocking count reads."""
    return jnp.any(before.omega != after.omega) | jnp.any(
        before.edge_active != after.edge_active)


class _ShardedBackend:
    """Shared machinery of the spmd and sim backends: state layout, the
    gather/scatter bridge, the wave executor, and the program cache. The only
    subclass hook is `_make(program, n_sharded)` — how a per-shard program is
    wrapped into a callable over global [P, ...] arrays."""

    name = "sharded"

    def __init__(
        self,
        graph: Graph,
        dg: DeviceGraph,
        template: Template,
        part: EdgePartition,
        *,
        wave: int = 1024,
        collect_stats: bool = False,
        nlcc_edge_prune: bool = False,
        tds_chunk: int = 4096,
        tds_max_rows: int = 2_000_000,
        work_aggregation: bool = True,
        guarantee_precision: bool = True,
        edge_elimination: bool = True,
        arc_order: Optional[np.ndarray] = None,
        injector=None,
    ):
        if not edge_elimination:
            raise ValueError(
                "edge_elimination=False (the Fig-6a ablation) is a "
                "local-backend-only mode; run it without mesh=/partition=")
        if part.arc_flat_slot is None:
            raise ValueError(
                "EdgePartition lacks arc_flat_slot (built by an old "
                "partition_graph?); rebuild the partition")
        self.dg = dg
        self.template = template
        self.tdev = TemplateDev(template)
        self.tm = TemplateMasks(self.tdev)
        self.part = part
        self.P = part.P
        self.B = part.B
        self.n_local = part.n_local
        self.wave = wave
        self.collect_stats = collect_stats
        self.nlcc_edge_prune = nlcc_edge_prune
        self.tds_chunk = tds_chunk
        self.tds_max_rows = tds_max_rows
        self.work_aggregation = work_aggregation
        self.guarantee_precision = guarantee_precision
        self.arrs = part.device_arrays()
        # per-arc slot of the DeviceGraph's dst-sorted arcs inside the
        # flattened [P, P, B] bucket tensor — the edge_active gather/scatter
        # map (`arc_order` = the dst-sort permutation the caller already
        # computed building the DeviceGraph; avoids a second O(m log m) sort)
        order = (arc_order if arc_order is not None
                 else DeviceGraph.dst_sort_order(graph))
        if part.P * part.P * part.B >= 2**31:
            # the device-side map below is int32 (x64 is off by default); a
            # bucket tensor past 2^31 slots would silently wrap — refuse
            raise NotImplementedError(
                f"bucket tensor has {part.P * part.P * part.B} >= 2^31 slots;"
                " the int32 edge gather/scatter map would overflow — shard"
                " the graph coarser or add a 64-bit map")
        self._arc_slot = jnp.asarray(part.arc_flat_slot[order], jnp.int32)
        self._fns: Dict[Any, Callable] = {}
        self._nlcc_routes_taken: set = set()
        self.omega_all: Optional[jnp.ndarray] = None
        self.ea_all: Optional[jnp.ndarray] = None
        self.injector = injector

    # -- resilience seam ----------------------------------------------------
    def _fire(self, site: str, **ctx) -> None:
        """Host-seam fault-injection point: the sharded programs are pure
        jitted collectives, so simulated failures fire between device
        dispatches — exactly where a real rank loss would surface."""
        if self.injector is not None:
            self.injector.event(site, **ctx)

    def _prims(self) -> Prims:
        """The collective bundle, wrapped for trace-time accounting (and
        prim-seam injection) when a fault injector is attached."""
        p = axis_prims(SHARD_AXIS)
        if self.injector is not None:
            from repro.core import resilience as _res

            p = _res.instrument_prims(p, self.injector)
        return p

    def snapshot(self):
        """Phase-entry device snapshot for in-place retry (immutable jnp
        arrays: two references, no copy)."""
        return (self.omega_all, self.ea_all)

    def restore_snapshot(self, snap) -> None:
        self.omega_all, self.ea_all = snap

    # -- wrapper hook -------------------------------------------------------
    def _make(self, program: Callable, n_sharded: int) -> Callable:
        raise NotImplementedError

    def _fn(self, key, program: Callable, n_sharded: int) -> Callable:
        if key not in self._fns:
            self._fns[key] = self._make(program, n_sharded)
        return self._fns[key]

    # -- state --------------------------------------------------------------
    def init(self, initial_state: Optional[PruneState]) -> None:
        if initial_state is None:
            self.omega_all, self.ea_all = init_sharded_state(self.part, self.template)
        else:
            self.omega_all, self.ea_all = self.scatter_state(initial_state)

    def gather_state(self) -> PruneState:
        """Global PruneState (dst-sorted DeviceGraph arc order) from the
        sharded arrays — the bridge TDS / edge-prune / the final result use."""
        n, n0 = self.part.n, self.tdev.n0
        flat = self.omega_all[:, :self.n_local].reshape(self.P * self.n_local, -1)
        omega = unpack_bits(flat, n0)[:n]
        ea = jnp.take(self.ea_all.reshape(-1), self._arc_slot)
        return PruneState(omega=omega, edge_active=ea)

    def scatter_state(self, state: PruneState) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Inverse of gather_state: block-partition a global PruneState."""
        n, n0 = self.part.n, self.tdev.n0
        W = packed_words(n0)
        bits = jnp.asarray(state.omega, bool)
        pad = self.P * self.n_local - n
        if pad:
            bits = jnp.concatenate([bits, jnp.zeros((pad, n0), bool)], axis=0)
        omega = pack_bits(bits).reshape(self.P, self.n_local, W)
        omega = jnp.concatenate(
            [omega, jnp.zeros((self.P, 1, W), jnp.uint32)], axis=1)
        ea_flat = jnp.zeros((self.P * self.P * self.B,), bool)
        ea_flat = ea_flat.at[self._arc_slot].set(jnp.asarray(state.edge_active, bool))
        return omega, ea_flat.reshape(self.P, self.P, self.B)

    def final_state(self) -> PruneState:
        return self.gather_state()

    # -- reporting ----------------------------------------------------------
    def record_routes(self, stats: Dict) -> None:
        from repro.kernels import registry
        from repro.core.nlcc import NLCC_ROUTE
        from repro.core.lcc import LCC_ROUTE

        stats["dispatch_routes"] = {
            # the partition exchange layout is packed words by construction.
            # prune.nlcc starts as the a-priori estimate for a 3-hop wave;
            # finalize_stats overwrites it with the route(s) actually taken
            # once the constraint lengths are known (the fused eligibility
            # gate depends on L)
            LCC_ROUTE: registry.ROUTE_PACKED,
            NLCC_ROUTE: self._nlcc_route(),
        }
        stats["dispatch_policy_active"] = registry.get_policy() is not None
        stats["sharded"] = {
            "backend": self.name,
            "P": self.P,
            "bucket": registry.bucket_key(
                registry.shard_bucket(self.P, self.n_local, self.wave)),
        }

    def counts_dev(self) -> jnp.ndarray:
        om = self.omega_all[:, :self.n_local]
        return jnp.stack([
            jnp.sum(jnp.any(om != 0, axis=-1), dtype=jnp.int32),
            jnp.sum(self.ea_all, dtype=jnp.int32),
            jnp.sum(jax.lax.population_count(om).astype(jnp.int32), dtype=jnp.int32),
        ])

    def shard_counts_dev(self) -> jnp.ndarray:
        """int32[P, 2] per-shard [active vertices, active arcs], computed
        SHARD-LOCALLY: vertices from each shard's omega block, arcs from each
        shard's send buckets (every arc lives at its src shard; padding slots
        are never active). No exchange, no full gather — the phase-boundary
        imbalance trigger reads this with one small transfer. Post-LCC an
        active arc already implies both endpoints active and compatible, so
        these equal the host oracle's endpoint-masked counts
        (loadbalance.imbalance_stats) at every phase boundary."""
        om = self.omega_all[:, :self.n_local]
        v = jnp.sum(jnp.any(om != 0, axis=-1), axis=-1, dtype=jnp.int32)
        e = jnp.sum(self.ea_all, axis=(1, 2), dtype=jnp.int32)
        return jnp.stack([v, e], axis=-1)

    def counts_host(self) -> Dict[str, int]:
        c = np.asarray(self.counts_dev())
        return {"active_vertices": int(c[0]), "active_edges": int(c[1]),
                "omega_bits": int(c[2])}

    def sync(self) -> None:
        """Fence the device stream (no transfer) so phase wall-times include
        the phase's own device work."""
        jax.block_until_ready((self.omega_all, self.ea_all))

    def finalize_stats(self, stats: Dict) -> None:
        """Replace the a-priori prune.nlcc route estimate with the route(s)
        the wave executor actually took (constraints of different walk
        lengths can resolve differently through the fused eligibility gate;
        multiple distinct routes render joined, e.g. "fused+packed"). A run
        whose constraints never reached the wave executor (TDS-only) reports
        "none" — never a route that did not execute."""
        if "dispatch_routes" in stats:
            from repro.core.nlcc import NLCC_ROUTE

            stats["dispatch_routes"][NLCC_ROUTE] = (
                "+".join(sorted(self._nlcc_routes_taken))
                if self._nlcc_routes_taken else "none")

    # -- LCC ----------------------------------------------------------------
    def lcc(self, stats: Dict) -> None:
        self._fire("lcc")
        tm, n_local = self.tm, self.n_local
        prims = self._prims()

        def program(sa_dict, omega, ea):
            sa = ShardArrays(**sa_dict)
            om, ea2, it = lcc_shard_fixpoint(omega, ea, sa, tm, prims)
            return om, ea2, it

        fn = self._fn("lcc", program, n_sharded=3)
        self.omega_all, self.ea_all, it = fn(self.arrs, self.omega_all, self.ea_all)
        if stats is not None:
            stats["lcc_iterations"] = stats.get("lcc_iterations", 0) + int(it[0])
            stats["lcc_calls"] = stats.get("lcc_calls", 0) + 1

    # -- NLCC cycle/path ----------------------------------------------------
    def _nlcc_route(self, length: int = 3) -> str:
        from repro.kernels import registry

        if self.wave % 32 != 0:
            return registry.ROUTE_UNPACKED
        eligible = sharded_fused_eligible(
            self.n_local, self.P, self.B, self.wave, length)
        default = registry.ROUTE_FUSED if eligible else registry.ROUTE_PACKED
        route = registry.resolve_route(
            "prune.nlcc", registry.shard_bucket(self.P, self.n_local, self.wave),
            default=default,
            allowed=(registry.ROUTE_FUSED, registry.ROUTE_PACKED,
                     registry.ROUTE_UNPACKED))
        if route == registry.ROUTE_FUSED and not eligible:
            # the kernel's eligibility gate, composed with shard-local shapes
            route = registry.ROUTE_PACKED
        return route

    def _omega_column(self, q: int) -> jnp.ndarray:
        """bool[P, n_local] candidacy plane of template vertex q."""
        w, b = q // 32, q % 32
        return ((self.omega_all[:, :self.n_local, w] >> jnp.uint32(b)) & 1).astype(bool)

    def _cand_stack(self, walk: Sequence[int]) -> jnp.ndarray:
        return jnp.stack([self._omega_column(q) for q in walk], axis=1)  # [P, L+1, n_local]

    def nlcc(self, c: NonLocalConstraint, cstats: Dict,
             direction: str = "default"):
        from repro.kernels import registry as _registry
        from repro.core import nlcc as nlcc_mod

        self._fire("nlcc")
        # captured BEFORE the edge-prune bridge: its edge eliminations must
        # count toward the change flag that triggers the LCC re-run
        omega_before, ea_before = self.omega_all, self.ea_all
        if self.nlcc_edge_prune:
            # beyond-paper frontier edge pruning is a host-side pass — bridge it
            state = self.gather_state()
            new = nlcc_mod._edge_prune_pass(
                self.dg, state, c, self.template, self.wave, cstats)
            if new is not state:
                self.omega_all, self.ea_all = self.scatter_state(new)

        walks = nlcc_mod.expand_walks(c, direction)
        heads = [w[0] for w in walks]
        L = len(walks[0]) - 1
        route = self._nlcc_route(L)
        self._nlcc_routes_taken.add(route)
        wave_stat = {
            _registry.ROUTE_FUSED: "nlcc_fused_waves",
            _registry.ROUTE_PACKED: "nlcc_packed_waves",
            _registry.ROUTE_UNPACKED: "nlcc_plane_waves",
        }[route]

        # ONE host sync per constraint: the head-candidacy planes size the wave
        # loops; everything downstream stays on device
        head_planes = np.asarray(
            jnp.stack([self._omega_column(q) for q in heads]))  # [H, P, n_local]
        head_global = head_planes.reshape(len(heads), -1)[:, :self.part.n]
        keep_cols = [jnp.zeros((self.P, self.n_local + 1), bool) for _ in walks]
        n_waves = 0
        n_tokens = 0
        n_overlapped = 0
        for wi, walk in enumerate(walks):
            cand = self._cand_stack(walk)
            is_cyclic = walk[0] == walk[-1]
            sources = np.flatnonzero(head_global[wi])
            # one-wave-deep software pipeline (the `overlap` schedule): wave
            # i's survivor reduction (the only psum) is dispatched together
            # with / after wave i+1's hop exchanges — the two touch disjoint
            # state, so the collective overlaps the next wave's compute
            # instead of fencing it. `pending` = the frontier awaiting its
            # survivor decision; flushed at the walk boundary.
            pending = None
            for idsp, n_real in nlcc_mod.wave_batches(sources, self.wave):
                self._fire("wave", wave=n_waves)
                ids_dev = jnp.asarray(idsp, jnp.int32)
                if route == _registry.ROUTE_FUSED and pending is not None:
                    keep_cols[wi], f = self._wave_overlapped(
                        L, is_cyclic, cand, keep_cols[wi],
                        pending[0], pending[1], ids_dev)
                    n_overlapped += 1
                else:
                    f = self._wave_frontier(route, L, cand, ids_dev)
                    if pending is not None:
                        keep_cols[wi] = self._wave_finish(
                            route, is_cyclic, pending[0], keep_cols[wi],
                            pending[1])
                        n_overlapped += 1
                pending = (f, ids_dev)
                n_waves += 1
                n_tokens += n_real
            if pending is not None:
                keep_cols[wi] = self._wave_finish(
                    route, is_cyclic, pending[0], keep_cols[wi], pending[1])
        # remove head candidacy from failing sources (Alg. 5 line 8), on device
        omega = self.omega_all
        for wi, q0 in enumerate(heads):
            w, b = q0 // 32, q0 % 32
            word = omega[..., w]
            cleared = word & jnp.uint32(~np.uint32(1 << b))
            omega = omega.at[..., w].set(
                jnp.where(keep_cols[wi], word, cleared))
        self.omega_all = omega
        if cstats is not None:
            cstats["nlcc_tokens"] = cstats.get("nlcc_tokens", 0) + n_tokens
            cstats[wave_stat] = cstats.get(wave_stat, 0) + n_waves
            cstats["nlcc_constraints"] = cstats.get("nlcc_constraints", 0) + 1
            cstats["nlcc_waves"] = cstats.get("nlcc_waves", 0) + n_waves
            cstats["nlcc_overlapped_waves"] = (
                cstats.get("nlcc_overlapped_waves", 0) + n_overlapped)
            cstats["nlcc_host_syncs"] = cstats.get("nlcc_host_syncs", 0) + 1
        return jnp.any(omega_before != self.omega_all) | jnp.any(
            ea_before != self.ea_all)

    # -- wave pipeline stages ----------------------------------------------
    def _frontier_program(self, L):
        """Per-shard hop phase of one wave: seed + L hops, returning the
        hop-L packed frontier WITHOUT the survivor decision (that belongs to
        the pipelined finish stage)."""
        n_local, prims = self.n_local, self._prims()

        def program(sa_dict, ea, cand_stack, source_ids):
            sa = ShardArrays(**sa_dict)
            p = prims.axis_index()
            fp = pack_bits(_seed_frontier_planes(
                cand_stack[0], source_ids, n_local, p))

            def hop(f, cand_r):
                return frontier_shard_hop(f, ea, sa, cand_r, prims), None

            fp, _ = jax.lax.scan(hop, fp, cand_stack[1:])
            return fp

        return program

    def _finish_program(self, packed, is_cyclic):
        """Survivor decision + keep-column scatter for one completed wave
        frontier (the wave's only psum)."""
        n_local, prims = self.n_local, self._prims()

        def finish(f, keep, source_ids):
            p = prims.axis_index()
            if packed:
                planes = jnp.concatenate([
                    unpack_bits(f[:n_local], source_ids.shape[0]),
                    jnp.zeros((1, source_ids.shape[0]), bool)], axis=0)
            else:
                planes = f
            survived = _sharded_wave_survivors(
                planes, source_ids, n_local, is_cyclic, prims)
            return _scatter_keep(keep, survived, source_ids, n_local, p)

        return finish

    def _wave_frontier(self, route, L, cand, ids_dev):
        """Dispatch the hop phase of one wave; returns the hop-L frontier
        (packed words or boolean planes)."""
        from repro.kernels import registry as _registry

        n_local, prims = self.n_local, self._prims()
        if route == _registry.ROUTE_FUSED:
            fn = self._fn(("wave_front_fused", L),
                          self._frontier_program(L), n_sharded=3)
            return fn(self.arrs, self.ea_all, cand, ids_dev)

        packed = route == _registry.ROUTE_PACKED

        def seed(cand0, source_ids):
            p = prims.axis_index()
            planes = _seed_frontier_planes(cand0, source_ids, n_local, p)
            return pack_bits(planes) if packed else planes

        def hop(sa_dict, ea, f, cand_r):
            sa = ShardArrays(**sa_dict)
            if packed:
                return frontier_shard_hop(f, ea, sa, cand_r, prims)
            return frontier_shard_hop_unpacked(f, ea, sa, cand_r, prims)

        seed_fn = self._fn(("wave_seed", packed), seed, n_sharded=1)
        hop_fn = self._fn(("wave_hop", packed), hop, n_sharded=4)
        f = seed_fn(cand[:, 0], ids_dev)
        for r in range(1, L + 1):
            f = hop_fn(self.arrs, self.ea_all, f, cand[:, r])
        return f

    def _wave_finish(self, route, is_cyclic, f, keep_col, ids_dev):
        from repro.kernels import registry as _registry

        packed = route in (_registry.ROUTE_FUSED, _registry.ROUTE_PACKED)
        fn = self._fn(("wave_finish", packed, is_cyclic),
                      self._finish_program(packed, is_cyclic), n_sharded=2)
        return fn(f, keep_col, ids_dev)

    def _wave_overlapped(self, L, is_cyclic, cand, keep_col, f_prev, ids_prev,
                         ids_cur):
        """Fused route, steady state: ONE dispatch that finishes wave i-1
        (its survivor psum) AND runs wave i's seed + hop scan. The two
        dataflows are independent inside the program, so XLA schedules the
        reduction concurrently with the hop exchanges — the wave-level
        `overlap` schedule."""
        front = self._frontier_program(L)
        finish = self._finish_program(True, is_cyclic)

        def program(sa_dict, ea, cand_stack, keep, f_pending, prev_ids,
                    cur_ids):
            keep2 = finish(f_pending, keep, prev_ids)
            f_cur = front(sa_dict, ea, cand_stack, cur_ids)
            return keep2, f_cur

        fn = self._fn(("wave_fused_ov", L, is_cyclic), program, n_sharded=5)
        return fn(self.arrs, self.ea_all, cand, keep_col, f_prev,
                  ids_prev, ids_cur)

    # -- enumeration join ---------------------------------------------------
    def join_context(self):
        """Context for the device-resident enumeration join (core/join.py):
        the join programs run through this backend's program wrapper (vmap /
        shard_map) against the partition's join plan, reading the
        device-resident omega_all / ea_all directly — the reduced subgraph is
        never gathered to the host for enumeration."""
        from repro.core import join as join_mod

        return join_mod.ShardedJoinContext(self)

    # -- TDS (gather bridge) ------------------------------------------------
    def tds(self, c: NonLocalConstraint, cstats: Dict):
        from repro.core import tds as tds_mod

        self._fire("tds")
        state = self.gather_state()
        new = tds_mod.verify_tds_constraint(
            self.dg, state, c, chunk=self.tds_chunk,
            max_rows=self.tds_max_rows, stats=cstats,
            annotate=(c.complete and self.guarantee_precision),
            dedup=self.work_aggregation,
        )
        # the bridge is host-synced anyway, so force the flag here and skip
        # the full repack/scatter for a no-op constraint
        changed = bool(_state_changed(state, new))
        if changed:
            self.omega_all, self.ea_all = self.scatter_state(new)
        if cstats is not None:
            cstats["tds_gather_bridge"] = cstats.get("tds_gather_bridge", 0) + 1
        return changed


class SimBackend(_ShardedBackend):
    """Single-process simulation: the per-shard programs run under
    ``jax.vmap(..., axis_name=SHARD_AXIS)`` — vmap's collective batching rules
    turn the all_to_all into a transpose and psum into a batch sum, so the
    sharded math is provable against the local engine on one device."""

    name = "sim"

    def _make(self, program: Callable, n_sharded: int) -> Callable:
        def call(*args):
            in_axes = (0,) * n_sharded + (None,) * (len(args) - n_sharded)
            return jax.vmap(program, in_axes=in_axes, axis_name=SHARD_AXIS)(*args)

        return jax.jit(call)


class SpmdBackend(_ShardedBackend):
    """shard_map over a real mesh: one `jax.lax.all_to_all` per sweep/hop, the
    convergence flag psum-reduced on device. `axis_names` of the mesh may be a
    tuple — the flattened product is the shard axis (pure data-parallel
    irregular workload)."""

    name = "spmd"

    def __init__(self, graph, dg, template, part, *, mesh, **kw):
        super().__init__(graph, dg, template, part, **kw)
        self.mesh = mesh
        if int(np.prod(tuple(mesh.shape.values()))) != part.P:
            raise ValueError(
                f"mesh has {int(np.prod(tuple(mesh.shape.values())))} devices "
                f"but the partition has P={part.P} shards")
        self._axes = tuple(mesh.axis_names)

    def _make(self, program: Callable, n_sharded: int) -> Callable:
        from repro.kernels import compat

        ax = self._axes
        spec = P(ax)

        def per_shard(*args):
            local = [jax.tree_util.tree_map(lambda x: x[0], a)
                     for a in args[:n_sharded]]
            out = program(*local, *args[n_sharded:])
            return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], out)

        def call(*args):
            in_specs = (spec,) * n_sharded + (P(),) * (len(args) - n_sharded)
            fn = compat.shard_map(
                per_shard, mesh=self.mesh, in_specs=in_specs,
                out_specs=spec, check_vma=False)
            return fn(*args)

        return jax.jit(call)


def make_backend(
    graph,
    template: Template,
    *,
    mesh=None,
    partition=None,
    **kw,
):
    """Build the execution backend `prune` drives.

    mesh=None, partition=None        -> local (single device, identity exchange)
    partition=EdgePartition|int      -> sim   (vmap-simulated shards)
    mesh=Mesh [, partition=...]      -> spmd  (shard_map on the mesh)
    """
    if mesh is None and partition is None:
        if isinstance(graph, Graph):
            dg = DeviceGraph.from_host(graph)
        else:
            dg = graph
        return LocalBackend(dg, template, **kw)

    if not isinstance(graph, Graph):
        raise TypeError(
            "sharded prune (mesh=/partition=) needs the host Graph — the "
            "edge partition is built from host arrays")
    # local-only knobs are meaningless on the sharded backends
    for k in ("blocked", "force_pallas"):
        if kw.pop(k, None):
            raise ValueError(
                f"{k}= composes with the local backend only; the sharded "
                "engine routes by shard-local shape buckets instead")
    if partition is None:
        partition = int(np.prod(tuple(mesh.shape.values())))
    if isinstance(partition, int):
        partition = partition_graph(graph, partition)
    # ONE dst-sort serves both the DeviceGraph build and the backend's
    # edge_active gather/scatter map
    order = DeviceGraph.dst_sort_order(graph)
    dg = DeviceGraph.from_host(graph, order=order)
    kw["arc_order"] = order
    if mesh is None:
        return SimBackend(graph, dg, template, partition, **kw)
    return SpmdBackend(graph, dg, template, partition, mesh=mesh, **kw)
