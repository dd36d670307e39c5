"""The main pruning loop (paper Alg. 1) — PruneJuice in JAX.

    G* <- LCC(G, G0)
    for C0 in K0 (ordered: CC/PC by length, then TDS):
        G* <- NLCC(G*, G0, C0)
        if anything was eliminated: G* <- LCC(G*, G0)

One driver serves every execution backend (core/engine.py): `local` (single
device — today's optimized path), `spmd` (`mesh=` — shard_map + all_to_all
over an `EdgePartition`; the whole pipeline runs where the partitioned state
lives) and `sim` (`partition=` without a mesh — vmap-simulated shards for
single-process parity tests). The driver's control decisions (run LCC after a
constraint?) read ONE device bool per constraint; phase snapshots accumulate
device-side and materialize once at the end (eager under collect_stats=True).

The driver is structured as a RE-ENTERABLE phase loop: phase 0 is the initial
LCC, phase k (1..K) is constraint k plus its conditional LCC re-run. Pruning
is monotone, so phase boundaries are consistency points — with
`resilience=` (core/resilience.py) the driver snapshots state there through
`repro.checkpoint`, wraps each phase in the degradation ladder
(retry -> ref kernels -> chunk back-off -> checkpoint-and-raise), and on
shard loss restores the last valid checkpoint onto a possibly *smaller*
shard count via `loadbalance.elastic_handoff` (the paper's LB-16/LB-1
recover-on-smaller-deployment). The same compact-and-reshuffle triggers from
device-side per-shard imbalance counts at phase boundaries even without a
fault. Checkpoints and results always live in ORIGINAL graph coordinates, so
a recovered run is bit-identical to a fault-free one (pinned in
tests/test_resilience.py). NOTE: informational counters (lcc_iterations,
nlcc_tokens, ...) accumulate across retried attempts; the phase trajectory
commits only successful attempts and stays exact.

Flags expose the paper's ablations:
  edge_elimination=False  — vertex-elimination-only baseline (Fig. 6a)
  work_aggregation=False  — TDS token dedup off (Fig. 6b)
  guarantee_precision     — generate + annotate the complete-walk TDS
                            constraint (zero false positives, Def. 1) vs. the
                            heuristic CC/PC/partial-TDS pipeline only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple, Union

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.graph.structs import Graph, DeviceGraph
from repro.core.template import Template, generate_constraints, NonLocalConstraint
from repro.core.state import PruneState
from repro.core import engine as engine_mod
from repro.core import planner as planner_mod
from repro.core import resilience as resilience_mod


@dataclasses.dataclass
class PhaseStat:
    phase: str
    constraint: Optional[str]
    seconds: float
    active_vertices: int
    active_edges: int
    omega_bits: int
    extra: Dict


@dataclasses.dataclass
class PruneResult:
    state: PruneState
    template: Template
    dg: DeviceGraph
    phases: List[PhaseStat]
    stats: Dict
    # the execution backend that ran the prune — a sharded result hands its
    # device-resident shard arrays straight to the enumeration join, so
    # `enumerate_matches(result)` never gathers the reduced subgraph. A run
    # that restarted elastically finishes on a COMPACTED graph whose shard
    # arrays no longer describe `dg`; it reports backend=None and enumeration
    # takes the host route over the original-coordinate state.
    backend: Optional[object] = None

    # The masks are device->host materializations hit repeatedly by benchmarks
    # and enumeration — computed once, cached on the instance.
    @functools.cached_property
    def vertex_mask(self) -> np.ndarray:
        return self.omega.any(axis=1)

    @functools.cached_property
    def edge_mask(self) -> np.ndarray:
        """Arc mask in the dst-sorted DeviceGraph order, endpoint-consistent."""
        vm = self.vertex_mask
        ea = np.asarray(self.state.edge_active)
        return ea & vm[np.asarray(self.dg.src)] & vm[np.asarray(self.dg.dst)]

    @functools.cached_property
    def omega(self) -> np.ndarray:
        return np.asarray(self.state.omega)

    def counts(self):
        return {
            "V*": int(self.vertex_mask.sum()),
            "E*": int(self.edge_mask.sum()),
        }


def prune(
    graph: Union[Graph, DeviceGraph],
    template: Template,
    *,
    guarantee_precision: bool = True,
    edge_elimination: bool = True,
    work_aggregation: bool = True,
    nlcc_edge_prune: bool = False,
    wave: int = 1024,
    tds_chunk: int = 4096,
    tds_max_rows: int = 2_000_000,
    label_freq: Optional[np.ndarray] = None,
    constraints: Optional[List[NonLocalConstraint]] = None,
    plan: Optional["planner_mod.QueryPlan"] = None,
    initial_state: Optional[PruneState] = None,
    collect_stats: bool = False,
    blocked=None,
    force_pallas: bool = False,
    mesh=None,
    partition=None,
    resilience: Optional[resilience_mod.ResilienceConfig] = None,
) -> PruneResult:
    """Run the full pruning pipeline on the chosen execution backend.

    `mesh=` (a jax Mesh) runs the ENTIRE pipeline sharded under shard_map —
    the initial LCC, the ordered NLCC constraint loop with the batched wave
    executor, psum-reduced convergence — over an `EdgePartition` built from
    the host graph (or passed via `partition=`, an EdgePartition or a shard
    count). `partition=` without a mesh uses the vmap-simulated `sim` backend
    (bit-identical math, single process). The result is the gathered global
    state, directly consumable by `enumerate_matches`.

    On the local backend, `blocked` (a graph.blocked.BlockedStructure) makes
    every LCC sweep and eligible NLCC wave *packed-capable*: the tuned
    dispatch policy (repro.kernels.registry, `registry.tune()` / the
    persisted policy cache) then picks the route per shape bucket — packed vs
    unpacked for LCC; packed, unpacked, or the fused multi-hop wave engine
    (one `bitset_wave` kernel call per NLCC wave, frontier resident across
    hops) for NLCC — and the kernel registry decides pallas / interpret / ref
    per call. Untuned, the routing matches the historical hardcoded choice
    (LCC: packed whenever `blocked` is given; NLCC: packed only where the
    kernel compiles, i.e. on TPU). On the sharded backends routes resolve per
    SHARD-LOCAL shape bucket (`registry.shard_bucket`) among the fused /
    packed / unpacked wave programs. The routes actually taken land in
    `stats["dispatch_routes"]`. `force_pallas` pins the packed interpret-mode
    kernel path for parity testing (local backend only).

    `resilience=` (a core/resilience.ResilienceConfig) turns on phase-boundary
    checkpointing, the per-phase degradation ladder, deterministic fault
    injection (when the config carries a FaultInjector), and elastic
    restart/rebalance — see the module docstring and core/resilience.py."""
    # every span of one request shares the root span's id as `query`
    with obs.span("prune", n0=template.n0) as root:
        if isinstance(graph, Graph) and label_freq is None:
            with obs.span("prune.plan", kind="host"):
                label_freq = graph.label_frequency()

        backend_kw = dict(
            wave=wave, blocked=blocked, force_pallas=force_pallas,
            edge_elimination=edge_elimination, collect_stats=collect_stats,
            nlcc_edge_prune=nlcc_edge_prune, tds_chunk=tds_chunk,
            tds_max_rows=tds_max_rows, work_aggregation=work_aggregation,
            guarantee_precision=guarantee_precision,
        )
        if resilience is not None and resilience.injector is not None:
            backend_kw["injector"] = resilience.injector
        backend = engine_mod.make_backend(
            graph, template, mesh=mesh, partition=partition, **backend_kw)
        dg = backend.dg
        stats: Dict = {"edge_elimination": edge_elimination,
                       "work_aggregation": work_aggregation,
                       "backend": backend.name}
        if resilience is not None:
            stats["resilience"] = {
                "checkpoints": 0, "checkpoint_seconds": [], "restarts": [],
                "rebalances": [], "ladder": [], "recovery_seconds": 0.0,
            }

        backend.init(initial_state)
        if template.n0 == 1:
            return PruneResult(backend.final_state(), template, dg, [], stats,
                               backend=backend)

        backend.record_routes(stats)  # each backend decides what (if anything) to record

        # Beyond-paper fast path: with forward-backward frontier edge pruning,
        # CC alone yields the exact edge set for unique-label edge-monocyclic
        # templates (every surviving edge lies on a completing label-cycle, and
        # unique labels make any such cycle a true match) — the complete-walk TDS
        # becomes unnecessary. Validated against the oracle in the property tests.
        skip_complete = (
            nlcc_edge_prune and guarantee_precision
            and not template.is_acyclic()
            and template.is_edge_monocyclic() and not template.repeated_labels()
        )
        if skip_complete:
            stats["tds_skipped_via_frontier_edge_prune"] = True
        with obs.span("prune.plan", kind="host"):
            # The constraint list is fixed ONCE, from the original graph's label
            # frequencies — an elastic restart must replay the identical phases.
            if constraints is None:
                constraints = generate_constraints(
                    template, label_freq=label_freq,
                    guarantee_precision=guarantee_precision and not skip_complete,
                )
                if plan is None:
                    # plan-level optimizer lookup (core/planner.py): only when the
                    # active policy carries tuned plans — an untuned checkout never
                    # touches graph stats and runs the heuristic order
                    # byte-identically
                    plan = _maybe_resolve_plan(graph, dg, template, constraints,
                                               label_freq)
            if plan is not None:
                _check_plan(plan, constraints)
                constraints = plan.constraints()
            else:
                plan = planner_mod.heuristic_plan(constraints)
        stats["n_constraints"] = len(constraints)
        root.attrs["constraints"] = len(constraints)
        stats["plan"] = {
            "source": plan.source,
            "phases": [
                {"sig": p.signature, "engine": p.engine,
                 "direction": p.direction,
                 "predicted_s": (plan.per_phase_s[i] if plan.per_phase_s
                                 else None),
                 "actual_s": None}
                for i, p in enumerate(plan.phases)
            ],
        }

        driver = _Driver(
            graph=graph, template=template, backend=backend, dg=dg, stats=stats,
            plan=plan, res=resilience, collect_stats=collect_stats,
            mesh=mesh, backend_kw=backend_kw, initial_state=initial_state,
        )
        driver.run()
        return driver.finish()


def _maybe_resolve_plan(graph, dg, template, constraints, label_freq):
    from repro.kernels import registry

    policy = registry.get_policy()
    if policy is None or not policy.plans:
        return None
    from repro.graph import stats as gstats

    if isinstance(graph, Graph):
        st = gstats.collect_graph_stats(graph)
    else:
        nl = (len(label_freq) if label_freq is not None
              else int(obs.to_host(dg.labels, "labels").max()) + 1)
        st = gstats.collect_graph_stats(dg, n_labels=nl)
    return planner_mod.resolve_query_plan(template, constraints, st)


def _check_plan(plan, constraints):
    """An explicit/cached plan must cover exactly the constraints this run
    generates — same multiset of signatures — or phase identity is broken."""
    want = sorted(planner_mod.constraint_signature(c) for c in constraints)
    got = sorted(plan.signatures())
    if want != got:
        raise ValueError(
            f"query plan does not match generated constraints: plan phases "
            f"{got} != constraints {want}")


class _Driver:
    """The re-enterable phase loop. Phase 0 = initial LCC; phase k (1..K) =
    constraint k + conditional LCC. `completed` is the last committed phase;
    a fault rolls it back to the restored checkpoint's phase and the loop
    simply re-enters. Phase snapshots are STAGED per attempt and committed
    only on success, so retried/replayed work never duplicates trajectory
    entries."""

    def __init__(self, *, graph, template, backend, dg, stats, plan,
                 res, collect_stats, mesh, backend_kw, initial_state):
        self.graph = graph
        self.template = template
        self.backend = backend
        self.dg = dg  # ORIGINAL DeviceGraph — result/checkpoint coordinates
        self.stats = stats
        self.plan = plan
        self.phases = plan.phases
        self.constraints = plan.constraints()
        # phase identity BY SIGNATURE (not positional index): checkpoints of
        # one plan must never resume under another (core/resilience.py).
        # Identity includes engine+direction — a direction change alters the
        # committed state, so same-order different-direction plans differ.
        self.plan_sigs = plan.identities()
        self.res = res
        self.inj = res.injector if res is not None else None
        self.collect_stats = collect_stats
        self.mesh = mesh
        self.backend_kw = backend_kw
        self.initial_state = initial_state
        self.K = len(self.constraints)
        self.completed = -1
        self.committed: List[Tuple[int, tuple]] = []  # (phase idx, raw entry)
        self._stage: List[tuple] = []
        # coordinate map back to the original graph after an elastic
        # compact-and-reshuffle; None = still in original coordinates
        self.remap: Optional["loadbalance.ElasticRemap"] = None
        self.restarts = 0
        self._recovery_t0: Optional[float] = None

    # -- phase bodies -------------------------------------------------------
    def _phase_initial(self):
        with obs.span("prune.phase", phase="LCC") as sp:
            self.backend.lcc(self.stats)
            self._fence()
        self._snap("LCC", None, sp.seconds, {})

    def _phase_constraint(self, k: int):
        p = self.phases[k - 1]
        c = p.constraint
        phase = f"NLCC-{c.kind}"
        cstats: Dict = {}
        with obs.span("prune.phase", phase=phase) as sp:
            if p.engine == planner_mod.ENGINE_NLCC:
                changed = self.backend.nlcc(c, cstats, direction=p.direction)
            else:
                changed = self.backend.tds(c, cstats)
            self._fence()
        self._snap(phase, str(c.walk), sp.seconds, cstats)
        # predicted-vs-actual for the plan report, the phase span's duration;
        # assignment (not +=) so a resilience replay of the phase records
        # only the committed attempt
        self.stats["plan"]["phases"][k - 1]["actual_s"] = sp.seconds
        # ONE device bool decides the re-run — not six blocking count reads
        if bool(obs.to_host(changed, "changed")):
            with obs.span("prune.phase", phase="LCC") as sp:
                self.backend.lcc(self.stats)
                self._fence()
            self._snap("LCC", None, sp.seconds, {})

    def _fence(self):
        # the phase's wall time must include its device work (the recorded
        # perf trajectory compares PR-over-PR), so fence the stream — a sync
        # with NO transfer — before the phase span closes
        with obs.span("prune.fence"):
            self.backend.sync()

    def _snap(self, phase, cname, secs, extra):
        # The snapshot counts stay a lazy device value until ONE
        # materialization at the end of the run; eager host counts only
        # under collect_stats=True (satellite of PR 4)
        counts = (self.backend.counts_host() if self.collect_stats
                  else self.backend.counts_dev())
        self._stage.append((phase, cname, secs, extra, counts))

    # -- driver loop --------------------------------------------------------
    def run(self):
        from repro.kernels import registry

        with contextlib.ExitStack() as stack:
            if self.inj is not None:
                # every registry.dispatch anywhere in the run reports to the
                # injector (the "dispatch" site / per-kernel fault seam)
                stack.enter_context(registry.dispatch_hook(self.inj.on_dispatch))
            counts = stack.enter_context(registry.count_dispatches())
            self._loop()
        # which kernels ran in which mode (pallas / interpret / ref)
        self.stats["kernel_dispatches"] = registry.dispatch_report(counts)

    def _loop(self):
        while True:
            try:
                while self.completed < self.K:
                    k = self.completed + 1
                    self._run_phase(k)
                    self._after_phase(k)
                return
            except (resilience_mod.ShardLost,
                    resilience_mod.PhaseFailed) as e:
                self._recover(e)

    def _run_phase(self, k: int):
        if self.inj is not None:
            self.inj.begin_phase(k)
        if k == 0:
            body = self._phase_initial
        else:
            body = functools.partial(self._phase_constraint, k)

        def attempt():
            self._stage = []
            body()

        if self.res is None:
            attempt()
        else:
            resilience_mod.run_phase_with_ladder(
                attempt,
                snapshot=self.backend.snapshot,
                restore=self.backend.restore_snapshot,
                retry=self.res.retry,
                injector=self.inj,
                on_chunk_backoff=self._chunk_backoff,
                ladder_log=self.stats["resilience"]["ladder"],
            )
        self.committed.extend((k, entry) for entry in self._stage)
        self._stage = []
        self.completed = k

    def _chunk_backoff(self, factor: int):
        # shrink the TDS chunk on the live backend AND in the restart kwargs,
        # so a later elastic restart keeps the backed-off size
        self.backend.tds_chunk = max(1, self.backend.tds_chunk // factor)
        self.backend_kw["tds_chunk"] = self.backend.tds_chunk

    def _after_phase(self, k: int):
        res = self.res
        if res is None:
            return
        every = max(res.checkpoint_every, 1)
        if res.checkpoint_dir is not None and k % every == 0:
            self._checkpoint(k)
        el = res.elastic
        if (el is not None and el.imbalance_trigger is not None
                and k < self.K and self._sharded()):
            # satellite: shard-local device counts, ONE small [P,2] readback
            counts = np.asarray(self.backend.shard_counts_dev())
            from repro.core import loadbalance

            bs = loadbalance.imbalance_stats_from_counts(
                counts[:, 0], counts[:, 1])
            if (counts[:, 1].sum() > 0
                    and bs.max_over_mean_edges > el.imbalance_trigger):
                self._rebalance(k, bs)

    def _sharded(self) -> bool:
        return isinstance(self.backend, engine_mod._ShardedBackend)

    def _freeze_committed(self):
        """Materialize committed deferred phase counts to host values. Called
        before the backend is swapped: the lazy device counts of already-
        committed phases live on the OLD backend's mesh and cannot be stacked
        with the new one's in the final one-sync materialization."""
        frozen = []
        for k, (phase, cname, secs, extra, counts) in self.committed:
            if not isinstance(counts, dict):
                c = np.asarray(counts)
                counts = {"active_vertices": int(c[0]),
                          "active_edges": int(c[1]),
                          "omega_bits": int(c[2])}
            frozen.append((k, (phase, cname, secs, extra, counts)))
        self.committed = frozen

    # -- checkpointing ------------------------------------------------------
    def _state_np_original(self) -> Tuple[np.ndarray, np.ndarray]:
        """(omega, edge_active) as host arrays in ORIGINAL coordinates."""
        from repro.core import loadbalance

        state = self.backend.final_state()
        omega = np.asarray(state.omega, bool)
        ea = np.asarray(state.edge_active, bool)
        if self.remap is not None:
            st = loadbalance.remap_state_to_original(
                PruneState(omega=omega, edge_active=ea), self.remap,
                self.template.n0)
            omega, ea = np.asarray(st.omega), np.asarray(st.edge_active)
        return omega, ea

    def _checkpoint(self, k: int):
        from repro.checkpoint import ckpt

        t0 = time.perf_counter()
        omega, ea = self._state_np_original()
        meta = {"phase": int(k), "backend": self.backend.name,
                "n": int(self.dg.n), "m": int(ea.size),
                "n0": int(self.template.n0),
                # phase identity BY CONSTRAINT SIGNATURE: a resumed run under
                # a different (e.g. newly tuned) plan must refuse cleanly
                # rather than replay the wrong phase at position k
                "phase_sig": self._phase_sig(k),
                "plan_sigs": list(self.plan_sigs)}
        part = getattr(self.backend, "part", None)
        if part is not None:
            meta["partition"] = part.meta()
        ckpt.save_checkpoint(
            self.res.checkpoint_dir, k, {"omega": omega, "edge_active": ea},
            extra_meta=meta, keep=self.res.keep)
        rs = self.stats["resilience"]
        rs["checkpoints"] += 1
        rs["checkpoint_seconds"].append(time.perf_counter() - t0)

    def _phase_sig(self, k: int) -> str:
        """Signature identity of phase k: the initial LCC for k=0, else the
        planned constraint the phase verified."""
        return "lcc:init" if k == 0 else self.plan_sigs[k - 1]

    def _check_ckpt_plan(self, meta: Dict, phase0: int):
        """Refuse to resume a checkpoint written under a different plan.
        Checkpoints predating the plan field (no "plan_sigs") fall back to
        the old positional-index identity."""
        stored = meta.get("plan_sigs")
        if stored is not None and list(stored) != list(self.plan_sigs):
            raise resilience_mod.PlanMismatch(
                f"checkpoint at phase {phase0} was written under plan "
                f"{list(stored)} but this run executes {list(self.plan_sigs)}"
                " — phases are keyed by constraint signature; delete the "
                "checkpoint or re-run under the original plan")
        stored_sig = meta.get("phase_sig")
        if (stored_sig is not None and 0 <= phase0 <= len(self.plan_sigs)
                and str(stored_sig) != self._phase_sig(phase0)):
            raise resilience_mod.PlanMismatch(
                f"checkpoint phase {phase0} is {stored_sig!r} but this "
                f"run's phase {phase0} is {self._phase_sig(phase0)!r}")

    # -- recovery -----------------------------------------------------------
    def _recover(self, cause: BaseException):
        from repro.checkpoint import ckpt

        res = self.res
        if res.checkpoint_dir is None:
            raise resilience_mod.ResilienceExhausted(
                "phase failed and no checkpoint_dir is configured — "
                "cannot recover") from cause
        if self.restarts >= res.max_restarts:
            raise resilience_mod.ResilienceExhausted(
                f"restart budget exhausted after {self.restarts} "
                "restarts") from cause
        self.restarts += 1
        t0 = time.perf_counter()
        if self._recovery_t0 is None:
            self._recovery_t0 = t0
        n, n0 = int(self.dg.n), self.template.n0
        m = int(np.asarray(self.dg.src).size)
        like = {"omega": np.zeros((n, n0), bool),
                "edge_active": np.zeros((m,), bool)}
        try:
            # torn/corrupt checkpoint dirs are skipped inside (satellite)
            tree, meta = ckpt.restore_checkpoint(res.checkpoint_dir, like)
            state0 = PruneState(
                omega=np.asarray(tree["omega"], bool),
                edge_active=np.asarray(tree["edge_active"], bool))
            phase0 = int(meta["phase"])
            self._check_ckpt_plan(meta, phase0)
        except FileNotFoundError:
            state0, phase0 = None, -1  # nothing saved yet: re-prune fresh
        P_old = int(getattr(self.backend, "P", 1))
        P_new = P_old
        if res.elastic is not None and res.elastic.restart_P:
            P_new = int(res.elastic.restart_P)
        self._switch_backend(state0, P_new)
        # phases past the snapshot will be re-run — drop their entries
        self.committed = [(k, e) for k, e in self.committed if k <= phase0]
        self.completed = phase0
        self.stats["resilience"]["restarts"].append({
            "cause": type(cause).__name__,
            "restored_phase": phase0,
            "from_P": P_old, "to_P": P_new,
            "seconds": time.perf_counter() - t0,
        })

    def _switch_backend(self, state0: Optional[PruneState], P_new: int):
        """Rebuild the execution backend after a fatal fault: compact the
        restored original-coordinate snapshot onto P_new shards (elastic),
        or — when nothing was pruned yet / the active subgraph is degenerate
        / the backend is local — plainly repartition the original graph."""
        from repro.core import loadbalance

        self._freeze_committed()
        was_sharded = self._sharded()
        kw = dict(self.backend_kw)
        seed = self.res.elastic.seed if self.res.elastic is not None else 0
        handoff = None
        if was_sharded and isinstance(self.graph, Graph) and state0 is not None:
            handoff = loadbalance.elastic_handoff(
                self.graph, self.dg, state0, P_new, seed=seed)
        if handoff is not None:
            g_new, part_new, state_new, remap = handoff
            self.backend = engine_mod.make_backend(
                g_new, self.template, mesh=self._mesh_for(P_new),
                partition=part_new, **kw)
            self.backend.init(PruneState(
                omega=jnp.asarray(state_new.omega),
                edge_active=jnp.asarray(state_new.edge_active)))
            self.remap = remap
        else:
            mesh_new = self._mesh_for(P_new) if was_sharded else None
            partition = (P_new if (was_sharded and mesh_new is None)
                         else None)
            self.backend = engine_mod.make_backend(
                self.graph, self.template, mesh=mesh_new,
                partition=partition, **kw)
            if state0 is not None:
                self.backend.init(PruneState(
                    omega=jnp.asarray(state0.omega),
                    edge_active=jnp.asarray(state0.edge_active)))
            else:
                self.backend.init(self.initial_state)
            self.remap = None
        self.backend.record_routes(self.stats)

    def _mesh_for(self, P_new: int):
        """The mesh a restarted spmd backend runs on: the original mesh when
        the shard count is unchanged, else a fresh flat mesh over the first
        P_new devices (the recover-onto-smaller-mesh path)."""
        if self.mesh is None:
            return None
        if int(np.prod(tuple(self.mesh.shape.values()))) == P_new:
            return self.mesh
        from repro.launch.mesh import make_shard_mesh

        return make_shard_mesh(P_new)

    # -- imbalance-triggered rebalance (no fault) ---------------------------
    def _rebalance(self, k: int, bs):
        from repro.core import loadbalance

        if not isinstance(self.graph, Graph):
            return
        el = self.res.elastic
        t0 = time.perf_counter()
        omega, ea = self._state_np_original()
        P_old = int(self.backend.P)
        P_new = int(el.rebalance_P) if el.rebalance_P else P_old
        handoff = loadbalance.elastic_handoff(
            self.graph, self.dg,
            PruneState(omega=omega, edge_active=ea), P_new, seed=el.seed)
        if handoff is None:
            return  # degenerate active subgraph: nothing to balance
        self._freeze_committed()
        g_new, part_new, state_new, remap = handoff
        self.backend = engine_mod.make_backend(
            g_new, self.template, mesh=self._mesh_for(P_new),
            partition=part_new, **dict(self.backend_kw))
        self.backend.init(PruneState(
            omega=jnp.asarray(state_new.omega),
            edge_active=jnp.asarray(state_new.edge_active)))
        self.remap = remap
        self.backend.record_routes(self.stats)
        self.stats["resilience"]["rebalances"].append({
            "phase": k, "from_P": P_old, "to_P": P_new,
            "max_over_mean_before": float(bs.max_over_mean_edges),
            "seconds": time.perf_counter() - t0,
        })

    # -- finalization -------------------------------------------------------
    def finish(self) -> PruneResult:
        self.backend.finalize_stats(self.stats)
        if self.res is not None and self._recovery_t0 is not None:
            self.stats["resilience"]["recovery_seconds"] = (
                time.perf_counter() - self._recovery_t0)
        raw = [entry for _, entry in self.committed]
        if self.remap is None:
            state = self.backend.final_state()
            result_backend = self.backend
        else:
            # the run finished on a compacted/reshuffled graph: express the
            # state in original coordinates (bit-identical to fault-free by
            # monotonicity) and drop the backend — its shard arrays no
            # longer describe `dg`, so enumeration takes the host route
            omega, ea = self._state_np_original()
            state = PruneState(omega=jnp.asarray(omega),
                               edge_active=jnp.asarray(ea))
            result_backend = None
        return PruneResult(state, self.template, self.dg,
                           _materialize(raw), self.stats,
                           backend=result_backend)


def _materialize(raw_phases: List[tuple]) -> List[PhaseStat]:
    """Turn accumulated snapshots into PhaseStats. Deferred (device-array)
    counts are stacked and transferred in ONE host sync."""
    deferred = [c for *_, c in raw_phases if not isinstance(c, dict)]
    if deferred:
        mat = iter(obs.to_host(jnp.stack(deferred), "phase_counts"))
    phases: List[PhaseStat] = []
    for phase, cname, secs, extra, counts in raw_phases:
        if isinstance(counts, dict):
            av, ae, ob = (counts["active_vertices"], counts["active_edges"],
                          counts["omega_bits"])
        else:
            av, ae, ob = (int(x) for x in next(mat))
        phases.append(PhaseStat(
            phase=phase, constraint=cname, seconds=secs,
            active_vertices=av, active_edges=ae, omega_bits=ob, extra=extra))
    return phases
