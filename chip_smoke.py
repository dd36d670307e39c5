"""Bring-up check of the constraint-checking pipeline on a TPU.

    python chip_smoke.py [--seed N]              # one chip: every phase below
    python chip_smoke.py --chips 4 [--seed N]    # four chips: the sharded path

One process runs every phase from one seed. Graphs are Graph500 R-MAT (edge
factor 16, preset graph500, degree labels) with 64 planted copies of a
4-vertex needle whose labels no background vertex carries, so the needle's
exact match count is 64 x |Aut|. Local-backend prunes run on the fused NLCC
route (one kernel call per wave).

  device  the first JAX device must be a TPU; there is no CPU fallback.
  kernel  scale 20, blocked at bn=64 (4.8 GiB of masks): prune() of the
          needle must dispatch bitset_spmm compiled (mode "pallas"), equal
          the same prune with every kernel on its jnp reference
          (registry.mode_override(MODE_REF)) bit for bit, and count
          64 x |Aut|.
  wave    the largest scale whose 1024-source wave the bitset_wave VMEM gate
          admits (13): the needle and T4-square-rare must dispatch
          bitset_wave compiled, bit-identical to the references; then the
          local-backend count of every served template.
  served  GraphQueryEngine count queries (prune_batch, the jnp shard
          programs) on the wave phase's graph; each count must equal the
          local backend's count of the same template.
  oracle  scale 11: every template's local-backend count must equal the
          brute-force enumerator of core/oracle.py.

With --chips 4 only the sharded path runs, at scale 12: the spmd backend on
a 4-device mesh against the local backend on device 0, bit for bit, with
the state sharded over four devices, and the spmd counts equal to the local
ones.

Every phase prints what it did and checked, and why it runs below scale 20;
times are labelled set-up, compile or run. The last line of stdout is one
JSON object, {"ok": true, "device": {...}}, printed only when every check
passed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.core.enumerate import count_automorphisms, count_matches  # noqa: E402
from repro.core.oracle import enumerate_matches_bruteforce  # noqa: E402
from repro.core.pipeline import prune  # noqa: E402
from repro.core.template import Template  # noqa: E402
from repro.graph import generators as gen  # noqa: E402
from repro.graph.blocked import build_blocked_structure  # noqa: E402
from repro.graph.structs import DeviceGraph, Graph  # noqa: E402
from repro.kernels import compat, ops, registry  # noqa: E402
from repro.serve.graph_query import MODE_COUNT, GraphQueryEngine, example_workload  # noqa: E402

N_NEEDLES = 64
NEEDLE_EDGES = [(0, 1), (1, 2), (2, 3), (3, 0)]
# WDC-like templates of benchmarks/common.py (labels, edges)
WDC_TEMPLATES = {
    "T3-square": ([3, 4, 5, 6], [(0, 1), (1, 2), (2, 3), (3, 0)]),
    "T4-square-rare": ([6, 7, 8, 7], [(0, 1), (1, 2), (2, 3), (3, 0)]),
}
BN = 64
WAVE = 32          # NLCC sources per wave: one packed word per vertex
WIDE_WAVE = 1024   # 32 words per vertex, the wave kernel's widest gated case
KERNEL_SCALE = 20
SHARDED_SCALE = 12
ORACLE_SCALE = 11
SERVED_TEMPLATES = ("needle", "T4-square-rare", "T3-square", "workload-0",
                    "workload-1", "workload-2", "workload-3")


class CheckFailed(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    log(f"  check ok: {what}")


# ------------------------------------------------------------------ graphs
def planted_graph(scale: int, seed: int):
    """(graph, needle template) — R-MAT background plus N_NEEDLES needles."""
    bg = gen.rmat_graph(scale, edge_factor=16, preset="graph500", seed=seed)
    top = int(bg.labels.max())
    labels = [top + 1, top + 2, top + 2, top + 1]
    needle = Graph.from_undirected_pairs(4, NEEDLE_EDGES, labels)
    g = gen.planted_pattern_graph(bg, needle, N_NEEDLES, seed=seed + 1)
    return g, Template(labels, NEEDLE_EDGES)


def setup_graph(scale: int, seed: int, blocked: bool = True):
    """Host graph, device graph and (optionally) the bn=BN blocked structure,
    with the set-up seconds of each printed."""
    t0 = time.perf_counter()
    g, needle = planted_graph(scale, seed)
    t1 = time.perf_counter()
    dg = DeviceGraph.from_host(g)
    jax.block_until_ready(dg.src)
    t2 = time.perf_counter()
    log(f"[graph] R-MAT scale {scale}, seed {seed}, {N_NEEDLES} needles "
        f"{[int(x) for x in needle.labels]}: n={g.n} m={g.m}; set-up: host "
        f"generation {t1 - t0:.2f} s, device upload {t2 - t1:.2f} s")
    bs = None
    if blocked:
        bs = build_blocked_structure(np.asarray(dg.src), np.asarray(dg.dst), dg.n, bn=BN)
        host = sum(a.nbytes for a in (bs.pairs, bs.edge_block, bs.edge_word,
                                      bs.edge_bit, bs.row_first, bs.row_last))
        masks = bs.nnzb * bs.words_per_block * 4
        log(f"  blocked bn={BN}: n_pad={bs.n_pad} nnzb={bs.nnzb}; host arrays "
            f"{host} B, device masks {masks} B ({masks / 2**30:.3f} GiB); "
            f"set-up {time.perf_counter() - t2:.2f} s")
    return g, needle, g.label_frequency(), dg, bs


def templates_for(g: Graph, needle: Template, seed: int):
    out = {"needle": needle}
    for name, (labels, edges) in WDC_TEMPLATES.items():
        out[name] = Template(labels, edges)
    top = int(g.labels.max()) - 2  # the background's highest label
    for i, t in enumerate(example_workload(4, seed=seed, labels_max=top)):
        out[f"workload-{i}"] = t
    return out


def wave_scale(top: int) -> int:
    """Largest R-MAT scale <= top whose planted graph the bitset_wave VMEM
    gate admits at a WIDE_WAVE-source wave and bn = BN."""
    for s in range(top, 5, -1):
        if ops.bitset_wave_vmem_bytes(n_pad_of(s), WIDE_WAVE // 32, BN) \
                <= ops.BITSET_WAVE_VMEM_BUDGET:
            return s
    raise CheckFailed("no R-MAT scale fits the bitset_wave VMEM budget")


def n_pad_of(scale: int) -> int:
    return -(-((1 << scale) + 4 * N_NEEDLES) // BN) * BN


# ------------------------------------------------------------------ prunes
@contextlib.contextmanager
def fused_nlcc():
    """Route local-backend NLCC waves to the fused wave engine. Untuned, the
    TPU default is one eager bitset_spmm launch per hop, each rebuilding the
    block masks. Where bitset_wave is ineligible its waves run the
    reference, one dispatch per wave, and the dispatch counts say so."""
    policy = registry.DispatchPolicy()
    policy.set_route("prune.nlcc", "tpu", registry.BUCKET_ANY, registry.ROUTE_FUSED)
    registry.set_policy(policy)
    try:
        yield
    finally:
        registry.set_policy(None)


def timed_prune(dg, t, lf, **kw):
    with registry.count_dispatches() as counts:
        t0 = time.perf_counter()
        res = prune(dg, t, label_freq=lf, **kw)
        jax.block_until_ready((res.state.omega, res.state.edge_active))
        dt = time.perf_counter() - t0
    return res, registry.dispatch_report(counts), dt


def check_same_state(a, b, what: str) -> None:
    check(np.array_equal(a.omega, b.omega)
          and np.array_equal(a.edge_mask, b.edge_mask),
          f"{what}: omega and edge mask bit-identical")


def kernel_run(name, dg, t, lf, bs, wave, expect):
    """Local-backend prune with the kernels, then the same prune with every
    kernel on its reference; returns the kernel-path result."""
    res, disp, dt = timed_prune(dg, t, lf, blocked=bs, wave=wave)
    log(f"  {name}: prune (compile + run) {dt:.2f} s, V*E* {res.counts()}, "
        f"routes {res.stats.get('dispatch_routes')}, dispatches {disp}")
    for kernel in expect:
        check(disp.get(f"{kernel}:{registry.MODE_PALLAS}", 0) > 0,
              f"{name}: {kernel} ran compiled (mode pallas)")
    check(not any(k.endswith(":" + registry.MODE_INTERPRET) for k in disp),
          f"{name}: no kernel ran in the interpreter")
    with registry.mode_override(registry.MODE_REF):
        ref, rdisp, rdt = timed_prune(dg, t, lf, blocked=bs, wave=wave)
    log(f"  {name}: reference prune (compile + run) {rdt:.2f} s, dispatches {rdisp}")
    check(all(k.endswith(":" + registry.MODE_REF) for k in rdisp),
          f"{name}: reference run dispatched only mode ref")
    check_same_state(res, ref, f"{name} kernels vs reference")
    return res


def counted(res, lf) -> int:
    return int(count_matches(res, label_freq=lf).n_embeddings)


def check_needle_count(res, needle, lf, where: str) -> None:
    t0 = time.perf_counter()
    got, want = counted(res, lf), N_NEEDLES * count_automorphisms(needle)
    check(got == want, f"needle count {where} {got} == {N_NEEDLES} x |Aut| = "
          f"{want} (count {time.perf_counter() - t0:.2f} s)")


# ------------------------------------------------------------------ phases
def phase_kernel(args):
    log(f"[kernel] local backend at scale {args.scale}, blocked bn={BN}, wave {WAVE}")
    g, needle, lf, dg, bs = setup_graph(args.scale, args.seed)
    res = kernel_run("needle", dg, needle, lf, bs, WAVE, ("bitset_spmm",))
    check_needle_count(res, needle, lf, f"at scale {args.scale}")
    heads = min(int(lf[x]) for x in WDC_TEMPLATES["T4-square-rare"][0])
    log(f"  T4-square-rare runs at scale {wave_scale(args.scale)} below, not "
        f"{args.scale}: its rarest label has {heads} vertices here, so each "
        f"of its walks takes {-(-heads // WAVE)} waves of {WAVE}, every one a "
        f"reference pass over all {dg.m} arcs (the wave kernel's gate refuses "
        f"this graph), in both the kernel and the reference prune; a "
        f"{WIDE_WAVE}-source reference hop would hold "
        f"{dg.m * WIDE_WAVE // 8 / 2**30:.1f} GiB of words next to the masks")
    peak()


def phase_wave_and_served(args):
    s = wave_scale(args.scale)
    log(f"[wave] scale {s}: the largest the bitset_wave gate admits at "
        f"{WIDE_WAVE} sources, bn={BN} (needs "
        f"{ops.bitset_wave_vmem_bytes(n_pad_of(s), WIDE_WAVE // 32, BN)} B of "
        f"{ops.BITSET_WAVE_VMEM_BUDGET}; scale {s + 1} needs "
        f"{ops.bitset_wave_vmem_bytes(n_pad_of(s + 1), WIDE_WAVE // 32, BN)} B)")
    g, needle, lf, dg, bs = setup_graph(s, args.seed)
    w = jax.ShapeDtypeStruct((g.n, WIDE_WAVE // 32), np.uint32)
    check(ops._wave_eligible(w, None, None, g.n, None, None, bs),
          f"scale {s}: bitset_wave eligible (nnzb {bs.nnzb} <= "
          f"{ops.BITSET_WAVE_MAX_BLOCKS})")
    tmpls = templates_for(g, needle, args.seed)
    # T4's repeated labels need multiplicity counts, which keep its LCC off
    # the packed kernel; its waves still run fused
    for name, expect in (("needle", ("bitset_spmm", "bitset_wave")),
                         ("T4-square-rare", ("bitset_wave",))):
        res = kernel_run(f"{name} @ scale {s}", dg, tmpls[name], lf, bs,
                         WIDE_WAVE, expect)
        if name == "needle":
            check_needle_count(res, needle, lf, f"at scale {s}")
    local = {}
    for name in SERVED_TEMPLATES:
        res, disp, dt = timed_prune(dg, tmpls[name], lf, blocked=bs, wave=WAVE)
        t0 = time.perf_counter()
        local[name] = counted(res, lf)
        log(f"  {name} {[int(x) for x in tmpls[name].labels]}: local-backend "
            f"count {local[name]} (prune compile + run {dt:.2f} s, count "
            f"{time.perf_counter() - t0:.2f} s, dispatches {disp})")
    log(f"[served] GraphQueryEngine on the scale-{s} graph, wave {WAVE}, "
        f"max_batch 8. Not scale {args.scale}: each served count is checked "
        f"against the local backend's count above, and at scale "
        f"{args.scale} those prunes take reference waves over every arc, as "
        f"said for T4-square-rare")
    phase_served(g, tmpls, local)


def phase_served(g, tmpls, local):
    queries = list(SERVED_TEMPLATES) + ["needle"]  # 8 queries, one batch
    eng = GraphQueryEngine(g, wave=WAVE, max_batch=8)
    kind = jax.devices()[0].device_kind
    for label in ("compile + run (first drain)", "run (second drain)"):
        ids = [eng.submit(tmpls[n], mode=MODE_COUNT) for n in queries]
        t0 = time.perf_counter()
        eng.drain()
        dt = time.perf_counter() - t0
        for n, qid in zip(queries, ids):
            r = eng.result(qid)
            check(r.status == "ok" and r.n_embeddings == local[n],
                  f"query {qid} {n}: served count {r.n_embeddings} == "
                  f"local-backend count {local[n]}")
        log(f"  {len(queries)} queries in {eng.stats['n_batches']} batches so "
            f"far, {label}: {dt:.2f} s (chip run, {kind})")


def phase_oracle(args):
    log(f"[oracle] scale {ORACLE_SCALE}: local-backend counts vs core/oracle.py")
    g, needle, lf, dg, bs = setup_graph(ORACLE_SCALE, args.seed)
    for name, t in templates_for(g, needle, args.seed).items():
        res, disp, _ = timed_prune(dg, t, lf, blocked=bs, wave=WAVE)
        got = counted(res, lf)
        want = len(enumerate_matches_bruteforce(g, t))
        check(got == want, f"{name} {[int(x) for x in t.labels]}: count {got} "
              f"== oracle {want} (dispatches {disp})")


def run_one_chip(args):
    with fused_nlcc():
        phase_kernel(args)
        phase_wave_and_served(args)
        phase_oracle(args)


def run_four_chips(args):
    from repro.launch.mesh import make_shard_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices == 4")
    s = min(SHARDED_SCALE, wave_scale(args.scale))
    log(f"[sharded] scale {s}: the local comparison runs both kernels there; "
        f"at scale {s + 1} the spmd count of T4-square-rare is refused by the "
        f"device join's int32 capacity guard (core/join.py)")
    g, needle, lf, dg, bs = setup_graph(s, args.seed)
    mesh = make_shard_mesh(4)
    log(f"  spmd backend on {mesh.devices.size} devices vs local on "
        f"{jax.devices()[0]}")
    tm = Template(*WDC_TEMPLATES["T4-square-rare"])
    for name, t in (("needle", needle), ("T4-square-rare", tm)):
        with fused_nlcc():
            local, ldisp, ldt = timed_prune(dg, t, lf, blocked=bs, wave=WIDE_WAVE)
        log(f"  {name}: local prune (compile + run) {ldt:.2f} s, dispatches {ldisp}")
        sharded, sdisp, sdt = timed_prune(g, t, lf, mesh=mesh, wave=WIDE_WAVE)
        log(f"  {name}: spmd P=4 prune (compile + run) {sdt:.2f} s, V*E* "
            f"{sharded.counts()}, dispatches {sdisp}")
        devs = sharded.backend.omega_all.sharding.device_set
        check(len(devs) == 4, f"{name}: spmd state sharded over devices "
              f"{sorted(d.id for d in devs)}")
        check_same_state(sharded, local, f"{name} spmd P=4 vs local")
        c_local, c_sharded = counted(local, lf), counted(sharded, lf)
        check(c_local == c_sharded, f"{name}: count {c_sharded} (spmd) == "
              f"{c_local} (local)")
        if name == "needle":
            check_needle_count(local, needle, lf, f"at scale {s}")


def peak() -> None:
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  device 0 peak_bytes_in_use so far: {stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    # the kernel phase's R-MAT scale; a CPU rehearsal sets a small one
    ap.set_defaults(scale=KERNEL_SCALE)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is platform "
              f"{dev.platform!r} ({dev.device_kind}); there is no CPU fallback",
              file=sys.stderr)
        return 2
    cache = compat.enable_compile_cache()
    registry.set_policy(None)  # no policy file in the checkout steers routing
    log(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache}")
    t0 = time.perf_counter()
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(args)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    log(f"[done] {time.perf_counter() - t0:.1f} s (set-up, compile and run)")
    peak()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
