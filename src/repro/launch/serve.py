"""Serving entry point: graph-query serving (the paper's multi-tenant
pattern-matching scenario), batched greedy generation (LM), or catalog
scoring (recsys) on the smoke configs.

  PYTHONPATH=src python -m repro.launch.serve --graph-queries 32 \
      --graph-scale 9 --max-batch 8
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-15b \
      --batch 4 --prompt-len 16 --max-new 32

Kernel calls in the serving hot loop (batched prune waves, attention,
embedding_bag) route through the dispatch registry; `--policy` loads a tuned
dispatch-policy cache (from `registry.tune()` / `python -m benchmarks.run`)
so serving uses the measured kernel-mode decisions for this host instead of
the untuned fallback — graph serving resolves batched routes under
b<B>-prefixed bucket keys.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_arch
from repro.configs.base import LMConfig, RecsysConfig
from repro.kernels import compat, registry
from repro.models import transformer, bert4rec
from repro import serve as serve_lib
from repro.data import MaskedSequenceStream


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS,
                    help="LM/recsys smoke-config serving (mutually "
                         "exclusive with --graph-queries)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--graph-queries", type=int, default=0, metavar="N",
                    help="serve N template queries against a synthetic "
                         "metadata graph through the batched prune engine")
    ap.add_argument("--graph-scale", type=int, default=9,
                    help="rmat graph scale (2^scale vertices)")
    ap.add_argument("--partition", type=int, default=None,
                    help="shard the background graph P ways")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait", type=float, default=0.05,
                    help="batcher max wait (seconds) before launching a "
                         "partial batch")
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-query serving deadline in seconds")
    ap.add_argument("--policy", default=None, metavar="PATH",
                    help="dispatch-policy cache to serve under "
                         "(default: the registry's lazy policy_path() load)")
    args = ap.parse_args()
    compat.enable_compile_cache()

    if args.policy:
        registry.set_policy(registry.DispatchPolicy.load(args.policy))
        print(f"dispatch policy: {args.policy} "
              f"({len(registry.get_policy().modes)} tuned kernel modes)")

    if args.graph_queries:
        _serve_graph(args)
        return
    if not args.arch:
        raise SystemExit("pass --arch (LM/recsys) or --graph-queries N")

    cfg = get_arch(args.arch).smoke()
    if isinstance(cfg, LMConfig):
        params, _ = transformer.init(jax.random.key(0), cfg)
        prompt = jax.random.randint(
            jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab)
        t0 = time.perf_counter()
        out = serve_lib.greedy_generate(
            params, cfg, prompt, args.max_new, args.prompt_len + args.max_new)
        dt = time.perf_counter() - t0
        toks = args.batch * args.max_new
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({toks / dt:.1f} tok/s batched greedy)")
        print(out[:2, :16])
    elif isinstance(cfg, RecsysConfig):
        params, _ = bert4rec.init(jax.random.key(0), cfg)
        items = MaskedSequenceStream(cfg.n_items, args.batch, cfg.seq_len)(0)["items"]
        t0 = time.perf_counter()
        scores = bert4rec.serve_scores(params, cfg, items)
        top = jax.lax.top_k(scores, 10)[1]
        print(f"scored {scores.shape} in {time.perf_counter()-t0:.2f}s; "
              f"top-10 for user 0: {top[0]}")
    else:
        raise SystemExit("GNN archs serve through examples/pattern_gnn.py")


def _serve_graph(args):
    from repro.graph import rmat_graph
    from repro.serve import GraphQueryEngine, example_workload, MODE_COUNT

    g = rmat_graph(args.graph_scale, edge_factor=8, seed=5)
    print(f"background graph: n={g.n} m={g.m} "
          f"(rmat scale {args.graph_scale})")
    eng = GraphQueryEngine(
        g, partition=args.partition, max_batch=args.max_batch,
        max_wait_s=args.max_wait)
    templates = example_workload(args.graph_queries, seed=1,
                                 labels_max=int(g.labels.max()))
    t0 = time.perf_counter()
    ids = [eng.submit(t, mode=MODE_COUNT, timeout_s=args.timeout)
           for t in templates]
    results = eng.drain()
    dt = time.perf_counter() - t0
    assert len(results) == len(ids)
    ok = [r for r in results if r.status == "ok"]
    missed = len(results) - len(ok)
    print(f"served {len(results)} queries in {dt:.2f}s "
          f"({len(results) / dt:.1f} q/s) across "
          f"{eng.stats['n_batches']} batches; deadline_missed={missed}")
    for b in eng.stats["batches"]:
        print(f"  batch {b['batch_id']}: B={b['B']} bucket={b['bucket']} "
              f"{b['seconds']:.2f}s")
    for r in ok[:4]:
        print(f"  query {r.query_id}: {r.n_embeddings} matches "
              f"(batch {r.batch_id}, waited {r.wait_s * 1e3:.0f}ms)")


if __name__ == "__main__":
    main()
