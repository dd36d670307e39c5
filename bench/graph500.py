"""The benchmark's own Graph500 graph, made on the device from a seed.

Kernel 0 of the Graph500 specification (graph500.org, "Graph 500
Benchmarks 1 ("Search") ... Kernel 0"): 16 * 2^scale edge tuples from the
Kronecker (R-MAT) initiator A, B, C, D = 0.57, 0.19, 0.19, 0.05, level by
level as in the specification's pseudo-code, then a random permutation of
the vertex ids ("scrambled" ids, so hubs do not sit in the lowest blocks).

Two choices make every seed give the same shapes, so one compiled program
serves every seed:

- the graph keeps the first `undirected_edges` distinct undirected pairs
  in the order they were drawn (self loops dropped). Graph500 keeps all its
  tuples; the fixed count sits just under the expected number of distinct
  pairs, so only the last fraction of a percent of draws is left out;
- `needles` copies of a 4-cycle are planted on vertex ids after the
  background's, each tied to a random background vertex by one edge, and
  after them `decoys` copies of the same 4-cycle unrolled into an 8-cycle
  (labels a b b a a b b a), tied the same way. Every decoy vertex has the
  neighbourhood of a needle vertex, so local constraint checking keeps it
  and only the cycle check removes it (the paper's figure 2(a)).

Labels are the paper's degree labels l(v) = ceil(log2(d(v) + 1))
(arXiv:1912.08453 section 5), taken from the background degrees; the
needles carry the two labels above the background's highest, as
(top + 1, top + 2, top + 2, top + 1) around the cycle.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

NEEDLE_EDGES = ((0, 1), (1, 2), (2, 3), (3, 0))
DECOY_EDGES = tuple((i, (i + 1) % 8) for i in range(8))


@dataclasses.dataclass
class BenchGraph:
    """One generated graph. `src`/`dst` are the arcs (both directions of
    every edge) sorted by (dst, src), on the device; `labels` too.
    `label_freq` is on the host."""

    n: int
    src: jax.Array
    dst: jax.Array
    labels: jax.Array
    label_freq: np.ndarray
    needle_labels: tuple

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


def _kronecker_tuples(key, scale: int, n_tuples: int, abcd):
    """Graph500 kernel-0 tuples (start, end), ids in [0, 2^scale)."""
    a, b, c, _ = abcd
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(i, uv):
        u, v = uv
        r1, r2 = jax.random.uniform(jax.random.fold_in(key, i), (2, n_tuples))
        u_bit = r1 > ab
        v_bit = r2 > jnp.where(u_bit, c_norm, a_norm)
        return (u | (u_bit.astype(jnp.int32) << i),
                v | (v_bit.astype(jnp.int32) << i))

    zero = jnp.zeros((n_tuples,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


@functools.partial(jax.jit, static_argnames=(
    "scale", "edge_factor", "undirected_edges", "needles", "decoys"))
def _generate(seed_lo, seed_hi, *, scale, edge_factor, abcd,
              undirected_edges, needles, decoys):
    n_bg = 1 << scale
    n_tuples = edge_factor << scale
    key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
    k_tuples, k_perm, k_anchor = jax.random.split(key, 3)
    u, v = _kronecker_tuples(k_tuples, scale, n_tuples, abcd)
    perm = jax.random.permutation(k_perm, n_bg).astype(jnp.int32)
    u, v = perm[u], perm[v]
    lo, hi = jnp.minimum(u, v), jnp.maximum(u, v)
    loop = lo == hi
    lo = jnp.where(loop, n_bg, lo)
    hi = jnp.where(loop, n_bg, hi)
    draw = jnp.arange(n_tuples, dtype=jnp.int32)
    lo, hi, draw = jax.lax.sort((lo, hi, draw), num_keys=3)
    first = jnp.concatenate([
        jnp.ones((1,), bool), (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    first &= lo < n_bg
    # distinct pairs in the order of their first draw; keep the first ones
    order_key = jnp.where(first, draw, n_tuples)
    order_key, lo, hi = jax.lax.sort((order_key, lo, hi), num_keys=1)
    enough = order_key[undirected_edges - 1] < n_tuples
    lo, hi = lo[:undirected_edges], hi[:undirected_edges]

    deg = jnp.zeros((n_bg,), jnp.int32).at[lo].add(1).at[hi].add(1)
    bg_labels = 32 - jax.lax.clz(deg)          # ceil(log2(d + 1))
    top = jnp.max(bg_labels)

    # needles, then decoys, on the ids after the background's; vertex 0 of
    # each tied to a random background vertex
    parts_lo, parts_hi = [lo], [hi]
    first_id = n_bg
    for count, ring in ((needles, NEEDLE_EDGES), (decoys, DECOY_EDGES)):
        size = len(ring)
        base = first_id + size * jnp.arange(count, dtype=jnp.int32)
        ring = jnp.asarray(ring, jnp.int32)
        parts_lo.append((base[:, None] + ring[None, :, 0]).reshape(-1))
        parts_hi.append((base[:, None] + ring[None, :, 1]).reshape(-1))
        k_anchor, k_this = jax.random.split(k_anchor)
        parts_lo.append(jax.random.randint(k_this, (count,), 0, n_bg, jnp.int32))
        parts_hi.append(base)
        first_id += size * count
    e_lo, e_hi = jnp.concatenate(parts_lo), jnp.concatenate(parts_hi)
    src = jnp.concatenate([e_lo, e_hi])
    dst = jnp.concatenate([e_hi, e_lo])
    dst, src = jax.lax.sort((dst, src), num_keys=2)
    ab = top + jnp.asarray([1, 2, 2, 1], jnp.int32)
    labels = jnp.concatenate(
        [bg_labels, jnp.tile(ab, needles), jnp.tile(ab, 2 * decoys)])
    return src, dst, labels, enough


def generate(seed: int, *, scale: int, edge_factor: int, abcd,
             undirected_edges: int, needles: int, decoys: int) -> BenchGraph:
    """The graph of one seed. Raises if the seed's draws hold fewer than
    `undirected_edges` distinct pairs (the count is chosen so that this
    does not happen)."""
    seed = int(seed)
    src, dst, labels, enough = _generate(
        np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF),
        scale=scale, edge_factor=edge_factor,
        abcd=tuple(float(x) for x in abcd),
        undirected_edges=undirected_edges, needles=needles, decoys=decoys)
    if not bool(enough):
        raise ValueError(
            f"seed {seed}: fewer than {undirected_edges} distinct pairs in "
            f"{edge_factor << scale} draws at scale {scale}")
    lab = np.asarray(labels)
    top = int(lab[: 1 << scale].max())
    return BenchGraph(
        n=int(lab.shape[0]), src=src, dst=dst,
        labels=labels, label_freq=np.bincount(lab),
        needle_labels=(top + 1, top + 2, top + 2, top + 1))
