"""Plain reference for the answer a query delivers: the pruned solution
subgraph, i.e. the union of all matches of a template (its vertex set and
its arc set), computed with dense boolean matrices over the vertices whose
labels the template uses.

It covers templates that are one cycle or one path, listed in walk order,
in which any two template vertices with the same label are adjacent. Then a
closed (or open) label walk through distinct template positions is a match:
adjacent positions hold distinct vertices because the graph has no self
loops, and the others differ in label. So an arc (u, v) carries template
edge (q_i, q_i+1) of some match exactly when u -> v is an arc and a walk of
the remaining template edges joins v back to u (cycle), or extends u to the
path's start and v to its end (path). A vertex is matched exactly when it
ends a matched arc.

It shares no code with the system under test: numpy on the host, from the
same arcs and labels the system was given.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def template_kind(labels: Sequence[int], edges: Sequence[Tuple[int, int]]) -> str:
    """'cycle' or 'path' for templates whose edges are (0,1), (1,2), ...
    (closed by (k-1, 0) for a cycle); raises for anything else."""
    k = len(labels)
    path = [(i, i + 1) for i in range(k - 1)]
    norm = [tuple(e) for e in edges]
    if norm == path:
        kind = "path"
    elif k >= 3 and norm == path + [(k - 1, 0)]:
        kind = "cycle"
    else:
        raise NotImplementedError(f"template edges {norm} are not a walk-order cycle or path")
    adj = set(norm) | {(b, a) for a, b in norm}
    for a in range(k):
        for b in range(a + 1, k):
            if labels[a] == labels[b] and (a, b) not in adj:
                raise NotImplementedError(
                    f"template positions {a} and {b} share label {labels[a]} "
                    "but are not adjacent: walks could repeat a vertex")
    return kind


def union_of_matches(src: np.ndarray, dst: np.ndarray, labels: np.ndarray,
                     t_labels: Sequence[int], t_edges) -> Tuple[np.ndarray, np.ndarray]:
    """(vertex mask bool[n], arc mask bool[m]) of the union of all matches,
    arcs in the order of `src`/`dst` (both arcs of a matched edge are set)."""
    t_labels = [int(x) for x in t_labels]
    kind = template_kind(t_labels, t_edges)
    k = len(t_labels)
    n, m = labels.shape[0], src.shape[0]
    members = {lab: np.flatnonzero(labels == lab) for lab in set(t_labels)}
    local = np.full(n, -1, np.int64)
    for ids in members.values():
        local[ids] = np.arange(ids.size)
    want = np.isin(labels, list(members))
    arc_ok = want[src] & want[dst]
    s_arc, d_arc = src[arc_ok], dst[arc_ok]

    def block(la: int, lb: int) -> np.ndarray:
        """float32 0/1 adjacency from label-la vertices to label-lb ones."""
        out = np.zeros((members[la].size, members[lb].size), np.float32)
        sel = (labels[s_arc] == la) & (labels[d_arc] == lb)
        out[local[s_arc[sel]], local[d_arc[sel]]] = 1.0
        return out

    n_steps = k if kind == "cycle" else k - 1
    steps = [block(t_labels[i], t_labels[(i + 1) % k]) for i in range(n_steps)]

    def chain(mats) -> np.ndarray:
        out = mats[0]
        for mat in mats[1:]:
            out = np.minimum(out @ mat, 1.0)
        return out

    if kind == "path":
        left = [np.ones(members[t_labels[0]].size, np.float32)]
        for r in steps:
            left.append(np.minimum(left[-1] @ r, 1.0))
        right = [np.ones(members[t_labels[-1]].size, np.float32)]
        for r in reversed(steps):
            right.append(np.minimum(r @ right[-1], 1.0))
        right = right[::-1]

    vmask = np.zeros(n, bool)
    key_parts = []
    for i in range(n_steps):
        if kind == "cycle":
            back = chain(steps[i + 1:] + steps[:i])      # v -> ... -> u
            hit = (steps[i] > 0) & (back.T > 0)
        else:
            hit = (steps[i] > 0) & (left[i][:, None] > 0) & (right[i + 1][None, :] > 0)
        uu, vv = np.nonzero(hit)
        u = members[t_labels[i]][uu]
        v = members[t_labels[(i + 1) % k]][vv]
        vmask[u] = True
        vmask[v] = True
        key_parts += [u.astype(np.int64) * n + v, v.astype(np.int64) * n + u]
    amask = np.zeros(m, bool)
    if key_parts:
        keys = np.unique(np.concatenate(key_parts))
        amask[arc_ok] = np.isin(s_arc.astype(np.int64) * n + d_arc, keys)
    return vmask, amask
