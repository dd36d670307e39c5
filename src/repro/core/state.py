"""Per-vertex / per-edge pruning state (paper Alg. 2) and pack/unpack helpers.

Canonical single-device representation:
  omega:       bool[n, n0]   — candidate template vertices per background vertex
  edge_active: bool[m]       — per arc, in the dst-sorted DeviceGraph order

The distributed engine and the `bitset_spmm` kernel use the packed form
uint32[n, W] with W = ceil(n0/32) (<= 2 since n0 <= 64).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.graph.structs import DeviceGraph
from repro.core.template import Template


def packed_words(n0: int) -> int:
    return (n0 + 31) // 32


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """bool[..., n0] -> uint32[..., W]."""
    n0 = bits.shape[-1]
    W = packed_words(n0)
    pad = W * 32 - n0
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), bits.dtype)], axis=-1
        )
    b = bits.reshape(bits.shape[:-1] + (W, 32)).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jnp.ndarray, n0: int) -> jnp.ndarray:
    """uint32[..., W] -> bool[..., n0]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,))
    return bits[..., :n0].astype(bool)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PruneState:
    omega: jnp.ndarray  # bool[n, n0]
    edge_active: jnp.ndarray  # bool[m] (dst-sorted arc order)

    @property
    def vertex_active(self) -> jnp.ndarray:
        return jnp.any(self.omega, axis=1)

    def counts(self) -> Dict[str, int]:
        return {
            "active_vertices": int(jnp.sum(jnp.any(self.omega, axis=1))),
            "active_edges": int(jnp.sum(self.edge_active)),
            "omega_bits": int(jnp.sum(self.omega)),
        }


def init_state(dg: DeviceGraph, template: Template) -> PruneState:
    """Alg. 2 initialization: omega(v) = {q : l(q) == l(v)}; all edges active."""
    n_labels = max(int(template.labels.max()) + 1,
                   int(obs.to_host(jnp.max(dg.labels), "labels_max")) + 1)
    lm = jnp.asarray(template.label_matrix(n_labels))  # [n0, L]
    omega = jnp.take(lm.T, dg.labels, axis=0)  # [n, n0]
    edge_active = jnp.ones((dg.m,), dtype=bool)
    return PruneState(omega=omega, edge_active=edge_active)


def solution_counts(state: PruneState) -> Dict[str, int]:
    return state.counts()
