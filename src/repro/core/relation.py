"""Fixed-capacity sharded relations (intermediate results).

A Relation is the SPMD stand-in for the paper's per-worker intermediate
result sets RS: a (W, cap, k) binding table + validity mask, where column j
binds variable ``vars[j]``.  The leading worker axis is shardable on the mesh
``data`` axis; padded rows are -1/invalid.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import trace
from .query import Var

__all__ = ["Relation"]


@jax.tree_util.register_pytree_node_class
@dataclass
class Relation:
    cols: jax.Array  # (W, cap, k) int32 bindings
    valid: jax.Array  # (W, cap) bool
    vars: tuple[Var, ...]  # static: variable bound by each column

    def tree_flatten(self):
        return (self.cols, self.valid), self.vars

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    # ------------------------------------------------------------ properties
    @property
    def n_workers(self) -> int:
        return self.cols.shape[0]

    @property
    def capacity(self) -> int:
        return self.cols.shape[1]

    @property
    def width(self) -> int:
        return self.cols.shape[2]

    def col_of(self, v: Var) -> int:
        return self.vars.index(v)

    def counts(self) -> jax.Array:
        return jnp.sum(self.valid, axis=1)

    def total(self) -> jax.Array:
        return jnp.sum(self.valid)

    # ------------------------------------------------------------- placement
    def device_put(self, sharding) -> "Relation":
        """Place the binding table under ``sharding`` (worker axis on the
        substrate mesh); stage outputs already carry it, this is for
        relations built host-side."""
        return Relation(
            jax.device_put(self.cols, sharding),
            jax.device_put(self.valid, sharding),
            self.vars,
        )

    # ------------------------------------------------------------ host utils
    def to_numpy(self) -> np.ndarray:
        """All valid binding rows concatenated across workers (host-side);
        works for worker shards spanning processes (fetch_global).  A
        relation still on the device costs one counted host sync."""
        from repro.compat import fetch_global

        if not isinstance(self.cols, jax.Array):
            return np.asarray(self.cols)[np.asarray(self.valid)]
        with trace.sync("relation"):
            cols = fetch_global(self.cols)
            valid = fetch_global(self.valid)
        return cols[valid]

    def to_set(self) -> set[tuple[int, ...]]:
        return {tuple(int(x) for x in row) for row in self.to_numpy()}

    def project_to(self, var_order: list[Var]) -> np.ndarray:
        """Host-side projection in a requested variable order (tests)."""
        idx = [self.col_of(v) for v in var_order]
        return self.to_numpy()[:, idx]

    @classmethod
    def empty(cls, n_workers: int, cap: int, vars: tuple[Var, ...]) -> "Relation":
        k = len(vars)
        return cls(
            cols=jnp.full((n_workers, cap, k), -1, jnp.int32),
            valid=jnp.zeros((n_workers, cap), bool),
            vars=vars,
        )
