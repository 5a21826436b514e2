"""End-to-end Poisson sampling queries via Index-and-Probe (paper §3).

    sampler = PoissonSampler(db, query)         # (1) build random-access index
    sample  = sampler.sample(key)               # (2) position-sample (3) probe

The index is built once; each .sample() draws a *fresh independent* Poisson
sample — the Monte-Carlo-loop usage pattern of the paper's EpiQL engine and
of this repo's training-data pipeline (data/pipeline.py).

Since the engine refactor (DESIGN.md §7), ``PoissonSampler`` is a thin
facade over ``repro.engine.QueryEngine``: it compiles one plan on a private
engine and delegates every call, so its results are bit-identical to
``engine.poisson_sample`` under the same key. New code that issues more
than one query should construct a ``QueryEngine`` directly to share the
compiled-plan and shred caches across queries.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .database import Database
from .jointree import JoinQuery

__all__ = ["JoinSample", "PoissonSampler"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class JoinSample:
    """A Poisson sample of the join result. Fixed capacity; lanes >= count
    are padding (mask with .valid()).

    Batched draws (``sample_batch``, DESIGN.md §10) reuse this container
    with a leading batch axis on every leaf: columns/positions ``(B, cap)``,
    count/overflow ``(B,)``. ``capacity``/``valid`` are batch-aware (the
    capacity is always the *last* axis; ``valid()`` broadcasts the per-draw
    counts), so masking code works unchanged on either layout.

    A float64 attribute's column is a ``relations.Float64Bits``: its int64
    bit patterns on the device; ``np.asarray(column)`` gives the stored
    float64 values, exactly (DESIGN.md §9)."""

    columns: Dict[str, jnp.ndarray]
    positions: jnp.ndarray  # (cap,) flat offsets into the virtual join
    count: jnp.ndarray  # () int64
    overflow: jnp.ndarray  # () bool

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        leaves = tuple(self.columns[n] for n in names) + (
            self.positions,
            self.count,
            self.overflow,
        )
        return leaves, names

    @classmethod
    def tree_unflatten(cls, names, leaves):
        cols = dict(zip(names, leaves[: len(names)]))
        return cls(cols, *leaves[len(names):])

    @property
    def capacity(self) -> int:
        return self.positions.shape[-1]

    @property
    def batch(self) -> Optional[int]:
        """Leading batch size for batched samples, else None."""
        return self.positions.shape[0] if self.positions.ndim == 2 else None

    def valid(self) -> jnp.ndarray:
        count = jnp.asarray(self.count)
        if count.ndim:
            count = count[..., None]
        return jnp.arange(self.capacity) < count


class PoissonSampler:
    """Index-and-Probe executor for ``Q = beta_y(R1 |><| ... |><| Rl)``.

    .. deprecated::
        Thin facade over ``repro.engine.QueryEngine`` (one engine, one
        compiled plan), kept so published call sites keep running.
        Construct a ``QueryEngine`` instead — it caches plans across
        queries, batches draws (``sample_batch``), shards over meshes, and
        consumes deltas, none of which this facade exposes (DESIGN.md §13).
    """

    def __init__(
        self,
        db: Database,
        query: JoinQuery,
        rep: str = "usr",
        method: str = "exprace",
        project: Optional[tuple] = None,
    ):
        """``project``: bag-based projection attributes A for queries of the
        paper's form beta_y(pi_A(Q^)) (eq. 2). For bag projection the paper
        notes beta_y(pi_A(Q^)) == pi_A(beta_y(Q^)), so sampling first and
        projecting the sample is exact; we simply restrict GET's output
        columns (y must be in A). Set-based (duplicate-eliminating) free-
        connex projection would need Carmeli et al.'s Q'/D' reduction —
        documented as out of scope in DESIGN.md §9."""
        # Imported lazily: repro.engine imports repro.core, and this module
        # is part of repro.core's own import sequence.
        from repro.engine import QueryEngine

        warnings.warn(
            "core.PoissonSampler is deprecated; use repro.engine.QueryEngine"
            " (engine.sample / engine.sample_batch) — it shares plan caches"
            " across queries and supports batching, sharding, and deltas",
            DeprecationWarning, stacklevel=2)
        if query.prob_var is None:
            raise ValueError("Poisson sampling needs query.prob_var (beta_y)")
        if project is not None and query.prob_var not in project:
            raise ValueError("prob_var (y) must be in the projection A")
        self.engine = QueryEngine(db, rep=rep)
        self._plan = self.engine.compile(
            query, rep=rep, method=method, project=project)
        self.project = self._plan.project
        self.query = query
        self.rep_default = self._plan.rep_default
        self.method = method
        self.shred = self._plan.shred
        self.w = self._plan.w
        self.p = self._plan.p
        self.prefE = self._plan.prefE

    # -- capacity planning ---------------------------------------------------
    @property
    def join_size(self) -> int:
        return self._plan.join_size

    def expected_k(self) -> float:
        return self._plan.expected_k()

    def default_capacity(self) -> int:
        return self._plan.default_capacity()

    def arrival_capacity(self) -> int:
        return self._plan.arrival_capacity()

    # -- sampling -------------------------------------------------------------
    def sample(self, key, cap: Optional[int] = None, rep: Optional[str] = None,
               acap: Optional[int] = None) -> JoinSample:
        return self._plan.sample(key, cap=cap, rep=rep, acap=acap)

    def sample_auto(self, key, max_doublings: int = 8) -> JoinSample:
        """Redraw with doubled capacity until no overflow (host loop)."""
        return self._plan.sample_auto(key, max_doublings=max_doublings)

    def uniform_sample(
        self, key, p: float, cap: Optional[int] = None, method: str = "hybrid"
    ) -> JoinSample:
        """beta_p with a fixed uniform probability (paper §6.1)."""
        return self._plan.uniform_sample(key, p, cap=cap, method=method)
