"""Delta batches: the unit of change between database snapshots.

The paper's index is built once over a static database; the serving system
needs the database to *move* without paying the full ``O(N log N)`` build
again (DESIGN.md §11). The model here is immutable versioned snapshots:

  * a ``Database`` never mutates — ``Database.apply(delta)`` produces a NEW
    snapshot (version + 1) sharing every untouched relation's arrays;
  * a ``DeltaBatch`` describes one transition: per-relation row inserts
    (appended after the surviving rows) and per-relation delete masks
    (boolean, True = delete);
  * the post-delta physical layout is canonical — surviving rows keep their
    relative order, inserts follow — which is what lets
    ``shred.reshred_incremental`` merge a delta into an existing sorted
    grouping and still be bit-identical to a from-scratch build.

Deltas are host-side objects (numpy): they describe bulk data movement, not
traced computation.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .relations import concat_columns, place_column

__all__ = ["DeltaBatch", "RelationDelta"]


@dataclasses.dataclass(frozen=True)
class RelationDelta:
    """Changes to one relation: a delete mask over the current rows plus
    rows to insert (column name -> 1-D numpy array, all equal length).

    ``delete_mask`` is None when nothing is deleted; ``inserts`` is an empty
    dict when nothing is inserted. Either side may be empty, not both.
    """

    delete_mask: Optional[np.ndarray] = None
    inserts: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    @property
    def num_deletes(self) -> int:
        if self.delete_mask is None:
            return 0
        if self.delete_mask.dtype == np.bool_:
            return int(self.delete_mask.sum())
        return int(self.delete_mask.shape[0])  # index form (pre-resolution)

    @property
    def num_inserts(self) -> int:
        if not self.inserts:
            return 0
        return int(next(iter(self.inserts.values())).shape[0])

    def validate(self, name: str, num_rows: int,
                 schema: Tuple[str, ...]) -> None:
        if self.delete_mask is not None:
            if self.delete_mask.dtype != np.bool_:
                raise ValueError(f"{name}: delete_mask must be boolean, "
                                 f"got {self.delete_mask.dtype}")
            if self.delete_mask.shape != (num_rows,):
                raise ValueError(
                    f"{name}: delete_mask has shape {self.delete_mask.shape}, "
                    f"relation has {num_rows} rows")
        if self.inserts:
            if set(self.inserts) != set(schema):
                raise ValueError(
                    f"{name}: insert columns {sorted(self.inserts)} != "
                    f"schema columns {sorted(schema)}")
            lens = {c: v.shape[0] for c, v in self.inserts.items()}
            if len(set(lens.values())) > 1:
                raise ValueError(f"{name}: ragged insert columns {lens}")
        if self.delete_mask is None and not self.inserts:
            raise ValueError(f"{name}: empty relation delta (no deletes, "
                             f"no inserts)")


@dataclasses.dataclass(frozen=True)
class DeltaBatch:
    """One atomic multi-relation change set: relation name -> RelationDelta.

    Build with ``DeltaBatch.of`` (keyword-per-relation convenience) or the
    raw constructor. Applying the batch via ``Database.apply`` yields a new
    snapshot whose touched relations are "survivors then inserts":

        rows' = rows[~delete_mask] ++ inserts

    Relations not named in the batch are shared by reference with the
    previous snapshot — a delta touching one relation copies nothing else.

    ``lsn`` is the batch's log sequence number once it has been appended to
    a replicated delta log (``launch.fleet.log.DeltaLog``, DESIGN.md §12):
    1-based, assigned by the log at append time, ``None`` for free-standing
    deltas. Along a log, ``snapshot.version == base_version + lsn`` — the
    invariant that lets every replica name "the snapshot this draw must
    read" by a single integer.
    """

    relations: Dict[str, RelationDelta]
    lsn: Optional[int] = None

    def __post_init__(self):
        if not self.relations:
            raise ValueError("DeltaBatch must touch at least one relation")

    def with_lsn(self, lsn: int) -> "DeltaBatch":
        """The same batch stamped with a log sequence number."""
        if self.lsn is not None and self.lsn != lsn:
            raise ValueError(f"delta already has lsn={self.lsn}, "
                             f"refusing to restamp as {lsn}")
        return dataclasses.replace(self, lsn=lsn)

    @staticmethod
    def of(**per_relation) -> "DeltaBatch":
        """Convenience constructor::

            DeltaBatch.of(
                R={"insert": {"x": [1, 2], "p": [0.3, 0.4]}},
                S={"delete": [0, 5]},          # row indices
            )

        ``delete`` accepts row indices or a boolean mask; ``insert`` is a
        column mapping. The delete mask is resolved against the relation's
        current row count at ``Database.apply`` time when given as indices.
        """
        rels = {}
        for name, spec in per_relation.items():
            ins = {c: np.asarray(v) for c, v in spec.get("insert", {}).items()}
            dele = spec.get("delete", None)
            mask = None
            if dele is not None:
                dele = np.asarray(dele)
                if dele.dtype == np.bool_:
                    mask = dele
                else:  # row indices: defer length validation to apply()
                    mask = dele.astype(np.int64)
            rels[name] = RelationDelta(delete_mask=mask, inserts=ins)
        return DeltaBatch(rels)

    def touched(self) -> Tuple[str, ...]:
        """Names of the relations this batch modifies."""
        return tuple(sorted(self.relations))

    def size(self) -> int:
        """|delta| = total rows inserted + deleted."""
        return sum(d.num_deletes + d.num_inserts
                   for d in self.relations.values())

    def resolved(self, num_rows: Mapping[str, int]) -> "DeltaBatch":
        """Normalize index-style delete specs into boolean masks (the form
        ``reshred_incremental`` consumes) against the given row counts.

        Index deletes are validated here: out-of-range (including negative
        — no numpy wraparound) and duplicate indices are errors, so
        ``num_deletes``/``size()`` always agree with what a later apply
        actually removes."""
        rels = {}
        for name, d in self.relations.items():
            mask = d.delete_mask
            if mask is not None and mask.dtype != np.bool_:
                n = num_rows[name]
                if mask.size and (mask.min() < 0 or mask.max() >= n):
                    raise ValueError(
                        f"{name}: delete indices out of range [0, {n}): "
                        f"{mask[(mask < 0) | (mask >= n)][:5].tolist()}")
                if np.unique(mask).size != mask.size:
                    raise ValueError(f"{name}: duplicate delete indices")
                m = np.zeros((n,), np.bool_)
                m[mask] = True
                mask = m
            rels[name] = RelationDelta(delete_mask=mask, inserts=d.inserts)
        return DeltaBatch(rels, lsn=self.lsn)


def apply_relation_delta(columns: Dict[str, jnp.ndarray],
                         d: RelationDelta) -> Dict[str, jnp.ndarray]:
    """Survivors-then-inserts column transform (the canonical layout)."""
    out = {}
    keep = None
    if d.delete_mask is not None:
        keep = jnp.asarray(~d.delete_mask)
    for c, v in columns.items():
        nv = v[keep] if keep is not None else v
        if d.inserts:
            ins = np.asarray(d.inserts[c]).astype(nv.dtype)
            nv = concat_columns([nv, place_column(ins)])
        out[c] = nv
    return out
