"""A tiny schema-aware database: named relations with ordered columns.

Snapshots are immutable and *versioned* (DESIGN.md §11): the only way to
change data is ``Database.apply(delta)``, which returns a NEW snapshot with
``version + 1``. Untouched relations are shared by reference, so a delta
over one relation costs O(|that relation| + |delta|) to apply and nothing
for the rest of the database.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Sequence, Tuple

from .relations import Relation, place_column
from .jointree import Atom

__all__ = ["Database"]


@dataclasses.dataclass
class Database:
    """relations: name -> Relation; schemas: name -> ordered column names.

    Atom variables bind positionally to the schema order, which is what makes
    self-joins (one relation, several aliases with different variables) work.

    ``version`` increases monotonically along an ``apply`` chain; two
    snapshots with the same version are NOT guaranteed identical (versions
    are per-lineage, not global) — the engine pairs version with object
    identity for cache coherence (DESIGN.md §11).
    """

    relations: Dict[str, Relation]
    schemas: Dict[str, Tuple[str, ...]]
    version: int = 0

    @staticmethod
    def from_columns(tables: Mapping[str, Mapping[str, Sequence]]) -> "Database":
        """Host columns placed on the device; float64 ones as their bit
        patterns (``relations.Float64Bits``)."""
        rels, schemas = {}, {}
        for name, cols in tables.items():
            schemas[name] = tuple(cols.keys())
            rels[name] = Relation({c: place_column(v) for c, v in cols.items()})
        return Database(rels, schemas)

    def size(self) -> int:
        """|db| = total number of tuples."""
        return sum(r.num_rows for r in self.relations.values())

    def instance_for(self, atom: Atom) -> Relation:
        """The atom's relation with columns renamed to the atom's variables."""
        rel = self.relations[atom.relation]
        schema = self.schemas[atom.relation]
        if len(schema) != len(atom.variables):
            raise ValueError(
                f"atom {atom.name}: {len(atom.variables)} variables for "
                f"{len(schema)}-column relation {atom.relation}"
            )
        return Relation({v: rel.columns[c] for c, v in zip(schema, atom.variables)})

    def apply(self, delta) -> "Database":
        """The next snapshot: ``delta`` (a ``core.delta.DeltaBatch``) applied
        to this one. Touched relations become "survivors then inserts"
        (``rows[~delete_mask] ++ inserts``); untouched relations are shared
        by reference. Never mutates ``self``.
        """
        from .delta import apply_relation_delta

        unknown = set(delta.relations) - set(self.relations)
        if unknown:
            raise KeyError(f"delta touches unknown relations {sorted(unknown)}")
        delta = delta.resolved({n: r.num_rows for n, r in self.relations.items()})
        rels = dict(self.relations)
        for name, d in delta.relations.items():
            d.validate(name, self.relations[name].num_rows, self.schemas[name])
            rels[name] = Relation(
                apply_relation_delta(self.relations[name].columns, d))
        return Database(rels, self.schemas, self.version + 1)
