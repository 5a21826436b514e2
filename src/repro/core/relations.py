"""Column-store relations (struct-of-arrays) with static shapes.

The paper targets a main-memory column store; the JAX-native equivalent is a
struct-of-arrays: a relation is a mapping ``attribute -> 1-D array``, all of
equal length. Tuples are addressed positionally (offset i), exactly like the
paper's ``R[i](ybar)`` notation.

Key design point for XLA: relations are immutable pytrees so they can cross
``jit`` boundaries, and every derived structure (shreds, indexes, samples)
keeps *static* shapes — dangling tuples are retained with weight zero rather
than compacted (see DESIGN.md §3).

A float64 attribute column is held on the device as its IEEE-754 bit
patterns in int64 (``Float64Bits``, DESIGN.md §9): a TPU has no float64 and
would round a float64 column to a float32 pair, while int64 moves through
its gathers exactly. Its values are decoded on the host, bit for bit.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Relation", "Float64Bits", "place_column", "take_column",
           "concat_columns", "zeros_like_column", "numeric_column",
           "pack_keys", "dense_keys"]


@jax.tree_util.register_pytree_node_class
class Float64Bits:
    """A float64 column held as its IEEE-754 bit patterns in one int64 leaf.

    It is a pytree over ``bits``, so jit, vmap, ``block_until_ready``,
    ``device_put`` and ``jax.tree.map`` move the int64 leaf unchanged;
    indexing indexes the leaf. ``np.asarray(col)`` is the float64 view of
    the leaf's host copy: the stored values, exactly. Nothing decodes the
    patterns on the device, where a float64 would be rounded again.
    """

    dtype = np.dtype(np.float64)

    def __init__(self, bits):
        self.bits = bits

    @classmethod
    def encode(cls, values) -> "Float64Bits":
        """The column of float64 host ``values``, placed on the device."""
        v = np.ascontiguousarray(np.asarray(values, np.float64))
        return cls(jnp.asarray(v.view(np.int64)))

    def tree_flatten(self):
        return (self.bits,), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(leaves[0])

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.bits.shape)

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, index) -> "Float64Bits":
        return Float64Bits(self.bits[index])

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self.bits).view(np.float64)
        if dtype is not None:
            return out.astype(dtype)
        return out.copy() if copy else out

    @property
    def at(self) -> "_HostUpdate":
        """``col.at[index].set(v)`` / ``.add(v)``: a new column with the
        update applied to the decoded values on the host (outside jit)."""
        return _HostUpdate(self)

    def __repr__(self) -> str:
        return f"Float64Bits({np.asarray(self)!r})"


class _HostUpdate:
    def __init__(self, col: Float64Bits, index=None):
        self._col, self._index = col, index

    def __getitem__(self, index) -> "_HostUpdate":
        return _HostUpdate(self._col, index)

    def set(self, value) -> Float64Bits:
        v = np.array(self._col)
        v[self._index] = value
        return Float64Bits.encode(v)

    def add(self, value) -> Float64Bits:
        v = np.array(self._col)
        np.add.at(v, self._index, value)
        return Float64Bits.encode(v)


def place_column(values):
    """A host column placed on the device: float64 as ``Float64Bits``,
    every other dtype as itself."""
    values = np.asarray(values)
    if values.dtype == np.float64:
        return Float64Bits.encode(values)
    return jnp.asarray(values)


def take_column(col, rows):
    """``jnp.take(col, rows, axis=0)``; a ``Float64Bits`` column gathers
    its bit patterns."""
    if isinstance(col, Float64Bits):
        return Float64Bits(jnp.take(col.bits, rows, axis=0))
    return jnp.take(col, rows, axis=0)


def concat_columns(cols):
    """Columns of one type joined end to end."""
    if isinstance(cols[0], Float64Bits):
        return Float64Bits(jnp.concatenate([c.bits for c in cols]))
    return jnp.concatenate(cols)


def zeros_like_column(col, shape):
    """A column of zeros of ``col``'s type."""
    if isinstance(col, Float64Bits):
        return Float64Bits(jnp.zeros(shape, jnp.int64))
    return jnp.zeros(shape, col.dtype)


def numeric_column(col):
    """``col`` as a device array of numbers, for arithmetic (a sampler's
    probabilities): a ``Float64Bits`` column is decoded on the host and
    placed as float64, which a TPU holds as a float32 pair."""
    if isinstance(col, Float64Bits):
        return jnp.asarray(np.asarray(col))
    return col


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Relation:
    """An immutable column-store relation.

    columns: mapping attribute name -> array of shape (n,); a float64
    column is a ``Float64Bits``.
    """

    columns: Dict[str, jnp.ndarray]

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return tuple(self.columns[n] for n in names), names

    @classmethod
    def tree_unflatten(cls, names, leaves):
        return cls(dict(zip(names, leaves)))

    # -- basic accessors ----------------------------------------------------
    @property
    def attrs(self) -> Tuple[str, ...]:
        return tuple(sorted(self.columns))

    @property
    def num_rows(self) -> int:
        if not self.columns:
            return 0
        return next(iter(self.columns.values())).shape[0]

    def __len__(self) -> int:  # pragma: no cover - convenience
        return self.num_rows

    def column(self, name: str) -> jnp.ndarray:
        return self.columns[name]

    def project(self, attrs: Sequence[str]) -> "Relation":
        return Relation({a: self.columns[a] for a in attrs})

    def rename(self, mapping: Mapping[str, str]) -> "Relation":
        return Relation({mapping.get(a, a): v for a, v in self.columns.items()})

    def take(self, rows: jnp.ndarray) -> "Relation":
        """Gather rows (positional); rows may repeat (bag semantics)."""
        return Relation({a: take_column(v, rows) for a, v in self.columns.items()})

    def concat(self, other: "Relation") -> "Relation":
        assert set(self.columns) == set(other.columns)
        return Relation(
            {a: concat_columns([self.columns[a], other.columns[a]]) for a in self.columns}
        )

    def to_numpy(self) -> Dict[str, np.ndarray]:
        return {a: np.asarray(v) for a, v in self.columns.items()}

    @staticmethod
    def from_numpy(cols: Mapping[str, np.ndarray]) -> "Relation":
        return Relation({a: place_column(v) for a, v in cols.items()})

    def validate(self) -> None:
        lens = {v.shape[0] for v in self.columns.values()}
        if len(lens) > 1:
            raise ValueError(f"ragged columns: { {a: v.shape for a, v in self.columns.items()} }")


def pack_keys(cols: Sequence[jnp.ndarray], radices: Sequence[int]) -> jnp.ndarray:
    """Pack multi-attribute integer keys into one int64 via mixed radix.

    ``radices[i]`` must strictly exceed every value of ``cols[i]``.
    """
    assert len(cols) == len(radices) and cols
    key = cols[0].astype(jnp.int64)
    for c, r in zip(cols[1:], radices[1:]):
        key = key * jnp.int64(r) + c.astype(jnp.int64)
    return key


def dense_keys(
    left: Sequence[jnp.ndarray], right: Sequence[jnp.ndarray]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Map multi-column join keys of two relations to one dense int64 id.

    The same attribute tuple receives the same id on both sides, so the ids
    are directly comparable / sortable / ``searchsorted``-able. Implemented by
    a single lexsort over the concatenation of both key sets — the TPU-native
    replacement for the paper's hash-table key grouping (DESIGN.md §3).
    Fully jittable (static shapes).
    """
    assert len(left) == len(right) and left
    m = left[0].shape[0]
    cols = [jnp.concatenate([l.astype(jnp.int64), r.astype(jnp.int64)]) for l, r in zip(left, right)]
    # lexsort uses the LAST key as primary; order doesn't matter for grouping.
    order = jnp.lexsort(tuple(cols))
    sorted_cols = [c[order] for c in cols]
    diff = jnp.zeros(sorted_cols[0].shape, dtype=jnp.bool_)
    for c in sorted_cols:
        diff = diff | jnp.concatenate([jnp.ones((1,), jnp.bool_), c[1:] != c[:-1]])
    gid_sorted = jnp.cumsum(diff.astype(jnp.int64)) - 1
    gid = jnp.zeros_like(gid_sorted).at[order].set(gid_sorted)
    return gid[:m], gid[m:]
