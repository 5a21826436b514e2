"""repro.core — Poisson sampling over acyclic joins (the paper's contribution).

Public API:
    Database, Relation, Atom, JoinQuery       data model / queries
    Float64Bits                               a float64 column, held as its
                                              int64 bit patterns (DESIGN.md §9)
    DeltaBatch, Database.apply                versioned snapshots (DESIGN.md §11)
    build_shred, Shred, get                   random-access index (CSR/USR)
    reshred_incremental                       merge a delta into an index
    PoissonSampler, JoinSample                end-to-end Index-and-Probe
    sampling.*                                position-sampling methods
    yannakakis.*                              full joins + M&S baselines
    distributed.*                             shard_map multi-pod sampling

Engine entry points: ``repro.engine.QueryEngine`` is the unified planner /
compiled-plan cache over these primitives — ``engine.full_join(q)`` and
``engine.poisson_sample(q, key)`` serve both workloads from one cached
shred index (DESIGN.md §7). ``PoissonSampler`` and ``yannakakis.full_join``
are DEPRECATED single-query facades over it (DeprecationWarning since the
DrawSpec consolidation, DESIGN.md §13); new code holds a ``QueryEngine``.

x64 note: join sizes reach 1e10 (paper §1), so offsets/prefix vectors are
int64. JAX only honors int64 with the x64 flag; importing repro.core enables
it process-wide. Model code (repro.models) is dtype-explicit everywhere and
unaffected.
"""
import jax as _jax

_jax.config.update("jax_enable_x64", True)

from .relations import Relation, Float64Bits, pack_keys, dense_keys  # noqa: E402
from .database import Database  # noqa: E402
from .delta import DeltaBatch, RelationDelta  # noqa: E402
from .jointree import Atom, JoinQuery, gyo_join_tree, is_acyclic, reroot_for  # noqa: E402
from .shred import (Shred, ShredNode, build_shred, build_plan,  # noqa: E402
                    reshred_incremental, PackedShred, PagedArena,
                    pack_arena, pack_index)
from .probe import (get, get_rows, csr_get_rows, usr_get_rows,  # noqa: E402
                    usr_get_rows_fused, usr_get_rows_paged)
from . import sampling, estimate, yannakakis  # noqa: E402
from .poisson import PoissonSampler, JoinSample  # noqa: E402

__all__ = [
    "Relation", "Float64Bits", "Database", "DeltaBatch", "RelationDelta", "Atom",
    "JoinQuery", "gyo_join_tree", "is_acyclic",
    "reroot_for", "Shred", "ShredNode", "build_shred", "build_plan",
    "reshred_incremental", "PackedShred", "PagedArena", "pack_arena",
    "pack_index", "get",
    "get_rows", "csr_get_rows", "usr_get_rows", "usr_get_rows_fused",
    "usr_get_rows_paged", "sampling", "estimate",
    "yannakakis", "PoissonSampler", "JoinSample", "pack_keys", "dense_keys",
]
