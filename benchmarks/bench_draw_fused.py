"""One-launch fused draw vs the multi-launch per-node path (DESIGN.md §14).

Feeds the ``probe`` suite (BENCH_probe.json) alongside the fused-GET rows.
Three regimes over the STATS-like chain:

* **dispatch-bound single draw** (``draw-eager`` rows) — the serving
  regime the tentpole targets and the rows the acceptance gate reads: the
  multi-launch path dispatches the whole EXPRACE ladder op by op (uniform
  gaps, cumsum, prefix search, dedupe, compaction, then a per-tree-node
  probe walk), while the fused path is ONE kernel launch from PRNG key to
  per-node rows plus the column gather. Gated individually in
  BENCH_probe.json (``gate_rows``) so the >=2x dispatch-floor win cannot
  regress behind a healthy suite median.
* **warm jitted plan** (``draw-jit`` rows) — both routes fully traced into
  one dispatch via ``CompiledPlan.sample``; informational on the CPU
  interpret leg, where emulated Pallas loses to native jnp once dispatch
  overhead is gone (same story as the ``probe/jit-*`` rows).
* **small batch** (``draw-batched`` rows) — the vmapped multi-draw
  executor (DESIGN.md §10) over a power-of-two key bucket.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import config
from repro.core import build_shred, probe, sampling
from repro.engine import QueryEngine

from .timing import row, time_fn, tiny
from .workloads import stats_like

SCALE = 3000
BATCH = 8


def run(out):
    scale = 300 if tiny() else SCALE
    batch = 4 if tiny() else BATCH

    db, q = stats_like(0, scale)
    eng = QueryEngine(db)
    plan_f = eng.compile(q, kernels="fused")
    plan_p = eng.compile(q, kernels="pernode")
    n = plan_f.join_size
    cap = plan_f.default_capacity()
    acap = plan_f.arrival_capacity()
    key = jax.random.key(7)
    keys = jax.random.split(key, batch)

    # -- dispatch-bound: eager single draw (the gated rows) -----------------
    shred = build_shred(db, q, rep="both")
    root = shred.root
    w, p, prefE = root.weight, root.data.column("p"), shred.root_prefE
    dparams = sampling.fused_draw_params(w, p, prefE)
    assert dparams is not None, "workload must be fused-capable"
    massE = sampling.exprace_mass(w, p)

    def eager_pernode():
        ps = sampling.exprace_positions(key, w, p, prefE, massE, cap,
                                        arrival_cap=acap)
        pos = jnp.minimum(ps.positions, jnp.maximum(prefE[-1] - 1, 0))
        return probe.get(shred, pos, rep="usr"), ps

    def eager_fused():
        rows, ps = probe.draw_fused(shred, dparams, key, method="exprace",
                                    cap=cap, acap=acap)
        return probe.gather_columns(shred, rows), ps

    us_p_e = time_fn(lambda: jax.block_until_ready(eager_pernode()))
    us_f_e = time_fn(lambda: jax.block_until_ready(eager_fused()))
    out(row("probe/draw-eager-pernode/1", us_p_e, f"|Q|={n};cap={cap}"))
    out(row("probe/draw-eager-fused/1", us_f_e,
            f"pernode/fused={us_p_e / us_f_e:.2f}x"))

    # -- dispatch-bound, paged regime (DESIGN.md §15): the same draw with
    # the index rebuilt one word over the VMEM budget, so the walk streams
    # pages (sample launch + paged probe). Gated individually (gate_rows):
    # losing the paged rung means falling back to the multi-launch
    # per-node ladder and this row regressing toward draw-eager-pernode.
    size = shred.packed.layout.size
    pol = dataclasses.replace(config.current_policy(), vmem_limit=size - 1)
    with config.override(pol):
        shred_pg = build_shred(db, q, rep="both")
        assert shred_pg.paged is not None, "workload must land in the paged regime"

        def eager_paged():
            rows, ps = probe.draw_paged(shred_pg, dparams, key,
                                        method="exprace", cap=cap, acap=acap)
            return probe.gather_columns(shred_pg, rows), ps

        us_g_e = time_fn(lambda: jax.block_until_ready(eager_paged()))
    out(row("probe/draw-eager-paged/1", us_g_e,
            f"pernode/paged={us_p_e / us_g_e:.2f}x"))

    # -- warm jitted plan: single draw --------------------------------------
    us_p_j = time_fn(lambda: plan_p.sample(key))
    us_f_j = time_fn(lambda: plan_f.sample(key))
    out(row("probe/draw-jit-pernode/1", us_p_j))
    out(row("probe/draw-jit-fused/1", us_f_j,
            f"pernode/fused={us_p_j / us_f_j:.2f}x"))

    # -- small batch: the vmapped multi-draw executor -----------------------
    us_p_b = time_fn(lambda: plan_p.sample_batch(keys))
    us_f_b = time_fn(lambda: plan_f.sample_batch(keys))
    out(row(f"probe/draw-batched-pernode/B={batch}", us_p_b))
    out(row(f"probe/draw-batched-fused/B={batch}", us_f_b,
            f"pernode/fused={us_p_b / us_f_b:.2f}x"))
