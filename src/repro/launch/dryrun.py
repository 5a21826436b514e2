import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every other import (jax locks the device count on first
# init). Do NOT replicate this anywhere global — tests/benches see 1 device.

"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input shape) cell and both production meshes,
lower + compile the correct step function (train_step / prefill /
serve_step), print memory_analysis() (proves it fits) and cost_analysis()
(FLOPs/bytes for §Roofline), and parse collective bytes from the compiled
HLO. Results land in experiments/dryrun/*.json for benchmarks/roofline.py.

Usage:
    python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod-only | --single-pod-only]
"""
import argparse
import json
import re
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import config, configs
from repro.launch.hlo_cost import HloCost
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import config as mcfg
from repro.models import layers, transformer
from repro.optim import AdamWConfig, adamw_init, adamw_update

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# v5e hardware constants (per chip) — §Roofline; peaks from the one
# device_kind-keyed table in repro.config.
_V5E = config.PEAK_FLOPS["TPU v5 lite"]
PEAK_FLOPS = _V5E["flops_bf16"]
HBM_BW = _V5E["hbm_bytes_per_s"]
ICI_BW = 50e9            # bytes/s/link


# ---------------------------------------------------------------------------
# sharding construction
# ---------------------------------------------------------------------------

def _dp_spec(mesh, size: int):
    dp = batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in dp]))
    return P(dp) if dp and size % total == 0 else P(None)


def batch_shardings(mesh, specs):
    dpB = {k: v.shape[0] for k, v in specs.items() if v.ndim >= 1}

    def spec_for(k, v):
        if v.ndim == 0:
            return P()
        lead = _dp_spec(mesh, v.shape[0])
        return P(*(tuple(lead) + (None,) * (v.ndim - 1)))

    return {k: NamedSharding(mesh, spec_for(k, v)) for k, v in specs.items()}


def cache_shardings(mesh, cfg, cache_shapes):
    """Decode-cache sharding: batch on (pod, data); the model axis goes on
    KV heads when divisible, else on head_dim (DUS-safe; see DESIGN.md §6)."""
    m = mesh.shape["model"]

    def leaf(path, v):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        dims = [None] * v.ndim
        # leading dims: (repeats, batch, ...)
        if v.ndim >= 2:
            dp = _dp_spec(mesh, v.shape[1])
            dims[1] = tuple(dp)[0] if tuple(dp) != (None,) else None
        if name in ("k", "v", "ck", "cv") and v.ndim == 5:
            if v.shape[3] % m == 0:
                dims[3] = "model"            # KV heads
            elif v.shape[2] % m == 0 and v.shape[2] >= 4096:
                dims[2] = "model"            # cache sequence (flash-decoding
                # parallelism: per-shard partial softmax, scalar psums —
                # replaces the 537MB/layer hd-sharded score psums, §Perf H4)
            elif v.shape[4] % m == 0:
                dims[4] = "model"            # head_dim
        elif name == "state" and v.ndim >= 4:
            if v.shape[2] % m == 0:
                dims[2] = "model"            # state heads
        elif name in ("conv",) and v.ndim == 4 and v.shape[3] % m == 0:
            dims[3] = "model"
        elif name in ("shift_t", "shift_c") and v.ndim == 3 and v.shape[2] % m == 0:
            dims[2] = "model"
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(leaf, cache_shapes)


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_train_step(cfg, opt_cfg):
    """Full training step; with cfg.grad_accum > 1 the global batch is split
    into sequential microbatches (activation memory / accum, the other half
    of what fits llama3-405b on a pod — see DESIGN.md §5/§6)."""
    accum = max(cfg.grad_accum, 1)

    def grad_of(params, batch):
        (loss, _), grads = jax.value_and_grad(
            transformer.loss_fn, has_aux=True)(params, cfg, batch)
        return loss, grads

    def train_step(params, opt_state, batch):
        pspecs = layers.param_specs(params)
        shard = lambda t: jax.lax.with_sharding_constraint(t, pspecs)
        if accum == 1:
            loss, grads = grad_of(params, batch)
            grads = shard(grads)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch)
            gdt = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32

            def body(acc, mb):
                loss_acc, g_acc = acc
                loss, g = grad_of(params, mb)
                # keep the accumulator ZeRO-sharded: per-microbatch gradients
                # reduce-scatter into it instead of replicating over 'data'
                g_acc = shard(jax.tree.map(lambda a, b: a + b.astype(gdt),
                                           g_acc, shard(g)))
                return (loss_acc + loss, g_acc), None

            zeros = shard(jax.tree.map(lambda p: jnp.zeros(p.shape, gdt), params))
            (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros),
                                            micro)
            loss = loss / accum
            grads = jax.tree.map(lambda g: g / accum, grads)
        params, opt_state, metrics = adamw_update(opt_cfg, params, grads, opt_state)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def make_prefill(cfg):
    def prefill_step(params, batch):
        memory = batch.get("memory")
        if cfg.has_encoder:
            memory = transformer.encode(params, cfg, batch["frames"])
        logits, _ = transformer.forward(params, cfg, batch["tokens"], memory)
        return logits[:, -1]

    return prefill_step


def make_serve_step(cfg):
    def serve_step(params, cache, tokens, cur):
        return transformer.decode_step(params, cfg, cache, tokens, cur)

    return serve_step


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4, "s16": 2,
          "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1}
_COLL = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")


def _shape_bytes(sig: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(sig):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES.get(dt[:4].rstrip("["), _BYTES.get(dt, 4))
    return total


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op, by type. all-reduce is
    counted 2x (reduce-scatter + all-gather equivalent ring traffic)."""
    out = {k: 0 for k in _COLL}
    counts = {k: 0 for k in _COLL}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT )?[%\w.\-]+ = (.*?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)", s)
        if not m:
            continue
        sig, op = m.group(1), m.group(2)
        b = _shape_bytes(sig)
        if op == "all-reduce":
            b *= 2
        out[op] += b
        counts[op] += 1
    return out, counts


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: str, multi_pod: bool, cache_mode: str = "auto",
             verbose: bool = True):
    cfg = configs.get_config(arch)
    skip = configs.shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape}__{mesh_name}"
    if skip:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "skipped": skip}
        _write(tag, rec)
        if verbose:
            print(f"[dryrun] SKIP {tag}: {skip}")
        return rec

    sp = configs.SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    layers.set_batch_axes(batch_axes(mesh) if sp.batch >= 32 else ())
    layers.set_moe_ep(getattr(cfg, "moe_ep", False))
    n_chips = int(np.prod(list(mesh.shape.values())))
    specs = configs.input_specs(cfg, shape)
    # eval_shape of init to get the param ShapeDtypeStructs without allocating
    param_shapes = jax.eval_shape(partial(transformer.init_model, cfg),
                                  jax.random.key(0))
    pspecs = layers.sanitize_pspecs(layers.param_specs(param_shapes),
                                    param_shapes, mesh)
    param_shards = jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))

    t0 = time.time()
    with mesh:
        if sp.kind == "train":
            opt_cfg = AdamWConfig(
                moment_dtype="bfloat16" if cfg.param_dtype == "bfloat16" else "float32",
                factored=cfg.opt_factored)
            opt_shapes = jax.eval_shape(partial(adamw_init, opt_cfg), param_shapes)

            def vshard(shape_struct, spec):
                if cfg.opt_factored and shape_struct.ndim >= 2:
                    sp = list(spec) + [None] * (shape_struct.ndim - len(spec))
                    return {"vr": NamedSharding(mesh, P(*sp[:-1])),
                            "vc": NamedSharding(mesh, P(*(sp[:-2] + sp[-1:])))}
                return NamedSharding(mesh, spec)

            opt_shards = {
                "step": NamedSharding(mesh, P()),
                "m": param_shards,
                "v": jax.tree.map(vshard, param_shapes, pspecs),
            }
            bshard = batch_shardings(mesh, specs)
            fn = jax.jit(make_train_step(cfg, opt_cfg),
                         in_shardings=(param_shards, opt_shards, bshard),
                         donate_argnums=(0, 1))
            lowered = fn.lower(param_shapes, opt_shapes, specs)
        elif sp.kind == "prefill":
            bshard = batch_shardings(mesh, specs)
            fn = jax.jit(make_prefill(cfg), in_shardings=(param_shards, bshard))
            lowered = fn.lower(param_shapes, specs)
        else:  # decode
            cache_shapes = jax.eval_shape(
                partial(transformer.init_cache, cfg, sp.batch, sp.seq,
                        cfg.n_memory_tokens))
            cshard = cache_shardings(mesh, cfg, cache_shapes)
            tshard = NamedSharding(mesh, P(*(tuple(_dp_spec(mesh, sp.batch)) + (None,))))
            fn = jax.jit(make_serve_step(cfg),
                         in_shardings=(param_shards, cshard, tshard,
                                       NamedSharding(mesh, P())),
                         donate_argnums=(1,))
            lowered = fn.lower(param_shapes, cache_shapes, specs["tokens"],
                               specs["cur"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    # trip-count-aware accounting (cost_analysis counts loop bodies once —
    # see hlo_cost.py); XLA's raw numbers are kept alongside for reference.
    hc = HloCost(hlo).entry_cost()
    flops = float(hc["flops"])
    bytes_acc = float(hc["bytes"])
    coll = {k: float(hc[k]) for k in
            ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")}
    coll_total = float(hc["collective_bytes"])

    mem_rec = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }

    # roofline terms (per §ROOFLINE): all quantities are per-partition
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_coll = coll_total / ICI_BW
    # assignment formula: MODEL_FLOPS = 6*N*D (N_active for MoE), D = tokens
    # this step processes. (For inference kinds 6ND overstates by ~3x vs the
    # 2ND forward cost — noted in EXPERIMENTS.md §Roofline.)
    ntok = sp.batch * (1 if sp.kind == "decode" else sp.seq)
    model_flops = 6 * cfg.active_param_count() * ntok

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": n_chips,
        "kind": sp.kind, "seq": sp.seq, "batch": sp.batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem_rec,
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "xla_flops_per_device_bodies_once": float(cost.get("flops", 0.0)),
        "xla_bytes_per_device_bodies_once": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_total_bytes": coll_total,
        "roofline": {
            "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
            "dominant": max(
                [("compute", t_compute), ("memory", t_memory), ("collective", t_coll)],
                key=lambda kv: kv[1])[0],
        },
        "model_flops_total": model_flops,
        "model_flops_per_device": model_flops / n_chips,
        "useful_flops_ratio": (model_flops / n_chips) / flops if flops else None,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    _write(tag, rec)
    if verbose:
        print(f"[dryrun] {tag}: lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem_rec}")
        print(f"  cost_analysis: flops/dev={flops:.3e} bytes/dev={bytes_acc:.3e}")
        print(f"  collectives: {coll}")
        r = rec["roofline"]
        print(f"  roofline: compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
              f"collective={r['collective_s']:.4f}s dominant={r['dominant']}")
    return rec


def _write(tag, rec):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    with open(OUT_DIR / f"{tag}.json", "w") as f:
        json.dump(rec, f, indent=2)


def run_paper_cell(multi_pod: bool, scale: int = 200_000):
    """Dry-run the paper's own pipeline: the multi-pod sharded Poisson
    sampler (core/distributed.py) on the production mesh at EpiQL-like
    relative scale (Q_c star join; root block-partitioned on (pod, data))."""
    from repro.core import Atom, Database, JoinQuery
    from repro.core.distributed import ShardedPoissonSampler

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    axes = ("pod", "data") if multi_pod else ("data",)
    rng = np.random.default_rng(0)
    npool, nage, npers = max(scale // 50, 4), 6, scale
    grid_n = npool * nage * nage
    db = Database.from_columns({
        "Person": {"pers": np.arange(npers),
                   "age": rng.integers(0, nage, npers),
                   "pool": rng.integers(0, npool, npers)},
        "ContactProb": {"pool": rng.integers(0, npool, grid_n),
                        "age1": rng.integers(0, nage, grid_n),
                        "age2": rng.integers(0, nage, grid_n),
                        "prob": rng.random(grid_n) * 0.05},
    })
    q = JoinQuery((
        Atom.of("ContactProb", "pool", "age1", "age2", "prob"),
        Atom.of("Person", "per1", "age1", "pool", alias="P1"),
        Atom.of("Person", "per2", "age2", "pool", alias="P2"),
    ), prob_var="prob")
    t0 = time.time()
    s = ShardedPoissonSampler(db, q, mesh, axes=axes)
    with mesh:
        compiled = s.lower_step().compile()
    hc = HloCost(compiled.as_text()).entry_cost()
    mem = compiled.memory_analysis()
    tc_, tm_, tl_ = (hc["flops"] / PEAK_FLOPS, hc["bytes"] / HBM_BW,
                     hc["collective_bytes"] / ICI_BW)
    rec = {
        "arch": "paper_qc_sampler", "shape": f"scale_{scale}", "mesh": mesh_name,
        "kind": "sample_step", "chips": int(np.prod(list(mesh.shape.values()))),
        "compile_s": round(time.time() - t0, 2),
        "memory": {"argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                   "temp_bytes": getattr(mem, "temp_size_in_bytes", None)},
        "flops_per_device": float(hc["flops"]),
        "bytes_per_device": float(hc["bytes"]),
        "collective_total_bytes": float(hc["collective_bytes"]),
        "roofline": {"compute_s": tc_, "memory_s": tm_, "collective_s": tl_,
                     "dominant": max([("compute", tc_), ("memory", tm_),
                                      ("collective", tl_)], key=lambda kv: kv[1])[0]},
        "per_shard_capacity": s.cap,
    }
    _write(f"paper_qc_sampler__scale{scale}__{mesh_name}", rec)
    print(f"[dryrun] paper sampler {mesh_name}: compile {rec['compile_s']}s "
          f"compute={tc_:.2e}s memory={tm_:.2e}s collective={tl_:.2e}s")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 only")
    ap.add_argument("--single-pod", action="store_true", help="16x16 only")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--paper", action="store_true",
                    help="dry-run the paper's sharded Poisson sampler")
    args = ap.parse_args()

    if args.paper:
        run_paper_cell(multi_pod=False)
        run_paper_cell(multi_pod=True)
        if not (args.all or args.arch):
            return

    meshes = []
    if args.multi_pod:
        meshes = [True]
    elif args.single_pod:
        meshes = [False]
    else:
        meshes = [False, True]

    cells = []
    archs = list(configs.ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(configs.SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = []
    for a, s, mp in cells:
        try:
            run_cell(a, s, mp)
        except Exception as e:  # noqa: BLE001 — report all failures at end
            failures.append((a, s, mp, repr(e)[:300]))
            print(f"[dryrun] FAIL {a} {s} multi_pod={mp}: {e}", file=sys.stderr)
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\n[dryrun] all cells passed")


if __name__ == "__main__":
    main()
