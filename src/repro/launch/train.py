"""Training driver: fault-tolerant loop with checkpoint/resume, straggler
watchdog, elastic re-meshing, and the Poisson-join data pipeline.

On this CPU container it runs the *reduced* configs end-to-end (the
examples/ scripts call into it); on TPU pods the same loop runs the full
configs — the mesh, shardings and step function are identical to the
dry-run's (launch/dryrun.py lowers exactly `make_train_step`).

Fault-tolerance story (DESIGN.md §6):
  * checkpoint manager: atomic + checksummed + keep-N + async; auto-resume
    from the newest valid step — node failure = restart-and-resume;
  * straggler watchdog: EWMA of step wall-time; a step exceeding
    ``straggler_factor`` x EWMA logs a straggler event (on real fleets this
    feeds the controller that re-schedules the slow host; here it is
    observable behavior under test);
  * elastic re-meshing: the data-parallel degree is re-derived from the
    live device count at (re)start; because batches are deterministic in
    (seed, step) and the global batch is fixed, scaling dp up/down between
    restarts changes only per-device microbatching, not the sample stream;
  * optional int8 gradient compression with error feedback for the DP
    all-reduce (parallel/compress.py) — opt-in flag.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import config, configs
from repro.checkpoint import CheckpointManager
from repro.data import PoissonJoinSource, SyntheticLMSource, make_corpus_db
from repro.launch.mesh import batch_axes, make_host_mesh
from repro.models import layers, transformer
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine


@dataclasses.dataclass
class TrainConfig:
    arch: str = "smollm_135m"
    reduced: bool = True
    steps: int = 200
    batch: int = 8
    seq_len: int = 64
    lr: float = 3e-3
    warmup: int = 20
    seed: int = 0
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    keep_n: int = 3
    straggler_factor: float = 3.0
    data: str = "poisson_join"  # or "synthetic"
    log_every: int = 10
    # Live-corpus schedule: ``(step, DeltaBatch)`` events applied by the
    # data source at step-aligned version barriers (DESIGN.md §13). The
    # schedule is part of the run's identity: resume replays it from the
    # base snapshot, and the checkpoint records the data version so a
    # mismatched schedule fails loudly instead of drifting silently.
    deltas: tuple = ()


def _train_step(cfg, opt_cfg, params, opt_state, batch, step):
    (loss, _), grads = jax.value_and_grad(
        transformer.loss_fn, has_aux=True)(params, cfg, batch)
    lr_scale = warmup_cosine(step, warmup=20, total=100000)
    params, opt_state, metrics = adamw_update(opt_cfg, params, grads, opt_state,
                                              lr_scale)
    metrics["loss"] = loss
    return params, opt_state, metrics


def train(tc: TrainConfig, hooks: Optional[Dict[str, Callable]] = None) -> Dict[str, Any]:
    hooks = hooks or {}
    cfg = configs.get_config(tc.arch)
    if tc.reduced:
        cfg = configs.reduced(cfg)
        cfg = dataclasses.replace(cfg, attn_chunk=max(tc.seq_len // 2, 16))

    # --- elastic mesh: dp degree derived from live devices -----------------
    mesh = make_host_mesh()
    multi = int(np.prod(list(mesh.shape.values()))) > 1
    layers.set_batch_axes(
        batch_axes(mesh) if multi and tc.batch % mesh.shape["data"] == 0 else ())

    key = jax.random.key(tc.seed)
    params = transformer.init_model(cfg, key)
    opt_cfg = AdamWConfig(lr=tc.lr, moment_dtype="float32")
    opt_state = adamw_init(opt_cfg, params)

    # --- data ---------------------------------------------------------------
    if tc.data == "poisson_join":
        db = make_corpus_db(n_docs=512, n_clusters=16, seq_len=tc.seq_len + 1,
                            vocab=cfg.vocab, seed=tc.seed)
        source = PoissonJoinSource(db, tc.seq_len + 1, tc.batch, seed=tc.seed,
                                   deltas=tc.deltas)
    else:
        source = SyntheticLMSource(cfg.vocab, tc.seq_len, tc.batch, seed=tc.seed)

    # --- resume ---------------------------------------------------------------
    ckpt = CheckpointManager(tc.ckpt_dir, keep_n=tc.keep_n)
    state_tpl = {"params": params, "opt": opt_state,
                 "data_version": np.zeros((), np.int64)}
    start, restored = ckpt.restore(state_tpl)
    if start is not None:
        params, opt_state = restored["params"], restored["opt"]
        if hasattr(source, "version_at") and start > 0:
            want = source.version_at(start - 1)
            got = int(restored["data_version"])
            if got != want:
                raise RuntimeError(
                    f"checkpoint data_version={got} but the delta schedule "
                    f"puts step {start - 1} at version {want}; resume must "
                    f"replay the run's exact schedule (DESIGN.md §13)")
        print(f"[train] resumed from step {start}")
    start = (start or 0)

    step_fn = jax.jit(partial(_train_step, cfg, opt_cfg))

    # --- loop with straggler watchdog ----------------------------------------
    ewma = None
    losses = []
    straggler_events = []
    doc_ids = []        # per-step sampled doc ids (poisson_join source)
    data_versions = []  # per-step snapshot version each batch was drawn at
    data_version = 0
    for step in range(start, tc.steps):
        batch = source.batch_at(step)
        batch.pop("sampled_k", None)
        step_docs = batch.pop("doc_ids", None)
        data_version = batch.pop("db_version", data_version)
        if step_docs is not None:
            doc_ids.append(np.asarray(step_docs))
        data_versions.append(data_version)
        t0 = time.time()
        with mesh:
            params, opt_state, metrics = step_fn(params, opt_state, batch,
                                                 jnp.asarray(step, jnp.int32))
        loss = float(metrics["loss"])
        dt = time.time() - t0
        if ewma is None:
            ewma = dt
        if dt > tc.straggler_factor * ewma and step > start + 3:
            straggler_events.append((step, dt, ewma))
            print(f"[train] STRAGGLER step {step}: {dt:.3f}s vs EWMA {ewma:.3f}s")
            if "on_straggler" in hooks:
                hooks["on_straggler"](step, dt, ewma)
        ewma = 0.9 * ewma + 0.1 * dt
        losses.append(loss)
        if step % tc.log_every == 0:
            print(f"[train] step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)")
        if "on_step" in hooks:
            hooks["on_step"](step, loss)
        if (step + 1) % tc.ckpt_every == 0 or step + 1 == tc.steps:
            ckpt.save(step + 1, {"params": params, "opt": opt_state,
                                 "data_version": np.asarray(data_version,
                                                            np.int64)})
    ckpt.wait()
    return {"losses": losses, "params": params, "straggler_events": straggler_events,
            "doc_ids": doc_ids, "data_versions": data_versions,
            "final_step": tc.steps}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--data", default="poisson_join")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--full", action="store_true", help="full (non-reduced) config")
    args = ap.parse_args()
    config.enable_compile_cache()
    out = train(TrainConfig(arch=args.arch, steps=args.steps, batch=args.batch,
                            seq_len=args.seq_len, data=args.data,
                            ckpt_dir=args.ckpt_dir, reduced=not args.full))
    print(f"[train] done. loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")


if __name__ == "__main__":
    main()
