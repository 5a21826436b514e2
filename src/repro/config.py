"""Process-wide kernel + platform configuration (DESIGN.md §14).

Before this module, kernel selection leaked through four environment
variables read at call time from four different places —
``REPRO_PALLAS_DISABLE``/``REPRO_PALLAS_INTERPRET``/``REPRO_PALLAS_PREFER``
in ``kernels/ops.py``, the fused-GET VMEM budget in ``core/probe.py``, the
bench-smoke flag in ``benchmarks/timing.py``, and the host-device count in
``launch/mesh.py``. ``KernelPolicy`` is the one value object for all of it:

  * **frozen + hashable** — a policy can be compared, cached against, and
    baked into plan identity without aliasing surprises;
  * **env vars are the default constructor only** — ``policy_from_env()``
    parses the three ``REPRO_PALLAS_*`` variables with their historical
    semantics (below) and nothing else ever reads them; the grep lint
    ``tools/check_env.py`` fails CI on raw ``REPRO_*`` reads outside this
    module;
  * **scoped override** — ``with override(KernelPolicy(...)):`` installs a
    policy for the dynamic extent (contextvar, so async/thread safe), and
    every ``policy=`` keyword threaded through ``kernels/ops.py`` /
    ``core/probe.py`` takes a per-call override on top.

Resolution order (first hit wins — DESIGN.md §14):

    per-call ``policy=``  >  ``override(...)`` context  >  environment

Env semantics:

    enabled   = REPRO_PALLAS_DISABLE  in ("", "0")   (default "0")
    interpret = REPRO_PALLAS_INTERPRET: "0" -> False, unset or "" -> the
                backend default (compiled on a TPU, interpreted
                elsewhere), anything else -> True
    prefer    = REPRO_PALLAS_PREFER   not in ("","0") (default "0")

Platform setup (``xla_force_host_platform_device_count``) lives here too so
launch scripts have one import that owns every process-level knob.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import os

__all__ = [
    "KernelPolicy", "policy_from_env", "current_policy", "override",
    "DEFAULT_VMEM_LIMIT", "PAGED_PACK_LIMIT", "force_host_devices",
    "bench_tiny", "set_bench_tiny", "backend", "device_kind", "backend_key",
    "PEAK_FLOPS", "peak_flops", "interpret_for_backend",
    "kernel_unsupported", "enable_compile_cache", "resolve_interpret",
    "resolves_interpret", "COMPILE_CACHE_DIR",
]

# int32 elements kept fully VMEM-resident (the fused-GET arena and the
# fused-draw scratch share this budget — DESIGN.md §9; ``kernels/ops.py``
# re-exports it as VMEM_PREF_LIMIT). Only kernels in TPU_UNLOWERABLE read
# it today, so it bounds the CPU/interpret routes; it is to be re-derived
# from ``memory_analysis()`` once an arena kernel lowers on the TPU.
DEFAULT_VMEM_LIMIT = 1 << 21

# Ceiling on the *total* size of a paged index arena (int32 elements,
# DESIGN.md §15): an arena bigger than the VMEM budget is still packed —
# page-sliced and streamed through VMEM by the paged kernels — up to this
# cap, past which the int32 copy stops paying for itself and the per-node
# int64 path stands (a 2^25-element arena is 128 MiB of extra HBM).
PAGED_PACK_LIMIT = 1 << 25


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """How Pallas kernels are selected, everywhere (DESIGN.md §14).

    enabled     master switch: False routes every wrapper through its
                pure-XLA/jnp fallback (the operator escape hatch for a
                kernel bug; historical ``REPRO_PALLAS_DISABLE=1``).
    interpret   run kernels in Pallas interpret mode. ``None`` (the
                default) resolves from the backend at construction:
                compiled on a TPU, interpreted on CPU/GPU
                (``interpret_for_backend``).
    prefer      prefer kernels over their XLA twins inside jitted hot
                paths even in interpret mode (the CI interpret leg pins
                this so the whole tier-1 suite exercises the kernels).
    vmem_limit  int32-element budget for VMEM-resident tables (prefix
                vectors, the packed index arena, fused-draw scratch).
                Arenas above it no longer drop to the per-node path:
                they run the *paged* rung (DESIGN.md §15) as long as
                every page fits this budget.
    fused_draw  allow the one-launch fused draw route (kernels/fused_draw)
                when capability gates pass; False pins the multi-launch
                per-node path without touching GET kernel selection.
    tuned       resolve kernel tile shapes through the committed
                ``kernels/TUNE_TABLE.json`` (per backend + problem-size
                bucket, DESIGN.md §15); False pins every kernel's builtin
                default tile (the pre-autotuner behavior).
    tile_overrides
                per-kernel tile pins that win over the tuning table: a
                tuple of ``(kernel_name, value)`` pairs (tuple-of-pairs —
                not a dict — so the policy stays hashable), e.g.
                ``(("tree_probe", 16), ("flash_prefill", (128, 256)))``.
    """

    enabled: bool = True
    interpret: bool = None
    prefer: bool = False
    vmem_limit: int = DEFAULT_VMEM_LIMIT
    fused_draw: bool = True
    tuned: bool = True
    tile_overrides: tuple = ()

    def __post_init__(self):
        if self.interpret is None:
            object.__setattr__(self, "interpret", interpret_for_backend())

    @property
    def preferred(self) -> bool:
        """Should jitted hot paths *prefer* Pallas kernels over their XLA
        twins when both are available? True in compiled mode (real TPU —
        the kernels are the point); in interpret mode the interpreter's
        per-access overhead loses to XLA inside an already-jitted
        executor, so hot paths default to XLA unless ``prefer`` pins the
        kernel path. Capability gates (``enabled``, dtype/VMEM fallbacks)
        still apply on top."""
        return self.enabled and (self.prefer or not self.interpret)

    def tile_override(self, kernel: str):
        """The pinned tile for ``kernel`` from ``tile_overrides``, or
        ``None`` — the first (highest-precedence) rung of the tile
        resolution ladder in ``kernels/autotune.tile_for``."""
        for name, value in self.tile_overrides:
            if name == kernel:
                return value
        return None


def interpret_for_backend(backend_name: str = None) -> bool:
    """The interpret-mode default for a backend: False (compiled Mosaic
    kernels) on a TPU, True everywhere else — the kernels are written for
    the TPU, and the interpreter is how CPU hosts run them."""
    return (backend_name or backend()) != "tpu"


def policy_from_env() -> KernelPolicy:
    """The default policy, parsed from the environment *at call time* (so
    tests and CI legs can flip a var without re-importing anything).
    ``REPRO_PALLAS_INTERPRET`` unset or empty means the backend default
    (``interpret_for_backend``); ``"0"`` forces compiled mode and any other
    value forces the interpreter."""
    env = os.environ.get
    interp = env("REPRO_PALLAS_INTERPRET", "")
    return KernelPolicy(
        enabled=env("REPRO_PALLAS_DISABLE", "0") in ("", "0"),
        interpret=None if interp == "" else interp != "0",
        prefer=env("REPRO_PALLAS_PREFER", "0") not in ("", "0"),
    )


_override: "contextvars.ContextVar[KernelPolicy]" = contextvars.ContextVar(
    "repro_kernel_policy", default=None)


def current_policy(policy: KernelPolicy = None) -> KernelPolicy:
    """Resolve the active policy: per-call ``policy=`` > ``override(...)``
    context > environment defaults (DESIGN.md §14)."""
    if policy is not None:
        return policy
    installed = _override.get()
    return installed if installed is not None else policy_from_env()


def resolve_interpret(interpret: bool = None) -> bool:
    """A kernel's ``interpret=`` argument: ``None`` takes the active
    policy's mode (compiled on a TPU, interpreted elsewhere)."""
    return current_policy().interpret if interpret is None else interpret


def resolves_interpret(kernel):
    """Decorate a jitted kernel entry whose static ``interpret`` argument
    defaults to ``None``: each call resolves it (``resolve_interpret``)
    before the jit cache key is formed, so a policy change never reuses a
    trace built for the other mode. ``.lower`` resolves the same way."""
    @functools.wraps(kernel)
    def call(*args, interpret: bool = None, **kwargs):
        return kernel(*args, interpret=resolve_interpret(interpret),
                      **kwargs)

    def lower(*args, interpret: bool = None, **kwargs):
        return kernel.lower(*args, interpret=resolve_interpret(interpret),
                            **kwargs)

    call.lower = lower
    return call


@contextlib.contextmanager
def override(policy: KernelPolicy):
    """Install ``policy`` for the dynamic extent of the ``with`` block::

        with repro.config.override(KernelPolicy(prefer=True)):
            plan = engine.compile(query)   # binds the fused routes

    Contextvar-scoped: concurrent threads/tasks see their own override.
    Note plans capture routing verdicts at *bind* time — a policy change
    after ``compile()`` does not rewire an existing plan (recompile, or
    let ``DrawSpec.kernels`` pin the route as plan identity)."""
    token = _override.set(policy)
    try:
        yield policy
    finally:
        _override.reset(token)


# Kernels the TPU compiler (Mosaic) refuses: kernel -> (the compiler's
# NotImplementedError message, where it comes from). A declared capability,
# read when a plan binds its routes (core/probe.select_rep / select_draw),
# never a try/except around a compile. tests/test_tpu_compile.py compiles
# each against a described v5e and checks it still fails with exactly this
# message, so the table cannot go stale.
TPU_UNLOWERABLE = {
    "tree_probe": (
        "Only 2D gather is supported",
        "the per-lane jnp.take from the 1-D VMEM arena in tree_walk"),
    "tree_probe_paged": (
        "Only 2D gather is supported",
        "the same tree_walk gathers, per page"),
    "fused_draw": (
        "Unimplemented primitive in Pallas TPU lowering for KernelType.TC: "
        "cumsum",
        "draw_core; its per-lane 1-D gathers are the ones tree_probe "
        "fails on"),
    "fused_sample": (
        "Unimplemented primitive in Pallas TPU lowering for KernelType.TC: "
        "cumsum",
        "draw_core, as in fused_draw"),
}


def kernel_unsupported(kernel: str, policy: KernelPolicy = None):
    """Why ``kernel`` cannot run under the active policy on this backend,
    or ``None`` when it can: compiled mode on a TPU rules out the kernels
    in ``TPU_UNLOWERABLE``; the interpreter runs every kernel."""
    if current_policy(policy).interpret or backend() != "tpu":
        return None
    if kernel not in TPU_UNLOWERABLE:
        return None
    message, where = TPU_UNLOWERABLE[kernel]
    return f"NotImplementedError: {message} ({where})"


# ---------------------------------------------------------------------------
# Platform setup (process-level, owned here so launch scripts import one
# module for every knob; launch/mesh.py delegates).
# ---------------------------------------------------------------------------

def force_host_devices(n: int) -> int:
    """Ask XLA for ``n`` virtual host (CPU) devices; returns the device
    count. Only effective before the backend initializes — appends
    ``--xla_force_host_platform_device_count`` to ``XLA_FLAGS`` — so it
    raises ``RuntimeError`` when fewer than ``n`` devices exist (the
    backend beat it to initialization, or ``XLA_FLAGS`` was pre-set):
    a run never silently proceeds on fewer devices than it asked for."""
    import jax

    if n > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={n}"
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()
    got = len(jax.devices())
    if got < n:
        raise RuntimeError(
            f"requested {n} devices, backend {jax.default_backend()!r} has "
            f"{got} (already initialized, or XLA_FLAGS pre-set)")
    return got


# Fixed, git-ignored compile-cache directory inside the checkout. JAX keys a
# cache entry by the directory too, so the path never carries a temporary
# name, a process id or a time.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    honoured as it is (JAX reads it itself; nothing else is set);
    otherwise the cache lives at ``COMPILE_CACHE_DIR``. Call it before the
    first compile — entry points (``chip_smoke.py``, ``benchmarks/run.py``,
    ``launch/serve.py``, ``launch/train.py``) do so first thing."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


# ---------------------------------------------------------------------------
# Backend detection (DESIGN.md §15). Centralized here so every kernel-
# selection seam (paged-probe DMA variant, tuning-table lookup, roofline
# peaks) asks the same question the same way; jax is imported lazily so
# stdlib-only tools (benchmarks/roofline.py aggregation) can import this
# module without pulling the runtime in.
# ---------------------------------------------------------------------------

def backend() -> str:
    """The active execution substrate: ``'tpu'`` | ``'gpu'`` | ``'cpu'``
    (``jax.default_backend()``). The paged tree-probe picks its streaming
    strategy off this (TPU: in-kernel double-buffered DMA; GPU/CPU: the
    portable per-page launch path — no ``pltpu``-only primitives), and the
    tuning table keys its entries off ``backend_key()``."""
    import jax

    return jax.default_backend()


def device_kind() -> str:
    """Normalized device-kind slug of device 0 (e.g. ``'tpu-v5e'``,
    ``'nvidia-h100'``, ``'cpu'``) — the second half of ``backend_key()``,
    so tuning entries distinguish device generations within a backend."""
    import jax

    kind = jax.devices()[0].device_kind
    return "-".join(str(kind).lower().split())


def backend_key() -> str:
    """``'<backend>/<device-kind>'`` — the tuning-table entry key for this
    process (DESIGN.md §15), e.g. ``'cpu/cpu'`` or ``'tpu/tpu-v5e'``."""
    return f"{backend()}/{device_kind()}"


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind`` exactly
# as JAX reports it. Source: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip). A device that is not in the
# table is an error, not a default: a roofline share against a guessed peak
# is not a measurement.
PEAK_FLOPS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak_flops(kind: str = None) -> float:
    """Peak bf16 FLOP/s of device kind ``kind`` (default: device 0 of this
    process). Raises ``KeyError`` for a device kind the table does not
    list."""
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    if kind not in PEAK_FLOPS:
        raise KeyError(f"no published peak for device kind {kind!r}; "
                       f"known: {sorted(PEAK_FLOPS)}")
    return PEAK_FLOPS[kind]["flops_bf16"]


# ---------------------------------------------------------------------------
# Bench-smoke flag (the only other REPRO_* variable; centralizing the read
# and the write here keeps the check_env lint trivially green).
# ---------------------------------------------------------------------------

def bench_tiny() -> bool:
    """True in bench-smoke mode (``benchmarks.run --tiny``): suites shrink
    their workloads so CI exercises every path in seconds."""
    return os.environ.get("REPRO_BENCH_TINY", "") not in ("", "0")


def set_bench_tiny(on: bool = True) -> None:
    """Flip bench-smoke mode for this process (and subprocesses). Set via
    env because suites size their workloads at module/run scope, possibly
    in spawned workers that inherit the environment."""
    if on:
        os.environ["REPRO_BENCH_TINY"] = "1"
    else:
        os.environ.pop("REPRO_BENCH_TINY", None)
