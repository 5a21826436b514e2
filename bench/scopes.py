"""The program's own names in a profiler trace: the device scopes its
jitted code opens (``jax.named_scope``, ``repro.obs.SCOPES``) and the host
spans it opens (``repro.obs.SPANS``).

A device op carries its scope in the op-name metadata of the executable
that ran it: ``jit(_sample_jit)/exprace/exprace_dedupe/search/while``
holds the scope path ``exprace/exprace_dedupe/search``. A TPU's trace
events carry no such metadata, so an op is looked up in the optimised HLO
of the process's live executables: in its own module where the trace
says which ran it (``events_from_xplane``), and by the name and shape the
reduced trace keeps (``tracing.op_name``) where it does not (the
per-layer readers, ``draw_scope_s``).

Against a program that opens no scope, every op reads the empty path and
every reader here returns ``None``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench import tracing

__all__ = ["program_names", "scope_path", "hlo_op_names", "live_op_names",
           "events_from_xplane", "ScopedTrace", "draw_scope_s"]

Path = Tuple[str, ...]
_WRAP = re.compile(r"^\w+\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def program_names() -> Tuple[tuple, tuple]:
    """``(SPANS, SCOPES)`` of the program, empty where it has none."""
    try:
        from repro import obs
    except ImportError:  # a program that names nothing
        return (), ()
    return obs.SPANS, obs.SCOPES


def scope_path(op_name: str, scopes: Iterable[str] = None) -> Path:
    """The program scopes in an op-name path, outermost first; a
    transform's wrapper (``vmap(exprace)``) counts as the name inside."""
    scopes = set(program_names()[1] if scopes is None else scopes)
    out = []
    for part in op_name.split("/"):
        while True:
            m = _WRAP.match(part)
            if not m:
                break
            part = m.group(1)
        if part in scopes:
            out.append(part)
    return tuple(out)


def hlo_op_names(text: str) -> Dict[str, str]:
    """The op-name metadata of every instruction of an HLO module's text
    ("" where it has none), keyed as the trace names device ops
    (``tracing.op_name``)."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if not line.startswith("%") or " = " not in line:
            continue
        m = _OP_NAME.search(line)
        out[tracing.op_name(line)] = m.group(1) if m else ""
    return out


def _merge(maps: Iterable[Dict[str, str]]) -> Dict[str, str]:
    """One map from several modules; a name that two modules place in
    different scopes maps to no op name."""
    out: Dict[str, str] = {}
    for m in maps:
        for k, v in m.items():
            if k in out and scope_path(out[k]) != scope_path(v):
                v = ""
            out[k] = v
    return out


def live_op_names() -> Dict[str, Dict[str, str]]:
    """``{module name: hlo_op_names(module)}`` over the live executables
    of this process whose HLO names a program scope."""
    import jax.extend.backend

    scopes = program_names()[1]
    out: Dict[str, Dict[str, str]] = {}
    if not scopes:
        return out
    for ex in jax.extend.backend.get_backend().live_executables():
        for mod in ex.hlo_modules():
            text = mod.to_string()
            if any(f"/{s}/" in text or f'/{s}"' in text for s in scopes):
                out[mod.name] = _merge([out.get(mod.name, {}),
                                        hlo_op_names(text)])
    return out


def events_from_xplane(path: str, modules: Dict[str, Dict[str, str]] = None
                       ) -> dict:
    """``tracing.events_from_xplane`` with each device op's op-name
    metadata as a fourth field, and the program's host spans kept beside
    the benchmark's. ``modules`` defaults to ``live_op_names()``.

    A TPU's "XLA Ops" events carry neither their metadata nor their
    module: an op belongs to the module whose "XLA Modules" event holds
    its start, and is looked up there by name; an op outside every scoped
    module has none."""
    import bisect

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    modules = live_op_names() if modules is None else modules
    # op names are unique within a module: look them up there by name
    bare = {m: {k.split(" ", 1)[0]: v for k, v in ops.items()}
            for m, ops in modules.items()}
    keep = set(tracing.SPAN_NAMES) | set(program_names()[0])
    data = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            # "jit__unknown(1531250744604669859)": module name, program id
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(", 1)[0])
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
            starts = [r[0] for r in runs]
            if "XLA Ops" not in lines:
                continue
            out = device.setdefault(plane.name, [])
            for e in lines["XLA Ops"].events:
                name = tracing.op_name(e.name)
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = runs[i][2] if i >= 0 and e.start_ns < runs[i][1] else ""
                out.append((name, float(e.start_ns), float(e.duration_ns),
                            bare.get(mod, {}).get(name.split(" ", 1)[0], "")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name in keep)
    return {"device": device, "host": host}


def _paths():
    """``scope_path`` with the program's scopes, remembered per op name."""
    scopes, seen = program_names()[1], {}

    def path(op_name: str) -> Path:
        if op_name not in seen:
            seen[op_name] = scope_path(op_name, scopes)
        return seen[op_name]

    return path


class ScopedTrace(tracing.Trace):
    """A reduced trace whose device ops carry their program scope path."""

    def __init__(self, device, host, scopes: Dict[str, List[Path]]):
        super().__init__(device, host)
        self.scopes = scopes  # per plane, one path per device event

    @classmethod
    def from_events(cls, ev: dict) -> "ScopedTrace":
        """From events whose fourth field is the op-name metadata."""
        path = _paths()
        return cls({p: [tuple(e[:3]) for e in evs]
                    for p, evs in ev["device"].items()},
                   [tuple(e) for e in ev["host"]],
                   {p: [path(e[3] if len(e) > 3 else "") for e in evs]
                    for p, evs in ev["device"].items()})

    @classmethod
    def of(cls, trace: tracing.Trace, op_names: Dict[str, str]
           ) -> "ScopedTrace":
        """A plain trace with each op's op-name metadata looked up by the
        name the trace gives it."""
        path = _paths()
        return cls(trace.device, trace.host,
                   {p: [path(op_names.get(n, "")) for n, _, _ in evs]
                    for p, evs in trace.device.items()})

    def has_scopes(self) -> bool:
        return any(any(p) for paths in self.scopes.values() for p in paths)

    def _union(self, plane: str, keep) -> List[tracing.Interval]:
        return tracing.union(
            (s, s + d) for (_, s, d), path
            in zip(self.device[plane], self.scopes[plane]) if keep(path))

    def _per_span(self, under: str, keep) -> Optional[float]:
        spans = self.spans(under)
        if not spans or not self.device:
            return None
        merged = [self._union(p, keep) for p in self.device]
        total = sum(tracing.covered(m, s, e) for m in merged for s, e in spans)
        return total / len(merged) / len(spans) / 1e9

    def scope_device_s(self, scope: str, under: str = "draw"
                       ) -> Optional[float]:
        """Mean device busy seconds, over the host spans ``under``, of the
        ops whose scope path holds ``scope``."""
        return self._per_span(under, lambda path: scope in path)

    def unscoped_device_s(self, under: str = "draw") -> Optional[float]:
        """The same for the ops in no program scope."""
        return self._per_span(under, lambda path: not path)

    def device_scopes(self) -> List[list]:
        """Device seconds in the window by innermost program scope ("none"
        for ops in none), a union per scope, averaged over the planes."""
        lo, hi = self.window()
        inner = sorted({p[-1] if p else "none"
                        for paths in self.scopes.values() for p in paths})
        out = []
        for name in inner:
            def keep(path, name=name):
                return (path[-1] if path else "none") == name
            t = sum(tracing.covered(self._union(p, keep), lo, hi)
                    for p in self.device) / max(len(self.device), 1) / 1e9
            out.append([name, t])
        return sorted(out, key=lambda kv: -kv[1])


def draw_scope_s(trace: tracing.Trace, scope: str) -> Optional[float]:
    """A draw's device seconds in program scope ``scope``, from a plain
    trace and the live executables' HLO; ``None`` where the program opens
    no scope."""
    if trace is None:
        return None
    st = ScopedTrace.of(trace, _merge(live_op_names().values()))
    return st.scope_device_s(scope, "draw") if st.has_scopes() else None
