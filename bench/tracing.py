"""Host spans and the reduction of a profiler trace to device numbers.

Spans are the benchmark's own, opened around its calls into each layer of
the program (``jax.profiler.TraceAnnotation``), so they land in the
profiler's trace on the host's timeline beside the device's operations.

A trace is reduced from plain event lists, so that the arithmetic is
tested on a small recorded trace without a device:

* ``device``: {plane name: [(op name, start_ns, duration_ns), ...]} from
  the "XLA Ops" line of every ``/device:`` plane;
* ``host``: [(span name, start_ns, duration_ns), ...] for the benchmark's
  span names, from the ``/host:`` planes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Spans", "SPAN_NAMES", "op_name", "events_from_xplane", "Trace",
           "union", "covered"]

# Every span the benchmark opens. A gap in device activity is named by the
# innermost of these that is open on the host when it happens.
SPAN_NAMES = ("window", "draw", "full_join", "make_keys", "flush",
              "schedule")

Interval = Tuple[float, float]


class Spans:
    """Opens a ``TraceAnnotation`` per span while a trace is recorded."""

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


def op_name(name: str) -> str:
    """A device op's name and result shape from its HLO text:
    ``%fusion.8 = u32[7113216]{0:T(1024)} fusion(...)`` -> ``fusion.8
    u32[7113216]``; a tuple-valued op (a ``while``) keeps its name alone."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    head = head.lstrip("%")
    if rest.startswith("("):
        return head
    return f"{head} {rest.split(' ', 1)[0].split('{', 1)[0]}"


def events_from_xplane(path: str) -> dict:
    """The device op events and the benchmark's host spans of one
    ``.xplane.pb`` file (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.setdefault(plane.name, []).extend(
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name in SPAN_NAMES)
    return {"device": device, "host": host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by disjoint sorted intervals."""
    total = 0.0
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        total += min(e, hi) - max(s, lo)
    return total


@dataclasses.dataclass
class Trace:
    """A reduced trace: per-device busy intervals and host spans (ns)."""

    device: Dict[str, List[Tuple[str, float, float]]]
    host: List[Tuple[str, float, float]]

    @classmethod
    def from_events(cls, ev: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in ev["device"].items()},
                   [tuple(e) for e in ev["host"]])

    def __post_init__(self):
        self._merged = {}

    def _busy(self, plane: str) -> List[Interval]:
        if plane not in self._merged:
            self._merged[plane] = union((s, s + d)
                                        for _, s, d in self.device[plane])
        return self._merged[plane]

    def spans(self, name: str) -> List[Interval]:
        return sorted((s, s + d) for n, s, d in self.host if n == name)

    def window(self) -> Interval:
        """The measured window: the host span ``window``."""
        w = self.spans("window")
        if not w:
            raise ValueError("trace holds no 'window' span")
        return w[0][0], w[-1][1]

    def busy_s(self, lo: float = None, hi: float = None) -> float:
        """Device busy seconds in ``[lo, hi]`` (default: the window),
        averaged over the device planes."""
        if lo is None:
            lo, hi = self.window()
        if not self.device:
            return 0.0
        return sum(covered(self._busy(p), lo, hi)
                   for p in self.device) / len(self.device) / 1e9

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def idle_share(self) -> Optional[float]:
        """Percent of the window in which no operation ran on the device."""
        if not self.device:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    def span_device_s(self, name: str) -> Optional[float]:
        """Mean device busy seconds under each host span ``name``."""
        spans = self.spans(name)
        if not spans or not self.device:
            return None
        return sum(self.busy_s(s, e) for s, e in spans) / len(spans)

    def top_ops(self, n: int = 10) -> List[list]:
        """Device operations that took most time in the window, by name,
        in seconds averaged over the device planes."""
        lo, hi = self.window()
        tot: Dict[str, float] = {}
        for plane in self.device.values():
            for name, s, d in plane:
                c = min(s + d, hi) - max(s, lo)
                if c > 0:
                    tot[name] = tot.get(name, 0.0) + c
        k = max(len(self.device), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k / 1e9] for name, t in top]

    def idle_gaps(self, n: int = 10, plane: str = None) -> List[list]:
        """The longest idle gaps of the device in the window, each named by
        the innermost benchmark span open on the host at its midpoint
        ("host" where none is)."""
        if not self.device:
            return []
        lo, hi = self.window()
        plane = plane or sorted(self.device)[0]
        busy = self._busy(plane)
        gaps, t = [], lo
        for s, e in busy:
            if e <= lo:
                continue
            if s >= hi:
                break
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._open_span((s + e) / 2), (e - s) / 1e9]
                for s, e in gaps[:n]]

    def _open_span(self, t: float) -> str:
        best, width, at = "host", float("inf"), None
        for name, s, d in self.host:
            if name != "window" and s <= t <= s + d and d < width:
                best, width, at = name, d, (s, s + d)
        if best in SPLIT:
            child, before, after = SPLIT[best]
            for s, e in self.spans(child):
                if at[0] <= s and e <= at[1]:
                    return before if t < s else after
        return best


# A span whose own time is named by where it falls around a child span:
# a micro-batcher flush builds the keys before its draw and copies the
# rows out after it.
SPLIT = {"flush": ("draw", "make_keys", "copy_out")}
