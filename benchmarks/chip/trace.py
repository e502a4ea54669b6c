"""Reduce a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three things: each device's operations, each device's programs (XLA
modules), and the benchmark's own host spans (``TraceAnnotation`` names
that start with ``bench.``).  Every interval is ``(name, start_ns, end_ns)``
on the trace's one clock.  The functions below do the arithmetic:

- ``union`` merges intervals, so a device's busy time counts overlapping
  operations once;
- ``gaps`` is the complement of the busy intervals inside a window;
- ``attribute`` splits each gap by the host spans open in it and names
  each piece by the innermost one: what the host was doing while the
  device idled;
- ``by_name`` sums operation time by name inside a window.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path

SPAN_PREFIX = "bench."
NO_SPAN = "(no span)"


#: an XLA operation's event name is its HLO text, ``%name = shape opcode(...)``
HLO = re.compile(r"^%?(?P<name>\S+) = .*?\s(?P<op>[a-z][\w\-]*)\(")


def op_name(text: str) -> str:
    """``name (opcode)`` of an operation's HLO text; other names as they are."""
    m = HLO.match(text)
    return f"{m['name']} ({m['op']})" if m else text


def tpu_lines(plane: str, line: str) -> str | None:
    """Which (plane, line) pairs hold device work on a TPU: the ``XLA Ops``
    and ``XLA Modules`` lines of each ``/device:TPU:n`` plane."""
    if plane.startswith("/device:TPU:") and line in ("XLA Ops", "XLA Modules"):
        return line
    return None


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[str, int, int]]]  # device plane -> operations
    modules: dict[str, list[tuple[str, int, int]]]  # device plane -> programs
    spans: list[tuple[str, int, int]]  # the benchmark's host spans
    lines: list[tuple[str, str]]  # every (plane, line) the file holds

    def span(self, name: str) -> tuple[int, int]:
        """The one host span called ``name`` (the window, say)."""
        found = [(s, e) for n, s, e in self.spans if n == name]
        if len(found) != 1:
            raise ValueError(f"{len(found)} host spans called {name!r}, expected 1")
        return found[0]


def xplane_file(trace_dir) -> Path:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(files[-1])


def load(path, device_lines=tpu_lines) -> Trace:
    """Read one xplane file.  ``device_lines(plane, line)`` returns
    ``"XLA Ops"``, ``"XLA Modules"`` or None for each line of the trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, modules, spans, lines = defaultdict(list), defaultdict(list), [], []
    for plane in data.planes:
        for line in plane.lines:
            lines.append((plane.name, line.name))
            kind = device_lines(plane.name, line.name)
            for ev in line.events:
                start = int(ev.start_ns)
                iv = (ev.name, start, start + int(ev.duration_ns))
                if kind == "XLA Ops":
                    ops[plane.name].append((op_name(ev.name), *iv[1:]))
                elif kind == "XLA Modules":
                    modules[plane.name].append(iv)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append(iv)
    return Trace(dict(ops), dict(modules), sorted(spans, key=lambda s: s[1]), lines)


def clip(intervals, lo: int, hi: int) -> list[tuple[str, int, int]]:
    """Intervals cut to [lo, hi]; those outside it dropped."""
    out = []
    for name, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged (start, end) pairs covering every interval once."""
    merged: list[list[int]] = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def gaps(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] that no merged interval covers."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def open_span(spans, t: int) -> str:
    """The innermost host span open at ``t`` (the latest-started one)."""
    best = None
    for name, s, e in spans:
        if s > t:
            break
        if e > t:
            best = name
    return best or NO_SPAN


def attribute(gap_list, spans) -> dict[str, int]:
    """Idle nanoseconds by host span: each gap is cut where a span opens or
    closes, and each piece goes to the innermost span open in it."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    out: dict[str, int] = defaultdict(int)
    for s, e in gap_list:
        cuts = [s, *edges[bisect.bisect_right(edges, s): bisect.bisect_left(edges, e)], e]
        for a, b in zip(cuts, cuts[1:]):
            out[open_span(spans, (a + b) // 2)] += b - a
    return dict(out)


def by_name(intervals, pattern: str | None = None) -> dict[str, int]:
    """Nanoseconds by name, of the names that match ``pattern`` (a regular
    expression, searched case-blind) where one is given."""
    rx = re.compile(pattern, re.IGNORECASE) if pattern else None
    out: dict[str, int] = defaultdict(int)
    for name, s, e in intervals:
        if rx is None or rx.search(name):
            out[name] += e - s
    return dict(out)


@dataclasses.dataclass
class Window:
    """One traced window reduced to what the metrics read: per device, its
    busy time, its gaps by host span, and its operations and programs."""

    lo: int
    hi: int
    busy_ns: dict[str, int]
    idle_by_span: dict[str, dict[str, int]]
    ops: dict[str, list[tuple[str, int, int]]]
    modules: dict[str, list[tuple[str, int, int]]]

    @property
    def seconds(self) -> float:
        return (self.hi - self.lo) / 1e9


def mean(per_device: dict) -> float:
    """The mean over devices of a per-device number."""
    return sum(per_device.values()) / len(per_device)


def reduce(trace: Trace, window_span: str) -> Window:
    """Cut ``trace`` to the host span ``window_span`` and reduce it."""
    lo, hi = trace.span(window_span)
    if not trace.ops:
        raise ValueError(f"the trace holds no device operations; its lines: {trace.lines}")
    busy, idle, ops, modules = {}, {}, {}, {}
    for dev, dev_ops in trace.ops.items():
        ops[dev] = clip(dev_ops, lo, hi)
        modules[dev] = clip(trace.modules.get(dev, []), lo, hi)
        merged = union(ops[dev])
        busy[dev] = total(merged)
        idle[dev] = attribute(gaps(merged, lo, hi), trace.spans)
    return Window(lo, hi, busy, idle, ops, modules)
