"""The program's own names in a traced run: device scopes and host spans.

The program names its phases with the JAX profiler.  ``jax.named_scope``
puts a scope in the name stack of every operation of the compiled step
(``repro.scatter_values``, ``repro.expand_a``, ``repro.expand_b``,
``repro.local``, ``repro.reduce_c``); the TPU trace carries an operation's
name stack in its ``tf_op`` stat.  ``jax.profiler.TraceAnnotation`` marks
the caller's host path (``repro.pack``, ``repro.call``,
``repro.unpack.fetch``, ``repro.unpack.reorder``).

``load`` re-reads a run's ``.xplane.pb`` and keeps what ``trace.load``
leaves out: each device operation's scope, and the ``repro.`` host spans
beside the benchmark's ``bench.`` ones.  It keeps the same device
operations as ``trace.load`` (the same lines, ``trace.tpu_lines``), and
``Scopes.window`` clips them to the same window, so a scope's time is a
part of what ``ctx.window`` counts.  One parse per trace file is cached:
the seven readers of a run share it.

- ``Scopes.scope_ns``: per device, the union of the operations under a set
  of scopes;
- ``Scopes.span_ns``: the summed length of the host spans of one name;
- ``Scopes.idle_by_program_span``: the device's idle stretches, each named
  by the innermost host span open in it, the program's or the benchmark's
  (``trace.gaps`` and ``trace.attribute``).

From the root of a checkout, after a traced run of a cell, it prints
that run's summary as JSON (scopes, host spans and idle per product, the
share no scope covers and its largest operations):

    python3 -m benchmarks.chip.scopes <cell>
"""
from __future__ import annotations

import dataclasses
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

from benchmarks.chip import trace

HERE = Path(__file__).resolve().parent
PROGRAM_PREFIX = "repro."
WINDOW_SPAN = "bench.window"
#: the stat of a TPU operation that holds its name stack
STACK_STAT = "tf_op"
#: a scope in a name stack: ``jit(run)/shard_map/repro.local/mul`` holds
#: ``repro.local``; a transform wraps it (``transpose(repro.local)``)
SCOPE = re.compile(r"(?<![\w.])repro\.[A-Za-z_][\w.]*")
UNSCOPED = "(no scope)"

SCATTER = ("repro.scatter_values",)
LOCAL = ("repro.local",)
EXCHANGE = ("repro.expand_a", "repro.expand_b", "repro.reduce_c")


def scope_of(stack: str | None) -> str | None:
    """The innermost ``repro.`` scope of a name stack, or None."""
    found = SCOPE.findall(stack or "")
    return found[-1] if found else None


@dataclasses.dataclass
class Scopes:
    """A traced run's device operations by scope and its host spans.

    ``ops[device][scope]`` lists ``(name, start_ns, end_ns)``, with the
    scope None for an operation under no ``repro.`` scope; ``spans`` are
    the host spans (``repro.`` and ``bench.``), sorted by start."""

    ops: dict[str, dict[str | None, list[tuple[str, int, int]]]]
    spans: list[tuple[str, int, int]]

    def window(self, lo: int, hi: int) -> "Scopes":
        """Everything cut to [lo, hi], as ``trace.reduce`` cuts it."""
        ops = {
            dev: {sc: trace.clip(ivs, lo, hi) for sc, ivs in by_scope.items()}
            for dev, by_scope in self.ops.items()
        }
        return Scopes(ops, trace.clip(self.spans, lo, hi))

    def count(self, dev: str) -> int:
        return sum(len(ivs) for ivs in self.ops.get(dev, {}).values())

    def scoped(self) -> bool:
        """Whether any device operation runs under a program scope."""
        return any(sc is not None for by in self.ops.values() for sc in by)

    def scope_ns(self, scopes) -> dict[str, int]:
        """Per device, the union of the operations under any of ``scopes``
        (None names the unscoped ones)."""
        return {
            dev: trace.total(trace.union(
                [iv for sc in scopes for iv in by_scope.get(sc, [])]))
            for dev, by_scope in self.ops.items()
        }

    def busy_ns(self) -> dict[str, int]:
        """Per device, the union of all its operations."""
        return self.scope_ns(self.all_scopes())

    def all_scopes(self) -> list[str | None]:
        return sorted({sc for by in self.ops.values() for sc in by},
                      key=lambda sc: (sc is None, sc or ""))

    def span_ns(self, name: str) -> int:
        return sum(e - s for n, s, e in self.spans if n == name)

    def span_count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == name)

    def idle_by_program_span(self, lo: int, hi: int) -> dict[str, float]:
        """Idle nanoseconds by the innermost host span open, mean over
        devices."""
        out: dict[str, float] = defaultdict(float)
        for dev in self.ops:
            merged = trace.union(
                [iv for ivs in self.ops[dev].values() for iv in ivs])
            idle = trace.attribute(trace.gaps(merged, lo, hi), self.spans)
            for name, ns in idle.items():
                out[name] += ns / len(self.ops)
        return dict(out)

    def top_ops(self, scope: str | None, n: int = 10) -> list[tuple[str, float]]:
        """The ``n`` costliest operations under ``scope``, nanoseconds summed
        over their events and averaged over devices."""
        out: dict[str, float] = defaultdict(float)
        for by_scope in self.ops.values():
            for name, ns in trace.by_name(by_scope.get(scope, [])).items():
                out[name] += ns / len(self.ops)
        return sorted(out.items(), key=lambda kv: -kv[1])[:n]


_PARSED: dict[tuple, Scopes] = {}


def load(path) -> Scopes:
    """Read one xplane file, once per file."""
    path = Path(path)
    st = path.stat()
    key = (str(path.resolve()), st.st_size, st.st_mtime_ns)
    if key not in _PARSED:
        _PARSED[key] = _parse(path)
    return _PARSED[key]


def _parse(path: Path) -> Scopes:
    from jax.profiler import ProfileData

    scope_by_name = scopes_of_metadata(path.read_bytes())
    data = ProfileData.from_file(str(path))
    ops: dict = defaultdict(lambda: defaultdict(list))
    spans = []
    for plane in data.planes:
        named = scope_by_name.get(plane.name, {})
        for line in plane.lines:
            kind = trace.tpu_lines(plane.name, line.name)
            for ev in line.events:
                start = int(ev.start_ns)
                end = start + int(ev.duration_ns)
                if kind == "XLA Ops":
                    ops[plane.name][named.get(ev.name)].append((trace.op_name(ev.name), start, end))
                elif kind is None and ev.name.startswith((PROGRAM_PREFIX, trace.SPAN_PREFIX)):
                    spans.append((ev.name, start, end))
    return Scopes({d: dict(by) for d, by in ops.items()},
                  sorted(spans, key=lambda s: s[1]))


# -- the event metadata, which ``ProfileData`` does not expose ---------------
# An XLA operation's stats (its ``tf_op`` among them) sit on its event's
# metadata, not on the event.  The few fields needed are read off the
# protobuf wire format of ``tsl/profiler/protobuf/xplane.proto``: XSpace
# ``planes`` = 1; XPlane ``name`` = 2, ``event_metadata`` = 4 and
# ``stat_metadata`` = 5 (maps: key 1, value 2); XEventMetadata ``name`` = 2,
# ``stats`` = 5; XStat ``metadata_id`` = 1, ``str_value`` = 5, ``ref_value``
# = 7 (the id of a stat metadata whose name is the string);
# XStatMetadata ``name`` = 2.
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview of the bytes otherwise."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, value


def _map_value(entry):
    return next((v for n, v in _fields(entry) if n == 2), b"")


def scopes_of_metadata(data: bytes) -> dict[str, dict[str, str | None]]:
    """For each plane, each event name's ``repro.`` scope (from its
    ``STACK_STAT``); None where the name has none, or where two events of
    one name disagree."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name, stat_names, metas = "", {}, []
        for n, v in _fields(plane):
            if n == 2:
                name = bytes(v).decode()
            elif n == 4:
                metas.append(_map_value(v))
            elif n == 5:
                stat_id, stat_name = 0, ""
                for m, w in _fields(_map_value(v)):
                    if m == 1:
                        stat_id = w
                    elif m == 2:
                        stat_name = bytes(w).decode()
                stat_names[stat_id] = stat_name
        found: dict[str, set] = defaultdict(set)
        for meta in metas:
            ev_name, stack = "", None
            for n, v in _fields(meta):
                if n == 2:
                    ev_name = bytes(v).decode()
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == STACK_STAT:
                        stack = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7)))
            found[ev_name].add(scope_of(stack))
        out[name] = {n: (sc.pop() if len(sc) == 1 else None) for n, sc in found.items()}
    return out


def trace_dir(cell: str, chip_dir: Path = HERE) -> Path:
    """Where a traced run of ``cell`` left its trace: ``Bench.trace_dir``."""
    return Path(chip_dir) / ".traces" / cell


def for_ctx(ctx, chip_dir: Path) -> Scopes | None:
    """The run's scopes and spans cut to its window, or None where the run
    was not traced or its trace cannot be found.  Raises where the window
    holds other operations than the run's own reduction counted."""
    if ctx.window is None or not ctx.products:
        return None
    try:
        path = trace.xplane_file(trace_dir(ctx.cell, chip_dir))
    except FileNotFoundError:
        return None
    got = load(path).window(ctx.window.lo, ctx.window.hi)
    for dev in got.ops:
        if dev in ctx.window.ops and got.count(dev) != len(ctx.window.ops[dev]):
            raise ValueError(
                f"{dev}: {got.count(dev)} operations in the window here, "
                f"{len(ctx.window.ops[dev])} in the run's reduction")
    return got


def device_ms(ctx, scopes, chip_dir: Path) -> float | None:
    """Milliseconds per product of the device operations under ``scopes``,
    the union per device, mean over devices; None where no operation of
    the window carries a program scope, or none of these."""
    got = for_ctx(ctx, chip_dir)
    if got is None or not got.ops or not got.scoped():
        return None
    ns = trace.mean(got.scope_ns(scopes))
    return ns / 1e6 / ctx.products if ns else None


def host_ms(ctx, span: str, chip_dir: Path) -> float | None:
    """Milliseconds per product in the program's host span ``span``; None
    where the program has no such span."""
    got = for_ctx(ctx, chip_dir)
    if got is None:
        return None
    ns = got.span_ns(span)
    return ns / 1e6 / ctx.products if ns else None


def summary(path) -> dict:
    """One traced run in milliseconds per product: each scope's device
    time, the share of the device's busy time under some program scope,
    the largest unscoped operations, each host span, and the device's idle
    time by span.  Products are the ``bench.step`` spans in the window."""
    whole = load(path)
    (lo, hi), = [(s, e) for n, s, e in whole.spans if n == WINDOW_SPAN]
    got = whole.window(lo, hi)
    products = got.span_count("bench.step")
    per = lambda ns: ns / 1e6 / products
    busy = trace.mean(got.busy_ns())
    scoped = trace.mean(got.scope_ns([sc for sc in got.all_scopes() if sc is not None]))
    return {
        "products": products,
        "window_ms": per(hi - lo),
        "busy_ms": per(busy),
        "scopes_ms": {sc or UNSCOPED: per(trace.mean(got.scope_ns([sc])))
                      for sc in got.all_scopes()},
        "scoped_share": scoped / busy if busy else None,
        "unscoped_top_ms": [[n, per(ns)] for n, ns in got.top_ops(None)],
        "spans_ms": {n: per(got.span_ns(n)) for n in sorted({n for n, _, _ in got.spans})},
        "idle_ms": {n: per(ns) for n, ns in sorted(got.idle_by_program_span(lo, hi).items(),
                                                    key=lambda kv: -kv[1])},
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    print(json.dumps(summary(trace.xplane_file(trace_dir(argv[0]))), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
