"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

- the cell (``workloads``) names a configuration and a traffic mix;
- the configuration is the JSON file its ``configs`` entry names, and it
  names its generator, ``generators/<generator>.py``, which builds the
  operand structures and the value pool;
- the traffic mix is ``traffic/<traffic>.json``, a file of parameters
  whose ``loop`` names the driver that reads them, ``loops/<loop>.py``:
  its ``warm(cell)`` runs in set-up and its ``drive(cell, seconds, seed)``
  runs the window and returns a ``WindowResult``;
- every metric, end to end or per layer, is read by ``metrics/<name>.py``,
  whose ``read(ctx)`` returns a number, or None where there is nothing to
  read.

So a later cell, mix or metric is new files and new entries, with no edit
here.  A product on the timed path is the library's value path for one
planned structure: ``CompiledSpGEMM.pack`` on the host, the compiled
executor step on the device, and ``owned_c_values`` back to C's canonical
order (``Cell.product``).
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import random
import shutil
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
BENCH_DIR = Path("benchmarks") / "chip"
WINDOW_SPAN = "bench.window"
#: session decisions that would make a run measure something else
REFUSED_EVENTS = ("engine_fallback", "model_downgrade", "store_error", "retry")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def load_module(path: Path):
    """Import one file as a module of its own (file names need not be
    Python identifiers, and a copy of the benchmark never shares them)."""
    name = "bench_" + hashlib.sha1(str(path).encode()).hexdigest()[:12]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Bench:
    """A checkout's benchmark: its manifest and the files it names."""

    root: Path
    manifest: dict

    @classmethod
    def at(cls, root) -> "Bench":
        root = Path(root).resolve()
        return cls(root, json.loads((root / "BENCHMARK.json").read_text()))

    @property
    def dir(self) -> Path:
        return self.root / BENCH_DIR

    def cell(self, name: str) -> dict:
        for cell in self.manifest["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for cfg in self.manifest["configs"]:
            if cfg["name"] == name:
                return json.loads((self.root / cfg["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def generator(self, name: str):
        return load_module(self.dir / "generators" / f"{name}.py")

    def loop(self, name: str):
        return load_module(self.dir / "loops" / f"{name}.py")

    def metrics(self, kind: str, cell: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
        return [
            m for m in self.manifest[kind]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py")

    def peaks(self, kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if kind not in table:
            raise KeyError(f"device kind {kind!r} is not in the peak table")
        return table[kind]

    # -- where a run keeps what it builds -------------------------------
    def program_hash(self) -> str:
        """Hash of the system under test's sources: a plan stored by one
        version of the program is never restored by another."""
        h = hashlib.sha256()
        src = self.root / "src" / "repro"
        for path in sorted(src.rglob("*.py")):
            h.update(str(path.relative_to(src)).encode() + b"\0")
            h.update(path.read_bytes() + b"\0")
        return h.hexdigest()[:16]

    def store_dir(self) -> Path:
        return self.dir / ".store" / self.program_hash()

    def compile_cache_dir(self) -> str:
        return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(self.dir / ".cache" / "jax")

    def trace_dir(self, cell: str) -> Path:
        return self.dir / ".traces" / cell


def use_program(root: Path) -> None:
    """Import the system under test from the checkout, never from elsewhere."""
    src = Path(root) / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise FileNotFoundError(f"the system under test is not at {src / 'repro'}")
    sys.path.insert(0, str(src))


def seed_int(seed: int) -> int:
    return int(seed) % (1 << 64)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def check_devices(chips: int, allow_cpu: bool = False):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU, found platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devices)}")
    return devices


def enable_compile_cache(bench: Bench) -> str:
    """JAX's persistent compilation cache at one fixed place, every program
    in it, so that only a checkout's first run of a cell compiles."""
    import jax

    path = bench.compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


_compiles = 0
_listening = False


def _on_event(event: str, duration: float, **_) -> None:
    global _compiles
    if event.endswith("backend_compile_duration"):
        _compiles += 1


def compile_count() -> int:
    """Backend compilations in this process since the first call (a
    ``jax.monitoring`` listener, registered once)."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True
    return _compiles


@dataclasses.dataclass
class Cell:
    """A cell set up and ready to measure."""

    name: str
    spec: dict  # the BENCHMARK.json workload entry
    config: dict
    traffic: dict
    generator: object  # the configuration's generator module
    loop: object  # the traffic mix's loop module
    a: object  # scipy CSR structures of the operands
    b: object
    pool: list  # value sets (A values, B values), float32, canonical order
    exe: object  # repro.api.CompiledSpGEMM
    plan: object  # its execution plan
    events: list  # the session's events in set-up

    def product(self, a_vals, b_vals) -> np.ndarray:
        """One product on the timed path: pack, device step, unpack."""
        from jax.profiler import TraceAnnotation

        from repro.distributed.spgemm_exec import owned_c_values

        with TraceAnnotation("bench.pack"):
            a, b = self.exe.pack(a_vals, b_vals)
        with TraceAnnotation("bench.step"):
            c = self.exe.runtime(a, b)
            c.block_until_ready()
        with TraceAnnotation("bench.unpack"):
            return owned_c_values(c, self.plan).reshape(-1)

    def sizes(self) -> dict:
        inst = self.exe.planned.instance
        return {
            "nnz_a": int(inst.a.nnz),
            "nnz_b": int(inst.b.nnz),
            "nnz_c": int(inst.c.nnz),
            "n_mult": int(inst.n_mult),
            "itemsize": np.dtype(self.config["dtype"]).itemsize,
        }

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        from repro.distributed import runtime

        self.exe = self.plan = None
        runtime.cache_clear()
        gc.collect()


def plan(bench: Bench, cfg: dict, a, b, log=print):
    """The session's entry for the structures, restored from the plan store.

    A checkout's first run plans and saves; it then restores what it saved
    in a session of its own, so that every run measures the plan as the
    store gives it back.  Returns the entry and the session events."""
    import repro
    from repro.sparse.structure import SparseStructure

    def entry():
        session = repro.session(
            p=cfg["p"], model=cfg["model"], eps=cfg["eps"], seed=cfg["plan_seed"],
            engine=cfg["engine"], store_dir=str(bench.store_dir()),
            dtype=np.dtype(cfg["dtype"]),
        )
        got = session.entry_for(SparseStructure.wrap(a), SparseStructure.wrap(b))
        return got, [e.kind for e in session.events]

    t = time.perf_counter()
    got, events = entry()
    if "saved" in events:
        del got
        gc.collect()
        got, again = entry()
        events += again
        if "restored" not in again:
            raise RuntimeError(f"the plan just saved did not restore: {again}")
    log(f"plan: {events} in {time.perf_counter() - t:.2f} s (store {bench.store_dir()})")
    refused = sorted(set(events) & set(REFUSED_EVENTS))
    if refused or got.model != cfg["model"]:
        raise RuntimeError(f"the session did not plan {cfg['model']!r} as configured: {events}")
    return got, events


def setup(bench: Bench, name: str, seed: int, log=print) -> Cell:
    """Build the structures, plan or restore, compile, build the value pool
    from ``seed`` and warm the path up.  Devices must have been checked."""
    spec = bench.cell(name)
    cfg = bench.config(spec["config"])
    traffic = bench.traffic(spec["traffic"])
    loop = bench.loop(traffic["loop"])
    gen = bench.generator(cfg["generator"])
    t = time.perf_counter()
    a, b = gen.structures(cfg)
    log(f"structures: A {a.shape} nnz {a.nnz}, B {b.shape} nnz {b.nnz} "
        f"({time.perf_counter() - t:.2f} s)")
    entry, events = plan(bench, cfg, a, b, log)
    pool = gen.value_pool(cfg, a, b, seed_int(seed), traffic["pool"])
    cell = Cell(name, spec, cfg, traffic, gen, loop, a, b, pool, entry.exe,
                entry.planned.execution_plan, events)
    t = time.perf_counter()
    loop.warm(cell)
    log(f"warm-up: {time.perf_counter() - t:.2f} s")
    return cell


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class WindowResult:
    seconds: float  # from the first product's start to the last one's end
    latencies: list[float]
    failed: int
    sample: list[tuple[int, np.ndarray]]  # (pool index, C values) drawn from the seed


class Reservoir:
    """The outputs that the check compares: ``keep`` of the window's
    products, each equally likely, drawn from the seed."""

    def __init__(self, keep: int, seed: int):
        self.keep = keep
        self.rng = random.Random(seed_int(seed))
        self.seen = 0
        self.sample: list[tuple[int, np.ndarray]] = []

    def offer(self, pool_index: int, out: np.ndarray) -> None:
        if len(self.sample) < self.keep:
            self.sample.append((pool_index, out))
        else:
            j = self.rng.randrange(self.seen + 1)
            if j < self.keep:
                self.sample[j] = (pool_index, out)
        self.seen += 1


def measure(cell: Cell, seconds: float, seed: int) -> WindowResult:
    """The window: the traffic mix's loop drives the cell for ``seconds``
    inside the ``bench.window`` span."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation(WINDOW_SPAN):
        return cell.loop.drive(cell, seconds, seed)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def references(cell: Cell, indices) -> dict:
    """scipy's product for each of the pool's value sets named."""
    from . import reference

    c_keys = reference.linear_keys(reference.symbolic(cell.a, cell.b))
    return {i: reference.Reference(cell.a, cell.b, c_keys, *cell.pool[i])
            for i in sorted(set(indices))}


def check(cell: Cell, sample) -> dict:
    """Compare every sampled output with scipy's product of its operands.
    Returns {name: (value, limit)} for each number compared."""
    refs = references(cell, [i for i, _ in sample])
    worst = max((refs[i].error(got) for i, got in sample), default=float("inf"))
    return {"max_err": (worst, cell.config["check"]["max_err"])}


# ---------------------------------------------------------------------------
# reading the metrics
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: str
    chips: int
    setup_s: float
    window_s: float
    latencies: list[float]
    sizes: dict
    peaks: dict | None = None
    window: object = None  # trace.Window of a traced run
    spans: list = dataclasses.field(default_factory=list)  # host spans in it

    @property
    def products(self) -> int:
        return len(self.latencies)


def read_metrics(bench: Bench, kind: str, ctx: Context) -> dict:
    out = {}
    for m in bench.metrics(kind, ctx.cell):
        value = bench.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(window) -> dict:
    """The device operations that took most time and the longest idle
    stretches by host span, each in seconds averaged over the devices."""
    from . import trace

    n = len(window.ops)
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    for dev in window.ops:
        for name, ns in trace.by_name(window.ops[dev]).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / n
        for name, ns in window.idle_by_span[dev].items():
            idle[name] = idle.get(name, 0.0) + ns / 1e9 / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(ops), "idle_gaps": top(idle)}


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(bench: Bench, name: str, seed: int, seconds: float, traced: bool,
        t0: float, allow_cpu: bool = False, log=None, device_lines=None) -> dict:
    """One run of a cell; returns the result line's object.  ``allow_cpu``
    and ``device_lines`` (which trace lines hold device work, the TPU's by
    default) let a test drive the run on the CPU."""
    import jax

    from . import trace

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = bench.cell(name)
    devices = check_devices(spec["chips"], allow_cpu)
    used = devices[: spec["chips"]]
    kind = devices[0].device_kind
    peaks = None if allow_cpu and devices[0].platform != "tpu" else bench.peaks(kind)
    log(f"compile cache: {enable_compile_cache(bench)}")
    compile_count()
    cell = setup(bench, name, seed, log)
    setup_s = time.perf_counter() - t0
    log(f"set-up: {setup_s:.3f} s")

    tdir = bench.trace_dir(name)
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    before = compile_count()
    win = measure(cell, seconds, seed)
    in_window = compile_count() - before
    if traced:
        jax.profiler.stop_trace()
    log(f"window: {len(win.latencies)} products in {win.seconds:.3f} s, "
        f"{win.failed} failed, {in_window} compilations inside the window")
    memory = peak_memory(used)
    sizes = cell.sizes()
    ctx = Context(name, spec["chips"], setup_s, win.seconds, win.latencies, sizes, peaks)
    result = {"correct": False, "attempted": len(win.latencies), "failed": win.failed}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory}
    if traced:
        recorded = trace.load(trace.xplane_file(tdir), device_lines or trace.tpu_lines)
        reduced = trace.reduce(recorded, WINDOW_SPAN)
        ctx.window = reduced
        ctx.spans = trace.clip(recorded.spans, reduced.lo, reduced.hi)
        device["busy_s"] = trace.mean(reduced.busy_ns) / 1e9
        device["window_s"] = reduced.seconds
        result["metrics"] = read_metrics(bench, "per_layer", ctx)
        result["breakdown"] = breakdown(reduced)
    else:
        result["metrics"] = read_metrics(bench, "end_to_end", ctx)
    result["device"] = device

    cell.release()
    t = time.perf_counter()
    checks = check(cell, win.sample)
    log(f"check: {len(win.sample)} sampled products against scipy in {time.perf_counter() - t:.2f} s")
    result["correct"] = win.failed == 0 and all(v <= lim for v, lim in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"{k}: {v!r} (limit {lim!r})")
    return result
