"""Every costly operation of the compiled executors sits under one program
scope.

The fine and monoC steps name their phases with ``jax.named_scope``
(``repro.scatter_values``, ``repro.expand_a``, ``repro.expand_b``,
``repro.local``, ``repro.reduce_c``), and a profiler trace names each
device operation by the ``op_name`` metadata the compiler keeps.  This
reads that metadata from the compiled HLO on the CPU: at p=1 in this
process, and at p=4 on four host devices in a child process (the device
count is fixed before jax is imported).

    python tests/test_named_scopes.py <model> <p>   # prints the child's JSON
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
#: the operations a trace's time is spent in
COSTLY = ("fusion", "gather", "scatter", "sort", "all-to-all")
SCOPE = re.compile(r"(?<![\w.])repro\.[A-Za-z_][\w.]*")
INSTR = re.compile(r"^\s*(?:ROOT )?%?(?P<name>\S+) = .*?\s(?P<op>[a-z][\w\-]*)\(")


def entry_ops(hlo: str) -> list[tuple[str, str, list[str]]]:
    """(opcode, name, distinct scopes of its op_name) of each instruction
    of the entry computation."""
    lines = hlo.splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    out = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        m = INSTR.match(line)
        if not m:
            continue
        md = re.search(r'op_name="([^"]*)"', line)
        scopes = sorted(set(SCOPE.findall(md.group(1)))) if md else []
        out.append((m["op"], m["name"], scopes))
    return out


def ops_by_scope(hlo: str, opcodes) -> dict[str, int]:
    """Instructions of ``opcodes`` in every computation (fused ones too),
    counted by the program scope of their ``op_name``."""
    out: dict[str, int] = {}
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if not m or m["op"] not in opcodes:
            continue
        md = re.search(r'op_name="([^"]*)"', line)
        for scope in set(SCOPE.findall(md.group(1))) if md else {""}:
            out[scope] = out.get(scope, 0) + 1
    return out


def compiled_ops(model: str, p: int) -> list[tuple[str, str, list[str]]]:
    """The entry computation of ``model``'s executor, planned for AMG n=6 at
    ``p`` devices and compiled through the front door."""
    import numpy as np

    import repro
    from repro.core.matrices import amg_instances

    inst, _ = amg_instances(6)
    exe = repro.plan(inst, p=p, model=model).compile(dtype=np.float32)
    return entry_ops(exe.runtime._compiled.as_text())


def costly_scopes(ops) -> dict[str, list[str]]:
    return {f"{op} {name}": scopes for op, name, scopes in ops if op in COSTLY}


def check(ops, want_scopes):
    costly = costly_scopes(ops)
    assert costly
    stray = {k: v for k, v in costly.items() if len(v) != 1}
    assert not stray, f"costly operations not under exactly one scope: {stray}"
    seen = {v[0] for v in costly.values()}
    assert seen == set(want_scopes), seen
    for key, (scope,) in costly.items():
        if key.startswith("all-to-all"):
            assert scope in ("repro.expand_a", "repro.expand_b", "repro.reduce_c"), key


def on_host_devices(model: str, p: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={p}",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, __file__, model, str(p)], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return [tuple(op) for op in json.loads(out.stdout.strip().splitlines()[-1])]


def test_entry_ops_reads_op_name_metadata():
    hlo = """HloModule m
%fused (p: f32[4]) -> f32[4] {
  %mul.9 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(run)/repro.expand_a/mul"}
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(run)/shard_map/repro.local/mul"}
  %copy.2 = f32[4]{0} copy(%fusion.1)
  ROOT %all-to-all.3 = f32[4]{0} all-to-all(%copy.2), metadata={op_name="jit(run)/repro.reduce_c/a;repro.reduce_c/b"}
}
"""
    assert entry_ops(hlo) == [
        ("parameter", "a", []),
        ("fusion", "fusion.1", ["repro.local"]),
        ("copy", "copy.2", []),
        ("all-to-all", "all-to-all.3", ["repro.reduce_c"]),
    ]


def test_fine_one_device_every_costly_op_is_scoped():
    # at p=1 the compiler drops the empty exchanges; the fold of arrivals
    # left is a copy-in under repro.reduce_c, and the owned tables are the
    # value vectors themselves, so nothing runs under repro.scatter_values
    ops = compiled_ops("fine", 1)
    check(ops, {"repro.local", "repro.reduce_c"})


@pytest.mark.parametrize("model, want", [
    ("fine", {"repro.scatter_values", "repro.expand_a", "repro.expand_b", "repro.local",
              "repro.reduce_c"}),
    ("monoC", {"repro.scatter_values", "repro.expand_a", "repro.expand_b", "repro.local"}),
])
def test_four_host_devices_every_costly_op_is_scoped(model, want):
    ops = on_host_devices(model, 4)
    check(ops, want)
    assert sum(op == "all-to-all" for op, _, _ in ops) == (3 if model == "fine" else 2)


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    print(json.dumps(compiled_ops(sys.argv[1], int(sys.argv[2]))))
