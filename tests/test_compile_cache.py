"""The persistent compilation cache lands where the entry points say.

Each case runs in a child process: jax reads ``JAX_COMPILATION_CACHE_DIR``
when it is imported, and the cache setting is process-global.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import jax
from repro.launch.compile_cache import enable_compile_cache
first, second = enable_compile_cache(), enable_compile_cache()
print(first, second, jax.config.jax_compilation_cache_dir, sep="\\n")
"""


def _probe(**env_overrides) -> list[str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout.strip().splitlines()[-3:]


def test_env_var_names_the_cache_dir(tmp_path):
    target = str(tmp_path / "cache")
    first, second, jax_dir = _probe(JAX_COMPILATION_CACHE_DIR=target)
    assert first == second == jax_dir == target


def test_default_cache_dir_is_fixed_in_checkout():
    first, second, jax_dir = _probe()
    assert first == second == jax_dir == str(ROOT / ".jax_cache")
