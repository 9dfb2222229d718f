"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache.

Each case runs in a child process, so the test process itself never turns
the cache on; the child only reads the configuration back and compiles
nothing.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
import jax
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, check=True,
                         timeout=120).stdout.split()
    want = str(tmp_path / "cache") if from_env else str(REPO / ".jax_cache")
    assert out == [want, want]
    if not from_env:
        ignored = (REPO / ".gitignore").read_text().splitlines()
        assert "/.jax_cache/" in ignored
