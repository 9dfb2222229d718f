"""Where JAX's persistent compilation cache lives for this program's
entry points (``chip_smoke.py``, ``bench/run.py``,
``python -m repro.launch.serve``).

A cached executable is keyed partly on the cache's path, so the path has to
stay put from one run to the next: it is never made from a temporary name,
a pid or the time. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache goes to ``.jax_cache``
at the root of the checkout (listed in ``.gitignore``). Library code and the
tests never call this: the cache is the entry point's choice.
"""
from __future__ import annotations

import os

import jax

#: the fixed in-checkout location used when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
