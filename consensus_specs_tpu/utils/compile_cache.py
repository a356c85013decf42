"""Where JAX's persistent compilation cache lives: decided from outside.

Every script that compiles (benchmark/run.py, chip_smoke.py, the tools/
smokes, tests/conftest.py) calls `configure()` once, before its first compile.
`JAX_COMPILATION_CACHE_DIR` set in the environment wins and nothing in
`jax.config` is touched (jax reads that variable itself at import);
otherwise the cache sits at the fixed `<checkout>/.cache/xla` — no temp
names, pids or times in the path, so a second process of the same
checkout always finds the first one's entries.
"""
from __future__ import annotations

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[2]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CONFIG_KEY = "jax_compilation_cache_dir"


def configure() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    cache_dir = _CHECKOUT / ".cache" / "xla"
    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update(CONFIG_KEY, str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return str(cache_dir)
