"""Where compiled XLA programs are kept between processes and runs.

JAX's persistent compilation cache is keyed by its path, so a directory
that moves never hits. One rule for every process of the program that
compiles (spawned workers, bench.py, chip_smoke.py): where
JAX_COMPILATION_CACHE_DIR is set it is used and code sets no other;
where it is not, the cache is one fixed directory at the root of the
checkout — never a temporary name, a pid or a time. Both knobs are
environment defaults, read by jax when it is imported, so this module
never imports jax and a parent that only spawns stays off the chip.
"""

from __future__ import annotations

import os
from typing import MutableMapping

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def env_defaults(env: MutableMapping[str, str] = os.environ) -> str:
    """Fill the cache settings into ``env`` where unset; returns the
    directory in force. Call before jax is imported in that process."""
    env.setdefault("JAX_COMPILATION_CACHE_DIR", DEFAULT_DIR)
    # jax's default keeps only programs that took >= 1 s to compile; the
    # step programs are far above either bar, the many small init and
    # decode programs of a server are not
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
    return env["JAX_COMPILATION_CACHE_DIR"]


def entry_count(path: str) -> int:
    """Number of cached programs under ``path`` (0 if it does not exist)."""
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
    except OSError:
        return 0
