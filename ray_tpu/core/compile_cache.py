"""Where compiled XLA programs are kept between processes and runs.

JAX's persistent compilation cache is keyed by its path, so a directory
that moves never hits. One rule for every process of the program that
compiles (spawned workers, benchmark/run.py, chip_smoke.py): where
JAX_COMPILATION_CACHE_DIR is set it is used and code sets no other;
where it is not, the cache is one fixed directory at the root of the
checkout — never a temporary name, a pid or a time. Both knobs are
environment defaults, read by jax when it is imported, so this module
never imports jax and a parent that only spawns stays off the chip.

It also counts what the process compiles (`listen`, `compile_count`,
`compile_seconds`): the directory cannot, because jax never writes a
program that compiled faster than the bar below. And it says which
program each was and what the cache did for it (`xla.compile`), and how
long its Python trace and its lowering took (`xla.trace`, `xla.lower`):
kept with tracing off from `KEPT_S` up, for the job's timeline.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import MutableMapping

from ray_tpu.util import tracing

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


# jax times every program it builds for a backend under this event, a
# compile and a load from the persistent cache alike; a call with shapes
# it has seen raises none. (jax._src.dispatch.BACKEND_COMPILE_EVENT)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# ... and, before it, the Python trace of a jitted function and its
# lowering to MLIR, each with a start and an end on time.time()
_STAGE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower"}
# What the persistent cache did for a program, raised inside the compile
# event's stretch on the compiling thread (jax._src.compiler
# .compile_or_get_cached). `cache_misses` is raised only where the entry
# is WRITTEN, so a request that used the cache and did not hit is a miss.
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# eager one-op programs raise hundreds of each; a job's timeline keeps
# what took this long
KEPT_S = 0.05
_lock = threading.Lock()
_listening = False
_count = 0
_seconds = 0.0
_since_compile = threading.local()   # cache, retrieval_s of this thread


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _since_compile.cache = "hit"
    elif event == _CACHE_ASKED:
        _since_compile.cache = "miss"        # until a hit says otherwise


def _on_duration(event: str, seconds: float, fun_name: str = "",
                 **_kw) -> None:
    global _count, _seconds
    if event == _CACHE_RETRIEVAL:
        _since_compile.retrieval_s = float(seconds)
        return
    if event != _COMPILE_EVENT:
        return
    with _lock:
        _count += 1
        _seconds += seconds
    seen = _since_compile.__dict__
    tracing.instant("xla.compile", {
        "seconds": float(seconds), "program": str(fun_name),
        "cache": seen.pop("cache", "off"),
        "retrieval_s": seen.pop("retrieval_s", 0.0)},
        always=seconds >= KEPT_S)


def _on_time_span(event: str, start: float, end: float, fun_name: str = "",
                  **_kw) -> None:
    name = _STAGE_SPANS.get(event)
    if name is not None and end - start >= KEPT_S:
        tracing.emit_span(name, start, end - start,
                          {"program": str(fun_name)}, always=True)


def listen() -> bool:
    """Start counting this process's compiles, once; call where jax is
    known to be imported (session start, engine start). Does nothing, and
    says so, where it is not: this module imports no jax."""
    global _listening
    if _listening:
        return True
    if "jax" not in sys.modules:
        return False
    import jax.monitoring

    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_time_span_listener(_on_time_span)
            _listening = True
    return True


def compile_count() -> int:
    """Programs this process built for a backend since `listen()`."""
    return _count


def compile_seconds() -> float:
    return _seconds
