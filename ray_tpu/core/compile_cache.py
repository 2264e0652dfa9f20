"""Where compiled XLA programs are kept between processes and runs.

TWO stores in ONE directory. JAX's persistent compilation cache is keyed
by the LOWERED module (and by its path, so a directory that moves never
hits): a warm process still traces and lowers every program in Python
before it can ask. The program store (`StoredProgram`, under
`<directory>/programs`; jax's eviction looks at the top level only) keeps
serialised executables under a key of what MAKES a program, made without
tracing, so a warm process loads its step program and does not trace it.
One rule for every process of the program that compiles (spawned workers,
benchmark/run.py, chip_smoke.py): where JAX_COMPILATION_CACHE_DIR is set
it is used and code sets no other; where it is not, the cache is one
fixed directory at the root of the checkout, never a temporary name, a
pid or a time. Both knobs are environment defaults, read by jax when it
is imported, so this module never imports jax as it is imported and a
parent that only spawns stays off the chip.

Both stores obey the same two settings and add none: a program is kept
only where making it took JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS,
and each holds at most JAX_COMPILATION_CACHE_MAX_SIZE bytes where that
is set, the least recently loaded going first (where it is not, jax's
cache has no bound and the program store `DEFAULT_MOST`: every edit of
the source orphans a whole set of its entries). The program store is on
exactly where jax's cache is on in that process. A step is then on disk
twice, once in each store. The store never evicts what the process
itself loaded or wrote (a job's two programs must not take turns), and a
program that cannot be kept under the bound leaves a few bytes in its
place that say so (`why` "too large"), so that the next process does not
serialise it again to find out.

A key that cannot be made is a miss. The program store's key
(`program_key`) digests the jitted function BY VALUE (its code and its
cells, so the loss, the optimizer with the learning rate inside its
nested functions, and the configuration a lambda closes over are in it
without a call site listing them) with jit's own arguments, what cannot
be pickled described (a mesh, a sharding, an array), the arguments'
shapes and placements, the source (every .py of this package, whichever
of them the makers name: a module imported inside a function's body is
seen by no pickle, and a miss costs one trace where a stale hit is a
wrong program; every other module the makers name that is no installed
distribution, with what those reach), the versions of python, jax,
jaxlib, libtpu, optax and numpy and of every installed distribution the
makers name (a loss's flax, chex or einops), the backend and jax's
configuration, and no path. A stale hit would be a WRONG program (an old
learning rate baked in), so whatever has no stable bytes or no version
means no store for that program, said once (`program.store`, `why`),
never a guess. What a key cannot see is a change made to a module's
attribute inside the process (a test's monkeypatch): such a test keeps
the cache off.

The store harms no job and trusts nobody else. A directory that takes no
write (read-only, full) is said (`why` "unwritable") as jax's cache
warns. An entry is one line of JSON and two parts; the parts ARE pickles
(jax's serialised executable is one, `jax.experimental
.serialize_executable` has no other form), so the store's directory is
made this user's alone (0700) and an entry that another user owns or
could write is not opened (`why` "untrusted"): where jax's cache never
runs what it reads, this one does, and so reads only its own.

It also counts what the process compiles (`listen`, `compile_count`,
`compile_seconds`): the directory cannot, because jax never writes a
program that compiled faster than the bar below. And it says which
program each was and what the cache did for it (`xla.compile`; a load
from the program store raises the same instant, `cache` "hit"), how
long its Python trace and its lowering took (`xla.trace`, `xla.lower`),
and what the program store did (`program.store`: hit, key, bytes,
seconds, why): kept with tracing off from `KEPT_S` up, for the job's
timeline.
"""

from __future__ import annotations

import dis
import functools
import hashlib
import importlib.metadata
import io
import json
import logging
import os
import pickle
import platform
import stat
import sys
import sysconfig
import threading
import time
import types
import zlib
from typing import Any, Callable, Dict, MutableMapping, Optional, Tuple

import cloudpickle

from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

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
    """Number of programs in jax's cache under ``path`` (0 if it does not
    exist); the program store's directory beside them is none of them."""
    try:
        return sum(1 for n in os.listdir(path)
                   if not n.endswith("-atime") and n != PROGRAMS)
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
# cache, retrieval_s of the compile this thread is in; last: the cache's
# answer to the one it finished last
_since_compile = threading.local()


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
    seen["last"] = cache = seen.pop("cache", "off")
    tracing.instant("xla.compile", {
        "seconds": float(seconds), "program": str(fun_name),
        "cache": cache, "retrieval_s": seen.pop("retrieval_s", 0.0)},
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


def _count_load(seconds: float, program: str) -> None:
    """A load from the program store, counted and said as jax's listener
    counts and says a load from jax's cache, as the load ends."""
    global _count, _seconds
    with _lock:
        _count += 1
        _seconds += seconds
    tracing.instant("xla.compile", {
        "seconds": float(seconds), "program": program, "cache": "hit",
        "retrieval_s": float(seconds)}, always=seconds >= KEPT_S)


# --- the program store: a key made without tracing ---------------------------
PROGRAMS = "programs"
# jax's settings that reach the compiler and not the trace (what reaches
# the trace is `trace_context()`: x64, matmul precision, the PRNG, ...).
# By name: settings that a later import defines would move a key made
# after it.
_COMPILER_SETTINGS = (
    "jax_disable_most_optimizations", "jax_optimization_level",
    "jax_memory_fitting_level", "jax_exec_time_optimization_effort",
    "jax_memory_fitting_effort", "jax_use_shardy_partitioner",
    "jax_enable_pgle", "jax_xla_profile_version",
    "jax_compiler_enable_remat_pass", "jax_backend_target")


class NoKey(Exception):
    """What makes a program has no stable bytes: no store for it."""


def store_dir() -> Optional[str]:
    """Where this process keeps serialised programs; None where jax's
    persistent cache is off in it (no directory, or switched off)."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.config.jax_enable_compilation_cache:
        return None
    directory = jax.config.jax_compilation_cache_dir
    return os.path.join(directory, PROGRAMS) if directory else None


def versions() -> Dict[str, str]:
    """Of what turns the makers into a program, whoever names it."""
    said = {"python": platform.python_version()}
    for name in ("jax", "jaxlib", "libtpu", "optax", "numpy"):
        try:
            said[name] = importlib.metadata.version(name)
        except importlib.metadata.PackageNotFoundError:
            said[name] = ""
    return said


def _installed(module) -> bool:
    """Whether ``module`` came with the interpreter or a distribution
    (a version speaks for it), not from a checkout or a user's file."""
    file = getattr(module, "__file__", None)
    if not file:
        return True                              # built in: python's version
    paths = sysconfig.get_paths()
    return any(os.path.abspath(file).startswith(
        os.path.abspath(paths[k]) + os.sep)
        for k in ("stdlib", "platstdlib", "purelib", "platlib"))


@functools.lru_cache(maxsize=None)
def _distribution_version(top: str) -> str:
    """The version of the distribution that installed the top-level
    module ``top``: under its own name where there is one (optax, chex,
    flax), else whichever distribution lists it (yaml: PyYAML)."""
    try:
        return importlib.metadata.version(top)
    except importlib.metadata.PackageNotFoundError:
        pass
    said = sorted(
        f"{name} {importlib.metadata.version(name)}"
        for name in importlib.metadata.packages_distributions().get(top, ()))
    if not said:
        raise NoKey(f"no version of the installed module {top}")
    return ", ".join(said)


def distribution_versions(named) -> Dict[str, str]:
    """The version of every installed distribution among the modules
    ``named`` (what the pickle of the makers names by reference): an
    upgrade of a loss's or an optimizer's library moves the key as an
    edit of a checkout's file does. NoKey where none can be found."""
    said = {}
    for name in named:
        top, module = (name or "").split(".")[0], sys.modules.get(name or "")
        if (module is not None and top not in sys.stdlib_module_names
                and getattr(module, "__file__", None) and _installed(module)):
            said[top] = _distribution_version(top)
    return dict(sorted(said.items()))


def _by_reference(obj) -> bool:
    """Whether pickle names ``obj`` (a function or a class) by its module
    and qualified name: it is what an import of that module finds there."""
    module = sys.modules.get(getattr(obj, "__module__", None) or "")
    if module is None or module.__name__ == "__main__":
        return False
    found = module
    for part in getattr(obj, "__qualname__", "").split("."):
        found = getattr(found, part, None)
    return found is obj


def _described(*what):
    """The constructor of a description's reduce value; never called."""
    return what


def _global_names(code) -> set:
    names = {i.argval for i in dis.get_instructions(code)
             if i.opname in ("LOAD_GLOBAL", "STORE_GLOBAL", "DELETE_GLOBAL")}
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            names |= _global_names(const)
    return names


class _Fingerprinter(cloudpickle.Pickler):
    """cloudpickle's bytes of the makers, with every path left out (a
    code object's file, a function's module file) and what cannot be
    pickled described; ``modules`` collects what was named by reference."""

    def __init__(self, file):
        super().__init__(file, protocol=4)
        self.modules = set()

    def reducer_override(self, obj):
        if isinstance(obj, types.ModuleType):
            self.modules.add(obj.__name__)
            return _described, ("module", obj.__name__)
        if isinstance(obj, types.CodeType):
            return _described, (
                "code", obj.co_name, obj.co_code, obj.co_consts, obj.co_names,
                obj.co_varnames, obj.co_freevars, obj.co_cellvars,
                obj.co_argcount, obj.co_posonlyargcount,
                obj.co_kwonlyargcount, obj.co_flags)
        if isinstance(obj, (types.FunctionType, type)) and _by_reference(obj):
            self.modules.add(obj.__module__)
            return NotImplemented                  # pickle's own: by name
        if isinstance(obj, types.FunctionType):
            # its state after its identity, so that a function reached
            # again from its own cells is a reference and no loop
            return _described, ("function", obj.__module__,
                                obj.__qualname__), {
                "code": obj.__code__, "defaults": obj.__defaults__,
                "kwdefaults": obj.__kwdefaults__, "dict": obj.__dict__,
                "globals": {n: obj.__globals__[n]
                            for n in sorted(_global_names(obj.__code__))
                            if n in obj.__globals__},
                "cells": [_cell(c) for c in obj.__closure__ or ()]}
        if isinstance(obj, type) and (obj.__module__ == "__main__"
                                      or "<locals>" in obj.__qualname__):
            # a class of a script's own: cloudpickle gives it a random id
            return _described, ("class", obj.__module__,
                                obj.__qualname__), {
                "bases": obj.__bases__,
                "dict": {k: v for k, v in vars(obj).items()
                         if k not in ("__dict__", "__weakref__")}}
        said = _jax_said(obj)
        if said is not None:
            return _described, said
        return super().reducer_override(obj)


def _cell(cell):
    try:
        return cell.cell_contents
    except ValueError:
        return _described                          # an empty cell


def _jax_said(obj) -> Optional[tuple]:
    """A mesh, a sharding, a device or an array, described: their own
    pickles hold device handles or more bytes than a key needs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    import numpy as np

    if isinstance(obj, jax.sharding.Mesh):
        return ("mesh", obj.axis_names, obj.devices.shape,
                repr(obj.axis_types), list(obj.devices.flat))
    if isinstance(obj, jax.sharding.NamedSharding):
        return ("named_sharding", obj.mesh, repr(obj.spec), obj.memory_kind)
    if isinstance(obj, jax.Device):
        return ("device", obj.platform, obj.device_kind, obj.id,
                obj.process_index)
    if isinstance(obj, jax.core.Tracer):
        raise NoKey(f"a tracer ({type(obj).__name__})")
    if isinstance(obj, (jax.Array, np.ndarray)):
        host = np.ascontiguousarray(obj)
        if host.dtype == object:
            raise NoKey("an array of objects")
        return ("array", host.shape, str(host.dtype),
                hashlib.sha256(host.tobytes()).hexdigest())
    return None


def source_digest(named=()) -> str:
    """Every .py of this package (all of it, not what ``named`` reaches:
    its functions import one another inside their bodies, where no
    module's globals show it), and the file of every other module of
    ``named`` that is no installed distribution, with the modules its
    globals reach: a user's own model comes in here."""
    digest = hashlib.sha256()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = []
    for directory, subdirs, names in os.walk(root):
        subdirs[:] = [d for d in subdirs if d != "__pycache__"]
        files += [(os.path.relpath(os.path.join(directory, n), root),
                   os.path.join(directory, n))
                  for n in names if n.endswith(".py")]
    package = __name__.split(".")[0]
    todo, seen = list(named), set()
    while todo:
        name = todo.pop()
        module = sys.modules.get(name or "")
        if (module is None or name in seen or name.split(".")[0] == package
                or _installed(module)):
            continue
        seen.add(name)
        files.append((name, module.__file__))
        todo += [v.__name__ if isinstance(v, types.ModuleType)
                 else v.__module__ for v in list(vars(module).values())
                 if isinstance(v, (types.ModuleType, types.FunctionType,
                                   type))]
    for name, path in sorted(files):
        try:
            with open(path, "rb") as f:
                body = f.read()
        except OSError as e:
            raise NoKey(f"the source of {name}: {e}") from e
        digest.update(f"{name}\0{len(body)}\0".encode())
        digest.update(body)
    return digest.hexdigest()


def _environment() -> dict:
    """What turns the same makers into another program: versions, the
    backend, the processes, the compiler's flags, jax's configuration
    (its settings and what a `with` has set on this thread)."""
    import jax
    from jax._src import config as jax_config

    backend = jax.devices()[0].client
    return {
        "versions": versions(),
        "backend": (backend.platform, backend.platform_version,
                    jax.default_backend()),
        "processes": (jax.process_count(), jax.process_index()),
        "flags": {k: os.environ.get(k, "")
                  for k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")},
        "config": [(k, repr(jax.config.values.get(k)))
                   for k in _COMPILER_SETTINGS],
        "context": repr(jax_config.trace_context())}


def program_key(makers: Any, arguments: Any = ()) -> str:
    """The store's key for the program that ``makers`` make of
    ``arguments`` (plain text: `arguments_text`), here and now; NoKey
    where a maker has no stable bytes."""
    out = io.BytesIO()
    pickler = _Fingerprinter(out)
    try:
        pickler.dump((makers, repr(arguments), _environment()))
    except NoKey:
        raise
    except Exception as e:        # whatever pickle refuses, in its words
        raise NoKey(f"{type(e).__name__}: {e}"[:160]) from e
    digest = hashlib.sha256(out.getvalue())
    digest.update(source_digest(pickler.modules).encode())
    digest.update(repr(distribution_versions(pickler.modules)).encode())
    return digest.hexdigest()


def said_of(args) -> Optional[Tuple[Any, tuple]]:
    """The arguments as a program sees them: their tree, and each leaf's
    shape, dtype, weak type and placement (a committed array's or a
    shape's sharding; None: wherever jax puts it). None where a leaf is a
    tracer (the call lies inside another trace) or no array."""
    import jax

    leaves, tree = jax.tree.flatten(args)
    said = []
    for x in leaves:
        if isinstance(x, jax.core.Tracer):
            return None
        try:
            aval = jax.api_util.shaped_abstractify(x)
        except TypeError:
            return None
        sharding = getattr(x, "sharding", None)
        if isinstance(x, jax.Array) and not x.committed:
            sharding = None
        said.append((aval.shape, str(aval.dtype), bool(aval.weak_type),
                     sharding))
    return tree, tuple(said)


def _sharding_text(sharding) -> str:
    """A placement in words that two equal placements share, whichever
    objects hold them: a pickle would tell a shared object from a copy."""
    if sharding is None:
        return ""
    devices = [(d.platform, d.device_kind, d.id, d.process_index)
               for d in sharding._device_assignment]
    mesh = getattr(sharding, "mesh", None)
    return f"{sharding!r} {getattr(mesh, 'axis_types', '')} on {devices}"


def arguments_text(said: Tuple[Any, tuple]) -> list:
    """What `said_of` gave, as the key and a stored entry hold it."""
    tree, leaves = said
    return [str(tree)] + [
        f"{shape} {dtype} {'weak ' if weak else ''}{_sharding_text(sharding)}"
        for shape, dtype, weak, sharding in leaves]


def _compress(body: bytes) -> Tuple[str, bytes]:
    try:
        import zstandard
    except ImportError:
        return "zlib", zlib.compress(body, 1)
    # every core: the pass is most of what a cold run pays for the store
    return "zstandard", zstandard.ZstdCompressor(threads=-1).compress(body)


def _decompress(codec: str, body: bytes) -> bytes:
    if codec == "zlib":
        return zlib.decompress(body)
    import zstandard

    return zstandard.ZstdDecompressor().decompress(body)


# Where JAX_COMPILATION_CACHE_MAX_SIZE is unset jax's cache has no bound;
# the store, which every edit of the source orphans a whole set of, has
# this one.
DEFAULT_MOST = 2 << 30
_STALE_TMP_S = 3600.0
# entries this process loaded or wrote: never evicted to make room for
# another of its own (a job's two programs must not take turns)
_mine: set = set()


def bound() -> int:
    """The most bytes the store holds: jax's setting where set (0: keep
    nothing), else `DEFAULT_MOST`."""
    import jax

    most = jax.config.jax_compilation_cache_max_size
    return DEFAULT_MOST if most < 0 else most


def _own(directory: str) -> None:
    """Make ``directory``, this user's alone. What loads from it is
    unpickled (jax's serialised executable is a pickle), so nobody else
    may write there: `_trusted` holds every entry to that."""
    os.makedirs(directory, mode=0o700, exist_ok=True)
    st = os.stat(directory)
    if st.st_uid == os.geteuid() and st.st_mode & 0o022:
        os.chmod(directory, stat.S_IMODE(st.st_mode) & ~0o022)


def _trusted(st: os.stat_result) -> bool:
    return st.st_uid == os.geteuid() and not st.st_mode & 0o022


def _make_room(directory: str, room: int, most: int) -> int:
    """Delete the least recently loaded entries of ``directory``, none of
    this process's own and no writer's temporary file, until ``room``
    more bytes fit under ``most``. The bytes that have to fit together
    (``room`` and what is kept): over ``most`` where they never can."""
    held, kept = [], room
    for name in os.listdir(directory):
        path = os.path.join(directory, name)
        try:
            st = os.stat(path)
        except OSError:
            continue                    # another process deleted it
        if path in _mine or (name.endswith(".tmp") and
                             time.time() - st.st_mtime < _STALE_TMP_S):
            kept += st.st_size
        else:
            held.append((st.st_mtime, st.st_size, path))
    if kept > most:
        return kept
    total = kept + sum(size for _, size, _ in held)
    for _, size, path in sorted(held):
        if total <= most:
            break
        try:
            os.remove(path)
        except OSError:
            pass
        total -= size
    return kept


def write_entry(directory: str, key: str, body: bytes, most: int) -> int:
    """``body`` under ``key``, whole or not at all (a temporary name of
    this process's, then a rename: a reader never sees half a file, and
    of two writers one whole entry stays), after making room under
    ``most`` bytes. The bytes written, or minus the bytes that would have
    had to fit where ``body`` cannot be kept beside this process's other
    entries (or alone)."""
    _own(directory)
    needs = _make_room(directory, len(body), most)
    if needs > most:
        return -needs
    path = os.path.join(directory, key)
    temporary = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    with open(os.open(temporary, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                      0o600), "wb") as f:
        f.write(body)
    os.replace(temporary, path)
    _mine.add(path)
    return len(body)


def _entry(header: dict, *parts: bytes) -> bytes:
    """An entry as it lies on disk: one line of JSON (what it is, for
    which arguments, how long its parts are), then the parts."""
    header = {**header, "parts": [len(p) for p in parts]}
    return json.dumps(header).encode() + b"\n" + b"".join(parts)


def _read_entry(path: str) -> Tuple[dict, list, int]:
    """(header, parts, bytes) of the entry at ``path``; PermissionError
    where it is not this user's alone."""
    with open(path, "rb") as f:
        if not (_trusted(os.fstat(f.fileno()))
                and _trusted(os.stat(os.path.dirname(path)))):
            raise PermissionError("not this user's alone")
        body = f.read()
    end = body.index(b"\n")
    header, rest = json.loads(body[:end]), memoryview(body)[end + 1:]
    parts, at = [], 0
    for n in header["parts"]:
        parts.append(rest[at:at + n])
        at += n
    if at != len(rest):
        raise ValueError("truncated")
    return header, parts, len(body)


class StoredProgram:
    """``jax.jit(fun, **jit_kwargs)`` that looks for its executable in
    the program store before it is traced. Called, or lowered and
    compiled with nothing between (the two paths that end in an
    executable for this backend), it loads what an earlier process made
    of the same makers; on a miss it goes jax's way
    (`jit.lower(...).compile()`, through jax's own cache) and writes what
    came of it. Everything else (`.lower(...)` followed by `as_text`,
    `cost_analysis` or a compile with options, `.trace`, `.eval_shape`)
    is the `jax.jit` object's own, and so is every call where the store
    is off, the arguments are tracers, or a committed argument lies
    elsewhere than the program wants it (jit moves it; an executable
    refuses it).

    The makers are ``fun`` BY VALUE (its code and its cells: whatever it
    closes over is in the key, with nothing for a call site to list) and
    ``jit_kwargs``. ``observes``: called as the key is made, for what the
    traced body reads of the process (a device's memory limit).
    ``devices``: the devices the program runs on, in the mesh's order."""

    def __init__(self, fun: Callable, devices,
                 observes: Optional[Callable[[], Any]] = None, **jit_kwargs):
        import jax

        self._jit = jax.jit(fun, **jit_kwargs)
        self._name = fun.__name__
        self._makers = (fun, jit_kwargs)
        self._devices = list(devices)
        self._observes = observes
        self._attached: Optional[bool] = None  # devices of this backend's
        self._programs: Dict[Any, Any] = {}   # arguments -> Compiled | None
        self._plans: Dict[Any, list] = {}     # arguments -> plans of a trace
        self._said: set = set()
        # what the last call ran; the next tries it first, and its own
        # check of its arguments sends any others the long way
        self._fast: Optional[Callable] = None

    def __getattr__(self, attr):
        if attr == "_jit":                # not made yet (a copy, a pickle)
            raise AttributeError(attr)
        return getattr(self._jit, attr)

    def __call__(self, *args, **kwargs):
        fast = self._fast
        if fast is self._jit:             # no key can be made: jit's own
            return fast(*args, **kwargs)
        if fast is not None and not kwargs:
            try:
                return fast(*args)
            except (TypeError, ValueError):
                pass   # other shapes, a tracer, an argument placed elsewhere
        found = None if kwargs else self._look(args)
        if found is None:
            return self._jit(*args, **kwargs)
        sig = found[1]
        if sig not in self._programs:
            lowering = self._lowering(args, *found)
            if lowering is None:          # no key, whatever the arguments
                self._fast = self._jit
                return self._jit(*args)
            lowering.compile()            # remembers what it compiled
        self._fast = self._programs[sig]
        return (self._fast or self._jit)(*args)

    def lower(self, *args, **kwargs):
        found = None if kwargs else self._look(args)
        lowering = found and self._lowering(args, *found)
        if lowering is None:
            return self._jit.lower(*args, **kwargs)
        return lowering

    def _look(self, args) -> Optional[Tuple[str, Any]]:
        """(directory, arguments as said) where the store can serve this
        call; None where it is jax.jit's own."""
        directory = store_dir()
        if directory is None:
            self._refused("cache off")
            return None
        sig = said_of(args)
        if sig is None:
            return None                  # inside a trace: nothing to say
        if self._attached is None:
            import jax

            self._attached = set(self._devices) <= set(jax.devices())
        if not self._attached:
            # compiled for a described topology: nothing loads there
            self._refused("cache off: devices not attached")
            return None
        return directory, sig

    def key(self, *args) -> str:
        """The store's key for the program these arguments (arrays or
        shapes) would get; NoKey where none can be made."""
        sig = said_of(args)
        if sig is None:
            raise NoKey("arguments that are no arrays")
        return self._key_of(arguments_text(sig))

    def _key_of(self, arguments: list) -> str:
        return program_key(
            (self._makers, self._observes and self._observes()), arguments)

    def _lowering(self, args, directory: str, sig) -> Optional["_Lowering"]:
        arguments = arguments_text(sig)
        try:
            key = self._key_of(arguments)
        except NoKey as e:
            self._refused(f"unfingerprintable: {e}")
            return None
        return _Lowering(self, args, sig, arguments, directory, key)

    def _refused(self, why: str) -> None:
        if why not in self._said:
            self._said.add(why)
            self._say(False, "", 0, 0.0, why)

    def _say(self, hit: bool, key: str, size: int, seconds: float,
             why: str = "") -> None:
        attrs = {"program": self._name, "hit": hit, "key": key[:12],
                 "bytes": int(size), "seconds": float(seconds)}
        if why:
            attrs["why"] = why
        logger.info("program store: %s", attrs)
        tracing.instant("program.store", attrs, always=True)


def _placed_for(compiled, said: tuple) -> bool:
    """Whether every committed argument lies where ``compiled`` wants
    it: jax.jit would move one that does not, an executable refuses it."""
    import jax

    wants = jax.tree.leaves(compiled.input_shardings)
    return len(wants) == len(said) and all(
        sharding is None or sharding.is_equivalent_to(want, len(shape))
        for (shape, _, _, sharding), want in zip(said, wants))


class _Lowering:
    """What `StoredProgram.lower` gives where the store is on: `compile()`
    loads the stored executable, or compiles and stores it; anything else
    is asked of jax's own `Lowered`, made on a miss at once (a trace that
    refuses its arguments refuses them here, as `jit.lower` does) and on
    a hit only when something other than `compile()` is asked."""

    def __init__(self, program: StoredProgram, args, sig, arguments: list,
                 directory: str, key: str):
        self._program, self._args, self._sig = program, args, sig
        self._arguments = arguments       # `sig` as the key and an entry say
        self._directory, self._key = directory, key
        self._path = os.path.join(directory, key)
        self._lowered = None
        self._lower_s = 0.0
        # why nothing loads; what is no "absent" or "unreadable" also
        # means that nothing is written (the entry says so, or the
        # directory is not to be trusted)
        self._why = "absent"
        if not os.path.exists(self._path):
            self._lower()

    def _lower(self):
        if self._lowered is None:
            t0 = time.perf_counter()
            with tracing.plans_said() as said:
                self._lowered = self._program._jit.lower(*self._args)
            self._lower_s = time.perf_counter() - t0
            if said:        # a trace jax remembered says nothing again
                self._program._plans[self._sig] = said
        return self._lowered

    def __getattr__(self, attr):
        if attr in ("_lowered", "_program"):
            raise AttributeError(attr)
        return getattr(self._lower(), attr)

    def compile(self, compiler_options=None):
        if compiler_options is not None:
            return self._lower().compile(compiler_options)
        compiled = self._load() if self._lowered is None else None
        if compiled is None:
            listen()
            _since_compile.last = None
            t0 = time.perf_counter()
            compiled = self._lower().compile()
            # None: jax had it in memory, compiled or loaded who knows when
            self._save(compiled, self._lower_s + time.perf_counter() - t0,
                       made_here=_since_compile.last in ("miss", "off"))
        # a later call with these arguments runs it, where jit would not
        # have had to move one of them first
        self._program._programs[self._sig] = compiled if _placed_for(
            compiled, self._sig[1]) else None
        return compiled

    def _load(self):
        from jax.experimental import serialize_executable

        program = self._program
        t0 = time.perf_counter()
        try:
            header, parts, size = _read_entry(self._path)
            if "needs" in header:         # no program: why none was kept
                if header["needs"] > bound():
                    self._why = (f"too large: {header['needs']} bytes to "
                                 f"keep, the bound is {bound()}")
                return None
            if header["arguments"] != self._arguments:
                raise ValueError("made for other arguments")
            in_tree, out_tree, plans = pickle.loads(parts[0])
            compiled = serialize_executable.deserialize_and_load(
                _decompress(header["codec"], parts[1]), in_tree, out_tree,
                execution_devices=program._devices)
        except PermissionError as e:
            self._why = f"untrusted: {e}"
            return None
        except Exception as e:  # truncated, another libtpu, a lost race
            logger.warning("program store: entry %s of %s does not load "
                           "(%s: %s); deleted, the program is made again",
                           self._key[:12], program._name,
                           type(e).__name__, e)
            try:
                os.remove(self._path)
            except OSError:
                pass
            self._why = "unreadable"
            return None
        seconds = time.perf_counter() - t0
        try:
            os.utime(self._path)           # the most recently loaded
        except OSError:
            pass                           # evicted meanwhile
        _mine.add(self._path)
        for name, attrs in plans:
            tracing.plan(name, attrs)
        _count_load(seconds, f"jit({program._name})")
        program._say(True, self._key, size, seconds)
        return compiled

    def _save(self, compiled, made_s: float, made_here: bool) -> None:
        from jax.experimental import serialize_executable

        import jax

        program, size, why = self._program, 0, self._why
        t0 = time.perf_counter()
        if why not in ("absent", "unreadable"):
            pass                # said by the entry, or by who owns it
        elif not made_here and program._devices[0].platform == "cpu":
            # XLA:CPU serialises an executable it LOADED without its
            # kernels: what loads from that fails as it first runs
            why = "unserialisable: loaded by jax's cache, not compiled here"
        elif made_s >= jax.config.jax_persistent_cache_min_compile_time_secs:
            try:
                payload, in_tree, out_tree = serialize_executable.serialize(
                    compiled)
                codec, payload = _compress(payload)
                body = _entry(
                    {"program": program._name, "made_s": made_s,
                     "codec": codec, "arguments": self._arguments},
                    cloudpickle.dumps((in_tree, out_tree, program._plans.get(
                        self._sig, []))), payload)
            except Exception as e:   # jax's refusals and the runtime's
                why = f"unserialisable: {type(e).__name__}: {e}"[:200]
            else:
                why, size = self._write(body, why)
        program._say(False, self._key, size, time.perf_counter() - t0, why)

    def _write(self, body: bytes, why: str) -> Tuple[str, int]:
        """(why, bytes kept) of writing ``body``; where it cannot be kept
        under the bound, a few bytes that say so in its place, so that
        the next process does not serialise it to find out again."""
        most = bound()
        try:
            size = write_entry(self._directory, self._key, body, most)
            if size < 0:
                why = (f"too large: {-size} bytes to keep, the bound is "
                       f"{most}")
                write_entry(self._directory, self._key, _entry(
                    {"program": self._program._name, "needs": -size}), most)
        except OSError as e:         # read-only, full, a file in the way
            return f"unwritable: {type(e).__name__}: {e}"[:200], 0
        return why, max(size, 0)
