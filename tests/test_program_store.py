"""The program store (ISSUE 62, ``ray_tpu/core/compile_cache.py``): a
train step's executable kept under a key of what MAKES it, so that a warm
process loads it and does not trace. On a two-matrix loss: what moves the
key and what does not, a round trip through two processes, every refusal
falling through to jax's own path with its reason, the size bound, two
writers of one key, and nothing at all where the cache is off."""

import json
import multiprocessing
import os
import subprocess
import sys
import threading
import time

import pytest

import program_store_toy as toy
from ray_tpu.core import compile_cache
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store(tmp_path):
    """jax's persistent cache, and so the program store, in a directory
    of this test's, keeping every program whatever it took."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_compilation_cache_max_size",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_enable_compilation_cache")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    yield str(tmp_path / "cache" / compile_cache.PROGRAMS)
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture
def said(monkeypatch):
    """Every ``program.store`` / ``xla.compile`` / plan instant raised."""
    seen = []
    monkeypatch.setattr(tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, dict(attrs or {}))))
    return seen


def _stores(seen, program="_step"):
    return [a for n, a in seen if n == "program.store"
            and a["program"] == program]


# ------------------------------------------------------------------- the key

def _other_module(tmp_path, monkeypatch, body: str):
    """A user's own module, imported from a directory that is no
    installed distribution; returns it."""
    import importlib

    (tmp_path / "users_model.py").write_text(body)
    monkeypatch.syspath_prepend(str(tmp_path))
    sys.modules.pop("users_model", None)
    importlib.invalidate_caches()
    return importlib.import_module("users_model")


USERS_MODEL = """
import jax.numpy as jnp
SCALE = 2.0

def loss(p, batch):
    y = jnp.tanh(batch["x"] @ p["a"]) @ p["b"] * SCALE
    return jnp.mean((y - batch["x"]) ** 2)
"""


@pytest.mark.parametrize("what,moves", [
    ("nothing", False), ("lr", True), ("config field", True),
    ("post_update", True), ("donate", True), ("batch shape", True),
    ("state dtype", True), ("mesh shape", True), ("source byte", True),
    ("version", True), ("XLA_FLAGS", True), ("device limit", True),
    ("matmul precision", True), ("a named distribution's version", True)])
def test_the_key_moves_with_what_makes_the_program(what, moves, tmp_path,
                                                   monkeypatch):
    """Everything that reaches the program reaches the key: a learning
    rate inside optax's nested functions, a field of the configuration a
    lambda closes over, the rule, donation, a shape, a dtype, the mesh, a
    byte of a user's module, a version, the compiler's flags, the
    device's limit the traced body reads, a ``with`` of jax's, and the
    version of whatever installed distribution the makers name (here
    cloudpickle, which ``versions()`` does not list: a loss's flax, chex
    or einops)."""
    import contextlib

    import jax

    from ray_tpu.parallel import train_step

    module = _other_module(tmp_path, monkeypatch, USERS_MODEL)
    knobs = {"loss": module.loss} if what == "source byte" else {}
    if what == "a named distribution's version":
        knobs = {"loss": _names_a_distribution()}

    def key(**more):
        _, step, state, batch = toy.build(**knobs, **more)
        return step.key(state, batch)

    base = key()
    context = contextlib.nullcontext()
    more = {"lr": {"lr": 2e-3}, "config field": {"scale": 0.5},
            "post_update": {"post": True}, "donate": {"donate": False},
            "batch shape": {"rows": 8}, "state dtype": {"dtype": "bfloat16"},
            "mesh shape": {"dp": 2}}.get(what, {})
    if what == "source byte":
        (tmp_path / "users_model.py").write_text(
            USERS_MODEL.replace("2.0", "3.0"))
    elif what == "version":
        was = compile_cache.versions()
        monkeypatch.setattr(compile_cache, "versions",
                            lambda: {**was, "optax": "0.0.1"})
    elif what == "XLA_FLAGS":
        monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                           + " --xla_cpu_enable_fast_math=true")
    elif what == "device limit":
        monkeypatch.setattr(train_step, "device_bytes_limit",
                            lambda mesh: 10 ** 15)
    elif what == "matmul precision":
        context = jax.default_matmul_precision("highest")
    elif what == "a named distribution's version":
        assert "cloudpickle" not in compile_cache.versions()
        was_version = compile_cache.importlib.metadata.version
        monkeypatch.setattr(
            compile_cache.importlib.metadata, "version",
            lambda name: "0.0.1" if name == "cloudpickle"
            else was_version(name))
        compile_cache._distribution_version.cache_clear()
    with context:
        assert (key(**more) != base) is moves
    assert len(base) == 64
    compile_cache._distribution_version.cache_clear()


def _names_a_distribution():
    """A loss that names, by reference, a function of an installed
    distribution whose version ``versions()`` does not list."""
    from cloudpickle import dumps

    def loss(p, batch):
        assert dumps is not None
        return (batch["x"] @ p["a"] @ p["b"]).sum()

    return loss


def test_an_installed_module_with_no_version_is_no_key(monkeypatch):
    """What names a module under site-packages that no distribution
    owns up to cannot say which code it is: no key, so no store."""
    import cloudpickle

    metadata = compile_cache.importlib.metadata

    def no_version(name):
        raise metadata.PackageNotFoundError(name)

    compile_cache._distribution_version.cache_clear()
    monkeypatch.setattr(metadata, "version", no_version)
    monkeypatch.setattr(metadata, "packages_distributions", lambda: {})
    with pytest.raises(compile_cache.NoKey, match="no version of .*cloudp"):
        compile_cache.program_key((cloudpickle.dumps,))
    monkeypatch.undo()
    compile_cache._distribution_version.cache_clear()
    assert compile_cache.distribution_versions(
        ["cloudpickle.cloudpickle", "json", "ray_tpu.core"]) == {
            "cloudpickle": metadata.version("cloudpickle")}


def _child(directory, cache, seed, ask) -> dict:
    """``program_store_toy.py`` in a process of its own, from a copy at
    ``directory`` beside a link to the package: another checkout."""
    os.makedirs(directory)
    os.symlink(os.path.join(ROOT, "ray_tpu"),
               os.path.join(directory, "ray_tpu"))
    script = os.path.join(directory, "program_store_toy.py")
    with open(toy.__file__) as src, open(script, "w") as dst:
        dst.write(src.read())
    env = {**os.environ, "PYTHONPATH": directory, "PYTHONHASHSEED": seed,
           "JAX_COMPILATION_CACHE_DIR": cache,
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    done = subprocess.run([sys.executable, script, json.dumps(ask)],
                          cwd=directory, env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _named(out, name, program=None):
    return [r for r in out["records"] if r["name"] == name and (
        program is None or program in r["attrs"].get("program", ""))]


def test_a_round_trip_through_two_processes(tmp_path):
    """The second process, from a checkout at another path and under
    another hash seed, makes the SAME key, loads both programs and
    traces neither; it says what the first said, and computes the same
    bits with the state donated."""
    cache = str(tmp_path / "cache")
    cold = _child(str(tmp_path / "one"), cache, "1", {"run": True})
    warm = _child(str(tmp_path / "two"), cache, "2", {"run": True})
    assert cold["key"] == warm["key"] and cold["pid"] != warm["pid"]
    for program in ("_step", "init_fn"):
        (miss,), (hit,) = [[r["attrs"] for r in _named(
            out, "program.store", program)] for out in (cold, warm)]
        assert (miss["hit"], miss["why"]) == (False, "absent")
        assert hit["hit"] is True and "why" not in hit
        assert miss["key"] == hit["key"] and len(hit["key"]) == 12
        assert miss["bytes"] == hit["bytes"] > 0
        # (optax's own `init_fn` is traced for its shapes in both)
        assert cold["traced"].count(program) \
            == warm["traced"].count(program) + 1
        assert not _named(warm, "xla.trace", program)
        assert not _named(warm, "xla.lower", program)
        load, = [r["attrs"] for r in _named(warm, "xla.compile",
                                            f"jit({program})")]
        assert load["cache"] == "hit"
        assert load["seconds"] == load["retrieval_s"] == hit["seconds"] > 0
    assert cold["key"].startswith(_named(cold, "program.store", "_step")[
        0]["attrs"]["key"])
    plans = [[r["attrs"] for r in _named(out, "toy.plan")]
             for out in (cold, warm)]
    assert plans[0] == plans[1] == [{"width": 16}]
    assert cold["result"] == warm["result"]
    assert cold["donated"] is warm["donated"] is True
    # a load counts as a load from jax's cache counts: one a program
    assert warm["compiles"] >= 2
    assert len(os.listdir(os.path.join(cache, compile_cache.PROGRAMS))) == 2


# ----------------------------------------------------- in one process: falls

def _run(**knobs):
    import jax

    init_fn, step, _, batch = toy.build(**knobs)
    state = init_fn(jax.random.PRNGKey(7))
    state, metrics = step(state, batch)
    return float(metrics["loss"])


def test_a_second_program_of_the_same_makers_loads_in_one_process(store,
                                                                  said):
    assert compile_cache.listen() is True
    first = _run()
    assert [a["hit"] for a in _stores(said)] == [False]
    assert _run() == first
    assert [a["hit"] for a in _stores(said)] == [False, True]
    assert [a for n, a in said if n == "toy.plan"] == [{"width": 16}] * 2
    assert [a["cache"] for n, a in said if n == "xla.compile"
            and a["program"] == "jit(_step)"] == ["miss", "hit"]
    assert len(os.listdir(store)) == 2              # init_fn and _step


def test_a_maker_with_no_stable_bytes_is_never_stored(store, said):
    """A loss that closes over a lock cannot be pickled: the step is
    jax.jit's own, nothing is written for it, and the reason is said."""
    lock = threading.Lock()

    def loss(p, batch):
        assert lock is not None
        return (batch["x"] @ p["a"] @ p["b"]).sum()

    _run(loss=loss)
    _run(loss=loss)
    refused = _stores(said)
    assert [a["hit"] for a in refused] == [False, False]
    assert all(a["why"].startswith("unfingerprintable: ") and "lock" in a[
        "why"] and a["bytes"] == 0 for a in refused)
    assert len(os.listdir(store)) == 1              # init_fn alone


def test_an_entry_that_does_not_load_is_deleted_and_made_again(store, said):
    first = _run()
    (key,) = [n for n in os.listdir(store) if n.startswith(
        _stores(said)[0]["key"])]
    with open(os.path.join(store, key), "rb") as f:
        whole = f.read()
    with open(os.path.join(store, key), "wb") as f:
        f.write(whole[:len(whole) // 2])            # truncated
    # (jax's own cache emptied too: what XLA:CPU loads from there it
    # cannot serialise again, and the store would say so and keep nothing)
    for name in os.listdir(os.path.dirname(store)):
        if name != compile_cache.PROGRAMS:
            os.remove(os.path.join(os.path.dirname(store), name))
    assert _run() == first
    again = _stores(said)[-1]
    assert (again["hit"], again["why"]) == (False, "unreadable")
    header, parts, _ = compile_cache._read_entry(os.path.join(store, key))
    assert header["program"] == "_step" and len(parts) == 2   # whole again
    assert _run() == first and _stores(said)[-1]["hit"] is True


def test_a_program_jax_will_not_serialise_is_a_miss_with_its_reason(
        store, said, monkeypatch):
    from jax.experimental import serialize_executable

    def refuses(compiled):
        raise NotImplementedError("serialize_executables with const_args")

    monkeypatch.setattr(serialize_executable, "serialize", refuses)
    first = _run()
    assert not os.path.exists(store)
    assert _stores(said)[-1]["why"] == (
        "unserialisable: NotImplementedError: serialize_executables with "
        "const_args")
    assert _run() == first


def test_what_jaxs_cache_loaded_on_the_cpu_is_not_stored_again(store, said):
    """XLA:CPU serialises an executable it LOADED without its kernels
    (what loads from that fails as it first runs): a step that jax's own
    cache served is left to jax's cache, and said."""
    first = _run()
    for name in os.listdir(store):
        os.remove(os.path.join(store, name))
    assert _run() == first
    assert _stores(said)[-1]["why"] == (
        "unserialisable: loaded by jax's cache, not compiled here")
    assert os.listdir(store) == [] and _run() == first


@pytest.mark.filterwarnings("ignore:Cache value for key")   # jax's own bound
def test_the_bound_evicts_the_least_recently_loaded(store, said, monkeypatch):
    """``JAX_COMPILATION_CACHE_MAX_SIZE`` bounds the program store as it
    bounds jax's cache: the entry loaded longest ago goes first, never
    one this process loaded or wrote itself, never a writer's temporary
    file. A program that cannot be kept leaves a few bytes that say so,
    and the next process does not serialise it to find out again."""
    import jax
    from jax.experimental import serialize_executable

    os.makedirs(store)
    for age, name in enumerate(("old", "loaded", "new", "writer.1.2.tmp")):
        with open(os.path.join(store, name), "wb") as f:
            f.write(b"x" * 1000)
        os.utime(os.path.join(store, name), (0, 1000.0 + age))
    os.utime(os.path.join(store, "old"), (0, 2000.0))    # loaded last
    os.utime(os.path.join(store, "writer.1.2.tmp"))      # being written
    assert compile_cache.write_entry(store, "fourth", b"y" * 1000, 4500) \
        == 1000
    assert sorted(os.listdir(store)) == [
        "fourth", "new", "old", "writer.1.2.tmp"]
    # alone over the bound; then beside what this process wrote ("fourth")
    assert compile_cache.write_entry(store, "huge", b"y" * 5000, 4500) \
        == -7000
    assert compile_cache.write_entry(store, "fifth", b"y" * 3000, 4500) \
        == -5000
    assert sorted(os.listdir(store)) == [
        "fourth", "new", "old", "writer.1.2.tmp"]
    # a temporary file nobody finished within the hour is rubbish
    os.utime(os.path.join(store, "writer.1.2.tmp"), (0, 1000.0))
    assert compile_cache.write_entry(store, "sixth", b"y" * 2000, 4500) \
        == 2000
    assert sorted(os.listdir(store)) == ["fourth", "old", "sixth"]
    # through the setting: a step program is larger than this bound
    jax.config.update("jax_compilation_cache_max_size", 4500)
    first = _run()
    refused = _stores(said)[-1]
    assert refused["bytes"] == 0 and refused["why"].startswith("too large: ")
    (key,) = [n for n in os.listdir(store) if n.startswith(refused["key"])]
    assert "needs" in compile_cache._read_entry(os.path.join(store, key))[0]

    def never(compiled):
        raise AssertionError("serialised again, to be refused again")

    real = serialize_executable.serialize
    monkeypatch.setattr(serialize_executable, "serialize", never)
    assert _run() == first
    assert _stores(said)[-1]["why"] == refused["why"]
    # ... and with room again the few bytes are no refusal
    monkeypatch.setattr(serialize_executable, "serialize", real)
    jax.config.update("jax_compilation_cache_max_size", 10 ** 9)
    for name in os.listdir(os.path.dirname(store)):     # (see above)
        if name != compile_cache.PROGRAMS:
            os.remove(os.path.join(os.path.dirname(store), name))
    assert _run() == first
    kept = _stores(said)[-1]
    assert (kept["why"], kept["bytes"] > 4500) == ("absent", True)


def test_a_directory_that_takes_no_write_is_said_and_harms_nothing(
        tmp_path, said):
    """jax's cache only warns where its directory is read-only or full;
    so does the store: the step runs, and ``why`` says ``unwritable``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / compile_cache.PROGRAMS).write_text("a file in the way")
    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        assert _run() == _run()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
    # (the second run's step came from jax's own cache: on the CPU the
    # store leaves that one alone before it tries to write)
    assert [a["why"].split(":")[0] for a in _stores(said)] == [
        "unwritable", "unserialisable"]
    assert _stores(said, "init_fn")[0]["why"].startswith(
        "unwritable: FileExistsError")


def test_an_entry_somebody_else_could_write_is_not_loaded(store, said):
    """What loads is unpickled, so only this user's own files do: an
    entry (or a directory) that others may write is left alone, said,
    and the program is jax's own."""
    first = _run()
    (key,) = [n for n in os.listdir(store) if n.startswith(
        _stores(said)[0]["key"])]
    assert os.stat(store).st_mode & 0o077 == 0
    os.chmod(os.path.join(store, key), 0o666)
    assert _run() == first
    assert _stores(said)[-1]["why"] == "untrusted: not this user's alone"
    assert os.path.exists(os.path.join(store, key))
    os.chmod(os.path.join(store, key), 0o600)
    assert _run() == first and _stores(said)[-1]["hit"] is True


def test_a_loop_of_calls_runs_the_executable_and_looks_nothing_up(
        store, said, monkeypatch):
    """After the first call with its arguments a step is the loaded
    executable's own call: no flatten, no signature, no key. Other
    arguments go the long way and come back."""
    import jax
    import jax.numpy as jnp

    init_fn, step, _, batch = toy.build()
    state = init_fn(jax.random.PRNGKey(7))
    state, _ = step(state, batch)
    looked = []
    was = compile_cache.said_of
    monkeypatch.setattr(compile_cache, "said_of",
                        lambda args: looked.append(1) or was(args))
    for _ in range(3):
        state, metrics = step(state, batch)
    assert looked == [] and int(metrics["step"]) == 4
    wide = {"x": jnp.ones((8, 8), jnp.float32)}
    state, metrics = step(state, wide)                # another program
    assert looked == [1] and int(metrics["step"]) == 5
    state, metrics = step(state, wide)
    assert looked == [1] and int(metrics["step"]) == 6
    # inside another trace the call is jax.jit's own, as it was
    shapes = jax.eval_shape(step, state, batch)
    assert shapes[1]["loss"].shape == ()


def _writes(store, letter, rounds):
    # (a bound under which the entry, this writer's bytes and the other
    # writer's temporary file just fit: nothing is deleted for room)
    for _ in range(rounds):
        assert compile_cache.write_entry(
            store, "key", letter * 200_000, 650_000) == 200_000


def test_two_processes_writing_one_key_leave_one_whole_entry(tmp_path):
    store = str(tmp_path / "programs")
    ctx = multiprocessing.get_context("spawn")
    writers = [ctx.Process(target=_writes, args=(store, letter, 40))
               for letter in (b"a", b"b")]
    for w in writers:
        w.start()
    wholes, deadline = 0, time.time() + 120
    while any(w.is_alive() for w in writers) and time.time() < deadline:
        try:
            with open(os.path.join(store, "key"), "rb") as f:
                body = f.read()
        except FileNotFoundError:
            continue
        assert body in (b"a" * 200_000, b"b" * 200_000)  # never half a file
        wholes += 1
    for w in writers:
        w.join(timeout=60)
        assert not w.is_alive() and w.exitcode == 0
    assert wholes > 0 and os.listdir(store) == ["key"]


def test_with_the_cache_off_the_step_is_jax_jits_own(no_persistent_cache,
                                                     tmp_path, said):
    """No directory, no store: ``.lower`` gives jax's own ``Lowered``,
    the call is ``jax.jit``'s, nothing is written, and it is said once."""
    import jax

    init_fn, step, shapes, batch = toy.build()
    assert compile_cache.store_dir() is None
    assert type(step.lower(shapes, batch)) is jax.stages.Lowered
    state = init_fn(jax.random.PRNGKey(7))
    for _ in range(2):
        state, metrics = step(state, batch)
    assert [(a["hit"], a["why"], a["bytes"]) for a in _stores(said)] == [
        (False, "cache off", 0)]
    assert "toy.plan" in [n for n, _ in said]        # it traced, as ever
