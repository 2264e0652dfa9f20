"""What every compile for a described TPU shares: fixtures, helpers, the rule.

``jax.experimental.topologies`` describes a v5e without a chip, and the
TPU's own compiler then accepts or refuses a program lowered for it. Such a
compile goes into the file of its family: ``tests/test_tpu_compile_dense.py``
(the dense model's steps, the engine's programs, every flash call; Granite
rides there), ``_latent.py`` (the GLM cells, MiniCPM-SALA: attention over
latents and sets), ``_moe.py`` (Mellum2, Command A+, the held experts'
walks, the grouped matmuls), ``_nemotron.py``, ``_lfm2.py`` and
``_falcon.py`` (a cell each).

What put each case where it is (PR 56; PERF.md 6). A whole-step compile
keeps about three cores busy, the driver runs six workers on eight cores,
and xdist's ``loadfile`` starts the files with the most tests first and
hands a worker its next file once two tests of the last are left. So the
suite's wall clock is its core-seconds over eight cores plus whatever
compiles are left to run side by side at the end, and the order is made
with the cases: the file with the most cases starts first and holds as
much compile as a file may (about 250 s of cases), the files of one cell
start last and hold one step each, a file puts its whole steps FIRST and
its cheap kernel cases after them. A new whole-step compile joins the file
of its family while that stays under about 250 s, else a file of its own;
a new kernel case goes where it raises the count of a file that should
start sooner.

Run together the files need ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the driver's
command sets it): without it only one process may load libtpu. The topology
and everything built from it come from module-scoped fixtures
(``tests/conftest.py`` registers them) that are never built at import and
never autouse, so every worker collects the same tests and a worker that
runs none of these files never loads the library. Compiles run in the
test's own process; nothing executes, so these say "the chip's compiler
accepts the program and it fits", never how fast or how right it is.

Kernels pick their chip branch from ``jax.default_backend()``, which is
"cpu" here: each test steers that with monkeypatch (``on_chip_branch``),
not a program option.
"""

import os

import pytest

V5E_HBM = 16e9


def describe(name: str):
    """A described (device-less) TPU topology, or the error that kept this
    process from describing one. Under ``ALLOW_MULTIPLE_LIBTPU_LOAD`` (the
    driver's command sets it) every process may load libtpu, so whatever
    goes wrong FAILS the test that asked. Only where the variable is unset
    and the error is the library's own lock (a developer running two of the
    files side by side by hand) is the test skipped."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name=name)
    except Exception as e:
        if "ALLOW_MULTIPLE_LIBTPU_LOAD" not in os.environ \
                and "lockfile" in str(e):
            pytest.skip(f"another process holds libtpu and "
                        f"ALLOW_MULTIPLE_LIBTPU_LOAD is not set: {e}")
        raise


@pytest.fixture(scope="module")
def topo():
    return describe("v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip (the next run warns and compiles
    again): keep the cache off around these compiles."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_chip_branch(monkeypatch, no_persistent_cache):
    """Make the kernels trace their TPU branch (Mosaic, not interpret /
    the jnp reference) although the default backend here is the CPU."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_shardings(shapes, shardings):
    import jax

    return jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                        shapes, shardings)


def _array_bytes(types: str) -> int:
    """Bytes of every array type named in ``types`` (one, or a tuple)."""
    import math
    import re

    item = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    return sum(item[t] * math.prod(int(d) for d in dims.split(",") if d)
               for t, dims in re.findall(r"\b(bf16|f32|s32|u32|pred)"
                                         r"\[([\d,]*)\]", types))


def _computations(text):
    """{name: [instruction lines]} of a compiled module's computations,
    the entry under ``ENTRY``."""
    import re

    comps, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", ln)
        if m:
            cur = comps["ENTRY" if m.group(1) else m.group(2)] = []
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln.strip())
    return comps


def _entry_ops(text):
    """[(opcode, result type, bytes read, bytes written, is a matmul)] of
    the entry computation of a compiled module. A fusion reads each
    operand once, one that its body only slices at the slices' size; a
    fusion whose body holds a convolution or whose kind is kOutput is a
    matmul fusion. Any other op is listed with its result's bytes both
    ways (a copy, a slice, a broadcast, a convert left outside every
    fusion is a pass over memory of its own)."""
    import re

    comps = _computations(text)
    ops = []
    for ln in comps["ENTRY"]:
        m = re.match(r"^(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", ln)
        if not m:
            continue
        result, opcode = m.groups()
        if opcode != "fusion":
            ops.append((opcode, result, _array_bytes(result),
                        _array_bytes(result), False))
            continue
        body = comps[re.search(r"calls=%?([\w.\-]+)", ln).group(1)]
        read = 0
        for b in body:
            pm = re.match(r"^%?([\w.\-]+) = (\S+) parameter\(", b)
            if not pm:
                continue
            users = [u.split(" = ", 1)[1] for u in body if re.search(
                rf"[(, ]%?{re.escape(pm.group(1))}[,)]",
                u.split(" = ", 1)[-1])]
            sliced = [u for u in users
                      if re.match(r"\S+ (dynamic-)?slice\(", u)]
            read += sum(_array_bytes(u.split(" ")[0]) for u in sliced) \
                if users and len(sliced) == len(users) \
                else _array_bytes(pm.group(2))
        matmul = "kind=kOutput" in ln or any(" convolution(" in b
                                             for b in body)
        ops.append(("fusion", result, read, _array_bytes(result), matmul))
    return ops


def _while_bodies(text):
    """The scheduled instructions of every ``while`` body of a compiled
    module: {computation name: [line, ...]}."""
    import re

    names = set(re.findall(r"body=%?([\w.\-]+)", text))
    bodies, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"^%?([\w.\-]+) \(.*\) -> .* \{$", ln)
        if m:
            cur = m.group(1) if m.group(1) in names else None
            if cur:
                bodies[cur] = []
        elif ln.startswith("}"):
            cur = None
        elif cur:
            bodies[cur].append(ln.strip())
    return bodies


# cell -> (config module of the benchmark, its function, family): the
# cells whose whole step is compiled here, from the cell's own files
_CELL_STEPS = {
    "train-glm52-ep32-s16384-b1": ("model_glm52", "latent_config", "latent"),
    "train-commandaplus-ep16-s8192-b1": ("model_commanda", "moe_config",
                                         "moe"),
    "train-granite4hs-ep8-s8192-b2": ("model_granite", "hybrid_config",
                                      "hybrid"),
    "train-mellum2-ep4-s16384-b1": ("model_mellum", "moe_config", "moe"),
    "train-nemotron3nano-ep8-s8192-b2": ("model_nemotron", "hybrid_config",
                                         "hybrid"),
    "train-minicpmsala-l4-s16384-b1": ("model_sala", "sala_config", "sala"),
    "train-lfm2-ep4-s16384-b1": ("model_lfm2", "hybrid_config", "hybrid"),
    "train-ling3flash-ep32-s16384-b1": ("model_ling", "ling_config", "ling"),
    "train-falconh1-l4-s16384-b1": ("model_falconh1", "falcon_config",
                                    "falcon"),
}


def _compile_cell_step(name, topo, monkeypatch):
    """A one-chip cell's train step by its recipe, compiled for a described
    v5e chip that states a v5e's limit, 16,909,336,064 (here no device
    states one):
    (compiled, plan bytes, the ``remat.plan`` instant's attributes)."""
    import importlib

    import jax
    import jax.numpy as jnp
    import optax

    from benchmark import resolve
    from ray_tpu.parallel import (MeshSpec, ShardingRules, build_mesh,
                                  train_step)
    from ray_tpu.util import tracing

    cell = resolve.cell(name)
    recipe, mix = cell["train"], cell["mix"]
    module, make, family = _CELL_STEPS[name]
    cfg = getattr(importlib.import_module(f"benchmark.{module}"), make)(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "gmm_impl", "ssd_impl", "kda_impl", "remat",
            "f32_logits")
            if k in recipe})
    fam = importlib.import_module(f"ray_tpu.models.{family}")
    said = []
    instant = tracing.instant
    monkeypatch.setattr(train_step, "device_bytes_limit",
                        lambda mesh: 16_909_336_064)
    monkeypatch.setattr(tracing, "instant", lambda n, attrs=None, **kw: (
        said.append((n, attrs)), instant(n, attrs, **kw))[1])
    mesh = build_mesh(MeshSpec(**recipe["mesh"]), devices=topo.devices[:1])
    rules, opt = getattr(ShardingRules, recipe["rules"])(), optax.adafactor(
        recipe["lr"])
    init_fn, state_sh = train_step.make_train_state_init(
        lambda k: fam.init_params(k, cfg), opt, mesh, rules,
        fam.param_specs(cfg))
    state = _with_shardings(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), state_sh)
    shape = {"tokens": jax.ShapeDtypeStruct(
        (mix["batch"], mix["seq"] + 1), jnp.int32)}
    batch = _with_shardings(shape,
                            train_step.batch_sharding(mesh, rules, shape))
    compiled = train_step.make_train_step(
        lambda p, b: fam.loss_fn(p, b, cfg, mesh=mesh, rules=rules), opt,
        mesh, rules, state_sh, batch_shapes=shape).lower(
            state, batch).compile()
    mem = compiled.memory_analysis()
    return compiled, int(
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes), [
            a for n, a in said if n == "remat.plan"]
