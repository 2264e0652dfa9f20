"""Every device op says which part of the model issued it (PR 36): the
step program's named scopes (PERF.md 3), read from the compiled program's
``op_name``s with the benchmark's own functions (``benchmark/op_scopes.py``)
at the rehearsal cells' toy sizes, one case a family and the dense model
also under the fake-device ``fsdp=2, tp=2`` mesh."""
import contextlib
import glob
import hashlib
import importlib
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark import op_scopes, resolve
from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh
from ray_tpu.parallel.train_step import (batch_sharding, hold_out,
                                         make_train_state_init,
                                         make_train_step)

# case: (rehearsal cell, the loader of its toy configuration, the family)
CASES = {"dense": ("rehearse-train", "model.llama_config", "llama"),
         "dense-fsdp2tp2": ("rehearse-train4", "model.llama_config", "llama"),
         "tiny-olmoe": ("rehearse-train-moe", "model_moe.moe_config", "moe"),
         "tiny-granite": ("rehearse-train-hybrid",
                          "model_granite.hybrid_config", "hybrid"),
         "tiny-glm": ("rehearse-train-latent", "model_glm.latent_config",
                      "latent"),
         "tiny-nemotron": ("rehearse-train-alternating",
                           "model_nemotron.hybrid_config", "hybrid")}
MIXERS = ("tiny-granite", "tiny-nemotron")    # families with a mixer half
PLANS = ("flash.fwd_plan", "flash.bwd_plan", "ssd.plan", "tp.overlap_plan")
_LINE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _step(case: str):
    """``(the cell's recipe, cfg, step, state, batch)``: the family's train
    step from shapes alone, at the rehearsal cell's sizes and options."""
    name, loader, family = CASES[case]
    cell = resolve.cell(name)
    recipe, mix = cell["train"], cell["mix"]
    loader, fn = loader.split(".")
    cfg = getattr(importlib.import_module(f"benchmark.{loader}"), fn)(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "gmm_impl", "ssd_impl", "remat", "f32_logits")
            if k in recipe})
    mod = importlib.import_module(f"ray_tpu.models.{family}")
    mesh = build_mesh(MeshSpec(**recipe["mesh"]),
                      devices=jax.devices()[:cell["chips"]])
    rules = getattr(ShardingRules, recipe["rules"])()
    opt, more = optax.adafactor(recipe["lr"]), {}
    if hasattr(mod, "post_update"):     # a family's rule and its leaves
        opt = hold_out(opt, mod.RULE_LEAVES)
        more = {"post_update": lambda p, aux: mod.post_update(p, aux, cfg)}
    init_fn, state_sh = make_train_state_init(
        lambda k: mod.init_params(k, cfg), opt, mesh, rules,
        mod.param_specs(cfg))
    place = lambda s, sh: jax.ShapeDtypeStruct(                 # noqa: E731
        s.shape, s.dtype, sharding=sh)
    state = jax.tree.map(place, jax.eval_shape(
        init_fn, jax.random.PRNGKey(0)), state_sh)
    ids = 2 if hasattr(mod, "further_losses") else 1
    shapes = {"tokens": jax.ShapeDtypeStruct(
        (mix["batch"], mix["seq"] + ids), jnp.int32)}
    batch = jax.tree.map(place, shapes, batch_sharding(mesh, rules, shapes))
    step = make_train_step(
        lambda p, b: mod.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        opt, mesh, rules, state_sh, batch_shapes=shapes, **more)
    return recipe, cfg, step, state, batch


def _plans(trace_dir: str) -> dict:
    """``{instant: [attributes, ...]}`` of the plan instants in a profile."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in PLANS:
                        out.setdefault(e.name, []).append(dict(e.stats))
    return out


def _digest(step, state, batch) -> str:
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(step)(
        state, batch)))
    # a set of mesh axes prints in the order of this process's hashes
    text = re.sub(r"\{[^{}]*\}", lambda m: "".join(sorted(m.group(0))), text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_matmul_and_no_kernel_call_outside_a_scope(
        case, tmp_path, monkeypatch, no_persistent_cache):
    # (the cache off: the plans are read from a profile around the
    # LOWERING, and a step the program store holds is lowered by nobody)
    recipe, cfg, step, state, batch = _step(case)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        lowered = step.lower(state, batch)
    finally:
        jax.profiler.stop_trace()
    plans = _plans(str(tmp_path))
    text = lowered.compile().as_text()

    paths, outside = [], []
    for ln in text.splitlines():
        m, name = _LINE.match(ln), _OP_NAME.search(ln)
        parts = op_scopes.elements(name.group(1) if name else "")
        if name:
            paths.append(parts)
        if m and m.group(1) in ("dot", "convolution", "custom-call",
                                "ragged-dot"):
            if op_scopes.bucket(parts) not in op_scopes.PARTS:
                outside.append(ln.strip()[:300])
    # every matmul, convolution and custom call lies in one part
    assert not outside, outside
    found = {op_scopes.bucket(p) for p in paths}
    want = {"embed", "attention", "feed_forward", "head_loss", "optimizer",
            "layer_loop"} | ({"mixer"} if case in MIXERS else set())
    assert want <= found, (want - found, found)
    assert ("mixer" in found) == (case in MIXERS)
    # the layer checkpoint's replay, the backward and the forward are told
    # apart, and each half is seen in all three
    assert recipe["remat"]
    passes = {(op_scopes.bucket(p), op_scopes.which_pass(p)) for p in paths}
    for half in ("attention", "feed_forward"):
        assert {(half, p) for p in ("forward", "replay", "backward")} \
            <= passes, passes
    assert ("optimizer", "none") in passes
    inside = {op_scopes.sub_scope(p, op_scopes.OPTIMIZER_SCOPES)
              for p in paths if op_scopes.bucket(p) == "optimizer"}
    assert inside == {None, "grad_norm"} | (
        {"rule"} if case in ("tiny-glm", "tiny-nemotron") else set()), inside
    if case in ("tiny-olmoe", "tiny-granite", "tiny-glm", "tiny-nemotron"):
        subs = {op_scopes.sub_scope(p) for p in paths
                if op_scopes.bucket(p) == "feed_forward"}
        shared = {"shared"} if getattr(cfg, "shared_d_ff", 0) else set()
        assert {"router", "dispatch", "experts", "combine"} | shared \
            <= subs, subs
    if case == "tiny-glm":      # the module's parts, in the model's scopes
        mtp = {op_scopes.bucket(p) for p in paths if "mtp" in p}
        assert {"embed", "attention", "feed_forward", "head_loss",
                "layer_loop"} <= mtp, mtp

    # a kernel call's scope carries the path its plan instant reported
    kernels = {op_scopes.kernel_scope(p) for p in paths} - {None}
    want = set()
    for a in plans.get("flash.fwd_plan", []):
        want.add(f"flash.fwd.{a['path']}")
    for a in plans.get("flash.bwd_plan", []):
        want |= {f"flash.dq.{a['dq_path']}", f"flash.dkdv.{a['path']}"}
    for a in plans.get("ssd.plan", []):
        want |= {f"ssd.fwd.{a['path']}", f"ssd.bwd.{a['path']}"}
    tp = {a["path"] for a in plans.get("tp.overlap_plan", [])}
    assert ("overlap" in tp) == (case == "dense-fsdp2tp2"), tp
    want |= {"tp.overlap"} if "overlap" in tp else set()
    # and where weights' gradients travel by the helpers' own permutes,
    # those stand under a scope of their own
    want |= {"tp.gradient"} if any(
        a["grad_sites"] for a in plans.get("tp.overlap_plan", [])) else set()
    if hasattr(cfg, "gmm_impl"):
        want |= {f"gmm.{cfg.gmm_impl}", f"tgmm.{cfg.gmm_impl}"}
    assert want and kernels == want, (kernels, want, plans)

    # metadata and nothing else: the jaxpr is the one without scopes
    with_scopes = _digest(step, state, batch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, _, bare, state, batch = _step(case)
    bare_text = bare.lower(state, batch).as_text(debug_info=True)
    assert "head_loss" not in bare_text and "flash.fwd." not in bare_text
    assert _digest(bare, state, batch) == with_scopes
