"""Is a cell's step program still its parent's? A digest of what is lowered.

    python tests/lowered_cells.py [--repo DIR] [--texts DIR] [--check] [cell ...]

lowers the train step of every cell of ``BENCHMARK.json`` (or of those
named) for a described v5e, by the cell's own files and nothing compiled,
and prints ``cell sha256[:16]`` of the StableHLO with every location
stripped: the text's own and those inside the Mosaic calls' bodies (base64
MLIR bytecode that carries file names and line numbers, decoded and printed
without them). Run it on an unpacked parent (``git archive <commit> | tar -x
-C DIR``, then ``--repo DIR``) and on the tree, and ``diff`` the two
outputs: a refactor that means to change no program changes no line
(``--texts`` keeps the texts, for the diff when one does; ``--check`` holds
the cells to ``PINNED``, the digests PR 61 and PR 63 found on their parents). About 90 s
for the fourteen cells; needs libtpu, no chip.
"""

import argparse
import base64
import hashlib
import importlib
import json
import os
import re
import sys

# kind of cell -> (config module of the benchmark, its function, family)
KINDS = {
    "train": ("model", "llama_config", "llama"),
    "train_moe": ("model_moe", "moe_config", "moe"),
    "train_hybrid": ("model_granite", "hybrid_config", "hybrid"),
    "train_latent": ("model_glm", "latent_config", "latent"),
    "train_mixed": ("model_mellum", "moe_config", "moe"),
    "train_parallel": ("model_commanda", "moe_config", "moe"),
    "train_sparse": ("model_glm52", "latent_config", "latent"),
    "train_alternating": ("model_nemotron", "hybrid_config", "hybrid"),
    "train_blockset": ("model_sala", "sala_config", "sala"),
    "train_shortconv": ("model_lfm2", "hybrid_config", "hybrid"),
    "train_kda": ("model_ling", "ling_config", "ling"),
    "train_solar": ("model_solar", "solar_config", "solar"),
    "train_falconh1": ("model_falconh1", "falcon_config", "falcon"),
}
V5E_LIMIT = 16_909_336_064        # what a v5e chip states (here none does)
# the digests of the twelve cells PR 61 found, taken on its PARENT (77455d7,
# jax PINNED_FROM) and equal on its tree: PR 61 added a second cut to
# ``ops/delta_rule.py``, a mixer that may report and a gate's leaf to
# ``llama._layer`` and ``_attention_half``, and left every program alone.
# A cell's text names its functions by what the process lowered before it,
# so these are the digests of ONE run over every cell in the manifest's order
# (``--check``, which takes no cell names, holds such a run to them); a cell
# lowered alone reads otherwise (``@_where_2099``, ``@closed_call_1357``: the
# numbers count what was traced before). The bounded cut's own program is
# held by tests/test_delta_rule.py (a jaxpr's digest at a tiny shape). A PR
# that MEANS to change a cell's program replaces its digest, and says so.
# PR 64 replaced the eight cells' whose layers turn tables through
# ``llama.apply_rope`` (one product with a signed swap where lanes were split
# and joined: l8, four chips, OLMoE, Mellum2, Command A+, MiniCPM-SALA, LFM2,
# Falcon-H1); the six that turn none or turn them inside their own
# projections (Granite, Nemotron, Solar, the GLM cells, Ling) kept theirs.
# The Mellum2 cell's is its step with q left to the replay in its three full
# layers (``remat.LEFT_BY_ONE_AMONG_STACKS``, the same PR: no other cell's
# plan has a run of one layer among stacks that has room for its q)
PINNED_FROM = "0.9.0"
PINNED = {
    "train-deepseek7b-l8": "46227b7e9f0a4a2b",
    "train-deepseek7b-fsdp2tp2": "3febe514848b1e40",
    "train-olmoe1b7b-s4096-b4": "32f89764f69ea8d4",
    "train-granite4hs-ep8-s8192-b2": "04f181234a94ae93",
    "train-glm47flash-ep8-s8192-b2": "a968ebda9000750d",
    "train-mellum2-ep4-s16384-b1": "d1070b522e0041cd",
    "train-commandaplus-ep16-s8192-b1": "a68cf63e6a72f622",
    "train-glm52-ep32-s16384-b1": "ca4de7c508591ea3",
    "train-nemotron3nano-ep8-s8192-b2": "05378a4b5d8d9ebc",
    "train-minicpmsala-l4-s16384-b1": "5429377a63fca4a6",
    "train-lfm2-ep4-s16384-b1": "2e7e7378c9879804",
    "train-ling3flash-ep32-s16384-b1": "c626e69b9c15ab7d",
    # PR 63's: the Solar cell's as its parent (9d3ba29) lowers it, equal on
    # its tree (a third layout in ``ops/ssd.py``, a third form of block in
    # ``llama._layer``, scopes inside ``hybrid.mixer_half``: every program
    # left alone), and the cell PR 63 added, as its tree lowers it
    # PR 66 replaced the Solar cell's: the delta rule's cut for a gate with
    # no bound (``ops/delta_rule.py`` ``_pair_blocks_free``,
    # ``_pair_grads_free``) makes its small levels on the vector unit and
    # streams the odd halves' rows alone; the thirteen others kept theirs
    # (the Ling cell calls the op with its bound and keeps the bounded cut)
    "train-solaropen2-ep32-s16384-b1": "6120360e671a58bd",
    "train-falconh1-l4-s16384-b1": "83c658e5ea66a12f",
}


def lowered(name, topo):
    """The StableHLO of the cell's train step, lowered for ``topo``'s
    devices with the rule leaves held out and the rule applied, as the
    cell's kind runs it."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark import resolve
    from ray_tpu.parallel import (MeshSpec, ShardingRules, build_mesh,
                                  train_step)

    cell = resolve.cell(name)
    recipe, mix = cell["train"], cell["mix"]
    module, make, family = KINDS[cell["kind"]]
    cfg = getattr(importlib.import_module(f"benchmark.{module}"), make)(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "gmm_impl", "ssd_impl", "kda_impl", "remat",
            "f32_logits")
            if k in recipe})
    fam = importlib.import_module(f"ray_tpu.models.{family}")
    mesh = build_mesh(MeshSpec(**recipe["mesh"]),
                      devices=topo.devices[:cell.get("chips", 1)])
    rules = getattr(ShardingRules, recipe["rules"])()
    opt, more = optax.adafactor(recipe["lr"]), {}
    if hasattr(fam, "RULE_LEAVES"):
        opt = train_step.hold_out(opt, fam.RULE_LEAVES)
        more = {"post_update": lambda p, aux: fam.post_update(p, aux, cfg)}

    def placed(shapes, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, shardings)

    init_fn, state_sh = train_step.make_train_state_init(
        lambda k: fam.init_params(k, cfg), opt, mesh, rules,
        fam.param_specs(cfg))
    state = placed(jax.eval_shape(init_fn, jax.random.PRNGKey(0)), state_sh)
    shape = {"tokens": jax.ShapeDtypeStruct(
        (mix["batch"], mix["seq"] + getattr(cfg, "n_mtp", 0) + 1), jnp.int32)}
    batch = placed(shape, train_step.batch_sharding(mesh, rules, shape))
    return train_step.make_train_step(
        lambda p, b: fam.loss_fn(p, b, cfg, mesh=mesh, rules=rules), opt,
        mesh, rules, state_sh, batch_shapes=shape, **more).lower(
            state, batch).as_text()


def without_locations(text):
    """``text`` with its ``loc(...)`` gone and every Mosaic call's body
    replaced by the digest of its module printed without debug info."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(match.group(2))) \
                .operation.get_asm(enable_debug_info=False)
        return match.group(1) + hashlib.sha256(asm.encode()).hexdigest() \
            + match.group(3)

    text = re.sub(r" loc\(.*?\)$", "", text, flags=re.M)
    return re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)', body, text)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--repo", default=os.path.join(os.path.dirname(
        os.path.abspath(__file__)), os.pardir))
    ap.add_argument("--texts", help="a directory to keep the texts in")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 where a cell's digest is not PINNED's")
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args()
    if args.check and args.cells:
        ap.error("--check holds a run over EVERY cell to PINNED")
    repo = os.path.abspath(args.repo)
    os.chdir(repo)
    sys.path.insert(0, repo)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from ray_tpu.parallel import train_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"         # the kernels' chip branch
    train_step.device_bytes_limit = lambda mesh: V5E_LIMIT
    with open("BENCHMARK.json") as f:
        cells = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    moved = []
    for name in cells:
        text = without_locations(lowered(name, topo))
        if args.texts:
            os.makedirs(args.texts, exist_ok=True)
            with open(os.path.join(args.texts, name + ".mlir"), "w") as f:
                f.write(text)
        digest = hashlib.sha256(text.encode()).hexdigest()[:16]
        print(name, digest, flush=True)
        if args.check and PINNED.get(name, digest) != digest:
            moved.append(name)
    if moved:
        sys.exit(f"not the pinned program (jax {PINNED_FROM}): {moved}")


if __name__ == "__main__":
    main()
