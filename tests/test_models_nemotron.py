"""Nemotron-H's block as Nemotron 3 Nano 30B-A3B has it (models/hybrid.py
with ``one_half``, ``mamba_groups``, ``expert_act`` "relu2", a biased
sigmoid router, an untied head) against ``reference_nemotron.py`` on seeded
weights at the CPU tests' size: values, one step's gradients leaf by leaf,
the bias after a step; every wrong model told from the right one under the
rehearsal cell's own limits; the expert shares add up to the uncut block;
adjacent blocks of a kind run as one stack and read the same."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hybrid, llama, moe, reference_nemotron, registry

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)


def tiny(**kw):
    return hybrid.PRESETS["tiny-nemotron"].replace(
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def ref_cfg(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def make(cfg, batch=2, seq=32, seed=0):
    """Seeded parameters with biases that matter, and tokens [B, S + 1]."""
    params = hybrid.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 5)

    def biased(stack):
        if "router_bias" not in stack:
            return stack
        return {**stack, "router_bias": 0.05 * jax.random.normal(
            key, stack["router_bias"].shape)}

    params["layers"] = [biased(run) for run in params["layers"]]
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq + 1), 0, cfg.vocab_size)
    return params, tokens


def program_nll(params, tokens, cfg):
    # ONE program a call: run eagerly the ten blocks are ten scans and
    # their eager ops, every one a loaded executable with memory mappings
    # of its own (tests/conftest.py ``few_mappings``)
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(lambda p, t: hybrid.forward_with_stats(
            p, t, cfg))(params, tokens[:, :-1])
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tokens[:, 1:, None], -1)[..., 0]
    b, s = nll.shape
    return nll, stats["experts"].reshape(-1, b, s, cfg.top_k), stats


def test_the_registry_knows_the_family_and_the_tree_is_the_models():
    cfg, mod = registry.get("nemotron_h", "tiny-nemotron")
    assert mod is hybrid and cfg.one_half and cfg.expert_act == "relu2"
    # no two adjacent blocks of a kind: a run, and a stack, a block
    assert hybrid.layer_runs(cfg) == [(k, 1) for k in (
        "mamba", "experts", "mamba", "attention", "experts") * 2]
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    run = params["layers"][:5]
    assert [sorted(s) for s in params["layers"][5:]] \
        == [sorted(s) for s in run]
    # one half a block: the three kinds' trees share nothing
    assert [sorted(s) for s in run] == [
        sorted(("mix_norm", "in_proj", "conv_w", "conv_b", "dt_bias",
                "a_log", "d_skip", "gate_norm", "out_proj")),
        sorted(("ffn_norm", "router", "router_bias", "we_up", "we_down",
                "ws_up", "ws_down"))] * 1 + [
        sorted(("mix_norm", "in_proj", "conv_w", "conv_b", "dt_bias",
                "a_log", "d_skip", "gate_norm", "out_proj")),
        sorted(("attn_norm", "wq", "wk", "wv", "wo")),
        sorted(("ffn_norm", "router", "router_bias", "we_up", "we_down",
                "ws_up", "ws_down"))]
    # the convolution over H P + 2 G N channels, the in-projection z | xBC | dt
    assert run[0]["conv_w"].shape == (1, 4, 64 + 2 * 2 * 16)
    assert run[0]["in_proj"].shape == (1, 48, 2 * 64 + 2 * 2 * 16 + 4)
    assert run[1]["we_up"].shape == (1, 2, 48, 24)       # 2 of 8 held
    assert params["lm_head"].shape == (48, 256)          # untied
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == hybrid.num_params(cfg)
    assert jax.tree.structure(hybrid.param_specs(cfg), is_leaf=lambda x:
                              isinstance(x, tuple)) \
        == jax.tree.structure(params)


def test_the_published_model_counts_31_6_billion():
    """The parameter tree at the published sizes is the model's: 23 mixer
    blocks of 38.74 M, 6 attention blocks of 23.40 M, 23 expert blocks of
    1,297.47 M, embedding and head of 352.3 M each: 31.58 B."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b-ep8.json")) as f:
        conf = json.load(f)
    from benchmark import model_nemotron

    pub = {**conf, **conf["published"], "deployment": {
        **conf["deployment"], "experts_held": 128}}
    cfg = model_nemotron.hybrid_config(pub)
    assert round(hybrid.num_params(cfg) / 1e9, 2) == 31.58
    held = model_nemotron.hybrid_config(conf)
    kinds = held.kinds
    assert (kinds.count("mamba"), kinds.count("experts"),
            kinds.count("attention")) == (9, 8, 3)
    assert round(hybrid.num_params(held) / 1e6, 1) == 1946.6
    # the cell's runs are of one kind: twenty blocks, twenty runs
    assert hybrid.layer_runs(held) == [(k, 1) for k in kinds]


def test_values_gradients_and_the_bias_against_the_reference():
    cfg = tiny()
    params, tokens = make(cfg)
    nll, routes, _ = program_nll(params, tokens, cfg)
    want, rec = reference_nemotron.token_losses(params, tokens, ref_cfg(cfg))
    np.testing.assert_allclose(nll, want, atol=2e-5)
    assert bool(jnp.all(jnp.sort(routes, -1) == jnp.sort(rec["experts"], -1)))
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: hybrid.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True)(params)
    (ref_loss, ref_aux), ref_grads = jax.value_and_grad(
        lambda p: reference_nemotron.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True)(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5
    assert abs(float(aux["moe_aux_loss"]) - float(ref_aux["aux"])) < 1e-5
    assert float(aux["moe_dropped"]) == 0
    flat = lambda t: jax.tree.leaves_with_path(t)   # noqa: E731
    for (path, g), (_, w) in zip(flat(grads), flat(ref_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-9
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    # no gradient reaches a bias; the rule moves it
    assert all(float(jnp.abs(lp["router_bias"]).max()) == 0
               for lp in reference_nemotron.blocks(grads)
               if "router_bias" in lp)
    moved, report = hybrid.post_update(params, aux, cfg)
    want = reference_nemotron.bias_update(
        reference_nemotron.biases(params), rec["counts"], ref_cfg(cfg))
    np.testing.assert_array_equal(reference_nemotron.biases(moved), want)
    assert "router_counts" not in report
    assert 0 < float(report["moe_bias_moved"]) <= 4 * cfg.n_experts


def test_adjacent_blocks_of_a_kind_are_one_stack_and_read_the_same():
    """``llama._forward``'s runs with one half a block: two adjacent
    mixers and two adjacent expert blocks are a scan of two each; values,
    the routers' counts in the layers' order, the gradients and the bias
    after a step are the reference's block by block."""
    cfg = tiny(n_layers=6, layer_types=(
        "mamba", "mamba", "experts", "experts", "attention", "experts"))
    assert hybrid.layer_runs(cfg) == [
        ("mamba", 2), ("experts", 2), ("attention", 1), ("experts", 1)]
    params, tokens = make(cfg)
    assert [jax.tree.leaves(run)[0].shape[0] for run in params["layers"]] \
        == [2, 2, 1, 1]
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: hybrid.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True)(params)
    (ref_loss, ref_aux), ref_grads = jax.value_and_grad(
        lambda p: reference_nemotron.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True)(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5
    _, rec = reference_nemotron.token_losses(params, tokens, ref_cfg(cfg))
    np.testing.assert_array_equal(aux["router_counts"], rec["counts"])
    for x, y in zip(reference_nemotron.blocks(grads),
                    reference_nemotron.blocks(ref_grads)):
        for k in x:
            np.testing.assert_allclose(
                x[k], y[k], rtol=2e-3, err_msg=k,
                atol=2e-3 * float(jnp.abs(y[k]).max()) + 1e-9)
    moved, _ = hybrid.post_update(params, aux, cfg)
    np.testing.assert_array_equal(
        reference_nemotron.biases(moved), reference_nemotron.bias_update(
            reference_nemotron.biases(params), rec["counts"], ref_cfg(cfg)))


def _fake_int8(w, axis):
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def _each(params, fn):
    return {**params, "layers": [fn(run) for run in params["layers"]]}


WRONG = ["as it is", "8-bit in- and out-projections", "8-bit expert weights",
         "one group in place of two", "the gated norm over all lanes",
         "silu in place of relu^2", "a gated three-matrix expert",
         "the scale 2.5 dropped", "the bias left out of the choice",
         "D left out"]


def wrong_model(cfg, params, how, monkeypatch):
    """``(config, parameters)`` of a program that is another model."""
    gn = cfg.mamba_groups * cfg.mamba_state
    inner = cfg.mamba_inner
    if how == "8-bit in- and out-projections":
        return cfg, _each(params, lambda s: {
            **s, "in_proj": _fake_int8(s["in_proj"], 1),
            "out_proj": _fake_int8(s["out_proj"], 1)}
            if "in_proj" in s else s)
    if how == "8-bit expert weights":
        return cfg, _each(params, lambda s: {
            **s, "we_up": _fake_int8(s["we_up"], 2),
            "we_down": _fake_int8(s["we_down"], 2)} if "we_up" in s else s)
    if how == "one group in place of two":
        # B and C of group 0 for every head: group 0's columns of the
        # projection and of the convolution stand in every group's place
        n = cfg.mamba_state

        def first(w, at):       # [..., channels]: at = where B | C start
            out = w
            for side in (0, 1):
                lo = at + side * gn
                for g in range(1, cfg.mamba_groups):
                    out = out.at[..., lo + g * n:lo + (g + 1) * n].set(
                        w[..., lo:lo + n])
            return out

        return cfg, _each(params, lambda s: {
            **s, "in_proj": first(s["in_proj"], 2 * inner),
            "conv_w": first(s["conv_w"], inner),
            "conv_b": first(s["conv_b"], inner)} if "in_proj" in s else s)
    if how == "the gated norm over all lanes":
        monkeypatch.setattr(hybrid, "_group_mean", lambda a, groups:
                            jnp.mean(a, axis=-1, keepdims=True))
        return cfg, params
    if how == "silu in place of relu^2":
        monkeypatch.setattr(moe, "_activation", lambda cfg, product:
                            jax.nn.silu(product("up")))
        return cfg, params
    if how == "a gated three-matrix expert":
        return cfg.replace(expert_act="swiglu"), _each(params, lambda s: {
            **s, "we_gate": s["we_up"], "ws_gate": s["ws_up"]}
            if "we_up" in s else s)
    if how == "the scale 2.5 dropped":
        return cfg.replace(route_scale=1.0), params
    if how == "the bias left out of the choice":
        return cfg, _each(params, lambda s: {
            **s, "router_bias": jnp.zeros_like(s["router_bias"])}
            if "router_bias" in s else s)
    if how == "D left out":
        return cfg, _each(params, lambda s: {
            **s, "d_skip": jnp.zeros_like(s["d_skip"])}
            if "d_skip" in s else s)
    assert how == "as it is", how
    return cfg, params


@pytest.mark.parametrize("how", WRONG)
def test_a_wrong_model_is_refused_under_the_cells_own_limits(how,
                                                             monkeypatch):
    """What decides the rehearsal cell's ``correct`` (the share of routes
    that differ, the per-token losses on the program's routes) passes the
    program as it is and refuses each wrong model by at least one limit."""
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "rehearse-train-alternating.json")) as f:
        tol = json.load(f)["train"]["check"]
    cfg = tiny()
    params, tokens = make(cfg, seq=64)
    run_cfg, run_params = wrong_model(cfg, params, how, monkeypatch)
    nll, routes, _ = program_nll(run_params, tokens, run_cfg)
    want, rec = reference_nemotron.token_losses(params, tokens, ref_cfg(cfg),
                                                routes)
    differ = float(jnp.mean(jnp.any(
        jnp.sort(routes, -1) != jnp.sort(rec["experts"], -1), axis=-1)))
    err = jnp.abs(nll - want)
    read = {"route_differ_share": differ,
            "route_gap_max": float(rec["route_gap"].max()),
            "token_mean_abs": float(err.mean()),
            "token_p999_abs": float(jnp.percentile(err, 99.9))}
    over = [k for k, v in read.items() if v > tol[k]]
    assert bool(over) == (how != "as it is"), (how, read)


def test_the_shares_add_up_to_the_uncut_block():
    """On one expert block and one input: the four expert shares (2 of 8
    each) with the shared expert counted ONCE add up to what the reference
    gives for the uncut block."""
    cfg = tiny(experts_held=None)
    params = hybrid.init_params(jax.random.PRNGKey(3), cfg)
    lp = next(b for b in reference_nemotron.blocks(params) if "router" in b)
    lp = {**lp, "router_bias": 0.05 * jax.random.normal(
        jax.random.PRNGKey(4), lp["router_bias"].shape)}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, cfg.d_model))
    whole, _ = reference_nemotron.block(x[0], lp, ref_cfg(cfg))
    h = llama.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        shared = jnp.square(jax.nn.relu(h @ lp["ws_up"])) @ lp["ws_down"]
        total = x[0] + shared[0]
        for first in range(0, 8, 2):
            share = cfg.replace(experts_held=(2, first))
            mine = {**lp, "we_up": lp["we_up"][first:first + 2],
                    "we_down": lp["we_down"][first:first + 2]}
            y, stats = moe.feed_forward(h, mine, share)
            total = total + (y - shared)[0]
            assert int(stats["counts"].sum()) == 64 * cfg.top_k
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def test_the_cached_forwards_refuse_the_config():
    from ray_tpu.models import cached

    cfg = tiny()
    params, tokens = make(cfg, batch=1, seq=8)
    with pytest.raises(NotImplementedError):
        cached.init_cache(cfg, 1, 16)


def test_plan_instants_carry_the_groups_the_runs_and_the_experts(monkeypatch):
    """``hybrid.layer_plan`` lists the runs with their repeats,
    ``mixer.plan`` and ``ssd.plan`` the groups, ``moe.expert_plan`` what an
    expert is and how its grouped matmuls are tiled; each once a traced
    body."""
    from ray_tpu.util import tracing

    seen = []
    monkeypatch.setattr(tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, attrs)))
    cfg = tiny(ssd_impl="pallas", gmm_impl="pallas")
    params, tokens = make(cfg)
    jax.make_jaxpr(lambda p: hybrid.forward(p, tokens[:, :-1], cfg))(params)
    of = lambda n: [a for name, a in seen if name == n]      # noqa: E731
    assert of("hybrid.layer_plan") == [{
        "kinds": 3, "runs": 10, "bodies": 3, "layers": 10,
        "pattern": ", ".join(["mamba x1, experts x1, mamba x1, attention x1, "
                              "experts x1"] * 2)}]
    # ten runs, ONE trace of each kind's body
    (mixer,), (scan,), (experts,) = (of("mixer.plan"), of("ssd.plan"),
                                     of("moe.expert_plan"))
    assert (mixer["groups"], mixer["group_lanes"], mixer["chunk"],
            mixer["channels"]) == (2, 32, 8, 64 + 2 * 2 * 16)
    assert (scan["groups"], scan["heads_per_group"],
            scan["heads_per_block"]) == (2, 2, 2)
    assert experts == {
        "act": "relu2", "matrices": 2, "width": 24, "shared_width": 40,
        "padded_width": 0, "held": 2, "rows": 128, "path": "pallas",
        "gmm_up": "128x48x24", "tgmm_up": "128x48x24",
        "gmm_down": "128x24x48", "tgmm_down": "128x24x48",
        "route_form": "rounds", "route_rounds": cfg.top_k}
    # the cell's: 2,688 in three tiles of 896, 1,856 whole or 1,024 + 832
    wide = moe.expert_plan(cfg.replace(
        d_model=2688, d_ff=1856, n_experts=128, top_k=6,
        experts_held=(16, 0), dtype=jnp.bfloat16), 16384)
    assert (wide["rows"], wide["gmm_up"], wide["gmm_down"], wide["tgmm_up"],
            wide["tgmm_down"]) == (18432, "256x896x1856", "256x1856x896",
                                   "256x896x1024", "256x1024x896")
