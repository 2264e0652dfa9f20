"""LFM2's block as LFM2-8B-A1B has it (models/hybrid.py with "conv" layers,
``n_dense`` leading dense layers, ``qk_head_norm``, a biased sigmoid router,
no shared expert, a tied head) against ``reference_lfm2.py`` on seeded
weights at the CPU tests' size: values, one step's gradients leaf by leaf,
the bias after a step; every wrong model told from the right one under the
rehearsal cell's own limits; the expert shares add up to the uncut layer;
adjacent layers of a kind run as one stack and read the same; the flash
kernels at a head of 64."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hybrid, llama, reference_lfm2, registry, remat

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
CONV = ("mix_norm", "in_proj", "conv_w", "out_proj")
ATTENTION = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
EXPERTS = ("ffn_norm", "router", "router_bias", "we_gate", "we_up", "we_down")
DENSE = ("ffn_norm", "w_gate", "w_up", "w_down")


def tiny(**kw):
    return hybrid.PRESETS["tiny-lfm2"].replace(
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def ref_cfg(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def make(cfg, batch=2, seq=32, seed=0):
    """Seeded parameters with biases that matter, norms off 1, and tokens
    [B, S + 1]."""
    params = hybrid.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 5)

    def moved(stack):
        out = dict(stack)
        if "router_bias" in stack:
            out["router_bias"] = 0.05 * jax.random.normal(
                key, stack["router_bias"].shape)
        for name in ("q_norm", "k_norm"):
            if name in stack:
                out[name] = 1.0 + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, len(name)), stack[name].shape)
        return out

    params["layers"] = [moved(run) for run in params["layers"]]
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq + 1), 0, cfg.vocab_size)
    return params, tokens


def program_nll(params, tokens, cfg):
    # ONE program a call (tests/conftest.py ``few_mappings``)
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(lambda p, t: hybrid.forward_with_stats(
            p, t, cfg))(params, tokens[:, :-1])
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tokens[:, 1:, None], -1)[..., 0]
    b, s = nll.shape
    return nll, stats["experts"].reshape(-1, b, s, cfg.top_k), stats


def test_the_registry_knows_the_family_and_the_tree_is_the_models():
    cfg, mod = registry.get("lfm2_moe", "tiny-lfm2")
    assert mod is hybrid and cfg.qk_head_norm and cfg.tied_head
    # a head width that is not the hidden size over the heads
    assert cfg.head_dim == 24 != cfg.d_model // cfg.n_heads
    assert hybrid.layer_runs(cfg) == [
        ("conv.dense", 1), ("conv", 1), ("attention", 1), ("conv", 3),
        ("attention", 1), ("conv", 1)]
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    assert [sorted(s) for s in params["layers"]] == [
        sorted(CONV + DENSE), sorted(CONV + EXPERTS),
        sorted(ATTENTION + EXPERTS), sorted(CONV + EXPERTS),
        sorted(ATTENTION + EXPERTS), sorted(CONV + EXPERTS)]
    run = params["layers"]
    assert run[0]["in_proj"].shape == (1, 64, 3 * 64)       # B | C | u
    assert run[3]["conv_w"].shape == (3, 3, 64)             # three taps
    assert run[0]["w_gate"].shape == (1, 64, 96)
    assert run[2]["q_norm"].shape == run[2]["k_norm"].shape == (1, 24)
    assert run[1]["we_up"].shape == (1, 2, 64, 32)          # 2 of 8 held
    assert run[1]["router_bias"].dtype == jnp.float32
    assert "lm_head" not in params                          # tied
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == hybrid.num_params(cfg)
    assert jax.tree.structure(hybrid.param_specs(cfg), is_leaf=lambda x:
                              isinstance(x, tuple)) \
        == jax.tree.structure(params)


def test_the_published_model_counts_8_3_billion_and_the_cut_2_4():
    """The parameter tree at the published sizes is the model's, 8,339.8 M
    matrix, tap and bias parameters with the embedding tied (8,339.9 M
    with the norms' 101,120 scales), and the chip's share 2,425.9 M over
    ALL 24 layers in 13 runs."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        conf = json.load(f)
    from benchmark import flops_lfm2, model_lfm2

    pub = {**conf, **conf["published"], "deployment": {
        **conf["deployment"], "experts_held": 32}}
    cfg = model_lfm2.hybrid_config(pub)
    norms = (2 * 24 + 1) * 2048 + 6 * 2 * 64
    assert round((hybrid.num_params(cfg) - norms) / 1e6, 1) == 8339.8
    assert hybrid.num_params(cfg) == 8_339_930_560
    held = model_lfm2.hybrid_config(conf)
    assert round((hybrid.num_params(held) - norms) / 1e6, 1) == 2425.9
    assert hybrid.num_params(held) == 2_425_961_920
    assert hybrid.num_params(held) == flops_lfm2.total_params(
        model_lfm2.sizes(conf))
    assert held.n_layers == 24 and held.head_dim == 64
    # 13 runs of adjacent layers of a kind; the cell runs every layer as a
    # stack of its own (the file's run.run_layers 1: the depth rule)
    whole = hybrid.layer_runs(held.replace(run_layers=0))
    assert [n for _, n in whole] == [2, 1, 3, 1, 3, 1, 3, 1, 3, 1, 2, 1, 2]
    assert [k for k, _ in whole] == ["conv.dense"] + ["attention", "conv"] * 6
    assert hybrid.layer_runs(held) == [(k, 1) for k in held.kinds]


def test_values_gradients_and_the_bias_against_the_reference():
    cfg = tiny()
    params, tokens = make(cfg)
    nll, routes, _ = program_nll(params, tokens, cfg)
    want, rec = reference_lfm2.token_losses(params, tokens, ref_cfg(cfg))
    np.testing.assert_allclose(nll, want, atol=2e-5)
    assert bool(jnp.all(jnp.sort(routes, -1) == jnp.sort(rec["experts"], -1)))
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: hybrid.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True))(params)
    (ref_loss, ref_aux), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference_lfm2.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True))(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5
    assert abs(float(aux["moe_aux_loss"]) - float(ref_aux["aux"])) < 1e-5
    assert float(aux["moe_dropped"]) == 0
    flat = lambda t: jax.tree.leaves_with_path(t)   # noqa: E731
    for (path, g), (_, w) in zip(flat(grads), flat(ref_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-9
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    # every leaf but the biases has a gradient: no gradient reaches a bias
    for lp in reference_lfm2.layers(grads):
        for name, g in lp.items():
            assert (float(jnp.abs(g).max()) == 0) == (name == "router_bias"), \
                name
    moved, report = hybrid.post_update(params, aux, cfg)
    want = reference_lfm2.bias_update(
        reference_lfm2.biases(params), rec["counts"], ref_cfg(cfg))
    np.testing.assert_array_equal(reference_lfm2.biases(moved), want)
    # a dense layer has no router and no part in the rule: 7 expert layers
    assert want.shape == (7, cfg.n_experts)
    assert "router_counts" not in report
    assert 0 < float(report["moe_bias_moved"]) <= 7 * cfg.n_experts


def test_the_checkpointed_step_is_the_plain_one():
    """Under the layer checkpoint (``remat``) the hand-written gradient of
    the gate-taps-gate pass runs from the replayed in-projection: the
    gradients are the plain program's."""
    cfg = tiny()
    params, tokens = make(cfg)
    grad = lambda c: jax.jit(jax.grad(lambda p: hybrid.loss_fn(  # noqa: E731
        p, {"tokens": tokens}, c)[0]))(params)
    plain, again = grad(cfg), grad(cfg.replace(remat=True))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(again)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_the_gated_convolutions_rule_is_the_plain_gradient():
    """``_gated_conv``'s hand-written gradient against jax's own of the
    same arithmetic, in float32 and at bfloat16's rounding."""
    key = jax.random.PRNGKey(0)
    bcu = jax.random.normal(key, (2, 16, 3 * 8))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 8))

    def plain(bcu, w):
        c, _ = hybrid._gate_conv(bcu, w)
        return (bcu[..., 8:16].astype(jnp.float32) * c).astype(bcu.dtype)

    g = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, 8))
    for dt, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)):
        a, b = bcu.astype(dt), w.astype(dt)
        np.testing.assert_array_equal(hybrid._gated_conv(a, b), plain(a, b))
        got = jax.vjp(hybrid._gated_conv, a, b)[1](g.astype(dt))
        want = jax.vjp(plain, a, b)[1](g.astype(dt))
        for x, y in zip(got, want):
            assert x.dtype == y.dtype == dt
            np.testing.assert_allclose(
                x.astype(jnp.float32), y.astype(jnp.float32), rtol=tol,
                atol=tol * float(jnp.abs(y.astype(jnp.float32)).max()))
    # causal: a later row moves no earlier output
    later = bcu.at[:, 9].add(1.0)
    np.testing.assert_array_equal(hybrid._gated_conv(later, w)[:, :9],
                                  hybrid._gated_conv(bcu, w)[:, :9])


def test_adjacent_layers_of_a_kind_are_one_stack_and_read_the_same():
    """Three adjacent conv layers are one scan of three, two dense ones a
    scan of two; values, the routers' counts in the layers' order, the
    gradients and the bias after a step are the reference's layer by layer,
    and the same as with every layer a run of its own (``run_layers`` 1)."""
    cfg = tiny(n_layers=7, n_dense=2, layer_types=(
        "conv", "conv", "conv", "conv", "conv", "attention", "attention"))
    assert hybrid.layer_runs(cfg) == [
        ("conv.dense", 2), ("conv", 3), ("attention", 2)]
    params, tokens = make(cfg)
    assert [jax.tree.leaves(run)[0].shape[0] for run in params["layers"]] \
        == [2, 3, 2]
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: hybrid.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True))(params)
    (ref_loss, _), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference_lfm2.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True))(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5
    _, rec = reference_lfm2.token_losses(params, tokens, ref_cfg(cfg))
    np.testing.assert_array_equal(aux["router_counts"], rec["counts"])
    for x, y in zip(reference_lfm2.layers(grads),
                    reference_lfm2.layers(ref_grads)):
        for k in x:
            np.testing.assert_allclose(
                x[k], y[k], rtol=2e-3, err_msg=k,
                atol=2e-3 * float(jnp.abs(y[k]).max()) + 1e-9)
    moved, _ = hybrid.post_update(params, aux, cfg)
    np.testing.assert_array_equal(
        reference_lfm2.biases(moved), reference_lfm2.bias_update(
            reference_lfm2.biases(params), rec["counts"], ref_cfg(cfg)))
    # singly: the same layers, each a stack of one
    single = cfg.replace(run_layers=1)
    assert hybrid.layer_runs(single) == [
        (k, 1) for k in ("conv.dense",) * 2 + ("conv",) * 3
        + ("attention",) * 2]
    apart = {**params, "layers": [
        jax.tree.map(lambda w, r=r: w[r:r + 1], run)
        for run in params["layers"]
        for r in range(jax.tree.leaves(run)[0].shape[0])]}
    with jax.default_matmul_precision("highest"):
        one = jax.jit(lambda p: hybrid.loss_fn(
            p, {"tokens": tokens}, single)[0])(apart)
    assert abs(float(one) - float(loss)) < 1e-6


def _fake_int8(w, axis):
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def _each(params, fn):
    return {**params, "layers": [fn(run) for run in params["layers"]]}


WRONG = ["as it is", "8-bit in- and out-projections",
         "the taps in reversed order", "the second gate left out",
         "the head norm left out", "the bias left out of the choice",
         "weights not renormalised"]


def wrong_model(cfg, params, how, monkeypatch):
    """``(config, parameters)`` of a program that is another model."""
    if how == "8-bit in- and out-projections":
        return cfg, _each(params, lambda s: {
            **s, "in_proj": _fake_int8(s["in_proj"], 1),
            "out_proj": _fake_int8(s["out_proj"], 1)}
            if "in_proj" in s else s)
    if how == "the taps in reversed order":
        return cfg, _each(params, lambda s: {
            **s, "conv_w": s["conv_w"][:, ::-1]} if "conv_w" in s else s)
    if how == "the second gate left out":
        monkeypatch.setattr(hybrid, "_gated_conv", lambda bcu, w:
                            hybrid._gate_conv(bcu, w)[0].astype(bcu.dtype))
        return cfg, params
    if how == "the head norm left out":
        return cfg.replace(qk_head_norm=False), params
    if how == "the bias left out of the choice":
        return cfg, _each(params, lambda s: {
            **s, "router_bias": jnp.zeros_like(s["router_bias"])}
            if "router_bias" in s else s)
    if how == "weights not renormalised":
        return cfg.replace(norm_topk=False), params
    assert how == "as it is", how
    return cfg, params


@pytest.mark.parametrize("how", WRONG)
def test_a_wrong_model_is_refused_under_the_cells_own_limits(how,
                                                             monkeypatch):
    """What decides the rehearsal cell's ``correct`` (the share of routes
    that differ, the per-token losses on the program's routes) passes the
    program as it is and refuses each wrong model by at least one limit."""
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "rehearse-train-shortconv.json")) as f:
        tol = json.load(f)["train"]["check"]
    cfg = tiny()
    params, tokens = make(cfg, seq=64)
    run_cfg, run_params = wrong_model(cfg, params, how, monkeypatch)
    nll, routes, _ = program_nll(run_params, tokens, run_cfg)
    want, rec = jax.jit(lambda p, t, r: reference_lfm2.token_losses(
        p, t, ref_cfg(cfg), r))(params, tokens, routes)
    differ = float(jnp.mean(jnp.any(
        jnp.sort(routes, -1) != jnp.sort(rec["experts"], -1), axis=-1)))
    err = jnp.abs(nll - want)
    read = {"route_differ_share": differ,
            "route_gap_max": float(rec["route_gap"].max()),
            "token_mean_abs": float(err.mean()),
            "token_p999_abs": float(jnp.percentile(err, 99.9))}
    over = [k for k, v in read.items() if v > tol[k]]
    assert bool(over) == (how != "as it is"), (how, read)


@pytest.mark.parametrize("kind", ["conv", "attention", "conv.dense"])
def test_the_four_shares_add_up_to_the_uncut_layer(kind):
    """On one layer and one input: the program's layer on each of the four
    expert shares (2 of 8 each), with what every chip computes alike (the
    operator, a dense layer's SwiGLU) counted ONCE, adds up to what the
    reference gives for the uncut layer."""
    cfg = tiny(experts_held=None)
    params, _ = make(cfg, seed=3)
    at = [k for k, _ in hybrid.layer_runs(cfg)].index(kind)
    lp = jax.tree.map(lambda w: w[0], params["layers"][at])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, cfg.d_model))
    rc = ref_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference_lfm2.layer(x[0], lp, rc)
        alike = reference_lfm2.operator(x[0], lp, rc)   # the same on every chip
        cos, sin = llama._rope_tables(cfg.rope_theta, 64, cfg.head_dim)
        total = alike
        for first in range(0, 8, 2):
            share = cfg.replace(experts_held=(2, first))
            mine = {k: (w[first:first + 2] if k.startswith("we_") else w)
                    for k, w in lp.items()}
            y, stats, _ = llama._layer(x, mine, share, cos, sin, kind=kind)
            if kind == "conv.dense":
                # no experts: every chip computes the whole layer
                assert stats is None
                np.testing.assert_allclose(y[0], whole, rtol=2e-4, atol=2e-5)
                return
            total = total + (y[0] - alike)
            assert int(stats["counts"].sum()) == 64 * cfg.top_k
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def test_flash_at_a_head_of_64_against_the_plain_attention():
    """The flash kernels (interpret mode here) at 4 query heads over 2 KV
    heads of 64, half a lane tile: forward and the three gradients against
    ``_attention_xla``."""
    from ray_tpu.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    B, S, H, KV, D = 1, 256, 4, 2, 64
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, D))
    g = jax.random.normal(jax.random.fold_in(key, 3), (B, S, H, D))

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=128, block_k=128)

    def plain(q, k, v):
        return llama._attention_xla(q, k, v, causal=True)

    with jax.default_matmul_precision("highest"):
        out, back = jax.vjp(flash, q, k, v)
        want, want_back = jax.vjp(plain, q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-5)
        for a, b in zip(back(g), want_back(g)):
            np.testing.assert_allclose(a, b, atol=2e-4)


def test_flash_plans_a_head_of_64_as_it_holds_it():
    """At a head of 64 a block is a whole lane tile in VMEM: the plans
    count it at 128 and take the stream plans at S 16,384 (counted at 64
    the dK/dV call took the resident plan and asked Mosaic for 105 MiB of
    the 103.5 it may); a head of 128 or 256 plans as before."""
    import sys

    from ray_tpu.ops import flash_attention as _  # noqa: F401

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    kw = dict(S=16384, T=16384, dtype=jnp.bfloat16, block_q=512, block_k=512)
    for call in ("fwd", "dq"):
        at64, at128 = (fa.kv_plan(D=d, call=call, **kw) for d in (64, 128))
        assert at64.pop("D") == 64 and at128.pop("D") == 128
        assert at64 == at128 and at64["path"] == "stream"
    dkdv = {d: fa.bwd_dkdv_plan(D=d, groups=4, causal=True, window=0,
                                vmem_bytes=fa._V5E_VMEM_BYTES, **kw)
            for d in (64, 128)}
    assert dkdv[64]["path"] == "stream"
    assert (dkdv[64]["span"], dkdv[64]["in_flight"]) \
        == (dkdv[128]["span"], dkdv[128]["in_flight"])
    assert dkdv[64]["walk_bytes"] == dkdv[128]["walk_bytes"]
    # HBM is counted at the head's own width
    assert dkdv[64]["hbm_bytes_per_head"] < dkdv[128]["hbm_bytes_per_head"]
    assert fa._vmem_lanes(128) == 128 and fa._vmem_lanes(256) == 256


def test_the_cached_forwards_refuse_the_config():
    from ray_tpu.models import cached

    with pytest.raises(NotImplementedError, match="short-convolution"):
        cached.init_cache(tiny(), 1, 16)


def test_plan_instants_carry_the_runs_the_dense_layers_and_the_taps(
        monkeypatch):
    """``hybrid.layer_plan`` lists the runs with their kinds, the dense
    layers, the taps and what the checkpoint kept a kind; ``mixer.plan``
    the convolution's pass; ``moe.expert_plan`` the experts; each once a
    traced body."""
    from ray_tpu.util import tracing

    seen = []
    monkeypatch.setattr(tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, attrs)))
    cfg = tiny(gmm_impl="pallas", remat=True)
    params, tokens = make(cfg)
    jax.make_jaxpr(lambda p: hybrid.forward(p, tokens[:, :-1], cfg))(params)
    of = lambda n: [a for name, a in seen if name == n]      # noqa: E731
    assert of("hybrid.layer_plan") == [{
        "kinds": 3, "runs": 6, "bodies": 3, "layers": 8,
        "pattern": "conv.dense x1, conv x1, attention x1, conv x3, "
                   "attention x1, conv x1",
        "dense_layers": 1, "dense_width": 96, "taps": 3,
        "kept": "conv.dense: - x0/1, conv: - x0/3, attention: - x0/2"}]
    (mixer,), (experts,) = of("mixer.plan")[:1], of("moe.expert_plan")[:1]
    assert len(of("mixer.plan")) == 2       # the dense and the sparse body
    assert (mixer["kind"], mixer["taps"], mixer["channels"],
            mixer["rows"]) == ("conv", 3, 64, 64)
    assert mixer["hbm_bytes_fwd"] == 4 * 64 * 64 * 4
    assert (experts["act"], experts["matrices"], experts["width"],
            experts["shared_width"], experts["held"]) \
        == ("swiglu", 3, 32, 0, 2)


def test_the_plan_counts_a_dense_layer_for_what_it_holds():
    """``remat.remat_plan`` on the tiny model with a limit that has room:
    a dense conv layer offers its SwiGLU's gate and up and its
    in-projection, a sparse conv layer its in-projection alone (no shared
    expert), an attention layer q, k and v; a dense layer keeps no routes
    and counts no rows in expert order."""
    from ray_tpu.parallel.train_step import StepMemory

    cfg = tiny(remat=True)
    rows, item = 2 * 32, 4
    assert dict(hybrid.remat_offers(cfg, "conv.dense", rows)) == {
        "ffn_gate": rows * 96 * item, "ffn_up": rows * 96 * item,
        "mix_proj": rows * 192 * item}
    assert dict(hybrid.remat_offers(cfg, "conv", rows)) == {
        "mix_proj": rows * 192 * item}
    assert hybrid.remat_offers(cfg, "attention", rows) == ()
    assert hybrid.remat_saved_bytes(cfg, "conv.dense", rows) == 0
    assert hybrid.remat_saved_bytes(cfg, "conv", rows) > 0
    assert not hybrid.routes(cfg, "conv.dense") and hybrid.routes(cfg, "conv")
    params = jax.eval_shape(lambda: hybrid.init_params(
        jax.random.PRNGKey(0), cfg))
    plan = remat.remat_plan(cfg, params, 2, 32, StepMemory(10 ** 9, 10 ** 6))
    assert plan.why == "room"
    # (three of the tiny model's conv layers lie in a stack, so its
    # attention layers are runs of one AMONG stacks and leave q to their
    # replay; the cell's 24 layers are runs of one each and keep it:
    # tests/test_remat_plan.py)
    assert [n for _, n, _ in remat._stacks(params, cfg)[0]] \
        == [1, 1, 1, 3, 1, 1]
    assert plan.kept == (
        ("ffn_gate", "ffn_up", "mix_proj"), ("mix_proj",),
        ("attn_k", "attn_v"), ("mix_proj",),
        ("attn_k", "attn_v"), ("mix_proj",))


# sha256 (16 hex) of the gradient's jaxpr, addresses masked, B 2 x S 32: at
# the parent commit of PR 54 (f0cf476) with the router's selection as PR 60
# made it (``moe.top_lanes``' rounds for ``lax.top_k``: every expert layer's
# program changed there and nothing else of these; pinned again at PR 60)
PARENTS_PROGRAMS = {("tiny", False): "16f9f5efa6cd238f",
                    ("tiny", True): "a718de20c6b412f1",
                    ("tiny-nemotron", False): "3781eaf9da8114a4",
                    ("tiny-nemotron", True): "8ba87644f2f51576"}


@pytest.mark.parametrize("preset,remat", sorted(PARENTS_PROGRAMS))
def test_the_familys_other_members_trace_to_their_parents_programs(preset,
                                                                   remat):
    """The third kind, the dense layers and the norm a head move nothing
    of the Granite and the Nemotron block's programs: the gradient of the
    loss traces to the parent's jaxpr to the character (the ten cells'
    step jaxprs were compared the same way at their own shapes, PERF.md
    6, PR 54)."""
    import hashlib
    import re

    cfg = hybrid.PRESETS[preset].replace(remat=remat)
    params = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(lambda p, t: hybrid.loss_fn(
        p, {"tokens": t}, cfg)[0]))(params, tokens))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENTS_PROGRAMS[preset, remat]
