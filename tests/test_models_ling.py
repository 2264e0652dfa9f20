"""Ling 3.0's block (models/ling.py: Kimi-Delta-Attention layers beside
latent attention without a query latent at value heads narrower than the
keys, leading dense layers, group-limited sigmoid-routed experts with a
shared one) against ``reference_ling.py`` on seeded weights at the CPU
tests' size: values, one step's gradients leaf by leaf, the bias after a
step; the router against a hand-worked case in which the group limit
changes the chosen experts; the shares of an expert layer add up to the
uncut layer; every wrong model told from the right one under the rehearsal
cell's own limits; the kernel path is the plain one; what the layer
checkpoint is told; the refusals."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (cached, latent, ling, llama, moe, reference_ling,
                            registry, remat)

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)


def tiny(**kw):
    return ling.PRESETS["tiny"].replace(
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def ref_cfg(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def make(cfg, batch=2, seq=32, seed=0):
    """Seeded parameters with biases that matter, a gate that spreads over
    (-5, 0), output norms off 1, and tokens [B, S + 1]."""
    params = ling.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 5)

    def moved(stack):
        out = dict(stack)
        for name, draw in (
                ("router_bias", lambda z: 0.05 * z),
                ("dt_bias", lambda z: z),
                ("o_norm", lambda z: 1.0 + 0.3 * z)):
            if name in stack:
                out[name] = draw(jax.random.normal(
                    jax.random.fold_in(key, len(name)), stack[name].shape))
        return out

    params["layers"] = [moved(run) for run in params["layers"]]
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq + 1), 0, cfg.vocab_size)
    return params, tokens


SEQ = 100        # a chunk of 64 and a part: the state crosses a chunk's end


def _nll(params, tokens, cfg):
    logits, stats = ling.forward_with_stats(
        params, tokens[:, :-1], cfg.replace(report_groups=True))
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - picked
    by_layer = lambda a: a.reshape(a.shape[0], *nll.shape, -1)  # noqa: E731
    return nll, by_layer(stats["experts"]), by_layer(stats["groups"]), stats


def _loss_and_grads(params, tokens, cfg):
    return jax.value_and_grad(
        lambda p: ling.loss_fn(p, {"tokens": tokens}, cfg), has_aux=True)(
            params)


# ONE compiled program a config and shape for all the tests of this file: a
# whole tiny model takes 5 to 10 s to compile, forward and backward
_NLL = jax.jit(_nll, static_argnums=2)
_GRADS = jax.jit(_loss_and_grads, static_argnums=2)
_REFERENCE = jax.jit(
    lambda params, tokens, cfg, routes, groups: reference_ling.token_losses(
        params, tokens, ref_cfg(cfg), routes, groups), static_argnums=2)


def program_nll(params, tokens, cfg, fresh=False):
    """(per-token losses [B, S], routes [L, B, S, K], the kept groups [L,
    B, S, G], statistics). ``fresh``: traced anew, for a program whose
    functions a test has replaced."""
    with jax.default_matmul_precision("highest"):
        if fresh:       # jit's cache goes by the function: a new one
            return jax.jit(lambda p, t: _nll(p, t, cfg))(params, tokens)
        return _NLL(params, tokens, cfg)


def reference_nll(params, tokens, cfg, routes, groups):
    """The reference on the program's routes and groups: (per-token losses,
    record; ``experts`` in it are the reference's OWN choice)."""
    with jax.default_matmul_precision("highest"):
        return _REFERENCE(params, tokens, cfg, routes, groups)


def test_the_registry_knows_the_family_and_the_tree_is_the_models():
    cfg, mod = registry.get("bailing_hybrid", "tiny")
    assert mod is ling and isinstance(cfg, ling.LingConfig)
    assert cfg.kinds == ("kda.dense", "kda.dense", "kda", "kda", "mla", "kda")
    assert ling.layer_runs(cfg) == [("kda.dense", 2), ("kda", 2), ("mla", 1),
                                    ("kda", 1)]
    params = ling.init_params(jax.random.PRNGKey(0), cfg)
    specs = ling.param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == ling.num_params(cfg)
    mla, kda = params["layers"][2], params["layers"][1]
    # no query latent, narrower values, a gate a head; a KDA half's leaves
    assert "wq_a" not in mla and mla["wq"].shape == (1, 64, 2 * 24)
    assert mla["wkv_b"].shape == (1, 16, 2 * (16 + 16))
    assert mla["wo"].shape == (1, 2 * 16, 64)
    assert mla["w_attn_gate"].shape == (1, 64, 2)
    assert kda["w_decay"].shape == (2, 64, 32) and kda["a_log"].shape == (2, 2)
    assert kda["dt_bias"].shape == (2, 32) and "wkv_a" not in kda
    assert ling.FAMILY.name == "ling" and ling.FAMILY.mixer_half \
        is ling.mixer_half
    # the period of six, counted in the published model
    full = cfg.replace(n_layers=12, layer_group_size=6)
    assert [i for i, k in enumerate(full.kinds) if k.startswith("mla")] \
        == [5, 11]


def test_the_published_model_counts_125_billion_and_the_cut_1_2():
    """Every width as the catalog's row states it: the whole model about
    125 B parameters with about 5.5 B active, the cell's cut 1.205 B."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-ep32-l8.json")) as f:
        conf = json.load(f)
    import sys
    sys.path.insert(0, ROOT)
    from benchmark import flops_ling, model_ling

    sizes = model_ling.sizes(conf)
    cfg = model_ling.ling_config(conf)
    assert ling.num_params(cfg) == flops_ling.total_params(sizes)
    assert abs(ling.num_params(cfg) / 1e9 - 1.205) < 0.002
    whole = cfg.replace(n_layers=42, n_experts=512, experts_held=None,
                        vocab_size=157184)
    assert 120e9 < ling.num_params(whole) < 130e9
    active = flops_ling.matmul_params_per_token(
        dict(sizes, n_layers=42, vocab_size=157184, experts_held=(512, 0),
             kinds=whole.kinds))
    assert 4.5e9 < sum(active.values()) < 6.0e9


def test_values_gradients_and_the_bias_against_the_reference():
    cfg = tiny()
    params, tokens = make(cfg, seq=SEQ)
    rc = ref_cfg(cfg)
    nll, routes, groups, stats = program_nll(params, tokens, cfg)
    want, rec = reference_nll(params, tokens, cfg, routes, groups)
    # each layer alone reads within 1e-5 of the reference's on the same
    # input; through six layers the difference grows a hundredfold, because
    # a KDA half norms an output of RMS 0.01 to 0.1 back to 1
    np.testing.assert_allclose(nll, want, atol=2e-3)
    # the reference's OWN choice is the program's
    np.testing.assert_array_equal(jnp.sort(routes, -1),
                                  jnp.sort(rec["experts"], -1))
    np.testing.assert_allclose(stats["group_kept"], rec["group_kept"],
                               atol=1e-6)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = _GRADS(params, tokens, cfg)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_ling.loss(p, tokens, rc)[0]))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-4
    np.testing.assert_array_equal(aux["router_counts"], rec["counts"])
    assert abs(float(aux["moe_group_kept_share"])
               - float(rec["group_kept"].mean())) < 1e-6
    for x, y in zip(reference_ling.layers(grads),
                    reference_ling.layers(ref_grads)):
        for k in x:
            if k == "router_bias":      # no gradient reaches it
                continue
            np.testing.assert_allclose(
                x[k], y[k], rtol=1e-2, err_msg=k,
                atol=1e-2 * float(jnp.abs(y[k]).max()) + 1e-9)
    moved, _ = ling.post_update(params, aux, cfg)
    np.testing.assert_array_equal(
        reference_ling.biases(moved), reference_ling.bias_update(
            reference_ling.biases(params), rec["counts"], rc))


def test_the_kernel_path_is_the_plain_one_and_remat_changes_nothing():
    cfg = tiny()
    params, tokens = make(cfg, seq=SEQ)
    # the plain path under the layer checkpoint against the kernel path
    # (interpreted) without it
    with jax.default_matmul_precision("highest"):
        (plain, _), plain_grads = _GRADS(params, tokens, cfg)
        (got, _), grads = _GRADS(params, tokens, cfg.replace(
            kda_impl="pallas", remat=False))
    assert abs(float(got) - float(plain)) < 2e-5
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(plain_grads)):
        np.testing.assert_allclose(
            a, b, atol=5e-3 * float(jnp.abs(b).max()) + 1e-9)


def test_the_group_limit_changes_the_chosen_experts_by_hand():
    """8 experts in 4 groups of 2, a token keeps 2 groups and 3 experts.
    Choice scores (score + bias): group 0 (0.9, 0.1) = 1.0, group 1 (0.6,
    0.55) = 1.15, group 2 (0.7, 0.5) = 1.2, group 3 (0.8, 0.05) = 0.85.
    Without the limit the three best are experts 0 (0.9), 6 (0.8), 4
    (0.7). With it groups 2 and 1 are kept, and the three best INSIDE them
    are 4 (0.7), 2 (0.6), 3 (0.55): experts 0 and 6, the two best of all,
    are out. The weights are the scores WITHOUT the bias, renormalised
    and scaled."""
    cfg = tiny(n_experts=8, n_group=4, topk_group=2, top_k=3,
               experts_held=None, route_scale=2.5)
    score = jnp.array([[0.85, 0.1, 0.6, 0.5, 0.7, 0.5, 0.8, 0.05]])
    bias = jnp.array([0.05, 0.0, 0.0, 0.05, 0.0, 0.0, 0.0, 0.0])
    logits = jnp.log(score / (1 - score))
    kept = moe.kept_groups(score + bias, cfg)
    np.testing.assert_array_equal(kept, [[False, True, True, False]])
    weights, experts, probs, said = moe.route(logits, cfg, bias)
    np.testing.assert_array_equal(said, kept)
    assert sorted(np.asarray(experts[0]).tolist()) == [2, 3, 4]
    order = np.argsort(np.asarray(experts[0]))
    np.testing.assert_allclose(
        np.asarray(weights[0])[order],
        2.5 * np.array([0.6, 0.5, 0.7]) / (0.6 + 0.5 + 0.7), rtol=1e-5)
    free, unlimited, _, none = moe.route(
        logits, cfg.replace(n_group=1, topk_group=1), bias)
    assert sorted(np.asarray(unlimited[0]).tolist()) == [0, 4, 6] \
        and none is None
    own, ref_kept, _ = reference_ling.choose(score, bias, ref_cfg(cfg))
    assert sorted(np.asarray(own[0]).tolist()) == [2, 3, 4]
    np.testing.assert_array_equal(ref_kept, kept)
    # equal group scores: the lower group is kept, in both
    tied = jnp.array([[0.5, 0.5, 0.5, 0.5, 0.5, 0.5, 0.5, 0.5]])
    np.testing.assert_array_equal(
        moe.kept_groups(tied, cfg), [[True, True, False, False]])
    np.testing.assert_array_equal(
        reference_ling.choose(tied, 0 * bias, ref_cfg(cfg))[1],
        [[True, True, False, False]])
    with pytest.raises(NotImplementedError, match="group limit"):
        moe.route(logits, cfg.replace(router_score="softmax"), None)


@pytest.mark.parametrize("kind", ["kda", "mla", "kda.dense"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """On one layer and one input: the program's layer on each of the
    four expert shares (8 of 32 each, every share ONE whole group: at the
    cell's size 32 shares of 16, four to a group), with what every chip
    computes alike (the first half, the shared expert, a dense layer's
    SwiGLU) counted ONCE, adds up to what the reference gives for the
    uncut layer."""
    cfg = tiny(experts_held=None)
    params, _ = make(cfg, seed=3)
    at = [k for k, _ in ling.layer_runs(cfg)].index(kind)
    lp = jax.tree.map(lambda w: w[0], params["layers"][at])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, cfg.d_model))
    rc = ref_cfg(cfg)
    cos, sin = llama._pair_tables(cfg.rope_theta, 64, cfg.rope_dim)

    def program(c, weights):        # one program a share: jitted, not eager
        return jax.jit(lambda x, w: llama._layer(x, w, c, cos, sin,
                                                 kind=kind)[:2])(x, weights)

    @jax.jit
    def reference(x, lp):
        f32 = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        first = reference_ling.first_half(x, f32, rc)
        normed = reference_ling._rms(first, f32["ffn_norm"], cfg.norm_eps)
        alike = first if kind == "kda.dense" else first + \
            reference_ling._swiglu(normed, f32["ws_gate"], f32["ws_up"],
                                   f32["ws_down"])
        return reference_ling.layer(x, lp, rc)[0], alike

    with jax.default_matmul_precision("highest"):
        whole, alike = reference(x[0], lp)
        if kind == "kda.dense":
            y, stats = program(cfg, lp)
            assert stats is None        # every chip computes the whole layer
            np.testing.assert_allclose(y[0], whole, rtol=2e-4, atol=2e-5)
            return
        total = alike
        for start in range(0, 32, 8):
            share = cfg.replace(experts_held=(8, start))
            mine = {k: (w[start:start + 8] if k.startswith("we_") else w)
                    for k, w in lp.items()}
            y, stats = program(share, mine)
            total = total + (y[0] - alike)
            assert int(stats["counts"].sum()) == 64 * cfg.top_k
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=1e-4)


def _nll_without_groups(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        logits, stats = jax.jit(lambda p, t: ling.forward_with_stats(
            p, t[:, :-1], cfg))(params, tokens)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - picked
    e = stats["experts"]
    return nll, e.reshape(e.shape[0], *nll.shape, -1), stats


WRONG = ["as it is", "the gate's softplus form without its bound",
         "beta left out of the erase term", "one decay a head",
         "the top experts of all groups", "the state in bfloat16",
         "the output gate left out", "8-bit KDA projections"]


def _each(params, fn):
    return {**params, "layers": [fn(run) for run in params["layers"]]}


def _fake_int8(w, axis):
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def wrong_model(cfg, params, how, monkeypatch):
    """``(config, parameters, whether the test replaced a function of the
    program)`` of a program that is another model."""
    from ray_tpu.ops import delta_rule

    if how == "the gate's softplus form without its bound":
        # Kimi Linear's first form: g = -exp(a_log) softplus(f + dt_bias)
        monkeypatch.setattr(
            ling, "decay_gate", lambda f, a_log, dt_bias, lower, width:
            jnp.maximum(-jnp.repeat(jnp.exp(a_log), width) * jax.nn.softplus(
                f.astype(jnp.float32) + dt_bias), -20.0))
        return cfg.replace(kda_lower_bound=-10.9), params, True
    if how == "beta left out of the erase term":
        # a gated linear attention: S = Diag(exp g) S + beta k v^T
        def gla(q, k, v, g, beta, **kw):
            def one(q, k, v, g, beta):
                def step(s, x):
                    q_t, k_t, v_t, g_t, b_t = x
                    s = s * jnp.exp(g_t)[:, :, None] \
                        + k_t[:, :, None] * (b_t[:, None] * v_t)[:, None, :]
                    return s, jnp.einsum("hc,hcv->hv", q_t, s)
                zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]))
                return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]
            return jax.vmap(one)(q, k, v, g, beta)
        monkeypatch.setattr(ling, "gated_delta_rule", gla)
        return cfg, params, True
    if how == "one decay a head":
        gate = ling.decay_gate
        monkeypatch.setattr(
            ling, "decay_gate", lambda f, a_log, dt_bias, lower, width:
            jnp.repeat(gate(f, a_log, dt_bias, lower, width).reshape(
                *f.shape[:2], -1, width).mean(-1), width, axis=-1))
        return cfg, params, True
    if how == "the top experts of all groups":
        return cfg.replace(n_group=1, topk_group=1), params, False
    if how == "the state in bfloat16":
        chunk = delta_rule._chunk_xla

        def rounded(state, *a):
            state, o = chunk(state, *a)
            return state.astype(jnp.bfloat16).astype(jnp.float32), o
        monkeypatch.setattr(delta_rule, "_chunk_xla", rounded)
        # chunks of 16: the state is rounded six times a sequence
        monkeypatch.setattr(ling, "gated_delta_rule", functools.partial(
            delta_rule.gated_delta_rule, chunk=16))
        return cfg, params, True
    if how == "the output gate left out":
        return cfg, _each(params, lambda s: {
            **s, "w_out_gate": jnp.zeros_like(s["w_out_gate"])}
            if "w_out_gate" in s else s), False
    if how == "8-bit KDA projections":
        return cfg, _each(params, lambda s: {
            **s, **{n: _fake_int8(s[n], 1) for n in ("wq", "wk", "wv",
                                                       "w_decay", "wo")}}
            if "w_decay" in s else s), False
    assert how == "as it is", how
    return cfg, params, False


@pytest.mark.parametrize("how", WRONG)
def test_a_wrong_model_is_refused_under_the_cells_own_limits(how,
                                                             monkeypatch):
    """What decides the rehearsal cell's ``correct`` (the share of routes
    that differ and the reference's gap there, the per-token losses on the
    program's routes) passes the program as it is and refuses each wrong
    model by at least one limit."""
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "rehearse-train-kda.json")) as f:
        tol = json.load(f)["train"]["check"]
    cfg = tiny()
    params, tokens = make(cfg, seq=SEQ)
    run_cfg, run_params, fresh = wrong_model(cfg, params, how, monkeypatch)
    if run_cfg.n_group == 1:        # a program without the limit says none
        nll, routes, _ = _nll_without_groups(run_params, tokens, run_cfg)
        groups = None
    else:
        nll, routes, groups, _ = program_nll(run_params, tokens, run_cfg,
                                             fresh)
    want, rec = reference_nll(params, tokens, cfg, routes, groups)
    differ = float(jnp.mean(jnp.any(
        jnp.sort(routes, -1) != jnp.sort(rec["experts"], -1), axis=-1)))
    err = jnp.abs(nll - want)
    read = {"route_differ_share": differ,
            "route_gap_max": float(rec["route_gap"].max()),
            "token_mean_abs": float(err.mean()),
            "token_p999_abs": float(jnp.percentile(err, 99.9))}
    over = [k for k, v in read.items() if v > tol[k]]
    assert bool(over) == (how != "as it is"), (how, read)


def test_the_latent_half_takes_narrower_values_and_no_query_latent():
    """``LatentConfig`` no longer refuses value heads narrower than the
    query/key heads: v is padded with zero lanes up to the kernel's width
    and the output cut back, against the reference's plain softmax over
    values of their own width; wider values are still refused; the GLM
    form (a query latent, one width, no gate) keeps its tree."""
    cfg = tiny()
    params, _ = make(cfg, seed=2)
    lp = jax.tree.map(lambda w: w[0], params["layers"][2])
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 128, cfg.d_model))
    cos, sin = llama._pair_tables(cfg.rope_theta, 128, cfg.rope_dim)
    rc = ref_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        want = reference_ling.first_half(x[0], lp, rc)
        for impl in ("xla", "flash"):
            got, _, said = ling.attention_half(
                x, lp, cfg.replace(attn_impl=impl), cos, sin, kind="mla")
            assert said is None
            np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    assert latent.plan(cfg, 1, 128)["v_zero_lanes"] == 8
    glm = latent.PRESETS["tiny"]
    assert "v_zero_lanes" not in latent.plan(glm, 1, 128)
    tree = latent.init_params(jax.random.PRNGKey(0), glm)["layers"][0]
    assert {"wq_a", "q_a_norm", "wq_b"} <= set(tree) \
        and "w_attn_gate" not in tree and "wq" not in tree
    with pytest.raises(NotImplementedError, match="WIDER"):
        glm.replace(v_dim=glm.head_dim + 8)
    with pytest.raises(NotImplementedError, match="query latent"):
        latent.PRESETS["tiny-glm52"].replace(q_rank=0)


def test_what_the_layer_checkpoint_is_told():
    """A KDA block is a mixer to the plan (no flash residuals, its own
    backward bytes), an MLA block an attention half; a KDA half offers q,
    k, v and the gate, and on the kernel path the scan's output and
    states; a dense block offers its SwiGLU's products, a sparse one its
    shared expert's."""
    from ray_tpu.models.family import _halves

    cfg = tiny().replace(dtype=jnp.bfloat16)
    assert _halves(cfg, "kda") == ("mixer", True)
    assert _halves(cfg, "kda.dense") == ("mixer", True)
    assert _halves(cfg, "mla") == ("attention", True)
    rows = 256
    names = lambda kind, c=cfg: [n for n, _ in remat._offers(  # noqa: E731
        c, kind, 1, rows)]
    assert names("kda") == ["shared_gate", "shared_up", "kda_q", "kda_k",
                            "kda_v", "kda_gate"]
    assert names("kda.dense") == ["ffn_gate", "ffn_up", "kda_q", "kda_k",
                                  "kda_v", "kda_gate"]
    assert names("mla") == ["shared_gate", "shared_up"]
    assert names("kda", cfg.replace(kda_impl="pallas"))[-2:] \
        == ["kda_out", "kda_states"]
    offers = dict(remat._offers(cfg.replace(kda_impl="pallas"), "kda", 1,
                                rows))
    assert offers["kda_q"] == rows * 32 * 2 and offers["kda_gate"] \
        == rows * 32 * 4
    assert offers["kda_states"] == (rows // 64) * 32 * 16 * 4
    assert set(n for k in cfg.kinds for n in names(k)) \
        <= set(ling.FAMILY.remat_offered)
    assert ling.mixer_backward_bytes(cfg, "kda", rows) > 0
    assert ling.remat_saved_bytes(cfg, "kda.dense", rows) == 0 \
        < ling.remat_saved_bytes(cfg, "kda", rows)
    # a step's plan on a device that states a limit: every run keeps names
    params = jax.eval_shape(lambda: ling.init_params(jax.random.PRNGKey(0),
                                                     cfg))
    from ray_tpu.parallel.train_step import StepMemory

    plan = remat.remat_plan(cfg, params, 1, rows, StepMemory(
        limit=16_909_336_064, state=2 * 260_000))
    assert plan.why == "room" and len(plan.kept) == 4
    assert "kda_q" in plan.kept[0] and "kda_q" not in plan.kept[2]


def test_plan_instants_say_the_path_the_chunk_and_the_states(monkeypatch):
    from ray_tpu.util import tracing

    said = []
    monkeypatch.setattr(tracing, "instant", lambda n, attrs=None, **kw:
                        said.append((n, attrs)))
    cfg = tiny(kda_impl="pallas")
    params, tokens = make(cfg, seq=SEQ)
    jax.jit(lambda p: ling.loss_fn(p, {"tokens": tokens}, cfg)[0]).lower(
        params)
    plans = [a for n, a in said if n == "kda.plan"]
    # the op's chunk of 64: two chunks hold 100 steps; the block's two
    # heads in ONE inverse of side 128, so a head and chunk costs 4 pair
    # products and half of 3 rounds of two forward, 8 more on the way back,
    # and in this float32 model the 5 and 12 products over the state and
    # the steps are float32 too
    assert plans and all(p["path"] == "pallas" and p["chunk"] == 64
                         and p["sub_block"] == 16 for p in plans)
    assert all(p["heads_per_block"] == 2 and p["inverse_side"] == 128
               and p["f32_products_fwd"] == 7 + 5
               and p["f32_products_bwd"] == 15 + 12 for p in plans)
    assert plans[0]["state_bytes_kept"] == 2 * 2 * 2 * 16 * 16 * 4
    halves = [a for n, a in said if n == "kda.half_plan"]
    assert halves and halves[0]["taps"] == 4 \
        and halves[0]["lower_bound"] == -5.0
    assert any(n == "mla.plan" and a.get("v_zero_lanes") == 8
               for n, a in said)


def test_the_refusals():
    cfg = tiny()
    with pytest.raises(NotImplementedError, match="Kimi-Delta-Attention"):
        cached.init_cache(cfg, 1)
    with pytest.raises(NotImplementedError, match="prediction module"):
        cfg.replace(n_mtp=1)

    class Mesh:
        size, shape = 4, {"dp": 4}

    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][1])
    with pytest.raises(NotImplementedError, match="one device"):
        ling.mixer_half(jnp.zeros((1, 32, 64)), lp,
                        cfg.replace(kda_impl="pallas"), "kda", mesh=Mesh())
