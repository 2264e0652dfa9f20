"""Solar Open2's block (models/solar.py: Kimi-Delta-Attention layers with a
gate that has no lower bound and beta in (0, 2) beside gated grouped-query
attention with no position table, sigmoid-routed experts of one group with
a shared one in every layer) against ``reference_solar.py`` on seeded
weights at the CPU tests' size: values, one step's gradients leaf by leaf,
the routes and the bias after a step; the shares of an expert layer add up
to the uncut layer; every wrong model told from the right one; the kernel
path is the plain one; what the layer checkpoint is told; the plans; the
refusals."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (cached, llama, reference_solar, registry, remat,
                            solar)

SEQ = 100        # a chunk of 64 and a part: the state crosses a chunk's end


def tiny(**kw):
    return solar.PRESETS["tiny"].replace(
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def ref_cfg(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def make(cfg, batch=2, seq=32, seed=0):
    """Seeded parameters with biases that matter, a gate that reaches far
    under -5, output norms off 1, and tokens [B, S + 1]."""
    params = solar.init_params(jax.random.PRNGKey(seed), cfg)
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


def routes_of(params, tokens, cfg):
    logits, stats = jax.jit(lambda p, t: solar.forward_with_stats(
        p, t[:, :-1], cfg))(params, tokens)
    b, s = tokens.shape[0], tokens.shape[1] - 1
    return logits, stats["experts"].reshape(-1, b, s, cfg.top_k), stats


def test_the_registry_knows_the_family_and_the_tree_is_the_models():
    cfg, mod = registry.get("solar_open2", "tiny")
    assert mod is solar and cfg.kinds == ("gqa", "kda", "kda", "kda", "gqa")
    assert solar.layer_runs(cfg) == [("gqa", 1), ("kda", 3), ("gqa", 1)]
    assert solar.layer_runs(cfg.replace(run_layers=2)) == [
        ("gqa", 1), ("kda", 2), ("kda", 1), ("gqa", 1)]
    params = solar.init_params(jax.random.PRNGKey(0), cfg)
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == solar.num_params(cfg)
    specs = solar.param_specs(cfg)
    flat = lambda t: jax.tree.structure(jax.tree.map(         # noqa: E731
        lambda a: 0, t, is_leaf=lambda a: isinstance(a, tuple)))
    assert flat(specs) == flat(params)
    for run, spec in zip(params["layers"], specs["layers"]):
        assert all(len(spec[k]) == w.ndim for k, w in run.items()), spec
    gqa, kda = params["layers"][0], params["layers"][1]
    assert gqa["w_attn_gate"].shape == (1, 64, 4 * 16)
    assert "w_attn_gate" not in kda and "wq" in gqa and "a_log" not in gqa
    assert kda["w_decay_a"].shape == (3, 64, 8) \
        and kda["w_gate_b"].shape == (3, 8, 32) \
        and kda["o_norm"].shape == (3, 16) and kda["dt_bias"].shape == (3, 32)
    assert kda["we_gate"].shape == (3, 5, 64, 32)      # 5 of 15 held
    assert kda["router"].shape == (3, 64, 15)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_values_gradients_routes_and_the_bias_against_the_reference(impl):
    cfg = tiny(kda_impl=impl)
    params, tokens = make(cfg, seq=SEQ)
    rc = ref_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        _, routes, _ = routes_of(params, tokens, cfg)
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: solar.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True))(params)
        (want, terms), want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_solar.loss(p, tokens, rc, routes),
            has_aux=True))(params)
        _, rec = jax.jit(lambda p: reference_solar.token_losses(
            p, tokens, rc))(params)
    # the program's K experts are the reference's own, token by token
    np.testing.assert_array_equal(np.sort(np.asarray(rec["experts"]), -1),
                                  np.sort(np.asarray(routes), -1))
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(aux["moe_main_loss"], terms["ce"], rtol=2e-6)
    np.testing.assert_allclose(aux["moe_aux_loss"], terms["aux"], rtol=1e-5)
    assert int(aux["moe_dropped"]) == 0
    # the gate really leaves the old kernel's bound
    assert float(aux["kda_gate_min"]) < -11.0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:           # no gradient reaches it
            assert not np.asarray(a).any(), name
            continue
        np.testing.assert_allclose(
            a, b, atol=1e-3 * float(jnp.abs(b).max()) + 1e-9, err_msg=name)
    # the rule after the step: the reference's, from the program's counts
    moved, _ = solar.post_update(params, dict(aux), cfg)
    np.testing.assert_allclose(
        reference_solar.biases(moved), reference_solar.bias_update(
            reference_solar.biases(params), aux["router_counts"], rc),
        atol=1e-7)


def test_remat_changes_nothing_and_a_mixer_reports_beside_its_output():
    cfg = tiny()
    params, tokens = make(cfg)
    loss = lambda c: jax.jit(lambda p: solar.loss_fn(           # noqa: E731
        p, {"tokens": tokens}, c))(params)
    (a, aux), (b, _) = loss(cfg), loss(cfg.replace(remat=False))
    np.testing.assert_allclose(a, b, rtol=1e-6)
    _, _, stats = routes_of(params, tokens, cfg)
    # the KDA runs report the least g a layer; the grouped-query runs none
    assert stats["gate_min"].shape == (3,)
    assert float(aux["kda_gate_min"]) == float(stats["gate_min"][0])
    assert stats["counts"].shape == (5, 15)


def test_the_gate_has_no_bound_and_beta_reaches_two():
    cfg = tiny()
    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][1])
    h = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (1, 64, cfg.d_model))
    q, k, v, g, beta = solar.scan_inputs(h, lp, cfg)
    assert g.dtype == jnp.float32 and float(g.max()) <= 0.0
    assert float(g.min()) < -20.0 and float((g < -5.0).mean()) > 0.05
    assert 0.0 < float(beta.min()) and 1.5 < float(beta.max()) < 2.0
    heads = lambda t: np.asarray(t).reshape(1, 64, 2, 16)        # noqa: E731
    np.testing.assert_allclose(np.linalg.norm(heads(k), axis=-1), 1.0,
                               atol=1e-3)
    np.testing.assert_allclose(np.linalg.norm(heads(q), axis=-1), 0.25,
                               atol=1e-3)


def test_the_shares_add_up_to_the_uncut_layer():
    """On one KDA layer and one input: the program's layer on each of the
    three expert shares (5 of 15 each: at the cell's size 32 shares of 10),
    with what every chip computes alike (the first half, the shared expert)
    counted ONCE, adds up to what the reference gives for the uncut
    layer."""
    cfg = tiny(experts_held=None)
    params, _ = make(cfg, seed=3)
    lp = jax.tree.map(lambda w: w[0], params["layers"][1])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, cfg.d_model))
    rc = ref_cfg(cfg)

    def program(c, weights):        # one program a share: jitted, not eager
        return jax.jit(lambda x, w: llama._layer(x, w, c, None, None,
                                                 kind="kda")[:2])(x, weights)

    @jax.jit
    def reference(x, lp):
        f32 = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        first = reference_solar.first_half(x, f32, rc)
        normed = reference_solar._rms(first, f32["ffn_norm"], cfg.norm_eps)
        alike = first + reference_solar._swiglu(
            normed, f32["ws_gate"], f32["ws_up"], f32["ws_down"])
        return reference_solar.layer(x, lp, rc)[0], alike

    with jax.default_matmul_precision("highest"):
        whole, alike = reference(x[0], lp)
        total = alike
        for start in range(0, 15, 5):
            share = cfg.replace(experts_held=(5, start))
            mine = {k: (w[start:start + 5] if k.startswith("we_") else w)
                    for k, w in lp.items()}
            y, stats = program(share, mine)
            total = total + (y[0] - alike)
            assert int(stats["counts"].sum()) == 64 * cfg.top_k
            assert float(stats["gate_min"]) < 0.0
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=1e-4)


def _nll(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        logits, routes, _ = routes_of(params, tokens, cfg)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked, routes


def _without(leaf):
    """(the parameters with ``leaf`` of every stack that has it zeroed, no
    patch)."""
    def wrong(params):
        return {**params, "layers": [
            {k: (jnp.zeros_like(w) if k == leaf else w)
             for k, w in run.items()} for run in params["layers"]]}, None
    return wrong


def _patched(name, fn):
    """(the parameters as they are, ``solar.<name>`` replaced by
    ``fn(its own)`` while the model traces)."""
    def wrong(params):
        return params, (name, fn)
    return wrong


# every wrong model the cell must tell from the right one, as a change of
# the parameters or of one function of the program
WRONG = {
    "beta without its factor 2": _patched(
        "scan_inputs", lambda orig: lambda h, lp, cfg: (
            lambda q, k, v, g, beta: (q, k, v, g, 0.5 * beta))(
                *orig(h, lp, cfg))),
    "the safe gate in place of the softplus form": _patched(
        "decay_gate", lambda orig: lambda f, a_log, dt_bias, width:
        -5.0 * jax.nn.sigmoid(jnp.repeat(jnp.exp(a_log), width)
                              * (f.astype(jnp.float32) + dt_bias))),
    "the gate clamped at the old bound": _patched(
        "decay_gate", lambda orig: lambda *a: jnp.maximum(orig(*a), -5.0)),
    "the grouped-query gate left out": _without("w_attn_gate"),
    "the bias left out of the choice": _without("router_bias"),
}


@functools.cache
def _told():
    """The right program's distance to the reference, and the two jitted
    passes every wrong program goes through again."""
    cfg = tiny()
    params, tokens = make(cfg, seq=SEQ)
    rc = ref_cfg(cfg)
    on_routes = jax.jit(lambda r: reference_solar.token_losses(
        params, tokens, rc, r)[0])
    with jax.default_matmul_precision("highest"):
        own = np.sort(np.asarray(jax.jit(
            lambda: reference_solar.token_losses(params, tokens, rc))()[1][
                "experts"]), -1)

    def distance(p):
        got, routes = _nll(p, tokens, cfg)
        with jax.default_matmul_precision("highest"):
            want = on_routes(routes)
        differ = np.mean(np.any(own != np.sort(np.asarray(routes), -1),
                                axis=-1))
        return float(jnp.abs(got - want).mean()), float(differ)

    return params, distance, distance(params)


@pytest.mark.parametrize("how", WRONG)
def test_a_wrong_model_is_told_from_the_right_one(how, monkeypatch):
    """Each wrong program against the reference on ITS routes: the right
    program's per-token loss lies within 1e-4 of the reference's, a wrong
    one at least twenty times as far, or its routes differ where the right
    program's do not."""
    params, distance, (right, right_routes) = _told()
    assert right < 1e-4 and right_routes == 0.0
    p, patch = WRONG[how](params)
    if patch is not None:
        name, fn = patch
        monkeypatch.setattr(solar, name, fn(getattr(solar, name)))
    wrong, wrong_routes = distance(p)
    assert wrong > 20 * right or wrong_routes > 0.05, (how, wrong,
                                                       wrong_routes)


def test_a_state_carried_in_bfloat16_and_a_gate_a_head_read_apart():
    """The two wrong programs that change the recurrence or the output's
    gate on the op's plain path: a state rounded to bfloat16 from chunk to
    chunk, and ONE output gate a head (the mean of the head's channels) in
    place of a gate a channel, each against the program as it is."""
    from ray_tpu.ops import delta_rule

    cfg = tiny()
    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][1])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, cfg.d_model))
    right = jax.jit(lambda x: solar.mixer_half(x, lp, cfg, "kda")[0])(x)
    gate = lp["w_gate_b"].reshape(8, 2, 16)
    a_head = {**lp, "w_gate_b": jnp.broadcast_to(
        gate.mean(axis=-1, keepdims=True), gate.shape).reshape(8, 32)}
    wrong = jax.jit(lambda x: solar.mixer_half(x, a_head, cfg, "kda")[0])(x)
    rel = lambda a: float(jnp.linalg.norm(a - right)           # noqa: E731
                          / jnp.linalg.norm(right - x))
    assert rel(wrong) > 0.05

    h = llama.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v, g, beta = (t.reshape(1, 128, 2, -1) if t.ndim == 3 and
                        t.shape[-1] == 32 else t
                        for t in solar.scan_inputs(h, lp, cfg))

    def rounded(state, *a):
        state, o = delta_rule._chunk_xla(state, *a)
        return jax.lax.reduce_precision(state, 8, 7), o

    chunks = lambda a: jnp.moveaxis(jnp.moveaxis(               # noqa: E731
        a.reshape(1, 2, 64, *a.shape[2:]), 3, 2), 1, 0)
    _, o = jax.lax.scan(lambda s, c: rounded(s, *c),
                        jnp.zeros((1, 2, 16, 16)),
                        tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(1, 128, 2, 16)
    want = delta_rule.gated_delta_rule(q, k, v, g, beta, lower_bound=None)
    carried = float(jnp.linalg.norm(o[:, 64:] - want[:, 64:])
                    / jnp.linalg.norm(want[:, 64:]))
    kernel = delta_rule.gated_delta_rule(q, k, v, g, beta, impl="pallas",
                                         lower_bound=None)
    own = float(jnp.linalg.norm(kernel - want) / jnp.linalg.norm(want))
    # the unbounded gate forgets fast, so a rounded state shows less than
    # under Ling's bound: still forty times the kernel's own distance
    assert own < 2e-5 and carried > 20 * own


def test_what_the_layer_checkpoint_is_told():
    """A KDA block is a mixer to the plan (no flash residuals, its own
    backward bytes), a grouped-query block llama's attention half (q, k and
    v offered at 4 over 2 heads of 16); a KDA half offers q, k, v and the
    gate, and on the kernel path the scan's output and states; every block
    its shared expert's products."""
    from ray_tpu.models.family import _halves, _takes_attention_half

    cfg = tiny().replace(dtype=jnp.bfloat16)
    assert _halves(cfg, "kda") == ("mixer", True)
    assert _halves(cfg, "gqa") == ("attention", True)
    assert _takes_attention_half(cfg, "gqa")
    rows = 256
    names = lambda kind, c=cfg: [n for n, _ in remat._offers(  # noqa: E731
        c, kind, 1, rows)]
    assert names("gqa") == ["attn_q", "attn_k", "attn_v", "shared_gate",
                            "shared_up"]
    assert names("kda") == ["shared_gate", "shared_up", "kda_q", "kda_k",
                            "kda_v", "kda_gate"]
    assert names("kda", cfg.replace(kda_impl="pallas"))[-2:] \
        == ["kda_out", "kda_states"]
    offers = dict(remat._offers(cfg.replace(kda_impl="pallas"), "kda", 1,
                                rows))
    assert offers["kda_q"] == rows * 32 * 2 and offers["kda_gate"] \
        == rows * 32 * 4
    assert offers["kda_states"] == (rows // 64) * 32 * 16 * 4
    assert dict(remat._offers(cfg, "gqa", 1, rows))["attn_k"] \
        == rows * 2 * 16 * 2
    assert set(n for k in cfg.kinds for n in names(k)) \
        <= set(remat._offered(cfg))
    assert solar.mixer_backward_bytes(cfg, "kda", rows) \
        == offers["kda_states"] + 2 * offers["kda_gate"]
    # a step's plan on a device that states a limit: every run keeps names
    params = jax.eval_shape(lambda: solar.init_params(jax.random.PRNGKey(0),
                                                      cfg))
    from ray_tpu.parallel.train_step import StepMemory

    plan = remat.remat_plan(cfg, params, 1, rows, StepMemory(
        limit=16_909_336_064, state=2 * 290_000))
    assert plan.why == "room" and len(plan.kept) == 3
    # (the attention layer, a run of one beside the KDA layers' stack,
    # keeps k and v and leaves q: ``remat.LEFT_BY_ONE_AMONG_STACKS``)
    assert [n for _, n, _ in remat._stacks(params, cfg)[0]][:2] == [1, 3]
    assert plan.kept[0][:2] == ("attn_k", "attn_v") \
        and "kda_gate" in plan.kept[1]


def test_plan_instants_say_the_cut_the_chunk_and_the_states(monkeypatch):
    from ray_tpu.util import tracing

    said = []
    monkeypatch.setattr(tracing, "instant", lambda n, attrs=None, **kw:
                        said.append((n, attrs)))
    cfg = tiny(kda_impl="pallas")
    params, tokens = make(cfg, seq=SEQ)
    jax.jit(lambda p: solar.loss_fn(p, {"tokens": tokens}, cfg)[0]).lower(
        params)
    plans = [a for n, a in said if n == "kda.plan"]
    # the cut in halves: 6 levels of a chunk of 64 where a stated bound
    # gives 4 sub-blocks, the 3 smallest on the vector unit and the 3 others
    # a product each (PR 66), 6 more on the way back; in this float32 model
    # the 5 and 12 products over the state and the steps are float32 too
    assert plans and all(
        p["path"] == "pallas" and p["chunk"] == 64 and p["cut"] == "halving"
        and p["lower_bound"] is None
        and p["cut_sizes"] == [2, 4, 8, 16, 32, 64] for p in plans)
    assert all(p["heads_per_block"] == 2 and p["inverse_side"] == 128
               and p["vector_levels"] == 3
               and p["f32_products_fwd"] == 6 + 5
               and p["f32_products_bwd"] == 12 + 12 for p in plans)
    halves = [a for n, a in said if n == "kda.half_plan"]
    assert halves and halves[0]["taps"] == 4 \
        and halves[0]["lower_bound"] == "none" \
        and halves[0]["gate_rank"] == 8
    kinds = [a for n, a in said if n == "attention.kind_plan"]
    assert all(not k.get("rope", False) for k in kinds)


def test_the_refusals():
    cfg = tiny()
    with pytest.raises(NotImplementedError, match="Kimi-Delta-Attention"):
        cached.init_cache(cfg, 1)
    with pytest.raises(NotImplementedError, match="Kimi-Delta-Attention"):
        cached.init_paged_cache(cfg, 4, 16)

    class Mesh:
        size, shape = 4, {"dp": 4}

    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][1])
    with pytest.raises(NotImplementedError, match="one device"):
        solar.mixer_half(jnp.zeros((1, 32, 64)), lp,
                         cfg.replace(kda_impl="pallas"), "kda", mesh=Mesh())
