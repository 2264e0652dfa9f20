"""The dropless expert model (models/moe.py) against its plain reference
(models/reference_olmoe.py), the grouped matmul's two paths against each
other, and what the layer checkpoint keeps."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu.models import llama, moe, reference_olmoe  # noqa: E402
from ray_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402
from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (make_train_state_init,  # noqa: E402
                                         make_train_step)

CFG = moe.PRESETS["tiny"].replace(dtype=jnp.float32, remat=False)
B, S = 2, 32


def ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in (
        "n_heads", "n_kv_heads", "d_model", "norm_eps", "rope_theta",
        "n_experts", "top_k", "norm_topk", "qk_norm", "router_aux_weight",
        "router_z_weight")}


def setup(cfg=CFG, seed=0, batch=B, seq=S):
    params = moe.init_params(jax.random.PRNGKey(seed), cfg)
    # norms away from 1, so that a missing norm shows
    params["layers"] = {
        k: (v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), v.shape,
                                             v.dtype))
            if k.endswith("norm") else v)
        for k, v in params["layers"].items()}
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq + 1), 0, cfg.vocab_size)
    return params, tokens


def program_routes(params, tokens, cfg):
    logits, stats = moe.forward_with_stats(params, tokens[:, :-1], cfg)
    b, s = tokens.shape[0], tokens.shape[1] - 1
    return logits, stats["experts"].reshape(cfg.n_layers, b, s, -1)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("norm_topk", [False, True])
def test_program_matches_reference_float32(impl, norm_topk):
    """Logits, the routes chosen and the three loss terms."""
    cfg = CFG.replace(gmm_impl=impl, norm_topk=norm_topk)
    params, tokens = setup(cfg)
    logits, routes = program_routes(params, tokens, cfg)
    ref_logits, rec = reference_olmoe.forward(params, tokens[:, :-1],
                                              ref_cfg(cfg))
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.sort(routes, -1),
                                  np.sort(rec["experts"], -1))
    loss, aux = moe.loss_fn(params, {"tokens": tokens}, cfg)
    ref_loss, terms = reference_olmoe.loss(params, tokens, ref_cfg(cfg))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(aux["moe_aux_loss"], terms["aux"], rtol=1e-5)
    np.testing.assert_allclose(aux["moe_z_loss"], terms["z"], rtol=1e-5)
    assert int(aux["moe_dropped"]) == 0
    # at balance the load-balancing loss is K; a random router is near it
    assert 0.8 * cfg.top_k < float(aux["moe_aux_loss"]) < 2 * cfg.top_k


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_gradients_match_the_reference(impl):
    """Every parameter's gradient against jax.grad of the plain
    reference (which has no sort, no grouped matmul, no custom VJP)."""
    cfg = CFG.replace(gmm_impl=impl)
    params, tokens = setup(cfg)
    g = jax.grad(lambda p: moe.loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
    g_ref = jax.grad(lambda p: reference_olmoe.loss(
        p, tokens, ref_cfg(cfg))[0])(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(g)
    flat_ref = jax.tree.leaves(g_ref)
    assert len(flat) == len(flat_ref) == 15
    for (path, a), b in zip(flat, flat_ref):
        scale = float(jnp.abs(b).max())
        assert scale > 0, path
        np.testing.assert_allclose(a, b, atol=2e-4 * scale, rtol=2e-3,
                                   err_msg=str(path))


def _dense_grouped(lhs, rhs, sizes):
    """Every row times every expert's matrix, masked: T x E work."""
    ends = np.cumsum(sizes)
    group = np.searchsorted(ends, np.arange(lhs.shape[0]), side="right")
    return jnp.einsum("mk,mkn->mn", lhs, rhs[group])


@pytest.mark.parametrize("sizes", [
    [16, 16, 16, 16], [5, 0, 40, 19], [0, 0, 64, 0], [64, 0, 0, 0],
    [1, 62, 0, 1]])
def test_grouped_matmul_paths_agree(sizes):
    """Uneven and EMPTY groups: both paths, values and both gradients,
    against the masked dense product."""
    m, k, n, e = 64, 24, 40, 4
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (e, k, n))
    gs = jnp.asarray(sizes, jnp.int32)

    def f(impl):
        def loss(a, w):
            out = grouped_matmul(a, w, gs, impl=impl)
            return (out * jnp.cos(out)).sum(), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(lhs, rhs)
        return out, grads

    def dense(a, w):
        out = _dense_grouped(a, w, np.asarray(sizes))
        return (out * jnp.cos(out)).sum(), out

    (_, want), want_g = jax.value_and_grad(dense, argnums=(0, 1),
                                           has_aux=True)(lhs, rhs)
    for impl in ("xla", "pallas"):
        out, grads = f(impl)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(grads[0], want_g[0], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(grads[1], want_g[1], rtol=1e-4, atol=1e-4)
        # an empty group's matrix gets a zero gradient, not garbage
        for i, s in enumerate(sizes):
            if s == 0:
                assert float(jnp.abs(grads[1][i]).max()) == 0.0


@pytest.mark.parametrize("sizes", [[16, 16, 16, 16], [5, 0, 40, 19],
                                   [0, 64, 0, 0]])
def test_grouped_matmul_pallas_at_several_k_and_n_tiles(sizes, monkeypatch):
    """Command A+'s experts are [4096, 4096]: ``gmm`` walks two K tiles
    and several N tiles in ONE call, ``tgmm`` a grid of K x N tiles (every
    other cell has one K tile or one N tile). The same here at tiles of
    128: K 256 is two tiles, N 384 three, against ``ragged_dot``: values
    and both gradients, uneven and empty groups."""
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "GMM_TILING", (16, 128, 128))
    monkeypatch.setattr(gm, "TGMM_TILING", (16, 128, 128))
    m, k, n, e = 64, 256, 384, 4
    assert gm._fit(gm.GMM_TILING, m, k, n, 4, halve_n=True) == (16, 128, 128)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (e, k, n)) * k ** -0.5
    gs = jnp.asarray(sizes, jnp.int32)

    def f(impl):
        def loss(a, w):
            out = grouped_matmul(a, w, gs, impl=impl)
            return (out * jnp.cos(out)).sum(), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(lhs, rhs)
        return out, grads

    want, want_g = f("xla")
    out, grads = f("pallas")
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(grads[0], want_g[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(grads[1], want_g[1], rtol=1e-4, atol=1e-4)
    for i, s in enumerate(sizes):
        if s == 0:
            assert float(jnp.abs(grads[1][i]).max()) == 0.0


@pytest.mark.parametrize("sizes", [[16, 16, 16, 16], [5, 0, 40, 19]])
def test_grouped_matmul_pallas_at_three_k_tiles(sizes, monkeypatch):
    """GLM-5.2's experts are [6144, 2048]: ``gmm`` walks THREE K tiles of
    2,048 in one call (two is the most of any other cell). The same here
    at tiles of 128: K 384 is three tiles, N 256 two, against
    ``ragged_dot``: values and both gradients, uneven and empty groups."""
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "GMM_TILING", (16, 128, 128))
    monkeypatch.setattr(gm, "TGMM_TILING", (16, 128, 128))
    m, k, n, e = 64, 384, 256, 4
    assert gm._fit(gm.GMM_TILING, m, k, n, 4, halve_n=True) == (16, 128, 128)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (e, k, n)) * k ** -0.5
    gs = jnp.asarray(sizes, jnp.int32)

    def f(impl):
        def loss(a, w):
            out = grouped_matmul(a, w, gs, impl=impl)
            return (out * jnp.cos(out)).sum(), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(lhs, rhs)
        return out, grads

    want, want_g = f("xla")
    out, grads = f("pallas")
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(grads[0], want_g[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(grads[1], want_g[1], rtol=1e-4, atol=1e-4)
    for i, s in enumerate(sizes):
        if s == 0:
            assert float(jnp.abs(grads[1][i]).max()) == 0.0


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)])
def test_grouped_matmul_pallas_at_a_width_of_no_whole_lanes(k, n):
    """Nemotron 3 Nano's experts are [2688, 1856] and [1856, 2688]: 1,856 =
    29 x 64 is no whole number of lanes (whole as ``gmm``'s N or K, 1,024
    and a ragged 832 in ``tgmm``); 2,688 = 21 x 128 goes in three tiles of
    896. The real widths and the real tiles against ``ragged_dot``: values
    and both gradients, uneven and empty groups."""
    from ray_tpu.ops import grouped_matmul as gm

    m, e = 64, 3
    tiles = gm._fit(gm.GMM_TILING, m, k, n, 4, halve_n=True)
    assert tiles[1:] == ((896, 1856) if k == 2688 else (1856, 896)), tiles
    assert gm._fit(gm.TGMM_TILING, m, k, n)[1:] == (
        (896, 1024) if k == 2688 else (1024, 896))
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (e, k, n)) * k ** -0.5
    gs = jnp.asarray([37, 0, 27], jnp.int32)

    def f(impl):
        def loss(a, w):
            out = grouped_matmul(a, w, gs, impl=impl)
            return (out * jnp.cos(out)).sum(), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                             has_aux=True)(lhs, rhs)
        return out, grads

    want, want_g = f("xla")
    out, grads = f("pallas")
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(grads[0], want_g[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(grads[1], want_g[1], rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(grads[1][1]).max()) == 0.0


def test_the_tiles_of_the_shapes_the_sweeps_saw_stand():
    """``_even`` looks further for equal tiles of whole lanes only where
    the fewest are none: every width a cell had keeps its tiles."""
    from ray_tpu.ops import grouped_matmul as gm

    assert [gm._even(t, d) for t, d in (
        (2048, 2048), (1024, 1536), (2048, 4096), (1024, 768), (2048, 2304),
        (1024, 2304), (2048, 6144), (1024, 896), (2048, 2688), (1024, 2688),
        (2048, 1856), (1024, 1856))] == [
        2048, 768, 2048, 768, 1152, 768, 2048, 896, 896, 896, 1856, 1024]


@pytest.mark.parametrize("held", [None, (2, 2)])
def test_two_matrix_experts_with_a_squared_relu(held):
    """``expert_act`` "relu2": the tree has no gate, the count says so,
    and both ways through the layer (every expert held: ``_dispatch``; a
    share: ``_held_experts``) give down(relu(up(x))^2) of the held
    experts, weighted, plus the shared one; gradients reach every leaf."""
    cfg = moe.PRESETS["tiny"].replace(
        expert_act="relu2", shared_d_ff=40, experts_held=held,
        dtype=jnp.float32, param_dtype=jnp.float32, router_z_weight=0.0)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    lay = params["layers"]
    assert "we_gate" not in lay and "ws_gate" not in lay
    assert lay["we_up"].shape == (2, cfg.n_held, 64, 96)
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == moe.num_params(cfg)
    assert set(moe.param_specs(cfg)["layers"]) == set(lay)
    assert moe.remat_offers(cfg, None, 10) == (("shared_up", 10 * 40 * 4),)
    lp = jax.tree.map(lambda w: w[0], lay)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64))
    with jax.default_matmul_precision("highest"):
        y, stats = moe.feed_forward(x, lp, cfg)
        logits = x[0] @ lp["router"]
        weights, experts, _, _ = moe.route(logits, cfg)
        want = jnp.square(jax.nn.relu(x[0] @ lp["ws_up"])) @ lp["ws_down"]
        first = held[1] if held else 0
        for e in range(cfg.n_held):
            w = jnp.sum(jnp.where(experts == e + first, weights, 0.0), -1)
            want = want + w[:, None] * (jnp.square(jax.nn.relu(
                x[0] @ lp["we_up"][e])) @ lp["we_down"][e])
        grads = jax.grad(lambda lp: jnp.sum(
            moe.feed_forward(x, lp, cfg)[0] ** 2))(lp)
    np.testing.assert_allclose(y[0], want, rtol=2e-4, atol=2e-5)
    assert all(float(jnp.abs(grads[w]).max()) > 0 for w in (
        "router", "we_up", "we_down", "ws_up", "ws_down"))


def test_the_tiles_at_glm52s_expert_widths():
    """[R, 6144] x [8, 6144, 2048] in bf16: what ``_fit`` gives ``gmm`` and
    ``tgmm`` there: three K tiles of 2,048."""
    from ray_tpu.ops import grouped_matmul as gm

    tm, tk, tn = gm._fit(gm.GMM_TILING, 16384, 6144, 2048, 2, halve_n=True)
    assert 6144 % tk == 0 and 6144 // tk == 3, (tm, tk, tn)
    tm, tk, tn = gm._fit(gm.TGMM_TILING, 16384, 6144, 2048)
    assert 6144 % tk == 0 and 2048 % tn == 0, (tm, tk, tn)


def test_the_tiles_at_command_a_pluss_expert_widths():
    """[R, 4096] x [8, 4096, 4096] in bf16: ``gmm`` takes two K tiles of
    2,048 and halves N to 1,024 (whole, 2 x (256 x 2048 + 2048 x 2048) x 2
    + 256 x 2048 x 6 bytes pass the 15 MiB), ``tgmm`` a 4 x 4 grid of
    tiles of 1,024."""
    from ray_tpu.ops import grouped_matmul as gm

    assert gm._fit(gm.GMM_TILING, 8192, 4096, 4096, 2, halve_n=True) \
        == (256, 2048, 1024)
    assert gm._fit(gm.TGMM_TILING, 8192, 4096, 4096) == (256, 1024, 1024)


def test_grouped_matmul_refuses_an_unknown_impl():
    with pytest.raises(ValueError, match="xla.*pallas"):
        grouped_matmul(jnp.ones((8, 4)), jnp.ones((2, 4, 4)),
                       jnp.asarray([4, 4]), impl="cuda")


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_no_token_is_dropped_when_one_expert_takes_every_row(impl):
    """The expert layer alone with router logits that put expert 0 first
    for EVERY token: its group holds T of the T*K rows (E/K = 2 times the
    mean; a capacity factor under 2 would drop), and the output equals the
    masked dense sum over experts."""
    cfg = CFG.replace(gmm_impl=impl)
    params, _ = setup(cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    d, e = cfg.d_model, cfg.n_experts
    # h >= 0 everywhere and a router whose column 0 is positive and whose
    # other columns are negative: logit 0 is the maximum for every token
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (B, S, d))) + 0.1
    router = -jnp.abs(lp["router"])
    lp["router"] = router.at[:, 0].set(jnp.abs(lp["router"][:, 0]) + 0.1)
    y, stats = moe.feed_forward(h, lp, cfg)
    t = B * S
    assert int(stats["counts"][0]) == t
    assert int(stats["counts"].sum()) == t * cfg.top_k
    assert float(stats["counts"].max()) / (t * cfg.top_k / e) == e / cfg.top_k
    want, rec = reference_olmoe._experts(h.reshape(t, d), lp, ref_cfg(cfg),
                                         None)
    np.testing.assert_allclose(y.reshape(t, d), want, rtol=1e-4, atol=1e-5)
    assert int(rec["counts"][0]) == t


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_remat_changes_nothing(impl):
    """Loss and every gradient with the layer checkpoint (routes kept,
    everything T*K rows wide recomputed) and without it."""
    cfg = CFG.replace(gmm_impl=impl)
    params, tokens = setup(cfg)
    (l1, _), g1 = jax.value_and_grad(lambda p: moe.loss_fn(
        p, {"tokens": tokens}, cfg), has_aux=True)(params)
    (l2, _), g2 = jax.value_and_grad(lambda p: moe.loss_fn(
        p, {"tokens": tokens}, cfg.replace(remat=True)), has_aux=True)(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g1, g2)


def _count(jaxpr, what):
    """Equations of a primitive in a jaxpr, sub-jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == what
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, what)
    return n


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_remat_keeps_flash_residuals_for_both_models(family):
    """PR 25's property through the ONE shared layer loop: with the layer
    checkpoint the grad jaxpr holds the flash forward once (forward, dq,
    dkdv: three Pallas calls), as without it, for the dense and the expert
    model; and the expert model's sort is not repeated in the backward
    (its routes are kept)."""
    if family == "dense":
        mod, cfg = llama, llama.PRESETS["tiny"].replace(
            dtype=jnp.float32, max_seq_len=256)
    else:
        mod, cfg = moe, CFG.replace(max_seq_len=256)
    cfg = cfg.replace(attn_impl="flash")
    params = mod.init_params(jax.random.PRNGKey(11), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(12), (2, 129),
                                          0, cfg.vocab_size)}

    def scalar(p, c):
        out = mod.loss_fn(p, batch, c)
        return out[0] if isinstance(out, tuple) else out

    def grad_jaxpr(c):
        return jax.make_jaxpr(jax.grad(lambda p: scalar(p, c)))(params).jaxpr

    with_remat, without = grad_jaxpr(cfg.replace(remat=True)), \
        grad_jaxpr(cfg.replace(remat=False))
    assert _count(with_remat, "pallas_call") == 3
    assert _count(without, "pallas_call") == 3
    if family == "moe":
        assert _count(with_remat, "sort") == _count(without, "sort") > 0


def test_pallas_path_refuses_a_mesh_of_several_devices():
    mesh = build_mesh(MeshSpec(dp=2), devices=jax.devices()[:2])
    cfg = CFG.replace(gmm_impl="pallas")
    params, tokens = setup(cfg)
    with pytest.raises(NotImplementedError, match="one device"):
        moe.loss_fn(params, {"tokens": tokens}, cfg, mesh=mesh,
                    rules=ShardingRules.dp())


def test_train_step_hands_on_the_routing_statistics():
    """make_train_step takes (loss, aux): the expert model's statistics
    are in the step's metrics beside loss, grad_norm and step; a dense
    loss stays a scalar and its metrics are the three."""
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    rules = ShardingRules.dp()
    opt = optax.adamw(1e-2)
    params, tokens = setup()
    batch = {"tokens": tokens}
    init_fn, state_sh = make_train_state_init(
        lambda k: moe.init_params(k, CFG), opt, mesh, rules,
        moe.param_specs(CFG))
    step = make_train_step(lambda p, b: moe.loss_fn(p, b, CFG), opt, mesh,
                           rules, state_sh)
    _, m = step(init_fn(jax.random.PRNGKey(0)), batch)
    assert set(m) == {"loss", "grad_norm", "step", "moe_aux_loss",
                      "moe_z_loss", "moe_load_max_over_mean", "moe_dropped",
                      "moe_remat_kept_gb"}
    assert int(m["moe_dropped"]) == 0
    assert float(m["moe_remat_kept_gb"]) == 0.0     # CFG has no checkpoint
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    dcfg = llama.PRESETS["tiny"].replace(dtype=jnp.float32)
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, dcfg), opt, mesh, rules,
        llama.param_specs(dcfg))
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, dcfg), opt, mesh,
                           rules, state_sh)
    _, m = step(init_fn(jax.random.PRNGKey(0)), batch)
    assert set(m) == {"loss", "grad_norm", "step"}


def test_moe_routing_shapes_and_grads():
    cfg = moe.PRESETS["tiny"].replace(dtype=jnp.float32, remat=False)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits = moe.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss, aux = moe.loss_fn(params, {"tokens": tokens}, cfg)
    assert float(aux["moe_aux_loss"]) > 0
    g = jax.grad(lambda p: moe.loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
    flat = jax.tree.leaves(g)
    assert all(np.isfinite(np.asarray(x)).all() for x in flat)
    # router must receive gradient (load balancing + gating paths)
    assert float(jnp.abs(g["layers"]["router"]).sum()) > 0


def test_moe_expert_parallel_training():
    """EP preset: experts sharded over (dp, fsdp); training step runs on the
    8-device mesh (the grouped matmul's XLA path under GSPMD) and the loss
    decreases."""
    cfg = moe.PRESETS["tiny"].replace(dtype=jnp.float32, remat=False)
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = ShardingRules.ep()
    opt = optax.adamw(1e-2)
    init_fn, state_sh = make_train_state_init(
        lambda k: moe.init_params(k, cfg), opt, mesh, rules,
        moe.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    step = make_train_step(
        lambda p, b: moe.loss_fn(p, b, cfg, mesh=mesh, rules=rules), opt,
        mesh, rules, state_sh, batch_shapes=jax.eval_shape(lambda: batch))
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    assert int(m["moe_dropped"]) == 0


def test_moe_expert_parallel_on_four_devices():
    """The same under MeshSpec(fsdp=2, tp=2) on four virtual devices:
    experts over fsdp, each expert's width over tp."""
    cfg = moe.PRESETS["tiny"].replace(dtype=jnp.float32, remat=True)
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    rules = ShardingRules.ep()
    opt = optax.adamw(1e-2)
    init_fn, state_sh = make_train_state_init(
        lambda k: moe.init_params(k, cfg), opt, mesh, rules,
        moe.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                          cfg.vocab_size)}
    step = make_train_step(
        lambda p, b: moe.loss_fn(p, b, cfg, mesh=mesh, rules=rules), opt,
        mesh, rules, state_sh, batch_shapes=jax.eval_shape(lambda: batch))
    # one device's loss on the same weights and batch
    want = float(moe.loss_fn(jax.device_get(state.params), batch, cfg)[0])
    state, m = step(state, batch)
    np.testing.assert_allclose(float(m["loss"]), want, rtol=1e-5)
    first = float(m["loss"])
    for _ in range(4):
        state, m = step(state, batch)
    assert float(m["loss"]) < first


def test_presets_and_parameter_count():
    cfg = moe.PRESETS["olmoe-1b-7b"]
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_experts, cfg.top_k,
            cfg.d_ff, cfg.vocab_size, cfg.n_layers) == (
        2048, 16, 128, 64, 8, 1024, 50304, 16)
    assert cfg.qk_norm and not cfg.norm_topk
    assert 6.9e9 < moe.num_params(cfg) < 7.0e9
    tiny = moe.PRESETS["tiny"]
    shapes = jax.eval_shape(lambda: moe.init_params(jax.random.PRNGKey(0),
                                                    tiny))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == moe.num_params(tiny)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        moe.param_specs(tiny), is_leaf=lambda x: isinstance(x, tuple))
    assert not hasattr(tiny, "capacity_factor")


# --- what decides ``correct`` in the benchmark's train_moe cells -----------
# (benchmark/kinds/train_moe.py: routes against the reference's own, then the
# reference on the program's routes). Limits as a cell sets them, a few times
# the agreement measured (here on the CPU at the toy size: exact in float32;
# in bf16 per-token mean 0.0059, 99.9th percentile 0.028, route gap 4.2e-4,
# 1.0% of (token, layer) pairs differing).
CHECK = {"float32": {"route_gap_max": 1e-6, "route_differ_share": 0.0,
                     "token_mean_abs": 1e-4, "token_p999_abs": 1e-3},
         "bfloat16": {"route_gap_max": 1.5e-3, "route_differ_share": 0.04,
                      "token_mean_abs": 0.012, "token_p999_abs": 0.06}}
WRONG = ["zeroed expert", "one expert fewer", "renormalised weights",
         "missing q/k norm", "dropped tokens", "8-bit expert weights"]


def _two_part_check(dtype, wrong="", monkeypatch=None):
    from benchmark import model_moe, resolve
    from benchmark.kinds import train_moe

    conf = dict(resolve.config("tiny-olmoe"),
                run={"dtype": dtype, "param_dtype": dtype})
    sizes = model_moe.sizes(conf)
    cfg = model_moe.moe_config(conf, attn_impl="xla")
    params = moe.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 257), 0,
                                cfg.vocab_size, "int32")
    run_params, run_cfg, layers = params, cfg, params["layers"]
    if wrong == "zeroed expert":
        run_params = dict(params, layers=dict(
            layers, we_down=layers["we_down"].at[:, 0].set(0)))
    elif wrong == "one expert fewer":
        run_cfg = cfg.replace(top_k=cfg.top_k - 1)
    elif wrong == "renormalised weights":
        run_cfg = cfg.replace(norm_topk=True)
    elif wrong == "missing q/k norm":
        # scales away from 1, as a trained model has them
        layers = dict(layers, q_norm=layers["q_norm"] * 1.5,
                      k_norm=layers["k_norm"] * 0.7)
        params = run_params = dict(params, layers=layers)
        run_cfg = cfg.replace(qk_norm=False)
    elif wrong == "dropped tokens":
        # what a capacity limit does: some assignments add nothing (here
        # every fourth token's last choice), the routes reported as chosen
        real = moe._down_combine

        def dropping(impl, h, w_down, weights, *rest):
            return real(impl, h, w_down, weights.at[::4, -1].set(0.0), *rest)

        monkeypatch.setattr(moe, "_down_combine", dropping)
    elif wrong == "8-bit expert weights":
        run_params = dict(params, layers={
            k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
                if k.startswith("we_") else w) for k, w in layers.items()})
    program, _ = train_moe.token_loss_fns(run_cfg, sizes)
    _, reference = train_moe.token_loss_fns(cfg, sizes)
    got, routes = program(run_params, tokens)
    ref, _, rec = reference(params, tokens, routes)
    a = train_moe.loss_agreement(got, ref)
    r = train_moe.route_agreement(routes, rec, cfg.top_k)
    tol = CHECK[dtype]
    checks = {**train_moe.route_checks(r, tol, cfg.top_k),
              "mean": a["token_mean_abs"] <= tol["token_mean_abs"],
              "p999": a["token_p999_abs"] <= tol["token_p999_abs"]}
    return checks, a, r


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_two_part_check_passes_the_program(dtype):
    checks, a, r = _two_part_check(dtype)
    assert all(checks.values()), (checks, a, r)
    if dtype == "bfloat16":
        # bf16 does swap near-tied experts: the check is exercised
        assert r["differ_share"] > 0 and r["gap_max"] > 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("wrong", WRONG)
def test_two_part_check_fails_a_wrong_model(dtype, wrong, monkeypatch):
    checks, a, r = _two_part_check(dtype, wrong, monkeypatch)
    assert not all(checks.values()), (checks, a, r)
