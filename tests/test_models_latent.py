"""The latent-attention family (models/latent.py) against its plain
reference (models/reference_glm.py) on seeded weights; latent attention
through the flash kernels at a head of 256 on every block plan; a chip's
share of the experts under the sigmoid router against the uncut layer; the
rule that moves the router's bias, through ``make_train_step``; and what
the shared code (llama.py, moe.py, flash, the train step) was given for
it."""

import dataclasses
import functools
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import (cached, latent, llama, moe, reference_glm,
                            registry)
from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh
from ray_tpu.parallel.train_step import (hold_out, make_train_state_init,
                                         make_train_step)


def _fa():
    return sys.modules["ray_tpu.ops.flash_attention"]


def ref_cfg(cfg) -> dict:
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def tiny(**kw):
    return latent.PRESETS["tiny"].replace(
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def _stacks(params):
    return list(params["layers"]) + [params["mtp"]["block"]]


def make(cfg, seed=0, batch=2, seq=128):
    """Seeded weights with the norms and the routers' biases drawn (ones
    and zeros hide a wrong index, and a zero bias chooses nothing), and
    tokens [batch, seq + 2]."""
    params = latent.init_params(jax.random.PRNGKey(seed), cfg)
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for stack in _stacks(params):
        for name in ("attn_norm", "ffn_norm", "q_a_norm", "kv_a_norm"):
            stack[name] = stack[name] + 0.3 * jax.random.normal(
                next(key), stack[name].shape)
        if "router_bias" in stack:
            stack["router_bias"] = 0.1 * jax.random.normal(
                next(key), stack["router_bias"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                (batch, seq + 2), 0, cfg.vocab_size, "int32")
    return params, tokens


def test_layer_runs_and_parameter_tree():
    cfg = tiny()
    assert latent.layer_runs(cfg) == [("dense", 1), ("sparse", 2)]
    assert latent.layer_runs(cfg.replace(n_dense=0)) == [("sparse", 3)]
    assert cfg.head_dim == 32 and cfg.rope_dim == 8
    assert registry.get("latent", "tiny")[1] is latent
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    specs = latent.param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple))
    for leaf, spec in zip(jax.tree.leaves(params), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, tuple))):
        assert leaf.ndim == len(spec), (leaf.shape, spec)
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == latent.num_params(cfg)
    dense, sparse = params["layers"]
    assert "w_gate" in dense and "router" not in dense
    assert sparse["router"].shape == (2, 64, 8)
    assert params["mtp"]["eh_proj"].shape == (128, 64)
    # llama's three projections of the hidden state are gone
    assert not {"wq", "wk", "wv"} & set(dense) | {"wq", "wk"} & set(sparse)
    assert sparse["wkv_b"].shape == (2, 16, 4 * (24 + 32))
    # the bias is float32 whatever the weights are
    half = latent.init_params(jax.random.PRNGKey(0), cfg.replace(
        param_dtype=jnp.bfloat16))
    assert half["layers"][1]["router_bias"].dtype == jnp.float32
    assert half["layers"][1]["router"].dtype == jnp.bfloat16
    assert "mtp" not in latent.init_params(jax.random.PRNGKey(0),
                                           cfg.replace(n_mtp=0))
    # narrower value heads are padded up to the kernel's width (PR 58:
    # tests/test_models_ling.py holds them to the reference); wider ones
    # have no such form
    assert tiny(v_dim=24).v_dim == 24
    with pytest.raises(NotImplementedError, match="one width"):
        tiny(v_dim=40)


def test_the_cells_count_of_parameters():
    from benchmark import flops_glm, model_glm, resolve

    conf = resolve.config("glm-4.7-flash-ep8-l12")
    cfg = model_glm.latent_config(conf)
    shapes = jax.eval_shape(
        lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == latent.num_params(cfg) \
        == flops_glm.total_params(model_glm.sizes(conf))
    assert abs(total / 1e9 - 1.454) < 1e-3
    # the published model: 47 layers, every expert, the whole vocabulary
    full = cfg.replace(n_layers=47, experts_held=None, vocab_size=154880)
    assert abs(latent.num_params(full) / 1e9 - 30.59) < 0.01  # "30B-A3B"


@pytest.mark.parametrize("held,attn,gmm", [
    (None, "xla", "xla"), ((4, 2), "xla", "xla"), ((4, 2), "flash", "pallas")],
    ids=["every-expert", "held-4-of-8", "held-kernels"])
def test_model_against_the_plain_reference(held, attn, gmm):
    """Forward, the three terms of the loss, the counts the rule reads and
    every gradient, under the layer checkpoint."""
    cfg = tiny(experts_held=held, attn_impl=attn, gmm_impl=gmm, remat=True)
    params, tokens = make(cfg)
    if held is not None:       # the tree holds the held experts alone
        assert params["layers"][1]["we_up"].shape[:2] == (2, held[0])
    (loss, aux), grads = jax.value_and_grad(
        lambda p: latent.loss_fn(p, {"tokens": tokens}, cfg),
        has_aux=True)(params)
    (want, parts), want_grads = jax.value_and_grad(
        lambda p: reference_glm.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True)(params)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(aux["moe_main_loss"], parts["main"], rtol=2e-6)
    np.testing.assert_allclose(aux["moe_mtp_loss"], parts["mtp"], rtol=2e-6)
    np.testing.assert_allclose(aux["moe_aux_loss"], parts["balance"],
                               rtol=2e-6)
    # three terms, each with its weight
    np.testing.assert_allclose(
        loss, aux["moe_main_loss"] + 0.3 * aux["moe_mtp_loss"]
        + 0.0001 * aux["moe_aux_loss"], rtol=1e-6)
    assert aux["router_counts"].shape == (3, 8)     # the module's block too
    np.testing.assert_array_equal(aux["router_counts"], parts["counts"])
    assert int(aux["moe_dropped"]) == 0
    err = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12)),
        grads, want_grads)
    flat = {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(err)}
    # nothing is learned through the bias: the selection has no gradient
    bias = [k for k in flat if "router_bias" in k]
    assert len(bias) == 2 and all(flat[k] == 0.0 for k in bias)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in (
        grads["layers"][1]["router_bias"],
        grads["mtp"]["block"]["router_bias"]))
    assert max(flat.values()) < 2e-4, sorted(flat.items(),
                                             key=lambda kv: -kv[1])[:5]
    # every weight of the prediction module is reached
    assert all(float(jnp.abs(g).max()) > 0 for k, g in
               jax.tree_util.tree_leaves_with_path(grads["mtp"])
               if "router_bias" not in jax.tree_util.keystr(k))
    # the per-token losses the benchmark's check reads
    main, ahead, stats = latent.token_losses(params, tokens, cfg)
    ref_main, ref_ahead, rec = reference_glm.token_losses(
        params, tokens, ref_cfg(cfg))
    np.testing.assert_allclose(main, ref_main, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ahead, ref_ahead, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(
        np.sort(stats["experts"].reshape(3, 2, 128, 2), -1),
        np.sort(rec["experts"], -1))


def test_the_loss_takes_two_ids_more_and_no_mask():
    cfg = tiny()
    params, tokens = make(cfg, seq=16)
    with pytest.raises(ValueError, match="no mask"):
        latent.loss_fn(params, {"tokens": tokens,
                                "mask": jnp.ones_like(tokens)}, cfg)
    with pytest.raises(ValueError, match="no mask"):
        latent.loss_fn(params, {"inputs": tokens, "targets": tokens}, cfg)
    # without a prediction module one id more, as every other family
    bare = cfg.replace(n_mtp=0)
    p = latent.init_params(jax.random.PRNGKey(0), bare)
    loss, aux = latent.loss_fn(p, {"tokens": tokens[:, :17]}, bare)
    assert float(aux["moe_mtp_loss"]) == 0.0
    np.testing.assert_allclose(
        loss, aux["moe_main_loss"] + 0.0001 * aux["moe_aux_loss"], rtol=1e-6)
    assert aux["router_counts"].shape == (2, 8)
    # the cached paths know three projections of the hidden state only
    with pytest.raises(NotImplementedError, match="attention half"):
        cached._refuse_stated(cfg)


# --- latent attention through the flash kernels at a head of 256 -----------
WIDE = dict(d_model=64, n_heads=2, n_kv_heads=2, q_rank=32, kv_rank=16,
            qk_nope_dim=192, qk_rope_dim=64, v_dim=256, attn_impl="flash")


def _force(monkeypatch, fwd_dq: str, dkdv: str, block: int = 32):
    """The plans are chosen by bytes: give the chooser the bytes that make
    the choice, and the kernels blocks of which S = 128 holds four."""
    fa = _fa()
    monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES",
                        {"loop": 2 ** 40, "stream": 0}[fwd_dq])
    monkeypatch.setattr(fa, "_vmem_bytes", lambda: {
        "resident": 128 * 2 ** 20, "stream": 1024}[dkdv])
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, block_q=block, block_k=block))


@pytest.mark.parametrize("dkdv", ["resident", "stream"])
@pytest.mark.parametrize("fwd_dq", ["loop", "stream"])
def test_latent_attention_through_flash_at_a_head_of_256(fwd_dq, dkdv,
                                                         monkeypatch):
    """The attention half (interpret mode) against the reference's explicit
    softmax built head by head from the latents, output and gradients, on
    both forward kernels, both dQ plans and both dK/dV plans."""
    seen = []
    monkeypatch.setattr(_fa().tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, attrs)))
    _force(monkeypatch, fwd_dq, dkdv)
    cfg = tiny(**WIDE)
    params, _ = make(cfg, seed=3)
    lp = jax.tree.map(lambda w: w[0], params["layers"][0])
    S = 128
    x = jax.random.normal(jax.random.PRNGKey(4), (1, S, cfg.d_model))
    probe = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    cos, sin = llama._pair_tables(cfg.rope_theta, S, cfg.rope_dim)

    def mine(x, lp):
        y = latent.attention_half(x, lp, cfg, cos, sin)[0]
        return jnp.sum(y * probe), y

    def plain(x, lp):
        h = reference_glm._rms(x[0], lp["attn_norm"], cfg.norm_eps)
        with jax.default_matmul_precision("highest"):
            y = x[0] + reference_glm._attention(h, lp, ref_cfg(cfg), 32)
        return jnp.sum(y * probe[0]), y

    (_, y), got = jax.value_and_grad(mine, (0, 1), has_aux=True)(x, lp)
    (_, want_y), want = jax.value_and_grad(plain, (0, 1), has_aux=True)(x, lp)
    np.testing.assert_allclose(y[0], want_y, rtol=2e-4, atol=2e-4)
    for name in ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b",
                 "wo", "attn_norm"):
        scale = float(jnp.abs(want[1][name]).max())
        np.testing.assert_allclose(got[1][name], want[1][name],
                                   rtol=2e-3, atol=2e-4 * scale, err_msg=name)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3, atol=2e-4)
    plans = {n: a for n, a in seen}
    assert plans["mla.plan"] == {
        "S": S, "heads": 2, "heads_held": 2, "qk_nope": 192, "qk_rope": 64,
        "v_dim": 256,
        "q_rank": 32, "kv_rank": 16, "form": "expanded",
        "k_bytes": S * 2 * 256 * 4, "rope": "projected",
        "kv": "split_weights", "extra_columns": 3 * 64,
        "zero_columns": 2 * 64,
        # q from its two products, k from its product and the key; the
        # kernel's float32 dq, dk, dv read once each
        "hbm_bytes_fwd": 4 * (4 * S * 2 * 256 + S * 64 * 6),
        "hbm_bytes_bwd": (12 + 12) * S * 2 * 256 + 4 * S * 64 * 3}
    # four blocks of 32 keys. loop: one span, the forward one block at a
    # time, the dQ call two; stream with no byte to spend (`_force`): one
    # block a grid step, the parent's walk. The grids of the two heads: a
    # step a q-block, or one for each of T's four blocks, of which the
    # causal triangle's ten a head hold a block of a band
    steps = {"loop": (8, 8), "stream": (32, 20)}
    assert plans["flash.fwd_plan"] == {
        "path": fwd_dq, "S": S, "D": 256,
        "kv_block_bytes": 2 * 2 * S * 256 * 4,
        "span": {"loop": S, "stream": 32}[fwd_dq], "in_flight": 1,
        "grid_steps": steps[fwd_dq][0], "band_steps": steps[fwd_dq][1],
        "whole_steps": 0}           # a span of one block is not written out
    back = plans["flash.bwd_plan"]
    assert back["path"] == dkdv
    assert (back["dq_path"], back["dq_span"], back["dq_in_flight"]) == {
        "loop": ("loop", S, 2), "stream": ("stream", 32, 1)}[fwd_dq]
    assert (back["dq_grid_steps"], back["dq_band_steps"]) == steps[fwd_dq]
    # the streamed dK/dV call holds what the same bytes leave it: all four
    # q-blocks a grid step, two in flight, or one
    assert (back["grid_steps"], back["band_steps"]) == {
        "resident": (8, 8), "stream": steps[fwd_dq]}[dkdv]
    assert (back["span"], back["in_flight"]) == (
        (S, 1) if dkdv == "resident" else
        {"loop": (S, 2), "stream": (32, 1)}[fwd_dq])


def _published_half(x, lp, cfg, cos, sin):
    """The attention half in the published form (transformers'
    ``deepseek_v3`` block, as ``models/latent.py`` ran it until the
    projections were arranged for the kernel): q and k built head by head,
    the rotary as a turn of interleaved pairs, K and V sliced out of
    ``c_kv wkv_b``."""
    B, S, _ = x.shape
    H, dn, dv, R = cfg.n_heads, cfg.qk_nope_dim, cfg.v_dim, cfg.qk_rope_dim
    w = lambda name: lp[name].astype(cfg.dtype)                # noqa: E731

    def turn(t):                   # [B, S, N, R]: lanes (2i, 2i+1) by angle i
        pairs = t.astype(jnp.float32).reshape(*t.shape[:-1], R // 2, 2)
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * c - b * s, b * c + a * s],
                         axis=-1).reshape(t.shape).astype(t.dtype)

    h = llama.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    c_q = llama.rms_norm(h @ w("wq_a"), lp["q_a_norm"], cfg.norm_eps)
    q = (c_q @ w("wq_b")).reshape(B, S, H, dn + R)
    c_kv = h @ w("wkv_a")
    k_r = turn(c_kv[..., None, cfg.kv_rank:])
    c_kv = llama.rms_norm(c_kv[..., :cfg.kv_rank], lp["kv_a_norm"],
                          cfg.norm_eps)
    kv = (c_kv @ w("wkv_b")).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r, (B, S, H, R))], axis=-1)
    out = llama._attention_xla(q, k, kv[..., dn:], causal=True)
    return x + out.reshape(B, S, H * dv) @ w("wo")


HALF_WIDTHS = {
    "tiny": {},
    "3-heads-of-24+8": dict(n_heads=3, n_kv_heads=3, qk_nope_dim=24,
                            qk_rope_dim=8, v_dim=32),
    "rope-12": dict(n_heads=4, n_kv_heads=4, qk_nope_dim=20, qk_rope_dim=12,
                    v_dim=32, q_rank=24, kv_rank=20),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("widths", sorted(HALF_WIDTHS))
def test_the_attention_half_is_the_published_form(widths, dtype, tol):
    """The projections arranged for the kernel (the rotary by a second
    projection of swapped columns, K and V from their own columns of
    ``wkv_b``) against the published form above: the output and the
    gradients of x and of all seven weights, from the STORED weights in
    their published order."""
    dt = jnp.dtype(dtype)
    cfg = latent.PRESETS["tiny"].replace(
        dtype=dt, param_dtype=dt, attn_impl="xla", **HALF_WIDTHS[widths])
    lp = {k: v[0] for k, v in latent._mla_params(
        jax.random.PRNGKey(3), cfg, 1).items()}
    lp["attn_norm"] = jnp.ones((cfg.d_model,), dt)
    for i, name in enumerate(("attn_norm", "q_a_norm", "kv_a_norm")):
        lp[name] = lp[name] + 0.3 * jax.random.normal(
            jax.random.PRNGKey(10 + i), lp[name].shape, dt)
    B, S = 2, 48
    x = jax.random.normal(jax.random.PRNGKey(5), (B, S, cfg.d_model), dt)
    probe = jax.random.normal(jax.random.PRNGKey(6), x.shape, jnp.float32)
    cos, sin = llama._pair_tables(cfg.rope_theta, S, cfg.rope_dim)

    def run(half):
        def f(x, lp):
            y = half(x, lp, cfg, cos, sin)
            return jnp.sum(y.astype(jnp.float32) * probe), y
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))(x, lp)

    # the half also returns what it hands on and reports: nothing here
    (_, y), (gx, glp) = run(
        lambda *a: latent.attention_half(*a)[0])
    assert latent.attention_half(x, lp, cfg, cos, sin)[1:] == (None, None)
    (_, want_y), (want_gx, want_glp) = run(_published_half)
    assert y.dtype == dt and set(glp) == set(want_glp) and len(glp) == 8

    def close(a, b, name):
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), (
            name, np.abs(a - b).max(), np.abs(b).max())

    close(y, want_y, "y")
    close(gx, want_gx, "x")
    for name in want_glp:
        assert glp[name].shape == lp[name].shape, name
        close(glp[name], want_glp[name], name)


def _explicit(q, k, v):
    s = jnp.einsum("bshd,bthd->bhst", q, k) / q.shape[-1] ** 0.5
    keep = jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])[None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v)


@pytest.mark.parametrize("mask", ["causal", "window64"])
def test_the_block_plans_agree_at_a_head_of_256(mask):
    """Kernel by kernel at D 256: the forward's two kernels, the dQ call's
    two plans and the dK/dV call's two run the same float32 sums in the
    same order, so each pair agrees to the last bit (dQ) or to 1e-6; and
    each against the explicit softmax and jax's gradient of it."""
    fa = _fa()
    B, S, H, D, block = 1, 128, 2, 256, 32
    window = 64 if mask == "window64" else 0
    key = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, g = (jax.random.normal(kk, (B, S, H, D)) * 0.5 for kk in key)
    kw = dict(causal=True, block_q=block, block_k=block, scale=D ** -0.5)
    # spans of two of the four k-blocks, walked two at a time
    stream = dict(path="stream", span=2 * block, in_flight=2)
    loop = dict(path="loop", span=S, in_flight=2)
    streamed, lse_s = fa._flash_fwd(q, k, v, window=window, plan=stream, **kw)
    if not window:
        looped, lse_l = fa._flash_fwd(q, k, v, plan=loop, **kw)
        np.testing.assert_allclose(looped, streamed, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(lse_l, lse_s, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(streamed, _explicit(q, k, v), rtol=2e-5,
                                   atol=2e-5)
    t = lambda x: x.transpose(0, 2, 1, 3)                      # noqa: E731
    args = (t(q), t(k), t(v), t(g), t(streamed), lse_s)   # lse [B, H, S, 128]
    dq = {plan["path"]: fa._flash_bwd_dq(
        *args, plan=plan, causal=True, block_q=block, block_k=block,
        window=window, scale=D ** -0.5) for plan in (loop, stream)}
    np.testing.assert_array_equal(dq["loop"], dq["stream"])
    dkdv = {path: fa._flash_bwd_dkdv(
        *args, causal=True, block_q=block, block_k=block, window=window,
        scale=D ** -0.5, vmem_bytes=vmem)
        for path, vmem in (("resident", 128 * 2 ** 20), ("stream", 1024))}
    for a, b in zip(dkdv["resident"], dkdv["stream"]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    if not window:
        want = jax.grad(lambda q, k, v: jnp.sum(_explicit(q, k, v) * g),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b in zip((dq["stream"],) + tuple(dkdv["stream"]), want):
            np.testing.assert_allclose(t(a), b, rtol=2e-4, atol=2e-4)


def test_the_plans_at_the_cells_shape_and_at_the_other_cells():
    """By bytes alone: 20 heads of 256 at S 8192 stream in all three
    calls; every shape that ran before keeps the plan it had."""
    fa = _fa()
    bf = jnp.bfloat16
    glm = fa.kv_plan(S=8192, T=8192, D=256, dtype=bf, block_q=512,
                     block_k=512)
    assert glm["path"] == "stream" and glm["kv_block_bytes"] == 16 * 2 ** 20
    # half a head's keys a grid step (two spans a q-block where the
    # parent's grid had sixteen steps), two k-blocks a step of the walk;
    # the dQ call's four score-sized temporaries a block leave room for one
    assert (glm["span"], glm["in_flight"]) == (4096, 2)
    dq = fa.kv_plan(S=8192, T=8192, D=256, dtype=bf, block_q=512,
                    block_k=512, call="dq")
    assert (dq["path"], dq["span"], dq["in_flight"]) == ("stream", 4096, 1)
    assert fa.bwd_dkdv_plan(
        S=8192, T=8192, D=256, dtype=bf, groups=1, block_q=512, block_k=512,
        causal=True, window=0, vmem_bytes=fa._V5E_VMEM_BYTES
    )["path"] == "stream"
    # the dense and OLMoE cells (S 4096), the Granite cell (S 8192), all at
    # D 128: the loop kernels, and the resident dK/dV plan
    for S in (1024, 4096, 8192):
        assert fa.kv_plan(S=S, T=S, D=128, dtype=bf, block_q=512,
                          block_k=512)["path"] == "loop", S
        assert fa.bwd_dkdv_plan(
            S=S, T=S, D=128, dtype=bf, groups=1, block_q=512, block_k=512,
            causal=True, window=0, vmem_bytes=fa._V5E_VMEM_BYTES
        )["path"] == "resident", S
    # what the loop kernel used to refuse now streams
    assert fa.kv_plan(S=16384, T=16384, D=128, dtype=bf, block_q=512,
                      block_k=512)["path"] == "stream"


# --- a chip's share of the experts under the sigmoid router ----------------
def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test with a sigmoid router, its bias and its
    scale: over 8 chips of 2 experts each, the routed parts that the
    shares compute, with the shared expert (computed alike on every chip)
    counted once, are the uncut reference's layer output."""
    cfg = tiny(n_experts=16, top_k=4)
    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][1])
    assert float(jnp.abs(lp["router_bias"]).max()) > 0.01
    t = 192
    h = jax.random.normal(jax.random.PRNGKey(5), (1, t, cfg.d_model))
    lp32 = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
    with jax.default_matmul_precision("highest"):
        whole, rec = reference_glm._experts(h[0], lp32, ref_cfg(cfg), None)
    x = h[0]
    shared = (jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])) \
        @ lp["ws_down"]
    total, rows = shared, 0
    for share in range(8):
        first = 2 * share
        part = cfg.replace(experts_held=(2, first))
        mine = {k: (w[first:first + 2] if k.startswith("we_") else w)
                for k, w in lp.items()}
        y, stats = moe.feed_forward(h, mine, part)
        np.testing.assert_array_equal(stats["counts"], rec["counts"])
        rows += int(stats["held_counts"].sum())
        total = total + (y[0] - shared)
        with jax.default_matmul_precision("highest"):
            same, _ = reference_glm._experts(x, mine, ref_cfg(part), None)
        np.testing.assert_allclose(y[0], same, rtol=2e-4, atol=2e-5)
    assert rows == t * cfg.top_k          # every assignment on one chip
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    # the bias chooses: without it other experts are taken
    bare, _ = moe.feed_forward(h, dict(lp, router_bias=jnp.zeros(16)), cfg)
    assert float(jnp.abs(bare[0] - whole).max()) > 1e-3


def test_the_router_scores_choose_with_the_bias_and_weigh_without_it():
    cfg = tiny(n_experts=4, top_k=2, route_scale=1.8)
    logits = jnp.log(jnp.array([[0.6, 0.5, 0.3, 0.2]]) /
                     (1 - jnp.array([[0.6, 0.5, 0.3, 0.2]])))
    bias = jnp.array([0.0, -0.4, 0.25, 0.0])
    w, e, s, _ = moe.route(logits, cfg, bias)
    assert sorted(np.asarray(e[0]).tolist()) == [0, 2]
    np.testing.assert_allclose(s[0], [0.6, 0.5, 0.3, 0.2], rtol=1e-6)
    by_expert = dict(zip(np.asarray(e[0]).tolist(), np.asarray(w[0])))
    np.testing.assert_allclose(by_expert[0], 1.8 * 0.6 / 0.9, rtol=1e-6)
    np.testing.assert_allclose(by_expert[2], 1.8 * 0.3 / 0.9, rtol=1e-6)
    # the softmax router is what it was, bias or none
    soft = moe.PRESETS["tiny"].replace(n_experts=4, top_k=2)
    w0, e0, p0, _ = moe.route(logits, soft)
    np.testing.assert_allclose(p0, jax.nn.softmax(logits), rtol=1e-6)
    np.testing.assert_array_equal(e0, jax.lax.top_k(p0, 2)[1])
    with pytest.raises(ValueError, match="router_score"):
        moe.route(logits, soft.replace(router_score="tanh"))


def test_grouped_matmul_tiles_for_a_width_that_is_no_whole_number_of_them():
    """``ops/grouped_matmul.py`` ``_fit``: 1536 under a tile of 1024 is two
    tiles of 768 and not one and a half; the forward's N tile is halved
    where both operands, the result and its accumulator pass 15 MiB; every
    shape of the sweep (OLMoE's, Granite's) keeps its tiles."""
    from ray_tpu.ops import grouped_matmul as gm

    fit = gm._fit
    assert fit(gm.GMM_TILING, 16384, 2048, 1536, 2, halve_n=True) \
        == (256, 2048, 768)
    assert fit(gm.GMM_TILING, 16384, 1536, 2048, 2, halve_n=True) \
        == (256, 1536, 1024)
    assert fit(gm.TGMM_TILING, 16384, 2048, 1536) == (256, 1024, 768)
    for m, k, n in ((131072, 2048, 1024), (131072, 1024, 2048),
                    (40960, 4096, 768), (40960, 768, 4096)):
        assert fit(gm.GMM_TILING, m, k, n, 2, halve_n=True) \
            == (256, min(2048, k), min(2048, n)), (m, k, n)
        assert fit(gm.TGMM_TILING, m, k, n) \
            == (256, min(1024, k), min(1024, n)), (m, k, n)
    with pytest.raises(ValueError, match="row tile"):
        fit(gm.GMM_TILING, 300, 64, 64)
    # and the tiles give the plain product (interpret mode)
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 384))
    sizes = jnp.array([200, 312], jnp.int32)
    got = gm.grouped_matmul(x, w, sizes, impl="pallas")
    want = gm.grouped_matmul(x, w, sizes, impl="xla")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


# --- the rule that moves the bias, through the train step ------------------
def test_the_bias_rule_over_three_steps_and_the_optimizer_leaves_it_alone():
    """``make_train_step(post_update=...)`` with ``hold_out``: after every
    step the biases are the reference's rule on the reference's counts of
    that step's batch at that step's parameters, bit for bit; the
    optimizer holds no state for them and adds nothing to them."""
    cfg = tiny(experts_held=(4, 2), remat=True)
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    rules = ShardingRules.dp()
    opt = hold_out(optax.adafactor(3e-2), latent.RULE_LEAVES)
    init_fn, state_sh = make_train_state_init(
        lambda k: latent.init_params(k, cfg), opt, mesh, rules,
        latent.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))
    step = make_train_step(
        lambda p, b: latent.loss_fn(p, b, cfg), opt, mesh, rules, state_sh,
        post_update=lambda p, aux: latent.post_update(p, aux, cfg),
        donate=False)
    # no optimizer state shaped like a bias: adafactor's factors of a
    # [layers, experts] leaf would be [layers] and [experts] vectors
    n_state = len(jax.tree.leaves(state.opt_state))
    plain = optax.adafactor(3e-2).init(state.params)
    assert n_state < len(jax.tree.leaves(plain))
    want = reference_glm.biases(state.params)
    assert float(jnp.abs(want).max()) == 0.0 and want.shape == (3, 8)
    for i in range(3):
        tokens = jax.random.randint(jax.random.PRNGKey(10 + i), (2, 66), 0,
                                    cfg.vocab_size, "int32")
        _, parts = reference_glm.loss(state.params, tokens, ref_cfg(cfg))
        want = reference_glm.bias_update(want, parts["counts"], ref_cfg(cfg))
        before = state.params
        state, m = step(state, {"tokens": tokens})
        np.testing.assert_array_equal(reference_glm.biases(state.params),
                                      want)
        moved = int((reference_glm.biases(state.params)
                     != reference_glm.biases(before)).sum())
        assert float(m["moe_bias_moved"]) == moved > 0
        np.testing.assert_allclose(m["moe_bias_abs_max"],
                                   jnp.abs(want).max(), rtol=1e-6)
        # the optimizer moved the weights the gradient reaches
        assert float(jnp.abs(state.params["layers"][1]["router"]
                             - before["layers"][1]["router"]).max()) > 0
    assert float(jnp.abs(want).max()) <= 3 * cfg.bias_rate + 1e-9
    assert set(m) == {
        "loss", "grad_norm", "step", "moe_main_loss", "moe_mtp_loss",
        "moe_aux_loss", "moe_bias_abs_max", "moe_bias_moved",
        "moe_load_max_over_mean", "moe_held_rows_share",
        "moe_held_more_passes", "moe_held_walked_share",
        "moe_held_pass_live_share", "moe_held_share_max_over_even",
        "moe_held_further_pass_share",
        "moe_dropped", "moe_remat_kept_gb"}          # the counts are used up
    assert all(v.shape == () for v in m.values())


def test_a_step_without_a_rule_is_the_program_it_was():
    """A scalar loss and a (loss, aux) loss without ``post_update`` trace
    to the same jaxpr whether the argument is left out or None."""
    dcfg = llama.PRESETS["tiny"].replace(dtype=jnp.float32)
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    rules = ShardingRules.dp()
    opt = optax.adafactor(3e-4)
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, dcfg), opt, mesh, rules,
        llama.param_specs(dcfg))
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 17), jnp.int32)}
    texts = [re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(make_train_step(
        lambda p, b: llama.loss_fn(p, b, dcfg), opt, mesh, rules, state_sh,
        **kw))(state, batch))) for kw in ({}, {"post_update": None})]
    assert texts[0] == texts[1]


# --- what a trace says of the model ------------------------------------------
def test_layer_plan_says_two_kinds_in_two_runs(monkeypatch):
    from ray_tpu.util import tracing

    seen = []
    monkeypatch.setattr(tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, attrs)))
    cfg = tiny()
    params, tokens = make(cfg, seq=32)
    jax.make_jaxpr(lambda p: latent.loss_fn(p, {"tokens": tokens}, cfg)[0])(
        params)
    assert [a for n, a in seen if n == "hybrid.layer_plan"] == [
        {"kinds": 2, "runs": 2, "bodies": 2, "layers": 3,
         "pattern": "dense x1, sparse x2"}]
    # the dense body, the sparse body: the module's block is scanned by
    # the sparse body, not by a third
    assert [a["form"] for n, a in seen if n == "mla.plan"] == ["expanded"] * 2


def test_plans_read_back_from_a_profile_around_a_lowering(tmp_path,
                                                          monkeypatch):
    """``mla.plan``, ``flash.fwd_plan`` and ``flash.bwd_plan`` (with its
    ``dq_path``) are events of jax's profiler (util/tracing.py): a profile
    taken around a lowering holds them with their attributes."""
    import glob
    import os

    from jax.profiler import ProfileData

    _force(monkeypatch, "stream", "stream")
    cfg = tiny(**WIDE)
    params, tokens = make(cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        jax.jit(jax.grad(
            lambda p: latent.loss_fn(p, {"tokens": tokens}, cfg)[0])
                ).lower(params)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("mla.plan", "flash.fwd_plan",
                                  "flash.bwd_plan"):
                        events.setdefault(e.name, []).append(dict(e.stats))
    assert events["mla.plan"][0] == {
        "S": 128, "heads": 2, "heads_held": 2, "qk_nope": 192, "qk_rope": 64,
        "v_dim": 256,
        "q_rank": 32, "kv_rank": 16, "form": "expanded",
        "k_bytes": 2 * 128 * 2 * 256 * 4, "rope": "projected",
        "kv": "split_weights", "extra_columns": 192, "zero_columns": 128,
        "hbm_bytes_fwd": 4 * (4 * 256 * 2 * 256 + 256 * 64 * 6),
        "hbm_bytes_bwd": 24 * 256 * 2 * 256 + 4 * 256 * 64 * 3}
    assert events["flash.fwd_plan"][0] == {
        "path": "stream", "S": 128, "D": 256,
        "kv_block_bytes": 2 * 2 * 128 * 256 * 4, "span": 32, "in_flight": 1,
        # two sequences of two heads: 4 x 4 steps each, 10 in the triangle
        "grid_steps": 64, "band_steps": 40, "whole_steps": 0}
    back = events["flash.bwd_plan"][0]
    assert back["path"] == "stream" and back["dq_path"] == "stream"
    assert (back["dq_span"], back["dq_in_flight"]) == (32, 1)
    assert back["S"] == 128 and back["block_q"] == 32
