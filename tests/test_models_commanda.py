"""A parallel block (models/llama.py ``parallel_block``, ``norm`` "layer";
models/moe.py ``n_shared``, ``shared_combine``, a sigmoid router without
bias, ``tied_head``, ``run_layers``; an ``AttentionKind`` that turns no
tables beside one that pairs neighbouring lanes: Command A+'s block)
against the plain reference (models/reference_commanda.py) on seeded
weights; wrong models told from the right one; a chip's share of the
heads and of the experts against the uncut layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import cached, llama, moe, reference_commanda, registry
from ray_tpu.util import tracing


def ref_cfg(cfg) -> dict:
    """The reference's dict of a config with named kinds of layer."""
    d = dataclasses.asdict(cfg)
    d["kinds"] = {
        name: {"window": of.window,
               "rope_theta": (of.rope_theta or cfg.rope_theta)
               if of.rope else None}
        for name, of in cfg.attn_kinds}
    d["logit_scale"] = 1.0
    return d


def tiny(**kw):
    kw.setdefault("experts_held", None)
    return moe.PRESETS["tiny-commanda"].replace(dtype=jnp.float32, **kw)


def make(cfg, seed=0, batch=2, seq=96):
    params = moe.init_params(jax.random.PRNGKey(seed), cfg)
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for lay in params["layers"]:     # unit norms hide a wrong index
        lay["attn_norm"] = lay["attn_norm"] + 0.3 * jax.random.normal(
            next(key), lay["attn_norm"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                (batch, seq + 1), 0, cfg.vocab_size, "int32")
    return params, tokens


def test_parameter_tree_stacks_and_the_published_count():
    cfg = tiny()
    assert moe.layer_runs(cfg) == [("window", 3), ("full", 1)] * 2
    one = cfg.replace(run_layers=1)
    assert moe.layer_runs(one) == [
        (k, 1) for k in ("window", "window", "window", "full") * 2]
    assert moe.layer_runs(cfg.replace(run_layers=2)) == [
        ("window", 2), ("window", 1), ("full", 1)] * 2
    params, _ = make(one)
    assert len(params["layers"]) == 8
    lay = params["layers"][0]
    # ONE norm a layer, no bias leaf, the shared experts side by side, the
    # head tied: no ``ffn_norm``, no ``router_bias``, no ``lm_head``
    assert sorted(lay) == sorted([
        "attn_norm", "wq", "wk", "wv", "wo", "router", "we_gate", "we_up",
        "we_down", "ws_gate", "ws_up", "ws_down"])
    assert "lm_head" not in params
    assert lay["ws_gate"].shape == (1, 64, 2 * 32)
    assert lay["ws_down"].shape == (1, 2 * 32, 64)
    assert lay["wq"].shape == (1, 64, 8 * 16)
    for c in (cfg, one, tiny(experts_held=(2, 2))):
        p = moe.init_params(jax.random.PRNGKey(0), c)
        assert moe.num_params(c) == sum(x.size for x in jax.tree.leaves(p))
        spec = moe.param_specs(c)
        is_axes = lambda x: isinstance(x, tuple)     # noqa: E731
        assert jax.tree.structure(spec, is_leaf=is_axes) \
            == jax.tree.structure(p)
    # the published model: 32 layers of 142.61 M (attention) + 0.52 M
    # (router) + 128 x 50.33 M + 4 x 50.33 M and one 262,144 x 4096 table
    full, mod = registry.get("cohere2_moe", "command-a-plus")
    assert mod is moe and full.head_dim == 128
    attention = 2 * 4096 * 16384 + 2 * 4096 * 1024
    layer = attention + 4096 * 128 + 132 * 3 * 4096 * 4096 + 4096
    assert moe.num_params(full) == 32 * layer + 262144 * 4096 + 4096
    assert round(moe.num_params(full) / 1e9) == 218
    assert [n for _, n in moe.layer_runs(full)] == [3, 1] * 8


def test_the_rotary_on_neighbouring_lanes_by_hand():
    """Lanes (2i, 2i + 1) turn by position x theta ^ (-2i / dim); a kind
    with ``rope`` False has no tables at all."""
    cfg = tiny()
    window, full = (llama.attention_kind(cfg, k) for k in ("window", "full"))
    assert window.pairs == "neighbours" and not full.rope
    cos, sin = llama._kind_tables(cfg, window, 8)
    assert cos.shape == (8, 16)       # a pair's angle on both its lanes
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    got = np.asarray(llama.apply_rope(x, cos, sin, window.pairs))
    pos, i = 5, 3                                     # lanes 6 and 7
    angle = pos * 10000.0 ** (-2 * i / 16)
    a, b = float(x[0, pos, 1, 6]), float(x[0, pos, 1, 7])
    assert got[0, pos, 1, 6] == pytest.approx(
        a * np.cos(angle) - b * np.sin(angle), abs=1e-5)
    assert got[0, pos, 1, 7] == pytest.approx(
        b * np.cos(angle) + a * np.sin(angle), abs=1e-5)
    np.testing.assert_allclose(
        got[0], reference_commanda._rope(x[0], 10000.0), atol=1e-5)
    # the halves' pairing is another function of the same angles: cos | cos
    half = llama._kind_tables(cfg, dataclasses.replace(window, pairs="halves"),
                              8)
    assert half[0].shape == (8, 16)
    np.testing.assert_array_equal(half[0][:, :8], half[0][:, 8:])
    np.testing.assert_array_equal(half[0][:, :8], cos[:, ::2])
    assert float(jnp.max(jnp.abs(llama.apply_rope(x, *half) - got))) > 0.1
    with pytest.raises(ValueError, match="pairing"):
        llama._kind_tables(cfg, dataclasses.replace(window, pairs="odd"), 8)


def test_the_layer_norm_subtracts_the_mean():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) + 3.0
    g = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    got = llama.layer_norm(x, g, 1e-5)
    want = (x - x.mean(-1, keepdims=True)) / jnp.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(
        got, reference_commanda._layer_norm(x, g, 1e-5), atol=1e-5)
    assert float(jnp.max(jnp.abs(got - llama.rms_norm(x, g, 1e-5)))) > 0.5
    with pytest.raises(ValueError, match="norm"):
        llama._norm(x, g, tiny(norm="batch"))


@pytest.mark.parametrize("case", ["all", "share", "stacks", "flash"])
def test_model_against_the_plain_reference(case):
    """Logits, routes, the loss and one step's gradients; ``share``: 2 of
    the 8 experts held; ``stacks``: every layer a stack of its own;
    ``flash`` through the kernels (interpret mode) at a window that is no
    multiple of the block."""
    cfg = tiny(experts_held=(2, 2) if case == "share" else None, remat=True,
               run_layers=1 if case == "stacks" else 0)
    seq = 96
    if case == "flash":
        cfg = cfg.replace(attn_impl="flash", attn_kinds=(
            ("window", dataclasses.replace(cfg.attn_kinds[0][1], window=40)),
            cfg.attn_kinds[1]))
        seq = 128
    params, tokens = make(cfg, seq=seq)
    logits, stats = moe.forward_with_stats(params, tokens[:, :-1], cfg)
    want = [reference_commanda.forward(params, t[:-1], ref_cfg(cfg))
            for t in tokens]
    np.testing.assert_allclose(logits, jnp.stack([w[0] for w in want]),
                               rtol=2e-4, atol=2e-4)
    own = jnp.stack([w[1]["experts"] for w in want], axis=1)   # [L, B, S, K]
    got = stats["experts"].reshape(own.shape)
    assert bool(jnp.all(jnp.sort(got, -1) == jnp.sort(own, -1)))
    (loss, aux), grads = jax.value_and_grad(
        lambda p: moe.loss_fn(p, {"tokens": tokens}, cfg),
        has_aux=True)(params)
    (ref_loss, _), ref_grads = jax.value_and_grad(
        lambda p: reference_commanda.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True)(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5      # no router loss
    assert float(aux["moe_dropped"]) == 0 and float(aux["moe_z_loss"]) == 0
    flat = lambda t: jax.tree.leaves_with_path(t)   # noqa: E731
    for (path, g), (_, w) in zip(flat(grads), flat(ref_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-9
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


WRONG = ["as it is", "a serial block", "an RMS norm",
         "tables on the full layers", "no tables anywhere",
         "rotate_half pairing", "shared experts summed", "a softmax router",
         "window one short", "h // 2 for h // 4", "a held expert left out"]


def wrong_model(cfg, params, how):
    """``(config, parameters)`` of a program that is another model."""
    window, full = (of for _, of in cfg.attn_kinds)
    kinds = lambda w, f: (("window", w), ("full", f))        # noqa: E731
    if how == "a serial block":
        return cfg.replace(parallel_block=False), dict(params, layers=[
            dict(lay, ffn_norm=lay["attn_norm"]) for lay in params["layers"]])
    if how == "an RMS norm":
        return cfg.replace(norm="rms"), params
    if how == "tables on the full layers":
        return cfg.replace(attn_kinds=kinds(window, dataclasses.replace(
            full, rope=True, pairs="neighbours"))), params
    if how == "no tables anywhere":
        return cfg.replace(attn_kinds=kinds(dataclasses.replace(
            window, rope=False), full)), params
    if how == "rotate_half pairing":
        return cfg.replace(attn_kinds=kinds(dataclasses.replace(
            window, pairs="halves"), full)), params
    if how == "shared experts summed":
        return cfg.replace(shared_combine="sum"), params
    if how == "a softmax router":
        return cfg.replace(router_score="softmax"), params
    if how == "window one short":
        return cfg.replace(attn_kinds=kinds(dataclasses.replace(
            window, window=window.window - 1), full)), params
    if how == "h // 2 for h // 4":
        # query heads dealt round the KV heads: head h reads KV head h % 2
        # where the reference reads h // 4
        h, hd = cfg.n_heads, cfg.head_dim
        swap = jnp.asarray([0, 2, 4, 6, 1, 3, 5, 7])

        def regroup(lay):
            wq = lay["wq"].reshape(*lay["wq"].shape[:2], h, hd)[:, :, swap]
            wo = lay["wo"].reshape(-1, h, hd, cfg.d_model)[:, swap]
            return dict(lay, wq=wq.reshape(lay["wq"].shape),
                        wo=wo.reshape(lay["wo"].shape))

        return cfg, dict(params, layers=[regroup(lay)
                                         for lay in params["layers"]])
    if how == "a held expert left out":
        held, first = cfg.experts_held or (cfg.n_experts, 0)
        return cfg.replace(experts_held=(held - 1, first)), dict(
            params, layers=[dict(lay, **{w: lay[w][:, :held - 1] for w in (
                "we_gate", "we_up", "we_down")}) for lay in params["layers"]])
    assert how == "as it is", how
    return cfg, params


@pytest.mark.parametrize("how", WRONG)
def test_a_wrong_model_is_told_from_the_right_one(how):
    """Each wrong model's logits leave the reference's by far more than
    the right model's rounding."""
    cfg = tiny()
    params, tokens = make(cfg, batch=1)
    run_cfg, run_params = wrong_model(cfg, params, how)
    logits = moe.forward(run_params, tokens[:, :-1], run_cfg)
    want = reference_commanda.forward(params, tokens[0, :-1], ref_cfg(cfg))[0]
    apart = float(jnp.max(jnp.abs(logits[0] - want)))
    if how == "as it is":
        assert apart < 2e-4
    else:
        assert apart > 2e-2, apart


def test_the_shares_add_up_to_the_uncut_layer():
    """On one layer and one input: the attention outputs of the two head
    shares (4 query heads over 1 KV head each, with their rows of ``wo``),
    the shared experts counted ONCE, and the four expert shares (2 of 8
    each) add up to what the reference gives for the uncut layer."""
    cfg = tiny()
    params, _ = make(cfg)
    for stack, kind in ((0, "window"), (1, "full")):
        lp = jax.tree.map(lambda w: w[0], params["layers"][stack])
        x = jax.random.normal(jax.random.PRNGKey(5 + stack),
                              (1, 64, cfg.d_model))
        whole, rec = reference_commanda.layer(
            x[0], lp, ref_cfg(cfg), ref_cfg(cfg)["kinds"][kind])
        n = llama.layer_norm(x, lp["attn_norm"], cfg.norm_eps)
        cos, sin = (None, None) if kind == "full" else llama._kind_tables(
            cfg, llama.attention_kind(cfg, kind), 64)
        total = x[0]
        hd, per = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
        for g in range(cfg.n_kv_heads):         # a head share a KV head
            share = cfg.replace(n_heads=per, n_kv_heads=1)
            q_cols = slice(g * per * hd, (g + 1) * per * hd)
            kv_cols = slice(g * hd, (g + 1) * hd)
            mine = dict(lp, wq=lp["wq"][:, q_cols], wk=lp["wk"][:, kv_cols],
                        wv=lp["wv"][:, kv_cols], wo=lp["wo"][q_cols])
            a = llama._attention_half(x, mine, share, cos, sin, kind=kind,
                                      normed=n)
            total = total + a[0]
        rows = 0
        for first in (0, 2, 4, 6):              # an expert share
            share = cfg.replace(experts_held=(2, first), shared_d_ff=0)
            mine = dict(lp, **{w: lp[w][first:first + 2]
                               for w in ("we_gate", "we_up", "we_down")})
            y, stats = moe.feed_forward(n, mine, share)
            total = total + y[0]
            rows += int(stats["held_counts"].sum())
        # what every chip computes alike, once
        only_shared = cfg.replace(experts_held=(0, 0))
        none = dict(lp, **{w: lp[w][:0]
                           for w in ("we_gate", "we_up", "we_down")})
        total = total + reference_commanda.shared(
            n[0], jax.tree.map(lambda w: w.astype(jnp.float32), none),
            ref_cfg(only_shared))
        np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-4)
        assert rows == 64 * cfg.top_k == int(rec["counts"].sum())
    # the program's shared experts are the reference's, averaged
    y, _ = moe.feed_forward(n, mine, cfg.replace(experts_held=(2, 6)))
    y0, _ = moe.feed_forward(n, mine, cfg.replace(experts_held=(2, 6),
                                                  shared_d_ff=0))
    np.testing.assert_allclose(
        (y - y0)[0], reference_commanda.shared(n[0], lp, ref_cfg(cfg)),
        rtol=2e-4, atol=2e-5)


def test_one_layer_one_attention_half_and_the_plans(monkeypatch):
    """Serial and parallel blocks run ``llama._layer`` and ONE
    ``llama._attention_half`` (a parallel block hands it the normed
    input); the norm and the sum lie under the scope ``block``, the
    shared experts under ``feed_forward/shared``; a traced forward says
    ``block.plan`` once and each kind's plan once."""
    cfg = tiny(remat=True)
    params, tokens = make(cfg)
    seen, said = [], []
    real = llama._attention_half

    def half(*a, kind=None, normed=None, **kw):
        seen.append((kind, normed is not None))
        return real(*a, kind=kind, normed=normed, **kw)

    monkeypatch.setattr(llama, "_attention_half", half)
    monkeypatch.setattr(tracing, "instant", lambda name, attrs=None, **kw:
                        said.append((name, attrs)))
    text = jax.jit(lambda p: moe.forward(p, tokens[:, :-1], cfg)).lower(
        params).as_text(debug_info=True)
    assert set(seen) == {("window", True), ("full", True)}
    for scope in ("attention/window", "attention/full", "layers/",
                  "feed_forward/shared", "block"):
        assert scope in text, scope
    assert [a for n, a in said if n == "block.plan"] == [{
        "residual": "parallel", "norm": "layer", "shared_experts": 2,
        "shared_combine": "average", "shared_width": 32}]
    by = {a["kind"]: a for n, a in said if n == "attn.kind_plan"}
    assert sorted(by) == ["full", "window"]
    assert by["window"]["rope"] == "gptj" and by["full"]["rope"] == "none"
    assert by["window"]["window"] == 24 and by["full"]["window"] == 0
    assert by["full"]["groups"] == 4 and by["full"]["kv_heads"] == 2
    layer = [a for n, a in said if n == "hybrid.layer_plan"]
    assert layer == [{"kinds": 2, "runs": 4, "bodies": 2, "layers": 8,
                      "pattern": "window x3, full x1, window x3, full x1"}]
    # a serial model says no block plan and hands no normed input
    seen.clear(), said.clear()
    serial = moe.PRESETS["tiny-mellum"].replace(dtype=jnp.float32)
    p2 = moe.init_params(jax.random.PRNGKey(0), serial)
    jax.jit(lambda p: moe.forward(p, tokens[:, :-1], serial)).lower(p2)
    assert set(seen) == {("window", False), ("full", False)}
    assert not [a for n, a in said if n == "block.plan"]


def test_the_router_without_a_bias():
    cfg = tiny()
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w, e, s, _ = moe.route(logits, cfg)
    np.testing.assert_allclose(s, jax.nn.sigmoid(logits), atol=1e-6)
    top = jnp.sort(s, -1)[:, -2:].sum(-1)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        w.max(-1), jnp.max(s, -1) / top, atol=1e-6)
    # with a bias leaf (GLM's) the bias chooses and does not weigh
    biased = cfg.replace(router_bias=True)
    bias = jnp.zeros(8).at[3].set(10.0)
    _, e2, _, _ = moe.route(logits, biased, bias)
    assert bool(jnp.all(jnp.any(e2 == 3, axis=-1)))
    assert "router_bias" in moe.init_params(
        jax.random.PRNGKey(0), biased)["layers"][0]


def test_the_cached_paths_refuse_a_parallel_block():
    cfg = tiny()
    with pytest.raises(NotImplementedError, match="parallel block"):
        cached.init_cache(cfg, batch=1)
    dense = llama.PRESETS["tiny"]
    with pytest.raises(NotImplementedError, match="layer norm"):
        cached._refuse_stated(dense.replace(norm="layer"))
    with pytest.raises(NotImplementedError, match="parallel block"):
        cached.init_paged_cache(dense.replace(parallel_block=True), 4, 16)
