"""Attention layers of several kinds in one model (models/moe.py
``layer_kinds``, llama ``attn_kinds``: Mellum2's window layers with plain
rotary tables beside full layers under YaRN, a stated head width, 8 query
heads a KV head) against the plain reference (models/reference_mellum.py)
on seeded weights; the YaRN table by hand; wrong models told from the
right one; a chip's share of the experts against the uncut layer."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import cached, llama, moe, reference_mellum, registry
from ray_tpu.util import tracing


def ref_cfg(cfg) -> dict:
    """The reference's dict of a config with named kinds of layer."""
    d = dataclasses.asdict(cfg)
    d["kinds"] = {
        name: {"window": of.window,
               "rope_theta": of.rope_theta or cfg.rope_theta,
               "yarn": of.yarn and dataclasses.asdict(of.yarn)}
        for name, of in cfg.attn_kinds}
    return d


def tiny(**kw):
    return moe.PRESETS["tiny-mellum"].replace(dtype=jnp.float32, **kw)


def make(cfg, seed=0, batch=2, seq=96):
    params = moe.init_params(jax.random.PRNGKey(seed), cfg)
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for lay in params["layers"]:     # unit norms hide a wrong index
        for name in ("attn_norm", "ffn_norm"):
            lay[name] = lay[name] + 0.3 * jax.random.normal(
                next(key), lay[name].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                (batch, seq + 1), 0, cfg.vocab_size, "int32")
    return params, tokens


def test_layer_runs_parameter_tree_and_the_published_count():
    cfg = tiny()
    assert moe.layer_runs(cfg) == [("window", 3), ("full", 1)] * 2
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads
    params, _ = make(cfg)
    assert [lay["wq"].shape for lay in params["layers"]] \
        == [(3, 48, 128), (1, 48, 128)] * 2
    assert params["layers"][0]["wo"].shape == (3, 128, 48)
    assert moe.num_params(cfg) == sum(x.size for x in jax.tree.leaves(params))
    spec = moe.param_specs(cfg)
    is_axes = lambda x: isinstance(x, tuple)     # noqa: E731
    assert jax.tree.structure(spec, is_leaf=is_axes) \
        == jax.tree.structure(params)
    for axes, w in zip(jax.tree.leaves(spec, is_leaf=is_axes),
                       jax.tree.leaves(params)):
        assert len(axes) == w.ndim, (axes, w.shape)
    with pytest.raises(ValueError, match="layer kinds"):
        moe.layer_runs(cfg.replace(n_layers=5))
    with pytest.raises(ValueError, match="layer kinds"):
        moe.layer_runs(cfg.replace(layer_kinds=("window",) * 7 + ("odd",)))
    # the published model: 28 layers of 21.23 M (attention) + 0.15 M
    # (router) + 64 x 6.193 M and two 98,304 x 2304 matrices: 12.1 B
    full, mod = registry.get("moe", "mellum2-12b-a2.5b")
    assert mod is moe and full.head_dim == 128
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    layer = attention + 2304 * 64 + 64 * 3 * 2304 * 896 + 2 * 2304
    assert moe.num_params(full) == 28 * layer + 2 * 98304 * 2304 + 2304
    assert round(moe.num_params(full) / 1e9, 1) == 12.1
    assert [n for _, n in moe.layer_runs(full)] == [3, 1] * 7


def test_the_yarn_table_against_hand_worked_values():
    """Mellum2's full layers: theta 500000, 128 lanes, factor 16 over
    8,192. dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): dim(32) =
    18.08, dim(1) = 34.98, so the ramp runs from lane 18 to lane 35."""
    cfg = moe.PRESETS["mellum2-12b-a2.5b"]
    yarn = llama.attention_kind(cfg, "full").yarn
    assert llama.yarn_range(500000.0, 128, yarn) == (18, 35)
    kinds = ref_cfg(cfg)["kinds"]
    assert reference_mellum.yarn_range(500000.0, 128, kinds["full"]["yarn"]) \
        == (18, 35)
    got = np.asarray(llama.rope_inv_freq(500000.0, 128, yarn))
    ref = np.asarray(reference_mellum.inv_freq(kinds["full"], 128))
    np.testing.assert_array_equal(got, ref)
    plain = lambda i: 500000.0 ** (-2 * i / 128)        # noqa: E731
    assert got[0] == 1.0 and got[18] == pytest.approx(plain(18), rel=1e-6)
    # lane 26: ramp (26 - 18) / 17 of the way to the divided frequency
    r = 8 / 17
    assert got[26] == pytest.approx(plain(26) * (1 - r + r / 16), rel=1e-5)
    assert got[35] == pytest.approx(plain(35) / 16, rel=1e-6)
    assert got[63] == pytest.approx(plain(63) / 16, rel=1e-6)
    # the sliding layers' table is the plain one
    np.testing.assert_allclose(
        llama.rope_inv_freq(500000.0, 128),
        [plain(i) for i in range(64)], rtol=1e-6)
    cos, sin = llama._kind_tables(cfg, llama.attention_kind(cfg, "full"), 4)
    assert float(cos[0, 0]) == pytest.approx(1.2772588722239782)
    assert float(sin[1, 0]) == pytest.approx(1.2772588722239782 * math.sin(1))
    cos, _ = llama._kind_tables(cfg, llama.attention_kind(cfg, "window"), 4)
    assert float(cos[0, 0]) == 1.0
    # an attention factor left out is 0.1 ln(factor) + 1
    bare = dataclasses.replace(yarn, attention_factor=None)
    assert float(llama._yarn_tables(500000.0, 2, 128, bare)[0][0, 0]) \
        == pytest.approx(0.1 * math.log(16) + 1)


@pytest.mark.parametrize("case", ["all", "share", "flash"])
def test_model_against_the_plain_reference(case):
    """Logits, routes, the loss's terms and one step's gradients; with
    ``flash`` through the kernels (interpret mode) at a window that is no
    multiple of the block."""
    cfg = tiny(experts_held=(4, 2) if case == "share" else None, remat=True)
    seq = 96
    if case == "flash":
        cfg = cfg.replace(attn_impl="flash", attn_kinds=(
            ("window", llama.AttentionKind(window=40)), cfg.attn_kinds[1]))
        seq = 128
    params, tokens = make(cfg, seq=seq)
    if case == "share":
        for lay in params["layers"]:
            for w in ("we_gate", "we_up", "we_down"):
                lay[w] = lay[w][:, :4]
        params["layers"] = [dict(lay) for lay in params["layers"]]
    logits, stats = moe.forward_with_stats(params, tokens[:, :-1], cfg)
    want = [reference_mellum.forward(params, t[:-1], ref_cfg(cfg))
            for t in tokens]
    np.testing.assert_allclose(logits, jnp.stack([w[0] for w in want]),
                               rtol=2e-4, atol=2e-4)
    own = jnp.stack([w[1]["experts"] for w in want], axis=1)   # [L, B, S, K]
    got = stats["experts"].reshape(own.shape)
    assert bool(jnp.all(jnp.sort(got, -1) == jnp.sort(own, -1)))
    (loss, aux), grads = jax.value_and_grad(
        lambda p: moe.loss_fn(p, {"tokens": tokens}, cfg),
        has_aux=True)(params)
    (ref_loss, terms), ref_grads = jax.value_and_grad(
        lambda p: reference_mellum.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True)(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5
    assert abs(float(aux["moe_aux_loss"]) - float(terms["aux"])) < 1e-5
    assert float(aux["moe_dropped"]) == 0
    flat = lambda t: jax.tree.leaves_with_path(t)   # noqa: E731
    for (path, g), (_, w) in zip(flat(grads), flat(ref_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-9
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def _wrong(cfg, how):
    window, full = (of for _, of in cfg.attn_kinds)
    kinds = lambda w, f: (("window", w), ("full", f))        # noqa: E731
    if how == "window one short":
        return cfg.replace(attn_kinds=kinds(
            dataclasses.replace(window, window=window.window - 1), full))
    if how == "window on every layer":
        return cfg.replace(attn_kinds=kinds(
            window, dataclasses.replace(full, window=window.window)))
    if how == "no window anywhere":
        return cfg.replace(attn_kinds=kinds(
            dataclasses.replace(window, window=None), full))
    if how == "plain table on the full layers":
        return cfg.replace(attn_kinds=kinds(
            window, dataclasses.replace(full, yarn=None)))
    if how == "yarn table on the window layers":
        return cfg.replace(attn_kinds=kinds(
            dataclasses.replace(window, yarn=full.yarn), full))
    if how == "no attention factor":
        return cfg.replace(attn_kinds=kinds(window, dataclasses.replace(
            full, yarn=dataclasses.replace(full.yarn, attention_factor=1.0))))
    assert how == "as it is", how
    return cfg


@pytest.mark.parametrize("how", [
    "as it is", "window one short", "window on every layer",
    "no window anywhere", "plain table on the full layers",
    "yarn table on the window layers", "no attention factor",
    "h // 4 for h // 8", "softmax in bf16"])
def test_a_wrong_model_is_told_from_the_right_one(how, monkeypatch):
    """Each wrong model's logits leave the reference's by far more than
    the right model's rounding: what the comparison of
    ``test_model_against_the_plain_reference`` holds the program to. (A
    softmax in bf16 where the configuration states float32 leaves it by
    ten times the limit; in a bf16 model at this size it cannot be told,
    benchmark/tests/test_mellum.py.)"""
    cfg = tiny()
    params, tokens = make(cfg, batch=1)
    run_params = params
    if how == "softmax in bf16":
        real = jax.nn.softmax
        monkeypatch.setattr(jax.nn, "softmax", lambda s, axis=-1: real(
            s.astype(jnp.bfloat16), axis=axis).astype(jnp.float32))
        run_cfg = cfg
    elif how == "h // 4 for h // 8":
        # two KV heads, of which the reference's grouping reads the first
        # for query heads 0-3 and the second for 4-7: a program that reads
        # head h // 2 (four groups of two) is another model
        cfg = cfg.replace(n_kv_heads=2)
        params, tokens = make(cfg, batch=1)
        swap = jnp.asarray([0, 2, 4, 6, 1, 3, 5, 7])

        def regroup(lay):        # query heads dealt round the KV heads
            wq = lay["wq"].reshape(*lay["wq"].shape[:2], 8, 16)[:, :, swap]
            wo = lay["wo"].reshape(-1, 8, 16, 48)[:, swap]
            return dict(lay, wq=wq.reshape(lay["wq"].shape),
                        wo=wo.reshape(lay["wo"].shape))

        run_params = dict(params, layers=[regroup(lay)
                                          for lay in params["layers"]])
        run_cfg = cfg
    else:
        run_cfg = _wrong(cfg, how)
    logits = moe.forward(run_params, tokens[:, :-1], run_cfg)
    want = reference_mellum.forward(params, tokens[0, :-1], ref_cfg(cfg))[0]
    apart = float(jnp.max(jnp.abs(logits[0] - want)))
    if how == "as it is":
        assert apart < 2e-4
    else:
        assert apart > (2e-3 if how == "softmax in bf16" else 2e-2), apart


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold 2 of the 8 experts each: the parts of the layer's
    output that the four shares give add up to what the reference gives
    for the whole layer of 8 (no shared expert to count once)."""
    cfg = tiny()
    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][0])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.d_model))
    whole, rec = reference_mellum.experts(
        h.reshape(-1, cfg.d_model), lp, dict(ref_cfg(cfg), experts_held=None))
    parts, rows = 0.0, 0
    for first in (0, 2, 4, 6):
        share = cfg.replace(experts_held=(2, first))
        mine = dict(lp, **{w: lp[w][first:first + 2]
                           for w in ("we_gate", "we_up", "we_down")})
        y, stats = moe.feed_forward(h, mine, share)
        parts = parts + y.reshape(-1, cfg.d_model)
        rows += int(stats["held_counts"].sum())
        ref_part, _ = reference_mellum.experts(
            h.reshape(-1, cfg.d_model), mine,
            dict(ref_cfg(cfg), experts_held=(2, first)))
        np.testing.assert_allclose(y.reshape(-1, cfg.d_model), ref_part,
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(parts, whole, rtol=2e-4, atol=2e-5)
    assert rows == 2 * 64 * cfg.top_k == int(rec["counts"].sum())


def test_one_attention_half_two_scopes_and_the_kind_plan(monkeypatch):
    """Both kinds of layer run ``llama._attention_half`` under a scope of
    the kind's name; a traced forward says each kind's plan once."""
    cfg = tiny(remat=True)
    params, tokens = make(cfg)
    seen, said = [], []
    real = llama._attention_half

    def half(*a, kind=None, **kw):
        seen.append(kind)
        return real(*a, kind=kind, **kw)

    monkeypatch.setattr(llama, "_attention_half", half)
    monkeypatch.setattr(tracing, "instant", lambda name, attrs=None, **kw:
                        said.append((name, attrs)))
    text = jax.jit(lambda p: moe.forward(p, tokens[:, :-1], cfg)).lower(
        params).as_text(debug_info=True)
    assert set(seen) == {"window", "full"}
    assert "attention/window" in text and "attention/full" in text
    plans = [a for n, a in said if n == "attn.kind_plan"]
    assert sorted(p["kind"] for p in plans) == ["full", "window"]
    by = {p["kind"]: p for p in plans}
    assert by["window"]["window"] == 24 and by["full"]["window"] == 0
    assert by["window"]["rope"] == "default" and by["full"]["rope"] == "yarn"
    assert by["full"]["factor"] == 4.0 and by["full"]["head_dim"] == 16
    assert by["full"]["heads"] == 8 and by["full"]["kv_heads"] == 1
    layer = [a for n, a in said if n == "hybrid.layer_plan"]
    assert layer == [{"kinds": 2, "runs": 4, "bodies": 2, "layers": 8,
                      "pattern": "window x3, full x1, window x3, full x1"}]


def test_a_dense_config_with_a_window_is_one_kind_as_before():
    cfg = llama.PRESETS["tiny"].replace(dtype=jnp.float32, sliding_window=16)
    assert llama.attention_kind(cfg) == llama.AttentionKind(window=16)
    assert llama.attention_kind(cfg, "attention").window == 16
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    assert isinstance(params["layers"], dict)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 256)
    banded = llama.forward(params, toks, cfg)
    full = llama.forward(params, toks, cfg.replace(sliding_window=None))
    np.testing.assert_allclose(banded[:, :16], full[:, :16], atol=1e-5)
    assert float(jnp.max(jnp.abs(banded[:, 32:] - full[:, 32:]))) > 1e-3


def test_the_cached_paths_refuse_layers_of_several_kinds():
    cfg = tiny()
    with pytest.raises(NotImplementedError, match="several kinds"):
        cached.init_cache(cfg, batch=1)
    with pytest.raises(NotImplementedError, match="several kinds"):
        cached._refuse_stated(cfg)
