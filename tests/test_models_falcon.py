"""A block of two first halves (models/falcon.py: a Mamba-2 mixer and
grouped-query attention read one norm side by side, each under its muP
multipliers, ahead of a serial SwiGLU) against ``reference_falconh1.py`` on
seeded weights at the CPU tests' size: the loss and every leaf's gradient;
every wrong program told from the right one by the same tolerance; the
kernel path is the plain one; what the layer checkpoint is told; the
plans; the refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (cached, falcon, hybrid, llama,
                            reference_falconh1, registry, remat)

LOSS_TOL = 2e-5  # |program - reference| of a token's loss, float32
GRAD_TOL = 2e-4  # relative L2 of a leaf's gradient


def tiny(**kw):
    return falcon.PRESETS["tiny"].replace(
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def ref_cfg(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def make(cfg, batch=2, seq=24, seed=0):
    """Seeded parameters in which every leaf matters (norm scales, the
    convolution's bias, D and the gated norm's scale off their initial
    values) and tokens [B, S + 1]."""
    params = falcon.init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 5)

    def moved(stack):
        out = dict(stack)
        for i, (name, draw) in enumerate((
                ("attn_norm", lambda z: 1.0 + 0.2 * z),
                ("ffn_norm", lambda z: 1.0 + 0.2 * z),
                ("gate_norm", lambda z: 1.0 + 0.3 * z),
                ("conv_b", lambda z: 0.5 * z),
                ("d_skip", lambda z: 1.0 + 0.5 * z),
                ("dt_bias", lambda z: z))):
            out[name] = draw(jax.random.normal(
                jax.random.fold_in(key, i), stack[name].shape))
        return out

    params["layers"] = [moved(run) for run in params["layers"]]
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, seq + 1), 0, cfg.vocab_size)
    return params, tokens


def program_losses(params, tokens, cfg):
    logits = falcon.forward(params, tokens[:, :-1], cfg)
    return llama.token_losses(logits, tokens[:, 1:])


def test_the_registry_knows_the_family_and_the_tree_is_the_models():
    cfg, mod = registry.get("falcon_h1", "tiny")
    assert mod is falcon
    assert falcon.layer_runs(cfg) == [("both", 2)]
    assert falcon.layer_runs(cfg.replace(run_layers=1)) == [("both", 1)] * 2
    assert falcon.layer_runs(cfg.replace(n_layers=5, run_layers=2)) == [
        ("both", 2), ("both", 2), ("both", 1)]
    # the one scale the attention kernel takes: k_m x head_width^-1/2
    assert cfg.attn_scale == 0.25 * 16 ** -0.5
    assert cfg.replace(key_multiplier=1.0).attn_scale == 16 ** -0.5
    for run_layers in (0, 1):
        c = cfg.replace(run_layers=run_layers)
        params = falcon.init_params(jax.random.PRNGKey(0), c)
        assert sum(x.size for x in jax.tree.leaves(params)) \
            == falcon.num_params(c)
        specs = falcon.param_specs(c)
        flat = lambda t: jax.tree.structure(jax.tree.map(     # noqa: E731
            lambda a: 0, t, is_leaf=lambda a: isinstance(a, tuple)))
        assert flat(specs) == flat(params)
        for run, spec in zip(params["layers"], specs["layers"]):
            assert all(len(spec[k]) == w.ndim for k, w in run.items()), spec
    lay = params["layers"][0]
    assert "mix_norm" not in lay and lay["in_proj"].shape == (
        1, 64, 2 * 64 + 2 * 2 * 32 + 4)
    # a_log = log(1 .. H) as the class sets it
    np.testing.assert_allclose(np.exp(lay["a_log"][0]), [1, 2, 3, 4],
                               rtol=1e-6)


@pytest.mark.parametrize("run_layers", [0, 1])
def test_loss_and_every_gradient_against_the_reference(run_layers):
    cfg = tiny(run_layers=run_layers)
    params, tokens = make(cfg)
    got = jax.jit(lambda p: program_losses(p, tokens, cfg))(params)
    want = jax.jit(lambda p: reference_falconh1.token_losses(
        p, tokens, ref_cfg(cfg), rows=8))(params)
    assert float(jnp.max(jnp.abs(got - want))) <= LOSS_TOL
    g = jax.jit(jax.grad(lambda p: falcon.loss_fn(
        p, {"tokens": tokens}, cfg)))(params)
    w = jax.jit(jax.grad(lambda p: reference_falconh1.loss(
        p, tokens, ref_cfg(cfg), rows=8)))(params)
    flat_g, flat_w = (jax.tree_util.tree_leaves_with_path(t) for t in (g, w))
    assert len(flat_g) == 3 + 17 * (run_layers + 1)
    for (path, a), (_, b) in zip(flat_g, flat_w):
        name = jax.tree_util.keystr(path)
        norm = float(jnp.linalg.norm(b))
        assert norm > 0, name              # every leaf is alive
        assert float(jnp.linalg.norm(a - b)) <= GRAD_TOL * norm, name


def _gate_after_norm(y, xs, z, d_skip, gate_norm, eps, groups=1):
    """rms_norm(y + D xs) gate_norm silu(z): ``mamba_norm_before_gate``."""
    v = y + xs * hybrid._lanes(d_skip, y.shape[-1])
    r = jax.lax.rsqrt(hybrid._group_mean(v * v, groups) + eps)
    return v * r * gate_norm * jax.nn.silu(z)


def _serial(x, lp, cfg, cos, sin, mesh, rules, kind):
    """The mixer reading the attention's RESULT: two serial first halves."""
    n = llama._norm(x, lp["attn_norm"], cfg)
    x = x + llama._attention_half(
        x, lp, cfg, cos, sin, kind=kind,
        normed=n * cfg.attention_in_multiplier) * cfg.attention_out_multiplier
    n = llama._norm(x, lp["attn_norm"], cfg)
    return x + hybrid.mixer_half(x, lp, cfg, kind, normed=n) \
        * cfg.ssm_out_multiplier


def _ones(field, at=None):
    def wrong(cfg, params, monkeypatch):
        value = getattr(cfg, field)
        one = 1.0 if at is None else tuple(
            1.0 if i == at else v for i, v in enumerate(value))
        return cfg.replace(**{field: one}), params
    return wrong


def _zeroed(leaf):
    def wrong(cfg, params, monkeypatch):
        return cfg, {**params, "layers": [
            {**run, leaf: jnp.zeros_like(run[leaf])}
            for run in params["layers"]]}
    return wrong


def _patched(module, name, fn):
    def wrong(cfg, params, monkeypatch):
        monkeypatch.setattr(module, name, fn)
        return cfg, params
    return wrong


def _swapped(cfg, params, monkeypatch):
    z, x, b, c, dt = cfg.ssm_multipliers
    return cfg.replace(ssm_multipliers=(z, x, c, b, dt)), params


def _one_group(cfg, params, monkeypatch):
    real = hybrid._gated_norm
    monkeypatch.setattr(hybrid, "_gated_norm", lambda *a: real(*a[:6], 1))
    return cfg, params


WRONG = {
    **{f"{f} left at 1": _ones(f) for f in (
        "embedding_multiplier", "attention_in_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier", "lm_head_multiplier")},
    **{f"ssm_multipliers' {n} left at 1": _ones("ssm_multipliers", i)
       for i, n in enumerate(("z", "x", "B", "C", "dt"))},
    **{f"mlp_multipliers' {n} left at 1": _ones("mlp_multipliers", i)
       for i, n in enumerate(("gate", "down"))},
    "m_B and m_C swapped": _swapped,
    "the gate after the norm": _patched(hybrid, "_gated_norm",
                                        _gate_after_norm),
    "one norm group for two": _one_group,
    "D left out": _zeroed("d_skip"),
    "the convolution's bias left out": _zeroed("conv_b"),
    "the halves run serially": _patched(llama, "_two_first_halves", _serial),
}


@pytest.mark.parametrize("how", sorted(WRONG))
def test_a_wrong_program_is_refused_by_the_same_tolerance(how, monkeypatch):
    """Each wrong program's per-token losses lie further from the
    reference's (the right model on the right weights) than the tolerance
    that holds the right program, by ten times at the least."""
    cfg = tiny()
    params, tokens = make(cfg)
    want = jax.jit(lambda p: reference_falconh1.token_losses(
        p, tokens, ref_cfg(cfg), rows=8))(params)
    wrong_cfg, wrong_params = WRONG[how](cfg, params, monkeypatch)
    got = jax.jit(lambda p: program_losses(p, tokens, wrong_cfg))(
        wrong_params)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * LOSS_TOL, how


def test_remat_changes_nothing_and_the_kernel_path_is_the_plain_one():
    cfg = tiny()
    params, tokens = make(cfg, batch=1, seq=32)
    batch = {"tokens": tokens}
    run = lambda c: jax.jit(jax.value_and_grad(                # noqa: E731
        lambda p: falcon.loss_fn(p, batch, c)))(params)
    (l0, g0), (l1, g1) = run(cfg), run(cfg.replace(remat=False))
    assert float(l0) == pytest.approx(float(l1), abs=1e-6)
    # the Mosaic scan in interpret mode (heads of 16: the pairs layout; the
    # tile layout at heads of 128 is tests/test_ops_ssd.py's)
    l2, g2 = run(cfg.replace(ssd_impl="pallas"))
    assert float(l2) == pytest.approx(float(l0), abs=2e-5)
    for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(g0)):
        assert float(jnp.linalg.norm(a - b)) <= 2e-3 * float(
            jnp.linalg.norm(b)) + 1e-7


def test_what_the_layer_checkpoint_is_told():
    """A block of two first halves is BOTH to the plan (flash's residuals
    and the query heads' lanes AND the mixer's backward bytes:
    tests/test_remat_plan.py holds the cell's estimate to its number); it
    offers q, k, v, then the SwiGLU's gate and up, then the
    in-projection."""
    from ray_tpu.models.family import (_halves, _runs_half,
                                       _takes_attention_half)
    from ray_tpu.parallel.train_step import StepMemory

    cfg = tiny().replace(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert _halves(cfg, "both") == ("both", True)
    assert _takes_attention_half(cfg, "both")
    assert _runs_half("both", "attention") and _runs_half("both", "mixer") \
        and not _runs_half("mixer", "attention")
    rows = 256
    offers = remat._offers(cfg, "both", 1, rows)
    assert [n for n, _ in offers] == ["attn_q", "attn_k", "attn_v",
                                      "ffn_gate", "ffn_up", "mix_proj"]
    assert list(remat._offered(cfg)) == [n for n, _ in offers]
    assert dict(offers)["attn_q"] == rows * 5 * 16 * 2
    assert dict(offers)["attn_k"] == rows * 1 * 16 * 2
    assert dict(offers)["ffn_up"] == rows * 96 * 2
    assert dict(offers)["mix_proj"] == rows * 260 * 2
    params = jax.eval_shape(lambda: falcon.init_params(
        jax.random.PRNGKey(0), cfg))
    state = 2 * falcon.num_params(cfg)
    plan = remat.remat_plan(cfg, params, 1, rows, StepMemory(
        limit=16_909_336_064, state=state))
    assert plan.why == "room" and plan.kept == (tuple(n for n, _ in offers),)


def test_plan_instants_say_the_block_the_mixer_and_the_scan(monkeypatch):
    from ray_tpu.util import tracing

    said = []
    monkeypatch.setattr(tracing, "instant", lambda n, attrs=None, **kw:
                        said.append((n, attrs)))
    cfg = tiny(run_layers=1)
    params, tokens = make(cfg)
    jax.jit(lambda p: falcon.loss_fn(p, {"tokens": tokens}, cfg)).lower(
        params)
    of = lambda name: [a for n, a in said if n == name]        # noqa: E731
    (block,) = of("hybrid.layer_plan")
    assert block["first_halves"] == "attention+mixer" \
        and block["feed_forward"] == "serial" and block["runs"] == 2 \
        and block["pattern"] == "both x1, both x1" and block["kept"] == "-,-"
    for name in falcon.MULTIPLIERS:
        assert block[name] == str(getattr(cfg, name))
    assert all(p["groups"] == 2 and p["group_lanes"] == 32
               and p["channels"] == 64 + 2 * 2 * 32 for p in of("mixer.plan"))
    assert all((p["groups"], p["heads_per_group"], p["state"], p["decay"])
               == (2, 2, 32, "stepped") for p in of("ssd.plan"))
    (kind,) = of("attn.kind_plan")
    assert (kind["groups"], kind["rope"], kind["head_dim"]) \
        == (5, "default", 16)


def test_the_refusals():
    cfg = tiny()
    with pytest.raises(NotImplementedError,
                       match="a mixer beside the attention half"):
        cached.init_cache(cfg, 1)
    with pytest.raises(NotImplementedError,
                       match="a mixer beside the attention half"):
        cached.init_paged_cache(cfg, 4, 16)

    class Mesh:
        size, shape = 4, {"dp": 4}

    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][0])
    x = jnp.zeros((1, 32, 64))
    with pytest.raises(NotImplementedError, match="one device"):
        hybrid.mixer_half(x, lp, cfg.replace(ssd_impl="pallas"), "both",
                          mesh=Mesh(), normed=x)
    # the two forms of side-by-side blocks are not mistaken for each other
    with pytest.raises(NotImplementedError,
                       match="mixer BESIDE attention.*'both'"):
        llama._parallel_layer(x, lp, hybrid.PRESETS["tiny"], None, None,
                              None, None, None, "mamba")
