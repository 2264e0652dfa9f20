"""The state-space / attention hybrid (models/hybrid.py) against its plain
reference (models/reference_granite.py) on seeded weights, a chip's share
of the experts against the uncut layer, and what the shared code
(llama.py, moe.py, flash) was given for it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import cached, hybrid, llama, moe, reference_granite
from ray_tpu.models import registry


def ref_cfg(cfg) -> dict:
    d = dataclasses.asdict(cfg)
    d["layer_types"] = cfg.kinds
    return d


def tiny(**kw):
    return hybrid.PRESETS["tiny"].replace(dtype=jnp.float32, mamba_chunk=32,
                                          **kw)


def make(cfg, seed=0, batch=2, seq=96):
    params = hybrid.init_params(jax.random.PRNGKey(seed), cfg)
    # zero biases and unit norms hide a wrong index: draw them
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for lay in params["layers"]:
        for name in ("conv_b", "d_skip", "gate_norm", "mix_norm", "attn_norm",
                     "ffn_norm"):
            if name in lay:
                lay[name] = lay[name] + 0.3 * jax.random.normal(
                    next(key), lay[name].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                (batch, seq + 1), 0, cfg.vocab_size, "int32")
    return params, tokens


def test_layer_runs_and_parameter_tree():
    cfg = tiny()
    assert hybrid.layer_runs(cfg) == [("mamba", 2), ("attention", 1),
                                      ("mamba", 1)]
    full = cfg.replace(n_layers=40, layer_types=(
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4)
    assert [n for _, n in hybrid.layer_runs(full)] \
        == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    params, _ = make(cfg)
    assert [sorted(lay)[:2] for lay in params["layers"]] == [
        ["a_log", "conv_b"], ["attn_norm", "ffn_norm"], ["a_log", "conv_b"]]
    assert hybrid.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    spec = hybrid.param_specs(cfg)
    is_axes = lambda x: isinstance(x, tuple)     # noqa: E731
    assert jax.tree.structure(spec, is_leaf=is_axes) \
        == jax.tree.structure(params)
    for axes, w in zip(jax.tree.leaves(spec, is_leaf=is_axes),
                       jax.tree.leaves(params)):
        assert len(axes) == w.ndim, (axes, w.shape)
    with pytest.raises(ValueError, match="layer types"):
        hybrid.layer_runs(cfg.replace(n_layers=5))
    assert registry.get("hybrid", "tiny")[1] is hybrid


def test_the_cells_count_of_parameters():
    """ISSUE 32's arithmetic: one period, 9 of 72 experts, an eighth of the
    vocabulary: 2.055 B parameters."""
    cfg = hybrid.HybridConfig(
        vocab_size=12544, d_model=4096, n_layers=10, n_heads=32, n_kv_heads=8,
        d_ff=768, n_experts=72, top_k=10, experts_held=(9, 0),
        shared_d_ff=1536, mamba_heads=128, mamba_head_dim=64,
        mamba_state=128, mamba_conv=4, mamba_chunk=256,
        layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    assert cfg.head_dim == 128
    mamba = 4096 * 16768 + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    rest = 2 * 4096 + 3 * 4096 * 1536 + 4096 * 72 + 9 * 3 * 4096 * 768
    assert hybrid.num_params(cfg) == 9 * mamba + attention + 10 * rest \
        + 12544 * 4096 + 4096
    assert round(hybrid.num_params(cfg) / 1e9, 3) == 2.055


@pytest.mark.parametrize("held", [None, (4, 2), (2, 4)],
                         ids=["all", "share", "over_one_pass"])
def test_model_against_the_plain_reference(held):
    over = held == (2, 4)
    cfg = tiny(experts_held=held, remat=True, n_experts=16 if over else 8)
    params, tokens = make(cfg, seq=256 if over else 96)
    if over:    # routers that send half the tokens to both held experts
        for lay in params["layers"]:
            lay["router"] = lay["router"].at[:, :, 4:6].add(
                jax.random.normal(jax.random.PRNGKey(7),
                                  lay["router"].shape[:2])[..., None])
    logits, stats = hybrid.forward_with_stats(params, tokens[:, :-1], cfg)
    want = [reference_granite.forward(params, t[:-1], ref_cfg(cfg))
            for t in tokens]
    np.testing.assert_allclose(logits, jnp.stack([w[0] for w in want]),
                               rtol=2e-4, atol=2e-4)
    own = jnp.stack([w[1]["experts"] for w in want], axis=1)   # [L, B, S, K]
    got = stats["experts"].reshape(own.shape)
    assert bool(jnp.all(jnp.sort(got, -1) == jnp.sort(own, -1)))
    (loss, aux), grads = jax.value_and_grad(
        lambda p: hybrid.loss_fn(p, {"tokens": tokens}, cfg),
        has_aux=True)(params)
    (ref_loss, terms), ref_grads = jax.value_and_grad(
        lambda p: reference_granite.loss(p, tokens, ref_cfg(cfg)),
        has_aux=True)(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5
    assert abs(float(aux["moe_aux_loss"]) - float(terms["aux"])) < 1e-5
    assert abs(float(aux["moe_z_loss"]) - float(terms["z"])) < 1e-4
    assert float(aux["moe_dropped"]) == 0
    if held is not None:
        assert (float(aux["moe_held_more_passes"]) > 0) == over
        rec = reference_granite.token_losses(params, tokens, ref_cfg(cfg))[1]
        t_k = tokens[:, :-1].size * cfg.top_k
        assert float(aux["moe_held_rows_share"]) == pytest.approx(
            float(rec["held_rows"].sum()) / (cfg.n_layers * t_k))
    flat = lambda t: jax.tree.leaves_with_path(t)   # noqa: E731
    for (path, g), (_, w) in zip(flat(grads), flat(ref_grads)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-9
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def plain_mixer(x, lp, cfg, kind="mamba", mesh=None):
    """``hybrid.mixer_half`` as the module docstring's equations, with the
    program's casts to ``cfg.dtype`` and the recurrence one step at a time,
    for jax to differentiate: what the hand-written rules are held to."""
    f32, dt_ = jnp.float32, cfg.dtype
    B, S, _ = x.shape
    H, P, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state
    inner = H * P
    w = lambda name: lp[name].astype(dt_)                     # noqa: E731
    u = llama.rms_norm(x, lp["mix_norm"], cfg.norm_eps)
    zxbcdt = u @ w("in_proj")
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * N], axis=-1)
    taps = lp["conv_w"].shape[0]
    padded = jnp.pad(xbc.astype(f32), ((0, 0), (taps - 1, 0), (0, 0)))
    pre = lp["conv_b"].astype(f32) + sum(
        lp["conv_w"][j].astype(f32) * padded[:, j:j + S] for j in range(taps))
    xbc = jax.nn.silu(pre).astype(dt_)
    xs, bm, cm = jnp.split(xbc, [inner, inner + N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    a = -jnp.exp(lp["a_log"].astype(f32))

    def step(s, at):
        x_t, b_t, c_t, dt_t = at              # [B, H, P], [B, N] x 2, [B, H]
        u_t = (x_t.astype(f32) * dt_t[..., None]).astype(dt_).astype(f32)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + u_t[..., None] * b_t.astype(f32)[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t.astype(f32))

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), f32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1).astype(dt_).astype(f32) \
        + xs.astype(f32) * lp["d_skip"].astype(f32)[:, None]
    y = y.reshape(B, S, inner) * jax.nn.silu(z.astype(f32))
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                           + cfg.norm_eps)).astype(dt_) * w("gate_norm")
    return llama._residual(x, y @ w("out_proj"), cfg)


MIXER_WEIGHTS = ("mix_norm", "in_proj", "conv_w", "conv_b", "dt_bias",
                 "a_log", "d_skip", "gate_norm", "out_proj")
RULE_CASES = [(impl, chunks, batch, taps, "float32")
              for impl in ("xla", "pallas") for chunks in (1, 3)
              for batch in (1, 3) for taps in (4, 2)] \
    + [("xla", 3, 3, 4, "bfloat16"), ("pallas", 3, 1, 4, "bfloat16")]


@pytest.mark.parametrize(
    "impl,chunks,batch,taps,dtype", RULE_CASES,
    ids=["-".join(map(str, c)) for c in RULE_CASES])
def test_the_mixers_gradient_rules_against_jax_on_the_plain_formulas(
        impl, chunks, batch, taps, dtype):
    """The three rules (``_conv_silu``, ``_gated_norm``, ``ssd._prologue``)
    through ``mixer_half``: the input's gradient and each of the nine
    weights', against jax's own of ``plain_mixer`` in float32, to 1e-5 of
    a gradient's largest entry. With bfloat16 activations the same float32
    gradients are the yardstick (jax's own bfloat16 ones add up a weight's
    gradient IN bfloat16, the norm's scale outside the rules too) and the
    limit is the rounding of the activations, three hundredths."""
    cfg = tiny(ssd_impl=impl, mamba_conv=taps)
    params, _ = make(cfg, seed=chunks + batch)
    lp = {k: params["layers"][0][k][0] for k in MIXER_WEIGHTS}
    assert float(jnp.abs(lp["conv_b"]).min()) > 0
    assert float(jnp.abs(lp["d_skip"] - 1).min()) > 0
    keys = jax.random.split(jax.random.PRNGKey(taps), 2)
    shape = (batch, chunks * cfg.mamba_chunk, cfg.d_model)
    x = jax.random.normal(keys[0], shape).astype(dtype).astype(jnp.float32)
    probe = jax.random.normal(keys[1], shape)

    def through(mixer, cfg):
        def loss(x, lp):
            return jnp.sum(mixer(x.astype(cfg.dtype), lp, cfg, "mamba"
                                 ).astype(jnp.float32) * probe)
        return jax.value_and_grad(loss, argnums=(0, 1))(x, lp)

    got, (g_x, g_lp) = through(hybrid.mixer_half,
                               cfg.replace(dtype=jnp.dtype(dtype)))
    want, (w_x, w_lp) = through(plain_mixer, cfg)
    exact = dtype == "float32"
    assert abs(float(got) - float(want)) < (1e-4 if exact else 0.05) \
        * (1 + abs(float(want)))
    for name, g, w in [("x", g_x, w_x)] + [(k, g_lp[k], w_lp[k])
                                            for k in MIXER_WEIGHTS]:
        assert g.dtype == w.dtype and g.shape == w.shape, name
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, name
        tol = 1e-5 if exact else 3e-2
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * scale,
                                   err_msg=name)


def test_the_model_under_the_layer_checkpoint_with_rules_and_without(
        monkeypatch):
    """The whole ``tiny`` model's loss gradient, every layer under
    ``remat._checkpoint``: the rules against the plain formulas patched in
    for ``mixer_half``."""
    cfg = tiny(remat=True)
    params, tokens = make(cfg)
    grad = lambda: jax.value_and_grad(                        # noqa: E731
        lambda p: hybrid.loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
    got, g_grads = grad()
    monkeypatch.setattr(hybrid, "FAMILY", hybrid.FAMILY.replace(
        "hybrid", mixer_half=plain_mixer))
    want, w_grads = grad()
    assert abs(float(got) - float(want)) < 1e-5
    flat = lambda t: jax.tree.leaves_with_path(t)   # noqa: E731
    for (path, g), (_, w) in zip(flat(g_grads), flat(w_grads)):
        np.testing.assert_allclose(
            g, w, rtol=2e-3, atol=2e-3 * float(jnp.max(jnp.abs(w)) + 1e-9),
            err_msg=jax.tree_util.keystr(path))


def test_kernel_paths_give_the_plain_paths_program():
    """flash, the Mosaic grouped matmul and the scan kernel, in interpret
    mode, against the "xla" paths of the same model."""
    cfg = tiny(experts_held=(4, 0))
    params, tokens = make(cfg, seq=128)
    batch = {"tokens": tokens}
    fast = cfg.replace(attn_impl="flash", gmm_impl="pallas",
                       ssd_impl="pallas")
    want, w_grads = jax.value_and_grad(
        lambda p: hybrid.loss_fn(p, batch, cfg)[0])(params)
    got, g_grads = jax.value_and_grad(
        lambda p: hybrid.loss_fn(p, batch, fast)[0])(params)
    assert abs(float(got) - float(want)) < 1e-5
    for g, w in zip(jax.tree.leaves(g_grads), jax.tree.leaves(w_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3 * float(
            jnp.max(jnp.abs(w)) + 1e-9))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: hybrid.loss_fn(p, batch, fast)[0]))(params)
    assert str(jaxpr).count("pallas_call") >= 3


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: over 8 chips of 2 experts each, the routed
    parts that the shares compute, with the shared expert (computed alike
    on every chip) counted once, are the uncut reference's layer output."""
    cfg = tiny(n_experts=16, top_k=4)
    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][0])
    t, d = 192, cfg.d_model
    h = jax.random.normal(jax.random.PRNGKey(5), (1, t, d))
    whole, rec = reference_granite._experts(h[0], lp, ref_cfg(cfg), None)
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
        assert int(stats["more_passes"]) == 0
        np.testing.assert_array_equal(stats["counts"], rec["counts"])
        rows += int(stats["held_counts"].sum())
        total = total + (y[0] - shared)
        # and the reference, given the same share, gives this chip's part
        same, _ = reference_granite._experts(x, mine, ref_cfg(part), None)
        np.testing.assert_allclose(y[0], same, rtol=2e-4, atol=2e-5)
    assert rows == t * cfg.top_k          # every assignment on one chip
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def _layer_and_reference(cfg, lp, h):
    """The program's expert layer and the reference's on the same input:
    ((y, gradients of sum(y * probe) by h and the weights) of each, the
    program's statistics)."""
    probe = jax.random.normal(jax.random.PRNGKey(9), h.shape)

    def mine(h, lp):
        y, stats = moe.feed_forward(h, lp, cfg)
        return jnp.sum(y * probe), (y, stats)

    def plain(h, lp):
        y, _ = reference_granite._experts(h[0], lp, ref_cfg(cfg), None)
        return jnp.sum(y * probe[0]), y

    (_, (y, stats)), got = jax.value_and_grad(mine, (0, 1), has_aux=True)(h, lp)
    (_, want_y), want = jax.value_and_grad(plain, (0, 1), has_aux=True)(h, lp)
    return (y[0], got), (want_y, want), stats


# the six held-expert cells: (tokens, top_k, held, experts) -> the rows of
# a first pass (BENCHMARK.json: Granite, Mellum2, Nemotron 3 Nano,
# GLM-4.7-Flash, Command A+, GLM-5.2)
_CELL_PASSES = {
    "granite": ((16384, 10, 9, 72), 30720),
    "mellum2": ((16384, 8, 16, 64), 49152),
    "nemotron": ((16384, 6, 16, 128), 18432),
    "glm47flash": ((16384, 4, 8, 64), 12288),
    "commandaplus": ((8192, 8, 8, 128), 6144),
    "glm52": ((16384, 8, 8, 256), 6144)}


@pytest.mark.parametrize("cell", list(_CELL_PASSES))
def test_a_first_pass_at_the_cells_sizes(cell):
    """3/2 of the held experts' even share of T x K, in whole row tiles."""
    (t, k, held, experts), rows = _CELL_PASSES[cell]
    cfg = tiny(n_experts=experts, top_k=k, experts_held=(held, 0))
    assert moe.held_rows(cfg, t) == rows and rows % 256 == 0
    assert moe.expert_rows(cfg, t) == rows
    assert moe.expert_rows(cfg.replace(experts_held=None), t) == t * k


@pytest.mark.parametrize("times,more", [(1.0, 0), (1.3, 0), (1.6, 1),
                                        (2.5, 4)],
                         ids=["even", "1.3x", "1.6x", "2.5x"])
def test_a_layer_over_one_pass_takes_further_passes_and_drops_nothing(
        times, more):
    """A router that gives the held experts ``times`` their even share of
    the assignments: the first pass holds 3/2 of it (HELD_PASS), the tail
    follows in short passes (none, none, one, four), the layer and
    its gradients are still the reference's, and the step's counters read
    what a count by hand gives."""
    held, k, t = 2, 2, 2048
    cfg = tiny(n_experts=8, top_k=k, experts_held=(held, 3), shared_d_ff=0)
    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][0])
    even = t * k * held // cfg.n_experts
    rows, short = moe.held_rows(cfg, t), 256
    assert (even, rows) == (1024, 1536) and rows < t * k
    # the router reads a token's first 8 lanes: ``chosen`` tokens give both
    # their assignments to the held experts, the others none
    chosen = round(times * even) // k
    n = chosen * k
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (1, t, cfg.d_model)))
    mine = (jax.random.permutation(jax.random.PRNGKey(6), t) < chosen)
    lanes = jnp.where(mine[:, None], 4.0, -4.0) * (
        (jnp.arange(8) >= 3) & (jnp.arange(8) < 3 + held))
    h = h.at[0, :, :8].set(lanes + 0.1 * h[0, :, :8])
    lp["router"] = jnp.zeros_like(lp["router"]).at[:8].set(jnp.eye(
        8, lp["router"].shape[1]))
    (y, got), (want_y, want), stats = _layer_and_reference(cfg, lp, h)
    assert int(stats["held_counts"].sum()) == n
    assert int(stats["more_passes"]) == more == -(-max(n - rows, 0) // short)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-5)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4 * float(
            jnp.max(jnp.abs(w)) + 1e-9))
    loss, aux = moe.finish_loss(0.0, jax.tree.map(lambda s: s[None], stats),
                                cfg)
    assert float(aux["moe_dropped"]) == 0
    assert float(aux["moe_held_more_passes"]) == more
    assert float(aux["moe_held_rows_share"]) == n / (t * k)
    assert float(aux["moe_held_pass_live_share"]) == pytest.approx(
        min(n, rows) / rows, rel=1e-6)
    assert float(aux["moe_held_share_max_over_even"]) == pytest.approx(
        n / even, rel=1e-6)
    assert float(aux["moe_held_further_pass_share"]) == float(more > 0)


def test_the_pass_counters_over_several_layers(small_chunks):
    """``held_aux`` over a stack of layers: the live share is the layers'
    mean, the fullest layer's share is over the even one, and the share of
    layers that took a further pass counts layers, not passes."""
    w = _WALK
    rows, even = w["rows"], w["t"] * w["k"] * w["held"] // 8
    lives = (0, even, rows, rows + 1, 8000)
    layers = []
    for live in lives:
        cfg, *inputs = _held_layer(live, jnp.float32)
        layers.append(moe._held_experts(*inputs, cfg)[1])
    stats = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    aux = moe.held_aux(stats["held_counts"].astype(jnp.float32), stats,
                       w["t"] * w["k"], 8)
    assert float(aux["moe_held_pass_live_share"]) == pytest.approx(
        sum(min(n, rows) / rows for n in lives) / len(lives))
    assert float(aux["moe_held_share_max_over_even"]) == pytest.approx(
        8000 / even)
    assert float(aux["moe_held_further_pass_share"]) == pytest.approx(2 / 5)
    assert float(aux["moe_held_more_passes"]) == 1 + -(-(8000 - rows)
                                                       // w["short"])


def test_layer_plan_instant_and_the_mesh_refusal(monkeypatch):
    from ray_tpu.util import tracing

    seen = []
    monkeypatch.setattr(tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, attrs)))
    cfg = tiny()
    params, tokens = make(cfg)
    jax.make_jaxpr(lambda p: hybrid.forward(p, tokens[:, :-1], cfg))(params)
    plans = [a for n, a in seen if n == "hybrid.layer_plan"]
    assert plans == [{"kinds": 2, "runs": 3, "bodies": 2, "layers": 4,
                      "pattern": "mamba x2, attention x1, mamba x1"}]
    # two runs of mamba layers, ONE trace of their body
    assert [a["path"] for n, a in seen if n == "ssd.plan"] == ["xla"]

    class Mesh:
        size, shape = 4, {"dp": 4}

    with pytest.raises(NotImplementedError, match="runs on one device"):
        hybrid.mixer_half(jnp.zeros((1, 32, cfg.d_model)), {},
                          cfg.replace(ssd_impl="pallas"), "mamba", mesh=Mesh())


def test_flash_with_a_stated_scale_against_xla():
    """H 4 over KV 2, scale 1/16 where head_dim ** -0.5 is 1/4: values and
    gradients of the kernel against ``_attention_xla``."""
    from ray_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (2, 256, 4, 16))
    k = jax.random.normal(ks[1], (2, 256, 2, 16))
    v = jax.random.normal(ks[2], (2, 256, 2, 16))
    probe = jax.random.normal(ks[3], q.shape)
    scale = 1.0 / 16

    def through(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * probe),
                                  argnums=(0, 1, 2))(q, k, v)

    want, w_grads = through(lambda q, k, v: llama._attention_xla(
        q, k, v, True, scale=scale))
    got, g_grads = through(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, scale=scale))
    default, _ = through(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64))
    assert abs(float(got) - float(want)) < 1e-3 * abs(float(want))
    assert abs(float(default) - float(want)) > 1e-2 * abs(float(want))
    for g, w in zip(g_grads, w_grads):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3)
    # the model's attention layer reaches the kernel with the scale
    cfg = tiny(attn_impl="flash")
    out = llama._attention(q, k, v, cfg.replace(attn_scale=scale))
    np.testing.assert_allclose(out, llama._attention_xla(
        q, k, v, True, scale=scale), rtol=2e-3, atol=2e-3)


def test_plans_read_back_from_a_profile_around_a_lowering(tmp_path):
    """``ssd.plan``, ``mixer.plan`` and ``hybrid.layer_plan`` are events of jax's profiler
    (util/tracing.py): a profile taken around a lowering holds them with
    their attributes, tracing on or off."""
    import glob
    import os

    from jax.profiler import ProfileData

    cfg = tiny(ssd_impl="pallas")
    params, tokens = make(cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        jax.jit(lambda p: hybrid.loss_fn(p, {"tokens": tokens}, cfg)[0]
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
                    if e.name in ("ssd.plan", "hybrid.layer_plan",
                                  "mixer.plan"):
                        events.setdefault(e.name, []).append(dict(e.stats))
    plan = events["hybrid.layer_plan"]
    assert [{k: p[k] for k in ("kinds", "runs", "bodies", "layers")}
            for p in plan] == [
        {"kinds": 2, "runs": 3, "bodies": 2, "layers": 4}]
    assert plan[0]["pattern"].startswith("mamba x")
    plan = events["ssd.plan"][0]
    assert plan["path"] == "pallas" and plan["chunk"] == 32
    assert plan["S"] == 96 and plan["heads_per_block"] == 8
    assert plan["vmem_bytes"] > 0 and plan["hbm_bytes_per_head"] > 0
    # once a traced mixer body, beside ssd.plan: the rules' own account at
    # B2 x S96, inner 128, 160 channels convolved, 8 heads, float32
    wide, conv, steps = 192 * 128 * 4, 192 * 160 * 4, 192 * 8 * 4
    assert events["mixer.plan"] == [{
        "path": "rules", "rows": 192, "groups": 1, "group_lanes": 128,
        "chunk": 32, "channels": 160, "residual_bytes": 0,
        "hbm_bytes_fwd": 2 * conv + 9 * wide + 3 * steps,
        "hbm_bytes_bwd": 15 * wide + 6 * conv + 4 * steps}]
    assert hybrid.plan(cfg.replace(remat=False), 2, 96)["residual_bytes"] \
        == conv + 3 * wide + steps


@pytest.mark.parametrize("lean", [-0.05, 0.0, 0.01, 0.1, 0.5])
def test_held_passes_hold_every_assignment_once(lean):
    """At a size of several row tiles an expert, with a router that leans
    away from the held experts, not at all (one pass nearly full), a little
    towards them (an expert's run straddles the first pass's end), further
    (the first pass and five a sixth as long) and wholly (ten): every
    assignment to a held expert stands in one live slot of one pass, in its
    expert's group; the passes' groups add up to the counts; the layer and
    its gradients are the reference's."""
    held, k, t = 2, 2, 2048
    cfg = tiny(n_experts=8, top_k=k, experts_held=(held, 3), shared_d_ff=0)
    rows = moe.held_rows(cfg, t)
    assert rows == 1536 and rows % 256 == 0      # 3/2 of the even 1024
    params, _ = make(cfg)
    lp = jax.tree.map(lambda w: w[0], params["layers"][0])
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (1, t, cfg.d_model)))
    lp["router"] = lp["router"].at[:, 3:5].add(lean * 0.05)
    (y, got), (want_y, want), stats = _layer_and_reference(cfg, lp, h)
    counts = np.asarray(stats["held_counts"])
    n = counts.sum()
    small = 256                # a further pass: a quarter, in row tiles
    assert int(stats["more_passes"]) == -(-max(n - rows, 0) // small)
    assert {-0.05: n < rows // 2, 0.0: rows - small < n <= rows,
            0.01: rows < n < rows + small, 0.1: rows + small < n < t * k,
            0.5: n == t * k}[lean], counts
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-5)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4 * float(
            jnp.max(jnp.abs(w)) + 1e-9))
    # the slots, pass by pass, as _held_pass lays them out
    experts = np.asarray(stats["experts"]).reshape(t * k)
    local = np.where((experts >= 3) & (experts < 3 + held), experts - 3, held)
    ranked = np.argsort(local, kind="stable")
    start = np.cumsum(counts) - counts
    seen, total = [], np.zeros(held, np.int64)
    passes = [(0, rows)] + [(lo, small) for lo in range(rows, t * k, small)]
    for lo, size in passes:
        slot = lo + np.arange(size)
        live = slot < counts.sum()
        sizes = np.maximum(np.minimum(start + counts, lo + size)
                           - np.maximum(start, lo), 0)
        assert sizes.sum() == live.sum()
        group = np.searchsorted(np.cumsum(sizes), np.arange(size),
                                side="right")
        order = ranked[np.minimum(slot, t * k - 1)]
        assert (local[order[live]] == group[live]).all()
        seen.extend(order[live])
        total += sizes
    assert (total == counts).all()
    assert sorted(seen) == sorted(np.flatnonzero(local < held))


# a pass of 4,096 rows over 10,800 assignments (3/2 of the even 2,700, in
# whole row tiles), in sixteen chunks of one row tile (the rule's threshold
# lowered to a size a test can afford)
_WALK = dict(held=2, first=3, k=2, t=5400, rows=4096, chunk=256, short=1024)


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(moe, "HELD_CHUNK_ROWS", _WALK["chunk"])


def _held_layer(live: int, dtype):
    """A layer's inputs with exactly ``live`` assignments to the held
    experts, scattered over the tokens: (cfg, x, weights, experts, lp)."""
    w = _WALK
    cfg = tiny(n_experts=8, top_k=w["k"], experts_held=(w["held"], w["first"]),
               shared_d_ff=0).replace(dtype=dtype)
    assert moe.held_rows(cfg, w["t"]) == w["rows"]
    assert moe.held_chunk(w["rows"]) == w["chunk"]
    key = jax.random.split(jax.random.PRNGKey(live), 6)
    n = w["t"] * w["k"]
    held = w["first"] + jnp.arange(n) % w["held"]
    away = (w["first"] + w["held"] + jnp.arange(n) % 3) % 8
    experts = jnp.where(jax.random.permutation(key[0], n) < live, held,
                        away).astype(jnp.int32).reshape(w["t"], w["k"])
    d, f = cfg.d_model, cfg.d_ff
    lp = {"we_gate": jax.random.normal(key[1], (w["held"], d, f)) / d ** .5,
          "we_up": jax.random.normal(key[2], (w["held"], d, f)) / d ** .5,
          "we_down": jax.random.normal(key[3], (w["held"], f, d)) / f ** .5}
    x = jax.random.normal(key[4], (w["t"], d)).astype(dtype)
    weights = jax.nn.softmax(jax.random.normal(key[5], (w["t"], w["k"])))
    return cfg, x, weights, experts, jax.tree.map(
        lambda a: a.astype(dtype), lp)


def _whole_pass(x, weights, experts, lp, cfg):
    """The held experts' part by the plain formulas: every assignment in
    one pass, ``x[token]`` into expert order and ``.at[token].add`` back,
    jax's own gradient."""
    k, (held, first) = cfg.top_k, cfg.experts_held
    local = experts.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    counts = jnp.sum(local[:, None] == jnp.arange(held), axis=0)
    live = (jnp.arange(order.size) < counts.sum())[:, None]
    token = order // k
    ys = moe._held_swiglu(
        jnp.where(live, x[token], 0), weights.reshape(-1)[order], live,
        counts, tuple(lp[n] for n in ("we_gate", "we_up", "we_down")), cfg)
    return jnp.zeros(x.shape, jnp.float32).at[token].add(ys).astype(x.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("live", [0, 1, 255, 256, 257, 4096, 4096 + 1500])
def test_chunked_walks_equal_the_whole_pass(live, dtype, small_chunks,
                                            monkeypatch):
    """The first pass's gather a chunk at a time, over the chunks that hold
    a live row, against the plain formulas: the layer, d_x, d_weights and
    the three weights' gradients, under a checkpoint too; and to the bit
    against the same walk in ONE chunk (the pass as it was walked
    before)."""
    cfg, x, weights, experts, lp = _held_layer(live, jnp.dtype(dtype))
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def through(layer):
        def loss(x, weights, lp):
            y = layer(x, weights, experts, lp, cfg)
            return jnp.sum(y.astype(jnp.float32) * probe), y
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)

    mine = lambda *a: moe._held_experts(*a)[0]              # noqa: E731
    (_, y), got = through(mine)(x, weights, lp)
    (_, kept_y), kept = through(jax.checkpoint(mine, static_argnums=4))(
        x, weights, lp)
    (_, want_y), want = through(_whole_pass)(x, weights, lp)
    stats = moe._held_experts(x, weights, experts, lp, cfg)[1]
    w = _WALK
    assert int(stats["held_counts"].sum()) == live
    assert int(stats["more_passes"]) == -(-max(live - w["rows"], 0)
                                          // w["short"])
    assert float(stats["walked_share"]) == \
        -(-min(live, w["rows"]) // w["chunk"]) * w["chunk"] / w["rows"]
    tol = {"float32": 2e-5, "bfloat16": 2e-2}[dtype]
    for g, k, r in zip(jax.tree.leaves((y, got)),
                       jax.tree.leaves((kept_y, kept)),
                       jax.tree.leaves((want_y, want))):
        assert g.dtype == r.dtype
        g, k, r = (np.asarray(a, np.float32) for a in (g, k, r))
        np.testing.assert_array_equal(g, k)
        np.testing.assert_allclose(g, r, rtol=tol,
                                   atol=tol * (np.abs(r).max() + 1e-9))
    monkeypatch.setattr(moe, "held_chunk", lambda rows: rows)
    (_, one_y), one = through(mine)(x, weights, lp)
    for g, o in zip(jax.tree.leaves((y, got)), jax.tree.leaves((one_y, one))):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(o, np.float32))


def test_walked_share_is_whole_chunks_and_reaches_the_report_span(
        small_chunks, monkeypatch):
    """``moe_held_walked_share``: the layers' mean of the chunks their
    first passes' gathers covered over the pass; a float of the step's
    metrics, so an attribute of the ``train.report`` span."""
    import contextlib

    from ray_tpu.train import session

    w = _WALK
    shares = []
    for live in (0, 700, 2049, 5000):
        cfg, x, weights, experts, lp = _held_layer(live, jnp.float32)
        shares.append(moe._held_experts(x, weights, experts, lp, cfg)[1])
        assert float(shares[-1]["walked_share"]) == {
            0: 0.0, 700: 0.1875, 2049: 0.5625, 5000: 1.0}[live]
    stats = jax.tree.map(lambda *a: jnp.stack(a), *shares)
    aux = moe.held_aux(stats["held_counts"].astype(jnp.float32), stats,
                       w["t"] * w["k"], 8)
    assert float(aux["moe_held_walked_share"]) == 0.4375
    assert float(aux["moe_held_more_passes"]) == 1.0
    # the step's metrics as a train loop reports them
    cfg = tiny(experts_held=(4, 2))
    params, tokens = make(cfg)
    _, metrics = hybrid.loss_fn(params, {"tokens": tokens}, cfg)
    assert float(metrics["moe_held_walked_share"]) == 1.0   # passes whole
    seen = {}
    monkeypatch.setattr(session, "get_context", lambda: None)
    monkeypatch.setattr(session, "_report", lambda *a: None)
    monkeypatch.setattr(
        session._tracing, "span",
        lambda name, attrs: seen.update({name: attrs})
        or contextlib.nullcontext())
    session.report({"step": 1, **{k: float(v) for k, v in metrics.items()
                                  if k.startswith("moe_")}})
    assert seen["train.report"]["moe_held_walked_share"] == float(
        metrics["moe_held_walked_share"])


@pytest.mark.parametrize("rows,chunk", [
    (49152, 3072), (65536, 4096),         # Mellum2, and as it was at 2x
    (30720, 30720), (18432, 18432),       # Granite, Nemotron: a sixteenth
    (12288, 12288), (6144, 6144),         # is under the threshold; GLM,
    (16384, 16384), (8192, 8192),         # Command A+; those two at 2x
    (40960, 2560),                        # Granite at 2x
    (32768, 2048), (32768 - 16, 32768 - 16), (32768 + 16 * 256, 2304),
    (16 * 2047, 16 * 2047),                          # no whole row tiles
    (40960 + 256, 40960 + 256), (300, 300), (256, 256)])
def test_the_chunk_rule(rows, chunk):
    """A chunk is a sixteenth of the pass where that is whole row tiles of
    the grouped matmul and 2,048 rows or more; a pass too small for that,
    or not sixteen whole tiles, is one chunk."""
    from ray_tpu.ops.grouped_matmul import GMM_TILING

    got = moe.held_chunk(rows)
    assert got == chunk and rows % got == 0
    assert got == rows or (got % GMM_TILING[0] == 0 and got >= 2048
                           and rows == 16 * got)


def test_the_cached_paths_refuse_a_config_that_states_its_own_scales():
    """prefill and decode embed, rotate, scale and add as llama does: a
    cache for a config that states otherwise is refused, llama's is not."""
    with pytest.raises(NotImplementedError, match="residual_multiplier"):
        cached.init_cache(tiny(), 1)
    with pytest.raises(NotImplementedError, match="rope"):
        cached.init_paged_cache(
            llama.PRESETS["tiny"].replace(rope=False), 4, 16)
    assert cached.init_cache(llama.PRESETS["tiny"], 1).k.shape[0] == 2


LETTERS = {"M": "mamba", "E": "experts", "*": "attention"}


@pytest.mark.parametrize("pattern,want", [
    ("MEMEM*EMEMEM*EMEMEM*", " ".join(f"[{c}]x1" for c in
                                      "MEMEM*EMEMEM*EMEMEM*")),
    ("MEMEM*EMEMEM*", " ".join(f"[{c}]x1" for c in "MEMEM*EMEMEM*")),
    ("MEM*EMEM*E", " ".join(f"[{c}]x1" for c in "MEM*EMEM*E")),
    ("MMMMM*MMMM", "[M]x5 [*]x1 [M]x4"),
    ("MMEE", "[M]x2 [E]x2"),
    ("MEEEM**", "[M]x1 [E]x3 [M]x1 [*]x2")])
def test_runs_of_blocks_that_hold_one_half(pattern, want):
    """With ``one_half`` a run is adjacent blocks of one of THREE kinds (a
    published pattern with no two alike: a run a block); the parameter tree
    holds one stack a run, as long as the run, of the kind's own leaves."""
    cfg = hybrid.PRESETS["tiny-nemotron"].replace(
        n_layers=len(pattern), layer_types=tuple(LETTERS[c] for c in pattern))
    back = {v: k for k, v in LETTERS.items()}
    runs = hybrid.layer_runs(cfg)
    assert " ".join(f"[{back[kind]}]x{n}" for kind, n in runs) == want
    shapes = jax.eval_shape(lambda: hybrid.init_params(
        jax.random.PRNGKey(0), cfg))["layers"]
    own = {"mamba": "in_proj", "attention": "wq", "experts": "router"}
    for (kind, n), run in zip(runs, shapes):
        assert all(leaf.shape[0] == n for leaf in jax.tree.leaves(run))
        assert [k for k in own.values() if k in run] == [own[kind]]


def test_one_group_and_two_halves_are_the_program_granite_has():
    """``mamba_groups`` 1 and ``one_half`` False are the defaults:
    Granite's tree, runs and mixer are what they were."""
    cfg = tiny()
    assert (cfg.mamba_groups, cfg.one_half, cfg.tied_head,
            cfg.expert_act) == (1, False, True, "swiglu")
    assert hybrid.halves(cfg, "mamba") == hybrid.halves(cfg, "attention") \
        == (True, True)
    params, _ = make(cfg)
    assert "lm_head" not in params
    assert all("we_gate" in run and "ffn_norm" in run
               for run in params["layers"])
    with pytest.raises(ValueError, match="unknown layer type"):
        hybrid.layer_runs(cfg.replace(layer_types=(
            "mamba", "experts", "attention", "mamba")))
    plan = hybrid.plan(cfg, 2, 96)
    assert (plan["groups"], plan["group_lanes"], plan["channels"]) \
        == (1, cfg.mamba_inner, cfg.mamba_inner + 2 * cfg.mamba_state)
