"""Llama model + sharded train step on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu.models import cached, llama  # noqa: E402
from ray_tpu.parallel import (MeshSpec, ShardingRules, build_mesh)  # noqa: E402
from ray_tpu.parallel.train_step import (make_train_state_init,  # noqa: E402
                                         make_train_step)

CFG = llama.PRESETS["tiny"].replace(remat=False, dtype=jnp.float32)


def _rope_halves_by_hand(x, cos, sin):
    """The body ``llama.apply_rope`` had before PR 64 for transformers'
    ``rotate_half``: the lanes split at HD / 2 and joined again; cos and sin
    one column a pair, [S, HD / 2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _rope_neighbours_by_hand(x, cos, sin):
    """The body it had for GPT-J's pairs: the pair's other lane by two
    shifts along the lanes and a select; cos and sin [S, HD], every column
    of the pairs' tables twice."""
    f = x.astype(jnp.float32)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    other = jnp.where(even, -jnp.roll(f, -1, axis=-1),
                      jnp.roll(f, 1, axis=-1))
    return (f * cos[None, :, None, :]
            + other * sin[None, :, None, :]).astype(x.dtype)


@pytest.mark.parametrize("offset", [0, 7])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("head_dim", [16, 64, 128, 256])
@pytest.mark.parametrize("pairs", llama.PAIRINGS)
def test_apply_rope_is_the_split_and_the_shifted_bodies_bit_for_bit(
        pairs, head_dim, dtype, offset):
    """One product with the pairing's signed swap and two multiplies give
    the values of the two bodies the function had (kept here as plain
    references), bit for bit, whatever the head's width, the type and the
    rows of the tables (``offset``: a decode step's slice); the gradient,
    the rotation back, is theirs bit for bit in float32 and within one
    bfloat16 ulp in bfloat16. Op by op, as the references run: a compiler
    may contract either form's multiply-adds its own way."""
    dt, S = getattr(jnp, dtype), 24
    kx, kw = jax.random.split(jax.random.PRNGKey(head_dim + offset))
    x = jax.random.normal(kx, (2, S, 3, head_dim), jnp.float32).astype(dt)
    w = jax.random.normal(kw, x.shape, jnp.float32)
    by_pair = tuple(t[offset:offset + S] for t in llama._pair_tables(
        10000.0, S + offset, head_dim))
    cos, sin = llama._on_lanes(by_pair, pairs)
    assert cos.shape == sin.shape == (S, head_dim)
    if pairs == "halves":
        by_hand = lambda x: _rope_halves_by_hand(x, *by_pair)  # noqa: E731
    else:
        by_hand = lambda x: _rope_neighbours_by_hand(x, cos, sin)  # noqa: E731
    turn = lambda x: llama.apply_rope(x, cos, sin, pairs)      # noqa: E731
    want, got = by_hand(x), turn(x)
    assert got.dtype == want.dtype == dt
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - x.astype(jnp.float32)))) > 0.5
    g_want, g_got = (
        np.asarray(jax.grad(lambda x: jnp.sum(
            f(x).astype(jnp.float32) * w))(x), np.float32)
        for f in (by_hand, turn))
    if dtype == "float32":
        np.testing.assert_array_equal(g_got, g_want)
    else:       # bfloat16 keeps 8 bits: an ulp is 2^-7 of the power of two
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(g_want), 1e-30)))
                      - 7)
        assert np.all(np.abs(g_got - g_want) <= ulp)


def test_apply_rope_refuses_tables_of_one_column_a_pair():
    x = jnp.ones((1, 4, 2, 16))
    cos, sin = llama._pair_tables(10000.0, 4, 16)
    with pytest.raises(ValueError, match="both its lanes"):
        llama.apply_rope(x, cos, sin)
    for refused in (lambda: llama._on_lanes((cos, sin), "odd"),
                    lambda: llama.apply_rope(
                        x, *llama._rope_tables(10000.0, 4, 16), "odd")):
        with pytest.raises(ValueError, match="pairing"):
            refused()


def test_apply_rope_takes_tables_of_every_rows_own_positions():
    """models/cached.py gathers the tables at each row's positions:
    [B, S, HD], a row's own tables turning that row."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 16))
    wide = llama._rope_tables(10000.0, 4, 16)
    rows = tuple(jnp.stack([t, t]) for t in wide)
    np.testing.assert_array_equal(
        llama.apply_rope(jnp.concatenate([x, x]), *rows)[1],
        llama.apply_rope(x, *wide)[0])


def test_forward_shapes():
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert jnp.isfinite(logits).all()


def test_causality():
    """Changing future tokens must not change past logits."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, 5:].set(9)
    l1 = llama.forward(params, t1, CFG)
    l2 = llama.forward(params, t2, CFG)
    np.testing.assert_allclose(np.asarray(l1[0, :5]), np.asarray(l2[0, :5]),
                               rtol=1e-5, atol=1e-5)


def test_kv_cache_matches_forward():
    params = llama.init_params(jax.random.PRNGKey(1), CFG)
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                CFG.vocab_size)
    full = llama.forward(params, tokens, CFG)

    cache = cached.init_cache(CFG, B, max_seq=32)
    # prefill first 8, then decode one at a time
    logits, cache = cached.forward_with_cache(params, tokens[:, :8], cache,
                                             CFG, 0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, 7]),
                               rtol=2e-4, atol=2e-4)
    for i in range(8, S):
        logits, cache = cached.forward_with_cache(params, tokens[:, i:i + 1],
                                                 cache, CFG, i)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, i]),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rules_name,mesh_spec", [
    ("dp", MeshSpec(dp=8)),
    ("fsdp", MeshSpec(dp=2, fsdp=4)),
    ("fsdp_tp", MeshSpec(dp=2, fsdp=2, tp=2)),
])
def test_sharded_training_loss_decreases(rules_name, mesh_spec):
    mesh = build_mesh(mesh_spec)
    rules = getattr(ShardingRules, rules_name)()
    cfg = CFG
    optimizer = optax.adamw(1e-2)

    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), optimizer, mesh, rules,
        llama.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), optimizer,
                           mesh, rules, state_sh,
                           batch_shapes=jax.eval_shape(lambda: batch))
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses
    assert np.isfinite(losses).all()


def test_sp_ring_training_step():
    """Sequence parallelism: rules 'full' with sp=4; the model's ring
    attention path must produce finite grads and match dp-only loss."""
    cfg = CFG.replace(attn_impl="ring")
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    rules = ShardingRules.full()
    optimizer = optax.sgd(1e-2)
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), optimizer, mesh, rules,
        llama.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    # sp shards the seq dim: use explicit inputs/targets of length 32 (=sp*8)
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}

    from ray_tpu.parallel.train_step import make_train_step as mts

    params_host = jax.device_get(state.params)   # before donation
    step = mts(lambda p, b: llama.loss_fn(p, b, cfg, mesh=mesh), optimizer, mesh, rules,
               state_sh, batch_shapes=jax.eval_shape(lambda: batch))
    state2, metrics = step(state, batch)
    sp_loss = float(metrics["loss"])

    # reference: same params, xla attention, no sharding
    cfg_ref = CFG
    ref_loss = float(llama.loss_fn(params_host, batch, cfg_ref))
    assert np.isfinite(sp_loss)
    np.testing.assert_allclose(sp_loss, ref_loss, rtol=2e-3)


def test_sp_ulysses_training_step():
    """Sequence parallelism: rules 'full' with sp=4; the model's Ulysses
    all-to-all attention path must produce finite grads and match dp-only loss."""
    cfg = CFG.replace(attn_impl="ulysses", n_kv_heads=4)
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    rules = ShardingRules.full()
    optimizer = optax.sgd(1e-2)
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), optimizer, mesh, rules,
        llama.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    # sp shards the seq dim: use explicit inputs/targets of length 32 (=sp*8)
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}

    from ray_tpu.parallel.train_step import make_train_step as mts

    params_host = jax.device_get(state.params)   # before donation
    step = mts(lambda p, b: llama.loss_fn(p, b, cfg, mesh=mesh), optimizer, mesh, rules,
               state_sh, batch_shapes=jax.eval_shape(lambda: batch))
    state2, metrics = step(state, batch)
    sp_loss = float(metrics["loss"])

    # reference: same params, xla attention, no sharding
    cfg_ref = CFG.replace(n_kv_heads=4)
    ref_loss = float(llama.loss_fn(params_host, batch, cfg_ref))
    assert np.isfinite(sp_loss)
    np.testing.assert_allclose(sp_loss, ref_loss, rtol=2e-3)


def test_sliding_window_attention():
    """cfg.sliding_window bands the attention: positions inside the
    window match full causal exactly, later positions diverge (xla
    path; the flash path is validated in test_ops_attention.py)."""
    import numpy as np

    cfg = llama.PRESETS["tiny"].replace(remat=False, dtype=jnp.float32,
                                        sliding_window=16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 64)), jnp.int32)
    banded = llama.forward(params, toks, cfg)
    full = llama.forward(params, toks, cfg.replace(sliding_window=None))
    assert float(jnp.abs(banded[:, :16] - full[:, :16]).max()) < 1e-5
    assert float(jnp.abs(banded[:, -1] - full[:, -1]).max()) > 1e-3


@pytest.mark.slow
def test_sliding_window_decode_and_guards():
    """decode_step applies the same band as training (identical to
    full-causal decode before W, diverges after); ring/ulysses reject
    sliding_window instead of silently computing full attention."""
    import numpy as np

    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, 1000, (1, 24)), jnp.int32)

    def decode_all(W):
        cfg = llama.PRESETS["tiny"].replace(remat=False,
                                            dtype=jnp.float32,
                                            sliding_window=W)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        cache = cached.init_cache(cfg, batch=1, max_seq=24)
        outs = []
        for t in range(24):
            lg, cache = cached.decode_step(params, toks[:, t:t + 1],
                                          cache, cfg)
            outs.append(lg)
        return jnp.stack(outs, 1)

    full, win = decode_all(None), decode_all(8)
    assert float(jnp.abs(win[:, :8] - full[:, :8]).max()) == 0.0
    assert float(jnp.abs(win[:, -1] - full[:, -1]).max()) > 1e-3

    cfg = llama.PRESETS["tiny"].replace(remat=False, dtype=jnp.float32,
                                        sliding_window=8,
                                        attn_impl="ring")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="sliding_window"):
        llama.forward(params, toks, cfg)


def _pallas_calls(jaxpr):
    """pallas_call equations of a jaxpr, sub-jaxprs (scan, checkpoint,
    custom_vjp, shard_map bodies) included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub)
    return n


# The KV layouts the flash backward tells apart: grouped (CFG: 4 heads
# over 2 KV heads) and multi-head, which every benchmark cell runs and
# where the dK/dV call writes the inputs' dtype with no group sum
KV_LAYOUTS = {"gqa": CFG.n_kv_heads, "mha": CFG.n_heads}


def _flash_cfg(remat, kv="gqa"):
    # S128 is the least length that takes the kernel (llama._attention)
    return CFG.replace(attn_impl="flash", remat=remat,
                       n_kv_heads=KV_LAYOUTS[kv])


def _flash_batch():
    return {"tokens": jax.random.randint(jax.random.PRNGKey(12), (2, 129), 0,
                                         CFG.vocab_size)}


@pytest.mark.parametrize("kv", sorted(KV_LAYOUTS))
def test_remat_keeps_flash_residuals_call_count(kv):
    """The per-layer checkpoint keeps the kernel's output and log-sum-exp,
    so the backward holds forward, dq and dkdv — the same three calls as
    without remat — and not the forward a second time."""
    params, batch = llama.init_params(
        jax.random.PRNGKey(11), _flash_cfg(True, kv)), _flash_batch()

    def calls(cfg):
        return _pallas_calls(jax.make_jaxpr(jax.grad(
            lambda p: llama.loss_fn(p, batch, cfg)))(params).jaxpr)

    assert calls(_flash_cfg(True, kv)) == 3
    assert calls(_flash_cfg(False, kv)) == 3


@pytest.mark.parametrize("kv", sorted(KV_LAYOUTS))
def test_remat_keeps_flash_residuals_grad_parity(kv):
    """What the checkpoint saves never changes the math: loss and every
    gradient leaf match the program without remat."""
    params, batch = llama.init_params(
        jax.random.PRNGKey(11), _flash_cfg(True, kv)), _flash_batch()
    l1, g1 = jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, _flash_cfg(False, kv)))(params)
    l2, g2 = jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, _flash_cfg(True, kv)))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g1, g2)


@pytest.mark.parametrize("kv", sorted(KV_LAYOUTS))
def test_remat_keeps_flash_residuals_under_shard_map(kv):
    """The same count where the kernel runs per shard under shard_map
    (fsdp x tp on four virtual devices): the names survive the map."""
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    rules = ShardingRules.fsdp_tp()
    cfg = _flash_cfg(True, kv)
    params, batch = llama.init_params(jax.random.PRNGKey(11), cfg), \
        _flash_batch()
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(
        p, batch, cfg, mesh=mesh, rules=rules)))(params).jaxpr
    assert "shard_map" in str(jaxpr)
    assert _pallas_calls(jaxpr) == 3


def test_bf16_logits_flag():
    """f32_logits=False keeps logits in the compute dtype; loss still
    computes its reductions in f32 and matches the f32-logits loss."""
    cfg16 = CFG.replace(dtype=jnp.bfloat16, f32_logits=False)
    cfg32 = CFG.replace(dtype=jnp.bfloat16, f32_logits=True)
    params = llama.init_params(jax.random.PRNGKey(7), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0,
                                CFG.vocab_size)
    out16 = llama.forward(params, tokens, cfg16)
    assert out16.dtype == jnp.bfloat16
    out32 = llama.forward(params, tokens, cfg32)
    assert out32.dtype == jnp.float32
    l16 = llama.loss_fn(params, {"tokens": tokens}, cfg16)
    l32 = llama.loss_fn(params, {"tokens": tokens}, cfg32)
    assert l16.dtype == jnp.float32
    np.testing.assert_allclose(float(l16), float(l32), rtol=2e-2)


def _flash_adafactor_steps(mesh, rules, cfg, n=3):
    opt = optax.adafactor(3e-3)
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), opt, mesh, rules,
        llama.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0,
                                          cfg.vocab_size)}
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh=mesh, rules=rules), opt,
        mesh, rules, state_sh, batch_shapes=jax.eval_shape(lambda: batch))
    losses = []
    for _ in range(n):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses, state, state_sh


@pytest.mark.parametrize("rules_name,mesh_spec", [
    ("fsdp_tp", MeshSpec(fsdp=2, tp=2)),
    ("fsdp", MeshSpec(fsdp=4)),
    ("dp", MeshSpec(dp=4)),
])
def test_sharded_flash_adafactor_step_matches_one_device(rules_name,
                                                         mesh_spec):
    """The two repairs behind four-chip training, kept in tier-1: the
    flash kernel (interpreted here) runs per shard under shard_map — a
    bare Mosaic call cannot be partitioned — and adafactor's factored
    rank-1 state gets a valid (replicated) sharding. Same losses as the
    one-device step. GQA (4 heads over 2 kv heads) with tp=2 keeps whole
    groups per shard."""
    # wide enough for adafactor to factor (both dims >= 128)
    cfg = CFG.replace(d_model=128, d_ff=256, remat=True, attn_impl="flash")
    devs = jax.devices()[:4]
    losses, state, state_sh = _flash_adafactor_steps(
        build_mesh(mesh_spec, devices=devs),
        getattr(ShardingRules, rules_name)(), cfg)
    one, _, _ = _flash_adafactor_steps(
        build_mesh(MeshSpec(dp=-1), devices=devs[:1]), ShardingRules.dp(),
        cfg)
    np.testing.assert_allclose(losses, one, rtol=1e-5)
    assert losses[-1] < losses[0]
    v_row = state.opt_state[0].v_row["layers"]["wq"]
    assert v_row.ndim == 2 and v_row.shape[-1] == 128     # factored
    assert state_sh.opt_state[0].v_row["layers"]["wq"].spec == \
        jax.sharding.PartitionSpec()
    if rules_name != "dp":
        wq = state.params["layers"]["wq"]
        assert wq.addressable_shards[0].data.shape != wq.shape


# --- the tensor axis's overlap plan (parallel/collective_matmul.py) ---------


def _loss_and_grads(mod, cfg, batch, mesh=None, rules=None):
    """(loss, gradients, jaxpr text) of ``mod.loss_fn`` on one set of
    weights, placed by ``rules`` where a mesh is given."""
    from ray_tpu.parallel import shard_params

    params = mod.init_params(jax.random.PRNGKey(3), cfg)
    if mesh is not None:
        params = shard_params(mesh, params, mod.param_specs(cfg), rules)

    def loss(p):
        out = mod.loss_fn(p, batch, cfg, mesh=mesh, rules=rules)
        return out[0] if isinstance(out, tuple) else out

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(value), jax.device_get(grads), str(jax.make_jaxpr(loss)(params))


def _moe_tiny():
    from ray_tpu.models import moe

    return moe, moe.PRESETS["tiny"].replace(dtype=jnp.float32)


def _parallel_tiny():
    from ray_tpu.models import moe

    return moe, moe.PRESETS["tiny-commanda"].replace(
        dtype=jnp.float32, experts_held=None, n_layers=4,
        layer_kinds=("window", "window", "window", "full"))


# (family and config, rules, tokens a row, whether the overlap plan engages)
TP_PLAN_CASES = {
    # S 128: the flash kernel under its shard_map between the two helpers
    "flash-s128": (lambda: (llama, _flash_cfg(True, "mha")), "fsdp_tp", 129,
                   True),
    "xla-s32-gqa": (lambda: (llama, CFG.replace(remat=True)), "fsdp_tp", 33,
                    True),
    # 31 rows over 2 shards: the plain program, line for line
    "rows-do-not-divide": (lambda: (llama, CFG), "fsdp_tp", 32, False),
    # one KV head over 2 shards: its width divides, the head does not, so
    # the flash shard_map keeps the heads whole and the plan stays out
    "kv-heads-do-not-divide": (lambda: (llama, _flash_cfg(True).replace(
        n_kv_heads=1)), "fsdp_tp", 129, False),
    "xla-kv-heads-do-not-divide": (lambda: (llama, CFG.replace(
        n_kv_heads=1)), "fsdp_tp", 33, False),
    # a block whose feed-forward is an expert layer stays plain as a whole
    "moe-under-ep": (_moe_tiny, "ep", 33, False),
    # a parallel block (one LayerNorm, attention and experts side by side,
    # layers of two kinds in two stacks) stays plain too, and its sharded
    # program is the one-device one
    "parallel-block-under-ep": (_parallel_tiny, "ep", 33, False),
}


@pytest.mark.parametrize("case", sorted(TP_PLAN_CASES))
def test_fsdp_tp_loss_and_gradients_match_one_device(case):
    """``loss_fn`` and every gradient under MeshSpec(fsdp=2, tp=2) against
    the single-device program, where the tensor-parallel matmuls overlap
    their own communication and where the plan does not engage."""
    family, rules_name, width, engages = TP_PLAN_CASES[case]
    mod, cfg = family()
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    rules = getattr(ShardingRules, rules_name)()
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(12), (4, width),
                                          0, cfg.vocab_size)}
    want, want_g, _ = _loss_and_grads(mod, cfg, batch)
    got, got_g, text = _loss_and_grads(mod, cfg, batch, mesh, rules)
    assert ("ppermute" in text) == engages
    np.testing.assert_allclose(got, want, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5), got_g, want_g)


def test_tp_overlap_plan_instant_says_which_path():
    """``tp.overlap_plan`` once a traced forward with a mesh: the
    attributes a profile around a lowering shows."""
    from ray_tpu.util import tracing

    seen = []
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    rules = ShardingRules.fsdp_tp()
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0),
                                                      CFG))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracing, "instant",
                   lambda name, attrs=None, **kw: seen.append((name, attrs)))
        for width in (32, 31):
            jax.eval_shape(lambda p, t: llama.forward(
                p, t, CFG, mesh=mesh, rules=rules), params,
                jax.ShapeDtypeStruct((4, width), jnp.int32))
        jax.eval_shape(lambda p, t: llama.forward(p, t, CFG), params,
                       jax.ShapeDtypeStruct((4, 32), jnp.int32))
    plans = [a for n, a in seen if n == "tp.overlap_plan"]
    assert plans == [
        {"path": "overlap", "shards": 2, "sites": 4, "rows_per_step": 16,
         # two rows of the batch a device x 16 rows x 64 wide x f32
         "bytes_per_permute": 2 * 16 * CFG.d_model * 4,
         # a layer's seven weights leave their helper as they are stored,
         # embed over fsdp; the largest part that travels is a quarter
         # (fsdp 2 x tp 2) of w_gate, f32
         "grad_shards": 2, "grad_sites": 7,
         "grad_bytes_per_permute": CFG.d_model * CFG.d_ff // 4 * 4},
        {"path": "plain", "shards": 1, "sites": 0, "rows_per_step": 31,
         "bytes_per_permute": 0, "grad_shards": 1, "grad_sites": 0,
         "grad_bytes_per_permute": 0}]


def _primitives(jaxpr, into=None):
    """How often each primitive stands in a jaxpr, its sub-jaxprs (scan
    and checkpoint bodies, custom-derivative calls) included."""
    import collections

    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, into)
    return into


# The one-device program is to stay the program it was before the overlap
# plan (PR 29's tree). What holds under any jax: it names no mesh and moves
# nothing between devices. Under the jax that traced them (JAXPRS_FROM), also
# the sha256 (first 16 hex digits) of str(jax.make_jaxpr(value_and_grad(
# loss))) with function addresses cut out, B2 x S128 tokens, as that tree
# traced it; another jax prints another text, and the digests are then left
# alone. A deliberate change to the model's forward changes them: trace the
# parent and the change, compare the primitive counts the failure prints,
# and replace them. (PR 37 replaced the three `flash` ones: the text holds
# the kernels' bodies, and the forward and dQ kernels changed; outside the
# three pallas_calls the primitive counts are the parent's, and the two
# `xla` digests stood. PR 43 replaced all five: `_attention_half` names q, k
# and v for the layer checkpoint whatever the attention call, three `name`
# equations a traced body, 2 -> 8, 0 -> 3, 7 -> 13 and 5 -> 8 in all; every
# other primitive's count is the parent's, and with no limit nothing more is
# kept, so the equations lower to nothing. PR 53 replaced the three dense
# ones: ``feed_forward`` names gate's and up's products, two ``name``
# equations a traced body, 8 -> 12, 8 -> 12 and 3 -> 5; every other
# primitive's count is the parent's and the two ``moe`` digests stood. PR 60
# replaced the two ``moe`` ones: the router selects by rounds
# (``moe.top_lanes``), ``top_k`` 2 -> 0 and 1 -> 0, ``scatter-add`` 4 -> 3
# both, ``reduce_min`` 0 -> 4 and 0 -> 2, ``reduce_max`` 7 -> 11 and 5 -> 7
# with the selects, compares and masks of the rounds; the three dense
# digests stood. PR 64 replaced all five: ``apply_rope`` is one product with
# a signed swap under a ``custom_vjp`` (``split`` 6 -> 0, 4 -> 0, 7 -> 1 and
# 5 -> 1, ``concatenate`` 6 -> 2, 4 -> 2, 9 -> 5 and 6 -> 4: what is left is
# the tables' cos | cos and sin | sin, once a step; ``custom_vjp_call`` + 6
# with a checkpoint's replay and + 4 without, ``dot_general`` likewise,
# ``optimization_barrier`` 0 -> 6 and 0 -> 4: x read as an array of its own,
# a product; mul, sub, neg and add_any fall with the
# halves' four products a tensor); every other primitive's count is the
# parent's.)
JAXPRS_FROM = "0.9.0"
ONE_DEVICE_JAXPRS = {
    ("dense", 2, "flash", True, "bfloat16"): "5e9496703650cc7c",
    ("dense", 4, "flash", True, "bfloat16"): "673d72968a7a01d9",
    ("dense", 2, "xla", False, "float32"): "a86f1c23162edebc",
    ("moe", 2, "flash", True, "bfloat16"): "c0dba656fbe24a74",
    ("moe", 2, "xla", False, "float32"): "263d461e4de10de6",
}


@pytest.mark.parametrize("case", sorted(ONE_DEVICE_JAXPRS, key=str))
def test_one_device_jaxpr_is_the_program_before_the_overlap_plan(case):
    import hashlib
    import re

    family, kv, attn, remat, dt = case
    if family == "dense":
        mod, cfg = llama, llama.PRESETS["tiny"].replace(n_kv_heads=kv)
    else:
        mod, cfg = _moe_tiny()
    cfg = cfg.replace(attn_impl=attn, remat=remat, dtype=getattr(jnp, dt),
                      max_seq_len=128)
    params = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 129), jnp.int32)}

    def loss(p, b):
        out = mod.loss_fn(p, b, cfg)
        return out[0] if isinstance(out, tuple) else out

    closed = jax.make_jaxpr(jax.value_and_grad(loss))(params, batch)
    counts = _primitives(closed.jaxpr)
    across = {"ppermute", "shard_map", "sharding_constraint", "psum",
              "all_gather", "all_to_all", "axis_index"} & set(counts)
    assert not across, across
    assert (counts["pallas_call"] > 0) == (attn == "flash")
    if jax.__version__ == JAXPRS_FROM:
        text = re.sub(r" at 0x[0-9a-f]+", "", str(closed))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            ONE_DEVICE_JAXPRS[case], sorted(counts.items())


@pytest.mark.parametrize("remat", [True, False])
def test_nothing_handed_on_leaves_the_scan_with_x_alone(remat):
    """A family whose attention half hands nothing on to the next layer
    (every family but one with a learned selection) carries x alone
    through the layer scan, as before the mechanism was there: one carry,
    and no handed value among the scan's constants."""
    import jax

    from ray_tpu.models import llama

    cfg = llama.PRESETS["debug-125m"].replace(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=256, max_seq_len=64, remat=remat)
    params = jax.eval_shape(lambda k: llama.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 33), "int32")
    jaxpr = jax.make_jaxpr(lambda p, t: llama.loss_fn(p, {"tokens": t}, cfg))(
        params, tokens)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    assert scans[0].params["num_carry"] == 1
    assert not [v for v in scans[0].invars if str(v.aval.dtype) == "int8"]


def test_join_stats_by_leaf_and_by_name():
    """Runs that report the same things are joined leaf by leaf, as they
    always were; runs that differ (a run whose layers report a term that
    another run's lack) name by name over the runs that have it."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    a = {"x": jnp.arange(2.0), "y": jnp.ones((2, 3))}
    b = {"x": jnp.arange(3.0), "y": jnp.zeros((3, 3))}
    same = llama._join_stats([a, b])
    assert same["x"].shape == (5,) and same["y"].shape == (5, 3)
    mixed = llama._join_stats([{"z": jnp.ones(1)}, a, {**b, "z": jnp.zeros(2)}])
    assert sorted(mixed) == ["x", "y", "z"]
    np.testing.assert_array_equal(mixed["z"], [1.0, 0.0, 0.0])
    np.testing.assert_array_equal(mixed["x"], [0.0, 1.0, 0.0, 1.0, 2.0])
