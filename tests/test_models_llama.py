"""Llama model + sharded train step on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.parallel import (MeshSpec, ShardingRules, build_mesh)  # noqa: E402
from ray_tpu.parallel.train_step import (make_train_state_init,  # noqa: E402
                                         make_train_step)

CFG = llama.PRESETS["tiny"].replace(remat=False, dtype=jnp.float32)


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

    cache = llama.init_cache(CFG, B, max_seq=32)
    # prefill first 8, then decode one at a time
    logits, cache = llama.forward_with_cache(params, tokens[:, :8], cache,
                                             CFG, 0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, 7]),
                               rtol=2e-4, atol=2e-4)
    for i in range(8, S):
        logits, cache = llama.forward_with_cache(params, tokens[:, i:i + 1],
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
        cache = llama.init_cache(cfg, batch=1, max_seq=24)
        outs = []
        for t in range(24):
            lg, cache = llama.decode_step(params, toks[:, t:t + 1],
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
