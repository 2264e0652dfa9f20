"""GPT-2 model test (the MoE tests live in test_models_moe.py)."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu.models import gpt2  # noqa: E402


def test_gpt2_forward_and_train():
    cfg = gpt2.PRESETS["tiny"].replace(dtype=jnp.float32, remat=False)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits = gpt2.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)

    opt = optax.adamw(1e-2)
    state = opt.init(params)
    batch = {"tokens": tokens}

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(gpt2.loss_fn)(params, batch, cfg)
        up, state = opt.update(g, state, params)
        return optax.apply_updates(params, up), state, loss

    losses = []
    for _ in range(8):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9
