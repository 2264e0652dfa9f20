"""The chunked gated delta rule (ops/delta_rule.py) against the recurrence
advanced a step at a time: values and all six gradients on both paths, over
lengths that are and are not whole chunks; the gate at its bound; the
inverse by block forward substitution; the plan and the refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import reference_ling
from ray_tpu.ops import delta_rule as dr


def recurrence(q, k, v, g, beta, state):
    """[B, S, H, .] inputs through ``reference_ling.delta_rule``, a
    sequence at a time."""
    return jax.vmap(lambda *a: reference_ling.delta_rule(*a)[0])(
        q, k, v, g, beta, state)


def make(S, B=2, H=2, dk=16, dv=16, seed=0, lower=-5.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = lower * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (B, S, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    state = 0.3 * jax.random.normal(ks[5], (B, H, dk, dv))
    weight = jax.random.normal(ks[6], (B, S, H, dv))
    return (q, k, v, g, beta, state), weight


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("S", [64, 100, 16])
def test_values_and_six_gradients_are_the_recurrences(impl, S):
    """A chunk of 32: two whole chunks, three and a part, half a chunk; q,
    k, v, g, beta and the initial state."""
    args, weight = make(S)

    def op(q, k, v, g, beta, state):
        return dr.gated_delta_rule(q, k, v, g, beta, initial_state=state,
                                   chunk=32, impl=impl)

    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        want_grads = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                              argnums=range(6))(*args)
        got = jax.jit(op)(*args)
        grads = jax.jit(jax.grad(lambda *a: (op(*a) * weight).sum(),
                                 argnums=range(6)))(*args)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for name, a, b in zip("q k v g beta state".split(), grads, want_grads):
        np.testing.assert_allclose(
            a, b, atol=3e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_gate_at_its_bound_stays_finite(impl):
    """g = -5 every step and channel for four chunks of 32: exp(-G) alone
    would overflow after 18 steps; the sub-blocks' factors, taken from the
    sub-block's middle, neither overflow nor fall among the denormals, and
    the values and gradients are the recurrence's."""
    (q, k, v, g, beta, state), weight = make(128)
    g = jnp.full_like(g, -5.0)

    def loss(fn):
        return lambda q, k, v, g: (fn(q, k, v, g) * weight).sum()

    op = lambda q, k, v, g: dr.gated_delta_rule(                # noqa: E731
        q, k, v, g, beta, initial_state=state, chunk=32, impl=impl)
    ref = lambda q, k, v, g: recurrence(q, k, v, g, beta, state)  # noqa
    with jax.default_matmul_precision("highest"):
        got = jax.jit(op)(q, k, v, g)
        grads = jax.jit(jax.grad(loss(op), argnums=range(4)))(q, k, v, g)
        want = ref(q, k, v, g)
        want_grads = jax.grad(loss(ref), argnums=range(4))(q, k, v, g)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=5e-6)
    # the running sum's own rounding shows at the bound (an ulp of 80 is
    # 8e-6, and exp carries it on)
    for a, b in zip(grads, want_grads):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))


def test_a_bound_the_sub_blocks_cannot_hold_is_refused():
    (q, k, v, g, beta, _), _ = make(64)
    with pytest.raises(ValueError, match="overflows float32"):
        dr.gated_delta_rule(q, k, v, g, beta, lower_bound=-11.0)
    with pytest.raises(ValueError, match="power of two"):
        dr.gated_delta_rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="'xla' or 'pallas'"):
        dr.gated_delta_rule(q, k, v, g, beta, impl="mosaic")


def test_the_inverse_by_block_substitution_is_the_inverse():
    """Adjacent rows alike and beta 1, where the powers of A grow like
    binomials: the block form holds."""
    c = 64
    a = jnp.tril(jnp.ones((c, c)), -1) * 0.97
    got = dr._unit_lower_inverse(a)
    np.testing.assert_allclose(got @ (jnp.eye(c) + a), jnp.eye(c), atol=2e-5)
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (c, c)), -1) * 0.3
    np.testing.assert_allclose(dr._unit_lower_inverse(a),
                               jnp.linalg.inv(jnp.eye(c) + a), rtol=2e-3,
                               atol=2e-3)


def test_the_plan_counts_the_states_and_the_vmem():
    plan = dr.plan(B=1, S=16384, H=32, dk=128, dv=128, chunk=64,
                   dtype=jnp.bfloat16, impl="pallas")
    assert plan["path"] == "pallas" and plan["sub_block"] == 16
    assert plan["heads_per_block"] == 2 and plan["chunk"] == 64
    # 256 chunks x 32 heads x [128, 128] float32
    assert plan["state_bytes_kept"] == 256 * 32 * 128 * 128 * 4
    assert 0 < plan["vmem_bytes"] <= 16 * 2 ** 20
    assert dr.plan(B=1, S=100, H=2, dk=16, dv=16, chunk=32,
                   dtype=jnp.float32, impl="xla")["vmem_bytes"] == 0
