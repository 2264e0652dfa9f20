"""Kernel correctness: flash attention (pallas, interpret on CPU) and ring
attention (8-device CPU mesh) vs the reference einsum implementation."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from ray_tpu.models.llama import _attention_xla  # noqa: E402
from ray_tpu.ops.flash_attention import flash_attention  # noqa: E402
from ray_tpu.ops.ring_attention import ring_attention  # noqa: E402


def _make(B=2, S=256, H=4, KV=2, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    return q, k, v


def test_flash_matches_reference():
    q, k, v = _make()
    ref = _attention_xla(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_gradients_match():
    q, k, v = _make(B=1, S=128, H=2, KV=2, D=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, block_q=32, block_k=32).sum()

    def loss_ref(q, k, v):
        return _attention_xla(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_ring_attention_matches_reference():
    mesh = jax.make_mesh((8,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=2, S=256, H=4, KV=4, D=32)
    ref = _attention_xla(q, k, v, causal=True)

    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_ring_attention_grads_match():
    mesh = jax.make_mesh((4,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=1, S=64, H=2, KV=2, D=16)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))

    g1 = jax.grad(lambda *a: ring(*a).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=True)
                  .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_gqa_flash():
    q, k, v = _make(B=1, S=128, H=8, KV=2, D=32)
    ref = _attention_xla(q, k, v, causal=True)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_gqa_flash_gradients():
    """GQA backward: dK/dV group-sum must match the broadcast reference."""
    q, k, v = _make(B=1, S=64, H=8, KV=2, D=16)

    g1 = jax.grad(lambda *a: flash_attention(
        *a, block_q=32, block_k=32).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_noncausal_flash_gradients():
    q, k, v = _make(B=1, S=64, H=2, KV=2, D=16)
    g1 = jax.grad(lambda *a: flash_attention(
        *a, causal=False, block_q=32, block_k=32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=False).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_pallas_bwd_matches_chunked_bwd():
    import sys

    fa = sys.modules["ray_tpu.ops.flash_attention"]

    q, k, v = _make(B=1, S=128, H=4, KV=2, D=32)

    def grads():
        return jax.grad(lambda *a: flash_attention(
            *a, block_q=32, block_k=32).sum(), argnums=(0, 1, 2))(q, k, v)

    old = fa.BACKWARD_IMPL
    try:
        fa.BACKWARD_IMPL = "pallas"
        gp = grads()
        fa.BACKWARD_IMPL = "chunked"
        gc = grads()
    finally:
        fa.BACKWARD_IMPL = old
    for a, b in zip(gp, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


def test_flash_residuals_compact_lse_feeds_both_backwards(monkeypatch):
    """The forward rule hands the backward `lse` as [B, H, S], not the
    kernel's 128-lane broadcast (which would be twice the size of `o`
    where a checkpoint keeps it), and both backward implementations give
    the same gradients from that one residual tuple."""
    import sys

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    B, S, H, KV, D = 1, 128, 4, 2, 32
    q, k, v = _make(B=B, S=S, H=H, KV=KV, D=D)
    out, res = fa._flash_vjp_fwd(q, k, v, True, 32, 32, 0)
    assert [r.shape for r in res] == [
        (B, S, H, D), (B, S, KV, D), (B, S, KV, D), (B, S, H, D), (B, H, S)]
    assert res[4].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(res[3]), np.asarray(out))
    # lse is the row's log-sum-exp of the masked, scaled scores
    s = jnp.einsum("bshd,bthd->bhst", q, jnp.repeat(k, H // KV, axis=2))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s * D ** -0.5, -jnp.inf)
    np.testing.assert_allclose(np.asarray(res[4]), np.asarray(
        jax.nn.logsumexp(s, axis=-1)), rtol=1e-4, atol=1e-4)

    g = jax.random.normal(jax.random.PRNGKey(9), out.shape, out.dtype)
    grads = {}
    for impl in ("pallas", "chunked"):
        monkeypatch.setattr(fa, "BACKWARD_IMPL", impl)
        grads[impl] = fa._flash_vjp_bwd(True, 32, 32, 0, res, g)
    for a, b in zip(grads["pallas"], grads["chunked"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


def test_ulysses_matches_reference():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = jax.make_mesh((8,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=2, S=256, H=8, KV=8, D=32)
    ref = _attention_xla(q, k, v, causal=True)

    uly = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = uly(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ulysses_gqa_matches_reference():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = jax.make_mesh((4,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    # H=8, KV=4 over sp=4: 2 q-heads + 1 kv-head per chip, G=2 preserved
    q, k, v = _make(B=1, S=128, H=8, KV=4, D=16)
    ref = _attention_xla(q, k, v, causal=True)
    uly = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    np.testing.assert_allclose(np.asarray(uly(q, k, v)), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ulysses_grads_match():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = jax.make_mesh((4,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=1, S=64, H=4, KV=4, D=16)

    uly = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))
    g1 = jax.grad(lambda *a: uly(*a).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=True)
                  .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def _dense_windowed(q, k, v, window):
    """f32 dense reference with the causal + sliding-window band mask."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    g = H // KV
    kf = jnp.repeat(k, g, axis=2)
    vf = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, kf) * D ** -0.5
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(S)[None, :]
    mask = (qp >= kp) & (qp - kp < window)
    s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), vf)


def test_flash_sliding_window_matches_reference():
    """Mistral-style banded attention (window=W) against the dense
    banded mask, incl. GQA. Absolute tolerance matches the f32
    attention noise floor (the f32 XLA dense itself differs from f64
    exact by ~6e-3 at these shapes)."""
    q, k, v = _make()
    for W in (64, 96, 256):
        out = flash_attention(q, k, v, window=W, block_q=64, block_k=64)
        ref = _dense_windowed(q, k, v, W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=8e-3)
    # window >= S degenerates to plain causal
    full = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    w_s = flash_attention(q, k, v, window=q.shape[1], block_q=64,
                          block_k=64)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(w_s))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)


def test_flash_sliding_window_gradients():
    q, k, v = _make(B=1, S=256, H=2, KV=2, D=32)
    W = 96

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, window=W, block_q=64,
                               block_k=64).sum()

    def loss_ref(q, k, v):
        return _dense_windowed(q, k, v, W).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-2)
