"""``ops/sparse_attention.py``: the kernels (Pallas interpret mode) against
a dense masked softmax in XLA: the output, both gradients' three arrays and
the head-mean probabilities, at sets with ties in their making, at queries
that see fewer keys than the set holds, and at rows whose only keys lie
blocks away."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import latent
from ray_tpu.ops import sparse_attention as sa


def _sets(kind: str, S: int, topk: int):
    """keep [1, S, S] int8 by the program's own selection."""
    key = jax.random.PRNGKey(3)
    if kind == "ties":          # scores from four values: ties everywhere
        scores = jax.random.randint(key, (S, S), 0, 4).astype(jnp.float32)
    elif kind == "far":         # the best keys are the earliest
        scores = -jnp.broadcast_to(jnp.arange(S, dtype=jnp.float32), (S, S))
    else:
        scores = jax.random.normal(key, (S, S))
    return latent.select(scores, 0, topk).astype(jnp.int8)[None]


def _dense(q, k, v, keep, scale):
    s = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    s = jnp.where((keep != 0)[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v), p.mean(axis=1)


@pytest.mark.parametrize("kind,topk", [("random", 40), ("ties", 40),
                                       ("far", 16), ("random", 300)])
def test_kernels_agree_with_a_dense_masked_softmax(kind, topk, monkeypatch):
    monkeypatch.setattr(sa, "BLOCK_Q", 128)
    monkeypatch.setattr(sa, "BLOCK_K", 128)
    monkeypatch.setattr(sa, "IMPL", "pallas")
    B, S, H, D = 1, 384, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    keep = _sets(kind, S, topk)
    sizes = np.asarray(keep[0].sum(-1))
    assert (sizes == np.minimum(topk, np.arange(S) + 1)).all()
    lanes = jnp.cos(jnp.arange(D, dtype=jnp.float32))

    def ours(q, k, v):
        o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
        return (o * lanes).sum(), (o, p)

    def dense(q, k, v):
        o, p = _dense(q, k, v, keep, D ** -0.5)
        return (o * lanes).sum(), (o, p)

    (_, (o, p)), g = jax.value_and_grad(ours, argnums=(0, 1, 2),
                                        has_aux=True)(q, k, v)
    (_, (o_w, p_w)), g_w = jax.value_and_grad(dense, argnums=(0, 1, 2),
                                              has_aux=True)(q, k, v)
    np.testing.assert_allclose(o, o_w, atol=2e-5)
    np.testing.assert_allclose(jnp.where(keep != 0, p, 0.0), p_w, atol=1e-6)
    # the probabilities of a query sum to one over its set
    np.testing.assert_allclose(jnp.where(keep != 0, p, 0.0).sum(-1), 1.0,
                               atol=1e-5)
    for a, b in zip(g, g_w):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_no_gradient_passes_through_the_head_mean_probabilities(monkeypatch):
    monkeypatch.setattr(sa, "IMPL", "pallas")
    B, S, H, D = 1, 128, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    keep = _sets("random", S, 24)
    g = jax.grad(lambda q, k: jnp.where(keep != 0, sa.sparse_attention(
        q, k, v, keep, with_probs=True)[1], 0.0).sum(), argnums=(0, 1))(q, k)
    assert all(float(jnp.abs(x).max()) == 0.0 for x in g)


def test_the_xla_path_is_the_same_mathematics(monkeypatch):
    """Off the chip and under 128 keys the op runs in plain jax.numpy."""
    B, S, H, D = 2, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    keep = jnp.concatenate([_sets("random", S, 8), _sets("ties", S, 8)])
    o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
    o_w, p_w = _dense(q, k, v, keep, D ** -0.5)
    np.testing.assert_allclose(o, o_w, atol=1e-5)
    np.testing.assert_allclose(p, p_w, atol=1e-6)


def test_the_plans_fields():
    plan = sa.plan(B=1, H=32, S=16384, T=16384, D=256, dtype=jnp.bfloat16,
                   call="dkdv")
    assert (plan["path"], plan["block_q"], plan["block_k"]) == (
        "mask", 512, 512)
    # the causal half of the grid works: 32 x 33 / 2 blocks a head
    assert plan["live_steps"] == 32 * 528 and plan["grid_steps"] == 32 * 1024
    assert plan["vmem_bytes"] < 16 * 2 ** 20


@pytest.mark.parametrize("topk", [1, 7, 64])
def test_select_is_exact_with_ties_to_the_earlier_key(topk):
    """``latent.select`` against a stable sort, rows of their own offset."""
    S, first = 64, 17
    for seed, values in ((0, None), (1, 3)):
        key = jax.random.PRNGKey(seed)
        scores = jax.random.normal(key, (S - first, S)) if values is None \
            else jax.random.randint(key, (S - first, S), 0, values).astype(
                jnp.float32) - 1.0
        scores = scores.at[0, 0].set(-0.0).at[0, 1].set(0.0)
        got = np.asarray(latent.select(scores, first, topk))
        for r in range(S - first):
            t = first + r
            order = sorted(range(t + 1),
                           key=lambda s: (-float(scores[r, s]), s))
            want = np.zeros(S, bool)
            want[order[:topk]] = True
            assert (got[r] == want).all(), (seed, r)
