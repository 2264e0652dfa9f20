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


def _ours(q, k, v, keep, lanes):
    def loss(q, k, v):
        o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
        return (o * lanes).sum(), (o, p)

    (_, (o, p)), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                        has_aux=True)(q, k, v)
    return o, jnp.where(keep != 0, p, 0.0), g


# (S, kind, topk, span, in flight): None leaves a grid step to the plan;
# at S 1024 a head's 8 k-blocks are walked 1, 2 and 4 a grid step, 1 and 2
# a step of the walk (every q-block's diagonal but the last of a span lies
# inside one; the first 300 rows hold fewer than topk keys), and each walk
# is held to the one-block walk bit for bit
WALKS = [(384, "random", 40, None, None), (384, "ties", 40, None, None),
         (384, "far", 16, None, None), (384, "random", 300, None, None),
         (1024, "random", 300, 1, 1), (1024, "random", 300, 2, 1),
         (1024, "ties", 300, 2, 2), (1024, "random", 300, 4, 1),
         (1024, "far", 300, 4, 2)]


@pytest.mark.parametrize("S,kind,topk,span,in_flight", WALKS)
def test_kernels_agree_with_a_dense_masked_softmax(S, kind, topk, span,
                                                   in_flight, monkeypatch):
    monkeypatch.setattr(sa, "BLOCK_Q", 128)
    monkeypatch.setattr(sa, "BLOCK_K", 128)
    monkeypatch.setattr(sa, "IMPL", "pallas")
    B, H, D = 1, 2, 128

    def force(span, in_flight):
        monkeypatch.setattr(sa, "_choose", lambda call, **_: (span, in_flight))

    if span is not None:
        force(span, in_flight)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    keep = _sets(kind, S, topk)
    sizes = np.asarray(keep[0].sum(-1))
    assert (sizes == np.minimum(topk, np.arange(S) + 1)).all()
    lanes = jnp.cos(jnp.arange(D, dtype=jnp.float32))

    def dense(q, k, v):
        o, p = _dense(q, k, v, keep, D ** -0.5)
        return (o * lanes).sum(), (o, p)

    o, p, g = _ours(q, k, v, keep, lanes)
    (_, (o_w, p_w)), g_w = jax.value_and_grad(dense, argnums=(0, 1, 2),
                                              has_aux=True)(q, k, v)
    np.testing.assert_allclose(o, o_w, atol=2e-5)
    np.testing.assert_allclose(p, p_w, atol=1e-6)
    # the probabilities of a query sum to one over its set
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-5)
    for a, b in zip(g, g_w):
        np.testing.assert_allclose(a, b, atol=5e-5)
    if span is not None:
        force(1, 1)
        o_1, p_1, g_1 = _ours(q, k, v, keep, lanes)
        for a, b in zip((o, p, *g), (o_1, p_1, *g_1)):
            np.testing.assert_array_equal(a, b)


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
    """The four calls at the GLM-5.2 cell's shape: what a grid step holds,
    chosen from the shapes and the 16 MiB alone."""
    cell = dict(B=1, H=32, S=16384, T=16384, D=256, dtype=jnp.bfloat16)
    plans = {call: sa.plan(call=call, **cell)
             for call in ("fwd", "probs", "dq", "dkdv")}
    for plan in plans.values():
        assert (plan["path"], plan["block_q"], plan["block_k"]) == (
            "mask", 512, 512)
        assert plan["vmem_bytes"] <= 16 * 2 ** 20
    took = {call: (p["span"], p["in_flight"]) for call, p in plans.items()}
    assert took == {"fwd": (4, 2), "probs": (4, 2), "dq": (4, 2),
                    "dkdv": (2, 2)}, took
    # the causal half of the grid works: 32 x 33 / 2 blocks a head, in
    # spans of 4 k-blocks 8 + 4 x (1 + ... + 8) grid steps of a head's 256
    for call in ("fwd", "probs", "dq"):
        span = plans[call]["span"]
        assert plans[call]["grid_steps"] == 32 * 32 * (32 // span)
        assert plans[call]["live_steps"] == 32 * sum(
            qi // span + 1 for qi in range(32))
    assert plans["fwd"]["live_steps"] == 32 * 144
    span = plans["dkdv"]["span"]
    assert plans["dkdv"]["grid_steps"] == 32 * 32 * (32 // span)
    assert plans["dkdv"]["live_steps"] == 32 * sum(
        32 // span - ki // span for ki in range(32))
    # a narrower head leaves a longer span room, one k-block none
    assert sa.plan(call="fwd", **{**cell, "D": 128})["span"] > plans[
        "fwd"]["span"]
    small = sa.plan(B=1, H=2, S=128, T=128, D=128, dtype=jnp.float32,
                    call="fwd")
    assert (small["span"], small["in_flight"], small["grid_steps"],
            small["live_steps"]) == (1, 1, 2, 2)


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
