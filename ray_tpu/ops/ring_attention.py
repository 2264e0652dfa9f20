"""Ring attention: sequence-parallel causal attention over the 'sp' mesh axis.

The long-context strategy SURVEY.md §5.7 requires (absent in the reference,
which delegates long sequences to wrapped frameworks). Each chip holds a
contiguous sequence chunk of Q, K, V; K/V blocks rotate around the ICI ring
via jax.lax.ppermute while every chip accumulates its chunk's attention with
the online-softmax recurrence. After sp steps every Q has attended to every
K/V at O(S/sp) activation memory per chip, with the transfers overlapping
compute (XLA schedules the ppermute DMA concurrently with the local block
matmul — the Pallas-level fused variant is a later-round optimization).

Call inside shard_map with q/k/v sharded on the seq axis:
    jax.shard_map(lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
                  mesh=mesh, in_specs=P(None, "sp", None, None), ...)

Causality across chunks: chunk i attends fully to chunks j < i, causally to
its own chunk, not at all to j > i — masking is done per rotation step from
the global chunk offsets, so the math exactly matches full causal attention.

Differentiable: the whole recurrence is jnp + ppermute, which have transpose
rules; jax.grad threads the ring backward automatically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One (q-chunk x kv-chunk) block. q [B,S,KV,G,D]; k/v [B,T,KV,D].
    Returns unnormalized o plus (m, l) for the online-softmax merge."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bskgd,btkd->bskgt", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    # all-masked rows: keep m finite so exp() underflows to 0 cleanly
    m = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bskgt,btkd->bskgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True):
    """q [B,Sc,H,D], k/v [B,Sc,KV,D] — Sc is this chip's chunk.
    Must be called inside shard_map/pjit with `axis_name` bound."""
    B, Sc, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    q5 = q.reshape(B, Sc, KV, G, D).astype(jnp.float32)
    pos_q = jnp.arange(Sc)
    pos_k = jnp.arange(Sc)

    def mask_for(kv_chunk_idx):
        if not causal:
            return jnp.ones((1, Sc, 1, 1, Sc), bool)
        # global positions: q at my*Sc + i, k at kv_chunk_idx*Sc + j
        qg = my * Sc + pos_q
        kg = kv_chunk_idx * Sc + pos_k
        return (qg[:, None] >= kg[None, :])[None, :, None, None, :]

    def step(carry, _):
        o, m, l, kk, vv, src = carry
        bo, bm, bl = _block_attn(q5, kk.astype(q.dtype), vv, mask_for(src))
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(bm - m_new)
        l_new = l * alpha + bl * beta
        o_new = o * alpha[..., None] + bo * beta[..., None]
        # rotate kv to the next chip on the ring (ICI neighbor exchange)
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        src = jax.lax.ppermute(src, axis_name, perm)
        return (o_new, m_new, l_new, kk, vv, src), None

    o0 = jnp.zeros((B, Sc, KV, G, D), jnp.float32)
    m0 = jnp.full((B, Sc, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sc, KV, G), jnp.float32)
    # JAX >= 0.8 tracks "varying manual axes" through shard_map: literals
    # created inside the body are unvarying while the rotated kv is varying;
    # promote the accumulators so the scan carry types line up.
    if hasattr(jax.lax, "pcast"):
        o0, m0, l0 = (jax.lax.pcast(x, (axis_name,), to="varying")
                      for x in (o0, m0, l0))
    carry = (o0, m0, l0, k, v, my)
    (o, m, l, _, _, _), _ = jax.lax.scan(step, carry, None, length=sp)
    l = jnp.maximum(l, 1e-30)
    out = (o / l[..., None]).reshape(B, Sc, H, D)
    return out.astype(q.dtype)
