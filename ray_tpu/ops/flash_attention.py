"""Fused causal attention (flash-style) for TPU in Pallas.

Forward: one kernel instance per (batch, head, q-block); the q-block stays in
VMEM while K/V stream through in chunks with the online-softmax recurrence —
O(S) memory instead of O(S^2), and the QK^T / PV matmuls hit the MXU at
[block_q x head_dim] x [head_dim x block_k] granularity.

Backward: full Pallas two-kernel backward (FlashAttention-2 style): a dQ
pass gridded over q-blocks and a dK/dV pass gridded over k-blocks, both
recomputing probabilities from the saved log-sum-exp so nothing O(S^2) is
ever materialized. A chunked-recompute JAX fallback remains selectable via
BACKWARD_IMPL for debugging.

GQA is handled in the kernel via the k/v index maps (kv_head = head // group)
— no KV broadcast materialization.

Across a checkpoint: the forward rule names the two residuals only the
kernel can produce, FLASH_RESIDUALS = the output `o` ([B, S, H, D] in
q.dtype: as large as the layer input a per-layer checkpoint already keeps)
and the log-sum-exp `lse`, held compact as [B, H, S] f32 (the kernel writes
it lane-broadcast over 128 lanes, twice the size of `o`; the backward
broadcasts it back). A `jax.checkpoint` whose policy saves those names
(models/llama.py::_checkpoint) runs the backward kernels from them; one
that does not runs the forward kernel a second time, a launch over S^2,
only to rebuild them. q, k and v carry no name: they are rebuilt from the
layer input by their projections.

Shapes: q [B, S, H, D], k/v [B, T, KV, D], output [B, S, H, D].
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# checkpoint_name tags of what _flash_vjp_fwd hands the backward: (o, lse)
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fwd_kernel_loop(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                     scale: float, causal: bool):
    """Full-K/V-resident variant: one grid instance per q-block streams
    k-blocks in a fori_loop. Fewer grid steps than the ki-minor kernel —
    faster at short/medium S where per-step overhead dominates; the
    ki-minor streaming kernel wins for windowed long-S (it never fetches
    out-of-band K/V)."""
    # q_ref: [1, 1, block_q, D]; k_ref/v_ref: [1, 1, T, D]
    block_q, D = q_ref.shape[2], q_ref.shape[3]
    T = k_ref.shape[2]
    qi = pl.program_id(2)
    # operands keep the input dtype (bf16 MXU rate); f32 accumulation
    q = q_ref[0, 0]

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(ki, carry):
        o, m, l = carry
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        o_new = o * alpha + jax.lax.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    if causal:
        # only k-blocks at or before this q-block contribute
        num_k = jax.lax.div((qi + 1) * block_q + block_k - 1, block_k)
    else:
        num_k = T // block_k
    o, m, l = jax.lax.fori_loop(0, num_k, body, (o0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (o / l).astype(o_ref.dtype)
    # Lane-broadcast (Mosaic wants last-dim 128 blocks; official TPU flash
    # kernel stores l/m the same way).
    lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(l), (block_q, 128))


def _flash_fwd_loop(q, k, v, *, causal: bool, block_q: int, block_k: int):
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    scale = D ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    grid = (B, H, S // block_q)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_loop, block_k=block_k, scale=scale,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, T, D),
                         lambda b, h, i, g=groups: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, T, D),
                         lambda b, h, i, g=groups: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 128), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr,
                       l_scr, *, block_q: int, block_k: int, scale: float,
                       causal: bool, window: int, num_k: int):
    """ki-minor streaming variant: grid (B, H, q-blocks, k-blocks).
    K/V arrive one block per step through a CLAMPED index_map, so blocks
    outside the causal/window band are never fetched (Mosaic elides the
    DMA when the block index repeats) — O(S*W) HBM traffic for sliding
    windows instead of O(S*T). acc/m/l live in VMEM scratch across the
    ki steps of one q-block (same structure as the official TPU flash
    kernel); the last ki step normalizes and writes o/lse."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    run = True
    if causal:
        run = qi * block_q + block_q > ki * block_k
        if window > 0:
            run = run & (qi * block_q < (ki + 1) * block_k + window)

    @pl.when(run)
    def _step():
        # operands stay in the input dtype (bf16 on TPU: 8x the f32 MXU
        # rate); the MXU accumulates in f32 via preferred_element_type —
        # an f32 cast here made the whole kernel f32-matmul-bound
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            keep = q_pos >= k_pos
            if window > 0:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        m = m_scr[...][:, 0:1]
        l = l_scr[...][:, 0:1]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        # p joins v's dtype for the second MXU pass (f32 accumulation);
        # standard flash practice, same as the official TPU kernel
        acc[...] = acc[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, 0:1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_k: int,
               window: int = 0):
    if window <= 0:
        # plain causal/full: the q-block loop kernel has 1/num_k the
        # grid steps — faster where per-step overhead dominates
        return _flash_fwd_loop(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k)
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    scale = D ** -0.5
    # layout: [B, H, S, D] per-instance slices
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    num_k = T // block_k
    grid = (B, H, S // block_q, num_k)

    def kv_idx(b, h, qi, ki, g=groups):
        # clamp into the band: out-of-band steps repeat a neighboring
        # index, so Mosaic elides their K/V DMA entirely
        j = ki
        if causal:
            hi = jax.lax.div(qi * block_q + block_q - 1, block_k)
            j = jax.lax.min(j, hi)
            if window > 0:
                lo = jax.lax.max(
                    0, jax.lax.div(qi * block_q - window + 1, block_k))
                j = jax.lax.max(j, lo)
        return (b, h // g, j, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_stream, block_q=block_q,
                          block_k=block_k, scale=scale, causal=causal,
                          window=window, num_k=num_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_idx),
            pl.BlockSpec((1, 1, block_k, D), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),     # acc
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
        ],
        interpret=_use_interpret(),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dq_ref, *,
                   block_k: int, scale: float, causal: bool, window: int):
    """One instance per (b, h, q-block): stream K/V, accumulate dQ
    (FlashAttention-2 backward, dQ pass). delta = rowsum(o * dO) is
    computed in-kernel from the resident blocks."""
    block_q, D = q_ref.shape[2], q_ref.shape[3]
    T = k_ref.shape[2]
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    g = g_ref[0, 0].astype(jnp.float32)
    o = o_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, 0:1]
    delta = jnp.sum(o * g, axis=-1, keepdims=True)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(ki, dq):
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            keep = q_pos >= k_pos
            if window > 0:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    start_k = 0
    if causal:
        num_k = jax.lax.div((qi + 1) * block_q + block_k - 1, block_k)
        if window > 0:
            start_k = jax.lax.max(
                0, jax.lax.div(qi * block_q - window + 1, block_k))
    else:
        num_k = T // block_k
    dq = jax.lax.fori_loop(start_k, num_k,
                           body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                     dk_ref, dv_ref, *, block_q: int, scale: float,
                     causal: bool, window: int):
    """Grid (b, h, k-block, q-block): the dk/dv output block is constant in
    the (minor) q axis, so Mosaic keeps it resident and this accumulates
    across sequential q steps — O(block) VMEM at any sequence length
    (FlashAttention-2 backward, dK/dV pass). dK/dV land per-query-head;
    the wrapper sums over GQA groups."""
    block_k, D = k_ref.shape[2], k_ref.shape[3]
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _zero():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    # Causal: a q-block strictly above the diagonal contributes nothing.
    run = True
    if causal:
        run = (qi + 1) * block_q > ki * block_k
        if window > 0:
            # windowed: q-blocks wholly past the window skip this k-block
            run = run & (qi * block_q < (ki + 1) * block_k + window)

    @pl.when(run)
    def _accumulate():
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)
        g = g_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, 0:1]
        delta = jnp.sum(o * g, axis=-1, keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            keep = q_pos >= k_pos
            if window > 0:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse)                                   # [bq, bk]
        dv_ref[0, 0] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # p^T @ g
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_ref[0, 0] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # ds^T @ q


def _flash_pallas_bwd(res, g, *, causal: bool, block_q: int, block_k: int,
                      window: int = 0):
    """Full Pallas backward: two kernels (dQ; dK/dV), GQA group-sum on the
    dK/dV results (FlashAttention-2, Dao 2023)."""
    q, k, v, out, lse = res
    # the residual is compact [B, H, S]; the kernels read 128-lane blocks
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (128,))
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    scale = D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, T)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    gt = g.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)

    q_blk = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, T, D),
                           lambda b, h, i, g_=groups: (b, h // g_, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, scale=scale,
                          causal=causal, window=window),
        grid=(B, H, S // block_q),
        in_specs=[
            q_blk,
            kv_spec,
            kv_spec,
            q_blk,
            q_blk,
            pl.BlockSpec((1, 1, block_q, 128), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=_use_interpret(),
    )(qt, kt, vt, gt, ot, lse)

    q_stream = pl.BlockSpec((1, 1, block_q, D),
                            lambda b, h, i, j: (b, h, j, 0))
    kv_blk = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, i, j, g_=groups: (b, h // g_, i, 0))
    dkv_spec = pl.BlockSpec((1, 1, block_k, D),
                            lambda b, h, i, j: (b, h, i, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, block_q=block_q, scale=scale,
                          causal=causal, window=window),
        grid=(B, H, T // block_k, S // block_q),
        in_specs=[
            q_stream,
            kv_blk,
            kv_blk,
            q_stream,
            q_stream,
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, T, D), jnp.float32),
        ],
        interpret=_use_interpret(),
    )(qt, kt, vt, gt, ot, lse)

    # GQA: sum per-query-head contributions into each kv head.
    dk = dk_h.reshape(B, KV, groups, T, D).sum(2).transpose(0, 2, 1, 3)
    dv = dv_h.reshape(B, KV, groups, T, D).sum(2).transpose(0, 2, 1, 3)
    return dq.transpose(0, 2, 1, 3), dk.astype(k.dtype), dv.astype(v.dtype)


def _reference_chunked_bwd(res, g, *, causal: bool, chunk: int,
                           window: int = 0):
    """Recompute-based backward, chunked over the key axis to stay O(S*chunk)
    in memory. Uses the forward's lse so probabilities are exact."""
    q, k, v, out, lse = res                            # lse [B, H, S]
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    scale = D ** -0.5

    qf = q.astype(jnp.float32)
    of = out.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(of * gf, axis=-1)                  # [B, S, H]

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kg = kf[:, :, :, None, :]                           # [B,T,KV,1,D]
    vg = vf[:, :, :, None, :]
    q5 = qf.reshape(B, S, KV, groups, D)
    g5 = gf.reshape(B, S, KV, groups, D)
    lse5 = lse.transpose(0, 2, 1).reshape(B, S, KV, groups)
    delta5 = delta.reshape(B, S, KV, groups)
    q_pos = jnp.arange(S)

    nchunks = max(1, T // chunk)
    csize = T // nchunks

    def body(carry, ci):
        dq_acc = carry
        ks = jax.lax.dynamic_slice_in_dim(kg, ci * csize, csize, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vg, ci * csize, csize, axis=1)
        s = jnp.einsum("bskgd,btkud->bskgt", q5, ks) * scale  # u==1 squeezed
        if causal:
            k_pos = ci * csize + jnp.arange(csize)
            mask = q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse5[..., None])                     # [B,S,KV,G,c]
        dv_c = jnp.einsum("bskgt,bskgd->btkd", p, g5)
        dp = jnp.einsum("bskgd,btkud->bskgt", g5, vs)
        ds = p * (dp - delta5[..., None]) * scale
        dq_c = jnp.einsum("bskgt,btkud->bskgd", ds, ks)
        dk_c = jnp.einsum("bskgt,bskgd->btkd", ds, q5)
        return dq_acc + dq_c, (dk_c, dv_c)

    dq0 = jnp.zeros_like(q5)
    dq, (dk_chunks, dv_chunks) = jax.lax.scan(body, dq0, jnp.arange(nchunks))
    dk = jnp.moveaxis(dk_chunks, 0, 1).reshape(B, T, KV, D)
    dv = jnp.moveaxis(dv_chunks, 0, 1).reshape(B, T, KV, D)
    return (dq.reshape(B, S, H, D).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, window):
    out, _ = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, window=window)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, window):
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, window=window)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    # drop the lane broadcast: [B, H, S, 128] -> [B, H, S]
    lse = checkpoint_name(lse[..., 0], FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


BACKWARD_IMPL = "pallas"   # "pallas" | "chunked" (recompute fallback)


def _flash_vjp_bwd(causal, block_q, block_k, window, res, g):
    if BACKWARD_IMPL == "pallas":
        return _flash_pallas_bwd(res, g, causal=causal, block_q=block_q,
                                 block_k=block_k, window=window)
    return _reference_chunked_bwd(res, g, causal=causal, chunk=block_k * 4,
                                  window=window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, window: Optional[int] = None):
    # 512x512 blocks measured +14% end-to-end over 256x256 on v5e at
    # S=1024 (llama-125m train step 110.5ms -> 95.5ms); scores block is
    # 1 MiB f32, comfortably inside VMEM alongside q/k/v tiles.
    """q [B,S,H,D], k/v [B,T,KV,D] -> [B,S,H,D]. S, T must divide blocks
    (pad upstream); returns in q.dtype. window=W (causal only) restricts
    each query to the last W keys — Mistral-style sliding-window
    attention; blocks wholly outside the band are skipped, so compute is
    O(S*W) instead of O(S^2)."""
    if window is not None and not causal:
        raise ValueError("window= requires causal=True")
    B, S, H, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, k.shape[1])
    while S % block_q:
        block_q //= 2
    while k.shape[1] % block_k:
        block_k //= 2
    return _flash(q, k, v, causal, max(block_q, 1), max(block_k, 1),
                  int(window or 0))
