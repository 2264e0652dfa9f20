"""Fused causal attention (flash-style) for TPU in Pallas.

Forward: one kernel instance per (batch, head, q-block); the q-block stays in
VMEM while K/V stream through in chunks with the online-softmax recurrence —
O(S) memory instead of O(S^2), and the QK^T / PV matmuls hit the MXU at
[block_q x head_dim] x [head_dim x block_k] granularity. Two kernels, told
apart by the shape alone (`kv_plan`; the instant `flash.fwd_plan`): `loop`
holds a head's whole K and V as blocks and walks them itself, where those
blocks, double-buffered, and a step's temporaries fit the 16 MiB a Mosaic
call gets that asks for no more (S 8192 at D 128); `stream` has the
k-blocks on a grid axis, O(block) VMEM at any S and D (S 8192 at D 256:
the loop's blocks alone are 16 MiB), and serves every windowed call.

Backward: full Pallas two-kernel backward (FlashAttention-2 style), both
recomputing probabilities from the saved log-sum-exp so nothing O(S^2) is
ever materialized. The dQ pass keeps a q-block resident and loops over
k-blocks: of a head's whole K and V held as blocks (`loop`, chosen by the
forward's bytes) or with the k-blocks on a grid axis, clamped into the
band, dq accumulated in the float32 output block (`stream`); both run
`_dq_step` in the same order and agree to the last bit. The dK/dV pass is
its mirror image and has two block plans, told apart by the shape alone
(`bwd_dkdv_plan`; the choices are the instant `flash.bwd_plan` of a trace):
  resident  one instance per (b, h, k-block); q, dO, o and lse of the
            whole head are blocks whose index is constant in the k axis,
            so they are fetched once a head, and the kernel loops over the
            q-blocks of its band. Taken where a head's query side, twice
            (Mosaic double-buffers), and one step's temporaries fit a
            quarter of the core's VMEM (S 8192 at D 128 on a v5e). HBM
            bytes a head at S 4096, D 128, bf16, H == KV: 5 MiB of query
            side + 2 of k and v + 2 of dk and dv = 9 MiB; k-blocks are
            walked last to first, so the next head's 5 MiB arrive under
            the head's longest instance, not its shortest.
  stream    grid (b, h, k-block, q-block) accumulating into the f32 output
            block: O(block) VMEM at any S. The query-side index maps are
            clamped into the band as the forward's `kv_idx` clamps k and
            v, so a step the mask skips fetches nothing: 0.625 MiB a step
            that runs, 35 of 64 at S 4096 causal = 22 MiB + 6 (f32
            results). Unclamped, as this grid was until PR 28, every step
            fetched: 46 MiB a head.
Both run the same accumulate step (`_dkdv_step`) in the same order, so
their results agree to the last bit. A chunked-recompute JAX fallback
remains selectable via BACKWARD_IMPL for debugging.

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
import operator
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util import tracing

NEG_INF = -1e30
_LSE_LANES = 128            # the kernels read and write lse lane-broadcast
# checkpoint_name tags of what _flash_vjp_fwd hands the backward: (o, lse)
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _scale(scale, head_dim: int) -> float:
    """What multiplies the scores: the model's own, else head_dim ** -0.5."""
    return head_dim ** -0.5 if scale is None else float(scale)


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


def _flash_fwd_loop(q, k, v, *, causal: bool, block_q: int, block_k: int,
                    scale: float):
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    grid = (B, H, S // block_q)

    call = pl.pallas_call(
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
    )
    with jax.named_scope("flash.fwd.loop"):     # flash.fwd_plan's path
        out, lse = call(qt, kt, vt)
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


# VMEM a Mosaic call gets on a v5e core when it asks for no limit of its own
_SCOPED_VMEM_BYTES = 16 * 2 ** 20


def kv_plan(*, S: int, T: int, D: int, dtype, block_q: int,
            block_k: int) -> dict:
    """Which kernel a call that walks k-blocks for a resident q-block takes
    (the forward, the dQ pass), and the bytes that decide it (also the
    attributes of `flash.fwd_plan`). loop: K and V of a whole head are
    blocks of the call, fetched once a head; taken where those blocks,
    double-buffered by Mosaic (`kv_block_bytes`), with the q-side blocks
    and the f32 temporaries of one step fit the VMEM a call gets without
    asking (the compiler refused S 8192 x D 256 at 17.5 MiB of 16); stream
    otherwise. Asking for more is not the way: a call's limit is taken out
    of XLA's own fast memory for as long as the call is scheduled
    (`bwd_dkdv_plan`)."""
    itemsize = jnp.dtype(dtype).itemsize
    kv_block_bytes = 2 * 2 * T * D * itemsize
    loop_bytes = (
        kv_block_bytes
        # q, dO, o, the result and the 128-lane lse, double-buffered
        + 2 * block_q * (4 * D * itemsize + _LSE_LANES * 4)
        # two [block_q, block_k] of s and p; the accumulator and a k-block
        + 4 * (2 * block_q * block_k + (block_q + block_k) * D))
    return dict(path="loop" if loop_bytes <= _SCOPED_VMEM_BYTES else "stream",
                S=S, D=D, kv_block_bytes=kv_block_bytes,
                loop_bytes=loop_bytes)


def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_k: int,
               scale: float, window: int = 0):
    B, S, H, D = q.shape
    plan = kv_plan(S=S, T=k.shape[1], D=D, dtype=k.dtype,
                   block_q=min(block_q, S), block_k=min(block_k, k.shape[1]))
    if window > 0:
        plan["path"] = "stream"     # never fetches k-blocks out of the band
    tracing.instant("flash.fwd_plan", {
        n: plan[n] for n in ("path", "S", "D", "kv_block_bytes")})
    if plan["path"] == "loop":
        # plain causal/full: the q-block loop kernel has 1/num_k the
        # grid steps — faster where per-step overhead dominates
        return _flash_fwd_loop(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, scale=scale)
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
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

    call = pl.pallas_call(
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
    )
    with jax.named_scope("flash.fwd.stream"):
        out, lse = call(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _dq_step(q, k, v, g, lse, delta, q_pos, ki, *, block_k: int, scale: float,
             causal: bool, window: int):
    """What k-block `ki` adds to the dQ of a q-block, [block_q, D] f32,
    from blocks already cast to f32 (lse, delta and the rows' positions
    q_pos [block_q, 1]). The one accumulate step of both dQ kernels."""
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
    return jax.lax.dot(ds, k, preferred_element_type=jnp.float32)


def _k_band(qi, *, num_k: int, block_q: int, block_k: int, causal: bool,
            window: int):
    """[lo, hi): the k-blocks that hold a key a row of q-block `qi` sees
    under the causal mask (and the window)."""
    if not causal:
        return 0, num_k
    div, _, most = _index_ops(qi)
    hi = div((qi + 1) * block_q + block_k - 1, block_k)
    lo = 0
    if window > 0:
        lo = most(0, div(qi * block_q - window + 1, block_k))
    return lo, hi


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dq_ref, *,
                   block_k: int, scale: float, causal: bool, window: int):
    """One instance per (b, h, q-block): K/V of the whole head are its
    blocks, dQ accumulates over the k-blocks of the band
    (FlashAttention-2 backward, dQ pass). delta = rowsum(o * dO) is
    computed in-kernel from the resident blocks."""
    block_q, D = q_ref.shape[2], q_ref.shape[3]
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
        return dq + _dq_step(q, k, v, g, lse, delta, q_pos, ki,
                             block_k=block_k, scale=scale, causal=causal,
                             window=window)

    lo, hi = _k_band(qi, num_k=k_ref.shape[2] // block_k, block_q=block_q,
                     block_k=block_k, causal=causal, window=window)
    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dq_stream_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dq_ref,
                          *, num_k: int, scale: float, causal: bool,
                          window: int):
    """Grid (b, h, q-block, k-block): the f32 dq output block is constant
    in the (minor) k axis, so Mosaic keeps it resident and this accumulates
    across sequential k steps, in the loop kernel's order: O(block) VMEM at
    any sequence length and head width. K and V arrive one block a step
    through an index map clamped into the band (`_flash_bwd_dq`), so a step
    outside it fetches nothing."""
    block_q, block_k = q_ref.shape[2], k_ref.shape[2]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _zero():
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    lo, hi = _k_band(qi, num_k=num_k, block_q=block_q, block_k=block_k,
                     causal=causal, window=window)

    @pl.when((ki >= lo) & (ki < hi))
    def _accumulate():
        g = g_ref[0, 0].astype(jnp.float32)
        delta = jnp.sum(o_ref[0, 0].astype(jnp.float32) * g, axis=-1,
                        keepdims=True)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        dq_ref[0, 0] += _dq_step(
            q_ref[0, 0].astype(jnp.float32), k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32), g, lse_ref[0, 0][:, 0:1], delta,
            q_pos, ki, block_k=block_k, scale=scale, causal=causal,
            window=window)


def _dkdv_step(q, k, v, g, o, lse, qi, ki, *, block_q: int, block_k: int,
               scale: float, causal: bool, window: int):
    """What q-block `qi` adds to the dK and dV of k-block `ki`, both
    [block_k, D] f32, from blocks already cast to f32 (lse [block_q, 1]).
    The one accumulate step of both block plans below."""
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
    p = jnp.exp(s - lse)                                       # [bq, bk]
    dv = jax.lax.dot_general(p, g, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # p^T @ g
    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # ds^T @ q
    return dk, dv


def _index_ops(i):
    """Floor division, min and max for a grid index: a traced scalar in a
    kernel or an index map, a Python int where the plan and the tests walk
    the grid on the host."""
    if isinstance(i, int):
        return operator.floordiv, min, max
    return jax.lax.div, jax.lax.min, jax.lax.max


def _q_band(ki, *, num_q: int, block_q: int, block_k: int, causal: bool,
            window: int):
    """[lo, hi): the q-blocks holding a row that the causal mask (and the
    window) lets see k-block `ki`; every other q-block adds nothing to
    that block's dK and dV."""
    if not causal:
        return 0, num_q
    div, least, _ = _index_ops(ki)
    lo = div(ki * block_k, block_q)
    hi = num_q
    if window > 0:
        hi = least(num_q, div((ki + 1) * block_k + window + block_q - 1,
                              block_q))
    return lo, hi


def _bwd_dkdv_resident_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                              dk_ref, dv_ref, *, block_q: int, scale: float,
                              causal: bool, window: int):
    """One instance per (b, h, k-block), the mirror image of the dQ pass:
    q, dO, o and lse of the WHOLE head are the instance's blocks, with an
    index constant in the k axis, so Mosaic fetches them once a head; the
    kernel loops over the q-blocks of this k-block's band itself and
    writes dK and dV once, in the result's dtype. dK/dV land
    per-query-head; the wrapper sums over GQA groups."""
    block_k = k_ref.shape[2]
    num_q = q_ref.shape[2] // block_q
    ki = pl.num_programs(2) - 1 - pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)

    def body(qi, carry):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dk_q, dv_q = _dkdv_step(
            q_ref[0, 0, rows, :].astype(jnp.float32), k, v,
            g_ref[0, 0, rows, :].astype(jnp.float32),
            o_ref[0, 0, rows, :].astype(jnp.float32),
            lse_ref[0, 0, rows, :][:, 0:1], qi, ki, block_q=block_q,
            block_k=block_k, scale=scale, causal=causal, window=window)
        return dk + dk_q, dv + dv_q

    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q, block_k=block_k,
                     causal=causal, window=window)
    zero = jnp.zeros(k.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (zero, zero))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd_dkdv_stream_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                            dk_ref, dv_ref, *, block_q: int, num_q: int,
                            scale: float, causal: bool, window: int):
    """Grid (b, h, k-block, q-block): the f32 dk/dv output block is
    constant in the (minor) q axis, so Mosaic keeps it resident and this
    accumulates across sequential q steps: O(block) VMEM at any sequence
    length. The query-side blocks arrive through index maps clamped into
    the band (`_flash_bwd_dkdv`), so a step outside it fetches nothing."""
    block_k = k_ref.shape[2]
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _zero():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q,
                     block_k=block_k, causal=causal, window=window)

    @pl.when((qi >= lo) & (qi < hi))
    def _accumulate():
        dk_q, dv_q = _dkdv_step(
            q_ref[0, 0].astype(jnp.float32), k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32), g_ref[0, 0].astype(jnp.float32),
            o_ref[0, 0].astype(jnp.float32), lse_ref[0, 0][:, 0:1], qi, ki,
            block_q=block_q, block_k=block_k, scale=scale, causal=causal,
            window=window)
        dv_ref[0, 0] += dv_q
        dk_ref[0, 0] += dk_q


# physical VMEM of a v5e core: what a trace with no chip attached plans for
# (interpret mode, a compile for a described device)
_V5E_VMEM_BYTES = 128 * 2 ** 20


def _vmem_bytes() -> int:
    """VMEM of the core this trace is for."""
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except ValueError:                     # no TPU is the default device
        return _V5E_VMEM_BYTES


def _q_block_index(ki, qi, *, num_q: int, block_q: int, block_k: int,
                   causal: bool, window: int):
    """The streaming plan's query-side block at grid step (ki, qi): qi
    clamped into k-block ki's band, as the forward's `kv_idx` clamps k and
    v. A step outside the band repeats the index of the band's near edge,
    and Mosaic elides a fetch whose index repeats."""
    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q, block_k=block_k,
                     causal=causal, window=window)
    _, least, most = _index_ops(qi)
    return most(least(qi, hi - 1), lo) if causal else qi


def hbm_bytes_per_head(path: str, *, S: int, T: int, D: int, block_q: int,
                       block_k: int, itemsize: int, out_itemsize: int,
                       q_index=None) -> int:
    """HBM bytes the dK/dV call moves for one (b, h): k and v read and dk
    and dv written once, plus the query side (q, dO, o and the 128-lane
    f32 lse): once on the resident plan; on the streaming plan one block
    each time `q_index(ki, qi)` changes along the grid's walk."""
    kv_bytes = 2 * T * D * (itemsize + out_itemsize)
    row_bytes = 3 * D * itemsize + _LSE_LANES * 4
    if path == "resident":
        return S * row_bytes + kv_bytes
    fetches, last = 0, None
    for ki in range(T // block_k):
        for qi in range(S // block_q):
            index = q_index(ki, qi)
            fetches += index != last
            last = index
    return fetches * block_q * row_bytes + kv_bytes


def bwd_dkdv_plan(*, S: int, T: int, D: int, dtype, groups: int,
                  block_q: int, block_k: int, causal: bool, window: int,
                  vmem_bytes: int) -> dict:
    """Which of the dK/dV call's two block plans a shape takes, and the
    bytes that decide it (also the attributes of `flash.bwd_plan`).
    resident: the query side of a whole head in VMEM, taken where its
    blocks (double-buffered by Mosaic) and the f32 temporaries of one
    accumulate step fit a quarter of the core's VMEM; stream otherwise.
    The rest is XLA's: it holds operands of the fusions around the call
    there, and a call's `vmem_limit_bytes` is taken out of that for as
    long as the call is scheduled (at 96 MiB the four-chip step lost a
    64 MiB operand of a weight-gradient fusion, 16 ms a step: PERF.md 6,
    PR 28), so the limit asked for is the estimate and a quarter."""
    itemsize = jnp.dtype(dtype).itemsize
    row_bytes = 3 * D * itemsize + _LSE_LANES * 4
    # a head's results leave in the inputs' dtype; a group's are summed in f32
    out_dtype = jnp.dtype(dtype if groups == 1 else jnp.float32)
    resident_bytes = (
        2 * S * row_bytes
        + 2 * 2 * block_k * D * (itemsize + out_dtype.itemsize)
        # two [block_q, block_k] of s/p/dp/ds; q, dO, o; k, v, dk, dv
        # (Mosaic planned 0.75-1.1 MiB under this at five shapes)
        + 4 * (2 * block_q * block_k + (3 * block_q + 4 * block_k) * D))
    path = "resident" if resident_bytes <= vmem_bytes // 4 else "stream"
    if path == "stream":                   # accumulated in the output block
        out_dtype = jnp.dtype(jnp.float32)
    dims = dict(block_q=block_q, block_k=block_k)
    return dict(
        dims, path=path, S=S, window=window, resident_bytes=resident_bytes,
        out_dtype=out_dtype, vmem_limit_bytes=resident_bytes * 5 // 4,
        hbm_bytes_per_head=hbm_bytes_per_head(
            path, S=S, T=T, D=D, itemsize=itemsize,
            out_itemsize=out_dtype.itemsize, **dims,
            q_index=functools.partial(
                _q_block_index, num_q=S // block_q, causal=causal,
                window=window, **dims)))


def _flash_bwd_dkdv(qt, kt, vt, gt, ot, lse, *, causal: bool, block_q: int,
                    block_k: int, window: int, scale: float = None,
                    vmem_bytes: int = None, dq_path: str = None):
    """The dK/dV call on [B, H|KV, S|T, D] operands (lse [B, H, S, 128]):
    per-query-head dK and dV, [B, H, T, D]."""
    B, H, S, D = qt.shape
    KV, T = kt.shape[1], kt.shape[2]
    groups = H // KV
    num_q = S // block_q
    plan = bwd_dkdv_plan(
        S=S, T=T, D=D, dtype=kt.dtype, groups=groups,
        block_q=block_q, block_k=block_k, causal=causal, window=window,
        vmem_bytes=vmem_bytes or _vmem_bytes())
    tracing.instant("flash.bwd_plan", {
        **{k: plan[k] for k in ("path", "S", "block_q", "block_k", "window",
                                "resident_bytes", "hbm_bytes_per_head")},
        **({"dq_path": dq_path} if dq_path else {})})
    kernel_args = dict(block_q=block_q, scale=_scale(scale, D),
                       causal=causal, window=window)
    if plan["path"] == "resident":
        kernel = functools.partial(_bwd_dkdv_resident_kernel, **kernel_args)
        grid = (B, H, T // block_k)
        last_k = T // block_k - 1          # the kernel walks them last to first

        def q_side(width):
            return pl.BlockSpec((1, 1, S, width), lambda b, h, i: (b, h, 0, 0))

        kv_blk = pl.BlockSpec((1, 1, block_k, D),
                              lambda b, h, i: (b, h // groups, last_k - i, 0))
        dkv_blk = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, i: (b, h, last_k - i, 0))
        params = pltpu.CompilerParams(
            vmem_limit_bytes=plan["vmem_limit_bytes"])
    else:
        kernel = functools.partial(_bwd_dkdv_stream_kernel, num_q=num_q,
                                   **kernel_args)
        grid = (B, H, T // block_k, num_q)

        def q_index(b, h, i, j):
            return (b, h, _q_block_index(
                i, j, num_q=num_q, block_q=block_q, block_k=block_k,
                causal=causal, window=window), 0)

        def q_side(width):
            return pl.BlockSpec((1, 1, block_q, width), q_index)

        kv_blk = pl.BlockSpec((1, 1, block_k, D),
                              lambda b, h, i, j: (b, h // groups, i, 0))
        dkv_blk = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, i, j: (b, h, i, 0))
        params = None
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_side(D), kv_blk, kv_blk, q_side(D), q_side(D),
                  q_side(_LSE_LANES)],
        out_specs=[dkv_blk, dkv_blk],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, D), plan["out_dtype"])] * 2,
        compiler_params=params,
        interpret=_use_interpret(),
    )
    with jax.named_scope(f"flash.dkdv.{plan['path']}"):
        return call(qt, kt, vt, gt, ot, lse)


def _flash_bwd_dq(qt, kt, vt, gt, ot, lse, *, path: str, causal: bool,
                  block_q: int, block_k: int, window: int, scale: float):
    """The dQ call on [B, H|KV, S|T, D] operands (lse [B, H, S, 128]), by
    the plan `kv_plan` chose: [B, H, S, D] in q's dtype."""
    B, H, S, D = qt.shape
    T, groups = kt.shape[2], H // kt.shape[1]
    if path == "loop":
        q_blk = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0))
        kv_spec = pl.BlockSpec((1, 1, T, D),
                               lambda b, h, i, g_=groups: (b, h // g_, 0, 0))
        call = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, block_k=block_k, scale=scale,
                              causal=causal, window=window),
            grid=(B, H, S // block_q),
            in_specs=[
                q_blk,
                kv_spec,
                kv_spec,
                q_blk,
                q_blk,
                pl.BlockSpec((1, 1, block_q, 128),
                             lambda b, h, i: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, D),
                                   lambda b, h, i: (b, h, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B, H, S, D), qt.dtype),
            interpret=_use_interpret(),
        )
        with jax.named_scope("flash.dq.loop"):      # flash.bwd_plan's dq_path
            return call(qt, kt, vt, gt, ot, lse)
    num_k = T // block_k

    def kv_idx(b, h, qi, ki):
        lo, hi = _k_band(qi, num_k=num_k, block_q=block_q, block_k=block_k,
                         causal=causal, window=window)
        if causal:      # a step outside the band repeats its near edge
            ki = jax.lax.max(jax.lax.min(ki, hi - 1), lo)
        return (b, h // groups, ki, 0)

    def q_side(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, qi, ki: (b, h, qi, 0))

    kv_blk = pl.BlockSpec((1, 1, block_k, D), kv_idx)
    call = pl.pallas_call(
        functools.partial(_bwd_dq_stream_kernel, num_k=num_k, scale=scale,
                          causal=causal, window=window),
        grid=(B, H, S // block_q, num_k),
        in_specs=[q_side(D), kv_blk, kv_blk, q_side(D), q_side(D),
                  q_side(_LSE_LANES)],
        out_specs=q_side(D),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
        interpret=_use_interpret(),
    )
    with jax.named_scope("flash.dq.stream"):
        return call(qt, kt, vt, gt, ot, lse).astype(qt.dtype)


def _flash_pallas_bwd(res, g, *, causal: bool, block_q: int, block_k: int,
                      scale: float, window: int = 0):
    """Full Pallas backward: two kernels (dQ; dK/dV), GQA group-sum on the
    dK/dV results (FlashAttention-2, Dao 2023)."""
    q, k, v, out, lse = res
    # the residual is compact [B, H, S]; the kernels read 128-lane blocks
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LSE_LANES,))
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, T)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    gt = g.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)

    dq_path = kv_plan(S=S, T=T, D=D, dtype=k.dtype, block_q=block_q,
                      block_k=block_k)["path"]
    dq = _flash_bwd_dq(qt, kt, vt, gt, ot, lse, path=dq_path, causal=causal,
                       block_q=block_q, block_k=block_k, window=window,
                       scale=scale)
    dk, dv = _flash_bwd_dkdv(qt, kt, vt, gt, ot, lse, causal=causal,
                             block_q=block_q, block_k=block_k, window=window,
                             scale=scale, dq_path=dq_path)
    if groups > 1:
        # GQA: sum per-query-head contributions into each kv head.
        dk = dk.reshape(B, KV, groups, T, D).sum(2)
        dv = dv.reshape(B, KV, groups, T, D).sum(2)
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3).astype(k.dtype),
            dv.transpose(0, 2, 1, 3).astype(v.dtype))


def _reference_chunked_bwd(res, g, *, causal: bool, chunk: int,
                           scale: float = None, window: int = 0):
    """Recompute-based backward, chunked over the key axis to stay O(S*chunk)
    in memory. Uses the forward's lse so probabilities are exact."""
    q, k, v, out, lse = res                            # lse [B, H, S]
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    scale = _scale(scale, D)

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, window, scale):
    out, _ = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, window=window, scale=scale)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, window, scale=None):
    scale = _scale(scale, q.shape[-1])
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, window=window, scale=scale)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    # drop the lane broadcast: [B, H, S, 128] -> [B, H, S]
    lse = checkpoint_name(lse[..., 0], FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


BACKWARD_IMPL = "pallas"   # "pallas" | "chunked" (recompute fallback)


def _flash_vjp_bwd(causal, block_q, block_k, window, scale, res, g):
    scale = _scale(scale, res[0].shape[-1])
    if BACKWARD_IMPL == "pallas":
        return _flash_pallas_bwd(res, g, causal=causal, block_q=block_q,
                                 block_k=block_k, window=window, scale=scale)
    return _reference_chunked_bwd(res, g, causal=causal, chunk=block_k * 4,
                                  window=window, scale=scale)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, window: Optional[int] = None,
                    scale: Optional[float] = None):
    # 512x512 blocks measured +14% end-to-end over 256x256 on v5e at
    # S=1024 (llama-125m train step 110.5ms -> 95.5ms); scores block is
    # 1 MiB f32, comfortably inside VMEM alongside q/k/v tiles.
    """q [B,S,H,D], k/v [B,T,KV,D] -> [B,S,H,D]. S, T must divide blocks
    (pad upstream); returns in q.dtype. window=W (causal only) restricts
    each query to the last W keys — Mistral-style sliding-window
    attention; blocks wholly outside the band are skipped, so compute is
    O(S*W) instead of O(S^2). scale multiplies the scores before the
    softmax: D ** -0.5 unless the model states its own."""
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
                  int(window or 0), _scale(scale, D))
