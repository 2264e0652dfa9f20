"""Attention over an index set, for TPU in Pallas: every query t attends to
the keys of its own set ``Sel_t`` and to no other (DeepSeek Sparse
Attention, arXiv:2512.02556), the set a 0/1 matrix ``keep [B, S, T]`` int8
that is already causal (``keep[b, t, s]`` is 0 for s > t):

    p[h, t, s] = softmax over {s : keep[t, s]} of scale * q[t, h] . k[s, h]
    o[t, h]    = sum_s p[h, t, s] v[s, h]

The form is the streaming flash walk with a membership test a pair (form
"mask"; PERF.md 6, PR 45 has what the gathered forms took beside it): grid
(batch, head, q-block, k-block), the online-softmax sums in VMEM scratch,
a k-block past the q-block's diagonal fetched and computed by nobody (the
index maps clamp into the causal band and Mosaic elides a fetch whose
index repeats). Nothing is gathered and nothing O(S T) is written but the
set itself. The backward is FlashAttention-2's two passes with the same
test: dQ by the forward's walk, dK/dV by its mirror image, both from the
saved log-sum-exp (``FLASH_RESIDUALS``' names, so the layer checkpoint
keeps o and lse as it does the dense calls').

``with_probs`` gives the second thing a learned selection needs: the
probabilities summed over heads, ``P[t, s] = (1 / H) sum_h p[h, t, s]``,
float32 ``[B, S, T]``, the target the indexer is trained towards. It is a
walk of its own, the heads innermost, from the forward's log-sum-exp; no
gradient passes through it. Blocks past the diagonal are never written:
read P only where ``keep`` is set.

Off the chip (interpret mode costs minutes at any real size) and under
128 keys the same mathematics run in plain ``jax.numpy``
(``_reference``), which is also what the tests hold the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (FLASH_RESIDUALS, NEG_INF,
                                         _use_interpret)
from ray_tpu.util import tracing

_LANES = 128                # lse travels lane-broadcast, as the flash calls'
BLOCK_Q = 512
BLOCK_K = 512
# "pallas" | "xla": the tests switch to "pallas" (interpret mode) at small
# sizes; None takes Pallas on a TPU from 128 keys up and XLA elsewhere
IMPL = None


def _blocks(S: int, T: int) -> tuple:
    bq, bk = min(BLOCK_Q, S), min(BLOCK_K, T)
    while S % bq:
        bq //= 2
    while T % bk:
        bk //= 2
    return bq, bk


def plan(*, B: int, H: int, S: int, T: int, D: int, dtype, call: str) -> dict:
    """What a call says of itself (instants ``sparse.fwd_plan`` and
    ``sparse.bwd_plan``): its tiles, the VMEM its blocks (double-buffered)
    and a step's temporaries take, the grid steps that work, and the
    path."""
    bq, bk = _blocks(S, T)
    e = jnp.dtype(dtype).itemsize
    scores = bq * bk * 4
    side = {"fwd": 1, "probs": 1, "dq": 3, "dkdv": 3}[call]   # q; q, g, o
    blocks = 2 * (side * bq * D * e + 2 * bk * D * e + bq * bk
                  + bq * _LANES * 4)
    out = {"fwd": 2 * bq * D * e + 3 * bq * D * 4,
           "probs": 2 * scores, "dq": 2 * bq * D * e + bq * D * 4,
           "dkdv": 4 * bk * D * e + 2 * bk * D * 4}[call]
    nq, nk = S // bq, T // bk
    live = sum(min(nk, ((qi + 1) * bq - 1) // bk + 1) for qi in range(nq))
    return {"path": "mask", "call": call, "S": S, "T": T, "D": D,
            "block_q": bq, "block_k": bk,
            "vmem_bytes": blocks + out + 4 * scores,
            "grid_steps": B * H * nq * nk, "live_steps": B * H * live}


def _last_k(qi, bq: int, bk: int):
    """The last k-block that holds a key a row of q-block ``qi`` may see."""
    return jax.lax.div((qi + 1) * bq - 1, bk)


def _first_q(ki, bq: int, bk: int):
    """The first q-block that holds a row that may see k-block ``ki``."""
    return jax.lax.div(ki * bk, bq)


def _scores(q, k, keep, scale: float):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(keep, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref, acc, m_scr,
                l_scr, *, scale: float):
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(ki <= _last_k(qi, bq, bk))
    def _block():
        v = v_ref[0, 0]
        keep = keep_ref[0].astype(jnp.int32) != 0
        s = _scores(q_ref[0, 0], k_ref[0, 0], keep, scale)
        m = m_scr[...][:, 0:1]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # a row with no key of this block yet has m_new NEG_INF, and
        # exp(s - m_new) would read 1 where nothing is kept
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l_scr[...][:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, 0:1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[...][:, 0:1] + jnp.log(l),
                                         (bq, _LANES))


def _probs_kernel(q_ref, k_ref, keep_ref, lse_ref, p_ref, *, scale: float,
                  heads: int):
    """Grid (b, q-block, k-block, head): the float32 P block is constant
    in the (minor) head axis, stays resident and takes every head's
    probabilities in turn."""
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    qi, ki, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(ki <= _last_k(qi, bq, bk))
    def _block():
        @pl.when(h == 0)
        def _zero():
            p_ref[0] = jnp.zeros_like(p_ref[0])

        keep = keep_ref[0].astype(jnp.int32) != 0
        s = _scores(q_ref[0, 0], k_ref[0, 0], keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0][:, 0:1]), 0.0)
        p_ref[0] += p * (1.0 / heads)


def _dq_kernel(q_ref, k_ref, v_ref, keep_ref, g_ref, o_ref, lse_ref, dq_ref,
               acc, *, scale: float):
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(ki <= _last_k(qi, bq, bk))
    def _block():
        k, v, g = k_ref[0, 0], v_ref[0, 0], g_ref[0, 0]
        keep = keep_ref[0].astype(jnp.int32) != 0
        s = _scores(q_ref[0, 0], k, keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0][:, 0:1]), 0.0)
        delta = jnp.sum(o_ref[0, 0].astype(jnp.float32)
                        * g.astype(jnp.float32), axis=-1, keepdims=True)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = acc[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, keep_ref, g_ref, o_ref, lse_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float):
    """Grid (b, h, k-block, q-block): a k-block's sums over the q-blocks
    from its diagonal on."""
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(qi >= _first_q(ki, bq, bk))
    def _block():
        q, g = q_ref[0, 0], g_ref[0, 0]
        keep = keep_ref[0].astype(jnp.int32) != 0
        s = _scores(q, k_ref[0, 0], keep, scale)
        p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0][:, 0:1]), 0.0)
        delta = jnp.sum(o_ref[0, 0].astype(jnp.float32)
                        * g.astype(jnp.float32), axis=-1, keepdims=True)
        dp = jax.lax.dot_general(g, v_ref[0, 0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dv_acc[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _q_walk_specs(bq: int, bk: int, D: int):
    """Block specs of a walk over (b, h, q-block, k-block): the q side
    stays a q-block's steps, K, V and the set's block follow the k-block
    clamped to the diagonal."""
    def q_side(width):
        return pl.BlockSpec((1, 1, bq, width), lambda b, h, qi, ki: (b, h, qi, 0))

    def at(qi, ki):
        return jax.lax.min(ki, _last_k(qi, bq, bk))

    kv = pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h, at(qi, ki), 0))
    keep = pl.BlockSpec((1, bq, bk), lambda b, h, qi, ki: (b, qi, at(qi, ki)))
    return q_side, kv, keep


def _fwd(qt, kt, vt, keep, scale: float):
    """qt, kt, vt [B, H, S|T, D] -> o [B, H, S, D], lse [B, H, S, 128]."""
    B, H, S, D = qt.shape
    T = kt.shape[2]
    bq, bk = _blocks(S, T)
    tracing.instant("sparse.fwd_plan", plan(
        B=B, H=H, S=S, T=T, D=D, dtype=qt.dtype, call="fwd"))
    q_side, kv, keep_spec = _q_walk_specs(bq, bk, D)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(B, H, S // bq, T // bk),
        in_specs=[q_side(D), kv, kv, keep_spec],
        out_specs=[q_side(D), q_side(_LANES)],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), qt.dtype),
                   jax.ShapeDtypeStruct((B, H, S, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32)],
        interpret=_use_interpret())
    with jax.named_scope("sparse.fwd.mask"):
        return call(qt, kt, vt, keep)


def _probs(qt, kt, keep, lse, scale: float):
    B, H, S, D = qt.shape
    T = kt.shape[2]
    bq, bk = _blocks(S, T)

    def at(qi, ki):
        return jax.lax.min(ki, _last_k(qi, bq, bk))

    call = pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, heads=H),
        grid=(B, S // bq, T // bk, H),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, qi, ki, h: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, qi, ki, h: (b, h, at(qi, ki), 0)),
            pl.BlockSpec((1, bq, bk), lambda b, qi, ki, h: (b, qi, at(qi, ki))),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b, qi, ki, h: (b, h, qi, 0))],
        out_specs=pl.BlockSpec((1, bq, bk),
                               lambda b, qi, ki, h: (b, qi, at(qi, ki))),
        out_shape=jax.ShapeDtypeStruct((B, S, T), jnp.float32),
        interpret=_use_interpret())
    with jax.named_scope("sparse.probs.mask"):
        return call(qt, kt, keep, lse)


def _bwd(qt, kt, vt, keep, gt, ot, lse, scale: float):
    B, H, S, D = qt.shape
    T = kt.shape[2]
    bq, bk = _blocks(S, T)
    tracing.instant("sparse.bwd_plan", plan(
        B=B, H=H, S=S, T=T, D=D, dtype=qt.dtype, call="dkdv"))
    q_side, kv, keep_spec = _q_walk_specs(bq, bk, D)
    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(B, H, S // bq, T // bk),
        in_specs=[q_side(D), kv, kv, keep_spec, q_side(D), q_side(D),
                  q_side(_LANES)],
        out_specs=q_side(D),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), qt.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=_use_interpret())
    with jax.named_scope("sparse.dq.mask"):
        dq = dq_call(qt, kt, vt, keep, gt, ot, lse)

    def at(ki, qi):
        return jax.lax.max(qi, _first_q(ki, bq, bk))

    def q_blk(width):
        return pl.BlockSpec((1, 1, bq, width),
                            lambda b, h, ki, qi: (b, h, at(ki, qi), 0))

    k_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, qi: (b, h, ki, 0))
    dkdv_call = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale),
        grid=(B, H, T // bk, S // bq),
        in_specs=[q_blk(D), k_blk, k_blk,
                  pl.BlockSpec((1, bq, bk),
                               lambda b, h, ki, qi: (b, at(ki, qi), ki)),
                  q_blk(D), q_blk(D), q_blk(_LANES)],
        out_specs=[k_blk, k_blk],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, D), kt.dtype),
                   jax.ShapeDtypeStruct((B, H, T, D), vt.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=_use_interpret())
    with jax.named_scope("sparse.dkdv.mask"):
        dk, dv = dkdv_call(qt, kt, vt, keep, gt, ot, lse)
    return dq, dk, dv


def _reference(q, k, v, keep, scale: float):
    """The same mathematics in ``jax.numpy``: (o [B, S, H, D], P [B, S,
    T] float32, the mean of the heads' probabilities)."""
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    on = (keep != 0)[:, None]
    s = jnp.where(on, s, NEG_INF)
    p = jnp.where(on, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), jnp.mean(p, axis=1)


def _pallas(S: int, T: int) -> bool:
    if IMPL is not None:
        return IMPL == "pallas"
    return jax.default_backend() == "tpu" and S >= 128 and T >= 128


def _sparse_fwd(q, k, v, keep, scale):
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    ot, lse = _fwd(qt, kt, vt, keep, scale)
    out = checkpoint_name(ot.transpose(0, 2, 1, 3), FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse[..., 0], FLASH_RESIDUALS[1])
    return (out, lse), (q, k, v, keep, out, lse)


def _sparse_bwd(scale, res, g):
    q, k, v, keep, out, lse = res
    g = g[0]                                # lse hands no gradient on
    lanes = jnp.broadcast_to(lse[..., None], lse.shape + (_LANES,))
    dq, dk, dv = _bwd(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)), keep,
                      g.transpose(0, 2, 1, 3), out.transpose(0, 2, 1, 3),
                      lanes, scale)
    return (*(t.transpose(0, 2, 1, 3) for t in (dq, dk, dv)), None)


def _sparse_out(q, k, v, keep, scale):
    return _sparse_fwd(q, k, v, keep, scale)[0]


_sparse = jax.custom_vjp(_sparse_out, nondiff_argnums=(4,))
_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def sparse_attention(q, k, v, keep, *, scale: float = None,
                     with_probs: bool = False):
    """q [B, S, H, D], k and v [B, T, H, D], keep [B, S, T] int8 (causal
    already) -> o [B, S, H, D] in q's dtype, or (o, P) with ``with_probs``:
    P [B, S, T] float32, the heads' mean probability of every kept pair
    (undefined where ``keep`` is 0), which carries no gradient."""
    B, S, H, D = q.shape
    scale = D ** -0.5 if scale is None else float(scale)
    if not _pallas(S, k.shape[1]):
        with jax.named_scope("sparse.xla"):
            out, probs = _reference(q, k, v, keep, scale)
        return (out, jax.lax.stop_gradient(probs)) if with_probs else out
    out, lse = _sparse(q, k, v, keep, scale)
    if not with_probs:
        return out
    qt, kt = (jax.lax.stop_gradient(t).transpose(0, 2, 1, 3) for t in (q, k))
    lanes = jnp.broadcast_to(
        jax.lax.stop_gradient(lse)[..., None], lse.shape + (_LANES,))
    return out, _probs(qt, kt, keep, lanes, scale)
