"""Attention over an index set, for TPU in Pallas: every query t attends to
the keys of its own set ``Sel_t`` and to no other (DeepSeek Sparse
Attention, arXiv:2512.02556), the set a 0/1 matrix ``keep [B, S, T]`` int8
that is already causal (``keep[b, t, s]`` is 0 for s > t):

    p[h, t, s] = softmax over {s : keep[t, s]} of scale * q[t, h] . k[s, h]
    o[t, h]    = sum_s p[h, t, s] v[s, h]

The form is the streaming flash walk with a membership test a pair (form
"mask"; PERF.md 6, PR 45 has what the gathered forms took beside it), in
the shape ``ops/flash_attention.py``'s stream plan has since PR 37: grid
(batch, head, q-block, SPAN). The q-block stays; K, V and the set's
columns arrive a span of k-blocks a grid step (the set's block is ``[512,
span x 512]`` int8) and the kernel walks the span's blocks itself, up to
the q-block's diagonal, two blocks a step of the walk where their
temporaries fit (both blocks' ``q k^T`` issued before the first block's
softmax); the online-softmax sums live in VMEM scratch across a q-block's
spans. A span past the diagonal is fetched and computed by nobody (the
index maps clamp into the causal band and Mosaic elides a fetch whose
index repeats). A span whole inside the band is written out as
straight-line code, the span on the diagonal walks in a loop
(``flash_attention._span_walk``, which the dense stream calls use too).
Nothing is gathered and nothing O(S T) is written but
the set itself. The backward is FlashAttention-2's two passes with the
same test: dQ by the forward's walk, dK/dV by its mirror image (a k-block
stays, q, dO, o, lse and the set's ROWS arrive a span of q-blocks a grid
step, from the k-block's diagonal on), both from the saved log-sum-exp
(``FLASH_RESIDUALS``' names, so the layer checkpoint keeps o and lse as it
does the dense calls').

``span`` and ``in_flight`` are chosen by the shapes alone (``_choose``):
the longest span, a divisor of the blocks, whose count of VMEM
(``_vmem_bytes``, held above what Mosaic really plans) fits the 16 MiB a
Mosaic call gets without asking, two in flight before one. At the GLM-5.2
cell's shape (S 16,384, D 256, bf16) that is forward 4 and 2, dQ 4 and 2,
probs 4 and 2, dK/dV 2 and 2, and a head's grid is 256 steps of which 144
work where it was 1,024 of which 528 (dK/dV: 512 of which 272). Every
block makes its own update of the sums in rising order whatever the span,
so all spans agree with the walk of one block a grid step to the last bit
(on the chip, at the cell's shape: PERF.md 6, PR 46, which also has each
call's time alone).

``with_probs`` gives the second thing a learned selection needs: the
probabilities summed over heads, ``P[t, s] = (1 / H) sum_h p[h, t, s]``,
float32 ``[B, S, T]``, the target the indexer is trained towards. It is a
walk of its own, the heads innermost, from the forward's log-sum-exp: the
float32 P block, a span of k-blocks wide, stays resident while every head
adds to it; no gradient passes through it. The call is bound by what it
fetches (q, its lse and a k-block a head and block: 0.75 MiB a 512 x 512
block one at a time), so the span of KEYS is what pays: q and the lse are
fetched once a span (a group of heads a grid step fetches the same bytes
and gained 6% where spans of 2 and 4 blocks gained 28% and 41%). Blocks
past the diagonal are never written, or hold zeros inside a span that
reaches it: read P only where ``keep`` is set. P is computed once a full
layer and step, never in a layer's replay: its one reader, the indexer's
loss, makes its gradient in the forward and the layer checkpoint keeps
that (``models/latent.py`` ``_index_loss``, ``INDEX_GRADS``).

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

from ray_tpu.ops.flash_attention import (_SCOPED_VMEM_BYTES, FLASH_RESIDUALS,
                                         NEG_INF, _span_walk, _use_interpret)
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


def _vmem_bytes(call: str, *, span: int, in_flight: int, bq: int, bk: int,
                D: int, e: int) -> int:
    """What a grid step of ``call`` holds in VMEM: its blocks, twice
    (Mosaic double-buffers), the sums it keeps across grid steps, and the
    temporaries of ``in_flight`` blocks' step of the walk. Held above the
    least ``vmem_limit_bytes`` Mosaic accepted for a described v5e at the
    GLM-5.2 cell's shape (S 16,384, D 256, bf16; span and in flight: the
    count | the least, MiB): forward (1, 1) 7.5 | 6.75, (4, 2) 14.5 |
    13.75, (8, 1) 18.0 | 18.0; dQ (1, 1) 7.0 | 6.5, (4, 2) 13.0 | 11.75,
    (8, 1) 17.5 | 17.0; probs (1, 1) 5.0 | 4.75, (4, 2) 15.0 | 13.75;
    dK/dV (1, 1) 9.0 | 7.75, (2, 2) 13.5 | 11.75, (4, 1) 16.5 | 15.25
    (which fits by three quarters of a MiB and took 51.7 ms a call against
    52.9 at (2, 2): the count leaves it out)."""
    scores = bq * bk
    lse = bq * _LANES * 4
    if call == "probs":
        # q and its lse; K, the set and the float32 P block a span; a
        # block's s on its way into P
        return (2 * (bq * D * e + lse + span * (bk * D * e + scores * 5))
                + in_flight * scores * 4)
    if call == "dkdv":
        # q, dO, o, lse and the set's rows a span; k, v, dk, dv; the two
        # sums; a block's s and dp in float32, p and ds in e
        return (2 * (span * (3 * bq * D * e + lse + scores) + 4 * bk * D * e)
                + 2 * bk * D * 4
                + in_flight * scores * (4 + 2 * e) + 3 * bk * D * 4)
    # K, V and the set a span; q, the result (o or dq) and the lse
    blocks = 2 * (span * (2 * bk * D * e + scores) + 2 * bq * D * e + lse)
    if call == "fwd":
        # the accumulator, the running max and sum; a block's s and p in
        # float32 and p in e; the accumulator on its way
        return (blocks + bq * D * 4 + 2 * lse
                + in_flight * scores * (2 * 4 + e) + 2 * bq * D * 4)
    # dq: dO and o beside q; the sum; a block's s or dp and ds in e
    return (blocks + 2 * 2 * bq * D * e + bq * D * 4
            + in_flight * scores * (4 + e) + 2 * bq * D * 4)


def _divisors(n: int) -> list:
    return [d for d in range(n, 0, -1) if n % d == 0]


def _choose(call: str, *, nq: int, nk: int, bq: int, bk: int, D: int,
            e: int) -> tuple:
    """(span, in_flight) of ``call``: the blocks a grid step holds and how
    many of them a step of the walk takes, the longest span whose count
    (``_vmem_bytes``) fits the VMEM a Mosaic call gets without asking, two
    in flight before one, as ``flash_attention.kv_plan`` chooses."""
    return next(
        ((n, f) for n in _divisors(nq if call == "dkdv" else nk)
         for f in (2, 1) if f <= n and _vmem_bytes(
             call, span=n, in_flight=f, bq=bq, bk=bk, D=D, e=e)
         <= _SCOPED_VMEM_BYTES), (1, 1))


def plan(*, B: int, H: int, S: int, T: int, D: int, dtype, call: str) -> dict:
    """What a call says of itself (instants ``sparse.fwd_plan``,
    ``sparse.probs_plan`` and ``sparse.bwd_plan``): its tiles, what a grid
    step holds (``span`` blocks of 512 keys, of 512 queries in the dK/dV
    call) and how many of them a step of the walk takes (``in_flight``),
    the VMEM counted for that (``_vmem_bytes``), the grid and the grid
    steps that work, and the path."""
    bq, bk = _blocks(S, T)
    nq, nk = S // bq, T // bk
    e = jnp.dtype(dtype).itemsize
    span, in_flight = _choose(call, nq=nq, nk=nk, bq=bq, bk=bk, D=D, e=e)
    if call == "dkdv":
        steps = nq // span
        live = sum(steps - (ki * bk // bq) // span for ki in range(nk))
        grid = nk * steps
    else:
        steps = nk // span
        live = sum(min(nk - 1, ((qi + 1) * bq - 1) // bk) // span + 1
                   for qi in range(nq))
        grid = nq * steps
    return {"path": "mask", "call": call, "S": S, "T": T, "D": D,
            "block_q": bq, "block_k": bk, "span": span,
            "in_flight": in_flight,
            "vmem_bytes": _vmem_bytes(call, span=span, in_flight=in_flight,
                                      bq=bq, bk=bk, D=D, e=e),
            "grid_steps": B * H * grid, "live_steps": B * H * live}


def _last_k(qi, bq: int, bk: int):
    """The last k-block that holds a key a row of q-block ``qi`` may see."""
    return jax.lax.div((qi + 1) * bq - 1, bk)


def _k_span(qi, si, bq: int, bk: int, span: int):
    """[lo, hi): the k-blocks of span ``si`` (``span`` blocks) that hold a
    key a row of q-block ``qi`` may see; empty past the diagonal."""
    lo = si * span
    return lo, jax.lax.min(_last_k(qi, bq, bk) + 1, lo + span)


def _k_span_at(qi, si, bq: int, bk: int, span: int):
    """The span an index map fetches at grid step (qi, si): ``si`` clamped
    to the span that holds the diagonal (a span past it repeats that
    index, and Mosaic elides a fetch whose index repeats)."""
    return jax.lax.min(si, jax.lax.div(_last_k(qi, bq, bk), span))


def _first_q(ki, bq: int, bk: int):
    """The first q-block that holds a row that may see k-block ``ki``."""
    return jax.lax.div(ki * bk, bq)


def _scores(q, k, keep, scale: float):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return jnp.where(keep, s, NEG_INF)


def _member(keep):
    return keep.astype(jnp.int32) != 0


def _fwd_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref, acc, m_scr,
                l_scr, *, scale: float, bk: int, in_flight: int):
    """Grid (b, h, q-block, span): the q-block stays, K, V and the set's
    columns arrive a span of k-blocks a grid step, and the kernel walks
    the span's blocks up to the q-block's diagonal (``_span_walk``): a
    step of the walk issues ``q k^T`` for all its blocks, then updates
    the sums block by block in rising order, the running max and sum read
    and written once a step, the accumulator in place a block (read once
    a step and carried through it measured the same: 35.5 against 35.7 ms
    a call)."""
    bq, span = q_ref.shape[2], k_ref.shape[2] // bk
    qi, si = pl.program_id(2), pl.program_id(3)
    lo, hi = _k_span(qi, si, bq, bk, span)

    @pl.when(si == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def body(offsets):
        at = [pl.ds(a, bk) for a in offsets]
        q = q_ref[0, 0]
        keeps = [_member(keep_ref[0, :, a]) for a in at]
        scores = [_scores(q, k_ref[0, 0, a, :], keep, scale)
                  for a, keep in zip(at, keeps)]
        m, l = m_scr[...][:, 0:1], l_scr[...][:, 0:1]
        for a, keep, s in zip(at, keeps, scores):
            v = v_ref[0, 0, a, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            # a row with no key of this block yet has m_new NEG_INF,
            # and exp(s - m_new) would read 1 where nothing is kept
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc[...] = acc[...] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m = m_new
        m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(lo < hi)
    def _span():
        _span_walk(lo, hi, lo, bk, span, in_flight, body)

    @pl.when(si == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, 0:1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[...][:, 0:1] + jnp.log(l),
                                         (bq, _LANES))


def _probs_kernel(q_ref, k_ref, keep_ref, lse_ref, p_ref, *, scale: float,
                  heads: int, bk: int, in_flight: int):
    """Grid (b, q-block, span, head): the float32 P block, a span of
    k-blocks wide, is constant in the (minor) head axis, stays resident
    and takes every head's probabilities in turn."""
    bq, span = q_ref.shape[2], k_ref.shape[2] // bk
    qi, si, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    lo, hi = _k_span(qi, si, bq, bk, span)

    @pl.when(lo < hi)
    def _span():
        @pl.when(h == 0)
        def _zero():
            p_ref[0] = jnp.zeros_like(p_ref[0])

        lse = lse_ref[0, 0][:, 0:1]

        def body(offsets):
            at = [pl.ds(a, bk) for a in offsets]
            q = q_ref[0, 0]
            keeps = [_member(keep_ref[0, :, a]) for a in at]
            scores = [_scores(q, k_ref[0, 0, a, :], keep, scale)
                      for a, keep in zip(at, keeps)]
            for a, keep, s in zip(at, keeps, scores):
                p_ref[0, :, a] += jnp.where(
                    keep, jnp.exp(s - lse), 0.0) * (1.0 / heads)

        _span_walk(lo, hi, lo, bk, span, in_flight, body)


def _dq_kernel(q_ref, k_ref, v_ref, keep_ref, g_ref, o_ref, lse_ref, dq_ref,
               acc, *, scale: float, bk: int, in_flight: int):
    """The forward's walk for dQ: a block's ``q k^T`` and ``g v^T`` need
    nothing of the sum, so a step of the walk issues them for all its
    blocks first; delta = rowsum(o * dO) once a step of the walk (kept
    across the walk it cost 0.7 ms a call more than it saved)."""
    bq, span = q_ref.shape[2], k_ref.shape[2] // bk
    qi, si = pl.program_id(2), pl.program_id(3)
    lo, hi = _k_span(qi, si, bq, bk, span)

    @pl.when(si == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    def body(offsets):
        at = [pl.ds(a, bk) for a in offsets]
        q, g = q_ref[0, 0], g_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = jnp.sum(o_ref[0, 0].astype(jnp.float32)
                        * g.astype(jnp.float32), axis=-1, keepdims=True)
        blocks = [(k_ref[0, 0, a, :], v_ref[0, 0, a, :],
                   _member(keep_ref[0, :, a])) for a in at]
        products = [(_scores(q, k, keep, scale), jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) for k, v, keep in blocks]
        for (k, _, keep), (s, dp) in zip(blocks, products):
            p = jnp.where(keep, jnp.exp(s - lse), 0.0)
            ds = p * (dp - delta) * scale
            acc[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    @pl.when(lo < hi)
    def _span():
        _span_walk(lo, hi, lo, bk, span, in_flight, body)

    @pl.when(si == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = acc[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, keep_ref, g_ref, o_ref, lse_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float, bq: int,
                 in_flight: int):
    """Grid (b, h, k-block, span): the mirror image, a k-block's sums over
    a span of q-blocks a grid step, from the k-block's diagonal on."""
    bk, span = k_ref.shape[2], q_ref.shape[2] // bq
    ki, si = pl.program_id(2), pl.program_id(3)
    first = si * span
    lo = jax.lax.max(_first_q(ki, bq, bk), first)
    hi = first + span

    @pl.when(si == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(offsets):
        at = [pl.ds(a, bq) for a in offsets]
        k, v = k_ref[0, 0], v_ref[0, 0]
        rows = [(q_ref[0, 0, a, :], g_ref[0, 0, a, :],
                 _member(keep_ref[0, a, :])) for a in at]
        products = [(_scores(q, k, keep, scale), jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) for q, g, keep in rows]
        for a, (q, g, keep), (s, dp) in zip(at, rows, products):
            p = jnp.where(keep, jnp.exp(s - lse_ref[0, 0, a, :][:, 0:1]),
                          0.0)
            delta = jnp.sum(o_ref[0, 0, a, :].astype(jnp.float32)
                            * g.astype(jnp.float32), axis=-1,
                            keepdims=True)
            ds = p * (dp - delta) * scale
            dv_acc[...] += jax.lax.dot_general(
                p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(lo < hi)
    def _span():
        _span_walk(lo, hi, first, bq, span, in_flight, body)

    @pl.when(si == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _q_walk_specs(bq: int, bk: int, D: int, span: int):
    """Block specs of a walk over (b, h, q-block, span): the q side stays
    a q-block's steps, K, V and the set's columns follow the span of
    ``span`` k-blocks, clamped to the diagonal (``_k_span_at``)."""
    def q_side(width):
        return pl.BlockSpec((1, 1, bq, width), lambda b, h, qi, si: (b, h, qi, 0))

    def at(qi, si):
        return _k_span_at(qi, si, bq, bk, span)

    kv = pl.BlockSpec((1, 1, span * bk, D),
                      lambda b, h, qi, si: (b, h, at(qi, si), 0))
    keep = pl.BlockSpec((1, bq, span * bk),
                        lambda b, h, qi, si: (b, qi, at(qi, si)))
    return q_side, kv, keep


def _say(name: str, call: str, qt, kt, **more) -> dict:
    B, H, S, D = qt.shape
    said = plan(B=B, H=H, S=S, T=kt.shape[2], D=D, dtype=qt.dtype, call=call)
    tracing.plan(name, {**said, **more})
    return said


def _fwd(qt, kt, vt, keep, scale: float):
    """qt, kt, vt [B, H, S|T, D] -> o [B, H, S, D], lse [B, H, S, 128]."""
    B, H, S, D = qt.shape
    said = _say("sparse.fwd_plan", "fwd", qt, kt)
    bq, bk, span = said["block_q"], said["block_k"], said["span"]
    q_side, kv, keep_spec = _q_walk_specs(bq, bk, D, span)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bk=bk,
                          in_flight=said["in_flight"]),
        grid=(B, H, S // bq, kt.shape[2] // (span * bk)),
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
    said = _say("sparse.probs_plan", "probs", qt, kt)
    bq, bk, span = said["block_q"], said["block_k"], said["span"]

    def at(qi, si):
        return _k_span_at(qi, si, bq, bk, span)

    call = pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, heads=H, bk=bk,
                          in_flight=said["in_flight"]),
        grid=(B, S // bq, kt.shape[2] // (span * bk), H),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, qi, si, h: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, span * bk, D),
                         lambda b, qi, si, h: (b, h, at(qi, si), 0)),
            pl.BlockSpec((1, bq, span * bk),
                         lambda b, qi, si, h: (b, qi, at(qi, si))),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b, qi, si, h: (b, h, qi, 0))],
        out_specs=pl.BlockSpec((1, bq, span * bk),
                               lambda b, qi, si, h: (b, qi, at(qi, si))),
        out_shape=jax.ShapeDtypeStruct((B, S, kt.shape[2]), jnp.float32),
        interpret=_use_interpret())
    with jax.named_scope("sparse.probs.mask"):
        return call(qt, kt, keep, lse)


def _bwd(qt, kt, vt, keep, gt, ot, lse, scale: float):
    B, H, S, D = qt.shape
    T = kt.shape[2]
    dq_said = plan(B=B, H=H, S=S, T=T, D=D, dtype=qt.dtype, call="dq")
    said = _say("sparse.bwd_plan", "dkdv", qt, kt, **{
        "dq_" + n: dq_said[n] for n in ("span", "in_flight", "vmem_bytes",
                                        "grid_steps", "live_steps")})
    bq, bk = said["block_q"], said["block_k"]
    q_side, kv, keep_spec = _q_walk_specs(bq, bk, D, dq_said["span"])
    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bk=bk,
                          in_flight=dq_said["in_flight"]),
        grid=(B, H, S // bq, T // (dq_said["span"] * bk)),
        in_specs=[q_side(D), kv, kv, keep_spec, q_side(D), q_side(D),
                  q_side(_LANES)],
        out_specs=q_side(D),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), qt.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=_use_interpret())
    with jax.named_scope("sparse.dq.mask"):
        dq = dq_call(qt, kt, vt, keep, gt, ot, lse)

    span = said["span"]

    def at(ki, si):
        return jax.lax.max(si, jax.lax.div(_first_q(ki, bq, bk), span))

    def q_span(width):
        return pl.BlockSpec((1, 1, span * bq, width),
                            lambda b, h, ki, si: (b, h, at(ki, si), 0))

    k_blk = pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, si: (b, h, ki, 0))
    dkdv_call = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, bq=bq,
                          in_flight=said["in_flight"]),
        grid=(B, H, T // bk, S // (span * bq)),
        in_specs=[q_span(D), k_blk, k_blk,
                  pl.BlockSpec((1, span * bq, bk),
                               lambda b, h, ki, si: (b, at(ki, si), ki)),
                  q_span(D), q_span(D), q_span(_LANES)],
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
