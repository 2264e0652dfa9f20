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

A second entry point, ``block_sparse_attention``, takes a set of BLOCKS a
KV group (InfLLM-V2, arXiv:2509.24663): q ``[B, S, H, D]`` over k, v ``[B,
T, KV, D]`` (H / KV query heads read one K, one V and one set) and ``sel
[B, KV, S, T / 64]`` int8, ``sel[b, g, t, j]`` set where query t of group
g attends to the keys ``64 j .. 64 j + 63`` that are not after it (8 MB a
layer at S 16,384 and two groups, where a byte a pair would be 537 MB). It
is the same walks: a kernel is handed how a tile's membership is read
(``_cols`` and ``_rows``: a slice of the pairs' 0/1 matrix; ``_block_cols``
and ``_block_rows``: the q-block's rows of ``sel``, all T / 64 of them,
widened to the tile's 512 lanes by a product with a 0/1 matrix of two
iotas, on the matrix unit, and the causal test inside the query's own
block), the K, V and set index maps divide the head by the group's size,
and dK/dV walks a group's heads innermost (grid (batch, KV head, k-block,
head of the group, span)) so that a group's sum stays in the float32
scratch and is written once. No tile is skipped for holding no selected
block: with a window of 32 blocks and 31 scored blocks a query, the 512
queries of a q-block leave no 512-key tile empty (the kind's check
counts them); ``block_pairs_walked`` says how many pairs the forward
computes so, for whoever counts what the walk wastes.

Off the chip (interpret mode costs minutes at any real size) and, for a
set of pairs, under 128 keys the same mathematics run in plain
``jax.numpy`` (``_reference``, ``_reference_blocks``), which is also what
the tests hold the kernels to. On the chip a set of blocks whose rows do
not fill whole lane tiles (T / 64 no multiple of 128) is refused
(``_block_path``): the plain path's scores are [B, KV, H / KV, S, T]
float32 there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (_SCOPED_VMEM_BYTES, _UNROLL_MOST,
                                         FLASH_RESIDUALS, NEG_INF,
                                         _span_walk, _use_interpret)
from ray_tpu.util import tracing

_LANES = 128                # lse travels lane-broadcast, as the flash calls'
BLOCK_Q = 512
BLOCK_K = 512
SET_BLOCK = 64              # keys a block of ``block_sparse_attention``'s sets
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
                D: int, e: int, blocks: int = 0) -> int:
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
    52.9 at (2, 2): the count leaves it out). ``blocks`` (a set of blocks
    a group: how many a row of it holds) puts the q-block's rows of the
    set, [bq, blocks] a q-block, in place of a [bq, bk] tile a k-block, and
    adds what widens them, a block in flight: the rows and the 0/1 matrix
    [blocks, bk] on their way to bfloat16 and the product in float32 (the
    forward at a span of 8, two in flight, D 128 and 256 blocks a row
    counts 15.5 MiB so, and Mosaic took it without asking for more)."""
    scores = bq * bk
    lse = bq * _LANES * 4
    of_set = span * scores          # the set's bytes a grid step
    widen = 0
    if blocks:
        of_set = (span if call == "dkdv" else 1) * bq * blocks
        widen = in_flight * ((bq + bk) * blocks * 4 + scores * 4)
    if call == "probs":
        # q and its lse; K, the set and the float32 P block a span; a
        # block's s on its way into P
        return (2 * (bq * D * e + lse + span * (bk * D * e + scores * 4)
                     + of_set) + in_flight * scores * 4)
    if call == "dkdv":
        # q, dO, o, lse and the set's rows a span; k, v, dk, dv; the two
        # sums; a block's s and dp in float32, p and ds in e
        return (2 * (span * (3 * bq * D * e + lse) + of_set + 4 * bk * D * e)
                + 2 * bk * D * 4 + widen
                + in_flight * scores * (4 + 2 * e) + 3 * bk * D * 4)
    # K, V and the set a span; q, the result (o or dq) and the lse
    held = 2 * (span * 2 * bk * D * e + of_set + 2 * bq * D * e + lse) + widen
    if call == "fwd":
        # the accumulator, the running max and sum; a block's s and p in
        # float32 and p in e; the accumulator on its way
        return (held + bq * D * 4 + 2 * lse
                + in_flight * scores * (2 * 4 + e) + 2 * bq * D * 4)
    # dq: dO and o beside q; the sum; a block's s or dp and ds in e
    return (held + 2 * 2 * bq * D * e + bq * D * 4
            + in_flight * scores * (4 + e) + 2 * bq * D * 4)


def _divisors(n: int) -> list:
    return [d for d in range(n, 0, -1) if n % d == 0]


def _choose(call: str, *, nq: int, nk: int, bq: int, bk: int, D: int,
            e: int, blocks: int = 0) -> tuple:
    """(span, in_flight) of ``call``: the blocks a grid step holds and how
    many of them a step of the walk takes, the longest span whose count
    (``_vmem_bytes``) fits the VMEM a Mosaic call gets without asking, two
    in flight before one, as ``flash_attention.kv_plan`` chooses; no
    longer than ``_span_walk`` writes out (at D 128 a span of 16 fits one
    block in flight and walks in a loop: the forward over a set of blocks
    took 49.3 ms a call so and 42.1 at 8 and 2; PERF.md 6, PR 52)."""
    return next(
        ((n, f) for n in _divisors(nq if call == "dkdv" else nk)
         if n <= _UNROLL_MOST
         for f in (2, 1) if f <= n and _vmem_bytes(
             call, span=n, in_flight=f, bq=bq, bk=bk, D=D, e=e,
             blocks=blocks) <= _SCOPED_VMEM_BYTES), (1, 1))


def plan(*, B: int, H: int, S: int, T: int, D: int, dtype, call: str,
         blocks: int = 0, group: int = 1) -> dict:
    """What a call says of itself (instants ``sparse.fwd_plan``,
    ``sparse.probs_plan`` and ``sparse.bwd_plan``): its tiles, what a grid
    step holds (``span`` blocks of 512 keys, of 512 queries in the dK/dV
    call) and how many of them a step of the walk takes (``in_flight``),
    the VMEM counted for that (``_vmem_bytes``), the grid, the grid steps
    that work and the [block_q, block_k] tiles their walks compute (a live
    step walks its span only as far as the diagonal; inside the causal band
    no tile is skipped), and the path: "mask", a membership test a pair, or
    "blocks" for a set of ``blocks`` blocks a row and KV group of
    ``group`` query heads (``block_sparse_attention``)."""
    bq, bk = _blocks(S, T)
    nq, nk = S // bq, T // bk
    e = jnp.dtype(dtype).itemsize
    span, in_flight = _choose(call, nq=nq, nk=nk, bq=bq, bk=bk, D=D, e=e,
                              blocks=blocks)
    if call == "dkdv":
        steps = nq // span
        first = [ki * bk // bq for ki in range(nk)]
        live = sum(steps - f // span for f in first)
        tiles = sum(nq - f for f in first)
        grid = nk * steps
    else:
        steps = nk // span
        last = [min(nk - 1, ((qi + 1) * bq - 1) // bk) for qi in range(nq)]
        live = sum(k // span + 1 for k in last)
        tiles = sum(k + 1 for k in last)
        grid = nq * steps
    of_blocks = {"set_blocks": blocks, "group": group} if blocks else {}
    return {"path": "blocks" if blocks else "mask", "call": call,
            "S": S, "T": T, "D": D,
            "block_q": bq, "block_k": bk, "span": span,
            "in_flight": in_flight,
            "vmem_bytes": _vmem_bytes(call, span=span, in_flight=in_flight,
                                      bq=bq, bk=bk, D=D, e=e, blocks=blocks),
            "grid_steps": B * H * grid, "live_steps": B * H * live,
            "walk_tiles": B * H * tiles, **of_blocks}


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


def _cols(keep_ref, at, where, bk: int):
    """A k-block's membership from a set of pairs: the columns ``at`` of
    the q-block's rows."""
    return _member(keep_ref[0, :, at])


def _rows(keep_ref, at, where, bk: int):
    """A q-block's membership from a set of pairs: the rows ``at`` of the
    k-block's columns."""
    return _member(keep_ref[0, at, :])


def _widened(sel, q0, k0, bk: int, block: int):
    """sel [bq, blocks] int8, a q-block's rows of a set of blocks of
    ``block`` keys (the first row query ``q0``) -> [bq, bk] bool: whether
    a query attends to each of the bk keys from key ``k0`` on. A row's
    blocks reach the keys' lanes by a product with ``W[j, l] = (j == (k0 +
    l) // block)`` (exact: a sum of at most one 1); inside its own block a
    query sees no key after itself."""
    bq, blocks = sel.shape
    j = jax.lax.broadcasted_iota(jnp.int32, (blocks, bk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (blocks, bk), 1)
    widen = j - jax.lax.shift_right_logical(
        lane, block.bit_length() - 1) == jax.lax.div(k0, block)
    held = jax.lax.dot(
        sel.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16),
        widen.astype(jnp.float32).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)
    ahead = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return (held > 0.5) & (ahead >= k0 - q0)


def _block_cols(keep_ref, at, where, bk: int, *, block: int):
    """``_cols`` from a set of blocks: keep_ref [1, 1, bq, blocks]."""
    return _widened(keep_ref[0, 0], *where(at), bk, block)


def _block_rows(keep_ref, at, where, bk: int, *, block: int):
    """``_rows`` from a set of blocks: keep_ref [1, 1, span x bq, blocks]."""
    return _widened(keep_ref[0, 0, at, :], *where(at), bk, block)


def _fwd_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, lse_ref, acc, m_scr,
                l_scr, *, scale: float, bk: int, in_flight: int,
                member=_cols):
    """Grid (b, h, q-block, span): the q-block stays, K, V and the set's
    columns arrive a span of k-blocks a grid step, and the kernel walks
    the span's blocks up to the q-block's diagonal (``_span_walk``): a
    step of the walk issues ``q k^T`` for all its blocks, then updates
    the sums block by block in rising order, the running max and sum read
    and written once a step, the accumulator in place a block (read once
    a step and carried through it measured the same: 35.5 against 35.7 ms
    a call). ``member`` reads a k-block's membership (``_cols``, or
    ``_block_cols`` from a set of blocks)."""
    bq, span = q_ref.shape[2], k_ref.shape[2] // bk
    qi, si = pl.program_id(2), pl.program_id(3)
    lo, hi = _k_span(qi, si, bq, bk, span)
    where = _q_walk_where(qi, si, bq, bk, span)

    @pl.when(si == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def body(offsets):
        at = [pl.ds(a, bk) for a in offsets]
        q = q_ref[0, 0]
        keeps = [member(keep_ref, a, where, bk) for a in at]
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
               acc, *, scale: float, bk: int, in_flight: int, member=_cols):
    """The forward's walk for dQ: a block's ``q k^T`` and ``g v^T`` need
    nothing of the sum, so a step of the walk issues them for all its
    blocks first; delta = rowsum(o * dO) once a step of the walk (kept
    across the walk it cost 0.7 ms a call more than it saved)."""
    bq, span = q_ref.shape[2], k_ref.shape[2] // bk
    qi, si = pl.program_id(2), pl.program_id(3)
    lo, hi = _k_span(qi, si, bq, bk, span)
    where = _q_walk_where(qi, si, bq, bk, span)

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
                   member(keep_ref, a, where, bk)) for a in at]
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
                 in_flight: int, member=_rows, group: int = 1):
    """Grid (b, h, k-block, span): the mirror image, a k-block's sums over
    a span of q-blocks a grid step, from the k-block's diagonal on. With
    a ``group`` of query heads a KV head the grid is (b, KV head, k-block,
    head of the group, span): the sums run over the group's heads too."""
    bk, span = k_ref.shape[2], q_ref.shape[2] // bq
    ki, si = pl.program_id(2), pl.program_id(3 if group == 1 else 4)
    first = si * span
    lo = jax.lax.max(_first_q(ki, bq, bk), first)
    hi = first + span

    def edge(of_span, of_group):
        # the k-block's first (last) grid step: the span's, and with a
        # group of heads the group's too
        if group == 1:
            return si == of_span
        return (si == of_span) & (pl.program_id(3) == of_group)

    def where(at):          # (the rows' first query, the k-block's first key)
        return first * bq + at.start, ki * bk

    @pl.when(edge(0, 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(offsets):
        at = [pl.ds(a, bq) for a in offsets]
        k, v = k_ref[0, 0], v_ref[0, 0]
        rows = [(q_ref[0, 0, a, :], g_ref[0, 0, a, :],
                 member(keep_ref, a, where, bk)) for a in at]
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

    @pl.when(edge(pl.num_programs(3 if group == 1 else 4) - 1, group - 1))
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _q_walk_where(qi, si, bq: int, bk: int, span: int):
    """at -> (the q-block's first query, the first key of the k-block at
    ``at`` of span ``si``): what a set of blocks is widened by."""
    return lambda at: (qi * bq, si * (span * bk) + at.start)


def _q_walk_specs(bq: int, bk: int, D: int, span: int, group: int = 1,
                  blocks: int = 0):
    """Block specs of a walk over (b, h, q-block, span): the q side stays
    a q-block's steps, K, V and the set's columns follow the span of
    ``span`` k-blocks, clamped to the diagonal (``_k_span_at``). With
    ``blocks`` the set is one of blocks a KV group of ``group`` heads: K
    and V are the head's group's, the set's block the q-block's rows of
    the group's set, all ``blocks`` of them."""
    def q_side(width):
        return pl.BlockSpec((1, 1, bq, width), lambda b, h, qi, si: (b, h, qi, 0))

    def at(qi, si):
        return _k_span_at(qi, si, bq, bk, span)

    if not blocks:
        kv = pl.BlockSpec((1, 1, span * bk, D),
                          lambda b, h, qi, si: (b, h, at(qi, si), 0))
        keep = pl.BlockSpec((1, bq, span * bk),
                            lambda b, h, qi, si: (b, qi, at(qi, si)))
        return q_side, kv, keep
    kv = pl.BlockSpec((1, 1, span * bk, D),
                      lambda b, h, qi, si: (b, h // group, at(qi, si), 0))
    keep = pl.BlockSpec((1, 1, bq, blocks),
                        lambda b, h, qi, si: (b, h // group, qi, 0))
    return q_side, kv, keep


def _of_set(qt, kt, keep) -> dict:
    """What a set says of its kind: nothing for a set of pairs [B, S, T];
    for one of blocks [B, KV, S, T / 64] how many blocks a row holds and
    the query heads a KV head."""
    if keep.ndim == 3:
        return {}
    return {"blocks": keep.shape[3], "group": qt.shape[1] // kt.shape[1]}


def _reads(member, qt, kt, keep) -> dict:
    """The kernel's ``member`` for a set of blocks (of T / blocks keys
    each); nothing for a set of pairs: the kernel's default reads it."""
    if keep.ndim == 3:
        return {}
    return {"member": functools.partial(
        member, block=kt.shape[2] // keep.shape[3])}


def _say(name: str, call: str, qt, kt, keep, **more) -> dict:
    B, H, S, D = qt.shape
    said = plan(B=B, H=H, S=S, T=kt.shape[2], D=D, dtype=qt.dtype, call=call,
                **_of_set(qt, kt, keep))
    tracing.plan(name, {**said, **more})
    return said


def _fwd(qt, kt, vt, keep, scale: float):
    """qt [B, H, S, D], kt, vt [B, H | KV, T, D], keep a set of pairs or of
    blocks (``_of_set``) -> o [B, H, S, D], lse [B, H, S, 128]."""
    B, H, S, D = qt.shape
    said = _say("sparse.fwd_plan", "fwd", qt, kt, keep)
    bq, bk, span = said["block_q"], said["block_k"], said["span"]
    of_set = _of_set(qt, kt, keep)
    q_side, kv, keep_spec = _q_walk_specs(bq, bk, D, span, **of_set)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bk=bk,
                          in_flight=said["in_flight"],
                          **_reads(_block_cols, qt, kt, keep)),
        grid=(B, H, S // bq, kt.shape[2] // (span * bk)),
        in_specs=[q_side(D), kv, kv, keep_spec],
        out_specs=[q_side(D), q_side(_LANES)],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), qt.dtype),
                   jax.ShapeDtypeStruct((B, H, S, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32)],
        interpret=_use_interpret())
    with jax.named_scope("sparse.fwd." + said["path"]):
        return call(qt, kt, vt, keep)


def _probs(qt, kt, keep, lse, scale: float):
    B, H, S, D = qt.shape
    said = _say("sparse.probs_plan", "probs", qt, kt, keep)
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
    of_set = _of_set(qt, kt, keep)
    dq_said = plan(B=B, H=H, S=S, T=T, D=D, dtype=qt.dtype, call="dq",
                   **of_set)
    said = _say("sparse.bwd_plan", "dkdv", qt, kt, keep, **{
        "dq_" + n: dq_said[n] for n in ("span", "in_flight", "vmem_bytes",
                                        "grid_steps", "live_steps")})
    bq, bk = said["block_q"], said["block_k"]
    q_side, kv, keep_spec = _q_walk_specs(bq, bk, D, dq_said["span"],
                                          **of_set)
    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bk=bk,
                          in_flight=dq_said["in_flight"],
                          **_reads(_block_cols, qt, kt, keep)),
        grid=(B, H, S // bq, T // (dq_said["span"] * bk)),
        in_specs=[q_side(D), kv, kv, keep_spec, q_side(D), q_side(D),
                  q_side(_LANES)],
        out_specs=q_side(D),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), qt.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=_use_interpret())
    with jax.named_scope("sparse.dq." + said["path"]):
        dq = dq_call(qt, kt, vt, keep, gt, ot, lse)

    span = said["span"]
    if of_set:
        with jax.named_scope("sparse.dkdv." + said["path"]):
            return (dq, *_dkdv_blocks(qt, kt, vt, keep, gt, ot, lse, scale,
                                      said, **of_set))

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


def _dkdv_blocks(qt, kt, vt, sel, gt, ot, lse, scale: float, said: dict, *,
                 blocks: int, group: int):
    """The dK/dV call over a set of blocks: grid (b, KV head, k-block, head
    of the group, span), a k-block's sums over the group's heads and their
    spans of q-blocks in scratch, written once."""
    B, H, S, D = qt.shape
    KV, T = kt.shape[1], kt.shape[2]
    bq, bk, span = said["block_q"], said["block_k"], said["span"]

    def at(ki, si):
        return jax.lax.max(si, jax.lax.div(_first_q(ki, bq, bk), span))

    def q_span(width):
        return pl.BlockSpec(
            (1, 1, span * bq, width),
            lambda b, g, ki, h, si: (b, g * group + h, at(ki, si), 0))

    k_blk = pl.BlockSpec((1, 1, bk, D), lambda b, g, ki, h, si: (b, g, ki, 0))
    call = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, bq=bq,
                          in_flight=said["in_flight"], group=group,
                          **_reads(_block_rows, qt, kt, sel)),
        grid=(B, KV, T // bk, group, S // (span * bq)),
        in_specs=[q_span(D), k_blk, k_blk,
                  pl.BlockSpec((1, 1, span * bq, blocks),
                               lambda b, g, ki, h, si: (b, g, at(ki, si), 0)),
                  q_span(D), q_span(D), q_span(_LANES)],
        out_specs=[k_blk, k_blk],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=_use_interpret())
    return call(qt, kt, vt, sel, gt, ot, lse)


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


def _reference_blocks(q, k, v, sel, scale: float):
    """``block_sparse_attention`` in ``jax.numpy``: the set widened to a
    0/1 matrix a pair and KV group, causal inside a block."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    keep = (jnp.repeat(sel, T // sel.shape[-1], axis=-1) != 0) \
        & (jnp.arange(T)[None, :] <= jnp.arange(S)[:, None])   # [B, KV, S, T]
    qg = q.reshape(B, S, KV, H // KV, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    on = keep[:, :, None]
    s = jnp.where(on, s, NEG_INF)
    p = jnp.where(on, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgst,btkd->bskgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, D).astype(q.dtype)


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


def _block_path(q, k, sel) -> str:
    """Which way ``block_sparse_attention`` takes these arrays: "blocks",
    the kernels (on the chip where a row of the set fills whole lane
    tiles; off it in interpret mode where ``IMPL`` asks), or "xla", plain
    ``jax.numpy`` (off the chip, or where ``IMPL`` asks). On the chip a
    shape the kernels do not take is refused and not handed to the plain
    path, whose scores are [B, KV, H / KV, S, T] float32 (19 GB at 12,288
    tokens and the cell's heads)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    blocks = sel.shape[-1]
    block = T // blocks
    if sel.shape != (B, KV, S, blocks) or T % blocks or block & (block - 1):
        raise ValueError(
            f"block_sparse_attention: a set {sel.shape} for q {q.shape} "
            f"over k {k.shape}: want [B, KV, S, T / block], block a power "
            "of two")
    if IMPL is not None:
        return "blocks" if IMPL == "pallas" else "xla"
    if jax.default_backend() != "tpu":
        return "xla"
    if S < 128 or T < 128 or blocks % _LANES:
        raise ValueError(
            f"block_sparse_attention: {S} queries over {T} keys in "
            f"{blocks} blocks of {block}; on the chip the kernels take a "
            f"set whose rows fill whole lane tiles (T / block a multiple "
            f"of {_LANES}: T a multiple of {block * _LANES} here), from 128 "
            "queries and keys up")
    return "blocks"


def block_pairs_walked(q, k, sel) -> int:
    """The (query, key) pairs ``block_sparse_attention``'s forward computes
    for these arrays, over the batch and every query head, on the path it
    takes: the kernels' walk the tiles of the causal band whole (``plan``'s
    ``walk_tiles``), plain ``jax.numpy`` every pair. What the walk wastes
    is this over the pairs the sets hold."""
    B, S, H, D = q.shape
    T = k.shape[1]
    if _block_path(q, k, sel) == "xla":
        return B * H * S * T
    said = plan(B=B, H=H, S=S, T=T, D=D, dtype=q.dtype, call="fwd",
                blocks=sel.shape[-1], group=H // k.shape[2])
    return said["walk_tiles"] * said["block_q"] * said["block_k"]


def block_sparse_attention(q, k, v, sel, *, scale: float = None):
    """q [B, S, H, D], k and v [B, T, KV, D] (H / KV query heads a KV
    head), sel [B, KV, S, T / block] int8: the blocks of ``block`` keys (a
    power of two; the models' is ``SET_BLOCK``) each query of a KV group
    attends to (a block that starts after the query is never set; inside
    its own block a query sees the keys up to itself) -> o [B, S, H, D] in
    q's dtype. Differentiable in q, k and v. On the chip the kernels take
    it where a row of the set fills whole lane tiles (T / block a multiple
    of 128) and any other shape raises (``_block_path``)."""
    scale = q.shape[-1] ** -0.5 if scale is None else float(scale)
    if _block_path(q, k, sel) == "xla":
        with jax.named_scope("sparse.xla"):
            return _reference_blocks(q, k, v, sel, scale)
    return _sparse(q, k, v, sel, scale)[0]
