"""Fused causal attention (flash-style) for TPU in Pallas.

Forward: one kernel (`_fwd_kernel`), grid (batch, head, q-block, span): the
q-block stays in VMEM while K/V arrive a SPAN of k-blocks a grid step and
the kernel walks the span's blocks of the causal/window band itself with
the online-softmax recurrence -- O(S) memory instead of O(S^2), and the
QK^T / PV matmuls hit the MXU at [block_q x head_dim] x [head_dim x
block_k] granularity. What a grid step holds is chosen by the shape alone
(`kv_plan`; the instant `flash.fwd_plan`):
  loop    one span: a head's whole K and V are blocks of the call, fetched
          once a head, where those blocks, double-buffered, and a step's
          temporaries fit the 16 MiB a Mosaic call gets that asks for no
          more (S 8192 at D 128). The sums are the loop's carry, one block
          an iteration.
  stream  several spans, through an index map clamped into the band (a
          span outside it fetches nothing): the longest span that fits (S
          8192 at D 256, where the loop's blocks alone are 16 MiB: 4,096
          keys, two grid steps a q-block where one block a step made
          sixteen; a grid step that works costs about a microsecond
          beside its blocks), the sums in VMEM scratch, and TWO k-blocks a
          step of the walk where their temporaries fit: both blocks'
          `q k^T` are issued before the first block's softmax, two chains
          in one straight-line body. A span that lies WHOLE inside the
          band is written out where that fits (`_span_walk`): static
          offsets, the sums read from scratch once at its top and written
          once at its end; the span that holds the diagonal walks in a
          loop, the sums through scratch once a step of it. Written out,
          every block's temporaries are held at once: at D 256 the
          forward's span of 8 blocks asks Mosaic for 19 MiB of the 16 and
          stays in the loop; at D 128 a span of 16 is too long to write
          out and the forward cuts it to 8, which are (S 16384: 31.9 ->
          30.6 ms a call; PERF.md 6, PR 49).
  band    a window that ends every q-block's band before T does: the
          longest band is the span (S 16384, window 1024, blocks of 512:
          3 k-blocks of T's 32), fetched at the k-block the band starts
          at, so the grid is as long as the band: ONE step a q-block and
          none that finds nothing to do (a grid of T's 16 spans with the
          index clamped into the band ran 14 empty steps of 16, 0.10 us
          each in the forward, 0.26 in the dQ call with its six
          operands), the sums in the loop's carry as on the loop plan,
          the band's blocks all in flight. A band too long to hold
          streams.
Each block makes its own online-softmax update in rising order on every
plan, so all of them agree to the last bit (on the chip: with the kernels
of one block a grid step they replaced, PERF.md 6, PR 37).

Backward: full Pallas two-kernel backward (FlashAttention-2 style), both
recomputing probabilities from the saved log-sum-exp so nothing O(S^2) is
ever materialized. The dQ pass (`_dq_kernel`) is the forward's walk: a
q-block resident, K and V a span a grid step by the forward's plan
(`loop`, `band`: dq is the loop's carry; `stream`: dq sums in a float32
VMEM scratch across a q-block's spans, whole spans written out as the
forward's are: at D 256 its span of 8, one block in flight, fits and took
15.3 -> 13.8 ms a call; at D 128 the span of 16 stays in the loop, the cut
to 8 lost 6%), two k-blocks a step of the walk where their temporaries fit
beside the span (their `q k^T` and `g v^T` first); every plan sums in the
same order and writes dq ONCE, in q's dtype. The dK/dV pass is its mirror
image and has three block plans, told apart by the shape alone
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
  stream    grid (b, h, k-block, SPAN): q, dO, o and lse arrive a span of
            q-blocks a grid step, the longest that fits the 16 MiB a call
            gets (4 at S 8192 x D 256, 8 at S 16384 x D 128), and the
            kernel walks the span's q-blocks of the band itself, a whole
            span written out, the span on the diagonal in a loop
            (`_span_walk`): O(span) VMEM at any S. dk and dv sum in two
            float32 VMEM scratch arrays and are written once, at the last
            span, in the result's dtype. The query-side index maps are
            clamped into the band as the forward's `kv_idx` clamps k and
            v, so a span the mask skips fetches nothing. One q-block a
            grid step, as this grid was until PR 49, ran 256 grid steps a
            head of which 136 worked at S 8192 (64 of which 40 now) and
            1,024 of which 528 at S 16384 (128 of which 80): 18.8 -> 18.1
            and 35.3 -> 29.0 ms a call (PERF.md 6, PR 49).
  band      a window that ends every k-block's band before S does: one
            q-block a grid step, summed in the float32 output block, the
            q axis as long as the longest band (S 16384, window 1024: 3
            q-blocks of S's 32) and counted from the band's first
            q-block, so a k-block's 29 steps outside its band are gone
            (15.03 -> 7.04 ms a call; PERF.md 6, PR 39).
A result's dtype: what the call hands on. o and dq in q's; dk and dv in
k's where every query head has a KV head of its own, float32 where a
group's heads are summed afterwards (`_flash_pallas_bwd`) and on the band
plan, whose output block is its sum.
All run the same accumulate step (`_dkdv_step`) in the same order, so
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
(models/remat.py::_checkpoint) runs the backward kernels from them; one
that does not runs the forward kernel a second time, a launch over S^2,
only to rebuild them. q, k and v carry no name: they are rebuilt from the
layer input by their projections.

At a head of 64 (D 64, half a lane tile; the LFM2 cell, 32 query heads over
8 KV heads, S 16384): a [rows, 64] block is a whole tile of 128 lanes in
VMEM, so the plans count VMEM at `_vmem_lanes(D)` and HBM at D itself, and
all three calls take the stream plans of the same S at D 128 (forward spans
of 8 blocks written out, dQ 16 in the loop, dK/dV 8 q-blocks a grid step);
counted at 64 the dK/dV call took the resident plan and Mosaic refused its
105 MiB of 103.5. QK^T contracts over half the MXU's depth and PV writes
half its width, and a score costs the VPU what it costs at 128: a call
takes what it takes at D 128 (forward 30.6 ms, forward with backward 78.5;
the Mellum2 full layers' 30.6 and 81 at 32 over 4 heads of 128), 24.9% of
the roofline reckoned at 64. Padding q, k and v to 128 lanes in the wrapper
gave the same bits and 30.1 | 79.4 ms: no gain, twice the HBM rows; the
heads go to the kernels as they are (`benchmark/tools/lfm2_flash_forms.py`,
my chip run, PR 54; PERF.md 6).

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


def _vmem_lanes(head_dim: int) -> int:
    """The lanes a [rows, head_dim] block takes in VMEM: whole tiles of
    128. At a head of 64, half a tile, every block and temporary of the
    kernels is as large there as at 128 (the plans count VMEM at this
    width, HBM at the head's own: the resident dK/dV plan counted at 64
    asked Mosaic for 105 MiB of the 103.5 it may, S 16384). Under 64 no
    model runs on the chip: the CPU tests' toy heads of 16 and 32, in
    interpret mode, are counted as they are."""
    if head_dim < 64:
        return head_dim
    return -(-head_dim // _LSE_LANES) * _LSE_LANES


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _scale(scale, head_dim: int) -> float:
    """What multiplies the scores: the model's own, else head_dim ** -0.5."""
    return head_dim ** -0.5 if scale is None else float(scale)


def _abt(a, b):
    """a b^T, float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mask(s, q_pos, ki, *, block_k: int, window: int):
    """Scores [block_q, block_k] of k-block `ki` with NEG_INF where a row
    (positions q_pos [block_q, 1]) does not see the key."""
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    keep = q_pos >= k_pos
    if window > 0:
        keep = keep & (q_pos - k_pos < window)
    return jnp.where(keep, s, NEG_INF)


def _walk(lo, hi, carry, step, in_flight: int):
    """`step(carry, ki, n)` over the k-blocks [lo, hi) in rising order:
    `n = in_flight` blocks a call while that many are left, then one a
    call. `step` issues the products that need nothing of the sums for
    all its n blocks before the first block's vector work (independent
    chains in one straight-line body, for the scheduler to interleave),
    then updates the sums block by block in order: they are those of a
    walk one block at a time, to the last bit."""
    n = in_flight
    groups = jax.lax.div(hi - lo, n)
    carry = jax.lax.fori_loop(
        0, groups, lambda i, c: step(c, lo + n * i, n), carry)
    if n > 1:
        carry = jax.lax.fori_loop(
            lo + n * groups, hi, lambda ki, c: step(c, ki, 1), carry)
    return carry


def _at(i, first, size: int, span: int):
    """Where block ``i`` starts in a span of ``span`` blocks of ``size``
    that starts at block ``first``."""
    return 0 if span == 1 else pl.multiple_of((i - first) * size, size)


def _block_of(at, first, size: int):
    """The block that starts at row ``at`` (`_at`) of a span of blocks of
    ``size`` that starts at block ``first``."""
    return first + (at // size if isinstance(at, int)
                    else jax.lax.div(at, size))


# the most blocks of a span a kernel's body is written out for
_UNROLL_MOST = 8


def _span_walk(lo, hi, first, size: int, span: int, in_flight: int, body,
               whole=None, written: bool = None):
    """``body(offsets)`` over the blocks [lo, hi) of a span of ``span``
    blocks of ``size`` that starts at block ``first``, ``in_flight`` a
    call in rising order. A span that lies whole inside the causal band
    (all but the one that holds the diagonal) is written out, its
    offsets constants of the program: straight-line code the scheduler
    runs a block's products under its neighbour's vector work (the
    sparse forward at spans of 4, 2 in flight: 39.05 ms a call through
    the loop alone, 35.5 written out; PERF.md 6, PR 46), and given to
    ``whole(groups)`` all at once where the caller has one (the dense
    stream calls read their sums from scratch once at its top and write
    them once at its end); the span on the diagonal walks in a loop
    (``_walk``), and so does every span where ``written`` says no: by
    default any longer than ``_UNROLL_MOST`` blocks (straight-line code
    holds every block's temporaries at once: the dense forward's span of
    8 at a head of 256 asked for 19 MiB written out, 15 through the
    loop)."""
    def step(carry, i, n):
        body([_at(i + j, first, size, span) for j in range(n)])
        return carry

    if written is None:
        written = span <= _UNROLL_MOST
    if span == 1 or not written:
        _walk(lo, hi, 0, step, in_flight)
        return
    inside = (lo == first) & (hi == first + span)

    @pl.when(inside)
    def _whole():
        groups = [[(j + i) * size for i in range(min(in_flight, span - j))]
                  for j in range(0, span, in_flight)]
        if whole is not None:
            whole(groups)
            return
        for offsets in groups:
            body(offsets)

    @pl.when(jnp.logical_not(inside))
    def _part():
        _walk(lo, hi, 0, step, in_flight)


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


def _span_steps(*, num_q: int, span: int, num_k: int, block_q: int,
                block_k: int, causal: bool, window: int,
                written: bool = None) -> Tuple[int, int, int]:
    """(steps, band_steps, whole_steps) of a head's walk at `span`
    k-blocks a grid step: the length of the span axis, how many of the
    head's num_q x steps grid steps hold a block of a band, and how many
    of those hold a span that lies whole inside its band and is written
    out (`_span_walk`). A span that is shorter than T and holds the
    longest band of any q-block is a banded call's: ONE step a q-block,
    the span fetched where the band starts (`_span_band`). Every other
    span is one of T's num_k / span, and the axis counts all of them; one
    axis step alone (`loop`) keeps the sums in the loop's carry and writes
    nothing out."""
    mask = dict(num_k=num_k, block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    bands = [_k_band(qi, **mask) for qi in range(num_q)]
    if max(hi - lo for lo, hi in bands) <= span < num_k:
        return 1, num_q, 0
    whole = 0
    if written is None:
        written = span <= _UNROLL_MOST
    if written and 1 < span < num_k:
        whole = sum(min(hi, (si + 1) * span) - max(lo, si * span) == span
                    for lo, hi in bands for si in range(num_k // span))
    return (num_k // span,
            sum((hi - 1) // span - lo // span + 1 for lo, hi in bands), whole)


def _band_start(qi, *, span: int, num_k: int, block_q: int, block_k: int,
                causal: bool, window: int):
    """The first k-block of a banded call's one span of q-block `qi`
    (`_span_steps`): the band's first, or as late as T lets a span
    start."""
    lo, _ = _k_band(qi, num_k=num_k, block_q=block_q, block_k=block_k,
                    causal=causal, window=window)
    _, least, _ = _index_ops(qi)
    return least(lo, num_k - span)


def _span_band(qi, si, *, span: int, steps: int, num_k: int, block_q: int,
               block_k: int, causal: bool, window: int):
    """(lo, hi, first), traced: the k-blocks [lo, hi) of q-block `qi`'s
    band that grid step `si` holds, at `span` blocks a step, and the
    span's first k-block (a function, for the kernel to call where it
    slices a block): all of the band in a banded call's one step
    (`_span_steps`), else what the si-th of T's spans holds of it."""
    mask = dict(num_k=num_k, block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    lo, hi = _k_band(qi, **mask)
    if steps == 1 and span < num_k:
        start = _band_start(qi, span=span, **mask)
        return lo, hi, lambda: start
    return (jax.lax.max(jnp.int32(lo), si * span),
            jax.lax.min(jnp.int32(hi), (si + 1) * span), lambda: si * span)


def _softmax_block(o, m, l, s, v):
    """One block's update of the online-softmax sums: the accumulator o,
    the running max m and the running sum l, from its (masked) scores s
    and its values v."""
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    # p joins v's dtype for the second MXU pass (f32 accumulation);
    # standard flash practice, same as the official TPU kernel
    o = o * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return o, m_new, l


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, block_k: int,
                num_k: int, steps: int, scale: float, causal: bool,
                window: int, in_flight: int, written: bool = None):
    """Grid (b, h, q-block, span): the q-block stays, K and V arrive a
    span of k-blocks a grid step through an index map clamped into the
    band (`_k_span_index`: a span outside it repeats its neighbour's index
    and Mosaic, which elides a fetch whose index repeats, fetches nothing:
    O(S*W) HBM traffic for sliding windows instead of O(S*T)), and the
    kernel walks the span's blocks of the band itself.
    One grid step a q-block (`steps` 1: the `loop` plan's span is a head's
    whole K and V, the `band` plan's the q-block's whole band, fetched
    where it starts): the accumulator, the running max and the running
    sum are the carry of the loop (`_walk`). Several (`stream`): the three
    live in VMEM scratch across the grid steps of a q-block (same
    structure as the official TPU flash kernel); a span that lies whole
    inside the band is written out, the sums read once at its top and
    written once at its end, the span on the diagonal walks in a loop
    that reads and writes them once a step (`_span_walk`); the last grid
    step normalizes and writes o and lse."""
    block_q, D = q_ref.shape[2], q_ref.shape[3]
    span = k_ref.shape[2] // block_k
    qi, si = pl.program_id(2), pl.program_id(3)
    lo, hi, first = _span_band(
        qi, si, span=span, steps=steps, num_k=num_k, block_q=block_q,
        block_k=block_k, causal=causal, window=window)

    def keep(o, m, l):
        acc, m_scr, l_scr = scratch
        acc[...] = o
        m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    def kept():
        acc, m_scr, l_scr = scratch
        return acc[...], m_scr[...][:, 0:1], l_scr[...][:, 0:1]

    def walk(carry):
        # operands stay in the input dtype (bf16 on TPU: 8x the f32 MXU
        # rate); the MXU accumulates in f32 via preferred_element_type --
        # an f32 cast here made the whole kernel f32-matmul-bound
        q = q_ref[0, 0]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)

        def scores(k):
            return _abt(q, k) * scale

        def masked(s, ki):
            return _mask(s, q_pos, ki, block_k=block_k, window=window)

        def block(ref, ki):
            rows = pl.multiple_of((ki - first()) * block_k, block_k)
            return ref[0, 0, pl.ds(rows, block_k), :]

        def step(carry, ki, n):
            kv = [(block(k_ref, ki + j), block(v_ref, ki + j))
                  for j in range(n)]
            ss = [scores(k) for k, _ in kv]
            o, m, l = carry
            for j, ((_, v), s) in enumerate(zip(kv, ss)):
                o, m, l = _softmax_block(
                    o, m, l, masked(s, ki + j) if causal else s, v)
            return o, m, l

        if steps == 1:
            return _walk(lo, hi, carry, step, in_flight)

        start = first()

        def at(sums, offsets):
            """The blocks at rows `offsets` of the span; `sums` the three
            or, read after the products are issued, where to get them."""
            kv = [(k_ref[0, 0, pl.ds(a, block_k), :],
                   v_ref[0, 0, pl.ds(a, block_k), :]) for a in offsets]
            ss = [scores(k) for k, _ in kv]
            o, m, l = sums() if callable(sums) else sums
            for a, (_, v), s in zip(offsets, kv, ss):
                o, m, l = _softmax_block(o, m, l, masked(s, _block_of(
                    a, start, block_k)) if causal else s, v)
            return o, m, l

        _span_walk(lo, hi, start, block_k, span, in_flight,
                   lambda offsets: keep(*at(kept, offsets)),
                   lambda groups: keep(*functools.reduce(at, groups, kept)),
                   written)

    def finish(o, m, l):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (o / l).astype(o_ref.dtype)
        # Lane-broadcast (Mosaic wants last-dim 128 blocks; official TPU
        # flash kernel stores l/m the same way).
        lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(l),
                                         (block_q, _LSE_LANES))

    zero = (jnp.zeros((block_q, D), jnp.float32),
            jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32))
    if steps == 1:
        finish(*walk(zero))
        return

    @pl.when(si == 0)
    def _init():
        keep(*zero)

    @pl.when(lo < hi)
    def _span():
        walk(0)

    @pl.when(si == steps - 1)
    def _finish():
        finish(*kept())


# VMEM a Mosaic call gets on a v5e core when it asks for no limit of its own
_SCOPED_VMEM_BYTES = 16 * 2 ** 20


def kv_plan(*, S: int, T: int, D: int, dtype, block_q: int, block_k: int,
            window: int = 0, call: str = "fwd", causal: bool = True) -> dict:
    """How a call that walks k-blocks for a resident q-block (`call`: the
    forward `fwd`, the dQ pass `dq`) holds K and V, and the bytes that
    decide it (the attributes of `flash.fwd_plan`; the dQ call's are in
    `flash.bwd_plan`).

    path  loop: K and V of a whole head are blocks of the call, fetched
          once a head; taken where those blocks, double-buffered by
          Mosaic (`kv_block_bytes`), with the q-side blocks and the f32
          temporaries of one block's step (`loop_bytes`) fit the VMEM a
          call gets without asking (the compiler refused S 8192 x D 256
          at 17.5 MiB of 16); stream otherwise, and for a windowed
          forward: spans of T through an index map clamped into the
          band; band: where such a call's window ends every q-block's
          band before T does and the longest band fits as ONE span (S
          16384, window 1024, blocks of 512: 3 k-blocks), the span axis
          is one step and the span is fetched where the band starts, at
          any k-block (an offset by elements): no grid step finds
          nothing to do, and the sums never leave the loop's carry (the
          banded forward 9.76 -> 5.77 ms a call, dQ 9.57 -> 4.48: PERF.md
          6, PR 39). Asking for more VMEM is not the way: a call's limit
          is taken out of XLA's own fast memory for as long as the call
          is scheduled (`bwd_dkdv_plan`).
    span  keys of K and V a grid step holds: T on the loop plan, the
          longest band on the band plan; on the stream plan the most
          k-blocks (a divisor of their number, no more than cover a
          window) whose step fits (`walk_bytes`). A grid step that works
          costs about a microsecond beside its blocks (S 8192, D 256 on a
          v5e: 256 steps a head of one block 14.56 ms a forward call, 32
          of eight 11.33; PERF.md 6, PR 37), so the longest span comes
          first.
    in_flight  k-blocks a step of the walk takes (`_walk`): 2 where the
          span leaves a second block's temporaries room, else 1; 1 in the
          loop plan's forward, whose carry of three arrays makes the
          second loop (the odd block's) cost more than the pairs win
          (S 4096, D 128: 5.62 ms a call against 5.57); on the band plan
          the whole band where its temporaries fit, one straight-line
          body a q-block (3 at the shape above: forward 5.77 ms against
          5.90 at 1 and 6.15 at 2, dQ 4.48 against 4.76 and 4.68).
    written  on the stream plan: a span that lies whole inside the band
          runs as straight-line code (`_span_walk`), where the span is no
          longer than `_UNROLL_MOST` blocks and the count of that form
          fits, which holds every block's temporaries at once (the dQ
          call at S 8192 x D 256, 8 blocks, one in flight: 15.3 -> 13.8 ms
          a call, the same span through the loop 15.3; the forward there
          would need 19 MiB and keeps the loop, 12.5 ms, where spans of 4
          written out took 12.6). A forward whose longest span would be
          over `_UNROLL_MOST` blocks takes `_UNROLL_MOST` instead where
          they fit written out (S 16384 x D 128: 16 in the loop 31.9 ms a
          call, 8 written out 30.6, 4: 31.7); the dQ call, whose grid
          step costs more (six operands, delta once a step), keeps the
          longest (21.3 against 22.5 and 23.6). PERF.md 6, PR 49.
    steps, band_steps, whole_steps  of one head: the span axis's length,
          how many of the head's S / block_q x steps grid steps hold a
          block of a band, and how many of those run a span written out
          (`_span_steps`).

    `walk_bytes` is above what Mosaic planned at every shape compiled for
    a v5e (the least `vmem_limit_bytes` it accepted, to a quarter MiB, PR
    37: D 128 and 256, spans of 1 to 16 blocks, by 0.2 to 1.9 MiB; PR 39,
    the band plan: bands of 3, 5 and 9 blocks, 1 to 5 in flight, the dQ
    call by 0.25 to 4 MiB, the forward by 2 and more; PR 49, written out,
    the count | the least, MiB, at the cells' own head counts, which the
    least follows: 20 heads of 256, forward 8 blocks and 2 in flight
    21.5 | 19.25, 8 and 1: 20.5 | 21.5 (under, refused either way), 4 and 2:
    13.5 | 12.5; dQ 8 and 1: 16.0 | 16.0 (taken, to the byte), 8 and 2:
    19.0 | 18.75, 4 and 2: 15.0 | 14.75; 32 over 4 heads of 128, forward
    8 and 2: 14.25 | 11.25, dQ 8 and 2: 11.75 | 10.5, 16 and 2 in the
    loop: 15.5 | 14.25)."""
    itemsize = jnp.dtype(dtype).itemsize
    head_dim, D = D, _vmem_lanes(D)         # every count below is of VMEM
    kv_block_bytes = 2 * 2 * T * D * itemsize
    # q, dO, o, the result and the 128-lane lse, double-buffered
    q_side_bytes = 2 * block_q * (4 * D * itemsize + _LSE_LANES * 4)
    loop_bytes = (
        kv_block_bytes + q_side_bytes
        # two [block_q, block_k] of s and p; the accumulator and a k-block
        + 4 * (2 * block_q * block_k + (block_q + block_k) * D))
    path = "stream" if loop_bytes > _SCOPED_VMEM_BYTES or (
        window > 0 and call == "fwd") else "loop"

    def walk_bytes(blocks: int, in_flight: int, written: bool = False) -> int:
        scores = block_q * block_k
        # a span written out holds every block's temporaries at once: the
        # forward a block's accumulator and its p, the dQ call the sum as
        # it was read beside the sum as it is carried
        more = 0
        if written:
            more = (blocks - in_flight) * (block_q * D * 4 + scores * 2) \
                if call == "fwd" else block_q * D * 4
        if call == "fwd":
            # q, o and the 128-lane lse, double-buffered
            q_side = 2 * block_q * (2 * D * itemsize + _LSE_LANES * 4)
            # a block in flight: s in f32 and p in the operands' dtype,
            # and the accumulator as it leaves it; the accumulator as it
            # was, twice; the accumulator, running max and sum kept
            # (scratch or carry)
            step = (in_flight * scores * 6 + (2 + in_flight) * block_q * D * 4
                    + 4 * block_q * (D + 2 * _LSE_LANES))
        else:
            # q, dO, o, the lse and the result; the float32 sum that a
            # q-block's spans add up in
            q_side = 2 * block_q * (4 * D * itemsize + _LSE_LANES * 4) + (
                block_q * D * 4 if path == "stream" else 0)
            # a block in flight: s and dp, and k and v cast to f32; q and
            # dO cast to f32, and the sum; three more of its size on the
            # band plan, where Mosaic planned 0.75 to 1.25 MiB over the
            # rest at D 256 (at 3 in flight 17.0 MiB of 16)
            step = (in_flight * (scores * 8 + 2 * block_k * D * 4)
                    + (6 if path == "band" else 3) * block_q * D * 4)
        return 2 * 2 * blocks * block_k * D * itemsize + q_side + step + more

    num_q, num_k = S // block_q, T // block_k
    mask = dict(num_k=num_k, block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    band = max(hi - lo for lo, hi in (
        _k_band(qi, **mask) for qi in range(num_q)))
    if path == "stream" and band < num_k:
        path = "band"                      # `walk_bytes` reads it
        if walk_bytes(band, 1) > _SCOPED_VMEM_BYTES:
            path = "stream"
    most = num_k if window == 0 else min(num_k, -(-window // block_k))
    spans = {"loop": [num_k], "band": [band]}.get(path) or [
        n for n in range(most, 0, -1) if num_k % n == 0]
    pairs = (1,) if call == "fwd" and path != "stream" else (2, 1)
    if path == "band":
        pairs = (band,) + pairs

    def fits(n, f, written):
        return f <= n and walk_bytes(n, f, written) <= _SCOPED_VMEM_BYTES

    short = [n for n in spans if 1 < n <= _UNROLL_MOST]
    longest = next((n for n in spans if fits(n, 1, False)), 0)
    if path == "stream" and call == "fwd" and short and (
            num_k > longest > _UNROLL_MOST) and fits(short[0], 2, True):
        spans = short      # the forward's span, cut to one it can write out
    blocks, in_flight, written = next(
        ((n, f, w) for n in spans for f in pairs
         for w in ((True, False) if path == "stream" and n in short
                   else (False,)) if fits(n, f, w)), (spans[-1], 1, False))
    steps, band_steps, whole_steps = _span_steps(
        num_q=num_q, span=blocks, written=written, **mask)
    return dict(path=path, S=S, D=head_dim, kv_block_bytes=kv_block_bytes,
                loop_bytes=loop_bytes, span=blocks * block_k,
                in_flight=in_flight, written=written,
                walk_bytes=walk_bytes(blocks, in_flight, written),
                steps=steps, band_steps=band_steps, whole_steps=whole_steps)


def _grid_steps(plan: dict, heads: int, blocks: int, prefix: str = "") -> dict:
    """What a plan instant says of its call's grid of `heads` x `blocks`
    x the plan's `steps`: `grid_steps`, all of it, `band_steps`, the
    steps that hold a block of a band (their quotient is the share of grid
    steps that work), and, of a call that walks k-blocks, `whole_steps`,
    the steps whose span is written out (over `band_steps`: the share of
    the working steps that run straight-line code)."""
    said = {prefix + "grid_steps": heads * blocks * plan["steps"],
            prefix + "band_steps": heads * plan["band_steps"]}
    if "whole_steps" in plan:
        said[prefix + "whole_steps"] = heads * plan["whole_steps"]
    return said


def _k_span_index(qi, si, *, span: int, num_k: int, block_q: int,
                  block_k: int, causal: bool, window: int):
    """The span of K and V at grid step (qi, si) of a call that holds a
    q-block and steps over all of T's spans of `span` k-blocks: si clamped
    into the spans that hold a block of q-block qi's band, so a span
    outside it repeats the index of the band's near edge and Mosaic, which
    elides a fetch whose index repeats, fetches nothing."""
    if not causal or span == num_k:
        return si
    lo, hi = _k_band(qi, num_k=num_k, block_q=block_q, block_k=block_k,
                     causal=causal, window=window)
    div, least, most = _index_ops(qi)
    return most(least(si, div(hi - 1, span)), div(lo, span))


def _walk_specs(*, groups: int, span: int, steps: int, num_k: int,
                block_q: int, block_k: int, D: int, causal: bool, window: int):
    """Block specs of a call on grid (b, h, q-block, span): `q_side(width)`
    for an operand or result that follows the q-block, and the spec of K
    and V, `span` k-blocks a grid step: by `_k_span_index` among T's
    spans, or, a banded call's one span (`_span_steps`), by the element
    its band starts at (a band starts at any k-block, not at a multiple of
    the span)."""
    mask = dict(span=span, num_k=num_k, block_q=block_q, block_k=block_k,
                causal=causal, window=window)

    def q_side(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, qi, si: (b, h, qi, 0))

    def kv_idx(b, h, qi, si):
        return (b, h // groups, _k_span_index(qi, si, **mask), 0)

    def kv_at(b, h, qi, si):
        return (b, h // groups, _band_start(qi, **mask) * block_k, 0)

    if steps == 1 and span < num_k:
        # Mosaic takes offsets by elements in all of a block's axes or none
        return q_side, pl.BlockSpec(tuple(
            pl.Element(n) for n in (1, 1, span * block_k, D)), kv_at)
    return q_side, pl.BlockSpec((1, 1, span * block_k, D), kv_idx)


def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_k: int,
               scale: float, window: int = 0, plan: dict = None):
    """The forward call by `kv_plan`'s plan (or the one given): o
    [B, S, H, D] in q's dtype and lse [B, H, S, 128] f32."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    if plan is None:
        plan = kv_plan(S=S, T=T, D=D, dtype=k.dtype, block_q=block_q,
                       block_k=block_k, window=window, causal=causal)
        tracing.plan("flash.fwd_plan", {
            **{n: plan[n] for n in ("path", "S", "D", "kv_block_bytes",
                                    "span", "in_flight")},
            **_grid_steps(plan, B * H, S // block_q)})
    # layout: [B, H, S, D] per-instance slices
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    num_k = T // block_k
    span = plan["span"] // block_k
    mask = dict(num_k=num_k, block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    steps, _, _ = _span_steps(num_q=S // block_q, span=span, **mask)
    q_side, kv_blk = _walk_specs(groups=groups, span=span, steps=steps, D=D,
                                 **mask)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, num_k=num_k,
                          steps=steps, scale=scale, causal=causal,
                          window=window, in_flight=plan["in_flight"],
                          written=plan.get("written")),
        grid=(B, H, S // block_q, steps),
        in_specs=[q_side(D), kv_blk, kv_blk],
        out_specs=[q_side(D), q_side(_LSE_LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[] if steps == 1 else [
            pltpu.VMEM((block_q, D), jnp.float32),            # acc
            pltpu.VMEM((block_q, _LSE_LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LSE_LANES), jnp.float32),   # running sum
        ],
        interpret=_use_interpret(),
    )
    with jax.named_scope(f"flash.fwd.{plan['path']}"):  # flash.fwd_plan's path
        out, lse = call(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dq_ref, *scratch,
               block_k: int, num_k: int, steps: int, scale: float,
               causal: bool, window: int, in_flight: int,
               written: bool = None):
    """Grid (b, h, q-block, span), the forward's walk (`_fwd_kernel`) for
    dQ (FlashAttention-2 backward, dQ pass): K and V arrive a span of
    k-blocks a grid step, and dQ accumulates over the span's blocks of the
    band, `in_flight` at a time (a block's `q k^T` and `g v^T` need
    nothing of the sum). delta = rowsum(o * dO) is computed in-kernel,
    once a grid step. One grid step a q-block (`loop`, `band`): the sum
    is the loop's carry. Several (`stream`): the sum lives in a float32
    VMEM scratch across a q-block's spans, read once and written once a
    span that is written out, once a step of the diagonal span's loop
    (`_span_walk`), in the loop plan's order. Either way dQ is written
    once, in q's dtype."""
    block_q, D = q_ref.shape[2], q_ref.shape[3]
    span = k_ref.shape[2] // block_k
    qi, si = pl.program_id(2), pl.program_id(3)
    lo, hi, first = _span_band(
        qi, si, span=span, steps=steps, num_k=num_k, block_q=block_q,
        block_k=block_k, causal=causal, window=window)

    def walk(carry):
        q = q_ref[0, 0].astype(jnp.float32)
        g = g_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, 0:1]
        delta = jnp.sum(o_ref[0, 0].astype(jnp.float32) * g, axis=-1,
                        keepdims=True)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)

        def products(k, v):
            return _abt(q, k) * scale, _abt(g, v)

        def masked(s, ki):
            return _mask(s, q_pos, ki, block_k=block_k, window=window)

        def add(dq, k, s, dp):
            p = jnp.exp(s - lse)
            ds = p * (dp - delta) * scale
            return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

        def block(ref, ki):
            rows = pl.multiple_of((ki - first()) * block_k, block_k)
            return ref[0, 0, pl.ds(rows, block_k), :].astype(jnp.float32)

        def step(dq, ki, n):
            kv = [(block(k_ref, ki + j), block(v_ref, ki + j))
                  for j in range(n)]
            made = [products(k, v) for k, v in kv]
            for j, ((k, _), (s, dp)) in enumerate(zip(kv, made)):
                dq = add(dq, k, masked(s, ki + j) if causal else s, dp)
            return dq

        if steps == 1:
            return _walk(lo, hi, carry, step, in_flight)

        acc, = scratch
        start = first()

        def at(dq, offsets):
            """The blocks at rows `offsets` of the span; `dq` the sum or,
            read after the products are issued, where to get it."""
            kv = [(k_ref[0, 0, pl.ds(a, block_k), :].astype(jnp.float32),
                   v_ref[0, 0, pl.ds(a, block_k), :].astype(jnp.float32))
                  for a in offsets]
            made = [products(k, v) for k, v in kv]
            dq = dq() if callable(dq) else dq
            for a, (k, _), (s, dp) in zip(offsets, kv, made):
                dq = add(dq, k, masked(s, _block_of(
                    a, start, block_k)) if causal else s, dp)
            return dq

        def kept():
            return acc[...]

        def keep(dq):
            acc[...] = dq

        _span_walk(lo, hi, start, block_k, span, in_flight,
                   lambda offsets: keep(at(kept, offsets)),
                   lambda groups: keep(functools.reduce(at, groups, kept)),
                   written)

    if steps == 1:
        dq_ref[0, 0] = walk(jnp.zeros((block_q, D), jnp.float32)).astype(
            dq_ref.dtype)
        return

    @pl.when(si == 0)
    def _zero():
        scratch[0][...] = jnp.zeros((block_q, D), jnp.float32)

    @pl.when(lo < hi)
    def _span():
        walk(0)

    @pl.when(si == steps - 1)
    def _finish():
        dq_ref[0, 0] = scratch[0][...].astype(dq_ref.dtype)


def _dkdv_step(q, k, v, g, o, lse, qi, ki, *, block_q: int, block_k: int,
               scale: float, causal: bool, window: int, products=None):
    """What q-block `qi` adds to the dK and dV of k-block `ki`, both
    [block_k, D] f32, from blocks already cast to f32 (lse [block_q, 1]).
    The one accumulate step of all three block plans below. `products`:
    (`q k^T` scaled, `g v^T`), the two products that need nothing of the
    rest, where the caller issued them for several q-blocks ahead."""
    s, dp = products or (None, None)
    delta = jnp.sum(o * g, axis=-1, keepdims=True)
    if s is None:
        s = _abt(q, k) * scale
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
    if dp is None:
        dp = _abt(g, v)
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


def _bwd_dkdv_band_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                          dk_ref, dv_ref, *, block_q: int, num_q: int,
                          scale: float, causal: bool, window: int):
    """Grid (b, h, k-block, q-block of the band), where a window ends
    every k-block's band before S does: the q axis is as long as the
    longest band and counts from the band's first q-block (the
    query-side blocks arrive through `_q_block_index`, clamped into the
    band, so a step past its end fetches nothing). The f32 dk/dv output
    block is constant in the (minor) q axis, so Mosaic keeps it resident
    and this accumulates across sequential q steps."""
    block_k = k_ref.shape[2]
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _zero():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q,
                     block_k=block_k, causal=causal, window=window)
    qi = qi + lo

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


def _bwd_dkdv_stream_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                            dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                            num_q: int, scale: float, causal: bool,
                            window: int, in_flight: int):
    """Grid (b, h, k-block, span): the k-block stays, q, dO, o and lse
    arrive a SPAN of q-blocks a grid step through index maps clamped into
    the band (`_q_span_index`: a span outside it fetches nothing), and the
    kernel walks the span's q-blocks of the band itself in rising order,
    `in_flight` at a time (their `q k^T` and `g v^T` first), a span that
    lies whole inside the band written out, the span on the diagonal in
    a loop (`_span_walk`): O(span) VMEM at any sequence length. dk and dv
    sum in two float32 scratch arrays and are written once, at the last
    step of the span axis, in the result's dtype."""
    block_k = k_ref.shape[2]
    span = q_ref.shape[2] // block_q
    ki, si = pl.program_id(2), pl.program_id(3)
    first = si * span
    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q, block_k=block_k,
                     causal=causal, window=window)
    lo = jax.lax.max(jnp.int32(lo), first)
    hi = jax.lax.min(jnp.int32(hi), first + span)

    @pl.when(si == 0)
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(lo < hi)
    def _span():
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)

        def body(offsets):
            at = [pl.ds(a, block_q) for a in offsets]
            rows = [(q_ref[0, 0, a, :].astype(jnp.float32),
                     g_ref[0, 0, a, :].astype(jnp.float32)) for a in at]
            made = [(_abt(q, k) * scale, _abt(g, v)) for q, g in rows]
            for offset, a, (q, g), products in zip(offsets, at, rows, made):
                dk_q, dv_q = _dkdv_step(
                    q, k, v, g, o_ref[0, 0, a, :].astype(jnp.float32),
                    lse_ref[0, 0, a, :][:, 0:1],
                    _block_of(offset, first, block_q), ki, block_q=block_q,
                    block_k=block_k, scale=scale, causal=causal,
                    window=window, products=products)
                dv_acc[...] += dv_q
                dk_acc[...] += dk_q

        _span_walk(lo, hi, first, block_q, span, in_flight, body)

    @pl.when(si == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# physical VMEM of a v5e core: what a trace with no chip attached plans for
# (interpret mode, a compile for a described device)
_V5E_VMEM_BYTES = 128 * 2 ** 20


def _vmem_bytes() -> int:
    """VMEM of the core this trace is for."""
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except ValueError:                     # no TPU is the default device
        return _V5E_VMEM_BYTES


def _q_steps(*, num_q: int, num_k: int, block_q: int, block_k: int,
             causal: bool, window: int, span: int = 1) -> Tuple[int, int]:
    """(steps, band_steps) of a head's streamed dK/dV walk at `span`
    q-blocks a grid step: the length of the q axis, and how many of a
    head's num_k x steps grid steps hold a q-block of a band. One block a
    step: the axis is as long as the most q-blocks the band of any k-block
    holds (all of S's unless a window ends the bands early: `band`).
    Several: it counts S's num_q / span spans."""
    bands = [_q_band(ki, num_q=num_q, block_q=block_q, block_k=block_k,
                     causal=causal, window=window) for ki in range(num_k)]
    if span > 1:
        return num_q // span, sum((hi - 1) // span - lo // span + 1
                                  for lo, hi in bands)
    return max(hi - lo for lo, hi in bands), sum(hi - lo for lo, hi in bands)


def _q_block_index(ki, qi, *, steps: int, num_q: int, block_q: int,
                   block_k: int, causal: bool, window: int, span: int = 1):
    """The streamed dK/dV call's query-side block (of `span` q-blocks) at
    grid step (ki, qi) of a q axis `steps` long: one as long as S's counts
    from S's first span, one shorter (a banded call) from the first
    q-block of k-block ki's band; either way clamped into the band, as the
    forward's `kv_idx` clamps k and v. A step outside the band repeats the
    index of the band's near edge, and Mosaic elides a fetch whose index
    repeats."""
    if not causal:
        return qi
    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q, block_k=block_k,
                     causal=causal, window=window)
    div, least, most = _index_ops(qi)
    if steps * span < num_q:
        return least(lo + qi, hi - 1)
    return most(least(qi, div(hi - 1, span)), div(lo, span))


def hbm_bytes_per_head(path: str, *, S: int, T: int, D: int, block_q: int,
                       block_k: int, itemsize: int, out_itemsize: int,
                       q_index=None, steps: int = None, span: int = 1) -> int:
    """HBM bytes the dK/dV call moves for one (b, h): k and v read and dk
    and dv written once, plus the query side (q, dO, o and the 128-lane
    f32 lse): once on the resident plan; on the streaming plans a span of
    `span` blocks each time `q_index(ki, qi)` changes along the walk of
    the grid, whose q axis is `steps` long (all of S's q-blocks if not
    given)."""
    kv_bytes = 2 * T * D * (itemsize + out_itemsize)
    row_bytes = 3 * D * itemsize + _LSE_LANES * 4
    if path == "resident":
        return S * row_bytes + kv_bytes
    fetches, last = 0, None
    for ki in range(T // block_k):
        for qi in range(steps or S // block_q):
            index = q_index(ki, qi)
            fetches += index != last
            last = index
    return fetches * span * block_q * row_bytes + kv_bytes


def bwd_dkdv_plan(*, S: int, T: int, D: int, dtype, groups: int,
                  block_q: int, block_k: int, causal: bool, window: int,
                  vmem_bytes: int) -> dict:
    """Which of the dK/dV call's block plans a shape takes, and the
    bytes that decide it (also the attributes of `flash.bwd_plan`).
    resident: the query side of a whole head in VMEM, taken where its
    blocks (double-buffered by Mosaic) and the f32 temporaries of one
    accumulate step fit a quarter of the core's VMEM; stream otherwise,
    on grid (b, h, k-block, span): `span` q-blocks a grid step, the
    longest divisor of S's q-blocks whose count (`walk_bytes`: the blocks
    twice, the two sums, the temporaries of `in_flight` q-blocks' step of
    the walk) fits the VMEM a call gets without asking, two in flight
    before one; band: a streamed call whose window ends every k-block's
    band before S's last q-block, so that its q axis is as long as the
    longest band (`steps`; S 16384, window 1024: 3 q-blocks a k-block
    where S has 32) and counts from the band's first q-block, one q-block
    a step. `band_steps`: how many of a head's grid steps hold a q-block
    of a band. The rest of VMEM is XLA's: it holds operands of the fusions
    around the call there, and a call's `vmem_limit_bytes` is taken out of
    that for as long as the call is scheduled (at 96 MiB the four-chip
    step lost a 64 MiB operand of a weight-gradient fusion, 16 ms a step:
    PERF.md 6, PR 28), so the resident plan's limit is the estimate and a
    quarter and the streamed plans ask for none.

    `walk_bytes` is no less than what Mosaic planned for a described v5e
    (the least `vmem_limit_bytes` it accepted, to a quarter MiB, PR 49;
    span and in flight: the count | the least, MiB): S 8,192, 20 heads of
    256, bf16 results (1, 1) 10.0 | 8.5, (2, 1) 12.0 | 11.25, (2, 2)
    16.0 | 15.25, (4, 1) 16.0 | 15.25 (taken: 18.1 ms a call against 18.4
    at both forms of 2), (4, 2) 20.0 | 19.5; S 16,384, 32 over 4 heads of
    128, float32 results (1, 1) 6.75 | 5.5, (2, 2) 11.0 | 9.75, (4, 1)
    10.5 | 10.25, (4, 2) 13.5 | 13.0, (8, 1) 15.5 | 15.25 (taken: 29.0 ms
    against 28.9 at (4, 1) and 29.5 at (4, 2)), (8, 2) 18.5 | 18.0. Two
    in flight never beat one on the chip (equal at D 256, 2% behind at
    D 128): the order is kept from the sparse mirror and decides nothing
    at either cell."""
    itemsize = jnp.dtype(dtype).itemsize
    head_dim, D = D, _vmem_lanes(D)         # VMEM's counts; HBM's at head_dim
    row_bytes = 3 * D * itemsize + _LSE_LANES * 4
    # a head's results leave in the inputs' dtype; a group's are summed in f32
    out_dtype = jnp.dtype(dtype if groups == 1 else jnp.float32)
    resident_bytes = (
        2 * S * row_bytes
        + 2 * 2 * block_k * D * (itemsize + out_dtype.itemsize)
        # two [block_q, block_k] of s/p/dp/ds; q, dO, o; k, v, dk, dv
        # (Mosaic planned 0.75-1.1 MiB under this at five shapes)
        + 4 * (2 * block_q * block_k + (3 * block_q + 4 * block_k) * D))

    def walk_bytes(span: int, in_flight: int) -> int:
        return (
            # the query side a span, k, v and the results, twice; the sums
            2 * (span * block_q * row_bytes
                 + 2 * block_k * D * (itemsize + out_dtype.itemsize))
            + 2 * block_k * D * 4
            # a q-block in flight: s and dp (p and ds take their place),
            # q, dO and o in f32, what it adds to dk or dv; k and v in f32
            + in_flight * (2 * block_q * block_k + (3 * block_q + block_k) * D) * 4
            + 2 * block_k * D * 4)

    num_q = S // block_q
    dims = dict(block_q=block_q, block_k=block_k)
    mask = dict(dims, num_q=num_q, causal=causal, window=window)
    path, steps, band_steps = "resident", 1, T // block_k
    span, in_flight = num_q, 1
    if resident_bytes > vmem_bytes // 4:
        span = 1
        steps, band_steps = _q_steps(num_k=T // block_k, **mask)
        path = "band" if steps < num_q else "stream"
    if path == "band":
        out_dtype = jnp.dtype(jnp.float32)  # accumulated in the output block
    if path == "stream":
        span, in_flight = next(
            ((n, f) for n in range(num_q, 0, -1) if num_q % n == 0
             for f in (2, 1)
             if f <= n and walk_bytes(n, f) <= _SCOPED_VMEM_BYTES), (1, 1))
        steps, band_steps = _q_steps(num_k=T // block_k, span=span, **mask)
    return dict(
        dims, path=path, S=S, window=window, resident_bytes=resident_bytes,
        out_dtype=out_dtype, vmem_limit_bytes=resident_bytes * 5 // 4,
        span=span * block_q, in_flight=in_flight,
        walk_bytes=walk_bytes(span, in_flight), steps=steps,
        band_steps=band_steps,
        hbm_bytes_per_head=hbm_bytes_per_head(
            path, S=S, T=T, D=head_dim, itemsize=itemsize,
            out_itemsize=out_dtype.itemsize, **dims, steps=steps, span=span,
            q_index=functools.partial(_q_block_index, steps=steps, span=span,
                                      **mask)))


def _flash_bwd_dkdv(qt, kt, vt, gt, ot, lse, *, causal: bool, block_q: int,
                    block_k: int, window: int, scale: float = None,
                    vmem_bytes: int = None, dq_plan: dict = None,
                    walk: Tuple[int, int] = None):
    """The dK/dV call on [B, H|KV, S|T, D] operands (lse [B, H, S, 128]):
    per-query-head dK and dV, [B, H, T, D]. `walk`: (q-blocks a grid step,
    q-blocks in flight) of a streamed call, for the plan's."""
    B, H, S, D = qt.shape
    KV, T = kt.shape[1], kt.shape[2]
    groups = H // KV
    num_q = S // block_q
    plan = bwd_dkdv_plan(
        S=S, T=T, D=D, dtype=kt.dtype, groups=groups,
        block_q=block_q, block_k=block_k, causal=causal, window=window,
        vmem_bytes=vmem_bytes or _vmem_bytes())
    tracing.plan("flash.bwd_plan", {
        **{k: plan[k] for k in ("path", "S", "block_q", "block_k", "window",
                                "resident_bytes", "hbm_bytes_per_head",
                                "span", "in_flight", "walk_bytes")},
        **_grid_steps(plan, B * H, T // block_k),
        **({"dq_" + k: dq_plan[k] for k in ("path", "span", "in_flight")}
           if dq_plan else {}),
        **(_grid_steps(dq_plan, B * H, num_q, "dq_") if dq_plan else {})})
    kernel_args = dict(block_q=block_q, scale=_scale(scale, D),
                       causal=causal, window=window)
    mask = dict(num_q=num_q, block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    scratch = []
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
        span, in_flight = plan["span"] // block_q, plan["in_flight"]
        steps = plan["steps"]
        if walk and plan["path"] == "stream":
            (span, in_flight), steps = walk, num_q // walk[0]
        if plan["path"] == "band":
            kernel = functools.partial(_bwd_dkdv_band_kernel, num_q=num_q,
                                       **kernel_args)
        else:
            kernel = functools.partial(_bwd_dkdv_stream_kernel, num_q=num_q,
                                       in_flight=in_flight, **kernel_args)
            scratch = [pltpu.VMEM((block_k, D), jnp.float32)] * 2
        grid = (B, H, T // block_k, steps)

        def q_index(b, h, i, j):
            return (b, h, _q_block_index(i, j, steps=steps, span=span,
                                         **mask), 0)

        def q_side(width):
            return pl.BlockSpec((1, 1, span * block_q, width), q_index)

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
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=_use_interpret(),
    )
    with jax.named_scope(f"flash.dkdv.{plan['path']}"):
        return call(qt, kt, vt, gt, ot, lse)


def _flash_bwd_dq(qt, kt, vt, gt, ot, lse, *, plan: dict, causal: bool,
                  block_q: int, block_k: int, window: int, scale: float):
    """The dQ call on [B, H|KV, S|T, D] operands (lse [B, H, S, 128]), by
    the plan `kv_plan` made for it (`path`, `span`, `in_flight`):
    [B, H, S, D] in q's dtype."""
    B, H, S, D = qt.shape
    T, groups = kt.shape[2], H // kt.shape[1]
    num_k = T // block_k
    span = plan["span"] // block_k
    mask = dict(num_k=num_k, block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    steps, _, _ = _span_steps(num_q=S // block_q, span=span, **mask)
    q_side, kv_blk = _walk_specs(groups=groups, span=span, steps=steps, D=D,
                                 **mask)
    call = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, num_k=num_k,
                          steps=steps, scale=scale, causal=causal,
                          window=window, in_flight=plan["in_flight"],
                          written=plan.get("written")),
        grid=(B, H, S // block_q, steps),
        in_specs=[q_side(D), kv_blk, kv_blk, q_side(D), q_side(D),
                  q_side(_LSE_LANES)],
        out_specs=q_side(D),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), qt.dtype),
        # several grid steps add up in float32
        scratch_shapes=[] if steps == 1 else [
            pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_use_interpret(),
    )
    with jax.named_scope(f"flash.dq.{plan['path']}"):  # flash.bwd_plan's dq_path
        return call(qt, kt, vt, gt, ot, lse)


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

    dq_plan = kv_plan(S=S, T=T, D=D, dtype=k.dtype, block_q=block_q,
                      block_k=block_k, window=window, call="dq",
                      causal=causal)
    dq = _flash_bwd_dq(qt, kt, vt, gt, ot, lse, plan=dq_plan, causal=causal,
                       block_q=block_q, block_k=block_k, window=window,
                       scale=scale)
    dk, dv = _flash_bwd_dkdv(qt, kt, vt, gt, ot, lse, causal=causal,
                             block_q=block_q, block_k=block_k, window=window,
                             scale=scale, dq_plan=dq_plan)
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
