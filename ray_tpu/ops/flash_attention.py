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
          beside its blocks), the sums in VMEM scratch, once a step of
          the walk, and TWO k-blocks a step of the walk where their
          temporaries fit: both blocks' `q k^T` are issued before the
          first block's softmax, two chains in one straight-line body.
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
(`loop`, `band`: dq is the loop's carry, written once in q's dtype;
`stream`: dq accumulated in the float32 output block, which stays resident
across a q-block's spans), two k-blocks a step of the walk where their
temporaries fit beside the span (their `q k^T` and `g v^T` first); every
plan sums in the same order. The dK/dV pass is
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
  band      the stream plan where a window ends every k-block's band
            before S does: the q axis is as long as the longest band (S
            16384, window 1024: 3 q-blocks of S's 32) and counts from the
            band's first q-block, so a k-block's 29 steps outside its band
            are gone (15.03 -> 7.04 ms a call; PERF.md 6, PR 39). The
            causal grid without a window keeps its empty triangle: a band
            as long as S has no shorter axis.
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
                block_k: int, causal: bool, window: int) -> Tuple[int, int]:
    """(steps, band_steps) of a head's walk at `span` k-blocks a grid
    step: the length of the span axis, and how many of the head's num_q x
    steps grid steps hold a block of a band. A span that is shorter than T
    and holds the longest band of any q-block is a banded call's: ONE step
    a q-block, the span fetched where the band starts (`_span_band`).
    Every other span is one of T's num_k / span, and the axis counts all
    of them."""
    mask = dict(num_k=num_k, block_q=block_q, block_k=block_k, causal=causal,
                window=window)
    bands = [_k_band(qi, **mask) for qi in range(num_q)]
    if max(hi - lo for lo, hi in bands) <= span < num_k:
        return 1, num_q
    return num_k // span, sum((hi - 1) // span - lo // span + 1
                              for lo, hi in bands)


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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, block_k: int,
                num_k: int, steps: int, scale: float, causal: bool,
                window: int, in_flight: int):
    """Grid (b, h, q-block, span): the q-block stays, K and V arrive a
    span of k-blocks a grid step through an index map clamped into the
    band (`_k_span_index`: a span outside it repeats its neighbour's index
    and Mosaic, which elides a fetch whose index repeats, fetches nothing:
    O(S*W) HBM traffic for sliding windows instead of O(S*T)), and the
    kernel walks the span's blocks of the band itself (`_walk`).
    One grid step a q-block (`steps` 1: the `loop` plan's span is a head's
    whole K and V, the `band` plan's the q-block's whole band, fetched
    where it starts): the accumulator, the running max and the running
    sum are the loop's carry. Several (`stream`): the three live in VMEM
    scratch across the grid steps of a q-block (same structure as the
    official TPU flash kernel) and a step of the walk reads and writes
    them once, not once a block; the last grid step normalizes and writes
    o and lse."""
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

        def block(ref, ki):
            rows = pl.multiple_of((ki - first()) * block_k, block_k)
            return ref[0, 0, pl.ds(rows, block_k), :]

        def step(carry, ki, n):
            kv = [(block(k_ref, ki + j), block(v_ref, ki + j))
                  for j in range(n)]
            scores = [jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale for k, _ in kv]
            o, m, l = carry if steps == 1 else kept()
            for j, ((_, v), s) in enumerate(zip(kv, scores)):
                if causal:
                    s = _mask(s, q_pos, ki + j, block_k=block_k,
                              window=window)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
                # p joins v's dtype for the second MXU pass (f32
                # accumulation); standard flash practice, same as the
                # official TPU kernel
                o = o * alpha + jax.lax.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                m = m_new
            if steps == 1:
                return o, m, l
            keep(o, m, l)
            return carry

        return _walk(lo, hi, carry, step, in_flight)

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
    steps, band_steps  of one head: the span axis's length, and how many
          of the head's S / block_q x steps grid steps hold a block of a
          band (`_span_steps`).

    `walk_bytes` is above what Mosaic planned at every shape compiled for
    a v5e (the least `vmem_limit_bytes` it accepted, to a quarter MiB, PR
    37: D 128 and 256, spans of 1 to 16 blocks, by 0.2 to 1.9 MiB; PR 39,
    the band plan: bands of 3, 5 and 9 blocks, 1 to 5 in flight, the dQ
    call by 0.25 to 4 MiB, the forward by 2 and more)."""
    itemsize = jnp.dtype(dtype).itemsize
    kv_block_bytes = 2 * 2 * T * D * itemsize
    # q, dO, o, the result and the 128-lane lse, double-buffered
    q_side_bytes = 2 * block_q * (4 * D * itemsize + _LSE_LANES * 4)
    loop_bytes = (
        kv_block_bytes + q_side_bytes
        # two [block_q, block_k] of s and p; the accumulator and a k-block
        + 4 * (2 * block_q * block_k + (block_q + block_k) * D))
    path = "stream" if loop_bytes > _SCOPED_VMEM_BYTES or (
        window > 0 and call == "fwd") else "loop"

    def walk_bytes(blocks: int, in_flight: int) -> int:
        scores = block_q * block_k
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
            # q, dO, o, the lse and the result (f32 where spans add up)
            q_side = 2 * block_q * (
                3 * D * itemsize + _LSE_LANES * 4
                + D * (4 if path == "stream" else itemsize))
            # a block in flight: s and dp, and k and v cast to f32; q and
            # dO cast to f32, and the sum; three more of its size on the
            # band plan, where Mosaic planned 0.75 to 1.25 MiB over the
            # rest at D 256 (at 3 in flight 17.0 MiB of 16)
            step = (in_flight * (scores * 8 + 2 * block_k * D * 4)
                    + (6 if path == "band" else 3) * block_q * D * 4)
        return 2 * 2 * blocks * block_k * D * itemsize + q_side + step

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
    blocks, in_flight = next(
        ((n, f) for n in spans for f in pairs
         if f <= n and walk_bytes(n, f) <= _SCOPED_VMEM_BYTES),
        (spans[-1], 1))
    steps, band_steps = _span_steps(num_q=num_q, span=blocks, **mask)
    return dict(path=path, S=S, D=D, kv_block_bytes=kv_block_bytes,
                loop_bytes=loop_bytes, span=blocks * block_k,
                in_flight=in_flight,
                walk_bytes=walk_bytes(blocks, in_flight), steps=steps,
                band_steps=band_steps)


def _grid_steps(plan: dict, heads: int, blocks: int, prefix: str = "") -> dict:
    """What a plan instant says of its call's grid of `heads` x `blocks`
    x the plan's `steps`: `grid_steps`, all of it, and `band_steps`, the
    steps that hold a block of a band (their quotient is the share of grid
    steps that work)."""
    return {prefix + "grid_steps": heads * blocks * plan["steps"],
            prefix + "band_steps": heads * plan["band_steps"]}


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
        tracing.instant("flash.fwd_plan", {
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
    steps, _ = _span_steps(num_q=S // block_q, span=span, **mask)
    q_side, kv_blk = _walk_specs(groups=groups, span=span, steps=steps, D=D,
                                 **mask)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, num_k=num_k,
                          steps=steps, scale=scale, causal=causal,
                          window=window, in_flight=plan["in_flight"]),
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


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dq_ref, *,
               block_k: int, num_k: int, steps: int, scale: float,
               causal: bool, window: int, in_flight: int):
    """Grid (b, h, q-block, span), the forward's walk (`_fwd_kernel`) for
    dQ (FlashAttention-2 backward, dQ pass): K and V arrive a span of
    k-blocks a grid step, and dQ accumulates over the span's blocks of the
    band, `in_flight` at a time (a block's `q k^T` and `g v^T` need
    nothing of the sum). delta = rowsum(o * dO) is computed in-kernel,
    once a grid step. One grid step a q-block (`loop`, `band`): the sum
    is the loop's carry and dQ is written once, in q's dtype. Several
    (`stream`): the f32 dq output block is constant in the (minor) span
    axis, so Mosaic keeps it resident and a step of the walk adds to it,
    in the loop plan's order."""
    block_q, D = q_ref.shape[2], q_ref.shape[3]
    span = k_ref.shape[2] // block_k
    streamed = steps > 1
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

        def block(ref, ki):
            rows = pl.multiple_of((ki - first()) * block_k, block_k)
            return ref[0, 0, pl.ds(rows, block_k), :].astype(jnp.float32)

        def step(carry, ki, n):
            kv = [(block(k_ref, ki + j), block(v_ref, ki + j))
                  for j in range(n)]
            products = [
                (jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 * scale,
                 jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32))
                for k, v in kv]
            dq = dq_ref[0, 0] if streamed else carry
            for j, ((k, _), (s, dp)) in enumerate(zip(kv, products)):
                if causal:
                    s = _mask(s, q_pos, ki + j, block_k=block_k,
                              window=window)
                p = jnp.exp(s - lse)
                ds = p * (dp - delta) * scale
                dq = dq + jax.lax.dot(ds, k,
                                      preferred_element_type=jnp.float32)
            if not streamed:
                return dq
            dq_ref[0, 0] = dq
            return carry

        return _walk(lo, hi, carry, step, in_flight)

    if not streamed:
        dq_ref[0, 0] = walk(jnp.zeros((block_q, D), jnp.float32)).astype(
            dq_ref.dtype)
        return

    @pl.when(si == 0)
    def _zero():
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(lo < hi)
    def _span():
        walk(0)


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
                            steps: int, scale: float, causal: bool,
                            window: int):
    """Grid (b, h, k-block, q-block): the f32 dk/dv output block is
    constant in the (minor) q axis, so Mosaic keeps it resident and this
    accumulates across sequential q steps: O(block) VMEM at any sequence
    length. The q axis has `steps` steps: all of S's q-blocks, or, where a
    window ends every band sooner (`band`), as many as the longest band
    holds, counted from the band's first q-block. The query-side blocks
    arrive through index maps clamped into the band (`_flash_bwd_dkdv`),
    so a step outside it fetches nothing."""
    block_k = k_ref.shape[2]
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _zero():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q,
                     block_k=block_k, causal=causal, window=window)
    if steps < num_q:                      # the axis counts from the band
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
             causal: bool, window: int) -> Tuple[int, int]:
    """(steps, band_steps) of a head's streamed dK/dV walk: the length of
    the q axis, which is the most q-blocks the band of any k-block holds
    (all of S's unless a window ends the bands early), and how many of a
    head's num_k x steps grid steps hold a q-block of a band."""
    bands = [_q_band(ki, num_q=num_q, block_q=block_q, block_k=block_k,
                     causal=causal, window=window) for ki in range(num_k)]
    return max(hi - lo for lo, hi in bands), sum(hi - lo for lo, hi in bands)


def _q_block_index(ki, qi, *, steps: int, num_q: int, block_q: int,
                   block_k: int, causal: bool, window: int):
    """The streamed dK/dV call's query-side block at grid step (ki, qi) of
    a q axis `steps` long: one as long as S's counts from q-block 0, one
    shorter (a banded call) from the first q-block of k-block ki's band;
    either way clamped into the band, as the forward's `kv_idx` clamps k
    and v. A step outside the band repeats the index of the band's near
    edge, and Mosaic elides a fetch whose index repeats."""
    if not causal:
        return qi
    lo, hi = _q_band(ki, num_q=num_q, block_q=block_q, block_k=block_k,
                     causal=causal, window=window)
    _, least, most = _index_ops(qi)
    if steps < num_q:
        return least(lo + qi, hi - 1)
    return most(least(qi, hi - 1), lo)


def hbm_bytes_per_head(path: str, *, S: int, T: int, D: int, block_q: int,
                       block_k: int, itemsize: int, out_itemsize: int,
                       q_index=None, steps: int = None) -> int:
    """HBM bytes the dK/dV call moves for one (b, h): k and v read and dk
    and dv written once, plus the query side (q, dO, o and the 128-lane
    f32 lse): once on the resident plan; on the streaming plans one block
    each time `q_index(ki, qi)` changes along the walk of the grid, whose
    q axis is `steps` long (all of S's q-blocks if not given)."""
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
    return fetches * block_q * row_bytes + kv_bytes


def bwd_dkdv_plan(*, S: int, T: int, D: int, dtype, groups: int,
                  block_q: int, block_k: int, causal: bool, window: int,
                  vmem_bytes: int) -> dict:
    """Which of the dK/dV call's two block plans a shape takes, and the
    bytes that decide it (also the attributes of `flash.bwd_plan`).
    resident: the query side of a whole head in VMEM, taken where its
    blocks (double-buffered by Mosaic) and the f32 temporaries of one
    accumulate step fit a quarter of the core's VMEM; stream otherwise,
    on grid (b, h, k-block, q-block); band: a streamed call whose window
    ends every k-block's band before S's last q-block, so that its q axis
    is as long as the longest band (`steps`; S 16384, window 1024: 3
    q-blocks a k-block where S has 32) and counts from the band's first
    q-block. `band_steps`: how many of a head's grid steps hold a q-block
    of a band. The rest of VMEM is XLA's: it holds operands of the fusions
    around the call there, and a call's `vmem_limit_bytes` is taken out of
    that for as long as the call is scheduled (at 96 MiB the four-chip
    step lost a 64 MiB operand of a weight-gradient fusion, 16 ms a step:
    PERF.md 6, PR 28), so the limit asked for is the estimate and a
    quarter."""
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
    dims = dict(block_q=block_q, block_k=block_k)
    mask = dict(dims, num_q=S // block_q, causal=causal, window=window)
    path, steps, band_steps = "resident", 1, T // block_k
    if resident_bytes > vmem_bytes // 4:
        steps, band_steps = _q_steps(num_k=T // block_k, **mask)
        path = "band" if steps < S // block_q else "stream"
        out_dtype = jnp.dtype(jnp.float32)  # accumulated in the output block
    return dict(
        dims, path=path, S=S, window=window, resident_bytes=resident_bytes,
        out_dtype=out_dtype, vmem_limit_bytes=resident_bytes * 5 // 4,
        steps=steps, band_steps=band_steps,
        hbm_bytes_per_head=hbm_bytes_per_head(
            path, S=S, T=T, D=D, itemsize=itemsize,
            out_itemsize=out_dtype.itemsize, **dims, steps=steps,
            q_index=functools.partial(_q_block_index, steps=steps, **mask)))


def _flash_bwd_dkdv(qt, kt, vt, gt, ot, lse, *, causal: bool, block_q: int,
                    block_k: int, window: int, scale: float = None,
                    vmem_bytes: int = None, dq_plan: dict = None):
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
        **_grid_steps(plan, B * H, T // block_k),
        **({"dq_" + k: dq_plan[k] for k in ("path", "span", "in_flight")}
           if dq_plan else {}),
        **(_grid_steps(dq_plan, B * H, num_q, "dq_") if dq_plan else {})})
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
        steps = plan["steps"]
        kernel = functools.partial(_bwd_dkdv_stream_kernel, num_q=num_q,
                                   steps=steps, **kernel_args)
        grid = (B, H, T // block_k, steps)

        def q_index(b, h, i, j):
            return (b, h, _q_block_index(
                i, j, steps=steps, num_q=num_q, block_q=block_q,
                block_k=block_k, causal=causal, window=window), 0)

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
    steps, _ = _span_steps(num_q=S // block_q, span=span, **mask)
    q_side, kv_blk = _walk_specs(groups=groups, span=span, steps=steps, D=D,
                                 **mask)
    call = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, num_k=num_k,
                          steps=steps, scale=scale, causal=causal,
                          window=window, in_flight=plan["in_flight"]),
        grid=(B, H, S // block_q, steps),
        in_specs=[q_side(D), kv_blk, kv_blk, q_side(D), q_side(D),
                  q_side(_LSE_LANES)],
        out_specs=q_side(D),
        # several grid steps add up in the output block, in float32
        out_shape=jax.ShapeDtypeStruct(
            (B, H, S, D), qt.dtype if steps == 1 else jnp.float32),
        interpret=_use_interpret(),
    )
    with jax.named_scope(f"flash.dq.{plan['path']}"):  # flash.bwd_plan's dq_path
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
