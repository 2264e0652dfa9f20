"""Chunked state-space scan (SSD, the Mamba-2 mixer's recurrence) for TPU
in Pallas, with its backward.

For every head h with a [P, N] state s (P the head's width, N the state
size), step by step over a sequence:

    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T        y_t = s_t C_t

(x_t [P]; B_t and C_t [N] a GROUP of adjacent heads, head h of H reading
group h // (H / G): one group shared by all heads as Granite has it, eight
of eight heads as Nemotron 3 Nano has it; dt_t > 0 and A < 0 a head; the
skip ``D x_t`` is the caller's, an elementwise term).
Written in chunks of Q steps (arXiv:2405.21060, section 6), with
``cum_t`` the running sum of ``dt A`` inside a chunk and ``u = dt x``:

    y_t   = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) u_s     (in the chunk)
            + exp(cum_t) h_in C_t                        (what came before it)
    h_out = exp(cum_last) h_in + sum_s exp(cum_last - cum_s) u_s B_s^T

Two paths, chosen by the caller the way ``attn_impl`` chooses "xla" or
"flash":

- ``"xla"``: the same chunks as plain einsums, the [Q, Q] decay block of
  every head and chunk an array in memory (H x Q floats a token: 2.1 GB a
  layer at 16,384 tokens, H 128, Q 256). The CPU tests, a mesh, small
  sizes. Gradients are jax's own.
- ``"pallas"``: one Mosaic call forward and one backward, grid (batch,
  head block, chunk). The decay block lives in VMEM as flash's scores do;
  the chunks of a sequence are walked in order (the backward last to
  first) and the state between them ([heads, P, N] float32) is carried in
  VMEM scratch, so the only state that reaches HBM is what the backward
  needs: each chunk's incoming state ``h_in`` ([B, chunks, H x P, N]
  float32, 268 MB a layer at B2 x S8192), written by the forward rule and
  read once. A head block lies inside ONE group (at most HEADS_PER_BLOCK
  heads, a divisor of the group's), so an instance reads its group's B and
  C block and computes ``C B^T`` once for all its heads; the gradients of
  B and C leave a block apart and the caller sums each group's blocks. One
  group is the case G = 1 of the same call. A Mosaic call cannot be
  partitioned by GSPMD; the caller refuses a mesh of several devices.
  Interpret mode off the TPU.

The kernel's layout. x arrives as it leaves the mixer's convolution,
[B, S, H x P], and is never transposed: a lane tile of 128 holds TWO
heads of 64, so the kernel walks a block's heads in pairs. Each head of
a pair has its own [Q, Q] block ``M = (C B^T) . L``; ``M_a @ u`` and
``M_b @ u`` are both taken over the pair's 128 lanes (the MXU is 128
wide either way) and the halves chosen by lane. Everything whose
contraction runs over the steps or over the state (the chunk's end state,
the term of ``h_in``, and their gradients) is one matmul a pair with no
waste. ``cum`` is needed along both axes of the block: it arrives twice,
[B, S, H] (steps on sublanes: a head's column is a masked lane reduction)
and [B, H, S] (steps on lanes: a head's row is a sublane slice), and its
gradient leaves in both layouts, summed by the caller. Matmul operands
are bfloat16 (x, B, C arrive so), products accumulate in float32, the
decay and every sum over it are float32.

A head as WIDE as a lane tile with keys of its own (P = 128 and G = H: a
Lightning linear-attention layer, arXiv:2401.04658, with x = v, B = k,
C = q / sqrt(P) and N = P) takes calls of its own (``_fwd_kernel_wide``,
``_bwd_kernel_wide``): one head a lane tile and no halves chosen by lane,
``C B^T`` a head, B and C arriving [B, S, H x N] a block of whole heads
(``WIDE_HEADS_PER_BLOCK``), dB and dC leaving in the same shape with nothing
to sum. Its decay is a CONSTANT a head (``dt`` None: every step is 1, so
``cum_t = a (t + 1)`` inside a chunk): the [H] rates travel in SMEM, the
kernel makes ``exp(a (t - s))`` from two iotas, and no [B, S, H] array, no
second layout and no gradient of a step or a rate exists on either path.

A head as wide as a lane tile WITH steps (P a multiple of 128, ``dt`` a
head and step, G groups of adjacent heads, any N: a Falcon-H1 mixer at 32
heads of 128, a state of 256, two groups) takes the third pair
(``_fwd_kernel_tile``, ``_bwd_kernel_tile``; ``layout`` "tile"): the pairs
calls' blocks and arguments (a block of heads inside ONE group reads the
group's B and C and computes ``C B^T`` once; ``cum`` in both layouts, its
gradient in both) walked a HEAD at a time, no halves chosen by lane, every
product MXU-wide: ``M_h @ u_h`` [Q, Q] x [Q, P], the state's term ``C``
[Q, N] x ``h_in^T`` [N, P], the update ``u^T`` [P, Q] x ``B`` [Q, N]. N is
no lane of u or y, so it need not be P. How many heads a block holds is what
the backward's blocks leave of ``TILE_VMEM`` (``plan``).

Across a layer checkpoint nothing of the scan is kept, so nothing of it
is named for ``remat._checkpoint``: its output is as large as two layer inputs and the states as
four, so the backward's recomputation of the layer runs the forward call
again (the numbers: PERF.md section 6, PR 32). What both paths take in
place of x, dt and A (``_prologue``: u = dt x, the running sums in two
layouts) has a gradient rule of its own that keeps x, dt and A and no
more; a head's dt reaches its P lanes, and ``du x`` is summed over them,
as a product with a 0/1 matrix (``_over_lanes``, ``_per_head``).

What this op does NOT compute: a term that ERASES what the state holds
along a key (the delta rule's ``I - beta k k^T``) and a decay that is a
VECTOR over the state's channels; a head's decay here is one scalar a step
(``exp(dt_t A)``) or one constant. Kimi Delta Attention's recurrence, which
has both, is ``ops/delta_rule.py`` (``gated_delta_rule``).

S must be a multiple of the chunk (pad upstream).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util import tracing

NEG_INF = -1e30
HEADS_PER_BLOCK = 16       # heads one kernel instance walks, in pairs
WIDE_HEADS_PER_BLOCK = 4   # heads of a lane tile each, with B and C of their own
LANE_TILE = 128            # a head of a multiple of it with steps: "tile"
# what one instance of the tile layout's backward call may hold (``plan``'s
# ``vmem_bytes``): under Mosaic's 16 MiB a call, with room for the values
# of an unrolled head that the count leaves out
TILE_VMEM = 12 * 2 ** 20


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --- the plain path ---------------------------------------------------------


def _chunks(a, q):
    """[B, S, ...] -> [B, S / q, q, ...]."""
    return a.reshape(a.shape[0], a.shape[1] // q, q, *a.shape[2:])


def _scan_xla(u, bm, cm, cum):
    """u [B, C, Q, H, P], bm and cm [B, C, Q, N], cum [B, C, Q, H] (all
    float32) -> y [B, C, Q, H, P]."""
    q = u.shape[2]
    g = jnp.einsum("bctn,bcsn->bcts", cm, bm)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # [B, C, t, s, H]
    seen = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(seen, seg, NEG_INF))
    y = jnp.einsum("bcts,bctsh,bcshp->bcthp", g, decay, u)
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)                # [B, C, Q, H]
    local = jnp.einsum("bcsh,bcshp,bcsn->bchpn", to_end, u, bm)
    whole = jnp.exp(cum[:, :, -1, :])                        # [B, C, H]

    def step(h, inp):
        s, d = inp
        return h * d[..., None, None] + s, h

    _, h_in = jax.lax.scan(step, jnp.zeros_like(local[:, 0]), (
        jnp.moveaxis(local, 1, 0), jnp.moveaxis(whole, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                          # [B, C, H, P, N]
    return y + jnp.einsum("bctn,bchpn,bcth->bcthp", cm, h_in, jnp.exp(cum))


# --- the kernels ------------------------------------------------------------


def _lane_is_first(width: int, half: int):
    """[1, width] bool: the lanes of a pair's first head."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) < half


def _column(tile, head):
    """Column ``head`` of tile [Q, H] as [Q, 1] (a masked lane reduction:
    ``head`` may be a traced scalar)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile.shape[1]), 1)
    return jnp.sum(jnp.where(lane == head, tile, 0.0), axis=1, keepdims=True)


def _last(row):
    """The chunk's last entry of row [1, Q] as [1, 1] (a masked lane
    reduction: Mosaic does not broadcast a slice taken at lane Q - 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _decay_block(col, row, seen):
    """L[t, s] = exp(cum_t - cum_s) for s <= t, else 0: [Q, Q] float32."""
    return jnp.exp(jnp.where(seen, col - row, NEG_INF))


_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product accumulated in float32. Float32 operands at full
    precision: the MXU's default for them is ONE bfloat16 pass, which is
    bfloat16 operands' product (a float32 run of the kernels is what
    holds the algorithm itself to the plain path on the chip)."""
    full = a.dtype == b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if full else None)


def _fwd_kernel(u_ref, b_ref, c_ref, col_ref, row_ref, y_ref, hin_ref, h_scr,
                *, heads: int, width: int):
    """One instance per (batch, head block, chunk), chunks in order.
    u_ref [1, Q, heads x P]; b_ref, c_ref [1, Q, N]; col_ref [1, Q, H];
    row_ref [1, heads, Q]; y_ref as u_ref; hin_ref [1, 1, heads x P, N]
    float32; h_scr [heads x P, N] float32, the state entering the chunk."""
    q = u_ref.shape[1]
    two = 2 * width
    mm = u_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    bm, cm = b_ref[0], c_ref[0]
    g = _dot(cm, bm, _NT)                                    # [Q, Q]
    seen = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    first = _lane_is_first(two, width)
    first_rows = jax.lax.broadcasted_iota(jnp.int32, (two, 1), 0) < width
    cols = col_ref[0]
    head0 = pl.program_id(1) * heads
    hin_ref[0, 0] = h_scr[...]
    for pair in range(heads // 2):
        lanes = slice(pair * two, (pair + 1) * two)
        u2 = u_ref[0, :, lanes]                              # [Q, 2P]
        h2 = h_scr[lanes, :]                                 # [2P, N]
        col = [_column(cols, head0 + 2 * pair + i) for i in (0, 1)]
        row = [row_ref[0, 2 * pair + i:2 * pair + i + 1, :] for i in (0, 1)]
        ys = [_dot((g * _decay_block(col[i], row[i], seen)).astype(mm), u2)
              for i in (0, 1)]
        y = jnp.where(first, ys[0], ys[1])
        y = y + _dot(cm, h2.astype(mm), _NT) * jnp.where(
            first, jnp.exp(col[0]), jnp.exp(col[1]))
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        last = [_last(r) for r in row]                        # [1, 1]
        to_end = jnp.where(first, jnp.exp(last[0] - col[0]),
                           jnp.exp(last[1] - col[1]))        # [Q, 2P]
        local = _dot((u2.astype(jnp.float32) * to_end).astype(mm), bm, _TN)
        h_scr[lanes, :] = h2 * jnp.where(
            first_rows, jnp.exp(last[0]), jnp.exp(last[1])) + local


def _bwd_kernel(u_ref, b_ref, c_ref, col_ref, row_ref, hin_ref, dy_ref,
                du_ref, db_ref, dc_ref, dcol_ref, drow_ref, dh_scr,
                *, heads: int, width: int):
    """The mirror image, chunks last to first; dh_scr [heads x P, N] is
    the gradient of the state LEAVING the chunk. db_ref, dc_ref
    [1, 1, Q, N] float32 are this head block's part (the caller sums the
    blocks); dcol_ref [1, 1, Q, H] float32 holds this block's heads in
    their own lanes and zeros elsewhere; drow_ref [1, heads, Q]."""
    q = u_ref.shape[1]
    two = 2 * width
    mm = u_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    bm, cm = b_ref[0], c_ref[0]
    g = _dot(cm, bm, _NT)
    seen = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    first = _lane_is_first(two, width)
    first_rows = jax.lax.broadcasted_iota(jnp.int32, (two, 1), 0) < width
    cols = col_ref[0]
    all_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, cols.shape[1]), 1)
    head0 = pl.program_id(1) * heads
    dg = jnp.zeros((q, q), f32)
    db = jnp.zeros(bm.shape, f32)
    dc = jnp.zeros(cm.shape, f32)
    dcols = jnp.zeros(cols.shape, f32)

    def half(i, a):                  # lanes of head i of the pair, else 0
        return jnp.where(first if i == 0 else ~first, a, 0.0)

    for pair in range(heads // 2):
        lanes = slice(pair * two, (pair + 1) * two)
        u2 = u_ref[0, :, lanes]
        dy2 = dy_ref[0, :, lanes]
        h2 = hin_ref[0, 0, lanes, :]                         # [2P, N] f32
        dh2 = dh_scr[lanes, :]
        col = [_column(cols, head0 + 2 * pair + i) for i in (0, 1)]
        row = [row_ref[0, 2 * pair + i:2 * pair + i + 1, :] for i in (0, 1)]
        last = [_last(r) for r in row]
        u2f, dy2f = u2.astype(f32), dy2.astype(f32)
        dus, dcol = [], []
        for i in (0, 1):
            decay = _decay_block(col[i], row[i], seen)
            m = g * decay
            dm = _dot(half(i, dy2f).astype(mm), u2, _NT)     # [Q(t), Q(s)]
            w = dm * m
            dg = dg + dm * decay
            dcol.append(jnp.sum(w, axis=1, keepdims=True))
            drow_ref[0, 2 * pair + i:2 * pair + i + 1, :] = \
                -jnp.sum(w, axis=0, keepdims=True)
            dus.append(_dot(m.astype(mm), dy2, _TN))         # M^T dy
        # what came before the chunk: y_t has exp(cum_t) h_in C_t
        grow = jnp.where(first, jnp.exp(col[0]), jnp.exp(col[1]))
        dye = dy2f * grow                                    # [Q, 2P]
        came = _dot(cm, h2.astype(mm), _NT)                  # [Q, 2P]
        dc = dc + _dot(dye.astype(mm), h2.astype(mm))
        dh_in = _dot(dye.astype(mm), cm, _TN)                # [2P, N]
        # the chunk's end state: h_out has exp(last - cum_s) u_s B_s^T
        to_end = jnp.where(first, jnp.exp(last[0] - col[0]),
                           jnp.exp(last[1] - col[1]))
        sent = _dot(bm, dh2.astype(mm), _NT) * to_end        # [Q, 2P]
        db = db + _dot((u2f * to_end).astype(mm), dh2.astype(mm))
        du_ref[0, :, lanes] = (jnp.where(first, dus[0], dus[1])
                               + sent).astype(du_ref.dtype)
        whole = jnp.where(first_rows, jnp.exp(last[0]), jnp.exp(last[1]))
        kept = dh2 * h2 * whole                              # [2P, N]
        for i in (0, 1):
            through = jnp.sum(half(i, u2f * sent), axis=1, keepdims=True)
            d_last = jnp.sum(through) + jnp.sum(jnp.where(
                first_rows if i == 0 else ~first_rows, kept, 0.0))
            d = dcol[i] + jnp.sum(half(i, dye * came), axis=1,
                                  keepdims=True) - through
            d = d + jnp.where(is_last, d_last, 0.0)
            dcols = jnp.where(all_lanes == head0 + 2 * pair + i, d, dcols)
        dh_scr[lanes, :] = dh_in + dh2 * whole
    dc_ref[0, 0] = dc + _dot(dg.astype(mm), bm)
    db_ref[0, 0] = db + _dot(dg.astype(mm), cm, _TN)
    dcol_ref[0, 0] = dcols


# --- a head as wide as a lane tile, with steps, in groups -------------------


def _fwd_kernel_tile(u_ref, b_ref, c_ref, col_ref, row_ref, y_ref, hin_ref,
                     h_scr, *, heads: int, width: int):
    """``_fwd_kernel`` for heads of whole lane tiles: the same blocks (a
    block's heads are of one group, b_ref and c_ref [1, Q, N] the group's),
    one head a step of the loop and no halves chosen by lane."""
    q = u_ref.shape[1]
    mm = u_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    bm, cm = b_ref[0], c_ref[0]
    g = _dot(cm, bm, _NT)                                    # [Q, Q]
    seen = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    cols = col_ref[0]
    head0 = pl.program_id(1) * heads
    hin_ref[0, 0] = h_scr[...]
    for i in range(heads):
        lanes = slice(i * width, (i + 1) * width)
        u, h = u_ref[0, :, lanes], h_scr[lanes, :]           # [Q, P], [P, N]
        col, row = _column(cols, head0 + i), row_ref[0, i:i + 1, :]
        last = _last(row)
        y = _dot((g * _decay_block(col, row, seen)).astype(mm), u) \
            + _dot(cm, h.astype(mm), _NT) * jnp.exp(col)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        local = _dot((u.astype(jnp.float32) * jnp.exp(last - col)).astype(mm),
                     bm, _TN)
        h_scr[lanes, :] = h * jnp.exp(last) + local


def _bwd_kernel_tile(u_ref, b_ref, c_ref, col_ref, row_ref, hin_ref, dy_ref,
                     du_ref, db_ref, dc_ref, dcol_ref, drow_ref, dh_scr,
                     *, heads: int, width: int):
    """``_bwd_kernel`` for heads of whole lane tiles, chunks last to first:
    the same blocks and results, one head a step of the loop."""
    q = u_ref.shape[1]
    mm = u_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    bm, cm = b_ref[0], c_ref[0]
    g = _dot(cm, bm, _NT)
    seen = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    cols = col_ref[0]
    all_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, cols.shape[1]), 1)
    head0 = pl.program_id(1) * heads
    dg = jnp.zeros((q, q), f32)
    db = jnp.zeros(bm.shape, f32)
    dc = jnp.zeros(cm.shape, f32)
    dcols = jnp.zeros(cols.shape, f32)
    for i in range(heads):
        lanes = slice(i * width, (i + 1) * width)
        u, dy = u_ref[0, :, lanes], dy_ref[0, :, lanes]      # [Q, P]
        h = hin_ref[0, 0, lanes, :]                          # [P, N] f32
        dh = dh_scr[lanes, :]
        col, row = _column(cols, head0 + i), row_ref[0, i:i + 1, :]
        last = _last(row)
        uf, hm, dhm = u.astype(f32), h.astype(mm), dh.astype(mm)
        decay = _decay_block(col, row, seen)
        m = g * decay
        dm = _dot(dy, u, _NT)                                # [Q(t), Q(s)]
        w = dm * m
        dg = dg + dm * decay
        drow_ref[0, i:i + 1, :] = -jnp.sum(w, axis=0, keepdims=True)
        # what came before the chunk: y_t has exp(cum_t) h_in C_t
        dye = dy.astype(f32) * jnp.exp(col)                  # [Q, P]
        came = _dot(cm, hm, _NT)                             # [Q, P]
        dc = dc + _dot(dye.astype(mm), hm)
        # the chunk's end state: h_out has exp(last - cum_s) u_s B_s^T
        to_end = jnp.exp(last - col)                         # [Q, 1]
        sent = _dot(bm, dhm, _NT) * to_end                   # [Q, P]
        db = db + _dot((uf * to_end).astype(mm), dhm)
        du_ref[0, :, lanes] = (_dot(m.astype(mm), dy, _TN)   # M^T dy
                               + sent).astype(du_ref.dtype)
        whole = jnp.exp(last)
        through = jnp.sum(uf * sent, axis=1, keepdims=True)
        d = jnp.sum(w, axis=1, keepdims=True) + jnp.sum(
            dye * came, axis=1, keepdims=True) - through
        d = d + jnp.where(is_last, jnp.sum(through)
                          + jnp.sum(dh * h * whole), 0.0)
        dcols = jnp.where(all_lanes == head0 + i, d, dcols)
        dh_scr[lanes, :] = _dot(dye.astype(mm), cm, _TN) + dh * whole
    dc_ref[0, 0] = dc + _dot(dg.astype(mm), bm)
    db_ref[0, 0] = db + _dot(dg.astype(mm), cm, _TN)
    dcol_ref[0, 0] = dcols


# --- a head as wide as a lane tile, keys of its own, a constant decay -------


def _steady(rate, q: int):
    """What a constant ``rate`` a step (a scalar of SMEM, negative) makes
    of a chunk of q steps, float32: the decay block L[t, s] = exp(rate (t
    - s)) for s <= t, else 0 [q, q]; exp(cum_t) = exp(rate (t + 1)) and
    exp(cum_last - cum_t) = exp(rate (q - 1 - t)) [q, 1]; exp(cum_last) =
    exp(rate q) [1, 1]."""
    f32 = jnp.float32
    t = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    decay = jnp.exp(jnp.where(t >= s, rate * (t - s).astype(f32), NEG_INF))
    at = t.astype(f32)
    return (decay, jnp.exp(rate * (at + 1.0)), jnp.exp(rate * (q - 1.0 - at)),
            jnp.exp(rate * jnp.full((1, 1), q, f32)))


def _fwd_kernel_wide(a_ref, u_ref, b_ref, c_ref, y_ref, hin_ref, h_scr, *,
                     heads: int, width: int):
    """``_fwd_kernel`` for heads of a lane tile each: a_ref [H] float32 in
    SMEM; u_ref [1, Q, heads x P]; b_ref, c_ref [1, Q, heads x N], a head's
    own N lanes; the rest as there. One head a step of the loop, every
    product a matmul of whole tiles."""
    q, n = u_ref.shape[1], b_ref.shape[2] // heads
    mm = u_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = jnp.zeros_like(h_scr)

    head0 = pl.program_id(1) * heads
    hin_ref[0, 0] = h_scr[...]
    for i in range(heads):
        lanes, own = (slice(i * width, (i + 1) * width),
                      slice(i * n, (i + 1) * n))
        u, bm, cm = u_ref[0, :, lanes], b_ref[0, :, own], c_ref[0, :, own]
        h = h_scr[lanes, :]                                  # [P, N]
        decay, grow, to_end, whole = _steady(a_ref[head0 + i], q)
        y = _dot((_dot(cm, bm, _NT) * decay).astype(mm), u) \
            + _dot(cm, h.astype(mm), _NT) * grow
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        local = _dot((u.astype(jnp.float32) * to_end).astype(mm), bm, _TN)
        h_scr[lanes, :] = h * whole + local


def _bwd_kernel_wide(a_ref, u_ref, b_ref, c_ref, hin_ref, dy_ref, du_ref,
                     db_ref, dc_ref, dh_scr, *, heads: int, width: int):
    """``_bwd_kernel`` for heads of a lane tile each: db_ref and dc_ref
    [1, Q, heads x N] in B's type are each head's own, nothing is left to
    sum; the rate is no argument of the program's loss, so nothing of
    ``cum`` is differentiated."""
    q, n = u_ref.shape[1], b_ref.shape[2] // heads
    mm = u_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    head0 = pl.program_id(1) * heads
    for i in range(heads):
        lanes, own = (slice(i * width, (i + 1) * width),
                      slice(i * n, (i + 1) * n))
        u, dy = u_ref[0, :, lanes], dy_ref[0, :, lanes]
        bm, cm = b_ref[0, :, own], c_ref[0, :, own]
        h = hin_ref[0, 0, lanes, :].astype(mm)               # [P, N]
        dh = dh_scr[lanes, :]
        decay, grow, to_end, whole = _steady(a_ref[head0 + i], q)
        m = _dot(cm, bm, _NT) * decay
        dg = (_dot(dy, u, _NT) * decay).astype(mm)           # [Q(t), Q(s)]
        dye = (dy.astype(f32) * grow).astype(mm)
        ue = (u.astype(f32) * to_end).astype(mm)
        sent = _dot(bm, dh.astype(mm), _NT) * to_end         # [Q, P]
        du_ref[0, :, lanes] = (_dot(m.astype(mm), dy, _TN)
                               + sent).astype(du_ref.dtype)
        dc_ref[0, :, own] = (_dot(dye, h) + _dot(dg, bm)).astype(dc_ref.dtype)
        db_ref[0, :, own] = (_dot(ue, dh.astype(mm))
                             + _dot(dg, cm, _TN)).astype(db_ref.dtype)
        dh_scr[lanes, :] = _dot(dye, cm, _TN) + dh * whole


# --- the block plan and the calls -------------------------------------------


def _stepped_blocks(q, heads, H, P, N, item):
    """Bytes of the backward call's blocks in the two layouts with steps
    (pairs and tile: the same blocks)."""
    return (3 * q * heads * P * item              # u, dy, du
            + 2 * q * N * item + 2 * q * N * 4    # B, C; dB, dC
            + 2 * q * H * 4 + 2 * heads * q * 4   # cum and its gradient
            + heads * P * N * 4)                  # h_in


def _vmem(blocks, q, heads, P, N):
    """An instance of the backward call: its blocks twice (Mosaic
    double-buffers), the state scratch, a head's [Q, Q] float32 values."""
    return 2 * blocks + heads * P * N * 4 + 8 * q * q * 4


def plan(*, S: int, H: int, P: int, N: int, chunk: int, dtype,
         impl: str, G: int = 1, steady: bool = False) -> dict:
    """The scan's block plan (also the attributes of ``ssd.plan``): how
    many heads an instance walks, the VMEM one instance of the backward
    call holds (its blocks twice, Mosaic double-buffers, the state scratch
    and the [Q, Q] float32 temporaries of a head) and the HBM bytes the
    two calls move for one head and sequence. A block's heads are of one
    of the G groups; ``steady`` (a constant decay a head, no steps) with
    a group a head takes the wide calls (``layout`` "wide": whole heads
    with their own B and C, on the chip a head whole lane tiles); heads of
    whole lane tiles with steps take the tile calls ("tile": as many heads
    a block, 16 at most, as ``TILE_VMEM`` holds); else "pairs"."""
    if H % G:
        raise ValueError(f"ssd_scan: {H} heads in {G} groups")
    wide = steady and G == H
    tile = not steady and P % LANE_TILE == 0
    item = jnp.dtype(dtype).itemsize
    q = chunk
    heads = min(WIDE_HEADS_PER_BLOCK if wide else HEADS_PER_BLOCK,
                H if wide else H // G)
    while (H if wide else H // G) % heads or (
            tile and heads > 1 and _vmem(_stepped_blocks(
                q, heads, H, P, N, item), q, heads, P, N) > TILE_VMEM):
        heads -= 1
    if wide:
        blocks = (3 * q * heads * P * item              # u, dy, du
                  + 4 * q * heads * N * item            # B, C; dB, dC
                  + heads * P * N * 4)                  # h_in
        hbm = S * (5 * P + 6 * N) * item + 2 * (S // q) * P * N * 4
    else:
        blocks = _stepped_blocks(q, heads, H, P, N, item)
        # a head's rows of u and y, forward; u, dy and du, backward; h_in
        # written and read
        hbm = S * P * item * 5 + 2 * (S // q) * P * N * 4
    return {"S": S, "chunk": q, "heads_per_block": heads, "path": impl,
            "groups": G, "heads_per_group": H // G,
            "layout": "wide" if wide else "tile" if tile else "pairs",
            "decay": "steady" if steady else "stepped", "state": N,
            "vmem_bytes": _vmem(blocks, q, heads, P, N)
            if impl == "pallas" else 0,
            "hbm_bytes_per_head": hbm if impl == "pallas" else 0}


def _specs(B, S, H, P, N, q, heads, groups):
    """B and C arrive [B, S, G x N]: a head block reads its group's N
    lanes (block ``h // blocks a group``; the one group's is block 0)."""
    wide = pl.BlockSpec((1, q, heads * P), lambda b, h, c: (b, c, h))
    per_group = H // groups // heads
    shared = pl.BlockSpec((1, q, N), (lambda b, h, c: (b, c, 0))
                          if groups == 1 else
                          (lambda b, h, c: (b, c, h // per_group)))
    col = pl.BlockSpec((1, q, H), lambda b, h, c: (b, c, 0))
    row = pl.BlockSpec((1, heads, q), lambda b, h, c: (b, h, c))
    state = pl.BlockSpec((1, 1, heads * P, N), lambda b, h, c: (b, c, h, 0))
    return wide, shared, col, row, state


def _backwards(spec, last):
    """The same block, its chunk index walked last to first."""
    index = spec.index_map

    def flipped(b, h, c):
        return index(b, h, last - c)

    return pl.BlockSpec(spec.block_shape, flipped)


def _kernels(width: int):
    """(forward, backward) of the stepped calls: a head a lane tile, or
    two heads a lane tile walked in pairs."""
    return (_fwd_kernel_tile, _bwd_kernel_tile) if width % LANE_TILE == 0 \
        else (_fwd_kernel, _bwd_kernel)


def _forward_call(u, bm, cm, col, row, *, chunk: int, heads: int, width: int,
                  groups: int):
    B, S, HP = u.shape
    H, N = col.shape[2], bm.shape[2] // groups
    wide, shared, colspec, rowspec, state = _specs(B, S, H, width, N, chunk,
                                                   heads, groups)
    call = pl.pallas_call(
        functools.partial(_kernels(width)[0], heads=heads, width=width),
        grid=(B, H // heads, S // chunk),
        in_specs=[wide, shared, shared, colspec, rowspec],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((B, S // chunk, HP, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads * width, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
    )
    with jax.named_scope("ssd.fwd.pallas"):         # ssd.plan's path
        return call(u, bm, cm, col, row)


def _backward_call(u, bm, cm, col, row, h_in, dy, *, chunk: int, heads: int,
                   width: int, groups: int):
    B, S, HP = u.shape
    H, N = col.shape[2], bm.shape[2] // groups
    blocks, n_chunks = H // heads, S // chunk
    rev = functools.partial(_backwards, last=n_chunks - 1)
    wide, shared, colspec, rowspec, state = map(
        rev, _specs(B, S, H, width, N, chunk, heads, groups))
    part = rev(pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)))
    dcol = rev(pl.BlockSpec((1, 1, chunk, H), lambda b, h, c: (b, h, c, 0)))
    f32 = jnp.float32
    call = pl.pallas_call(
        functools.partial(_kernels(width)[1], heads=heads, width=width),
        grid=(B, blocks, n_chunks),
        in_specs=[wide, shared, shared, colspec, rowspec, state, wide],
        out_specs=[wide, part, part, dcol, rowspec],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((B, blocks, S, N), f32),
                   jax.ShapeDtypeStruct((B, blocks, S, N), f32),
                   jax.ShapeDtypeStruct((B, blocks, S, H), f32),
                   jax.ShapeDtypeStruct(row.shape, f32)],
        scratch_shapes=[pltpu.VMEM((heads * width, N), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
    )
    with jax.named_scope("ssd.bwd.pallas"):
        du, db, dc, dcols, drow = call(u, bm, cm, col, row, h_in, dy)
    # a block's heads stand in their own lanes of dcols, zeros elsewhere
    return (du, _over_blocks(db, groups).astype(bm.dtype),
            _over_blocks(dc, groups).astype(cm.dtype), dcols.sum(axis=1),
            drow)


def _over_blocks(parts, groups: int):
    """The head blocks' parts of dB or dC [B, blocks, S, N] summed over
    each group's blocks -> [B, S, G x N]."""
    if groups == 1:
        return parts.sum(axis=1)
    B, blocks, S, N = parts.shape
    return parts.reshape(B, groups, blocks // groups, S, N).sum(
        axis=2).transpose(0, 2, 1, 3).reshape(B, S, groups * N)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan_pallas(u, bm, cm, col, row, chunk, heads, width, groups):
    return _forward_call(u, bm, cm, col, row, chunk=chunk, heads=heads,
                         width=width, groups=groups)[0]


def _scan_pallas_fwd(u, bm, cm, col, row, chunk, heads, width, groups):
    y, h_in = _forward_call(u, bm, cm, col, row, chunk=chunk, heads=heads,
                            width=width, groups=groups)
    return y, (u, bm, cm, col, row, h_in)


def _scan_pallas_bwd(chunk, heads, width, groups, res, dy):
    return _backward_call(*res, dy.astype(res[0].dtype), chunk=chunk,
                          heads=heads, width=width, groups=groups)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def _wide_specs(P, N, q, heads):
    """The wide calls' blocks: the [H] rates whole in SMEM, ``heads`` whole
    heads of u (and y, dy, du) and of B and C (and dB, dC), their states."""
    rates = pl.BlockSpec(memory_space=pltpu.SMEM)
    wide = pl.BlockSpec((1, q, heads * P), lambda b, h, c: (b, c, h))
    own = pl.BlockSpec((1, q, heads * N), lambda b, h, c: (b, c, h))
    state = pl.BlockSpec((1, 1, heads * P, N), lambda b, h, c: (b, c, h, 0))
    return rates, wide, own, state


def _forward_call_wide(u, bm, cm, a, *, chunk: int, heads: int, width: int):
    B, S, HP = u.shape
    H = HP // width
    N = bm.shape[2] // H
    rates, wide, own, state = _wide_specs(width, N, chunk, heads)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel_wide, heads=heads, width=width),
        grid=(B, H // heads, S // chunk),
        in_specs=[rates, wide, own, own],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((B, S // chunk, HP, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads * width, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
    )
    with jax.named_scope("ssd.fwd.pallas"):         # ssd.plan's path
        return call(a, u, bm, cm)


def _backward_call_wide(u, bm, cm, a, h_in, dy, *, chunk: int, heads: int,
                        width: int):
    B, S, HP = u.shape
    H = HP // width
    N = bm.shape[2] // H
    rates, *rest = _wide_specs(width, N, chunk, heads)
    wide, own, state = map(
        functools.partial(_backwards, last=S // chunk - 1), rest)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel_wide, heads=heads, width=width),
        grid=(B, H // heads, S // chunk),
        in_specs=[rates, wide, own, own, state, wide],
        out_specs=[wide, own, own],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(cm.shape, cm.dtype)],
        scratch_shapes=[pltpu.VMEM((heads * width, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
    )
    with jax.named_scope("ssd.bwd.pallas"):
        return call(a, u, bm, cm, h_in, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _scan_wide(u, bm, cm, a, chunk, heads, width):
    return _forward_call_wide(u, bm, cm, a, chunk=chunk, heads=heads,
                              width=width)[0]


def _scan_wide_fwd(u, bm, cm, a, chunk, heads, width):
    y, h_in = _forward_call_wide(u, bm, cm, a, chunk=chunk, heads=heads,
                                 width=width)
    return y, (u, bm, cm, a, h_in)


def _scan_wide_bwd(chunk, heads, width, res, dy):
    u, bm, cm, a, h_in = res
    du, db, dc = _backward_call_wide(u, bm, cm, a, h_in, dy.astype(u.dtype),
                                     chunk=chunk, heads=heads, width=width)
    return du, db, dc, jnp.zeros_like(a)        # the rate is a constant


_scan_wide.defvjp(_scan_wide_fwd, _scan_wide_bwd)


def _spread(heads: int, width: int):
    """[H, H x width] float32, 1 where the lane is the head's."""
    return jnp.repeat(jnp.eye(heads, dtype=jnp.float32), width, axis=1)


def _over_lanes(per_head, width: int):
    """[B, S, H] float32 -> [B, S, H x width]: a head's value on each of
    its lanes. A product with a 0/1 matrix, not a broadcast and a reshape:
    the TPU's tiles hold 128 lanes, two heads of 64, and XLA writes such a
    broadcast out as a float32 array of its own and copies it into the
    rows' layout."""
    return jnp.einsum("bsh,hl->bsl", per_head,
                      _spread(per_head.shape[-1], width),
                      precision=jax.lax.Precision.HIGHEST)


def _per_head(wide, heads: int):
    """[B, S, H x width] float32 -> [B, S, H]: the sum over a head's lanes,
    the transpose of ``_over_lanes`` and the same product."""
    return jnp.einsum("bsl,hl->bsh", wide,
                      _spread(heads, wide.shape[-1] // heads),
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _prologue(x, dt, a, chunk):
    """What both paths take in place of x [B, S, H x P], dt [B, S, H] and
    a [H]: u = dt x in x's shape and type, and the running sum of dt a
    inside each chunk in the two layouts the kernel reads, col [B, S, H]
    and row [B, H, S], float32. Its gradient is written out below, not
    transposed by jax: the product ``du x`` is summed over a head's lanes
    in the pass that makes it."""
    f32 = jnp.float32
    step = dt.astype(f32) * a.astype(f32)                    # [B, S, H] < 0
    col = jnp.cumsum(_chunks(step, chunk), axis=2).reshape(step.shape)
    width = x.shape[-1] // dt.shape[-1]
    u = (x.astype(f32) * _over_lanes(dt.astype(f32), width)).astype(x.dtype)
    return u, col, col.transpose(0, 2, 1)


def _prologue_fwd(x, dt, a, chunk):
    return _prologue(x, dt, a, chunk), (x, dt, a)


def _prologue_bwd(chunk, res, grads):
    x, dt, a = res
    du, dcol, drow = grads
    f32 = jnp.float32
    heads = dt.shape[-1]
    # dt over the lanes again, not the forward's product kept in float32
    wide = _over_lanes(jax.lax.optimization_barrier(dt.astype(f32)),
                       x.shape[-1] // heads)
    dx = (du.astype(f32) * wide).astype(x.dtype)
    dcum = _chunks(dcol + drow.transpose(0, 2, 1), chunk)
    # the transpose of a running sum: the running sum from the chunk's end
    dstep = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2).reshape(
        dt.shape)
    ddt = _per_head(du.astype(f32) * x.astype(f32), heads) \
        + dstep * a.astype(f32)
    da = jnp.sum(dstep * dt.astype(f32), axis=(0, 1))
    return dx, ddt.astype(dt.dtype), da.astype(a.dtype)


_prologue.defvjp(_prologue_fwd, _prologue_bwd)


def ssd_scan(x, dt, a, bm, cm, *, chunk: int = 256, impl: str = "xla"):
    """x [B, S, H, P], dt [B, S, H] (positive: after its softplus), a [H]
    (negative), bm and cm [B, S, N] (one group) or [B, S, G, N] -> y
    [B, S, H, P] in x's type, the recurrence of the module docstring from
    a zero state, without the skip term. Differentiable in all five on
    both paths. ``dt`` None: every step is 1 and ``a`` a constant of the
    model, a head's decay ``exp(a)`` a step (differentiable in x, bm and
    cm; nothing [B, S, H] is made).

    The Pallas path takes three layouts (``plan``): "pairs", heads of 64
    with steps, an even number of them a group (a lane tile a pair);
    "tile", heads of a multiple of 128 with steps, in groups, a state of
    any size; and "wide", heads of a multiple of 128 with a B and a C each
    and a constant decay (``dt`` None, G == H)."""
    B, S, H, P = x.shape
    G = 1 if bm.ndim == 3 else bm.shape[2]
    if bm.ndim == 4 and G == 1:         # one group, stated: the same call
        bm, cm = bm[:, :, 0], cm[:, :, 0]
    if S % chunk:
        raise ValueError(f"ssd_scan: {S} steps are no multiple of the chunk "
                         f"{chunk}; pad upstream")
    if impl not in ("xla", "pallas"):
        raise ValueError(f"ssd_scan impl must be 'xla' or 'pallas', got "
                         f"{impl!r}")
    p = plan(S=S, H=H, P=P, N=bm.shape[-1], chunk=chunk, dtype=x.dtype,
             impl=impl, G=G, steady=dt is None)
    tracing.plan("ssd.plan", p)
    f32 = jnp.float32
    takes = ("the kernel takes three layouts: 'pairs', heads of 64 with "
             "steps, an even number of them a group (a block of 2 to 16 "
             "heads of one group); 'tile', heads of a multiple of 128 with "
             "steps, in groups (a block of 8 or 16 heads of one group, or "
             "all the heads); 'wide', heads of a multiple of 128 with a B "
             "and a C each (G == H) and a constant decay (dt None)")
    if dt is None:
        a = jax.lax.stop_gradient(a.astype(f32))
        if impl == "pallas":
            if p["layout"] != "wide" or (P % 128 and not _use_interpret()):
                raise ValueError(f"ssd_scan: a constant decay at {H} heads "
                                 f"of {P} in {G} groups; {takes}")
            flat = lambda t: t.astype(x.dtype).reshape(B, S, -1)  # noqa: E731
            y = _scan_wide(flat(x), flat(bm), flat(cm), a, chunk,
                           p["heads_per_block"], P)
            return y.reshape(B, S, H, P)
        u = x.reshape(B, S, H * P)
        inside = (jnp.arange(S) % chunk + 1).astype(f32)  # steps into a chunk
        col = jnp.broadcast_to(inside[None, :, None] * a, (B, S, H))
    else:
        u, col, row = _prologue(x.reshape(B, S, H * P), dt, a, chunk)
    if impl == "xla":
        with jax.named_scope("ssd.fwd.xla"):    # jax transposes it itself
            args = (_chunks(u.reshape(x.shape), chunk).astype(f32),
                    _chunks(bm, chunk).astype(f32),
                    _chunks(cm, chunk).astype(f32), _chunks(col, chunk))
            if bm.ndim == 3:
                y = _scan_xla(*args)
            else:       # a group's heads with the group's B and C
                u5, bg, cg, cum = args
                by_group = lambda a: a.reshape(              # noqa: E731
                    *a.shape[:3], G, H // G, *a.shape[4:])
                y = jax.vmap(_scan_xla, in_axes=3, out_axes=3)(
                    by_group(u5), bg, cg, by_group(cum))
        return y.reshape(B, S, H, P).astype(x.dtype)
    heads = p["heads_per_block"]
    # pairs walk two heads a lane tile; a tile block of cum's rows
    # [heads, Q] is whole sublane tiles or the whole array
    fits = heads % 2 == 0 if p["layout"] == "pairs" else (
        heads % 8 == 0 or heads == H or _use_interpret())
    if not fits:
        raise ValueError(f"ssd_scan: {H} heads of {P} in {G} groups give a "
                         f"block of {heads}; {takes}")
    flat = lambda a: a.astype(x.dtype) if a.ndim == 3 else \
        a.astype(x.dtype).reshape(B, S, -1)                  # noqa: E731
    y = _scan_pallas(u, flat(bm), flat(cm), col, row, chunk, heads, P, G)
    return y.reshape(B, S, H, P)
