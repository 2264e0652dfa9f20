"""Chunked gated delta rule (the recurrence of Kimi Delta Attention,
arXiv:2510.26692) for TPU in Pallas, with its backward.

For every head with a [dk, dv] state S, step by step over a sequence:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(q_t, k_t [dk]; v_t [dv]; g_t [dk] <= 0 the log of a decay a KEY CHANNEL;
beta_t in (0, 2) a head: over 1 the eigenvalue 1 - beta_t of I - beta_t k_t
k_t^T along a unit k_t is negative, Kimi Linear's ``allow_neg_eigval``, and
the state stays bounded while it lies in (-1, 1)). The state is first
decayed channel by channel,
then what it holds along k_t is erased by beta_t and beta_t k_t v_t^T is
written: with u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t) it reads
S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T. ``ops/ssd.py`` computes neither
term: its decay is one scalar a head and it erases nothing.

Written in chunks of C steps (the WY form of the delta rule), ``G_t`` the
running sum of g inside the chunk, S the state entering it:

    Akk[t, s] = sum_c k_tc k_sc exp(G_tc - G_sc)     s <  t
    Aqk[t, s] = sum_c q_tc k_sc exp(G_tc - G_sc)     s <= t
    X  = (I + Diag(beta) Akk)^-1                     unit lower triangular
    V' = X Diag(beta) (V - (K * exp(G)) S)           the u_t of the chunk
    O  = (Q * exp(G)) S + Aqk V'
    S' = Diag(exp(G_C)) S + (K * exp(G_C - G))^T V'

The decay between two steps is a VECTOR over the key channels, so there is
no [C, C] decay block a head to multiply ``K K^T`` by: exp(G_tc - G_sc) has
to be split into a factor of t and a factor of s, and exp(-G_s) alone
overflows float32 after 18 steps at g = -5. The pair products are made a
SUB-BLOCK of ``SUB`` = 16 rows at a time, both factors taken from the
running sum r at the sub-block's MIDDLE (its row 7): rows carry exp(G_t -
r), columns exp(r - G_s). At g >= -5 a step both lie within exp(+-40) =
2.4e17 for every pair inside the sub-block (8 x 5 = 40 < 88, the most
float32's exp takes; a sum over 128 channels of products of two such
factors stays under 1e37), and a column BEFORE the sub-block carries a
factor <= 1 that may underflow to 0 where the true product is under
exp(-47). Taken from the sub-block's START the factors would reach exp(-80)
and exp(80): finite, but the low parts of a float32 product at 1e-36 are
denormal and flushed, and the last row of a sub-block held at the bound
read 0.6% off. That is what the caller's bound on g buys (``lower_bound``,
-5 a step and channel: Ling's ``kda_lower_bound`` with ``kda_safe_gate``);
``SUB`` / 2 x |lower_bound| must stay under 88 and the op refuses a bound
that does not. Columns after the sub-block are masked out of the result
and their exponent is clamped.

A caller that states NO bound (``lower_bound`` None: Kimi Linear's first
gate, g = -exp(A_log) softplus(.), a step of which can decay a channel by
exp(-100)) gets a cut that needs none (``_pair_blocks_free``), because no
factor of it passes 1. The pairs s < t of a chunk are cut in HALVES: for t
in the later half and s in the earlier one exp(G_t - G_s) is split at the
earlier half's last row m, exp(G_t - G_m) exp(G_m - G_s), both exponents
sums of g <= 0; each half is cut again the same way, down to blocks of one
row, and the diagonal of Aqk is q_t . k_t itself. A pair belongs to the one
level at which the highest differing bit of t and s lies. A level keeps t
in the ODD halves of its blocks and s in the even ones, so from blocks of
``VECTOR_ROWS`` = 8 rows (a sublane tile) a level is ONE product of the odd
halves' rows of q and k alone, gathered as whole tiles, against every
column ([C, dk] x [dk, C], masked to the level's pairs): log2(C) - 3 = 3
products a head and chunk of 64 where the bounded cut has 4, and 6 more on
the way back where it has 8 (the level's gradient blocks are zero outside
those rows). The levels of 1, 2 and 4 rows are the seven subdiagonals
INSIDE a sublane tile and take no product (``_inside_blocks``): the pair d
steps apart is sum_c q_tc k_(t-d)c exp(g summed over the d rows between),
a row shift, exact float32 multiplies and a lane sum a subdiagonal on the
vector unit, its ONE factor exp of a sum of at most seven g <= 0 made by
adds (``_shifted_sums``). A factor
can underflow, and where either does the true product is under float32's
least: a channel that dies in one step reads as nothing, not as NaN or inf,
forward and backward (the gradient at an underflowed pair is the pair's
value, 0). The sums the factors are made of (``_aligned_sums``: the running
sum inside aligned blocks of 1, 2, 4 .. rows, and each block's even half's
total) are made bottom up by adds, so a sum over few rows is the sum of few
terms and not the difference of two running sums of the chunk (at -60 a
step a chunk's running sum reaches -3,800, where a float32 ulp is 2e-4);
the chunk's running sum is the last level. On a v5e the two calls at [1,
16384, 64, 128] bfloat16, every array an argument of the timed program,
take 21.6 ms forward and 36.0 backward on this cut against 18.7 and 31.5
on the bounded one over the same inputs (10.9 / 18.1 against 9.5 / 15.9 at
32 heads over bounded inputs; PR 61's form, six whole products a level of
every row with every column and twelve on the way back, took 28.8 and
53.0: PERF.md 6, PR 66, where every form that was timed is listed; they
are kept in ``tests/delta_rule_cut_forms.py``), so a caller that CAN state
a bound keeps the cut it had, with the program it had. What the cut's time
followed was neither the count of its products nor the rows they stream
(``plan()``'s ``mxu_rows_*``; the form with the fewest rows was not the
fastest) but the work of the vector unit round them: the factors, the
three bfloat16 parts of every float32 operand, the masks and selects.

The inverse of the unit lower triangular [C, C] matrix is made by block
forward substitution with products only (a TPU has no triangular solve):
from blocks of one (the identity) the diagonal blocks double, inv([[P1,
0], [A21, P2]]) = [[P1, 0], [-P2 A21 P1, P2]], which for all blocks at
once is P <- P - P M P with M the lower-left halves: log2(C) rounds. No
power of A is ever formed (the product form (I - A)(I + A^2)(I + A^4).. is
exact too, but its powers grow like binomials where adjacent keys are
alike). The rounds are the same products for beta up to 2; what grows is
their float32 rounding where adjacent keys are alike: with EVERY key the
same, no decay and beta 1.99 (I + Diag(beta) Akk is 1.99 in every entry
under the diagonal, its inverse alternates at 1.99 x (-0.99)^n, and a
round's sums of 32 terms of size 4 cancel to it) an entry of the inverse of
side 64 reads 2.5e-5 off (6e-8 at beta 0.97, 6e-8 at 1.5) and the output's
relative L2 distance to the step-by-step recurrence in float64 is 2.5e-5 on
the CPU and 1.8e-5 on the chip (S 256; PR 61), where ``solve_triangular``
reads 2e-6; on a model's seeded inputs with beta = 2 sigmoid(.) the calls
read 5e-7 to 2.4e-6 off the plain path in float32 (part (e) of the Solar
cell, [1, 16384, 64, 128]). On a v5e the two calls' time is the CHAIN of these rounds, each
waiting on the last, far more than the size of their products (about 0.2
us a [128, 128] float32 product at ``HIGHEST`` in the chain, 0.14 us a
[64, 64] one, 0.05 us a pair product, which waits on nothing: PERF.md 6,
PR 59), so the rounds are cut to the fewest and widest products that are
the same mathematics at the same precision:

- the first round's P is the identity, so P - P M P is I - M: no product;
- the second and third round (from blocks of 2 and of 4) are
  ``_shifted_round``: P has one subdiagonal, then three, so M P and P (M P)
  are a lane shift and a row shift a subdiagonal with a float32
  multiply-add each on the vector unit (exact float32 products, where
  ``HIGHEST`` is six bfloat16 passes); from blocks of 8 the seven shifts a
  side cost more than the two products they spare;
- the other log2(C) - 3 rounds are two float32 products each, as wide as
  the MXU's tile: the heads of a kernel instance stand two (at C = 64)
  side by side in ONE block-diagonal [128, 128] matrix whose rounds stop
  at the chunk, so a round is two products for both heads, and what lies
  between the heads stays exactly 0;
- from blocks of 8 rows (a sublane tile) a round multiplies the rows of
  the odd blocks alone: P M P is zero in the others, so half the rows go
  through the two products, bit for bit the same.

A head and chunk of 64 steps then costs 7 float32 products forward (4 of
the pair blocks, half of 6 of the inverse) where a [64, 64] inverse a head
with all six rounds cost 17, and 15 backward (8 more on the way back
through the pair blocks) for 25. A chunk of 128 with a head an inverse
counts the same products a step and timed 6 ms a layer and step behind
(twice the pair products' columns, and the float32 reading of dg twice as
far from the plain path: the running sum reaches twice as far).

Two paths, chosen by the caller the way ``attn_impl`` chooses "xla" or
"flash":

- ``"xla"``: a ``lax.scan`` over the chunks, each chunk plain einsums over
  the [C, C, dk] decay array and ``solve_triangular``; the chunk's body is
  checkpointed, so the backward keeps the chunks' incoming states and
  rebuilds the rest. The CPU tests, a mesh, small sizes. Gradients are
  jax's own.
- ``"pallas"``: one Mosaic call forward and one backward, grid (batch, head
  block, chunk), a block ``HEADS_PER_BLOCK`` heads (four or eight a block
  time 2 and 3 ms a layer and step better, and the step program, which
  compiles each of its 21 calls, 8 and 23 s longer). The [C, C]
  blocks, the inverse and the state live in VMEM; the chunks of a sequence
  are walked in order (the backward last to first) with the state between
  them carried in VMEM scratch (float32, stored [dv, dk] so that the
  channel decay runs along the lanes), and the only state that reaches HBM
  is what the backward needs: each chunk's incoming state ([B, S / C, H x
  dv, dk] float32), written by the forward rule and read once. The
  backward rebuilds G, the pair blocks, the inverse and V' from the
  chunk's inputs and that state. The running sums (G down the rows, dg's
  up them) are log2(C) shifted adds of float32 rows on the vector unit, no
  product: the sum heads the chunk's chain of dependent products, and as
  a product with the 0/1 triangle it timed 5 ms a layer and step behind
  (by three passes, which is exact: the triangle has no middle and low
  part in bfloat16; 6 ms by the six of ``HIGHEST``). The pair blocks and
  the inverse are float32 products at ``HIGHEST``; products that contract
  over the state or the steps take the inputs' type (bfloat16 in a model;
  float32 inputs are multiplied at ``HIGHEST`` too) and accumulate in
  float32. A Mosaic call cannot be partitioned by GSPMD; the caller
  refuses a mesh of several devices. Interpret mode off the TPU.

Sequences that are no multiple of the chunk are padded at their end with
steps that change nothing (k = 0, beta = 0, g = 0) and the outputs cut.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.util import tracing

CHUNK = 64                 # steps a chunk (the source names none)
SUB = 16                   # rows a sub-block of the pair products
HEADS_PER_BLOCK = 2        # heads one kernel instance walks
_SIDE = 128                # the MXU's tile: the side an inverse fills
SHIFTED_BLOCKS = 4         # the inverse's rounds from blocks of up to so many
#                            rows are shifted multiply-adds, no product
HALF_ROWS = 8              # from blocks of so many rows (a sublane tile) a
#                            round multiplies the odd blocks' rows alone
VECTOR_ROWS = 8            # the cut in halves: the pairs inside aligned blocks
#                            of so many rows (the levels under it) are shifted
#                            multiply-adds, a level from it up one product of
#                            the odd halves' rows
_EXP_MOST = 88.0           # exp of more overflows float32
# checkpoint_name tags of what the kernel path's forward rule hands its
# backward beside the inputs: the output and the chunks' incoming states. A
# layer checkpoint that keeps BOTH runs no forward call in its replay
RESIDUALS = ("kda_out", "kda_states")
_HIGHEST = jax.lax.Precision.HIGHEST


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _clamp(lower_bound: float, sub: int = SUB) -> float:
    """The most a pair's factor's exponent reaches inside a sub-block of
    ``sub`` rows whose factors are taken from its middle row."""
    most = sub // 2 * -lower_bound
    if not lower_bound < 0 or most >= _EXP_MOST:
        raise ValueError(
            f"gated_delta_rule: a gate down to {lower_bound} a step over "
            f"sub-blocks of {sub} steps: exp({most:g}) overflows float32 "
            f"(want sub / 2 x |lower_bound| < {_EXP_MOST:g})")
    return float(most)


# --- the plain path ---------------------------------------------------------


def _chunk_xla(state, q, k, v, g, beta):
    """One chunk for every batch and head: state [B, H, dk, dv]; q, k, g
    [B, H, C, dk]; v [B, H, C, dv]; beta [B, H, C], all float32 ->
    (the state leaving the chunk, o [B, H, C, dv])."""
    c = q.shape[2]
    cum = jnp.cumsum(g, axis=2)
    seen = jnp.tril(jnp.ones((c, c), bool))
    before = jnp.tril(jnp.ones((c, c), bool), -1)
    decay = jnp.exp(jnp.where(
        seen[:, :, None], cum[:, :, :, None, :] - cum[:, :, None, :, :],
        -jnp.inf))                                        # [B, H, t, s, dk]
    akk = jnp.where(before, jnp.einsum("bhtc,bhsc,bhtsc->bhts", k, k, decay),
                    0.0)
    aqk = jnp.einsum("bhtc,bhsc,bhtsc->bhts", q, k, decay)
    grown = jnp.exp(cum)
    rest = v - jnp.einsum("bhtc,bhcv->bhtv", k * grown, state)
    unit = jnp.eye(c, dtype=g.dtype) + beta[..., None] * akk
    new = jax.scipy.linalg.solve_triangular(
        unit, beta[..., None] * rest, lower=True, unit_diagonal=True)
    o = jnp.einsum("bhtc,bhcv->bhtv", q * grown, state) \
        + jnp.einsum("bhts,bhsv->bhtv", aqk, new)
    last = cum[:, :, -1:, :]
    state = jnp.exp(last)[:, :, 0, :, None] * state + jnp.einsum(
        "bhtc,bhtv->bhcv", k * jnp.exp(last - cum), new)
    return state, o


def _scan_xla(q, k, v, g, beta, state, chunk: int):
    """q, k, g [B, S, H, dk], v [B, S, H, dv], beta [B, S, H], state
    [B, H, dk, dv] -> o [B, S, H, dv], all float32."""
    B, S, H, _ = q.shape

    def chunks(a):          # [B, S, H, ...] -> [S / C, B, H, C, ...]
        a = a.reshape(B, S // chunk, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    body = jax.checkpoint(lambda s, x: _chunk_xla(s, *x))
    _, o = jax.lax.scan(body, state, tuple(map(chunks, (q, k, v, g, beta))))
    return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, S, H, -1)


# --- the kernels ------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b


_TALLIES: list = []     # open counts [float32 products, MXU passes, rows
#                         streamed x passes]: plan()


def _count(f32: int, passes: int, a, dims):
    """Counts a product the kernels' code emits while ``plan()`` traces:
    ``passes`` bfloat16 passes of the MXU, each streaming the rows of ``a``
    (its free dimension under ``dims``) against the other operand."""
    for tally in _TALLIES:
        tally[0] += f32
        tally[1] += passes
        tally[2] += passes * a.shape[1 - dims[0][0][0]]


def _dot(a, b, dims, mm):
    """A product in the inputs' type ``mm``, accumulated in float32. For
    float32 inputs at full precision: the MXU's default for float32
    operands is ONE bfloat16 pass, which is bfloat16 inputs' product."""
    if mm == jnp.float32:
        return _dot32(a, b, dims)
    _count(0, 1, a, dims)
    return jax.lax.dot_general(a.astype(mm), b.astype(mm), dims,
                               preferred_element_type=jnp.float32)


def _dot32(a, b, dims=_NN):
    """A float32 product at full precision (the pair blocks, the inverse):
    six bfloat16 passes of the MXU."""
    _count(1, 6, a, dims)
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _rows_cols(c: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0),
            jax.lax.broadcasted_iota(jnp.int32, (1, c), 1))


def _column(tile, i: int):
    """Column ``i`` of tile [C, n] as [C, 1]: a masked lane reduction."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile.shape[1]), 1)
    return jnp.sum(jnp.where(lane == i, tile, 0.0), axis=1, keepdims=True)


def _running_sum(x, from_end: bool = False):
    """x [C, n] float32 -> its running sum down the rows (up them,
    ``from_end``) by log2(C) shifted adds on the vector unit: after the
    round of shift 2^j a row holds the sum of the 2^(j + 1) rows that end
    (start) at it. No product (module docstring)."""
    c = x.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    shift = 1
    while shift < c:
        if from_end:
            x = x + jnp.where(t < c - shift, pltpu.roll(x, c - shift, 0), 0.0)
        else:
            x = x + jnp.where(t >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _sub_factors(cum, lo: int, sub: int, clamp: float):
    """The two factors of exp(G_t - G_s) for the rows ``lo .. lo + sub`` of
    a chunk: (rows' exp(G_t - r) [sub, dk], columns' exp(min(r - G_s,
    clamp)) [C, dk]) with r the running sum at the sub-block's middle."""
    mid = lo + sub // 2 - 1
    middle = cum[mid:mid + 1]
    return (jnp.exp(cum[lo:lo + sub] - middle),
            jnp.exp(jnp.minimum(middle - cum, clamp)))


def _pair_blocks(q, k, cum, sub: int, clamp: float):
    """(Aqk [C, C] with s <= t, Akk [C, C] with s < t) from float32 q, k
    and the running sums cum [C, dk], a sub-block of rows at a time."""
    c = k.shape[0]
    qk, kk = [], []
    for lo in range(0, c, sub):
        rows, cols = _sub_factors(cum, lo, sub, clamp)
        both = jnp.concatenate([q[lo:lo + sub] * rows, k[lo:lo + sub] * rows])
        block = _dot32(both, k * cols, _NT)                  # [2 sub, C]
        qk.append(block[:sub])
        kk.append(block[sub:])
    t, s = _rows_cols(c)
    return (jnp.where(t >= s, jnp.concatenate(qk), 0.0),
            jnp.where(t > s, jnp.concatenate(kk), 0.0))


def _pair_grads(q, k, cum, d_qk, d_kk, sub: int, clamp: float):
    """The way back through ``_pair_blocks``: d_qk and d_kk [C, C] (zero
    where the blocks are masked) -> (dq, dk, dcum) [C, dk] float32."""
    c = k.shape[0]
    dq, dk_rows, dcum_rows = [], [], []
    dk_cols = jnp.zeros_like(k)
    for lo in range(0, c, sub):
        rows, cols = _sub_factors(cum, lo, sub, clamp)
        qs, ks = q[lo:lo + sub], k[lo:lo + sub]
        d_both = jnp.concatenate([d_qk[lo:lo + sub], d_kk[lo:lo + sub]])
        back = _dot32(d_both, k * cols) * jnp.concatenate([rows, rows])
        dq.append(back[:sub])
        dk_rows.append(back[sub:])
        dcum_rows.append(qs * back[:sub] + ks * back[sub:])
        both = jnp.concatenate([qs * rows, ks * rows])
        dk_cols = dk_cols + _dot32(d_both, both, _TN) * cols
    return (jnp.concatenate(dq), jnp.concatenate(dk_rows) + dk_cols,
            jnp.concatenate(dcum_rows) - k * dk_cols)


def _aligned_sums(g):
    """g [C, dk] float32 -> ([(P_h, E_h) for h = 1, 2, 4 .. C / 2], the
    running sum G down the whole chunk): the sums the cut that needs no
    bound on g splits exp(G_t - G_s) at. P_h[t] is the sum of g over the
    rows up to and including t of t's ALIGNED block of h rows; E_h[t] the
    total of the even half of t's aligned block of 2 h rows (P_h at that
    half's last row), the same in every row of the block. Made bottom up,
    P_2h = P_h + E_h in the odd halves, so a sum over few rows is the sum
    of few terms and not the difference of two long running sums; G is
    P_C. Blocks inside a sublane tile are gathered by row shifts and
    selects, whole tiles by a row broadcast."""
    c = g.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    p, h, levels = g, 1, []
    while h < c:
        if 2 * h < 8:
            at = t & (2 * h - 1)
            e = jnp.where(at == h - 1, p, 0.0)
            for j in range(2 * h):
                if j != h - 1:  # row t + (h - 1 - j), inside t's block
                    e = jnp.where(at == j,
                                  pltpu.roll(p, (j - (h - 1)) % c, 0), e)
        else:
            e = jnp.concatenate([
                jnp.broadcast_to(p[lo + h - 1:lo + h], (2 * h, p.shape[1]))
                for lo in range(0, c, 2 * h)])
        levels.append((p, e))
        p = p + jnp.where((t & h) != 0, e, 0.0)
        h *= 2
    return levels, p


def _level(t, s, h: int):
    """The pairs s < t a cut of blocks of 2 h rows splits: t and s agree
    above bit h, which t has and s has not."""
    return ((t ^ s) < 2 * h) & ((t & ~s & h) != 0)


def _level_factors(p, e):
    """The two factors of exp(G_t - G_s) for t in the odd half and s in
    the even half of an aligned block: (rows' exp(G_t - G_m), columns'
    exp(G_m - G_s)), m the even half's last row. Both exponents are sums
    of g <= 0 (the columns' clamped at 0 in the odd halves, which the
    level masks out), so neither factor passes 1 whatever the gate: they
    can underflow, and where either does the true product is under
    float32's least."""
    return jnp.exp(p), jnp.exp(jnp.minimum(e - p, 0.0))


def _halves(x, h: int, odd: bool):
    """The rows of the odd (even) halves of x's aligned blocks of 2 h rows,
    one under the other: whole sublane tiles from h = 8."""
    return jnp.concatenate([x[lo:lo + h] for lo in range(h if odd else 0,
                                                         x.shape[0], 2 * h)])


def _spread(x, h: int, odd: bool):
    """``_halves`` undone: x's rows back in the odd (even) halves, zeros in
    the others."""
    zero = jnp.zeros((h, x.shape[1]), x.dtype)
    pieces = [x[lo:lo + h] for lo in range(0, x.shape[0], h)]
    return jnp.concatenate([half for piece in pieces for half in (
        (zero, piece) if odd else (piece, zero))])


def _shifted_sums(g, rows: int):
    """[the sum of g over the d rows that end at each row, d = 1 .. rows -
    1]: the exponents of the pairs d steps apart, each made of the last by
    ONE add (a sum of d terms, not a difference of running sums). A row's
    sum that reaches over the chunk's first row wraps round: its pair lies
    in no block."""
    sums = [g]
    for d in range(1, rows - 1):
        sums.append(sums[-1] + pltpu.roll(g, d, 0))
    return sums


def _steps_apart(c: int, rows: int):
    """[C, C]: t - s at the pairs s < t inside aligned blocks of ``rows``
    rows, 0 elsewhere."""
    t, s = _rows_cols(c)
    return jnp.where(((t ^ s) < rows) & (t > s), t - s, 0)


def _inside_blocks(q, k, g, rows: int):
    """(Aqk, Akk) at the pairs s < t inside aligned blocks of ``rows`` <= 8
    rows (the levels of 1 .. rows / 2 rows together: the block's rows - 1
    subdiagonals), zero elsewhere, without a product: the pair d steps
    apart is sum_c q_tc k_(t-d)c exp(g summed over the d rows between), a
    row shift, exact float32 multiplies and a lane sum a subdiagonal on the
    vector unit. The one factor is exp of a sum of g <= 0."""
    c = k.shape[0]
    apart = _steps_apart(c, rows)
    aqk = akk = jnp.zeros((c, c), jnp.float32)
    for d, run in enumerate(_shifted_sums(g, rows), 1):
        w = pltpu.roll(k, d, 0) * jnp.exp(run)
        aqk = jnp.where(apart == d, jnp.sum(q * w, axis=1, keepdims=True),
                        aqk)
        akk = jnp.where(apart == d, jnp.sum(k * w, axis=1, keepdims=True),
                        akk)
    return aqk, akk


def _inside_blocks_grads(q, k, g, d_qk, d_kk, rows: int):
    """The way back through ``_inside_blocks``: (dq, k's gradient as a row,
    k's gradient as a column) [C, dk] of the pairs inside the blocks."""
    c = k.shape[0]
    apart = _steps_apart(c, rows)
    dq = dk_rows = dk_cols = jnp.zeros_like(k)
    for d, run in enumerate(_shifted_sums(g, rows), 1):
        a = jnp.sum(jnp.where(apart == d, d_qk, 0.0), axis=1, keepdims=True)
        b = jnp.sum(jnp.where(apart == d, d_kk, 0.0), axis=1, keepdims=True)
        decay = jnp.exp(run)
        w = pltpu.roll(k, d, 0) * decay                      # of k_(t-d)
        dq = dq + a * w
        dk_rows = dk_rows + b * w
        # k_(t-d)'s gradient is made at row t and moved up d rows
        dk_cols = dk_cols + pltpu.roll((a * q + b * k) * decay, c - d, 0)
    return dq, dk_rows, dk_cols


def _product_levels(levels):
    """(h, P_h, E_h) of the levels that are a product: those of
    ``VECTOR_ROWS`` rows and more."""
    return [(1 << i, p, e) for i, (p, e) in enumerate(levels)
            if 1 << i >= VECTOR_ROWS]


def _level_operands(q, k, p, e, h: int):
    """The two operands of a level of h >= 8 rows and their factors: ([q;
    k] of the odd halves' rows times exp(G_t - G_m) [C, dk], k times
    exp(G_m - G_s) [C, dk], the rows' factor [C / 2, dk], the columns' [C,
    dk])."""
    rows, cols = _level_factors(p, e)
    rows = _halves(rows, h, True)
    return (jnp.concatenate([_halves(q, h, True) * rows,
                             _halves(k, h, True) * rows]), k * cols, rows,
            cols)


def _pair_blocks_free(q, k, levels):
    """``_pair_blocks`` for a gate with no bound: the pairs s < t of a
    chunk are cut in halves, the quarter between the halves split at the
    halves' boundary (``_level_factors``), and the halves cut again down
    to blocks of one row. A level of ``VECTOR_ROWS`` rows or more is ONE
    product, the odd halves' rows of q and k (whole sublane tiles, the only
    rows the level keeps) against every column, masked to the level's
    pairs; the levels under it are ``_inside_blocks``. The diagonal of Aqk
    is q_t . k_t."""
    c = k.shape[0]
    t, s = _rows_cols(c)
    aqk, akk = _inside_blocks(q, k, levels[0][0], VECTOR_ROWS)
    aqk = jnp.where(t == s, jnp.sum(q * k, axis=1, keepdims=True), aqk)
    for h, p, e in _product_levels(levels):
        both, columns, _, _ = _level_operands(q, k, p, e, h)
        block = _dot32(both, columns, _NT)                   # [C, C]
        level = _level(t, s, h)
        aqk = jnp.where(level, _spread(block[:c // 2], h, True), aqk)
        akk = jnp.where(level, _spread(block[c // 2:], h, True), akk)
    return aqk, akk


def _pair_grads_free(q, k, levels, d_qk, d_kk):
    """The way back through ``_pair_blocks_free``: as ``_pair_grads``. A
    level's gradient blocks are zero but in the odd halves' rows, which
    alone go through its two products. A level's split point cancels in
    every pair's exponent, so dcum is the rows' part (q and k times their
    gradients as rows) less the columns' part; at a pair whose factor
    underflowed the gradient is the pair's value, 0."""
    c = k.shape[0]
    t, s = _rows_cols(c)
    dq, dk_rows, dk_cols = _inside_blocks_grads(q, k, levels[0][0], d_qk,
                                                d_kk, VECTOR_ROWS)
    for h, p, e in _product_levels(levels):
        both, columns, rows, cols = _level_operands(q, k, p, e, h)
        level = _level(t, s, h)
        d_both = jnp.concatenate([
            _halves(jnp.where(level, d, 0.0), h, True) for d in (d_qk, d_kk)])
        back = _dot32(d_both, columns) * jnp.concatenate([rows, rows])
        dq = dq + _spread(back[:c // 2], h, True)
        dk_rows = dk_rows + _spread(back[c // 2:], h, True)
        dk_cols = dk_cols + _dot32(d_both, both, _TN) * cols
    diagonal = jnp.sum(jnp.where(t == s, d_qk, 0.0), axis=1, keepdims=True)
    return (dq + diagonal * k, dk_rows + dk_cols + diagonal * q,
            q * dq + k * (dk_rows - dk_cols))


def _shifted_round(p, m, b: int):
    """P M P for P unit lower triangular with diagonal blocks of ``b`` rows
    (so b - 1 subdiagonals), float32, without a product: (M P)[t, s] = M[t,
    s] + sum_d M[t, s + d] P[s + d, s] and (P X)[t, s] = X[t, s] + sum_d
    P[t, t - d] X[t - d, s], a lane shift and a row shift a subdiagonal on
    the vector unit. A shift that wraps round meets a subdiagonal's zero."""
    n = p.shape[0]
    t, s = _rows_cols(n)
    diagonals = [jnp.where(t - s == d, p, 0.0) for d in range(1, b)]
    mp = m
    for d, sub in enumerate(diagonals, 1):
        mp = mp + pltpu.roll(m, n - d, 1) * jnp.sum(sub, axis=0,
                                                    keepdims=True)
    out = mp
    for d, sub in enumerate(diagonals, 1):
        out = out + jnp.sum(sub, axis=1, keepdims=True) * pltpu.roll(mp, d, 0)
    return out


def _unit_lower_inverse(a, block: int | None = None):
    """(I + a)^-1, float32, by block forward substitution with the diagonal
    blocks doubling (module docstring). a [n, n] is strictly lower
    triangular, or block diagonal with such blocks of ``block`` rows (the
    heads of a kernel instance side by side): the rounds stop at the
    block, and what lies between the blocks stays exactly 0."""
    n = a.shape[0]
    t, s = _rows_cols(n)

    def lower_left(b):      # of every diagonal block of 2 b rows: t and s
        # agree above bit b, which t has and s has not
        return jnp.where(((t ^ s) < 2 * b) & ((t & ~s & b) != 0), a, 0.0)

    # the first round's P is the identity: P - P M P is I - M, no product
    p = jnp.where(t == s, 1.0, 0.0) - lower_left(1)
    b = 2
    while b < (block or n):
        m = lower_left(b)
        if b <= SHIFTED_BLOCKS:
            p = p - _shifted_round(p, m, b)
        elif b < HALF_ROWS:
            p = p - _dot32(_dot32(p, m), p)
        else:
            # P M P is zero but in the rows of the odd blocks: half the
            # rows go through the two products (whole sublane tiles)
            odd = jnp.concatenate([p[i:i + b] for i in range(b, n, 2 * b)])
            odd = odd - _dot32(_dot32(odd, m), p)
            p = jnp.concatenate([
                rows for j, i in enumerate(range(0, n, 2 * b))
                for rows in (p[i:i + b], odd[j * b:(j + 1) * b])])
        b *= 2
    return p


def _unit_lower_inverses(blocks):
    """[(I + a)^-1 for a in blocks]: the heads that share one inverse
    (``plan()``'s ``inverse_side`` over the chunk), as ONE block-diagonal
    matrix whose rounds are one product for all of them."""
    c = blocks[0].shape[0]
    if len(blocks) == 1:
        return [_unit_lower_inverse(blocks[0])]
    zero = jnp.zeros_like(blocks[0])
    x = _unit_lower_inverse(jnp.concatenate([
        jnp.concatenate([a if j == i else zero for j in range(len(blocks))],
                        axis=1) for i, a in enumerate(blocks)]), c)
    return [x[i * c:(i + 1) * c, i * c:(i + 1) * c]
            for i in range(len(blocks))]


def _head(i: int, heads: int, q, k, v, g, betas, state):
    """Head ``i`` of a block's arrays, float32: (q, k [C, dk], v [C, dv], g
    [C, dk], beta [C, 1], st [dv, dk] the entering state TRANSPOSED)."""
    f32 = jnp.float32
    dk, dv = q.shape[1] // heads, v.shape[1] // heads
    kl, vl = slice(i * dk, (i + 1) * dk), slice(i * dv, (i + 1) * dv)
    return (q[:, kl].astype(f32), k[:, kl].astype(f32), v[:, vl].astype(f32),
            g[:, kl], _column(betas, i), state[vl])


def _chunk_forms(heads, mm, sub: int, clamp: float):
    """What both kernels compute of one chunk for the heads that share an
    inverse: a list of ``_head``'s tuples -> a dict of the chunk's forms a
    head."""
    forms = []
    for q, k, v, g, beta, st in heads:
        if clamp is None:       # no bound on g: the cut in halves
            levels, cum = _aligned_sums(g)
            aqk, akk = _pair_blocks_free(q, k, levels)
        else:
            levels, cum = None, _running_sum(g)
            aqk, akk = _pair_blocks(q, k, cum, sub, clamp)
        forms.append(dict(cum=cum, aqk=aqk, akk=akk, levels=levels))
    inverses = _unit_lower_inverses(
        [beta * f["akk"] for (*_, beta, _), f in zip(heads, forms)])
    for (q, k, v, g, beta, st), f, x in zip(heads, forms, inverses):
        cum = f["cum"]
        grown = jnp.exp(cum)
        last = cum[-1:]                                      # [1, dk]
        to_end = jnp.exp(last - cum)
        kg, qg, ke = k * grown, q * grown, k * to_end
        rest = v - _dot(kg, st, _NT, mm)                     # [C, dv]
        new = _dot(x, beta * rest, _NN, mm)                  # V'
        f.update(x=x, grown=grown, last=last, to_end=to_end, kg=kg, qg=qg,
                 ke=ke, rest=rest, new=new)
    return forms


def _block_forward(q, k, v, g, betas, state, *, heads: int, per: int, mm,
                   sub: int, clamp: float):
    """One chunk of a block of ``heads`` heads, ``per`` of them an inverse:
    q, k [C, heads x dk] and v [C, heads x dv] in the inputs' type, g as q
    float32, betas [C, heads], state [heads x dv, dk] float32 entering ->
    (o [C, heads x dv] float32, the state leaving)."""
    o, left = [], []
    for first in range(0, heads, per):
        group = [_head(i, heads, q, k, v, g, betas, state)
                 for i in range(first, first + per)]
        for (*_, st), f in zip(group, _chunk_forms(group, mm, sub, clamp)):
            o.append(_dot(f["qg"], st, _NT, mm)
                     + _dot(f["aqk"], f["new"], _NN, mm))
            left.append(st * jnp.exp(f["last"])
                        + _dot(f["new"], f["ke"], _TN, mm))
    return jnp.concatenate(o, axis=1), jnp.concatenate(left)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, sin_ref,
                s_scr, **form):
    """One instance per (batch, head block, chunk), chunks in order. q_ref,
    k_ref [1, C, heads x dk]; v_ref [1, C, heads x dv]; g_ref as q_ref,
    float32; beta_ref [1, 1, C, heads] float32; s0_ref [1, heads x dv, dk]
    the sequence's initial state; o_ref as v_ref; sin_ref [1, 1, heads x
    dv, dk] float32; s_scr [heads x dv, dk] float32, the state entering the
    chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_scr[...] = s0_ref[0]

    sin_ref[0, 0] = s_scr[...]
    o, s_scr[...] = _block_forward(
        q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0, 0], s_scr[...],
        mm=q_ref.dtype, **form)
    o_ref[0] = o.astype(o_ref.dtype)


def _block_backward(q, k, v, g, betas, state, do, d_state, *, heads: int,
                    per: int, mm, sub: int, clamp: float):
    """The way back through ``_block_forward``: its inputs (state the one
    ENTERING the chunk), do [C, heads x dv] and d_state [heads x dv, dk]
    the gradient of the state leaving -> (dq, dk, dv, dg as their inputs,
    float32; dbetas [C, heads]; the gradient of the state entering)."""
    c, dv = q.shape[0], v.shape[1] // heads
    t, s = _rows_cols(c)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    dbetas = jnp.zeros(betas.shape, jnp.float32)
    dqs, dks, dvs, dgs, d_entering = [], [], [], [], []
    for first in range(0, heads, per):
        among = range(first, first + per)
        group = [_head(i, heads, q, k, v, g, betas, state) for i in among]
        forms = _chunk_forms(group, mm, sub, clamp)
        for i, (qi, ki, _, _, beta, st), f in zip(among, group, forms):
            vl = slice(i * dv, (i + 1) * dv)
            dst, do_i = d_state[vl], do[:, vl]
            # o = qg st^T + aqk new; the state leaving: st exp(last) + new^T ke
            d_new = _dot(f["aqk"], do_i, _TN, mm) + _dot(f["ke"], dst, _NT, mm)
            d_aqk = jnp.where(t >= s, _dot(do_i, f["new"], _NT, mm), 0.0)
            d_qg = _dot(do_i, st, _NN, mm)
            d_ke = _dot(f["new"], dst, _NN, mm)
            decay = jnp.exp(f["last"])
            d_last = jnp.sum(st * dst, axis=0, keepdims=True) * decay
            d_st = dst * decay + _dot(do_i, f["qg"], _TN, mm)
            # new = x (beta rest), x = (I + beta akk)^-1
            d_y = _dot(f["x"], d_new, _TN, mm)
            d_a = jnp.where(t > s, -_dot(d_y, f["new"], _NT, mm), 0.0)
            d_rest = beta * d_y
            d_beta = jnp.sum(d_y * f["rest"], axis=1, keepdims=True) \
                + jnp.sum(d_a * f["akk"], axis=1, keepdims=True)
            # rest = v - kg st^T
            d_kg = -_dot(d_rest, st, _NN, mm)
            d_st = d_st - _dot(d_rest, f["kg"], _TN, mm)
            if clamp is None:
                dq, dk_, dcum = _pair_grads_free(qi, ki, f["levels"], d_aqk,
                                                 beta * d_a)
            else:
                dq, dk_, dcum = _pair_grads(qi, ki, f["cum"], d_aqk,
                                            beta * d_a, sub, clamp)
            through = d_ke * f["ke"]
            dcum = dcum + d_qg * f["qg"] + d_kg * f["kg"] - through
            d_last = d_last + jnp.sum(through, axis=0, keepdims=True)
            dcum = dcum + jnp.where(t == c - 1, d_last, 0.0)
            dqs.append(dq + d_qg * f["grown"])
            dks.append(dk_ + d_kg * f["grown"] + d_ke * f["to_end"])
            dvs.append(d_rest)
            # the transpose of a running sum: the running sum from the end
            dgs.append(_running_sum(dcum, from_end=True))
            dbetas = jnp.where(lanes == i, d_beta, dbetas)
            d_entering.append(d_st)
    wide = lambda parts: jnp.concatenate(parts, axis=1)        # noqa: E731
    return (wide(dqs), wide(dks), wide(dvs), wide(dgs), dbetas,
            jnp.concatenate(d_entering))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sin_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds0_ref, ds_scr,
                **form):
    """The mirror image, chunks last to first; ds_scr [heads x dv, dk] is
    the gradient of the (transposed) state LEAVING the chunk, and after the
    first chunk that of the initial state (ds0_ref [1, heads x dv, dk])."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    dq, dk, dv, dg, dbetas, ds_scr[...] = _block_backward(
        q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0, 0], sin_ref[0, 0],
        do_ref[0], ds_scr[...], mm=q_ref.dtype, **form)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dg_ref[0] = dg
    dbeta_ref[0, 0] = dbetas
    ds0_ref[0] = ds_scr[...]


# --- the block plan and the calls -------------------------------------------


@functools.lru_cache(maxsize=None)
def _products(c: int, heads: int, per: int, dk: int, dv: int, dtype,
              bounded: bool = True):
    """(float32 products, MXU passes, rows streamed through the MXU over
    those passes) a head and chunk of the forward and of the backward call,
    counted by tracing one block's chunk through the code the kernels run
    (``bounded``: with the cut a bound on g allows)."""
    f32 = jnp.float32
    form = dict(heads=heads, per=per, mm=jnp.dtype(dtype), sub=min(SUB, c),
                clamp=40.0 if bounded else None)
    wide = lambda n, t: jax.ShapeDtypeStruct((c, heads * n), t)  # noqa: E731
    block = (wide(dk, dtype), wide(dk, dtype), wide(dv, dtype), wide(dk, f32),
             wide(1, f32), jax.ShapeDtypeStruct((heads * dv, dk), f32))
    counts = []
    for rule, more in ((_block_forward, ()),
                       (_block_backward, (wide(dv, dtype), block[-1]))):
        tally = [0, 0, 0]
        _TALLIES.append(tally)
        try:
            jax.eval_shape(functools.partial(rule, **form), *block, *more)
        finally:
            _TALLIES.remove(tally)
        counts.append(tuple(n // heads for n in tally))
    return counts


def plan(*, B: int, S: int, H: int, dk: int, dv: int, dtype, impl: str,
         chunk: int = CHUNK, lower_bound: float | None = -5.0) -> dict:
    """The op's block plan (also the attributes of ``kda.plan``), from the
    shapes alone: the chunk (a sequence shorter than ``chunk`` is ONE
    chunk, the least power of two that holds it), the sub-block of the pair
    products, how many heads an instance walks and how many of them share
    one inverse
    (``inverse_side`` over the chunk: as many as fit the MXU's tile of
    ``_SIDE`` side by side), the float32 products, the MXU passes and the
    rows those passes stream (``mxu_rows_*``: a product's free rows times
    its passes) a head and chunk of each call (``_products``: counted from
    the kernels' own code), the VMEM one instance of the backward call holds
    (its blocks twice, Mosaic double-buffers; the state scratch; the
    float32 forms of the heads of an inverse: a dozen of its side squared,
    a dozen [C, dk] and half a dozen [C, dv] a head, three [dv, dk]; the
    cut that needs no bound two [C, dk] more for each level that is a
    product), which cut of the
    pair products ran (``cut`` "bounded": sub-blocks split at their middle
    row; "halving": ``lower_bound`` None, blocks of ``cut_sizes`` rows each
    split at its halves' boundary, the ``vector_levels`` smallest made on
    the vector unit without a product), the
    bytes of the chunks' incoming states the forward rule keeps for the
    backward, and the HBM bytes the two calls move for one head and
    sequence."""
    on = impl == "pallas"
    c = min(chunk, max(SUB, 1 << (S - 1).bit_length()))
    heads = min(HEADS_PER_BLOCK, H)
    while H % heads:
        heads -= 1
    per = max(1, min(heads, _SIDE // c))
    while heads % per:
        per -= 1
    item = jnp.dtype(dtype).itemsize
    steps = -(-S // c) * c
    blocks = (heads * c * (2 * dk + 2 * dv) * item        # q, k, v, do
              + heads * c * (2 * dk + dv) * item          # dq, dk, dv
              + 2 * heads * c * dk * 4                    # g, dg
              + 2 * c * 128 * 4                           # beta, dbeta
              + 2 * heads * dv * dk * 4)                  # state in, ds0
    bounded = lower_bound is not None
    sizes = [] if bounded else [2 << i for i in range(c.bit_length() - 1)]
    on_vector = sum(size <= VECTOR_ROWS for size in sizes)
    forms = 12 * (per * c) ** 2 * 4 + per * (
        (12 + 2 * (len(sizes) - on_vector)) * c * dk + 6 * c * dv) * 4 \
        + 3 * dv * dk * 4
    states = B * (steps // c) * H * dv * dk * 4
    hbm = steps * ((2 * dk + dv) * item + dk * 4 + 4) * 2 \
        + steps * dv * item * 2 + 2 * (steps // c) * dv * dk * 4
    said = {"path": impl, "S": S, "chunk": c, "sub_block": SUB,
            "heads_per_block": heads, "lower_bound": lower_bound,
            "cut": "bounded" if bounded else "halving", "cut_sizes": sizes,
            "vector_levels": on_vector,
            "vmem_bytes": 0, "state_bytes_kept": states,
            "hbm_bytes_per_head": 0}
    if on:
        (f32_fwd, fwd, rows_fwd), (f32_bwd, bwd, rows_bwd) = _products(
            c, heads, per, dk, dv, jnp.dtype(dtype), bounded)
        said.update(
            inverse_side=per * c, f32_products_fwd=f32_fwd,
            f32_products_bwd=f32_bwd, mxu_passes_fwd=fwd, mxu_passes_bwd=bwd,
            mxu_rows_fwd=rows_fwd, mxu_rows_bwd=rows_bwd,
            vmem_bytes=2 * blocks + heads * dv * dk * 4 + forms,
            hbm_bytes_per_head=hbm)
    return said


def _specs(c: int, heads: int, dk: int, dv: int):
    keys = pl.BlockSpec((1, c, heads * dk), lambda b, h, n: (b, n, h))
    values = pl.BlockSpec((1, c, heads * dv), lambda b, h, n: (b, n, h))
    beta = pl.BlockSpec((1, 1, c, heads), lambda b, h, n: (b, h, n, 0))
    first = pl.BlockSpec((1, heads * dv, dk), lambda b, h, n: (b, h, 0))
    states = pl.BlockSpec((1, 1, heads * dv, dk),
                          lambda b, h, n: (b, n, h, 0))
    return keys, values, beta, states, first


def _backwards(spec, last: int):
    """The same block, its chunk index walked last to first."""
    index = spec.index_map
    return pl.BlockSpec(spec.block_shape,
                        lambda b, h, n: index(b, h, last - n))


def _call(kernel, B, H, S, *, chunk, heads, per, dk, dv, clamp, **io):
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, per=per, sub=min(SUB, chunk),
                          clamp=clamp),
        grid=(B, H // heads, S // chunk),
        scratch_shapes=[pltpu.VMEM((heads * dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(), **io)


def _forward_call(q, k, v, g, beta, s0, *, chunk, heads, dk, dv, **form):
    B, S, _ = q.shape
    H = q.shape[2] // dk
    keys, values, betas, states, first = _specs(chunk, heads, dk, dv)
    call = _call(
        _fwd_kernel, B, H, S, chunk=chunk, heads=heads, dk=dk, dv=dv, **form,
        in_specs=[keys, keys, values, keys, betas, first],
        out_specs=[values, states],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, S // chunk, H * dv, dk),
                                        jnp.float32)])
    with jax.named_scope("kda.fwd.pallas"):         # kda.plan's path
        return call(q, k, v, g, beta, s0)


def _backward_call(q, k, v, g, beta, s_in, do, *, chunk, heads, dk, dv,
                   **form):
    B, S, _ = q.shape
    H = q.shape[2] // dk
    *walked, first = _specs(chunk, heads, dk, dv)
    keys, values, betas, states = (
        _backwards(spec, S // chunk - 1) for spec in walked)
    call = _call(
        _bwd_kernel, B, H, S, chunk=chunk, heads=heads, dk=dk, dv=dv, **form,
        in_specs=[keys, keys, values, keys, betas, states, values],
        out_specs=[keys, keys, values, keys, betas, first],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(beta.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, H * dv, dk), jnp.float32)])
    with jax.named_scope("kda.bwd.pallas"):
        return call(q, k, v, g, beta, s_in, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_pallas(q, k, v, g, beta, s0, form):
    """``form``: the calls' static arguments, a tuple of pairs."""
    return _forward_call(q, k, v, g, beta, s0, **dict(form))[0]


def _scan_pallas_fwd(q, k, v, g, beta, s0, form):
    o, s_in = _forward_call(q, k, v, g, beta, s0, **dict(form))
    o, s_in = (checkpoint_name(a, name) for a, name in zip((o, s_in),
                                                           RESIDUALS))
    return o, (q, k, v, g, beta, s_in)


def _scan_pallas_bwd(form, res, do):
    q, k, v, g, beta, s_in = res
    return _backward_call(q, k, v, g, beta, s_in, do.astype(v.dtype),
                          **dict(form))


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def gated_delta_rule(q, k, v, g, beta, *, initial_state=None,
                     chunk: int = CHUNK, impl: str = "xla",
                     lower_bound: float | None = -5.0):
    """q, k [B, S, H, dk], v [B, S, H, dv], g [B, S, H, dk] float32 (the
    log of the decay a step and key channel, in [lower_bound, 0]; any g <=
    0 where the caller states no bound, ``lower_bound`` None: the kernels
    then cut the pair products in halves, module docstring), beta
    [B, S, H] float32 in (0, 2), initial_state [B, H, dk, dv] float32 or None (zero)
    -> o [B, S, H, dv] in v's type: the recurrence of the module docstring.
    Differentiable in all six on both paths. q and k arrive as the model
    made them (normed, q scaled): the op scales nothing."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if impl not in ("xla", "pallas"):
        raise ValueError(f"gated_delta_rule impl must be 'xla' or 'pallas', "
                         f"got {impl!r}")
    if chunk % SUB or chunk & (chunk - 1):
        raise ValueError(f"gated_delta_rule: a chunk of {chunk} steps; want "
                         f"a power of two and a multiple of {SUB}")
    clamp = None if lower_bound is None else _clamp(lower_bound)
    said = plan(B=B, S=S, H=H, dk=dk, dv=dv, chunk=chunk, dtype=q.dtype,
                impl=impl, lower_bound=lower_bound)
    tracing.plan("kda.plan", said)
    chunk = said["chunk"]
    f32 = jnp.float32
    g, beta = g.astype(f32), beta.astype(f32)
    state = jnp.zeros((B, H, dk, dv), f32) if initial_state is None \
        else initial_state.astype(f32)
    pad = -S % chunk
    if pad:     # steps that change nothing: k 0, beta 0, g 0
        q, k, v, g, beta = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    if impl == "xla":
        with jax.named_scope("kda.fwd.xla"):    # jax transposes it itself
            o = _scan_xla(q.astype(f32), k.astype(f32), v.astype(f32), g,
                          beta, state, chunk)
        return o[:, :S].astype(v.dtype)
    heads = said["heads_per_block"]
    if (dk % 128 or dv % 128) and not _use_interpret():
        raise ValueError(f"gated_delta_rule: heads of {dk} keys and {dv} "
                         "values; the kernel takes whole lane tiles of 128")
    steps = S + pad
    flat = lambda a: a.reshape(B, steps, -1)                   # noqa: E731
    by_block = beta.reshape(B, steps, H // heads, heads).transpose(0, 2, 1, 3)
    s0 = state.transpose(0, 1, 3, 2).reshape(B, H * dv, dk)
    form = (("chunk", chunk), ("heads", heads),
            ("per", said["inverse_side"] // chunk), ("dk", dk), ("dv", dv),
            ("clamp", clamp))
    o = _scan_pallas(flat(q), flat(k), flat(v), flat(g), by_block, s0, form)
    return o.reshape(B, steps, H, dv)[:, :S]
