"""Forms of the delta rule's cut in halves (``lower_bound`` None) that were
timed against the one ``ops/delta_rule.py`` runs (PERF.md 6, PR 66): each is
a replacement for ``_pair_blocks_free`` or ``_pair_grads_free`` with the
same arguments and results, patched in by ``tests/delta_rule_forms.py
--cut`` while the calls trace. ``tests/test_delta_rule.py`` holds every one
to the masked whole product of a level. ``first``: the levels under it are
left out (another form makes them)."""

import jax
import jax.numpy as jnp

from ray_tpu.ops import delta_rule as dr


def _diagonal(q, k):
    c = k.shape[0]
    t, s = dr._rows_cols(c)
    return jnp.where(t == s, jnp.sum(q * k, axis=1, keepdims=True), 0.0)


def _whole_blocks(q, k, p, e):
    """A level's (Aqk, Akk) [C, C] as ONE product of every row with every
    column, not yet masked."""
    rows, cols = dr._level_factors(p, e)
    block = dr._dot32(jnp.concatenate([q * rows, k * rows]), k * cols, dr._NT)
    return block[:k.shape[0]], block[k.shape[0]:]


def whole_products(q, k, levels, first: int = 0):
    """PR 61's: a level is ONE product [2 C, dk] x [dk, C] of every row
    with every column, masked to the level's pairs."""
    c = k.shape[0]
    t, s = dr._rows_cols(c)
    aqk = _diagonal(q, k)
    akk = jnp.zeros((c, c), jnp.float32)
    for i, (p, e) in list(enumerate(levels))[first:]:
        block_qk, block_kk = _whole_blocks(q, k, p, e)
        level = dr._level(t, s, 1 << i)
        aqk = jnp.where(level, block_qk, aqk)
        akk = jnp.where(level, block_kk, akk)
    return aqk, akk


def _turned_level_mask(c: int, h: int):
    """``_level`` for a block held transposed, Aqk^T beside Akk^T: rows s,
    lanes t (twice)."""
    s = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * c), 1) & (c - 1)
    return dr._level(t, s, h)


def narrow_side_streamed(q, k, levels, first: int = 0,
                         gather_from: int = 1 << 20):
    """ISSUE 66's form 1 (and 2 with ``gather_from`` 8): the operands
    exchanged, so that the C columns' rows stream into all 2 C lanes; the
    level's Aqk and Akk come out transposed side by side and are turned
    once a chunk. From blocks of ``gather_from`` rows the even halves' rows
    alone are streamed."""
    c = k.shape[0]
    turned = jnp.zeros((c, 2 * c), jnp.float32)
    for i, (p, e) in list(enumerate(levels))[first:]:
        h = 1 << i
        both = jnp.concatenate([q, k]) * jnp.exp(jnp.concatenate([p, p]))
        if h >= gather_from:
            pe, ee, ke = (dr._halves(x, h, False) for x in (p, e, k))
            part = dr._dot32(ke * jnp.exp(jnp.minimum(ee - pe, 0.0)), both,
                             dr._NT)                     # [C / 2, 2 C]
            block = dr._spread(part, h, False)
        else:
            block = dr._dot32(k * jnp.exp(jnp.minimum(e - p, 0.0)), both,
                              dr._NT)                    # [C, 2 C]
        turned = jnp.where(_turned_level_mask(c, h), block, turned)
    both = turned.T                                      # [2 C, C]
    return both[:c] + _diagonal(q, k), both[c:]


def narrow_side_gathered(q, k, levels, first: int = 0):
    return narrow_side_streamed(q, k, levels, first, 8)


def odd_rows_streamed(q, k, levels, first: int = 0, gather_from: int = 8):
    """ISSUE 66's form 2 alone: the parent's orientation, from blocks of
    ``gather_from`` rows the odd halves' rows of q and k alone against
    every column."""
    c = k.shape[0]
    t, s = dr._rows_cols(c)
    aqk = _diagonal(q, k)
    akk = jnp.zeros((c, c), jnp.float32)
    for i, (p, e) in list(enumerate(levels))[first:]:
        h = 1 << i
        if h >= gather_from:
            both, columns, _, _ = dr._level_operands(q, k, p, e, h)
            part = dr._dot32(both, columns, dr._NT)      # [C, C]
            block_qk = dr._spread(part[:c // 2], h, True)
            block_kk = dr._spread(part[c // 2:], h, True)
        else:
            block_qk, block_kk = _whole_blocks(q, k, p, e)
        level = dr._level(t, s, h)
        aqk = jnp.where(level, block_qk, aqk)
        akk = jnp.where(level, block_kk, akk)
    return aqk, akk


def _lanes_spread(x, h: int):
    """x [n, C / 2], lane block j of h lanes -> lane block 2 j of [n, C]:
    the even halves' columns back in their places, zeros between."""
    zero = jnp.zeros((x.shape[0], h), x.dtype)
    return jnp.concatenate(
        [piece for j in range(x.shape[1] // h)
         for piece in (x[:, j * h:(j + 1) * h], zero)], axis=1)


def both_sides_gathered(q, k, levels, first: int = 0,
                        gather_from: int = 8):
    """The odd halves' rows against the even halves' columns alone: [C, dk]
    x [dk, C / 2], the columns then moved back along the lanes."""
    c = k.shape[0]
    t, s = dr._rows_cols(c)
    aqk = _diagonal(q, k)
    akk = jnp.zeros((c, c), jnp.float32)
    for i, (p, e) in list(enumerate(levels))[first:]:
        h = 1 << i
        if h >= gather_from:
            po, qo, ko = (dr._halves(x, h, True) for x in (p, q, k))
            pe, ee, ke = (dr._halves(x, h, False) for x in (p, e, k))
            rows = jnp.exp(po)
            part = dr._dot32(jnp.concatenate([qo * rows, ko * rows]),
                             ke * jnp.exp(jnp.minimum(ee - pe, 0.0)),
                             dr._NT)                     # [C, C / 2]
            part = _lanes_spread(part, h)
            block_qk = dr._spread(part[:c // 2], h, True)
            block_kk = dr._spread(part[c // 2:], h, True)
        else:
            block_qk, block_kk = _whole_blocks(q, k, p, e)
        level = dr._level(t, s, h)
        aqk = jnp.where(level, block_qk, aqk)
        akk = jnp.where(level, block_kk, akk)
    return aqk, akk


def with_vector_levels(products, count: int):
    """``products`` for the levels above the ``count`` smallest, whose pairs
    (those inside aligned blocks of 2^count rows) ``_inside_blocks`` makes
    on the vector unit."""
    def form(q, k, levels):
        aqk, akk = products(q, k, levels, count)
        in_qk, in_kk = dr._inside_blocks(q, k, levels[0][0], 1 << count)
        inside = dr._steps_apart(k.shape[0], 1 << count) > 0
        return jnp.where(inside, in_qk, aqk), jnp.where(inside, in_kk, akk)
    return form


def whole_grads(q, k, levels, d_qk, d_kk, first: int = 0):
    """PR 61's way back: a level is two products, [2 C, C] x [C, dk] for
    the rows and [C, 2 C] x [2 C, dk] for the columns."""
    c = k.shape[0]
    t, s = dr._rows_cols(c)
    diagonal = jnp.sum(jnp.where(t == s, d_qk, 0.0), axis=1, keepdims=True)
    dq, dk_, dcum = diagonal * k, diagonal * q, jnp.zeros_like(k)
    for i, (p, e) in list(enumerate(levels))[first:]:
        rows, cols = dr._level_factors(p, e)
        level = dr._level(t, s, 1 << i)
        d_both = jnp.concatenate([jnp.where(level, d_qk, 0.0),
                                  jnp.where(level, d_kk, 0.0)])
        back = dr._dot32(d_both, k * cols) * jnp.concatenate([rows, rows])
        both = jnp.concatenate([q * rows, k * rows])
        dk_cols = dr._dot32(d_both, both, dr._TN) * cols
        dq = dq + back[:c]
        dk_ = dk_ + back[c:] + dk_cols
        dcum = dcum + q * back[:c] + k * back[c:] - k * dk_cols
    return dq, dk_, dcum


def odd_rows_grads(q, k, levels, d_qk, d_kk, first: int = 0,
                   gather_from: int = 8, turn: bool = True):
    """ISSUE 66's form 3: from blocks of ``gather_from`` rows the level's
    gradient blocks are read at the odd halves' rows alone (they are zero
    in the others); ``turn``: the columns' product streams the even halves'
    rows of the blocks TURNED, else every column (``_TN``)."""
    c = k.shape[0]
    t, s = dr._rows_cols(c)
    diagonal = jnp.sum(jnp.where(t == s, d_qk, 0.0), axis=1, keepdims=True)
    dq, dk_, dcum = diagonal * k, diagonal * q, jnp.zeros_like(k)
    for i, (p, e) in list(enumerate(levels))[first:]:
        h = 1 << i
        level = dr._level(t, s, h)
        if h < gather_from:
            rows, cols = dr._level_factors(p, e)
            d_both = jnp.concatenate([jnp.where(level, d_qk, 0.0),
                                      jnp.where(level, d_kk, 0.0)])
            back = dr._dot32(d_both, k * cols) * jnp.concatenate([rows, rows])
            both = jnp.concatenate([q * rows, k * rows])
            dk_cols = dr._dot32(d_both, both, dr._TN) * cols
            back_q, back_k = back[:c], back[c:]
        else:
            po, qo, ko = (dr._halves(x, h, True) for x in (p, q, k))
            rows = jnp.exp(po)
            d_both = jnp.concatenate([
                dr._halves(jnp.where(level, d, 0.0), h, True)
                for d in (d_qk, d_kk)])                      # [C, C]
            both = jnp.concatenate([qo * rows, ko * rows])   # [C, dk]
            if turn:
                pe, ee, ke = (dr._halves(x, h, False) for x in (p, e, k))
                cols = jnp.exp(jnp.minimum(ee - pe, 0.0))    # [C / 2, dk]
                turned = dr._halves(d_both.T, h, False)      # [C / 2, C]
                back = dr._dot32(turned.T, ke * cols)
                part = dr._dot32(turned, both) * cols
                dk_cols = dr._spread(part, h, False)
            else:
                cols = jnp.exp(jnp.minimum(e - p, 0.0))
                back = dr._dot32(d_both, k * cols)
                dk_cols = dr._dot32(d_both, both, dr._TN) * cols
            back = back * jnp.concatenate([rows, rows])
            back_q = dr._spread(back[:c // 2], h, True)
            back_k = dr._spread(back[c // 2:], h, True)
        dq = dq + back_q
        dk_ = dk_ + back_k + dk_cols
        dcum = dcum + q * back_q + k * back_k - k * dk_cols
    return dq, dk_, dcum


def with_vector_grads(products, count: int):
    """``products``' way back for the levels above the ``count`` smallest,
    ``_inside_blocks_grads`` for those."""
    def form(q, k, levels, d_qk, d_kk):
        dq, dk_, dcum = products(q, k, levels, d_qk, d_kk, count)
        in_q, in_rows, in_cols = dr._inside_blocks_grads(
            q, k, levels[0][0], d_qk, d_kk, 1 << count)
        return (dq + in_q, dk_ + in_rows + in_cols,
                dcum + q * in_q + k * (in_rows - in_cols))
    return form


def _forms():
    import functools

    no_turn = functools.partial(odd_rows_grads, turn=False)
    yield "whole products a level (PR 61's)", whole_products, whole_grads
    yield "the narrow side streamed, turned once a chunk", \
        narrow_side_streamed, whole_grads
    yield "the narrow side's even halves streamed from blocks of 8", \
        narrow_side_gathered, whole_grads
    yield "the odd halves' rows from blocks of 8, no level on the vector " \
        "unit", odd_rows_streamed, no_turn
    yield "the odd halves' rows, the columns' product of the blocks turned", \
        odd_rows_streamed, odd_rows_grads
    yield "odd rows against even columns, moved back along the lanes", \
        both_sides_gathered, no_turn
    for count in (1, 2):
        yield (f"the {count} smallest levels on the vector unit, the odd "
               "halves' rows above", with_vector_levels(odd_rows_streamed,
                                                        count),
               with_vector_grads(no_turn, count))
    yield ("the 3 smallest levels on the vector unit, whole products above",
           with_vector_levels(whole_products, 3),
           with_vector_grads(whole_grads, 3))
    yield ("the 3 smallest levels on the vector unit, the narrow side's even "
           "halves above, the columns' product turned",
           with_vector_levels(narrow_side_gathered, 3),
           with_vector_grads(odd_rows_grads, 3))


FORMS = list(_forms())
