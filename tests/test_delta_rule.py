"""The chunked gated delta rule (ops/delta_rule.py) against the recurrence
advanced a step at a time: values and all six gradients on both paths, over
lengths that are and are not whole chunks, two heads an inverse and one;
the gate at its bound; the inverse by block forward substitution at every
side the plan chooses; the running sums by shifted adds and by three
passes; the plan and the refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import delta_rule_cut_forms as cut_forms
from ray_tpu.models import reference_ling
from ray_tpu.ops import delta_rule as dr


def recurrence(q, k, v, g, beta, state):
    """[B, S, H, .] inputs through ``reference_ling.delta_rule``, a
    sequence at a time."""
    return jax.vmap(lambda *a: reference_ling.delta_rule(*a)[0])(
        q, k, v, g, beta, state)


def make(S, B=2, H=2, dk=16, dv=16, seed=0, lower=-5.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = lower * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (B, S, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    state = 0.3 * jax.random.normal(ks[5], (B, H, dk, dv))
    weight = jax.random.normal(ks[6], (B, S, H, dv))
    return (q, k, v, g, beta, state), weight


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("S, chunk", [(64, 32), (100, 32), (16, 32),
                                      (128, 64), (256, 128)])
def test_values_and_six_gradients_are_the_recurrences(impl, S, chunk):
    """A chunk of 32: two whole chunks, three and a part, half a chunk; two
    whole chunks of the op's own 64 (on the kernel path the block's two
    heads in ONE inverse of side 128) and two of 128 (a head an inverse);
    q, k, v, g, beta and the initial state."""
    args, weight = make(S)

    def op(q, k, v, g, beta, state):
        return dr.gated_delta_rule(q, k, v, g, beta, initial_state=state,
                                   chunk=chunk, impl=impl)

    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        want_grads = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                              argnums=range(6))(*args)
        got = jax.jit(op)(*args)
        grads = jax.jit(jax.grad(lambda *a: (op(*a) * weight).sum(),
                                 argnums=range(6)))(*args)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for name, a, b in zip("q k v g beta state".split(), grads, want_grads):
        np.testing.assert_allclose(
            a, b, atol=3e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("S, chunk", [(128, 32), (128, 64), (256, 128)])
def test_the_gate_at_its_bound_stays_finite(impl, S, chunk):
    """g = -5 every step and channel for four chunks of 32, two of the
    op's own 64 and two of 128: exp(-G) alone would overflow after 18
    steps; the
    sub-blocks' factors, taken from the sub-block's middle, neither
    overflow nor fall among the denormals, and the values and gradients are
    the recurrence's."""
    (q, k, v, g, beta, state), weight = make(S)
    g = jnp.full_like(g, -5.0)

    def loss(fn):
        return lambda q, k, v, g: (fn(q, k, v, g) * weight).sum()

    op = lambda q, k, v, g: dr.gated_delta_rule(                # noqa: E731
        q, k, v, g, beta, initial_state=state, chunk=chunk, impl=impl)
    ref = lambda q, k, v, g: recurrence(q, k, v, g, beta, state)  # noqa
    with jax.default_matmul_precision("highest"):
        got = jax.jit(op)(q, k, v, g)
        grads = jax.jit(jax.grad(loss(op), argnums=range(4)))(q, k, v, g)
        want = ref(q, k, v, g)
        want_grads = jax.grad(loss(ref), argnums=range(4))(q, k, v, g)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=5e-6)
    # the running sum's own rounding shows at the bound (an ulp of 80 is
    # 8e-6, and exp carries it on)
    for a, b in zip(grads, want_grads):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))


def test_a_bound_the_sub_blocks_cannot_hold_is_refused():
    (q, k, v, g, beta, _), _ = make(64)
    with pytest.raises(ValueError, match="overflows float32"):
        dr.gated_delta_rule(q, k, v, g, beta, lower_bound=-11.0)
    with pytest.raises(ValueError, match="power of two"):
        dr.gated_delta_rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="'xla' or 'pallas'"):
        dr.gated_delta_rule(q, k, v, g, beta, impl="mosaic")


def in_a_kernel(fn, *arrays):
    """``fn(*arrays)`` inside a Mosaic call in interpret mode: the sublane
    and lane shifts of the kernels' helpers live in kernels only. fn
    returns one array or a list of arrays of one shape."""
    from jax.experimental import pallas as pl

    shapes = jax.eval_shape(fn, *arrays)

    def kernel(*refs):
        ins, outs = refs[:len(arrays)], refs[len(arrays):]
        got = fn(*(r[...] for r in ins))
        for o, x in zip(outs, got if isinstance(got, list) else [got]):
            o[...] = x
    return pl.pallas_call(kernel, interpret=True, out_shape=shapes)(*arrays)


@pytest.mark.parametrize("side, heads", [(64, 1), (128, 1), (64, 2),
                                         (32, 4)])
def test_the_inverse_by_block_substitution_is_the_inverse(side, heads):
    """Adjacent rows alike and beta 1, where the powers of A grow like
    binomials: the block form holds, at a chunk of 64 and of 128 alone and
    for the heads of a block side by side in ONE matrix (two chunks of 64
    in a side of 128, four of 32): each head's own inverse to the bit, and
    exactly 0 between the heads."""
    alike = jnp.tril(jnp.ones((side, side)), -1) * 0.97
    keys = jax.random.split(jax.random.PRNGKey(0), heads)
    blocks = [alike] + [jnp.tril(jax.random.normal(k, (side, side)), -1) * 0.3
                        for k in keys[1:]]
    if heads == 1:
        blocks.append(jnp.tril(jax.random.normal(keys[0], (side, side)), -1)
                      * 0.3)
        got = [in_a_kernel(dr._unit_lower_inverse, a) for a in blocks]
    else:
        got = in_a_kernel(lambda *a: dr._unit_lower_inverses(list(a)),
                          *blocks)
        whole = in_a_kernel(
            lambda a: dr._unit_lower_inverse(a, side),
            jax.scipy.linalg.block_diag(*blocks))
        for x, a in zip(got, blocks):
            np.testing.assert_array_equal(
                x, in_a_kernel(dr._unit_lower_inverse, a))
        between = jax.scipy.linalg.block_diag(
            *[jnp.ones((side, side))] * heads) == 0
        assert not bool(jnp.any(jnp.where(between, whole, 0.0) != 0.0))
    eye = jnp.eye(side)
    np.testing.assert_allclose(got[0] @ (eye + alike), eye, atol=4e-5)
    np.testing.assert_allclose(got[1], jnp.linalg.inv(eye + blocks[1]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("shifted, half", [(1, 1 << 20), (2, 1 << 20),
                                           (1, 8), (2, 8), (8, 16)])
def test_every_form_of_a_round_is_the_round(monkeypatch, shifted, half):
    """A round as two float32 products of all rows (the parent's, from the
    second round on), by shifted multiply-adds on the vector unit where the
    blocks are small, and through the products with the odd blocks' rows
    alone: the same inverse as the op's own mix of them, two heads of 64 in
    one matrix."""
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    blocks = [jnp.tril(jax.random.normal(k, (64, 64)), -1) * 0.3
              for k in keys]
    inverses = lambda *a: dr._unit_lower_inverses(list(a))      # noqa: E731
    want = in_a_kernel(inverses, *blocks)
    monkeypatch.setattr(dr, "SHIFTED_BLOCKS", shifted)
    monkeypatch.setattr(dr, "HALF_ROWS", half)
    for x, y in zip(in_a_kernel(inverses, *blocks), want):
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("form", ["shifted adds", "three passes"])
def test_a_running_sum_is_the_float32_product_with_the_triangle(form):
    """128 steps of gates at the bound and near it, down the rows and up
    them, against the 0/1 triangle's product at ``HIGHEST`` (the parent's
    form) to 1e-6: the op's log2(C) shifted adds, and the form that lost
    to them on the chip (``tests/delta_rule_forms.py``): a 0/1 operand is
    exact in bfloat16, so one pass for each of the other operand's three
    bfloat16 parts, which sum to it exactly, is the float32 product."""
    import delta_rule_forms as forms

    g = -5.0 + 1e-3 * jax.random.uniform(jax.random.PRNGKey(1), (128, 16))
    g = g.at[:, 0].set(-5.0)
    assert bool((sum(p.astype(jnp.float32) for p in forms.three_parts(g))
                 == g).all())
    running = dr._running_sum if form == "shifted adds" \
        else forms.three_pass_sum
    for from_end in (False, True):
        want = forms.six_pass_sum(g, from_end)
        got = in_a_kernel(lambda x: running(x, from_end), g)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        exact = np.cumsum(np.asarray(g, np.float64)[::-1 if from_end else 1],
                          axis=0)[::-1 if from_end else 1]
        np.testing.assert_allclose(got, exact, rtol=1e-6)


def test_the_plan_counts_the_states_and_the_vmem():
    plan = dr.plan(B=1, S=16384, H=32, dk=128, dv=128, dtype=jnp.bfloat16,
                   impl="pallas")
    assert plan["path"] == "pallas" and plan["sub_block"] == 16
    assert plan["heads_per_block"] == 2 and plan["chunk"] == 64
    # 256 chunks x 32 heads x [128, 128] float32
    assert plan["state_bytes_kept"] == 256 * 32 * 128 * 128 * 4
    assert 0 < plan["vmem_bytes"] <= 16 * 2 ** 20
    # two heads in ONE inverse of [128, 128]: a head and chunk 4 sub-blocks'
    # pair products and half of 3 rounds of two (the first round is no
    # product, the second and third shifted multiply-adds); the backward
    # makes them again and goes back through the pair blocks with 8. The
    # parent's count was 17 and 25
    assert plan["inverse_side"] == 128
    assert plan["f32_products_fwd"] == 4 + 3
    assert plan["f32_products_bwd"] == 4 + 3 + 8
    # the running sums are no product; the five others are one pass each
    assert plan["mxu_passes_fwd"] == 6 * 7 + 5
    # the rows those passes stream, a head: 4 pair products of [2 x 16, dk]
    # at six passes; the inverse's 3 rounds of two products of the odd
    # blocks' 64 of 128 rows, for two heads; four bfloat16 products of C
    # rows and the state's update of dv
    assert plan["mxu_rows_fwd"] == 4 * 32 * 6 + 3 * 2 * 64 * 6 // 2 \
        + 4 * 64 + 128
    # the backward makes the float32 products again, goes back through a
    # sub-block by [32, C] x [C, dk] and [C, 32] x [32, dk], and has twelve
    # bfloat16 products, two of them of dv rows
    assert plan["mxu_rows_bwd"] == 768 + 1152 + 4 * (32 + 64) * 6 \
        + 10 * 64 + 2 * 128
    assert plan["vector_levels"] == 0
    # a chunk of 128 is a head an inverse: 8 pair products, 4 rounds of two
    whole = dr.plan(B=1, S=16384, H=32, dk=128, dv=128, chunk=128,
                    dtype=jnp.bfloat16, impl="pallas")
    assert whole["inverse_side"] == 128 \
        and whole["f32_products_fwd"] == 8 + 8 \
        and whole["f32_products_bwd"] == 8 + 8 + 16
    assert 2 * whole["state_bytes_kept"] == plan["state_bytes_kept"]
    # an odd head count walks a head a block: its inverse is its own
    odd = dr.plan(B=1, S=64, H=3, dk=128, dv=128, dtype=jnp.bfloat16,
                  impl="pallas")
    assert odd["heads_per_block"] == 1 and odd["chunk"] == 64 \
        and odd["inverse_side"] == 64 and odd["f32_products_fwd"] == 4 + 6
    plain = dr.plan(B=1, S=100, H=2, dk=16, dv=16, chunk=32,
                    dtype=jnp.float32, impl="xla")
    assert plain["vmem_bytes"] == 0 and "inverse_side" not in plain


# --- the cut that needs no bound on the gate (lower_bound None) -------------


def free_gate(case: str, S: int, B=1, H=2, dk=16, dv=16, seed=0):
    """``make`` with a gate no bound holds and beta in (0, 2): ``drawn``
    g uniform in (-60, 0) on half the (step, channel) pairs and 0 on the
    others; ``zero`` g = 0 everywhere; ``dies`` every channel of head 0
    decays by exp(-200) at step 5 and a channel of head 1 by exp(-1000) at
    every step; ``alike`` beta 1.99, every key the same, no decay."""
    (q, k, v, g, beta, state), weight = make(S, B=B, H=H, dk=dk, dv=dv,
                                             seed=seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 3)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[0], beta.shape))
    if case == "drawn":
        g = -60.0 * jax.random.uniform(ks[1], g.shape) \
            * (jax.random.uniform(ks[2], g.shape) < 0.5)
    elif case == "zero":
        g = jnp.zeros_like(g)
    elif case == "dies":
        g = -jax.random.uniform(ks[1], g.shape)
        g = g.at[:, 5, 0, :].set(-200.0).at[:, :, 1, 3].set(-1000.0)
    elif case == "alike":
        g = jnp.zeros_like(g)
        k = jnp.broadcast_to(k[:, :1], k.shape)
        beta = jnp.full_like(beta, 1.99)
    return (q, k, v, g, beta, state), weight


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case, S, chunk", [
    ("drawn", 100, 32), ("drawn", 128, 64),
    ("zero", 64, 32), ("dies", 64, 32), ("dies", 128, 64),
    ("alike", 64, 32), ("alike", 128, 64),
    ("drawn", 40, 16), ("dies", 32, 16), ("zero", 256, 128),
    ("dies", 256, 128)])
def test_any_gate_with_no_stated_bound_is_the_recurrences(impl, case, S,
                                                          chunk):
    """``lower_bound`` None: the pair products cut in halves, each half
    split at its boundary, so that no factor passes 1. Gates down to -60 a
    step, no decay at all, channels that die in one step (nothing, not NaN
    or inf, forward and backward, dg included), beta 1.99 with every key
    alike (the inverse's entries alternate at +-2), a length that is no
    whole chunk: the output and all six gradients are the step-by-step
    recurrence's. Chunks of 16, 32, 64 and 128: one, two, three and four
    levels that are a product of the odd halves' rows above the three made
    on the vector unit (PR 66)."""
    args, weight = free_gate(case, S)

    def op(q, k, v, g, beta, state):
        return dr.gated_delta_rule(q, k, v, g, beta, initial_state=state,
                                   chunk=chunk, impl=impl, lower_bound=None)

    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        want_grads = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                              argnums=range(6))(*args)
        got = jax.jit(op)(*args)
        grads = jax.jit(jax.grad(lambda *a: (op(*a) * weight).sum(),
                                 argnums=range(6)))(*args)
    assert bool(jnp.isfinite(got).all())
    # every key alike at beta 1.99 and no decay: I + Diag(beta) Akk is 1.99
    # in every entry under the diagonal, its inverse alternates at 1.99 x
    # (-0.99)^n, and the rounds' sums of 32 terms of size 4 cancel to it:
    # the inverse by products reads 2.5e-5 off in an entry (6e-8 at beta
    # 0.97) and the output 1.8e-4 of 4 (relative L2 2.5e-5; the plain
    # path's solve 2e-6), the worst case there is
    loose = {"pallas": 100.0, "xla": 4.0}[impl] if case == "alike" else 1.0
    np.testing.assert_allclose(got, want, atol=5e-6 * loose)
    for name, a, b in zip("q k v g beta state".split(), grads, want_grads):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, atol=3e-5 * loose * float(jnp.abs(b).max()), err_msg=name)


def test_the_aligned_sums_are_sums_of_few_terms():
    """``_aligned_sums``: P_h the running sum inside aligned blocks of h
    rows, E_h the even half's total of a block of 2 h, made bottom up so
    that a sum over few rows never is the difference of two long sums: at
    gates of -60 beside gates of -1e-3 the sums over 1, 2 and 4 rows are
    float32's own (exact to an ulp of the block's total, where the chunk's
    running sum at -3,000 has an ulp of 2e-4); the last level's sum is the
    chunk's running sum."""
    c, dk = 64, 16
    g = jnp.where(jax.random.uniform(jax.random.PRNGKey(0), (c, dk)) < 0.5,
                  -60.0, -1e-3)
    got = in_a_kernel(
        lambda x: [a for p, e in dr._aligned_sums(x)[0] for a in (p, e)]
        + [dr._aligned_sums(x)[1]], g)
    exact = np.asarray(g, np.float64)
    for i in range(6):
        h = 1 << i
        blocks = exact.reshape(c // h, h, dk)
        want_p = np.cumsum(blocks, axis=1).reshape(c, dk)
        totals = blocks.sum(axis=1)                       # [c / h, dk]
        want_e = np.repeat(totals[0::2], 2 * h, axis=0)
        np.testing.assert_allclose(got[2 * i], want_p, rtol=3e-7, atol=1e-9)
        np.testing.assert_allclose(got[2 * i + 1], want_e, rtol=3e-7,
                                   atol=1e-9)
    np.testing.assert_allclose(got[-1], np.cumsum(exact, axis=0), rtol=3e-7)
    # the levels made on the vector unit (PR 66) take their exponents from
    # ``_shifted_sums``: the sum of the d rows that end at a row, each made
    # of the last by one add, for every pair inside a block of 8 rows
    shifted = in_a_kernel(lambda x: dr._shifted_sums(x, 8), g)
    assert len(shifted) == 7
    for d, got_d in enumerate(shifted, 1):
        want = sum(exact[d - 1 - j:c - j] for j in range(d))
        np.testing.assert_allclose(got_d[d - 1:], want, rtol=3e-7, atol=1e-9)


def test_no_factor_of_the_cut_in_halves_passes_one():
    """Both factors of every level lie in [0, 1] whatever the gate (a
    channel at -1000 a step underflows to 0, never an inf or a NaN), and
    their product at the level's pairs is exp(G_t - G_s)."""
    c, dk = 32, 8
    g = -jax.random.uniform(jax.random.PRNGKey(2), (c, dk)) * 3.0
    g = g.at[:, 0].set(-1000.0).at[7, 1].set(-300.0)
    factors = in_a_kernel(
        lambda x: [f for p, e in dr._aligned_sums(x)[0]
                   for f in dr._level_factors(p, e)], g)
    cum = np.cumsum(np.asarray(g, np.float64), axis=0)
    for i in range(5):
        rows, cols = (np.asarray(f, np.float64) for f in factors[2 * i:][:2])
        assert (rows >= 0).all() and (rows <= 1).all()
        assert (cols >= 0).all() and (cols <= 1).all()
        h = 1 << i
        for t in range(c):
            for s in range(t):
                if (t ^ s) < 2 * h and t & ~s & h:
                    np.testing.assert_allclose(
                        rows[t] * cols[s], np.exp(cum[t] - cum[s]),
                        rtol=1e-4, atol=1e-37)
    # a pair inside a block of 8 rows has ONE factor (PR 66): exp of the sum
    # of g over the rows between, in [0, 1] too
    for d, run in enumerate(in_a_kernel(lambda x: dr._shifted_sums(x, 8), g),
                            1):
        factor = np.exp(np.asarray(run, np.float64))
        in_float32 = np.asarray(jnp.exp(run))
        assert (in_float32 >= 0).all() and (in_float32 <= 1).all()
        for t in range(d, c):
            if t % 8 >= d:
                np.testing.assert_allclose(
                    factor[t], np.exp(cum[t] - cum[t - d]), rtol=1e-4,
                    atol=1e-37)


CUTS = [("the op's own", dr._pair_blocks_free, dr._pair_grads_free)] \
    + cut_forms.FORMS


@pytest.mark.parametrize("c", [16, 32, 64, 128])
@pytest.mark.parametrize("form", CUTS, ids=[name for name, _, _ in CUTS])
def test_every_form_of_a_level_is_the_level(form, c):
    """The cut in halves as the op makes it (the levels of 1, 2 and 4 rows
    by shifted multiply-adds, a level from 8 rows up one product of the odd
    halves' rows) and every form that was timed against it
    (``tests/delta_rule_cut_forms.py``: the narrow side streamed and turned,
    the even halves' columns alone, one, two or three levels on the vector
    unit) against PR 61's: ONE product of every row with every column a
    level, masked to the level's pairs; forward and the way back, at chunks
    of 16, 32, 64 and 128 (one to four levels that are a product). On the
    chip a product's entry does not depend on which other rows stream with
    it; the CPU's may, so float32 rounding of the same sums is what is
    held."""
    name, forward, back = form
    dk = 16
    ks = jax.random.split(jax.random.PRNGKey(c), 6)
    q, k = (jax.random.normal(key, (c, dk)) * 0.3 for key in ks[:2])
    g = -20.0 * jax.random.uniform(ks[2], (c, dk)) \
        * (jax.random.uniform(ks[3], (c, dk)) < 0.5)
    t, s = np.arange(c)[:, None], np.arange(c)[None]
    d_qk = jnp.where(t >= s, jax.random.normal(ks[4], (c, c)), 0.0)
    d_kk = jnp.where(t > s, jax.random.normal(ks[5], (c, c)), 0.0)

    def both_ways(forward, back):
        return (in_a_kernel(lambda q, k, g: list(forward(
                    q, k, dr._aligned_sums(g)[0])), q, k, g)
                + in_a_kernel(lambda q, k, g, a, b: list(back(
                    q, k, dr._aligned_sums(g)[0], a, b)), q, k, g, d_qk,
                    d_kk))

    want = both_ways(cut_forms.whole_products, cut_forms.whole_grads)
    got = both_ways(forward, back or cut_forms.whole_grads)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6,
                                   atol=2e-6 * float(jnp.abs(b).max()),
                                   err_msg=name)


# sha256[:16] of the jaxpr of the kernel path and its five gradients (the
# kernels' bodies are in it) for a caller that STATES its bound, at Ling's
# tiny shape ([2, 128, 2, 16] float32, chunks of 64), taken at PR 61's
# PARENT (77455d7, jax 0.9.0): the cut a bound allows is the program it was
# (the Ling cell's lowered step says the same at the cell's size,
# tests/lowered_cells.py). A digest that moves with a change that MEANS to
# change the bounded cut is replaced, and says so.
BOUNDED_FROM = "0.9.0"
BOUNDED_JAXPR = "fd1329bd10fe131f"


@pytest.mark.parametrize("stated", [{}, {"lower_bound": -5.0}])
def test_a_caller_that_states_its_bound_gets_the_parents_program(stated):
    import hashlib
    import re

    if jax.__version__ != BOUNDED_FROM:
        pytest.skip(f"the digest was taken under jax {BOUNDED_FROM}")
    x = jax.ShapeDtypeStruct((2, 128, 2, 16), jnp.float32)
    beta = jax.ShapeDtypeStruct((2, 128, 2), jnp.float32)
    loss = lambda q, k, v, g, b: dr.gated_delta_rule(          # noqa: E731
        q, k, v, g, b, impl="pallas", chunk=64, **stated).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(
        x, x, x, x, beta))
    text = re.sub(r"0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == BOUNDED_JAXPR


def test_the_plan_says_which_cut_ran():
    """``lower_bound`` None: the cut in halves, its block sizes, how many of
    its levels are made on the vector unit, its float32 products a head and
    chunk (3 levels of the odd halves' rows where the bounded cut has 4
    sub-blocks and PR 61's form had 6 whole levels; 6 more on the way back
    where they have 8 and 12), the rows its passes stream, and two [C, dk]
    forms more in VMEM for each level that is a product; a stated bound:
    the parent's plan."""
    shape = dict(B=1, S=16384, H=64, dk=128, dv=128, dtype=jnp.bfloat16,
                 impl="pallas")
    free = dr.plan(**shape, lower_bound=None)
    assert free["cut"] == "halving" and free["lower_bound"] is None
    assert free["cut_sizes"] == [2, 4, 8, 16, 32, 64]
    assert free["vector_levels"] == 3
    assert free["f32_products_fwd"] == 3 + 3
    assert free["f32_products_bwd"] == 3 + 3 + 6
    # a level streams [q; k] of its odd halves, 2 x 32 rows; PR 61's form
    # streamed 6 levels of 2 x 64: 768 x 6 = 4,608 in the pair blocks alone
    assert free["mxu_rows_fwd"] == 3 * 64 * 6 + 1152 + 384 < 768 * 6
    assert free["mxu_rows_bwd"] == 2 * 1152 + 3 * (64 + 64) * 6 \
        + 10 * 64 + 2 * 128
    assert free["state_bytes_kept"] == 256 * 64 * 128 * 128 * 4
    bounded = dr.plan(**shape)
    assert bounded["cut"] == "bounded" and bounded["cut_sizes"] == []
    assert bounded["vector_levels"] == 0
    assert bounded["f32_products_fwd"] == 4 + 3
    assert bounded["mxu_rows_fwd"] == 4 * 32 * 6 + 1152 + 384
    assert bounded["vmem_bytes"] < free["vmem_bytes"] <= 16 * 2 ** 20
    assert free["vmem_bytes"] - bounded["vmem_bytes"] \
        == 2 * 3 * 2 * 64 * 128 * 4      # two heads, 3 levels, p and e
    # a chunk of 16 has ONE level that is a product, a chunk of 128 four
    for chunk, levels in ((16, 1), (32, 2), (128, 4)):
        said = dr.plan(**shape, chunk=chunk, lower_bound=None)
        assert said["vector_levels"] == 3
        assert len(said["cut_sizes"]) - 3 == levels
