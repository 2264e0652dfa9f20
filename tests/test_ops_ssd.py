"""The chunked state-space scan (ops/ssd.py) against the recurrence
written token by token in float32: values and gradients, the kernel in
interpret mode and the einsum path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd


def recurrence(x, dt, a, bm, cm):
    """s_t = exp(dt_t a) s_{t-1} + dt_t x_t B_t^T, y_t = s_t C_t, one
    step at a time. x [B, S, H, P], dt [B, S, H], a [H], bm, cm [B, S, N]."""
    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = s * jnp.exp(dtt * a)[..., None, None] \
            + jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, bt)
        return s, jnp.einsum("bhpn,bn->bhp", s, ct)

    B, _, H, P = x.shape
    s0 = jnp.zeros((B, H, P, bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


def inputs(seed, B, S, H, P, N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    # steps and rates as a mixer makes them: dt in (0.001, 0.7), a in -(1, 16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) * 2 - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                    maxval=jnp.log(16.0)))
    bm = jax.random.normal(ks[3], (B, S, N), jnp.float32) * N ** -0.25
    cm = jax.random.normal(ks[4], (B, S, N), jnp.float32) * N ** -0.25
    return x, dt, a, bm, cm


# several chunks; heads fewer than the kernel's block of 16 (one head
# block, short), and more than one block (32 heads: two)
SHAPES = {"one short head block": (2, 96, 4, 16, 8, 32),
          "two head blocks, width 64": (1, 64, 32, 64, 16, 32),
          "a chunk of 128": (1, 384, 2, 8, 8, 128)}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_scan_values_against_the_recurrence(shape, impl):
    B, S, H, P, N, chunk = SHAPES[shape]
    args = inputs(0, B, S, H, P, N)
    want = recurrence(*args)
    got = ssd.ssd_scan(*args, chunk=chunk, impl=impl)
    assert got.shape == (B, S, H, P) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", sorted(SHAPES)[:2])
def test_scan_gradients_against_the_recurrence(shape, impl):
    B, S, H, P, N, chunk = SHAPES[shape]
    args = inputs(1, B, S, H, P, N)
    probe = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, P))

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * probe),
                        argnums=(0, 1, 2, 3, 4))(*args)

    want = through(recurrence)
    got = through(lambda *a: ssd.ssd_scan(*a, chunk=chunk, impl=impl))
    for name, g, w in zip(("x", "dt", "a", "B", "C"), got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-3 * scale,
                                   err_msg=name)


def grouped(fn, groups):
    """``fn`` (the recurrence) a group of adjacent heads at a time: x
    [B, S, H, P], bm and cm [B, S, G, N]."""
    def run(x, dt, a, bm, cm):
        per = x.shape[2] // groups
        return jnp.concatenate([
            fn(x[:, :, g * per:(g + 1) * per], dt[..., g * per:(g + 1) * per],
               a[g * per:(g + 1) * per], bm[:, :, g], cm[:, :, g])
            for g in range(groups)], axis=2)
    return run


def group_inputs(seed, B, S, H, P, N, groups):
    x, dt, a, _, _ = inputs(seed, B, S, H, P, N)
    k = jax.random.split(jax.random.PRNGKey(seed + 100), 2)
    bm, cm = (jax.random.normal(kk, (B, S, groups, N), jnp.float32)
              * N ** -0.25 for kk in k)
    return x, dt, a, bm, cm


# (B, S, H, P, N, chunk, G): a block's heads are of one group. 16 heads in
# 8 groups: blocks of a pair; 32 in 2: one block of 16 a group; 16 in 1
# given as [B, S, 1, N]
GROUPED = {"8 groups of a pair": (1, 64, 16, 16, 8, 32, 8),
           "2 groups of 16": (2, 64, 32, 64, 16, 32, 2),
           "1 group, stated": (1, 96, 16, 16, 8, 32, 1)}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", sorted(GROUPED))
def test_grouped_scan_against_the_recurrence_a_group(shape, impl):
    """B and C a group of heads: values and every gradient against the
    token-by-token recurrence run a group at a time."""
    B, S, H, P, N, chunk, G = GROUPED[shape]
    args = group_inputs(3, B, S, H, P, N, G)
    probe = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, P))

    def through(fn):
        return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * probe),
                                  argnums=(0, 1, 2, 3, 4))(*args)

    np.testing.assert_allclose(
        ssd.ssd_scan(*args, chunk=chunk, impl=impl),
        grouped(recurrence, G)(*args), rtol=2e-4, atol=2e-4)
    (_, want), (_, got) = through(grouped(recurrence, G)), through(
        lambda *a: ssd.ssd_scan(*a, chunk=chunk, impl=impl))
    for name, g, w in zip(("x", "dt", "a", "B", "C"), got, want):
        assert g.shape == w.shape, name
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-3 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_one_group_stated_is_the_one_group_call_bit_for_bit(impl):
    """[B, S, 1, N] and [B, S, N] are one program's values: the grouped
    call at G = 1 is the call Granite makes."""
    args = inputs(4, 1, 96, 16, 16, 8)
    stated = args[:3] + tuple(t[:, :, None] for t in args[3:])
    run = lambda a: jax.value_and_grad(                      # noqa: E731
        lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=32, impl=impl) ** 2),
        argnums=(0, 1, 2, 3, 4))(*a)
    (v0, g0), (v1, g1) = run(args), run(stated)
    assert float(v0) == float(v1)
    for a, b in zip(g0, g1):
        np.testing.assert_array_equal(np.asarray(a).ravel(),
                                      np.asarray(b).ravel())


def test_long_decay_neither_overflows_nor_vanishes():
    """exp(cum_t - cum_s) is taken of the DIFFERENCE: a chunk whose
    running sum passes -700 (exp underflows in float32, its inverse
    overflows) still gives the recurrence's values and finite gradients."""
    B, S, H, P, N, chunk = 1, 256, 2, 8, 8, 128
    x, dt, a, bm, cm = inputs(2, B, S, H, P, N)
    dt = jnp.full_like(dt, 0.7)
    a = jnp.array([-16.0, -1.0])            # cum reaches -1,433 in a chunk
    want = recurrence(x, dt, a, bm, cm)
    for impl in ("xla", "pallas"):
        got = ssd.ssd_scan(x, dt, a, bm, cm, chunk=chunk, impl=impl)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        g = jax.grad(lambda dt: jnp.sum(ssd.ssd_scan(
            x, dt, a, bm, cm, chunk=chunk, impl=impl)))(dt)
        assert bool(jnp.all(jnp.isfinite(g)))


def test_bf16_inputs_give_bf16_out_and_stay_near():
    B, S, H, P, N, chunk = 1, 128, 4, 64, 32, 64
    x, dt, a, bm, cm = inputs(3, B, S, H, P, N)
    want = recurrence(x, dt, a, bm, cm)
    bf = jnp.bfloat16
    for impl in ("xla", "pallas"):
        got = ssd.ssd_scan(x.astype(bf), dt, a, bm.astype(bf), cm.astype(bf),
                           chunk=chunk, impl=impl)
        assert got.dtype == bf
        err = jnp.abs(got.astype(jnp.float32) - want)
        assert float(err.mean()) < 0.02 * float(jnp.abs(want).mean())


def test_refusals_and_plan():
    args = inputs(0, 1, 96, 4, 16, 8)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd.ssd_scan(*args, chunk=64)
    with pytest.raises(ValueError, match="'xla' or 'pallas'"):
        ssd.ssd_scan(*args, chunk=32, impl="cuda")
    p = ssd.plan(S=8192, H=128, P=64, N=128, chunk=256, dtype=jnp.bfloat16,
                 impl="pallas")
    assert p["heads_per_block"] == 16 and p["path"] == "pallas"
    assert p["vmem_bytes"] < 16 * 2 ** 20     # Mosaic's default scoped limit
    # u, y; u, dy, du of 8192 rows of 64 in bf16 and 32 states in and out
    assert p["hbm_bytes_per_head"] == 8192 * 64 * 2 * 5 + 2 * 32 * 64 * 128 * 4
    assert ssd.plan(S=96, H=4, P=16, N=8, chunk=32, dtype=jnp.float32,
                    impl="xla")["vmem_bytes"] == 0
    assert (p["groups"], p["heads_per_group"]) == (1, 128)
    # Nemotron 3 Nano's: 64 heads in 8 groups, a block is a group's 8 heads
    p = ssd.plan(S=8192, H=64, P=64, N=128, chunk=128, dtype=jnp.bfloat16,
                 impl="pallas", G=8)
    assert (p["heads_per_block"], p["groups"], p["heads_per_group"]) \
        == (8, 8, 8)
    assert p["vmem_bytes"] < 16 * 2 ** 20
    with pytest.raises(ValueError, match="groups"):
        ssd.plan(S=96, H=6, P=16, N=8, chunk=32, dtype=jnp.float32,
                 impl="xla", G=4)


def test_ssd_plan_instant_once_a_trace(monkeypatch):
    from ray_tpu.util import tracing

    seen = []
    monkeypatch.setattr(tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, attrs)))
    args = inputs(0, 1, 96, 4, 16, 8)
    fn = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=32, impl="pallas"))
    fn(*args)
    fn(*args)                                # compiled: no second trace
    plans = [a for n, a in seen if n == "ssd.plan"]   # a compile is one too
    assert len(plans) == 1
    assert plans[0]["chunk"] == 32 and plans[0]["heads_per_block"] == 4


# --- heads of a lane tile with steps, in groups: the "tile" layout ----------

# (H, G, N, chunk, dtype): P = 128 and S = 64 in all; G in (1, 2, H), N in
# (P, 2 P), two chunks, both types
TILE = {"one group, N = P, float32": (4, 1, 128, 16, "float32"),
        "two groups, N = 2 P, float32": (4, 2, 256, 32, "float32"),
        "a group a head, N = 2 P, float32": (2, 2, 256, 16, "float32"),
        "two groups, N = 2 P, bfloat16": (4, 2, 256, 32, "bfloat16"),
        "one group, N = P, bfloat16": (4, 1, 128, 16, "bfloat16"),
        "a group a head, N = P, bfloat16": (2, 2, 128, 32, "bfloat16")}


@pytest.mark.parametrize("case", sorted(TILE))
def test_tile_layout_against_the_plain_path_and_the_recurrence(case):
    """Heads of 128 with steps (``layout`` "tile") in interpret mode: the
    value and all five gradients against the "xla" path on the same inputs
    and against the token-by-token recurrence in float32."""
    H, G, N, chunk, dtype = TILE[case]
    B, S, P = 1, 64, ssd.LANE_TILE
    plan = ssd.plan(S=S, H=H, P=P, N=N, chunk=chunk, dtype=dtype,
                    impl="pallas", G=G)
    assert (plan["layout"], plan["decay"], plan["state"]) \
        == ("tile", "stepped", N)
    x, dt, a, bm, cm = group_inputs(5, B, S, H, P, N, G)
    x = x * 0.25                            # sums over 128 lanes stay O(1)
    probe = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, P))
    low = jnp.dtype(dtype)
    cast = lambda x, dt, a, bm, cm: (                        # noqa: E731
        x.astype(low), dt, a, bm.astype(low), cm.astype(low))

    def through(fn, args):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe),
            argnums=(0, 1, 2, 3, 4)))(*args)

    args = cast(x, dt, a, bm, cm)
    exact = tuple(t.astype(jnp.float32) for t in args)   # the rounded inputs
    (v, got), (vx, plain), (vr, want) = (
        through(lambda *a: ssd.ssd_scan(*a, chunk=chunk, impl="pallas"), args),
        through(lambda *a: ssd.ssd_scan(*a, chunk=chunk, impl="xla"), args),
        through(grouped(recurrence, G), exact))
    tol = 2e-3 if dtype == "float32" else 3e-2
    scale = float(jnp.sqrt(jnp.mean(probe ** 2)) * S * H * P) ** 0.5
    assert abs(float(v) - float(vr)) < tol * scale * 10, (v, vr)
    assert abs(float(vx) - float(vr)) < tol * scale * 10, (vx, vr)
    for name, g, px, w in zip(("x", "dt", "a", "B", "C"), got, plain, want):
        assert g.shape == w.shape and g.dtype == px.dtype, name
        norm = float(jnp.linalg.norm(w.astype(jnp.float32)))
        for which, other in (("recurrence", w), ("plain path", px)):
            err = float(jnp.linalg.norm(
                g.astype(jnp.float32) - other.astype(jnp.float32)))
            assert err <= tol * norm, (name, which, err / norm)


def test_tile_plan_at_a_falcon_mixer_and_the_three_layouts_named():
    """32 heads of 128 in 2 groups, a state of 256, chunk 128: a block of a
    group's heads under ``TILE_VMEM``; the refusal names the layouts."""
    p = ssd.plan(S=16384, H=32, P=128, N=256, chunk=128, dtype=jnp.bfloat16,
                 impl="pallas", G=2)
    assert (p["layout"], p["decay"], p["groups"], p["heads_per_group"],
            p["state"]) == ("tile", "stepped", 2, 16, 256)
    assert p["heads_per_block"] in (8, 16)
    assert p["vmem_bytes"] <= ssd.TILE_VMEM < 16 * 2 ** 20
    # the pairs and wide plans are what they were
    assert ssd.plan(S=8192, H=64, P=64, N=128, chunk=128, dtype=jnp.bfloat16,
                    impl="pallas", G=8)["layout"] == "pairs"
    assert ssd.plan(S=16384, H=32, P=128, N=128, chunk=256,
                    dtype=jnp.bfloat16, impl="pallas", G=32,
                    steady=True)["layout"] == "wide"
    x, dt, a, bm, cm = group_inputs(0, 1, 32, 3, 16, 8, 1)
    with pytest.raises(ValueError, match="'pairs'.*'tile'.*'wide'"):
        ssd.ssd_scan(x, dt, a, bm, cm, chunk=16, impl="pallas")
