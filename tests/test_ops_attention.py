"""Kernel correctness: flash attention (pallas, interpret on CPU) and ring
attention (8-device CPU mesh) vs the reference einsum implementation."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from ray_tpu.models.llama import _attention_xla  # noqa: E402
from ray_tpu.ops.flash_attention import flash_attention  # noqa: E402
from ray_tpu.ops.ring_attention import ring_attention  # noqa: E402


def _make(B=2, S=256, H=4, KV=2, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    return q, k, v


def test_flash_matches_reference():
    q, k, v = _make()
    ref = _attention_xla(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_gradients_match():
    q, k, v = _make(B=1, S=128, H=2, KV=2, D=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, block_q=32, block_k=32).sum()

    def loss_ref(q, k, v):
        return _attention_xla(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_ring_attention_matches_reference():
    mesh = jax.make_mesh((8,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=2, S=256, H=4, KV=4, D=32)
    ref = _attention_xla(q, k, v, causal=True)

    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_ring_attention_grads_match():
    mesh = jax.make_mesh((4,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=1, S=64, H=2, KV=2, D=16)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))

    g1 = jax.grad(lambda *a: ring(*a).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=True)
                  .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_gqa_flash():
    q, k, v = _make(B=1, S=128, H=8, KV=2, D=32)
    ref = _attention_xla(q, k, v, causal=True)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_gqa_flash_gradients():
    """GQA backward: dK/dV group-sum must match the broadcast reference."""
    q, k, v = _make(B=1, S=64, H=8, KV=2, D=16)

    g1 = jax.grad(lambda *a: flash_attention(
        *a, block_q=32, block_k=32).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_noncausal_flash_gradients():
    q, k, v = _make(B=1, S=64, H=2, KV=2, D=16)
    g1 = jax.grad(lambda *a: flash_attention(
        *a, causal=False, block_q=32, block_k=32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=False).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("heads,window", [
    ((4, 2), None), ((8, 1), 64), ((8, 1), 100), ((8, 1), 20), ((8, 1), 256),
    ((8, 1), 300)], ids=["groups2-full", "groups8-w64", "groups8-w100",
                         "groups8-w20", "groups8-wS", "groups8-w300"])
def test_pallas_bwd_matches_chunked_bwd(heads, window, monkeypatch):
    """The two Pallas calls of the backward against the chunked recompute
    (``_reference_chunked_bwd``) from the same residuals; with 8 query
    heads a KV head at windows of two blocks, of no multiple of a block,
    of under a block, of S and over, with no VMEM to speak of, so that
    the dQ call holds a q-block's whole band in one grid step and the
    dK/dV call steps over a k-block's band alone wherever the window ends
    the bands before S does."""
    import sys

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    if window:
        monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES", 200_000)
        monkeypatch.setattr(fa, "_vmem_bytes", lambda: 1024)
    q, k, v = _make(B=1, S=256 if window else 128, H=heads[0], KV=heads[1],
                    D=32)

    def grads():
        return jax.grad(lambda *a: flash_attention(
            *a, block_q=32, block_k=32, window=window).sum(),
            argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setattr(fa, "BACKWARD_IMPL", "pallas")
    gp = grads()
    monkeypatch.setattr(fa, "BACKWARD_IMPL", "chunked")
    gc = grads()
    for a, b in zip(gp, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


def test_flash_residuals_compact_lse_feeds_both_backwards(monkeypatch):
    """The forward rule hands the backward `lse` as [B, H, S], not the
    kernel's 128-lane broadcast (which would be twice the size of `o`
    where a checkpoint keeps it), and both backward implementations give
    the same gradients from that one residual tuple."""
    import sys

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    B, S, H, KV, D = 1, 128, 4, 2, 32
    q, k, v = _make(B=B, S=S, H=H, KV=KV, D=D)
    out, res = fa._flash_vjp_fwd(q, k, v, True, 32, 32, 0)
    assert [r.shape for r in res] == [
        (B, S, H, D), (B, S, KV, D), (B, S, KV, D), (B, S, H, D), (B, H, S)]
    assert res[4].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(res[3]), np.asarray(out))
    # lse is the row's log-sum-exp of the masked, scaled scores
    s = jnp.einsum("bshd,bthd->bhst", q, jnp.repeat(k, H // KV, axis=2))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s * D ** -0.5, -jnp.inf)
    np.testing.assert_allclose(np.asarray(res[4]), np.asarray(
        jax.nn.logsumexp(s, axis=-1)), rtol=1e-4, atol=1e-4)

    g = jax.random.normal(jax.random.PRNGKey(9), out.shape, out.dtype)
    grads = {}
    for impl in ("pallas", "chunked"):
        monkeypatch.setattr(fa, "BACKWARD_IMPL", impl)
        grads[impl] = fa._flash_vjp_bwd(True, 32, 32, 0, None, res, g)
    for a, b in zip(grads["pallas"], grads["chunked"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


def test_ulysses_matches_reference():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = jax.make_mesh((8,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=2, S=256, H=8, KV=8, D=32)
    ref = _attention_xla(q, k, v, causal=True)

    uly = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    out = uly(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ulysses_gqa_matches_reference():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = jax.make_mesh((4,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    # H=8, KV=4 over sp=4: 2 q-heads + 1 kv-head per chip, G=2 preserved
    q, k, v = _make(B=1, S=128, H=8, KV=4, D=16)
    ref = _attention_xla(q, k, v, causal=True)
    uly = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp")))
    np.testing.assert_allclose(np.asarray(uly(q, k, v)), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ulysses_grads_match():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = jax.make_mesh((4,), ("sp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    q, k, v = _make(B=1, S=64, H=4, KV=4, D=16)

    uly = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))
    g1 = jax.grad(lambda *a: uly(*a).astype(jnp.float32).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: _attention_xla(*a, causal=True)
                  .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def _dense_windowed(q, k, v, window):
    """f32 dense reference with the causal + sliding-window band mask."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    g = H // KV
    kf = jnp.repeat(k, g, axis=2)
    vf = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, kf) * D ** -0.5
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(S)[None, :]
    mask = (qp >= kp) & (qp - kp < window)
    s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), vf)


def test_flash_sliding_window_matches_reference():
    """Mistral-style banded attention (window=W) against the dense
    banded mask, incl. GQA. Absolute tolerance matches the f32
    attention noise floor (the f32 XLA dense itself differs from f64
    exact by ~6e-3 at these shapes)."""
    q, k, v = _make()
    for W in (64, 96, 256):
        out = flash_attention(q, k, v, window=W, block_q=64, block_k=64)
        ref = _dense_windowed(q, k, v, W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=8e-3)
    # window >= S degenerates to plain causal
    full = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    w_s = flash_attention(q, k, v, window=q.shape[1], block_q=64,
                          block_k=64)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(w_s))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)


def test_flash_sliding_window_gradients():
    q, k, v = _make(B=1, S=256, H=2, KV=2, D=32)
    W = 96

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, window=W, block_q=64,
                               block_k=64).sum()

    def loss_ref(q, k, v):
        return _dense_windowed(q, k, v, W).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-2)


@pytest.mark.parametrize("window", [100, None, 64, 40, 256, 300],
                         ids=["w100", "full", "w64", "w40", "wS", "w300"])
@pytest.mark.parametrize("what", ["fwd", "dq", "dkdv"])
@pytest.mark.parametrize("plans", ["by_bytes", "streamed"])
def test_flash_at_eight_query_heads_a_kv_head_against_xla(plans, what, window,
                                                          monkeypatch):
    """Mellum2's grouping (32 query heads over 4 KV heads: 8 a group) at
    windows of a block, of no multiple of it, of under a block, of S and
    over, and with none: the banded forward, dQ and dK/dV calls against
    the model's own einsum attention (``llama._attention_xla``) under the
    same mask. ``by_bytes``: the plans these small shapes get (a windowed
    forward holds its band or streams, the dQ call loops, the dK/dV call
    is resident); ``streamed``: no VMEM to speak of, so no call holds a
    head: in blocks of 32 the forward and the dQ call hold a q-block's
    whole band in one grid step and the dK/dV call steps over a k-block's
    band alone wherever the window ends the bands before T does, and all
    three step over T (the dK/dV call over S, two q-blocks a grid step)
    where it does not."""
    from ray_tpu.models.llama import _attention_xla

    fa = _fa()
    block = 64
    if plans == "streamed":
        # room for a band of three k-blocks all in flight, for one of five
        # with one (forward) or two (dQ), for half of T's eight, not for T
        block = 32
        monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES", 205_000)
        monkeypatch.setattr(fa, "_vmem_bytes", lambda: 1024)
        seen = {}
        monkeypatch.setattr(fa.tracing, "instant",
                            lambda name, attrs=None, **kw: seen.update(
                                {name: attrs}))
    q, k, v = _make(B=1, S=256, H=8, KV=1, D=32, seed=11)
    g = jax.random.normal(jax.random.PRNGKey(12), q.shape, q.dtype)

    def flash(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=block,
                               block_k=block)

    def xla(q, k, v):
        return _attention_xla(q, k, v, True, window=window)

    if what == "fwd":
        got, want = flash(q, k, v), xla(q, k, v)
    else:
        argnums = (0,) if what == "dq" else (1, 2)
        got, want = (jax.grad(lambda *a: (f(*a) * g).sum(), argnums)(q, k, v)
                     for f in (flash, xla))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-2)
    if window is not None and window <= 256 and what == "fwd":
        # one key more or fewer is another result: the band's edge counts
        off = flash_attention(q, k, v, window=window - 1, block_q=block,
                              block_k=block)
        assert float(jnp.max(jnp.abs(off - got))) > 1e-3
    if plans == "by_bytes":
        return
    # the calls took the plans meant: a window of 40 to 100 keys (bands
    # of 3 to 5 of the 8 blocks) ends every band early
    banded = window in (40, 64, 100)
    path = "band" if banded else "stream"
    band = {40: 96, 64: 96, 100: 160}.get(window)
    fwd = seen["flash.fwd_plan"]
    assert (fwd["path"], fwd["span"], fwd["in_flight"]) == (
        (path, band, 1 if window == 100 else 3) if banded
        else (path, 128, 2)), fwd
    # 8 heads x 8 blocks x the axis: one step a q-block, or T's two spans
    grids = [(fwd["grid_steps"], fwd["band_steps"], 1 if banded else 2)]
    if what != "fwd":
        back = seen["flash.bwd_plan"]
        assert (back["path"], back["dq_path"], back["dq_span"],
                back["dq_in_flight"]) == (
            (path, path, band, 2 if window == 100 else 3) if banded
            else (path, path, 128, 2)), back
        # the dK/dV call's q axis: the longest band of a k-block, one
        # q-block a step, or S's 8 q-blocks in spans of two
        assert (back["span"], back["in_flight"]) == (
            (32, 1) if banded else (64, 1)), back
        grids += [(back["dq_grid_steps"], back["dq_band_steps"],
                   1 if banded else 2),
                  (back["grid_steps"], back["band_steps"],
                   {40: 3, 64: 3, 100: 5}.get(window, 4))]
    for steps, work, axis in grids:
        assert 0 < work <= steps == 8 * 8 * axis, (steps, work, axis, seen)
        assert not banded or work > 0.7 * steps, (steps, work)


# -- the dK/dV call's two block plans (ops/flash_attention.py) ---------------

def _fa():
    import sys

    return sys.modules["ray_tpu.ops.flash_attention"]


def _dkdv(path, res, g, *, causal, window, block):
    """The dK/dV call alone on the backward's residuals, one plan forced
    through the VMEM size the plan is made for; returns [B, T, KV, D]."""
    fa = _fa()
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    KV = k.shape[2]
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (128,))
    # `stream4x2`: the streamed call at 4 q-blocks a grid step, 2 in flight
    path, _, walk = path.partition("stream")
    path, walk = path or "stream", tuple(map(int, walk.split("x"))) if walk \
        else None
    vmem = {"resident": 128 * 2 ** 20, "stream": 1024}[path]
    plan = fa.bwd_dkdv_plan(
        S=S, T=S, D=D, dtype=q.dtype, groups=H // KV,
        block_q=block, block_k=block, causal=causal, window=window,
        vmem_bytes=vmem)
    # a streamed call whose window ends the bands early is named `band`
    assert plan["path"] in {"resident": ("resident",),
                            "stream": ("stream", "band")}[path]
    dk, dv = fa._flash_bwd_dkdv(
        t(q), t(k), t(v), t(g), t(out), lse, causal=causal, block_q=block,
        block_k=block, window=window, vmem_bytes=vmem, walk=walk)
    return [x.astype(jnp.float32).reshape(B, KV, H // KV, S, D).sum(2)
            .transpose(0, 2, 1, 3) for x in (dk, dv)]


def _window(mask: str) -> int:
    """``window64`` -> 64, ``window100`` (no multiple of the tests' block
    of 32: the band's edge cuts a block) -> 100, else 0."""
    return int(mask[6:]) if mask.startswith("window") else 0


# the streamed call also at 2 q-blocks a grid step, both in flight, at 8
# blocks: whole, diagonal and empty spans all occur
# (test_the_walk_of_k_blocks_... holds every form to the last bit)
DKDV_PLANS = [(path, blocks) for path in ("resident", "stream")
              for blocks in (1, 4)] + [("stream2x2", 8)]


@pytest.mark.parametrize("heads", [(2, 2), (4, 2), (8, 1)],
                         ids=["H=KV", "groups2", "groups8"])
@pytest.mark.parametrize("mask", ["causal", "noncausal", "window64",
                                  "window100"])
@pytest.mark.parametrize("path,blocks", DKDV_PLANS,
                         ids=[f"{p}-S={b}blocks" for p, b in DKDV_PLANS])
def test_dkdv_block_plans(path, mask, heads, blocks):
    """Each plan against the chunked reference at the file's tolerance,
    and against the other plan to 1e-6: the same float32 sums in the same
    order, only the blocks arrive differently (a window that ends the
    bands early keeps the streamed call on its one q-block a step)."""
    fa = _fa()
    block = 32
    causal, window = mask != "noncausal", _window(mask)
    q, k, v = _make(B=1, S=block * blocks, H=heads[0], KV=heads[1], D=32,
                    seed=3)
    _, res = fa._flash_vjp_fwd(q, k, v, causal, block, block, window)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)
    got = _dkdv(path, res, g, causal=causal, window=window, block=block)
    _, dk_ref, dv_ref = fa._reference_chunked_bwd(
        res, g, causal=causal, chunk=block, window=window)
    other = _dkdv("stream" if path == "resident" else "resident", res, g,
                  causal=causal, window=window, block=block)
    for a, ref, b in zip(got, (dk_ref, dv_ref), other):
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref),
                                   rtol=3e-3, atol=3e-3)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# the parent's grid (PR 26): every grid step fetched query-side block qi,
# float32 results
PARENT_DKDV_PLAN = dict(path="stream", out_itemsize=4,
                        q_index=lambda ki, qi: qi)


def test_dkdv_plan_bytes_at_the_benchmark_shape():
    """The benchmark's attention shape (S 4096, D 128, bf16, 512-blocks,
    H == KV) takes the resident plan and moves under 10 MB a head where
    the parent's grid moved 47; a small VMEM takes the streaming plan,
    whose clamped maps fetch only the band."""
    fa = _fa()
    shape = dict(S=4096, T=4096, D=128, block_q=512, block_k=512)
    mask = dict(causal=True, window=0)
    plan = fa.bwd_dkdv_plan(dtype=jnp.bfloat16, groups=1, vmem_bytes=128 * 2 ** 20,
                            **shape, **mask)
    assert plan["path"] == "resident"
    # Mosaic planned 14 MiB for this call (tests/test_tpu_compile_dense.py,
    # ``FLASH_WIDTHS``, holds that the limit suffices); a quarter of a v5e's
    # VMEM is the budget
    assert 14 * 2 ** 20 <= plan["resident_bytes"] <= 32 * 2 ** 20
    assert plan["resident_bytes"] <= plan["vmem_limit_bytes"] <= 32 * 2 ** 20
    assert plan["hbm_bytes_per_head"] == 9 * 2 ** 20 <= 10e6
    parent = fa.hbm_bytes_per_head(itemsize=2, **shape, **PARENT_DKDV_PLAN)
    assert parent == 46 * 2 ** 20 and 47e6 < parent < 49e6
    # groups: float32 results for the group sum, 2 MB a head more
    grouped = fa.bwd_dkdv_plan(dtype=jnp.bfloat16, groups=4,
                               vmem_bytes=128 * 2 ** 20, **shape, **mask)
    assert grouped["path"] == "resident"
    assert grouped["hbm_bytes_per_head"] == 11 * 2 ** 20
    # a core with 16 MiB of VMEM streams, all 8 q-blocks ONE span of the
    # call (15 MiB counted): the query side is fetched once a head, as the
    # resident plan fetches it (at one q-block a grid step, until PR 49,
    # 35 fetches of 640 KiB and float32 results: 28 MiB a head)
    small = fa.bwd_dkdv_plan(dtype=jnp.bfloat16, groups=1, vmem_bytes=16 * 2 ** 20,
                             **shape, **mask)
    assert (small["path"], small["span"], small["in_flight"], small["steps"],
            small["band_steps"]) == ("stream", 4096, 1, 1, 8)
    assert small["walk_bytes"] == 15 * 2 ** 20
    assert small["hbm_bytes_per_head"] == 9 * 2 ** 20
    assert fa.hbm_bytes_per_head(
        "stream", itemsize=2, out_itemsize=4, **shape,
        q_index=functools.partial(
            fa._q_block_index, steps=8, num_q=8, block_q=512, block_k=512,
            **mask)) == (35 * 640 + 6 * 1024) * 1024
    # the two cells whose full layers stream (bwd_dkdv_plan's docstring
    # has what Mosaic planned beside each count): GLM-4.7-Flash, a head
    # of 256, H == KV: 4 q-blocks a grid step, one in flight, bf16 results,
    # 16 k-blocks x 4 spans of which 40 hold a block of a band (256 of
    # which 136 at one q-block a step); Mellum2's full layers, 8 query
    # heads a KV head: spans of 8, float32 results for the group's sum
    glm = fa.bwd_dkdv_plan(S=8192, T=8192, D=256, dtype=jnp.bfloat16, groups=1,
                           block_q=512, block_k=512, vmem_bytes=128 * 2 ** 20,
                           **mask)
    assert [glm[n] for n in ("path", "span", "in_flight", "walk_bytes",
                             "steps", "band_steps", "out_dtype")] == [
        "stream", 2048, 1, 16 * 2 ** 20, 4, 40, jnp.bfloat16]
    mellum = fa.bwd_dkdv_plan(S=16384, T=16384, D=128, dtype=jnp.bfloat16,
                              groups=8, block_q=512, block_k=512,
                              vmem_bytes=128 * 2 ** 20, **mask)
    assert [mellum[n] for n in ("path", "span", "in_flight", "walk_bytes",
                                "steps", "band_steps", "out_dtype")] == [
        "stream", 4096, 1, 15.5 * 2 ** 20, 4, 80, jnp.float32]
    for plan in (small, glm, mellum):
        assert plan["walk_bytes"] <= fa._SCOPED_VMEM_BYTES
    # a MiB less and the GLM call holds 2 q-blocks a grid step
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_SCOPED_VMEM_BYTES", 15 * 2 ** 20)
        less = fa.bwd_dkdv_plan(
            S=8192, T=8192, D=256, dtype=jnp.bfloat16, groups=1, block_q=512,
            block_k=512, vmem_bytes=128 * 2 ** 20, **mask)
        assert (less["span"], less["in_flight"], less["steps"],
                less["band_steps"]) == (1024, 1, 8, 72)
        mp.setattr(fa, "_SCOPED_VMEM_BYTES", 0)   # nothing fits: one, one
        assert [fa.bwd_dkdv_plan(
            S=8192, T=8192, D=256, dtype=jnp.bfloat16, groups=1, block_q=512,
            block_k=512, vmem_bytes=128 * 2 ** 20, **mask)[n]
            for n in ("span", "in_flight", "steps", "band_steps")] == [
            512, 1, 16, 136]
    # S 8192 with Mistral's window still fits; its band is what streams
    long = dict(shape, S=8192, T=8192)
    assert fa.bwd_dkdv_plan(
        dtype=jnp.bfloat16, groups=4, vmem_bytes=128 * 2 ** 20, causal=True,
        window=4096, **long)["path"] == "resident"
    banded = fa.bwd_dkdv_plan(
        dtype=jnp.bfloat16, groups=4, vmem_bytes=16 * 2 ** 20, causal=True,
        window=4096, **long)
    # 9 q-blocks see a k-block: the q axis is 9 long where S has 16
    assert (banded["path"], banded["steps"]) == ("band", 9)
    assert banded["hbm_bytes_per_head"] < 0.5 * fa.hbm_bytes_per_head(
        itemsize=2, **long, **PARENT_DKDV_PLAN)


# windows of a block or two, of no multiple of a block, of under a block,
# of S and over; 0 is none
WINDOWS = dict(argvalues=[0, 64, 100, 20, 256, 300],
               ids=["causal", "w64", "w100", "w20", "wS", "w300"])


@pytest.mark.parametrize("window", **WINDOWS)
@pytest.mark.parametrize("span", [1, 2, 4])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (32, 64), (64, 32)])
def test_dkdv_stream_grid_fetches_only_its_band(window, span, block_q,
                                                block_k):
    """Walk the streamed dK/dV call's grid on the host in the order Mosaic
    does. At one q-block a grid step the q axis is as long as the longest
    band of a k-block and no longer (all of S's q-blocks without a window,
    or with one that reaches the sequence's end from the first k-block);
    where it is shorter than S's it counts from the band's first q-block.
    At a span of q-blocks a step it counts S's spans. Every q-block of
    every band is walked exactly once, in rising order, by the bounds the
    kernel computes; on a step the kernel runs, the query-side index is
    the block (the span) the kernel masks for. On a step it skips, the
    index is the previous step's (nothing is fetched) or, before a
    k-block's band opens, the band's first block (fetched early, once):
    so over a head the index changes once per step that runs, never for
    one skipped, and never leaves the axis."""
    fa = _fa()
    S = 256
    num_q, num_k = S // block_q, S // block_k
    kw = dict(num_q=num_q, block_q=block_q, block_k=block_k, causal=True,
              window=window)
    steps, band_steps = fa._q_steps(num_k=num_k, span=span, **kw)
    bands = [fa._q_band(ki, **kw) for ki in range(num_k)]
    if span == 1:
        assert steps == max(hi - lo for lo, hi in bands) <= num_q
    else:
        assert steps == num_q // span
    banded = steps * span < num_q
    # the bands end early only under a window that cannot reach the end
    assert banded == (span == 1
                      and 0 < window <= S - block_q - block_k + 1), steps
    index_of = functools.partial(fa._q_block_index, steps=steps, span=span,
                                 **kw)
    prev, fetches, ran = None, 0, 0
    for ki, (lo, hi) in enumerate(bands):
        visited = []
        for j in range(steps):
            # the kernel's: the axis counts from the band where shorter,
            # and a step holds the q-blocks of its span that the band has
            first = j + lo if banded else j * span
            mine = range(max(lo, first), min(hi, first + span))
            for qi in range(first, first + span):
                # what the kernel's pl.when computes, from positions
                rows = np.arange(qi * block_q, (qi + 1) * block_q)[:, None]
                cols = np.arange(ki * block_k, (ki + 1) * block_k)[None, :]
                keep = (rows >= cols) & (rows < S)
                if window:
                    # the band test is by blocks: the row at the window's
                    # edge, that sees nothing, still counts as in the band
                    keep &= rows - cols <= window
                assert (qi in mine) == bool(keep.any()), (ki, qi)
            index = index_of(ki, j)
            assert 0 <= index < num_q // span, (ki, j, index)
            if mine:
                visited += mine
                assert index == first // span
            else:
                assert index in (prev, lo // span), (ki, j, index, prev)
            if not banded:          # the parent's grid and indices, by spans
                assert index == max(min(j, (hi - 1) // span), lo // span)
            fetches += index != prev
            ran += len(mine) > 0
            prev = index
        assert visited == list(range(lo, hi)), (ki, visited)
    assert fetches <= ran == band_steps
    # the plan walks the same grid for its bytes
    assert fa.hbm_bytes_per_head(
        "stream", S=S, T=S, D=32, block_q=block_q, block_k=block_k,
        itemsize=4, out_itemsize=4, steps=steps, span=span, q_index=index_of
    ) == fetches * span * block_q * (3 * 32 * 4 + 512) + 2 * S * 32 * 8
    # the same functions trace: an index map gets traced scalars
    traced = jax.jit(index_of)
    for ki, j in [(0, 0), (num_k - 1, 0), (0, steps - 1),
                  (num_k // 2, 1 % steps)]:
        assert int(traced(ki, j)) == index_of(ki, j)


# -- the forward's and the dQ call's walk over k-blocks ----------------------

def _one_block_walk(call, q, k, v, g=None, out=None, lse=None, *, causal,
                    window, block):
    """The walk the kernels replaced, written out: grid (b, h, q-block,
    k-block), ONE k-block a grid step, the sums in VMEM scratch (forward)
    or in the float32 output block (dQ), every step masked; for dK/dV its
    mirror image, grid (b, h, k-block, q-block), ONE q-block a grid step,
    both sums in their float32 output blocks. Operands [B, H|KV, S, D];
    returns (o, lse [B, H, S, 128]), dq or (dk, dv) a query head."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, D = q.shape
    groups, num_k, scale = H // k.shape[1], S // block, D ** -0.5
    neg = _fa().NEG_INF

    def scores(q_blk, k_blk, qi, ki):
        s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            keep = q_pos >= k_pos
            if window:
                keep = keep & (q_pos - k_pos < window)
            s = jnp.where(keep, s, neg)
        return s

    def in_band(qi, ki):
        if not causal:
            return True
        run = qi * block + block > ki * block
        if window:
            run = run & (qi * block < (ki + 1) * block + window)
        return run

    def fwd(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr):
        qi, ki = pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)
            m_scr[...] = jnp.full_like(m_scr, neg)
            l_scr[...] = jnp.zeros_like(l_scr)

        @pl.when(in_band(qi, ki))
        def _():
            s = scores(q_ref[0, 0], k_ref[0, 0], qi, ki)
            m, l = m_scr[...][:, 0:1], l_scr[...][:, 0:1]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc[...] = acc[...] * alpha + jax.lax.dot(
                p.astype(v_ref.dtype), v_ref[0, 0],
                preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(ki == num_k - 1)
        def _():
            l = jnp.maximum(l_scr[...][:, 0:1], 1e-30)
            o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)
            lse_ref[0, 0] = m_scr[...] + jnp.log(l)

    def dq(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dq_ref):
        qi, ki = pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _():
            dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

        @pl.when(in_band(qi, ki))
        def _():
            f32 = lambda ref: ref[0, 0].astype(jnp.float32)  # noqa: E731
            delta = jnp.sum(f32(o_ref) * f32(g_ref), axis=-1, keepdims=True)
            p = jnp.exp(scores(f32(q_ref), f32(k_ref), qi, ki)
                        - lse_ref[0, 0][:, 0:1])
            dp = jax.lax.dot_general(
                f32(g_ref), f32(v_ref), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_ref[0, 0] += jax.lax.dot(
                p * (dp - delta) * scale, f32(k_ref),
                preferred_element_type=jnp.float32)

    def dkdv(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, dk_ref, dv_ref):
        ki, qi = pl.program_id(2), pl.program_id(3)

        @pl.when(qi == 0)
        def _():
            dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
            dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

        @pl.when(in_band(qi, ki))
        def _():
            f32 = lambda ref: ref[0, 0].astype(jnp.float32)  # noqa: E731
            delta = jnp.sum(f32(o_ref) * f32(g_ref), axis=-1, keepdims=True)
            p = jnp.exp(scores(f32(q_ref), f32(k_ref), qi, ki)
                        - lse_ref[0, 0][:, 0:1])
            dv_ref[0, 0] += jax.lax.dot_general(
                p, f32(g_ref), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                f32(g_ref), f32(v_ref), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_ref[0, 0] += jax.lax.dot_general(
                p * (dp - delta) * scale, f32(q_ref),
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if call == "dkdv":
        def rows(width):
            return pl.BlockSpec((1, 1, block, width),
                                lambda b, h, i, j: (b, h, j, 0))

        keys = pl.BlockSpec((1, 1, block, D),
                            lambda b, h, i, j: (b, h // groups, i, 0))
        sums = pl.BlockSpec((1, 1, block, D), lambda b, h, i, j: (b, h, i, 0))
        return pl.pallas_call(
            dkdv, grid=(B, H, num_k, S // block),
            in_specs=[rows(D), keys, keys, rows(D), rows(D), rows(128)],
            out_specs=[sums, sums],
            out_shape=[jax.ShapeDtypeStruct((B, H, S, D), jnp.float32)] * 2,
            interpret=True)(q, k, v, g, out, lse)

    def rows(width):
        return pl.BlockSpec((1, 1, block, width),
                            lambda b, h, i, j: (b, h, i, 0))

    keys = pl.BlockSpec((1, 1, block, D),
                        lambda b, h, i, j: (b, h // groups, j, 0))
    grid = (B, H, S // block, num_k)
    if call == "fwd":
        return pl.pallas_call(
            fwd, grid=grid, in_specs=[rows(D), keys, keys],
            out_specs=[rows(D), rows(128)],
            out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                       jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32),
                            pltpu.VMEM((block, 128), jnp.float32),
                            pltpu.VMEM((block, 128), jnp.float32)],
            interpret=True)(q, k, v)
    return pl.pallas_call(
        dq, grid=grid,
        in_specs=[rows(D), keys, keys, rows(D), rows(D), rows(128)],
        out_specs=rows(D),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
        interpret=True)(q, k, v, g, out, lse).astype(q.dtype)


# (call, plan, blocks of S, mask, heads). The forward and the dQ call on the
# loop plan (one span: a head's whole K and V) and the stream plan at spans of
# half the blocks, under every mask; the dK/dV call on its resident and
# stream plans at 8 blocks; and all three on the stream plan at `<blocks a
# grid step>x<blocks in flight>` with whole spans written out (`l`: walked in
# a loop), at 8 and 16 blocks, where whole, diagonal and empty spans all occur
MASKS = ("causal", "noncausal", "window64", "window100")
WALKS = [(call, plan, blocks, mask, heads) for call in ("fwd", "dq")
         for plan in ("loop", "stream") for blocks in (1, 3, 8)
         for mask in MASKS for heads in ((2, 2), (4, 2))] + [
    ("dkdv", plan, 8, mask, (2, 2)) for plan in ("loop", "stream")
    for mask in MASKS] + [
    (call, plan, blocks, mask, heads) for call in ("fwd", "dq", "dkdv")
    for plan, blocks in (("stream2x1", 8), ("stream2x2", 8), ("stream4x1", 8),
                         ("stream4x2l", 8), ("stream4x2", 16),
                         ("stream8x2", 16), ("stream8x1", 16))
    for mask, heads in (("causal", (4, 2)), ("window100", (2, 2)))]
_ONE_BLOCK_WALKS = {}      # (call, blocks, mask, heads) -> what it returned


@pytest.mark.parametrize("call,path,blocks,mask,heads", WALKS, ids=[
    f"{c}-{p}-S={b}blocks-{m}-{'H=KV' if h == (2, 2) else 'groups2'}"
    for c, p, b, m, h in WALKS])
def test_the_walk_of_k_blocks_is_the_one_block_walk_to_the_last_bit(
        call, path, mask, heads, blocks):
    """The forward and the dQ call, on the loop plan (one span: a head's
    whole K and V) and the stream plan (spans of half the k-blocks, of one
    where their number is odd), two k-blocks a step of the walk wherever a
    span holds two, a span that lies whole inside the band written out
    with the sums read from scratch once at its top: bit-equal to a walk
    of one k-block a grid step, the parent's, written out above. Three
    blocks walk a pair and an odd one; a window's band starts inside a
    span. The dK/dV call, resident and streamed at every span of q-blocks
    and number in flight, likewise to a walk of one q-block a grid step
    (a window that ends the bands early keeps it on the band plan's one
    q-block a step, whatever walk is asked for)."""
    fa = _fa()
    block = 32
    causal, window = mask != "noncausal", _window(mask)
    S = block * blocks
    # D 64: the scale is a power of two. XLA's CPU backend contracts
    # `s * scale - m` into one rounding where both land in one fusion,
    # which depends on the program around them; with an exact product the
    # two roundings are one, and interpret mode is bit-equal as the chip
    # is (PERF.md 6, PR 37: every plan against the parent's kernels)
    q, k, v = _make(B=1, S=S, H=heads[0], KV=heads[1], D=64, seed=5)
    g = jax.random.normal(jax.random.PRNGKey(6), q.shape, q.dtype)
    span = blocks if path == "loop" else max(
        n for n in range(1, blocks // 2 + 1) if blocks % n == 0) \
        if blocks > 1 else 1
    plan = dict(path=path, span=span * block, in_flight=min(2, span))
    form = path.partition("stream")[2]
    if form:
        span, in_flight = map(int, form.rstrip("l").split("x"))
        plan = dict(path="stream", span=span * block, in_flight=in_flight,
                    written=not form.endswith("l"))
    kw = dict(causal=causal, block_q=block, block_k=block, window=window,
              scale=64 ** -0.5)
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731

    def one_block_walk(call, *args):
        key = (call, blocks, mask, heads)
        if key not in _ONE_BLOCK_WALKS:     # the same inputs in every case
            _ONE_BLOCK_WALKS[key] = _one_block_walk(
                call, *args, causal=causal, window=window, block=block)
        return _ONE_BLOCK_WALKS[key]

    want_o, want_lse = one_block_walk("fwd", t(q), t(k), t(v))
    if call == "fwd":
        got_o, got_lse = fa._flash_fwd(q, k, v, plan=plan, **kw)
        np.testing.assert_array_equal(t(got_o), want_o)
        np.testing.assert_array_equal(got_lse, want_lse)
        return
    args = (t(q), t(k), t(v), t(g), want_o, want_lse)
    want = one_block_walk(call, *args)
    if call == "dq":
        np.testing.assert_array_equal(
            fa._flash_bwd_dq(*args, plan=plan, **kw), want)
        return
    got = fa._flash_bwd_dkdv(
        *args, **kw, vmem_bytes=128 * 2 ** 20 if path == "loop" else 1024,
        walk=(span, plan["in_flight"]))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("window", **WINDOWS)
@pytest.mark.parametrize("span", [1, 2, 3, 4])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (32, 64), (64, 32)])
def test_kv_span_grid_fetches_only_its_band(window, span, block_q, block_k):
    """Walk the grid of a call that holds a q-block and steps over spans
    of k-blocks, on the host, in the order Mosaic does. A span shorter
    than T that holds the longest band is a banded call's: ONE step a
    q-block, the span fetched at the k-block the band starts at (or as
    late as T lets a span start), every block of the band in it. Any
    other span is one of T's and the axis counts all of them, as the
    parent's did: on a step whose span holds a block of the band the
    index of K and V is that span; on any other step it is the previous
    step's (nothing is fetched) or, before a q-block's band opens, the
    band's first span (fetched early, once). Either way every block of
    every band is walked exactly once, in rising order, the index changes
    at most once per step that runs, and nothing outside T is indexed."""
    fa = _fa()
    S = 256
    num_q, num_k = S // block_q, S // block_k
    kw = dict(num_k=num_k, block_q=block_q, block_k=block_k, causal=True,
              window=window)
    bands = [fa._k_band(qi, **kw) for qi in range(num_q)]
    banded = max(hi - lo for lo, hi in bands) <= span < num_k
    if not window or window >= S:
        assert not banded
    if num_k % span and not banded:
        pytest.skip("no plan cuts T into spans that do not divide it")
    steps, band_steps, whole_steps = fa._span_steps(num_q=num_q, span=span,
                                                    **kw)
    assert steps == (1 if banded else num_k // span)
    prev, fetches, ran, whole = None, 0, 0, 0
    for qi, (lo, hi) in enumerate(bands):
        first, last = lo // span, (hi - 1) // span
        walked = []
        for si in range(steps):
            if banded:
                index = fa._band_start(qi, span=span, **kw)
                assert index == min(lo, num_k - span) >= 0
                assert hi <= index + span    # the whole band is in the span
                mine = range(lo, hi)
            else:
                # what the kernel's pl.when computes, from positions
                rows = np.arange(qi * block_q, (qi + 1) * block_q)[:, None]
                cols = np.arange(si * span * block_k,
                                 (si + 1) * span * block_k)[None, :]
                keep = rows >= cols
                if window:
                    # the band test is by blocks: the row at the window's
                    # edge, that sees nothing, still counts as in the band
                    keep &= rows - cols <= window + block_k - 1
                mine = range(max(lo, si * span), min(hi, (si + 1) * span))
                if not window:
                    assert (len(mine) > 0) == bool(keep.any()), (qi, si)
                else:
                    assert mine or not (rows - cols < window)[keep].any()
                # the parent's grid and indices
                index = fa._k_span_index(qi, si, span=span, **kw)
                assert index == max(min(si, last), first) < num_k // span
                if mine:
                    assert index == si
                else:
                    assert index in (prev, first), (qi, si, index, prev)
            # the kernel's own bounds of the step's walk, and where it
            # slices the span's first block from
            at, to, start = fa._span_band(qi, si, span=span, steps=steps, **kw)
            assert list(range(int(at), int(to))) == list(mine)
            assert start() == (index if banded else si * span)
            walked += mine
            ran += len(mine) > 0
            # a span all of whose blocks are the band's is written out
            whole += len(mine) == span > 1 and steps > 1
            fetches += index != prev
            prev = index
        assert walked == list(range(lo, hi)), (qi, walked)
    assert fetches <= ran == band_steps
    assert whole == whole_steps <= band_steps
    assert fa._span_steps(num_q=num_q, span=span, written=False,
                          **kw) == (steps, band_steps, 0)
    # the same functions trace: an index map gets traced scalars
    index_of = functools.partial(fa._band_start, span=span, **kw) if banded \
        else lambda qi, si: fa._k_span_index(qi, si, span=span, **kw)
    traced = jax.jit(index_of)
    for qi, si in [(0, 0), (num_q - 1, 0), (0, steps - 1),
                   (num_q // 2, 1 % steps)]:
        args = (qi,) if banded else (qi, si)
        assert int(traced(*args)) == index_of(*args)


def test_kv_plan_takes_the_longest_span_that_fits_then_a_second_block():
    """`kv_plan` by bytes alone: the loop plan holds a head's whole K and
    V and its forward walks one block at a time, its dQ call two; the
    stream plan takes the longest span whose step fits the 16 MiB a call
    gets, and a second block in flight where that span leaves room; a
    window that ends the bands before T makes the longest band the span,
    all its blocks in flight where they fit, one grid step a q-block."""
    fa = _fa()
    bf = jnp.bfloat16
    shape = dict(dtype=bf, block_q=512, block_k=512)
    l8 = {c: fa.kv_plan(S=4096, T=4096, D=128, call=c, **shape)
          for c in ("fwd", "dq")}
    assert [(p["path"], p["span"], p["in_flight"]) for p in l8.values()] == [
        ("loop", 4096, 1), ("loop", 4096, 2)]
    glm = {c: fa.kv_plan(S=8192, T=8192, D=256, call=c, **shape)
           for c in ("fwd", "dq")}
    assert [(p["path"], p["span"], p["in_flight"]) for p in glm.values()] == [
        ("stream", 4096, 2), ("stream", 4096, 1)]
    # a span that lies whole inside the band is written out where that
    # fits: the dQ call's eight blocks do, one in flight (16.0 MiB counted,
    # 16.0 planned: kv_plan's docstring), 10 of a head's 24 working steps;
    # the forward's do not (21.5 counted, 19.25 planned) and walk in the
    # loop, as one span of the loop plan does
    assert [(p["written"], p["whole_steps"], p["band_steps"])
            for p in list(glm.values()) + list(l8.values())] == [
        (False, 0, 24), (True, 10, 24), (False, 0, 8), (False, 0, 8)]
    assert glm["dq"]["walk_bytes"] == 16 * 2 ** 20
    # the Mellum2 cell's full layers (S 16,384, D 128): the forward's span
    # of 16 blocks is too long to write out and is cut to 8, which are
    # (31.9 -> 30.6 ms a call; the dQ call lost 6% by the same cut and
    # keeps its 16 in the loop: PERF.md 6, PR 49)
    mellum = {c: fa.kv_plan(S=16384, T=16384, D=128, call=c, **shape)
              for c in ("fwd", "dq")}
    assert [(p["path"], p["span"], p["in_flight"], p["written"], p["steps"],
             p["band_steps"], p["whole_steps"]) for p in mellum.values()] == [
        ("stream", 4096, 2, True, 4, 80, 52),
        ("stream", 8192, 2, False, 2, 48, 0)]
    for plan in [*l8.values(), *glm.values(), *mellum.values()]:
        assert plan["walk_bytes"] <= fa._SCOPED_VMEM_BYTES
    # a MiB less: half the span, where the dQ call's pair fits again; at
    # 12 MiB the longer span with one block still comes before the pair
    with pytest.MonkeyPatch.context() as mp:
        for budget, want in ((15, (2048, 2)), (12, (2048, 1))):
            mp.setattr(fa, "_SCOPED_VMEM_BYTES", budget * 2 ** 20)
            small = fa.kv_plan(S=8192, T=8192, D=256, call="dq", **shape)
            assert (small["span"], small["in_flight"]) == want, budget
        # nothing fits: one block a grid step, one at a time (the parent's)
        mp.setattr(fa, "_SCOPED_VMEM_BYTES", 0)
        assert [fa.kv_plan(S=8192, T=8192, D=256, call=c, **shape)[n]
                for c in ("fwd", "dq") for n in ("span", "in_flight")] == [
            512, 1, 512, 1]
    # a windowed forward holds its longest band where that fits; the dQ
    # call keeps the loop plan where a head's K and V fit
    banded = fa.kv_plan(S=8192, T=8192, D=128, window=1024, **shape)
    assert (banded["path"], banded["span"], banded["in_flight"]) == (
        "band", 1536, 3)
    # a q-block's three k-blocks are ONE span, fetched where they start:
    # one grid step a q-block, and every one of the 16 works
    assert (banded["steps"], banded["band_steps"]) == (1, 16)
    # at the Mellum2 cell's S the dQ call cannot hold a head either
    assert [fa.kv_plan(S=16384, T=16384, D=128, window=1024, call=c,
                       **shape)[n] for c in ("fwd", "dq")
            for n in ("path", "span", "in_flight", "steps", "band_steps")
            ] == ["band", 1536, 3, 1, 32] * 2
    # a window as long as S ends no band early: T in one span, or the
    # head's K and V as the loop plan holds them
    assert [fa.kv_plan(S=8192, T=8192, D=128, window=w, **shape)[n]
            for w in (8192, 0) for n in ("path", "steps", "band_steps")] == [
        "stream", 1, 16, "loop", 1, 16]
    # a band too long to hold streams over T as it did (33 blocks of 64),
    # the forward in spans of 8 blocks, written out, for 16 in a loop
    long = fa.kv_plan(S=32768, T=32768, D=128, window=16384, **shape)
    assert (long["path"], long["span"], long["steps"], long["written"]) == (
        "stream", 4096, 8, True)
    assert fa.kv_plan(S=8192, T=8192, D=128, window=1024, call="dq",
                      **shape)["path"] == "loop"


def test_flash_bwd_plan_instant_once_a_trace(monkeypatch):
    """The plan is chosen while the backward is traced, and says so once:
    one `flash.fwd_plan` and one `flash.bwd_plan` instant a compile, none
    when the compiled program runs again."""
    fa = _fa()
    seen = []
    monkeypatch.setattr(fa.tracing, "instant",
                        lambda name, attrs=None, **kw: seen.append(
                            (name, attrs)))
    q, k, v = _make(B=1, S=128, H=2, KV=2, D=32)
    grad = jax.jit(jax.grad(lambda *a: flash_attention(
        *a, block_q=32, block_k=32).sum(), argnums=(0, 1, 2)))
    jax.block_until_ready(grad(q, k, v))
    jax.block_until_ready(grad(q, k, v))
    # the forward's kernel is chosen where the forward is traced, and says
    # so beside it (`flash.fwd_plan`); the backward's instant names the dQ
    # call's plan too
    assert [name for name, _ in seen] == ["flash.fwd_plan", "flash.bwd_plan"]
    # four blocks of 32 keys: the loop plan holds all of them, and its
    # forward walks them one at a time, its dQ call two at a time
    # (a grid of 2 heads x 4 q-blocks, every step at work)
    assert seen[0][1] == {"path": "loop", "S": 128, "D": 32,
                          "kv_block_bytes": 2 * 2 * 128 * 32 * 4,
                          "span": 128, "in_flight": 1, "grid_steps": 8,
                          "band_steps": 8, "whole_steps": 0}
    # the dK/dV call's span, in queries (a resident call holds all of S),
    # the q-blocks in flight and the bytes counted for a streamed call's
    # step; `whole_steps`: no span is written out on the loop plan
    attrs = seen[1][1]
    assert attrs == {
        "path": "resident", "S": 128, "block_q": 32, "block_k": 32,
        "window": 0, "resident_bytes": attrs["resident_bytes"],
        "hbm_bytes_per_head": fa.hbm_bytes_per_head(
            "resident", S=128, T=128, D=32, block_q=32, block_k=32,
            itemsize=4, out_itemsize=4), "span": 128, "in_flight": 1,
        "walk_bytes": attrs["walk_bytes"], "grid_steps": 8, "band_steps": 8,
        "dq_path": "loop", "dq_span": 128, "dq_in_flight": 2,
        "dq_grid_steps": 8, "dq_band_steps": 8, "dq_whole_steps": 0}
    assert all(isinstance(x, (int, str)) for x in attrs.values())
    # streamed (eight blocks of 32 and a budget of 200,000 bytes: four
    # k-blocks a grid step, written out, two q-blocks in the dK/dV call):
    # the instants count their grids in spans, and the steps whose span
    # is written out
    seen.clear()
    monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES", 200_000)
    monkeypatch.setattr(fa, "_vmem_bytes", lambda: 1024)
    q, k, v = _make(B=1, S=256, H=2, KV=2, D=32)
    jax.block_until_ready(jax.jit(jax.grad(lambda *a: flash_attention(
        *a, block_q=32, block_k=32).sum(), argnums=(0, 1, 2)))(q, k, v))
    fwd, back = seen[0][1], seen[1][1]
    assert [fwd[n] for n in ("path", "span", "in_flight", "grid_steps",
                             "band_steps", "whole_steps")] == [
        "stream", 128, 2, 32, 24, 12]
    assert [back[n] for n in ("path", "span", "in_flight", "grid_steps",
                              "band_steps", "dq_path", "dq_span",
                              "dq_in_flight", "dq_grid_steps",
                              "dq_band_steps", "dq_whole_steps")] == [
        "stream", 64, 1, 64, 40, "stream", 128, 2, 32, 24, 12]
    assert back["walk_bytes"] == 188_416


@pytest.mark.parametrize("window", [None, 100, 192],
                         ids=["full", "w100", "w192"])
@pytest.mark.parametrize("what", ["fwd", "dq", "dkdv"])
def test_flash_at_sixteen_query_heads_a_kv_head_against_xla(what, window):
    """Command A+'s grouping (128 query heads over 8 KV heads: 16 a group;
    a chip's 32 over 2) banded and full: forward, dQ and dK/dV (whose
    per-query-head float32 results are summed over the 16) against the
    model's own einsum attention under the same mask, two KV heads so
    that a query head reading the wrong one shows."""
    from ray_tpu.models.llama import _attention_xla

    q, k, v = _make(B=1, S=256, H=32, KV=2, D=32, seed=21)
    g = jax.random.normal(jax.random.PRNGKey(22), q.shape, q.dtype)

    def flash(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=64, block_k=64)

    def xla(q, k, v):
        return _attention_xla(q, k, v, True, window=window)

    if what == "fwd":
        got, want = flash(q, k, v), xla(q, k, v)
    else:
        argnums = (0,) if what == "dq" else (1, 2)
        got, want = (jax.grad(lambda *a: (f(*a) * g).sum(), argnums)(q, k, v)
                     for f in (flash, xla))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=2e-2)
    if what == "fwd":
        # heads 0-15 read KV head 0 and 16-31 KV head 1: with the KV heads
        # swapped the two halves of the query heads swap results
        swapped = flash(jnp.concatenate([q[:, :, 16:], q[:, :, :16]], 2),
                        k[:, :, ::-1], v[:, :, ::-1])
        np.testing.assert_allclose(
            np.asarray(swapped[:, :, :16]), np.asarray(got[:, :, 16:]),
            rtol=1e-5, atol=1e-5)
