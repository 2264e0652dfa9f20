"""The tensor-parallel matmuls that carry their own communication
(parallel/collective_matmul.py) against the collectives they decompose,
on the CPU's virtual devices: values and gradients, at 2 and 4 shards of
the tensor axis and at 1, 2 and 4 of the batch axis under ``embed``."""
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh
from ray_tpu.parallel import collective_matmul as cm

B, S, D, N, F = 4, 16, 8, 16, 24


def _plan(shards, **batch_axes):
    """The plan under ``fsdp_tp`` on a mesh of ``tp=shards`` and the given
    batch axes (fsdp=2 unless said)."""
    spec = MeshSpec(tp=shards, **(batch_axes or {"fsdp": 2}))
    mesh = build_mesh(spec, devices=jax.devices()[:math.prod(spec.sizes())])
    plan = cm.overlap_plan(mesh, ShardingRules.fsdp_tp(), B, S, (N, F))
    assert (plan.axis, plan.shards) == ("tp", shards)
    return plan


def _reference(plan):
    """The same three results by one blocking collective each."""
    rows, cols = plan.rows(), plan.columns()
    w_cols, w_rows = P(None, plan.axis), P(plan.axis, None)

    def gathered(h, *ws):
        h = jax.lax.all_gather(h, plan.axis, axis=1, tiled=True)
        return tuple(jnp.matmul(h, w) for w in ws)

    def scattered(a, w):
        return jax.lax.psum_scatter(jnp.matmul(a, w), plan.axis,
                                    scatter_dimension=1, tiled=True)

    def allgather_matmul(h, ws):
        return jax.shard_map(
            gathered, mesh=plan.mesh, in_specs=(rows,) + (w_cols,) * len(ws),
            out_specs=(cols,) * len(ws), check_vma=False)(h, *ws)

    def matmul_reduce_scatter(a, w):
        return jax.shard_map(scattered, mesh=plan.mesh,
                             in_specs=(cols, w_rows), out_specs=rows,
                             check_vma=False)(a, w)

    return allgather_matmul, matmul_reduce_scatter


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


def _three(plan, h, a, w1, w2, wo, reference=False):
    """All three helpers' results, or the same by blocking collectives."""
    if reference:
        ref_gather, ref_scatter = _reference(plan)
        y1, y2 = ref_gather(h, (w1, w2))
        return y1, y2, ref_scatter(a, wo), ref_scatter(_swiglu(y1, y2), wo)
    y1, y2 = cm.allgather_matmul(h, (w1, w2), plan)
    z = cm.matmul_reduce_scatter(a, wo, plan)
    return y1, y2, z, cm.gather_apply_scatter(h, (w1, w2), _swiglu, wo, plan)


@pytest.mark.parametrize("shards", [2, 4])
def test_helpers_match_the_blocking_collectives(shards):
    plan = _plan(shards)
    k = jax.random.split(jax.random.PRNGKey(shards), 6)
    h, a = jax.random.normal(k[0], (B, S, D)), jax.random.normal(k[1], (B, S, N))
    w1, w2 = jax.random.normal(k[2], (D, N)), jax.random.normal(k[3], (D, N))
    wo = jax.random.normal(k[4], (N, D))
    args = (h, a, w1, w2, wo)
    ours, theirs = (lambda *xs: _three(plan, *xs),
                    lambda *xs: _three(plan, *xs, reference=True))

    def scalar(f):      # every result reaches the gradient, none linearly
        return lambda *xs: sum((r ** 2).sum() * (i + 1)
                               for i, r in enumerate(f(*xs)))

    for got, want in zip(jax.jit(ours)(*args), jax.jit(theirs)(*args)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    grad = lambda f: jax.jit(jax.grad(scalar(f), argnums=range(5)))(*args)  # noqa: E731
    for got, want in zip(grad(ours), grad(theirs)):
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(want).max()))
    # the decomposition, not the collective it replaces: nothing is
    # gathered but the weights' embed over fsdp, nothing summed by a psum
    text = str(jax.make_jaxpr(ours)(*args))
    assert "ppermute" in text and "psum" not in text
    assert set(re.findall(r"all_gather\[.*?axis_name=\((.*?)\)", text,
                          re.S)) == {"'fsdp',"}


def _weight_gradients(plan, reference=False):
    """(the gradients of one scalar of all three helpers' results with
    respect to w1, w2 [D, N] and wo [N, D], the function differentiated,
    its arguments): the weights placed as the parameters are stored,
    ``embed`` (D) over the batch axis under it, N over the tensor axis."""
    mesh = plan.mesh
    k = jax.random.split(jax.random.PRNGKey(11), 5)
    h = jax.random.normal(k[0], (B, S, D))
    a = jax.random.normal(k[1], (B, S, N))
    stored = {"w1": P(plan.grad_axis, plan.axis),
              "w2": P(plan.grad_axis, plan.axis),
              "wo": P(plan.axis, plan.grad_axis)}
    ws = {n: jax.device_put(
        jax.random.normal(key, (N, D) if n == "wo" else (D, N)),
        NamedSharding(mesh, stored[n]))
        for n, key in zip(stored, k[2:])}

    def scalar(ws, h, a):
        return sum((r ** 2).sum() * (i + 1) for i, r in enumerate(_three(
            plan, h, a, ws["w1"], ws["w2"], ws["wo"], reference=reference)))

    return jax.jit(jax.grad(scalar))(ws, h, a), stored, scalar, (ws, h, a)


# the batch axes of the mesh beside tp=2 -> the axis and shards a weight's
# gradient is reduce-scattered over by the helpers' own permutes
GRADIENT_MESHES = {
    "fsdp2": (dict(fsdp=2), "fsdp", 2),
    # the ring of more than two shards: three permutes a weight, own last
    "fsdp4": (dict(fsdp=4), "fsdp", 4),
    # the batch on dp AND fsdp: scattered over fsdp, summed over dp by jax
    "dp2-fsdp2": (dict(dp=2, fsdp=2), "fsdp", 2),
    # nothing under embed: the weights enter whole, as before
    "tp-alone": (dict(dp=1), None, 1),
    "dp2": (dict(dp=2), None, 1),
}


@pytest.mark.parametrize("case", sorted(GRADIENT_MESHES))
def test_weight_gradients_match_and_leave_as_the_parameters_are_stored(case):
    """The three helpers' gradients with respect to their weights against
    the blocking program's (the weights whole over the batch axes, jax's
    psum and XLA's slice after it), and sharded as the parameters are:
    ``embed`` over the batch axis under it, the other dimension over tp."""
    axes, grad_axis, grad_shards = GRADIENT_MESHES[case]
    plan = _plan(2, **axes)
    assert (plan.grad_axis, plan.grad_shards) == (grad_axis, grad_shards)
    got, stored, scalar, args = _weight_gradients(plan)
    want = _weight_gradients(plan, reference=True)[0]
    for name, spec in stored.items():
        np.testing.assert_allclose(
            got[name], want[name], rtol=1e-4,
            atol=1e-4 * float(jnp.abs(want[name]).max()))
        assert got[name].sharding.is_equivalent_to(
            NamedSharding(plan.mesh, spec), 2), (name, got[name].sharding)
    # six weight sites (a gather of two, a scatter, the SwiGLU's three), a
    # quarter or an eighth of a weight a permute, f32
    sites = [D * N * 4 // (2 * grad_shards)] * 6 if grad_axis else []
    assert plan.grad_sites == sites
    # n - 1 permutes a weight over the gradient's axis, none where nothing
    # lies under embed, and no sum over it left to jax
    text = str(jax.make_jaxpr(jax.grad(scalar))(*args))
    over = re.findall(r"ppermute\[\s*axis_name=\('(\w+)'", text)
    assert over.count("fsdp") == 6 * (grad_shards - 1), over
    assert not re.findall(r"psum\w*\[[^\]]*axes=\('fsdp',\)", text)


# str(jax.make_jaxpr(grad(...))) of the three helpers on a mesh with ONE
# shard under ``embed``, as the tree before the gradients' own permutes
# traced it (PR 56's; function addresses cut out, a frozenset's members
# sorted: their printed order is the process's), under the jax that traced
# them; another jax prints another text and the digests are left alone
JAXPRS_FROM = "0.9.0"
ONE_SHARD_UNDER_EMBED = {"tp-alone": "120750711698ee5b",
                         "dp2": "6a303fee45fe65c5"}


@pytest.mark.parametrize("case", sorted(ONE_SHARD_UNDER_EMBED))
def test_one_shard_under_embed_traces_the_program_before(case):
    plan = _plan(2, **GRADIENT_MESHES[case][0])
    _, _, scalar, args = _weight_gradients(plan)
    closed = jax.make_jaxpr(jax.grad(scalar))(*args)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(closed))
    text = re.sub(r"frozenset\(\{(.*?)\}\)", lambda m: "frozenset({%s})" % (
        ", ".join(sorted(m.group(1).split(", ")))), text)
    assert "all_gather" not in text and "custom_vjp_call" in text  # _split
    assert set(re.findall(r"ppermute\[\s*axis_name=\('(\w+)'", text)) == {
        "tp"}
    if jax.__version__ == JAXPRS_FROM:
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            ONE_SHARD_UNDER_EMBED[case]


@pytest.mark.parametrize("shards", [2, 4])
def test_then_runs_on_each_shards_rows_before_the_join(shards):
    """``then`` sees the rows of one shard and that shard's index: a
    row-wise function of the position gives what it gives on the whole."""
    plan = _plan(shards)
    k = jax.random.split(jax.random.PRNGKey(7), 2)
    h, w = jax.random.normal(k[0], (B, S, D)), jax.random.normal(k[1], (D, N))
    scale = jnp.arange(S, dtype=jnp.float32) + 1.0

    def then(i, y, shard, scale):
        rows = y.shape[1]
        at = jax.lax.dynamic_slice_in_dim(scale, shard * rows, rows)
        return (y * at[None, :, None]).reshape(*y.shape[:2], -1, 2)

    got, = jax.jit(lambda h, w: cm.allgather_matmul(
        h, (w,), plan, then=then, extras=(scale,)))(h, w)
    want = ((h @ w) * scale[None, :, None]).reshape(B, S, N // 2, 2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("why,kw", [
    ("no mesh", dict(mesh=None)),
    ("no rules", dict(rules=None)),
    ("rows do not divide", dict(seq=S + 1)),
    ("a head count does not divide", dict(units=(N, F + 1))),
    ("batch does not divide", dict(batch=B + 1)),
    ("no tensor axis in the rules", dict(rules=ShardingRules.fsdp())),
    ("heads and mlp on different axes",
     dict(rules=ShardingRules.fsdp_tp().with_(mlp="fsdp"))),
    ("a tensor axis of one shard", dict(mesh=MeshSpec(fsdp=4))),
    ("a sequence the rules shard already",
     dict(mesh=MeshSpec(sp=2, tp=2), rules=ShardingRules.full())),
])
def test_no_plan_where_the_plain_matmul_is_the_program(why, kw):
    given = dict(mesh=MeshSpec(fsdp=2, tp=2), rules=ShardingRules.fsdp_tp(),
                 batch=B, seq=S, units=(N, F))
    given.update(kw)
    if given["mesh"] is not None:
        given["mesh"] = build_mesh(given["mesh"], devices=jax.devices()[:4])
    assert cm.overlap_plan(**given) is None, why
