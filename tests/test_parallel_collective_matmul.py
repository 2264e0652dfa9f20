"""The tensor-parallel matmuls that carry their own communication
(parallel/collective_matmul.py) against the collectives they decompose,
on the CPU's virtual devices: values and gradients, at 2 and 4 shards."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh
from ray_tpu.parallel import collective_matmul as cm

B, S, D, N, F = 4, 16, 8, 16, 24


def _plan(shards):
    mesh = build_mesh(MeshSpec(fsdp=2, tp=shards),
                      devices=jax.devices()[:2 * shards])
    plan = cm.overlap_plan(mesh, ShardingRules.fsdp_tp(), B, S, (N, F))
    assert (plan.axis, plan.shards, plan.batch) == ("tp", shards, ("fsdp",))
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


@pytest.mark.parametrize("shards", [2, 4])
def test_helpers_match_the_blocking_collectives(shards):
    plan = _plan(shards)
    ref_gather, ref_scatter = _reference(plan)
    k = jax.random.split(jax.random.PRNGKey(shards), 6)
    h, a = jax.random.normal(k[0], (B, S, D)), jax.random.normal(k[1], (B, S, N))
    w1, w2 = jax.random.normal(k[2], (D, N)), jax.random.normal(k[3], (D, N))
    wo = jax.random.normal(k[4], (N, D))
    args = (h, a, w1, w2, wo)

    def ours(h, a, w1, w2, wo):
        y1, y2 = cm.allgather_matmul(h, (w1, w2), plan)
        z = cm.matmul_reduce_scatter(a, wo, plan)
        g = cm.gather_apply_scatter(h, (w1, w2), _swiglu, wo, plan)
        return y1, y2, z, g

    def theirs(h, a, w1, w2, wo):
        y1, y2 = ref_gather(h, (w1, w2))
        return y1, y2, ref_scatter(a, wo), ref_scatter(_swiglu(y1, y2), wo)

    def scalar(f):      # every result reaches the gradient, none linearly
        return lambda *xs: sum((r ** 2).sum() * (i + 1)
                               for i, r in enumerate(f(*xs)))

    for got, want in zip(jax.jit(ours)(*args), jax.jit(theirs)(*args)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    grad = lambda f: jax.jit(jax.grad(scalar(f), argnums=range(5)))(*args)  # noqa: E731
    for got, want in zip(grad(ours), grad(theirs)):
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(want).max()))
    # the decomposition, not the collective it replaces
    text = str(jax.make_jaxpr(ours)(*args))
    assert "ppermute" in text and "all_gather" not in text \
        and "psum" not in text


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
