"""Tensor-parallel matmuls that carry their own communication.

A Megatron block under a tensor axis of n shards ends its row-parallel
matmuls (``wo``, ``w_down``) in an all-reduce of the whole activation and
starts its column-parallel ones (q/k/v, gate/up) from a replicated input.
XLA emits that all-reduce as a blocking op between the matmul that makes
its operand and the norm that reads its result: nothing else runs on the
core meanwhile (PERF.md 5: 150 of them a step on the four-chip cell).

Here the residual stream between the blocks is sharded over the SEQUENCE
on the tensor axis (Megatron sequence parallelism), so the all-reduce
splits into a reduce-scatter after the row-parallel matmul and an
all-gather before the column-parallel one, and each of those is
decomposed into n - 1 ``ppermute``s of one shard's rows around a ring,
with the matmul of the rows already here running under the transfer of
the next (the collective matmul of Wang et al., ASPLOS 2023). At n = 2
each is ONE permute of half the rows.

  allgather_matmul(h, ws)        h rows-sharded, each w column-sharded:
                                 step t multiplies the rows of shard
                                 i - t while they travel on to i + 1
  matmul_reduce_scatter(a, w)    a column-sharded, w row-sharded: step t
                                 multiplies the rows that belong to shard
                                 i - 1 - t, adds what arrived, sends it
                                 on; the own rows come last
  gather_apply_scatter(...)      both around a row-wise function (the
                                 SwiGLU): the gathered rows are never
                                 joined

All three are ``jax.shard_map``s manual over every mesh axis, as the
flash kernel's is (models/llama.py ``_flash_sharded``) and for its
reason. The tensor axis' side is differentiated by jax's own
transposition: the transpose of a gather-side permute is a scatter-side
one, so the backward overlaps the same way, with the two row permutations
(``_join``, ``_split``) each naming the other as its transpose. What was
tried on the chip and lost: PERF.md 6, PR 30.

The BATCH axis' side has backward code of its own (``_products``). A
weight's ``embed`` dimension is stored sharded over a batch axis (``fsdp``)
and every shard of that axis sees other tokens, so a weight's gradient is
a sum over the axis that ends sharded as the weight is stored: a
reduce-scatter. Left to jax and XLA (the weight entering whole over that
axis) it is one blocking ``all-reduce-scatter`` fusion a weight after its
gradient product. Here the weight enters AS STORED and is gathered inside,
and the gradient leaves as stored: the part of it that belongs to another
shard is produced first (a product over that shard's part of ``embed``)
and travels as a ``ppermute`` while the own part's product runs, then the
two are added (scope ``tp.gradient``): at 2 shards ONE permute of half
the gradient, at more the ring of ``_scattered``. With no batch axis of
n > 1 shards under ``embed`` the weights enter whole and the program is
the one without any of this. What the chip read, and the form that splits
the whole gradient after its products instead: PERF.md 6, PR 57.

Whether a forward takes them, and which axis lies under ``embed``, is read
from what it is given (``overlap_plan``), never set: no config field, no
environment variable.
"""

from __future__ import annotations

import contextlib
import functools
import math
import operator
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import ShardingRules, mesh_axes

# the named scope round each helper's shard_map: ``tp.`` and the ``path``
# that the instant ``tp.overlap_plan`` reports when these run (the plain
# program calls none of them), so a trace's permutes and half-row matmuls
# say which plan issued them
SCOPE = "tp.overlap"
# inside it, round the permutes and sums that reduce-scatter a weight's
# gradient over the batch axis under ``embed``: a trace lists them apart
# from the tensor axis' (``benchmark/op_scopes.py`` takes the innermost)
GRAD_SCOPE = "tp.gradient"


@dataclass(frozen=True)
class OverlapPlan:
    """Where the tensor axis lies. ``batch``: the mesh axes (of size > 1)
    that shard the batch dimension, possibly none. ``grad_axis``: the one
    of them that the weights' ``embed`` dimension is stored over (in
    ``grad_shards`` > 1 shards), or None: the axis a weight's gradient is
    reduce-scattered over. ``sites``: the gathers and scatters traced
    under this plan so far, one name each; ``grad_sites``: the weights
    whose gradient travels by the helpers' own permutes, the bytes of one
    permute each."""
    mesh: Any
    axis: str
    shards: int
    batch: Tuple[str, ...]
    grad_axis: Optional[str] = None
    sites: List[str] = field(default_factory=list, compare=False, repr=False)
    grad_sites: List[int] = field(default_factory=list, compare=False,
                                  repr=False)

    @property
    def batch_shards(self) -> int:
        return math.prod(int(self.mesh.shape[a]) for a in self.batch)

    @property
    def grad_shards(self) -> int:
        return int(self.mesh.shape[self.grad_axis]) if self.grad_axis else 1

    def rows(self) -> P:
        """[batch, seq over the tensor axis, features]."""
        return P(self.batch or None, self.axis, None)

    def columns(self) -> P:
        """[batch, seq, features over the tensor axis, ...]."""
        return P(self.batch or None, None, self.axis)

    def rows_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.rows())

    def gathered_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.batch or None, None, None))

    def stored(self, embed: int, *ws) -> bool:
        """Whether ``ws``, whose ``embed`` dimension is ``embed`` long,
        enter their helper as they are stored, ``embed`` over
        ``grad_axis``, and their gradients leave so; else they enter whole
        over the batch axes: no such axis, or an ``embed`` its shards do
        not divide. Counts the sites."""
        if self.grad_axis is None or embed % self.grad_shards:
            return False
        self.grad_sites.extend(
            w.size // (self.shards * self.grad_shards) * w.dtype.itemsize
            for w in ws)
        return True

    def weight(self, at: int, stored: bool) -> P:
        """A weight's spec: its ``embed`` dimension (``at``) over
        ``grad_axis`` where it enters as stored, the other over the
        tensor axis."""
        spec = [self.axis, self.axis]
        spec[at] = self.grad_axis if stored else None
        return P(*spec)


def overlap_plan(mesh, rules: Optional[ShardingRules], batch: int, seq: int,
                 units: Sequence[int]) -> Optional[OverlapPlan]:
    """The plan for a forward over ``[batch, seq]`` tokens whose
    tensor-parallel matmuls have ``units`` indivisible columns on their
    sharded side (heads, not their widths: a shard holds whole heads), or
    None where the plain ``h @ w`` is the program: no mesh or rules, no
    ONE mesh axis of size n > 1 under both ``heads`` and ``mlp``, a
    sequence the rules already shard (``seq`` on ``sp``), or rows, units
    or batch that do not divide."""
    if mesh is None or rules is None:
        return None
    heads, mlp = mesh_axes("heads", rules, mesh), mesh_axes("mlp", rules, mesh)
    if heads != mlp or len(heads) != 1:
        return None
    axis = heads[0]
    n = int(mesh.shape[axis])
    over, embed = (mesh_axes(a, rules, mesh) for a in ("batch", "embed"))
    plan = OverlapPlan(mesh, axis, n, over, embed[0] if len(
        embed) == 1 and embed[0] in over else None)
    if axis in plan.batch or mesh_axes("seq", rules, mesh) or seq % n \
            or batch % plan.batch_shards or any(u % n for u in units):
        return None
    return plan


def _ring(n: int):
    return [(j, (j + 1) % n) for j in range(n)]


def _travelling(x, plan: OverlapPlan):
    """The rows of every shard, one a step, as they come round the ring:
    step t yields the rows of shard i - t. The next step's rows are sent
    on before this step's are handed out, so that what the caller does
    with them runs under the transfer."""
    n = plan.shards
    for t in range(n):
        nxt = jax.lax.ppermute(x, plan.axis, _ring(n)) if t + 1 < n else None
        yield x
        x = nxt


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _join(parts, plan: OverlapPlan, first: int = 0):
    """``parts[t]``: the rows of shard i - first - t, [b, s, ...] each ->
    the whole sequence [b, n * s, ...] in its own order: block b of it is
    the part that arrived at step i - first - b. Written as a choice
    among the parts and not as an update of a buffer at a computed row:
    the choice fuses into whatever reads the sequence next, the update is
    a copy of every part."""
    n = plan.shards
    i = jax.lax.axis_index(plan.axis)
    return jnp.concatenate(
        [jax.lax.select_n((i + 2 * n - first - b) % n, *parts)
         for b in range(n)], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _split(a, plan: OverlapPlan, first: int = 0):
    """The inverse of ``_join``: [b, n * s, K] -> the rows of shard
    i - first - t for t = 0..n-1. A permutation: its transpose is its
    inverse, so the gradient is ``_join`` of the parts' gradients (jax's
    own for n dynamic slices would be n padded copies and their sum)."""
    n, s = plan.shards, a.shape[1] // plan.shards
    i = jax.lax.axis_index(plan.axis)
    return tuple(jax.lax.dynamic_slice_in_dim(
        a, (i + 2 * n - first - t) % n * s, s, axis=1) for t in range(n))


_split.defvjp(lambda a, plan, first: (_split(a, plan, first), None),
              lambda plan, first, _, g: (_join(tuple(g), plan, first),))
_join.defvjp(lambda parts, plan, first: (_join(parts, plan, first), None),
             lambda plan, first, _, g: (_split(g, plan, first),))


def _ring_sum(parts, axis: str, n: int, scope: Optional[str] = None):
    """Reduce-scatter round the ring of ``axis``: ``parts`` yields n
    products one after the other, the one that belongs to shard
    i - 1 - t at step t (t = n - 1: the own). Each is added to what
    arrived from shard i - 1 and sent on to i + 1; the own product is
    made while the last transfer runs. ``scope`` names the sums and
    permutes, not the products."""
    within = contextlib.nullcontext if scope is None else \
        functools.partial(jax.named_scope, scope)
    acc = None
    for t, part in enumerate(parts):
        with within():
            if acc is not None:
                # both sides of the sum stand in memory before it is
                # taken: fused into the matmul's own output the sum would
                # make the matmul wait for the transfer it is there to
                # cover
                part, acc = jax.lax.optimization_barrier((part, acc))
                part = part + acc
            acc = jax.lax.ppermute(part, axis, _ring(n)) \
                if t + 1 < n else part
    return acc


def _scattered(rows, w, plan: OverlapPlan, at: Optional[int] = None):
    """Reduce-scatter of ``rows[t] @ w`` around the tensor axis' ring.
    ``rows[t]``: this shard's columns of the rows of shard i - 1 - t
    (t = n - 1: its own)."""
    return _ring_sum((part for part, in _matmuls(rows, (w,), plan, at)),
                     plan.axis, plan.shards)


def _matmuls(rows, ws, plan: OverlapPlan, at: Optional[int]):
    """``[r @ w for w in ws]`` for each ``r`` of ``rows`` in turn, which
    may be made as they are asked for (``_travelling``). ``at`` None: the
    weights are here whole over the batch axes. Else they are the stored
    shards, ``embed`` (dimension ``at``) over ``plan.grad_axis``, and all
    of a weight's products stand under ONE derivative rule
    (``_products``), which wants the rows together."""
    if at is None:
        for r in rows:
            yield [r @ w for w in ws]
    else:
        rows = tuple(rows)
        yield from zip(*(_products(rows, w, plan, at) for w in ws))


def _whole(w, plan: OverlapPlan, at: int):
    """A stored shard's weight, ``embed`` gathered: XLA's all-gather, as
    it made of a weight that entered whole."""
    return jax.lax.all_gather(w, plan.grad_axis, axis=at, tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _products(rows, w, plan: OverlapPlan, at: int):
    """``tuple(r @ W for r in rows)``: ``rows`` [b, s, K] each, ``w`` this
    device's stored shard of W [K, M], W's dimension ``at`` (its
    ``embed``) over ``plan.grad_axis``, gathered here.

    Every shard of that axis has other tokens, so W's gradient is the sum
    of theirs, and it leaves as ``w`` came: a reduce-scatter, which the
    backward rule makes of products and permutes as ``_scattered`` makes
    the tensor axis'. The part of the gradient that belongs to shard
    j - 1 - u is produced at step u, over that shard's part of ``embed``
    (rows of dW: a slice of ``rows``' features; columns: of the
    cotangents'), added to what arrived and sent on; the own part's
    products run under the last transfer. At 2 shards: ONE permute of
    half the gradient."""
    return _products_fwd(rows, w, plan, at)[0]


def _products_fwd(rows, w, plan, at):
    W = _whole(w, plan, at)
    return tuple(r @ W for r in rows), (rows, W)


def _products_bwd(plan, at, kept, dys):
    rows, W = kept
    f, j = plan.grad_shards, jax.lax.axis_index(plan.grad_axis)
    width = W.shape[at] // f

    def part(u):
        # rows^T dy over the tokens, at shard j - 1 - u's part of embed
        def cut(x):
            return jax.lax.dynamic_slice_in_dim(
                x, (j + 2 * f - 1 - u) % f * width, width, axis=2)

        return functools.reduce(operator.add, (
            jax.lax.dot_general(cut(r) if at == 0 else r,
                                dy if at == 0 else cut(dy),
                                (((0, 1), (0, 1)), ((), ()))).astype(W.dtype)
            for r, dy in zip(rows, dys)))

    drows = tuple(jax.lax.dot_general(
        dy, W, (((2,), (1,)), ((), ()))).astype(r.dtype)
        for r, dy in zip(rows, dys))
    return drows, _ring_sum(map(part, range(f)), plan.grad_axis, f,
                            GRAD_SCOPE)


_products.defvjp(_products_fwd, _products_bwd)


def allgather_matmul(h, ws: Sequence[Any], plan: OverlapPlan,
                     then: Optional[Callable] = None, extras=()):
    """``h`` [B, S, D], S over the tensor axis; each ``w`` [D, N], N over
    it -> ``[h_all @ w for w in ws]``, each [B, S, N] with N over the
    tensor axis. One gather serves all of ``ws`` (q/k/v; gate/up).
    ``then(k, y, shard, *extras)`` is applied to the product ``y`` of
    ``ws[k]`` with the rows of ``shard`` before they are joined (what is
    row-wise after the matmul: the split into heads, the rotary), so that
    the join is the last thing before the consumer and fuses into it;
    ``extras`` reach it whole on every shard. It may split the last
    dimension: [b, s, N] -> [b, s, H, d]."""
    plan.sites.append("gather")
    stored = plan.stored(h.shape[-1], *ws)

    def shard(h, extras, *ws):
        i, n = jax.lax.axis_index(plan.axis), plan.shards
        parts = []
        for t, ys in enumerate(_matmuls(_travelling(h, plan), ws, plan,
                                        0 if stored else None)):
            if then is not None:
                ys = [then(k, y, (i + n - t) % n, *extras)
                      for k, y in enumerate(ys)]
            parts.append(ys)
        return tuple(_join(tuple(p[k] for p in parts), plan)
                     for k in range(len(ws)))

    with jax.named_scope(SCOPE):
        return jax.shard_map(
            shard, mesh=plan.mesh,
            in_specs=(plan.rows(), P())
            + (plan.weight(0, stored),) * len(ws),
            out_specs=(plan.columns(),) * len(ws), check_vma=False)(
                h, tuple(extras), *ws)


def matmul_reduce_scatter(a, w, plan: OverlapPlan):
    """``a`` [B, S, K], K over the tensor axis; ``w`` [K, D], K over it ->
    ``a @ w`` summed over the shards, [B, S, D] with S over the tensor
    axis."""
    plan.sites.append("scatter")
    stored = plan.stored(w.shape[1], w)

    def shard(a, w):
        return _scattered(_split(a, plan, 1), w, plan, 1 if stored else None)

    with jax.named_scope(SCOPE):
        return jax.shard_map(
            shard, mesh=plan.mesh,
            in_specs=(plan.columns(), plan.weight(1, stored)),
            out_specs=plan.rows(), check_vma=False)(a, w)


def gather_apply_scatter(h, ws: Sequence[Any], fn: Callable, w_out,
                         plan: OverlapPlan):
    """``matmul_reduce_scatter(fn(*allgather_matmul(h, ws)), w_out)`` for
    an ``fn`` that works row by row (the SwiGLU): every shard's rows go
    from the gather through ``fn`` to the scatter on their own, so the
    gathered [B, S, N] is never assembled. The own rows arrive first and
    leave last: ``fn`` of them waits while the others pass."""
    plan.sites.extend(("gather", "scatter"))
    stored = plan.stored(h.shape[-1], w_out, *ws)

    def shard(h, w_out, *ws):
        mid = [fn(*ys) for ys in _matmuls(_travelling(h, plan), ws, plan,
                                          0 if stored else None)]
        # mid[t]: the rows of shard i - t; the scatter wants i - 1, ..., i
        return _scattered(mid[1:] + mid[:1], w_out, plan,
                          1 if stored else None)

    with jax.named_scope(SCOPE):
        return jax.shard_map(
            shard, mesh=plan.mesh,
            in_specs=(plan.rows(), plan.weight(1, stored))
            + (plan.weight(0, stored),) * len(ws),
            out_specs=plan.rows(), check_vma=False)(h, w_out, *ws)
