"""Sharded train-step builder: the GSPMD replacement for process-group DDP.

Reference inversion (SURVEY.md §2.4): where the reference wires
torch.distributed.init_process_group(nccl) per worker
(train/torch/config.py:69) and lets torch DDP/FSDP allreduce outside the
graph, here ONE jitted function carries params, optimizer state and batch
shardings; XLA emits reduce-scatter/all-gather/psum over ICI:

- DP:   batch sharded over (dp, fsdp); grads psum'd automatically.
- FSDP (ZeRO-3): params + optimizer state sharded over fsdp; per-layer
  all-gather on use, reduce-scatter on grads — emitted by GSPMD from the
  shardings alone.
- TP:   tensor axes from the rules preset.
- SP:   sequence axis sharded; ring attention inside the model.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.core.compile_cache import StoredProgram
from ray_tpu.parallel.sharding import ShardingRules, named_sharding, tree_shardings


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


class StepMemory(NamedTuple):
    """What a train step knows of a device's memory while its loss is
    traced, for a model that decides from the bytes left what its layer
    checkpoint keeps (models/remat.py ``remat_plan``): shapes and the
    device's own limit, never what the process happens to hold."""
    limit: int      # the device's ``bytes_limit``; 0 where it states none
    state: int      # a device's share of the parameters and optimizer state


_step_memory: contextvars.ContextVar[Optional[StepMemory]] = \
    contextvars.ContextVar("ray_tpu_step_memory", default=None)


def step_memory() -> Optional[StepMemory]:
    """The memory of the train step whose loss is being traced; None
    outside one (an evaluation, a forward alone)."""
    return _step_memory.get()


@contextlib.contextmanager
def _bound(memory: StepMemory):
    token = _step_memory.set(memory)
    try:
        yield
    finally:
        _step_memory.reset(token)


def device_bytes_limit(mesh) -> int:
    """The memory one of this process's devices of ``mesh`` says it has
    (16,909,336,064 of a v5e chip's 16 GiB); 0 from a backend that says
    nothing (the CPU's) and from a device this process cannot ask (a
    described topology's, compiled for and not attached)."""
    try:
        stats = (mesh.local_devices or [mesh.devices.flat[0]])[
            0].memory_stats()
    except jax.errors.JaxRuntimeError:
        return 0
    return int((stats or {}).get("bytes_limit", 0))


def state_bytes(state, shardings=None) -> int:
    """A device's share of the bytes of ``state`` (arrays or shapes) under
    ``shardings`` (a matching tree; None: all of every leaf)."""
    leaves = jax.tree.leaves(state)
    shapes = [x.shape for x in leaves] if shardings is None else [
        sh.shard_shape(x.shape)
        for x, sh in zip(leaves, jax.tree.leaves(shardings))]
    return sum(math.prod(shape) * jnp.dtype(x.dtype).itemsize
               for x, shape in zip(leaves, shapes))


def _replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def opt_state_shardings(opt_state_shapes, params_shapes, param_shardings, mesh):
    """Shard optimizer-state subtrees that mirror the param tree like the
    params (ZeRO), everything else replicated. Works for optax chains whose
    states embed params-shaped pytrees (adam/adamw/sgd-momentum/...). A
    leaf of such a subtree whose shape is not its parameter's (adafactor's
    factored rank-1 v_row/v_col, its (1,) placeholders) is replicated: the
    parameter's spec does not apply to it."""
    params_treedef = jax.tree.structure(params_shapes)
    param_sh_flat = jax.tree.leaves(param_shardings)
    param_shape_flat = [p.shape for p in jax.tree.leaves(params_shapes)]

    def rec(node):
        try:
            td = jax.tree.structure(node)
        except Exception:
            td = None
        if td == params_treedef:
            return jax.tree.unflatten(td, [
                sh if leaf.shape == shape else _replicated(mesh)
                for leaf, shape, sh in zip(jax.tree.leaves(node),
                                           param_shape_flat, param_sh_flat)])
        # descend through tuples/namedtuples/lists/dicts
        if isinstance(node, tuple) and type(node) is not tuple:  # namedtuple
            return type(node)(*(rec(c) for c in node))
        if isinstance(node, tuple):
            return tuple(rec(c) for c in node)
        if isinstance(node, list):
            return [rec(c) for c in node]
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return _replicated(mesh)

    return rec(opt_state_shapes)


def batch_sharding(mesh, rules: ShardingRules, batch_shapes):
    """Batch pytree: dim0=batch over (dp,fsdp); dim1=seq over sp (if ranked)."""
    def one(shape):
        ndim = len(shape.shape) if hasattr(shape, "shape") else 0
        if ndim == 0:
            return _replicated(mesh)
        if ndim == 1:
            return named_sharding(mesh, ("batch",), rules)
        return named_sharding(mesh, ("batch", "seq") + (None,) * (ndim - 2), rules)

    return jax.tree.map(one, batch_shapes)


def make_train_state_init(init_params_fn: Callable, optimizer, mesh,
                          rules: ShardingRules, param_logical):
    """Returns (init_fn, state_shardings). init_fn(key) -> TrainState, with
    every array created directly into its shard (jit out_shardings) — no
    host-side full materialization. A warm process loads the program and
    does not trace it (``make_train_step``)."""
    key_shape = jax.eval_shape(lambda k: k, jax.random.PRNGKey(0))
    params_shapes = jax.eval_shape(init_params_fn, key_shape)
    param_sh = tree_shardings(mesh, param_logical, rules)
    opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
    opt_sh = opt_state_shardings(opt_shapes, params_shapes, param_sh, mesh)
    state_sh = TrainState(param_sh, opt_sh, _replicated(mesh))

    def init_fn(key) -> TrainState:
        params = init_params_fn(key)
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    return StoredProgram(init_fn, mesh.devices.flat,
                         out_shardings=state_sh), state_sh


def hold_out(optimizer, names: Tuple[str, ...]):
    """``optimizer`` over every leaf but those whose key in their dict is
    one of ``names``: those get an update of zero and no optimizer state,
    whatever their gradient. For state that a rule of the model's moves
    (``make_train_step``'s ``post_update``) and no gradient reaches."""
    import optax

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "rule" if getattr(path[-1], "key", None) in names
            else "optimizer", params)

    return optax.multi_transform(
        {"optimizer": optimizer, "rule": optax.set_to_zero()}, labels)


def make_train_step(loss_fn: Callable, optimizer, mesh, rules: ShardingRules,
                    state_shardings, batch_shapes=None, donate: bool = True,
                    post_update: Optional[Callable] = None):
    """loss_fn(params, batch) -> scalar loss, or -> (loss, aux) with
    ``aux`` a dict of scalars that the step hands on (an expert model's
    routing statistics, models/moe.py ``finish_loss``). Returns jitted
    step(state, batch) -> (state, metrics); metrics holds ``loss``,
    ``grad_norm``, ``step`` and every key of ``aux``; a scalar loss gives
    the program it always gave.

    ``post_update(params, aux) -> (params, aux)`` is a rule of the model's
    that changes parameters no gradient reaches from what the step's
    ``aux`` carries (a router's bias from the step's counts,
    models/latent.py): it runs after the optimizer's update, inside the
    same jitted step, and takes out of ``aux`` what is no scalar to hand
    on. The optimizer is told to leave those leaves alone (``hold_out``).
    Without a rule the program is the one it was.

    Everything after the gradient lies in the named scope ``optimizer``
    (the rule in ``optimizer/rule``, the norm in ``optimizer/grad_norm``):
    with the model's own scopes (models/llama.py) every device op of the
    step says which part issued it.

    While the loss is traced the step's memory is bound (``step_memory``:
    the device's limit and a device's share of ``state``, parameters AND
    the optimizer's leaves), so the model knows the optimizer's bytes
    without guessing the optimizer.

    Where jax's persistent cache is on, the first call and
    ``.lower(state, batch).compile()`` look for the executable under a key
    of ``_step`` BY VALUE (its code and what it closes over: ``loss_fn``,
    ``optimizer``, ``post_update``, the mesh), the shardings, donation,
    the arguments' shapes, the device's limit and the source
    (core/compile_cache.py ``StoredProgram``): a warm process loads its
    step and does not trace it, and the plans the trace said are said
    again. A loss that must RUN as the step is first called (it counts,
    it appends) is no such loss: keep the cache off around it."""
    batch_sh = (batch_sharding(mesh, rules, batch_shapes)
                if batch_shapes is not None else None)

    def _step(state: TrainState, batch):
        memory = StepMemory(device_bytes_limit(mesh),
                            state_bytes(state, state_shardings))

        def lf(p):
            with _bound(memory):
                out = loss_fn(p, batch)     # (loss, aux) or a scalar
            return out if isinstance(out, tuple) else (out, {})

        (loss, aux), grads = jax.value_and_grad(lf, has_aux=True)(
            state.params)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  state.params, updates)
            if post_update is not None:
                with jax.named_scope("rule"):
                    params, aux = post_update(params, aux)
            with jax.named_scope("grad_norm"):
                gnorm = optax_global_norm(grads)
        metrics = {**aux, "loss": loss, "grad_norm": gnorm,
                   "step": state.step + 1}
        return TrainState(params, opt_state, state.step + 1), metrics

    kwargs = {}
    if donate:
        kwargs["donate_argnums"] = (0,)
    return StoredProgram(
        _step, mesh.devices.flat, observes=lambda: device_bytes_limit(mesh),
        in_shardings=(state_shardings, batch_sh),
        out_shardings=(state_shardings, _replicated(mesh)), **kwargs)


def optax_global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def make_eval_step(loss_fn: Callable, mesh, rules: ShardingRules,
                   state_shardings):
    def _eval(state: TrainState, batch):
        return loss_fn(state.params, batch)

    return jax.jit(_eval, in_shardings=(state_shardings, None),
                   out_shardings=_replicated(mesh))
