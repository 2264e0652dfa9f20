"""Logical-axis sharding rules: parallelism strategies as presets.

Models annotate every parameter/activation with *logical* axis names
('batch', 'seq', 'embed', 'heads', 'mlp', 'vocab', 'layers', 'experts', ...).
A ShardingRules preset maps logical names to mesh axes; swapping presets
switches the parallelism strategy without touching model code — the
TPU-native replacement for the reference's per-framework backends
(DDP train/torch/config.py:69, FSDP/DeepSpeed _lightning_utils.py:67,101):
there, strategy lives in the wrapped framework; here it's a dict.

The preset table mirrors SURVEY.md §2.4's inventory:
    dp()       — replicated params, batch over dp            (DDP-equiv)
    fsdp()     — params+optimizer sharded over fsdp          (ZeRO-3-equiv)
    fsdp_tp()  — + Megatron-style tensor axes over tp        (TP)
    full()     — + sequence over sp (ring attention)         (SP/CP)
Expert parallelism maps 'experts' over ('dp','fsdp') (EP); pipeline
parallelism shards 'stages' over pp (see pipeline.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple, Union

AxisVal = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    rules: Tuple[Tuple[str, AxisVal], ...]

    def as_dict(self) -> Dict[str, AxisVal]:
        return dict(self.rules)

    def with_(self, **updates) -> "ShardingRules":
        d = self.as_dict()
        d.update(updates)
        return ShardingRules(tuple(d.items()))

    # ---- presets -----------------------------------------------------------

    @classmethod
    def dp(cls) -> "ShardingRules":
        """Pure data parallel: replicated params (DDP-equivalent)."""
        return cls((
            ("batch", ("dp", "fsdp")),
            ("seq", None), ("embed", None), ("mlp", None), ("heads", None),
            ("kv_heads", None), ("head_dim", None), ("vocab", None),
            ("layers", None), ("stages", "pp"), ("experts", None),
            ("expert_mlp", None),
        ))

    @classmethod
    def fsdp(cls) -> "ShardingRules":
        """ZeRO-3-equivalent: params/grads/optimizer sharded on fsdp, batch
        on (dp, fsdp); XLA inserts per-layer all-gather + reduce-scatter."""
        return cls.dp().with_(embed="fsdp")

    @classmethod
    def fsdp_tp(cls) -> "ShardingRules":
        """+ Megatron tensor parallelism: head/mlp/vocab dims on tp.

        ``seq`` stays unnamed here, and yet a dense llama forward under
        these rules shards the SEQUENCE dimension of the residual stream
        over tp between blocks (Megatron sequence parallelism): with
        ``heads`` and ``mlp`` on one axis of n > 1 shards dividing the
        sequence, ``models/llama.py`` ends its row-parallel matmuls in a
        reduce-scatter and starts its column-parallel ones from an
        all-gather, both as half-row permutes that run under the matmuls
        (``parallel/collective_matmul.py``), instead of one blocking
        all-reduce of the whole activation. Inside attention the sequence
        is whole again and the heads are sharded. The same helpers take a
        layer's weights as stored, ``embed`` over fsdp, and reduce-scatter
        their gradients over fsdp themselves: one permute of the other
        shard's half under the own half's gradient products, instead of
        XLA's blocking fusion after them. ``kv_heads`` is not on tp: wk/wv
        and their optimizer state are replicated over it and their
        gradients, scattered over fsdp by then, gathered over tp once a
        step (ROADMAP.md S1)."""
        return cls.fsdp().with_(mlp="tp", heads="tp", vocab="tp")

    @classmethod
    def full(cls) -> "ShardingRules":
        """+ sequence parallelism: activation seq dim on sp (ring attention
        handles the cross-chunk attention; see ops/ring_attention.py)."""
        return cls.fsdp_tp().with_(seq="sp")

    @classmethod
    def ep(cls) -> "ShardingRules":
        """Expert parallel MoE: experts over the data axes, dense dims as in
        fsdp_tp. Routing uses all-to-all over ('dp','fsdp')."""
        return cls.fsdp_tp().with_(experts=("dp", "fsdp"), expert_mlp="tp",
                                   embed=None)


def logical_to_mesh(logical_spec: Tuple[Optional[str], ...],
                    rules: ShardingRules, mesh=None):
    """Map a tuple of logical axis names to a jax PartitionSpec.

    Mesh axes of size 1 are dropped (cleaner SPMD annotations; XLA treats
    them as replicated anyway).
    """
    from jax.sharding import PartitionSpec

    table = rules.as_dict()
    out = []
    for name in logical_spec:
        if name is None:
            out.append(None)
            continue
        axes = table.get(name)
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        if mesh is not None:
            axes = tuple(a for a in axes if int(mesh.shape.get(a, 1)) > 1)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def mesh_axes(logical: str, rules: ShardingRules, mesh) -> Tuple[str, ...]:
    """The mesh axes of size > 1 that one logical axis is sharded over."""
    spec = logical_to_mesh((logical,), rules, mesh)
    a = spec[0] if len(spec) else None
    return () if a is None else (a,) if isinstance(a, str) else tuple(a)


def named_sharding(mesh, logical_spec, rules: ShardingRules):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, logical_to_mesh(tuple(logical_spec), rules, mesh))


def tree_shardings(mesh, logical_tree: Any, rules: ShardingRules):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    import jax

    return jax.tree.map(
        lambda spec: named_sharding(mesh, spec, rules), logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def shard_params(mesh, params, logical_tree, rules: ShardingRules):
    """device_put a param pytree according to its logical annotations."""
    import jax

    shardings = tree_shardings(mesh, logical_tree, rules)
    return jax.device_put(params, shardings)
