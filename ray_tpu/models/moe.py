"""Sparse-expert llama variant: the dense model's block with its SwiGLU
replaced by a DROPLESS top-k mixture of experts, as OLMoE-1B-7B has it
(arXiv:2409.02060; transformers ``modeling_olmoe.py``).

This module is only what differs from ``models/llama.py``: the config,
the parameter tree, the expert layer (``feed_forward``) and the router
losses (``finish_loss``). Embedding, attention (with OLMoE's q/k norm,
``qk_norm``), the layer loop, remat and its policy, activation shardings,
the flash kernel under ``shard_map`` and the logsumexp-form cross-entropy
with bf16 logits are the dense model's code: ``forward`` and ``loss_fn``
ARE ``llama.forward`` and ``llama.loss_fn``, which take the feed-forward
half of a block from the module that defines the config's class.

The expert layer, for T tokens, E experts, K per token:
router logits [T, E] in float32 -> softmax over all E -> the K largest
probabilities and their experts (kept as they are, or renormalised with
``norm_topk``) -> a stable sort of the T*K assignments by expert, group
sizes by a count (static shapes, no host round trip) -> rows gathered into
expert order -> three grouped matmuls (gate, up, down;
``ops/grouped_matmul.py``) -> rows gathered back into token order and
summed with their weights. Every gradient of a gather is written as a
gather (``_dispatch``, ``_down_combine``), never a scatter-add. There is no capacity: every assignment is
computed, ``moe_dropped`` counts the rows no group covers and is 0.

Across the layer checkpoint the routes are kept (REMAT_SAVED: experts,
weights, sort order and its inverse, group sizes: 36 bytes a token and
layer at K 8); of what is T*K rows wide the backward recomputes the rows
in expert order, gate, up and their SwiGLU, and not the down projection
(``_down_combine``; the numbers: PERF.md section 6, PR 26).

On a mesh that shards ``experts`` (``ShardingRules.ep()``) the "xla" path
runs under GSPMD; the "pallas" path refuses any mesh of several devices.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import llama as _ll
from ray_tpu.ops.grouped_matmul import grouped_matmul


@dataclass(frozen=True)
class MoEConfig(_ll.LlamaConfig):
    """``d_ff`` is the width of ONE expert."""
    n_experts: int = 8
    top_k: int = 2
    # RMS norm of the whole projected q and k vectors before the split
    # into heads (llama._attention_half)
    qk_norm: bool = False
    # divide the K kept probabilities by their sum (OLMoE does not)
    norm_topk: bool = False
    # weights of the two router losses added to the cross-entropy
    router_aux_weight: float = 0.01     # load balancing
    router_z_weight: float = 0.001      # mean squared logsumexp
    gmm_impl: str = "xla"               # "xla" | "pallas"

    def replace(self, **kw) -> "MoEConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, MoEConfig] = {
    "tiny": MoEConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=96, max_seq_len=128, n_experts=4,
                      top_k=2, qk_norm=True),
    # allenai/OLMoE-1B-7B-0125-Instruct config.json: 6.9 B parameters,
    # 1.3 B of them active for a token
    "olmoe-1b-7b": MoEConfig(vocab_size=50304, d_model=2048, n_layers=16,
                             n_heads=16, n_kv_heads=16, d_ff=1024,
                             max_seq_len=4096, n_experts=64, top_k=8,
                             qk_norm=True),
}

# what the layer checkpoint keeps of an expert layer (llama._checkpoint)
REMAT_SAVED = ("moe_route",)


def param_specs(cfg: MoEConfig) -> Dict[str, Any]:
    spec = _ll.param_specs(cfg)
    L = ("layers",)
    lay = dict(spec["layers"])
    for w in ("w_gate", "w_up", "w_down"):
        del lay[w]
    if cfg.qk_norm:
        lay["q_norm"] = L + ("heads",)
        lay["k_norm"] = L + ("kv_heads",)
    lay["router"] = L + ("embed", "experts")
    lay["we_gate"] = L + ("experts", "embed", "expert_mlp")
    lay["we_up"] = L + ("experts", "embed", "expert_mlp")
    lay["we_down"] = L + ("experts", "expert_mlp", "embed")
    spec["layers"] = lay
    return spec


def init_params(key, cfg: MoEConfig) -> Dict[str, Any]:
    params = _ll.init_params(key, cfg.replace(d_ff=1))   # no dense SwiGLU
    pd = cfg.param_dtype
    L, D, F, E = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(jax.random.fold_in(key, 1), 4)
    lay = dict(params["layers"])
    for w in ("w_gate", "w_up", "w_down"):
        del lay[w]
    if cfg.qk_norm:
        lay["q_norm"] = jnp.ones((L, cfg.n_heads * cfg.head_dim), pd)
        lay["k_norm"] = jnp.ones((L, cfg.n_kv_heads * cfg.head_dim), pd)
    lay["router"] = jax.random.normal(ks[0], (L, D, E), pd) * 0.02
    lay["we_gate"] = jax.random.normal(ks[1], (L, E, D, F), pd) * D ** -0.5
    lay["we_up"] = jax.random.normal(ks[2], (L, E, D, F), pd) * D ** -0.5
    lay["we_down"] = jax.random.normal(ks[3], (L, E, F, D), pd) * F ** -0.5
    params["layers"] = lay
    return params


def num_params(cfg: MoEConfig) -> int:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff
    qk = (cfg.n_heads + cfg.n_kv_heads) * cfg.head_dim if cfg.qk_norm else 0
    return _ll.num_params(cfg.replace(d_ff=0)) + cfg.n_layers * (
        qk + D * E + 3 * E * D * F)


def _rows(tokens, k, order):
    """Rows in expert order of a [T, ...] array: row i belongs to token
    order[i] // K (assignment order[i] of the T*K, token-major)."""
    return tokens[order // k]


def _tokens(rows, k, back):
    """[T*K, D] rows in expert order -> [T, K, D] in token order."""
    return rows[back].reshape(back.shape[0] // k, k, rows.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(k, x, order, back):
    """x [T, D] -> its rows in expert order [T*K, D]. The gradient is a
    gather too (rows back into token order, summed over K), where jax's
    own for a gather would be a scatter-add."""
    return _rows(x, k, order)


def _dispatch_fwd(k, x, order, back):
    return _rows(x, k, order), back


def _dispatch_bwd(k, back, g):
    return _tokens(g, k, back).sum(axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _down_combine(impl, h, w_down, weights, order, back, sizes):
    """The down projection of the rows in expert order and their weighted
    sum back into token order: h [T*K, F], w_down [E, F, D], weights
    [T, K] float32 -> y [T, D], y[t] = sum_k weights[t, k] x (h w_down)
    [back[t*K + k]].

    One custom VJP over both, so that the backward needs h and not the
    [T*K, D] product: with u = dy's rows times w_down^T (one grouped
    matmul), dh = w x u, d weights = <u, h> row by row, d w_down = (w x
    h)^T (dy's rows). Under the layer checkpoint the down projection is
    then not recomputed, nor its rows gathered a second time."""
    ys = grouped_matmul(h, w_down, sizes, impl=impl)
    k = weights.shape[1]
    y = (_tokens(ys, k, back).astype(jnp.float32)
         * weights[..., None]).sum(axis=1)
    return y.astype(h.dtype)


def _down_combine_fwd(impl, h, w_down, weights, order, back, sizes):
    return (_down_combine(impl, h, w_down, weights, order, back, sizes),
            (h, w_down, weights, order, back, sizes))


def _down_combine_bwd(impl, res, dy):
    h, w_down, weights, order, back, sizes = res
    k = weights.shape[1]
    w_rows = weights.reshape(-1)[order]                        # [T*K]
    # the product is linear in each operand: its VJP at (w x h, w_down)
    # gives u = dy_rows w_down^T and (w x h)^T dy_rows; the product itself
    # is never used and falls away
    _, vjp = jax.vjp(
        lambda a, w: grouped_matmul(a, w, sizes, impl=impl),
        (h.astype(jnp.float32) * w_rows[:, None]).astype(h.dtype), w_down)
    u, d_w_down = vjp(_rows(dy, k, order).astype(h.dtype))
    u = u.astype(jnp.float32)
    d_h = (u * w_rows[:, None]).astype(h.dtype)
    d_weights = (u * h.astype(jnp.float32)).sum(axis=-1)[back].reshape(
        weights.shape)
    return d_h, d_w_down, d_weights, None, None, None


_down_combine.defvjp(_down_combine_fwd, _down_combine_bwd)


def route(logits, cfg: MoEConfig):
    """Router logits [T, E] float32 -> (weights [T, K] float32, experts
    [T, K] int32, probabilities [T, E])."""
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return weights, experts, probs


def feed_forward(h, lp, cfg: MoEConfig, mesh=None, rules=None, tp=None):
    """The expert layer: normed h [B, S, D] -> (its output [B, S, D],
    this layer's routing statistics for ``finish_loss``). It takes every
    row of a sequence and no overlap plan (llama._tp_plan gives none)."""
    assert tp is None, "the expert layer is not row-parallel"
    if cfg.gmm_impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "gmm_impl='pallas' runs on one device: GSPMD cannot partition "
            "the Mosaic grouped matmul, and the expert layer has no "
            f"shard_map of its own yet (mesh {dict(mesh.shape)}); use "
            "gmm_impl='xla' on a mesh")
    B, S, D = h.shape
    E, K = cfg.n_experts, cfg.top_k
    T, dt = B * S, cfg.dtype
    x = h.reshape(T, D)
    logits = jnp.dot(x, _ll._dq(lp["router"], dt),
                     preferred_element_type=jnp.float32)            # [T, E]
    weights, experts, probs = route(logits, cfg)
    flat = experts.reshape(T * K)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)   # row -> slot
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))                    # slot -> row
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    weights, experts, order, back, sizes = checkpoint_name(
        (weights, experts, order, back, sizes), REMAT_SAVED[0])

    xs = _dispatch(K, x, order, back)            # rows in expert order
    mm = lambda w: grouped_matmul(xs, _ll._dq(lp[w], dt), sizes,   # noqa: E731
                                  impl=cfg.gmm_impl)
    y = _down_combine(cfg.gmm_impl, jax.nn.silu(mm("we_gate")) * mm("we_up"),
                      _ll._dq(lp["we_down"], dt), weights, order, back, sizes)
    stats = {"counts": sizes,
             "prob_sum": probs.sum(axis=0),
             "z_sum": jnp.square(jax.nn.logsumexp(logits, axis=-1)).sum(),
             "experts": experts}
    return y.reshape(B, S, D), stats


def finish_loss(loss, stats, cfg: MoEConfig):
    """Cross-entropy + the router losses, from the layers' stacked
    statistics -> (loss, aux): transformers' ``load_balancing_loss_func``
    (the router outputs of all layers concatenated: E x sum over experts
    of the share of assignments times the mean probability) and the
    router z-loss (mean squared logsumexp of the router logits)."""
    E, K = cfg.n_experts, cfg.top_k
    counts = stats["counts"]                                   # [L, E]
    rows = counts.shape[0] * stats["experts"].shape[1]         # L x T
    share = counts.sum(axis=0).astype(jnp.float32) / rows
    aux = E * jnp.sum(share * stats["prob_sum"].sum(axis=0) / rows)
    z = stats["z_sum"].sum() / rows
    per_layer = rows // counts.shape[0] * K                    # T x K
    return (loss + cfg.router_aux_weight * aux + cfg.router_z_weight * z, {
        "moe_aux_loss": aux, "moe_z_loss": z,
        "moe_load_max_over_mean":
            counts.max().astype(jnp.float32) * E / per_layer,
        "moe_dropped": (per_layer - counts.sum(axis=1)).sum()})


forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn
