"""Sparse-attention / linear-attention hybrid with a dense SwiGLU in every
layer, as MiniCPM-SALA has it (``minicpm_sala``): a few layers of
grouped-query softmax attention that, past a stated length, attend to the
best BLOCKS of keys a query and KV group (InfLLM-V2, arXiv:2509.24663, as
MiniCPM4 ships it, arXiv:2506.07900), the rest Lightning linear-attention
layers (Lightning Attention-2, arXiv:2401.04658), under MiniCPM's three
multipliers.

This module is only what differs from ``models/llama.py``: the config (the
layers' kinds, the selection's sizes, the decay), the parameter tree, the
two attention halves (``attention_half``, by the layer's kind) and the
counters they report. Embedding and its multiplier, the loop over runs of
layers, remat and its policy, ``_norm``, ``_project`` (a norm a head),
``_residual`` (the depth multiplier), the dense SwiGLU, the head, the
logits' divisor and the cross-entropy are ``llama.forward_with_stats`` and
``llama.loss_fn``. Layers of two kinds cannot be one stack:
``params["layers"]`` is a LIST of stacks, one a run of adjacent layers of a
kind (``layer_runs``), as ``models/hybrid.py`` has it.

A "sparse" layer, x the normed input, H query heads over KV groups of
H / KV heads, no rotary:

    q_h = rms_head(x Wq)_h    k_g = rms_head(x Wk)_g    v_g = (x Wv)_g
    S <= dense_len:  o = causal softmax attention, scale 1 / sqrt(HD)
    else:            Sel = select_blocks(q, k)      (no gradient)
                     o_{h,t} = softmax over {s <= t, s // block in
                               Sel_{g,t}} of (q_{h,t} . k_{g,s} / sqrt(HD))
    out = (o * sigmoid(x Wg)) Wo

``select_blocks``, a named stage of its own (scope ``block_select``,
instant ``sala.select_plan``), a block of ``SELECT_ROWS`` queries at a
time so that no [S, S] array exists (the scores are [B, H, rows, S /
stride] float32: 134 MB a block of 1,024 queries at S 16,384):

    kbar_{g,j} = mean(k_g[stride j : stride j + kernel])   kernels that lie
                                                  whole inside the sequence
    a_{h,t,j}  = softmax_j(q_{h,t} . kbar_{g,j} / sqrt(HD))   over kernels
                                                  that END at or before t
    A_{g,t,j}  = sum of a over the group's heads
    score_{g,t,b} = max_j A_{g,t,j}, j in [per b - 1, per b + per - 1]
                                (per = block / stride: max-pool per + 1,
                                stride per, padding 1)
    block b < init_blocks and the window / block blocks that end with the
    query's own score +inf; Sel_{g,t} = the topk best of the blocks b <=
    t // block, all of them where there are at most topk, ties to the
    lower index (an exact ``lax.top_k`` for the topk-th value, then the
    ties counted in order: no sort of indices, no ``approx_max_k``).

The set ``[B, KV, S, S / block]`` int8 is computed once a layer and step:
the layer checkpoint keeps it (``BLOCK_SET``; 8 MB at S 16,384), so the
replay never scores again. Attention over it is
``ops/sparse_attention.block_sparse_attention``.

A "lightning" layer, LH heads of HD with keys and values a head of their
own, rotary (``rotate_half`` pairs over the head's lanes):

    q_h, k_h = rope(rms_head(x Wq)_h), rope(rms_head(x Wk)_h)   v_h = (x Wv)_h
    S_t = exp(-s_h) S_{t-1} + k_t^T v_t      o_t = (q_t / sqrt(HD)) S_t
    out = (rms_head(o) * sigmoid(x Wg)) Wo       s_h = 2^(-8 (h + 1) / LH)

which is ``ops/ssd.py``'s recurrence with x = v, B = k, C = q / sqrt(HD),
N = P = HD, a group a head and a constant decay (``dt`` None): "xla" or
"pallas" by ``ssd_impl``, as ``models/hybrid.py`` chooses.

The kernel paths run on one device: GSPMD cannot partition a Mosaic call
and neither half has a shard_map of its own.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import llama as _ll
from ray_tpu.ops.flash_attention import NEG_INF
from ray_tpu.ops.sparse_attention import (block_pairs_walked,
                                          block_sparse_attention)
from ray_tpu.ops.ssd import _over_lanes, _per_head, ssd_scan
from ray_tpu.util import tracing

KINDS = ("sparse", "lightning")
# the checkpoint_name tag of a sparse layer's set, kept across the layer
# checkpoint (remat._checkpoint) so that the replay selects nothing; beyond
# it, where the step's memory has room (remat.remat_plan), the dense SwiGLU's
# gate and up, of either kind of layer (``FAMILY``: llama's)
BLOCK_SET = "block_set"
REMAT_SAVED = (BLOCK_SET,)
# queries a block of the selection scores at once
SELECT_ROWS = 1024
# steps a chunk of the lightning layers' scan (the sequence where it is
# shorter): at 32 heads of 128 over 16,384 steps the scan's forward and
# backward took 6.27 ms at 128, 5.76 at 256 and 5.90 at 512 (PERF.md 6, PR
# 52); the source config has no such key
LIGHTNING_CHUNK = 256
COUNTERS = ("sparse_blocks_selected", "sparse_pairs_selected",
            "sparse_set_forced", "sparse_pairs_walked")


@dataclass(frozen=True)
class SalaConfig(_ll.LlamaConfig):
    """``n_heads``, ``n_kv_heads`` and ``head_width`` are the sparse
    layers'; a lightning layer has ``lightning_heads`` heads of the same
    width. ``residual_multiplier`` is scale_depth / sqrt(the PUBLISHED
    depth), ``logits_scaling`` hidden_size / dim_model_base,
    ``embedding_multiplier`` scale_emb (LlamaConfig's fields)."""
    # one kind a layer, "sparse" or "lightning"; () = every layer "sparse"
    layer_types: Tuple[str, ...] = ()
    qk_head_norm: bool = True           # llama._project: a norm a head
    rope: bool = True                   # the lightning layers'; see attn_kinds
    attn_kinds: Tuple[Tuple[str, _ll.AttentionKind], ...] = (
        ("sparse", _ll.AttentionKind(rope=False)),
        ("lightning", _ll.AttentionKind()))
    # the selection (InfLLM-V2): keys a pooled kernel and between kernels,
    # keys a block, blocks a query, blocks forced at the start, the keys of
    # the forced window, and the length up to which attention is dense
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    dense_len: int = 8192
    lightning_heads: int = 32
    ssd_impl: str = "xla"               # "xla" | "pallas"
    # the sets themselves among a sparse layer's statistics (evaluation)
    report_sets: bool = False

    @property
    def kinds(self) -> Tuple[str, ...]:
        return self.layer_types or ("sparse",) * self.n_layers

    def replace(self, **kw) -> "SalaConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, SalaConfig] = {
    # the CPU tests' size: one sparse layer to three lightning layers and
    # a trailing sparse one, 4 query heads over 2 KV heads of 16, blocks of
    # 8 keys (kernels of 4 at stride 2), the 4 best of up to 16 blocks,
    # dense up to 32 tokens
    "tiny": SalaConfig(
        vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_width=16, d_ff=128, max_seq_len=128, norm_eps=1e-6,
        layer_types=("sparse", "lightning", "lightning", "lightning",
                     "sparse"),
        embedding_multiplier=12.0, residual_multiplier=1.4 / 32 ** 0.5,
        logits_scaling=4.0, sparse_kernel=4, sparse_stride=2, sparse_block=8,
        sparse_topk=4, sparse_init_blocks=1, sparse_window=16, dense_len=32,
        lightning_heads=4),
}


def layer_runs(cfg: SalaConfig) -> List[Tuple[str, int]]:
    """[(kind, how many adjacent layers of it), ...] in the layers' order."""
    if len(cfg.kinds) != cfg.n_layers:
        raise ValueError(f"{len(cfg.kinds)} layer types for {cfg.n_layers} "
                         "layers")
    runs: List[Tuple[str, int]] = []
    for kind in cfg.kinds:
        if kind not in KINDS:
            raise ValueError(f"unknown layer type {kind!r}")
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def _widths(cfg: SalaConfig, kind: str) -> Tuple[int, int]:
    """(the lanes of q, of o and of the gate; the lanes of k and of v)."""
    hd = cfg.head_dim
    if kind == "lightning":
        return cfg.lightning_heads * hd, cfg.lightning_heads * hd
    return cfg.n_heads * hd, cfg.n_kv_heads * hd


def remat_saved_bytes(cfg: SalaConfig, kind, rows: int) -> int:
    """A sparse layer's set over ``rows`` tokens of ONE sequence (several
    sequences of the same total hold less)."""
    return rows * cfg.n_kv_heads * (rows // cfg.sparse_block) \
        if kind == "sparse" and rows > cfg.dense_len else 0


def param_specs(cfg: SalaConfig) -> Dict[str, Any]:
    L = ("layers",)

    def stack(kind):
        own = {"o_norm": L + (None,)} if kind == "lightning" else {}
        return {
            "attn_norm": L + ("embed_nr",),
            "wq": L + ("embed", "heads"), "wk": L + ("embed", "kv_heads"),
            "wv": L + ("embed", "kv_heads"),
            "q_norm": L + (None,), "k_norm": L + (None,),
            "w_out_gate": L + ("embed", "heads"),
            "wo": L + ("heads", "embed"), **own,
            "ffn_norm": L + ("embed_nr",),
            "w_gate": L + ("embed", "mlp"), "w_up": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed")}

    return {"embed": ("vocab", "embed"),
            "layers": [stack(kind) for kind, _ in layer_runs(cfg)],
            "final_norm": ("embed_nr",), "lm_head": ("embed", "vocab")}


def init_params(key, cfg: SalaConfig) -> Dict[str, Any]:
    """Norms 1, projections normal over the square root of their fan-in,
    the embedding 0.02; the lightning layers' decay is no parameter."""
    pd = cfg.param_dtype
    D, F, hd = cfg.d_model, cfg.d_ff, cfg.head_dim

    def stack(kind, n, i):
        wide, narrow = _widths(cfg, kind)
        ks = iter(jax.random.split(jax.random.fold_in(key, 100 + i), 8))

        def dense(shape, fan_in):
            return jax.random.normal(next(ks), (n, *shape), pd) * fan_in ** -0.5

        own = {"o_norm": jnp.ones((n, hd), pd)} if kind == "lightning" else {}
        return {
            "attn_norm": jnp.ones((n, D), pd),
            "wq": dense((D, wide), D), "wk": dense((D, narrow), D),
            "wv": dense((D, narrow), D),
            "q_norm": jnp.ones((n, hd), pd), "k_norm": jnp.ones((n, hd), pd),
            "w_out_gate": dense((D, wide), D), "wo": dense((wide, D), wide),
            **own,
            "ffn_norm": jnp.ones((n, D), pd),
            "w_gate": dense((D, F), D), "w_up": dense((D, F), D),
            "w_down": dense((F, D), F)}

    return {"embed": jax.random.normal(jax.random.fold_in(key, 0),
                                       (cfg.vocab_size, D), pd) * 0.02,
            "layers": [stack(kind, n, i)
                       for i, (kind, n) in enumerate(layer_runs(cfg))],
            "final_norm": jnp.ones((D,), pd),
            "lm_head": jax.random.normal(jax.random.fold_in(key, 1),
                                         (D, cfg.vocab_size), pd) * D ** -0.5}


def num_params(cfg: SalaConfig) -> int:
    D, hd = cfg.d_model, cfg.head_dim
    total = 2 * cfg.vocab_size * D + D
    for kind in cfg.kinds:
        wide, narrow = _widths(cfg, kind)
        total += (2 * D + 3 * D * wide + 2 * D * narrow + 3 * D * cfg.d_ff
                  + (3 if kind == "lightning" else 2) * hd)
    return total


def slopes(heads: int):
    """The lightning heads' decay rates, float32 [heads]: ALiBi's slopes
    ``2^(-8 (h + 1) / heads)``; a head's state shrinks by exp(-s_h) a
    step."""
    return 2.0 ** (-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                   / heads)


# --- the selection -----------------------------------------------------------


def select_plan(cfg: SalaConfig, B: int, S: int) -> dict:
    """The selection's account of itself (also the attributes of
    ``sala.select_plan``): the pooled kernels and blocks a sequence has,
    the queries scored at once and their scores' bytes, what a query
    keeps, and the set's bytes."""
    rows = min(SELECT_ROWS, S)
    kernels = (S - cfg.sparse_kernel) // cfg.sparse_stride + 1
    blocks = S // cfg.sparse_block
    return {"path": "rows", "S": S, "kernels": kernels, "blocks": blocks,
            "rows_per_block": rows, "row_blocks": S // rows,
            "score_bytes": B * cfg.n_heads * rows * kernels * 4,
            "topk": min(cfg.sparse_topk, blocks),
            "forced": cfg.sparse_init_blocks
            + cfg.sparse_window // cfg.sparse_block,
            "set_bytes": B * cfg.n_kv_heads * S * blocks, "exact": True}


def pooled_keys(k, cfg: SalaConfig):
    """k [B, S, KV, D] -> float32 [B, J, KV, D]: the mean of every kernel
    of ``sparse_kernel`` keys at ``sparse_stride`` that lies whole inside
    the sequence (a kernel is ``kernel / stride`` adjacent strides)."""
    B, S, KV, D = k.shape
    stride, per = cfg.sparse_stride, cfg.sparse_kernel // cfg.sparse_stride
    if cfg.sparse_kernel % stride or S % stride:
        raise ValueError(f"kernels of {cfg.sparse_kernel} at {stride} over "
                         f"{S} keys: want whole strides")
    parts = k.astype(jnp.float32).reshape(B, S // stride, stride, KV, D) \
        .mean(axis=2)
    n = S // stride - per + 1
    return sum(parts[:, i:i + n] for i in range(per)) / per


def block_scores(scores, cfg: SalaConfig):
    """scores [..., J] (a KV group's summed probabilities of the pooled
    kernels, >= 0) -> [..., J // per + 1] a block: the largest of the
    kernels ``per b - 1 .. per b + per - 1`` (a max-pool of per + 1 at stride
    per, padding 1; per = block / stride), kernels outside the sequence
    counting -1."""
    per = cfg.sparse_block // cfg.sparse_stride
    blocks = scores.shape[-1] // per + 1
    lead = scores.shape[:-1]
    padded = jnp.pad(scores, [(0, 0)] * len(lead) + [
        (1, per * blocks + per - 1 - scores.shape[-1])], constant_values=-1.0)
    first = padded[..., :per * blocks].reshape(*lead, blocks, per).max(-1)
    return jnp.maximum(first, padded[..., per:per * blocks + per:per])


def forced_blocks(t, blocks: int, cfg: SalaConfig):
    """Queries t [rows] -> bool [rows, blocks]: the blocks a query keeps
    whatever they score: the first ``sparse_init_blocks`` and the
    ``sparse_window / sparse_block`` that end with its own."""
    own = (t // cfg.sparse_block)[:, None]
    b = jnp.arange(blocks)[None, :]
    window = cfg.sparse_window // cfg.sparse_block
    return ((b < cfg.sparse_init_blocks) | (b > own - window)) & (b <= own)


def top_blocks(scores, t, cfg: SalaConfig):
    """scores [..., rows, blocks] float32, t [rows] the rows' queries ->
    int8 0/1 of the same shape: a query's forced blocks and the best of the
    others, ``sparse_topk`` in all, of the blocks that do not start after
    it (all of them where there are no more), ties to the lower index. The
    topk-th value by an exact ``lax.top_k``; blocks that score more are in,
    those that tie with it in rising order until the set is full."""
    blocks = scores.shape[-1]
    seen = jnp.arange(blocks)[None, :] <= (t // cfg.sparse_block)[:, None]
    if blocks <= cfg.sparse_topk:
        return jnp.broadcast_to(seen, scores.shape).astype(jnp.int8)
    ranked = jnp.where(forced_blocks(t, blocks, cfg), 1e30,
                       jnp.where(seen, scores, -2.0))
    least = jax.lax.top_k(ranked, cfg.sparse_topk)[0][..., -1:]
    above, ties = ranked > least, ranked == least
    room = cfg.sparse_topk - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (ties & (jnp.cumsum(ties, axis=-1) <= room))
    return (chosen & seen).astype(jnp.int8)


def group_scores(q, pooled, t, cfg: SalaConfig):
    """q [B, rows, H, D] (queries t [rows]), pooled [B, J, KV, D] in q's
    type -> float32 [B, KV, rows, J]: each head's softmax over the pooled
    kernels that end at or before its query, summed over the KV group's
    heads (a query before the first kernel's end scores 0 everywhere)."""
    B, rows, H, D = q.shape
    J, KV = pooled.shape[1], pooled.shape[2]
    s = jnp.einsum("brkgd,bjkd->bkgrj",
                   q.reshape(B, rows, KV, H // KV, D), pooled,
                   preferred_element_type=jnp.float32) * D ** -0.5
    ends = jnp.arange(J) * cfg.sparse_stride + cfg.sparse_kernel - 1
    on = ends[None, :] <= t[:, None]                         # [rows, J]
    s = jnp.where(on, s, NEG_INF)
    p = jnp.where(on, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return p.sum(axis=2)


def select_blocks(q, k, cfg: SalaConfig):
    """q [B, S, H, D], k [B, S, KV, D] as the attention call takes them ->
    the sets, int8 [B, KV, S, S / sparse_block] (the module docstring has
    the rule), ``SELECT_ROWS`` queries at a time. No gradient passes."""
    B, S, H, D = q.shape
    rows = min(SELECT_ROWS, S)
    if S % rows or S % cfg.sparse_block:
        raise ValueError(f"{S} queries in blocks of {rows} and keys in "
                         f"blocks of {cfg.sparse_block}")
    q, k = jax.lax.stop_gradient((q, k))
    with jax.named_scope("block_select"):
        pooled = pooled_keys(k, cfg).astype(q.dtype)

        def block(i):
            t = i * rows + jnp.arange(rows)
            scores = group_scores(
                jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1),
                pooled, t, cfg)
            return top_blocks(block_scores(scores, cfg), t, cfg)

        sets = jax.lax.map(block, jnp.arange(S // rows))  # [n, B, KV, rows, nb]
        return jnp.moveaxis(sets, 0, 2).reshape(B, -1, S, S // cfg.sparse_block)


def set_counts(sel, cfg: SalaConfig) -> dict:
    """What a layer's sets count, int32 over the batch and the KV groups:
    the selected blocks, the (query, key) pairs they hold (a query's own
    block up to itself) and the forced blocks among them."""
    S, blocks = sel.shape[2], sel.shape[3]
    t = jnp.arange(S)
    on = sel.astype(jnp.int32)
    own = jax.nn.one_hot(t // cfg.sparse_block, blocks, dtype=jnp.int32)
    pairs = cfg.sparse_block * jnp.sum(on) - jnp.sum(
        on * own * (cfg.sparse_block - 1 - t % cfg.sparse_block)[:, None])
    forced = forced_blocks(t, blocks, cfg).astype(jnp.int32)
    return {"sparse_blocks_selected": jnp.sum(on),
            "sparse_pairs_selected": pairs,
            "sparse_set_forced": jnp.sum(on * forced)}


# --- the two halves ----------------------------------------------------------


def _one_device(mesh, what: str):
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            f"{what} runs on one device: GSPMD cannot partition the Mosaic "
            f"calls and the half has no shard_map of its own (mesh "
            f"{dict(mesh.shape)})")


def _no_counts() -> dict:
    return dict.fromkeys(COUNTERS, jnp.zeros((), jnp.int32))


def _sparse_half(h, lp, cfg: SalaConfig, mesh, rules):
    """The sparse layer's attention over its normed input h: (the heads'
    outputs [B, S, H x HD] before the gate, what it reports)."""
    B, S, _ = h.shape
    H, KV = cfg.n_heads, cfg.n_kv_heads
    q = _ll._project(h, lp, cfg, "wq", H, "q_norm")
    k = _ll._project(h, lp, cfg, "wk", KV, "k_norm")
    v = _ll._project(h, lp, cfg, "wv", KV)
    said = _no_counts()
    if S <= cfg.dense_len:
        with jax.named_scope("dense"):
            out = _ll._attention(q, k, v, cfg, causal=True, mesh=mesh,
                                 rules=rules, kind="sparse")
        return out.reshape(B, S, -1), said
    _one_device(mesh, "attention over a set of blocks")
    tracing.plan("sala.select_plan", select_plan(cfg, B, S))
    sel = checkpoint_name(select_blocks(q, k, cfg), BLOCK_SET)
    with jax.named_scope("block_sparse"):
        out = block_sparse_attention(q, k, v, sel)
    with jax.named_scope("block_select"):
        said = {**set_counts(sel, cfg), "sparse_pairs_walked": jnp.int32(
            block_pairs_walked(q, k, sel) // 1024)}
        if cfg.report_sets:
            said["block_set"] = sel
    return out.reshape(B, S, -1), said


def _head_norm(x, scale, heads: int, eps: float):
    """``llama.rms_norm`` over each head's lanes of x [B, S, heads x HD]
    with the heads' one scale [HD], the rows left as the scan writes them:
    a head's mean square and its way back to the lanes are products with a
    0/1 matrix (``ops/ssd.py``). The same norm over x as [B, S, heads, HD]
    costs a relayout of the float32 rows there and back (1.85 + 2.43 ms a
    layer, forward and again backward, at the cell's shape: PERF.md 6, PR
    52)."""
    f = x.astype(jnp.float32)
    width = x.shape[-1] // heads
    r = jax.lax.rsqrt(_per_head(f * f, heads) * (1.0 / width) + eps)
    return (f * _over_lanes(r, width)).astype(x.dtype) \
        * jnp.tile(scale.astype(x.dtype), heads)


def _lightning_half(h, lp, cfg: SalaConfig, cos, sin, mesh):
    """The lightning layer's linear attention over its normed input h: the
    heads' outputs after their norm, [B, S, LH x HD]."""
    B, S, _ = h.shape
    LH, hd = cfg.lightning_heads, cfg.head_dim
    if cfg.ssd_impl == "pallas":
        _one_device(mesh, "ssd_impl='pallas'")
    pairs = _ll.attention_kind(cfg, "lightning").pairs
    q, k = (_ll.apply_rope(_ll._project(h, lp, cfg, w, LH, norm), cos, sin,
                           pairs)
            for w, norm in (("wq", "q_norm"), ("wk", "k_norm")))
    v = _ll._project(h, lp, cfg, "wv", LH)
    with jax.named_scope("scan"):
        o = ssd_scan(v, None, -slopes(LH), k,
                     (q * hd ** -0.5).astype(q.dtype),
                     chunk=min(LIGHTNING_CHUNK, S), impl=cfg.ssd_impl)
    return _head_norm(o.reshape(B, S, -1), lp["o_norm"], LH, cfg.norm_eps)


def attention_half(x, lp, cfg: SalaConfig, cos, sin, mesh=None, rules=None,
                   carried=None, kind=None):
    """The first half of a block by its ``kind``: x [B, S, D] -> (x + its
    attention's output under the sigmoid gate, ``carried`` as it came, what
    the half reports: a sparse layer's counters (``COUNTERS``:
    ``set_counts`` and the pairs its walk computed in units of 1,024),
    zeros where it attended densely and in a lightning layer)."""
    h = _ll._norm(x, lp["attn_norm"], cfg)
    with jax.named_scope(kind):
        if kind == "lightning":
            out, said = _lightning_half(h, lp, cfg, cos, sin, mesh), \
                _no_counts()
        else:
            out, said = _sparse_half(h, lp, cfg, mesh, rules)
        gate = jax.nn.sigmoid(h @ _ll._dq(lp["w_out_gate"], cfg.dtype))
        out = (out * gate) @ _ll._dq(lp["wo"], cfg.dtype)
    return _ll._residual(x, out, cfg), carried, said


def finish_loss(loss, stats, cfg: SalaConfig):
    """The loss as it is (the selection has no term of its own) and the
    step's counters beside it: the sparse layers' sums (int32; the walked
    pairs in units of 1,024) and how many of them selected."""
    aux = {name: jnp.sum(stats[name]) for name in COUNTERS}
    aux["sparse_layers_selecting"] = jnp.sum(
        (stats["sparse_blocks_selected"] > 0).astype(jnp.int32))
    return loss, aux


forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn

# what the family supplies to the shared layer: the feed-forward and what
# it offers the layer checkpoint are the dense model's
FAMILY = _ll.FAMILY.replace(
    "sala", remat_saved=REMAT_SAVED, remat_saved_bytes=remat_saved_bytes,
    layer_runs=layer_runs, attention_half=attention_half,
    finish_loss=finish_loss)
