"""Latent-attention llama variant with sparse experts and a multi-token-
prediction module, as DeepSeek-V2/V3 have it and GLM-4.7-Flash runs it
(transformers ``glm4_moe_lite``, which takes its block from ``deepseek_v3``;
arXiv:2405.04434 section 2.1 for the attention, arXiv:2412.19437 sections
2.1.2 and 2.2 for the router and the further prediction).

This module is only what differs from ``models/llama.py`` and
``models/moe.py``: the config, the parameter tree, the attention half
(``attention_half``), which feed-forward a layer of which kind has
(``feed_forward``), the further pass of the prediction module
(``further_losses``), the loss's terms (``finish_loss``) and the rule that
moves the router's bias after each step (``post_update``). The expert layer
is ``moe.feed_forward`` (``router_score`` "sigmoid", ``route_scale``,
``experts_held``, ``shared_d_ff``), the dense layer's SwiGLU
``llama.feed_forward``; embedding, the loop over the runs of layers, remat
and its policy, the head and the cross-entropy are ``llama._forward`` and
``llama.loss_fn``.

The attention half, for h = rms_norm(x) [B, S, D], H heads:

    c_q = rms_norm(h W_qa)                       [q_rank]
    q = c_q W_qb -> H x [q_nope | q_rope]        [qk_nope_dim | qk_rope_dim]
    [c_kv | k_r] = h W_kva                       [kv_rank | qk_rope_dim]
    [k_nope | v] = rms_norm(c_kv) W_kvb -> H x   [qk_nope_dim | v_dim]
    q = [q_nope | rope(q_rope)]; k = [k_nope | rope(k_r)]
    out = concat_h softmax(q k^T / sqrt(qk_nope_dim + qk_rope_dim), causal) v  W_o

``k_r`` is ONE vector a token, shared by all heads; the rotary turns
interleaved pairs (2i, 2i+1) of the ``qk_rope_dim`` lanes. This is the
EXPANDED form, the one training and prefill run: K goes to the kernel as
[B, H, S, qk_nope_dim + qk_rope_dim] with the shared rotary key written
into every head's last lanes (instant ``mla.plan``). The kernel takes q, k
and v of one width (GLM-4.7-Flash: ``v_dim`` 256 = 192 + 64). Value heads
NARROWER than the query/key heads (Ling 3.0: 128 beside 128 + 64) reach it
padded: ``wkv_b``'s v columns are followed by zero columns up to the
kernel's width, zero columns of v give zero columns of o, and the output's
first ``v_dim`` lanes go on to ``wo`` (``plan``'s ``v_zero_lanes``). Exact,
and a third of P V's width is wasted; a value width of the kernels' own is
what would save it. Without a query latent (``q_rank`` 0: the source's
``q_lora_rank`` null) q is ONE projection ``wq`` of the normed input and
there is no ``wq_a``, ``q_a_norm`` or ``wq_b``. With ``attn_gate`` every
head's output is scaled by one sigmoid gate of the normed input before
``wo`` (``w_attn_gate`` [D, H]: Ling's ``head_wise`` granularity).

The stored weights keep the published order of columns: ``wq_b`` a head's
[q_nope | q_rope] H times, the rotary lanes as interleaved pairs; ``wkv_a``
[c_kv | k_r], pairs likewise; ``wkv_b`` a head's [k_nope | v] H times.
What the projections WRITE is what the kernel reads, [B, H, S, .] with a
head's lanes minor, because their columns are gathered inside the traced
step (a few MB a layer; the weights' gradients come back through the
gather in the stored order) and no row is rolled, split, padded or joined
afterwards:

- the rotary is linear, rope(x) = x cos + (x P) sin with P the signed swap
  of a pair's lanes, and x = c W gives x P = c (W P): a second projection
  of ``wq_b``'s rotary columns, swapped and signed (``_swapped``; H x
  qk_rope_dim more columns), and of ``wkv_a``'s (qk_rope_dim more). q is
  its product times (1 | cos) plus the swapped product times (0 | sin),
  the product's own epilogue;
- k comes from ``wkv_b``'s k_nope columns with zero columns under a head's
  rotary lanes, and the turned key is added to every head in that
  product's epilogue; v from ``wkv_b``'s v columns, as its product wrote
  it; ``wo`` contracts heads and lanes of the kernel's output in place.

A config with an indexer (``index_heads`` > 0: DeepSeek Sparse Attention,
arXiv:2512.02556, as GLM-5.2 runs it) attends over a learned set. A FULL
layer (``index_full[l]``) scores every earlier key with ``index_heads``
small heads of ``index_dim`` lanes and keeps each query's ``index_topk``
best (sg = stop-gradient; the rotary on the FIRST ``qk_rope_dim`` lanes of
both, interleaved pairs, the attention's own tables):

    qI = sg(c_q) W_Iq -> index_heads x [index_dim]
    kI = layer_norm(sg(h) W_Ik)                  (scale and bias)
    w  = sg(h) W_Iw * index_heads^(-1/2) * index_dim^(-1/2)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])      s <= t, float32
    Sel_t = the min(index_topk, t + 1) keys of largest I[t, .], equal
            scores to the smaller s                     (exact: ``_select``)

and every layer's softmax runs over ``Sel_t`` alone
(``ops/sparse_attention.py``). A SHARED layer has no ``W_I*`` and attends
over the set of the nearest full layer before it: the set is the value the
attention half takes and returns (``carried``: 0/1 ``[B, S, S]`` int8,
causal already), ``llama._forward`` carries it from layer to layer and the
layer checkpoint keeps it (``index_set``), so a replay never selects
again. The indexer learns from the attention it steers and from nothing
else: with P[t, s] = sg(mean_h p[h, t, s]) on Sel_t,

    LI = mean_t sum_{s in Sel_t} P[t, s] (log P[t, s]
                                          - log softmax_{Sel_t}(I[t, .])[s])

joins the loss with ``index_loss_weight``; ``W_I*`` get gradient from LI
only and every other leaf from the rest only. LI's cotangent is a constant
of the step, so LI makes its gradient where it makes its value
(``_index_loss``, a ``jax.custom_vjp``): the forward rule computes a block
of queries' scores once, from them the block's terms and d LI / d I =
(softmax_Sel(I) sum_s P - P) / S on the set, goes straight back through
``index_scores`` to d qI, d w and d kI and names them (``INDEX_GRADS``);
the backward rule only scales them by the cotangent that arrives. The layer
checkpoint keeps those names beside the set, as it keeps the sparse call's
``o`` and ``lse``: the replay of a full layer finds them saved, so P and
LI's scores are computed once a full layer and step and never in a replay
(the indexer's projections are: their weights' gradients are made in the
backward from the kept three). An evaluation computes LI alone.

Layers are a list of runs (``layer_runs``): ``n_dense`` leading layers
whose feed-forward is a SwiGLU of ``dense_d_ff``, then sparse layers
(``d_ff`` the width of ONE expert). The prediction module
(``params["mtp"]``) is one more sparse block of its own weights over
[rms_norm(x_last) ; rms_norm(embed[t+1])] W_eh, with its own final norm and
the model's embedding and head, trained on the token after the next.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import llama as _ll
from ray_tpu.models import moe as _moe
from ray_tpu.util import tracing


@dataclass(frozen=True)
class LatentConfig(_moe.MoEConfig):
    """``d_ff`` is the width of ONE routed expert; ``n_layers`` counts the
    dense and the sparse layers and not the prediction module's block."""
    q_rank: int = 32
    kv_rank: int = 16
    qk_nope_dim: int = 24
    qk_rope_dim: int = 8
    v_dim: int = 32
    n_dense: int = 1                    # leading layers with a dense SwiGLU
    dense_d_ff: int = 128
    router_score: str = "sigmoid"
    norm_topk: bool = True
    n_mtp: int = 1                      # prediction modules: 0 or 1
    mtp_weight: float = 0.3             # of the second cross-entropy
    router_aux_weight: float = 0.0001   # of the sequence-wise balance loss
    router_z_weight: float = 0.0        # the sigmoid router has no z-loss
    # the learned selection (module docstring); 0 heads: none, every query
    # attends to every earlier key and the program is the one without
    index_heads: int = 0
    index_dim: int = 128
    index_topk: int = 2048
    # a layer's own: True scores and selects (it has ``W_I*``), False
    # attends over the set of the full layer before it. A tuple a layer and
    # no formula, because the source states a list (``indexer_types``)
    index_full: Tuple[bool, ...] = ()
    index_loss_weight: float = 1.0
    # a full layer reports its set itself too (``index_set`` [B, S, S]
    # int8 among its statistics) and every layer the fingerprint of the
    # set it attended over (``index_attended``, ``set_fingerprint``): for
    # a check of the sets and of their way through the layers, not for a
    # step
    index_report_sets: bool = False
    # one sigmoid gate a head on the attention's output, before ``wo``
    attn_gate: bool = False

    def __post_init__(self):
        if self.v_dim > self.qk_nope_dim + self.qk_rope_dim:
            raise NotImplementedError(
                f"value heads of {self.v_dim} WIDER than query/key heads of "
                f"{self.qk_nope_dim} + {self.qk_rope_dim}: the attention "
                "kernels take q, k and v of one width, and only v is padded "
                "up to it")
        if self.n_mtp not in (0, 1) or not 0 <= self.n_dense < self.n_layers:
            raise ValueError(f"n_mtp {self.n_mtp}, n_dense {self.n_dense} of "
                             f"{self.n_layers} layers")
        if self.index_heads:
            full = self.index_full
            if len(full) != self.n_layers:
                raise ValueError(f"index_full {full} for {self.n_layers} "
                                 "layers: one entry a layer")
            if self.n_mtp:
                raise NotImplementedError(
                    "a prediction module over a learned set: no key of the "
                    "source says whose set its block attends over")
            if self.index_dim < self.qk_rope_dim:
                raise ValueError(f"index_dim {self.index_dim} under the "
                                 f"rotary's {self.qk_rope_dim} lanes")
            if not self.q_rank:
                raise NotImplementedError(
                    "an indexer without a query latent: its queries are a "
                    "projection of c_q")

    @property
    def head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def rope_dim(self) -> int:
        return self.qk_rope_dim

    def replace(self, **kw) -> "LatentConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, LatentConfig] = {
    "tiny": LatentConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=32, max_seq_len=128, n_experts=8, top_k=2, shared_d_ff=32,
        route_scale=1.8),
    # the learned selection at toy sizes: a dense full layer, a period of
    # three shared and one full sparse layers, one shared layer more
    "tiny-glm52": LatentConfig(
        vocab_size=256, d_model=64, n_layers=6, n_heads=2, n_kv_heads=2,
        d_ff=32, max_seq_len=256, n_experts=8, top_k=2, shared_d_ff=32,
        experts_held=(2, 2), route_scale=1.8, n_mtp=0, index_heads=2,
        index_dim=16, index_topk=8,
        index_full=(True, False, False, False, True, False)),
    # GLM-5.2 (zai-org/GLM-5.2, glm_moe_dsa) at its published widths, one
    # of 32 chips' share of the source's layers 2-6: 32 of 64 heads, 8 of
    # 256 experts, an eighth of the vocabulary, no prediction module
    # (benchmark/configs/glm-5.2-ep32-l5.json has the cut and its reasons)
    "glm-5.2-ep32-l5": LatentConfig(
        vocab_size=19360, d_model=6144, n_layers=5, n_heads=32,
        n_kv_heads=32, d_ff=2048, dense_d_ff=12288, shared_d_ff=2048,
        max_seq_len=1048576, rope_theta=8e6, norm_eps=1e-5, q_rank=2048,
        kv_rank=512, qk_nope_dim=192, qk_rope_dim=64, v_dim=256,
        n_experts=256, top_k=8, experts_held=(8, 0), route_scale=2.5,
        n_dense=1, n_mtp=0, run_layers=1, index_heads=32, index_dim=128,
        index_topk=2048, index_full=(True, False, False, False, True),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16),
}

# beside the expert layer's routes, the set a full layer chose (the replay
# of a layer never scores and selects again) and LI's gradients to the index
# queries, head weights and keys, made where LI is (``_index_loss``: the
# replay computes neither the head-mean probabilities nor LI's scores)
INDEX_SET = "index_set"
INDEX_GRADS = ("index_grad_q", "index_grad_w", "index_grad_k")
REMAT_SAVED = _moe.REMAT_SAVED + (INDEX_SET,) + INDEX_GRADS
# rows of queries whose index scores are alive at once ([rows, keys]
# float32), and of those whose per-head products are ([heads, rows, keys])
SCORE_BLOCK_ROWS = 2048
SCORE_CHUNK_ROWS = 256


def _ffn(kind: str) -> str:
    """A layer kind's feed-forward: "dense" | "sparse" (the kind itself
    for a config without an indexer, "sparse.full" and the like with)."""
    return kind.split(".")[0]


def _selects(kind: str) -> bool:
    return kind.endswith(".full")


def index_grad_bytes(cfg: "LatentConfig", rows: int) -> int:
    """Bytes of a full layer's ``INDEX_GRADS`` over ``rows`` tokens: the
    index queries' and keys' in the activations' dtype, the head weights'
    in float32."""
    item = jnp.dtype(cfg.dtype).itemsize
    return rows * ((cfg.index_heads + 1) * cfg.index_dim * item
                   + cfg.index_heads * 4)


def remat_saved_bytes(cfg: "LatentConfig", kind, rows: int) -> int:
    own = rows * min(rows, cfg.max_seq_len) + index_grad_bytes(cfg, rows) \
        if _selects(kind) else 0
    return own + (0 if _ffn(kind) == "dense"
                  else _moe.remat_saved_bytes(cfg, kind, rows))


def remat_offers(cfg: "LatentConfig", kind, rows: int):
    return () if _ffn(kind) == "dense" else _moe.remat_offers(cfg, kind, rows)


def further_stacks(params, cfg: "LatentConfig"):
    """The prediction module's block, for what counts a step's layers
    (remat._stacks): a further pass over the same rows."""
    return [("sparse", cfg.n_mtp, params["mtp"]["block"])] if cfg.n_mtp \
        else []
RULE_LEAVES = _moe.RULE_LEAVES
_PROJECTIONS = ("wq", "wk", "wv", "wo")     # llama's, which MLA replaces


def layer_runs(cfg: LatentConfig) -> List[Tuple[str, int]]:
    """[(kind, how many adjacent layers of it), ...] in the layers' order.
    A kind is the layer's feed-forward, "dense" | "sparse", and with an
    indexer also whether the layer selects: "dense.full", "sparse.shared",
    "sparse.full" (only full layers have indexer leaves, so the stacks'
    trees differ); a run holds ``run_layers`` layers at most (0: all)."""
    ffn = ["dense"] * cfg.n_dense + ["sparse"] * (cfg.n_layers - cfg.n_dense)
    if not cfg.index_heads:
        return [(k, len(list(g))) for k, g in itertools.groupby(ffn)]
    if not cfg.index_full[0]:
        raise ValueError("the first layer has no set to attend over: "
                         f"index_full {cfg.index_full}")
    kinds = [f"{f}.{'full' if own else 'shared'}"
             for f, own in zip(ffn, cfg.index_full)]
    most = cfg.run_layers or cfg.n_layers
    return [(kind, min(most, n - at))
            for kind, n in ((k, len(list(g)))
                            for k, g in itertools.groupby(kinds))
            for at in range(0, n, most)]


def carried_init(cfg: LatentConfig, B: int, S: int):
    """What ``llama._forward`` hands the first layer's attention half and
    carries on from it: the set (nobody's yet), or None without an
    indexer, and then nothing is carried."""
    return jnp.zeros((B, S, S), jnp.int8) if cfg.index_heads else None


def hands_on(cfg: LatentConfig, kind) -> bool:
    """Whether a layer of ``kind`` replaces the carried set (a full
    layer); a shared layer only reads it."""
    return _selects(kind)


def _mla_specs(cfg: LatentConfig):
    L = ("layers",)
    q = {"wq_a": L + ("embed", None), "q_a_norm": L + (None,),
         "wq_b": L + (None, "heads")} if cfg.q_rank \
        else {"wq": L + ("embed", "heads")}
    gate = {"w_attn_gate": L + ("embed", None)} if cfg.attn_gate else {}
    return {**q, "wkv_a": L + ("embed", None),
            "kv_a_norm": L + (None,), "wkv_b": L + (None, "heads"),
            "wo": L + ("heads", "embed"), **gate}


def _mla_params(key, cfg: LatentConfig, n: int):
    pd, D, H = cfg.param_dtype, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)

    def dense(k, shape):
        return jax.random.normal(k, (n,) + shape, pd) * shape[0] ** -0.5

    q = {"wq_a": dense(ks[0], (D, cfg.q_rank)),
         "q_a_norm": jnp.ones((n, cfg.q_rank), pd),
         "wq_b": dense(ks[1], (cfg.q_rank, H * cfg.head_dim))} \
        if cfg.q_rank else {"wq": dense(ks[0], (D, H * cfg.head_dim))}
    gate = {"w_attn_gate": dense(ks[5], (D, H))} if cfg.attn_gate else {}
    return {**q, **gate,
            "wkv_a": dense(ks[2], (D, cfg.kv_rank + cfg.qk_rope_dim)),
            "kv_a_norm": jnp.ones((n, cfg.kv_rank), pd),
            "wkv_b": dense(ks[3], (cfg.kv_rank,
                                   H * (cfg.qk_nope_dim + cfg.v_dim))),
            "wo": dense(ks[4], (H * cfg.v_dim, D))}


def _index_specs():
    L = ("layers",)
    return {"wi_q": L + (None, None), "wi_k": L + ("embed", None),
            "wi_k_norm": L + (None,), "wi_k_bias": L + (None,),
            "wi_w": L + ("embed", None)}


def _index_params(key, cfg: LatentConfig, n: int):
    """A full layer's indexer: projections normal over the square root of
    their fan-in, the key norm's scale 1 and bias 0."""
    pd, D, ID = cfg.param_dtype, cfg.d_model, cfg.index_dim
    ks = jax.random.split(key, 3)

    def dense(k, shape):
        return jax.random.normal(k, (n,) + shape, pd) * shape[0] ** -0.5

    return {"wi_q": dense(ks[0], (cfg.q_rank, cfg.index_heads * ID)),
            "wi_k": dense(ks[1], (D, ID)),
            "wi_k_norm": jnp.ones((n, ID), pd),
            "wi_k_bias": jnp.zeros((n, ID), pd),
            "wi_w": dense(ks[2], (D, cfg.index_heads))}


def _stacks(cfg: LatentConfig):
    """(kind, the config that makes a stack of that many layers of the
    kind): the runs, then the prediction module's one sparse block."""
    out = [(kind, cfg.replace(n_layers=n, n_dense=0,
                              index_full=(_selects(kind),) * n))
           for kind, n in layer_runs(cfg)]
    if cfg.n_mtp:
        out.append(("sparse", cfg.replace(n_layers=1, n_dense=0)))
    return out


def _stack_specs(kind: str, run: LatentConfig):
    base = _ll if _ffn(kind) == "dense" else _moe
    lay = dict(base.param_specs(run)["layers"])
    for w in _PROJECTIONS:
        del lay[w]
    return {**lay, **_mla_specs(run), **(_index_specs() if _selects(kind)
                                         else {})}


def _stack_params(key, kind: str, run: LatentConfig):
    if _ffn(kind) == "dense":
        lay = _ll.init_params(key, run.replace(d_ff=run.dense_d_ff,
                                               vocab_size=1))["layers"]
    else:
        lay = _moe.init_params(key, run.replace(vocab_size=1))["layers"]
    lay = {k: v for k, v in lay.items() if k not in _PROJECTIONS}
    own = _index_params(jax.random.fold_in(key, 11), run, run.n_layers) \
        if _selects(kind) else {}
    return {**lay, **_mla_params(jax.random.fold_in(key, 7), run,
                                 run.n_layers), **own}


def param_specs(cfg: LatentConfig) -> Dict[str, Any]:
    stacks = [_stack_specs(kind, run) for kind, run in _stacks(cfg)]
    spec = {"embed": ("vocab", "embed"), "final_norm": ("embed_nr",),
            "lm_head": ("embed", "vocab"),
            "layers": stacks[:len(stacks) - cfg.n_mtp]}
    if cfg.n_mtp:
        spec["mtp"] = {"h_norm": ("embed_nr",), "e_norm": ("embed_nr",),
                       "eh_proj": (None, "embed"), "block": stacks[-1],
                       "final_norm": ("embed_nr",)}
    return spec


def init_params(key, cfg: LatentConfig) -> Dict[str, Any]:
    """Norms 1, projections normal over the square root of their fan-in,
    the embedding 0.02, every router bias 0 in float32."""
    pd, D = cfg.param_dtype, cfg.d_model
    stacks = [_stack_params(jax.random.fold_in(key, 100 + i), kind, run)
              for i, (kind, run) in enumerate(_stacks(cfg))]
    ks = jax.random.split(key, 3)
    params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, D), pd) * 0.02,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": jax.random.normal(ks[1], (D, cfg.vocab_size), pd)
        * D ** -0.5,
        "layers": stacks[:len(stacks) - cfg.n_mtp]}
    if cfg.n_mtp:
        params["mtp"] = {
            "h_norm": jnp.ones((D,), pd), "e_norm": jnp.ones((D,), pd),
            "eh_proj": jax.random.normal(ks[2], (2 * D, D), pd)
            * (2 * D) ** -0.5,
            "block": stacks[-1], "final_norm": jnp.ones((D,), pd)}
    return params


def num_params(cfg: LatentConfig) -> int:
    D, H = cfg.d_model, cfg.n_heads
    mla = ((D * cfg.q_rank + cfg.q_rank + cfg.q_rank * H * cfg.head_dim
            if cfg.q_rank else D * H * cfg.head_dim)
           + D * H * cfg.attn_gate
           + D * (cfg.kv_rank + cfg.qk_rope_dim) + cfg.kv_rank
           + cfg.kv_rank * H * (cfg.qk_nope_dim + cfg.v_dim)
           + H * cfg.v_dim * D)
    sparse = (mla + 2 * D + D * cfg.n_experts + cfg.n_experts
              + 3 * cfg.n_held * D * cfg.d_ff + 3 * D * cfg.shared_d_ff)
    dense = mla + 2 * D + 3 * D * cfg.dense_d_ff
    index = sum(cfg.index_full) * (
        cfg.q_rank * cfg.index_heads * cfg.index_dim + D * cfg.index_dim
        + 2 * cfg.index_dim + D * cfg.index_heads) if cfg.index_heads else 0
    return (2 * cfg.vocab_size * D + D + cfg.n_dense * dense
            + (cfg.n_layers - cfg.n_dense) * sparse
            + cfg.n_mtp * (sparse + 2 * D * D + 3 * D) + index)


def _swapped(w):
    """w P for the rotary's signed swap P of interleaved pairs: columns
    (2i, 2i+1) of the last axis become (-w[2i+1], w[2i]), so that with x =
    c w the rotary of x is x * cos + (c (w P)) * sin, pair by pair."""
    pairs = w.reshape(*w.shape[:-1], -1, 2)
    return jnp.stack([-pairs[..., 1], pairs[..., 0]],
                     axis=-1).reshape(w.shape)


def _rotary_tables(cos, sin, dn: int):
    """cos/sin [S, R/2] -> float32 [S, dn + R] each, (1 | cos) and (0 |
    sin) with an angle's value on both lanes of its pair: a head's lanes
    times the first plus its swapped lanes times the second is the head
    with its last R lanes turned and its first ``dn`` as they were."""
    c, s = (jnp.repeat(t.astype(jnp.float32), 2, axis=-1) for t in (cos, sin))
    return (jnp.pad(c, ((0, 0), (dn, 0)), constant_values=1.0),
            jnp.pad(s, ((0, 0), (dn, 0))))


def plan(cfg: LatentConfig, B: int, S: int) -> dict:
    """What a traced attention half says of itself (instant ``mla.plan``):
    the sizes, the form it runs in (``form``: K expanded, the shared rotary
    key written out for every head, ``k_bytes`` of it; ``rope``: by a
    second projection of swapped columns; ``kv``: K and V each from its
    own columns of ``wkv_b``), the columns the projections compute beyond
    the stored ones (``extra_columns`` swapped, ``zero_columns`` under K's
    rotary lanes) and the form's own account of its row passes, every
    operand read once and every result written once, the products' other
    operands left out: forward, q from its two products and k from its
    product and the turned key (v is its product's result); backward, the
    kernel's float32 dq, dk and dv each read once for the bf16 gradient
    rows of the products (dq's twice over the rotary lanes, dk's with its
    sum over heads)."""
    H, R, e = cfg.n_heads, cfg.qk_rope_dim, jnp.dtype(cfg.dtype).itemsize
    rows, heads = B * S, B * S * cfg.n_heads * cfg.head_dim
    # v's zero lanes up to the kernel's width, said only where there are any
    padded = {"v_zero_lanes": cfg.head_dim - cfg.v_dim} \
        if cfg.v_dim < cfg.head_dim else {}
    return {**padded,
            "S": S, "heads": H, "heads_held": H, "qk_nope": cfg.qk_nope_dim,
            "qk_rope": R,
            "v_dim": cfg.v_dim, "q_rank": cfg.q_rank, "kv_rank": cfg.kv_rank,
            "form": "expanded", "k_bytes": heads * e, "rope": "projected",
            "kv": "split_weights", "extra_columns": (H + 1) * R,
            "zero_columns": H * R,
            "hbm_bytes_fwd": e * (4 * heads + rows * R * (H + 4)),
            "hbm_bytes_bwd": 3 * 4 * heads + e * (3 * heads
                                                  + rows * R * (H + 1))}


def index_plan(cfg: LatentConfig, B: int, S: int, kind: str) -> dict:
    """What a traced attention half over a learned set says of itself
    (instant ``dsa.plan``): the sizes, whether the layer selects (``kind``
    full) or reads (shared), the form of the sparse attention (``mask``:
    a membership test a pair, nothing gathered), the pairs the sets hold
    beside the causal ones, the rows of queries whose scores are alive at
    once, the set's bytes, and where LI's gradient is made
    (``index_grad`` "forward": with its value, in every full layer of
    every step) and what the layer checkpoint keeps for it
    (``index_grad_kept_bytes``: ``INDEX_GRADS``; 0 in a shared layer)."""
    k = min(cfg.index_topk, S)
    full = _selects(kind)
    return {"S": S, "topk": cfg.index_topk, "index_heads": cfg.index_heads,
            "index_dim": cfg.index_dim,
            "kind": "full" if full else "shared", "form": "mask",
            "selected_pairs": B * (k * (k + 1) // 2 + (S - k) * k),
            "causal_pairs": B * S * (S + 1) // 2,
            "score_block_rows": min(S, SCORE_BLOCK_ROWS),
            "set_bytes": B * S * S, "gathered_bytes_fwd": 0,
            "index_grad": "forward",
            "index_grad_kept_bytes": index_grad_bytes(cfg, B * S) if full
            else 0}


def _index_rotary(x, cos, sin):
    """The rotary on the FIRST lanes of x [..., S, (heads,) index_dim]:
    interleaved pairs of as many lanes as the tables turn (cos, sin
    [S, R / 2]), the lanes after them as they were."""
    R = 2 * cos.shape[-1]
    c, s = (jnp.repeat(t.astype(jnp.float32), 2, axis=-1) for t in (cos, sin))
    pad = ((0, 0), (0, x.shape[-1] - R))
    c, s = jnp.pad(c, pad, constant_values=1.0), jnp.pad(s, pad)
    if x.ndim == 4:                                    # [B, S, heads, lanes]
        c, s = c[:, None], s[:, None]
    turned = jnp.pad(_swapped(x[..., :R]), [(0, 0)] * (x.ndim - 1) + [pad[1]])
    return (x.astype(jnp.float32) * c
            + turned.astype(jnp.float32) * s).astype(x.dtype)


def _indexer(h, c_q, lp, cfg: LatentConfig, cos, sin):
    """A full layer's index queries, keys and head weights from the normed
    input h [B, S, D] and the query latent c_q [B, S, q_rank], both
    detached: (qI [B, S, heads, index_dim], kI [B, S, index_dim], w
    [B, S, heads] float32)."""
    B, S, _ = h.shape
    IH, ID, dt = cfg.index_heads, cfg.index_dim, cfg.dtype
    h, c_q = jax.lax.stop_gradient(h), jax.lax.stop_gradient(c_q)
    w = lambda name: _ll._dq(lp[name], dt)                     # noqa: E731
    qI = (c_q @ w("wi_q")).reshape(B, S, IH, ID)
    kI = _ll.layer_norm(h @ w("wi_k"), lp["wi_k_norm"], cfg.norm_eps) \
        + lp["wi_k_bias"].astype(dt)
    weight = (h @ w("wi_w")).astype(jnp.float32) * (IH ** -0.5 * ID ** -0.5)
    return _index_rotary(qI, cos, sin), _index_rotary(kI, cos, sin), weight


def index_scores(qI, weight, kI):
    """I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) for the queries qI
    [R, heads, lanes] (weights [R, heads] float32) over the keys kI [T,
    lanes] -> [R, T] float32, ``SCORE_CHUNK_ROWS`` queries' per-head
    products alive at once, forward and (recomputed) backward."""
    R, IH, ID = qI.shape
    c = math.gcd(R, SCORE_CHUNK_ROWS)

    @jax.checkpoint
    def chunk(qw):
        q, w = qw
        products = jnp.einsum("cjd,td->cjt", q, kI,
                              preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(products) * w[:, :, None], axis=1)

    out = jax.lax.map(chunk, (qI.reshape(R // c, c, IH, ID),
                              weight.reshape(R // c, c, IH)))
    return out.reshape(R, kI.shape[0])


def _sortable(x):
    """float32 -> uint32 that orders as the floats do (-0.0 as 0.0)."""
    u = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def select(scores, first_row: int, topk: int):
    """The exact selection: scores [R, T] float32 of the queries at
    positions ``first_row + r`` over the keys 0..T-1 -> keep [R, T] bool,
    for each query the min(topk, t + 1) causal keys of largest score,
    equal scores to the smaller s. No sort: the topk-th largest score of a
    row is found bit by bit (32 counting passes over the row's keys as
    ordered integers), the keys above it are kept and, of those equal to
    it, the first that fill the set (log2 T counting passes more)."""
    R, T = scores.shape
    t = first_row + jnp.arange(R)[:, None]
    causal = jnp.arange(T)[None, :] <= t
    key = jnp.where(causal, _sortable(scores), jnp.uint32(0))

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], axis=1) >= topk
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((R,), jnp.uint32))[:, None]
    above = key > kth
    room = topk - jnp.sum(above, axis=1)
    equal = causal & (key == kth)
    at = jnp.arange(T, dtype=jnp.int32)[None, :]
    bits = max(T - 1, 1).bit_length()

    # of the keys equal to the topk-th score the first ``room``: the
    # position of the room-th of them, bit by bit too (a cumulative sum
    # over the row is a dozen passes of the compiler's own, which a trace
    # bills to nobody)
    def place(i, last):
        cand = last | (jnp.int32(1) << (bits - 1 - i))
        before = jnp.sum(equal & (at < cand[:, None]), axis=1)
        return jnp.where(before < room, cand, last)

    last = jax.lax.fori_loop(0, bits, place, jnp.zeros((R,), jnp.int32))
    return causal & (above | (equal & (at <= last[:, None])))


def _score_blocks(S: int):
    """[(first row, rows, keys), ...]: the queries in blocks of
    ``SCORE_BLOCK_ROWS`` and the keys each block's rows may see."""
    rows = min(S, SCORE_BLOCK_ROWS)
    while S % rows:
        rows //= 2
    return [(at, rows, at + rows) for at in range(0, S, rows)]


def _select_set(qI, weight, kI, topk: int):
    """One sequence's set [S, S] int8 from its index queries, weights and
    keys, a block of queries at a time."""
    S = qI.shape[0]
    out = []
    for at, rows, keys in _score_blocks(S):
        with jax.named_scope("indexer"):
            scores = index_scores(qI[at:at + rows], weight[at:at + rows],
                                  kI[:keys])
        with jax.named_scope("select"):
            keep = select(scores, at, topk).astype(jnp.int8)
            out.append(jnp.pad(keep, ((0, 0), (0, S - keys))))
    with jax.named_scope("select"):
        return jnp.concatenate(out, axis=0)


def _block_terms(scores, p, on):
    """A block of queries' share of LI's sum from its index scores [rows,
    keys] float32, the attention's probabilities p and its set ``on``
    (int8): (the sum of P (log P - log softmax_Sel(I)) over the block's
    pairs, d of it / d scores = softmax_Sel(I) x sum_s P - P on the set
    and 0 off it)."""
    on = on != 0
    scores = jnp.where(on, scores, -1e30)
    top = jnp.max(scores, axis=1, keepdims=True)
    norm = top + jnp.log(jnp.sum(
        jnp.where(on, jnp.exp(scores - top), 0.0), axis=1, keepdims=True))
    p = jnp.where(on, p, 0.0)
    total = jnp.sum(jnp.where(
        p > 0, p * (jnp.log(jnp.maximum(p, 1e-37)) - (scores - norm)), 0.0))
    p = jnp.where(p > 0, p, 0.0)
    soft = jnp.where(on, jnp.exp(scores - norm), 0.0)
    return total, soft * jnp.sum(p, axis=1, keepdims=True) - p


def _index_blocks(qI, weight, kI, probs, keep):
    """(keys, the block's queries, weights, keys, probabilities and set)
    for each of ``_score_blocks``."""
    for at, rows, keys in _score_blocks(qI.shape[0]):
        yield keys, (qI[at:at + rows], weight[at:at + rows], kI[:keys],
                     probs[at:at + rows, :keys], keep[at:at + rows, :keys])


@jax.custom_vjp
def _index_loss(qI, weight, kI, probs, keep):
    """One sequence's LI (module docstring): the indexer's scores against
    the attention's head-mean probabilities probs [S, S] float32 on the
    set keep [S, S] int8, a block of queries at a time. Differentiated, it
    makes its gradient where it makes its value (``_index_loss_fwd``);
    this is the value alone, an evaluation's."""
    # the scope INSIDE the function, so that a trace reads
    # ``attention/index_loss`` as adjacent parts: the term's own pass over
    # the scores (and its gradient's) is the term's cost, not the
    # selection's
    with jax.named_scope("index_loss"):
        total = sum(_block_terms(index_scores(q, w, k), p, on)[0]
                    for _, (q, w, k, p, on) in _index_blocks(
                        qI, weight, kI, probs, keep))
    return total / qI.shape[0]


def _index_loss_fwd(qI, weight, kI, probs, keep):
    """LI and, for a cotangent of one, its gradients to qI, weight and kI
    (``INDEX_GRADS``, the names the layer checkpoint keeps): LI's
    cotangent is a constant of the step, so nothing waits for the
    backward, and the replay of the layer finds nothing of LI to do. A
    block's scores are computed once; from them its terms and d LI / d
    scores; that goes straight back through ``index_scores`` (whose
    chunks recompute their per-head products: a row's normaliser needs
    all its keys before any gradient exists, so two passes over a row's
    keys are inherent)."""
    S = qI.shape[0]
    with jax.named_scope("index_loss"):
        total, dq, dw, dk = 0.0, [], [], jnp.zeros_like(kI)
        for keys, (q, w, k, p, on) in _index_blocks(qI, weight, kI, probs,
                                                    keep):
            # one block at a time, and none before P is there: what a
            # block reads waits for the block before it. Left alone, the
            # scheduler computes every block's scores before the sparse
            # call and holds them across it (the cell's step planned
            # 14.09e9 bytes for 13.25e9: PERF.md 6, PR 47)
            q, w, k, p, on, dk = jax.lax.optimization_barrier(
                (q, w, k, p, on, dk))
            scores, back = jax.vjp(index_scores, q, w, k)
            terms, g = _block_terms(scores, p, on)
            total = total + terms
            a, b, c = back(g / S)
            dq.append(a)
            dw.append(b)
            dk = dk.at[:keys].add(c)
        grads = tuple(checkpoint_name(x, name) for x, name in zip(
            (jnp.concatenate(dq), jnp.concatenate(dw), dk), INDEX_GRADS))
    return total / S, grads


def _index_loss_bwd(grads, g):
    with jax.named_scope("index_loss"):
        return (*((g * x.astype(jnp.float32)).astype(x.dtype)
                  for x in grads), None, None)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def attention_half(x, lp, cfg: LatentConfig, cos, sin, mesh=None, rules=None,
                   carried=None, kind=None):
    """The latent-attention half of a block: x [B, S, D] -> (x + its
    attention's output, ``carried``, what the half reports of itself), the
    module docstring has the equations and how the stored columns are
    arranged for the kernel. ``carried`` is the set the layer attends over
    (None for a config without an indexer: causal attention over every
    earlier key, nothing reported). A full layer (``kind``) scores, selects
    and hands its own set on, and reports its ``index_loss`` (LI), the
    share of the causal pairs it selected and the share of its set that
    the set it was handed holds (first sequence); a shared layer attends
    over the set it was handed and hands it on."""
    B, S, _ = x.shape
    H, dn, dv, dt = cfg.n_heads, cfg.qk_nope_dim, cfg.v_dim, cfg.dtype
    R, rk, f32 = cfg.qk_rope_dim, cfg.kv_rank, jnp.float32
    tracing.plan("mla.plan", plan(cfg, B, S))
    w = lambda name: _ll._dq(lp[name], dt)                     # noqa: E731
    # without a query latent q is one projection of the normed input
    wq_b = (w("wq_b") if cfg.q_rank else w("wq")).reshape(-1, H, dn + R)
    wq_s = _swapped(wq_b[..., dn:])                            # [q_rank, H, R]
    wkv_a = w("wkv_a")
    wkv_a = jnp.concatenate([wkv_a, _swapped(wkv_a[:, rk:])], axis=-1)
    wkv_b = w("wkv_b").reshape(rk, H, dn + dv)
    wk = jnp.pad(wkv_b[..., :dn], ((0, 0), (0, 0), (0, R)))
    one, turn = _rotary_tables(cos, sin, dn)                   # [S, dn + R]
    h = _ll.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    c_q = _ll.rms_norm(h @ w("wq_a"), lp["q_a_norm"], cfg.norm_eps) \
        if cfg.q_rank else h
    q = jnp.einsum("bsr,rhd->bhsd", c_q, wq_b)
    q_s = jnp.pad(jnp.einsum("bsr,rhd->bhsd", c_q, wq_s),
                  ((0, 0), (0, 0), (0, 0), (dn, 0)))
    q = (q.astype(f32) * one + q_s.astype(f32) * turn).astype(dt)
    c_kv = h @ wkv_a                                  # [c_kv | k_r | k_r P]
    # the ONE rotary key a token, by the tables' rotary lanes (cos | sin)
    k_r = (c_kv[..., rk:rk + R].astype(f32) * one[:, dn:]
           + c_kv[..., rk + R:].astype(f32) * turn[:, dn:]).astype(dt)
    c_kv = _ll.rms_norm(c_kv[..., :rk], lp["kv_a_norm"], cfg.norm_eps)
    k = jnp.einsum("bsr,rhd->bhsd", c_kv, wk) \
        + jnp.pad(k_r, ((0, 0), (0, 0), (dn, 0)))[:, None]
    wv = wkv_b[..., dn:]
    if dv < dn + R:     # zero columns up to the kernel's one width
        wv = jnp.pad(wv, ((0, 0), (0, 0), (0, dn + R - dv)))
    v = jnp.einsum("bsr,rhd->bhsd", c_kv, wv)
    # the kernel's own transposes, so XLA writes no copy for them
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    said = None
    if carried is None:
        out = _ll._attention(q, k, v, cfg, causal=True, mesh=mesh,
                             rules=rules)
    else:
        out, carried, said = _attend_set(q, k, v, h, c_q, lp, cfg, cos, sin,
                                         carried, kind)
    if dv < dn + R:
        out = out[..., :dv]
    if cfg.attn_gate:
        out = out * jax.nn.sigmoid(h @ w("w_attn_gate"))[..., None]
    return _ll._residual(
        x, jnp.einsum("bshd,hde->bse", out, w("wo").reshape(H, dv, -1)),
        cfg), carried, said


def set_fingerprint(keep):
    """A set's fingerprint, uint32 a sequence: the sum over its pairs (t,
    s) of ``(40503 t + 9973 s) mod 2^16``, mod 2^32. keep [B, S, S]."""
    at = jnp.arange(keep.shape[-1], dtype=jnp.uint32)
    weight = (at[:, None] * 40503 + at[None, :] * 9973) & 0xFFFF
    return jnp.sum(keep.astype(jnp.uint32) * weight, axis=(1, 2))


def _attend_set(q, k, v, h, c_q, lp, cfg: LatentConfig, cos, sin, carried,
                kind):
    """Attention over the learned set (q, k, v [B, S, H, .] as the dense
    call takes them): a full layer's scores, selection and LI round the
    sparse call, a shared layer's sparse call over ``carried``."""
    from ray_tpu.ops.sparse_attention import sparse_attention

    B, S = q.shape[:2]
    tracing.plan("dsa.plan", index_plan(cfg, B, S, kind))
    scale = cfg.attn_scale                      # None: head_dim ** -0.5
    if not _selects(kind):
        with jax.named_scope("sparse"):
            out = sparse_attention(q, k, v, carried, scale=scale)
        return out, carried, {"index_attended": set_fingerprint(carried)[0]} \
            if cfg.index_report_sets else None
    with jax.named_scope("indexer"):
        qI, kI, weight = _indexer(h, c_q, lp, cfg, cos, sin)
    own = jax.lax.stop_gradient(jax.vmap(
        lambda a, b, c: _select_set(a, b, c, cfg.index_topk))(qI, weight, kI))
    own = checkpoint_name(own, INDEX_SET)
    with jax.named_scope("sparse"):
        out, probs = sparse_attention(q, k, v, own, scale=scale,
                                      with_probs=True)
    loss = jnp.mean(jax.vmap(_index_loss)(qI, weight, kI, probs, own))
    with jax.named_scope("select"):
        pairs = jnp.sum(own[0].astype(jnp.int32))
        said = {"index_loss": loss,
                "index_selected": pairs / (S * (S + 1) / 2),
                "index_overlap": jnp.sum(
                    (own[0] & carried[0]).astype(jnp.int32)) / pairs}
        if cfg.index_report_sets:
            said.update(index_set=own, index_attended=set_fingerprint(own)[0])
    return out, own, said


def feed_forward(h, lp, cfg: LatentConfig, mesh=None, rules=None, tp=None,
                 kind=None):
    """A dense layer's SwiGLU or a sparse layer's experts, by ``kind``."""
    half = _ll if _ffn(kind) == "dense" else _moe
    return half.feed_forward(h, lp, cfg, mesh=mesh, rules=rules, tp=tp)


def _ahead(params, tokens, hidden, stats, cfg: LatentConfig, run):
    """The prediction module's pass over tokens [B, S + 2]: hidden [B, S,
    D] the main stack's output before its final norm, ``run`` the scan of
    a stack of layers as the main forward ran it (llama._forward). Returns
    (its logits [B, S, V] for the token after the next, through the
    model's own embedding and head; the layers' statistics with the
    module's block joined on, one expert layer more)."""
    m, S = params["mtp"], tokens.shape[1] - 2
    # the module's parts under ``mtp``, in the main model's scopes: its
    # embedding, its block (``run`` opens ``layers``), ``eh_proj`` and its
    # head with the heads and losses
    with jax.named_scope("mtp"):
        with jax.named_scope("embed"):
            ahead = _ll._embed(params, tokens[:, 1:S + 1], cfg.dtype)
        with jax.named_scope("head_loss"):
            g = jnp.concatenate(
                [_ll.rms_norm(hidden, m["h_norm"], cfg.norm_eps),
                 _ll.rms_norm(ahead, m["e_norm"], cfg.norm_eps)], axis=-1) \
                @ _ll._dq(m["eh_proj"], cfg.dtype)
        x, more = run("sparse", g, m["block"])
        with jax.named_scope("head_loss"):
            logits = _ll._logits(
                params, _ll.rms_norm(x, m["final_norm"], cfg.norm_eps), cfg)
        with jax.named_scope("layers"):
            stats = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), stats,
                                 more)
    return logits, stats


def further_losses(params, tokens, hidden, stats, cfg: LatentConfig, run):
    """What ``llama.loss_fn`` asks of a family that predicts further than
    the next token: the statistics with the prediction module's block
    joined on and its cross-entropy against the token after the next
    (``mtp_loss``), the same logsumexp form over the same kind of logits
    as the main loss."""
    if not cfg.n_mtp:
        return stats
    logits, stats = _ahead(params, tokens, hidden, stats, cfg, run)
    S = tokens.shape[1] - 2
    with jax.named_scope("mtp"), jax.named_scope("head_loss"):
        return {**stats, "mtp_loss": _ll.cross_entropy(logits,
                                                       tokens[:, 2:S + 2])}


def token_losses(params, tokens, cfg: LatentConfig, mesh=None, rules=None):
    """Every position's two losses, for evaluation: tokens [B, S + 2] ->
    (the main model's against the next token, the prediction module's
    against the one after, both float32 [B, S], the expert layers'
    statistics stacked, the module's block last)."""
    S = tokens.shape[1] - 2
    logits, stats, hidden, run, _ = _ll._forward(params, tokens[:, :S], cfg,
                                                 mesh=mesh, rules=rules)
    main = _ll.token_losses(logits, tokens[:, 1:S + 1])
    logits, stats = _ahead(params, tokens, hidden, stats, cfg, run)
    return main, _ll.token_losses(logits, tokens[:, 2:S + 2]), stats


def finish_loss(loss, stats, cfg: LatentConfig):
    """loss = L_main + mtp_weight L_mtp + router_aux_weight L_balance (+
    index_loss_weight x the sum of the full layers' LI), from
    the expert layers' stacked statistics (the module's block among them)
    -> (loss, aux). ``aux`` carries the step's counts over all experts
    (``router_counts`` [layers, E]) for ``post_update``."""
    balance = stats["balance"].mean()
    mtp = stats.get("mtp_loss", jnp.zeros((), jnp.float32))
    aux = {"moe_main_loss": loss, "moe_mtp_loss": mtp,
           "moe_aux_loss": balance, "router_counts": stats["counts"]}
    if cfg.experts_held is not None:
        aux.update(_moe.held_aux(
            stats["held_counts"].astype(jnp.float32), stats,
            stats["experts"].shape[1] * cfg.top_k, cfg.n_experts))
    else:
        aux["moe_dropped"] = jnp.zeros((), jnp.int32)
    loss = loss + cfg.mtp_weight * mtp + cfg.router_aux_weight * balance
    if "index_loss" in stats:
        # the full layers' LI, in the layers' order; the last full layer's
        # overlap with the set it was handed (the first was handed nobody's)
        each = stats["index_loss"]
        aux.update({f"index_loss_{i}": each[i] for i in range(each.shape[0])})
        aux.update(index_loss=each.sum(),
                   index_selected_share=stats["index_selected"].mean(),
                   index_overlap=stats["index_overlap"][-1])
        loss = loss + cfg.index_loss_weight * each.sum()
    return loss, aux


# the router's bias rule, where the hybrid family finds it too
post_update = _moe.post_update


forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn

# what the latent-attention models supply to the shared layer: the names
# offered (a dense layer offers none of them) and the rows in expert order
# are the expert model's
FAMILY = _moe.FAMILY.replace(
    "latent", feed_forward=feed_forward, remat_saved=REMAT_SAVED,
    remat_saved_bytes=remat_saved_bytes, remat_offers=remat_offers,
    layer_runs=layer_runs, attention_half=attention_half,
    rotary_tables=_ll._kind_pair_tables,
    further_losses=further_losses, finish_loss=finish_loss,
    carried_init=carried_init, hands_on=hands_on,
    further_stacks=further_stacks)
