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
(``_down_combine``; the numbers: PERF.md section 6, PR 26). Where the step's
memory has room (``remat.remat_plan``) the shared SwiGLU's two products of x
are kept as well, run of layers by run (SHARED_OFFERED, ``remat_offers``:
what a layer offers and what each name weighs), and the replay of a run
that keeps them runs neither a second time.

On a mesh that shards ``experts`` (``ShardingRules.ep()``) the "xla" path
runs under GSPMD; the "pallas" path refuses any mesh of several devices.

Attention layers of several kinds (``layer_kinds`` names each layer's,
``attn_kinds`` what a kind has of its own: Mellum2's window layers with
plain rotary tables beside full layers under YaRN): ``params["layers"]`` is
then a LIST of stacks, one a run of adjacent layers of one kind
(``layer_runs``), each run one ``lax.scan``, every run of a kind that keeps
the same names scanned by the same traced body; every layer is the same
block but for its kind's
window and tables (``llama._attention_half``).

A chip's share of the experts (``experts_held``: how many, and the first):
the router, ``route`` and the statistics stay ``n_experts`` wide; the
weights' first axis, the group sizes and the rows gathered are the held
experts' only (``_held_experts``, ``_held_combine``: a second way through
the layer, because its rows are a data-dependent FEW of the T*K: the
assignments sorted by held expert with the absent last, computed in a
first pass of 3/2 of the even share (HELD_PASS: experts placed by load)
and, in a layer that got more, in further short passes; ``_dispatch`` and
``_down_combine`` gather all T*K rows and serve the layer that holds every
expert). An assignment to an absent
expert contributes nothing and is no dropped row: its part of the result
is another chip's; every assignment to a held expert is computed.
``shared_d_ff`` adds a dense SwiGLU that every token passes beside the
routed experts, whole on every chip. With every expert held and no shared
width the program is the one described above.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import llama as _ll
from ray_tpu.models.family import Family
from ray_tpu.ops.grouped_matmul import GMM_TILING, grouped_matmul, tiles
from ray_tpu.util import tracing


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
    # (count, first): the experts whose weights live here, of the
    # n_experts the router scores; None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # width of a SwiGLU every token passes beside its experts; 0: none
    shared_d_ff: int = 0
    # how many such shared experts, each ``shared_d_ff`` wide, and what is
    # made of their outputs: their "sum" or their "average" (Command A+:
    # four, averaged). Their weights lie side by side in ``ws_gate``,
    # ``ws_up`` [D, n x width] and ``ws_down`` [n x width, D]: n SwiGLUs
    # summed ARE one of n times the width
    n_shared: int = 1
    shared_combine: str = "sum"
    # what the router makes of its logits: "softmax" over all experts, the
    # K largest kept; or "sigmoid" (DeepSeek-V3's): the K experts with the
    # largest score PLUS a per-expert bias that no gradient reaches (the
    # leaf ``router_bias``, moved by a rule after each step:
    # models/latent.py ``post_update``), weighted by their scores WITHOUT it
    router_score: str = "softmax"
    # False: a sigmoid router with no bias leaf (Command A+'s: the K
    # largest scores as they are)
    router_bias: bool = True
    # multiplies the K weights (DeepSeek's ``routed_scaling_factor``)
    route_scale: float = 1.0
    # group-limited selection (DeepSeek-V3's ``noaux_tc``, Ling 3.0's): the
    # experts lie in ``n_group`` groups of adjacent experts, a token keeps
    # the ``topk_group`` groups whose two largest choice scores sum highest
    # and its K experts come from those alone (``kept_groups``); 1 and 1
    # limit nothing and the program is the one without
    n_group: int = 1
    topk_group: int = 1
    # every token's kept groups [T, n_group] among an expert layer's
    # statistics ("groups"): for a comparison that tells a near tie of
    # groups from one of experts, not for a step
    report_groups: bool = False
    # what one step moves a biased router's bias by (``post_update``)
    bias_rate: float = 0.001
    # what an expert, routed or shared, is: "swiglu", silu(x W_gate) x
    # (x W_up) then W_down, three matrices; or "relu2" (Nemotron-H's),
    # relu(x W_up)^2 then W_down, two: the tree has no ``we_gate`` and no
    # ``ws_gate``
    expert_act: str = "swiglu"
    # one name a layer where the attention layers are of several kinds
    # (``attn_kinds``: Mellum2's three window layers to one full layer);
    # () = every layer of the config's one kind, one stack
    layer_kinds: Tuple[str, ...] = ()
    # the head is the embedding (no ``lm_head`` leaf)
    tied_head: bool = False
    # the most layers one stack of ``layer_kinds``' runs holds; 0: a whole
    # run of adjacent layers of one kind. The optimizer's float32
    # temporaries are as large as the largest stacked leaf: at Command A+'s
    # widths three layers' experts in one stack are 1.6 GB a temporary
    run_layers: int = 0

    @property
    def shared_width(self) -> int:
        """All shared experts' widths together: ``ws_gate``'s columns."""
        return self.n_shared * self.shared_d_ff

    @property
    def n_held(self) -> int:
        return self.experts_held[0] if self.experts_held else self.n_experts

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
    # JetBrains/Mellum2-12B-A2.5B-Instruct config.json: 28 layers, three
    # sliding-window layers of 1,024 (plain rotary tables) to one full
    # layer (YaRN x16 over 8,192), 32 query heads over 4 KV heads of a
    # STATED 128 (hidden 2304), 64 experts of 896, 8 a token renormalised
    "mellum2-12b-a2.5b": MoEConfig(
        vocab_size=98304, d_model=2304, n_layers=28, n_heads=32,
        n_kv_heads=4, head_width=128, d_ff=896, max_seq_len=131072,
        n_experts=64, top_k=8, norm_topk=True, norm_eps=1e-6,
        rope_theta=500000.0, router_z_weight=0.0,
        layer_kinds=("window", "window", "window", "full") * 7,
        attn_kinds=(
            ("window", _ll.AttentionKind(window=1024)),
            ("full", _ll.AttentionKind(yarn=_ll.Yarn(
                factor=16.0, original=8192, beta_fast=32.0, beta_slow=1.0,
                attention_factor=1.2772588722239782))))),
    # the same block at the CPU tests' size: two periods, a window shorter
    # than the tests' sequences, a head width no quotient of the hidden size
    "tiny-mellum": MoEConfig(
        vocab_size=256, d_model=48, n_layers=8, n_heads=8, n_kv_heads=1,
        head_width=16, d_ff=32, max_seq_len=256, n_experts=8, top_k=2,
        norm_topk=True, norm_eps=1e-6, rope_theta=10000.0,
        router_z_weight=0.0,
        layer_kinds=("window", "window", "window", "full") * 2,
        attn_kinds=(
            ("window", _ll.AttentionKind(window=24)),
            ("full", _ll.AttentionKind(yarn=_ll.Yarn(
                factor=4.0, original=32, beta_fast=4.0, beta_slow=0.5))))),
    # CohereLabs/command-a-plus-05-2026 config.json (``cohere2_moe``): 32
    # parallel blocks (one LayerNorm, attention and experts side by side),
    # three sliding-window layers of 4,096 with GPT-J rotary to one full
    # layer with no position embedding, 128 query heads over 8 KV heads of
    # 128, 128 experts of 4,096, 8 a token by sigmoid score renormalised,
    # four shared experts averaged, tied embedding, no router loss
    "command-a-plus": MoEConfig(
        vocab_size=262144, d_model=4096, n_layers=32, n_heads=128,
        n_kv_heads=8, head_width=128, d_ff=4096, max_seq_len=200000,
        n_experts=128, top_k=8, norm_topk=True, norm_eps=1e-5,
        rope_theta=50000.0, router_score="sigmoid", router_bias=False,
        router_aux_weight=0.0, router_z_weight=0.0, shared_d_ff=4096,
        n_shared=4, shared_combine="average", norm="layer",
        parallel_block=True, tied_head=True,
        layer_kinds=("window", "window", "window", "full") * 8,
        attn_kinds=(
            ("window", _ll.AttentionKind(window=4096, pairs="neighbours")),
            ("full", _ll.AttentionKind(rope=False)))),
    # the same block at the CPU tests' size: two periods, a window shorter
    # than the tests' sequences, 4 query heads a KV head, 2 of 8 experts
    # held here, two shared experts
    "tiny-commanda": MoEConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=8, n_kv_heads=2,
        head_width=16, d_ff=32, max_seq_len=256, n_experts=8, top_k=2,
        norm_topk=True, norm_eps=1e-5, rope_theta=10000.0,
        router_score="sigmoid", router_bias=False, router_aux_weight=0.0,
        router_z_weight=0.0, shared_d_ff=32, n_shared=2,
        shared_combine="average", norm="layer", parallel_block=True,
        tied_head=True, experts_held=(2, 0),
        layer_kinds=("window", "window", "window", "full") * 2,
        attn_kinds=(
            ("window", _ll.AttentionKind(window=24, pairs="neighbours")),
            ("full", _ll.AttentionKind(rope=False)))),
}

# what the layer checkpoint keeps of an expert layer (remat._checkpoint)
REMAT_SAVED = ("moe_route",)
# what it keeps besides where the step's memory has room (remat.remat_plan):
# the shared SwiGLU's two products of x, before the activation. silu(gate)
# x up is not offered: it is elementwise work from the two
SHARED_OFFERED = ("shared_gate", "shared_up")
# leaves that no gradient reaches and ``post_update`` moves: the optimizer
# is told to leave them alone (parallel.train_step.hold_out)
RULE_LEAVES = ("router_bias",)


def _matrices(cfg: "MoEConfig") -> Tuple[str, ...]:
    """An expert's products of x before its activation, by the names of
    their leaves' endings and checkpoint tags: gate and up, or up alone."""
    if cfg.expert_act == "swiglu":
        return ("gate", "up")
    if cfg.expert_act == "relu2":
        return ("up",)
    raise ValueError(f"unknown expert_act {cfg.expert_act!r}")


def _activation(cfg: "MoEConfig", product):
    """The expert's activation of its products of x: ``product`` gives x's
    product with the matrix of a name of ``_matrices``, when asked."""
    if cfg.expert_act == "swiglu":
        return jax.nn.silu(product("gate")) * product("up")
    (up,) = _matrices(cfg)
    return jnp.square(jax.nn.relu(product(up)))


def remat_saved_bytes(cfg: "MoEConfig", kind, tokens: int) -> int:
    """Bytes of a layer's REMAT_SAVED: the routes of ``tokens`` tokens
    (``feed_forward``: weights, experts, order and its inverse, 4 bytes an
    assignment each; for a share of the experts weights and ``ranked``)."""
    return tokens * cfg.top_k * (8 if cfg.experts_held else 16)


def expert_rows(cfg: "MoEConfig", tokens: int) -> int:
    """Rows of the arrays in expert order: every assignment, or a pass of
    the held experts' (``held_rows``)."""
    return held_rows(cfg, tokens) if cfg.experts_held else tokens * cfg.top_k


def remat_offers(cfg: "MoEConfig", kind, tokens: int):
    """((name, bytes a layer), ...): what a layer's feed-forward offers the
    layer checkpoint beyond REMAT_SAVED, dearest replay a byte first."""
    each = tokens * cfg.shared_width * jnp.dtype(cfg.dtype).itemsize
    return tuple(("shared_" + m, each) for m in _matrices(cfg)) if each \
        else ()


def layer_runs(cfg: MoEConfig) -> List[Tuple[str, int]]:
    """[(kind, how many adjacent layers of it), ...] in the layers' order,
    for a config that names its layers' kinds: one stack and one scan a
    run, one traced body a kind (llama._forward)."""
    named, known = set(cfg.layer_kinds), set(dict(cfg.attn_kinds))
    if len(cfg.layer_kinds) != cfg.n_layers or named - known:
        raise ValueError(
            f"{len(cfg.layer_kinds)} layer kinds {sorted(named)} for "
            f"{cfg.n_layers} layers of kinds {sorted(known)}")
    runs = [(kind, len(list(run)))
            for kind, run in itertools.groupby(cfg.layer_kinds)]
    most = cfg.run_layers or cfg.n_layers
    return [(kind, min(most, n - at)) for kind, n in runs
            for at in range(0, n, most)]


def _run_configs(cfg: MoEConfig) -> List[MoEConfig]:
    """The config of each run's stack: that many layers of one kind."""
    return [cfg.replace(n_layers=n, layer_kinds=())
            for _, n in layer_runs(cfg)]


def param_specs(cfg: MoEConfig) -> Dict[str, Any]:
    if cfg.layer_kinds:         # a list of stacks, one a run (layer_runs)
        runs = [param_specs(run) for run in _run_configs(cfg)]
        return {**runs[0], "layers": [r["layers"] for r in runs]}
    spec = _ll.param_specs(cfg)
    L = ("layers",)
    lay = dict(spec["layers"])
    for w in ("w_gate", "w_up", "w_down") + _absent(cfg):
        del lay[w]
    if cfg.tied_head:
        del spec["lm_head"]
    if cfg.qk_norm:
        lay["q_norm"] = L + ("heads",)
        lay["k_norm"] = L + ("kv_heads",)
    lay["router"] = L + ("embed", "experts")
    if _has_bias(cfg):
        lay["router_bias"] = L + ("experts",)
    for m in _matrices(cfg):
        lay["we_" + m] = L + ("experts", "embed", "expert_mlp")
    lay["we_down"] = L + ("experts", "expert_mlp", "embed")
    if cfg.shared_d_ff:
        for m in _matrices(cfg):
            lay["ws_" + m] = L + ("embed", "mlp")
        lay["ws_down"] = L + ("mlp", "embed")
    spec["layers"] = lay
    return spec


def _absent(cfg: MoEConfig) -> Tuple[str, ...]:
    """Leaves of the dense layer that this config's layer has not."""
    return ("ffn_norm",) if cfg.parallel_block else ()


def _has_bias(cfg: MoEConfig) -> bool:
    return cfg.router_score == "sigmoid" and cfg.router_bias


def init_params(key, cfg: MoEConfig) -> Dict[str, Any]:
    if cfg.layer_kinds:         # a list of stacks, one a run (layer_runs)
        runs = [init_params(jax.random.fold_in(key, 100 + i),
                            run.replace(vocab_size=1))["layers"]
                for i, run in enumerate(_run_configs(cfg))]
        return {**init_params(key, cfg.replace(n_layers=0, layer_kinds=())),
                "layers": runs}
    params = _ll.init_params(key, cfg.replace(d_ff=1))   # no dense SwiGLU
    pd = cfg.param_dtype
    L, D, F, E = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_experts
    H, Fs = cfg.n_held, cfg.shared_width
    ks = jax.random.split(jax.random.fold_in(key, 1), 4)
    lay = dict(params["layers"])
    for w in ("w_gate", "w_up", "w_down") + _absent(cfg):
        del lay[w]
    if cfg.tied_head:
        del params["lm_head"]
    if cfg.qk_norm:
        lay["q_norm"] = jnp.ones((L, cfg.n_heads * cfg.head_dim), pd)
        lay["k_norm"] = jnp.ones((L, cfg.n_kv_heads * cfg.head_dim), pd)
    lay["router"] = jax.random.normal(ks[0], (L, D, E), pd) * 0.02
    if _has_bias(cfg):                      # float32 whatever the weights are
        lay["router_bias"] = jnp.zeros((L, E), jnp.float32)
    keys = {"gate": 1, "up": 2}
    for m in _matrices(cfg):
        lay["we_" + m] = jax.random.normal(
            ks[keys[m]], (L, H, D, F), pd) * D ** -0.5
    lay["we_down"] = jax.random.normal(ks[3], (L, H, F, D), pd) * F ** -0.5
    if Fs:
        ks = jax.random.split(jax.random.fold_in(key, 2), 3)
        for m in _matrices(cfg):
            lay["ws_" + m] = jax.random.normal(
                ks[keys[m] - 1], (L, D, Fs), pd) * D ** -0.5
        lay["ws_down"] = jax.random.normal(ks[2], (L, Fs, D), pd) \
            * cfg.shared_d_ff ** -0.5     # the fan-in of ONE shared expert
    params["layers"] = lay
    return params


def num_params(cfg: MoEConfig) -> int:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff
    qk = (cfg.n_heads + cfg.n_kv_heads) * cfg.head_dim if cfg.qk_norm else 0
    bias = E if _has_bias(cfg) else 0
    return _ll.num_params(cfg.replace(d_ff=0)) + cfg.n_layers * (
        qk + D * E + bias + (len(_matrices(cfg)) + 1) * (
            cfg.n_held * D * F + D * cfg.shared_width)
        - D * len(_absent(cfg))) - D * cfg.vocab_size * cfg.tied_head


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


# The router keeps k of n scores by k ROUNDS of a masked maximum while k is
# at most this, and by ``lax.top_k`` above it. A round is a maximum and a
# minimum along the row (elementwise work and two lane reductions over the
# [T, n] scores); ``lax.top_k`` on a TPU sorts the n lanes of every row, and
# its gradient (and ``take_along_axis``'s) is a scatter-add. On the chip at
# [16384, n], selection, picked scores and their gradient, ms (PERF.md 6, PR
# 60): n 512, k 8 rounds 0.75 against the sort's 2.89, k 16 2.18 | 4.12, k
# 24 4.35 | 5.41, k 32 7.46 | 6.62; n 128, k 16 1.99 | 2.62, k 24 3.69 |
# 3.76, k 32 5.86 | 4.91; n 64 the rounds win at every k to 48 (0.78 |
# 5.42). The rounds grow faster than k (the picked scores are k more masked
# sums), the sort about with k: they cross between 24 and 32 where they
# cross at all, and 16 is under every crossing and above every router's k
# (4 to 10). A selection of another order (``sala.py``'s 64 blocks of 256,
# the indexer's 2,048 keys) is no router's and calls ``lax.top_k`` itself.
ROUND_MAX_K = 16


def _rounds(scores, k: int):
    """k rounds over the rows of scores [..., n] -> (values [..., k], lanes
    [..., k] int32, taken [..., n] bool: the k lanes chosen). A round takes
    the row's maximum over the lanes not yet taken and the FIRST such lane
    that holds it, and strikes that lane in ``taken`` (not with a -inf
    written into the scores: a row may hold -inf already, a masked group's
    experts, and a lane is taken once)."""
    n = scores.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    taken = jnp.zeros(scores.shape, bool)
    values, lanes = [], []
    for _ in range(k):
        top = jnp.max(jnp.where(taken, -jnp.inf, scores), axis=-1,
                      keepdims=True)
        first = jnp.min(jnp.where((scores == top) & ~taken, lane, n),
                        axis=-1, keepdims=True)
        taken = taken | (lane == first)
        values.append(top), lanes.append(first)
    return (jnp.concatenate(values, axis=-1),
            jnp.concatenate(lanes, axis=-1), taken)


def top_lanes(scores, k: int):
    """The k largest of every row of scores [..., n] -> (values [..., k],
    lanes [..., k] int32), in ``lax.top_k``'s own order: values falling,
    equal values to the lower lane. Up to ROUND_MAX_K by rounds, above it
    ``lax.top_k`` itself."""
    return jax.lax.top_k(scores, k) if k > ROUND_MAX_K \
        else _rounds(scores, k)[:2]


def at_lanes(scores, lanes):
    """scores [T, n] at lanes [T, k] -> [T, k]: ``take_along_axis``, up to
    ROUND_MAX_K lanes as k masked sums, whose gradient is a select (d
    scores = sum_k where(lane == lanes_k, d out_k, 0)) where a gather's is
    a scatter-add that a TPU walks row by row."""
    if lanes.shape[-1] > ROUND_MAX_K:
        return jnp.take_along_axis(scores, lanes, axis=-1)
    lane = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    return jnp.stack([jnp.sum(jnp.where(lane == lanes[..., k:k + 1], scores,
                                        0), axis=-1)
                      for k in range(lanes.shape[-1])], axis=-1)


def route_rounds(cfg: MoEConfig) -> int:
    """How many rounds of a masked maximum one token's selection takes (a
    group's two largest, the ``topk_group`` groups, the K experts); 0:
    the experts come from ``lax.top_k`` (``ROUND_MAX_K``)."""
    if cfg.top_k > ROUND_MAX_K:
        return 0
    return cfg.top_k + (2 + cfg.topk_group if cfg.n_group > 1 else 0)


def kept_groups(choice, cfg: MoEConfig):
    """Choice scores [T, E] (score plus bias) -> bool [T, n_group]: the
    ``topk_group`` groups a token keeps, a group's score the sum of its two
    largest choice scores, equal scores to the lower group. The ONE place
    the group limit lives: ``route`` masks with it and hands the mask on,
    the expert layer counts with that (``group_kept``), the references
    state the rule themselves."""
    T, E = choice.shape
    best = top_lanes(choice.reshape(T, cfg.n_group, E // cfg.n_group),
                     2)[0].sum(axis=-1)                           # [T, G]
    return _rounds(best, cfg.topk_group)[2]


def route(logits, cfg: MoEConfig, bias=None):
    """Router logits [T, E] float32 (and, for the sigmoid router, its bias
    [E] float32) -> (weights [T, K] float32, experts [T, K] int32, every
    expert's score [T, E]: softmax probabilities, or sigmoids; the groups
    every token kept, bool [T, n_group], or None without a group limit).
    With ``n_group`` > 1 the biased sigmoid router chooses inside the
    groups a token keeps (``kept_groups``): an expert of another group
    scores -inf for the choice, whatever its score and bias. The K experts
    are ``top_lanes`` of the choice scores (no gradient passes the
    choice), the weights the scores ``at_lanes``."""
    if cfg.n_group > 1 and (cfg.router_score != "sigmoid" or bias is None):
        raise NotImplementedError(
            f"n_group {cfg.n_group} on a {cfg.router_score} router "
            f"{'without' if bias is None else 'with'} a bias: the group "
            "limit is the biased sigmoid router's")
    kept = None
    if cfg.router_score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    elif cfg.router_score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router_score {cfg.router_score!r}")
    choice = jax.lax.stop_gradient(probs)
    if bias is not None:
        # the bias chooses and does not weigh; nothing is learned through it
        choice = choice + jax.lax.stop_gradient(bias.astype(jnp.float32))
        if cfg.n_group > 1:
            kept = kept_groups(choice, cfg)
            choice = jnp.where(jnp.repeat(
                kept, cfg.n_experts // cfg.n_group, axis=1), choice,
                -jnp.inf)
    _, experts = top_lanes(choice, cfg.top_k)
    weights = at_lanes(probs, experts)
    if cfg.norm_topk:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    if cfg.route_scale != 1.0:
        weights = weights * cfg.route_scale
    return weights, experts, probs, kept


# Rows computed AT A TIME for a share of the experts: this margin (a
# numerator over a denominator) over what the held experts get of T*K
# assignments when every expert is as likely. It presumes experts PLACED BY
# LOAD, as expert-parallel deployments place them and as every cell that
# holds a share does ("placement": "balanced"): a step's MEAN held share
# then sits at the even one, and a pass twice the even share was half dead
# rows that every scatter-add, dy's gather and every [rows, F] and
# [rows, D] form still walked (shapes are static; only the grouped matmul
# skips the tiles no group covers, and the gather of x the chunks beyond
# the last live row, ``held_chunk``). Single layers spread wider than the
# mean, because the routers train on after the placement: over a 40 s
# window 0 to 22% of layer-steps passed 5/4 of the even share in the six
# cells and 0 to 5% passed 3/2 (PERF.md 6, PR 51), so 3/2 it is. A layer
# whose held experts got more runs further passes, a quarter as long,
# over the tail (``_held_combine``), so no assignment is ever dropped and
# the step takes as long as the router made it: experts held as they come
# (a layer's share of 9 in 72 read 0.044 to 0.242 with random weights; PR
# 32) pay in further passes what the first one saves.
HELD_PASS = (3, 2)


def _count(ids, n: int):
    """How many of ``ids`` [M] are each of 0..n-1, int32 [n]: a compare
    and a sum (a scatter-add of M ones walks them one by one on a TPU)."""
    return jnp.sum(ids[:, None] == jnp.arange(n, dtype=ids.dtype), axis=0,
                   dtype=jnp.int32)


def held_rows(cfg: MoEConfig, tokens: int) -> int:
    """Rows of the first pass over the held experts' assignments of
    ``tokens`` tokens: HELD_PASS times their even share in whole row tiles
    of the grouped matmul, at most every assignment."""
    over, even = HELD_PASS
    rows = -(-tokens * cfg.top_k * cfg.n_held * over
             // (cfg.n_experts * even))
    tile = GMM_TILING[0]
    return min(-(-rows // tile) * tile, tokens * cfg.top_k)


# The first pass's gather moves a sixteenth of the pass at a time and no
# chunk beyond the last live row: experts placed by load leave the last
# third of a pass empty (half of it before PR 51, when this was sized).
# Under HELD_CHUNK_ROWS a chunk the loop saves nothing (one layer alone on
# the chip, PERF.md 6, PR 42: 16,384 rows in chunks of 1,024 -0.05 ms,
# 8,192 in chunks of 512 +0.73 ms), and the pass stays whole.
HELD_CHUNKS = 16
HELD_CHUNK_ROWS = 2048


def held_chunk(rows: int) -> int:
    """Rows of one chunk of a pass of ``rows``: a sixteenth of it where
    that is whole row tiles of the grouped matmul and HELD_CHUNK_ROWS or
    more, else the pass itself, one chunk."""
    chunk, left = divmod(rows, HELD_CHUNKS)
    if left or chunk % GMM_TILING[0] or chunk < HELD_CHUNK_ROWS:
        return rows
    return chunk


def _chunks(counts, rows: int):
    """How many chunks of a first pass of ``rows`` its gather walks: those
    that hold a live row; the one chunk of a pass that stays whole."""
    chunk = held_chunk(rows)
    if chunk == rows:
        return jnp.ones((), jnp.int32)
    return -(-jnp.minimum(counts.sum(), rows) // chunk)


def _gather_rows(x, token, live, n=None):
    """``where(live, x[token], 0)`` [rows, D]; given ``n``, over the first
    ``n`` chunks of the rows alone (all the live ones: ``_chunks``), in a
    loop whose length the data decide: the rest stays 0 and is never read
    or written. A pass of one chunk is gathered whole."""
    chunk = held_chunk(token.shape[0])
    if n is None or chunk == token.shape[0]:
        return jnp.where(live, x[token], 0)

    def one(c, xs):
        rows = jnp.where(
            jax.lax.dynamic_slice_in_dim(live, c * chunk, chunk),
            x[jax.lax.dynamic_slice_in_dim(token, c * chunk, chunk)], 0)
        return jax.lax.dynamic_update_slice_in_dim(xs, rows, c * chunk, 0)

    return jax.lax.fori_loop(
        0, n, one, jnp.zeros((token.shape[0],) + x.shape[1:], x.dtype))


def _held_slots(lo, rows: int, ranked, counts):
    """The slots of one pass over the assignments to held experts, ``rows``
    of them from the ``lo``-th on in the held experts' order (ranked
    [T*K]: the assignments sorted by held expert, the absent last; counts
    [held]) -> (order [rows], the assignment in each slot; live [rows, 1],
    false beyond the last held one; sizes [held] for the grouped matmul:
    the part of each expert's run that falls into the pass)."""
    start = jnp.cumsum(counts) - counts          # an expert's run in ranked
    slot = lo + jnp.arange(rows, dtype=jnp.int32)
    sizes = jnp.maximum(jnp.minimum(start + counts, lo + rows)
                        - jnp.maximum(start, lo), 0)
    return (ranked[jnp.minimum(slot, ranked.shape[0] - 1)],
            (slot < counts.sum())[:, None], sizes)


def _held_swiglu(xs, w_rows, live, sizes, we, cfg: MoEConfig):
    """The rows of a pass through their experts: xs [rows, D] (dead rows
    0), w_rows [rows] their routing weights, we the (gate, up, down)
    weights [held, ...], or (up, down) (``expert_act``) -> [rows, D],
    dead rows 0."""
    # the kernel leaves the rows no group covers unwritten: cleared, or
    # what is there meets a gradient of 0 and may be no number
    mm = lambda a, w: jnp.where(live, grouped_matmul(           # noqa: E731
        a, w, sizes, impl=cfg.gmm_impl), 0)
    # a row's weight multiplies it where it is narrow, before the down
    # projection (linear in its rows)
    of = dict(zip(_matrices(cfg), we))
    h = _activation(cfg, lambda m: mm(xs, of[m]))
    return mm((h.astype(jnp.float32) * w_rows[:, None]).astype(xs.dtype),
              we[-1])


def _held_rows(lo, rows: int, x, weights, ranked, counts, k: int,
               chunks=None):
    """A pass's rows (``_held_slots``): (token [rows], order, live, sizes,
    the tokens' rows of x with the dead ones 0, the rows' weights). With
    ``chunks``, only so many chunks of the rows are gathered: the first
    pass's live ones (``_gather_rows``)."""
    order, live, sizes = _held_slots(lo, rows, ranked, counts)
    token = order // k
    return (token, order, live, sizes, _gather_rows(x, token, live, chunks),
            weights.reshape(-1)[order])


def _passes(counts, skip: int, rows: int):
    """How many passes of ``rows`` the assignments to held experts beyond
    the first ``skip`` need: none in all but a layer of the tail."""
    return jnp.maximum(-(-(counts.sum() - skip) // rows), 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_combine(cfg, rows, short, x, weights, ranked, counts, we):
    """The held experts' part of the layer's output [T, D]: x [T, D],
    weights [T, K], ranked [T*K] (the assignments sorted by held expert,
    the absent last), counts [held], we the (gate, up, down) weights.

    The first ``rows`` assignments to held experts are one pass (HELD_PASS
    over their even share: it presumes experts placed by load), and all
    of them in all but a layer of the tail; where a layer's counts ask for
    more, the tail follows ``short`` rows at a time in a loop whose length
    the data decide, so no assignment is dropped whatever the router does,
    and experts not placed by load pay in further passes what the first
    one saves.
    Such a loop has no gradient of jax's own, hence the custom VJP: the
    first pass's backward is jax's (of ``_held_swiglu``, kept from the
    forward), each further pass runs again under its own, and all of them
    add the rows' gradients into the accumulators the first pass made, so
    the loops hold one short pass at a time and nothing more that is T
    rows wide. The
    sum back into token order is a scatter-add of the rows (a gather T*K
    rows wide would move several times as much).

    The first pass's shapes are static and its live rows a prefix of it,
    about two thirds of it where experts are placed by load: the gather
    of x into expert order, forward and in a checkpoint's replay, goes a
    chunk of rows at a time where the pass gives chunks (``held_chunk``)
    and stops at the last live row, as the grouped matmuls do. dy's gather
    and the three scatter-adds walk the whole pass, so the pass is no
    longer than the margin asks (PERF.md 6, PR 51): XLA's scatter-add in
    chunks is slower a row than whole, dy's gather as a loop beside the
    replayed one makes the compiler order every update after the last
    backward (the plan +3 to +4 GB), and one op over the live prefix, its
    static length chosen by a ``lax.switch``, makes the step program half
    as large again: it loads 12 s longer and the compiler rematerializes
    other layers' work to fit it (PERF.md 6, PR 42)."""
    return _held_combine_fwd(cfg, rows, short, x, weights, ranked, counts,
                             we)[0]


def _held_combine_fwd(cfg, rows, short, x, weights, ranked, counts, we):
    K = cfg.top_k
    with jax.named_scope("dispatch"):
        token, order, live, sizes, xs, w_rows = _held_rows(
            0, rows, x, weights, ranked, counts, K, _chunks(counts, rows))
    with jax.named_scope("experts"):
        ys, back = jax.vjp(lambda xs, w_rows, we: _held_swiglu(
            xs, w_rows, live, sizes, we, cfg), xs, w_rows, we)

    def one(p, y):
        with jax.named_scope("dispatch"):
            token, _, live, sizes, xs, w_rows = _held_rows(
                rows + p * short, short, x, weights, ranked, counts, K)
        with jax.named_scope("experts"):
            ys = _held_swiglu(xs, w_rows, live, sizes, we, cfg)
        return y.at[token].add(ys)

    y = jax.lax.fori_loop(0, _passes(counts, rows, short), one,
                          jnp.zeros(x.shape, jnp.float32).at[token].add(ys))
    return y.astype(x.dtype), (back, token, order, live, x, weights, ranked,
                               counts, we)


def _held_combine_bwd(cfg, rows, short, res, dy):
    back, token, order, live, x, weights, ranked, counts, we = res
    K = cfg.top_k

    def add(acc, token, order, live, d_xs, d_rows, d_we):
        return (acc[0].at[token].add(jnp.where(live, d_xs, 0)),
                acc[1].at[order].add(jnp.where(live[:, 0], d_rows, 0)),
                d_we if acc[2] is None else jax.tree.map(jnp.add, acc[2],
                                                         d_we))

    def one(p, acc):
        with jax.named_scope("dispatch"):
            token, order, live, sizes, xs, w_rows = _held_rows(
                rows + p * short, short, x, weights, ranked, counts, K)
        with jax.named_scope("experts"):
            _, again = jax.vjp(lambda xs, w_rows, we: _held_swiglu(
                xs, w_rows, live, sizes, we, cfg), xs, w_rows, we)
        return add(acc, token, order, live, *rows_back(again, token))

    def rows_back(vjp, token):
        with jax.named_scope("dispatch"):
            dys = dy[token]
        with jax.named_scope("experts"):
            return vjp(dys)

    more = _passes(counts, rows, short)
    zeros = (jnp.zeros_like(x), jnp.zeros(weights.size, weights.dtype), None)
    d_x, d_weights, d_we = jax.lax.fori_loop(
        0, more, one, add(zeros, token, order, live, *rows_back(back, token)))
    return d_x, d_weights.reshape(weights.shape), None, None, d_we


_held_combine.defvjp(_held_combine_fwd, _held_combine_bwd)


def _held_experts(x, weights, experts, lp, cfg: MoEConfig):
    """The held experts' part of the layer's output: x [T, D], weights and
    experts [T, K] (over all n_experts) -> (y [T, D], the layer's
    statistics: the counts of the held experts [held], how many passes
    beyond the first they took, the shares of the first pass's rows that
    its gather walked and that are live). The assignments are sorted by
    held expert, the absent last, and the held ones computed ``held_rows``
    at a time, then a quarter as many (``_held_combine``)."""
    T, K, dt = x.shape[0], cfg.top_k, cfg.dtype
    held, first = cfg.experts_held
    with jax.named_scope("dispatch"):
        local = experts.reshape(T * K) - first
        local = jnp.where((local >= 0) & (local < held), local, held)
        ranked = jnp.argsort(local, stable=True).astype(jnp.int32)
    with jax.named_scope("router"):
        counts = _count(local, held)
    weights, ranked, counts = checkpoint_name((weights, ranked, counts),
                                              REMAT_SAVED[0])
    rows, tile = held_rows(cfg, T), GMM_TILING[0]
    short = max(rows // 4 // tile * tile, min(tile, rows))
    with jax.named_scope("experts"):
        we = tuple(_ll._dq(lp["we_" + m], dt)
                   for m in _matrices(cfg) + ("down",))
    # the pass's own scopes lie inside (``_held_combine_fwd``, ``_bwd``)
    with jax.named_scope("combine"):
        y = _held_combine(cfg, rows, short, x, weights, ranked, counts, we)
    return y, {"held_counts": counts,
               "more_passes": _passes(counts, rows, short),
               "walked_share": _chunks(counts, rows) * (held_chunk(rows)
                                                        / rows),
               "live_share": jnp.minimum(counts.sum(), rows) / rows}


def expert_plan(cfg: MoEConfig, tokens: int) -> dict:
    """What an expert layer's experts are and how their grouped matmuls
    are tiled (also the attributes of ``moe.expert_plan``, once a traced
    body): the activation and its matrices (3: gate, up, down; 2: up,
    down), the widths as stored (``padded_width`` 0: the config's own),
    the experts held, the rows of a pass, the (tm, tk, tn) the Mosaic
    calls take for the up and the down product and for their weight
    gradients (``ops/grouped_matmul.py`` ``tiles``; "" on the xla path),
    and how the router selects: ``route_form`` "rounds" with the rounds a
    token's selection takes, or "top_k" with 0 (``route_rounds``)."""
    rows, item = expert_rows(cfg, tokens), jnp.dtype(cfg.dtype).itemsize
    rounds = route_rounds(cfg)
    said = {"act": cfg.expert_act, "matrices": len(_matrices(cfg)) + 1,
            "width": cfg.d_ff, "shared_width": cfg.shared_width,
            "padded_width": 0, "held": cfg.n_held, "rows": rows,
            "path": cfg.gmm_impl, "route_rounds": rounds,
            "route_form": "rounds" if rounds else "top_k"}
    for name, (k, n) in (("up", (cfg.d_model, cfg.d_ff)),
                         ("down", (cfg.d_ff, cfg.d_model))):
        gmm, tgmm = tiles(rows, k, n, item) if cfg.gmm_impl == "pallas" \
            else ((), ())
        said["gmm_" + name] = "x".join(map(str, gmm))
        said["tgmm_" + name] = "x".join(map(str, tgmm))
    return said


def feed_forward(h, lp, cfg: MoEConfig, mesh=None, rules=None, tp=None,
                 kind=None):
    """The expert layer: normed h [B, S, D] -> (its output [B, S, D],
    this layer's routing statistics for ``finish_loss``). It takes every
    row of a sequence and no overlap plan (llama._tp_plan gives none);
    every kind of layer has the same."""
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
    tracing.plan("moe.expert_plan", expert_plan(cfg, T))
    # the layer's sub-scopes (PERF.md 3): router, dispatch, experts,
    # combine, shared
    with jax.named_scope("router"):
        logits = jnp.dot(x, _ll._dq(lp["router"], dt),
                         preferred_element_type=jnp.float32)        # [T, E]
        weights, experts, probs, kept = route(logits, cfg,
                                              lp.get("router_bias"))
        flat = experts.reshape(T * K)
    if cfg.experts_held is not None:
        y, stats = _held_experts(x, weights, experts, lp, cfg)
        with jax.named_scope("router"):
            stats = {"counts": _count(flat, E), **stats}
            if kept is not None:
                # the share of the tokens whose kept groups include the
                # one the held experts lie in (they lie in one: the config
                # says which, ``experts_held``'s first)
                held_group = cfg.experts_held[1] // (E // cfg.n_group)
                stats["group_kept"] = kept[:, held_group].mean(
                    dtype=jnp.float32)
                if cfg.report_groups:
                    stats["groups"] = kept
        return _finish(y, stats, x, lp, cfg, logits, experts, probs, (B, S, D))
    with jax.named_scope("dispatch"):
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # row -> slot
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * K, dtype=jnp.int32))                   # slot -> row
    with jax.named_scope("router"):
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    weights, experts, order, back, sizes = checkpoint_name(
        (weights, experts, order, back, sizes), REMAT_SAVED[0])

    with jax.named_scope("dispatch"):
        xs = _dispatch(K, x, order, back)        # rows in expert order
    mm = lambda w: grouped_matmul(xs, _ll._dq(lp[w], dt), sizes,   # noqa: E731
                                  impl=cfg.gmm_impl)
    with jax.named_scope("experts"):
        h = _activation(cfg, lambda m: mm("we_" + m))
    with jax.named_scope("combine"):
        y = _down_combine(cfg.gmm_impl, h, _ll._dq(lp["we_down"], dt),
                          weights, order, back, sizes)
    return _finish(y, {"counts": sizes}, x, lp, cfg, logits, experts, probs,
                   (B, S, D))


def _finish(y, stats, x, lp, cfg: MoEConfig, logits, experts, probs, shape):
    """The routed experts' y [T, D] plus the shared SwiGLU of x, where the
    config has one, in the layer's shape, and the layer's statistics."""
    with jax.named_scope("router"):
        if cfg.router_score == "sigmoid":
            stats = {**stats, "experts": experts, "balance":
                     _sequence_balance(probs, experts, cfg, shape[0])}
        else:
            stats = {**stats, "prob_sum": probs.sum(axis=0), "z_sum":
                     jnp.square(jax.nn.logsumexp(logits, axis=-1)).sum(),
                     "experts": experts}
    if cfg.shared_d_ff:
        dt = cfg.dtype
        with jax.named_scope("shared"):
            products = {m: checkpoint_name(x @ _ll._dq(lp["ws_" + m], dt),
                                           "shared_" + m)
                        for m in _matrices(cfg)}
            shared = _activation(cfg, products.__getitem__) \
                @ _ll._dq(lp["ws_down"], dt)
            if cfg.shared_combine not in ("sum", "average"):
                raise ValueError(
                    f"unknown shared_combine {cfg.shared_combine!r}")
            if cfg.shared_combine == "average":
                shared = shared * (1.0 / cfg.n_shared)
            y = y + shared
    return y.reshape(shape), stats


def _sequence_balance(scores, experts, cfg: MoEConfig, batch: int):
    """DeepSeek-V3's sequence-wise balance loss of one layer, averaged over
    the batch's sequences: for a sequence of S tokens sum_i f_i P_i, f_i =
    E / (K S) x its assignments to expert i, P_i the mean over its tokens
    of s_i / sum_j s_j. scores [T, E], experts [T, K], T = batch x S."""
    E, K = cfg.n_experts, cfg.top_k
    S = scores.shape[0] // batch
    share = (scores / scores.sum(axis=-1, keepdims=True)).reshape(
        batch, S, E).mean(axis=1)                                  # P [B, E]
    counts = jax.vmap(lambda ids: _count(ids, E))(
        experts.reshape(batch, S * K))                             # [B, E]
    return jnp.mean(jnp.sum(counts.astype(jnp.float32) * (E / (K * S))
                            * share, axis=-1))


def held_aux(held, stats, per_layer: int, n_experts: int):
    """What a step reports of a chip's share of the experts: held [L, held]
    the held experts' counts in float32, per_layer = T x K assignments to
    all ``n_experts``."""
    return {
        # the largest held expert over the held experts' mean, worst layer
        "moe_load_max_over_mean": jnp.max(
            held.max(axis=1) * held.shape[1]
            / jnp.maximum(held.sum(axis=1), 1.0)),
        "moe_held_rows_share": held.sum() / (held.shape[0] * per_layer),
        # passes beyond the first that the held experts' rows took
        "moe_held_more_passes":
            stats["more_passes"].sum().astype(jnp.float32),
        # rows the first passes' gathers walked over the rows of those
        # passes (whole chunks, ``held_chunk``; 1.0: the passes whole)
        "moe_held_walked_share": stats["walked_share"].mean(),
        # live rows of the first passes over their rows, mean of the layers
        "moe_held_pass_live_share": stats["live_share"].mean(),
        # the fullest layer's held share over the even share (HELD_PASS is
        # the margin a first pass has over it)
        "moe_held_share_max_over_even": jnp.max(held.sum(axis=1)) * (
            n_experts / (held.shape[1] * per_layer)),
        # the share of the step's expert layers that took a further pass
        "moe_held_further_pass_share":
            (stats["more_passes"] > 0).mean(dtype=jnp.float32),
        # every assignment to a held expert is computed (_held_experts)
        "moe_dropped": jnp.zeros((), jnp.int32)}


def finish_loss(loss, stats, cfg: MoEConfig):
    """Cross-entropy + the router losses, from the layers' stacked
    statistics -> (loss, aux): transformers' ``load_balancing_loss_func``
    (the router outputs of all layers concatenated: E x sum over experts
    of the share of assignments times the mean probability) and the
    router z-loss (mean squared logsumexp of the router logits)."""
    E, K = cfg.n_experts, cfg.top_k
    counts = stats["counts"]                                   # [L, E]
    rows = counts.shape[0] * stats["experts"].shape[1]         # L x T
    if "balance" in stats:      # a sigmoid router: the sequence-wise loss
        aux, z = stats["balance"].mean(), jnp.zeros((), jnp.float32)
    else:
        share = counts.sum(axis=0).astype(jnp.float32) / rows
        aux = E * jnp.sum(share * stats["prob_sum"].sum(axis=0) / rows)
        z = stats["z_sum"].sum() / rows
    per_layer = rows // counts.shape[0] * K                    # T x K
    # a biased router's counts over all experts, for ``post_update``
    ruled = {"router_counts": counts} if _has_bias(cfg) else {}
    if cfg.experts_held is not None:
        held = stats["held_counts"].astype(jnp.float32)        # [L, held]
        return loss + cfg.router_aux_weight * aux + cfg.router_z_weight * z, {
            "moe_aux_loss": aux, "moe_z_loss": z, **ruled,
            **held_aux(held, stats, per_layer, E)}
    return (loss + cfg.router_aux_weight * aux + cfg.router_z_weight * z, {
        "moe_aux_loss": aux, "moe_z_loss": z, **ruled,
        "moe_load_max_over_mean":
            counts.max().astype(jnp.float32) * E / per_layer,
        "moe_dropped": (per_layer - counts.sum(axis=1)).sum()})


def post_update(params, aux, cfg: MoEConfig):
    """The rule no gradient carries (DeepSeek-V3 2.1.2): after a step,
    every router's bias moves by ``bias_rate`` towards balance, b += u x
    sign(mean(c) - c) from the step's counts c of ALL experts, a layer.
    (params, aux) -> (params, aux): ``router_counts`` (the statistics'
    order: the runs' expert layers in the layers' order, then a prediction
    module's block) is used up; ``moe_bias_abs_max`` and ``moe_bias_moved``
    (how many biases the step moved) are the rule's report. A config whose routers have no bias reports no counts and has no rule:
    params and aux go back as they came."""
    if "router_counts" not in aux:
        return params, aux
    aux = dict(aux)
    counts = aux.pop("router_counts").astype(jnp.float32)      # [layers, E]
    step = cfg.bias_rate * jnp.sign(
        counts.mean(axis=1, keepdims=True) - counts)
    at, biases = 0, []

    def moved(stack):
        nonlocal at
        if "router_bias" not in stack:
            return stack
        n = stack["router_bias"].shape[0]
        biases.append(stack["router_bias"] + step[at:at + n])
        at += n
        return {**stack, "router_bias": biases[-1]}

    params = dict(params, layers=[moved(s) for s in params["layers"]])
    if "mtp" in params:
        params["mtp"] = dict(params["mtp"],
                             block=moved(params["mtp"]["block"]))
    assert at == counts.shape[0], (at, counts.shape)
    aux["moe_bias_abs_max"] = jnp.max(jnp.abs(jnp.concatenate(biases)))
    aux["moe_bias_moved"] = jnp.count_nonzero(step).astype(jnp.float32)
    return params, aux


forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn

# what an expert model supplies to the shared layer (models/family.py)
FAMILY = Family(
    "moe", feed_forward=feed_forward, remat_saved=REMAT_SAVED,
    remat_offered=SHARED_OFFERED, remat_saved_bytes=remat_saved_bytes,
    remat_offers=remat_offers, layer_runs=layer_runs,
    finish_loss=finish_loss, expert_rows=expert_rows)
