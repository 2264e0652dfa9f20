"""State-space / attention hybrid with sparse experts, as Granite-4.0-H
has it (transformers ``modeling_granitemoehybrid.py``, whose state-space
layer is Bamba's Mamba-2 mixer, arXiv:2405.21060): most layers mix the
sequence by a selective state-space recurrence, one in ten by causal
grouped-query attention with no position embedding, and every layer is
followed by routed experts beside a shared SwiGLU.

This module is only what differs from ``models/llama.py`` and
``models/moe.py``: the config (the layer pattern, the mixer's sizes, the
three multipliers), the parameter tree and the Mamba-2 mixer
(``mixer_half``). The attention layer is ``llama._attention_half`` (no
rotary tables, ``attn_scale`` for the softmax), the expert layer is
``moe.feed_forward`` (Granite's softmax over the K largest logits IS
``route`` with ``norm_topk``; ``experts_held``, ``shared_d_ff``), the
router losses ``moe.finish_loss``; embedding, the loop over the layers,
remat and its policy, the head (tied to the embedding here) and the
cross-entropy are ``llama.forward_with_stats`` and ``llama.loss_fn``.

A second member of the family, Nemotron-H's block (``nemotron_h``;
Nemotron 3 Nano 30B-A3B), holds ONE half (``one_half``): a block is a
mixer ("mamba"), an attention layer ("attention") or an expert layer
("experts") alone, x + half(rms_norm(x)), and the three kinds' parameter
trees share nothing; its mixer's B and C come in ``mamba_groups`` groups of
adjacent heads (head h reads group h // (H / G)), the convolution runs
over H P + 2 G N channels and the gated norm over each group's lanes
apart; its experts are two matrices and a squared ReLU (``expert_act``),
routed by sigmoid scores with a bias that ``moe.post_update`` moves; its
head is a leaf of its own (``tied_head`` False). Its published pattern has
no two adjacent blocks of a kind: every run is one block.

A third member, LFM2's block (``lfm2_moe``; LFM2-8B-A1B), has a first half
of a third kind, "conv": a gated short convolution,

    [B | C | u] = rms_norm(x) @ in_proj   (3 D columns, no bias)
    c[t] = sum_j conv_w[j] (B u)[t - taps + 1 + j]   depthwise, causal,
                                          ``conv_taps`` taps, no bias, NO
                                          activation
    out = (C c) @ out_proj

with no state but the last ``conv_taps`` - 1 rows: no scan, no decay, no
heads. Its attention layers norm each head of q and of k
(``qk_head_norm``: ``llama._project``) before the rotary; its first
``n_dense`` layers' feed-forward is a dense SwiGLU of ``dense_d_ff``
(``llama.feed_forward`` under the scope ``dense``) with no router, no
routes kept and no part in ``post_update``; the others route by sigmoid
scores with a bias. A dense layer's kind is its first half's with
".dense" after it ("conv.dense"): the trees differ, so it is a run of its
own. What lies between the operator's two projections is ONE pass
(``_gated_conv``), its gradient written by hand as the Mamba-2 mixer's
passes are.

Layers of two kinds cannot be one stack: ``params["layers"]`` is a LIST of
stacks, one a run of adjacent layers of one kind (``layer_runs``; the
published pattern is [5 mamba, attention, 4 mamba] four times over: runs
of 5, 1, 9, 1, 9, 1, 9, 1, 4; ``run_layers`` layers a stack at most where
it is set: a stack of ONE layer is no loop, and its gradients meet the
optimizer as the backward makes them), each run one ``lax.scan``; every run of a
kind that keeps the same names across the layer checkpoint
(``remat.remat_plan``: the step's memory decides run by run) is scanned by
the same function, traced under ``remat._checkpoint`` (instant
``hybrid.layer_plan``).

The mixer, for u = rms_norm(x) [B, S, D], H heads of width P, state N:

    [z | xBC | dt] = u @ in_proj          (H P | H P + 2 N | H, no bias)
    xBC = silu(conv(xBC) + b)             depthwise, causal, ``mamba_conv`` taps
    x [H, P], B [G, N], C [G, N] = split(xBC)   G groups (Granite: one)
    dt = softplus(dt + dt_bias); A = -exp(a_log)             float32, a head
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T; y_t = s_t C_t + D x_t
                                          (head h with its group's B, C)
    y = rms_norm(y * silu(z)) * gate_norm       over each group's H P / G
    out = y @ out_proj

The recurrence is ``ops/ssd.py``: "xla" (plain einsums; the CPU, a mesh)
or "pallas" (the Mosaic calls), chosen by ``ssd_impl`` as ``attn_impl``
and ``gmm_impl`` choose theirs; the kernel path refuses a mesh of several
devices.

What lies between the two projections is memory-bound row work, and its
gradient is written by hand (``_conv_silu``, ``_gated_norm`` here,
``_prologue`` in ``ops/ssd.py``; instant ``mixer.plan``), whatever
``ssd_impl`` says. A rule keeps only arrays the forward already has, in
the activations' type (the convolution's input; x, y, z; the steps),
computes every float32 value again inside the pass that needs it, and
hands each cotangent on in the type its consumer takes, so that no float32
[B, S, H P] array stands in memory between two fusions (jax's transposition
left five). Across the layer checkpoint the rules keep NOTHING: the
backward's replay of the layer makes their residuals again (from the
in-projection's product where the plan kept it, ``MIX_OFFERED``). The results
of a pass go through ``optimization_barrier``: XLA otherwise moves a
pass's arithmetic into its consumers' fusions, twice where there are two
(tests/test_tpu_compile_dense.py holds the compiled layer to the account).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import llama as _ll
from ray_tpu.models import moe as _moe
from ray_tpu.ops.ssd import _over_lanes, _per_head, ssd_scan
from ray_tpu.util import tracing


_DENSE = ".dense"       # ends the kind of a layer with a dense feed-forward


def _first(kind: str) -> str:
    """A kind's first half: "mamba" | "conv" | "attention" | "experts"."""
    return kind.split(".")[0]


def _dense(kind: str) -> bool:
    return kind.endswith(_DENSE)


@dataclass(frozen=True)
class HybridConfig(_moe.MoEConfig):
    """``n_heads``, ``n_kv_heads`` are the attention layers'; ``d_ff`` is
    the width of ONE routed expert."""
    # one kind a layer: "mamba", "conv" or "attention" (each followed by
    # its feed-forward) or, with ``one_half``, "experts" too; () = every
    # layer "mamba"
    layer_types: Tuple[str, ...] = ()
    # a block is ONE of mixer, attention and expert layer, with one norm
    # and one residual add (Nemotron-H), not a first half and its experts
    one_half: bool = False
    mamba_heads: int = 8
    mamba_head_dim: int = 16
    mamba_state: int = 16
    # groups of adjacent heads that share a B and a C, and a gated norm
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 64
    ssd_impl: str = "xla"               # "xla" | "pallas"
    # taps of a "conv" layer's depthwise convolution (LFM2's conv_L_cache;
    # ``mamba_conv`` is the Mamba-2 mixer's, with its bias and its SiLU)
    conv_taps: int = 3
    # leading layers whose feed-forward is a dense SwiGLU of ``dense_d_ff``
    n_dense: int = 0
    dense_d_ff: int = 0
    # an RMS norm over each head of q and of k (``llama._project``)
    qk_head_norm: bool = False
    # (the three multipliers and ``attn_scale`` are LlamaConfig's fields)
    rope: bool = False                  # no position embedding
    norm_topk: bool = True              # softmax over the K largest logits
    tied_head: bool = True              # False: an ``lm_head`` leaf

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def kinds(self) -> Tuple[str, ...]:
        """A kind a layer: its first half's, ".dense" after it where its
        feed-forward is the dense SwiGLU."""
        types = self.layer_types or ("mamba",) * self.n_layers
        return tuple(t + _DENSE if i < self.n_dense else t
                     for i, t in enumerate(types))

    def replace(self, **kw) -> "HybridConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, HybridConfig] = {
    "tiny": HybridConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=32, max_seq_len=128, n_experts=8, top_k=2, shared_d_ff=48,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0, attn_scale=1.0 / 16),
    # Nemotron-H's block at the CPU tests' size: the pattern MEM*EMEM*E
    # ([M E] [M *] ... no two adjacent alike), 4 mixer heads of 16 in 2
    # groups, 4 query heads over 2 KV heads, 2 of 8 experts held, an
    # expert width that is no multiple of a tile
    "tiny-nemotron": HybridConfig(
        vocab_size=256, d_model=48, n_layers=10, n_heads=4, n_kv_heads=2,
        head_width=16, d_ff=24, max_seq_len=128, n_experts=8, top_k=2,
        shared_d_ff=40, experts_held=(2, 0), one_half=True,
        tied_head=False, expert_act="relu2",
        router_score="sigmoid", route_scale=2.5, router_aux_weight=0.0001,
        router_z_weight=0.0, mamba_heads=4, mamba_head_dim=16,
        mamba_state=16, mamba_groups=2, mamba_chunk=8,
        layer_types=tuple({"M": "mamba", "E": "experts", "*": "attention"}[c]
                          for c in "MEM*EMEM*E")),
    # LFM2's block at the CPU tests' size: two periods of conv, conv,
    # attention, conv behind a dense first layer, 4 query heads over 2 KV
    # heads of 24 (the hidden size over the heads is 16), 2 of 8 experts
    # held, 3 a token by sigmoid score with a bias, no shared expert
    "tiny-lfm2": HybridConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_width=24, d_ff=32, max_seq_len=256, n_experts=8, top_k=3,
        shared_d_ff=0, experts_held=(2, 0), n_dense=1, dense_d_ff=96,
        conv_taps=3, qk_head_norm=True, rope=True, rope_theta=1000000.0,
        norm_eps=1e-5, router_score="sigmoid", route_scale=1.0,
        router_aux_weight=0.0001, router_z_weight=0.0,
        layer_types=("conv", "conv", "attention", "conv") * 2),
}

# what a mixer's layer has not of the expert family's tree: the attention
# half (the heads' norms with ``qk_head_norm``)
_ATTENTION = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
# a first half's kinds; "experts", a block of its own, with ``one_half``
_KINDS = ("mamba", "conv", "attention", "experts")
# a mixer's leaves, by kind: the Mamba-2 mixer's; the short convolution's
_MIXER_LEAVES = {
    "mamba": ("mix_norm", "in_proj", "conv_w", "conv_b", "dt_bias", "a_log",
              "d_skip", "gate_norm", "out_proj"),
    "conv": ("mix_norm", "in_proj", "conv_w", "out_proj")}

# what the layer checkpoint keeps beside the layer's input and flash's
# residuals (remat._checkpoint): the expert layer's routes; of a mixer
# nothing: its scan runs again, and so does its in-projection unless the
# step's memory has room for the product (MIX_OFFERED, after a dense
# SwiGLU's and the shared expert's: 15 ms of replay a GB against 23 and
# 22, PERF.md 6)
MIX_OFFERED = "mix_proj"
REMAT_OFFERED = _ll.FFN_OFFERED + _moe.SHARED_OFFERED + (MIX_OFFERED,)
post_update = _moe.post_update
RULE_LEAVES = _moe.RULE_LEAVES


def halves(cfg: HybridConfig, kind) -> Tuple[bool, bool]:
    """(whether a block of ``kind`` runs a first half: its mixer or its
    attention; whether it runs the feed-forward), for ``llama._layer``."""
    return (kind != "experts", kind == "experts") if cfg.one_half \
        else (True, True)


def routes(cfg: HybridConfig, kind) -> bool:
    """Whether a block of ``kind`` runs an EXPERT layer (its feed-forward,
    and not the dense SwiGLU): it has routes to keep and rows in expert
    order (``remat._step_estimate``)."""
    return halves(cfg, kind)[1] and not _dense(kind)


def remat_saved_bytes(cfg: HybridConfig, kind, tokens: int) -> int:
    return _moe.remat_saved_bytes(cfg, kind, tokens) \
        if routes(cfg, kind) else 0


def remat_offers(cfg: HybridConfig, kind, tokens: int):
    """What a block of ``kind`` offers the layer checkpoint: its dense
    SwiGLU's gate and up or its expert layer's shared products, then a
    mixer's ``u @ in_proj`` [tokens, z | xBC | dt] or [tokens, B | C | u]."""
    item = jnp.dtype(cfg.dtype).itemsize
    if _dense(kind):
        ffn = tuple((name, tokens * cfg.dense_d_ff * item)
                    for name in _ll.FFN_OFFERED)
    else:
        ffn = _moe.remat_offers(cfg, kind, tokens) \
            if halves(cfg, kind)[1] else ()
    columns = {"mamba": _mamba_sizes(cfg)[2],
               "conv": 3 * cfg.d_model}.get(_first(kind))
    if columns is None:
        return ffn
    return ffn + ((MIX_OFFERED, tokens * columns * item),)


def mixer_backward_bytes(cfg: HybridConfig, kind, tokens: int) -> int:
    """Bytes a mixer's backward holds beside its matrices' products and
    their gradients (``remat._step_estimate`` counts those from the
    leaves): what the replay leaves for the rules (``plan``'s residuals with
    nothing checkpointed: the convolution's input, x, y and z, the steps),
    the scan's state at every chunk's start (float32 [chunks, H, P, N]) and
    one float32 pass over the convolution's channels and one over the gated
    norm's lanes. A short convolution's: its rule's residual ([tokens,
    B | C | u] in the activations' type) and one float32 pass over it."""
    if _first(kind) == "conv":
        return tokens * 3 * cfg.d_model * (jnp.dtype(cfg.dtype).itemsize + 4)
    inner, conv_dim, _ = _mamba_sizes(cfg)
    chunks = -(-tokens // cfg.mamba_chunk)
    return (_rule_residual_bytes(cfg, tokens)
            + chunks * inner * cfg.mamba_state * 4
            + tokens * (conv_dim + inner) * 4)


def _rule_residual_bytes(cfg: HybridConfig, tokens: int) -> int:
    """What the mixer's hand-written rules keep of a forward: the
    convolution's input, x, y and z in the activations' type, the steps."""
    inner, conv_dim, _ = _mamba_sizes(cfg)
    return tokens * ((conv_dim + 3 * inner) * jnp.dtype(cfg.dtype).itemsize
                     + cfg.mamba_heads * 4)


def layer_runs(cfg: HybridConfig) -> List[Tuple[str, int]]:
    """[(kind, how many adjacent layers of it), ...] in the layers' order."""
    if len(cfg.kinds) != cfg.n_layers:
        raise ValueError(f"{len(cfg.kinds)} layer types for {cfg.n_layers} "
                         "layers")
    if cfg.n_dense and (cfg.one_half or not cfg.dense_d_ff):
        raise ValueError(
            f"{cfg.n_dense} dense layers of width {cfg.dense_d_ff}"
            + (" in a model of one-half blocks" if cfg.one_half else ""))
    runs: List[Tuple[str, int]] = []
    for kind in cfg.kinds:
        if _first(kind) not in _KINDS[:4 if cfg.one_half else 3]:
            raise ValueError(f"unknown layer type {kind!r}")
        # ``run_layers``: the most layers one stack holds (0: a whole run)
        if runs and runs[-1][0] == kind \
                and runs[-1][1] != (cfg.run_layers or cfg.n_layers):
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def layer_plan_says(cfg: HybridConfig, runs, plan) -> dict:
    """What ``hybrid.layer_plan`` says of a model with short convolutions
    or leading dense layers beside its runs: the dense layers, the taps,
    and what the layer checkpoint kept of each kind's runs beyond the
    parent's list ("kind: names xN/M": in N of the kind's M runs). Nothing
    for the family's other members."""
    if not cfg.n_dense and "conv" not in cfg.layer_types:
        return {}
    kept = {}
    for at, (kind, _) in enumerate(runs):
        names = plan.of(at) if plan is not None else ()
        mine = kept.setdefault(kind, [(), 0, 0])
        mine[0] = mine[0] or names
        mine[1] += bool(names)
        mine[2] += 1
    return {"dense_layers": cfg.n_dense, "dense_width": cfg.dense_d_ff,
            "taps": cfg.conv_taps,
            "kept": ", ".join(f"{k}: {'+'.join(n) or '-'} x{some}/{of}"
                              for k, (n, some, of) in kept.items())}


def _run_configs(cfg: HybridConfig):
    return [(kind, cfg.replace(n_layers=n)) for kind, n in layer_runs(cfg)]


def _mamba_sizes(cfg: HybridConfig):
    """(the heads' lanes H P, the convolution's channels H P + 2 G N, the
    in-projection's columns z | xBC | dt)."""
    inner, n = cfg.mamba_inner, cfg.mamba_state * cfg.mamba_groups
    return inner, inner + 2 * n, 2 * inner + 2 * n + cfg.mamba_heads


def _of_kind(lay: dict, kind: str, cfg: HybridConfig, mixer: dict,
             dense: dict = None) -> dict:
    """A stack of ``kind``'s leaves from the expert family's ``lay`` (an
    attention half and an expert layer), the ``mixer``'s (every kind's:
    the kind's own are taken) and, for a layer with a dense feed-forward,
    the dense family's ``dense``: both halves of a block, or with
    ``one_half`` the kind's own alone."""
    first, second = halves(cfg, kind)
    out = {}
    if second and _dense(kind):
        out = {k: dense[k] for k in ("ffn_norm", "w_gate", "w_up", "w_down")}
    elif second:
        out = {k: v for k, v in lay.items() if k not in _ATTENTION}
    if first and _first(kind) == "attention":
        out.update({k: lay[k] for k in _ATTENTION if k in lay})
    elif first:
        out.update({k: mixer[k] for k in _MIXER_LEAVES[_first(kind)]})
    return out


def _mixer_specs() -> Dict[str, Any]:
    """The logical axes of a mixer's leaves (every kind's)."""
    L = ("layers",)
    return {
        "mix_norm": L + ("embed_nr",),
        "in_proj": L + ("embed", "mlp"),
        "conv_w": L + (None, "mlp"), "conv_b": L + ("mlp",),
        "dt_bias": L + (None,), "a_log": L + (None,),
        "d_skip": L + (None,), "gate_norm": L + ("mlp",),
        "out_proj": L + ("mlp", "embed")}


def param_specs(cfg: HybridConfig) -> Dict[str, Any]:
    L = ("layers",)
    mixer = _mixer_specs()
    # a scale of ONE head's width, shared by the heads (models/sala.py's)
    heads = {"q_norm": L + (None,), "k_norm": L + (None,)} \
        if cfg.qk_head_norm else {}
    runs = [_of_kind({**_moe.param_specs(
        run.replace(tied_head=False))["layers"], **heads}, kind, cfg, mixer,
        _ll.param_specs(run)["layers"]) for kind, run in _run_configs(cfg)]
    head = {} if cfg.tied_head else {"lm_head": ("embed", "vocab")}
    return {"embed": ("vocab", "embed"), "layers": runs,
            "final_norm": ("embed_nr",), **head}


def _mamba_params(k, cfg: HybridConfig, n: int) -> dict:
    """``n`` Mamba-2 mixers' leaves as Mamba-2 sets them: ``dt_bias`` the
    inverse softplus of steps log-uniform in [0.001, 0.1], ``a_log`` the
    log of rates uniform in [1, 16], ``d_skip`` 1."""
    pd, D, H = cfg.param_dtype, cfg.d_model, cfg.mamba_heads
    inner, conv_dim, proj = _mamba_sizes(cfg)
    ks = jax.random.split(jax.random.fold_in(k, 3), 5)
    step = jnp.exp(jax.random.uniform(
        ks[2], (n, H), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    return {
        "mix_norm": jnp.ones((n, D), pd),
        "in_proj": jax.random.normal(ks[0], (n, D, proj), pd) * D ** -0.5,
        "conv_w": jax.random.normal(
            ks[1], (n, cfg.mamba_conv, conv_dim), pd)
        * cfg.mamba_conv ** -0.5,
        "conv_b": jnp.zeros((n, conv_dim), pd),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
        "a_log": jnp.log(jax.random.uniform(
            ks[3], (n, H), minval=1.0, maxval=16.0)).astype(pd),
        "d_skip": jnp.ones((n, H), pd),
        "gate_norm": jnp.ones((n, inner), pd),
        "out_proj": jax.random.normal(ks[4], (n, inner, D), pd)
        * inner ** -0.5}


def _conv_params(k, cfg: HybridConfig, n: int) -> dict:
    """``n`` short convolutions' leaves: the two projections normal over
    the square root of their fan-in, the taps over the square root of
    their number."""
    pd, D = cfg.param_dtype, cfg.d_model
    ks = jax.random.split(jax.random.fold_in(k, 3), 3)
    return {
        "mix_norm": jnp.ones((n, D), pd),
        "in_proj": jax.random.normal(ks[0], (n, D, 3 * D), pd) * D ** -0.5,
        "conv_w": jax.random.normal(ks[1], (n, cfg.conv_taps, D), pd)
        * cfg.conv_taps ** -0.5,
        "out_proj": jax.random.normal(ks[2], (n, D, D), pd) * D ** -0.5}


def init_params(key, cfg: HybridConfig) -> Dict[str, Any]:
    """Norms 1, projections normal over the square root of their fan-in,
    a mixer's own by its kind (``_mamba_params``, ``_conv_params``), every
    router bias 0 in float32."""
    pd, D = cfg.param_dtype, cfg.d_model

    def stack(kind, run, i):
        k, n = jax.random.fold_in(key, 100 + i), run.n_layers
        lay = _moe.init_params(k, run.replace(
            vocab_size=1, tied_head=False))["layers"]
        if cfg.qk_head_norm:
            lay = {**lay, "q_norm": jnp.ones((n, cfg.head_dim), pd),
                   "k_norm": jnp.ones((n, cfg.head_dim), pd)}
        mixer = {"mamba": _mamba_params, "conv": _conv_params}.get(
            _first(kind), lambda *_: {})(k, cfg, n)
        dense = _ll.init_params(jax.random.fold_in(k, 5), run.replace(
            vocab_size=1, d_ff=cfg.dense_d_ff))["layers"] \
            if _dense(kind) else None
        return _of_kind(lay, kind, cfg, mixer, dense)

    head = {} if cfg.tied_head else {"lm_head": jax.random.normal(
        jax.random.fold_in(key, 1), (D, cfg.vocab_size), pd) * D ** -0.5}
    return {"embed": jax.random.normal(jax.random.fold_in(key, 0),
                                       (cfg.vocab_size, D), pd) * 0.02,
            "layers": [stack(kind, run, i) for i, (kind, run) in enumerate(
                _run_configs(cfg))],
            "final_norm": jnp.ones((D,), pd), **head}


def num_params(cfg: HybridConfig) -> int:
    D, H = cfg.d_model, cfg.mamba_heads
    inner, conv_dim, proj = _mamba_sizes(cfg)
    first = {
        "attention": (D * cfg.n_heads * cfg.head_dim * 2
                      + 2 * D * cfg.n_kv_heads * cfg.head_dim
                      + 2 * cfg.head_dim * cfg.qk_head_norm),
        "mamba": (D * proj + (cfg.mamba_conv + 1) * conv_dim + 3 * H + inner
                  + inner * D),
        "conv": 3 * D * D + cfg.conv_taps * D + D * D,
        "experts": 0}       # a block that is its feed-forward alone
    each = len(_moe._matrices(cfg)) + 1            # matrices an expert
    experts = (D * cfg.n_experts + each * cfg.n_held * D * cfg.d_ff
               + each * D * cfg.shared_width
               + (cfg.n_experts if _moe._has_bias(cfg) else 0))
    total = cfg.vocab_size * D * (1 if cfg.tied_head else 2) + D
    for kind in cfg.kinds:
        one, two = halves(cfg, kind)
        ffn = 3 * D * cfg.dense_d_ff if _dense(kind) else experts
        total += (D + first[_first(kind)]) * one + (D + ffn) * two
    return total


def _causal_conv(x, w, b):
    """Depthwise causal convolution over the sequence: x [B, S, C],
    w [taps, C], b [C] -> out[t] = b + sum_j w[j] x[t - taps + 1 + j],
    float32 (x before the sequence is zero)."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = b.astype(jnp.float32)
    for j in range(taps):
        out = out + padded[:, j:j + s].astype(jnp.float32) \
            * w[j].astype(jnp.float32)
    return out


def _again(x):
    """x in float32, as a value of its own: x + 0 with a zero the compiler
    cannot see through. Two passes that compute the same float32 values
    from the same array would else share them, by way of memory."""
    return x.astype(jnp.float32) + jax.lax.optimization_barrier(
        jnp.zeros((), jnp.float32))


@jax.custom_vjp
def _conv_silu(x, w, b):
    """silu(conv(x) + b) in x's type: x [B, S, C], w [taps, C], b [C]."""
    return jax.lax.optimization_barrier(
        jax.nn.silu(_causal_conv(x, w, b)).astype(x.dtype))


def _conv_silu_fwd(x, w, b):
    return _conv_silu(x, w, b), (x, w, b)


def _conv_silu_bwd(res, g):
    """The pre-activation again from x; ``dpre`` ONCE, in x's type (the
    type jax's transposition gave each tap's product). ``dpre`` padded at
    the END and read at one shift a tap serves both dx, its transposed
    convolution, and dw[j] = sum over the rows of dpre[t + taps - 1 - j]
    x[t]; dw and db are float32 sums over every row."""
    x, w, b = res
    f32 = jnp.float32
    taps, s = w.shape[0], x.shape[1]
    pre = _causal_conv(x, w, b)
    sig = jax.nn.sigmoid(pre)
    dpre = (g.astype(f32) * sig * (1.0 + pre * (1.0 - sig))).astype(x.dtype)
    ahead = jnp.pad(dpre, ((0, 0), (0, taps - 1), (0, 0)))
    dx, dw = 0.0, []
    for j in range(taps):
        shifted = ahead[:, taps - 1 - j:taps - 1 - j + s].astype(f32)
        dx = dx + shifted * w[j].astype(f32)
        dw.append(jnp.sum(shifted * x.astype(f32), axis=(0, 1)))
    db = jnp.sum(dpre.astype(f32), axis=(0, 1))
    return (dx.astype(x.dtype), jnp.stack(dw).astype(w.dtype),
            db.astype(b.dtype))


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def _lanes(d_skip, inner: int):
    """d_skip [H] -> [H P] float32: a head's D on each of its lanes."""
    return jnp.repeat(d_skip.astype(jnp.float32), inner // d_skip.shape[0])


def _gated(y, xs, z, d_skip):
    """(y + D xs) silu(z), float32: y, xs, z [B, S, H P], d_skip [H]."""
    f32 = jnp.float32
    y = y.astype(f32) + xs.astype(f32) * _lanes(d_skip, y.shape[-1])
    return y * jax.nn.silu(z.astype(f32))


def _group_mean(a, groups: int):
    """The mean of a [B, S, H P] float32 over each group's lanes, on every
    lane of the group ([B, S, 1] for the one group: it broadcasts). By
    group it is two products with a 0/1 matrix, as a head's step reaches
    its lanes in ``ops/ssd.py``: a reshape to [B, S, G, lanes] and a
    broadcast back are written out as float32 arrays of their own (three
    relayout copies of 268 MB a mixer block at the cell's shape)."""
    if groups == 1:
        return jnp.mean(a, axis=-1, keepdims=True)
    lanes = a.shape[-1] // groups
    return _over_lanes(_per_head(a, groups) * (1.0 / lanes), lanes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gated_norm(y, xs, z, d_skip, gate_norm, eps, groups=1):
    """The skip, the gate and the RMS norm over all H P, or over each of
    ``groups`` groups' lanes apart: rms_norm((y + D xs) silu(z))
    gate_norm, [B, S, H P] in y's type."""
    v = _gated(y, xs, z, d_skip)
    r = jax.lax.rsqrt(_group_mean(v * v, groups) + eps)
    return jax.lax.optimization_barrier(
        (v * r).astype(y.dtype) * gate_norm.astype(y.dtype))


def _gated_norm_fwd(y, xs, z, d_skip, gate_norm, eps, groups):
    return (_gated_norm(y, xs, z, d_skip, gate_norm, eps, groups),
            (y, xs, z, d_skip, gate_norm))


def _gated_norm_bwd(eps, groups, res, g):
    """Two passes over the rows. The first leaves a row's two scalars:
    r = rsqrt(mean v^2 + eps) and m = mean(dn v), [B, S, 1] float32. The
    (a group's two, on the group's lanes, where the norm is by group). The
    second computes v again (from values ``_again`` sets apart: shared with
    the first pass they would be written out in float32) and writes dy,
    dxs, dz in their own types and the sums d d_skip [H], d gate_norm
    [H P], float32 over every row: with n = v r, dv = r (dn - n m r)."""
    y, xs, z, d_skip, gate_norm = res
    f32, dt_ = jnp.float32, y.dtype
    scale = gate_norm.astype(f32)
    v = _gated(y, xs, z, d_skip)
    r = jax.lax.rsqrt(_group_mean(v * v, groups) + eps)
    m = _group_mean(g.astype(f32) * scale * v, groups)
    y, xs, z, g = (_again(t) for t in (y, xs, z, g))
    skip = _lanes(d_skip, y.shape[-1])
    y = y + xs * skip
    gate = jax.nn.sigmoid(z)
    n = y * (z * gate) * r
    dv = r * (g * scale - n * (m * r))
    dy = dv * (z * gate)
    dz = dv * y * gate * (1.0 + z * (1.0 - gate))
    d_gate = jnp.sum(g * n.astype(dt_).astype(f32), axis=(0, 1))
    d_d = jnp.sum(dy * xs, axis=(0, 1)).reshape(d_skip.shape[0], -1).sum(-1)
    # results of THIS pass, not terms of their consumers' fusions
    return jax.lax.optimization_barrier(
        (dy.astype(dt_), (dy * skip).astype(dt_), dz.astype(dt_),
         d_d.astype(d_skip.dtype), d_gate.astype(gate_norm.dtype)))


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def _gate_conv(bcu, w):
    """(c, v) float32: v = B u [B, S, D] and its causal convolution c[t] =
    sum_j w[j] v[t - taps + 1 + j], from bcu [B, S, B | C | u] and the taps
    w [taps, D]. B and u are padded in their own type and multiplied at
    each shift: no float32 array but c and v is made."""
    f32 = jnp.float32
    taps, (s, D) = w.shape[0], (bcu.shape[1], w.shape[1])
    front = ((0, 0), (taps - 1, 0), (0, 0))
    gate, u = jnp.pad(bcu[..., :D], front), jnp.pad(bcu[..., 2 * D:], front)
    c = 0.0
    for j in range(taps):       # the last shift is v itself
        v = gate[:, j:j + s].astype(f32) * u[:, j:j + s].astype(f32)
        c = c + v * w[j].astype(f32)
    return c, v


@jax.custom_vjp
def _gated_conv(bcu, w):
    """The short convolution between its two projections, ONE pass over
    [B, S, 3 D] -> [B, S, D] in bcu's type with float32 sums: C conv(B u),
    no bias, no activation."""
    D = w.shape[1]
    c, _ = _gate_conv(bcu, w)
    return jax.lax.optimization_barrier(
        (bcu[..., D:2 * D].astype(jnp.float32) * c).astype(bcu.dtype))


def _gated_conv_fwd(bcu, w):
    return _gated_conv(bcu, w), (bcu, w)


def _gated_conv_bwd(res, g):
    """c and v again from bcu. dC = g c; dc = g C ONCE, in bcu's type,
    padded at the END and read at one shift a tap for both dv, its
    transposed convolution, and dw[j] = sum over the rows of
    dc[t + taps - 1 - j] v[t] (``_conv_silu_bwd``'s walk); dB = dv u,
    du = dv B. The three gradients leave as ONE [B, S, 3 D] array in
    bcu's type, which is what the in-projection's two gradient products
    read; dw is a float32 sum over every row."""
    bcu, w = res
    f32 = jnp.float32
    taps, (s, D) = w.shape[0], (bcu.shape[1], w.shape[1])
    c, v = _gate_conv(bcu, w)
    gate = bcu[..., D:2 * D].astype(f32)
    dc = (g.astype(f32) * gate).astype(bcu.dtype)
    ahead = jnp.pad(dc, ((0, 0), (0, taps - 1), (0, 0)))
    dv, dw = 0.0, []
    for j in range(taps):
        shifted = ahead[:, taps - 1 - j:taps - 1 - j + s].astype(f32)
        dv = dv + shifted * w[j].astype(f32)
        dw.append(jnp.sum(shifted * v, axis=(0, 1)))
    d_bcu = jnp.concatenate([
        (dv * bcu[..., 2 * D:].astype(f32)).astype(bcu.dtype),
        (g.astype(f32) * c).astype(bcu.dtype),
        (dv * bcu[..., :D].astype(f32)).astype(bcu.dtype)], axis=-1)
    # results of THIS pass, not terms of their consumers' fusions
    return jax.lax.optimization_barrier(
        (d_bcu, jnp.stack(dw).astype(w.dtype)))


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def conv_plan(cfg: HybridConfig, B: int, S: int) -> dict:
    """The short convolution's own account of its one elementwise pass
    (the attributes of ``mixer.plan`` for a "conv" layer): every operand
    read once and every result written once, in bytes, forward (B, C, u ->
    y) and backward (B, C, u, g -> dB, dC, du; the taps' gradient is
    [taps, D])."""
    rows, item = B * S, jnp.dtype(cfg.dtype).itemsize
    wide = rows * cfg.d_model * item
    return {"path": "rules", "kind": "conv", "rows": rows,
            "taps": cfg.conv_taps, "channels": cfg.d_model,
            "residual_bytes": 0 if cfg.remat else 3 * wide,
            "hbm_bytes_fwd": 4 * wide, "hbm_bytes_bwd": 7 * wide}


def short_conv_half(x, lp, cfg: HybridConfig):
    """The gated short convolution half of a block: x [B, S, D] -> x + its
    operator's output (the module docstring has the equations)."""
    B, S, _ = x.shape
    dt_ = cfg.dtype
    tracing.plan("mixer.plan", conv_plan(cfg, B, S))
    with jax.named_scope("short_conv"):
        u = _ll.rms_norm(x, lp["mix_norm"], cfg.norm_eps)
        with jax.named_scope("in_proj"):
            # kept across the layer checkpoint where the step's memory has
            # room
            bcu = checkpoint_name(u @ _ll._dq(lp["in_proj"], dt_),
                                  MIX_OFFERED)
        with jax.named_scope("gated_conv"):
            y = _gated_conv(bcu, lp["conv_w"])
        with jax.named_scope("out_proj"):
            y = y @ _ll._dq(lp["out_proj"], dt_)
        return _ll._residual(x, y, cfg)


def plan(cfg: HybridConfig, B: int, S: int) -> dict:
    """The rules' own account of a mixer's elementwise passes (also the
    attributes of ``mixer.plan``): every operand of a pass read once and
    every result written once, in bytes, for one forward and for the
    backward; and what the rules keep beyond the layer's input: nothing
    under the layer checkpoint (the forward runs again), else the
    convolution's input, x, y and z in the activations' type and the
    steps."""
    rows, item = B * S, jnp.dtype(cfg.dtype).itemsize
    inner, conv_dim, _ = _mamba_sizes(cfg)
    wide, conv = rows * inner * item, rows * conv_dim * item
    steps = rows * cfg.mamba_heads * 4
    return {
        "path": "rules", "rows": rows, "groups": cfg.mamba_groups,
        "group_lanes": inner // cfg.mamba_groups,
        "chunk": min(cfg.mamba_chunk, S), "channels": conv_dim,
        "residual_bytes": 0 if cfg.remat else _rule_residual_bytes(cfg, rows),
        # xBC -> its activation; x, dt -> u and the sums twice; y, x, z ->
        # a row's scalar, the three again -> the normed rows
        "hbm_bytes_fwd": 2 * conv + (2 * wide + 3 * steps) + 7 * wide,
        # g, y, x, z -> a row's scalars; the four again -> dy, dx, dz;
        # du, x -> d dt (with the sums' gradients), du, dt -> dx;
        # x, g -> dpre; dpre, x -> dx, d conv_w
        "hbm_bytes_bwd": 11 * wide + (4 * wide + 4 * steps) + 6 * conv}


def _segment_multipliers(cfg):
    """[z | xBC | dt]'s multiplier a column, where the config states one a
    SEGMENT (``ssm_multipliers``: z, x, B, C, dt; models/falcon.py) and one
    ahead of the projection (``ssm_in_multiplier``, folded in: a constant
    times a constant); None for a config that states neither."""
    per = getattr(cfg, "ssm_multipliers", None)
    if per is None:
        return None
    inner, n = cfg.mamba_inner, cfg.mamba_state * cfg.mamba_groups
    by = np.repeat(np.asarray(per, np.float64),
                   (inner, inner, n, n, cfg.mamba_heads))
    return jnp.asarray(by * cfg.ssm_in_multiplier, jnp.float32)


def scan_inputs(u, lp, cfg: HybridConfig):
    """The mixer's normed input u [B, S, D] -> (z [B, S, H P], x
    [B, S, H P] after the convolution, what ``ssd_scan`` takes: x
    [B, S, H, P], the steps dt [B, S, H] float32 after their softplus, A
    [H] float32, B and C [B, S, N] or, in groups, [B, S, G, N])."""
    B, S, _ = u.shape
    N, G = cfg.mamba_state, cfg.mamba_groups
    inner, conv_dim, _ = _mamba_sizes(cfg)
    dt_, f32 = cfg.dtype, jnp.float32
    with jax.named_scope("in_proj"):
        zxbcdt = u @ _ll._dq(lp["in_proj"], dt_)
    by = _segment_multipliers(cfg)
    if by is not None:      # the product's epilogue, float32 constants
        with jax.named_scope("segments"):
            zxbcdt = (zxbcdt.astype(f32) * by).astype(dt_)
    # kept across the layer checkpoint where the step's memory has room
    zxbcdt = checkpoint_name(zxbcdt, MIX_OFFERED)
    z, step = zxbcdt[..., :inner], zxbcdt[..., inner + conv_dim:]
    # the heads' channels and B | C, convolved apart: x is an array of its
    # own in the passes below (a slice of the joint one splits them in two)
    with jax.named_scope("conv"):
        xs = _conv_silu(zxbcdt[..., inner:2 * inner],
                        lp["conv_w"][:, :inner], lp["conv_b"][:inner])
        bc = _conv_silu(zxbcdt[..., 2 * inner:inner + conv_dim],
                        lp["conv_w"][:, inner:], lp["conv_b"][inner:])
    bm, cm = bc[..., :G * N], bc[..., G * N:]
    if G > 1:                       # a group's B and C: [B, S, G, N]
        bm, cm = bm.reshape(B, S, G, N), cm.reshape(B, S, G, N)
    with jax.named_scope("scan"):
        step = jax.nn.softplus(step.astype(f32) + lp["dt_bias"].astype(f32))
        heads = xs.reshape(B, S, cfg.mamba_heads, cfg.mamba_head_dim)
        a = -jnp.exp(lp["a_log"].astype(f32))
    return z, xs, (heads, step, a, bm, cm)


def mixer_half(x, lp, cfg: HybridConfig, kind: str, mesh=None, normed=None):
    """A block's mixer by its kind: the Mamba-2 half, x [B, S, D] -> x +
    its mixer's output (the module docstring has the equations), or the
    gated short convolution (``short_conv_half``). A block of two first
    halves (``llama._layer``) hands its one normed input as ``normed``: the
    half then norms nothing and adds nothing, its result is the mixer's
    output alone."""
    if _first(kind) == "conv":
        assert normed is None, kind
        return short_conv_half(x, lp, cfg)
    assert _first(kind) == "mamba" or normed is not None, kind
    if cfg.ssd_impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "ssd_impl='pallas' runs on one device: GSPMD cannot partition "
            "the Mosaic scan, and the mixer has no shard_map of its own yet "
            f"(mesh {dict(mesh.shape)}); use ssd_impl='xla' on a mesh")
    B, S, _ = x.shape
    G, inner, dt_ = cfg.mamba_groups, cfg.mamba_inner, cfg.dtype
    tracing.plan("mixer.plan", plan(cfg, B, S))
    u = _ll.rms_norm(x, lp["mix_norm"], cfg.norm_eps) if normed is None \
        else normed
    z, xs, scan = scan_inputs(u, lp, cfg)
    # the prologue and the kernel calls (``ssd.fwd.pallas``)
    with jax.named_scope("scan"):
        y = ssd_scan(*scan, chunk=min(cfg.mamba_chunk, S), impl=cfg.ssd_impl)
    with jax.named_scope("gated_norm"):
        y = _gated_norm(y.reshape(B, S, inner), xs, z, lp["d_skip"],
                        lp["gate_norm"], cfg.norm_eps, G)
    with jax.named_scope("out_proj"):
        y = y @ _ll._dq(lp["out_proj"], dt_)
    return y if normed is not None else _ll._residual(x, y, cfg)


def feed_forward(h, lp, cfg: HybridConfig, mesh=None, rules=None, tp=None,
                 kind=None):
    """A layer's feed-forward by its kind: the expert layer, or for a
    leading dense layer the SwiGLU of ``dense_d_ff`` (scope ``dense``),
    which reports nothing."""
    if kind is not None and _dense(kind):
        with jax.named_scope("dense"):
            return _ll.feed_forward(h, lp, cfg, mesh=mesh, rules=rules, tp=tp)
    return _moe.feed_forward(h, lp, cfg, mesh=mesh, rules=rules, tp=tp,
                             kind=kind)


forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn

# what the hybrids supply to the shared layer: the routes kept, the rows in
# expert order and the router losses are the expert model's
FAMILY = _moe.FAMILY.replace(
    "hybrid", feed_forward=feed_forward, remat_offered=REMAT_OFFERED,
    remat_saved_bytes=remat_saved_bytes, remat_offers=remat_offers,
    layer_runs=layer_runs, mixer_half=mixer_half,
    mixer_backward_bytes=mixer_backward_bytes, halves=halves, routes=routes,
    layer_plan_says=layer_plan_says)
