"""Kimi-Delta-Attention / gated grouped-query hybrid with sparse experts in
every layer, as Solar Open2 has it (upstage ``solar_open2``: the language
model of Solar-Open2-250B): three layers whose first half is Kimi Delta
Attention (arXiv:2510.26692, as ``fla``'s ``KimiDeltaAttention`` states it)
with its FIRST gate, which has no lower bound, and beta in (0, 2), to one of
grouped-query attention with no position table and an elementwise sigmoid
gate; every layer with 8 of 320 sigmoid-routed experts of one group and one
shared expert beside them, no dense layer.

This module is only what differs from ``models/moe.py``, and it is built
FROM that family's record (``moe.FAMILY.replace``): the config (a layer's
kind by ``gqa_layers``, the KDA sizes), the parameter tree, the KDA half
(``mixer_half``), what a KDA half offers the layer checkpoint and two
counters. The grouped-query half is ``llama._attention_half`` under the
kind "gqa" (``attn_kinds``: no rotary) with the gate's leaf
``w_attn_gate``; the expert layer is ``moe.feed_forward`` (``n_group`` 1);
the router's bias rule is ``moe.post_update``, the balance term
``moe._sequence_balance``; what a KDA half keeps for its backward, its L2
norm a head and its names for the checkpoint are ``models/ling.py``'s;
embedding, the loop over runs of layers, remat, the head and the
cross-entropy are llama's.

Layer ``l`` is "gqa" iff ``l in gqa_layers`` (the source lists 0, 4, 8, ..),
else "kda": ``layer_runs`` (``ling.layer_runs``) gives the stacks (``params["layers"]`` is a LIST,
one stack a run of adjacent layers of a kind). h the normed input [B, S, D];
every norm RMS.

The KDA half, H = ``kda_heads`` heads of dk = dv = ``kda_head_dim``, every
array kept [B, S, H x dk] as the projections write it and the scan reads it:

    q, k, v  = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))
                                  causal, depthwise, ``conv_taps`` taps, no
                                  bias
    q_h, k_h = q_h / |q_h|, k_h / |k_h|       L2 a head, q scaled dk^-1/2
    g        = -exp(A_log_h) softplus((h Wfa) Wfb + dt_bias)    float32, a
                                  value a step and KEY CHANNEL, any g <= 0:
                                  NO lower bound (``kda_use_full_proj``
                                  false: the pair D -> ``gate_rank`` -> H dk)
    beta     = 2 sigmoid(h Wb)    [H], float32 (``kda_allow_neg_eigval``:
                                  the eigenvalue of I - beta k k^T along k
                                  lies in (-1, 1))
    o        = gated_delta_rule(q, k, v, g, beta, lower_bound=None)
                                  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t)
                                  S_{t-1} + beta_t k_t v_t^T, o_t = S_t^T
                                  q_t, state float32 (ops/delta_rule.py:
                                  the cut of the pair products that needs no
                                  bound on g)
    out      = (rms_head(o) w sigmoid((h Wga) Wgb)) Wo          the scale w
                                  [dv] shared by the heads, the gate a
                                  CHANNEL [H dv] through its own low-rank
                                  pair (``fla``'s FusedRMSNormGated)

No rotary: the recurrence carries position. The grouped-query half:
``n_heads`` query heads over ``n_kv_heads`` key/value heads of
``head_width``, no table (``use_rope`` false), causal softmax at
head_width^-1/2, out = (attend(q, k, v) sigmoid(h Wg)) Wo with Wg [D, H x
head_width] (``use_gqa_gate``), no norm of q or k. The kernel path
(``kda_impl`` "pallas") runs on one device.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import ling as _ling
from ray_tpu.models import llama as _ll
from ray_tpu.models import moe as _moe
from ray_tpu.models.hybrid import _conv_silu
from ray_tpu.models.sala import _head_norm
from ray_tpu.ops.delta_rule import gated_delta_rule
from ray_tpu.util import tracing

KDA_OFFERED = _ling.KDA_OFFERED
REMAT_OFFERED = _moe.SHARED_OFFERED + KDA_OFFERED
RULE_LEAVES = _moe.RULE_LEAVES
_GQA = ("wq", "wk", "wv", "wo")         # llama's, which a KDA half replaces


@dataclass(frozen=True)
class SolarConfig(_moe.MoEConfig):
    """``n_heads`` over ``n_kv_heads`` heads of ``head_width`` in a
    grouped-query half, ``kda_heads`` of ``kda_head_dim`` in a KDA half;
    ``d_ff`` the width of ONE routed expert. ``n_layers`` layers of the
    published model from its first on."""
    router_score: str = "sigmoid"
    norm_topk: bool = True
    router_aux_weight: float = 0.0001   # of the sequence-wise balance loss
    router_z_weight: float = 0.0        # the sigmoid router has no z-loss
    rope: bool = False
    attn_kinds: Tuple[Tuple[str, _ll.AttentionKind], ...] = (
        ("gqa", _ll.AttentionKind(rope=False)),)
    gqa_layers: Tuple[int, ...] = (0,)
    kda_heads: int = 2
    kda_head_dim: int = 16
    conv_taps: int = 4
    gate_rank: int = 8                  # of the decay's and the output gate's
    kda_impl: str = "xla"               # "xla" | "pallas"

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple("gqa" if i in self.gqa_layers else "kda"
                     for i in range(self.n_layers))

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def replace(self, **kw) -> "SolarConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, SolarConfig] = {
    # the CPU tests' size: one period and a layer (gqa, kda, kda, kda, gqa);
    # 4 query heads over 2 KV heads of 16, 2 KDA heads of 16, pairs of rank
    # 8; 15 experts in one group, 2 a token, 5 held from the sixth on (no
    # power of two), one shared expert
    "tiny": SolarConfig(
        vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_width=16, d_ff=32, shared_d_ff=32, max_seq_len=128,
        norm_eps=1e-5, n_experts=15, top_k=2, experts_held=(5, 5),
        gqa_layers=(0, 4), kda_heads=2, kda_head_dim=16, gate_rank=8),
}


# [(kind, how many adjacent layers of it), ...] in the layers' order, a run
# of ``run_layers`` layers at most: the rule is Ling's, over ``cfg.kinds``
layer_runs = _ling.layer_runs


# --- the parameter tree ------------------------------------------------------


def _kda_specs():
    L = ("layers",)
    wide = L + ("embed", "heads")
    low = L + ("embed", None)
    return {"wq": wide, "wk": wide, "wv": wide,
            "w_decay_a": low, "w_decay_b": L + (None, "heads"),
            "conv_q": L + (None, "heads"), "conv_k": L + (None, "heads"),
            "conv_v": L + (None, "heads"), "a_log": L + (None,),
            "dt_bias": L + ("heads",), "w_beta": low,
            "w_gate_a": low, "w_gate_b": L + (None, "heads"),
            "o_norm": L + (None,), "wo": L + ("heads", "embed")}


def _kda_params(key, cfg: SolarConfig, n: int):
    """Projections normal over the square root of their fan-in, the taps
    uniform within the square root of their number (torch's Conv1d), the
    output norm 1; ``a_log`` the log of a rate drawn in [1, 16) and
    ``dt_bias`` the inverse softplus of a step drawn log-uniform in [1e-3,
    1e-1], both as ``fla``'s layer draws them, float32."""
    pd, D, W, H = cfg.param_dtype, cfg.d_model, cfg.kda_width, cfg.kda_heads
    R = cfg.gate_rank
    ks = iter(jax.random.split(key, 14))

    def dense(shape):
        return jax.random.normal(next(ks), (n,) + shape, pd) * shape[0] ** -0.5

    def taps():
        return jax.random.uniform(
            next(ks), (n, cfg.conv_taps, W), pd, -1.0, 1.0) \
            * cfg.conv_taps ** -0.5

    step = jnp.exp(jax.random.uniform(
        next(ks), (n, W), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {"wq": dense((D, W)), "wk": dense((D, W)), "wv": dense((D, W)),
            "w_decay_a": dense((D, R)), "w_decay_b": dense((R, W)),
            "conv_q": taps(), "conv_k": taps(), "conv_v": taps(),
            "a_log": jnp.log(jax.random.uniform(
                next(ks), (n, H), jnp.float32, 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "w_beta": dense((D, H)),
            "w_gate_a": dense((D, R)), "w_gate_b": dense((R, W)),
            "o_norm": jnp.ones((n, cfg.kda_head_dim), pd),
            "wo": dense((W, D))}


def _run(cfg: SolarConfig, n: int) -> SolarConfig:
    """The config that makes a stack of ``n`` layers of one kind."""
    return cfg.replace(n_layers=n, gqa_layers=())


def _stack(kind: str, lay: dict, gate, kda_leaves):
    """A stack's tree (specs or parameters): the expert model's stack with
    the gate's leaf beside the four projections for a "gqa" layer, with the
    four replaced by a KDA half's leaves for a "kda" layer."""
    if kind == "gqa":
        return {**lay, "w_attn_gate": gate()}
    return {**{k: v for k, v in lay.items() if k not in _GQA}, **kda_leaves()}


def _stack_specs(kind: str, run: SolarConfig):
    return _stack(kind, _moe.param_specs(run)["layers"],
                  lambda: ("layers", "embed", "heads"), _kda_specs)


def _stack_params(key, kind: str, run: SolarConfig):
    D, wide = run.d_model, run.n_heads * run.head_dim
    lay = _moe.init_params(key, run.replace(vocab_size=1))["layers"]
    return _stack(
        kind, lay,
        lambda: jax.random.normal(jax.random.fold_in(key, 11),
                                  (run.n_layers, D, wide), run.param_dtype)
        * D ** -0.5,
        lambda: _kda_params(jax.random.fold_in(key, 13), run, run.n_layers))


def param_specs(cfg: SolarConfig) -> Dict[str, Any]:
    return {"embed": ("vocab", "embed"), "final_norm": ("embed_nr",),
            "lm_head": ("embed", "vocab"),
            "layers": [_stack_specs(kind, _run(cfg, n))
                       for kind, n in layer_runs(cfg)]}


def init_params(key, cfg: SolarConfig) -> Dict[str, Any]:
    """Norms 1, projections normal over the square root of their fan-in,
    the embedding 0.02, every router bias 0 in float32; a KDA half's own
    leaves as ``_kda_params`` says."""
    pd, D = cfg.param_dtype, cfg.d_model
    ks = jax.random.split(key, 2)
    return {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, D), pd) * 0.02,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": jax.random.normal(ks[1], (D, cfg.vocab_size), pd)
        * D ** -0.5,
        "layers": [_stack_params(jax.random.fold_in(key, 100 + i), kind,
                                 _run(cfg, n))
                   for i, (kind, n) in enumerate(layer_runs(cfg))]}


def num_params(cfg: SolarConfig) -> int:
    D, H, W, R = cfg.d_model, cfg.kda_heads, cfg.kda_width, cfg.gate_rank
    wide = cfg.n_heads * cfg.head_dim
    gqa = 3 * D * wide + 2 * D * cfg.n_kv_heads * cfg.head_dim
    kda = (4 * D * W + 2 * (D * R + R * W) + D * H + 3 * cfg.conv_taps * W
           + H + W + cfg.kda_head_dim)
    experts = (2 * D + D * cfg.n_experts + cfg.n_experts
               + 3 * cfg.n_held * D * cfg.d_ff + 3 * D * cfg.shared_width)
    return 2 * cfg.vocab_size * D + D + sum(
        (gqa if kind == "gqa" else kda) + experts for kind in cfg.kinds)


# --- what the layer checkpoint is told ---------------------------------------


def remat_offers(cfg: SolarConfig, kind, rows: int):
    """What a block of ``kind`` offers the layer checkpoint: its shared
    expert's gate and up, then a KDA half's own (the states only on the
    kernel path, which names them)."""
    ffn = _moe.remat_offers(cfg, kind, rows)
    if kind == "gqa":
        return ffn
    own = _ling._kda_bytes(cfg, rows)
    names = KDA_OFFERED if cfg.kda_impl == "pallas" else KDA_OFFERED[:4]
    return ffn + tuple((name, own[name]) for name in names)


def mixer_backward_bytes(cfg: SolarConfig, kind, rows: int) -> int:
    """Bytes a KDA half's backward holds beside its matrices' products and
    their gradients (``remat._step_estimate`` counts those from the leaves,
    the three convolutions' among them: q, k, v, the decay's and the gate's
    projections at 64 heads ARE the layer's products): the chunks' incoming
    states, and the gate with its gradient in float32. At this model's
    widths the step's plan reads 12.17 GB with nothing kept where the
    estimate with ``ling.mixer_backward_bytes`` read 18.13 and kept nothing
    for want of room (a described v5e's compiler, PR 61)."""
    own = _ling._kda_bytes(cfg, rows)
    return own["kda_states"] + 2 * own["kda_gate"]


# --- the KDA half ------------------------------------------------------------


def kda_plan(cfg: SolarConfig, B: int, S: int) -> dict:
    """What a traced KDA half says of its row work (instant
    ``kda.half_plan``; the scan says its own, ``kda.plan``): the sizes, and
    the HBM bytes of the passes round the scan, every operand read once and
    every result written once, the projections' other operands left out:
    three convolutions with their activation, two L2 norms, the gate, the
    output's norm and its gate a channel."""
    rows, W = B * S, cfg.kda_width
    item = jnp.dtype(cfg.dtype).itemsize
    return {"S": S, "heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
            "taps": cfg.conv_taps, "gate_rank": cfg.gate_rank,
            "lower_bound": "none", "path": cfg.kda_impl,
            "row_bytes_fwd": rows * W * (3 * 2 * item + 2 * 2 * item
                                         + item + 4 + 3 * item)}


def decay_gate(f, a_log, dt_bias, width: int):
    """Kimi Linear's first gate: f [B, S, H x dk] ((h Wfa) Wfb), a_log [H],
    dt_bias [H x dk] -> g = -exp(a_log_h) softplus(f + dt_bias) float32:
    any value <= 0, no bound."""
    rate = jnp.repeat(jnp.exp(a_log.astype(jnp.float32)), width)
    return -rate * jax.nn.softplus(
        f.astype(jnp.float32) + dt_bias.astype(jnp.float32))


def scan_inputs(h, lp, cfg: SolarConfig):
    """The normed input h [B, S, D] -> what the scan takes of it: q, k, v
    [B, S, H x dk] in the config's type (after the convolution, the
    activation and the norm a head), g [B, S, H x dk] and beta [B, S, H]
    float32. Under the half's scopes ``proj``, ``conv`` and ``gate``, as a
    Ling half's; q, k, v and g tagged as the scan takes them."""
    H, dk, dt = cfg.kda_heads, cfg.kda_head_dim, cfg.dtype
    none = jnp.zeros((cfg.kda_width,), dt)
    with jax.named_scope("proj"):
        pq, pk, pv, pb = (h @ _ll._dq(lp[n], dt) for n in (
            "wq", "wk", "wv", "w_beta"))
        pf = (h @ _ll._dq(lp["w_decay_a"], dt)) @ _ll._dq(lp["w_decay_b"], dt)
    with jax.named_scope("conv"):
        q, k, v = (_conv_silu(p, lp["conv_" + n], none)
                   for p, n in zip((pq, pk, pv), "qkv"))
        q = _ling._l2_heads(q, H, dk ** -0.5)
        k = _ling._l2_heads(k, H)
    with jax.named_scope("gate"):
        g = decay_gate(pf, lp["a_log"], lp["dt_bias"], dk)
        beta = 2.0 * jax.nn.sigmoid(pb.astype(jnp.float32))
    q, k, v, g = (checkpoint_name(t, name) for t, name in zip(
        (q, k, v, g), KDA_OFFERED))
    return q, k, v, g, beta


def mixer_half(x, lp, cfg: SolarConfig, kind: str, mesh=None):
    """The KDA half of a block: x [B, S, D] -> (x + its output, what it
    reports: the least g of the layer, ``gate_min``). The module docstring
    has the equations."""
    if cfg.kda_impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "kda_impl='pallas' runs on one device: GSPMD cannot partition "
            "the Mosaic scan, and the half has no shard_map of its own yet "
            f"(mesh {dict(mesh.shape)}); use kda_impl='xla' on a mesh")
    B, S, _ = x.shape
    H, dk, dt = cfg.kda_heads, cfg.kda_head_dim, cfg.dtype
    tracing.plan("kda.half_plan", kda_plan(cfg, B, S))
    h = _ll.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("kda"):
        q, k, v, g, beta = scan_inputs(h, lp, cfg)
        with jax.named_scope("proj"):
            pg = (h @ _ll._dq(lp["w_gate_a"], dt)) \
                @ _ll._dq(lp["w_gate_b"], dt)
        heads = lambda t: t.reshape(B, S, H, dk)               # noqa: E731
        with jax.named_scope("scan"):
            o = gated_delta_rule(heads(q), heads(k), heads(v), heads(g), beta,
                                 impl=cfg.kda_impl, lower_bound=None)
        with jax.named_scope("out"):
            o = _head_norm(o.reshape(B, S, -1), lp["o_norm"], H, cfg.norm_eps)
            o = o * jax.nn.sigmoid(pg.astype(jnp.float32)).astype(dt)
        with jax.named_scope("proj"):
            o = o @ _ll._dq(lp["wo"], dt)
        with jax.named_scope("gate"):
            least = jax.lax.stop_gradient(jnp.min(g))
    return _ll._residual(x, o, cfg), {"gate_min": least}


# --- the loss ----------------------------------------------------------------


def finish_loss(loss, stats, cfg: SolarConfig):
    """loss = the cross-entropy + router_aux_weight x the sequence-wise
    balance term, from the expert layers' stacked statistics -> (loss,
    aux). ``aux`` carries the step's counts over all experts
    (``router_counts`` [layers, E]) for ``post_update``, what a share of
    the experts reports (``moe.held_aux``) and the least g of the model's
    first KDA layer (``kda_gate_min``)."""
    balance = stats["balance"].mean()
    aux = {"moe_main_loss": loss, "moe_aux_loss": balance,
           "router_counts": stats["counts"]}
    if "gate_min" in stats:         # a cut with a KDA layer in it
        aux["kda_gate_min"] = stats["gate_min"][0]
    if cfg.experts_held is not None:
        aux.update(_moe.held_aux(
            stats["held_counts"].astype(jnp.float32), stats,
            stats["experts"].shape[1] * cfg.top_k, cfg.n_experts))
    else:
        aux["moe_dropped"] = jnp.zeros((), jnp.int32)
    return loss + cfg.router_aux_weight * balance, aux


post_update = _moe.post_update
forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn

# what the family supplies to the shared layer: the expert model's record
# with a KDA half beside llama's attention half
FAMILY = _moe.FAMILY.replace(
    "solar", remat_offered=REMAT_OFFERED, remat_offers=remat_offers,
    layer_runs=layer_runs, mixer_half=mixer_half,
    mixer_backward_bytes=mixer_backward_bytes, finish_loss=finish_loss)
