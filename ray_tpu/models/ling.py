"""Kimi-Delta-Attention / latent-attention hybrid with group-limited sparse
experts, as Ling 3.0 has it (inclusionAI ``bailing_hybrid``: the language
model of Ling-3.0-flash): five layers whose first half is Kimi Delta
Attention (arXiv:2510.26692, as ``fla``'s ``KimiDeltaAttention`` states it)
to one of latent (MLA) attention, two leading layers with a dense SwiGLU,
the rest with 8 of 512 sigmoid-routed experts chosen inside the 4 best of 8
expert groups, one shared expert beside them.

This module is only what differs from ``models/latent.py``, and it is
built FROM that family's record (``latent.FAMILY.replace``): the config (a
layer's kind by ``layer_group_size``, the KDA sizes), the parameter tree,
the KDA half (``mixer_half``), which first half and which feed-forward a
layer of which kind has (``halves``, ``feed_forward``), what a KDA half
offers the layer checkpoint, and one counter. The latent half is
``latent.attention_half`` WITHOUT a query latent (``q_rank`` 0), with value
heads narrower than the query/key heads (``v_dim`` 128 beside 128 + 64:
padded up to the kernels' one width) and a head-wise sigmoid gate on its
output (``attn_gate``); the expert layer is ``moe.feed_forward`` with
``n_group`` 8 and ``topk_group`` 4 (``moe.kept_groups``); the router's
bias rule, the loss's terms, embedding, the loop over runs of layers, remat,
the head and the cross-entropy are the latent family's and llama's.

Layer ``l`` attends through latents iff ``(l + 1) % layer_group_size == 0``,
else its first half is KDA; its feed-forward is dense iff ``l < n_dense``. A
layer's kind is "kda" | "mla", with ".dense" after it for a dense
feed-forward: ``layer_runs`` gives the stacks (``params["layers"]`` is a
LIST, one stack a run of adjacent layers of a kind).

The KDA half, h the normed input [B, S, D], H heads of dk = dv =
``kda_head_dim``, every array kept [B, S, H x dk] as the projections write
it and the scan reads it (a head a lane tile):

    q, k, v  = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))
                                  causal, depthwise, ``conv_taps`` taps
                                  (``hybrid._conv_silu``), no bias
    q_h, k_h = q_h / |q_h|, k_h / |k_h|       L2 a head (sum of squares +
                                  1e-6), q scaled dk^-1/2
    g        = L sigmoid(exp(A_log_h) (h Wf + dt_bias))   float32, L =
                                  ``kda_lower_bound`` < 0: the safe gate,
                                  g in (L, 0) a step and key channel
    beta     = sigmoid(h Wb)      [H], float32
    o        = gated_delta_rule(q, k, v, g, beta)     ops/delta_rule.py
    out      = (rms_head(o) * sigmoid(h Wg)_h) Wo     a norm a head with one
                                  scale [dv], ONE gate a head

No rotary: the recurrence carries position. The head's sums and the way
back to its lanes are products with a 0/1 matrix (``ops/ssd.py``), the norm
of the scan's output is ``sala._head_norm``. The kernel path
(``kda_impl`` "pallas") runs on one device.
"""

from __future__ import annotations

import dataclasses
import itertools
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import latent as _lt
from ray_tpu.models import llama as _ll
from ray_tpu.models import moe as _moe
from ray_tpu.models.hybrid import _conv_silu
from ray_tpu.models.sala import _head_norm
from ray_tpu.ops.delta_rule import CHUNK, RESIDUALS, gated_delta_rule
from ray_tpu.ops.ssd import _over_lanes, _per_head
from ray_tpu.util import tracing

_DENSE = ".dense"       # ends the kind of a layer with a dense feed-forward
# checkpoint_name tags of what a KDA half offers the layer checkpoint where
# the step's memory has room, in the order taken: q, k and v as the scan
# takes them (after the convolution, the activation and the norm), the
# gate, then the scan's output and the chunks' incoming states (kept
# TOGETHER they spare the replay the forward call)
KDA_OFFERED = ("kda_q", "kda_k", "kda_v", "kda_gate") + RESIDUALS
REMAT_OFFERED = _ll.FFN_OFFERED + _moe.SHARED_OFFERED + KDA_OFFERED
RULE_LEAVES = _lt.RULE_LEAVES


def _first(kind: str) -> str:
    return kind.split(".")[0]


def _dense(kind: str) -> bool:
    return kind.endswith(_DENSE)


@dataclass(frozen=True)
class LingConfig(_lt.LatentConfig):
    """``n_heads`` heads in both halves; ``d_ff`` the width of ONE routed
    expert, ``dense_d_ff`` the leading layers'. ``n_layers`` layers of the
    published model from its first on."""
    q_rank: int = 0                     # no query latent
    attn_gate: bool = True
    n_mtp: int = 0
    n_dense: int = 2
    layer_group_size: int = 6
    kda_head_dim: int = 16
    conv_taps: int = 4
    kda_lower_bound: float = -5.0
    kda_impl: str = "xla"               # "xla" | "pallas"

    def __post_init__(self):
        super().__post_init__()
        if self.n_mtp or self.index_heads:
            raise NotImplementedError(
                "a prediction module or an indexer in a KDA hybrid: no key "
                "of the source states either")

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(
            ("mla" if (i + 1) % self.layer_group_size == 0
             else "kda") + (_DENSE if i < self.n_dense else "")
            for i in range(self.n_layers))

    @property
    def kda_width(self) -> int:
        return self.n_heads * self.kda_head_dim

    def replace(self, **kw) -> "LingConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, LingConfig] = {
    # the CPU tests' size: two leading dense KDA layers, then a period cut
    # to four (kda, kda, mla by a group of 5, kda); 2 heads of 16 beside
    # latent heads of 16 + 8 over values of 16; 32 experts in 4 groups, a
    # token keeps 2 groups and 2 experts, 4 of group 1's 8 experts held
    "tiny": LingConfig(
        vocab_size=256, d_model=64, n_layers=6, n_heads=2, n_kv_heads=2,
        d_ff=32, dense_d_ff=96, shared_d_ff=32, max_seq_len=128,
        norm_eps=1e-6, kv_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
        n_experts=32, top_k=2, n_group=4, topk_group=2,
        experts_held=(4, 8), route_scale=2.5, layer_group_size=5,
        kda_head_dim=16),
}


def layer_runs(cfg: LingConfig) -> List[Tuple[str, int]]:
    """[(kind, how many adjacent layers of it), ...] in the layers' order;
    a run holds ``run_layers`` layers at most (0: all)."""
    most = cfg.run_layers or cfg.n_layers
    return [(kind, min(most, n - at))
            for kind, n in ((k, len(list(g)))
                            for k, g in itertools.groupby(cfg.kinds))
            for at in range(0, n, most)]


def halves(cfg: LingConfig, kind) -> Tuple[str, bool]:
    """A block's first half by its kind, and its feed-forward."""
    return ("attention" if _first(kind) == "mla" else "mixer"), True


def routes(cfg: LingConfig, kind) -> bool:
    return not _dense(kind)


# --- the parameter tree ------------------------------------------------------


def _kda_specs():
    L = ("layers",)
    wide = L + ("embed", "heads")
    return {"wq": wide, "wk": wide, "wv": wide, "w_decay": wide,
            "conv_q": L + (None, "heads"), "conv_k": L + (None, "heads"),
            "conv_v": L + (None, "heads"), "a_log": L + (None,),
            "dt_bias": L + ("heads",), "w_beta": L + ("embed", None),
            "w_out_gate": L + ("embed", None), "o_norm": L + (None,),
            "wo": L + ("heads", "embed")}


def _kda_params(key, cfg: LingConfig, n: int):
    """Projections normal over the square root of their fan-in, the taps
    uniform within the square root of their number (torch's Conv1d), the
    output norm 1; ``a_log`` the log of a rate drawn in [1, 16) and
    ``dt_bias`` the inverse softplus of a step drawn log-uniform in [1e-3,
    1e-1], both as ``fla``'s layer draws them, float32."""
    pd, D, W, H = cfg.param_dtype, cfg.d_model, cfg.kda_width, cfg.n_heads
    ks = iter(jax.random.split(key, 12))

    def dense(shape):
        return jax.random.normal(next(ks), (n,) + shape, pd) * shape[0] ** -0.5

    def taps():
        return jax.random.uniform(
            next(ks), (n, cfg.conv_taps, W), pd, -1.0, 1.0) \
            * cfg.conv_taps ** -0.5

    step = jnp.exp(jax.random.uniform(
        next(ks), (n, W), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {"wq": dense((D, W)), "wk": dense((D, W)), "wv": dense((D, W)),
            "w_decay": dense((D, W)),
            "conv_q": taps(), "conv_k": taps(), "conv_v": taps(),
            "a_log": jnp.log(jax.random.uniform(
                next(ks), (n, H), jnp.float32, 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "w_beta": dense((D, H)), "w_out_gate": dense((D, H)),
            "o_norm": jnp.ones((n, cfg.kda_head_dim), pd),
            "wo": dense((W, D))}


def _run(cfg: LingConfig, n: int) -> LingConfig:
    """The config that makes a stack of ``n`` layers of one kind."""
    return cfg.replace(n_layers=n, n_dense=0)


def _stack(kind: str, run: LingConfig, latent_stack, kda_leaves):
    """A stack's tree (specs or parameters): the latent family's stack of
    the kind's feed-forward as it is for an MLA layer, with the latent
    half's leaves replaced by a KDA half's for a KDA layer."""
    lay = latent_stack("dense" if _dense(kind) else "sparse", run)
    if _first(kind) == "mla":
        return lay
    return {**{k: v for k, v in lay.items() if k not in _lt._mla_specs(run)},
            **kda_leaves()}


def _stack_specs(kind: str, run: LingConfig):
    return _stack(kind, run, _lt._stack_specs, _kda_specs)


def _stack_params(key, kind: str, run: LingConfig):
    return _stack(
        kind, run, lambda ffn, r: _lt._stack_params(key, ffn, r),
        lambda: _kda_params(jax.random.fold_in(key, 13), run, run.n_layers))


def param_specs(cfg: LingConfig) -> Dict[str, Any]:
    return {"embed": ("vocab", "embed"), "final_norm": ("embed_nr",),
            "lm_head": ("embed", "vocab"),
            "layers": [_stack_specs(kind, _run(cfg, n))
                       for kind, n in layer_runs(cfg)]}


def init_params(key, cfg: LingConfig) -> Dict[str, Any]:
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


def num_params(cfg: LingConfig) -> int:
    D, H, W = cfg.d_model, cfg.n_heads, cfg.kda_width
    mla = (D * H * cfg.head_dim + D * H
           + D * (cfg.kv_rank + cfg.qk_rope_dim) + cfg.kv_rank
           + cfg.kv_rank * H * (cfg.qk_nope_dim + cfg.v_dim)
           + H * cfg.v_dim * D)
    kda = (4 * D * W + 3 * cfg.conv_taps * W + H + W + 2 * D * H
           + cfg.kda_head_dim + W * D)
    sparse = (2 * D + D * cfg.n_experts + cfg.n_experts
              + 3 * cfg.n_held * D * cfg.d_ff + 3 * D * cfg.shared_d_ff)
    dense = 2 * D + 3 * D * cfg.dense_d_ff
    return 2 * cfg.vocab_size * D + D + sum(
        (mla if _first(kind) == "mla" else kda)
        + (dense if _dense(kind) else sparse) for kind in cfg.kinds)


# --- what the layer checkpoint is told ---------------------------------------


def _kda_bytes(cfg: LingConfig, rows: int) -> Dict[str, int]:
    """Bytes of each of ``KDA_OFFERED`` over ``rows`` tokens of a layer."""
    wide = rows * cfg.kda_width
    item = jnp.dtype(cfg.dtype).itemsize
    chunks = -(-rows // CHUNK)
    return {"kda_q": wide * item, "kda_k": wide * item, "kda_v": wide * item,
            "kda_gate": wide * 4, "kda_out": wide * item,
            "kda_states": chunks * cfg.kda_width * cfg.kda_head_dim * 4}


def remat_saved_bytes(cfg: LingConfig, kind, rows: int) -> int:
    return 0 if _dense(kind) else _moe.remat_saved_bytes(cfg, kind, rows)


def remat_offers(cfg: LingConfig, kind, rows: int):
    """What a block of ``kind`` offers the layer checkpoint: its dense
    SwiGLU's gate and up or its shared expert's, then a KDA half's own
    (the states only on the kernel path, which names them)."""
    item = jnp.dtype(cfg.dtype).itemsize
    ffn = tuple((name, rows * cfg.dense_d_ff * item)
                for name in _ll.FFN_OFFERED) if _dense(kind) \
        else _moe.remat_offers(cfg, kind, rows)
    if _first(kind) == "mla":
        return ffn
    own = _kda_bytes(cfg, rows)
    names = KDA_OFFERED if cfg.kda_impl == "pallas" else KDA_OFFERED[:4]
    return ffn + tuple((name, own[name]) for name in names)


def mixer_backward_bytes(cfg: LingConfig, kind, rows: int) -> int:
    """Bytes a KDA half's backward holds beside its matrices' products and
    their gradients (``remat._step_estimate`` counts those from the
    leaves): the three convolutions' residuals and one float32 pass over
    each, q, k, v and the scan's output with their gradients, the gate and
    its gradient in float32, the chunks' incoming states."""
    own = _kda_bytes(cfg, rows)
    item = jnp.dtype(cfg.dtype).itemsize
    return (3 * rows * cfg.kda_width * (item + 4)
            + 2 * (own["kda_q"] + own["kda_k"] + own["kda_v"]
                   + own["kda_out"] + own["kda_gate"]) + own["kda_states"])


# --- the KDA half ------------------------------------------------------------


def kda_plan(cfg: LingConfig, B: int, S: int) -> dict:
    """What a traced KDA half says of its row work (instant
    ``kda.half_plan``; the scan says its own, ``kda.plan``): the sizes, and
    the HBM bytes of the passes round the scan, every operand read once and
    every result written once, the projections' other operands left out:
    three convolutions with their activation, two L2 norms, the gate, the
    output's norm and gate."""
    rows, W = B * S, cfg.kda_width
    item = jnp.dtype(cfg.dtype).itemsize
    return {"S": S, "heads": cfg.n_heads, "head_dim": cfg.kda_head_dim,
            "taps": cfg.conv_taps, "lower_bound": cfg.kda_lower_bound,
            "path": cfg.kda_impl,
            "row_bytes_fwd": rows * W * (3 * 2 * item + 2 * 2 * item
                                         + item + 4 + 2 * item)}


def _l2_heads(x, heads: int, scale: float = 1.0):
    """x [B, S, heads x dk] -> every head of it over its L2 norm (the root
    of the sum of squares + 1e-6) times ``scale``, in x's type, the rows
    left as they lie (``sala._head_norm`` says why)."""
    f = x.astype(jnp.float32)
    r = jax.lax.rsqrt(_per_head(f * f, heads) + 1e-6) * scale
    return (f * _over_lanes(r, x.shape[-1] // heads)).astype(x.dtype)


def decay_gate(f, a_log, dt_bias, lower_bound: float, width: int):
    """The safe gate: f [B, S, H x dk] (h Wf), a_log [H], dt_bias [H x dk]
    -> g = lower_bound x sigmoid(exp(a_log_h) (f + dt_bias)) float32, in
    (lower_bound, 0)."""
    rate = jnp.repeat(jnp.exp(a_log.astype(jnp.float32)), width)
    return lower_bound * jax.nn.sigmoid(
        rate * (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)))


def scan_inputs(h, lp, cfg: LingConfig):
    """The normed input h [B, S, D] -> what the scan takes of it: q, k, v
    [B, S, H x dk] in the config's type (after the convolution, the
    activation and the norm a head), g [B, S, H x dk] and beta [B, S, H]
    float32. Under the half's scopes ``proj`` (the products with the half's
    matrices) and, apart from them, ``conv`` (taps, activation, the norm a
    head) and ``gate``; q, k, v and g tagged as the scan takes them: kept
    across the layer checkpoint where the step's memory has room
    (``remat.remat_plan``)."""
    H, dk, dt = cfg.n_heads, cfg.kda_head_dim, cfg.dtype
    none = jnp.zeros((cfg.kda_width,), dt)
    with jax.named_scope("proj"):
        pq, pk, pv, pf, pb = (h @ _ll._dq(lp[n], dt) for n in (
            "wq", "wk", "wv", "w_decay", "w_beta"))
    with jax.named_scope("conv"):
        q, k, v = (_conv_silu(p, lp["conv_" + n], none)
                   for p, n in zip((pq, pk, pv), "qkv"))
        q = _l2_heads(q, H, dk ** -0.5)
        k = _l2_heads(k, H)
    with jax.named_scope("gate"):
        g = decay_gate(pf, lp["a_log"], lp["dt_bias"], cfg.kda_lower_bound,
                       dk)
        beta = jax.nn.sigmoid(pb.astype(jnp.float32))
    q, k, v, g = (checkpoint_name(t, name) for t, name in zip(
        (q, k, v, g), KDA_OFFERED))
    return q, k, v, g, beta


def mixer_half(x, lp, cfg: LingConfig, kind: str, mesh=None):
    """The KDA half of a block: x [B, S, D] -> x + its output (the module
    docstring has the equations)."""
    if cfg.kda_impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "kda_impl='pallas' runs on one device: GSPMD cannot partition "
            "the Mosaic scan, and the half has no shard_map of its own yet "
            f"(mesh {dict(mesh.shape)}); use kda_impl='xla' on a mesh")
    B, S, _ = x.shape
    H, dk, dt = cfg.n_heads, cfg.kda_head_dim, cfg.dtype
    tracing.plan("kda.half_plan", kda_plan(cfg, B, S))
    h = _ll.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope("kda"):
        q, k, v, g, beta = scan_inputs(h, lp, cfg)
        with jax.named_scope("proj"):
            pg = h @ _ll._dq(lp["w_out_gate"], dt)
        heads = lambda t: t.reshape(B, S, H, dk)               # noqa: E731
        with jax.named_scope("scan"):
            o = gated_delta_rule(heads(q), heads(k), heads(v), heads(g), beta,
                                 impl=cfg.kda_impl,
                                 lower_bound=cfg.kda_lower_bound)
        with jax.named_scope("out"):
            gate = jax.nn.sigmoid(pg.astype(jnp.float32))
            o = _head_norm(o.reshape(B, S, -1), lp["o_norm"], H, cfg.norm_eps)
            o = o * _over_lanes(gate, dk).astype(dt)
        with jax.named_scope("proj"):
            o = o @ _ll._dq(lp["wo"], dt)
    return _ll._residual(x, o, cfg)


def attention_half(x, lp, cfg: LingConfig, cos, sin, mesh=None, rules=None,
                   carried=None, kind=None):
    """The latent half of a block, ``latent.attention_half`` under a scope
    of its own (a trace tells the two first halves apart by ``mla`` and
    ``kda``)."""
    with jax.named_scope("mla"):
        return _lt.attention_half(x, lp, cfg, cos, sin, mesh=mesh,
                                  rules=rules, carried=carried, kind=kind)


# --- the feed-forward and the loss -------------------------------------------


def feed_forward(h, lp, cfg: LingConfig, mesh=None, rules=None, tp=None,
                 kind=None):
    """A dense layer's SwiGLU (scope ``dense``, as ``models/hybrid.py`` has
    it) or a sparse layer's experts, by ``kind``."""
    if _dense(kind):
        with jax.named_scope("dense"):
            return _ll.feed_forward(h, lp, cfg, mesh=mesh, rules=rules, tp=tp)
    return _moe.feed_forward(h, lp, cfg, mesh=mesh, rules=rules, tp=tp)


def finish_loss(loss, stats, cfg: LingConfig):
    """The latent family's terms and report, and beside them the share of
    the tokens whose kept groups include the held experts' group, the mean
    over the expert layers (``moe_group_kept_share``)."""
    loss, aux = _lt.finish_loss(loss, stats, cfg)
    if "group_kept" in stats:
        aux["moe_group_kept_share"] = stats["group_kept"].mean()
    return loss, aux


post_update = _moe.post_update
forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn

# what the family supplies to the shared layer: the latent family's record
# with a second kind of first half beside its attention
FAMILY = _lt.FAMILY.replace(
    "ling", feed_forward=feed_forward, remat_offered=REMAT_OFFERED,
    remat_saved=_moe.REMAT_SAVED, remat_saved_bytes=remat_saved_bytes,
    remat_offers=remat_offers, layer_runs=layer_runs, halves=halves,
    routes=routes, mixer_half=mixer_half,
    attention_half=attention_half,
    mixer_backward_bytes=mixer_backward_bytes, finish_loss=finish_loss)
