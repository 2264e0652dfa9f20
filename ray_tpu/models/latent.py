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
and v of one width, so ``v_dim`` has to equal ``qk_nope_dim +
qk_rope_dim`` (GLM-4.7-Flash: 192 + 64 = 256).

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

Layers are a list of runs (``layer_runs``): ``n_dense`` leading layers
whose feed-forward is a SwiGLU of ``dense_d_ff``, then sparse layers
(``d_ff`` the width of ONE expert). The prediction module
(``params["mtp"]``) is one more sparse block of its own weights over
[rms_norm(x_last) ; rms_norm(embed[t+1])] W_eh, with its own final norm and
the model's embedding and head, trained on the token after the next.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

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
    # what one step moves a router's bias by (``post_update``)
    bias_rate: float = 0.001
    n_mtp: int = 1                      # prediction modules: 0 or 1
    mtp_weight: float = 0.3             # of the second cross-entropy
    router_aux_weight: float = 0.0001   # of the sequence-wise balance loss
    router_z_weight: float = 0.0        # the sigmoid router has no z-loss

    def __post_init__(self):
        if self.v_dim != self.qk_nope_dim + self.qk_rope_dim:
            raise NotImplementedError(
                f"value heads of {self.v_dim} beside query/key heads of "
                f"{self.qk_nope_dim} + {self.qk_rope_dim}: the attention "
                "kernels take q, k and v of one width")
        if self.n_mtp not in (0, 1) or not 0 <= self.n_dense < self.n_layers:
            raise ValueError(f"n_mtp {self.n_mtp}, n_dense {self.n_dense} of "
                             f"{self.n_layers} layers")

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
}

REMAT_SAVED = _moe.REMAT_SAVED
expert_rows = _moe.expert_rows


def remat_saved_bytes(cfg: "LatentConfig", kind, rows: int) -> int:
    return 0 if kind == "dense" else _moe.remat_saved_bytes(cfg, kind, rows)


def remat_offers(cfg: "LatentConfig", kind, rows: int):
    return () if kind == "dense" else _moe.remat_offers(cfg, kind, rows)


def further_stacks(params, cfg: "LatentConfig"):
    """The prediction module's block, for what counts a step's layers
    (llama._stacks): a further pass over the same rows."""
    return [("sparse", cfg.n_mtp, params["mtp"]["block"])] if cfg.n_mtp \
        else []
# leaves that no gradient reaches and ``post_update`` moves: the optimizer
# is told to leave them alone (parallel.train_step.hold_out)
RULE_LEAVES = ("router_bias",)
_PROJECTIONS = ("wq", "wk", "wv", "wo")     # llama's, which MLA replaces


def layer_runs(cfg: LatentConfig) -> List[Tuple[str, int]]:
    """[(kind, how many adjacent layers of it), ...] in the layers' order."""
    runs = [("dense", cfg.n_dense), ("sparse", cfg.n_layers - cfg.n_dense)]
    return [r for r in runs if r[1]]


def _mla_specs():
    L = ("layers",)
    return {"wq_a": L + ("embed", None), "q_a_norm": L + (None,),
            "wq_b": L + (None, "heads"), "wkv_a": L + ("embed", None),
            "kv_a_norm": L + (None,), "wkv_b": L + (None, "heads"),
            "wo": L + ("heads", "embed")}


def _mla_params(key, cfg: LatentConfig, n: int):
    pd, D, H = cfg.param_dtype, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)

    def dense(k, shape):
        return jax.random.normal(k, (n,) + shape, pd) * shape[0] ** -0.5

    return {"wq_a": dense(ks[0], (D, cfg.q_rank)),
            "q_a_norm": jnp.ones((n, cfg.q_rank), pd),
            "wq_b": dense(ks[1], (cfg.q_rank, H * cfg.head_dim)),
            "wkv_a": dense(ks[2], (D, cfg.kv_rank + cfg.qk_rope_dim)),
            "kv_a_norm": jnp.ones((n, cfg.kv_rank), pd),
            "wkv_b": dense(ks[3], (cfg.kv_rank,
                                   H * (cfg.qk_nope_dim + cfg.v_dim))),
            "wo": dense(ks[4], (H * cfg.v_dim, D))}


def _stacks(cfg: LatentConfig):
    """(kind, the config that makes a stack of that many layers of the
    kind): the runs, then the prediction module's one sparse block."""
    out = [(kind, cfg.replace(n_layers=n, n_dense=0))
           for kind, n in layer_runs(cfg)]
    return out + [("sparse", cfg.replace(n_layers=1, n_dense=0))] * cfg.n_mtp


def _stack_specs(kind: str, run: LatentConfig):
    base = _ll if kind == "dense" else _moe
    lay = dict(base.param_specs(run)["layers"])
    for w in _PROJECTIONS:
        del lay[w]
    return {**lay, **_mla_specs()}


def _stack_params(key, kind: str, run: LatentConfig):
    if kind == "dense":
        lay = _ll.init_params(key, run.replace(d_ff=run.dense_d_ff,
                                               vocab_size=1))["layers"]
    else:
        lay = _moe.init_params(key, run.replace(vocab_size=1))["layers"]
    lay = {k: v for k, v in lay.items() if k not in _PROJECTIONS}
    return {**lay, **_mla_params(jax.random.fold_in(key, 7), run,
                                 run.n_layers)}


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
    mla = (D * cfg.q_rank + cfg.q_rank + cfg.q_rank * H * cfg.head_dim
           + D * (cfg.kv_rank + cfg.qk_rope_dim) + cfg.kv_rank
           + cfg.kv_rank * H * (cfg.qk_nope_dim + cfg.v_dim)
           + H * cfg.v_dim * D)
    sparse = (mla + 2 * D + D * cfg.n_experts + cfg.n_experts
              + 3 * cfg.n_held * D * cfg.d_ff + 3 * D * cfg.shared_d_ff)
    dense = mla + 2 * D + 3 * D * cfg.dense_d_ff
    return (2 * cfg.vocab_size * D + D + cfg.n_dense * dense
            + (cfg.n_layers - cfg.n_dense) * sparse
            + cfg.n_mtp * (sparse + 2 * D * D + 3 * D))


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
    return {"S": S, "heads": H, "qk_nope": cfg.qk_nope_dim, "qk_rope": R,
            "v_dim": cfg.v_dim, "q_rank": cfg.q_rank, "kv_rank": cfg.kv_rank,
            "form": "expanded", "k_bytes": heads * e, "rope": "projected",
            "kv": "split_weights", "extra_columns": (H + 1) * R,
            "zero_columns": H * R,
            "hbm_bytes_fwd": e * (4 * heads + rows * R * (H + 4)),
            "hbm_bytes_bwd": 3 * 4 * heads + e * (3 * heads
                                                  + rows * R * (H + 1))}


def attention_half(x, lp, cfg: LatentConfig, cos, sin, mesh=None, rules=None):
    """The latent-attention half of a block: x [B, S, D] -> x + its
    attention's output (the module docstring has the equations and how the
    stored columns are arranged for the kernel)."""
    B, S, _ = x.shape
    H, dn, dv, dt = cfg.n_heads, cfg.qk_nope_dim, cfg.v_dim, cfg.dtype
    R, rk, f32 = cfg.qk_rope_dim, cfg.kv_rank, jnp.float32
    tracing.instant("mla.plan", plan(cfg, B, S))
    w = lambda name: _ll._dq(lp[name], dt)                     # noqa: E731
    wq_b = w("wq_b").reshape(cfg.q_rank, H, dn + R)
    wq_s = _swapped(wq_b[..., dn:])                            # [q_rank, H, R]
    wkv_a = w("wkv_a")
    wkv_a = jnp.concatenate([wkv_a, _swapped(wkv_a[:, rk:])], axis=-1)
    wkv_b = w("wkv_b").reshape(rk, H, dn + dv)
    wk = jnp.pad(wkv_b[..., :dn], ((0, 0), (0, 0), (0, R)))
    one, turn = _rotary_tables(cos, sin, dn)                   # [S, dn + R]
    h = _ll.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    c_q = _ll.rms_norm(h @ w("wq_a"), lp["q_a_norm"], cfg.norm_eps)
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
    v = jnp.einsum("bsr,rhd->bhsd", c_kv, wkv_b[..., dn:])
    # the kernel's own transposes, so XLA writes no copy for them
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    out = _ll._attention(q, k, v, cfg, causal=True, mesh=mesh, rules=rules)
    return _ll._residual(
        x, jnp.einsum("bshd,hde->bse", out, w("wo").reshape(H, dv, -1)), cfg)


def feed_forward(h, lp, cfg: LatentConfig, mesh=None, rules=None, tp=None,
                 kind=None):
    """A dense layer's SwiGLU or a sparse layer's experts, by ``kind``."""
    half = _ll if kind == "dense" else _moe
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
    """loss = L_main + mtp_weight L_mtp + router_aux_weight L_balance, from
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
            stats["experts"].shape[1] * cfg.top_k))
    else:
        aux["moe_dropped"] = jnp.zeros((), jnp.int32)
    return (loss + cfg.mtp_weight * mtp + cfg.router_aux_weight * balance,
            aux)


def post_update(params, aux, cfg: LatentConfig):
    """The rule no gradient carries (DeepSeek-V3 2.1.2): after a step,
    every router's bias moves by ``bias_rate`` towards balance, b += u x
    sign(mean(c) - c) from the step's counts c of ALL experts, a layer.
    (params, aux) -> (params, aux): ``router_counts`` (the statistics'
    order: the runs' expert layers, then the prediction module's block) is
    used up; ``moe_bias_abs_max`` and ``moe_bias_moved`` (how many biases
    the step moved) are the rule's report."""
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
