"""The plain reference of the Mellum2 block (JetBrains/Mellum2-12B-A2.5B:
``model_type`` "mellum", a Qwen3-MoE style block whose attention layers are
of two kinds) in straightforward ``jax.numpy`` and float32: RMSNorm; causal
grouped-query attention as an explicit softmax over a masked score matrix
(masks as ``where``: ``j <= i``, and in a sliding layer ``i - j < window``),
a query block at a time so that the scores fit; rotary on all lanes of a
head, halves ``(i, i + head_dim / 2)`` paired (transformers'
``rotate_half``), from the table of the layer's kind: plain for the sliding
layers, YaRN (transformers' ``_compute_yarn_parameters``: the ramp between
the lanes ``low`` and ``high``, cos and sin both times the attention
factor) for the full ones; a router with the softmax written out over all
experts, the K largest kept and divided by their sum (``norm_topk_prob``);
experts as a loop over the experts HELD here with a 0/1 mask times the
weight (what an absent expert would add is left out, as in the program);
no shared expert; the final norm, the untied head over the vocabulary held,
the cross-entropy and the two router losses. It shares nothing with the
program but the layout of the parameter tree (``models/moe.py``
``init_params`` with ``layer_kinds``: a list of stacks, one a run of layers
of one kind).

``cfg`` is a dict: ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_width``,
``norm_eps``, ``n_experts``, ``top_k``, ``experts_held`` ((count, first) or
None), ``layer_kinds`` (one name a layer), ``kinds`` ({name: {"window":
int or None, "rope_theta", "yarn": None or {"factor", "original",
"beta_fast", "beta_slow", "attention_factor"}}}), ``router_aux_weight``,
``router_z_weight``. Parameters arrive in the type they are trained in and
are cast to float32 one layer at a time; matmuls run at ``highest``
precision, because on a TPU a float32 matmul is otherwise computed in
bfloat16 passes.

Departures from the source, each at its line below: the experts and the
vocabulary are this chip's share; the loss adds the load-balancing term
(and a z-loss of weight 0) that the source's config gives no coefficient
for; the source's "MTP head" (``described_as``) has no key in its config
and is not here.

Routing is discrete. ``routes`` ([L, B, S, K] int32: the experts another
implementation chose, numbered over all ``n_experts``) makes the reference
compute with THOSE experts and its own float32 weights for them, and
report per token and layer how far its own choice lay from them
(``route_gap``): where the sets differ, the largest of its softmax
probabilities that the other gave up less the smallest it took instead.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _runs(cfg: dict) -> list:
    """[(kind, layers), ...]: adjacent layers of one kind."""
    runs = []
    for kind in cfg["layer_kinds"]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return runs


def yarn_range(theta: float, dim: int, yarn: dict) -> tuple:
    """(low, high) of the ramp: the lane that turns ``beta_fast`` times
    over the original context, rounded down, and the lane that turns
    ``beta_slow`` times, rounded up, inside [0, dim - 1]."""
    def lane(turns):
        return dim * math.log(yarn["original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(lane(yarn["beta_fast"])), 0),
            min(math.ceil(lane(yarn["beta_slow"])), dim - 1))


def inv_freq(kind: dict, dim: int):
    """The rotary frequencies of one kind of layer, float32 [dim / 2]."""
    theta, yarn = kind["rope_theta"], kind.get("yarn")
    pos = theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    if yarn is None:
        return 1.0 / pos
    low, high = yarn_range(theta, dim, yarn)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return 1.0 / (yarn["factor"] * pos) * ramp + 1.0 / pos * (1.0 - ramp)


def rope_tables(kind: dict, seq: int, dim: int) -> tuple:
    """cos and sin [seq, dim]: an angle on both lanes of its pair (i,
    i + dim / 2); under YaRN both times the attention factor."""
    angles = jnp.outer(jnp.arange(seq, dtype=F32), inv_freq(kind, dim))
    angles = jnp.concatenate([angles, angles], axis=-1)
    yarn = kind.get("yarn")
    by = 1.0 if yarn is None else (
        yarn.get("attention_factor") or 0.1 * math.log(yarn["factor"]) + 1.0)
    return jnp.cos(angles) * by, jnp.sin(angles) * by


def _rope(x, cos, sin):
    """x [S, heads, dim]: x cos + rotate_half(x) sin."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _attention(y, lp, cfg: dict, kind: dict, q_block: int):
    """y [S, D] (normed) -> the attention layer's output [S, D]."""
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    s = y.shape[0]
    cos, sin = rope_tables(kind, s, hd)
    q = _rope((y @ lp["wq"]).reshape(s, h, hd), cos, sin)
    k = _rope((y @ lp["wk"]).reshape(s, kv, hd), cos, sin)
    # query head i reads KV head i // (h / kv)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat((y @ lp["wv"]).reshape(s, kv, hd), h // kv, axis=1)
    qb = q_block if s % q_block == 0 else s
    kpos = jnp.arange(s)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        att = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        qpos = (i * qb + jnp.arange(qb))[:, None]
        seen = kpos[None, :] <= qpos
        if kind.get("window") is not None:
            seen = seen & (qpos - kpos[None, :] < kind["window"])
        att = jnp.where(seen, att, -jnp.inf)
        att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", att, v)

    o = jax.lax.map(rows, jnp.arange(s // qb))                # [nb, qb, H, HD]
    return o.reshape(s, h * hd) @ lp["wo"]


def experts(y, lp, cfg: dict, routes=None):
    """y [T, D] (normed) -> (the routed experts held here [T, D], this
    layer's record). Departure: the source sums all ``n_experts``' parts;
    this is the chip's share of them (``experts_held``; None: all)."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    held, first = cfg["experts_held"] or (e_n, 0)
    logits = y @ lp["router"]                                      # [T, E]
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(z) / jnp.sum(jnp.exp(z), axis=-1, keepdims=True)
    _, own = jax.lax.top_k(p, k_n)
    chosen = own if routes is None else routes
    w = jnp.take_along_axis(p, chosen, axis=-1)                    # [T, K]
    w = w / jnp.sum(w, axis=-1, keepdims=True)            # norm_topk_prob
    hot = chosen[..., None] == jnp.arange(e_n)                     # [T, K, E]
    weight = jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)    # [T, E]

    def one(acc, ew):
        wg, wu, wd, col = ew
        gate = y @ wg
        return acc + col[:, None] * (
            (gate / (1.0 + jnp.exp(-gate)) * (y @ wu)) @ wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["we_gate"], lp["we_up"], lp["we_down"],
        weight.T[first:first + held]))
    in_own = jnp.any(own[..., None] == jnp.arange(e_n), axis=1)    # [T, E]
    in_chosen = jnp.any(hot, axis=1)
    gave_up = jnp.max(jnp.where(in_own & ~in_chosen, p, 0.0), axis=-1)
    took = jnp.min(jnp.where(in_chosen & ~in_own, p, jnp.inf), axis=-1)
    gap = jnp.where(gave_up > 0, gave_up - jnp.where(
        jnp.isfinite(took), took, 0.0), 0.0)
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1)) + jnp.max(logits, axis=-1)
    counts = jnp.sum(hot, axis=(0, 1))
    return out, {"experts": own, "route_gap": gap, "counts": counts,
                 "held_rows": jnp.sum(counts[first:first + held]),
                 "prob_sum": jnp.sum(p, axis=0),
                 "z_sum": jnp.sum(lse * lse)}


def trunk(params, tokens, cfg: dict, routes=None, q_block: int = 256):
    """tokens [S] (ONE sequence) -> (the residual stream after the final
    norm, float32 [S, D], record). ``record``: per layer (leading axis L)
    the reference's own ``experts`` [L, S, K], ``route_gap`` [L, S] (0
    without ``routes``), ``held_rows`` [L] and the sums the router losses
    need (``counts`` [L, E] of the experts computed with, ``prob_sum``
    [L, E], ``z_sum`` [L])."""
    x = params["embed"].astype(F32)[tokens]

    def layer(kind, x, inp):
        lp, route = inp
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        y = _rms(x, lp["attn_norm"], cfg["norm_eps"])
        x = x + _attention(y, lp, cfg, cfg["kinds"][kind], q_block)
        y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
        out, rec = experts(y, lp, cfg, route)
        return x + out, rec

    recs, at = [], 0
    assert len(_runs(cfg)) == len(params["layers"])
    for (kind, n), stack in zip(_runs(cfg), params["layers"]):
        if routes is None:
            x, rec = jax.lax.scan(
                lambda x, lp, kind=kind: layer(kind, x, (lp, None)), x, stack)
        else:
            x, rec = jax.lax.scan(
                lambda x, inp, kind=kind: layer(kind, x, inp), x,
                (stack, routes[at:at + n]))
        recs.append(rec)
        at += n
    rec = jax.tree.map(lambda *r: jnp.concatenate(r), *recs)
    return _rms(x, params["final_norm"], cfg["norm_eps"]), rec


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 256):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V] over the
    vocabulary held, record)."""
    with jax.default_matmul_precision("highest"):
        x, rec = trunk(params, tokens, cfg, routes, q_block)
        return x @ params["lm_head"].astype(F32), rec


def router_losses(rec: dict, cfg: dict) -> tuple:
    """(load-balancing loss, z-loss) of the sums of ``token_losses``'
    record over every token of every layer, over ALL experts' counts and
    probabilities: transformers' ``load_balancing_loss_func`` (E x sum
    over experts of the share of assignments times the mean probability,
    all layers concatenated) and the mean squared logsumexp of the router
    logits. Departure: the source's config names no coefficient for
    either; the configuration file's are ``assumed``."""
    rows = jnp.sum(rec["counts"]) / rec["experts"].shape[-1]       # L x T
    share = jnp.sum(rec["counts"], axis=0) / rows
    aux = cfg["n_experts"] * jnp.sum(
        share * jnp.sum(rec["prob_sum"], axis=0) / rows)
    return aux, jnp.sum(rec["z_sum"]) / rows


def token_losses(params, tokens, cfg: dict, routes=None,
                 head_rows: int = 2048):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    (float32 [B, S], record), one sequence at a time and the head
    ``head_rows`` positions at a time, so that one sequence's scores,
    expert activations and one block of logits are all that is alive.
    ``routes`` [L, B, S, K]."""
    head = params["lm_head"].astype(F32)

    def one(inp):
        seq, route = inp
        x, rec = trunk(params, seq[:-1], cfg, route)
        rows = head_rows if x.shape[0] % head_rows == 0 else x.shape[0]

        def block(part):
            xs, targets = part
            logits = xs @ head
            picked = jnp.take_along_axis(logits, targets[:, None],
                                         axis=-1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - picked

        nll = jax.lax.map(block, (x.reshape(-1, rows, x.shape[-1]),
                                  seq[1:].reshape(-1, rows)))
        return nll.reshape(-1), rec

    with jax.default_matmul_precision("highest"):
        if routes is None:
            nll, rec = jax.lax.map(lambda seq: one((seq, None)), tokens)
        else:
            nll, rec = jax.lax.map(one, (tokens, jnp.moveaxis(routes, 1, 0)))
    # [B, L, S, ...] -> [L, B, S, ...]; the sums over the batch
    rec = {"experts": jnp.moveaxis(rec["experts"], 0, 1),
           "route_gap": jnp.moveaxis(rec["route_gap"], 0, 1),
           "counts": rec["counts"].sum(0), "prob_sum": rec["prob_sum"].sum(0),
           "held_rows": rec["held_rows"].sum(0), "z_sum": rec["z_sum"].sum(0)}
    return nll, rec


def loss(params, tokens, cfg: dict, routes=None):
    """The training loss of tokens [B, S+1] and its three terms:
    (cross-entropy + aux weight x load balancing + z weight x z-loss,
    {"ce", "aux", "z"})."""
    nll, rec = token_losses(params, tokens, cfg, routes)
    aux, z = router_losses(rec, cfg)
    ce = nll.mean()
    return (ce + cfg["router_aux_weight"] * aux
            + cfg["router_z_weight"] * z), {"ce": ce, "aux": aux, "z": z}
