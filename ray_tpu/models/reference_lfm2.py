"""The plain reference of LFM2's block as LFM2-8B-A1B has it
(``model_type`` ``lfm2_moe``) in straightforward ``jax.numpy`` and float32.
Every layer is x += operator(rms_norm(x)) then x += feed_forward(
rms_norm(x)); which operator and which feed-forward, the leaves its
parameters have say:

- the gated short convolution: [B | C | u] = h W_in, v = B u, c[t] the sum
  over the taps j of w[j] v[t - taps + 1 + j] as a sum of shifted copies
  (depthwise, causal, no bias, NO activation), y = (C c) W_out; no state,
  no scan;
- causal grouped-query attention as an explicit S x S softmax in blocks of
  queries, scaled by head_dim^-0.5, an RMS norm with a learned scale over
  EACH head of q and of k before the rotary (the two-halves rotation:
  lanes i and i + head_dim / 2 turn together by t theta^(-2 i / head_dim));
- a dense SwiGLU (the leading layers: ``w_gate``, ``w_up``, ``w_down``), or
  the expert layer: sigmoid scores, the K largest of score + bias, the K
  scores (without the bias) divided by their sum + 1e-20 and scaled;
  experts of three matrices, down(silu(gate(x)) up(x)), as a loop over the
  experts HELD here with a 0/1 mask times the weight (what an absent
  expert would add is left out, as in the program); no shared expert;

then the final RMS norm, the head TIED to the embedding, the cross-entropy,
DeepSeek-V3's sequence-wise balance term and the rule that moves the
routers' biases after a step (``bias_update``). It shares nothing with the
program but the layout of the parameter tree (``models/hybrid.py``
``init_params``: ``params["layers"]`` a list of runs, a run one stack of
adjacent layers of a kind).

Departures from the source, each where it is made: the published config
gives neither the bias's rule nor a balance term: both are DeepSeek-V3's
at the rates the configuration's file assumes (``bias_update``,
``_experts``); the weights are normalised over the K chosen with 1e-20
added to their sum (``_experts``; the source's sum of four sigmoids is
never near it); a share of the experts and of the vocabulary is what the
parameters hold, nothing here asks.

``cfg`` is a dict of HybridConfig field names (``n_heads``, ``n_kv_heads``,
``norm_eps``, ``rope_theta``, ``n_experts``, ``top_k``, ``experts_held``
((count, first) or None), ``route_scale``, ``router_aux_weight``,
``bias_rate``). Parameters arrive in the type they are trained in and are
cast to float32 one layer at a time; matmuls run at ``highest`` precision,
because on a TPU a float32 matmul is otherwise computed in bfloat16 passes.

Routing is discrete. ``routes`` ([L, B, S, K] int32, L the expert layers
in the layers' order: the experts another implementation chose, numbered
over all ``n_experts``) makes the reference compute with THOSE experts and
its own float32 weights for them, and report per token and expert layer how
far its own choice lay from them (``route_gap``): where the sets differ,
the largest of its biased scores that the other gave up less the smallest
it took instead. A near tie reads a few times the rounding of the other's
scores; a wrong router reads a whole score.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def layers(params) -> list:
    """The layers' parameters in the layers' order, each one layer's
    leaves (no leading axis): a run's stack layer by layer."""
    return [jax.tree.map(lambda w, r=r: w[r], run)
            for run in params["layers"]
            for r in range(jax.tree.leaves(run)[0].shape[0])]


def short_conv(y, lp):
    """y [S, D] (normed) -> the operator's output [S, D], one sequence."""
    s, d = y.shape
    proj = y @ lp["in_proj"]
    gate_b, gate_c, u = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    taps = lp["conv_w"].shape[0]
    v = jnp.concatenate([jnp.zeros((taps - 1, d), F32), gate_b * u])
    c = sum(v[j:j + s] * lp["conv_w"][j] for j in range(taps))
    return (gate_c * c) @ lp["out_proj"]


def _rotary(x, theta: float):
    """x [S, heads, HD]: lanes i and i + HD / 2 turned by t theta^(-2i/HD)."""
    s, _, hd = x.shape
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]     # [S, HD/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(y, lp, cfg: dict, q_block: int = 512):
    """y [S, D] (normed) -> the attention operator's output [S, D]."""
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    s = y.shape[0]
    hd = lp["wq"].shape[1] // h
    q = _rms((y @ lp["wq"]).reshape(s, h, hd), lp["q_norm"], cfg["norm_eps"])
    k = _rms((y @ lp["wk"]).reshape(s, kv, hd), lp["k_norm"], cfg["norm_eps"])
    q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat((y @ lp["wv"]).reshape(s, kv, hd), h // kv, axis=1)
    qb = q_block if s % q_block == 0 else s
    kpos = jnp.arange(s)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        att = jnp.einsum("qhd,khd->hqk", qi, k) * hd ** -0.5
        seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        att = jnp.where(seen, att, -jnp.inf)
        att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", att, v)

    out = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, h * hd)
    return out @ lp["wo"]


def _swiglu(y, w_gate, w_up, w_down):
    return (_silu(y @ w_gate) * (y @ w_up)) @ w_down


def experts(y, lp, cfg: dict, routes=None):
    """y [S, D], one sequence -> (the routed experts held here [S, D], this
    layer's record). The K largest of ALL experts' biased scores; the
    weights the chosen scores WITHOUT the bias over their sum."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    held, first = cfg["experts_held"] or (e_n, 0)
    score = 1.0 / (1.0 + jnp.exp(-(y @ lp["router"])))             # [S, E]
    biased = score + lp["router_bias"]
    _, own = jax.lax.top_k(biased, k_n)
    chosen = own if routes is None else routes
    w = jnp.take_along_axis(score, chosen, axis=-1)                # no bias
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg["route_scale"]
    hot = chosen[..., None] == jnp.arange(e_n)                     # [S, K, E]
    weight = jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)    # [S, E]

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + col[:, None] * _swiglu(y, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["we_gate"], lp["we_up"], lp["we_down"],
        weight.T[first:first + held]))
    in_own = jnp.any(own[..., None] == jnp.arange(e_n), axis=1)    # [S, E]
    in_chosen = jnp.any(hot, axis=1)
    gave_up = jnp.max(jnp.where(in_own & ~in_chosen, biased, -jnp.inf), -1)
    took = jnp.min(jnp.where(in_chosen & ~in_own, biased, jnp.inf), -1)
    gap = jnp.where(jnp.isfinite(gave_up) & jnp.isfinite(took),
                    gave_up - took, 0.0)
    counts = jnp.sum(hot, axis=(0, 1))                             # [E]
    # the sequence-wise balance loss of this sequence: sum_i f_i P_i
    share = jnp.mean(score / jnp.sum(score, axis=-1, keepdims=True), axis=0)
    balance = jnp.sum(counts * (e_n / (k_n * y.shape[0])) * share)
    return out, {"experts": own, "route_gap": gap, "counts": counts,
                 "held_rows": jnp.sum(counts[first:first + held]),
                 "balance": balance}


def operator(x, lp, cfg: dict, q_block: int = 512):
    """x [S, D] -> x + the layer's operator of the normed x (float32
    leaves): the short convolution or attention, as the leaves say."""
    eps = cfg["norm_eps"]
    if "wq" in lp:
        return x + attention(_rms(x, lp["attn_norm"], eps), lp, cfg, q_block)
    return x + short_conv(_rms(x, lp["mix_norm"], eps), lp)


def layer(x, lp, cfg: dict, route=None, q_block: int = 512):
    """One layer of one sequence: x [S, D] -> (x after its operator and
    its feed-forward, the expert layer's record or None for a dense one)."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    x = operator(x, lp, cfg, q_block)
    y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
    if "router" not in lp:
        return x + _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    out, rec = experts(y, lp, cfg, route)
    return x + out, rec


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 512):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V], record).
    ``record``: per expert layer (leading axis L) the reference's own
    ``experts`` [L, S, K], ``route_gap`` [L, S] (0 without ``routes``),
    ``held_rows`` [L], ``counts`` [L, E] of the experts computed with and
    the sequence's ``balance`` [L]."""
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        x = embed[tokens]
        recs = []
        for lp in layers(params):
            route = None if routes is None or "router" not in lp \
                else routes[len(recs)]
            x, rec = layer(x, lp, cfg, route, q_block)
            if rec is not None:
                recs.append(rec)
        rec = jax.tree.map(lambda *r: jnp.stack(r), *recs)
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        return x @ embed.T, rec                 # the head is the embedding


def token_losses(params, tokens, cfg: dict, routes=None):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    (float32 [B, S], record), one sequence at a time so that one
    sequence's scores, logits and expert activations are all that is
    alive. ``routes`` [L, B, S, K]."""
    def one(inp):
        seq, route = inp
        logits, rec = forward(params, seq[:-1], cfg, route)
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, rec

    if routes is None:
        nll, rec = jax.lax.map(lambda seq: one((seq, None)), tokens)
    else:
        nll, rec = jax.lax.map(one, (tokens, jnp.moveaxis(routes, 1, 0)))
    # [B, L, S, ...] -> [L, B, S, ...]; the sums over the batch; the
    # balance term averaged over the batch's sequences
    rec = {"experts": jnp.moveaxis(rec["experts"], 0, 1),
           "route_gap": jnp.moveaxis(rec["route_gap"], 0, 1),
           "counts": rec["counts"].sum(0),
           "held_rows": rec["held_rows"].sum(0),
           "balance": rec["balance"].mean(0)}
    return nll, rec


def loss(params, tokens, cfg: dict, routes=None):
    """The training loss of tokens [B, S+1] and its terms: (cross-entropy
    + router_aux_weight x the balance term averaged over the expert
    layers, {"ce", "aux"})."""
    nll, rec = token_losses(params, tokens, cfg, routes)
    ce, aux = nll.mean(), rec["balance"].mean()
    return ce + cfg["router_aux_weight"] * aux, {"ce": ce, "aux": aux}


def biases(params):
    """The routers' biases [L, E] in the layers' order."""
    return jnp.stack([lp["router_bias"] for lp in layers(params)
                      if "router_bias" in lp])


def bias_update(bias, counts, cfg: dict):
    """The rule after a step: bias [L, E] and the step's assignments to
    every expert, an expert layer -> b + u x sign(mean(c) - c)."""
    c = counts.astype(F32)
    return bias + cfg["bias_rate"] * jnp.sign(
        jnp.mean(c, axis=-1, keepdims=True) - c)
