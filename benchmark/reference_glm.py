"""The benchmark's copy of ``ray_tpu/models/reference_glm.py`` (kept word
for word below this paragraph; ``benchmark/tests/test_glm.py`` compares the
two): the yardstick reads nothing of the program, so that a change to the
program's copy cannot move what decides ``correct``.

The plain reference of the GLM-4.7-Flash block (transformers
``glm4_moe_lite``, whose block is DeepSeek-V3's: arXiv:2405.04434 section
2.1 for the latent attention, arXiv:2412.19437 sections 2.1.2 and 2.2 for
the router and the multi-token prediction) in straightforward ``jax.numpy``
and float32: RMSNorm; latent attention with q and k built HEAD BY HEAD from
the two latents exactly as the equations say (no fused projection, no
kernel), the rotary over interleaved pairs written as a complex
multiplication, ONE rotary key a token shared by all heads, an explicit S x
S softmax in blocks of queries; a leading dense SwiGLU layer; a router that
scores with a sigmoid, chooses the K largest of score PLUS bias and weighs
by the chosen scores over their sum times the scaling factor; experts as a
loop over the experts HELD here with a 0/1 mask times the weight (what an
absent expert would add is left out, as in the program); the shared
SwiGLU; the prediction module (its two norms, the joint projection, one
sparse block, its own final norm, the model's embedding and head); the
three loss terms; and the rule that moves the bias after a step. It shares
nothing with the program but the layout of the parameter tree
(``models/latent.py`` ``init_params``).

``cfg`` is a dict of LatentConfig field names (``d_model``, ``n_heads``,
``norm_eps``, ``rope_theta``, ``q_rank``, ``kv_rank``, ``qk_nope_dim``,
``qk_rope_dim``, ``v_dim``, ``n_experts``, ``top_k``, ``experts_held``
((count, first) or None), ``route_scale``, ``bias_rate``, ``mtp_weight``,
``router_aux_weight``). Parameters arrive in the type they are trained in
and are cast to float32 one layer at a time; matmuls run at ``highest``
precision, because on a TPU a float32 matmul is otherwise computed in
bfloat16 passes.

Routing is discrete. ``routes`` ([L, B, S, K] int32 over the L expert
layers, the prediction module's block last: the experts another
implementation chose, numbered over all ``n_experts``) makes the reference
compute with THOSE experts and its own float32 weights for them, and
report per token and layer how far its own choice lay from them
(``route_gap``): where the sets differ, the largest of its score-plus-bias
that the other gave up less the smallest it took instead. A near tie reads
a few times the rounding of the other's logits; a wrong router reads a
whole score.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _swiglu(y, wg, wu, wd):
    gate = y @ wg
    return (gate / (1.0 + jnp.exp(-gate)) * (y @ wu)) @ wd


def _turn(x, theta: float):
    """Rotary position embedding of x [S, R] at positions 0..S-1: lanes
    (2i, 2i+1) are one complex number, multiplied by exp(i t theta^(-2i/R))."""
    s, r = x.shape
    angle = jnp.arange(s, dtype=F32)[:, None] \
        / theta ** (jnp.arange(0, r, 2, dtype=F32) / r)[None, :]
    z = jax.lax.complex(x[:, 0::2], x[:, 1::2]) \
        * jax.lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(s, r)


def _attention(y, lp, cfg: dict, q_block: int):
    """y [S, D] (normed) -> the latent attention's output [S, D]."""
    h_n, dn, dr, dv = (cfg["n_heads"], cfg["qk_nope_dim"],
                       cfg["qk_rope_dim"], cfg["v_dim"])
    s, eps, kvr = y.shape[0], cfg["norm_eps"], cfg["kv_rank"]
    c_q = _rms(y @ lp["wq_a"], lp["q_a_norm"], eps)
    joint = y @ lp["wkv_a"]
    c_kv = _rms(joint[:, :kvr], lp["kv_a_norm"], eps)
    k_r = _turn(joint[:, kvr:], cfg["rope_theta"])         # shared by heads
    qb = q_block if s % q_block == 0 else s
    kpos = jnp.arange(s)
    heads = []
    for h in range(h_n):
        wq = lp["wq_b"][:, h * (dn + dr):(h + 1) * (dn + dr)]
        wkv = lp["wkv_b"][:, h * (dn + dv):(h + 1) * (dn + dv)]
        q = jnp.concatenate([c_q @ wq[:, :dn],
                             _turn(c_q @ wq[:, dn:], cfg["rope_theta"])], -1)
        k = jnp.concatenate([c_kv @ wkv[:, :dn], k_r], axis=-1)
        v = c_kv @ wkv[:, dn:]

        def rows(i, q=q, k=k, v=v):
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
            att = (qi @ k.T) / jnp.sqrt(F32(dn + dr))
            seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
            att = jnp.where(seen, att, -jnp.inf)
            att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
            return (att / jnp.sum(att, axis=-1, keepdims=True)) @ v

        heads.append(jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, dv))
    return jnp.concatenate(heads, axis=-1) @ lp["wo"]


def _experts(y, lp, cfg: dict, routes):
    """y [S, D], one sequence -> (routed experts held here + the shared
    SwiGLU [S, D], this layer's record)."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    held, first = cfg["experts_held"] or (e_n, 0)
    score = 1.0 / (1.0 + jnp.exp(-(y @ lp["router"])))             # [S, E]
    biased = score + lp["router_bias"]
    _, own = jax.lax.top_k(biased, k_n)
    chosen = own if routes is None else routes
    w = jnp.take_along_axis(score, chosen, axis=-1)                # no bias
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg["route_scale"]
    hot = chosen[..., None] == jnp.arange(e_n)                     # [S, K, E]
    weight = jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)    # [S, E]

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + col[:, None] * _swiglu(y, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["we_gate"], lp["we_up"], lp["we_down"],
        weight.T[first:first + held]))
    out = out + _swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
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


def _block(x, lp, route, cfg: dict, q_block: int):
    """One block: latent attention, then the feed-forward the layer's
    parameters say (a dense SwiGLU where it has no router)."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    x = x + _attention(_rms(x, lp["attn_norm"], cfg["norm_eps"]), lp, cfg,
                       q_block)
    y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
    if "router" not in lp:
        return x + _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    out, rec = _experts(y, lp, cfg, route)
    return x + out, rec


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 512):
    """tokens [S + 2] (ONE sequence: its S inputs and the two ids after
    them) -> (float32 logits of the main model [S, V], of the prediction
    module [S, V], record). ``record``: per expert layer (leading axis L,
    the module's block last) the reference's own ``experts`` [L, S, K],
    ``route_gap`` [L, S] (0 without ``routes``), ``counts`` [L, E] of the
    experts computed with, ``held_rows`` [L] and ``balance`` [L]."""
    s = tokens.shape[0] - 2
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        head = params["lm_head"].astype(F32)
        x = embed[tokens[:s]]
        recs, at = [], 0
        for stack in list(params["layers"]) + [params["mtp"]["block"]]:
            sparse = "router" in stack
            n = jax.tree.leaves(stack)[0].shape[0]
            if stack is params["mtp"]["block"]:
                m = params["mtp"]
                main = _rms(x, params["final_norm"], cfg["norm_eps"]) @ head
                x = jnp.concatenate(
                    [_rms(x, m["h_norm"], cfg["norm_eps"]),
                     _rms(embed[tokens[1:s + 1]], m["e_norm"],
                          cfg["norm_eps"])], axis=-1) \
                    @ m["eh_proj"].astype(F32)
            route = None if routes is None or not sparse \
                else routes[at:at + n]
            x, rec = jax.lax.scan(
                lambda x, inp: _block(x, inp[0], inp[1], cfg, q_block), x,
                (stack, route))
            if sparse:
                recs.append(rec)
                at += n
        rec = jax.tree.map(lambda *r: jnp.concatenate(r), *recs)
        ahead = _rms(x, params["mtp"]["final_norm"], cfg["norm_eps"]) @ head
        return main, ahead, rec


def token_losses(params, tokens, cfg: dict, routes=None):
    """Cross-entropy of every position of tokens [B, S + 2] -> (the main
    model's against the next token, the prediction module's against the
    one after, both float32 [B, S], record), one sequence at a time so
    that one sequence's scores, logits and expert activations are all that
    is alive. ``routes`` [L, B, S, K]."""
    def one(inp):
        seq, route = inp
        s = seq.shape[0] - 2
        main, ahead, rec = forward(params, seq, cfg, route)

        def nll(logits, targets):
            picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - picked

        return nll(main, seq[1:s + 1]), nll(ahead, seq[2:s + 2]), rec

    if routes is None:
        main, ahead, rec = jax.lax.map(lambda seq: one((seq, None)), tokens)
    else:
        main, ahead, rec = jax.lax.map(
            one, (tokens, jnp.moveaxis(routes, 1, 0)))
    # [B, L, S, ...] -> [L, B, S, ...]; counts summed, balance averaged
    # over the batch's sequences
    rec = {"experts": jnp.moveaxis(rec["experts"], 0, 1),
           "route_gap": jnp.moveaxis(rec["route_gap"], 0, 1),
           "counts": rec["counts"].sum(0), "balance": rec["balance"].mean(0),
           "held_rows": rec["held_rows"].sum(0)}
    return main, ahead, rec


def loss(params, tokens, cfg: dict, routes=None):
    """The training loss of tokens [B, S + 2] and its three terms: (main
    cross-entropy + mtp_weight x the module's + router_aux_weight x the
    balance loss averaged over sequences and expert layers, {"main",
    "mtp", "balance", "counts" [L, E]})."""
    main, ahead, rec = token_losses(params, tokens, cfg, routes)
    parts = {"main": main.mean(), "mtp": ahead.mean(),
             "balance": rec["balance"].mean(), "counts": rec["counts"]}
    return (parts["main"] + cfg["mtp_weight"] * parts["mtp"]
            + cfg["router_aux_weight"] * parts["balance"]), parts


def biases(params):
    """Every expert layer's router bias [L, E], the module's block last."""
    stacks = [s for s in params["layers"] if "router_bias" in s] \
        + [params["mtp"]["block"]]
    return jnp.concatenate([s["router_bias"].astype(F32) for s in stacks])


def bias_update(bias, counts, cfg: dict):
    """The rule after a step: bias [L, E] and the step's assignments to
    every expert, a layer -> b + u x sign(mean(c) - c)."""
    c = counts.astype(F32)
    return bias + cfg["bias_rate"] * jnp.sign(
        jnp.mean(c, axis=-1, keepdims=True) - c)
