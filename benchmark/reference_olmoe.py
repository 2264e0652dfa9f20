"""The benchmark's copy of ``ray_tpu/models/reference_olmoe.py`` (kept
word for word below this paragraph; ``benchmark/tests/test_flops_moe.py``
compares the two): the yardstick reads nothing of the program, so that a
change to the program's copy cannot move what decides ``correct``.

The plain reference of the OLMoE block (arXiv:2409.02060; transformers
``modeling_olmoe.py``) in straightforward ``jax.numpy`` and float32:
RMSNorm, q/k RMS norm over the whole projected vector, rotary embedding,
causal attention as an explicit S x S softmax, a router with an explicit
softmax over all experts, the K largest probabilities as they are (or
renormalised, ``norm_topk``), experts as a loop over ALL of them with a
0/1 mask times the weight, untied head, and the three loss terms. No
sort, no grouped matmul, no kernel, no remat. It shares nothing with the
program but the layout of the parameter tree (``models/moe.py``
``init_params``) and the rotary convention (first and second half of a
head rotate together).

``cfg`` is a dict of MoEConfig field names (``n_heads``, ``n_kv_heads``,
``d_model``, ``norm_eps``, ``rope_theta``, ``n_experts``, ``top_k``,
``norm_topk``, ``qk_norm``, ``router_aux_weight``, ``router_z_weight``).
Parameters arrive in the type they are trained in and are cast to float32
one layer at a time; matmuls run at ``highest`` precision, because on a
TPU a float32 matmul is otherwise computed in bfloat16 passes.

Routing is discrete. ``routes`` ([L, B, S, K] int32: the experts another
implementation chose) makes the reference compute with THOSE experts and
its own float32 probabilities for them, and report per token and layer
how far its own choice lay from them (``route_gap``): where the sets
differ, the largest of its probabilities that the other gave up less the
smallest it took instead (an expert missing without replacement counts
as taken at probability 0). A near tie reads a few times the rounding of
the other's logits; a wrong router reads a whole probability.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x [B, S, N, HD]; pairs (i, i + HD/2) rotate by pos * theta^(-2i/HD)."""
    s, hd = x.shape[1], x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _experts(y, lp, cfg: dict, routes):
    """y [T, D] -> (expert layer's output [T, D], this layer's record)."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    logits = y @ lp["router"]                                      # [T, E]
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(z) / jnp.sum(jnp.exp(z), axis=-1, keepdims=True)
    _, own = jax.lax.top_k(p, k_n)
    chosen = own if routes is None else routes
    w = jnp.take_along_axis(p, chosen, axis=-1)                    # [T, K']
    if cfg["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    hot = chosen[..., None] == jnp.arange(e_n)                     # [T, K', E]
    weight = jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)    # [T, E]

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + col[:, None] * (
            (jax.nn.silu(y @ wg) * (y @ wu)) @ wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["we_gate"], lp["we_up"], lp["we_down"], weight.T))
    in_own = jnp.any(own[..., None] == jnp.arange(e_n), axis=1)    # [T, E]
    in_chosen = jnp.any(hot, axis=1)
    gave_up = jnp.max(jnp.where(in_own & ~in_chosen, p, 0.0), axis=-1)
    took = jnp.min(jnp.where(in_chosen & ~in_own, p, jnp.inf), axis=-1)
    gap = jnp.where(gave_up > 0, gave_up - jnp.where(
        jnp.isfinite(took), took, 0.0), 0.0)
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1)) + jnp.max(logits, axis=-1)
    return out, {"experts": own, "route_gap": gap,
                 "counts": jnp.sum(hot, axis=(0, 1)),
                 "prob_sum": jnp.sum(p, axis=0),
                 "z_sum": jnp.sum(lse * lse)}


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 512):
    """tokens [B, S] -> (float32 logits [B, S, V], record). ``record``:
    per layer (leading axis L) the reference's own ``experts`` [L, B, S,
    K], ``route_gap`` [L, B, S] (0 without ``routes``), and the sums the
    router losses need (``counts`` [L, E] of the experts computed with,
    ``prob_sum`` [L, E], ``z_sum`` [L]). Attention is the explicit softmax
    over all keys, ``q_block`` query rows at a time."""
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg["d_model"] // h
    b, s = tokens.shape
    qb = q_block if s % q_block == 0 else s
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        kpos = jnp.arange(s)

        def layer(x, inp):
            lp, route = inp
            lp = jax.tree.map(lambda w: w.astype(F32), lp)
            y = _rms(x, lp["attn_norm"], cfg["norm_eps"])
            q, k, v = y @ lp["wq"], y @ lp["wk"], y @ lp["wv"]
            if cfg["qk_norm"]:
                q = _rms(q, lp["q_norm"], cfg["norm_eps"])
                k = _rms(k, lp["k_norm"], cfg["norm_eps"])
            q = _rope(q.reshape(b, s, h, hd), cfg["rope_theta"])
            k = _rope(k.reshape(b, s, kv, hd), cfg["rope_theta"])
            v = v.reshape(b, s, kv, hd)
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)

            def rows(i):
                qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
                att = jnp.einsum("bqhd,bkhd->bhqk", qi, k) * hd ** -0.5
                seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
                att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
                return jnp.einsum("bhqk,bkhd->bqhd", att, v)

            o = jax.lax.map(rows, jnp.arange(s // qb))      # [nb, B, qb, H, HD]
            o = jnp.moveaxis(o, 0, 1).reshape(b, s, h * hd)
            x = x + o @ lp["wo"]
            y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
            out, rec = _experts(
                y.reshape(b * s, -1), lp, cfg,
                None if route is None else route.reshape(b * s, -1))
            rec["experts"] = rec["experts"].reshape(b, s, -1)
            rec["route_gap"] = rec["route_gap"].reshape(b, s)
            return x + out.reshape(b, s, -1), rec

        n_layers = params["layers"]["wq"].shape[0]
        if routes is None:
            x, rec = jax.lax.scan(lambda x, lp: layer(x, (lp, None)), x,
                                  params["layers"])
        else:
            assert routes.shape[0] == n_layers, routes.shape
            x, rec = jax.lax.scan(layer, x, (params["layers"], routes))
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        return x @ params["lm_head"].astype(F32), rec


def router_losses(rec: dict, cfg: dict) -> tuple:
    """(load-balancing loss, z-loss) of the sums of ``forward``'s record
    over every token of every layer, as transformers'
    ``load_balancing_loss_func`` has the first (E x sum over experts of
    the share of assignments times the mean probability, all layers
    concatenated) and the OLMoE paper the second (mean squared logsumexp
    of the router logits)."""
    rows = jnp.sum(rec["counts"]) / rec["experts"].shape[-1]       # L x T
    share = jnp.sum(rec["counts"], axis=0) / rows
    aux = cfg["n_experts"] * jnp.sum(
        share * jnp.sum(rec["prob_sum"], axis=0) / rows)
    return aux, jnp.sum(rec["z_sum"]) / rows


def token_losses(params, tokens, cfg: dict, routes=None):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    (float32 [B, S], record with a leading batch axis), one sequence at a
    time so that one sequence's scores, logits and expert activations are
    all that is alive. ``routes`` [L, B, S, K] as in ``forward``."""
    def one(inp):
        seq, route = inp
        logits, rec = forward(params, seq[None, :-1], cfg,
                              None if route is None else route[:, None])
        logits = logits[0]
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, rec

    if routes is None:
        nll, rec = jax.lax.map(lambda seq: one((seq, None)), tokens)
    else:
        nll, rec = jax.lax.map(one, (tokens, jnp.moveaxis(routes, 1, 0)))
    # [B, L, 1, S, ...] -> [L, B, S, ...]; the sums over the batch
    rec = {"experts": jnp.moveaxis(rec["experts"][:, :, 0], 0, 1),
           "route_gap": jnp.moveaxis(rec["route_gap"][:, :, 0], 0, 1),
           "counts": rec["counts"].sum(0), "prob_sum": rec["prob_sum"].sum(0),
           "z_sum": rec["z_sum"].sum(0)}
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
