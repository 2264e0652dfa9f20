"""The benchmark's copy of ``ray_tpu/models/reference_granite.py`` (kept
word for word below this paragraph; ``benchmark/tests/test_granite.py``
compares the two): the yardstick reads nothing of the program, so that a
change to the program's copy cannot move what decides ``correct``.

The plain reference of the Granite-4.0-H block (transformers
``modeling_granitemoehybrid.py``; its state-space layer is Bamba's Mamba-2
mixer, arXiv:2405.21060) in straightforward ``jax.numpy`` and float32:
RMSNorm; the Mamba-2 mixer with its recurrence written ONE TOKEN AT A
TIME (a ``lax.scan`` over the sequence carrying the [H, P, N] state: no
chunks, no decay matrix, no kernel), a depthwise causal convolution as a
sum of shifted copies, the gated RMS norm; causal grouped-query attention
without position embedding as an explicit S x S softmax scaled by
``attn_scale``; a router with the softmax written out over the K largest
logits; experts as a loop over the experts HELD here with a 0/1 mask times
the weight (what an absent expert would add is left out, as in the
program); the shared SwiGLU; the embedding, residual and logit
multipliers; the head tied to the embedding; and the three loss terms. It
shares nothing with the program but the layout of the parameter tree
(``models/hybrid.py`` ``init_params``: a list of stacks, one a run of
layers of one kind).

``cfg`` is a dict of HybridConfig field names (``d_model``, ``n_heads``,
``n_kv_heads``, ``norm_eps``, ``attn_scale``, ``layer_types``,
``mamba_heads``, ``mamba_head_dim``, ``mamba_state``, ``n_experts``,
``top_k``, ``experts_held`` ((count, first) or None), the three
multipliers, ``router_aux_weight``, ``router_z_weight``). Parameters
arrive in the type they are trained in and are cast to float32 one layer
at a time; matmuls run at ``highest`` precision, because on a TPU a
float32 matmul is otherwise computed in bfloat16 passes.

Routing is discrete. ``routes`` ([L, B, S, K] int32: the experts another
implementation chose, numbered over all ``n_experts``) makes the
reference compute with THOSE experts and its own float32 weights for
them, and report per token and layer how far its own choice lay from them
(``route_gap``): where the sets differ, the largest of its softmax
probabilities (over all experts) that the other gave up less the smallest
it took instead. A near tie reads a few times the rounding of the other's
logits; a wrong router reads a whole probability.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _runs(cfg: dict) -> list:
    """[(kind, layers), ...]: adjacent layers of one kind."""
    runs = []
    for kind in cfg["layer_types"]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return runs


def _mamba(y, lp, cfg: dict):
    """y [S, D] (normed) -> the mixer's output [S, D], one sequence."""
    h_n, p_n, n_n = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                     cfg["mamba_state"])
    inner = h_n * p_n
    s = y.shape[0]
    proj = y @ lp["in_proj"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + inner + 2 * n_n],
                  proj[:, inner + inner + 2 * n_n:])
    taps = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
    conv = lp["conv_b"] + sum(padded[j:j + s] * lp["conv_w"][j]
                              for j in range(taps))
    xbc = conv / (1.0 + jnp.exp(-conv))                            # silu
    x = xbc[:, :inner].reshape(s, h_n, p_n)
    b, c = xbc[:, inner:inner + n_n], xbc[:, inner + n_n:]
    dt = jnp.logaddexp(dt + lp["dt_bias"], 0.0)                    # softplus
    a = -jnp.exp(lp["a_log"])                                      # [H]

    def step(state, inp):
        xt, dtt, bt, ct = inp                          # [H, P], [H], [N], [N]
        state = state * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return state, jnp.sum(state * ct[None, None, :], axis=-1)

    _, out = jax.lax.scan(step, jnp.zeros((h_n, p_n, n_n), F32),
                          (x, dt, b, c))
    out = (out + lp["d_skip"][:, None] * x).reshape(s, inner)
    out = out * (z / (1.0 + jnp.exp(-z)))
    return _rms(out, lp["gate_norm"], cfg["norm_eps"]) @ lp["out_proj"]


def _attention(y, lp, cfg: dict, q_block: int):
    """y [S, D] (normed) -> the attention layer's output [S, D]."""
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    s = y.shape[0]
    hd = lp["wq"].shape[1] // h
    q = (y @ lp["wq"]).reshape(s, h, hd)
    k = jnp.repeat((y @ lp["wk"]).reshape(s, kv, hd), h // kv, axis=1)
    v = jnp.repeat((y @ lp["wv"]).reshape(s, kv, hd), h // kv, axis=1)
    qb = q_block if s % q_block == 0 else s
    kpos = jnp.arange(s)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        att = jnp.einsum("qhd,khd->hqk", qi, k) * cfg["attn_scale"]
        seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        att = jnp.where(seen, att, -jnp.inf)
        att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", att, v)

    o = jax.lax.map(rows, jnp.arange(s // qb))                # [nb, qb, H, HD]
    return o.reshape(s, h * hd) @ lp["wo"]


def _experts(y, lp, cfg: dict, routes):
    """y [T, D] -> (routed experts held here + the shared SwiGLU [T, D],
    this layer's record)."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    held, first = cfg["experts_held"] or (e_n, 0)
    logits = y @ lp["router"]                                      # [T, E]
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(z) / jnp.sum(jnp.exp(z), axis=-1, keepdims=True)
    _, own = jax.lax.top_k(logits, k_n)
    chosen = own if routes is None else routes
    w = jnp.take_along_axis(p, chosen, axis=-1)                    # [T, K']
    w = w / jnp.sum(w, axis=-1, keepdims=True)     # softmax over the chosen
    hot = chosen[..., None] == jnp.arange(e_n)                     # [T, K', E]
    weight = jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)    # [T, E]

    def one(acc, ew):
        wg, wu, wd, col = ew
        gate = y @ wg
        return acc + col[:, None] * (
            (gate / (1.0 + jnp.exp(-gate)) * (y @ wu)) @ wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["we_gate"], lp["we_up"], lp["we_down"],
        weight.T[first:first + held]))
    if "ws_gate" in lp:
        gate = y @ lp["ws_gate"]
        out = out + (gate / (1.0 + jnp.exp(-gate)) * (y @ lp["ws_up"])) \
            @ lp["ws_down"]
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


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 512):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V], record).
    ``record``: per layer (leading axis L) the reference's own ``experts``
    [L, S, K], ``route_gap`` [L, S] (0 without ``routes``), ``held_rows``
    [L] and the sums the router losses need (``counts`` [L, E] of the
    experts computed with, ``prob_sum`` [L, E], ``z_sum`` [L])."""
    by = cfg["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens] * cfg["embedding_multiplier"]

        def layer(kind, x, inp):
            lp, route = inp
            lp = jax.tree.map(lambda w: w.astype(F32), lp)
            if kind == "mamba":
                y = _rms(x, lp["mix_norm"], cfg["norm_eps"])
                x = x + by * _mamba(y, lp, cfg)
            else:
                y = _rms(x, lp["attn_norm"], cfg["norm_eps"])
                x = x + by * _attention(y, lp, cfg, q_block)
            y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
            out, rec = _experts(y, lp, cfg, route)
            return x + by * out, rec

        recs, at = [], 0
        assert len(_runs(cfg)) == len(params["layers"])
        for (kind, n), stack in zip(_runs(cfg), params["layers"]):
            if routes is None:
                x, rec = jax.lax.scan(
                    lambda x, lp, kind=kind: layer(kind, x, (lp, None)),
                    x, stack)
            else:
                x, rec = jax.lax.scan(
                    lambda x, inp, kind=kind: layer(kind, x, inp), x,
                    (stack, routes[at:at + n]))
            recs.append(rec)
            at += n
        rec = jax.tree.map(lambda *r: jnp.concatenate(r), *recs)
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        logits = x @ params["embed"].astype(F32).T / cfg["logits_scaling"]
        return logits, rec


def router_losses(rec: dict, cfg: dict) -> tuple:
    """(load-balancing loss, z-loss) of the sums of ``token_losses``'
    record over every token of every layer, over ALL experts' counts and
    probabilities: transformers' ``load_balancing_loss_func`` (E x sum
    over experts of the share of assignments times the mean probability,
    all layers concatenated) and the mean squared logsumexp of the router
    logits."""
    rows = jnp.sum(rec["counts"]) / rec["experts"].shape[-1]       # L x T
    share = jnp.sum(rec["counts"], axis=0) / rows
    aux = cfg["n_experts"] * jnp.sum(
        share * jnp.sum(rec["prob_sum"], axis=0) / rows)
    return aux, jnp.sum(rec["z_sum"]) / rows


def token_losses(params, tokens, cfg: dict, routes=None):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    (float32 [B, S], record), one sequence at a time so that one
    sequence's states, scores, logits and expert activations are all that
    is alive. ``routes`` [L, B, S, K]."""
    def one(inp):
        seq, route = inp
        logits, rec = forward(params, seq[:-1], cfg, route)
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, rec

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
