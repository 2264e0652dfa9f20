"""The benchmark's copy of ``ray_tpu/models/reference_nemotron.py`` (kept
word for word below this paragraph; ``benchmark/tests/test_nemotron.py``
compares the two): the yardstick reads nothing of the program, so that a
change to the program's copy cannot move what decides ``correct``.

The plain reference of the Nemotron-H block as Nemotron 3 Nano 30B-A3B
has it (``model_type`` ``nemotron_h``; the mixer is Mamba-2,
arXiv:2405.21060) in straightforward ``jax.numpy`` and float32. Every
block is x + half(rms_norm(x)) with ONE half, told from the leaves its
parameters have:

- the Mamba-2 mixer with its recurrence written ONE TOKEN AT A TIME (a
  ``lax.scan`` over the sequence carrying the [H, P, N] state: no chunks,
  no decay matrix, no kernel), B and C indexed BY GROUP (head h reads group
  h // (H / G)), a depthwise causal convolution over all H P + 2 G N
  channels as a sum of four shifted copies, the gated RMS norm with the
  mean square taken over each group's lanes apart;
- causal grouped-query attention with NO position embedding as an explicit
  S x S softmax in blocks of queries, scaled by head_dim^-0.5;
- the expert layer: sigmoid scores, the K largest of score + bias, the K
  scores (without the bias) divided by their sum + 1e-20 and scaled;
  experts of TWO matrices, down(relu(up(x))^2), as a loop over the experts
  HELD here with a 0/1 mask times the weight (what an absent expert would
  add is left out, as in the program); the shared expert likewise;

then the final RMS norm, an UNTIED head, the cross-entropy, DeepSeek-V3's
sequence-wise balance term and the rule that moves the routers' biases
after a step (``bias_update``). It shares nothing with the program but the
layout of the parameter tree (``models/hybrid.py`` ``init_params``:
``params["layers"]`` a list of runs, a run one stack of adjacent blocks of
a kind).

Departures from the source, each where it is made: no rotary tables (the
family's reports: no position embedding; ``_attention``);
``rescale_prenorm_residual`` is an initialisation and no part of the
forward (nothing here); the router's ``n_group`` 1 / ``topk_group`` 1 is no
group limit (``_experts``); the bias rule and the balance term are
DeepSeek-V3's at the rates the configuration's file assumes.

``cfg`` is a dict of HybridConfig field names (``n_heads``, ``n_kv_heads``,
``norm_eps``, ``mamba_heads``, ``mamba_head_dim``, ``mamba_state``,
``mamba_groups``, ``n_experts``, ``top_k``, ``experts_held`` ((count,
first) or None), ``route_scale``, ``router_aux_weight``, ``bias_rate``).
Parameters arrive in the type they are trained in and are cast to float32
one block at a time; matmuls run at ``highest`` precision, because on a TPU
a float32 matmul is otherwise computed in bfloat16 passes.

Routing is discrete. ``routes`` ([L, B, S, K] int32, L the expert blocks
in the layers' order: the experts another implementation chose, numbered
over all ``n_experts``) makes the reference compute with THOSE experts and
its own float32 weights for them, and report per token and expert block how
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


def blocks(params) -> list:
    """The blocks' parameters in the layers' order, each one block's
    leaves (no leading axis): a run's stack layer by layer."""
    return [jax.tree.map(lambda w, r=r: w[r], run)
            for run in params["layers"]
            for r in range(jax.tree.leaves(run)[0].shape[0])]


def _mamba(y, lp, cfg: dict):
    """y [S, D] (normed) -> the mixer's output [S, D], one sequence."""
    h_n, p_n, n_n, g_n = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                          cfg["mamba_state"], cfg["mamba_groups"])
    inner, s = h_n * p_n, y.shape[0]
    proj = y @ lp["in_proj"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g_n * n_n],
                  proj[:, 2 * inner + 2 * g_n * n_n:])
    taps = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
    conv = lp["conv_b"] + sum(padded[j:j + s] * lp["conv_w"][j]
                              for j in range(taps))
    xbc = conv / (1.0 + jnp.exp(-conv))                            # silu
    x = xbc[:, :inner].reshape(s, h_n, p_n)
    b = xbc[:, inner:inner + g_n * n_n].reshape(s, g_n, n_n)
    c = xbc[:, inner + g_n * n_n:].reshape(s, g_n, n_n)
    dt = jnp.logaddexp(dt + lp["dt_bias"], 0.0)                    # softplus
    a = -jnp.exp(lp["a_log"])                                      # [H]
    group = jnp.arange(h_n) // (h_n // g_n)            # head -> its group

    def step(state, inp):
        xt, dtt, bt, ct = inp                    # [H, P], [H], [G, N] twice
        state = state * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[group][:, None, :]
        return state, jnp.sum(state * ct[group][:, None, :], axis=-1)

    _, out = jax.lax.scan(step, jnp.zeros((h_n, p_n, n_n), F32),
                          (x, dt, b, c))
    out = (out + lp["d_skip"][:, None] * x).reshape(s, inner)
    out = (out * (z / (1.0 + jnp.exp(-z)))).reshape(s, g_n, inner // g_n)
    # the mean square over each group's lanes apart
    out = out * jax.lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True)
                              + cfg["norm_eps"])
    return (out.reshape(s, inner) * lp["gate_norm"]) @ lp["out_proj"]


def _attention(y, lp, cfg: dict, q_block: int):
    """y [S, D] (normed) -> the attention block's output [S, D]: no rotary
    tables (``rope_theta`` stays in the config unused)."""
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
        att = jnp.einsum("qhd,khd->hqk", qi, k) * hd ** -0.5
        seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        att = jnp.where(seen, att, -jnp.inf)
        att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", att, v)

    out = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, h * hd)
    return out @ lp["wo"]


def _relu2(y, w_up, w_down):
    return jnp.square(jnp.maximum(y @ w_up, 0.0)) @ w_down


def _experts(y, lp, cfg: dict, routes):
    """y [S, D], one sequence -> (routed experts held here + the shared
    expert [S, D], this block's record). The K largest of ALL experts'
    biased scores: ``n_group`` 1 / ``topk_group`` 1 limit nothing."""
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
        wu, wd, col = ew
        return acc + col[:, None] * _relu2(y, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["we_up"], lp["we_down"], weight.T[first:first + held]))
    out = out + _relu2(y, lp["ws_up"], lp["ws_down"])
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


def block(x, lp, cfg: dict, route=None, q_block: int = 512):
    """One block of one sequence: x [S, D] -> (x + its ONE half of the
    normed x, the expert block's record or None). Which half, the leaves
    say."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    eps = cfg["norm_eps"]
    if "in_proj" in lp:
        return x + _mamba(_rms(x, lp["mix_norm"], eps), lp, cfg), None
    if "wq" in lp:
        return x + _attention(_rms(x, lp["attn_norm"], eps), lp, cfg,
                              q_block), None
    out, rec = _experts(_rms(x, lp["ffn_norm"], eps), lp, cfg, route)
    return x + out, rec


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 512):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V], record).
    ``record``: per expert block (leading axis L) the reference's own
    ``experts`` [L, S, K], ``route_gap`` [L, S] (0 without ``routes``),
    ``held_rows`` [L], ``counts`` [L, E] of the experts computed with and
    the sequence's ``balance`` [L]."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        recs = []
        for lp in blocks(params):
            route = None if routes is None or "router" not in lp \
                else routes[len(recs)]
            x, rec = block(x, lp, cfg, route, q_block)
            if rec is not None:
                recs.append(rec)
        rec = jax.tree.map(lambda *r: jnp.stack(r), *recs)
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        return x @ params["lm_head"].astype(F32), rec


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
    blocks, {"ce", "aux"})."""
    nll, rec = token_losses(params, tokens, cfg, routes)
    ce, aux = nll.mean(), rec["balance"].mean()
    return ce + cfg["router_aux_weight"] * aux, {"ce": ce, "aux": aux}


def biases(params):
    """The routers' biases [L, E] in the layers' order."""
    return jnp.stack([lp["router_bias"] for lp in blocks(params)
                      if "router_bias" in lp])


def bias_update(bias, counts, cfg: dict):
    """The rule after a step: bias [L, E] and the step's assignments to
    every expert, an expert block -> b + u x sign(mean(c) - c)."""
    c = counts.astype(F32)
    return bias + cfg["bias_rate"] * jnp.sign(
        jnp.mean(c, axis=-1, keepdims=True) - c)
