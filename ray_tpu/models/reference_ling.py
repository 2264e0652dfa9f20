"""The plain reference of Ling 3.0's block (``model_type``
``bailing_hybrid``: the language model of Ling-3.0-flash) in straightforward
``jax.numpy`` and float32. Every layer is x += first_half(rms_norm(x)) then
x += feed_forward(rms_norm(x)); which first half and which feed-forward,
the leaves its parameters have say:

- Kimi Delta Attention (arXiv:2510.26692): q, k, v = silu of a causal
  depthwise convolution (a sum of shifted copies, no bias) of three
  projections; q and k divided head by head by their L2 norm (the root of
  the sum of squares + 1e-6), q scaled dk^-0.5; the gate g = L
  sigmoid(exp(A_log_h) (y Wf + dt_bias)) with L = ``kda_lower_bound``, a
  value a step and KEY CHANNEL; beta = sigmoid(y Wb) a head; then the state
  S [dk, dv] of every head advanced ONE STEP AT A TIME by a ``lax.scan``
  over the sequence,
      S <- Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t);
      S <- S + k_t u^T;       o_t = S^T q_t,
  no chunk, no triangular solve, no running sum: independent of the
  program's chunked form; an RMS norm over each head of o with one learned
  scale [dv], ONE sigmoid gate a head, the output projection. No rotary;
- latent (MLA) attention WITHOUT a query latent: q = y Wq a head [nope |
  rope]; [c | k_r] = y Wkv_a; [k_nope | v] = rms(c) Wkv_b a head; the
  rotary turns interleaved pairs (2i, 2i + 1) of q's last lanes and of k_r,
  ONE rotary key a token shared by the heads; a causal softmax over an
  explicit block of scores, a block of queries at a time, scaled by (nope +
  rope)^-0.5, over values NARROWER than the keys (nothing is padded here);
  one sigmoid gate a head on the output, the output projection;
- a dense SwiGLU (the leading layers), or the expert layer: sigmoid scores
  s; choice scores s + b; a GROUP's score the sum of its two largest choice
  scores (``n_group`` groups of adjacent experts); the ``topk_group`` best
  groups kept (equal scores to the lower group); the K largest choice
  scores inside them (equal scores to the lower expert); the K scores
  WITHOUT the bias divided by their sum + 1e-20 and scaled; experts of
  three matrices as a loop over the experts HELD here with a 0/1 mask times
  the weight (what an absent expert would add is left out, as in the
  program), plus the shared SwiGLU;

then the final RMS norm, the head, the cross-entropy, DeepSeek-V3's
sequence-wise balance term and the rule that moves the routers' biases
after a step (``bias_update``). It shares nothing with the program but the
layout of the parameter tree (``models/ling.py`` ``init_params``).

Departures from the published description, each where it is made: the row
of the catalog states keys and no code, so the block is the two published
mechanisms it names (``kda``, ``mla``), and the readings the row does not
settle are the configuration file's ``assumed``: which layers attend
through latents (``(l + 1) % layer_group_size == 0``: the leaves say here),
no rotary in a KDA half, ``use_qk_norm`` read as the L2 norm a head in a
KDA half and as the latent's norm in an MLA half, the head-wise gates
before the output projections, a group's score the sum of its TWO largest
choice scores, experts of other groups out of the choice whatever they
score (``-inf``, where DeepSeek-V3's published code writes 0.0), the
balance term and the bias's rule at the rates the file assumes, 1e-20 added
to the K weights' sum. The expert clamp (``expert_swiglu_limit_list``) is 0
in every layer of the cut and is not built. A share of the experts and of
the vocabulary is what the parameters hold, nothing here asks.

``cfg`` is a dict of LingConfig field names (``n_heads``, ``norm_eps``,
``rope_theta``, ``kv_rank``, ``qk_nope_dim``, ``qk_rope_dim``, ``v_dim``,
``kda_head_dim``, ``kda_lower_bound``, ``n_experts``, ``top_k``,
``n_group``, ``topk_group``, ``experts_held`` ((count, first) or None),
``route_scale``, ``router_aux_weight``, ``bias_rate``). Parameters arrive
in the type they are trained in and are cast to float32 one layer at a
time; matmuls run at ``highest`` precision, because on a TPU a float32
matmul is otherwise computed in bfloat16 passes.

Routing is discrete. ``routes`` ([L, B, S, K] int32, L the expert layers in
the layers' order: the experts another implementation chose, numbered over
all ``n_experts``) makes the reference compute with THOSE experts and its
own float32 weights for them, and report per token and expert layer how far
its own choice lay from them (``route_gap``), in two parts, whichever is
larger. The GROUPS: ``groups`` ([L, B, S, G] bool: the groups the other
kept; without them, the reference's own and any group the other took an
expert from) against the reference's kept groups: for a group the other
kept and the reference did not, the reference's weakest kept group's score
less that group's. The EXPERTS, given the other's groups: the reference's
own K best inside THOSE groups against the other's K: the largest choice
score the other gave up less the smallest it took instead. A near tie, of
groups or of experts, reads a few times the rounding of the other's scores;
a wrong router reads a whole score, a router without the group limit the
distance between groups.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def layers(params) -> list:
    """The layers' parameters in the layers' order, each one layer's
    leaves (no leading axis): a run's stack layer by layer."""
    return [jax.tree.map(lambda w, r=r: w[r], run)
            for run in params["layers"]
            for r in range(jax.tree.leaves(run)[0].shape[0])]


def _conv_silu(x, w):
    """x [S, C], w [taps, C] -> silu of the causal depthwise convolution."""
    s, taps = x.shape[0], w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), F32), x])
    return _silu(sum(padded[j:j + s] * w[j] for j in range(taps)))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence a step at a time: q, k, g [S, H, dk], v [S, H, dv],
    beta [S, H] -> (o [S, H, dv], the last state [H, dk, dv])."""
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hc,hcv->hv", k_t, s))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hc,hcv->hv", q_t, s)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def kda(y, lp, cfg: dict):
    """y [S, D] (normed) -> the KDA half's output [S, D], one sequence."""
    s = y.shape[0]
    h, dk = cfg["n_heads"], cfg["kda_head_dim"]
    q, k, v = (_conv_silu(y @ lp["w" + n], lp["conv_" + n]).reshape(s, h, dk)
               for n in "qkv")
    q, k = _l2(q) * dk ** -0.5, _l2(k)
    rate = jnp.exp(lp["a_log"])[:, None]                           # [H, 1]
    g = cfg["kda_lower_bound"] * _sigmoid(
        rate * ((y @ lp["w_decay"]) + lp["dt_bias"]).reshape(s, h, dk))
    beta = _sigmoid(y @ lp["w_beta"])                              # [S, H]
    o, _ = delta_rule(q, k, v, g, beta)
    o = _rms(o, lp["o_norm"], cfg["norm_eps"]) \
        * _sigmoid(y @ lp["w_out_gate"])[:, :, None]
    return o.reshape(s, h * dk) @ lp["wo"]


def _rotary_pairs(x, theta: float):
    """x [S, ..., R]: interleaved pairs (2i, 2i + 1) turned by t
    theta^(-2 i / R)."""
    s, r = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    angle = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]      # [S, R/2]
    shape = (s,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def mla(y, lp, cfg: dict, q_block: int = 512):
    """y [S, D] (normed) -> the latent half's output [S, D]."""
    s, h = y.shape[0], cfg["n_heads"]
    dn, r, dv, rk = (cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_dim"],
                     cfg["kv_rank"])
    q = (y @ lp["wq"]).reshape(s, h, dn + r)
    q = jnp.concatenate(
        [q[..., :dn], _rotary_pairs(q[..., dn:], cfg["rope_theta"])], axis=-1)
    kv_a = y @ lp["wkv_a"]
    k_r = _rotary_pairs(kv_a[:, rk:], cfg["rope_theta"])           # [S, R]
    kv = (_rms(kv_a[:, :rk], lp["kv_a_norm"], cfg["norm_eps"])
          @ lp["wkv_b"]).reshape(s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r[:, None, :], (s, h, r))], axis=-1)
    v = kv[..., dn:]
    qb = q_block if s % q_block == 0 else s
    kpos = jnp.arange(s)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        att = jnp.einsum("qhd,khd->hqk", qi, k) * (dn + r) ** -0.5
        seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        att = jnp.where(seen, att, -jnp.inf)
        att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", att, v)

    out = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, h, dv)
    out = out * _sigmoid(y @ lp["w_attn_gate"])[:, :, None]
    return out.reshape(s, h * dv) @ lp["wo"]


def _swiglu(y, w_gate, w_up, w_down):
    return (_silu(y @ w_gate) * (y @ w_up)) @ w_down


def choose(score, bias, cfg: dict):
    """Scores [S, E] and the bias [E] -> (the K experts chosen [S, K], the
    kept groups [S, G] bool, the groups' scores [S, G])."""
    e_n, g_n = cfg["n_experts"], cfg["n_group"]
    biased = score + bias
    grouped = biased.reshape(-1, g_n, e_n // g_n)
    two = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)        # [S, G]
    # the topk_group best groups, equal scores to the lower group: a
    # group is kept iff fewer than topk_group groups beat it
    at = jnp.arange(g_n)
    beats = (two[:, None, :] > two[:, :, None]) | (
        (two[:, None, :] == two[:, :, None]) & (at[None, :] < at[:, None]))
    kept = jnp.sum(beats, axis=-1) < cfg["topk_group"]             # [S, G]
    inside = jnp.where(jnp.repeat(kept, e_n // g_n, axis=1), biased,
                       -jnp.inf)
    _, own = jax.lax.top_k(inside, cfg["top_k"])
    return own, kept, two


def experts(y, lp, cfg: dict, routes=None, groups=None):
    """y [S, D], one sequence -> (the routed experts held here plus the
    shared one [S, D], this layer's record). ``routes`` [S, K] and
    ``groups`` [S, G]: another implementation's experts and kept groups."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    held, first = cfg["experts_held"] or (e_n, 0)
    score = _sigmoid(y @ lp["router"])                             # [S, E]
    own, kept, two = choose(score, lp["router_bias"], cfg)
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
    out = out + _swiglu(y, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    per = e_n // cfg["n_group"]
    in_chosen = jnp.any(hot, axis=1)                               # [S, E]
    # the other's groups: as given, and any group it took an expert from
    theirs = (kept if groups is None else groups.astype(bool)) | jnp.any(
        in_chosen.reshape(-1, cfg["n_group"], per), axis=-1)       # [S, G]
    weakest = jnp.min(jnp.where(kept, two, jnp.inf), axis=-1, keepdims=True)
    group_gap = jnp.max(jnp.where(theirs & ~kept, weakest - two, 0.0),
                        axis=-1)
    # the reference's own K best inside THEIR groups, against their K
    ranked = jnp.where(jnp.repeat(theirs, per, axis=1),
                       score + lp["router_bias"], -jnp.inf)
    _, best = jax.lax.top_k(ranked, k_n)
    in_best = jnp.any(best[..., None] == jnp.arange(e_n), axis=1)
    gave_up = jnp.max(jnp.where(in_best & ~in_chosen, ranked, -jnp.inf), -1)
    took = jnp.min(jnp.where(in_chosen & ~in_best, ranked, jnp.inf), -1)
    gap = jnp.maximum(group_gap, jnp.where(
        jnp.isfinite(gave_up) & jnp.isfinite(took), gave_up - took, 0.0))
    counts = jnp.sum(hot, axis=(0, 1))                             # [E]
    # the sequence-wise balance loss of this sequence: sum_i f_i P_i
    share = jnp.mean(score / jnp.sum(score, axis=-1, keepdims=True), axis=0)
    balance = jnp.sum(counts * (e_n / (k_n * y.shape[0])) * share)
    return out, {"experts": own, "route_gap": gap, "counts": counts,
                 "held_rows": jnp.sum(counts[first:first + held]),
                 "balance": balance,
                 "group_kept": kept[:, first // (e_n // cfg["n_group"])]
                 .mean(dtype=F32)}


def first_half(x, lp, cfg: dict, q_block: int = 512):
    """x [S, D] -> x + the layer's first half of the normed x (float32
    leaves): KDA or latent attention, as the leaves say."""
    y = _rms(x, lp["attn_norm"], cfg["norm_eps"])
    if "wkv_a" in lp:
        return x + mla(y, lp, cfg, q_block)
    return x + kda(y, lp, cfg)


def layer(x, lp, cfg: dict, route=None, q_block: int = 512, groups=None):
    """One layer of one sequence: x [S, D] -> (x after its two halves, the
    expert layer's record or None for a dense one)."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    x = first_half(x, lp, cfg, q_block)
    y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
    if "router" not in lp:
        return x + _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    out, rec = experts(y, lp, cfg, route, groups)
    return x + out, rec


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 512,
            groups=None):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V], record).
    ``record``: per expert layer (leading axis L) the reference's own
    ``experts`` [L, S, K], ``route_gap`` [L, S] (0 without ``routes``),
    ``held_rows`` [L], ``counts`` [L, E] of the experts computed with, the
    sequence's ``balance`` [L] and ``group_kept`` [L]."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        recs = []
        for lp in layers(params):
            route, kept = (None if r is None or "router" not in lp
                           else r[len(recs)] for r in (routes, groups))
            x, rec = layer(x, lp, cfg, route, q_block, kept)
            if rec is not None:
                recs.append(rec)
        rec = jax.tree.map(lambda *r: jnp.stack(r), *recs)
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        return x @ params["lm_head"].astype(F32), rec


def token_losses(params, tokens, cfg: dict, routes=None, groups=None):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    (float32 [B, S], record), one sequence at a time so that one
    sequence's scores, logits and expert activations are all that is
    alive. ``routes`` [L, B, S, K], ``groups`` [L, B, S, G] (with routes
    only)."""
    def one(inp):
        seq, route, kept = inp
        logits, rec = forward(params, seq[:-1], cfg, route, groups=kept)
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, rec

    if routes is None:
        nll, rec = jax.lax.map(lambda seq: one((seq, None, None)), tokens)
    elif groups is None:
        nll, rec = jax.lax.map(lambda x: one((*x, None)),
                               (tokens, jnp.moveaxis(routes, 1, 0)))
    else:
        nll, rec = jax.lax.map(one, (tokens, jnp.moveaxis(routes, 1, 0),
                                     jnp.moveaxis(groups, 1, 0)))
    # [B, L, S, ...] -> [L, B, S, ...]; the sums over the batch; the
    # balance term and the kept share averaged over the batch's sequences
    rec = {"experts": jnp.moveaxis(rec["experts"], 0, 1),
           "route_gap": jnp.moveaxis(rec["route_gap"], 0, 1),
           "counts": rec["counts"].sum(0),
           "held_rows": rec["held_rows"].sum(0),
           "balance": rec["balance"].mean(0),
           "group_kept": rec["group_kept"].mean(0)}
    return nll, rec


def loss(params, tokens, cfg: dict, routes=None, groups=None):
    """The training loss of tokens [B, S+1] and its terms: (cross-entropy
    + router_aux_weight x the balance term averaged over the expert
    layers, {"ce", "aux"})."""
    nll, rec = token_losses(params, tokens, cfg, routes, groups)
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
