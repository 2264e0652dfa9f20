"""The benchmark's copy of ``ray_tpu/models/reference_solar.py`` (kept word
for word below this paragraph; ``benchmark/tests/test_solar.py`` compares
the two): the yardstick reads nothing of the program, so that a change to
the program's copy cannot move what decides ``correct``.

The plain reference of Solar Open2's block (``model_type`` ``solar_open2``:
the language model of Solar-Open2-250B) in straightforward ``jax.numpy`` and
float32. Every layer is x += first_half(rms_norm(x)) then x +=
experts(rms_norm(x)); which first half, the leaves its parameters have say:

- Kimi Delta Attention (arXiv:2510.26692) with its FIRST gate: q, k, v =
  silu of a causal depthwise convolution (a sum of shifted copies, no bias)
  of three projections; q and k divided head by head by their L2 norm (the
  root of the sum of squares + 1e-6), q scaled dk^-0.5; the gate g =
  -exp(A_log_h) softplus((y Wfa) Wfb + dt_bias), a value a step and KEY
  CHANNEL with NO lower bound; beta = 2 sigmoid(y Wb) a head, in (0, 2);
  then the state S [dk, dv] of every head advanced ONE STEP AT A TIME by a
  ``lax.scan`` over the sequence,
      S <- Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t);
      S <- S + k_t u^T;       o_t = S^T q_t,
  no chunk, no triangular solve, no running sum, no split of a decay into
  factors: independent of the program's chunked form and of its cut; an RMS
  norm over each head of o with one learned scale [dv] shared by the heads,
  a sigmoid gate a CHANNEL through its own low-rank pair (y Wga) Wgb, the
  output projection. No rotary;
- grouped-query attention with NO position table: q = y Wq a query head, k
  and v a key/value head shared by H / KV query heads; a causal softmax
  over an explicit block of scores, a block of queries at a time, scaled by
  head_width^-0.5; an elementwise sigmoid gate sigmoid(y Wg) on the
  output, the output projection; no norm of q or k;
- the expert layer: sigmoid scores s; choice scores s + b; the K largest
  choice scores (equal scores to the lower expert; ONE group); the K scores
  WITHOUT the bias divided by their sum + 1e-20 and scaled; experts of
  three matrices as a loop over the experts HELD here with a 0/1 mask times
  the weight (what an absent expert would add is left out, as in the
  program), plus the shared SwiGLU;

then the final RMS norm, the head, the cross-entropy, DeepSeek-V3's
sequence-wise balance term and the rule that moves the routers' biases
after a step (``bias_update``). It shares nothing with the program but the
layout of the parameter tree (``models/solar.py`` ``init_params``).

Departures from the published description, each where it is made: the row
of the catalog states keys and no code, so the block is the two published
mechanisms it names (Kimi Delta Attention, DeepSeek-V3's ``noaux_tc``
expert layer), and the readings the row does not settle are the
configuration file's ``assumed``: the gate's form (softplus, unbounded),
the rank of both low-rank pairs, the output gate a channel through a gated
norm, the grouped-query gate elementwise and before ``wo``, the score
function and its bias, one group, the shared expert's width, the balance
term and the bias's rule at the rates the file assumes, 1e-20 added to the K
weights' sum. ``intermediate_size`` is the width of a dense layer the model
does not have. A share of the experts and of the vocabulary is what the
parameters hold, nothing here asks.

``cfg`` is a dict of SolarConfig field names (``n_heads``, ``n_kv_heads``,
``head_width``, ``norm_eps``, ``kda_heads``, ``kda_head_dim``,
``n_experts``, ``top_k``, ``experts_held`` ((count, first) or None),
``route_scale``, ``router_aux_weight``, ``bias_rate``). Parameters arrive
in the type they are trained in and are cast to float32 one layer at a
time; matmuls run at ``highest`` precision, because on a TPU a float32
matmul is otherwise computed in bfloat16 passes.

Routing is discrete. ``routes`` ([L, B, S, K] int32, L the expert layers in
the layers' order: the experts another implementation chose, numbered over
all ``n_experts``) makes the reference compute with THOSE experts and its
own float32 weights for them, and report per token and expert layer how far
its own choice lay from them (``route_gap``): the largest choice score the
other gave up less the smallest it took instead. A near tie reads a few
times the rounding of the other's scores; a wrong router reads a whole
score.
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


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


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


def kda_inputs(y, lp, cfg: dict):
    """y [S, D] (normed) -> what the recurrence takes: q, k, v, g [S, H,
    dk] and beta [S, H]."""
    s = y.shape[0]
    h, dk = cfg["kda_heads"], cfg["kda_head_dim"]
    q, k, v = (_conv_silu(y @ lp["w" + n], lp["conv_" + n]).reshape(s, h, dk)
               for n in "qkv")
    q, k = _l2(q) * dk ** -0.5, _l2(k)
    rate = jnp.exp(lp["a_log"])[:, None]                           # [H, 1]
    g = -rate * _softplus(
        ((y @ lp["w_decay_a"]) @ lp["w_decay_b"] + lp["dt_bias"])
        .reshape(s, h, dk))
    beta = 2.0 * _sigmoid(y @ lp["w_beta"])                        # [S, H]
    return q, k, v, g, beta


def kda(y, lp, cfg: dict):
    """y [S, D] (normed) -> the KDA half's output [S, D], one sequence."""
    s = y.shape[0]
    h, dk = cfg["kda_heads"], cfg["kda_head_dim"]
    o, _ = delta_rule(*kda_inputs(y, lp, cfg))
    o = _rms(o, lp["o_norm"], cfg["norm_eps"]).reshape(s, h * dk) \
        * _sigmoid((y @ lp["w_gate_a"]) @ lp["w_gate_b"])
    return o @ lp["wo"]


def gqa(y, lp, cfg: dict, q_block: int = 512):
    """y [S, D] (normed) -> the grouped-query half's output [S, D]."""
    s, h, kv, hd = (y.shape[0], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_width"])
    q = (y @ lp["wq"]).reshape(s, kv, h // kv, hd)
    k = (y @ lp["wk"]).reshape(s, kv, hd)
    v = (y @ lp["wv"]).reshape(s, kv, hd)
    qb = q_block if s % q_block == 0 else s
    kpos = jnp.arange(s)

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        att = jnp.einsum("qgrd,kgd->grqk", qi, k) * hd ** -0.5
        seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        att = jnp.where(seen, att, -jnp.inf)
        att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("grqk,kgd->qgrd", att, v)

    out = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, h * hd)
    return (out * _sigmoid(y @ lp["w_attn_gate"])) @ lp["wo"]


def _swiglu(y, w_gate, w_up, w_down):
    return (_silu(y @ w_gate) * (y @ w_up)) @ w_down


def experts(y, lp, cfg: dict, routes=None):
    """y [S, D], one sequence -> (the routed experts held here plus the
    shared one [S, D], this layer's record). ``routes`` [S, K]: another
    implementation's experts."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    held, first = cfg["experts_held"] or (e_n, 0)
    score = _sigmoid(y @ lp["router"])                             # [S, E]
    ranked = score + lp["router_bias"]
    _, own = jax.lax.top_k(ranked, k_n)
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
    in_chosen = jnp.any(hot, axis=1)                               # [S, E]
    in_best = jnp.any(own[..., None] == jnp.arange(e_n), axis=1)
    gave_up = jnp.max(jnp.where(in_best & ~in_chosen, ranked, -jnp.inf), -1)
    took = jnp.min(jnp.where(in_chosen & ~in_best, ranked, jnp.inf), -1)
    gap = jnp.where(jnp.isfinite(gave_up) & jnp.isfinite(took),
                    gave_up - took, 0.0)
    counts = jnp.sum(hot, axis=(0, 1))                             # [E]
    # the sequence-wise balance loss of this sequence: sum_i f_i P_i
    share = jnp.mean(score / jnp.sum(score, axis=-1, keepdims=True), axis=0)
    balance = jnp.sum(counts * (e_n / (k_n * y.shape[0])) * share)
    return out, {"experts": own, "route_gap": gap, "counts": counts,
                 "held_rows": jnp.sum(counts[first:first + held]),
                 "balance": balance}


def first_half(x, lp, cfg: dict, q_block: int = 512):
    """x [S, D] -> x + the layer's first half of the normed x (float32
    leaves): KDA or grouped-query attention, as the leaves say."""
    y = _rms(x, lp["attn_norm"], cfg["norm_eps"])
    if "w_attn_gate" in lp:
        return x + gqa(y, lp, cfg, q_block)
    return x + kda(y, lp, cfg)


def layer(x, lp, cfg: dict, route=None, q_block: int = 512):
    """One layer of one sequence: x [S, D] -> (x after its two halves, the
    expert layer's record)."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    x = first_half(x, lp, cfg, q_block)
    out, rec = experts(_rms(x, lp["ffn_norm"], cfg["norm_eps"]), lp, cfg,
                       route)
    return x + out, rec


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 512):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V], record).
    ``record``: per expert layer (leading axis L) the reference's own
    ``experts`` [L, S, K], ``route_gap`` [L, S] (0 without ``routes``),
    ``held_rows`` [L], ``counts`` [L, E] of the experts computed with and
    the sequence's ``balance`` [L]."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        recs = []
        for n, lp in enumerate(layers(params)):
            x, rec = layer(x, lp, cfg, None if routes is None else routes[n],
                           q_block)
            recs.append(rec)
        rec = jax.tree.map(lambda *r: jnp.stack(r), *recs)
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        return x @ params["lm_head"].astype(F32), rec


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
    return jnp.stack([lp["router_bias"] for lp in layers(params)])


def bias_update(bias, counts, cfg: dict):
    """The rule after a step: bias [L, E] and the step's assignments to
    every expert, an expert layer -> b + u x sign(mean(c) - c)."""
    c = counts.astype(F32)
    return bias + cfg["bias_rate"] * jnp.sign(
        jnp.mean(c, axis=-1, keepdims=True) - c)
