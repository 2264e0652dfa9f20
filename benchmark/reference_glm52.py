"""The benchmark's copy of ``ray_tpu/models/reference_glm52.py`` (kept word
for word below this paragraph; ``benchmark/tests/test_glm52.py`` compares
the two): the yardstick reads nothing of the program, so that a change to
the program's copy cannot move what decides ``correct``.

The plain reference of the GLM-5.2 block (``glm_moe_dsa``: DeepSeek-V3's
block, arXiv:2405.04434 section 2.1 for the latent attention and
arXiv:2412.19437 section 2.1.2 for the router, with DeepSeek-V3.2's learned
sparse attention over it, arXiv:2512.02556: the lightning indexer, the
top-k selection and the indexer's own loss of its sparse training stage) in
straightforward ``jax.numpy`` and float32: RMSNorm; latent attention with q
and k built HEAD BY HEAD from the two latents (no fused projection, no
kernel), the rotary over interleaved pairs written as a complex
multiplication, ONE rotary key a token shared by all heads; in a FULL layer
the indexer's scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` as
dense ``[t, s]`` blocks, index head by index head, and the set ``Sel_t`` by
a STABLE SORT of a row's causal scores (the ``min(topk, t + 1)`` largest,
equal scores to the smaller s); in a SHARED layer the set of the nearest
full layer before it; every layer's softmax over the set as a 0/1 ``where``
on the dense scores, a block of queries at a time; the indexer's term
``LI`` with its stop-gradients written out; a leading dense SwiGLU layer; a
router that scores with a sigmoid, chooses the K largest of score PLUS
bias and weighs by the chosen scores over their sum times the scaling
factor; experts as a loop over the experts HELD here; the shared SwiGLU;
the loss's terms; and the rule that moves the bias after a step. It shares
nothing with the program but the layout of the parameter tree
(``models/latent.py`` ``init_params``).

Departures from the source, each at its line below: the prediction module
is left out (``num_nextn_predict_layers`` 0: no key says whose set its
block attends over in training); the Hadamard rotation of the index
queries and keys and the FP8 cast of DeepSeek's inference code are left
out (the rotation is orthogonal and cancels in ``qI . kI``; the cast is an
inference format); heads, experts and vocabulary entries that other chips
hold add nothing here, and ``P`` is the mean over the heads held.

``cfg`` is a dict of LatentConfig field names (``d_model``, ``n_heads``,
``norm_eps``, ``rope_theta``, ``q_rank``, ``kv_rank``, ``qk_nope_dim``,
``qk_rope_dim``, ``v_dim``, ``n_experts``, ``top_k``, ``experts_held``,
``route_scale``, ``bias_rate``, ``router_aux_weight``, ``index_heads``,
``index_dim``, ``index_topk``, ``index_full``, ``index_loss_weight``).
Parameters arrive in the type they are trained in and are cast to float32
one layer at a time; matmuls run at ``highest`` precision, because on a
TPU a float32 matmul is otherwise computed in bfloat16 passes.

Both discrete choices can be GIVEN. ``routes`` ([L, B, S, K] int32 over
the L expert layers) and ``sets`` ([F, B, S, S] bool or int8 over the F
full layers, causal) make the reference compute with what another
implementation chose, and report how far its own choice lay from that:
``route_gap`` as ``reference_glm.py`` has it, and for the sets the share
of a query's selections that differ (``set_differ``) and, where they
differ, the largest index score the other gave up less the smallest it
took instead (``set_gap``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
sg = jax.lax.stop_gradient


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale + bias


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


def _heads(y, lp, cfg: dict):
    """y [S, D] (normed) -> (c_q [S, q_rank], q, k, v each [H, S, .]), head
    by head from the two latents."""
    dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_dim"]
    eps, kvr, theta = cfg["norm_eps"], cfg["kv_rank"], cfg["rope_theta"]
    c_q = _rms(y @ lp["wq_a"], lp["q_a_norm"], eps)
    joint = y @ lp["wkv_a"]
    c_kv = _rms(joint[:, :kvr], lp["kv_a_norm"], eps)
    k_r = _turn(joint[:, kvr:], theta)                     # shared by heads
    q, k, v = [], [], []
    for h in range(cfg["n_heads"]):
        wq = lp["wq_b"][:, h * (dn + dr):(h + 1) * (dn + dr)]
        wkv = lp["wkv_b"][:, h * (dn + dv):(h + 1) * (dn + dv)]
        q.append(jnp.concatenate([c_q @ wq[:, :dn],
                                  _turn(c_q @ wq[:, dn:], theta)], axis=-1))
        k.append(jnp.concatenate([c_kv @ wkv[:, :dn], k_r], axis=-1))
        v.append(c_kv @ wkv[:, dn:])
    return c_q, jnp.stack(q), jnp.stack(k), jnp.stack(v)


def _index_inputs(y, c_q, lp, cfg: dict):
    """A full layer's index queries [IH, S, ID], keys [S, ID] and head
    weights [S, IH], all from DETACHED inputs (the indexer's inputs carry
    no gradient back into the model)."""
    ih, idim, dr = cfg["index_heads"], cfg["index_dim"], cfg["qk_rope_dim"]
    theta = cfg["rope_theta"]
    y, c_q = sg(y), sg(c_q)

    def turned(x):       # the rotary on the FIRST dr lanes, the rest as is
        return jnp.concatenate([_turn(x[:, :dr], theta), x[:, dr:]], axis=-1)

    flat = c_q @ lp["wi_q"]
    q_i = jnp.stack([turned(flat[:, j * idim:(j + 1) * idim])
                     for j in range(ih)])
    # (the Hadamard rotation of q_i and k_i is left out: orthogonal, it
    # cancels in their product; so is the FP8 cast, an inference format)
    k_i = turned(_layer_norm(y @ lp["wi_k"], lp["wi_k_norm"],
                             lp["wi_k_bias"], cfg["norm_eps"]))
    w = (y @ lp["wi_w"]) * (ih ** -0.5) * (idim ** -0.5)
    return q_i, k_i, w


def own_set(scores, first_row, topk: int):
    """scores [R, S] of the queries at first_row + r -> the reference's set
    [R, S] bool by a stable sort: a row's causal scores in falling order,
    equal scores in rising s, the first min(topk, t + 1) kept."""
    r, s = scores.shape
    t = first_row + jnp.arange(r)[:, None]
    causal = jnp.arange(s)[None, :] <= t
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=1,
                        stable=True)
    rank = jnp.argsort(order, axis=1, stable=True)     # a key's place
    return causal & (rank < topk)


def _attention(y, lp, cfg: dict, given, handed, q_block: int,
               compare: bool = True):
    """y [S, D] (normed) -> (the attention's output [S, D], the set the
    layer attended over [S, S] bool, the layer's record or None). given:
    another implementation's set for this FULL layer or None; handed: the
    set of the full layer before (what a SHARED layer attends over). A
    layer is full where its parameters hold an indexer. Without
    ``compare`` a given set is taken as it is and the reference's own
    choice is not made (its sort is most of a full layer's time)."""
    h_n, dv = cfg["n_heads"], cfg["v_dim"]
    s = y.shape[0]
    full = "wi_q" in lp
    c_q, q, k, v = _heads(y, lp, cfg)
    scale = 1.0 / jnp.sqrt(F32(cfg["qk_nope_dim"] + cfg["qk_rope_dim"]))
    qb = q_block if s % q_block == 0 else s
    if full:
        q_i, k_i, w_i = _index_inputs(y, c_q, lp, cfg)

    def rows(i):
        at = i * qb
        out = {}
        if full:
            # I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]), head by head
            def index_head(acc, qw):
                qj, wj = qw
                qj = jax.lax.dynamic_slice_in_dim(qj, at, qb, axis=0)
                wj = jax.lax.dynamic_slice_in_dim(wj, at, qb, axis=0)
                return acc + wj[:, None] * jnp.maximum(qj @ k_i.T, 0.0), None

            scores, _ = jax.lax.scan(index_head, jnp.zeros((qb, s), F32),
                                     (q_i, w_i.T))
            if given is None:
                keep = own = own_set(sg(scores), at, cfg["index_topk"])
            else:
                keep = jax.lax.dynamic_slice_in_dim(given, at, qb,
                                                    axis=0) != 0
                own = own_set(sg(scores), at, cfg["index_topk"]) \
                    if compare else keep
            # how far the reference's own choice lay from the given one
            gave_up = jnp.max(jnp.where(own & ~keep, scores, -jnp.inf), -1)
            took = jnp.min(jnp.where(keep & ~own, scores, jnp.inf), -1)
            out["set_gap"] = sg(jnp.where(
                jnp.isfinite(gave_up) & jnp.isfinite(took), gave_up - took,
                0.0))
            out["set_differ"] = jnp.sum(own & ~keep, axis=-1) \
                / jnp.sum(own, axis=-1)
        else:
            keep = jax.lax.dynamic_slice_in_dim(handed, at, qb, axis=0)

        def head(total, qkv):
            qh, kh, vh = qkv
            att = (jax.lax.dynamic_slice_in_dim(qh, at, qb, axis=0)
                   @ kh.T) * scale
            att = jnp.where(keep, att, -jnp.inf)       # the set as 0/1
            att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
            p = att / jnp.sum(att, axis=-1, keepdims=True)
            return total + p, p @ vh

        total, outs = jax.lax.scan(head, jnp.zeros((qb, s), F32), (q, k, v))
        out["o"] = jnp.moveaxis(outs, 0, 1).reshape(qb, h_n * dv)
        out["keep"] = keep
        if full:
            # LI's rows: P = sg(mean over the heads HELD of p) on the set,
            # against the indexer's own softmax over the same set
            p = sg(total / h_n)
            on = jnp.where(keep, scores, -jnp.inf)
            top = jnp.max(on, axis=-1, keepdims=True)
            log_q = on - (top + jnp.log(jnp.sum(jnp.exp(on - top), axis=-1,
                                                keepdims=True)))
            out["li"] = jnp.sum(jnp.where(
                keep & (p > 0), p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                     - jnp.where(keep, log_q, 0.0)), 0.0),
                axis=-1)
        return out

    got = jax.lax.map(rows, jnp.arange(s // qb))
    flat = {n: a.reshape((s,) + a.shape[2:]) for n, a in got.items()}
    rec = None
    if full:
        rec = {"index_loss": jnp.mean(flat["li"]),
               "set_gap": flat["set_gap"], "set_differ": flat["set_differ"]}
    return flat["o"] @ lp["wo"], flat["keep"], rec


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


def block(x, lp, cfg: dict, route=None, given=None, handed=None,
          q_block: int = 512, compare: bool = True):
    """One block on one sequence x [S, D]: the attention over its set,
    then the feed-forward the layer's parameters say (a dense SwiGLU where
    it has no router). -> (x, the set attended over, the indexer's record
    or None, the expert layer's record or None)."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    a, keep, index = _attention(_rms(x, lp["attn_norm"], cfg["norm_eps"]),
                                lp, cfg, given, handed, q_block, compare)
    x = x + a
    y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
    if "router" not in lp:
        return x + _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), \
            keep, index, None
    out, rec = _experts(y, lp, cfg, route)
    return x + out, keep, index, rec


def forward(params, tokens, cfg: dict, routes=None, sets=None,
            q_block: int = 512, compare: bool = True):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V], record).
    ``record``: ``index`` per full layer (leading axis F): ``index_loss``
    [F], ``set_gap`` and ``set_differ`` [F, S]; ``own_sets`` [F, S, S]
    bool, the sets the layers attended over (the given ones where given);
    per expert layer (leading axis L) the reference's own ``experts``
    [L, S, K], ``route_gap`` [L, S], ``counts`` [L, E], ``held_rows`` [L]
    and ``balance`` [L]. (No prediction module: the configuration leaves
    it out.)"""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        index, experts, used, handed = [], [], [], None
        at = full_at = 0
        for stack in params["layers"]:
            for n in range(jax.tree.leaves(stack)[0].shape[0]):
                lp = jax.tree.map(lambda w, n=n: w[n], stack)
                sparse, full = "router" in lp, "wi_q" in lp
                x, handed, ind, rec = block(
                    x, lp, cfg,
                    None if routes is None or not sparse else routes[at],
                    None if sets is None or not full else sets[full_at],
                    handed, q_block, compare)
                if sparse:
                    experts.append(rec)
                    at += 1
                if full:
                    index.append(ind)
                    used.append(handed)
                    full_at += 1
        logits = _rms(x, params["final_norm"], cfg["norm_eps"]) \
            @ params["lm_head"].astype(F32)
        stack = lambda recs: jax.tree.map(                     # noqa: E731
            lambda *r: jnp.stack(r), *recs)
        return logits, {"index": stack(index), "own_sets": jnp.stack(used),
                        **stack(experts)}


def token_losses(params, tokens, cfg: dict, routes=None, sets=None,
                 q_block: int = 512, compare: bool = True):
    """Cross-entropy of every position of tokens [B, S + 1] -> (float32
    [B, S], record), one sequence at a time. ``routes`` [L, B, S, K],
    ``sets`` [F, B, S, S]."""
    def one(seq, route, given):
        logits, rec = forward(params, seq[:-1], cfg, route, given, q_block,
                              compare)
        picked = jnp.take_along_axis(logits, seq[1:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, rec

    got = [one(tokens[b], None if routes is None else routes[:, b],
               None if sets is None else sets[:, b])
           for b in range(tokens.shape[0])]
    nll = jnp.stack([g[0] for g in got])
    recs = [g[1] for g in got]
    over = lambda name, axis=1: jnp.stack(                     # noqa: E731
        [r[name] for r in recs], axis=axis)
    rec = {"experts": over("experts"), "route_gap": over("route_gap"),
           "counts": over("counts", 0).sum(0),
           "balance": over("balance", 0).mean(0),
           "held_rows": over("held_rows", 0).sum(0),
           "own_sets": over("own_sets"),
           "index_loss": jnp.stack([r["index"]["index_loss"]
                                    for r in recs]).mean(0),
           "set_gap": jnp.stack([r["index"]["set_gap"] for r in recs], 1),
           "set_differ": jnp.stack([r["index"]["set_differ"]
                                    for r in recs], 1)}
    return nll, rec


def loss(params, tokens, cfg: dict, routes=None, sets=None,
         q_block: int = 512, compare: bool = True):
    """The training loss of tokens [B, S + 1] and its terms: (cross-entropy
    + router_aux_weight x the balance loss averaged over sequences and
    expert layers + index_loss_weight x the SUM of the full layers' LI,
    {"main", "balance", "index" [F], "counts" [L, E]})."""
    nll, rec = token_losses(params, tokens, cfg, routes, sets, q_block,
                            compare)
    parts = {"main": nll.mean(), "balance": rec["balance"].mean(),
             "index": rec["index_loss"], "counts": rec["counts"]}
    return (parts["main"] + cfg["router_aux_weight"] * parts["balance"]
            + cfg["index_loss_weight"] * jnp.sum(parts["index"])), parts


def biases(params):
    """Every expert layer's router bias [L, E], in the layers' order."""
    return jnp.concatenate([s["router_bias"].astype(F32)
                            for s in params["layers"] if "router_bias" in s])


def bias_update(bias, counts, cfg: dict):
    """The rule after a step: bias [L, E] and the step's assignments to
    every expert, a layer -> b + u x sign(mean(c) - c)."""
    c = counts.astype(F32)
    return bias + cfg["bias_rate"] * jnp.sign(
        jnp.mean(c, axis=-1, keepdims=True) - c)
