"""The plain reference of the Command A+ block (CohereLabs/command-a-plus-
05-2026: ``model_type`` "cohere2_moe") in straightforward ``jax.numpy`` and
float32. A layer is a PARALLEL block: one LayerNorm (the mean subtracted, a
scale and no bias) feeds the attention and the experts side by side, and
the layer is ``x + attention(n) + routed(n) + shared(n)``. Attention:
causal grouped-query attention as an explicit softmax over a masked score
matrix (masks as ``where``: ``j <= i``, and in a sliding layer ``i - j <
window``), a query block at a time so that the scores fit; in the sliding
layers rotary on all lanes of a head, NEIGHBOURING lanes ``(2i, 2i + 1)``
paired (``position_embedding_type`` "rope_gptj"), written on the pairs; in
the full layers q and k as projected, no table. Experts: a router whose
scores are sigmoids over all experts, the K largest kept and divided by
their sum; the routed experts as a loop over the experts HELD here with a
0/1 mask times the weight (what an absent expert would add is left out, as
in the program); the shared experts computed one by one and averaged. The
final norm is the same LayerNorm, the head the tied embedding over the
vocabulary held (times ``logit_scale``), the loss the cross-entropy alone.
It shares nothing with the program but the layout of the parameter tree
(``models/moe.py`` ``init_params`` with ``layer_kinds``: a list of stacks,
one a run of layers of one kind; the shared experts' weights side by side
in ``ws_gate``, ``ws_up`` [D, n x width] and ``ws_down`` [n x width, D]).

``cfg`` is a dict: ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_width``,
``norm_eps``, ``n_experts``, ``top_k``, ``experts_held`` ((count, first) or
None), ``layer_kinds`` (one name a layer), ``kinds`` ({name: {"window": int
or None, "rope_theta": float or None (None: no table)}}), ``n_shared``,
``shared_d_ff`` (one shared expert's width), ``logit_scale``. Parameters
arrive in the type they are trained in and are cast to float32 one layer at
a time; matmuls run at ``highest`` precision, because on a TPU a float32
matmul is otherwise computed in bfloat16 passes.

Departures from the source, each at its line below: the query heads, the
KV heads, the routed experts and the vocabulary are this chip's share; the
vision tower is not the language model's and is not here.

Routing is discrete. ``routes`` ([L, B, S, K] int32: the experts another
implementation chose, numbered over all ``n_experts``) makes the reference
compute with THOSE experts and its own float32 weights for them, and
report per token and layer how far its own choice lay from them
(``route_gap``): where the sets differ, the largest of its scores that the
other gave up less the smallest it took instead.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, scale, eps):
    """(x - mean) / sqrt(var + eps) x scale over the lanes, no bias."""
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta: float):
    """x [S, heads, dim]: lanes (2i, 2i + 1) turned by position x theta ^
    (-2i / dim), the pair as a complex number's two parts."""
    s, _, dim = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angles = jnp.outer(jnp.arange(s, dtype=F32), inv)[:, None, :]
    pair = x.reshape(*x.shape[:-1], dim // 2, 2)
    a, b = pair[..., 0], pair[..., 1]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(y, lp, cfg: dict, kind: dict, q_block: int):
    """y [S, D] (normed) -> the attention's output [S, D]. Departure: the
    source has 128 query heads over 8 KV heads; these are the ``n_heads``
    over ``n_kv_heads`` held here, with their rows of ``wo``."""
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    s = y.shape[0]
    q = (y @ lp["wq"]).reshape(s, h, hd)
    k = (y @ lp["wk"]).reshape(s, kv, hd)
    if kind.get("rope_theta") is not None:      # a full layer turns nothing
        q, k = _rope(q, kind["rope_theta"]), _rope(k, kind["rope_theta"])
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


def _swiglu(y, wg, wu, wd):
    gate = y @ wg
    return (gate / (1.0 + jnp.exp(-gate)) * (y @ wu)) @ wd


def shared(y, lp, cfg: dict):
    """y [T, D] (normed) -> the shared experts' part [T, D]: each of the
    ``n_shared`` experts on its own, their outputs averaged
    (``shared_expert_combination_strategy`` "average")."""
    n, f = cfg["n_shared"], cfg["shared_d_ff"]
    out = jnp.zeros_like(y)
    for j in range(n):
        cols = slice(j * f, (j + 1) * f)
        out = out + _swiglu(y, lp["ws_gate"][:, cols], lp["ws_up"][:, cols],
                            lp["ws_down"][cols])
    return out / n


def routed(y, lp, cfg: dict, routes=None):
    """y [T, D] (normed) -> (the routed experts held here [T, D], this
    layer's record). Departure: the source sums all ``n_experts``' parts;
    this is the chip's share of them (``experts_held``; None: all)."""
    e_n, k_n = cfg["n_experts"], cfg["top_k"]
    held, first = cfg["experts_held"] or (e_n, 0)
    score = 1.0 / (1.0 + jnp.exp(-(y @ lp["router"])))             # [T, E]
    _, own = jax.lax.top_k(score, k_n)
    chosen = own if routes is None else routes
    w = jnp.take_along_axis(score, chosen, axis=-1)                # [T, K]
    w = w / jnp.sum(w, axis=-1, keepdims=True)            # norm_topk_prob
    hot = chosen[..., None] == jnp.arange(e_n)                     # [T, K, E]
    weight = jnp.sum(jnp.where(hot, w[..., None], 0.0), axis=1)    # [T, E]

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + col[:, None] * _swiglu(y, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["we_gate"], lp["we_up"], lp["we_down"],
        weight.T[first:first + held]))
    in_own = jnp.any(own[..., None] == jnp.arange(e_n), axis=1)    # [T, E]
    in_chosen = jnp.any(hot, axis=1)
    gave_up = jnp.max(jnp.where(in_own & ~in_chosen, score, 0.0), axis=-1)
    took = jnp.min(jnp.where(in_chosen & ~in_own, score, jnp.inf), axis=-1)
    gap = jnp.where(gave_up > 0, gave_up - jnp.where(
        jnp.isfinite(took), took, 0.0), 0.0)
    counts = jnp.sum(hot, axis=(0, 1))
    return out, {"experts": own, "route_gap": gap, "counts": counts,
                 "held_rows": jnp.sum(counts[first:first + held])}


def layer(x, lp, cfg: dict, kind: dict, route=None, q_block: int = 256):
    """One parallel block: x [S, D] -> (x + attention(n) + routed(n) +
    shared(n), record), n the ONE norm of x."""
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    n = _layer_norm(x, lp["attn_norm"], cfg["norm_eps"])
    out, rec = routed(n, lp, cfg, route)
    return x + _attention(n, lp, cfg, kind, q_block) + out \
        + shared(n, lp, cfg), rec


def trunk(params, tokens, cfg: dict, routes=None, q_block: int = 256):
    """tokens [S] (ONE sequence) -> (the residual stream after the final
    norm, float32 [S, D], record). ``record``: per layer (leading axis L)
    the reference's own ``experts`` [L, S, K], ``route_gap`` [L, S] (0
    without ``routes``), ``held_rows`` [L], ``counts`` [L, E] of the
    experts computed with."""
    x = params["embed"].astype(F32)[tokens]
    recs, at = [], 0
    for stack in params["layers"]:      # a stack: adjacent layers of a kind
        n = stack["wq"].shape[0]
        assert len(set(cfg["layer_kinds"][at:at + n])) == 1, (at, n)
        of = cfg["kinds"][cfg["layer_kinds"][at]]
        if routes is None:
            x, rec = jax.lax.scan(
                lambda x, lp, of=of: layer(x, lp, cfg, of, None, q_block),
                x, stack)
        else:
            x, rec = jax.lax.scan(
                lambda x, inp, of=of: layer(x, inp[0], cfg, of, inp[1],
                                            q_block),
                x, (stack, routes[at:at + n]))
        recs.append(rec)
        at += n
    assert at == len(cfg["layer_kinds"]), at
    rec = jax.tree.map(lambda *r: jnp.concatenate(r), *recs)
    return _layer_norm(x, params["final_norm"], cfg["norm_eps"]), rec


def forward(params, tokens, cfg: dict, routes=None, q_block: int = 256):
    """tokens [S] (ONE sequence) -> (float32 logits [S, V] over the
    vocabulary held, record). Departure: the source's table has 262,144
    rows; these are the rows held here."""
    with jax.default_matmul_precision("highest"):
        x, rec = trunk(params, tokens, cfg, routes, q_block)
        return x @ params["embed"].astype(F32).T * cfg["logit_scale"], rec


def token_losses(params, tokens, cfg: dict, routes=None,
                 head_rows: int = 2048):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    (float32 [B, S], record), one sequence at a time and the head
    ``head_rows`` positions at a time, so that one sequence's scores,
    expert activations and one block of logits are all that is alive.
    ``routes`` [L, B, S, K]."""
    head = params["embed"].astype(F32).T * cfg["logit_scale"]   # tied

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
           "counts": rec["counts"].sum(0),
           "held_rows": rec["held_rows"].sum(0)}
    return nll, rec


def loss(params, tokens, cfg: dict, routes=None):
    """The training loss of tokens [B, S+1]: the mean cross-entropy (the
    source's config names no router loss) and ``{"ce"}``."""
    nll, _ = token_losses(params, tokens, cfg, routes)
    ce = nll.mean()
    return ce, {"ce": ce}
