"""The plain reference of the MiniCPM-SALA block (``minicpm_sala``) in
straightforward ``jax.numpy`` and float32: RMSNorm; a "sparse" layer's
grouped-query attention with a norm a head on q and k, no rotary, and, past
``dense_len`` tokens, attention over the best BLOCKS of keys a query and KV
group (InfLLM-V2, arXiv:2509.24663): pooled keys, each head's softmax over
the pooled kernels that end at or before the query, the group's sum, a
max-pool to blocks, the forced blocks, and the selection BY A SORT (a
stable argsort of the negated scores: ties to the lower index), then an
explicit masked softmax over the selected blocks' keys up to the query; a
"lightning" layer's linear attention (Lightning Attention-2,
arXiv:2401.04658) with its recurrence written ONE TOKEN AT A TIME (a
``lax.scan`` over the sequence carrying the [H, D, D] state: no chunks, no
decay matrix, no kernel), rotary on q and k, a norm a head on q, k and the
output; the sigmoid output gate of both; the dense SwiGLU; MiniCPM's
embedding, depth and logit multipliers; an untied head; the
cross-entropy. It shares nothing with the program but the layout of the
parameter tree (``models/sala.py`` ``init_params``: a list of stacks, one
a run of layers of one kind).

``cfg`` is a dict of SalaConfig field names (``d_model``, ``n_heads``,
``n_kv_heads``, ``head_width``, ``norm_eps``, ``rope_theta``,
``layer_types``, ``lightning_heads``, the three multipliers and the
selection's seven sizes). Parameters arrive in the type they are trained
in and are cast to float32 one layer at a time; matmuls run at ``highest``
precision, because on a TPU a float32 matmul is otherwise computed in
bfloat16 passes. Queries, the SwiGLU's rows and the head's rows are taken a
block at a time (``q_block``, ``ROWS``) so that no [S, S] and no [S, V]
array exists at 16,384 tokens.

Selection is discrete. ``sets`` ([F, B, KV, S, S / block] int8: the sets
another implementation chose, one a sparse layer) makes the reference
attend over THOSE sets, and report per query, group and layer how far its
own choice lay from them: ``set_differ`` the share of a query's
selections that differ, ``set_gap`` where they differ the largest block
score the other gave up less the smallest it took instead. A near tie
reads a few times the rounding of the other's scores; a wrong selection
(no forced window, half the blocks) reads whole probabilities.

A departure the reference notes: the shipped kernel takes the softmax's
log-sum-exp from a coarser pooling of the keys; here, as in the program,
the softmax over the pooled kernels is exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 2048         # rows of the SwiGLU and of the head at once


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


def _by_rows(fn, x, rows: int):
    """fn over x [B, S, ...] a block of ``rows`` of S at a time."""
    B, S = x.shape[:2]
    rows = min(rows, S)
    if S % rows:
        return fn(x)
    parts = jnp.moveaxis(x.reshape(B, S // rows, rows, *x.shape[2:]), 1, 0)
    out = jax.lax.map(fn, parts)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, *out.shape[3:])


def _swiglu(y, lp):
    wg, wu, wd = (lp[n].astype(F32) for n in ("w_gate", "w_up", "w_down"))
    return _by_rows(lambda r: (jax.nn.silu(r @ wg) * (r @ wu)) @ wd, y, ROWS)


def _rope(x, theta: float):
    """x [B, S, H, D]: lanes (i, i + D / 2) turned together by the angle
    position x theta^(-2 i / D)."""
    S, D = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    angle = jnp.arange(S, dtype=F32)[:, None] * freq[None, :]
    c, s = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _heads(y, lp, name: str, norm, heads: int, cfg: dict):
    """A projection of y [B, S, D] in its heads [B, S, heads, HD], each
    head under an RMS norm with the learned scale ``norm`` (None: none)."""
    out = (y @ lp[name].astype(F32)).reshape(*y.shape[:2], heads,
                                             cfg["head_width"])
    return out if norm is None else _rms(out, lp[norm], cfg["norm_eps"])


def pooled_keys(k, cfg: dict):
    """k [B, S, KV, D] -> [B, J, KV, D]: the means of the kernels of
    ``sparse_kernel`` keys at ``sparse_stride`` that lie whole inside the
    sequence."""
    kernel, stride = cfg["sparse_kernel"], cfg["sparse_stride"]
    return jnp.stack([k[:, s0:s0 + kernel].mean(axis=1) for s0 in range(
        0, k.shape[1] - kernel + 1, stride)], axis=1)


def block_scores(q, pooled, t, S: int, cfg: dict):
    """q [B, rows, H, D] (queries t [rows]), pooled [B, J, KV, D] -> float32
    [B, KV, rows, S / block]: a KV group's score of every block of keys. A
    head's softmax runs over the kernels that end at or before its query;
    a block scores the largest of its group's summed probabilities over
    the kernels ``per b - 1 .. per b + per - 1`` (per = block / stride)."""
    B, J, KV, D = pooled.shape
    kernel, stride = cfg["sparse_kernel"], cfg["sparse_stride"]
    per, blocks = cfg["sparse_block"] // stride, S // cfg["sparse_block"]
    starts = jnp.arange(J) * stride
    rows, H = q.shape[1], q.shape[2]
    s = jnp.einsum("brkgd,bjkd->bkgrj", q.reshape(B, rows, KV, H // KV, D),
                   pooled) / D ** 0.5
    on = (starts + kernel - 1)[None, :] <= t[:, None]
    s = jnp.where(on, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(on, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    group = p.sum(axis=2)                                # [B, KV, rows, J]
    cols = []
    for b in range(blocks):
        lo, hi = max(per * b - 1, 0), min(per * b + per - 1, J - 1)
        cols.append(group[..., lo:hi + 1].max(axis=-1))
    return jnp.stack(cols, axis=-1)


def forced(t, blocks: int, cfg: dict):
    """bool [rows, blocks]: the first ``sparse_init_blocks`` blocks and the
    ``sparse_window / sparse_block`` blocks that end with the query's own."""
    own = (t // cfg["sparse_block"])[:, None]
    b = jnp.arange(blocks)[None, :]
    window = cfg["sparse_window"] // cfg["sparse_block"]
    return ((b < cfg["sparse_init_blocks"]) | (b > own - window)) & (b <= own)


def own_set(scores, t, cfg: dict):
    """scores [..., rows, blocks] -> (int8 0/1 sets of the same shape, the
    scores as ranked): the forced blocks and the best of the rest,
    ``sparse_topk`` in all, of the blocks that do not start after the
    query, by a stable sort: ties go to the lower index."""
    blocks = scores.shape[-1]
    seen = jnp.arange(blocks)[None, :] <= (t // cfg["sparse_block"])[:, None]
    ranked = jnp.where(forced(t, blocks, cfg), jnp.inf,
                       jnp.where(seen, scores, -jnp.inf))
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)      # a block's place
    return ((rank < cfg["sparse_topk"]) & seen).astype(jnp.int8), ranked


def _sparse_attention(y, lp, cfg: dict, given, q_block: int, compare: bool):
    """The sparse layer's heads' outputs [B, S, H x HD] and its record."""
    B, S, _ = y.shape
    H, KV, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    q = _heads(y, lp, "wq", "q_norm", H, cfg)
    k = _heads(y, lp, "wk", "k_norm", KV, cfg)
    v = _heads(y, lp, "wv", None, KV, cfg)
    selects = S > cfg["dense_len"]
    block = cfg["sparse_block"]
    rows = min(q_block, S)
    pooled = pooled_keys(k, cfg) if selects else None

    def part(i):
        t = i * rows + jnp.arange(rows)
        qi = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        keep = jnp.arange(S)[None, :] <= t[:, None]              # [rows, S]
        keep = jnp.broadcast_to(keep, (B, KV, rows, S))
        rec = {}
        if selects:
            mine = None
            if given is None or compare:
                mine, ranked = own_set(
                    block_scores(qi, pooled, t, S, cfg), t, cfg)
            sel = mine if given is None else jax.lax.dynamic_slice_in_dim(
                given, i * rows, rows, axis=2)
            if given is not None and compare:
                lost = (mine != 0) & (sel == 0)     # mine, not the other's
                took = (sel != 0) & (mine == 0)
                size = jnp.maximum(jnp.sum(mine != 0, axis=-1), 1)
                rec["set_differ"] = jnp.sum(lost, axis=-1) / size
                gap = jnp.max(jnp.where(lost, ranked, -jnp.inf), axis=-1) \
                    - jnp.min(jnp.where(took, ranked, jnp.inf), axis=-1)
                rec["set_gap"] = jnp.where(jnp.any(lost, axis=-1)
                                           & jnp.any(took, axis=-1), gap, 0.0)
            rec["set"] = sel
            keep = keep & (jnp.repeat(sel, block, axis=-1) != 0)
        s = jnp.einsum("brkgd,btkd->bkgrt",
                       qi.reshape(B, rows, KV, H // KV, D), k) / D ** 0.5
        s = jnp.where(keep[:, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgrt,btkd->brkgd", p, v).reshape(B, rows, H * D)
        return o, rec

    outs, recs = jax.lax.map(part, jnp.arange(S // rows))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H * D)
    # [n, B, KV, rows, ...] -> [B, KV, S, ...]
    recs = {n: jnp.moveaxis(a, 0, 2).reshape(B, KV, S, *a.shape[4:])
            for n, a in recs.items()}
    return out, recs


def _lightning_attention(y, lp, cfg: dict):
    """The lightning layer's heads' outputs after their norm, [B, S, LH x
    HD]: S_t = exp(-s_h) S_{t-1} + k_t^T v_t, o_t = (q_t / sqrt(HD)) S_t,
    a token at a time from a zero state; s_h = 2^(-8 (h + 1) / LH)."""
    B, S, _ = y.shape
    LH, D = cfg["lightning_heads"], cfg["head_width"]
    q = _rope(_heads(y, lp, "wq", "q_norm", LH, cfg), cfg["rope_theta"])
    k = _rope(_heads(y, lp, "wk", "k_norm", LH, cfg), cfg["rope_theta"])
    v = _heads(y, lp, "wv", None, LH, cfg)
    keeps = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(LH, dtype=F32) + 1.0) / LH)))

    def step(state, at):
        qt, kt, vt = at                                     # [B, LH, D]
        state = state * keeps[None, :, None, None] \
            + kt[..., :, None] * vt[..., None, :]           # [B, LH, Dk, Dv]
        return state, jnp.einsum("bhk,bhkv->bhv", qt / D ** 0.5, state)

    _, o = jax.lax.scan(step, jnp.zeros((B, LH, D, D), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    o = _rms(jnp.moveaxis(o, 0, 1), lp["o_norm"], cfg["norm_eps"])
    return o.reshape(B, S, LH * D)


def block(x, lp, kind: str, cfg: dict, given=None, q_block: int = 512,
          compare: bool = True):
    """One layer: (x after both halves, the sparse layer's record)."""
    by = cfg["residual_multiplier"]
    y = _rms(x, lp["attn_norm"], cfg["norm_eps"])
    rec = {}
    if kind == "lightning":
        o = _lightning_attention(y, lp, cfg)
    else:
        o, rec = _sparse_attention(y, lp, cfg, given, q_block, compare)
    gate = jax.nn.sigmoid(y @ lp["w_out_gate"].astype(F32))
    x = x + by * ((o * gate) @ lp["wo"].astype(F32))
    y = _rms(x, lp["ffn_norm"], cfg["norm_eps"])
    return x + by * _swiglu(y, lp), rec


def forward(params, tokens, cfg: dict, sets=None, q_block: int = 512,
            compare: bool = True):
    """tokens [B, S] -> (the residual stream after the final norm [B, S,
    D] float32, the sparse layers' records stacked [F, ...])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
        recs, full = [], 0
        for (kind, n), stack in zip(_runs(cfg), params["layers"]):
            for i in range(n):
                lp = jax.tree.map(lambda a: a[i].astype(F32), stack)
                given = None
                if kind == "sparse" and sets is not None:
                    given = sets[full]
                x, rec = block(x, lp, kind, cfg, given, q_block, compare)
                if kind == "sparse":
                    full += 1
                    recs.append(rec)
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        rec = {n: jnp.stack([r[n] for r in recs]) for n in recs[0]} \
            if recs else {}
        return x, rec


def token_losses(params, tokens, cfg: dict, sets=None, q_block: int = 512,
                 compare: bool = True):
    """tokens [B, S + 1] -> (every position's cross-entropy [B, S]
    float32, the sparse layers' records), the head a block of rows at a
    time."""
    x, rec = forward(params, tokens[:, :-1], cfg, sets, q_block, compare)
    head = params["lm_head"].astype(F32)
    both = jnp.concatenate(
        [x, tokens[:, 1:, None].astype(F32)], axis=-1)      # the target rides

    def rows(r):
        with jax.default_matmul_precision("highest"):
            logits = (r[..., :-1] @ head) / cfg["logits_scaling"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        at = r[..., -1].astype(jnp.int32)
        return (lse - jnp.take_along_axis(logits, at[..., None],
                                          axis=-1)[..., 0])[..., None]

    return _by_rows(rows, both, ROWS)[..., 0], rec


def loss(params, tokens, cfg: dict, sets=None, q_block: int = 512,
         compare: bool = True):
    nll, rec = token_losses(params, tokens, cfg, sets, q_block, compare)
    return nll.mean(), rec
