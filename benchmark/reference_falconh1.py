"""The benchmark's copy of ``ray_tpu/models/reference_falconh1.py`` (kept
word for word below this paragraph; ``benchmark/tests/test_falconh1.py``
compares the two): the yardstick reads nothing of the program, so that a
change to the program's copy cannot move what decides ``correct``.

The plain reference of the Falcon-H1 block (transformers
``modeling_falcon_h1.py``: ``FalconH1DecoderLayer.forward``,
``FalconH1Mixer``, ``FalconH1RMSNormGated``, ``FalconH1Attention``,
``FalconH1MLP``, ``compute_mup_vector``) in straightforward ``jax.numpy``
and float32: RMSNorm; ONE norm a layer read by a Mamba-2 mixer and by
grouped-query attention side by side, each under its multipliers; the
mixer's recurrence written ONE TOKEN AT A TIME (a ``lax.scan`` over the
sequence carrying the [H, P, N] state: no chunks, no decay matrix, no
kernel), its depthwise causal convolution as a sum of shifted copies, the
gate BEFORE an RMS norm over each group's lanes; attention as an explicit
masked softmax over every key, the rotary by ``rotate_half``'s pairing;
the SwiGLU with a multiplier inside the activation and one after the down
projection; the embedding's and the head's multipliers; the next-token
cross-entropy. It shares nothing with the program (``ops/``,
``models/hybrid.py``, ``models/llama.py``) but the layout of the parameter
tree (``models/falcon.py`` ``init_params``: a list of stacks of layers).

Departures from the class, each of form and none of value: one sequence at
a time; queries, the SwiGLU's rows and the head's rows in blocks (``rows``)
so that it fits a chip at 16,384 tokens; the mixer's ``mup_vector`` built
here from ``ssm_multipliers`` as ``compute_mup_vector`` builds it;
``dt``'s limit (0, inf) is no clamp; no cache, no padding mask.

``cfg`` is a dict of FalconConfig field names (``d_model``, ``n_heads``,
``n_kv_heads``, ``head_width``, ``norm_eps``, ``rope_theta``,
``mamba_heads``, ``mamba_head_dim``, ``mamba_state``, ``mamba_groups`` and
the multipliers under their published names). Parameters arrive in the
type they are trained in and are cast to float32 one layer at a time;
matmuls run at ``highest`` precision, because on a TPU a float32 matmul is
otherwise computed in bfloat16 passes.
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


def _in_blocks(fn, x, rows: int):
    """fn over x [S, ...] a block of ``rows`` rows at a time."""
    s = x.shape[0]
    rows = rows if s % rows == 0 else s
    out = jax.lax.map(fn, x.reshape(s // rows, rows, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def mup_vector(cfg: dict):
    """``compute_mup_vector``: a multiplier a column of [z | x | B | C |
    dt]."""
    inner = cfg["mamba_heads"] * cfg["mamba_head_dim"]
    n = cfg["mamba_groups"] * cfg["mamba_state"]
    return jnp.concatenate([jnp.full((w,), m, F32) for w, m in zip(
        (inner, inner, n, n, cfg["mamba_heads"]), cfg["ssm_multipliers"])])


def _mixer(n, lp, cfg: dict):
    """n [S, D] (normed) -> the mixer's output [S, D], one sequence."""
    h_n, p_n, n_n, g_n = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                          cfg["mamba_state"], cfg["mamba_groups"])
    inner, s = h_n * p_n, n.shape[0]
    proj = ((n * cfg["ssm_in_multiplier"]) @ lp["in_proj"]) * mup_vector(cfg)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g_n * n_n],
                  proj[:, 2 * inner + 2 * g_n * n_n:])
    taps = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
    xbc = _silu(lp["conv_b"] + sum(padded[j:j + s] * lp["conv_w"][j]
                                   for j in range(taps)))
    x = xbc[:, :inner].reshape(s, h_n, p_n)
    b = xbc[:, inner:inner + g_n * n_n].reshape(s, g_n, n_n)
    c = xbc[:, inner + g_n * n_n:].reshape(s, g_n, n_n)
    dt = jnp.logaddexp(dt + lp["dt_bias"], 0.0)                    # softplus
    a = -jnp.exp(lp["a_log"])                                      # [H]

    def step(state, inp):
        xt, dtt, bt, ct = inp              # [H, P], [H], [G, N], [G, N]
        bt, ct = (jnp.repeat(t, h_n // g_n, axis=0) for t in (bt, ct))
        state = state * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((h_n, p_n, n_n), F32), (x, dt, b, c))
    y = (y + lp["d_skip"][:, None] * x).reshape(s, inner) * _silu(z)
    # the gate BEFORE the norm, the norm over each group's lanes
    y = y.reshape(s, g_n, inner // g_n)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg["norm_eps"])
    return (y.reshape(s, inner) * lp["gate_norm"]) @ lp["out_proj"]


def _rotary(x, theta: float):
    """x [S, heads, HD]: transformers' ``apply_rotary_pos_emb`` (lane i
    with lane i + HD / 2)."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], axis=-1)[:, None, :]
                for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(h, lp, cfg: dict, rows: int):
    """h [S, D] (normed, times ``attention_in_multiplier``) -> the
    attention's output [S, D]."""
    nh, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    s = h.shape[0]
    q = _rotary((h @ lp["wq"]).reshape(s, nh, hd), cfg["rope_theta"])
    k = _rotary((h @ lp["wk"]).reshape(s, kv, hd) * cfg["key_multiplier"],
                cfg["rope_theta"])
    k = jnp.repeat(k, nh // kv, axis=1)
    v = jnp.repeat((h @ lp["wv"]).reshape(s, kv, hd), nh // kv, axis=1)
    qb = rows if s % rows == 0 else s
    kpos = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        att = jnp.einsum("qhd,khd->hqk", qi, k) * hd ** -0.5
        seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        att = jnp.where(seen, att, -jnp.inf)
        att = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        att = att / jnp.sum(att, axis=-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", att, v)

    o = jax.lax.map(block, jnp.arange(s // qb))               # [nb, qb, H, HD]
    return o.reshape(s, nh * hd) @ lp["wo"]


def _mlp(h, lp, cfg: dict, rows: int):
    g_m, d_m = cfg["mlp_multipliers"]
    return _in_blocks(
        lambda y: ((y @ lp["w_up"]) * _silu((y @ lp["w_gate"]) * g_m))
        @ lp["w_down"] * d_m, h, rows)


def hidden(params, tokens, cfg: dict, rows: int = 512):
    """tokens [S] (ONE sequence) -> the residual stream after the last
    layer and the final norm, float32 [S, D]."""
    x = params["embed"].astype(F32)[tokens] * cfg["embedding_multiplier"]

    def layer(x, lp):
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        n = _rms(x, lp["attn_norm"], cfg["norm_eps"])
        x = x + cfg["attention_out_multiplier"] * _attention(
            n * cfg["attention_in_multiplier"], lp, cfg, rows) \
            + cfg["ssm_out_multiplier"] * _mixer(n, lp, cfg)
        return x + _mlp(_rms(x, lp["ffn_norm"], cfg["norm_eps"]), lp, cfg,
                        rows), None

    for stack in params["layers"]:
        x, _ = jax.lax.scan(layer, x, stack)
    return _rms(x, params["final_norm"], cfg["norm_eps"])


def forward(params, tokens, cfg: dict, rows: int = 512):
    """tokens [S] -> float32 logits [S, V]."""
    with jax.default_matmul_precision("highest"):
        return (hidden(params, tokens, cfg, rows)
                @ params["lm_head"].astype(F32)) * cfg["lm_head_multiplier"]


def token_losses(params, tokens, cfg: dict, rows: int = 512):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    float32 [B, S], one sequence at a time and the head a block of rows at
    a time, so that one sequence's states, one block's scores and one
    block's logits are all that is alive."""
    head = params["lm_head"].astype(F32)

    def one(seq):
        with jax.default_matmul_precision("highest"):
            x = hidden(params, seq[:-1], cfg, rows)

            def block(inp):
                xb, target = inp
                logits = (xb @ head) * cfg["lm_head_multiplier"]
                picked = jnp.take_along_axis(logits, target[:, None],
                                             axis=-1)[:, 0]
                return jax.nn.logsumexp(logits, axis=-1) - picked

            s = x.shape[0]
            rb = rows if s % rows == 0 else s
            return jax.lax.map(block, (
                x.reshape(s // rb, rb, -1),
                seq[1:].reshape(s // rb, rb))).reshape(s)

    return jax.lax.map(one, tokens)


def loss(params, tokens, cfg: dict, rows: int = 512):
    """The training loss of tokens [B, S+1]: the mean cross-entropy (the
    model has no auxiliary term)."""
    return token_losses(params, tokens, cfg, rows).mean()
