"""The plain reference: a llama-style decoder in straightforward
``jax.numpy`` and float32 — RMSNorm, rotary embedding, grouped-query
causal attention as an explicit S x S softmax, SwiGLU, untied head. No
kernel, no cache, no batching tricks, no remat. It shares nothing with the
program but the layout of the parameter tree (``models/llama.py``
``init_params``: stacked ``layers``) and the rotary convention (first and
second half of a head rotate together), and decides ``correct``.

``cfg`` is a configuration file's ``model`` group. Parameters arrive in
the type they are served or trained in and are cast to float32 one layer
at a time; matmuls run at ``highest`` precision, because on a TPU a
float32 matmul is otherwise computed in bfloat16 passes.
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


def forward(params, tokens, cfg: dict, q_block: int = 512):
    """tokens [B, S] -> float32 logits [B, S, V]. Attention is the explicit
    softmax over all keys, taken ``q_block`` query rows at a time so that
    the scores of a 4096-token sequence need not all be alive at once."""
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg["d_model"] // h
    b, s = tokens.shape
    qb = q_block if s % q_block == 0 else s
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        kpos = jnp.arange(s)

        def layer(x, lp):
            lp = jax.tree.map(lambda w: w.astype(F32), lp)
            y = _rms(x, lp["attn_norm"], cfg["norm_eps"])
            q = _rope((y @ lp["wq"]).reshape(b, s, h, hd), cfg["rope_theta"])
            k = _rope((y @ lp["wk"]).reshape(b, s, kv, hd), cfg["rope_theta"])
            v = (y @ lp["wv"]).reshape(b, s, kv, hd)
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
            x = x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
                @ lp["w_down"]
            return x, None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms(x, params["final_norm"], cfg["norm_eps"])
        return x @ params["lm_head"].astype(F32)


def token_losses(params, tokens, cfg: dict):
    """Next-token cross-entropy of every position of tokens [B, S+1] ->
    float32 [B, S], one sequence at a time so that the S x S scores and
    the logits of one sequence are all that is alive. With random weights
    a token's loss is logsumexp - its own logit, and that logit is a
    projection of the last hidden state: unlike the mean, the vector
    follows every layer."""
    def one(seq):
        logits = forward(params, seq[None, :-1], cfg)[0]
        picked = jnp.take_along_axis(logits, seq[1:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jax.lax.map(one, tokens)


def served_margin(params, tokens, served, n_prompt: int, cfg: dict):
    """Teacher-forced over ``tokens`` = prompt + served[:-1] (an int32
    array [n_prompt + len(served) - 1]): for each served token, how far
    its reference logit lies below the reference maximum at its position,
    and the argmax there. Arrays, not constants: one program serves every
    seed."""
    at = forward(params, tokens[None, :], cfg)[0, n_prompt - 1:]
    got = jnp.take_along_axis(at, served[:, None], axis=-1)[:, 0]
    return jnp.max(at, axis=-1) - got, jnp.argmax(at, axis=-1)
