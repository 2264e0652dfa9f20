"""Ulysses sequence parallelism: head-sharded all-to-all attention.

The second long-context strategy SURVEY.md §5.7 calls for (DeepSpeed
Ulysses, Jacobs et al. 2023 — absent in the reference, which delegates
long sequences to wrapped frameworks). Where ring attention keeps the
sequence sharded and rotates KV around the ICI ring (sp communication
steps), Ulysses does ONE all-to-all each way: scatter heads / gather
sequence, run full-sequence attention on H/sp local heads, then invert.
Communication volume is O(S·D·H/sp) per chip independent of sp, so it
beats the ring when the head count comfortably divides over the axis and
the full-S attention fits memory; the ring wins at extreme S. Both are
mesh-axis presets over the same 'sp' axis — pick per workload.

Call inside shard_map with q/k/v sharded on the seq axis:
    jax.shard_map(lambda q, k, v: ulysses_attention(q, k, v),
                  mesh=mesh, in_specs=P(None, "sp", None, None), ...)

Constraints: n_heads % sp == 0 and n_kv_heads % sp == 0 (contiguous head
blocks keep GQA groups chip-local; the group ratio G = H/KV is preserved
because H/sp = G·(KV/sp)).

Differentiable: all_to_all has a transpose rule (its inverse), so
jax.grad threads the exchange backward automatically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _full_attention(q, k, v, causal: bool):
    """Reference einsum attention with GQA broadcast (the per-chip compute
    after the exchange; mirrors models/llama.py _attention_xla, duplicated
    here so ops does not import models)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    q = q.reshape(B, S, KV, groups, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) / (D ** 0.5)
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, D)


def ulysses_attention(q, k, v, axis_name: str = "sp",
                      causal: bool = True):
    """q [B, Sc, H, D], k/v [B, Sc, KV, D] — Sc is this chip's sequence
    chunk. Must be called inside shard_map/pjit with `axis_name` bound.
    Positions (RoPE) must already be applied with global offsets, exactly
    as the ring path does."""
    sp = jax.lax.axis_size(axis_name)
    H, KV = q.shape[2], k.shape[2]
    if H % sp or KV % sp:
        raise ValueError(
            f"ulysses needs heads divisible by the sp axis: "
            f"H={H}, KV={KV}, sp={sp} (use ring attention instead)")

    def scatter_heads(x):
        # [B, Sc, N, D] -> [B, Sc*sp, N/sp, D]: each chip receives every
        # chip's chunk for its head block (one ICI all-to-all)
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qg = scatter_heads(q)
    kg = scatter_heads(k)
    vg = scatter_heads(v)
    out = _full_attention(qg, kg, vg, causal)
    # inverse exchange: split seq back out, gather this chip's heads
    return jax.lax.all_to_all(out, axis_name, split_axis=1,
                              concat_axis=2, tiled=True)
