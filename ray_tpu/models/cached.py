"""Llama's block over a KV cache: the model side of serving (serve/llm.py
jits these; the training path imports nothing from here).

Six forwards run ONE layer (``_block``) and own one thing each, their
``attend(q, k, v, a, b) -> (o, a, b)``: where the layer's new k and v go in
its slices ``a``, ``b`` of the two buffers, and what q then attends over. The
block reads its norm, projections, residual and feed-forward through the
training side (models/llama.py), so a family states them once; what it still
takes from llama alone is what ``_refuse_stated`` refuses.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.models.llama import (LlamaConfig, _dq, _embed, _family, _norm,
                                  _project, _residual, _rope_tables)


# the pairing of ``_rope_tables``, the only tables a cached forward turns by
# (a config of several attention kinds, where another may be stated, is
# refused: ``_refuse_stated``)
PAIRS = "halves"


class KVCache(NamedTuple):
    k: jax.Array        # [L, B, max_seq, KV, HD]
    v: jax.Array
    length: jax.Array   # [B] int32 — per-sequence filled length


def _refuse_stated(cfg: LlamaConfig):
    """What a cache is refused for: served under a config that states one
    of these, a cached forward that does not apply it is another model. The
    block applies two already (``_residual``, ``_norm``); each stays refused
    until a test holds the six forwards to ``forward`` under it (ROADMAP
    D1)."""
    stated = [f for f in ("embedding_multiplier", "residual_multiplier",
                          "logits_scaling", "attn_scale")
              if getattr(cfg, f) is not None] + ([] if cfg.rope else ["rope"])
    if _family(cfg).attention_half is not None:
        stated.append("an attention half of its own")
    if cfg.attn_kinds:
        stated.append("attention layers of several kinds")
    if cfg.parallel_block:
        stated.append("a parallel block")
    if getattr(cfg, "one_half", False):
        stated.append("blocks that hold one half each")
    if "conv" in getattr(cfg, "layer_types", ()):
        stated.append("short-convolution layers (their state, the last "
                      "taps - 1 rows, has no place in a KV cache)")
    if hasattr(cfg, "kda_head_dim"):
        stated.append("Kimi-Delta-Attention layers (a KDA half is TRAINED, "
                      "not served: its state, [dk, dv] float32 a head with "
                      "the convolutions' last taps - 1 rows, has no place "
                      "in a KV cache)")
    if hasattr(cfg, "ssm_out_multiplier"):
        stated.append("a mixer beside the attention half (a block of two "
                      "first halves holds two kinds of state side by side, "
                      "keys and values AND a mixer's [H, P, N] state with "
                      "its convolution's last rows: a KV cache has a place "
                      "for the first alone)")
    if getattr(cfg, "n_dense", 0) and hasattr(cfg, "conv_taps"):
        stated.append("leading dense layers among expert layers")
    if cfg.norm != "rms":
        stated.append(f"a {cfg.norm} norm")
    if stated:
        raise NotImplementedError(
            f"a KV cache for a config that states {', '.join(stated)}: the "
            "cached forwards are held to none of them (forward_with_stats is)")


def init_cache(cfg: LlamaConfig, batch: int, max_seq: Optional[int] = None,
               dtype=None) -> KVCache:
    _refuse_stated(cfg)
    S = max_seq or cfg.max_seq_len
    shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.head_dim)
    dt = dtype or cfg.dtype
    return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                   jnp.zeros((batch,), jnp.int32))


def init_paged_cache(cfg: LlamaConfig, num_pages: int, page_size: int,
                     dtype=None):
    """Paged KV pools [L, KV, num_pages, page_size, HD] (SURVEY §7.9 /
    ops/paged_attention.py layout; page 0 is the trash page inactive
    slots write into). HBM scales with pages, not slots*max_seq."""
    _refuse_stated(cfg)
    dt = dtype or cfg.dtype
    shape = (cfg.n_layers, cfg.n_kv_heads, num_pages, page_size,
             cfg.head_dim)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def _layer_scan_with_kv(body, x, a_all, b_all, layers):
    """lax.scan over stacked per-layer inputs with two stacked KV
    buffers ([L, ...]) kept in the CARRY, each layer's slice read and
    written back in place via dynamic_(index|update_index)_in_dim.

    This is the memory shape every cached forward uses: passing the
    buffers as scan xs with restacked ys makes XLA materialize a second
    full-size copy (and the layout-assignment copies that follow), which
    at 2.7B+ pools/caches is multiple GB of HBM temp — enough that the
    decode program alone exceeded the 16 GB chip before this form.

    body(x, layer_xs, a_slice, b_slice) -> (x, new_a_slice, new_b_slice)
    """
    def wrap(carry, lx):
        x, a_all, b_all, li = carry
        a = jax.lax.dynamic_index_in_dim(a_all, li, 0, keepdims=False)
        b = jax.lax.dynamic_index_in_dim(b_all, li, 0, keepdims=False)
        x, a, b = body(x, lx, a, b)
        a_all = jax.lax.dynamic_update_index_in_dim(a_all, a, li, 0)
        b_all = jax.lax.dynamic_update_index_in_dim(b_all, b, li, 0)
        return (x, a_all, b_all, li + 1), None

    (x, a_all, b_all, _), _ = jax.lax.scan(
        wrap, (x, a_all, b_all, jnp.int32(0)), layers)
    return x, a_all, b_all


def _block(x, lp, a, b, cfg: LlamaConfig, turn, attend):
    """One layer over a cache: x [B, T, D] and the layer's slices ``a``,
    ``b`` of the two buffers -> (x, a, b). ``turn``: the rotary for the rows'
    positions, over q and k [B, T, N, HD]; ``attend(q, k, v, a, b) -> (o, a,
    b)``: the entry point's own, o with the heads' lanes last. The
    feed-forward is the family's, its statistics dropped (nothing trains
    here). Llama's serial block and no other, so it refuses where it is
    traced: a caller can hold a cache that ``init_cache`` did not make, and
    ``prefill`` needs none."""
    _refuse_stated(cfg)
    h = _norm(x, lp["attn_norm"], cfg)
    q = turn(_project(h, lp, cfg, "wq", cfg.n_heads, "q_norm"))
    k = turn(_project(h, lp, cfg, "wk", cfg.n_kv_heads, "k_norm"))
    v = _project(h, lp, cfg, "wv", cfg.n_kv_heads)
    o, a, b = attend(q, k, v, a, b)
    x = _residual(x, o.reshape(*x.shape[:2], -1) @ _dq(lp["wo"], cfg.dtype),
                  cfg)
    y, _ = _family(cfg).feed_forward(_norm(x, lp["ffn_norm"], cfg), lp, cfg)
    return _residual(x, y, cfg), a, b


def _layers(params, x, a_all, b_all, cfg: LlamaConfig, turn, attend):
    """``_block`` over every layer, the two buffers in the carry."""
    return _layer_scan_with_kv(
        lambda x, lp, a, b: _block(x, lp, a, b, cfg, turn, attend),
        x, a_all, b_all, params["layers"])


def _turn_by_row(cfg: LlamaConfig, pos):
    """``turn`` for rows at positions of their own, the tables gathered there.
    pos [B]: one token a row, inside the tables (serve/llm.py sizes them to
    its ``max_seq``); [B, T]: a right-padded chunk a row, whose pad positions
    may run past the tables and are held to their last row."""
    tables = _rope_tables(cfg.rope_theta, cfg.max_seq_len, cfg.head_dim)
    if pos.ndim == 2:
        pos = jnp.minimum(pos, cfg.max_seq_len - 1)
    cos, sin = (t[pos] if pos.ndim == 2 else t[pos][:, None, :]
                for t in tables)                      # [B, T, HD]
    return lambda x: llama.apply_rope(x, cos, sin, PAIRS)  # [B, T, N, HD]


def _view_mask(qpos, prefix_len, tail_len, S: int, cfg: LlamaConfig):
    """[B, T, S]: which of a row's S key positions each query of a chunk
    sees. Causal against absolute key position, bounded by each row's
    total length, inside the config's window."""
    kv_pos = jnp.arange(S)[None, :]                              # [1, S]
    total = (prefix_len + tail_len)[:, None]
    inside = kv_pos < total                                      # [B, S]
    causal = kv_pos[:, None, :] <= qpos[:, :, None]              # [B, T, S]
    mask = inside[:, None, :] & causal
    if cfg.sliding_window is not None:
        mask = mask & (qpos[:, :, None] - kv_pos[:, None, :]
                       < cfg.sliding_window)
    return mask


def _attend_view(q, kg, vg, mask, cfg: LlamaConfig):
    """q [B, T, H, HD] over a row's gathered key space kg, vg
    [B, KV, S, HD] under mask [B, T, S], the softmax in float32:
    [B, T, H, HD] in the compute dtype."""
    grp = cfg.n_heads // cfg.n_kv_heads
    kg = jnp.repeat(kg, grp, axis=1)              # GQA -> [B, H, S, HD]
    vg = jnp.repeat(vg, grp, axis=1)
    qh = q.transpose(0, 2, 1, 3)                  # [B, H, T, HD]
    scores = jnp.einsum("bhtd,bhsd->bhts", qh.astype(jnp.float32),
                        kg.astype(jnp.float32)) / (cfg.head_dim ** 0.5)
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhts,bhsd->bhtd", probs,
                   vg.astype(jnp.float32)).astype(cfg.dtype)
    return o.transpose(0, 2, 1, 3)


def last_logits(params, x, lengths, cfg: LlamaConfig):
    """The final norm, one position a row of x [B, T, D] and the head:
    float32 [B, V]. lengths [B]: each row's true length in the chunk, the
    position its last REAL token (a row with none reads position 0); None:
    the chunk's last position."""
    x = _norm(x, params["final_norm"], cfg)
    if lengths is None:
        last = x[:, x.shape[1] - 1]
    else:
        idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return (last @ _dq(params["lm_head"], cfg.dtype)).astype(jnp.float32)


def prefill(params, tokens, lengths, cfg: LlamaConfig):
    """Batched prefill for the continuous-batching engine. tokens [n, P]
    right-padded; lengths [n] true lengths. Returns (logits_at_last [n, V],
    k_layers [L, n, P, KV, HD], v_layers). Pad positions produce garbage
    k/v but are never attended later (decode masks kpos < length and new
    tokens overwrite pad slots)."""
    x = _embed(params, tokens, cfg.dtype)
    cos, sin = _rope_tables(cfg.rope_theta, tokens.shape[1], cfg.head_dim)
    turn = lambda t: llama.apply_rope(t, cos, sin, PAIRS)    # noqa: E731

    # nothing stored: the chunk attends over itself from the origin (the
    # flash kernel at P >= 128) and k and v leave as the scan's ys
    def attend(q, k, v, a, b):
        return llama._attention(q, k, v, cfg), k, v

    def body(x, lp):
        x, k, v = _block(x, lp, None, None, cfg, turn, attend)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    return last_logits(params, x, lengths, cfg), ks, vs


def forward_with_cache(params, tokens, cache: KVCache, cfg: LlamaConfig,
                       offset) -> Tuple[jax.Array, KVCache]:
    """Run [B, S] tokens at position `offset` (scalar — uniform across batch
    for the bucketed serving path), filling the cache. Returns last-position
    logits [B, vocab] and the updated cache."""
    dt = cfg.dtype
    S = tokens.shape[1]
    x = _embed(params, tokens, dt)
    cos, sin = (jax.lax.dynamic_slice_in_dim(t, offset, S, axis=0)
                for t in _rope_tables(cfg.rope_theta, cfg.max_seq_len,
                                      cfg.head_dim))
    turn = lambda t: llama.apply_rope(t, cos, sin, PAIRS)    # noqa: E731

    def attend(q, k, v, ck, cv):
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, offset, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, offset, 0, 0))
        # mask out cache slots beyond offset+S via causal offset
        o = llama._attention(q, ck.astype(dt), cv.astype(dt), cfg,
                             q_offset=offset)
        return o, ck, cv

    x, nk, nv = _layers(params, x, cache.k, cache.v, cfg, turn, attend)
    return last_logits(params, x, None, cfg), \
        KVCache(nk, nv, cache.length + S)


def decode_step(params, tokens, cache: KVCache, cfg: LlamaConfig,
                active=None) -> Tuple[jax.Array, KVCache]:
    """One continuous-batching decode step with PER-ROW positions.
    tokens [B, 1]; cache.length [B] gives each row's write position; rows
    where active==0 keep their cache untouched. Returns (logits [B, V],
    updated cache)."""
    dt = cfg.dtype
    B = tokens.shape[0]
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = cache.length                                    # [B]
    if active is None:
        active = jnp.ones((B,), jnp.int32)
    turn = _turn_by_row(cfg, pos)
    x = _embed(params, tokens, dt)                # [B, 1, D]
    S = cache.k.shape[2]
    kpos = jnp.arange(S)[None, :]                         # [1, S]
    attn_mask = (kpos <= pos[:, None]) & (active[:, None] > 0)  # [B, S]
    if cfg.sliding_window is not None:
        # banded decode matches banded training: only the last W cached
        # keys are visible (cache layout unchanged)
        attn_mask = attn_mask & (pos[:, None] - kpos < cfg.sliding_window)

    def attend(q, k, v, ck, cv):
        # Unconditional one-position write per row; inactive rows write
        # back the value already there. A vmapped lax.cond would lower to
        # SELECTs over the whole [S, KV, HD] cache per row (both branches
        # materialized) — this form touches O(KV*HD) per row instead.
        def write_at(c, new, p, a):
            old = jax.lax.dynamic_slice(c, (p, 0, 0), new.shape)
            val = jnp.where(a > 0, new, old)
            return jax.lax.dynamic_update_slice(c, val, (p, 0, 0))

        upd = jax.vmap(write_at)(ck, k.astype(ck.dtype)[:, 0][:, None],
                                 pos, active)
        vpd = jax.vmap(write_at)(cv, v.astype(cv.dtype)[:, 0][:, None],
                                 pos, active)
        kk = upd.astype(dt)                                # [B, S, KV, HD]
        vv = vpd.astype(dt)
        q5 = q.reshape(B, 1, KV, H // KV, HD)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q5, kk,
                       preferred_element_type=jnp.float32) / (HD ** 0.5)
        s = jnp.where(attn_mask[:, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vv), upd, vpd

    x, nk, nv = _layers(params, x, cache.k, cache.v, cfg, turn, attend)
    return last_logits(params, x, None, cfg), \
        KVCache(nk, nv, cache.length + active)


def decode_step_paged(params, tokens, k_pools, v_pools, page_table,
                      lengths, cfg: LlamaConfig, active=None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One continuous-batching decode step over a PAGED KV cache.
    tokens [S, 1]; k_pools/v_pools [L, KV, NP, ps, HD]; page_table
    [S, maxP]; lengths [S] = tokens already stored per slot. Returns
    (logits [S, V], new k_pools, new v_pools, new lengths). Rows with
    active==0 skip the KV write entirely and keep length (only the
    kernel's unwritten-window flush may touch the reserved trash page
    0). Write+attend is ops/paged_attention.py's fused Pallas kernel
    (XLA scatter+gather reference off-TPU)."""
    from ray_tpu.ops.paged_attention import paged_decode_attention_inplace

    if cfg.sliding_window is not None:
        raise ValueError("paged decode does not support sliding_window")
    dt = cfg.dtype
    if active is None:
        active = jnp.ones((tokens.shape[0],), jnp.int32)
    turn = _turn_by_row(cfg, lengths)              # the write position
    # the fused kernel derives each slot's tip page/offset from attn_len;
    # inactive rows (attn_len 0) skip the write entirely
    attn_len = jnp.where(active > 0, lengths + 1, 0)
    x = _embed(params, tokens, dt)                 # [S, 1, D]

    # Pools ride the scan CARRY; the new token's k/v write happens INSIDE
    # the fused Pallas kernel through pool-aliased outputs (see
    # ops/paged_attention.py paged_decode_attention_inplace). The earlier
    # forms — pools-as-xs with restacked ys, or an XLA scatter per layer —
    # each materialized extra full-pool copies (the scatter's KV-minor
    # layout preference alone cost two +3 GB layout copies at 2.7B, and
    # the decode program exceeded the 16 GB chip).
    def attend(q, k, v, kp, vp):
        o, kp, vp = paged_decode_attention_inplace(
            q[:, 0].astype(dt), k[:, 0].astype(kp.dtype),
            v[:, 0].astype(vp.dtype), kp, vp, page_table, attn_len)
        # fully-masked (inactive) rows return garbage — zero them
        return jnp.where((active > 0)[:, None, None], o, 0.0), kp, vp

    x, nk, nv = _layers(params, x, k_pools, v_pools, cfg, turn, attend)
    return last_logits(params, x, None, cfg), nk, nv, lengths + active


def prefill_paged_tail(params, tokens, tail_len, prefix_len, page_table,
                       k_pools, v_pools, cfg: LlamaConfig
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked prefill of a prompt TAIL against existing paged prefix KV
    (the compute half of automatic prefix caching — ref: vLLM's chunked
    prefill with prefix blocks). tokens [B, T] right-padded tail tokens;
    tail_len [B] true tail lengths; prefix_len [B] tokens already in the
    pages; page_table [B, maxP]. Writes the tail's KV into the pages and
    returns (logits at each row's final tail token [B, V], k_pools,
    v_pools). Cost O(T * (prefix+T)) instead of the full O((prefix+T)^2)
    re-prefill — and ONE device call instead of T decode steps."""
    B, T = tokens.shape
    KV, HD = cfg.n_kv_heads, cfg.head_dim
    ps = k_pools.shape[3]
    S_view = page_table.shape[1] * ps

    qpos = prefix_len[:, None] + jnp.arange(T)[None, :]          # [B, T]
    valid = (jnp.arange(T)[None, :] < tail_len[:, None])         # [B, T]
    turn = _turn_by_row(cfg, qpos)

    # physical write targets; padded rows land in trash page 0
    page_ids = jnp.take_along_axis(page_table, qpos // ps, axis=1)  # [B, T]
    page_ids = jnp.where(valid, page_ids, 0)
    offsets = qpos % ps
    pid_f = page_ids.reshape(-1)
    off_f = offsets.reshape(-1)
    mask = _view_mask(qpos, prefix_len, tail_len, S_view, cfg)
    x = _embed(params, tokens, cfg.dtype)                # [B, T, D]

    def attend(q, k, v, kp, vp):
        # write tail KV FIRST: the gathered view then covers prefix+tail
        # and one causal mask handles both
        k_f = k.reshape(B * T, KV, HD).transpose(1, 0, 2)
        v_f = v.reshape(B * T, KV, HD).transpose(1, 0, 2)
        kp = kp.at[:, pid_f, off_f, :].set(k_f.astype(kp.dtype))
        vp = vp.at[:, pid_f, off_f, :].set(v_f.astype(vp.dtype))
        # gather each row's pages into a contiguous [S_view] key space
        kg = jnp.take(kp, page_table, axis=1)         # [KV, B, maxP, ps, HD]
        vg = jnp.take(vp, page_table, axis=1)
        kg = kg.transpose(1, 0, 2, 3, 4).reshape(B, KV, S_view, HD)
        vg = vg.transpose(1, 0, 2, 3, 4).reshape(B, KV, S_view, HD)
        return _attend_view(q, kg, vg, mask, cfg), kp, vp

    x, nk, nv = _layers(params, x, k_pools, v_pools, cfg, turn, attend)
    return last_logits(params, x, tail_len, cfg), nk, nv


def prefill_tail_contiguous(params, tokens, tail_len, prefix_len,
                            cache: KVCache, slot_ids, cfg: LlamaConfig
                            ) -> Tuple[jax.Array, KVCache]:
    """Chunked prefill of a prompt segment into CONTIGUOUS cache rows —
    the contiguous-layout twin of prefill_paged_tail, so both KV layouts
    share the chunked-prefill admission path (ref: vLLM chunked prefill;
    the reference has no native engine, its serve layer delegates to user
    code). tokens [B, T] right-padded; tail_len [B] true chunk lengths;
    prefix_len [B] tokens already in each row; slot_ids [B] DISTINCT cache
    rows (duplicates would make scatter order undefined). Writes the
    chunk's KV at positions prefix..prefix+tail of each slot row, attends
    causally over the row's full filled length, and returns (logits at
    each row's final chunk token [B, V], cache with length[slot] advanced
    to prefix+tail for rows with tail_len>0). Cost O(T * S) attention per
    chunk instead of the O(S^2) full re-prefill."""
    T = tokens.shape[1]
    S = cache.k.shape[2]
    qpos = prefix_len[:, None] + jnp.arange(T)[None, :]          # [B, T]
    valid = jnp.arange(T)[None, :] < tail_len[:, None]           # [B, T]
    safe_q = jnp.minimum(qpos, S - 1)
    turn = _turn_by_row(cfg, qpos)
    mask = _view_mask(qpos, prefix_len, tail_len, S, cfg)
    x = _embed(params, tokens, cfg.dtype)                # [B, T, D]

    def attend(q, k, v, ck, cv):
        # masked scatter: pad positions write back what is already there
        # (their safe_q indices all clamp to S-1, and last-write order is
        # undefined for duplicates — writing the old value makes any
        # order a no-op)
        old_k = ck[slot_ids[:, None], safe_q]                    # [B, T, KV, HD]
        old_v = cv[slot_ids[:, None], safe_q]
        kw = jnp.where(valid[..., None, None], k.astype(ck.dtype), old_k)
        vw = jnp.where(valid[..., None, None], v.astype(cv.dtype), old_v)
        ck = ck.at[slot_ids[:, None], safe_q].set(kw)
        cv = cv.at[slot_ids[:, None], safe_q].set(vw)
        o = _attend_view(q, ck[slot_ids].transpose(0, 2, 1, 3),
                         cv[slot_ids].transpose(0, 2, 1, 3), mask, cfg)
        return o, ck, cv

    x, nk, nv = _layers(params, x, cache.k, cache.v, cfg, turn, attend)
    logits = last_logits(params, x, tail_len, cfg)
    old_len = cache.length[slot_ids]
    new_len = jnp.where(tail_len > 0,
                        (prefix_len + tail_len).astype(old_len.dtype),
                        old_len)
    return logits, KVCache(nk, nv, cache.length.at[slot_ids].set(new_len))


def scatter_prefill_pages(k_pools, v_pools, ks, vs, page_table, slots,
                          lengths, page_size: int):
    """Write prefill k/v into the pools. ks/vs [L, n, P, KV, HD] (from
    ``prefill``), slots [n] slot ids, lengths [n] true lengths;
    positions past a row's length go to trash page 0. Returns updated
    pools."""
    L, n, P, KV, HD = ks.shape
    ps = page_size
    pos = jnp.arange(P)[None, :]                           # [1, P]
    chunk = pos // ps                                      # [1, P]
    pages = jnp.take_along_axis(
        page_table[slots], jnp.broadcast_to(chunk, (n, P)), axis=1)
    pages = jnp.where(pos < lengths[:, None], pages, 0)    # [n, P]
    offs = jnp.broadcast_to(pos % ps, (n, P))
    pages_f = pages.reshape(-1)
    offs_f = offs.reshape(-1)

    # Scatter one LAYER at a time with the pools as scan carry: a
    # whole-pool scatter forces a full pool-sized layout copy in the
    # compiled program (+2.7 GB transient at 2.7B; see
    # _layer_scan_with_kv) — per-layer, the transient is 1/L of that.
    def body(x, inp, kp, vp):
        k_l, v_l = inp                                 # [n, P, KV, HD]
        k_f = k_l.transpose(2, 0, 1, 3).reshape(KV, n * P, HD)
        v_f = v_l.transpose(2, 0, 1, 3).reshape(KV, n * P, HD)
        kp = kp.at[:, pages_f, offs_f, :].set(k_f.astype(kp.dtype))
        vp = vp.at[:, pages_f, offs_f, :].set(v_f.astype(vp.dtype))
        return x, kp, vp

    _, k_pools, v_pools = _layer_scan_with_kv(
        body, jnp.int32(0), k_pools, v_pools, (ks, vs))
    return k_pools, v_pools
