"""Paged decode attention for the continuous-batching LLM engine.

SURVEY.md §7.9 hard parts ("paged attention kernels (Pallas)") /
VERDICT r2 item 5. KV lives in a global pool of fixed-size pages,
[num_pages, page_size, KV, HD]; each decode slot owns a list of page
indices (its page table) instead of a contiguous [max_seq] stripe, so
HBM scales with TOKENS IN USE, not worst-case-per-slot (the vLLM
memory model, re-designed for XLA's static shapes).

TPU kernel design (paged_decode_attention_inplace, which also writes the
current token's k/v into its slot's tip page): one grid instance per
(slot, kv_head, page). The page table and per-slot lengths ride in as
SCALAR-PREFETCH arguments (pltpu.PrefetchScalarGridSpec) so the k/v
BlockSpec index_maps can point each grid step's DMA at that slot's next
physical page — Mosaic fetches exactly the pages the slot owns, never
materializing the gathered [slots, max_pages*page_size] view the way an
XLA gather would. Out-of-range steps clamp their index (repeat DMA,
elided) and skip compute via pl.when; online-softmax state (acc/m/l)
lives in VMEM scratch across the page steps of one (slot, kv_head),
exactly like ops/flash_attention.py's streaming kernel.

Shapes: q [S, H, HD] (one new token per slot), pools [KV, NP, ps, HD]
(kv-head major so the kernel's page block keeps (ps, HD) as its last two
dims — a Mosaic tiling requirement), page_table [S, maxP] int32,
lengths [S] int32 (tokens INCLUDING the current one). Output [S, H, HD].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def paged_attention_reference(q, k_pool, v_pool, page_table, lengths):
    """Pure-XLA reference: gather the pages, mask, attend. Materializes
    the [S, maxP*ps] view — fine for CPU tests and as the interpret-mode
    fallback; the kernel exists to avoid exactly this materialization."""
    S, H, HD = q.shape
    KV, NP, ps, _ = k_pool.shape
    maxP = page_table.shape[1]
    groups = H // KV
    k = k_pool[:, page_table].reshape(KV, S, maxP * ps, HD)  # [KV, S, T, HD]
    v = v_pool[:, page_table].reshape(KV, S, maxP * ps, HD)
    qf = q.reshape(S, KV, groups, HD).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("skgd,kstd->skgt", qf, kf) * (HD ** -0.5)
    pos = jnp.arange(maxP * ps)[None, :]                   # [1, T]
    mask = pos < lengths[:, None]                          # [S, T]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (inactive slots) produce uniform p; output unused
    out = jnp.einsum("skgt,kstd->skgd", p, v.astype(jnp.float32))
    return out.reshape(S, H, HD).astype(q.dtype)


def paged_decode_attention_inplace_reference(q, k_new, v_new, k_pool,
                                             v_pool, page_table, lengths):
    """Pure-XLA reference for the fused write+attend decode kernel:
    scatter the new token's k/v into each active slot's tip page, then
    attend. Inactive slots (length 0) write nothing."""
    S = q.shape[0]
    ps = k_pool.shape[2]
    pos = jnp.maximum(lengths - 1, 0)
    page = jnp.take_along_axis(page_table, (pos // ps)[:, None],
                               axis=1)[:, 0]
    # inactive rows write back what is already there (no trash page)
    off = pos % ps
    old_k = k_pool[:, page, off, :]                    # [KV, S, HD]
    old_v = v_pool[:, page, off, :]
    kn = k_new.transpose(1, 0, 2).astype(k_pool.dtype)  # [KV, S, HD]
    vn = v_new.transpose(1, 0, 2).astype(v_pool.dtype)
    live = (lengths > 0)[None, :, None]
    k_pool = k_pool.at[:, page, off, :].set(jnp.where(live, kn, old_k))
    v_pool = v_pool.at[:, page, off, :].set(jnp.where(live, vn, old_v))
    o = paged_attention_reference(q, k_pool, v_pool, page_table, lengths)
    return o, k_pool, v_pool


def _kernel_inplace(pt_ref, len_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                    o_ref, ko_ref, vo_ref, acc, m_scr, l_scr, *,
                    page_size: int, max_pages: int, scale: float):
    """Fused write+attend, grid (S, KV, maxP). The current token's k/v is
    patched into its (s, kv) tip-page block in registers, used for the
    online-softmax step, and stored back ONCE through the pool-aliased
    output — the pools never pass through an XLA scatter, whose
    KV-minor layout preference forced two full-pool layout copies
    (+6 GB transient at 2.7B) around the decode loop."""
    s = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    length = len_ref[s]
    n_pages = jax.lax.div(length + page_size - 1, page_size)
    pos = jax.lax.max(length - 1, 0)
    wp = jax.lax.div(pos, page_size)        # tip-page ORDINAL for slot s
    off = jax.lax.rem(pos, page_size)
    is_wp = jnp.logical_and(p == wp, length > 0)

    @pl.when(p < n_pages)
    def _step():
        q = q_ref[0, 0]                                # [G, HD]
        k = k_ref[0, 0]                                # [ps, HD]
        v = v_ref[0, 0]
        # patch the new token into the tip page (registers, not HBM)
        row = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
        sel = jnp.logical_and(row == off, is_wp)       # [ps, 1]
        k = jnp.where(sel, kn_ref[0, 0].astype(k.dtype), k)   # kn [1, HD]
        v = jnp.where(sel, vn_ref[0, 0].astype(v.dtype), v)
        st = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        tok = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        st = jnp.where(tok < length, st, NEG_INF)      # [G, ps]
        m = m_scr[...][:, 0:1]
        l = l_scr[...][:, 0:1]
        m_new = jnp.maximum(m, jnp.max(st, axis=1, keepdims=True))
        pr = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot(
            pr.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        # store the patched tip page back through the aliased output —
        # only this one block per (s, kv) is ever written
        @pl.when(is_wp)
        def _write():
            ko_ref[0, 0] = k.astype(ko_ref.dtype)
            vo_ref[0, 0] = v.astype(vo_ref.dtype)

    @pl.when(p == max_pages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, 0:1], 1e-30)
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


def paged_decode_attention_inplace(q, k_new, v_new, k_pool, v_pool,
                                   page_table, lengths):
    """Fused decode step: write each active slot's new k/v [S, KV, HD]
    into its tip page AND attend, in one kernel. Pools are input/output
    ALIASED (callers must donate them); returns (o [S, H, HD], k_pool,
    v_pool). lengths INCLUDE the current token; length-0 slots skip both
    the write and the compute (callers mask their output)."""
    S, H, HD = q.shape
    KV, NP, ps, _ = k_pool.shape
    maxP = page_table.shape[1]
    G = H // KV
    if jax.default_backend() != "tpu":
        return paged_decode_attention_inplace_reference(
            q, k_new, v_new, k_pool, v_pool, page_table, lengths)

    qt = q.reshape(S, KV, G, HD)
    kn4 = k_new.reshape(S, KV, 1, HD)
    vn4 = v_new.reshape(S, KV, 1, HD)

    def q_idx(s, kv, p, pt, ln):
        return (s, kv, 0, 0)

    def kv_idx(s, kv, p, pt, ln):
        length = ln[s]
        n_pages = jax.lax.div(length + ps - 1, ps)
        j = jax.lax.min(p, jax.lax.max(n_pages - 1, 0))
        return (kv, pt[s, j], 0, 0)

    def write_idx(s, kv, p, pt, ln):
        # constant across p: the tip page for live slots; THE trash page
        # (0, reserved by PagePool) for length-0 rows. Pallas flushes
        # each (s, kv) output window even when the pl.when store never
        # fired, so a length-0 slot's flush must land on the
        # garbage-tolerant trash page — NOT pt[s, 0], which for an
        # occupied-but-decode-masked slot (mid-chunked-prefill) is a
        # real, possibly prefix-SHARED page.
        pos = jax.lax.max(ln[s] - 1, 0)
        pg = pt[s, jax.lax.div(pos, ps)]
        return (kv, jax.lax.select(ln[s] > 0, pg, 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, KV, maxP),
        in_specs=[
            pl.BlockSpec((1, 1, G, HD), q_idx),
            pl.BlockSpec((1, 1, 1, HD), q_idx),
            pl.BlockSpec((1, 1, 1, HD), q_idx),
            pl.BlockSpec((1, 1, ps, HD), kv_idx),
            pl.BlockSpec((1, 1, ps, HD), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, HD), q_idx),
            pl.BlockSpec((1, 1, ps, HD), write_idx),
            pl.BlockSpec((1, 1, ps, HD), write_idx),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, HD), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
    )
    o, k_pool, v_pool = pl.pallas_call(
        functools.partial(_kernel_inplace, page_size=ps, max_pages=maxP,
                          scale=HD ** -0.5),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, KV, G, HD), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        input_output_aliases={5: 1, 6: 2},
    )(page_table, lengths, qt, kn4, vn4, k_pool, v_pool)
    return o.reshape(S, H, HD), k_pool, v_pool
