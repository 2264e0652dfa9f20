"""The six cached forwards (models/cached.py) against ``llama.forward``:
every one serves the forward's logits at the positions it serves; a cache
is refused for every config the shared block would serve as another model."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import cached, latent, llama, moe  # noqa: E402

B, S, PAGE, MAX_SEQ = 2, 12, 4, 16
TINY = llama.PRESETS["tiny"].replace(remat=False, dtype=jnp.float32,
                                     max_seq_len=MAX_SEQ)
# slot r's pages; page 0 is the trash page
PAGE_TABLE = jnp.asarray(1 + np.arange(B * MAX_SEQ // PAGE).reshape(B, -1),
                         jnp.int32)
i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731


def _rows(logits, positions, active=(True,) * B):
    """(logits of one row, the row, the position they predict from)."""
    return [(logits[r], r, int(positions[r])) for r in range(B) if active[r]]


def _prefill_then_decode(cfg, params, tokens, decode=True):
    """``prefill`` of 8 and 5 tokens, then ``decode_step`` from its k and v
    to 12 tokens a row, each row at its own position."""
    lens = np.array([8, 5])
    logits, ks, vs = jax.jit(lambda p, t, n: cached.prefill(p, t, n, cfg))(
        params, tokens[:, :8], i32(lens))
    yield from _rows(logits, lens - 1)
    if not decode:
        return
    empty = cached.init_cache(cfg, B, max_seq=MAX_SEQ)
    cache = cached.KVCache(empty.k.at[:, :, :8].set(ks),
                           empty.v.at[:, :, :8].set(vs), i32(lens))
    step = jax.jit(lambda p, t, c, a: cached.decode_step(p, t, c, cfg,
                                                         active=a))
    pos = lens.copy()
    while pos.min() < S:
        active = pos < S
        tok = tokens[np.arange(B), np.minimum(pos, S - 1)][:, None]
        logits, cache = step(params, tok, cache, i32(active))
        yield from _rows(logits, pos, active)
        pos += active
    np.testing.assert_array_equal(cache.length, [S, S])


def _decode(cfg, params, tokens, paged=False):
    """``decode_step`` or ``decode_step_paged`` from an empty cache, row 1
    three steps behind row 0 and each row inactive outside its 12 steps:
    per-row positions and the active mask."""
    if paged:
        kp, vp = cached.init_paged_cache(cfg, 1 + B * MAX_SEQ // PAGE, PAGE)
        lengths = jnp.zeros((B,), jnp.int32)
        step = jax.jit(lambda p, t, kp, vp, n, a: cached.decode_step_paged(
            p, t, kp, vp, PAGE_TABLE, n, cfg, active=a))
    else:
        cache = cached.init_cache(cfg, B, max_seq=MAX_SEQ)
        step = jax.jit(lambda p, t, c, a: cached.decode_step(p, t, c, cfg,
                                                             active=a))
    for t in range(S + 3):
        pos = t - np.array([0, 3])
        active = (pos >= 0) & (pos < S)
        tok = tokens[np.arange(B), np.clip(pos, 0, S - 1)][:, None]
        if paged:
            logits, kp, vp, lengths = step(params, tok, kp, vp, lengths,
                                           i32(active))
        else:
            logits, cache = step(params, tok, cache, i32(active))
        yield from _rows(logits, pos, active)
    np.testing.assert_array_equal(lengths if paged else cache.length, [S, S])


def _forward_with_cache(cfg, params, tokens):
    """8 tokens at offset 0, then one at a time to 12."""
    run = jax.jit(lambda p, t, c, o: cached.forward_with_cache(p, t, c, cfg,
                                                               o))
    cache = cached.init_cache(cfg, B, max_seq=MAX_SEQ)
    logits, cache = run(params, tokens[:, :8], cache, 0)
    yield from _rows(logits, [7, 7])
    for i in range(8, S):
        logits, cache = run(params, tokens[:, i:i + 1], cache, i)
        yield from _rows(logits, [i, i])


def _tail(cfg, params, tokens, paged=False):
    """A prefix of 4 and 3 tokens from ``prefill``, then the tail in two
    right-padded chunks of (4, 2) and (4, 4) tokens: into pages
    (``prefill_paged_tail``), or into rows 2 and 0 of a three-row cache
    (``prefill_tail_contiguous``)."""
    filled = np.array([4, 3])
    _, ks, vs = cached.prefill(params, tokens[:, :4], i32(filled), cfg)
    if paged:
        kp, vp = cached.init_paged_cache(cfg, 1 + B * MAX_SEQ // PAGE, PAGE)
        kp, vp = cached.scatter_prefill_pages(
            kp, vp, ks, vs, PAGE_TABLE, jnp.arange(B), i32(filled), PAGE)
        run = jax.jit(lambda p, t, n, at, kp, vp: cached.prefill_paged_tail(
            p, t, n, at, PAGE_TABLE, kp, vp, cfg))
    else:
        slots = i32([2, 0])
        empty = cached.init_cache(cfg, 3, max_seq=MAX_SEQ)
        cache = cached.KVCache(empty.k.at[:, slots, :4].set(ks),
                               empty.v.at[:, slots, :4].set(vs),
                               empty.length.at[slots].set(i32(filled)))
        run = jax.jit(lambda p, t, n, at, c: cached.prefill_tail_contiguous(
            p, t, n, at, c, slots, cfg))
    for tail in np.array([[4, 2], [4, 4]]):
        chunk = np.zeros((B, 4), np.int32)
        for r in range(B):
            chunk[r, :tail[r]] = tokens[r, filled[r]:filled[r] + tail[r]]
        if paged:
            logits, kp, vp = run(params, chunk, i32(tail), i32(filled), kp,
                                 vp)
        else:
            logits, cache = run(params, chunk, i32(tail), i32(filled), cache)
        filled = filled + tail
        yield from _rows(logits, filled - 1)
    if not paged:
        np.testing.assert_array_equal(cache.length, [filled[1], 0, filled[0]])


FORWARDS = {
    "prefill": _prefill_then_decode,
    "forward_with_cache": _forward_with_cache,
    "decode_step": _decode,
    "prefill_tail_contiguous": _tail,
    "decode_step_paged": lambda *a: _decode(*a, paged=True),
    "prefill_paged_tail": lambda *a: _tail(*a, paged=True),
}
# the paged forwards take no window
WINDOWED = ("prefill", "forward_with_cache", "decode_step",
            "prefill_tail_contiguous")


def _dequantised(params):
    """``quantize_params_int8``'s tree as the float32 arrays it stands for."""
    return jax.tree.map(
        lambda w: llama._dq(w, jnp.float32) if isinstance(w, dict) else w,
        params, is_leaf=lambda w: isinstance(w, dict) and "q8" in w)


CASES = (
    [(f, f"kv{kv}") for f in FORWARDS for kv in (2, 4)]
    + [(f, "window4") for f in WINDOWED]
    + [(f, "int8") for f in FORWARDS]
    # the parent ran an expert model through the two that went by the
    # training layer, and through no other
    + [("prefill", "moe"), ("forward_with_cache", "moe")])


@pytest.mark.parametrize("forward,variant", CASES,
                         ids=[f"{f}-{v}" for f, v in CASES])
def test_a_cached_forward_serves_the_forwards_logits(forward, variant):
    """At every position a cached forward serves, its logits are those of
    ``llama.forward`` over the same tokens."""
    family, cfg, kw = llama, TINY, {}
    if variant.startswith("kv"):
        cfg = TINY.replace(n_kv_heads=int(variant[2:]))
    elif variant == "window4":
        cfg = TINY.replace(sliding_window=4)
    elif variant == "moe":
        family, cfg = moe, moe.PRESETS["tiny"].replace(
            remat=False, dtype=jnp.float32, max_seq_len=MAX_SEQ)
        kw = {"decode": False} if forward == "prefill" else {}
    params = family.init_params(jax.random.PRNGKey(1), cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                           cfg.vocab_size))
    reference = params
    if variant == "int8":
        params = llama.quantize_params_int8(params)
        reference = _dequantised(params)
    full = np.asarray(llama.forward(reference, jnp.asarray(tokens), cfg))
    served = list(FORWARDS[forward](cfg, params, tokens, **kw))
    assert served, forward
    for logits, row, position in served:
        np.testing.assert_allclose(
            np.asarray(logits), full[row, position], rtol=2e-4, atol=2e-4,
            err_msg=f"{forward} row {row} position {position}")


REFUSED = {
    "embedding_multiplier": TINY.replace(embedding_multiplier=12.0),
    "residual_multiplier": TINY.replace(residual_multiplier=0.22),
    "logits_scaling": TINY.replace(logits_scaling=8.0),
    "attn_scale": TINY.replace(attn_scale=0.0078125),
    "rope": TINY.replace(rope=False),
    "an attention half of its own": latent.PRESETS["tiny"],
    "attention layers of several kinds": TINY.replace(
        attn_kinds=(("window", llama.AttentionKind(window=4)),)),
    "a parallel block": TINY.replace(parallel_block=True),
    "a layer norm": TINY.replace(norm="layer"),
}


@pytest.mark.parametrize("stated", REFUSED)
def test_a_cache_is_refused_for_what_the_block_does_not_apply(stated):
    """Every item ``_refuse_stated`` names, by both layouts, with the item
    in the message."""
    cfg = REFUSED[stated]
    with pytest.raises(NotImplementedError, match=stated):
        cached.init_cache(cfg, batch=1)
    with pytest.raises(NotImplementedError, match=stated):
        cached.init_paged_cache(cfg, 4, PAGE)


def test_the_block_refuses_where_no_cache_was_asked_for():
    """``prefill`` needs no cache, and a caller can hold one that
    ``init_cache`` did not make: the block refuses at its trace."""
    cfg = REFUSED["a parallel block"]
    params = llama.init_params(jax.random.PRNGKey(0), TINY)
    with pytest.raises(NotImplementedError, match="a parallel block"):
        cached.prefill(params, jnp.zeros((1, 4), jnp.int32), i32([4]), cfg)
