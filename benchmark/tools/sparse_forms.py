#!/usr/bin/env python3
"""The forms of attention over an index set, each timed ALONE on the chip
at a cell's shape (ISSUE 45, tentpole 2), and the ways to the set itself.

    python3 benchmark/tools/sparse_forms.py [cell] [--rows N]

One layer, one sequence, the cell's heads held: q, k, v [1, S, H, 256]
bf16, the latent [S, 512 + 64], sets of ``index_topk`` keys a query drawn
from random index scores. Forms:

  mask     ``ops/sparse_attention.py``: the streaming flash walk with a
           membership test a pair, causal blocks only (what the program
           runs); forward, and forward + backward.
  gather   (a) in Pallas: q folded through ``wkv_b``'s key columns, ONE
           gather of [c_kv | k_r] rows a query (a DMA a row) serving all
           heads, values the latent; forward only, on ``--rows`` queries,
           scaled to S.
  xla      (c) the same folded mathematics with ``jnp.take`` in XLA, a
           block of queries at a time; forward, and forward + backward
           (the gather's transpose is a scatter-add), on ``--rows``
           queries, scaled to S.

and for the selection over [S, S] float32 scores: ``latent.select`` (exact,
32 counting passes), ``lax.top_k`` (exact, a sort), ``lax.approx_max_k`` at
recall 0.95 (NOT exact: timed for the record, never run by the program).
Prints one JSON line a timing; a form the compiler refuses prints its
error and the others go on.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def timed(name, fn, *args, scale=1.0, reps=3, **note):
    import jax

    try:
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        first = time.perf_counter() - t0
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
        print(json.dumps({"form": name, "ms": 1e3 * scale * min(times),
                          "first_call_s": first, "scaled_by": scale, **note}),
              flush=True)
    except Exception as e:       # noqa: BLE001 - the cause is the output
        print(json.dumps({"form": name, "error": f"{type(e).__name__}: "
                          f"{str(e)[:600]}"}), flush=True)


def gather_pallas(qf, latent, sel, *, kv_rank: int, scale: float):
    """Form (a), forward: qf [R, H, C] (q folded onto the latent's lanes),
    latent [S, C], sel [R, K] int32 -> o [R, H, kv_rank] float32, the
    attention's output on the latent (``wkv_b``'s value columns after)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, H, C = qf.shape
    K = sel.shape[1]

    def kernel(sel_ref, q_ref, lat_ref, o_ref, buf, sem):
        def fetch(j, _):
            pltpu.make_async_copy(lat_ref.at[pl.ds(sel_ref[0, 0, j], 1)],
                                  buf.at[pl.ds(j, 1)], sem).start()
            return 0

        jax.lax.fori_loop(0, K, fetch, 0)

        def wait(j, _):
            pltpu.make_async_copy(lat_ref.at[pl.ds(0, 1)],
                                  buf.at[pl.ds(j, 1)], sem).wait()
            return 0

        jax.lax.fori_loop(0, K, wait, 0)
        rows = buf[...]
        s = jax.lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        p = p / jnp.sum(p, axis=1, keepdims=True)
        o_ref[0] = jax.lax.dot(p.astype(rows.dtype), rows[:, :kv_rank],
                               preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel, grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, K), lambda r: (r, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, H, C), lambda r: (r, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec((1, H, kv_rank), lambda r: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H, kv_rank), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, C), latent.dtype),
                        pltpu.SemaphoreType.DMA],
        interpret=jax.default_backend() != "tpu")(sel[:, None], qf, latent)


def gather_xla(qf, latent, sel, *, kv_rank: int, scale: float, block: int):
    """Form (c): the same mathematics with ``jnp.take`` in XLA."""
    import jax
    import jax.numpy as jnp

    R, H, C = qf.shape

    def rows(args):
        q, idx = args                                   # [b, H, C], [b, K]
        got = jnp.take(latent, idx, axis=0)             # [b, K, C]
        s = jnp.einsum("bhc,bkc->bhk", q, got,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhk,bkc->bhc", p.astype(got.dtype),
                          got[..., :kv_rank],
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(rows, (qf.reshape(R // block, block, H, C),
                             sel.reshape(R // block, block, -1)))
    return out.reshape(R, H, kv_rank)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import model_glm52, resolve
    from ray_tpu.models import latent as lat
    from ray_tpu.ops.sparse_attention import sparse_attention

    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    cell = resolve.cell(args[0] if args else "train-glm52-ep32-s16384-b1")
    rows = int(sys.argv[sys.argv.index("--rows") + 1]) \
        if "--rows" in sys.argv else 1024
    sizes = model_glm52.sizes(cell["config"])
    S, H = cell["mix"]["seq"], sizes["n_heads"]
    D = sizes["qk_nope_dim"] + sizes["qk_rope_dim"]
    rk, C = sizes["kv_rank"], sizes["kv_rank"] + sizes["qk_rope_dim"]
    topk = min(sizes["index_topk"], S)
    rows = min(rows, S - topk)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "S": S, "heads": H, "D": D, "topk": topk,
                      "rows": rows}), flush=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(ks[i], (1, S, H, D), jnp.bfloat16)
               for i in range(3))
    scores = jax.random.normal(ks[3], (S, S), jnp.float32)

    select = jax.jit(lambda x: lat.select(x, 0, topk))
    timed("select.exact_counting", select, scores)
    causal = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)
    timed("select.lax_top_k", jax.jit(
        lambda x: jax.lax.top_k(x, topk)[1]), causal)
    timed("select.approx_max_k_recall_0.95_NOT_EXACT", jax.jit(
        lambda x: jax.lax.approx_max_k(x, topk, recall_target=0.95)[1]),
        causal)
    keep = select(scores).astype(jnp.int8)[None]
    del causal

    fwd = jax.jit(lambda q, k, v, m: sparse_attention(q, k, v, m))
    both = jax.jit(jax.grad(lambda q, k, v, m: sparse_attention(
        q, k, v, m).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    probs = jax.jit(lambda q, k, v, m: sparse_attention(
        q, k, v, m, with_probs=True))
    timed("mask.fwd", fwd, q, k, v, keep)
    timed("mask.fwd_with_head_mean_probs", probs, q, k, v, keep)
    timed("mask.fwd_bwd", both, q, k, v, keep)
    del q, k, v, keep

    # the gathered forms on the LAST ``rows`` queries (each holds topk
    # keys), scaled to the S - topk queries that select and the topk that
    # see every earlier key at half that cost
    whole = ((S - topk) + topk / 2) / rows
    latent = jax.random.normal(ks[4], (S, C), jnp.bfloat16)
    qf = jax.random.normal(ks[5], (rows, H, C), jnp.bfloat16)
    sel = jax.lax.top_k(scores[S - rows:], topk)[1].astype(jnp.int32)
    sel = jnp.sort(sel, axis=1)
    del scores
    note = {"rows": rows, "gathered_bytes": rows * topk * C * 2}
    kw = dict(kv_rank=rk, scale=D ** -0.5)
    timed("gather_pallas.fwd", jax.jit(functools.partial(
        gather_pallas, **kw)), qf, latent, sel, scale=whole, **note)
    xla = functools.partial(gather_xla, block=128, **kw)
    timed("gather_xla.fwd", jax.jit(xla), qf, latent, sel, scale=whole,
          **note)
    timed("gather_xla.fwd_bwd", jax.jit(jax.grad(
        lambda q, l, s: xla(q, l, s).sum(), argnums=(0, 1))), qf, latent,
        sel, scale=whole, **note)
    timed("gather_only_xla", jax.jit(lambda l, s: jnp.take(
        l, s[:128], axis=0)), latent, sel, scale=whole * rows / 128,
        rows=128)
    return 0


if __name__ == "__main__":
    sys.exit(main())
