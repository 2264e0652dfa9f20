"""Times the grouped matmul's calls at OLMoE's shapes on the chip, one tiling after
another, and XLA's ragged_dot beside them: what ray_tpu/ops/grouped_matmul.py's
GMM_TILING / TGMM_TILING were chosen from (PERF.md 6, PR 26).

    chiprun --chips 1 -- python3 benchmark/tools/gmm_sweep.py [rows experts d f]
"""
import importlib
import itertools
import sys
import time

import jax
import jax.numpy as jnp

rows, e, d, f = (int(a) for a in sys.argv[1:5]) if len(sys.argv) > 4 \
    else (131072, 64, 2048, 1024)
mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
key = jax.random.PRNGKey(0)
sizes = jax.random.multinomial(key, rows, jnp.ones(e) / e).astype(jnp.int32) \
    if hasattr(jax.random, "multinomial") else jnp.full((e,), rows // e, jnp.int32)
x = jax.random.normal(key, (rows, d), jnp.bfloat16)
h = jax.random.normal(key, (rows, f), jnp.bfloat16)
w_up = jax.random.normal(key, (e, d, f), jnp.bfloat16)
w_down = jax.random.normal(key, (e, f, d), jnp.bfloat16)
print("device", jax.devices()[0].device_kind, "rows", rows, "group sizes",
      int(sizes.min()), int(sizes.max()), flush=True)
tflop = 2.0 * rows * d * f / 1e12


def bench(name, fn, *args):
    try:
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / 5 * 1e3
        print(f"{name:58s} {ms:8.3f} ms  {tflop / ms * 1e3:6.1f} TFLOP/s", flush=True)
    except Exception as ex:  # noqa: BLE001 - a tiling the compiler refuses
        print(f"{name:58s} refused: {str(ex).splitlines()[0][:120]}", flush=True)


tilings = [t for t in itertools.product((256, 512, 1024), (512, 1024, 2048), (512, 1024, 2048))
           if (t[0] * t[1] + t[1] * t[2] + t[0] * t[2]) * 4 + t[0] * t[2] * 4 <= 15 * 2 ** 20]
for t in tilings:
    bench(f"gmm up    [M,{d}]x[E,{d},{f}] {t}", lambda a, w, t=t: mb.gmm(
        a, w, sizes, jnp.bfloat16, t), x, w_up)
for t in tilings:
    bench(f"gmm down  [M,{f}]x[E,{f},{d}] {t}", lambda a, w, t=t: mb.gmm(
        a, w, sizes, jnp.bfloat16, t), h, w_down)
for t in tilings:
    bench(f"gmm d_x   [M,{f}]x[E,{d},{f}]^T {t}", lambda a, w, t=t: mb.gmm(
        a, w, sizes, jnp.bfloat16, t, transpose_rhs=True), h, w_up)
for t in tilings:
    bench(f"gmm d_h   [M,{d}]x[E,{f},{d}]^T {t}", lambda a, w, t=t: mb.gmm(
        a, w, sizes, jnp.bfloat16, t, transpose_rhs=True), x, w_down)
for t in tilings:
    bench(f"tgmm d_w_up   [M,{d}]^T[M,{f}] {t}", lambda a, g, t=t: mb.tgmm(
        a.swapaxes(0, 1), g, sizes, jnp.bfloat16, t), x, h)
for t in tilings:
    bench(f"tgmm d_w_down [M,{f}]^T[M,{d}] {t}", lambda a, g, t=t: mb.tgmm(
        a.swapaxes(0, 1), g, sizes, jnp.bfloat16, t), h, x)
bench("ragged_dot up", lambda a, w: jax.lax.ragged_dot(
    a, w, sizes, preferred_element_type=jnp.bfloat16), x, w_up)
bench("ragged_dot down", lambda a, w: jax.lax.ragged_dot(
    a, w, sizes, preferred_element_type=jnp.bfloat16), h, w_down)
bench("ragged_dot up, grads", jax.grad(lambda a, w: jax.lax.ragged_dot(
    a, w, sizes, preferred_element_type=jnp.bfloat16).astype(jnp.float32).sum(),
    argnums=(0, 1)), x, w_up)
order = jax.random.permutation(key, rows)
bench("gather rows [M,2048] (dispatch)", lambda a, i: a[i], x, order)
bench("argsort s32[M] stable", lambda i: jnp.argsort(i % e, stable=True), order)
bench("scatter inverse permutation", lambda i: jnp.zeros_like(i).at[i].set(
    jnp.arange(rows, dtype=i.dtype)), order)
