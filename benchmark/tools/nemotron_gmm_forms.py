"""Times the grouped matmul's calls at Nemotron 3 Nano's two shapes on the chip,
[rows, 2688] x [16, 2688, 1856] and [rows, 1856] x [16, 1856, 2688], in the forms
a width of 1,856 (no whole number of lanes) can take: the tiles
ray_tpu/ops/grouped_matmul.py picks, the parent's fallback (a ragged second
tile), other tiles, and the weights STORED at 1,920 (PERF.md 6, PR 48).

    chiprun --chips 1 -- python3 benchmark/tools/nemotron_gmm_forms.py [rows live]
"""
import importlib
import sys
import time

import jax
import jax.numpy as jnp

rows, live = (int(a) for a in sys.argv[1:3]) if len(sys.argv) > 2 \
    else (24576, 12288)
E, D = 16, 2688
mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
key = jax.random.PRNGKey(0)
# the held experts' rows are a prefix of the pass, the rest no group's
share = jax.random.dirichlet(key, jnp.ones(E) * 8.0)
sizes = jnp.floor(share * live / 256).astype(jnp.int32) * 256
sizes = sizes.at[0].add(live - sizes.sum())
print("device", jax.devices()[0].device_kind, "rows", rows, "live", live,
      "sizes", [int(s) for s in sizes], flush=True)


def bench(name, fn, *args):
    try:
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / 10 * 1e3
        print(f"{name:64s} {ms:8.3f} ms", flush=True)
        return ms
    except Exception as ex:  # noqa: BLE001 - a tiling the compiler refuses
        print(f"{name:64s} refused: {str(ex).splitlines()[0][:100]}", flush=True)
        return None


for F in (1856, 1920):
    x = jax.random.normal(key, (rows, D), jnp.bfloat16)
    h = jax.random.normal(key, (rows, F), jnp.bfloat16)
    w_up = jax.random.normal(key, (E, D, F), jnp.bfloat16)
    w_down = jax.random.normal(key, (E, F, D), jnp.bfloat16)
    whole = F                       # a block equal to the dimension
    best = {}
    calls = {
        "up   [M,2688]x[E,2688,F]": (
            lambda a, w, t: mb.gmm(a, w, sizes, jnp.bfloat16, t), (x, w_up),
            [(256, 2048, whole), (256, 896, whole), (256, 384, whole),
             (256, 896, 1024), (512, 896, whole), (256, 2688, 640)]),
        "down [M,F]x[E,F,2688]": (
            lambda a, w, t: mb.gmm(a, w, sizes, jnp.bfloat16, t), (h, w_down),
            [(256, whole, 2048), (256, whole, 896), (256, whole, 1344),
             (256, 1024, 896), (512, whole, 896), (256, 640, 2688)]),
        "d_x  [M,F]x[E,2688,F]^T": (
            lambda a, w, t: mb.gmm(a, w, sizes, jnp.bfloat16, t,
                                   transpose_rhs=True), (h, w_up),
            [(256, whole, 2048), (256, whole, 896), (256, 1024, 896)]),
        "d_h  [M,2688]x[E,F,2688]^T": (
            lambda a, w, t: mb.gmm(a, w, sizes, jnp.bfloat16, t,
                                   transpose_rhs=True), (x, w_down),
            [(256, 2048, whole), (256, 896, whole), (256, 896, 1024)]),
        "d_w_up   [M,2688]^T[M,F]": (
            lambda a, g, t: mb.tgmm(a.swapaxes(0, 1), g, sizes, jnp.bfloat16,
                                    t, num_actual_groups=E), (x, h),
            [(256, 1024, 1024), (256, 896, 1024), (256, 896, whole),
             (256, 896, 640), (512, 896, 1024), (256, 384, whole)]),
        "d_w_down [M,F]^T[M,2688]": (
            lambda a, g, t: mb.tgmm(a.swapaxes(0, 1), g, sizes, jnp.bfloat16,
                                    t, num_actual_groups=E), (h, x),
            [(256, 1024, 1024), (256, 1024, 896), (256, whole, 896),
             (256, 640, 896), (512, 1024, 896), (256, whole, 384)]),
    }
    for what, (fn, args, tilings) in calls.items():
        for t in tilings:
            ms = bench(f"F {F} {what} {t}", lambda a, b, t=t, fn=fn: fn(a, b, t),
                       *args)
            if ms is not None and ms < best.get(what, (1e9,))[0]:
                best[what] = (ms, t)
    print(f"F {F}: best of each", {k: (round(v[0], 3), v[1]) for k, v in best.items()},
          "sum", round(sum(v[0] for v in best.values()), 3), "ms", flush=True)
