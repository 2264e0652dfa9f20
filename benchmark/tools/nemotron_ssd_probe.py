"""Times the state-space scan's two Mosaic calls at Nemotron 3 Nano's shape on the
chip (B2 x S8192, 64 heads of 64, state 128, chunks of 128) with B and C in 8 groups
and in ONE group at the same sizes, at the Granite cell's shape (128 heads, chunks
of 256, one group) beside them, and checks the grouped kernel against the plain
einsum path in float32 at a smaller batch (PERF.md 6, PR 48).

    chiprun --chips 1 -- python3 benchmark/tools/nemotron_ssd_probe.py
"""
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssd

print("device", jax.devices()[0].device_kind, flush=True)


def inputs(b, s, h, p, n, g, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) * 2 - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=jnp.log(16.0)))
    shape = (b, s, n) if g is None else (b, s, g, n)
    bm = (jax.random.normal(ks[3], shape) * n ** -0.25).astype(dtype)
    cm = (jax.random.normal(ks[4], shape) * n ** -0.25).astype(dtype)
    return x, dt, a, bm, cm


def run(impl, chunk):
    return lambda *a: ssd.ssd_scan(*a, chunk=chunk, impl=impl)


def grads(impl, chunk):
    return jax.grad(lambda *a: jnp.sum(run(impl, chunk)(*a).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2, 3, 4))


def bench(name, fn, *args, n=10):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name:56s} {(time.perf_counter() - t0) / n * 1e3:9.3f} ms", flush=True)


small = inputs(1, 1024, 64, 64, 128, 8, jnp.float32)
with jax.default_matmul_precision("highest"):
    want = jax.jit(run("xla", 128))(*small)
    want_g = jax.jit(grads("xla", 128))(*small)
for dtype in (jnp.float32, jnp.bfloat16):
    args = tuple(t.astype(dtype) if i in (0, 3, 4) else t for i, t in enumerate(small))
    got = jax.jit(run("pallas", 128))(*args).astype(jnp.float32)
    print(f"values {jnp.dtype(dtype).name}: mean |y| {float(jnp.abs(want).mean()):.4f}, "
          f"mean |kernel - plain| {float(jnp.abs(got - want).mean()):.2e}, "
          f"max {float(jnp.abs(got - want).max()):.2e}", flush=True)
    for name, g, w in zip(("x", "dt", "a", "B", "C"), jax.jit(grads("pallas", 128))(*args),
                          want_g):
        g = g.astype(jnp.float32)
        print(f"  d{name}: mean |.| {float(jnp.abs(w).mean()):.3e}, mean |kernel - plain| "
              f"{float(jnp.abs(g - w).mean()):.2e}, max {float(jnp.abs(g - w).max()):.2e}",
              flush=True)

for what, (h, g, chunk) in {"H64 G8 Q128 (the cell's)": (64, 8, 128),
                            "H64 G1 Q128": (64, None, 128),
                            "H64 G8 Q256": (64, 8, 256),
                            "H64 G1 Q256": (64, None, 256),
                            "H128 G1 Q256 (the Granite cell's)": (128, None, 256)}.items():
    big = inputs(2, 8192, h, 64, 128, g, jnp.bfloat16)
    bench(f"{what}: forward call", run("pallas", chunk), *big)
    bench(f"{what}: forward + backward calls", grads("pallas", chunk), *big)
