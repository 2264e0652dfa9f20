"""Times the state-space scan's two Mosaic calls at the Granite cell's shapes on the
chip, and checks the kernel against the plain einsum path in float32 at a smaller
batch (PERF.md 6, PR 32).

    chiprun --chips 1 -- python3 benchmark/tools/ssd_probe.py [batch seq heads width state]
"""
import sys
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssd

B, S, H, P, N = (int(a) for a in sys.argv[1:6]) if len(sys.argv) > 5 \
    else (2, 8192, 128, 64, 128)
print("device", jax.devices()[0].device_kind, (B, S, H, P, N), flush=True)


def inputs(b, s, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, H)) * 2 - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), maxval=jnp.log(16.0)))
    bm = (jax.random.normal(ks[3], (b, s, N)) * N ** -0.25).astype(dtype)
    cm = (jax.random.normal(ks[4], (b, s, N)) * N ** -0.25).astype(dtype)
    return x, dt, a, bm, cm


def run(impl):
    return lambda *a: ssd.ssd_scan(*a, chunk=256, impl=impl)


def grads(impl):
    return jax.grad(lambda *a: jnp.sum(run(impl)(*a).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2, 3, 4))


def bench(name, fn, *args, n=5):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name:40s} {(time.perf_counter() - t0) / n * 1e3:9.3f} ms", flush=True)


# agreement: kernel (bf16 operands, f32 sums) and plain path, both against the plain
# path in float32 at highest precision
small = inputs(1, 1024, jnp.float32)
with jax.default_matmul_precision("highest"):
    want = jax.jit(run("xla"))(*small)
    want_g = jax.jit(grads("xla"))(*small)
for dtype in (jnp.float32, jnp.bfloat16):
    args = tuple(t.astype(dtype) if i in (0, 3, 4) else t for i, t in enumerate(small))
    got = jax.jit(run("pallas"))(*args).astype(jnp.float32)
    print(f"values {jnp.dtype(dtype).name}: mean |y| {float(jnp.abs(want).mean()):.4f}, "
          f"mean |kernel - plain| {float(jnp.abs(got - want).mean()):.2e}, "
          f"max {float(jnp.abs(got - want).max()):.2e}", flush=True)
    for name, g, w in zip(("x", "dt", "a", "B", "C"), jax.jit(grads("pallas"))(*args), want_g):
        g = g.astype(jnp.float32)
        print(f"  d{name}: mean |.| {float(jnp.abs(w).mean()):.3e}, mean |kernel - plain| "
              f"{float(jnp.abs(g - w).mean()):.2e}, max {float(jnp.abs(g - w).max()):.2e}",
              flush=True)

big = inputs(B, S, jnp.bfloat16)
bench("forward call", run("pallas"), *big)
bench("forward + backward calls", grads("pallas"), *big)
for heads in (8, 32):
    ssd.HEADS_PER_BLOCK = heads
    bench(f"forward, {heads} heads a block", run("pallas"), *big)
    bench(f"forward + backward, {heads} heads a block", grads("pallas"), *big)
