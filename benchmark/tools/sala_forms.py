"""The two mechanisms of a train_blockset cell alone on the chip, form by form, at
the cell's shape: the wide scan (forward, and forward with backward) at chunks of
128, 256 and 512; attention over a set of blocks (the model's kind of set: the
first block, a window of 32 and 31 others a query) with the walk's span and blocks
in flight forced, call by call; the selection. One process, no cluster; prints one
JSON line a form, milliseconds a call (ten calls in flight, the median of five rounds).

    chiprun --chips 1 -- python3 benchmark/tools/sala_forms.py <cell> [scan|walk|select ...]
"""
import json
import os
import statistics
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import flops, flops_sala, model_sala, resolve  # noqa: E402
from ray_tpu.models import sala  # noqa: E402
from ray_tpu.ops import sparse_attention as sa  # noqa: E402
from ray_tpu.ops import ssd  # noqa: E402

cell = resolve.cell(sys.argv[1])
parts = sys.argv[2:] or ["scan", "walk", "select"]
sizes, mix = model_sala.sizes(cell["config"]), cell["mix"]
cfg = model_sala.sala_config(cell["config"])
B, S, D = mix["batch"], mix["seq"], cfg.head_dim
H, KV, LH = cfg.n_heads, cfg.n_kv_heads, cfg.lightning_heads
peak = resolve.peak(jax.devices()[0].device_kind)
print("device", jax.devices()[0].device_kind, flush=True)
keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))


def ms(fn, *args, calls: int = 10):
    """Milliseconds a call: ``calls`` of them sent one after the other and
    waited for at the end, so that the host's dispatch (a millisecond, as
    long as a scan's forward) runs under the device's work; the median of
    five such rounds after one warm one."""
    rounds = []
    for i in range(6):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(calls)])
        rounds.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(rounds[1:])


def normal(shape):
    return jax.random.normal(next(keys), shape, jnp.bfloat16)


if "scan" in parts:
    x, bm, cm = (normal((B, S, LH, D)) for _ in range(3))
    a = -sala.slopes(LH)
    layer = flops.least_seconds(flops_sala.lightning_layer(sizes, B, S), peak)
    for chunk in (128, 256, 512):
        def run(x, bm, cm, chunk=chunk):
            return ssd.ssd_scan(x, None, a, bm, cm, chunk=chunk, impl="pallas")

        fwd = ms(jax.jit(run), x, bm, cm)
        both = ms(jax.jit(jax.grad(
            lambda *t: run(*t).astype(jnp.float32).sum(), argnums=(0, 1, 2))),
            x, bm, cm)
        print(json.dumps({"form": "scan", "chunk": chunk, "fwd_ms": fwd,
                          "fwd_bwd_ms": both,
                          "layer_least_ms": layer["seconds"] * 1e3,
                          "bound": layer["bound"]}), flush=True)

if "walk" in parts or "select" in parts:
    q, k, v = normal((B, S, H, D)), normal((B, S, KV, D)), normal((B, S, KV, D))

if "select" in parts:
    pick = jax.jit(lambda q, k: sala.select_blocks(q, k, cfg))
    print(json.dumps({"form": "select", "rows": sala.SELECT_ROWS,
                      "ms": ms(pick, q, k)}), flush=True)

if "walk" in parts:
    sel = jax.jit(lambda q, k: sala.select_blocks(q, k, cfg))(q, k)
    layer = flops.least_seconds(
        flops_sala.block_sparse_attention_layer(sizes, B, S), peak)
    choose = sa._choose
    # (forward and dQ, dK/dV): None leaves the call to the plan
    forms = [(None, None), ((16, 1), None), ((8, 1), None), ((4, 2), None),
             ((2, 2), None), (None, (2, 2)), (None, (2, 1)), (None, (8, 1))]
    for walk, mirror in forms:
        def forced(call, **kw):
            want = mirror if call == "dkdv" else walk
            return want or choose(call, **kw)

        with mock.patch.object(sa, "_choose", forced):
            try:
                fwd = ms(jax.jit(lambda q, k, v: sa.block_sparse_attention(
                    q, k, v, sel)), q, k, v)
                both = ms(jax.jit(jax.grad(
                    lambda q, k, v: sa.block_sparse_attention(
                        q, k, v, sel).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))), q, k, v)
            except Exception as e:          # noqa: BLE001 - a form that does not fit
                print(json.dumps({"form": "walk", "fwd_dq": walk,
                                  "dkdv": mirror, "failed": str(e)[:300]}),
                      flush=True)
                continue
        print(json.dumps({"form": "walk", "fwd_dq": walk, "dkdv": mirror,
                          "fwd_ms": fwd, "fwd_bwd_ms": both,
                          "layer_least_ms": layer["seconds"] * 1e3}),
              flush=True)
