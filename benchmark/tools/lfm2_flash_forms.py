"""The two mechanisms new to a train_shortconv cell alone on the chip, form by form,
at the cell's shape. ``flash``: the three flash calls at 32 query heads over 8 KV
heads of 64 as they are (blocks of 64 lanes, half a tile) against a wrapper that pads
q, k and v to 128 lanes (zeros add nothing to q k^T; the output's upper lanes are cut
off), forward alone and forward with backward. ``conv``: one short-convolution
operator's gate-taps-gate pass [T, 6144] -> [T, 2048], the hand-written gradient
(``hybrid._gated_conv``) against jax's own of the same arithmetic, forward with
backward, and each form's compiled temporaries. One process, no cluster; prints one
JSON line a form, milliseconds a call (ten calls in flight, the median of five rounds).

    chiprun --chips 1 -- python3 benchmark/tools/lfm2_flash_forms.py <cell> [flash|conv ...]
"""
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import flops, flops_lfm2, model_lfm2, resolve  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402
from ray_tpu.ops.flash_attention import flash_attention  # noqa: E402

cell = resolve.cell(sys.argv[1])
parts = sys.argv[2:] or ["flash", "conv"]
sizes, mix = model_lfm2.sizes(cell["config"]), cell["mix"]
B, S, D = mix["batch"], mix["seq"], sizes["d_model"]
H, KV, HD = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_width"]
peak = resolve.peak(jax.devices()[0].device_kind)
print("device", jax.devices()[0].device_kind, flush=True)
keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))


def ms(fn, *args, calls: int = 10):
    """Milliseconds a call: ``calls`` of them sent one after the other and waited
    for at the end; the median of five such rounds after one warm one."""
    rounds = []
    for _ in range(6):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(calls)])
        rounds.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(rounds[1:])


def normal(shape):
    return jax.random.normal(next(keys), shape, jnp.bfloat16)


if "flash" in parts:
    q, k, v = normal((B, S, H, HD)), normal((B, S, KV, HD)), normal((B, S, KV, HD))
    g = normal((B, S, H, HD))

    def native(q, k, v):
        return flash_attention(q, k, v)

    def padded(q, k, v):
        wide = [jnp.pad(t, ((0, 0),) * 3 + ((0, 128 - HD),)) for t in (q, k, v)]
        return flash_attention(*wide, scale=HD ** -0.5)[..., :HD]

    least = {w: flops.least_seconds(flops_lfm2.flash_call(sizes, B, S, w), peak)
             ["seconds"] * 1e3 for w in ("fwd", "dq", "dkdv")}
    outs = {}
    for name, fn in (("heads of 64 as they are", native),
                     ("padded to 128 lanes in the wrapper", padded)):
        fwd = jax.jit(fn)
        both = jax.jit(lambda q, k, v, fn=fn: jax.vjp(fn, q, k, v)[1](g))
        outs[name] = fwd(q, k, v)
        t_f, t_b = ms(fwd, q, k, v), ms(both, q, k, v)
        print(json.dumps({
            "part": "flash", "form": name, "fwd_ms": t_f, "fwd_bwd_ms": t_b,
            "least_fwd_ms": least["fwd"], "least_all_ms": sum(least.values()),
            "roofline_fwd": least["fwd"] / t_f,
            "roofline_all": sum(least.values()) / t_b}), flush=True)
    a, b = (o.astype(jnp.float32) for o in outs.values())
    print(json.dumps({"part": "flash", "forms_differ_max":
                      float(jnp.abs(a - b).max())}), flush=True)

if "conv" in parts:
    bcu, w, g = normal((B, S, 3 * D)), normal((sizes["conv_taps"], D)), \
        normal((B, S, D))

    def plain(bcu, w):
        c, _ = hybrid._gate_conv(bcu, w)
        return (bcu[..., D:2 * D].astype(jnp.float32) * c).astype(bcu.dtype)

    one = flops_lfm2.gate_conv_step({**sizes, "layer_types": ("conv",)}, B * S)
    least = flops.least_seconds(one, peak)["seconds"] * 1e3
    for name, fn in (("the hand-written gradient", hybrid._gated_conv),
                     ("jax's own gradient", plain)):
        both = jax.jit(lambda bcu, w, fn=fn: jax.vjp(fn, bcu, w)[1](g))
        mem = both.lower(bcu, w).compile().memory_analysis()
        t = ms(both, bcu, w)
        print(json.dumps({
            "part": "conv", "form": name, "fwd_bwd_ms": t, "least_ms": least,
            "roofline": least / t, "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes)}), flush=True)
