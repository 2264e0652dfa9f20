"""What decides ``correct`` in a train_falconh1 cell, read at the cell's real size on
the chip for the program as it is (every seed given) and, on the first seed, for
wrong programs: ``attention_out_multiplier``, ``ssm_out_multiplier``,
``key_multiplier``, the SwiGLU's gate multiplier and the segment multiplier of x left
at 1 in turn, m_B and m_C swapped, the attention half's projections and the mixer's
rounded to 8 bits (the nearest precision below the configuration's bf16), and the gate
AFTER the gated norm. One process, no cluster; prints one JSON line a case: the
per-token losses against the reference's (computed once a seed, on the right model),
the forward's mean loss less the reference's, and the two "alive" readings.

    chiprun --chips 1 -- python3 benchmark/tools/falconh1_precision_probe.py <cell> [--op] [seed ...]

``--op``: part (c) alone, the scan's two calls on the first layer's own inputs at the
cell's shape through the kind's ``op_agreement``, every seed given: the kernel pair in
the timed type and on the same values in float32, each line with the largest of the
six parts beside the limit of the cell's ``check`` it is held to.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import model_falconh1, resolve  # noqa: E402
from benchmark.kinds import train_falconh1 as kind  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402

cell = resolve.cell(sys.argv[1])
only_op = "--op" in sys.argv
seeds = [int(s) for s in sys.argv[2:] if not s.startswith("--")] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_falconh1.sizes(cell["config"])
cfg = model_falconh1.falcon_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "ssd_impl", "remat", "f32_logits") if k in recipe})
B, S = mix["batch"], mix["seq"]
print("device", jax.devices()[0].device_kind, flush=True)

# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26)
_rounded = jax.jit(lambda w: jax.lax.reduce_precision(w, exponent_bits=4,
                                                      mantissa_bits=3))


def each(params, fn):
    return dict(params, layers=[fn(run) for run in params["layers"]])


def rounded(*names):
    return lambda p: each(p, lambda run: {
        **run, **{n: _rounded(run[n]) for n in names}})


def at(field, i, value=1.0):
    old = getattr(cfg, field)
    return cfg.replace(**{field: tuple(value if j == i else v
                                       for j, v in enumerate(old))})


def gate_after_norm(y, xs, z, d_skip, gate_norm, eps, groups=1):
    f32 = jnp.float32
    v = y.astype(f32) + xs.astype(f32) * hybrid._lanes(d_skip, y.shape[-1])
    r = jax.lax.rsqrt(hybrid._group_mean(v * v, groups) + eps)
    return (v * r * gate_norm.astype(f32)
            * jax.nn.silu(z.astype(f32))).astype(y.dtype)


z, x, b, c, dt = cfg.ssm_multipliers
WRONG = [
    ("attention_out_multiplier at 1", cfg.replace(attention_out_multiplier=1.0), None),
    ("ssm_out_multiplier at 1", cfg.replace(ssm_out_multiplier=1.0), None),
    ("key_multiplier at 1", cfg.replace(key_multiplier=1.0), None),
    ("mlp gate multiplier at 1", at("mlp_multipliers", 0), None),
    ("ssm_multipliers' x at 1", at("ssm_multipliers", 1), None),
    ("m_B and m_C swapped", cfg.replace(ssm_multipliers=(z, x, c, b, dt)), None),
    ("attention projections in 8 bits", cfg, rounded("wq", "wk", "wv", "wo")),
    ("mixer projections in 8 bits", cfg, rounded("in_proj", "out_proj")),
    ("the gate after the norm", cfg, "patch"),
]

for n, seed in enumerate(seeds):
    params = jax.jit(lambda k: kind.seeded_weights(k, cfg, S))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey((seed + 1) % (2 ** 31)), 0),
        (B, S + 1), 0, cfg.vocab_size, "int32")
    alive, halves, op_in = jax.jit(
        lambda p, t: kind.first_layer(cfg, p, t))(params, tokens)
    if only_op:
        read = kind.op_agreement(
            op_in, jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                                     op_in[0].shape, jnp.float32),
            min(cfg.mamba_chunk, S))
        for which, dtype in (("timed", cfg.dtype), ("float32", jnp.float32)):
            got = read(cfg.ssd_impl, dtype)
            print(json.dumps({"seed": seed, "case": "op " + which, **got,
                              "worst": max(got.values()),
                              "limit": recipe["check"]["op_rel_" + which]}),
                  flush=True)
        continue
    del op_in
    program, reference = kind.token_loss_fns(cfg, sizes)
    ref = reference(params, tokens)

    def report(name, fn, p):
        got = fn(p, tokens)
        a = kind.loss_agreement(got, ref)
        print(json.dumps({"seed": seed, "case": name, **a,
                          "forward_loss_less_ref":
                              a["program_loss"] - a["ref_loss"]}), flush=True)

    print(json.dumps({"seed": seed, "case": "alive",
                      **{k: float(v) for k, v in alive.items()},
                      **{"rms_" + k: float(v) for k, v in halves.items()}}),
          flush=True)
    report("as it is", program, params)
    if n:
        continue
    for name, wrong_cfg, change in WRONG:
        real = hybrid._gated_norm
        if change == "patch":
            hybrid._gated_norm = gate_after_norm
        try:
            fn, _ = kind.token_loss_fns(wrong_cfg, sizes)
            report(name, fn, change(params) if callable(change) else params)
        finally:
            hybrid._gated_norm = real
