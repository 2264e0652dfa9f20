"""What decides ``correct`` in a train_alternating cell, read at the cell's real size
on the chip for the program as it is (every seed given) and, on the first seed, for
wrong programs: the mixers' projections or the experts' weights rounded to 8 bits
(the nearest precision below the configuration's bf16), ONE group of B and C in place
of eight, the gated norm over all lanes, silu in place of relu^2, a gated
(three-matrix) expert, the routes' scale dropped, the bias left out of the choice, the
skip term left out. One process, no cluster; prints one JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/nemotron_precision_probe.py <cell> [seed ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import model_nemotron, resolve  # noqa: E402
from benchmark.kinds import train_alternating as kind  # noqa: E402
from ray_tpu.models import hybrid, moe  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_nemotron.sizes(cell["config"])
cfg = model_nemotron.hybrid_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "ssd_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
program, reference = kind.token_loss_fns(cfg, sizes)


def each(params, fn):
    """``fn`` over every stack of the runs (a run of a sequence holds a list)."""
    return dict(params, layers=[[fn(s) for s in run] if isinstance(run, list)
                                else fn(run) for run in params["layers"]])


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26). A leaf at a time: a wrong program's tree
# shares every leaf it does not change with the right one's (at 20 blocks two whole
# trees and a forward do not fit the chip)
_rounded = jax.jit(lambda w: jax.lax.reduce_precision(w, exponent_bits=4,
                                                      mantissa_bits=3))


def eight_bit(params, names):
    return each(params, lambda s: {k: (_rounded(w) if k in names else w)
                                   for k, w in s.items()})


def group_zero(params):
    """B and C of group 0 for every head: its columns in every group's place."""
    inner, n, g = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_groups

    def first(w, at):
        for side in (0, 1):
            lo = at + side * g * n
            for i in range(1, g):
                w = w.at[..., lo + i * n:lo + (i + 1) * n].set(w[..., lo:lo + n])
        return w

    at = jax.jit(first, static_argnums=1)
    return each(params, lambda s: {
        **s, "in_proj": at(s["in_proj"], 2 * inner),
        "conv_w": at(s["conv_w"], inner), "conv_b": at(s["conv_b"], inner)}
        if "in_proj" in s else s)


def zeroed(params, name):
    return each(params, lambda s: {k: (jnp.zeros_like(w) if k == name else w)
                                   for k, w in s.items()})


def report(seed, name, fn, p, params, tokens):
    got, routes, _ = fn(p, tokens)
    ref, _, rec = reference(params, tokens, routes)
    print(json.dumps({"seed": seed, "case": name, **kind.loss_agreement(got, ref),
                      **kind.route_agreement(routes, rec, cfg.top_k)}), flush=True)


for seed in seeds:
    params = jax.jit(lambda k: hybrid.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    # biases that matter to the choice, as a few dozen steps of the rule leave them
    params = each(params, lambda s: {**s, "router_bias": 0.02 * jax.random.normal(
        jax.random.PRNGKey(seed % 1000), s["router_bias"].shape)}
        if "router_bias" in s else s)
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    report(seed, "as it is", program, params, params, tokens)
    if seed != seeds[0]:
        continue
    for name, p in {
            "8-bit in- and out-projections": eight_bit(params, ("in_proj", "out_proj")),
            "8-bit expert weights": eight_bit(
                params, ("we_up", "we_down", "ws_up", "ws_down")),
            "ONE group in place of eight": group_zero(params),
            "the bias left out of the choice": zeroed(params, "router_bias"),
            "D left out": zeroed(params, "d_skip")}.items():
        report(seed, name, program, p, params, tokens)
        del p
    report(seed, "the scale 2.5 dropped",
           kind.token_loss_fns(cfg.replace(route_scale=1.0), sizes)[0], params,
           params, tokens)
    gated = each(params, lambda s: {**s, "we_gate": s["we_up"], "ws_gate": s["ws_up"]}
                 if "we_up" in s else s)
    report(seed, "a gated (three-matrix) expert",
           kind.token_loss_fns(cfg.replace(expert_act="swiglu"), sizes)[0], gated,
           params, tokens)
    del gated
    mean = hybrid._group_mean
    hybrid._group_mean = lambda a, groups: jnp.mean(a, axis=-1, keepdims=True)
    report(seed, "the gated norm over all 4,096 lanes",
           kind.token_loss_fns(cfg, sizes)[0], params, params, tokens)
    hybrid._group_mean = mean
    act = moe._activation
    moe._activation = lambda cfg, product: jax.nn.silu(product("up"))
    report(seed, "silu in place of relu^2", kind.token_loss_fns(cfg, sizes)[0],
           params, params, tokens)
    moe._activation = act
