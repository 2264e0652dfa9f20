"""What decides ``correct`` in a train_shortconv cell, read at the cell's real size
on the chip for the program as it is (every seed given) and, on the first seed, for
wrong programs: the operator's in- and out-projections rounded to 8 bits (the nearest
precision below the configuration's bf16), the taps in reversed order, the second gate
(``C *``) left out, the head norm left out, the bias left out of the choice, the
weights not renormalised over the 4. One process, no cluster; prints one JSON line a
case.

    chiprun --chips 1 -- python3 benchmark/tools/lfm2_precision_probe.py <cell> [seed ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import model_lfm2, resolve  # noqa: E402
from benchmark.kinds import train_shortconv as kind  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_lfm2.sizes(cell["config"])
cfg = model_lfm2.hybrid_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
program, reference = kind.token_loss_fns(cfg, sizes)


def each(params, fn):
    return dict(params, layers=[fn(run) for run in params["layers"]])


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26). A leaf at a time: a wrong program's tree
# shares every leaf it does not change with the right one's
_rounded = jax.jit(lambda w: jax.lax.reduce_precision(w, exponent_bits=4,
                                                      mantissa_bits=3))


def eight_bit(stack, names):
    return {k: (_rounded(w) if k in names else w) for k, w in stack.items()}


def report(seed, name, fn, p, params, tokens):
    got, routes, _ = fn(p, tokens)
    ref, _, rec = reference(params, tokens, routes)
    print(json.dumps({"seed": seed, "case": name, **kind.loss_agreement(got, ref),
                      **kind.route_agreement(routes, rec, cfg.top_k)}), flush=True)


for seed in seeds:
    params = jax.jit(lambda k: kind.seeded_weights(k, cfg))(
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
    flip = jax.jit(lambda w: w[:, ::-1])
    zero = jax.jit(lambda w: w * 0)
    for name, p in {
            "8-bit in- and out-projections": each(params, lambda s: eight_bit(
                s, ("in_proj", "out_proj"))),
            "the taps in reversed order": each(params, lambda s: {
                **s, "conv_w": flip(s["conv_w"])} if "conv_w" in s else s),
            "the bias left out of the choice": each(params, lambda s: {
                **s, "router_bias": zero(s["router_bias"])}
                if "router_bias" in s else s)}.items():
        report(seed, name, program, p, params, tokens)
        del p
    report(seed, "the head norm left out",
           kind.token_loss_fns(cfg.replace(qk_head_norm=False), sizes)[0], params,
           params, tokens)
    report(seed, "weights not renormalised over the 4",
           kind.token_loss_fns(cfg.replace(norm_topk=False), sizes)[0], params,
           params, tokens)
    gated = hybrid._gated_conv
    hybrid._gated_conv = lambda bcu, w: hybrid._gate_conv(bcu, w)[0].astype(bcu.dtype)
    report(seed, "the second gate left out", kind.token_loss_fns(cfg, sizes)[0],
           params, params, tokens)
    hybrid._gated_conv = gated
