"""What decides ``correct`` in a train_moe cell, read at the cell's real size on the
chip for the program as it is and for wrong programs: expert weights rounded to 8
bits (the nearest precision below the configuration's bf16), one expert fewer per
token, renormalised weights. One process, no cluster; prints one JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/moe_precision_probe.py <cell> [seed ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import model_moe, resolve  # noqa: E402
from benchmark.kinds import train_moe  # noqa: E402
from ray_tpu.models import moe  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_moe.sizes(cell["config"])
cfg = model_moe.moe_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
_, reference = train_moe.token_loss_fns(cfg, sizes)
programs = {}


def program(c):
    if c not in programs:
        programs[c] = train_moe.token_loss_fns(c, sizes)[0]
    return programs[c]


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (xla_allow_excess_precision), and did (PERF.md 6, PR 26)
eight_bit = jax.jit(lambda layers: {
    k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
        if k.startswith("we_") else w) for k, w in layers.items()})
for seed in seeds:
    params = jax.jit(lambda k: moe.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    cases = {"as it is": (params, cfg)}
    if seed == seeds[0]:
        cases["8-bit expert weights"] = (
            dict(params, layers=eight_bit(params["layers"])), cfg)
        cases["one expert fewer"] = (params, cfg.replace(top_k=cfg.top_k - 1))
        cases["renormalised weights"] = (params, cfg.replace(norm_topk=True))
    for name, (p, c) in cases.items():
        got, routes = program(c)(p, tokens)
        ref, total, rec = reference(params, tokens, routes)
        print(json.dumps({"seed": seed, "case": name,
                          **train_moe.loss_agreement(got, ref),
                          **train_moe.route_agreement(routes, rec, cfg.top_k)}),
              flush=True)
        del got, routes, ref, rec
