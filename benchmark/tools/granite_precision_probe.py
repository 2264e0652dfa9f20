"""What decides ``correct`` in a train_hybrid cell, read at the cell's real size on
the chip for the program as it is and for wrong programs: the mixers' projections
or the experts' weights rounded to 8 bits (the nearest precision below the
configuration's bf16), the skip term left out. One process, no cluster; prints one
JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/granite_precision_probe.py <cell> [seed ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import model_granite, resolve  # noqa: E402
from benchmark.kinds import train_hybrid  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_granite.sizes(cell["config"])
cfg = model_granite.hybrid_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "ssd_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
program, reference = train_hybrid.token_loss_fns(cfg, sizes)


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26)
def eight_bit(names):
    return jax.jit(lambda layers: [
        {k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
             if k in names else w) for k, w in lay.items()} for lay in layers])


for seed in seeds:
    params = jax.jit(lambda k: hybrid.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    cases = {"as it is": params}
    if seed == seeds[0]:
        cases["8-bit mixer weights"] = dict(params, layers=eight_bit(
            ("in_proj", "out_proj"))(params["layers"]))
        cases["8-bit expert weights"] = dict(params, layers=eight_bit(
            ("we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down"))(
                params["layers"]))
        cases["D left out"] = dict(params, layers=[
            {k: (jnp.zeros_like(w) if k == "d_skip" else w)
             for k, w in lay.items()} for lay in params["layers"]])
    for name, p in cases.items():
        got, routes = program(p, tokens)
        ref, total, rec = reference(params, tokens, routes)
        print(json.dumps({"seed": seed, "case": name,
                          **train_hybrid.loss_agreement(got, ref),
                          **train_hybrid.route_agreement(routes, rec, cfg.top_k)}),
              flush=True)
        del got, routes, ref, rec
