"""What decides ``correct`` in a train_mixed cell, read at the cell's real size on
the chip for the program as it is and for wrong programs: the attention's or the
experts' weights rounded to 8 bits (the nearest precision below the configuration's
bf16), the window one key short, one block wide or on every layer, the plain table on
the full layers. One process, no cluster; prints one JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/mellum_precision_probe.py <cell> [seed ...]
"""
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import model_mellum, resolve  # noqa: E402
from benchmark.kinds import train_mixed  # noqa: E402
from ray_tpu.models import moe  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_mellum.sizes(cell["config"])
cfg = model_mellum.moe_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
_, reference = train_mixed.token_loss_fns(cfg, sizes)
(_, full), (_, window) = cfg.attn_kinds             # sorted by name


def with_kinds(window, full):
    return cfg.replace(attn_kinds=(("full", full), ("window", window)))


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26)
def eight_bit(names):
    return jax.jit(lambda layers: [
        {k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
             if k in names else w) for k, w in lay.items()} for lay in layers])


for seed in seeds:
    params = jax.jit(lambda k: moe.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    cases = {"as it is": (cfg, params)}
    if seed == seeds[0]:
        w = window.window
        cases.update({
            "8-bit attention weights": (cfg, dict(params, layers=eight_bit(
                ("wq", "wk", "wv", "wo"))(params["layers"]))),
            "8-bit expert weights": (cfg, dict(params, layers=eight_bit(
                ("we_gate", "we_up", "we_down"))(params["layers"]))),
            "window one key short": (with_kinds(dataclasses.replace(
                window, window=w - 1), full), params),
            "window one block wide": (with_kinds(dataclasses.replace(
                window, window=w + 512), full), params),
            "window on every layer": (with_kinds(window, dataclasses.replace(
                full, window=w)), params),
            "plain table on the full layers": (with_kinds(
                window, dataclasses.replace(full, yarn=None)), params)})
    for name, (run_cfg, p) in cases.items():
        got, routes = train_mixed.token_loss_fns(run_cfg, sizes)[0](p, tokens)
        ref, total, rec = reference(params, tokens, routes)
        print(json.dumps({"seed": seed, "case": name,
                          **train_mixed.loss_agreement(got, ref),
                          **train_mixed.route_agreement(routes, rec, cfg.top_k)}),
              flush=True)
        del got, routes, ref, rec
