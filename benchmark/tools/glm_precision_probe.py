"""What decides ``correct`` in a train_latent cell, read at the cell's real size on
the chip for the program as it is and for wrong programs: the latents' up-projections
(``wq_b``, ``wkv_b``) or the experts' weights rounded to 8 bits (e4m3, the nearest
precision below the configuration's bf16), the shared rotary key left out. One
process, no cluster; prints one JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/glm_precision_probe.py <cell> [seed ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import model_glm, resolve  # noqa: E402
from benchmark.kinds import train_latent  # noqa: E402
from ray_tpu.models import latent  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_glm.sizes(cell["config"])
cfg = model_glm.latent_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
program, reference = train_latent.token_loss_fns(cfg, sizes)


def every_stack(change, params):
    return dict(params, layers=[change(s) for s in params["layers"]],
                mtp=dict(params["mtp"], block=change(params["mtp"]["block"])))


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26)
def eight_bit(names):
    return jax.jit(lambda params: every_stack(lambda s: {
        k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
            if k in names else w) for k, w in s.items()}, params))


for seed in seeds:
    params = jax.jit(lambda k: latent.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 2), 0,
                                cfg.vocab_size, "int32")
    cases = {"as it is": params}
    if seed == seeds[0]:
        cases["8-bit latent up-projections"] = eight_bit(("wq_b", "wkv_b"))(params)
        cases["8-bit expert weights"] = eight_bit(
            ("we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down"))(params)
        cases["the rotary key left out"] = every_stack(lambda s: dict(
            s, wkv_a=s["wkv_a"].at[..., cfg.kv_rank:].set(0)), params)
    for name, p in cases.items():
        got, ahead, routes, _ = program(p, tokens)
        ref, ref_ahead, total, rec = reference(params, tokens, routes)
        print(json.dumps({
            "seed": seed, "case": name, **train_latent.loss_agreement(got, ref),
            **{"mtp_" + k: v for k, v in
               train_latent.loss_agreement(ahead, ref_ahead).items()},
            **train_latent.route_agreement(routes, rec, cfg.top_k)}), flush=True)
        del got, ahead, routes, ref, ref_ahead, rec
