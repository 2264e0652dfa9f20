"""What decides ``correct`` in a train_parallel cell, read at the cell's real size on
the chip for the program as it is and for wrong programs: the shared experts', the
attention's or the routed experts' weights rounded to 8 bits (the nearest precision
below the configuration's bf16), the window one key short or long, a serial block, an RMS
norm, tables on the full layers, rotate_half pairing, the shared experts summed; the
window's edge (train_parallel.window_edge) beside each. One process, no cluster;
prints one JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/commanda_precision_probe.py <cell> [seed ...]
"""
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import model_commanda, resolve  # noqa: E402
from benchmark.kinds import train_parallel  # noqa: E402
from ray_tpu.models import moe  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_commanda.sizes(cell["config"])
cfg = model_commanda.moe_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
_, reference = train_parallel.token_loss_fns(cfg, sizes)
(_, full), (_, window) = cfg.attn_kinds             # sorted by name


def with_kinds(window, full):
    return cfg.replace(attn_kinds=(("full", full), ("window", window)))


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26). Only the named leaves are made anew: a
# second copy of all 5.4 GB of parameters does not fit beside the reference
def eight_bit(params, names):
    round8 = jax.jit(lambda w: jax.lax.reduce_precision(
        w, exponent_bits=4, mantissa_bits=3))
    return dict(params, layers=[
        {k: (round8(w) if k in names else w) for k, w in lay.items()}
        for lay in params["layers"]])


for seed in seeds:
    params = jax.jit(lambda k: moe.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    # (name, the config run, its parameters: made when the case runs)
    cases = [("as it is", cfg, lambda: params)]
    if seed == seeds[0]:
        cases += [
            ("8-bit shared-expert weights", cfg, lambda: eight_bit(
                params, ("ws_gate", "ws_up", "ws_down"))),
            ("8-bit attention weights", cfg, lambda: eight_bit(
                params, ("wq", "wk", "wv", "wo"))),
            ("8-bit routed-expert weights", cfg, lambda: eight_bit(
                params, ("we_gate", "we_up", "we_down"))),
            ("window one key short", with_kinds(dataclasses.replace(
                window, window=window.window - 1), full), lambda: params),
            ("window one key long", with_kinds(dataclasses.replace(
                window, window=window.window + 1), full), lambda: params),
            ("a serial block", cfg.replace(parallel_block=False),
             lambda: dict(params, layers=[dict(lay, ffn_norm=lay["attn_norm"])
                                          for lay in params["layers"]])),
            ("an RMS norm", cfg.replace(norm="rms"), lambda: params),
            ("tables on the full layers", with_kinds(window, dataclasses.replace(
                full, rope=True, pairs="neighbours")), lambda: params),
            ("rotate_half pairing", with_kinds(dataclasses.replace(
                window, pairs="halves"), full), lambda: params),
            ("shared experts summed", cfg.replace(shared_combine="sum"),
             lambda: params)]
    for name, run_cfg, made in cases:
        p = made()
        got, routes = train_parallel.token_loss_fns(run_cfg, sizes)[0](p, tokens)
        del p
        ref, total, rec = reference(params, tokens, routes)
        print(json.dumps({"seed": seed, "case": name,
                          **train_parallel.loss_agreement(got, ref),
                          **train_parallel.route_agreement(routes, rec, cfg.top_k),
                          "window_edge": train_parallel.window_edge(
                              run_cfg, sizes, mix["seq"])}),
              flush=True)
        del got, routes, ref, rec
