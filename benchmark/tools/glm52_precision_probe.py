"""What decides ``correct`` in a train_sparse cell, read at the cell's real size on
the chip for the program as it is and for wrong programs: the attention's or the
indexer's weights rounded to 8 bits (e4m3, the nearest precision below the
configuration's bf16), a top-k one short, a shared layer reading the wrong full
layer's set, dense attention in place of sparse, ``approx_max_k`` at recall 0.95 in
place of the exact selection. One process, no cluster; prints one JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/glm52_precision_probe.py <cell> [seed ...]
"""
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import model_glm52, resolve  # noqa: E402
from benchmark.kinds import train_sparse  # noqa: E402
from ray_tpu.models import latent  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_glm52.sizes(cell["config"])
cfg = model_glm52.latent_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
_, reference = train_sparse.token_loss_fns(cfg, sizes)


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26)
def eight_bit(names):
    return jax.jit(lambda params: dict(params, layers=[{
        k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
            if k in names else w) for k, w in s.items()}
        for s in params["layers"]]))


def approx_select(scores, first_row, topk):
    """NOT the model: the keys ``approx_max_k`` finds at recall 0.95."""
    rows, keys = scores.shape
    causal = jnp.arange(keys)[None, :] <= first_row + jnp.arange(rows)[:, None]
    _, idx = jax.lax.approx_max_k(jnp.where(causal, scores, -jnp.inf),
                                  min(topk, keys), recall_target=0.95)
    hit = jnp.zeros((rows, keys), bool).at[jnp.arange(rows)[:, None], idx].set(True)
    return hit & causal


_attend_set = latent._attend_set


def wrong_layers_set(q, k, v, h, c_q, lp, cfg, cos, sin, carried, kind):
    """NOT the model: the last full layer selects, hands its set on and
    learns as it should, but ATTENDS over the set it was handed (the full
    layer's before it), as a shared layer would."""
    out, own, said = _attend_set(q, k, v, h, c_q, lp, cfg, cos, sin, carried,
                                 kind)
    if kind == "sparse.full":
        out, _, read = _attend_set(q, k, v, h, c_q, lp, cfg, cos, sin,
                                   carried, "sparse.shared")
        said = {**said, **(read or {})}
    return out, own, said


WRONG = {
    "a top-k of one fewer": (cfg.replace(index_topk=cfg.index_topk - 1), None),
    "dense attention in place of sparse": (
        cfg.replace(index_topk=mix["seq"]), None),
    "the set of the wrong full layer": (cfg, lambda: mock.patch.object(
        latent, "_attend_set", wrong_layers_set)),
    "approx_max_k at recall 0.95": (cfg, lambda: mock.patch.object(
        latent, "select", approx_select)),
}

for seed in seeds:
    params = jax.jit(lambda k: latent.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    # a case's parameters are made when its turn comes and dropped after
    # it: two 8-bit copies beside the model's own do not fit the chip
    cases = {"as it is": (cfg, None, lambda: params)}
    if seed == seeds[0]:
        cases["8-bit attention weights"] = (cfg, None, lambda: eight_bit(
            ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))(params))
        cases["8-bit indexer weights"] = (cfg, None, lambda: eight_bit(
            ("wi_q", "wi_k", "wi_w"))(params))
        for name, (wrong, patch) in WRONG.items():
            cases[name] = (wrong, patch, lambda: params)
    for name, (c, patch, made) in cases.items():
        p = made()
        with (patch() if patch else mock.patch.object(latent, "__doc__",
                                                      latent.__doc__)):
            program, _ = train_sparse.token_loss_fns(c, sizes)
            got, routes, _, sets, index, attended = program(p, tokens)
        del p, program
        ref, total, rec = reference(params, tokens, routes, sets)
        print(json.dumps({
            "seed": seed, "case": name, **train_sparse.loss_agreement(got, ref),
            **train_sparse.set_agreement(sets, rec, cfg.index_topk, attended,
                                         cfg.index_full),
            "index_loss_abs": max(abs(float(a) - float(b)) for a, b in zip(
                index, rec["index_loss"])),
            **{"route_" + k: v for k, v in train_sparse.route_agreement(
                routes, rec, cfg.top_k).items()}}), flush=True)
        del got, routes, sets, ref, rec
