"""What decides ``correct`` in a train_blockset cell, read at the cell's real size
on the chip for the program as it is and for wrong programs: the lightning layers'
q, k and v projections rounded to 8 bits (e4m3, the nearest precision below the
configuration's bf16), the scan's decay left out, the forced blocks left out of
the selection (the query's own block alone stays), top-32 for top-64. Each case
goes through the kind's own ``set_checks`` and ``loss_checks`` under the cell's
``train.check`` (the step's loss is ``sala.loss_fn``'s, the timed step's forward;
the two checks after the first update are left out: the probe makes no update), so
a line says which limits refuse the case. The 8-bit case runs on every seed, the
wrong models on the first. One process, no cluster; prints one JSON line a case.

    chiprun --chips 1 -- python3 benchmark/tools/sala_precision_probe.py <cell> [seed ...]
"""
import contextlib
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import model_sala, resolve  # noqa: E402
from benchmark.kinds import train_blockset  # noqa: E402
from ray_tpu.models import sala  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_sala.sizes(cell["config"])
cfg = model_sala.sala_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "ssd_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
_, reference = train_blockset.token_loss_fns(cfg, sizes)


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26)
@jax.jit
def eight_bit_lightning(params):
    return dict(params, layers=[{
        k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
            if k in ("wq", "wk", "wv") and "o_norm" in s else w)
        for k, w in s.items()} for s in params["layers"]])


WRONG = {
    "the decay left out": (cfg, lambda: mock.patch.object(
        sala, "slopes", lambda heads: jnp.zeros((heads,), jnp.float32))),
    "the forced blocks left out": (cfg.replace(
        sparse_init_blocks=0, sparse_window=cfg.sparse_block), None),
    "top-32 for top-64": (cfg.replace(sparse_topk=cfg.sparse_topk // 2), None),
}

for seed in seeds:
    params = jax.jit(lambda k: sala.init_params(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    cases = {"as it is": (cfg, None, lambda: params),
             "8-bit lightning projections": (
                 cfg, None, lambda: eight_bit_lightning(params))}
    if seed == seeds[0]:
        for name, (wrong, patch) in WRONG.items():
            cases[name] = (wrong, patch, lambda: params)
    for name, (c, patch, made) in cases.items():
        p = made()
        with patch() if patch else contextlib.nullcontext():
            program, _ = train_blockset.token_loss_fns(c, sizes)
            got, sets = program(p, tokens)
            step_loss = float(jax.jit(lambda p, t, c=c: sala.loss_fn(
                p, {"tokens": t}, c)[0])(p, tokens))
        del p, program
        ref, rec = reference(params, tokens, sets)
        agreement = train_blockset.loss_agreement(got, ref)
        selecting = train_blockset.set_agreement(sets, rec, sizes)
        checks = {
            **train_blockset.set_checks(selecting, recipe["check"], sizes),
            **{k: v for k, v in train_blockset.loss_checks(
                {"agreement": agreement, "first_loss": step_loss,
                 "second_loss": step_loss,
                 "ref_loss_updated": agreement["ref_loss"]},
                recipe["check"]).items() if "first update" not in k}}
        print(json.dumps({
            "seed": seed, "case": name, **agreement, **selecting,
            "step_loss": step_loss,
            "step_loss_apart": abs(step_loss - agreement["ref_loss"]),
            "correct": all(checks.values()),
            "refused_by": [k for k, ok in checks.items() if not ok]}),
            flush=True)
        del got, sets, ref, rec
