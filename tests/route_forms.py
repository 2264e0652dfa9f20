"""The router's selection alone on the chip: where rounds stop paying, and
whether the routes are the sorted form's.

    chiprun --chips 1 -- python tests/route_forms.py [cross] [same] [--tiny]

``cross``: ``moe.top_lanes`` and ``at_lanes`` by rounds and masked sums
against the same by ``lax.top_k`` and ``take_along_axis`` (the limit moved
to either side of k), value and with the gradient, at
[16384, n] float32 for n 64, 128, 512 and k 4 to 64, ten dependent repeats
inside one program, ms a repeat: what ``moe.ROUND_MAX_K`` rests on (PERF.md
6, PR 60). ``same``: ``moe.route`` against ``tests/test_moe_route.py``'s
``sorted_route`` for every expert cell's own router at its tokens a step,
on logits made as a cell makes them (bf16 rows times a bf16 router of std
0.02, so scores crowd round 0.5 and rows hold exact ties; the bias 0, then
a few steps of 0.001): rows with a tie, rows whose experts or kept groups
differ, whether scores and the weights before the norm are equal bit for
bit, the normed weights' largest relative difference. ``--tiny`` rehearses
at 256 tokens on the CPU. A reader by hand: no metric, no test.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import moe  # noqa: E402

REPEATS = 10


def picked(s, k):
    """The program's own selection and picked scores; ``cross`` moves
    ``moe.ROUND_MAX_K`` round it to take either form at any k."""
    _, lanes = moe.top_lanes(jax.lax.stop_gradient(s), k)
    return moe.at_lanes(s, lanes), lanes


def repeated(k, grad):
    def once(s, g):
        def scalar(s):
            w, lanes = picked(s, k)
            return (w * g).sum() + 1e-9 * lanes.sum()
        return jax.grad(scalar)(s) if grad else jnp.zeros_like(s) + scalar(s)

    return jax.jit(lambda s, g: jax.lax.fori_loop(
        0, REPEATS, lambda i, s: s + 1e-6 * once(s, g), s))


def cross(tokens):
    limit = moe.ROUND_MAX_K
    try:
        _cross(tokens)
    finally:
        moe.ROUND_MAX_K = limit


def _cross(tokens):
    for n in (64, 128, 512):
        s = jax.random.normal(jax.random.PRNGKey(n), (tokens, n), jnp.float32)
        for k in (4, 8, 16, 24, 32, 48, 64):
            if k >= n:
                continue
            g = jax.random.normal(jax.random.PRNGKey(k), (tokens, k))
            row = {"n": n, "k": k}
            for name, limit in (("rounds", k), ("sort", 0)):
                moe.ROUND_MAX_K = limit         # read while f is traced
                for grad in (False, True):
                    f = repeated(k, grad)
                    jax.block_until_ready(f(s, g))
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(s, g))
                    row[name + ("_grad" if grad else "") + "_ms"] = round(
                        1e3 * (time.perf_counter() - t0) / REPEATS, 4)
            print(json.dumps(row), flush=True)


def same(tokens):
    import test_moe_route as t

    for cell in t.CELLS:
        cfg, cell_tokens = t.cell_config(cell)
        rows = tokens or cell_tokens
        raw = cfg.replace(norm_topk=False, route_scale=1.0)
        new = jax.jit(lambda l, b: (moe.route(l, cfg, b),
                                    moe.route(l, raw, b)[0]))
        old = jax.jit(lambda l, b: (t.sorted_route(l, cfg, b),
                                    t.sorted_route(l, raw, b)[0]))
        out = {"cell": cell, "E": cfg.n_experts, "K": cfg.top_k, "draws": []}
        for draw in range(4):
            k = jax.random.split(jax.random.PRNGKey(100 + draw), 3)
            x = jax.random.normal(k[0], (rows, 2048), jnp.bfloat16)
            w = (0.02 * jax.random.normal(k[1], (2048, cfg.n_experts))
                 ).astype(jnp.bfloat16)
            logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
            bias = None
            if moe._has_bias(cfg):
                bias = 0.001 * (draw % 2) * jnp.round(3 * jax.random.normal(
                    k[2], (cfg.n_experts,), jnp.float32))
            (gw, ge, gp, gk), graw = new(logits, bias)
            (ww, we, wp, wk), wraw = old(logits, bias)
            choice = jnp.sort(gp + (0 if bias is None else bias), axis=-1)
            out["draws"].append({
                "rows_with_an_exact_tie": int(jnp.any(
                    choice[:, 1:] == choice[:, :-1], axis=1).sum()),
                "rows_tied_at_the_kth": int((choice[:, -cfg.top_k]
                                             == choice[:, -cfg.top_k - 1]
                                             ).sum()),
                "rows_experts_differ": int(jnp.any(ge != we, axis=1).sum()),
                "rows_kept_differ": None if gk is None else int(
                    jnp.any(gk != wk, axis=1).sum()),
                "scores_equal": bool(jnp.array_equal(gp, wp)),
                "raw_weights_equal": bool(jnp.array_equal(graw, wraw)),
                "weights_max_rel_diff": float(jnp.max(
                    jnp.abs(gw - ww) / jnp.abs(ww)))})
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    tiny = "--tiny" in sys.argv
    parts = [a for a in sys.argv[1:] if not a.startswith("--")] \
        or ["cross", "same"]
    print("device", jax.devices()[0].device_kind, flush=True)
    if "cross" in parts:
        cross(256 if tiny else 16384)
    if "same" in parts:
        same(256 if tiny else None)
