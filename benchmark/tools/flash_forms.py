#!/usr/bin/env python3
"""The dense flash calls on the stream plan, each timed ALONE on the chip
at a cell's attention shape, one form after another (ISSUE 49).

    python3 benchmark/tools/flash_forms.py [glm|mellum|B,S,H,KV,D] \\
        [--fwd 8x2,4x2] [--dq 8x1,4x2] [--dkdv 2x1,4x1] [--parent DIR] \\
        [--reps N] [--seed N]

A form is ``<blocks a grid step>x<blocks in flight>`` (blocks of 512:
k-blocks in the forward and the dQ call, q-blocks in the dK/dV call), with
``w`` or ``l`` after it for whole spans written out or walked in a loop
(the forward and the dQ call; without it a span of up to 8 blocks is
written out), handed to ``ops/flash_attention.py``'s calls in place of the
plan's own
(``_flash_fwd(plan=)``, ``_flash_bwd_dq(plan=)``, ``_flash_bwd_dkdv(walk=)``);
``plan`` is what ``kv_plan`` / ``bwd_dkdv_plan`` choose. Without ``--fwd``
/ ``--dq`` / ``--dkdv`` a call runs its plan's form and spans of 4 and 8
blocks beside it. Every form's results are compared bit for bit with the
first thing timed: with ``--parent DIR`` (an unpacked commit,
``git archive <commit> | tar -x -C DIR``) that is DIR's own
``ray_tpu/ops/flash_attention.py`` on ITS plan, loaded beside this tree's
in one process; else this tree's plan. bf16 inputs from ``--seed``; the
forward's time holds the wrapper's two transposes, as the step's does.
Prints one JSON line a timing (``ms``: the median of ``--reps`` calls,
``best_ms`` the least) and appends them to ``chiprun_out/flash_forms.jsonl``;
a form the compiler refuses prints its error and the others go on.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# (batch, seq, heads, kv heads, head width) of the attention call one device
# makes in the two cells whose layers stream
SHAPES = {"glm": (2, 8192, 20, 20, 256), "mellum": (1, 16384, 32, 4, 128)}
BLOCK = 512


def _module(root: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "ray_tpu", "ops", "flash_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _forms(text: str) -> list:
    """[(blocks, in flight, written)]: written True, False or None."""
    return [(*(int(n) for n in f.rstrip("wl").split("x")),
             {"w": True, "l": False}.get(f[-1])) for f in text.split(",") if f]


def _name(n: int, f: int, written) -> str:
    return f"{n}x{f}" + {True: "w", False: "l", None: ""}[written]


def own_of(plan: dict) -> tuple:
    return (plan["span"] // BLOCK, plan["in_flight"], plan["written"])


def _say(out, **line):
    print(json.dumps(line), flush=True)
    out.write(json.dumps(line) + "\n")
    out.flush()


def _timed(fn, args, reps: int):
    import jax

    t0 = time.perf_counter()
    got = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return got, {"ms": statistics.median(times), "best_ms": min(times),
                 "first_call_s": first}


def _equal(got, want) -> bool:
    import jax
    import jax.numpy as jnp

    return all(bool(jnp.array_equal(a.astype(b.dtype), b)) for a, b in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("shape", nargs="?", default="glm")
    ap.add_argument("--fwd")
    ap.add_argument("--dq")
    ap.add_argument("--dkdv")
    ap.add_argument("--parent")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import ray_tpu.ops.flash_attention  # noqa: F401 - the module, not the call

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    parent = _module(args.parent, "flash_attention_parent") \
        if args.parent else None
    B, S, H, KV, D = SHAPES.get(args.shape) or tuple(
        int(n) for n in args.shape.split(","))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "flash_forms.jsonl"), "a")
    dev = jax.devices()[0]
    note = dict(shape=[B, S, H, KV, D], device=dev.device_kind, seed=args.seed)

    keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(keys[0], (B, S, H, D), bf)
    k = jax.random.normal(keys[1], (B, S, KV, D), bf)
    v = jax.random.normal(keys[2], (B, S, KV, D), bf)
    g = jax.random.normal(keys[3], (B, S, H, D), bf)
    kw = dict(causal=True, block_q=BLOCK, block_k=BLOCK, window=0,
              scale=D ** -0.5)
    plans = {c: fa.kv_plan(S=S, T=S, D=D, dtype=bf, block_q=BLOCK,
                           block_k=BLOCK, call=c) for c in ("fwd", "dq")}
    dkdv_plan = fa.bwd_dkdv_plan(
        S=S, T=S, D=D, dtype=bf, groups=H // KV, block_q=BLOCK, block_k=BLOCK,
        causal=True, window=0, vmem_bytes=fa._vmem_bytes())
    _say(out, plans={
        **{c: [p["path"], _name(*own_of(p)), p["walk_bytes"]]
           for c, p in plans.items()},
        "dkdv": [dkdv_plan["path"], _name(
            dkdv_plan["span"] // BLOCK, dkdv_plan["in_flight"], None),
            dkdv_plan["walk_bytes"]]}, **note)

    def beside(own, asked):
        if asked is not None:
            return _forms(asked)
        return [own] + [(n, f, True) for n in (4, 8) for f in (2, 1)
                        if (n, f, True) != own and (S // BLOCK) % n == 0]

    def run(call, form, fn, operands, want):
        try:
            got, line = _timed(jax.jit(fn), operands, args.reps)
        except Exception as e:   # noqa: BLE001 - the cause is the output
            _say(out, call=call, form=form,
                 error=f"{type(e).__name__}: {str(e)[:400]}")
            return None
        _say(out, call=call, form=form, **line,
             equal=None if want is None else _equal(got, want))
        return got

    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    # the forward, whose results the two backward calls read
    want = None
    if parent:
        want = run("fwd", "parent", lambda *a: parent._flash_fwd(*a, **kw),
                   (q, k, v), None)
    o = lse = None
    own = own_of(plans["fwd"])
    for n, f, w in beside(own, args.fwd):
        plan = dict(path=plans["fwd"]["path"], span=n * BLOCK, in_flight=f,
                    written=w)
        got = run("fwd", _name(n, f, w) + ("=plan" if (n, f, w) == own else ""),
                  lambda *a, plan=plan: fa._flash_fwd(*a, plan=plan, **kw),
                  (q, k, v), want)
        if got is not None and want is None:
            want = got
        if got is not None and o is None:
            o, lse = got
    if o is None:
        return 1
    back = (t(q), t(k), t(v), t(g), t(o), lse)

    want = None
    if parent:
        pplan = parent.kv_plan(S=S, T=S, D=D, dtype=bf, block_q=BLOCK,
                               block_k=BLOCK, call="dq")
        want = run("dq", "parent", lambda *a: parent._flash_bwd_dq(
            *a, plan=pplan, **kw), back, None)
    own = own_of(plans["dq"])
    for n, f, w in beside(own, args.dq):
        plan = dict(path=plans["dq"]["path"], span=n * BLOCK, in_flight=f,
                    written=w)
        got = run("dq", _name(n, f, w) + ("=plan" if (n, f, w) == own else ""),
                  lambda *a, plan=plan: fa._flash_bwd_dq(*a, plan=plan, **kw),
                  back, want)
        if got is not None and want is None:
            want = got

    want = None
    if parent:
        want = run("dkdv", "parent", lambda *a: parent._flash_bwd_dkdv(
            *a, **kw), back, None)
        if want is not None and H == KV:    # the parent's float32, rounded
            want = [x.astype(bf) for x in want]
    own = (dkdv_plan["span"] // BLOCK, dkdv_plan["in_flight"])
    asked = [form[:2] for form in _forms(args.dkdv)] \
        if args.dkdv is not None else [own] + [
            (n, f) for n, f in ((1, 1), (2, 1), (2, 2), (4, 1), (4, 2))
            if (n, f) != own]
    for n, f in asked:
        got = run("dkdv", f"{n}x{f}" + ("=plan" if (n, f) == own else ""),
                  lambda *a, walk=(n, f): fa._flash_bwd_dkdv(
                      *a, walk=walk, **kw), back, want)
        if got is not None and want is None:
            want = got
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
