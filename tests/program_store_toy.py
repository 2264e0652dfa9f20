"""The two-matrix program of tests/test_program_store.py, and what a
subprocess of that test does with it (``python program_store_toy.py
'<json>'``): make the key, run the state program and two steps, and print
what the process kept (every record `tracing` made, the keys, a digest of
the results) as one JSON line. With ``{"loop": N}`` it times N calls of
the step in a row, what ``make_train_step`` returns against the plain
``jax.jit`` object inside it (what the parent returned), on whatever
device jax has: what a user's ``step(state, batch)`` loop pays a call."""

import dataclasses
import hashlib
import json
import os
import sys


@dataclasses.dataclass(frozen=True)
class Toy:
    width: int = 16
    scale: float = 1.0


def rule(params, aux):
    """A ``post_update`` that moves nothing."""
    return params, aux


def build(lr=1e-3, width=16, scale=1.0, post=False, donate=True, rows=4,
          dtype="float32", dp=1, loss=None, shapes=True, leaves=0):
    """(init_fn, step, shapes of the state, a batch) of a two-matrix
    autoencoder under ``make_train_state_init`` / ``make_train_step``;
    the shapes (a trace of the state program) only where asked."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh
    from ray_tpu.parallel.train_step import (make_train_state_init,
                                             make_train_step)
    from ray_tpu.util import tracing

    cfg = Toy(width, scale)
    mesh = build_mesh(MeshSpec(dp=dp), devices=jax.devices()[:dp])
    rules, opt = ShardingRules.dp(), optax.sgd(lr)

    def init(key):
        a, b = jax.random.split(key)
        more = {f"w{i}": jnp.zeros((8, 8), dtype) for i in range(leaves)}
        return {"a": jax.random.normal(a, (8, cfg.width), dtype),
                "b": jax.random.normal(b, (cfg.width, 8), dtype), **more}

    def loss_fn(p, batch):
        tracing.plan("toy.plan", {"width": cfg.width})
        y = jnp.tanh(batch["x"] @ p["a"]) @ p["b"] * cfg.scale
        y = y + sum(p[f"w{i}"].sum() for i in range(leaves))
        return jnp.mean((y - batch["x"]) ** 2)

    init_fn, state_sh = make_train_state_init(
        init, opt, mesh, rules, {k: (None, None) for k in (
            "a", "b", *(f"w{i}" for i in range(leaves)))})
    step = make_train_step(loss or loss_fn, opt, mesh, rules, state_sh,
                           batch_shapes={"x": jax.ShapeDtypeStruct(
                               (rows * dp, 8), dtype)}, donate=donate,
                           post_update=rule if post else None)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0)) if shapes else None
    batch = {"x": jnp.linspace(0.0, 1.0, rows * dp * 8, dtype=dtype
                               ).reshape(rows * dp, 8)}
    return init_fn, step, state, batch


def loop(calls: int, leaves: int = 200, rounds: int = 3) -> dict:
    """Microseconds a call of ``calls`` steps in a row on a state of
    ``leaves`` + 2 leaves (an SGD step of a few hundred flops: all
    dispatch), the stored program's and the jit object's in turn."""
    import time

    import jax

    init_fn, step, _, batch = build(leaves=leaves, shapes=False)
    said = {"device": str(jax.devices()[0]), "calls": calls,
            "leaves": leaves + 2, "stored_us": [], "jit_us": []}
    for _ in range(rounds):
        for name, call in (("stored_us", step), ("jit_us", step._jit)):
            state = init_fn(jax.random.PRNGKey(7))
            state, _ = call(state, batch)                 # made or loaded
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for _ in range(calls):
                state, metrics = call(state, batch)
            jax.block_until_ready(state)
            said[name].append(round(
                (time.perf_counter() - t0) / calls * 1e6, 2))
    # what a call would pay to say its arguments anew (it does so once)
    from ray_tpu.core import compile_cache

    t0 = time.perf_counter()
    for _ in range(calls):
        compile_cache.said_of((state, batch))
    said["signature_us"] = round((time.perf_counter() - t0) / calls * 1e6, 2)
    return said


def main(ask: dict) -> dict:
    """One process of the round trip: records everything `tracing`
    makes, runs the state program and two steps, says what came of it."""
    import jax
    import numpy as np

    from ray_tpu.core import compile_cache
    from ray_tpu.util import tracing

    if ask.get("loop"):
        return loop(int(ask["loop"]), **ask.get("knobs", {}))
    records = []
    tracing._record = records.append
    tracing.enable()                  # every stage, whatever it took
    compile_cache.listen()
    traced = []       # every function jax traced, however short the trace

    def on_span(event, start, end, fun_name="", **_kw):
        if event.endswith("jaxpr_trace_duration"):
            traced.append(str(fun_name))

    jax.monitoring.register_event_time_span_listener(on_span)
    init_fn, step, _, batch = build(**ask.get("knobs", {}), shapes=False)
    out = {"pid": os.getpid()}
    if ask.get("run"):
        state = init_fn(jax.random.PRNGKey(7))
        out["key"] = step.key(state, batch)
        first = state
        state, metrics = step(state, batch)
        out["donated"] = bool(first.params["a"].is_deleted())
        state, metrics = step(state, batch)
        leaves = jax.tree.leaves((state, metrics))
        out["result"] = hashlib.sha256(b"".join(
            np.asarray(x).tobytes() for x in leaves)).hexdigest()
        out["compiles"] = compile_cache.compile_count()
    out["traced"] = traced
    out["records"] = [{k: r.get(k) for k in ("kind", "name", "ts", "dur",
                                             "attrs")} for r in records]
    return out


if __name__ == "__main__":
    print("\n" + json.dumps(main(json.loads(sys.argv[1]))))
