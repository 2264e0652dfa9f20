#!/usr/bin/env python3
"""chip_smoke.py — does the main path still start on the chip?

Drives both halves of ray_tpu's main path once, through the entry points
a user calls, at the full width of the ``2b7`` preset (32 layers, random
weights from ``--seed``), and checks what comes out:

  train      ray_tpu.init() -> JaxTrainer (one worker, one chip) -> the
             loop builds 2b7 (bf16 params, flash, remat, bf16 logits,
             adafactor, B5 x S1024) with make_train_state_init /
             make_train_step and takes a few steps on one batch.
  serve      serve.run(build_llm_app(use_sim=False, num_replicas=1,
             preset="2b7", kv_layout="paged")) -> HTTP requests.
  reference  a plain llama.forward over one served prompt with the same
             parameters, in a process that opens the chip after the
             replica has gone; the served tokens must agree with it.
  --chips 4  instead of all the above: the fsdp x tp sharded train step
             on four chips against the same steps on one device.

A smoke, not a benchmark: the seconds it prints include compilation and
say nothing about speed. Every phase runs between its own
ray_tpu.init()/shutdown(); shutdown() reaps the workers, so the process
that held the chip is gone before the next one opens it. This parent
never initialises a JAX backend: the device line comes from the workers.

Exit code 0 and a last line {"ok": true, "device": {...}} only when
every phase passed ON A TPU. ``--size tiny`` is the CPU rehearsal: the
same code path at the tiny preset, every phase runs and reports, and the
run then fails the platform check.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import urllib.error
import urllib.request

# (preset, train batch, train seq, serve max_seq_len, page size,
#  prompt length, shared-prefix length, new tokens per request)
SIZES = {
    "2b7": dict(preset="2b7", batch=5, seq=1024, serve_seq=1024, page=64,
                prompt=300, shared=256, new_tokens=8),
    "tiny": dict(preset="tiny", batch=2, seq=128, serve_seq=128, page=16,
                 prompt=70, shared=48, new_tokens=8),
}
# Served tokens are compared with a teacher-forced plain forward in the
# serving dtype. Random weights give logits ~ N(0, 1) over the vocabulary,
# whose top two are often closer than bf16 rounding through 32 layers, so
# "equal argmax" is asked of the reference logits up to this margin: the
# served token's reference logit must be within it of the reference
# maximum. (A wrong token sits ~4 below the maximum.) Exact matches are
# counted and printed.
LOGIT_MARGIN = {"bfloat16": 0.25, "float32": 1e-3}
# The sharded and the one-device step run the same math in bf16 with
# different reduction orders; losses near 10 agree to about this.
SHARDED_LOSS_TOL = 0.05


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


# --------------------------------------------------------------------------
# code that runs in the worker that holds the chip


def _device_info():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require(platform: str, want_tpu: bool, who: str) -> None:
    """Before the first trace: a full-size run off the chip would take
    hours on the CPU and prove nothing, so it fails here with the cause."""
    if want_tpu and platform != "tpu":
        raise RuntimeError(
            f"{who}: jax gave platform {platform!r}, not 'tpu' "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            "refusing to run the full-size smoke off the chip")


class _CacheEvents:
    """Counts jax's persistent-compilation-cache events in this process."""

    def __init__(self):
        import jax

        self.hits = self.requests = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def report(self) -> dict:
        return {"cache_hits": self.hits, "cache_requests": self.requests}


def _build_train(size: dict, seed: int, mesh, rules, batch: int, on_tpu: bool):
    """2b7 in bf16 with flash, remat, bf16 logits and adafactor. Returns
    (state, compiled step, batch, what the compiler says of it, cfg)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.train_step import (make_train_state_init,
                                             make_train_step)

    dt = jnp.bfloat16 if on_tpu else jnp.float32
    cfg = llama.PRESETS[size["preset"]].replace(
        dtype=dt, param_dtype=dt, remat=True, attn_impl="flash",
        f32_logits=not on_tpu)
    opt = optax.adafactor(3e-4)
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), opt, mesh, rules,
        llama.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, size["seq"] + 1), 0, cfg.vocab_size)
    data = {"tokens": tokens}
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        opt, mesh, rules, state_sh, batch_shapes=jax.eval_shape(lambda: data))
    t0 = time.perf_counter()
    compiled = step.lower(state, data).compile()   # the program that runs
    compile_s = time.perf_counter() - t0
    text, mem = compiled.as_text(), compiled.memory_analysis()
    program = {
        "compile_s": compile_s,
        "pallas_calls": text.count("tpu_custom_call"),
        "collectives": {c: text.count(c) for c in (
            "all-gather", "reduce-scatter", "all-reduce", "all-to-all",
            "collective-permute")},
        # per device, as the compiler planned them
        "argument_bytes": int(mem.argument_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes)}
    return state, compiled, data, program, cfg


def _peaks() -> list:
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()]


def _run_steps(state, compiled, data, n: int) -> list:
    losses = []
    for _ in range(n):
        state, m = compiled(state, data)
        losses.append(float(m["loss"]))   # host fetch = the step is done
    del state
    return losses


def train_loop(config: dict) -> None:
    """JaxTrainer's per-worker loop: one chip, the mesh ScalingConfig asks
    for (dp over this worker's one device)."""
    import jax

    from ray_tpu.train import session

    events = _CacheEvents()
    dev = _device_info()
    _require(dev["platform"], config["want_tpu"], "train worker")
    on_tpu = dev["platform"] == "tpu"
    size = config["size"]
    mesh, rules = session.get_mesh(), session.get_rules()
    t0 = time.perf_counter()
    state, compiled, data, program, cfg = _build_train(
        size, config["seed"], mesh, rules, size["batch"], on_tpu)
    losses = _run_steps(state, compiled, data, config["steps"])
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    session.report({
        "device": dev, "losses": losses, "vocab": cfg.vocab_size,
        "program": program, "peak_bytes": _peaks(), "bytes_limit": limit,
        "total_s": time.perf_counter() - t0, "pid": os.getpid(),
        **events.report()})


def sharded_loop(config: dict) -> None:
    """--chips 4: one process drives four chips. The fsdp x tp step first
    (so its per-device peak is its own), then the same steps on a
    one-device mesh in the same process."""
    import jax

    from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh
    from ray_tpu.train import session

    events = _CacheEvents()
    dev = _device_info()
    _require(dev["platform"], config["want_tpu"], "train worker")
    on_tpu = dev["platform"] == "tpu"
    size, seed, steps = config["size"], config["seed"], config["steps"]
    batch = config["batch"]
    out = {"device": dev, "batch": batch, "pid": os.getpid()}

    mesh, rules = session.get_mesh(), session.get_rules()
    out["mesh"] = {"shape": {k: int(v) for k, v in mesh.shape.items()
                             if int(v) > 1},
                   "device_ids": [int(d.id) for d in mesh.devices.flat]}
    state, compiled, data, program, cfg = _build_train(
        size, seed, mesh, rules, batch, on_tpu)
    wq = state.params["layers"]["wq"]
    out["wq_shard_shape"] = list(wq.addressable_shards[0].data.shape)
    out["wq_shape"] = list(wq.shape)
    del wq
    out["sharded"] = {"losses": _run_steps(state, compiled, data, steps),
                      "program": program, "peak_bytes": _peaks()}
    del state, compiled, data

    one = build_mesh(MeshSpec(dp=-1), devices=jax.devices()[:1])
    state, compiled, data, program, _ = _build_train(
        size, seed, one, ShardingRules.dp(), batch, on_tpu)
    out["one_device"] = {"losses": _run_steps(state, compiled, data, steps),
                         "program": program, "peak_bytes": _peaks()[:1]}
    out["vocab"] = cfg.vocab_size
    session.report({**out, **events.report()})


def reference_tokens(size: dict, seed: int, prompt: list, served: list,
                     want_tpu: bool) -> dict:
    """Teacher-forced plain llama.forward (XLA attention, no cache, no
    kernel) over prompt + served tokens with the parameters the engine
    made from the same seed. For each served token: the reference argmax
    at its position and how far below the reference maximum it sits."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    dev = _device_info()
    _require(dev["platform"], want_tpu, "reference worker")
    # what LLMEngine does with preset= and no params
    cfg = llama.PRESETS[size["preset"]]
    if dev["platform"] != "tpu":
        cfg = cfg.replace(dtype=jnp.float32)
    cfg = cfg.replace(param_dtype=cfg.dtype, max_seq_len=size["serve_seq"])
    params = llama.init_params(jax.random.PRNGKey(seed), cfg)
    toks = jnp.asarray([prompt + served[:-1]], jnp.int32)
    logits = jax.jit(lambda p, t: llama.forward(p, t, cfg))(params, toks)[0]
    at = logits[len(prompt) - 1:]                     # [len(served), V]
    best = jnp.max(at, axis=-1)
    got = at[jnp.arange(len(served)), jnp.asarray(served)]
    return {"device": dev, "dtype": str(jnp.dtype(cfg.dtype)),
            "argmax": [int(x) for x in jnp.argmax(at, axis=-1)],
            "below_max": [float(x) for x in best - got],
            "finite": bool(jnp.isfinite(logits).all())}


# --------------------------------------------------------------------------
# phases (parent: no jax here)


def _init_cluster(want_tpu: bool, chips: int = 1) -> None:
    import ray_tpu
    from ray_tpu.core.node import detect_tpu_chips

    found = detect_tpu_chips()
    if want_tpu and found < chips:
        raise SmokeFailure(
            f"this host shows {found} TPU chip(s) (/dev/accel*, "
            f"/dev/vfio/<n>, or RAY_TPU_CHIPS), the smoke needs {chips}")
    # CPUs are scheduling tokens: controller + proxy + router + replica
    # each take one, whatever the host has
    ray_tpu.init(num_cpus=max(os.cpu_count() or 1, 8))


def _fit(loop, config: dict, name: str, args, want_tpu: bool, chips: int,
         mesh, rules: str) -> dict:
    """One JaxTrainer run of ``loop`` on one worker holding ``chips``
    chips, between its own init() and shutdown(); returns the loop's
    last report."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    _init_cluster(want_tpu, chips)
    try:
        result = JaxTrainer(
            loop,
            train_loop_config={"size": SIZES[args.size], "seed": args.seed,
                               "steps": args.steps, "want_tpu": want_tpu,
                               **config},
            scaling_config=ScalingConfig(num_workers=1,
                                         chips_per_worker=chips, mesh=mesh,
                                         rules=rules),
            run_config=RunConfig(name=name,
                                 storage_path=args.scratch)).fit()
    finally:
        ray_tpu.shutdown()
    if result.error:
        raise SmokeFailure(f"{name} failed:\n{result.error}")
    return result.metrics


def _loss_checks(losses: list, vocab: int) -> None:
    # logits of the random init are ~N(0,1) (lm_head std D^-0.5 on a
    # unit-RMS input), so the first loss sits near ln(V) + 1/2
    want = math.log(vocab) + 0.5
    check(all(math.isfinite(x) for x in losses), f"losses finite: {losses}")
    check(abs(losses[0] - want) < 0.35,
          f"first loss {losses[0]:.4f} within 0.35 of ln({vocab})+0.5 = "
          f"{want:.4f}")
    check(losses[-1] < losses[0] and all(
        b < a + 1e-3 for a, b in zip(losses, losses[1:])),
        "losses fall on the repeated batch")


def phase_train(size: dict, args, want_tpu: bool) -> dict:
    from ray_tpu.parallel import MeshSpec

    log("== phase train: ray_tpu.init -> JaxTrainer(1 worker x 1 chip) -> "
        f"{size['preset']} B{size['batch']} x S{size['seq']}, "
        f"{args.steps} steps")
    t0 = time.time()
    m = _fit(train_loop, {}, "chip_smoke_train", args, want_tpu, 1,
             MeshSpec(dp=-1), "dp")
    prog = m["program"]
    log(f"  device {m['device']}  worker pid {m['pid']}")
    log(f"  losses {[round(x, 4) for x in m['losses']]}")
    log(f"  compile {prog['compile_s']:.1f} s, phase in worker "
        f"{m['total_s']:.1f} s, wall {time.time() - t0:.1f} s; "
        f"persistent cache: {m['cache_hits']} hits of "
        f"{m['cache_requests']} requests")
    log(f"  step program: arguments {prog['argument_bytes']} + temporaries "
        f"{prog['temp_bytes']} bytes; peak_bytes_in_use {m['peak_bytes']} "
        f"of limit {m['bytes_limit']}")
    _loss_checks(m["losses"], m["vocab"])
    if m["device"]["platform"] == "tpu":
        check(prog["pallas_calls"] >= 3,
              f"step program holds {prog['pallas_calls']} Pallas calls "
              "(tpu_custom_call: flash forward, dq, dkdv)")
        check(0 < m["peak_bytes"][0] < m["bytes_limit"]
              and prog["argument_bytes"] + prog["temp_bytes"]
              < m["bytes_limit"],
              "peak_bytes_in_use and the program's plan under the chip's "
              "memory")
    check(not os.path.exists(f"/proc/{m['pid']}"),
          f"train worker {m['pid']} is gone after shutdown()")
    log(f"phase train passed on {m['device']['platform']}")
    return m["device"]


def _post(url: str, body: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"HTTP {e.code} from the server: {e.read().decode()[:2000]}")


def phase_serve(size: dict, args, want_tpu: bool) -> dict:
    import random

    import ray_tpu
    from ray_tpu import serve

    log("== phase serve: serve.run(build_llm_app(use_sim=False, "
        f"num_replicas=1, preset={size['preset']!r}, kv_layout='paged')) "
        "-> HTTP")
    rng = random.Random(args.seed)
    vocab = 32000 if size["preset"] != "tiny" else 256
    n, shared = size["prompt"], size["shared"]
    prompt_a = [rng.randrange(vocab) for _ in range(n)]
    prompt_b = [rng.randrange(vocab) for _ in range(n - 40)]
    prompt_c = prompt_a[:shared] + [rng.randrange(vocab)
                                    for _ in range(n - shared)]
    t0 = time.time()
    _init_cluster(want_tpu)
    try:
        port = serve.start()
        serve.run(serve.build_llm_app(
            use_sim=False, num_replicas=1, preset=size["preset"],
            kv_layout="paged", page_size=size["page"], max_slots=8,
            max_seq_len=size["serve_seq"], seed=args.seed),
            route_prefix="/llm")
        replica = serve.get_deployment_handle("llm_server")
        report = ray_tpu.get(replica.method("device_report").remote(),
                             timeout=600)
        log(f"  replica up after {time.time() - t0:.1f} s: {report}")
        _require(report["platform"], want_tpu, "serve replica")
        url = f"http://127.0.0.1:{port}/llm"

        def ask(name, prompt):
            t = time.time()
            out = _post(url, {"prompt": prompt,
                              "max_new_tokens": size["new_tokens"]},
                        timeout=120)
            if "tokens" not in out:
                raise SmokeFailure(f"request {name}: no tokens in {out}")
            log(f"  {name}: {len(prompt)} prompt tokens -> {out['tokens']} "
                f"in {time.time() - t:.1f} s")
            return out["tokens"]

        a1 = ask("A", prompt_a)
        b = ask("B", prompt_b)
        before = ray_tpu.get(replica.method("stats").remote(), timeout=60)
        a2 = ask("A again", prompt_a)
        c = ask(f"C (first {shared} tokens of A)", prompt_c)
        stats = ray_tpu.get(replica.method("stats").remote(), timeout=60)
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    log(f"  engine: requests {stats['requests']}, tokens_generated "
        f"{stats['tokens_generated']}, prefix_hits "
        f"{stats.get('prefix_hits', 0)} ({stats.get('prefix_hit_tokens', 0)}"
        f" tokens), prefix_cache {stats.get('prefix_cache')}, "
        f"decode_blocks {stats.get('decode_blocks')}")
    for name, toks in (("A", a1), ("B", b), ("C", c)):
        check(len(toks) == size["new_tokens"]
              and all(0 <= t < vocab for t in toks),
              f"{name}: {size['new_tokens']} tokens in the vocabulary")
    check(a1 == a2, "the repeated prompt returns the same tokens")
    check(a1 != b, "different prompts return different tokens")
    hits = stats.get("prefix_hits", 0) - before.get("prefix_hits", 0)
    hit_toks = (stats.get("prefix_hit_tokens", 0)
                - before.get("prefix_hit_tokens", 0))
    check(hits >= 2 and hit_toks >= 2 * shared,
          f"'A again' and 'C' were admitted from the prefix cache "
          f"({hits} hits, {hit_toks} tokens)")
    if report["platform"] == "tpu":
        check(report["decode_has_pallas_call"],
              "the decode block holds the paged Pallas kernel")
        check(report["dtype"] == "bfloat16", "the replica serves in bf16")
    log(f"phase serve passed on {report['platform']} "
        f"(wall {time.time() - t0:.1f} s)")
    return {"report": report, "prompt": prompt_a, "served": a1}


def phase_reference(size: dict, args, want_tpu: bool, serve_out: dict) -> None:
    import ray_tpu

    log("== phase reference: plain llama.forward over prompt A in a fresh "
        "process, after the replica has gone")
    _init_cluster(want_tpu)
    try:
        ref = ray_tpu.get(
            ray_tpu.remote(num_tpus=1)(reference_tokens).remote(
                size, args.seed, serve_out["prompt"], serve_out["served"],
                want_tpu), timeout=1200)
    finally:
        ray_tpu.shutdown()
    served = serve_out["served"]
    exact = sum(int(a == b) for a, b in zip(ref["argmax"], served))
    margin = LOGIT_MARGIN[ref["dtype"]]
    log(f"  reference on {ref['device']} in {ref['dtype']}: argmax "
        f"{ref['argmax']}")
    log(f"  served                      {served}")
    log(f"  exact {exact}/{len(served)}; served token below the reference "
        f"maximum by {[round(x, 4) for x in ref['below_max']]}")
    check(ref["finite"], "reference logits finite")
    check(ref["dtype"] == serve_out["report"]["dtype"],
          "reference and replica use the same dtype")
    check(all(x <= margin for x in ref["below_max"]),
          f"every served token is the reference argmax up to {margin} "
          f"logits ({ref['dtype']})")
    log(f"phase reference passed on {ref['device']['platform']}")


def phase_sharded(size: dict, args, want_tpu: bool) -> dict:
    from ray_tpu.parallel import MeshSpec

    batch = 4   # B8 cut to what one chip holds too, and used on both
    log("== phase sharded: JaxTrainer(1 worker x 4 chips) -> "
        f"{size['preset']} B{batch} x S{size['seq']}, MeshSpec(fsdp=2, tp=2)"
        f" + fsdp_tp vs one device, {args.steps} steps each")
    m = _fit(sharded_loop, {"batch": batch}, "chip_smoke_sharded", args,
             want_tpu, 4, MeshSpec(fsdp=2, tp=2), "fsdp_tp")
    sh, one = m["sharded"], m["one_device"]
    log(f"  device {m['device']}  mesh {m['mesh']}")
    log(f"  wq {m['wq_shape']} -> per-device shard {m['wq_shard_shape']}")
    for name, r in (("sharded   ", sh), ("one device", one)):
        p = r["program"]
        log(f"  {name} losses {[round(x, 4) for x in r['losses']]}  compile "
            f"{p['compile_s']:.1f} s  per-device arguments "
            f"{p['argument_bytes']} + temporaries {p['temp_bytes']}  "
            f"peak_bytes_in_use {r['peak_bytes']}")
    shp = sh["program"]
    log(f"  sharded program: {shp['pallas_calls']} Pallas calls, "
        f"collectives {shp['collectives']}; persistent cache "
        f"{m['cache_hits']} hits of {m['cache_requests']} requests")
    _loss_checks(sh["losses"], m["vocab"])
    check(len(set(m["mesh"]["device_ids"])) == 4,
          f"the mesh lists four distinct devices {m['mesh']['device_ids']}")
    diffs = [abs(a - b) for a, b in zip(sh["losses"], one["losses"])]
    check(max(diffs) <= SHARDED_LOSS_TOL,
          f"sharded and one-device losses agree within {SHARDED_LOSS_TOL} "
          f"(max difference {max(diffs):.4f})")
    check(m["wq_shard_shape"] != m["wq_shape"],
          "a parameter's per-device shard is smaller than the parameter")
    if m["device"]["platform"] == "tpu":
        peaks = sh["peak_bytes"]
        check(max(peaks) < 0.6 * one["peak_bytes"][0]
              and shp["argument_bytes"]
              < 0.6 * one["program"]["argument_bytes"],
              "per-device peak and arguments well under the one-device "
              "figures")
        check(max(peaks) < 1.25 * min(peaks),
              "per-device peaks roughly equal on all four")
        check(shp["pallas_calls"] >= 3, "the sharded program holds the "
              "Pallas calls")
        check(shp["collectives"]["all-gather"] > 0
              and (shp["collectives"]["reduce-scatter"]
                   + shp["collectives"]["all-reduce"]) > 0,
              "the sharded program holds the collectives")
    log(f"phase sharded passed on {m['device']['platform']}")
    return m["device"]


def _daemon_log_tails(sessions: str, lines: int = 30) -> None:
    """After a failure: the end of what the last cluster's gcs and nodelet
    logged. Workers' output reaches this process as it is written; the
    daemons' does not, and a kill or a refused lease is recorded only
    there."""
    import glob

    dirs = glob.glob(os.path.join(sessions, "session_[0-9]*"))
    if not dirs:
        return
    logs = os.path.join(max(dirs, key=os.path.getmtime), "logs")
    for path in sorted(glob.glob(os.path.join(logs, "gcs.err"))
                       + glob.glob(os.path.join(logs, "nodelet*.err"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        log(f"-- last lines of {path}")
        for line in tail:
            log("   " + line.rstrip())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", choices=sorted(SIZES), default="2b7",
                    help="2b7 = the smoke (needs a TPU); tiny = the CPU "
                         "rehearsal of the same path (always exits non-zero "
                         "off the chip)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    size = SIZES[args.size]
    want_tpu = args.size != "tiny"

    here = os.path.dirname(os.path.abspath(__file__))
    args.scratch = os.path.join(here, ".chip_smoke")
    # daemons' session directories and logs stay inside the checkout
    os.environ.setdefault("RAY_TPU_TMPDIR",
                          os.path.join(args.scratch, "sessions"))
    try:
        from ray_tpu.core import compile_cache
    except ImportError as e:
        log(f"FAILED: cannot import ray_tpu next to {__file__}: {e}")
        return 1
    cache_dir = compile_cache.env_defaults()     # children inherit it
    entries_before = compile_cache.entry_count(cache_dir)
    log(f"compile cache {cache_dir}: {entries_before} entries before")

    device, failure = None, None
    t0 = time.time()
    try:
        if args.chips == 4:
            device = phase_sharded(size, args, want_tpu)
        else:
            device = phase_train(size, args, want_tpu)
            served = phase_serve(size, args, want_tpu)
            phase_reference(size, args, want_tpu, served)
            check(served["report"]["kind"] == device["kind"],
                  "train worker and serve replica saw the same device kind")
    except SmokeFailure as e:
        failure = str(e)
    except Exception as e:   # noqa: BLE001 — the cause is the output
        import traceback

        traceback.print_exc()
        failure = f"{type(e).__name__}: {e}"
    log(f"compile cache {cache_dir}: {entries_before} entries before, "
        f"{compile_cache.entry_count(cache_dir)} after; "
        f"total {time.time() - t0:.1f} s")

    # one process for each chip: this parent must never have opened one
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is not None and getattr(bridge, "_backends", None):
        failure = failure or ("the parent process initialised a jax "
                              f"backend: {list(bridge._backends)}")
    if failure is None and device["platform"] != "tpu":
        failure = (f"every phase passed, but on {device['platform']!r}: "
                   "this is a rehearsal, not a chip run")
    if failure is None and device["count"] != args.chips:
        failure = (f"ran on {device['count']} chip(s), asked for "
                   f"{args.chips}")
    if failure is not None:
        _daemon_log_tails(os.environ["RAY_TPU_TMPDIR"])
        log(f"FAILED: {failure}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
