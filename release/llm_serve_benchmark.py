"""LLM serving north-star: req/s + p50 TTFT (BASELINE.json target 4:
continuous-batched serving on TPU; ref: release/serve_tests/workloads/*
emit qps + latency percentiles).

Drives the continuous-batching engine (serve/llm.py) with concurrent
request threads, all in ONE process, which holds the chip; it starts no
child. Every number is as measured on the device jax gives it, which the
output names.

    python release/llm_serve_benchmark.py --preset tiny --requests 64 \
        --concurrency 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cache_init(llama, cfg, quantize: str):
    """The engine's own init recipe (serve dtype + optional int8), run
    host-side so it can be cached across benchmark invocations."""
    import jax

    params = llama.init_params(jax.random.PRNGKey(0),
                               cfg.replace(param_dtype=cfg.dtype))
    if quantize == "int8":
        params = llama.quantize_params_int8(params)
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--decode-block", type=int, default=4)
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=["contiguous", "paged"])
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None)
    ap.add_argument("--max-slots", type=int, default=None)
    ap.add_argument("--max-queue-depth", type=int, default=None)
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="engine sequence budget (default: the preset's "
                    "max_seq_len). The paged decode kernel's grid and the "
                    "tail-prefill attention view scale with THIS, not with "
                    "live tokens — size it to the serving workload "
                    "(prompt+new rounded up) or pay for max_seq worth of "
                    "clamped grid steps per decode")
    ap.add_argument("--params-cache", default=None,
                    help="npz path to cache initialized (and quantized) "
                    "params: a 7B host-side random init costs ~20 min of "
                    "one vCPU per run; the cache turns reruns into a "
                    "~1 min disk load")
    ap.add_argument("--quantize", choices=["none", "int8"], default="none",
                    help="weight-only int8: at-rest HBM halves (7B fits "
                    "one 16 GB v5e chip), layers dequantize in-scan")
    ap.add_argument("--prefix-caching", choices=["on", "off"],
                    default="on",
                    help="paged-only: every request here shares one "
                    "prompt, so 'on' measures the warm prefix-hit path "
                    "(recorded in the output for comparability)")
    args = ap.parse_args()

    from ray_tpu.serve.llm import LLMQueueFull, LLMServer

    max_slots = args.max_slots or args.concurrency
    # admission control is layout-independent: pass the depth always
    kw = {"max_queue_depth": args.max_queue_depth}
    if args.max_seq_len:
        kw["max_seq_len"] = args.max_seq_len
    if args.kv_layout == "paged":
        kw.update(kv_layout="paged", page_size=args.page_size,
                  num_pages=args.num_pages,
                  prefix_caching=args.prefix_caching == "on")
    if args.quantize != "none":
        kw["quantize"] = args.quantize
    if args.params_cache:
        import jax
        import numpy as _np

        from ray_tpu.models import llama

        cfg = llama.PRESETS[args.preset]
        import ml_dtypes

        treedef = jax.tree.structure(jax.eval_shape(
            lambda: _cache_init(llama, cfg, args.quantize)))
        fingerprint = f"{args.preset}|{args.quantize}"
        if os.path.exists(args.params_cache):
            flat = dict(_np.load(args.params_cache))
            got = str(flat.get("fingerprint", ""))
            if got != fingerprint:
                sys.exit(f"--params-cache {args.params_cache} was built "
                         f"for '{got}', this run needs '{fingerprint}' — "
                         "delete it or point at a different path")
            n = sum(1 for k in flat if k.startswith("a"))
            leaves = []
            for i in range(n):
                a = flat[f"a{i}"]
                dt = str(flat[f"d{i}"])
                if a.dtype.kind in ("V", "u") and dt == "bfloat16":
                    a = a.view(ml_dtypes.bfloat16)
                leaves.append(a)
            tree = jax.tree.unflatten(treedef, leaves)
            kw["params"] = jax.device_put(tree, jax.devices()[0])
            print("# params loaded from cache", file=sys.stderr, flush=True)
        else:
            with jax.default_device(jax.devices("cpu")[0]):
                tree = _cache_init(llama, cfg, args.quantize)
            out = {"fingerprint": _np.asarray(fingerprint)}
            for i, v in enumerate(jax.tree.leaves(tree)):
                a = _np.asarray(v)
                out[f"d{i}"] = _np.asarray(str(a.dtype))
                # npz cannot round-trip ml_dtypes.bfloat16 — store the
                # raw uint16 view and re-view on load
                out[f"a{i}"] = (a.view(_np.uint16)
                                if a.dtype == ml_dtypes.bfloat16 else a)
            _np.savez(args.params_cache, **out)
            kw["params"] = jax.device_put(tree, jax.devices()[0])
            print("# params initialized and cached", file=sys.stderr,
                  flush=True)
    server = LLMServer(preset=args.preset, max_slots=max_slots,
                       decode_block=args.decode_block, **kw)

    # Warmup: drive every prefill bucket + decode-block compilation once,
    # so measured TTFT reflects steady-state serving, not XLA compiles
    # (the reference's serve benchmarks likewise exclude cold start).
    t_warm = time.time()
    print(f"# warmup: initial batch ({min(4, args.concurrency)} reqs) — "
          "first prefill+decode compiles", file=sys.stderr, flush=True)
    warm = [server.engine.submit(list(range(2, 2 + args.prompt_len)),
                                 args.max_new_tokens)
            for _ in range(min(4, args.concurrency))]
    server._wake.set()
    for w in warm:
        w.done_event.wait(timeout=3600)
    print(f"# warmup: initial batch done in {time.time() - t_warm:.0f}s",
          file=sys.stderr, flush=True)
    # post-registration waves: prefix-cache hits compile the chunked
    # tail-prefill program per (batch, tail) bucket — cover the batch
    # buckets steady-state admission uses, or each lands as a ~25s
    # outlier inside the measured window
    waves = []
    nb = 1
    while nb < args.concurrency:      # every pow2 batch bucket admission
        waves.append(nb)              # can produce at this concurrency
        nb *= 2
    waves.append(nb)
    for wave in reversed(waves):
        t_wave = time.time()
        ws = [server.engine.submit(list(range(2, 2 + args.prompt_len)),
                                   args.max_new_tokens)
              for _ in range(wave)]
        server._wake.set()
        for w in ws:
            w.done_event.wait(timeout=3600)
        print(f"# warmup: batch bucket {wave} done in "
              f"{time.time() - t_wave:.0f}s", file=sys.stderr, flush=True)
    for k in server.engine.metrics:
        server.engine.metrics[k] = 0

    prompt = list(range(2, 2 + args.prompt_len))
    ttfts = []
    lat = []
    lock = threading.Lock()
    sem = threading.Semaphore(args.concurrency)
    done = threading.Event()
    left = [args.requests]

    rejected = [0]

    def one():
        t0 = time.time()
        while True:
            try:
                req = server.engine.submit(prompt, args.max_new_tokens)
                break
            except LLMQueueFull:
                # the 429 path: shed + client retry with backoff — TTFT
                # stays bounded because queue wait is capped by depth
                with lock:
                    rejected[0] += 1
                time.sleep(0.05)
        server._wake.set()
        req.done_event.wait(timeout=600)
        t1 = time.time()
        with lock:
            if req.first_token_time:
                ttfts.append(req.first_token_time - req.submit_time)
            lat.append(t1 - t0)
            left[0] -= 1
            if left[0] <= 0:
                done.set()
        sem.release()

    t_start = time.time()
    for _ in range(args.requests):
        sem.acquire()
        threading.Thread(target=one, daemon=True).start()
    done.wait(timeout=1200)
    wall = time.time() - t_start

    ttfts.sort()
    lat.sort()

    def pct(xs, p):
        return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else None

    # Engine-only TTFT floor, MEASURED (not estimated): one warmed
    # prefill dispatch+fetch on the live engine. The serving TTFT above
    # it is admission and queue wait.
    import jax
    import jax.numpy as jnp
    import numpy as _np
    toks0 = jnp.asarray(_np.zeros((1, len(prompt)), _np.int32))
    lens0 = jnp.asarray(_np.asarray([len(prompt)], _np.int32))
    _ = server.engine._prefill(server.engine.params, toks0, lens0)
    t0 = time.perf_counter()
    for _ in range(10):
        lg, _k, _v = server.engine._prefill(server.engine.params, toks0,
                                            lens0)
    jax.block_until_ready(lg)
    engine_prefill_s = (time.perf_counter() - t0) / 10

    p50 = pct(ttfts, 0.50)
    dev = jax.devices()[0]
    out = {
        "bench": "llm_serve",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "preset": args.preset,
        "requests": args.requests,
        "concurrency": args.concurrency,
        "req_per_s": round(args.requests / wall, 2),
        "tokens_per_s": round(
            args.requests * args.max_new_tokens / wall, 1),
        "ttft_p50_ms": round(p50 * 1e3, 1) if p50 else None,
        "ttft_p95_ms": round((pct(ttfts, 0.95) or 0) * 1e3, 1),
        "latency_p50_ms": round((pct(lat, 0.50) or 0) * 1e3, 1),
        "engine_prefill_ms": round(engine_prefill_s * 1e3, 1),
        "kv_layout": args.kv_layout,
        "quantize": args.quantize,
        "prefix_caching": (args.prefix_caching == "on"
                           if args.kv_layout == "paged" else None),
        "max_slots": max_slots,
        "rejected_429": rejected[0],
        "stats": server.stats(),
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
