"""Kind ``serve``: tokens and latencies as clients see them over HTTP, the
way ``chip_smoke.py`` reaches the chip: ``serve.start -> serve.run`` of one
replica behind ``LLMRouter`` and the HTTP proxy, wired with the same public
calls as the monolithic branch of ``build_llm_app``.

The replica is the program's ``LLMServer``; ``BenchLLMServer`` adds what
only the process that holds the chip can do: make the weights in one
jitted call, compile the prefill shapes before the window, start and stop
the profiler, and run the plain reference over a served prompt with the
engine's own parameters. The same class serves in both runs. The parent
(``run``) generates the load from one process and never touches jax.
"""

from __future__ import annotations

import http.client
import json
import os
import threading
import time

from ray_tpu.serve.llm import LLMServer

# Served tokens are greedy. Random weights give logits ~N(0,1) over the
# vocabulary, whose top two are often closer than bf16 rounding through
# the layers, so "the served token is the reference argmax" is asked up to
# this margin of the float32 reference's own logits (chip_smoke.py's
# margin; a wrong token sits ~4 below the maximum).
LOGIT_MARGIN = {"bfloat16": 0.25, "float32": 1e-3}


class BenchLLMServer(LLMServer):
    def __init__(self, bench: dict, **kw):
        import jax

        from benchmark import model
        from ray_tpu.models import llama

        platform = jax.devices()[0].platform
        if bench["want_tpu"] and platform != "tpu":
            raise RuntimeError(
                f"serve replica: jax gave platform {platform!r}, not 'tpu'; "
                "a real configuration is not measured off the chip")
        cfg = model.llama_config(bench["config"])
        # weights on the device in one jitted call, in the serving type
        params = jax.jit(lambda k: llama.init_params(k, cfg))(
            jax.random.PRNGKey(bench["seed"] % (2 ** 31)))
        super().__init__(cfg=cfg, params=params, **kw)
        self._bench = bench
        self._sizes = model.sizes(bench["config"])

    def peak_bytes(self) -> list:
        import jax

        return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.devices()]

    def device_info(self) -> dict:
        import jax

        d = jax.devices()[0]
        e = self.engine
        plan = 0
        try:        # the decode block's plan: weights, pools and temporaries
            args = (e.params, e._last, e.kp, e.vp, e._pt_dev, e._len_dev,
                    e._active_dev, e._temps_dev, e._key)
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
            m = e._decode_n_paged.lower(
                *shapes, n=self.decode_block).compile().memory_analysis()
            plan = int(m.argument_size_in_bytes + m.temp_size_in_bytes
                       + m.output_size_in_bytes - m.alias_size_in_bytes)
        except Exception as err:   # noqa: BLE001 - the peak still stands
            plan = 0
            self._plan_error = repr(err)
        # device_report: platform, kind, count, dtype, whether the decode
        # block holds the Pallas call
        return {"plan_bytes": plan, **self.engine.device_report()}

    def warm_shapes(self, batches: list, lengths: list) -> dict:
        """Compile (or load from the cache) the chunked-prefill program of
        every (batch bucket, length bucket) the window can ask for, on
        empty rows: a row of tail length 0 writes only to the trash page.
        Called while the engine is idle."""
        import jax.numpy as jnp
        import numpy as np

        e = self.engine
        t0, n = time.perf_counter(), 0
        for nb in batches:
            for tb in lengths:
                zeros = jnp.zeros((nb,), jnp.int32)
                tab = jnp.zeros((nb, e.pool.table.shape[1]), jnp.int32)
                logits, e.kp, e.vp = e._prefill_tail(
                    e.params, jnp.zeros((nb, tb), jnp.int32), zeros, zeros,
                    tab, e.kp, e.vp)
                np.asarray(e._sample(logits, [0.0] * nb))
                n += 1
        return {"programs": n, "seconds": time.perf_counter() - t0}

    def _context(self) -> list:
        """Tokens cached for each busy slot, now (host-side lengths)."""
        e = self.engine
        with e.lock:
            return [int(e._len_host[i]) for i, r in enumerate(e.slots)
                    if r is not None]

    def trace_start(self, trace_dir: str) -> list:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the device and the runtime only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._trace = (trace_dir, time.perf_counter())
        return self._context()

    def trace_stop(self) -> list:
        import jax

        trace_dir, t0 = self._trace
        self._trace = (trace_dir, time.perf_counter() - t0)
        context = self._context()
        jax.profiler.stop_trace()
        return context

    def trace_reduced(self) -> str:
        """After the window: the reduction, in the process that traced,
        written beside the trace (op names are whole HLO lines: too much
        for an actor's reply). Returns the file's path."""
        from benchmark import trace_reduce

        trace_dir, span = self._trace
        red = trace_reduce.reduce_file(trace_reduce.find_xplane(trace_dir),
                                       window_s=span)
        red["structure"] = red["structure"][:80]
        red["idle_gaps"] = red.get("idle_gaps", [])[:20]
        path = os.path.join(trace_dir, "reduced.json")
        with open(path, "w") as f:
            json.dump(red, f)
        return path

    def reference_check(self, prompt: list, served: list) -> dict:
        import jax
        import jax.numpy as jnp

        from benchmark import reference

        tokens = jnp.asarray(list(prompt) + list(served[:-1]), jnp.int32)
        below, best = jax.jit(
            lambda p, t, s: reference.served_margin(
                p, t, s, len(prompt), self._sizes))(
            self.engine.params, tokens, jnp.asarray(served, jnp.int32))
        return {"below_max": [float(x) for x in below],
                "argmax": [int(x) for x in best]}


# --------------------------------------------------------------------------
# the load generator (parent process; no jax)


class _Client(threading.Thread):
    """One caller of a closed loop: sends its next request when the last
    one has ended. Streams over HTTP and timestamps every chunk."""

    def __init__(self, port: int, route: str, feed, log: list, stop):
        super().__init__(daemon=True)
        self.port, self.route, self.feed, self.log, self.stop_ev = \
            port, route, feed, log, stop

    def run(self):
        while not self.stop_ev.is_set():
            req = self.feed()
            if req is None:
                return
            self.log.append(one_request(self.port, self.route, req,
                                        time.time(), self.stop_ev))


def one_request(port: int, route: str, req: dict, due: float, stop=None
                ) -> dict:
    rec = {"due": due, "sent": None, "first": None, "last": None,
           "end": None, "tokens": 0, "prompt_tokens": len(req["prompt"]),
           "chunks": [], "ok": False, "error": None, "served": []}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        body = json.dumps(req)
        rec["sent"] = time.time()
        conn.request("POST", route + "?stream=1", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read()[:300]!r}"
            rec["end"] = time.time()
            return rec
        while True:
            line = resp.readline()
            now = time.time()
            if not line:
                rec["error"] = rec["error"] or "stream ended with no last frame"
                break
            item = json.loads(line)
            if item.get("tokens"):
                n = len(item["tokens"])
                rec["first"] = rec["first"] or now
                rec["last"] = now
                rec["tokens"] += n
                rec["chunks"].append((now, n))
                rec["served"].extend(item["tokens"])
            if item.get("error"):
                rec["error"] = str(item["error"])[:300]
            if item.get("done"):
                rec["ok"] = (rec["error"] is None and rec["tokens"]
                             == req["max_new_tokens"])
                if not rec["ok"] and rec["error"] is None:
                    rec["error"] = (f"{rec['tokens']} tokens, asked "
                                    f"{req['max_new_tokens']}")
                break
            if stop is not None and stop.is_set():
                rec["error"] = "cut at the end of the run"
                break
    except Exception as e:   # noqa: BLE001 - a failed request is a datum
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    rec["end"] = time.time()
    return rec


def _delta(after: dict, before: dict, key: str):
    return after.get(key, 0) - before.get(key, 0)


def run(cell: dict, args, ctx: dict) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import compile_cache
    from ray_tpu.core.node import detect_tpu_chips
    from ray_tpu.serve.llm_router import LLMRouter

    from benchmark import latency, model, traffic

    log = ctx["log"]
    eng, mix, chips = cell["serve"], cell["mix"], cell["chips"]
    want_tpu = not cell.get("rehearsal", False)
    if want_tpu and detect_tpu_chips() < chips:
        raise ctx["Refused"](f"this host shows {detect_tpu_chips()} TPU "
                             f"chip(s), the cell needs {chips}")
    if mix["loop"] != "closed":
        raise ctx["Refused"]("only the closed loop is built yet (PERF.md, "
                             "Open questions: the open-loop cells)")
    sizes = model.sizes(cell["config"])
    warm_s = eng["warm_seconds"]
    # enough requests for warm-up and window at any plausible rate
    reqs = traffic.serve_requests(mix, sizes["vocab_size"], args.seed,
                                  mix["population"] * eng["passes"])
    log(f"serve: 1 replica ({eng['engine']}), closed loop of "
        f"{mix['clients']} clients, {len(reqs)} requests ready, prompts "
        f"{min(len(r['prompt']) for r in reqs)}-"
        f"{max(len(r['prompt']) for r in reqs)}, outputs "
        f"{min(r['max_new_tokens'] for r in reqs)}-"
        f"{max(r['max_new_tokens'] for r in reqs)}")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    route = "/llm"
    stop = threading.Event()
    ray_tpu.init(num_cpus=max(os.cpu_count() or 1, 8))
    try:
        port = serve.start()
        t0 = time.time()
        llm = serve.deployment(
            BenchLLMServer, name="llm_server", num_replicas=1,
            ray_actor_options={"num_tpus": 1},
            health_check_timeout_s=600.0).bind(
            bench={"config": cell["config"], "seed": args.seed,
                   "want_tpu": want_tpu}, **eng["engine"])
        router = serve.deployment(
            LLMRouter, name="llm_server_router", num_replicas=1).bind(
            llm, policy="affinity")
        serve.run(router, route_prefix=route)
        replica = serve.get_deployment_handle("llm_server")

        def call(method, *a, timeout=600):
            return ray_tpu.get(replica.method(method).remote(*a),
                               timeout=timeout)

        router_h = serve.get_deployment_handle("llm_server_router")

        def router_stats():
            try:
                return ray_tpu.get(router_h.method("stats").remote(),
                                   timeout=30)
            except Exception as e:   # noqa: BLE001 - a log line, no metric
                return {"error": repr(e)[:200]}

        info = call("device_info")
        log(f"  replica up after {time.time() - t0:.1f} s: {info}")
        if want_tpu and info["platform"] != "tpu":
            raise ctx["Refused"](f"replica on {info['platform']!r}")
        warmed = call("warm_shapes", eng["warm_batches"], eng["warm_lengths"])
        log(f"  prefill shapes warmed: {warmed}")

        # plain reference over one served request, engine otherwise idle
        check_req = {"prompt": reqs[-1]["prompt"][:eng["check_prompt"]],
                     "max_new_tokens": eng["check_tokens"],
                     "temperature": 0.0}
        rec = one_request(port, route, check_req, time.time())
        if not rec["ok"]:
            raise RuntimeError(f"the check request failed: {rec['error']}")
        ref = call("reference_check", check_req["prompt"], rec["served"])
        exact = sum(a == b for a, b in zip(ref["argmax"], rec["served"]))
        log(f"  reference: {exact}/{len(rec['served'])} served tokens are "
            f"the reference argmax; below the maximum by "
            f"{[round(x, 4) for x in ref['below_max']]}")

        # closed loop: runs from here through warm-up and window
        feed_lock, cursor, logs = threading.Lock(), [0], []

        def feed():
            with feed_lock:
                i = cursor[0]
                cursor[0] += 1
            return reqs[i] if i < len(reqs) - 1 else None

        clients = [_Client(port, route, feed, logs, stop)
                   for _ in range(mix["clients"])]
        t_loop = time.time()
        for c in clients:        # no burst of all callers at once
            c.start()
            time.sleep(eng.get("start_gap_s", 0.0))
        time.sleep(max(0.0, t_loop + warm_s - time.time()))
        router0, load0 = router_stats(), os.getloadavg()
        before = call("stats", timeout=60)
        entries0 = compile_cache.entry_count(cache_dir)
        w0 = time.time()
        traced, mean_context = None, None
        if args.trace:
            time.sleep(eng["trace_from"])
            context = [call("trace_start", ctx["trace_dir"], timeout=60)]
            time.sleep(eng["trace_seconds"])
            context.append(call("trace_stop", timeout=120))
            # cached tokens of each busy slot, mean of the two ends
            n = max(1, min(len(c) for c in context))
            mean_context = [sum(sum(c) for c in context) / 2.0 / n] * n
        time.sleep(max(0.0, w0 + args.seconds - time.time()))
        after = call("stats", timeout=60)
        w1 = time.time()
        entries1 = compile_cache.entry_count(cache_dir)
        stop.set()
        router1, load1 = router_stats(), os.getloadavg()
        if args.trace:
            with open(call("trace_reduced", timeout=300)) as f:
                traced = json.load(f)
        peaks = call("peak_bytes", timeout=60)
        for c in clients:
            c.join(timeout=20)
    finally:
        stop.set()
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()

    s = latency.summarize(list(logs), w0, w1)
    window = w1 - w0
    errors = sorted({r["error"] for r in logs if r["error"]
                     and w0 <= (r["end"] or 0) <= w1})
    log(f"  window {window:.2f} s after {warm_s} s of warm-up: "
        f"{s['attempted']} requests ended, {s['failed']} failed {errors[:3]}; "
        f"ttft p50 {s['ttft_p50_ms']} p95 {s['ttft_p95_ms']} ms; tpot p50 "
        f"{s['tpot_p50_ms']} p95 {s['tpot_p95_ms']} ms; {s['serve_tok_s']:.1f} "
        f"tokens/s; {s['attempted'] / window:.2f} requests/s")
    # where a run reads far off, these lines say when and where it stood
    # still: tokens that reached the clients in each 2 s of the window, the
    # longest silence of any one stream, the router's own counts, the
    # host's load average
    buckets = [0] * (int(window // 2) + 1)
    longest = 0.0
    for r in list(logs):
        times = [t for t, _ in r["chunks"]]
        for t, n in r["chunks"]:
            if w0 <= t <= w1:
                buckets[int((t - w0) // 2)] += n
        for a, b in zip([r["sent"]] + times, times):
            if w0 <= b <= w1:
                longest = max(longest, b - a)
    router = {k: (router0.get(k), router1.get(k))
              for k in ("reroutes", "sheds", "requests", "error")
              if k in router0 or k in router1}
    log(f"  tokens per 2 s: {buckets}; longest silence of a stream "
        f"{longest:.2f} s; load average {load0[0]:.1f} -> {load1[0]:.1f}; "
        f"router (before, after) {router}")

    counters = {k: _delta(after, before, k) for k in (
        "requests", "tokens_generated", "admit_s", "decode_block_s",
        "decode_blocks", "ttft_sum", "ttft_count", "prefix_hit_tokens",
        "prefix_hits", "preemptions", "failed", "rejected")}
    counters["compiles_in_window"] = entries1 - entries0
    counters["prompt_tokens_sent"] = sum(
        r["prompt_tokens"] for r in logs if w0 <= r["sent"] <= w1)
    log(f"  engine over the window: {counters}")
    margin = LOGIT_MARGIN[info["dtype"]]
    checks = {
        f"every served token of the check request is the float32 "
        f"reference's argmax up to {margin} logits":
            all(x <= margin for x in ref["below_max"]),
        "no request failed in the window": s["failed"] == 0,
        "requests ended in the window": s["attempted"] > 0,
    }
    if info["platform"] == "tpu":
        checks["the decode block holds the paged Pallas kernel"] = bool(
            info.get("decode_has_pallas_call"))
    structure = (traced or {}).pop("structure", None)
    if structure:
        log("  trace planes and lines: " + "; ".join(
            f"{p} / {ln}: {n}" for p, ln, n in structure))
    peak = max(peaks + [info["plan_bytes"]])
    return {
        "checks": checks, "attempted": s["attempted"], "failed": s["failed"],
        "device": {"platform": info["platform"], "kind": info["kind"],
                   "count": info["count"], "memory_peak_bytes": peak},
        "window_start": w0,
        "end_to_end": {k: s[k] for k in ("serve_tok_s", "ttft_p95_ms",
                                         "tpot_p95_ms")},
        "obs": {"counters": counters,
                "values": {"window_s": window,
                           "client_ttft_mean_s": (s["ttft_mean_ms"] or 0) / 1e3,
                           "mean_context_lens": mean_context,
                           "ttft_p50_ms": s["ttft_p50_ms"],
                           "ttft_p95_ms": s["ttft_p95_ms"],
                           "tpot_p50_ms": s["tpot_p50_ms"],
                           "tpot_p95_ms": s["tpot_p95_ms"]},
                "trace": traced or None, "sizes": sizes, "cell": cell},
    }
