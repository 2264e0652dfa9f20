"""Headline benchmark: llama train-step tokens/sec/chip on the local TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Methodology mirrors the reference's train benchmarks (BASELINE.md:
release/air_tests/air_benchmarks emit time_taken for a fixed workload; the
north-star metric for this framework is Train tokens/sec/chip). The
reference publishes no absolute numbers (BASELINE.json published={}), so
vs_baseline is reported against a reference-class expectation: GPU-era
data-parallel trainers in the reference's ecosystem typically sustain
~30% MFU on a 125M-class causal LM with Adam; vs_baseline =
achieved_MFU / 0.30 (>1.0 beats that envelope on-chip).
"""

from __future__ import annotations

import json
import time

# bf16 peak FLOP/s of one chip, keyed by the device_kind string jax
# reports. Only a string that was seen on a chip goes in: "TPU v5 lite"
# is what jax 0.9.0 calls a v5e (chip_smoke.py, PR 21); its peak is
# Google Cloud's "TPU v5e" documentation, 197 TFLOP/s. A device that is
# not in the table is an error, not a default: a utilization against a
# guessed peak means nothing. Add a generation when it is run on.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def detect_peak(device) -> float:
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py: no peak FLOP/s on record for device_kind "
            f"{device.device_kind!r} (platform {device.platform!r}); the "
            f"train bench measures a TPU — known: {sorted(PEAK_FLOPS)}")


def run_train_bench(preset: str = "debug-125m", batch=None, seq=None,
                    metric_name=None, config_overrides=None,
                    optimizer: str = "adamw"):
    """Measure one model preset's train step on the local chip; returns
    the result dict (shared by bench.py's 2.7B headline and
    release/train_benchmark.py's other presets). Needs a TPU: the
    platform is checked before the first trace and the Pallas kernel is
    looked for in the compiled step, so neither the interpreted kernel
    nor a CPU timing can pass for a chip number."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh
    from ray_tpu.parallel.train_step import (make_train_state_init,
                                             make_train_step)

    dev = jax.devices()[0]
    peak = detect_peak(dev)          # exits unless this is a known TPU
    dt = jnp.bfloat16

    # Pallas flash attention (fwd + FlashAttention-2 bwd kernels).
    # bf16 logits + logsumexp-form CE (models/llama.py loss_fn): the
    # [B, S, 32k] logits tensor is the biggest activation.
    cfg = llama.PRESETS[preset].replace(
        dtype=dt, remat=True, attn_impl="flash", f32_logits=False)
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    B = 8 if batch is None else batch
    S = 1024 if seq is None else seq
    mesh = build_mesh(MeshSpec(dp=-1), devices=jax.devices()[:1])
    rules = ShardingRules.dp()
    if optimizer == "adafactor":
        # the largest-fits single-chip recipe: factored second moment
        # keeps optimizer state ~O(params) instead of 2x params f32
        opt = optax.adafactor(3e-4)
    else:
        opt = optax.adamw(3e-4, weight_decay=0.01)

    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), opt, mesh, rules,
        llama.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(0))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh,
                           rules, state_sh,
                           batch_shapes=jax.eval_shape(lambda: batch))
    step = step.lower(state, batch).compile()
    if cfg.attn_impl == "flash" and "tpu_custom_call" not in step.as_text():
        raise SystemExit("bench.py: attn_impl='flash' but the compiled step "
                         "holds no Pallas call (tpu_custom_call)")

    def run_n(state, n):
        """n steps; the clock stops when the device has finished."""
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = step(state, batch)
        jax.block_until_ready(m)
        return state, time.perf_counter() - t0

    state, _ = run_n(state, 2)       # warm-up
    n = 25
    state, t = run_n(state, n)
    dt_s = t / n

    tokens_per_step = B * S
    tokens_per_sec = tokens_per_step / dt_s

    n_params = llama.num_params(cfg)
    L, D = cfg.n_layers, cfg.d_model
    flops_per_step = 6 * n_params * tokens_per_step \
        + 12 * L * B * S * S * D            # attention fwd+bwd
    mfu = flops_per_step / dt_s / peak
    vs_baseline = mfu / 0.30

    return {
        "metric": metric_name
        or f"llama_{preset}_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 3),
        "extra": {
            "preset": preset,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "batch": B, "seq": S, "steps_timed": n,
            "step_time_s": round(dt_s, 4), "mfu": round(mfu, 4),
            "params": n_params, "dtype": str(dt.__name__),
            # the scoreboard must say what configuration produced the
            # number
            "f32_logits": bool(cfg.f32_logits),
            "param_dtype": jnp.dtype(cfg.param_dtype).name,
            "optimizer": optimizer,
            "remat": bool(cfg.remat),
            "attn_impl": cfg.attn_impl,
        },
    }


def run_collective_bench(world_sizes=(2, 4, 16),
                         payload_mib=(0.0625, 1.0, 8.0, 64.0),
                         backends=("gather", "ring", "hier", "auto"),
                         rounds: int = 5,
                         out_path: str = "BENCH_collective.json"):
    """Sweep host-collective allreduce: payload size x world size x
    backend (ray_tpu.collective). Emits BENCH_collective.json in the
    BENCH_r*.json parsed style; the headline value is the best ring
    bandwidth. Invoked via `python bench.py --bench collective` — slow
    (spawns world_size lane-packed member actors per cell), never part
    of tier-1.

    Per (world, payload) cell the static backends run first, then
    ``auto`` — so the auto-selector's agreement round prices its
    candidates from edge EWMAs the static cells just warmed (the
    measured path, not priors). ``ring_mailbox`` rows re-run ring with
    transport="mailbox" (the legacy inline-chunk transport) at the bulk
    cells, quantifying the zero-copy win. 64 MiB cells are capped at
    world ≤ 4: the gather funnel would combine world×64 MiB per round
    through one process, which measures swap, not transport.
    """
    import numpy as np

    import ray_tpu

    @ray_tpu.remote
    class _BenchMember:
        def __init__(self, rank, world):
            self.rank, self.world = rank, world

        def run(self, backend, group, nbytes, rounds, transport="auto"):
            import time as _t

            import numpy as _np

            from ray_tpu import collective as col

            col.init_collective_group(self.world, self.rank, group,
                                      backend=backend, timeout_s=300,
                                      transport=transport)
            x = _np.ones(max(1, nbytes // 8), dtype=_np.float64)
            col.allreduce(x, group)              # warm the path
            col.reset_transfer_stats(group)
            times = []
            for _ in range(rounds):
                t0 = _t.perf_counter()
                col.allreduce(x, group)
                times.append(_t.perf_counter() - t0)
            gs = col.group_stats(group)
            col.barrier(group)
            chosen = sorted({d["backend"]
                             for k, d in gs["decisions"].items()
                             if k.startswith("allreduce")})
            return {"median_s": sorted(times)[len(times) // 2],
                    "bytes_sent": gs["transfer"]["bytes_sent"] / rounds,
                    "zc_sends": gs["transfer"]["zc_sends"],
                    "chosen": chosen}

    def _run_cell(backend, world, mib, transport, label):
        nbytes = int(mib * (1 << 20))
        group = f"bench_{label}_{world}_{nbytes}"
        members = [_BenchMember.options(num_cpus=0.25).remote(i, world)
                   for i in range(world)]
        r = rounds if mib < 64 else max(3, rounds - 2)
        cell = {"backend": label, "world": world, "payload_mib": mib,
                "transport": transport}
        try:
            outs = ray_tpu.get(
                [m.run.remote(backend, group, nbytes, r, transport)
                 for m in members], timeout=900)
            med = max(o["median_s"] for o in outs)
            cell.update({
                "median_s": round(med, 6),
                "mib_per_s": round(mib / max(med, 1e-9), 2),
                "bytes_sent_per_rank": max(o["bytes_sent"] for o in outs),
                "zero_copy": any(o["zc_sends"] > 0 for o in outs),
            })
            if backend == "auto":
                cell["chosen"] = outs[0]["chosen"]
        except Exception as e:  # noqa: BLE001 — sweep must finish
            cell["error"] = str(e)[:200]
        finally:
            from ray_tpu import collective as col

            try:
                col.destroy_collective_group(group)
            except Exception:
                pass
            for m in members:
                try:
                    ray_tpu.kill(m)
                except Exception:
                    pass
        return cell

    # Explicit CPU budget: auto-detection on a 1-core box would admit a
    # single 1.0-CPU slot and the member actors could never all schedule.
    ray_tpu.init(num_cpus=max(8, max(world_sizes) + 2),
                 ignore_reinit_error=True)
    sweep = []
    for world in world_sizes:
        for mib in payload_mib:
            if mib >= 64 and world > 4:
                continue
            # static backends first, "auto" last: its selection round
            # then prices candidates from freshly-warmed edge EWMAs
            for backend in backends:
                sweep.append(_run_cell(backend, world, mib, "auto", backend))
            if mib >= 1 and world <= 4:
                # legacy-transport comparison rows (the zero-copy claim)
                sweep.append(_run_cell("ring", world, mib, "mailbox",
                                       "ring_mailbox"))

    def _cells(**kv):
        return [c for c in sweep if "mib_per_s" in c
                and all(c.get(k) == v for k, v in kv.items())]

    # auto-vs-best-static and zero-copy-vs-mailbox acceptance summaries
    auto_checks, zc_speedups = [], {}
    for world in world_sizes:
        for mib in payload_mib:
            statics = [c for c in _cells(world=world, payload_mib=mib)
                       if c["backend"] in ("gather", "ring", "hier")]
            auto = _cells(world=world, payload_mib=mib, backend="auto")
            if statics and auto:
                best = max(c["mib_per_s"] for c in statics)
                got = auto[0]["mib_per_s"]
                auto_checks.append({
                    "world": world, "payload_mib": mib,
                    "auto_mib_per_s": got, "best_static_mib_per_s": best,
                    "auto_within_15pct": bool(got >= 0.85 * best),
                    "chosen": auto[0].get("chosen")})
            mb = _cells(world=world, payload_mib=mib, backend="ring_mailbox")
            zc = _cells(world=world, payload_mib=mib, backend="ring")
            if mb and zc:
                zc_speedups[f"w{world}_{mib}mib"] = round(
                    zc[0]["mib_per_s"] / max(mb[0]["mib_per_s"], 1e-9), 2)

    ring_bw = [c["mib_per_s"] for c in sweep
               if c.get("backend") == "ring" and "mib_per_s" in c]
    result = {
        "metric": "collective_allreduce_ring_best_mib_per_s",
        "value": max(ring_bw) if ring_bw else 0.0,
        "unit": "MiB/s",
        "vs_baseline": None,
        "extra": {"sweep": sweep, "rounds": rounds,
                  "auto_vs_best_static": auto_checks,
                  "zerocopy_vs_mailbox_ring_speedup": zc_speedups,
                  "note": "host allreduce bandwidth per backend; "
                          "bytes_sent_per_rank shows ring's 2(N-1)/N "
                          "vs gather's full-payload fan-in; ring_mailbox "
                          "rows force the legacy inline transport"},
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def run_data_bench(stage_counts=(1, 2, 3), block_rows=(4096, 65536),
                   budgets_blocks=(2, 8), num_blocks: int = 16,
                   out_path: str = "BENCH_data.json"):
    """Sweep the data streaming executor vs the legacy fused path:
    pipeline depth x block size x per-op budget. Each cell runs an
    identical map chain (scale + add per stage) both ways and records
    throughput plus the executor's peak unconsumed-output bytes (the
    thing the budget bounds; the fused path has no per-op number, its
    admission window is global). Emits BENCH_data.json in the parsed
    style; headline = streaming/fused throughput ratio at the deepest
    pipeline. Single-core runnable; invoked via
    `python bench.py --bench data`."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.data.execution import get_context, get_last_execution_stats

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    ctx = get_context()
    saved = (ctx.per_op_budget_bytes, ctx.execution_policy)

    def build(rows, stages):
        blocks = [{"x": np.arange(rows, dtype=np.float64) + i * rows}
                  for i in range(num_blocks)]
        ds = rd.Dataset([ray_tpu.put(b) for b in blocks], [])
        for s in range(stages):
            ds = ds.map_batches(
                lambda b, s=s: {"x": b["x"] * 1.0001 + s})
        return ds

    sweep = []
    try:
        for stages in stage_counts:
            for rows in block_rows:
                block_bytes = rows * 8
                total_rows = rows * num_blocks
                for bblocks in budgets_blocks:
                    ctx.per_op_budget_bytes = bblocks * block_bytes
                    cell = {"stages": stages, "block_rows": rows,
                            "budget_blocks": bblocks}
                    for policy in ("fused", "streaming"):
                        try:
                            ds = build(rows, stages)
                            t0 = time.perf_counter()
                            n = sum(len(b["x"]) for b in
                                    ds._iter_blocks(policy=policy))
                            dt = time.perf_counter() - t0
                            assert n == total_rows, (n, total_rows)
                            cell[f"{policy}_rows_per_s"] = round(n / dt)
                            if policy == "streaming":
                                st = get_last_execution_stats()
                                cell["peak_queued_bytes"] = \
                                    st["peak_queued_bytes"]
                                cell["budget_bytes"] = \
                                    st["per_op_budget_bytes"]
                        except Exception as e:  # noqa: BLE001 — finish sweep
                            cell[f"{policy}_error"] = str(e)[:200]
                    sweep.append(cell)
    finally:
        ctx.per_op_budget_bytes, ctx.execution_policy = saved

    deep = [c for c in sweep if c["stages"] == max(stage_counts)
            and "streaming_rows_per_s" in c and "fused_rows_per_s" in c]
    ratio = (max(c["streaming_rows_per_s"] / max(c["fused_rows_per_s"], 1)
                 for c in deep) if deep else 0.0)
    result = {
        "metric": "data_streaming_vs_fused_throughput_ratio",
        "value": round(ratio, 3),
        "unit": "x (deepest pipeline, best cell)",
        "vs_baseline": None,
        "extra": {"sweep": sweep, "num_blocks": num_blocks,
                  "note": "peak_queued_bytes vs budget_bytes shows the "
                          "ResourceManager holding unconsumed operator "
                          "output under the per-op budget; fused has one "
                          "global admission window instead"},
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def run_serve_router_bench(concurrencies=(64, 256), replica_counts=(1, 2, 4),
                           policies=("affinity", "random"),
                           requests_per_conc: int = 2,
                           out_path: str = "BENCH_serve_router.json"):
    """LLM router sweep: concurrency x replicas x routing policy over
    SimLLMServer replicas (deterministic asyncio engines honoring the
    LLMServer streaming/stats/prefix-cache contract — llm_deployment.py).
    Measured per cell: sustained req/s, aggregate tok/s, client-observed
    TTFT p50/p99, and prefix-cache hit rate from the replicas' own
    counters. The workload is 32 prefix groups x 3 shared pages against
    a 64-page per-replica cache: the groups' combined working set (96
    pages) thrashes ONE replica's cache but fits when affinity
    partitions it across >=2 — the regime prefix-aware routing exists
    for. Writes BENCH_serve_router.json; headline is the affinity/random
    TTFT-p99 improvement at the largest cell."""
    import queue as _q
    import random as _rnd
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_deployment import build_llm_app

    # tail is 15 tokens — a PARTIAL page, so per-request uniqueness
    # never registers junk pages that would evict the shared prefixes
    GROUPS, PREFIX_TOK, TAIL_TOK, MAX_NEW = 32, 48, 15, 8

    def run_cell(concurrency, replicas, policy, compiled_hop=None,
                 warm=False):
        rkw = {"max_inflight": 100_000, "stats_interval_s": 0.25,
               "prefix_tokens": PREFIX_TOK}
        if compiled_hop is not None:
            rkw["compiled_hop"] = compiled_hop
        app = build_llm_app(
            use_sim=True, num_replicas=replicas, router_policy=policy,
            router_kwargs=rkw,
            max_slots=4, max_queue_depth=None,
            prefill_s_per_token=0.001, decode_s_per_token=0.004,
            tokens_per_frame=4, prefix_cache_pages=64)
        handle = serve.run(app)
        rng = _rnd.Random(0)
        n_requests = concurrency * requests_per_conc
        work: "_q.Queue" = _q.Queue()
        for i in range(n_requests):
            g = rng.randrange(GROUPS)
            prompt = [g] * PREFIX_TOK + [10_000 + i] * TAIL_TOK
            work.put({"prompt": prompt, "max_new_tokens": MAX_NEW})
        ttfts, lock = [], threading.Lock()
        tokens = [0]

        def worker():
            while True:
                try:
                    body = work.get_nowait()
                except _q.Empty:
                    return
                t0 = time.time()
                first = None
                got = 0
                gen = handle.options(stream=True).method(
                    "stream_request").remote(body)
                for ref in gen:
                    item = ray_tpu.get(ref)
                    if item.get("tokens") and first is None:
                        first = time.time() - t0
                    got += len(item.get("tokens", []))
                with lock:
                    if first is not None:
                        ttfts.append(first)
                    tokens[0] += got

        # warm the routing tables/handles before timing
        ray_tpu.get(handle.method("stats").remote())
        if warm:
            # touch every replica's stream path once so one-time costs
            # (standing-channel negotiation for the compiled hop,
            # engine spin-up) don't ride the timed TTFT
            for g in range(0, GROUPS, 4):
                gen = handle.options(stream=True).method(
                    "stream_request").remote(
                        {"prompt": [g] * PREFIX_TOK + [99_000 + g],
                         "max_new_tokens": 1})
                for ref in gen:
                    ray_tpu.get(ref)
        threads = [threading.Thread(target=worker)
                   for _ in range(concurrency)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
        controller = ray_tpu.get_actor("_serve_controller",
                                       namespace="serve")
        reps = ray_tpu.get(controller.get_replicas.remote("llm_server"))
        stats = ray_tpu.get([r.handle_request.remote("stats", (), {}, None)
                             for r in reps])
        rstats = ray_tpu.get(handle.method("stats").remote())
        serve.shutdown()
        hit_tokens = sum(s["prefix_hit_tokens"] for s in stats)
        served = sum(s["requests"] for s in stats)
        # shareable prefix tokens per request = the 3 full prefix pages
        shareable = served * PREFIX_TOK
        ttfts.sort()

        def pct(p):
            return ttfts[min(int(p * len(ttfts)), len(ttfts) - 1)] \
                if ttfts else None

        return {
            "concurrency": concurrency, "replicas": replicas,
            "policy": policy, "n_requests": n_requests,
            "req_per_s": round(n_requests / wall, 2),
            "tok_per_s": round(tokens[0] / wall, 1),
            "ttft_p50_s": round(pct(0.50), 4) if ttfts else None,
            "ttft_p99_s": round(pct(0.99), 4) if ttfts else None,
            "prefix_hit_rate": round(hit_tokens / max(shareable, 1), 4),
            "affinity_picks": rstats.get("affinity_picks", 0),
            "reroutes": rstats.get("reroutes", 0),
            "compiled_streams": rstats.get("compiled_streams", 0),
            "legacy_streams": rstats.get("legacy_streams", 0),
        }

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    sweep = []
    for concurrency in concurrencies:
        for replicas in replica_counts:
            for policy in policies:
                cell = run_cell(concurrency, replicas, policy)
                sweep.append(cell)
                print(json.dumps(cell))
    # compiled router->replica hop on vs off at one fixed cell: the
    # stream-frame path over a standing channel vs the legacy per-frame
    # handle_request_streaming dispatch. Measured UNSATURATED (clients
    # fit in the replicas' slots) so TTFT reflects per-frame hop cost,
    # not queue wait — at saturation the delta drowns in queueing.
    hop_cells = []
    for hop in (True, False):
        cell = run_cell(min(min(concurrencies), 8), 2, "affinity",
                        compiled_hop=hop, warm=True)
        cell["compiled_hop"] = hop
        hop_cells.append(cell)
        print(json.dumps(cell))
    ray_tpu.shutdown()

    def find(c, r, p):
        for cell in sweep:
            if (cell["concurrency"], cell["replicas"],
                    cell["policy"]) == (c, r, p):
                return cell
        return None

    cmax = max(concurrencies)
    headline, scaling = None, {}
    aff2, rnd2 = find(cmax, 2, "affinity"), find(cmax, 2, "random")
    if aff2 and rnd2 and aff2["ttft_p99_s"]:
        headline = round(rnd2["ttft_p99_s"] / aff2["ttft_p99_s"], 2)
    for pol in policies:
        one, two = find(cmax, 1, pol), find(cmax, 2, pol)
        if one and two:
            scaling[pol] = round(two["tok_per_s"]
                                 / max(one["tok_per_s"], 1e-9), 2)
    hop_on = next((c for c in hop_cells if c.get("compiled_hop")), None)
    hop_off = next((c for c in hop_cells
                    if c.get("compiled_hop") is False), None)
    hop_delta = None
    if (hop_on and hop_off and hop_on.get("ttft_p50_s")
            and hop_off.get("ttft_p50_s")):
        hop_delta = round(hop_off["ttft_p50_s"] - hop_on["ttft_p50_s"], 4)
    result = {
        "metric": "serve_router_ttft_p99_affinity_speedup_vs_random",
        "value": headline or 0.0,
        "unit": "x",
        "vs_baseline": None,
        "extra": {"sweep": sweep,
                  "tok_per_s_scaling_1_to_2_replicas": scaling,
                  "compiled_hop_ttft": {
                      "cells": hop_cells,
                      "ttft_p50_delta_s_legacy_minus_compiled": hop_delta},
                  "note": "prefix-affinity vs random routing over "
                          "SimLLMServer replicas; hit rate = prefix "
                          "tokens served from cache / shareable prefix "
                          "tokens; TTFT measured client-side under "
                          "saturation (queue wait included)"},
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def run_serve_disagg_bench(concurrency: int = 48, n_long: int = 48,
                           n_short: int = 144, prefill_replicas: int = 2,
                           decode_replicas: int = 2, repeats: int = 3,
                           out_path: str = "BENCH_serve_disagg.json",
                           init_cluster: bool = True):
    """Disaggregated (prefill pool + decode pool, serve/disagg.py) vs
    monolithic serving at MATCHED replica budget under mixed traffic:
    long prompts (shared 128-token prefix + unique 384-token tail,
    prefill-bound) and short chats (24-token prompt, 32 new tokens,
    decode-bound). The sim models DistServe's co-location contention —
    a prefill sharing the engine inflates co-scheduled decode steps
    (colocation_interference) — which a single-phase replica never pays.

    Measured per cell: per-class + overall client TTFT p50/p99 and
    aggregate tok/s. Disagg-only: cluster-global shared-prefix hit rate
    from the replicas' own counters (vs the replica-local 0.61 baseline
    in BENCH_serve_router.json), and transfer accounting — exporter puts
    across the prefill pool must equal the number of DISTINCT page
    groups, proving each group's bytes cross the store exactly once
    (shared prefixes ride refs, never re-puts). Writes
    BENCH_serve_disagg.json; headline is the short-chat (decode-class)
    TTFT-p99 improvement."""
    import queue as _q
    import random as _rnd
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_deployment import build_llm_app

    PAGE, GROUP = 16, 4
    GTOK = PAGE * GROUP
    N_PREFIX, PREFIX_TOK = 8, 2 * GTOK          # 2 page groups each
    LONG_TAIL = 6 * GTOK                        # 6 unique groups / long
    SHORT_LEN, LONG_NEW, SHORT_NEW = 24, 16, 32
    total_replicas = prefill_replicas + decode_replicas
    sim_kw = dict(max_slots=4, max_queue_depth=None,
                  prefill_s_per_token=0.001, decode_s_per_token=0.004,
                  tokens_per_frame=4, prefix_cache_pages=1024,
                  retained_groups=1024, colocation_interference=2.0)

    def _prefix(g):
        return [g * 1000 + j for j in range(PREFIX_TOK)]

    def _bodies():
        rng = _rnd.Random(0)
        longs = [{"prompt": _prefix(rng.randrange(N_PREFIX))
                  + [500_000 + i * 1000 + j for j in range(LONG_TAIL)],
                  "max_new_tokens": LONG_NEW}
                 for i in range(n_long)]
        shorts = [{"prompt": [900_000 + i * 100 + j
                              for j in range(SHORT_LEN)],
                   "max_new_tokens": SHORT_NEW}
                  for i in range(n_short)]
        mixed = [("long", b) for b in longs] + \
            [("short", b) for b in shorts]
        rng.shuffle(mixed)
        return mixed

    def _pool_stats(name):
        controller = ray_tpu.get_actor("_serve_controller",
                                       namespace="serve")
        reps = ray_tpu.get(controller.get_replicas.remote(name))
        return ray_tpu.get([r.handle_request.remote("stats", (), {}, None)
                            for r in reps])

    def _sum(stats, key):
        return sum(s.get(key, 0) for s in stats)

    def run_cell(disaggregated):
        name = "dz" if disaggregated else "mono"
        if disaggregated:
            app = build_llm_app(name=name, use_sim=True,
                                disaggregated=True,
                                prefill_replicas=prefill_replicas,
                                decode_replicas=decode_replicas,
                                router_kwargs={"max_inflight": 100_000,
                                               "stats_interval_s": 0.25},
                                **sim_kw)
            pools = (f"{name}_prefill", f"{name}_decode")
        else:
            app = build_llm_app(name=name, use_sim=True,
                                num_replicas=total_replicas,
                                router_kwargs={"max_inflight": 100_000,
                                               "stats_interval_s": 0.25},
                                **sim_kw)
            pools = (name,)
        handle = serve.run(app)
        # warm: register every shared prefix ONCE (replica page caches,
        # exporter retained maps, global directory) so the timed phase
        # measures steady-state reuse, not first-touch fills
        for g in range(N_PREFIX):
            gen = handle.options(stream=True).method(
                "stream_request").remote(
                    {"prompt": _prefix(g), "max_new_tokens": 4})
            for ref in gen:
                ray_tpu.get(ref)
        base = {p: _pool_stats(p) for p in pools}
        work: "_q.Queue" = _q.Queue()
        for item in _bodies():
            work.put(item)
        lock = threading.Lock()
        ttfts = {"long": [], "short": []}
        tokens = [0]

        def worker():
            while True:
                try:
                    cls, body = work.get_nowait()
                except _q.Empty:
                    return
                t0 = time.time()
                first, got = None, 0
                gen = handle.options(stream=True).method(
                    "stream_request").remote(body)
                for ref in gen:
                    item = ray_tpu.get(ref)
                    if item.get("tokens") and first is None:
                        first = time.time() - t0
                    got += len(item.get("tokens", []))
                with lock:
                    if first is not None:
                        ttfts[cls].append(first)
                    tokens[0] += got

        threads = [threading.Thread(target=worker)
                   for _ in range(concurrency)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
        after = {p: _pool_stats(p) for p in pools}
        rstats = ray_tpu.get(handle.method("stats").remote())
        serve.shutdown()

        def delta(pool, key):
            return _sum(after[pool], key) - _sum(base[pool], key)

        def pct(xs, p):
            xs = sorted(xs)
            return round(xs[min(int(p * len(xs)), len(xs) - 1)], 4) \
                if xs else None

        cell = {
            "mode": "disaggregated" if disaggregated else "monolithic",
            "replicas": total_replicas,
            "n_requests": n_long + n_short,
            "req_per_s": round((n_long + n_short) / wall, 2),
            "tok_per_s": round(tokens[0] / wall, 1),
            "ttft_p50_s": {c: pct(ttfts[c], 0.50) for c in ttfts},
            "ttft_p99_s": {c: pct(ttfts[c], 0.99) for c in ttfts},
            "interference_stall_s": round(
                sum(delta(p, "interference_stall_s") for p in pools), 3),
        }
        shareable = n_long * PREFIX_TOK
        if disaggregated:
            pf = f"{name}_prefill"
            local = delta(pf, "prefix_hit_tokens")
            glob = delta(pf, "global_prefix_hit_tokens")
            # every long's shared prefix should be warm SOMEWHERE in the
            # cluster after the warm phase — local page cache or global
            # directory, whichever replica the request landed on
            cell["shared_prefix_hit_rate"] = round(
                min(local + glob, shareable) / max(shareable, 1), 4)
            cell["global_hit_tokens"] = glob
            cell["local_hit_tokens"] = local
            # transfer accounting: the timed phase may put ONLY the
            # n_long unique tail groups — every shared-prefix group was
            # exported during warm and rides refs afterwards
            cell["handoff_puts_timed"] = delta(pf, "handoff_puts")
            cell["handoff_puts_total"] = _sum(after[pf], "handoff_puts")
            cell["distinct_groups"] = (N_PREFIX * (PREFIX_TOK // GTOK)
                                       + n_long * (LONG_TAIL // GTOK))
            cell["handoff_reused_groups"] = _sum(after[pf],
                                                 "handoff_reused_groups")
            cell["handoff_put_bytes"] = _sum(after[pf],
                                             "handoff_put_bytes")
            cell["adopted_bytes"] = _sum(after[f"{name}_decode"],
                                         "adopt_adopted_bytes")
            cell["handoffs"] = rstats.get("handoffs", 0)
            cell["handoffs_lost"] = rstats.get("handoffs_lost", 0)
        else:
            cell["shared_prefix_hit_rate"] = round(
                min(delta(name, "prefix_hit_tokens"), shareable)
                / max(shareable, 1), 4)
        cell["_ttfts"], cell["_wall"], cell["_tokens"] = \
            ttfts, wall, tokens[0]
        return cell

    def _merge(runs):
        """Pool repeats: p50/p99 over ALL samples (a 3x sample pool
        tames single-run p99 jitter), throughput over summed wall."""
        n = len(runs)
        out = {k: v for k, v in runs[0].items() if not k.startswith("_")}
        pooled = {c: sorted(sum((r["_ttfts"][c] for r in runs), []))
                  for c in ("long", "short")}
        wall = sum(r["_wall"] for r in runs)

        def pct(xs, p):
            return round(xs[min(int(p * len(xs)), len(xs) - 1)], 4) \
                if xs else None

        out["runs"] = n
        out["n_requests"] = n * (n_long + n_short)
        out["req_per_s"] = round(out["n_requests"] / wall, 2)
        out["tok_per_s"] = round(sum(r["_tokens"] for r in runs) / wall, 1)
        out["ttft_p50_s"] = {c: pct(pooled[c], 0.50) for c in pooled}
        out["ttft_p99_s"] = {c: pct(pooled[c], 0.99) for c in pooled}
        for k in ("interference_stall_s", "global_hit_tokens",
                  "local_hit_tokens", "handoff_puts_timed",
                  "handoff_puts_total", "handoff_reused_groups",
                  "handoff_put_bytes", "adopted_bytes", "handoffs",
                  "handoffs_lost"):
            if k in runs[0]:
                out[k] = round(sum(r[k] for r in runs), 3)
        if "shared_prefix_hit_rate" in runs[0]:
            out["shared_prefix_hit_rate"] = round(
                sum(r["shared_prefix_hit_rate"] for r in runs) / n, 4)
        if "handoff_puts_total" in runs[0]:
            # the directory + store OUTLIVE redeploys: repeat runs adopt
            # run 1's groups by ref and put zero new bytes, so the
            # exactly-once claim is cluster-lifetime — cumulative puts
            # across every run equals the distinct group count once
            out["distinct_groups"] = runs[0]["distinct_groups"]
            out["exactly_once_cluster_lifetime"] = (
                out["handoff_puts_total"] == out["distinct_groups"])
        return out

    if init_cluster:
        ray_tpu.init(num_cpus=max(16, total_replicas + 4),
                     ignore_reinit_error=True)
    mono_runs, dz_runs = [], []
    for _ in range(max(repeats, 1)):   # interleave: load drift hits both
        mono_runs.append(run_cell(False))
        dz_runs.append(run_cell(True))
    mono, dz = _merge(mono_runs), _merge(dz_runs)
    print(json.dumps(mono))
    print(json.dumps(dz))
    if init_cluster:
        ray_tpu.shutdown()

    def p99(cell, cls):
        v = cell["ttft_p99_s"].get(cls)
        return v if v is not None else float("inf")

    headline = round(p99(mono, "short") / max(p99(dz, "short"), 1e-9), 2)
    tok_ratio = round(dz["tok_per_s"] / max(mono["tok_per_s"], 1e-9), 3)
    exactly_once = bool(dz.get("exactly_once_cluster_lifetime"))
    acceptance = {
        "disagg_beats_mono_decode_ttft_p99": headline > 1.0,
        "tok_per_s_within_10pct": tok_ratio >= 0.9,
        "global_hit_rate_above_local_0_61_baseline":
            dz.get("shared_prefix_hit_rate", 0) > 0.61,
        "page_bytes_cross_store_exactly_once": exactly_once,
    }
    result = {
        "metric": "serve_disagg_short_ttft_p99_speedup_vs_monolithic",
        "value": headline,
        "unit": "x",
        "vs_baseline": None,
        "extra": {
            "monolithic": mono,
            "disaggregated": dz,
            "tok_per_s_ratio_disagg_vs_mono": tok_ratio,
            "replica_local_hit_rate_baseline": 0.61,
            "acceptance": acceptance,
            "note": "matched replica budget "
                    f"({total_replicas} monolithic vs {prefill_replicas}"
                    f"+{decode_replicas} disagg); mixed traffic = "
                    f"{n_long} long (shared {PREFIX_TOK}-token prefix + "
                    f"{LONG_TAIL}-token unique tail) + {n_short} short "
                    "chats; TTFT client-side under saturation; hit rate "
                    "= shared-prefix tokens served warm (local cache OR "
                    "global directory) / shareable; transfer accounting "
                    "= exporter puts == distinct page groups",
        },
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def run_serve_multiplex_bench(n_models: int = 8, n_tenants: int = 4,
                              num_replicas: int = 3,
                              concurrency: int = 12,
                              requests_per_phase: int = 160,
                              flood_concurrency: int = 8,
                              max_models_per_replica: int = 4,
                              repeats: int = 1,
                              out_path: str = "BENCH_serve_multiplex.json",
                              init_cluster: bool = True,
                              autoscale_phase: bool = True):
    """Fleet-scale model multiplexing under a SKEWED multi-model,
    multi-tenant workload (zipf-ish popularity over n_models, tenants
    round-robin). Three measurements:

    1. warm-model hit rate, model-affinity vs random placement at a
       matched replica budget. Each replica's LRU holds
       max_models_per_replica < n_models, so random placement THRASHES
       (every replica keeps cold-loading the whole catalog) while the
       (model, prefix) rendezvous key partitions the catalog so each
       replica's working set fits. hit rate = 1 - cold_loads/requests,
       from the replicas' own load counters. A single-model cell at the
       same budget gives the no-multiplexing tok/s baseline.
    2. weighted-fair admission: per-tenant client TTFT p99 uncontended,
       then with one tenant flooding. Acceptance: compliant tenants'
       p99 stays within 1.5x of uncontended and the flooder absorbs
       every shed (typed 429s, per-tenant counters).
    3. per-model autoscaling: sustained demand on one model; the
       controller's decision table must grow its serving set toward
       load/target (sampled timeline recorded).

    Writes BENCH_serve_multiplex.json; headline is the affinity cell's
    warm-model hit rate."""
    import queue as _q
    import random as _rnd
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_deployment import build_llm_app

    tenants = [f"tenant-{i}" for i in range(n_tenants)]
    model_w = [1.0 / (i + 1) for i in range(n_models)]   # zipf-ish skew

    def pct(xs, p):
        xs = sorted(xs)
        return round(xs[min(int(p * len(xs)), len(xs) - 1)], 4) \
            if xs else None

    def _bodies(seed):
        rng = _rnd.Random(seed)
        out = []
        for i in range(requests_per_phase):
            m = rng.choices(range(n_models), weights=model_w)[0]
            out.append({"prompt": [m * 1000 + j for j in range(16)]
                        + [777_000 + i],
                        "max_new_tokens": 16,
                        "model": f"model-{m}",
                        "tenant": tenants[i % n_tenants]})
        return out

    def _pool_stats(name):
        controller = ray_tpu.get_actor("_serve_controller",
                                       namespace="serve")
        reps = ray_tpu.get(controller.get_replicas.remote(name))
        return ray_tpu.get([r.handle_request.remote("stats", (), {}, None)
                            for r in reps])

    def _drive(handle, bodies, n_workers):
        """Run bodies at fixed concurrency; returns per-tenant TTFTs,
        token count and wall."""
        work: "_q.Queue" = _q.Queue()
        for b in bodies:
            work.put(b)
        lock = threading.Lock()
        ttfts: dict = {}
        tokens = [0]
        sheds = [0]

        def worker():
            while True:
                try:
                    body = work.get_nowait()
                except _q.Empty:
                    return
                t0 = time.time()
                first, got, shed = None, 0, False
                gen = handle.options(stream=True).method(
                    "stream_request").remote(body)
                for ref in gen:
                    item = ray_tpu.get(ref)
                    if item.get("status") == 429:
                        shed = True
                    if item.get("tokens") and first is None:
                        first = time.time() - t0
                    got += len(item.get("tokens", []))
                with lock:
                    if shed:
                        sheds[0] += 1
                    elif first is not None:
                        ttfts.setdefault(body.get("tenant", "default"),
                                         []).append(first)
                    tokens[0] += got

        threads = [threading.Thread(target=worker)
                   for _ in range(n_workers)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return ttfts, tokens[0], time.time() - t0, sheds[0]

    sim_kw = dict(max_slots=8, max_queue_depth=None,
                  decode_s_per_token=0.002, model_load_s=0.08,
                  multiplexed=True, max_models=max_models_per_replica)

    def run_hit_cell(policy):
        app = build_llm_app(
            name="mx", use_sim=True, num_replicas=num_replicas,
            router_policy=policy,
            router_kwargs={"max_inflight": 100_000,
                           "stats_interval_s": 0.25},
            **sim_kw)
        handle = serve.run(app)
        ttfts, toks, wall, _ = [], 0, 0.0, 0
        agg = {}
        for rep in range(max(repeats, 1)):
            tt, tk, w, _ = _drive(handle, _bodies(rep), concurrency)
            for t, xs in tt.items():
                agg.setdefault(t, []).extend(xs)
            toks += tk
            wall += w
        stats = _pool_stats("mx")
        reqs = sum(s["requests"] for s in stats)
        loads = sum(s["model_loads"] for s in stats)
        evics = sum(s["model_evictions"] for s in stats)
        rstats = ray_tpu.get(handle.method("stats").remote())
        serve.shutdown()
        return {
            "policy": policy,
            "n_requests": reqs,
            "tok_per_s": round(toks / wall, 1),
            "cold_loads": loads,
            "evictions": evics,
            "warm_hit_rate": round(1.0 - loads / max(reqs, 1), 4),
            "ttft_p99_s_per_tenant": {t: pct(xs, 0.99)
                                      for t, xs in sorted(agg.items())},
            "warm_model_picks": rstats.get("warm_model_picks", 0),
            "cold_model_picks": rstats.get("cold_model_picks", 0),
        }

    def run_single_model_cell():
        """No multiplexing, one model: the tok/s baseline the multi-model
        cells are compared against at the same replica budget."""
        kw = dict(sim_kw)
        kw["multiplexed"] = False
        app = build_llm_app(
            name="mono", use_sim=True, num_replicas=num_replicas,
            router_policy="affinity",
            router_kwargs={"max_inflight": 100_000,
                           "stats_interval_s": 0.25}, **kw)
        handle = serve.run(app)
        bodies = [{"prompt": b["prompt"],
                   "max_new_tokens": b["max_new_tokens"]}
                  for b in _bodies(0)]
        _, toks, wall, _ = _drive(handle, bodies, concurrency)
        serve.shutdown()
        return {"tok_per_s": round(toks / wall, 1),
                "n_requests": len(bodies)}

    def run_fairness():
        """Uncontended per-tenant p99, then one tenant floods."""
        # admission bound sized so the flood ALONE can saturate it —
        # compliant tenants stay inside their guaranteed shares while
        # the flooder's borrow attempts past the cap eat the 429s
        app = build_llm_app(
            name="fair", use_sim=True, num_replicas=num_replicas,
            router_policy="p2c",
            router_kwargs={"max_inflight": max(4, flood_concurrency),
                           "stats_interval_s": 0.25},
            tenant_weights={t: 1.0 for t in tenants},
            max_slots=4 * concurrency, max_queue_depth=None,
            decode_s_per_token=0.004, multiplexed=False)
        handle = serve.run(app)
        compliant = tenants[1:]
        flood = tenants[0]

        def tenant_bodies(ts, n):
            return [{"prompt": [4] * 12, "max_new_tokens": 16,
                     "tenant": ts[i % len(ts)]} for i in range(n)]

        # phase A: everyone compliant, light concurrency
        tt_a, _, _, sheds_a = _drive(
            handle, tenant_bodies(tenants, requests_per_phase),
            len(tenants))
        p99_a = {t: pct(xs, 0.99) for t, xs in sorted(tt_a.items())}
        # phase B: flood tenant hammers with flood_concurrency loopers
        # while the compliant tenants repeat phase A's pattern
        stop = threading.Event()

        def flooder():
            while not stop.is_set():
                gen = handle.options(stream=True).method(
                    "stream_request").remote(
                        {"prompt": [6] * 12, "max_new_tokens": 48,
                         "tenant": flood})
                for ref in gen:
                    ray_tpu.get(ref)

        fthreads = [threading.Thread(target=flooder)
                    for _ in range(flood_concurrency)]
        for t in fthreads:
            t.start()
        try:
            time.sleep(0.5)   # let the flood reach the admission bound
            tt_b, _, _, _ = _drive(
                handle, tenant_bodies(compliant, requests_per_phase),
                len(compliant))
        finally:
            stop.set()
            for t in fthreads:
                t.join(timeout=60)
        p99_b = {t: pct(xs, 0.99) for t, xs in sorted(tt_b.items())}
        rstats = ray_tpu.get(handle.method("stats").remote())
        ts_stats = rstats["tenant_stats"]
        serve.shutdown()
        ratios = [p99_b[t] / max(p99_a[t], 1e-9)
                  for t in compliant if p99_a.get(t) and p99_b.get(t)]
        return {
            "uncontended_p99_s": p99_a,
            "contended_p99_s": p99_b,
            "uncontended_sheds": sheds_a,
            "compliant_p99_ratio_max": round(max(ratios), 3)
            if ratios else None,
            "flood_tenant": flood,
            "sheds_per_tenant": {t: int(v.get("shed", 0))
                                 for t, v in sorted(ts_stats.items())},
            "admits_per_tenant": {t: int(v.get("requests", 0))
                                  for t, v in sorted(ts_stats.items())},
        }

    def run_autoscale():
        """Pump one model, sample the controller's per-model table."""
        app = build_llm_app(
            name="scale", use_sim=True, num_replicas=num_replicas,
            router_policy="affinity",
            model_autoscaling_config={
                "target_load_per_model_replica": 1.0,
                "look_back_period_s": 1.0, "upscale_delay_s": 0.0,
                "downscale_delay_s": 120.0},
            router_kwargs={"stats_interval_s": 0.25},
            multiplexed=True, max_slots=2, decode_s_per_token=0.02,
            model_load_s=0.02, max_queue_depth=None)
        handle = serve.run(app)
        controller = ray_tpu.get_actor("_serve_controller",
                                       namespace="serve")
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                gen = handle.options(stream=True).method(
                    "stream_request").remote(
                        {"prompt": [5] * 8, "max_new_tokens": 8,
                         "model": "hot"})
                for ref in gen:
                    ray_tpu.get(ref)

        threads = [threading.Thread(target=pump) for _ in range(6)]
        for t in threads:
            t.start()
        samples = []
        try:
            deadline = time.time() + 40
            t0 = time.time()
            while time.time() < deadline:
                st = ray_tpu.get(controller.model_status.remote("scale"))
                hot = (st.get("models") or {}).get("hot")
                if hot:
                    samples.append({"t_s": round(time.time() - t0, 2),
                                    "serving": hot["serving"],
                                    "want": hot["want"],
                                    "load": round(hot["load"], 2)})
                    if hot["serving"] >= 2 and hot["want"] >= 2:
                        break
                time.sleep(0.25)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
        serve.shutdown()
        final = samples[-1] if samples else {}
        return {"samples": samples[-12:],
                "final_serving": final.get("serving", 0),
                "final_want": final.get("want", 0),
                "converged": bool(final) and final["serving"] >= 2}

    if init_cluster:
        ray_tpu.init(num_cpus=max(16, num_replicas + 4),
                     ignore_reinit_error=True)
    affinity = run_hit_cell("affinity")
    randomly = run_hit_cell("random")
    single = run_single_model_cell()
    fairness = run_fairness()
    scale = run_autoscale() if autoscale_phase else None
    if init_cluster:
        ray_tpu.shutdown()

    ratio = fairness["compliant_p99_ratio_max"]
    sheds = fairness["sheds_per_tenant"]
    flood = fairness["flood_tenant"]
    compliant_sheds = sum(v for t, v in sheds.items() if t != flood)
    acceptance = {
        "affinity_beats_random_warm_hit_rate":
            affinity["warm_hit_rate"] > randomly["warm_hit_rate"],
        "compliant_p99_within_1p5x_of_uncontended":
            ratio is not None and ratio <= 1.5,
        "flooder_shed_first":
            sheds.get(flood, 0) > 0 and compliant_sheds == 0,
    }
    if scale is not None:
        acceptance["per_model_autoscale_converges"] = scale["converged"]
    result = {
        "metric": "serve_multiplex_warm_hit_rate_affinity",
        "value": affinity["warm_hit_rate"],
        "unit": "fraction",
        "vs_baseline": randomly["warm_hit_rate"],
        "extra": {
            "affinity": affinity,
            "random": randomly,
            "single_model_baseline": single,
            "fairness": fairness,
            "autoscale": scale,
            "acceptance": acceptance,
            "note": f"skewed {n_models}-model catalog (zipf-ish), "
                    f"{n_tenants} tenants, {num_replicas} replicas x "
                    f"{max_models_per_replica}-model LRU; hit rate = "
                    "1 - cold_loads/requests from replica counters; "
                    "fairness = per-tenant client TTFT p99, one tenant "
                    "flooding vs uncontended; autoscale = controller "
                    "per-model decision table timeline",
        },
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def run_dag_bench(chain_len: int = 4, iters: int = 150,
                  data_blocks: int = 50, data_rows_per_block: int = 512,
                  out_path: str = "BENCH_dag.json"):
    """Per-hop dispatch cost: `.remote()` ref-chaining vs lazy DAG
    execute vs compiled execution graphs. A chain of `chain_len` Echo
    actors forwards a scalar `iters` times; wall time / (iters *
    chain_len) is each variant's per-hop cost. The compiled rows ride
    standing channels negotiated once at experimental_compile() — each
    execute() is a raw frame enqueue with no scheduler, no lease
    round-trip, and no per-call graph walk. Also runs one fixed 2-op
    map chain under the streaming executor vs the compiled data policy
    for a rows/s delta (compile setup included). Headline = compiled
    pipelined us/hop; vs_baseline = remote serial / compiled pipelined
    (acceptance: >= 10x). Single-core runnable via
    `python bench.py --bench dag`."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.dag import InputNode, bind_actor

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)

    @ray_tpu.remote
    class Echo:
        def fwd(self, x):
            return x

    acts = [Echo.remote() for _ in range(chain_len)]
    ray_tpu.get([a.fwd.remote(1) for a in acts], timeout=60)  # warm pool

    def per_hop(dt):
        return round(dt / (iters * chain_len) * 1e6, 1)

    # .remote() ref-chaining, one execution in flight — the dispatch
    # path a compiled graph replaces
    t0 = time.perf_counter()
    for i in range(iters):
        r = i
        for a in acts:
            r = a.fwd.remote(r)
        assert ray_tpu.get(r, timeout=60) == i
    remote_serial = per_hop(time.perf_counter() - t0)

    # .remote() ref-chaining, all iterations in flight
    t0 = time.perf_counter()
    outs = []
    for i in range(iters):
        r = i
        for a in acts:
            r = a.fwd.remote(r)
        outs.append(r)
    assert ray_tpu.get(outs, timeout=120) == list(range(iters))
    remote_pipe = per_hop(time.perf_counter() - t0)

    with InputNode() as inp:
        d = inp
        for a in acts:
            d = bind_actor(a).fwd.bind(d)

    # lazy DAG: same graph, re-dispatched through .remote() per execute
    t0 = time.perf_counter()
    outs = [d.execute(i) for i in range(iters)]
    assert ray_tpu.get(outs, timeout=120) == list(range(iters))
    lazy_pipe = per_hop(time.perf_counter() - t0)

    comp = d.experimental_compile()
    try:
        comp.execute(0).get(timeout=30)          # warm the channels
        t0 = time.perf_counter()
        for i in range(iters):
            assert comp.execute(i).get(timeout=30) == i
        comp_serial = per_hop(time.perf_counter() - t0)
        t0 = time.perf_counter()
        refs = [comp.execute(i) for i in range(iters)]
        for i, r in enumerate(refs):
            assert r.get(timeout=60) == i
        comp_pipe = per_hop(time.perf_counter() - t0)
    finally:
        comp.teardown()

    # fixed data chain: identical 2-op map chain through the streaming
    # executor vs the compiled policy (whole chain fused into one
    # CompiledChainMapOperator; compile setup counted against it)
    total_rows = data_blocks * data_rows_per_block
    data_cell = {"blocks": data_blocks,
                 "rows_per_block": data_rows_per_block}
    for policy in ("streaming", "compiled"):
        try:
            blocks = [{"x": np.arange(data_rows_per_block,
                                      dtype=np.float64)
                       + i * data_rows_per_block}
                      for i in range(data_blocks)]
            ds = (rd.Dataset([ray_tpu.put(b) for b in blocks], [])
                  .map_batches(lambda b: {"x": b["x"] * 1.0001})
                  .map_batches(lambda b: {"x": b["x"] + 1.0}))
            t0 = time.perf_counter()
            n = sum(len(b["x"]) for b in ds._iter_blocks(policy=policy))
            dt = time.perf_counter() - t0
            assert n == total_rows, (n, total_rows)
            data_cell[f"{policy}_rows_per_s"] = round(n / dt)
        except Exception as e:  # noqa: BLE001 — headline must print
            data_cell[f"{policy}_error"] = str(e)[:200]
    ray_tpu.shutdown()

    result = {
        "metric": "dag_compiled_pipelined_us_per_hop",
        "value": comp_pipe,
        "unit": "us/hop",
        "vs_baseline": round(remote_serial / max(comp_pipe, 1e-9), 1),
        "extra": {
            "chain_len": chain_len, "iters": iters,
            "remote_serial_us_per_hop": remote_serial,
            "remote_pipelined_us_per_hop": remote_pipe,
            "lazy_pipelined_us_per_hop": lazy_pipe,
            "compiled_serial_us_per_hop": comp_serial,
            "compiled_serial_speedup_vs_remote_serial": round(
                remote_serial / max(comp_serial, 1e-9), 1),
            "compiled_pipelined_speedup_vs_remote_pipelined": round(
                remote_pipe / max(comp_pipe, 1e-9), 1),
            "data_chain": data_cell,
            "note": "vs_baseline = remote serial / compiled pipelined "
                    "us/hop; compiled rows ride standing channels "
                    "negotiated at compile time, so execute() is a raw "
                    "frame enqueue; data_chain compares the streaming "
                    "executor against the compiled policy on the same "
                    "2-op chain, compile setup included",
        },
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def _elastic_bench_loop(config):
    """Shared loop for the elastic bench cells: optional hard-exit of
    one rank (chaos) and optional generation-1 slowdown (straggler);
    every step couples the gang through a host-collective allreduce so
    one slow rank degrades everyone, like a real pjit program."""
    import os as _os
    import time as _time

    import numpy as np

    from ray_tpu import collective as col
    from ray_tpu.train import session

    ck = session.get_checkpoint()
    start = ck.load_state()["step"] if ck else 0
    gen = session.get_context().elastic_meta.get("generation", 1)
    group = session.get_collective_group()
    for step in range(start, config["steps"]):
        slow = (gen == 1
                and session.world_rank() == config.get("slow_rank", -1)
                and step >= config.get("slow_from", 1 << 30))
        t0 = _time.time()
        _time.sleep(config.get("slow_s", 0.3) if slow else 0.01)
        compute = _time.time() - t0
        if group and session.world_size() > 1:
            col.allreduce(np.ones(2, dtype=np.float32), group)
        session.report({"step": step, "compute_s": compute},
                       state={"step": step + 1})
        if (ck is None
                and session.world_rank() == config.get("die_rank", -1)
                and step == config.get("die_at", -1)):
            _os._exit(1)
    return "done"


def run_train_elastic_bench(steps: int = 16,
                            out_path: str = "BENCH_train_elastic.json"):
    """Self-healing elastic training: what a fault costs. Three fits of
    the same collectively-coupled loop on a 2-worker CPU gang: (1) no
    fault — steady-state step time; (2) chaos — rank 1 hard-exits
    mid-run, the cell reports the remediation outage (largest hole in
    rank 0's report stream: quarantine + respawn + collective re-form
    + checkpoint resume) and the post-recovery step time; (3)
    straggler — rank 1 slows ~30x on generation 1, the cell reports
    pre/slow/post gang step times and the demotion outage. Headline =
    chaos recovery seconds; vs_baseline = post-recovery step time /
    steady step time (acceptance: ~1x — recovery is complete).
    Single-core runnable via `python bench.py --bench train_elastic`."""
    import os
    import statistics
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import ray_tpu
    from ray_tpu.train import (Backend, ElasticConfig, JaxTrainer,
                               RunConfig, ScalingConfig)
    from ray_tpu.train.config import CheckpointConfig

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)

    def fit(name, loop_cfg, **elastic_kw):
        trainer = JaxTrainer(
            _elastic_bench_loop,
            train_loop_config=dict({"steps": steps}, **loop_cfg),
            scaling_config=ScalingConfig(
                num_workers=2, use_tpu=False,
                resources_per_worker={"CPU": 0.5},
                elastic=ElasticConfig(min_workers=1, poll_interval_s=0.1,
                                      **elastic_kw)),
            run_config=RunConfig(
                name=name,
                storage_path=tempfile.mkdtemp(prefix="bench_elastic_"),
                checkpoint_config=CheckpointConfig(num_to_keep=2)),
            backend=Backend())
        r = trainer.fit()
        assert r.ok, f"{name}: {r.error}"
        return r

    def rank0_times(result):
        by_step = {}
        for r in result.metrics_history:
            if r["_rank"] == 0:
                by_step[r["step"]] = r["_ts"]       # last occurrence wins
        return [by_step[s] for s in sorted(by_step)]

    def step_gaps(ts, lo, hi):
        return [ts[i + 1] - ts[i]
                for i in range(max(lo, 0), min(hi, len(ts) - 1))]

    def outage(result):
        # largest wall-clock hole in rank 0's report stream == the
        # remediation: drain, quarantine, respawn, re-setup, resume
        ts = sorted(r["_ts"] for r in result.metrics_history
                    if r["_rank"] == 0)
        return max(ts[i + 1] - ts[i] for i in range(len(ts) - 1))

    # 1. steady state: the same gang and loop with no fault (first two
    #    gaps skipped: the peers' first-save orbax cold start couples in)
    base = fit("bench-steady", {})
    steady = statistics.median(step_gaps(rank0_times(base), 2, steps))

    # 2. chaos: rank 1 hard-exits at step 3
    chaos = fit("bench-chaos", {"die_rank": 1, "die_at": 3})
    recovery = outage(chaos)
    kts = rank0_times(chaos)
    chaos_post = statistics.median(step_gaps(kts, steps - 6, steps))

    # 3. straggler: rank 1 slows from step 6 until demoted
    slow_from = 6
    strag = fit("bench-straggler",
                {"slow_rank": 1, "slow_from": slow_from, "slow_s": 0.3},
                refill=False, grow=False, straggler_k=3.0,
                straggler_min_reports=4)
    sts = rank0_times(strag)
    ray_tpu.shutdown()

    result = {
        "metric": "elastic_chaos_recovery_s",
        "value": round(recovery, 2),
        "unit": "s",
        "vs_baseline": round(chaos_post / max(steady, 1e-9), 2),
        "extra": {
            "steps": steps,
            "steady_step_s": round(steady, 4),
            "chaos": {
                "recovery_s": round(recovery, 2),
                "post_step_s": round(chaos_post, 4),
                "world_sizes": chaos.elastic["world_sizes"],
                "remediations": [e["action"] for e in
                                 chaos.elastic["remediations"]],
            },
            "straggler": {
                "pre_step_s": round(statistics.median(
                    step_gaps(sts, 2, slow_from - 1)), 4),
                "slow_step_s": round(max(
                    step_gaps(sts, slow_from, slow_from + 2)), 4),
                "post_step_s": round(statistics.median(
                    step_gaps(sts, steps - 5, steps)), 4),
                "demotion_outage_s": round(outage(strag), 2),
                "world_sizes": strag.elastic["world_sizes"],
            },
            "note": "vs_baseline = chaos post-recovery step time / "
                    "no-fault steady step time (~1x means the refilled "
                    "gang fully recovered); recovery_s is the largest "
                    "hole in rank 0's report stream, i.e. the whole "
                    "quarantine -> respawn -> collective re-form -> "
                    "checkpoint-resume sequence",
        },
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def run_telemetry_bench(inc_iters: int = 50_000, flush_iters: int = 300,
                        dispatch_tasks: int = 100,
                        out_path: str = "BENCH_telemetry.json"):
    """Observability overhead: (1) Counter.inc() ops/s with the batched
    TelemetryAgent vs an emulated per-increment kv_put flush (exactly
    what util/metrics._flush did before the agent existed), (2) no-op
    task dispatch traced vs untraced, (3) edge_stats() population after
    a world=2 allreduce + cross-actor object transfer. Headline =
    batched/per-flush inc throughput ratio (acceptance: >= 10x). Emits
    BENCH_telemetry.json in the parsed style; single-core runnable via
    `python bench.py --bench telemetry`."""
    import numpy as np

    import ray_tpu
    from ray_tpu.util import metrics, state, tracing

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    rt = ray_tpu._rt.get_runtime()

    # 1a. batched hot loop: local lock + dict update, zero RPCs
    c = metrics.Counter("bench_inc_batched")
    t0 = time.perf_counter()
    for _ in range(inc_iters):
        c.inc()
    dt_batched = time.perf_counter() - t0
    batched_ops = inc_iters / dt_batched

    # 1b. the pre-agent baseline: one synchronous GCS kv_put per inc —
    # the exact payload shape the old _flush shipped
    c2 = metrics.Counter("bench_inc_per_flush")
    t0 = time.perf_counter()
    for i in range(flush_iters):
        c2.inc()
        payload = {"kind": "counter", "description": "",
                   "series": [{"tags": {}, "value": float(i + 1),
                               "count": i + 1}], "ts": time.time()}
        rt.kv_put("metrics", b"bench_inc_per_flush",
                  json.dumps(payload).encode())
    dt_flush = time.perf_counter() - t0
    flush_ops = flush_iters / dt_flush

    # 2. dispatch overhead: traced vs untraced no-op round trips
    @ray_tpu.remote
    def _nop():
        return 1

    ray_tpu.get(_nop.remote())  # warm the worker

    def _dispatch_cell(per_task=None, repeats=3, n=None):
        """Best-of-N mean round trip: a ~1 ms dispatch is noisy enough
        that a single run can swing more than the overheads measured."""
        n = n or dispatch_tasks
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(n):
                if per_task is not None:
                    per_task()
                ray_tpu.get(_nop.remote())
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    untraced_s = _dispatch_cell()
    tracing.enable()
    try:
        traced_s = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(dispatch_tasks):
                with tracing.span("bench::dispatch"):
                    ray_tpu.get(_nop.remote())
            traced_s = min(traced_s,
                           (time.perf_counter() - t0) / dispatch_tasks)
    finally:
        tracing.disable()

    # 2b. health-plane overhead on the same cell: per round trip the
    # watchdog adds exactly one Beacon.tick() (two attribute stores);
    # per telemetry report interval the agent additionally snapshots
    # every registered beacon off the hot path. Both are measured
    # directly and composed — an end-to-end A/B on a shared box cannot
    # resolve tens of nanoseconds against ±15% dispatch variance and
    # would only report the noise. Acceptance: < 2% of a dispatch.
    from ray_tpu.observability import health

    wb = health.beacon("bench:dispatch", deadline_s=30.0)
    wb.arm(bench=True)
    n_ticks = 1_000_000
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        wb.tick()
    tick_s = (time.perf_counter() - t0) / n_ticks
    t0 = time.perf_counter()
    for _ in range(1000):
        health.snapshot_beacons()
    snap_s = (time.perf_counter() - t0) / 1000
    wb.disarm()
    health.drop_beacon("bench:dispatch")
    report_interval = getattr(rt.cfg, "telemetry_report_interval_s", 1.0)
    # dispatches carried per report interval share one snapshot
    dispatches_per_interval = max(report_interval / untraced_s, 1.0)
    beacon_per_dispatch_s = tick_s + snap_s / dispatches_per_interval
    watchdog_pct = 100.0 * beacon_per_dispatch_s / max(untraced_s, 1e-9)

    # 3. the edge model after a collective + object-transfer workload.
    # Each member allreduces (collective edges recorded worker-side) and
    # returns a large array — the driver's get() pulls it out of the
    # worker's store, recording object_pull edges driver-side.
    @ray_tpu.remote
    class _EdgeMember:
        def __init__(self, rank, world):
            self.rank, self.world = rank, world

        def run(self, group):
            import numpy as _np

            import ray_tpu as _r
            from ray_tpu import collective as col

            col.init_collective_group(self.world, self.rank, group,
                                      backend="ring", timeout_s=120)
            x = _np.ones(1 << 16, dtype=_np.float64)
            for _ in range(3):
                col.allreduce(x, group)
            # ship this worker's edge observations before returning
            _r._rt.get_runtime().flush_task_events(wait=True)
            return _np.ones(1 << 18, dtype=_np.float64)

    workload_err = None
    try:
        members = [_EdgeMember.options(num_cpus=0.25).remote(i, 2)
                   for i in range(2)]
        ray_tpu.get([m.run.remote("bench_edges") for m in members],
                    timeout=300)
    except Exception as e:  # noqa: BLE001 — report the headline regardless
        workload_err = str(e)[:200]
    finally:
        try:
            from ray_tpu import collective as col

            col.destroy_collective_group("bench_edges")
        except Exception:
            pass
    try:
        edges = state.edge_stats()
    except Exception as e:  # noqa: BLE001
        edges = {}
        workload_err = workload_err or str(e)[:200]
    if workload_err:
        edges = dict(edges, error=workload_err)

    # 4. raylint wall time: cold analysis vs warm result-cache run over
    # the whole package, normalized per active rule so the cell stays
    # comparable as the catalog grows
    import os
    import shutil
    import tempfile

    from ray_tpu.devtools.lint import all_rules, run_lint

    lint_cache = tempfile.mkdtemp(prefix="raylint_bench_")
    try:
        pkg_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "ray_tpu")
        t0 = time.perf_counter()
        cold_rep = run_lint([pkg_dir], cache_dir=lint_cache)
        lint_cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_lint([pkg_dir], cache_dir=lint_cache)
        lint_warm_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(lint_cache, ignore_errors=True)
    n_rules = len(all_rules())
    lint_cell = {
        "files_scanned": cold_rep.files_scanned,
        "rules": n_rules,
        "cold_s": round(lint_cold_s, 3),
        "warm_s": round(lint_warm_s, 3),
        "cold_ms_per_rule": round(1000.0 * lint_cold_s / max(n_rules, 1), 2),
        "warm_pct_of_cold": round(
            100.0 * lint_warm_s / max(lint_cold_s, 1e-9), 1),
    }

    ratio = batched_ops / max(flush_ops, 1e-9)
    result = {
        "metric": "telemetry_counter_inc_batched_vs_per_flush",
        "value": round(ratio, 1),
        "unit": "x (inc ops/s ratio)",
        "vs_baseline": round(ratio, 1),
        "extra": {
            "batched_inc_ops_per_s": round(batched_ops),
            "per_flush_inc_ops_per_s": round(flush_ops),
            "untraced_dispatch_s": round(untraced_s, 6),
            "traced_dispatch_s": round(traced_s, 6),
            "tracing_overhead_pct": round(
                100.0 * (traced_s - untraced_s) / max(untraced_s, 1e-9), 1),
            "beacon_tick_s": tick_s,
            "beacon_snapshot_s": snap_s,
            "watchdog_overhead_pct": round(watchdog_pct, 4),
            "edge_stats": edges,
            "raylint_wall_time": lint_cell,
            "note": "per_flush emulates the pre-agent synchronous kv_put "
                    "per Counter.inc(); edge_stats should show populated "
                    "EWMA latency/bandwidth after the allreduce + pull",
        },
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return result


def run_memory_bench(iters: int = 150, repeats: int = 3,
                     nbytes: int = 1 << 18,
                     out_path: str = "BENCH_telemetry.json"):
    """Memory-attribution overhead on the object-store hot path: the
    same put+get loop timed with the tracker disabled (attribute() is a
    first-branch no-op) and enabled (ownership record + primary pin +
    temperature touch per object). Objects are 256 KiB — above
    max_direct_call_object_size, so every put is store-resident and
    walks the attributed path end to end. The headline overhead is
    composed from directly-measured primitive costs (attribute+pin+
    release cycle, temperature touch) against the disabled put+get
    round trip — the same approach as the watchdog cell, because an
    end-to-end A/B cannot resolve ~us of bookkeeping against ~ms of
    dispatch variance; the interleaved best-of-N A/B rides along in
    the cell as a sanity bound. Acceptance: composed overhead < 2%.
    Merges into BENCH_telemetry.json
    under extra["memory_attribution"] (standalone result doc if that
    file is absent); single-core runnable via
    `python bench.py --bench memory`."""
    import gc

    import numpy as np

    import ray_tpu
    from ray_tpu.observability import memory

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    arr = np.ones(nbytes // 8, dtype=np.float64)

    def _cycle(n):
        """Mean s/round-trip over n store-resident put+get pairs; refs
        are freed outside the timed window so both modes pay the same
        release cost."""
        refs = []
        t0 = time.perf_counter()
        for _ in range(n):
            r = ray_tpu.put(arr)
            ray_tpu.get(r)
            refs.append(r)
        dt = time.perf_counter() - t0
        del refs
        gc.collect()
        return dt / n

    _cycle(20)  # warm the store, shm pool, and pin RPC path
    best = {True: float("inf"), False: float("inf")}
    for _ in range(repeats):
        for enabled in (False, True):
            memory.set_enabled(enabled)
            memory.tracker().reset()
            best[enabled] = min(best[enabled], _cycle(iters))
    memory.set_enabled(True)
    memory.tracker().reset()

    ab_pct = (100.0 * (best[True] - best[False])
              / max(best[False], 1e-9))

    # primitive costs, composed per put+get round trip: one
    # attribute+pin(+eventual release) on the nodelet put path, one
    # temperature touch on the get path
    mem = memory.tracker()
    prim_n = 50_000
    t0 = time.perf_counter()
    for i in range(prim_n):
        key = "bench:%d" % i
        mem.attribute(key, "user", nbytes, owner="bench")
        mem.pin(key, "primary")
        mem.release(key)
    attr_cycle_s = (time.perf_counter() - t0) / prim_n
    mem.attribute("bench:touch", "user", nbytes, store=False)
    t0 = time.perf_counter()
    for _ in range(prim_n):
        memory.touch("bench:touch")
    touch_s = (time.perf_counter() - t0) / prim_n
    mem.reset()

    overhead_pct = (100.0 * (attr_cycle_s + touch_s)
                    / max(best[False], 1e-9))
    cell = {
        "putget_disabled_s": round(best[False], 7),
        "putget_enabled_s": round(best[True], 7),
        "ab_overhead_pct": round(ab_pct, 3),
        "attribute_pin_release_s": round(attr_cycle_s, 9),
        "touch_s": round(touch_s, 9),
        "attribution_overhead_pct": round(overhead_pct, 3),
        "object_nbytes": nbytes,
        "iters_per_mode": iters * repeats,
        "pass_lt_2pct": bool(overhead_pct < 2.0),
    }
    try:
        with open(out_path) as f:
            result = json.load(f)
    except Exception:
        result = None
    if not isinstance(result, dict) or "extra" not in result:
        result = {
            "metric": "memory_attribution_overhead_pct",
            "value": cell["attribution_overhead_pct"],
            "unit": "% put+get slowdown (enabled vs disabled)",
            "vs_baseline": cell["attribution_overhead_pct"],
            "extra": {},
        }
    result["extra"]["memory_attribution"] = cell
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps({"metric": "memory_attribution_overhead_pct", **cell}))
    return cell


def main():
    """Headline = the LARGEST model that trains on this chip: 125M wastes
    the MXU at small width, so largest-fits is the honest per-chip
    capability number. 2.7B is the reference's own LLM scale proof model
    (release/alpa_tests/train_opt_2_7b_minimum.py). Recipe: bf16 params
    + adafactor (adam's 2x-f32 state needs 32 GB; this is the standard
    single-accelerator recipe at this size), batch 5 (what fits the
    16 GB chip with room for the scheduler). The 125M and 1B presets
    ride along in extra. Needs a TPU; the first failure ends the run
    with a non-zero exit code."""
    import gc

    import jax.numpy as jnp

    result = run_train_bench(
        "2b7", batch=5, optimizer="adafactor",
        config_overrides={"param_dtype": jnp.bfloat16},
        metric_name="llama2b7_train_tokens_per_sec_per_chip")
    for preset, batch, key in (("debug-125m", 8, "llama125m"),
                               ("1b", 4, "llama1b")):
        gc.collect()             # drop the previous preset's HBM state
        r = run_train_bench(preset, batch=batch, seq=1024)
        result["extra"][key] = {
            "tokens_per_sec_per_chip": r["value"],
            "mfu": r["extra"]["mfu"],
            "batch": batch, "seq": 1024,
            "f32_logits": r["extra"]["f32_logits"],
        }
    print(json.dumps(result))


if __name__ == "__main__":
    import argparse

    from ray_tpu.core import compile_cache

    compile_cache.env_defaults()     # before anything imports jax

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bench", default="train",
                    choices=("train", "collective", "data", "telemetry",
                             "serve_router", "serve_disagg",
                             "serve_multiplex", "dag",
                             "memory", "train_elastic"),
                    help="train = headline tokens/s/chip (default); "
                         "collective = host-collective backend sweep "
                         "(slow, writes BENCH_collective.json); "
                         "data = streaming executor vs fused path sweep "
                         "(writes BENCH_data.json); "
                         "telemetry = metric/tracing overhead + edge model "
                         "(writes BENCH_telemetry.json); "
                         "serve_router = LLM router concurrency x replicas "
                         "x policy sweep (writes BENCH_serve_router.json); "
                         "serve_disagg = disaggregated prefill/decode vs "
                         "monolithic under mixed traffic (writes "
                         "BENCH_serve_disagg.json); "
                         "serve_multiplex = model multiplexing + "
                         "weighted-fair tenants: warm-hit rate, fairness "
                         "under flood, per-model autoscale (writes "
                         "BENCH_serve_multiplex.json); "
                         "dag = per-hop .remote() vs lazy vs compiled "
                         "graph dispatch (writes BENCH_dag.json); "
                         "memory = attribution overhead on the put/get "
                         "hot path (merges into BENCH_telemetry.json); "
                         "train_elastic = self-healing gang fault cost: "
                         "kill/resume recovery + straggler demotion "
                         "(writes BENCH_train_elastic.json)")
    ns = ap.parse_args()
    if ns.bench == "collective":
        run_collective_bench()
    elif ns.bench == "data":
        run_data_bench()
    elif ns.bench == "telemetry":
        run_telemetry_bench()
    elif ns.bench == "serve_router":
        run_serve_router_bench()
    elif ns.bench == "serve_disagg":
        run_serve_disagg_bench()
    elif ns.bench == "serve_multiplex":
        run_serve_multiplex_bench()
    elif ns.bench == "dag":
        run_dag_bench()
    elif ns.bench == "memory":
        run_memory_bench()
    elif ns.bench == "train_elastic":
        run_train_elastic_bench()
    else:
        main()
