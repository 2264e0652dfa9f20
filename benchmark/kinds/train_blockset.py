"""Kind ``train_blockset``: kind ``train`` (tokens trained per chip-second in
the user's loop under ``JaxTrainer``) for the sparse-attention /
linear-attention hybrid of ``ray_tpu/models/sala.py`` (MiniCPM-SALA: layers
of grouped-query attention over the 64 best BLOCKS of keys a query and KV
group, chosen from the attention's own pooled keys with no weights and no
loss, beside Lightning linear-attention layers on the chunked scan at heads
of 128; a dense SwiGLU in every layer). The recipe, the set-up marks, the
rate (steps x tokens over the window's host clock), ``loss_checks`` and the
``obs`` handed to the readers are kind ``train``'s (imported, not copied).
What decides ``correct``, against ``reference_sala.py`` given the same
layers:

(a) Sets. The program's sets of every sparse layer of the first batch:
    every query t holds exactly min(topk, t // block + 1) blocks, none that
    starts after it, its forced blocks (the first and the window's) among
    them; against the reference's own choice by a stable sort, the share of
    a query's selections that differ and, where they differ, the
    block-score gap between what the reference gave up and what it took
    instead (``set_differ``, ``set_gap``).
(b) Numbers. The reference evaluated ON the program's sets, at the
    published widths and the timed S, a block of queries at a time, the
    recurrence a token at a time in float32 at ``highest``: per-token losses
    (mean, 99.9th percentile), the step's loss before and after the first
    update, and the descent of the reference's loss (``train.loss_checks``).
(c) The step's counters: the pairs the sets hold are the ``flops`` file's
    exact count; the step program holds its Pallas calls; every loss is
    finite.

The limits are the cell's ``train.check``; measured values and their
origin: PERF.md 4.
"""

from __future__ import annotations

import importlib.util
import math
import os
import statistics
import time

from benchmark.kinds.train import FIRST_LOSS_TOL, loss_agreement, loss_checks
from benchmark.kinds.train_hybrid import stall_lines

COUNTERS = ("sparse_blocks_selected", "sparse_pairs_selected",
            "sparse_set_forced", "sparse_pairs_walked",
            "sparse_layers_selecting")


def token_loss_fns(cfg, sizes: dict, mesh=None, rules=None) -> tuple:
    """``(program, reference)``. program: tokens [B, S+1] -> (every
    position's loss [B, S] float32, the sparse layers' sets [F, B, KV, S,
    S / block] int8) through the program's own forward. reference:
    (params, tokens, sets) -> (losses [B, S], record) through the plain
    reference on those sets; ``compare`` False takes the sets as they are
    and leaves the reference's own selection unmade. Each is one jitted
    program."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_sala
    from ray_tpu.models import sala

    seen = cfg.replace(report_sets=True)

    def program(p, t):
        logits, stats = sala.forward_with_stats(p, t[:, :-1], seen,
                                                mesh=mesh, rules=rules)
        picked = jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) \
            - picked.astype(jnp.float32)
        return nll, stats["block_set"]

    def reference(p, t, sets, compare=True):
        return reference_sala.token_losses(p, t, sizes, sets,
                                           compare=compare)

    return jax.jit(program), jax.jit(reference, static_argnums=3)


def set_agreement(sets, rec: dict, sizes: dict) -> dict:
    """The program's ``sets`` [F, B, KV, S, blocks] int8 by themselves
    (sizes, causality, the forced blocks, the 512 x 512 tiles of the causal
    band in which no query selected anything) and against the reference's
    record on them."""
    import jax
    import jax.numpy as jnp

    block, topk = sizes["sparse_block"], sizes["sparse_topk"]
    window = sizes["sparse_window"] // block

    @jax.jit
    def stats(sets, differ, gap):
        S, blocks = sets.shape[-2:]
        own = (jnp.arange(S) // block)[:, None]
        b = jnp.arange(blocks)[None, :]
        on = sets != 0
        must = ((b < sizes["sparse_init_blocks"]) | (b > own - window)) \
            & (b <= own)
        tile = min(512, S)
        per = tile // block
        tiles = on.reshape(*on.shape[:3], S // tile, tile, blocks // per,
                           per).any(axis=(-1, -3))
        band = jnp.arange(blocks // per)[None, :] \
            <= jnp.arange(S // tile)[:, None]
        return {"sized": jnp.all(jnp.sum(on, axis=-1)
                                 == jnp.minimum(topk, own[:, 0] + 1)),
                "late": jnp.sum(on & (b > own)),
                "unforced": jnp.sum(must & ~on),
                "empty_tiles": jnp.sum(band & ~tiles),
                "tiles": jnp.sum(band) * math.prod(sets.shape[:3]),
                "differ_share": jnp.mean(differ),
                "differ_max": jnp.max(differ), "gap_max": jnp.max(gap),
                "gap_p999": jnp.percentile(gap, 99.9)}

    out = stats(sets, rec["set_differ"], rec["set_gap"])
    return {"layers": int(sets.shape[0]), "sized": bool(out["sized"]),
            **{k: int(out[k]) for k in ("late", "unforced", "empty_tiles",
                                        "tiles")},
            **{k: float(out[k]) for k in ("differ_share", "differ_max",
                                          "gap_max", "gap_p999")}}


def set_checks(s: dict, tol: dict, sizes: dict) -> dict:
    topk, block = sizes["sparse_topk"], sizes["sparse_block"]
    return {
        f"every query t of every sparse layer ({s['layers']}) holds exactly "
        f"min({topk}, t // {block} + 1) blocks": s["sized"],
        f"no set holds a block that starts after its query (such: "
        f"{s['late']})": s["late"] == 0,
        f"every set holds its forced blocks, the first and the window's "
        f"(missing: {s['unforced']})": s["unforced"] == 0,
        f"sets: where program and reference differ, the reference's block "
        f"score gap is at most {s['gap_max']:.2e} <= {tol['set_gap_max']} "
        f"(99.9th percentile {s['gap_p999']:.2e})":
            s["gap_max"] <= tol["set_gap_max"],
        f"sets: {100 * s['differ_share']:.3f}% of the selections differ <= "
        f"{100 * tol['set_differ_share']}% (most in a query: "
        f"{100 * s['differ_max']:.2f}%)":
            s["differ_share"] <= tol["set_differ_share"],
    }


def train_loop(config: dict) -> None:
    import jax
    import optax

    from benchmark import model_sala, trace_reduce
    from ray_tpu.core import compile_cache
    from ray_tpu.models import sala
    from ray_tpu.parallel.train_step import (batch_sharding,
                                             make_train_state_init,
                                             make_train_step)
    from ray_tpu.train import session

    cell, seed = config["cell"], config["seed"]
    marks = [("worker in the loop", time.time())]   # set-up, phase by phase
    dev0 = jax.devices()[0]
    marks.append(("chips open", time.time()))
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    if config["want_tpu"] and device["platform"] != "tpu":
        raise RuntimeError(
            f"train worker: jax gave platform {device['platform']!r}, not "
            "'tpu'; a real configuration is not measured off the chip")
    recipe, mix = cell["train"], cell["mix"]
    cfg = model_sala.sala_config(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "ssd_impl", "remat", "f32_logits") if k in recipe})
    sizes = model_sala.sizes(cell["config"])
    mesh, rules = session.get_mesh(), session.get_rules()
    if recipe["optimizer"] != "adafactor":
        raise ValueError(f"unknown optimizer {recipe['optimizer']!r}")
    opt = optax.adafactor(recipe["lr"])
    init_fn, state_sh = make_train_state_init(
        lambda k: sala.init_params(k, cfg), opt, mesh, rules,
        sala.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(seed % (2 ** 31)))   # one jitted call
    B, S, V = mix["batch"], mix["seq"], cfg.vocab_size
    shapes = {"tokens": jax.ShapeDtypeStruct((B, S + 1), "int32")}
    key = jax.random.PRNGKey((seed + 1) % (2 ** 31))
    # the key is an argument, not a constant of the program: a program
    # that held the seed would compile anew for every seed
    draw = jax.jit(
        lambda key, i: {"tokens": jax.random.randint(
            jax.random.fold_in(key, i), (B, S + 1), 0, V, "int32")},
        out_shardings=batch_sharding(mesh, rules, shapes))

    def make_batch(i):
        return draw(key, i)

    step = make_train_step(
        lambda p, b: sala.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        opt, mesh, rules, state_sh, batch_shapes=shapes)
    batch = make_batch(0)
    jax.block_until_ready((state, batch))
    marks.append(("state and first batch made", time.time()))
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    plan = int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    text = compiled.as_text()
    program = {"compile_s": compile_s, "plan_bytes": plan,
               "argument_bytes": int(mem.argument_size_in_bytes),
               "temp_bytes": int(mem.temp_size_in_bytes),
               "pallas_calls": text.count("tpu_custom_call")}
    del text
    marks.append(("step program compiled or loaded", time.time()))

    # the plain reference on the first batch and the program's sets, before
    # the step donates the state, and again after the step's first update;
    # the second warm step runs on the same batch, so the program's loss
    # there is known too
    program_nll, reference_nll = token_loss_fns(cfg, sizes, mesh, rules)
    t0 = time.perf_counter()
    got, sets = program_nll(state.params, batch["tokens"])
    ref, rec = reference_nll(state.params, batch["tokens"], sets)
    agreement = loss_agreement(got, ref)
    selecting = set_agreement(sets, rec, sizes)
    del got, ref, rec, sets
    losses, stats = [], []

    def fetch(m):
        host = jax.device_get(m)         # host fetch: the step is done
        return float(host["loss"]), {k: int(host[k]) for k in COUNTERS}

    for i in range(2):                    # the two warm steps
        if i == 1:
            _, sets = program_nll(state.params, batch["tokens"])
            # the sets were compared on the first pass: no sort here
            ref_loss_updated = float(reference_nll(
                state.params, batch["tokens"], sets, False)[0].mean())
            del sets
            reference_s = time.perf_counter() - t0
            marks.append(("checked against the reference", time.time()))
        state, m = compiled(state, batch)
        loss, counts = fetch(m)
        losses.append(loss)
        stats.append(counts)
    marks.append(("warm steps", time.time()))

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    entries0 = compile_cache.entry_count(cache_dir)
    # --trace 1: steps [trace_from, trace_from + trace_steps) run under the
    # profiler; that run reports no end-to-end metric
    trace = config["trace"]
    t_from = recipe.get("trace_from", 3)
    t_to = t_from + recipe.get("trace_steps", 4)
    step_s, report_s, trace_span = [], [], None
    i = len(losses)
    window_start = time.time()
    t_first = t_prev = time.perf_counter()
    while True:
        n = len(step_s)
        if trace and n == t_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the device and the runtime only
            jax.profiler.start_trace(trace, profiler_options=opts)
            t_trace = t_prev = time.perf_counter()
        state, m = compiled(state, make_batch(i))
        loss, counts = fetch(m)
        t_step = time.perf_counter()
        session.report({"loss": loss, "step": i, **counts})
        t_rep = time.perf_counter()
        losses.append(loss)
        stats.append(counts)
        step_s.append(t_step - t_prev)
        report_s.append(t_rep - t_step)
        t_prev = t_rep
        i += 1
        if trace and n + 1 == t_to:
            trace_span = t_rep - t_trace
            jax.profiler.stop_trace()
            t_prev = time.perf_counter()
        if t_prev - t_first >= config["seconds"] and (not trace or n + 1 >= t_to):
            break
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - t_first
    entries1 = compile_cache.entry_count(cache_dir)
    steps = len(step_s)
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()]
    out = {
        "device": device, "program": program, "peak_bytes": peaks,
        "first_loss": losses[0], "second_loss": losses[1],
        "agreement": agreement, "selecting": selecting,
        "ref_loss_updated": ref_loss_updated,
        "losses_head": losses[:6], "last_loss": losses[-1],
        "all_finite": all(math.isfinite(x) for x in losses),
        # the step's counters: the first step's, and whether every step of
        # the window counted the same (the sets' sizes are the shapes')
        "counters": stats[0],
        "counters_steady": all(s == stats[0] for s in stats),
        "vocab": V, "steps": steps, "elapsed_s": elapsed,
        "tokens_per_step": B * S, "window_start": window_start,
        "reference_s": reference_s, "setup_marks": marks,
        "step_ms_median": statistics.median(step_s) * 1e3,
        "report_ms_median": statistics.median(report_s) * 1e3,
        # a stall shows here and not in the medians: (ms, which step)
        "longest_step": max((t * 1e3, n) for n, t in enumerate(step_s)),
        "longest_report": max((t * 1e3, n) for n, t in enumerate(report_s)),
        "long_steps": [(t * 1e3, n) for n, t in enumerate(step_s)
                       if t > 1.1 * statistics.median(step_s)],
        "compiles_in_window": entries1 - entries0,
    }
    if trace_span is not None:
        red = trace_reduce.reduce_file(trace_reduce.find_xplane(trace),
                                       window_s=trace_span)
        out["trace_structure"] = red.pop("structure")[:80]
        if red:                  # a trace with no device plane reads nothing
            out["trace"] = {**red, "idle_gaps": red["idle_gaps"][:20]}
    session.report(out)


def run(cell: dict, args, ctx: dict) -> dict:
    """Parent side. Returns the observations that ``run.py`` turns into
    the result line."""
    if importlib.util.find_spec("ray_tpu.models.sala") is None:
        # fail before a cluster starts: a program without the family
        # cannot run this kind
        raise ctx["Refused"]("this program has no ray_tpu/models/sala.py: "
                             "it has no attention over a set of blocks and "
                             "no lightning layer for this kind to train")
    import ray_tpu
    from ray_tpu.core.node import detect_tpu_chips
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark import flops_sala, model_sala

    log = ctx["log"]
    recipe, chips = cell["train"], cell["chips"]
    want_tpu = not cell.get("rehearsal", False)
    found = detect_tpu_chips()
    if want_tpu and found < chips:
        raise ctx["Refused"](f"this host shows {found} TPU chip(s), the "
                             f"cell needs {chips}")
    sizes = model_sala.sizes(cell["config"])
    batch, seq = cell["mix"]["batch"], cell["mix"]["seq"]
    if seq <= sizes["dense_len"]:
        raise ctx["Refused"](
            f"{seq} tokens a sequence are within the model's dense length "
            f"of {sizes['dense_len']}: no layer selects, and this kind "
            "checks sets")
    log(f"train_blockset: JaxTrainer(1 worker x {chips} chip(s)), mesh "
        f"{recipe['mesh']}, rules {recipe['rules']}, B{batch} x S{seq} "
        f"(+1 id), layers {sizes['layer_types']}, {sizes['n_heads']} query "
        f"heads over {sizes['n_kv_heads']} KV heads of "
        f"{sizes['head_width']}, the {sizes['sparse_topk']} best blocks of "
        f"{sizes['sparse_block']} keys past {sizes['dense_len']} tokens, "
        f"{sizes['lightning_heads']} lightning heads")
    ray_tpu.init(num_cpus=max(os.cpu_count() or 1, 8))
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "cell": cell, "seed": args.seed, "seconds": args.seconds,
                "want_tpu": want_tpu,
                "trace": ctx["trace_dir"] if args.trace else None},
            scaling_config=ScalingConfig(
                num_workers=1, chips_per_worker=chips,
                mesh=MeshSpec(**recipe["mesh"]), rules=recipe["rules"]),
            run_config=RunConfig(name="bench_" + cell["name"],
                                 storage_path=ctx["out_dir"])).fit()
    finally:
        ray_tpu.shutdown()
    if result.error:
        raise RuntimeError(f"train loop failed:\n{result.error}")
    m = result.metrics
    device, prog, a, sel, counts = (m["device"], m["program"], m["agreement"],
                                    m["selecting"], m["counters"])
    tol = recipe["check"]
    tok_s_chip = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] \
        / device["count"]
    per_token = flops_sala.train_flops_per_token(sizes, seq)
    log(f"  device {device}; step program: compile "
        f"{prog['compile_s']:.1f} s, plan {prog['plan_bytes']} bytes "
        f"a device (arguments {prog['argument_bytes']}, temporaries "
        f"{prog['temp_bytes']}), {prog['pallas_calls']} Pallas calls")
    log(f"  {m['steps']} steps of {m['tokens_per_step']} tokens in "
        f"{m['elapsed_s']:.3f} s; step median "
        f"{m['step_ms_median']:.2f} ms, report median "
        f"{m['report_ms_median']:.3f} ms; longest step "
        f"{m['longest_step'][0]:.1f} ms (step {m['longest_step'][1]}), "
        f"longest report {m['longest_report'][0]:.3f} ms (step "
        f"{m['longest_report'][1]}); losses {m['losses_head']} "
        f"... {m['last_loss']:.4f}; reference pass "
        f"{m['reference_s']:.1f} s")
    # loss_checks prints both to five places, and the limit is finer
    log(f"  step loss less the reference's: "
        f"{m['first_loss'] - a['ref_loss']:+.2e} before and "
        f"{m['second_loss'] - m['ref_loss_updated']:+.2e} after the first "
        f"update (limit {tol['step_loss_abs']})")
    stalls = stall_lines(os.environ.get("RAY_TPU_TMPDIR", ""))
    log(f"  steps over 1.1 x the median: "
        f"{[(round(ms, 1), n) for ms, n in m['long_steps']] or 'none'}; "
        f"stall lines in the workers' logs: {len(stalls)}")
    for ln in stalls:
        log("    " + ln)
    # what the sets of one step hold, a head (the sets are a KV group's:
    # every head of the group attends over the same pairs)
    selecting = sum(k == "sparse" for k in sizes["layer_types"])
    selected = counts["sparse_pairs_selected"] / (
        batch * sizes["n_kv_heads"] * selecting)
    walked = counts["sparse_pairs_walked"] * 1024 / (
        batch * sizes["n_heads"] * selecting)
    exact = flops_sala.selected_pairs(seq, sizes)
    causal = flops_sala.causal_pairs(seq)
    log(f"  selection: {counts['sparse_layers_selecting']} layer(s) "
        f"selected; a head attends to {selected:.0f} of {causal} causal "
        f"pairs ({selected / causal:.4f}; the flops file counts {exact}), "
        f"its walk computes {walked:.0f}; blocks selected "
        f"{counts['sparse_blocks_selected']}, forced among them "
        f"{counts['sparse_set_forced']}; {sel['empty_tiles']} of "
        f"{sel['tiles']} tiles of the causal band hold no selected block "
        f"(the walk skips none); every step counted the same: "
        f"{m['counters_steady']}")
    at, phases = ctx["t_start"], []
    for what, t in m["setup_marks"] + [("window", m["window_start"])]:
        phases.append(f"{what} {t - at:.1f}")
        at = t
    log("  set-up, seconds a phase: " + ", ".join(phases))
    if device["platform"] == "tpu":
        peak_flops = ctx["peak"](device["kind"])["bf16_flops_per_s"]
        log(f"  model FLOP/s utilization "
            f"{tok_s_chip * per_token / peak_flops:.4f} = {tok_s_chip:.1f} "
            f"tokens/s/chip x {per_token / 1e9:.3f} GFLOP/token / "
            f"{peak_flops / 1e12:.0f} TFLOP/s")
    if "trace_structure" in m:
        log("  trace planes and lines: " + "; ".join(
            f"{p} / {ln}: {n}" for p, ln, n in m["trace_structure"]))
    # random init: logits ~N(0, 1) over the logits' divisor
    want = math.log(m["vocab"]) + 0.5 / sizes["logits_scaling"] ** 2
    checks = {
        **set_checks(sel, tol, sizes),
        **loss_checks(m, tol),
        f"the sets hold the flops file's exact count of pairs a head "
        f"({selected:.0f} == {exact}) in every step":
            selected == exact and m["counters_steady"]
            and counts["sparse_layers_selecting"] == selecting,
        f"first loss within {FIRST_LOSS_TOL} of ln(V) = {want:.4f}":
            abs(m["first_loss"] - want) < FIRST_LOSS_TOL,
        "all losses finite": m["all_finite"],
        f"ran on {chips} device(s)": device["count"] == chips or not want_tpu,
    }
    if device["platform"] == "tpu" and recipe.get("attn_impl") == "flash" \
            and recipe.get("ssd_impl") == "pallas":
        # a run of layers is one scanned body: the sparse attention's
        # forward, dQ and dK/dV; the scan's forward, its forward again in
        # the replay and its backward
        kinds = set(sizes["layer_types"])
        least = 3 * len(kinds)
        checks[f"the step program holds the Pallas calls of its "
               f"{len(kinds)} kinds of layer ({prog['pallas_calls']} >= "
               f"{least})"] = prog["pallas_calls"] >= least
    return {
        "checks": checks, "attempted": m["steps"], "failed": 0,
        "device": {**device, "memory_peak_bytes": max(
            [prog["plan_bytes"]] + m["peak_bytes"])},
        "window_start": m["window_start"],
        "end_to_end": {"train_tok_s_chip": tok_s_chip},
        "obs": {"counters": {"compiles_in_window": m["compiles_in_window"],
                             "sparse_pairs_selected": selected,
                             "sparse_pairs_walked": walked,
                             "causal_pairs": causal},
                "values": {"train_step_ms": m["step_ms_median"],
                           "train_report_ms": m["report_ms_median"]},
                "trace": m.get("trace"), "sizes": sizes, "cell": cell},
    }
