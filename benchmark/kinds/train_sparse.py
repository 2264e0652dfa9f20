"""Kind ``train_sparse``: kind ``train`` (tokens trained per chip-second in
the user's loop under ``JaxTrainer``) for the latent-attention model with a
LEARNED SELECTION of ``ray_tpu/models/latent.py`` (GLM-5.2: an indexer that
scores every earlier key, each query attending to its ``index_topk`` best,
shared layers that reuse the set of the full layer before them, the
indexer's own loss term; sparse experts; no prediction module). The recipe,
the set-up marks, the rate (steps x tokens over the window's host clock)
and the ``obs`` handed to the readers are kind ``train``'s; the held
experts are placed by load and the bias rule is checked as kind
``train_latent`` does both (``place_experts``, ``bias_agreement``: imported,
not copied). What decides ``correct``, against ``reference_glm52.py`` given
the same share of the heads, the experts and the vocabulary:

(a) Sets. The program's sets of every full layer of the first batch: every
    query t holds exactly min(index_topk, t + 1) keys, none after t (a set
    is a 0/1 row, so its keys are unique and in order by construction);
    every layer attended over the set of the full layer at or before it
    (the fingerprint each layer takes of the set handed to its attention
    call against the reported sets': the reference runs ON the sets the
    program reports, so a layer that attended over another set than the
    one it should shows in (c) only as far as the losses move, on the
    chip the 99.9th percentile and not the mean);
    against the reference's own choice by a stable sort, the share of a
    query's selections that differ and, where they differ, the index-score
    gap between what the reference gave up and what it took instead
    (``set_differ``, ``set_gap``).
(b) Routes. As kind ``train_latent`` (a).
(c) Numbers. The reference evaluated ON the program's sets and routes, at
    the published widths and the timed S, a block of queries at a time:
    per-token losses (mean, 99.9th percentile), each full layer's LI, the
    step's total loss (cross-entropy + balance weight x balance + index
    weight x the sum of the LI) before and after the first update, and the
    descent of the reference's loss (``train.loss_checks``).
(d) The rule. As kind ``train_latent`` (c).
(e) ``moe_dropped`` is 0 in every step, the step program holds its Pallas
    calls, every loss is finite.

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
from benchmark.kinds.train_latent import bias_agreement, place_experts
from benchmark.kinds.train_moe import route_agreement, route_checks


def token_loss_fns(cfg, sizes: dict, mesh=None, rules=None) -> tuple:
    """``(program, reference)``. program: tokens [B, S+1] -> (every
    position's loss [B, S] float32, routes [L, B, S, K], counts [L, E],
    the full layers' sets [F, B, S, S] int8, their LI [F], every layer's
    fingerprint of the set it attended over [layers]) through the
    program's own forward. reference: (params, tokens, routes, sets) ->
    (losses [B, S], total loss, record) through the plain reference on
    those routes and sets; ``compare`` False takes the sets as they are
    and leaves the reference's own selection (two sorts a block of rows)
    unmade. Each is one jitted program."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_glm52
    from ray_tpu.models import latent

    seen = cfg.replace(index_report_sets=True)

    def program(p, t):
        logits, stats = latent.forward_with_stats(p, t[:, :-1], seen,
                                                  mesh=mesh, rules=rules)
        picked = jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) \
            - picked.astype(jnp.float32)
        b, s = nll.shape
        experts = stats["experts"]                        # [L, B*S, K]
        return (nll, experts.reshape(experts.shape[0], b, s, -1),
                stats["counts"], stats["index_set"], stats["index_loss"],
                stats["index_attended"])

    def reference(p, t, routes, sets, compare=True):
        nll, rec = reference_glm52.token_losses(p, t, sizes, routes, sets,
                                                compare=compare)
        balance = rec["balance"].mean()
        total = nll.mean() + sizes["router_aux_weight"] * balance \
            + sizes["index_loss_weight"] * jnp.sum(rec["index_loss"])
        own = jax.vmap(lambda e: jnp.bincount(
            e.reshape(-1), length=sizes["n_experts"]))(rec["experts"])
        rec = {k: v for k, v in rec.items() if k != "own_sets"}
        return nll, total, {"balance_mean": balance, "own_counts": own, **rec}

    return jax.jit(program), jax.jit(reference, static_argnums=4)


def set_agreement(sets, rec: dict, topk: int, attended=None,
                  index_full=()) -> dict:
    """The program's ``sets`` [F, B, S, S] int8 by themselves (sizes,
    causality), against the reference's record on them, and on their way
    through the layers: ``attended`` [layers], each layer's fingerprint of
    the set it attended over (first sequence: the sum over the set's pairs
    (t, s) of (40503 t + 9973 s) mod 2^16, mod 2^32), has to be that of
    the set of the nearest full layer at or before it (``index_full``)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(sets, differ, gap):
        s = sets.shape[-1]
        t = jnp.arange(s)
        size = jnp.sum(sets.astype(jnp.int32), axis=-1)            # [F, B, S]
        late = jnp.sum((sets != 0) & (t[None, :] > t[:, None]))
        at = t.astype(jnp.uint32)
        weight = (at[:, None] * 40503 + at[None, :] * 9973) & 0xFFFF
        prints = jnp.sum(sets[:, 0].astype(jnp.uint32) * weight, axis=(1, 2))
        return {"sized": jnp.all(size == jnp.minimum(topk, t + 1)),
                "late": late, "differ_share": jnp.mean(differ),
                "differ_max": jnp.max(differ), "gap_max": jnp.max(gap),
                "gap_p999": jnp.percentile(gap, 99.9), "prints": prints}

    out = stats(sets, rec["set_differ"], rec["set_gap"])
    prints = [int(x) for x in out["prints"]]
    owner, want = -1, []
    for full in index_full:
        owner += bool(full)
        want.append(prints[owner])
    misled = [n for n, (a, b) in enumerate(zip(
        [] if attended is None else [int(x) for x in attended], want))
        if a != b]
    return {"layers": int(sets.shape[0]), "sized": bool(out["sized"]),
            "late": int(out["late"]), "misled": misled,
            "followed": attended is not None and len(attended) == len(want),
            **{k: float(out[k]) for k in ("differ_share", "differ_max",
                                          "gap_max", "gap_p999")}}


def set_checks(s: dict, tol: dict, topk: int) -> dict:
    return {
        f"every query t of every full layer ({s['layers']}) holds exactly "
        f"min({topk}, t + 1) keys": s["sized"],
        f"no set holds a key after its query (such pairs: {s['late']})":
            s["late"] == 0,
        f"every layer attended over the set of the full layer at or before "
        f"it (layers that did not: {s['misled'] or 'none'})":
            s["followed"] and not s["misled"],
        f"sets: where program and reference differ, the reference's index "
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
    import jax.numpy as jnp
    import optax

    from benchmark import model_glm52, reference_glm52, trace_reduce
    from ray_tpu.core import compile_cache
    from ray_tpu.models import latent
    from ray_tpu.parallel.train_step import (batch_sharding, hold_out,
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
    cfg = model_glm52.latent_config(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "gmm_impl", "remat", "f32_logits") if k in recipe})
    sizes = model_glm52.sizes(cell["config"])
    mesh, rules = session.get_mesh(), session.get_rules()
    if recipe["optimizer"] != "adafactor":
        raise ValueError(f"unknown optimizer {recipe['optimizer']!r}")
    # the routers' biases are the rule's (latent.post_update), not adafactor's
    opt = hold_out(optax.adafactor(recipe["lr"]), latent.RULE_LEAVES)
    init_fn, state_sh = make_train_state_init(
        lambda k: latent.init_params(k, cfg), opt, mesh, rules,
        latent.param_specs(cfg))
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
        lambda p, b: latent.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        opt, mesh, rules, state_sh, batch_shapes=shapes,
        post_update=lambda p, aux: latent.post_update(p, aux, cfg))
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

    # the plain reference on the first batch and the program's sets and
    # routes, before the step donates the state, and again after the
    # step's first update; the second warm step runs on the same batch, so
    # the program's loss there is known too
    program_nll, reference_nll = token_loss_fns(cfg, sizes, mesh, rules)
    placed = None
    dep = cell["config"]["deployment"]
    if dep.get("placement") == "balanced":
        # on batches of their own (none of them the window's), as kind
        # ``train_parallel`` places: with ONE sequence a batch a group's
        # share of the routes swings from batch to batch
        seen = [make_batch(10_000 + j)["tokens"]
                for j in range(dep.get("placement_batches", 1))]
        params, placed = place_experts(
            state.params, lambda p: jnp.concatenate(
                [program_nll(p, t)[1] for t in seen], axis=1), sizes)
        state = state._replace(params=params)
        jax.block_until_ready(state)
        marks.append(("experts placed by load", time.time()))
    t0 = time.perf_counter()
    got, routes, own_counts, sets, index_got, attended = program_nll(
        state.params, batch["tokens"])
    ref, ref_total, rec = reference_nll(state.params, batch["tokens"],
                                        routes, sets)
    agreement = {**loss_agreement(got, ref), "ref_loss": float(ref_total),
                 "ref_ce": float(ref.mean()),
                 "ref_balance": float(rec["balance_mean"])}
    routing = route_agreement(routes, rec, cfg.top_k)
    selecting = set_agreement(sets, rec, cfg.index_topk, attended,
                              cfg.index_full)
    index = {"program": [float(x) for x in index_got],
             "reference": [float(x) for x in rec["index_loss"]]}
    bias_before = jax.device_get(reference_glm52.biases(state.params))
    counts = jax.device_get((own_counts, rec["own_counts"]))
    del got, ref, rec, sets
    losses, stats = [], []

    def fetch(m):
        host = jax.device_get(m)         # host fetch: the step is done
        return float(host["loss"]), {
            k: float(v) for k, v in host.items()
            if k.startswith(("moe_", "index_"))}

    for i in range(2):                    # the two warm steps
        if i == 1:
            bias = bias_agreement(
                jax.device_get(reference_glm52.biases(state.params)),
                bias_before, *counts, sizes)
            _, routes, _, sets, _, _ = program_nll(state.params,
                                                   batch["tokens"])
            # the sets were compared on the first pass: no sort here
            ref_loss_updated = float(reference_nll(
                state.params, batch["tokens"], routes, sets, False)[1])
            del routes, sets
            reference_s = time.perf_counter() - t0
            marks.append(("checked against the reference", time.time()))
        state, m = compiled(state, batch)
        loss, moe_stats = fetch(m)
        losses.append(loss)
        stats.append(moe_stats)
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
        loss, moe_stats = fetch(m)
        t_step = time.perf_counter()
        session.report({"loss": loss, "step": i, **moe_stats})
        t_rep = time.perf_counter()
        losses.append(loss)
        stats.append(moe_stats)
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

    def over(name):
        return [s[name] for s in stats]

    out = {
        "device": device, "program": program, "peak_bytes": peaks,
        "first_loss": losses[0], "second_loss": losses[1],
        "first_main_loss": stats[0]["moe_main_loss"],
        "agreement": agreement, "routing": routing, "selecting": selecting,
        "index": index, "bias": bias, "ref_loss_updated": ref_loss_updated,
        "losses_head": losses[:6], "last_loss": losses[-1],
        "all_finite": all(math.isfinite(x) for x in losses),
        "dropped_max": max(over("moe_dropped")),
        "placed": placed and [[float(x) for x in side] for side in placed],
        "load_max_over_mean": (stats[0]["moe_load_max_over_mean"],
                               max(over("moe_load_max_over_mean"))),
        # the share of the assignments that the held experts got: first
        # step, least, most, and the mean over the window's steps
        "held_rows_share": (stats[0]["moe_held_rows_share"],
                            min(over("moe_held_rows_share")),
                            max(over("moe_held_rows_share")),
                            statistics.fmean(over("moe_held_rows_share")[2:])),
        # passes beyond the first over the held experts' rows, all layers
        # of a step: (steps that took any, most in a step, which steps)
        "more_passes": (
            sum(x > 0 for x in over("moe_held_more_passes")),
            max(over("moe_held_more_passes")),
            [n - 2 for n, x in enumerate(over("moe_held_more_passes"))
             if x > 0][:20]),
        "terms_first": (stats[0]["moe_main_loss"], stats[0]["moe_aux_loss"],
                        stats[0]["index_loss"]),
        # the selection's report: first step and last
        "index_first": {k: v for k, v in stats[0].items()
                        if k.startswith("index_")},
        "index_last": {k: v for k, v in stats[-1].items()
                       if k.startswith("index_")},
        "bias_moved_first": stats[0]["moe_bias_moved"],
        "bias_abs_max_last": stats[-1]["moe_bias_abs_max"],
        "vocab": V, "top_k": cfg.top_k, "index_topk": cfg.index_topk,
        "steps": steps, "elapsed_s": elapsed,
        "tokens_per_step": B * S, "window_start": window_start,
        "reference_s": reference_s, "setup_marks": marks,
        "step_ms_median": statistics.median(step_s) * 1e3,
        "report_ms_median": statistics.median(report_s) * 1e3,
        # a stall shows here and not in the medians: (ms, which step)
        "longest_step": max((t * 1e3, n) for n, t in enumerate(step_s)),
        "longest_report": max((t * 1e3, n) for n, t in enumerate(report_s)),
        # every step longer than 1.1 x the median, for the account of a
        # window that did not stand still: (ms, which step)
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
    if importlib.util.find_spec("ray_tpu.models.reference_glm52") is None:
        # fail before a cluster starts: a program without the learned
        # selection cannot run this kind
        raise ctx["Refused"]("this program has no ray_tpu/models/"
                             "reference_glm52.py: its latent attention has "
                             "no learned selection for this kind to train")
    import ray_tpu
    from ray_tpu.core.node import detect_tpu_chips
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark import flops_glm52, model_glm52

    log = ctx["log"]
    recipe, chips = cell["train"], cell["chips"]
    want_tpu = not cell.get("rehearsal", False)
    found = detect_tpu_chips()
    if want_tpu and found < chips:
        raise ctx["Refused"](f"this host shows {found} TPU chip(s), the "
                             f"cell needs {chips}")
    sizes = model_glm52.sizes(cell["config"])
    stacks = model_glm52.stacks(sizes)
    seq = cell["mix"]["seq"]
    log(f"train_sparse: JaxTrainer(1 worker x {chips} chip(s)), mesh "
        f"{recipe['mesh']}, rules {recipe['rules']}, "
        f"B{cell['mix']['batch']} x S{seq} (+1 id), stacks {stacks}, "
        f"{sizes['n_heads']} heads of "
        f"{sizes['qk_nope_dim']}+{sizes['qk_rope_dim']} over latents "
        f"{sizes['q_rank']}/{sizes['kv_rank']}, an indexer of "
        f"{sizes['index_heads']} heads of {sizes['index_dim']} keeping "
        f"{sizes['index_topk']} keys a query, "
        f"{sizes['experts_held'][0]} of {sizes['n_experts']} experts held, "
        f"{sizes['top_k']} a token")
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
    device, prog, a, r, b, sel = (m["device"], m["program"], m["agreement"],
                                  m["routing"], m["bias"], m["selecting"])
    tol = recipe["check"]
    tok_s_chip = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] \
        / device["count"]
    per_token = flops_glm52.train_flops_per_token(sizes, seq)
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
    stalls = stall_lines(os.environ.get("RAY_TPU_TMPDIR", ""))
    log(f"  steps over 1.1 x the median: "
        f"{[(round(ms, 1), n) for ms, n in m['long_steps']] or 'none'}; "
        f"stall lines in the workers' logs: {len(stalls)}")
    for ln in stalls:
        log("    " + ln)
    more = m["more_passes"]
    log(f"  further passes over the held experts' rows: in {more[0]} of "
        f"{m['steps'] + 2} steps (the two warm ones counted, steps -2 and "
        f"-1), at most {more[1]:g} in a step; the steps that took any: "
        f"{more[2] or 'none'}")
    if m.get("placed"):
        log("  experts placed by load (deployment.placement balanced): a "
            "layer's held share of the placement's batches before "
            f"{[round(x, 4) for x in m['placed'][0]]}, after "
            f"{[round(x, 4) for x in m['placed'][1]]}")
    share, terms = m["held_rows_share"], m["terms_first"]
    log(f"  routing: the held experts got {share[0]:.4f} of the "
        f"assignments in the first step, {share[1]:.4f} to {share[2]:.4f} "
        f"over all steps (even: "
        f"{sizes['experts_held'][0] / sizes['n_experts']:.4f}); largest "
        f"held expert over their mean, first step "
        f"{m['load_max_over_mean'][0]:.4f}, worst step "
        f"{m['load_max_over_mean'][1]:.4f}; route gap 99.9th percentile "
        f"{r['gap_p999']:.2e}, max {r['gap_max']:.2e}")
    log(f"  selection: first step {m['index_first']}, last step "
        f"{m['index_last']}; each full layer's LI on the first batch, "
        f"program {m['index']['program']}, reference on the program's sets "
        f"{m['index']['reference']}")
    log(f"  first step's terms: main {terms[0]:.5f} (reference "
        f"{a['ref_ce']:.5f}), balance {terms[1]:.5f} (reference "
        f"{a['ref_balance']:.5f}), the indexers' {terms[2]:.5f}; the rule "
        f"moved {m['bias_moved_first']:g} "
        f"biases in the first step ({b['moved']} by the parameters), "
        f"largest bias after the last step {m['bias_abs_max_last']:.4f}; "
        f"counts of the program's forward and the reference's at most "
        f"{b['counts_apart_max']:g} apart")
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
    want = math.log(m["vocab"]) + 0.5
    apart = max(abs(x - y) for x, y in zip(m["index"]["program"],
                                           m["index"]["reference"]))
    checks = {
        **set_checks(sel, tol, m["index_topk"]),
        **route_checks(r, tol, m["top_k"]),
        **loss_checks(m, tol),
        f"each full layer's LI: largest |program - reference on the "
        f"program's sets| {apart:.5f} <= {tol['index_loss_abs']}":
            apart <= tol["index_loss_abs"],
        f"the biases after the first update are the reference's rule's at "
        f"every decided count ({100 * b['decided_share']:.1f}% of them >= "
        f"{100 * tol['bias_decided_share']}%; wrong: {b['wrong']})":
            b["wrong"] == 0
            and b["decided_share"] >= tol["bias_decided_share"],
        f"first main loss within {FIRST_LOSS_TOL} of ln(V)+0.5 = {want:.4f}":
            abs(m["first_main_loss"] - want) < FIRST_LOSS_TOL,
        "all losses finite": m["all_finite"],
        f"no assignment to a held expert dropped in any step (most: "
        f"{m['dropped_max']:g})": m["dropped_max"] == 0,
        f"ran on {chips} device(s)": device["count"] == chips or not want_tpu,
    }
    if device["platform"] == "tpu" and recipe.get("attn_impl") == "flash" \
            and recipe.get("gmm_impl") == "pallas":
        # a stack of layers is one scanned body: the sparse attention's
        # forward, dQ and dK/dV (its forward again in the replay), the
        # head-mean probabilities of a full layer, the grouped matmuls of
        # a sparse one
        least = 4 * len(stacks)
        checks[f"the step program holds the Pallas calls of its "
               f"{len(stacks)} stacks of layers ({prog['pallas_calls']} >= "
               f"{least})"] = prog["pallas_calls"] >= least
    tokens = m["tokens_per_step"]
    return {
        "checks": checks, "attempted": m["steps"], "failed": 0,
        "device": {**device, "memory_peak_bytes": max(
            [prog["plan_bytes"]] + m["peak_bytes"])},
        "window_start": m["window_start"],
        "end_to_end": {"train_tok_s_chip": tok_s_chip},
        "obs": {"counters": {"compiles_in_window": m["compiles_in_window"]},
                "values": {"train_step_ms": m["step_ms_median"],
                           "train_report_ms": m["report_ms_median"],
                           # rows the held experts got, a layer and step
                           "held_rows": share[3] * tokens * sizes["top_k"]},
                "trace": m.get("trace"), "sizes": sizes, "cell": cell},
    }
