"""Kind ``train_solar``: kind ``train_kda`` (tokens trained per chip-second
in the user's loop under ``JaxTrainer``; its recipe, set-up marks, rate,
loop records and ``obs``) for Solar Open2's block of
``ray_tpu/models/solar.py``: three layers whose first half is Kimi Delta
Attention with a gate that has NO lower bound and beta in (0, 2) (the
chunked gated delta rule of ``ops/delta_rule.py`` on the cut of its pair
products that needs no bound) to one of grouped-query attention with no
position table and an elementwise gate, every layer with sigmoid-routed
experts of one group and a shared expert. What decides ``correct`` is kind
``train_kda``'s five parts against ``reference_solar.py`` (which advances
the KDA state a step at a time) given the same share of the experts and of
the vocabulary:

(a) Routes. The program's K experts (of the router's scores plus bias) of
    every token and expert layer of the first batch against the
    reference's own (``train_moe.route_agreement``, ``route_checks``).
(b) Numbers. The reference evaluated on the PROGRAM's routes: per-token
    losses, step loss before and after the first update (cross-entropy
    plus the sequence-wise balance term) and the descent of the
    reference's loss (``train.loss_checks``).
(c) The rule. The routers' biases after the first update against the
    reference's rule at every decided count
    (``train_latent.bias_agreement``).
(d) ``moe_dropped`` is 0 in every step, the step program holds its Pallas
    calls (the delta rule's two among them), every loss is finite, the
    first loss lies in its band, and the first KDA layer's gate really
    left the old kernel's bound: the least g of every step, and the share
    of (step, channel) pairs of the first batch under -5 and under -11.
(e) The kernel pair alone (``train_kda.op_agreement``). After the window,
    on the first KDA layer's own q, k, v, g and beta of the first batch at
    the timed shape (``scan_inputs``: layer 1's, behind the grouped-query
    layer): the delta rule's two Mosaic calls, told no bound, against the
    op's plain path in float32, the output and all five gradients, in the
    timed type and on the same values in float32.

The weights are ``seeded_weights``: the program's own initial values but
for what the comparison could not otherwise see (the KDA gate's bias drawn
about 0, so that the softplus gate reaches far under the old bound channel
by channel and step by step; the output norms' scales and the routers'
biases drawn about their initial values). Which experts the chip holds is
the deployment's to say (``deployment.placement`` ``balanced``:
``train_alternating.place_experts``). The limits are the cell's
``train.check``; measured values and their origin: PERF.md 4.
"""

from __future__ import annotations

import importlib.util
import math
import os
import statistics
import time

from benchmark.kinds.train import FIRST_LOSS_TOL, loss_agreement, loss_checks
from benchmark.kinds.train_alternating import place_experts
from benchmark.kinds.train_hybrid import stall_lines
from benchmark.kinds.train_kda import (GATE_BIAS_SIGMA, HEAD_NORM_SIGMA,
                                       ROUTER_BIAS_SIGMA, op_agreement)
from benchmark.kinds.train_latent import bias_agreement
from benchmark.kinds.train_moe import route_agreement, route_checks

# the old kernel's bound a step, and what its sub-blocks could not hold
OLD_BOUNDS = (-5.0, -11.0)


def seeded_weights(key, cfg):
    """The cell's weights from its seed: ``solar.init_params``, but for
    three leaves whose initial values hide what the comparison has to see,
    drawn as kind ``train_kda`` draws them. (1) ``dt_bias``: the source's
    draw (the inverse softplus of a step in [1e-3, 1e-1]) lies at -7 to
    -2.3, where softplus reads 0.001 to 0.1 and the gate -1.6 to 0: inside
    the OLD kernel's bound, so a clamp at -5 would read as the right
    program. Drawn normal about 0 (deviation ``GATE_BIAS_SIGMA``) the gate
    is -exp(A_log) softplus(N(0, 2)): a rate of 1 to 16 times 0.1 to 3, far
    under -5 and -11 in whole heads. (2) ``o_norm``: log-normal about 1.
    (3) ``router_bias``: normal, so that the choice by score PLUS bias and
    the weights WITHOUT it differ from the first step on."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import solar

    params = solar.init_params(key, cfg)

    def seeded(i, stack):
        out = dict(stack)
        for j, (name, draw) in enumerate((
                ("dt_bias", lambda z: GATE_BIAS_SIGMA * z),
                ("o_norm", lambda z: jnp.exp(HEAD_NORM_SIGMA * z)),
                ("router_bias", lambda z: ROUTER_BIAS_SIGMA * z))):
            if name in stack:
                z = jax.random.normal(
                    jax.random.fold_in(key, 7919 + 3 * i + j),
                    stack[name].shape, jnp.float32)
                out[name] = draw(z).astype(stack[name].dtype)
        return out

    return {**params, "layers": [seeded(i, s) for i, s in enumerate(
        params["layers"])]}


def token_loss_fns(cfg, sizes: dict, mesh=None, rules=None) -> tuple:
    """``(program, reference)``. program: tokens [B, S+1] -> (every
    position's loss [B, S] float32, routes [L, B, S, K], counts [L, E])
    through the program's own forward. reference: (params, tokens, routes)
    -> (losses [B, S], total loss with the balance term, record) through
    the plain reference on those routes. Each is one jitted program."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_solar
    from ray_tpu.models import solar

    def program(p, t):
        logits, stats = solar.forward_with_stats(p, t[:, :-1], cfg,
                                                 mesh=mesh, rules=rules)
        picked = jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) \
            - picked.astype(jnp.float32)
        b, s = nll.shape
        experts = stats["experts"]                        # [L, B*S, K]
        return (nll, experts.reshape(experts.shape[0], b, s, -1),
                stats["counts"])

    def reference(p, t, routes):
        nll, rec = reference_solar.token_losses(p, t, sizes, routes)
        balance = rec["balance"].mean()
        total = nll.mean() + sizes["router_aux_weight"] * balance
        # how many assignments the reference's OWN choice gives each
        # expert, an expert layer [L, E]: what its rule moves the biases by
        own = jax.vmap(lambda e: jnp.bincount(
            e.reshape(-1), length=sizes["n_experts"]))(rec["experts"])
        return nll, total, {"aux": balance, "own_counts": own, **rec}

    return jax.jit(program), jax.jit(reference)


def scan_inputs(cfg, params, tokens):
    """What the first KDA layer's scan takes of tokens [B, S + 1]: layer 1
    (``model_solar.sizes`` holds the cut to it), whose input is layer 0's
    output, the grouped-query layer with its experts run as the program
    runs it: q, k, v, g [B, S, H, dk] and beta [B, S, H] as
    ``solar.scan_inputs`` makes them, q, k, v in the run's type, g and beta
    float32."""
    import jax

    from ray_tpu.models import llama, solar

    first, second = (jax.tree.map(lambda w: w[0], params["layers"][r])
                     for r in (0, 1))
    x = llama._embed(params, tokens[:, :-1], cfg.dtype)
    x, _, _ = llama._layer(x, first, cfg, None, None, kind="gqa")
    h = llama.rms_norm(x, second["attn_norm"], cfg.norm_eps)
    *wide, beta = solar.scan_inputs(h, second, cfg)
    return (*(t.reshape(*t.shape[:2], cfg.kda_heads, -1) for t in wide), beta)


def train_loop(config: dict) -> None:
    import jax
    import optax

    from benchmark import model_solar, reference_solar, trace_reduce
    from ray_tpu.core import compile_cache
    from ray_tpu.models import solar
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
    cfg = model_solar.solar_config(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "gmm_impl", "kda_impl", "remat", "f32_logits")
            if k in recipe})
    sizes = model_solar.sizes(cell["config"])
    mesh, rules = session.get_mesh(), session.get_rules()
    if recipe["optimizer"] != "adafactor":
        raise ValueError(f"unknown optimizer {recipe['optimizer']!r}")
    # the routers' biases are the rule's (moe.post_update), not adafactor's
    opt = hold_out(optax.adafactor(recipe["lr"]), solar.RULE_LEAVES)
    init_fn, state_sh = make_train_state_init(
        lambda k: seeded_weights(k, cfg), opt, mesh, rules,
        solar.param_specs(cfg))
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
        lambda p, b: solar.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        opt, mesh, rules, state_sh, batch_shapes=shapes,
        post_update=lambda p, aux: solar.post_update(p, aux, cfg))
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
               "pallas_calls": text.count("tpu_custom_call"),
               # the loops XLA compiled: a run's scan forward and backward
               "while_loops": text.count(" while(")}
    del text
    marks.append(("step program compiled or loaded", time.time()))

    # the plain reference on the first batch and the program's routes,
    # before the step donates the state, and again after the step's first
    # update; the second warm step runs on the same batch, so the
    # program's loss there is known too
    program_nll, reference_nll = token_loss_fns(cfg, sizes, mesh, rules)
    placed = None
    if cell["config"]["deployment"].get("placement") == "balanced":
        params, placed = place_experts(
            state.params, lambda p: program_nll(p, batch["tokens"])[1],
            sizes)
        state = state._replace(params=params)
        jax.block_until_ready(state)
        marks.append(("experts placed by load", time.time()))
    t0 = time.perf_counter()
    got, routes, own_counts = program_nll(state.params, batch["tokens"])
    ref, ref_total, rec = reference_nll(state.params, batch["tokens"], routes)
    agreement = {**loss_agreement(got, ref), "ref_loss": float(ref_total),
                 "ref_ce": float(ref.mean()), "ref_aux": float(rec["aux"])}
    routing = route_agreement(routes, rec, cfg.top_k)
    bias_before = jax.device_get(reference_solar.biases(state.params))
    counts = jax.device_get((own_counts, rec["own_counts"]))
    del got, ref, rec
    losses, stats = [], []

    def fetch(m):
        host = jax.device_get(m)         # host fetch: the step is done
        return float(host["loss"]), {
            k: float(v) for k, v in host.items()
            if k.startswith(("moe_", "kda_"))}

    for i in range(2):                    # the two warm steps
        if i == 1:
            bias = bias_agreement(
                jax.device_get(reference_solar.biases(state.params)),
                bias_before, *counts, sizes)
            _, routes, _ = program_nll(state.params, batch["tokens"])
            ref_loss_updated = float(reference_nll(
                state.params, batch["tokens"], routes)[1])
            del routes
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
    # (e) the kernel pair alone, at the timed shape, on the first batch's
    # own scan inputs under the trained weights; the state goes first, the
    # plain path's float32 forms take its room
    t0 = time.perf_counter()
    op_in = jax.jit(lambda p, t: scan_inputs(cfg, p, t))(state.params,
                                                         batch["tokens"])
    jax.block_until_ready(op_in)
    del state
    import jax.numpy as jnp
    from ray_tpu.ops.delta_rule import gated_delta_rule
    under = [float(jnp.mean(op_in[3] < bound)) for bound in OLD_BOUNDS]
    read = op_agreement(
        op_in, jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                                 op_in[2].shape, jnp.float32), None)
    kernel = lambda *a: gated_delta_rule(                       # noqa: E731
        *a, impl=cfg.kda_impl, lower_bound=None)
    op = {"timed": read(kernel, cfg.dtype)}
    if jnp.dtype(cfg.dtype) != jnp.float32:
        op["float32"] = read(kernel, jnp.float32)
    op_s = time.perf_counter() - t0
    del op_in, read
    out = {
        "op": op, "op_s": op_s, "gate_under": under,
        "gate_min": (stats[0]["kda_gate_min"],
                     max(s["kda_gate_min"] for s in stats)),
        "device": device, "program": program, "peak_bytes": peaks,
        "first_loss": losses[0], "second_loss": losses[1],
        "agreement": agreement, "routing": routing, "bias": bias,
        "bias_moved_first": stats[0]["moe_bias_moved"],
        "bias_abs_max_last": stats[-1]["moe_bias_abs_max"],
        "ref_loss_updated": ref_loss_updated,
        "losses_head": losses[:6], "last_loss": losses[-1],
        "all_finite": all(math.isfinite(x) for x in losses),
        "dropped_max": max(s["moe_dropped"] for s in stats),
        "placed": placed and [[float(x) for x in side] for side in placed],
        "load_max_over_mean": (stats[0]["moe_load_max_over_mean"], max(
            s["moe_load_max_over_mean"] for s in stats)),
        # the share of the assignments that the held experts got: first
        # step, least, most, and the mean over the window's steps
        "held_rows_share": (stats[0]["moe_held_rows_share"],
                            min(s["moe_held_rows_share"] for s in stats),
                            max(s["moe_held_rows_share"] for s in stats),
                            statistics.fmean(s["moe_held_rows_share"]
                                             for s in stats[2:])),
        # passes beyond the first over the held experts' rows, all layers
        # of a step: (steps that took any, most in a step, which steps)
        "more_passes": (
            sum(s.get("moe_held_more_passes", 0) > 0 for s in stats),
            max(s.get("moe_held_more_passes", 0) for s in stats),
            [n - 2 for n, s in enumerate(stats)
             if s.get("moe_held_more_passes", 0) > 0][:20]),
        "router_losses_first": (stats[0]["moe_aux_loss"], 0.0),

        "vocab": V, "top_k": cfg.top_k,
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
    # fail before a cluster starts: a program without the family cannot
    # run this kind (the parent commit's)
    if importlib.util.find_spec("ray_tpu.models.solar") is None:
        raise ctx["Refused"]("this program has no ray_tpu/models/solar.py: "
                             "it has no Kimi Delta Attention with an "
                             "unbounded gate for this kind to train")
    import ray_tpu
    from ray_tpu.core.node import detect_tpu_chips
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark import flops_solar, model_solar

    log = ctx["log"]
    recipe, chips = cell["train"], cell["chips"]
    want_tpu = not cell.get("rehearsal", False)
    found = detect_tpu_chips()
    if want_tpu and found < chips:
        raise ctx["Refused"](f"this host shows {found} TPU chip(s), the "
                             f"cell needs {chips}")
    sizes = model_solar.sizes(cell["config"])
    seq = cell["mix"]["seq"]
    log(f"train_solar: JaxTrainer(1 worker x {chips} chip(s)), mesh "
        f"{recipe['mesh']}, rules {recipe['rules']}, "
        f"B{cell['mix']['batch']} x S{seq}, layers "
        f"{' '.join(sizes['kinds'])}, {sizes['kda_heads']} heads of "
        f"{sizes['kda_head_dim']} (KDA, pairs of rank {sizes['gate_rank']}) "
        f"and {sizes['n_heads']} over {sizes['n_kv_heads']} of "
        f"{sizes['head_width']} (GQA, no table), "
        f"{sizes['experts_held'][0]} of {sizes['n_experts']} experts held "
        f"from {sizes['experts_held'][1]}, {sizes['top_k']} a token")
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
    device, prog, a, r, b = (m["device"], m["program"], m["agreement"],
                             m["routing"], m["bias"])
    tol = recipe["check"]
    tok_s_chip = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] \
        / device["count"]
    per_token = flops_solar.train_flops_per_token(sizes, seq)
    log(f"  device {device}; step program: compile "
        f"{prog['compile_s']:.1f} s, plan {prog['plan_bytes']} bytes "
        f"a device (arguments {prog['argument_bytes']}, temporaries "
        f"{prog['temp_bytes']}), {prog['pallas_calls']} Pallas calls, "
        f"{prog['while_loops']} while loops")
    log(f"  {m['steps']} steps of {m['tokens_per_step']} tokens in "
        f"{m['elapsed_s']:.3f} s; step median "
        f"{m['step_ms_median']:.2f} ms, report median "
        f"{m['report_ms_median']:.3f} ms; longest step "
        f"{m['longest_step'][0]:.1f} ms (step {m['longest_step'][1]}), "
        f"longest report {m['longest_report'][0]:.3f} ms (step "
        f"{m['longest_report'][1]}); losses {m['losses_head']} "
        f"... {m['last_loss']:.4f}; reference pass "
        f"{m['reference_s']:.1f} s")
    log(f"  the kernel pair alone against the plain path took "
        f"{m['op_s']:.1f} s after the window; the first KDA layer's gate: "
        f"least g {m['gate_min'][0]:.2f} in the first step, "
        f"{m['gate_min'][1]:.2f} at its mildest step; "
        + ", ".join(f"{100 * s:.2f}% of (step, channel) pairs under {bound:g}"
                    for s, bound in zip(m["gate_under"], OLD_BOUNDS)))
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
            "layer's held share of the first batch before "
            f"{[round(x, 4) for x in m['placed'][0]]}, after "
            f"{[round(x, 4) for x in m['placed'][1]]}")
    share = m["held_rows_share"]
    log(f"  routing: the held experts got {share[0]:.4f} of the "
        f"assignments in the first step, {share[1]:.4f} to {share[2]:.4f} "
        f"over all steps (even: "
        f"{sizes['experts_held'][0] / sizes['n_experts']:.4f}); largest "
        f"held expert over their mean, first step "
        f"{m['load_max_over_mean'][0]:.4f}, worst step "
        f"{m['load_max_over_mean'][1]:.4f}; first step's load-balancing "
        f"loss {m['router_losses_first'][0]:.5f} (reference "
        f"{a['ref_aux']:.5f}); the rule moved {m['bias_moved_first']:g} "
        f"biases in the first step ({b['moved']} by the parameters), "
        f"largest bias after the last step {m['bias_abs_max_last']:.4f}; "
        f"reference cross-entropy "
        f"{a['ref_ce']:.5f}; route gap 99.9th percentile "
        f"{r['gap_p999']:.2e}, max {r['gap_max']:.2e}")
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
    want = math.log(m["vocab"]) + tol.get("first_loss_over_ln_v", 0.5)
    checks = {
        **route_checks(r, tol, m["top_k"]),
        **loss_checks(m, tol),
        f"the biases after the first update are the reference's rule's at "
        f"every decided count ({100 * b['decided_share']:.1f}% of them >= "
        f"{100 * tol['bias_decided_share']}%; wrong: {b['wrong']})":
            b["wrong"] == 0
            and b["decided_share"] >= tol["bias_decided_share"],
        f"first loss within {FIRST_LOSS_TOL} of ln(V) = {want:.4f}":
            abs(m["first_loss"] - want) < FIRST_LOSS_TOL,
        f"the first KDA layer's gate left the old bound: "
        f"{100 * m['gate_under'][0]:.2f}% of its (step, channel) pairs "
        f"under {OLD_BOUNDS[0]:g} (>= {100 * tol['gate_under_old_bound']}%), "
        f"its least g in every step under {OLD_BOUNDS[1]:g} (mildest "
        f"{m['gate_min'][1]:.2f})":
            m["gate_under"][0] >= tol["gate_under_old_bound"]
            and m["gate_min"][1] < OLD_BOUNDS[1],
        **{f"the delta rule's calls alone, {which}: the output and five "
           f"gradients within {tol['op_rel_' + which]} of the plain path "
           f"in float32 (relative L2: " + ", ".join(
               f"{k} {v:.2e}" for k, v in read.items()) + ")":
           max(read.values()) <= tol["op_rel_" + which]
           for which, read in m["op"].items()},
        "all losses finite": m["all_finite"],
        f"no assignment to a held expert dropped in any step (most: "
        f"{m['dropped_max']:g})": m["dropped_max"] == 0,
        f"ran on {chips} device(s)": device["count"] == chips or not want_tpu,
    }
    if device["platform"] == "tpu" and recipe.get("attn_impl") == "flash" \
            and recipe.get("gmm_impl") == "pallas" \
            and recipe.get("kda_impl") == "pallas":
        # the delta rule's forward, replayed forward and backward in the
        # KDA run's bodies; flash forward, dq, dkdv in the grouped-query
        # layer's; the grouped matmuls of both runs' expert layers
        checks["the step program holds the Pallas calls"] = \
            prog["pallas_calls"] >= 20
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
                "trace": m.get("trace"),
                "sizes": sizes,
                "cell": cell},
    }
