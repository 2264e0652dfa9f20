"""Kind ``train``: tokens trained per chip-second in the user's loop under
``JaxTrainer``, the way ``chip_smoke.py`` reaches the chip:
``ray_tpu.init -> JaxTrainer(1 worker x N chips) -> loop``.

The parent (``run``) never touches jax. The loop runs in the worker that
holds the chips: it builds the model on the device from the seed, compiles
the step ahead of time, checks the first batch's loss against the plain
reference, warms up, then steps for ``seconds`` and reports.
"""

from __future__ import annotations

import math
import os
import statistics
import time

# What decides ``correct`` (measured values and their origin: PERF.md 4).
# The program computes in the cell's types (bf16 with bf16 logits and a
# flash kernel), the reference in float32 throughout. The limits are the
# cell's own, ``train.check`` in its workload file, a few times the
# agreement measured on the chip at its depth:
#
# token_mean_abs, token_p999_abs: every position's loss of the first
#   batch, from the program's own forward (the step's configuration:
#   kernel, remat, types) against the reference's: mean and 99.9th
#   percentile of the difference. With random weights a token's loss is
#   logsumexp less its own logit, a projection of the last hidden state,
#   so the vector follows every layer: a dropped layer, a wrong mask, a
#   wrong rotary base or 8-bit weights move it by many times the rounding
#   of bf16 (tests/test_correct.py), where the MEAN moves by thousandths.
# step_loss_abs: the step program's loss against the reference's mean,
#   before and after the step's first update.
# min_descent: the least by which that update must lower the REFERENCE's
#   loss on the batch it was made from (the backward pass and the
#   optimizer: a wrong sign raises it, a gradient of noise leaves it).
FIRST_LOSS_TOL = 0.35   # random init: logits ~N(0,1), so ln(V) + 1/2


def token_loss_fns(cfg, sizes: dict, mesh=None, rules=None) -> tuple:
    """``(program, reference)``: tokens [B, S+1] -> every position's loss
    [B, S] in float32, through the program's own forward and through the
    plain reference. Each is one jitted program."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference
    from ray_tpu.models import llama

    def program(p, t):
        logits = llama.forward(p, t[:, :-1], cfg, mesh=mesh, rules=rules)
        picked = jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) \
            - picked.astype(jnp.float32)

    return jax.jit(program), jax.jit(
        lambda p, t: reference.token_losses(p, t, sizes))


def loss_agreement(got, ref) -> dict:
    """Both means, and how far the per-token losses lie apart."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(got, ref):
        d = jnp.abs(got - ref).reshape(-1)
        return {"program_loss": got.mean(), "ref_loss": ref.mean(),
                "token_mean_abs": d.mean(),
                "token_p999_abs": jnp.percentile(d, 99.9),
                "token_max_abs": d.max()}

    return {k: float(v) for k, v in stats(got, ref).items()}


def loss_checks(m: dict, tol: dict) -> dict:
    """``m``: the worker's ``agreement`` (before the first update),
    ``first_loss``/``second_loss`` (the step program on the first batch,
    before and after its first update) and ``ref_loss_updated``; ``tol``:
    the cell's ``train.check``."""
    a, step_tol = m["agreement"], tol["step_loss_abs"]
    fell = a["ref_loss"] - m["ref_loss_updated"]
    return {
        f"per-token loss: mean |program - reference| {a['token_mean_abs']:.5f}"
        f" <= {tol['token_mean_abs']}":
            a["token_mean_abs"] <= tol["token_mean_abs"],
        f"per-token loss: 99.9th percentile {a['token_p999_abs']:.5f} <= "
        f"{tol['token_p999_abs']} (max {a['token_max_abs']:.4f})":
            a["token_p999_abs"] <= tol["token_p999_abs"],
        f"step loss {m['first_loss']:.5f} within {step_tol} of the "
        f"reference {a['ref_loss']:.5f}":
            abs(m["first_loss"] - a["ref_loss"]) <= step_tol,
        f"after the first update, step loss {m['second_loss']:.5f} within "
        f"{step_tol} of the reference {m['ref_loss_updated']:.5f}":
            abs(m["second_loss"] - m["ref_loss_updated"]) <= step_tol,
        f"the first update lowers the reference's loss on its batch by "
        f"{fell:.5f} >= {tol['min_descent']}": fell >= tol["min_descent"],
    }


def train_loop(config: dict) -> None:
    import jax
    import optax

    from benchmark import model, trace_reduce
    from ray_tpu.core import compile_cache
    from ray_tpu.models import llama
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
    cfg = model.llama_config(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "remat", "f32_logits") if k in recipe})
    sizes = model.sizes(cell["config"])
    mesh, rules = session.get_mesh(), session.get_rules()
    if recipe["optimizer"] != "adafactor":
        raise ValueError(f"unknown optimizer {recipe['optimizer']!r}")
    opt = optax.adafactor(recipe["lr"])
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), opt, mesh, rules,
        llama.param_specs(cfg))
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
        lambda p, b: llama.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
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
               "pallas_calls": text.count("tpu_custom_call"),
               "collectives": {c: text.count(c + "(") + text.count(
                   c + "-start(") for c in (
                   "all-gather", "reduce-scatter", "all-reduce",
                   "all-to-all", "collective-permute")}}
    del text
    marks.append(("step program compiled or loaded", time.time()))

    # the plain reference on the first batch, before the step donates the
    # state, and again after the step's first update; the second warm step
    # runs on the same batch, so the program's loss there is known too
    t0 = time.perf_counter()
    program_nll, reference_nll = token_loss_fns(cfg, sizes, mesh, rules)
    agreement = loss_agreement(program_nll(state.params, batch["tokens"]),
                               reference_nll(state.params, batch["tokens"]))
    losses = []
    for i in range(2):                    # the two warm steps
        if i == 1:
            ref_loss_updated = float(
                reference_nll(state.params, batch["tokens"]).mean())
            reference_s = time.perf_counter() - t0
            marks.append(("checked against the reference", time.time()))
        state, m = compiled(state, batch)
        losses.append(float(m["loss"]))
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
        loss = float(m["loss"])          # host fetch: the step is done
        t_step = time.perf_counter()
        session.report({"loss": loss, "step": i})
        t_rep = time.perf_counter()
        losses.append(loss)
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
        "agreement": agreement, "ref_loss_updated": ref_loss_updated,
        "losses_head": losses[:6], "last_loss": losses[-1],
        "all_finite": all(math.isfinite(x) for x in losses),
        "vocab": V, "dtype": str(jax.numpy.dtype(cfg.dtype)),
        "steps": steps, "elapsed_s": elapsed,
        "tokens_per_step": B * S, "window_start": window_start,
        "reference_s": reference_s, "setup_marks": marks,
        "step_ms_median": statistics.median(step_s) * 1e3,
        "report_ms_median": statistics.median(report_s) * 1e3,
        # a stall shows here and not in the medians: (ms, which step)
        "longest_step": max((t * 1e3, n) for n, t in enumerate(step_s)),
        "longest_report": max((t * 1e3, n) for n, t in enumerate(report_s)),
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
    import ray_tpu
    from ray_tpu.core.node import detect_tpu_chips
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark import flops, model

    log = ctx["log"]
    recipe, chips = cell["train"], cell["chips"]
    want_tpu = not cell.get("rehearsal", False)
    found = detect_tpu_chips()
    if want_tpu and found < chips:
        raise ctx["Refused"](f"this host shows {found} TPU chip(s), the "
                             f"cell needs {chips}")
    log(f"train: JaxTrainer(1 worker x {chips} chip(s)), mesh "
               f"{recipe['mesh']}, rules {recipe['rules']}, "
               f"B{cell['mix']['batch']} x S{cell['mix']['seq']}")
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
    device, prog = m["device"], m["program"]
    sizes = model.sizes(cell["config"])
    tok_s_chip = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] \
        / device["count"]
    per_token = flops.train_flops_per_token(sizes, cell["mix"]["seq"])
    log(f"  device {device}; step program: compile "
               f"{prog['compile_s']:.1f} s, plan {prog['plan_bytes']} bytes "
               f"a device (arguments {prog['argument_bytes']}, temporaries "
               f"{prog['temp_bytes']}), {prog['pallas_calls']} Pallas calls, "
               f"collectives {prog['collectives']}")
    log(f"  {m['steps']} steps of {m['tokens_per_step']} tokens in "
               f"{m['elapsed_s']:.3f} s; step median "
               f"{m['step_ms_median']:.2f} ms, report median "
               f"{m['report_ms_median']:.3f} ms; longest step "
               f"{m['longest_step'][0]:.1f} ms (step {m['longest_step'][1]}), "
               f"longest report {m['longest_report'][0]:.3f} ms (step "
               f"{m['longest_report'][1]}); losses {m['losses_head']} "
               f"... {m['last_loss']:.4f}; reference pass "
               f"{m['reference_s']:.1f} s")
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
    checks = {
        **loss_checks(m, recipe["check"]),
        f"first loss within {FIRST_LOSS_TOL} of ln(V)+0.5 = {want:.4f}":
            abs(m["first_loss"] - want) < FIRST_LOSS_TOL,
        "all losses finite": m["all_finite"],
        f"ran on {chips} device(s)": device["count"] == chips or not want_tpu,
    }
    if device["platform"] == "tpu" and recipe.get("attn_impl") == "flash":
        checks["the step program holds the Pallas calls"] = \
            prog["pallas_calls"] >= 3
    return {
        "checks": checks, "attempted": m["steps"], "failed": 0,
        "device": {**device, "memory_peak_bytes": max(
            [prog["plan_bytes"]] + m["peak_bytes"])},
        "window_start": m["window_start"],
        "end_to_end": {"train_tok_s_chip": tok_s_chip},
        "obs": {"counters": {"compiles_in_window": m["compiles_in_window"]},
                "values": {"train_step_ms": m["step_ms_median"],
                           "train_report_ms": m["report_ms_median"]},
                "trace": m.get("trace"), "sizes": sizes, "cell": cell},
    }
