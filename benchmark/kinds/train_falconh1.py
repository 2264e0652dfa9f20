"""Kind ``train_falconh1``: kind ``train`` (tokens trained per chip-second in
the user's loop under ``JaxTrainer``; its recipe, set-up marks, rate, loop
records and ``obs``) for Falcon-H1's block of ``ray_tpu/models/falcon.py``:
a Mamba-2 mixer (32 heads of 128 with steps, a state of 256, two groups, on
``ops/ssd.py``'s tile layout) and grouped-query attention (5 query heads a
KV head) read ONE norm side by side, each under its muP multipliers, ahead
of a serial SwiGLU. What decides ``correct``, against
``reference_falconh1.py`` (which advances the mixer's state a token at a
time) given the same layers and the same slice of the vocabulary:

(a) Numbers. Per-token losses of the first batch at the timed shape (mean,
    99.9th percentile), the step's loss before and after the first update,
    and the descent of the reference's loss (``train.loss_checks``).
(b) Alive. Two readings of the first layer on the first batch, reported in
    every ``train.report``: the deviation of its softmax scores (with
    ``key_multiplier`` 0.011 an attention whose scores read 0.02 is an
    average that no wrong rotary or mask would move) and the spread of its
    per-step decay ``exp(dt A)`` over heads and tokens (1st and 99th
    percentile); each inside the range the cell states. Beside them, for
    the log, what each half adds to the residual stream (RMS).
(c) The kernel pair alone. After the window, on the first layer's own x,
    dt, A, B and C of the first batch at the timed shape: the scan's two
    Mosaic calls against the op's plain path in float32 at ``highest``,
    the output and all five gradients, in the timed type and on the same
    values in float32 (``op_agreement``).
(d) The step program holds its Pallas calls, every loss is finite, the
    first loss lies in its band.

The weights are ``seeded_weights``: the program's own initial values but
for the matrices whose deviation decides whether a mechanism is alive under
the multipliers (the configuration file's ``assumed`` (c) has each factor
and the reading it gives). The limits are the cell's ``train.check``;
measured values and their origin: PERF.md 4.
"""

from __future__ import annotations

import importlib.util
import math
import os
import statistics
import time

from benchmark.kinds.train import FIRST_LOSS_TOL, loss_agreement, loss_checks
from benchmark.kinds.train_hybrid import stall_lines

OP_PARTS = ("y", "dx", "ddt", "dA", "dB", "dC")
# what each half should add to the residual stream, RMS: about the
# embedding's own 0.02 x embedding_multiplier
HALF_RMS = 0.1
DT_SIGMA = 2.0          # of dt + dt_bias before its softplus
ALIVE = ("alive_scores_dev", "alive_decay_p01", "alive_decay_p99")


def seed_factors(cfg, seq: int) -> dict:
    """What ``seeded_weights`` multiplies each matrix's initial value
    (normal over the square root of its fan-in) by, from the config's
    multipliers alone, so that under them: the softmax's scores read a
    deviation of 1 (Wq and Wk: key_multiplier^-1/2 over
    attention_in_multiplier each); every segment of
    the in-projection's product reads 1 (``DT_SIGMA`` for dt) after
    ssm_in_multiplier and its own; the SwiGLU's gate reads 1 inside its
    activation; the logits read 1; and each half adds about ``HALF_RMS`` to
    the residual stream: the attention's output is a mean over
    e^-1 x S / 2 keys at a deviation of 1, a SwiGLU's product of two unit
    normals reads 0.6."""
    per = tuple(1.0 / (cfg.ssm_in_multiplier * m) for m in cfg.ssm_multipliers)
    mean_of = (math.e / (seq / 2)) ** 0.5       # sqrt(sum p^2), scores N(0,1)
    qk = cfg.key_multiplier ** -0.5 / cfg.attention_in_multiplier
    return {
        "wq": qk, "wk": qk,
        "wo": HALF_RMS / (mean_of * cfg.attention_out_multiplier
                          * cfg.attention_in_multiplier),
        "in_proj": per[:4] + (DT_SIGMA * per[4],),
        "out_proj": HALF_RMS / cfg.ssm_out_multiplier,
        "w_gate": 1.0 / cfg.mlp_multipliers[0],
        "w_down": HALF_RMS / (0.6 * cfg.mlp_multipliers[1]),
        "lm_head": 1.0 / cfg.lm_head_multiplier}


def seeded_weights(key, cfg, seq: int):
    """The cell's weights from its seed: ``falcon.init_params`` with the
    matrices of ``seed_factors`` scaled (the in-projection a segment at a
    time). Everything else is the program's own initial value: the mixer's
    dt_bias, A_log and D as Mamba-2 and the class set them, norms 1."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import falcon

    by = seed_factors(cfg, seq)
    inner, n = cfg.mamba_inner, cfg.mamba_groups * cfg.mamba_state
    columns = jnp.asarray(np.repeat(
        by["in_proj"], (inner, inner, n, n, cfg.mamba_heads)), jnp.float32)
    params = falcon.init_params(key, cfg)

    def seeded(stack):
        out = dict(stack)
        for name in ("wq", "wk", "wo", "out_proj", "w_gate", "w_down"):
            out[name] = (stack[name].astype(jnp.float32) * by[name]).astype(
                stack[name].dtype)
        out["in_proj"] = (stack["in_proj"].astype(jnp.float32)
                          * columns).astype(stack["in_proj"].dtype)
        return out

    head = params["lm_head"]
    return {**params, "layers": [seeded(s) for s in params["layers"]],
            "lm_head": (head.astype(jnp.float32) * by["lm_head"]).astype(
                head.dtype)}


def token_loss_fns(cfg, sizes: dict, mesh=None, rules=None) -> tuple:
    """``(program, reference)``: tokens [B, S+1] -> every position's loss
    [B, S] float32, through the program's own forward and through the plain
    reference. Each is one jitted program."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_falconh1
    from ray_tpu.models import falcon

    def program(p, t):
        logits = falcon.forward(p, t[:, :-1], cfg, mesh=mesh, rules=rules)
        picked = jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) \
            - picked.astype(jnp.float32)

    def reference(p, t):
        return reference_falconh1.token_losses(p, t, sizes)

    return jax.jit(program), jax.jit(reference)


def first_layer(cfg, params, tokens):
    """The first layer on tokens [B, S + 1], by the program's own pieces:
    ``(alive, halves, scan)``. alive: the deviation of the softmax's scores
    (the last 256 queries against every key they see, every head, the
    kernel's own scale) and the 1st, 50th and 99th percentile of the
    per-step decay exp(dt A); halves: the RMS of the embedding and of what
    the attention half, the mixer and the SwiGLU add to the residual
    stream; scan: what the mixer's scan takes (x [B, S, H, P], dt, A, B,
    C)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import falcon, hybrid, llama

    f32 = jnp.float32
    lp = jax.tree.map(lambda w: w[0], params["layers"][0])
    x = llama._embed(params, tokens[:, :-1], cfg.dtype)
    x = (x * cfg.embedding_multiplier).astype(cfg.dtype)
    B, S, _ = x.shape
    n = llama._norm(x, lp["attn_norm"], cfg)
    h = (n * cfg.attention_in_multiplier).astype(cfg.dtype)
    cos, sin = llama._rope_tables(cfg.rope_theta, S, cfg.head_dim)
    q = llama.apply_rope(llama._project(h, lp, cfg, "wq", cfg.n_heads),
                         cos, sin)
    k = llama.apply_rope(llama._project(h, lp, cfg, "wk", cfg.n_kv_heads),
                         cos, sin)
    last = min(256, S)
    per = cfg.n_heads // cfg.n_kv_heads
    scores = jnp.einsum(
        "bqkgd,btkd->bkgqt",
        q[:, S - last:].astype(f32).reshape(B, last, cfg.n_kv_heads, per, -1),
        k.astype(f32), precision="highest") * cfg.attn_scale
    seen = jnp.arange(S)[None, :] <= (S - last + jnp.arange(last))[:, None]
    count = jnp.sum(seen) * B * cfg.n_heads
    mean = jnp.sum(jnp.where(seen, scores, 0.0)) / count
    dev = jnp.sqrt(jnp.sum(jnp.where(seen, (scores - mean) ** 2, 0.0))
                   / count)
    _, _, scan = hybrid.scan_inputs(n, lp, cfg)
    decay = jnp.percentile(jnp.exp(scan[1] * scan[2]),
                           jnp.asarray([1.0, 50.0, 99.0]))
    rms = lambda t: jnp.sqrt(jnp.mean(t.astype(f32) ** 2))     # noqa: E731
    att = llama._attention_half(x, lp, cfg, cos, sin, normed=h) \
        * cfg.attention_out_multiplier
    mix = hybrid.mixer_half(x, lp, cfg, falcon.KIND, normed=n) \
        * cfg.ssm_out_multiplier
    x1 = x + att.astype(x.dtype) + mix.astype(x.dtype)
    ffn, _ = falcon.feed_forward(llama._norm(x1, lp["ffn_norm"], cfg), lp,
                                 cfg)
    alive = {"alive_scores_dev": dev, "alive_decay_p01": decay[0],
             "alive_decay_p50": decay[1], "alive_decay_p99": decay[2]}
    halves = {"embedding": rms(x), "attention": rms(att), "mixer": rms(mix),
              "feed_forward": rms(ffn)}
    return alive, halves, scan


def op_agreement(inputs, weight, chunk: int):
    """-> ``read``: (impl, dtype) -> {part: relative L2 distance}: the
    scan's output and the five gradients of sum(y x weight) through
    ``ssd_scan(impl=...)`` on x, B and C cast to ``dtype``, against the
    op's plain path on the inputs' own values in float32 at ``highest``
    (computed once). inputs as ``first_layer`` gives them; weight
    [B, S, H, P] float32."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.ssd import ssd_scan

    def parts(impl):
        def run(x, dt, a, bm, cm, weight):
            y, back = jax.vjp(lambda *t: ssd_scan(*t, chunk=chunk, impl=impl),
                              x, dt, a, bm, cm)
            return (y, *back(weight.astype(y.dtype)))
        return jax.jit(run)

    def plain(*t):
        with jax.default_matmul_precision("highest"):
            return parts("xla")(*t)

    f32 = jnp.float32
    distance = jax.jit(lambda a, b: jnp.linalg.norm(
        (a.astype(f32) - b.astype(f32)).ravel())
        / jnp.linalg.norm(b.astype(f32).ravel()))
    x, dt, a, bm, cm = inputs
    want = plain(x.astype(f32), dt, a, bm.astype(f32), cm.astype(f32), weight)

    def read(impl, dtype):
        got = parts(impl)(x.astype(dtype), dt, a, bm.astype(dtype),
                          cm.astype(dtype), weight)
        return {name: float(distance(g, w))
                for name, g, w in zip(OP_PARTS, got, want)}

    return read


def train_loop(config: dict) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark import model_falconh1, trace_reduce
    from ray_tpu.core import compile_cache
    from ray_tpu.models import falcon
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
    cfg = model_falconh1.falcon_config(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "ssd_impl", "remat", "f32_logits") if k in recipe})
    sizes = model_falconh1.sizes(cell["config"])
    mesh, rules = session.get_mesh(), session.get_rules()
    if recipe["optimizer"] != "adafactor":
        raise ValueError(f"unknown optimizer {recipe['optimizer']!r}")
    opt = optax.adafactor(recipe["lr"])
    B, S, V = mix["batch"], mix["seq"], cfg.vocab_size
    init_fn, state_sh = make_train_state_init(
        lambda k: seeded_weights(k, cfg, S), opt, mesh, rules,
        falcon.param_specs(cfg))
    state = init_fn(jax.random.PRNGKey(seed % (2 ** 31)))   # one jitted call
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
        lambda p, b: falcon.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
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
               "while_loops": text.count(" while(")}
    del text
    marks.append(("step program compiled or loaded", time.time()))

    # the plain reference on the first batch before the step donates the
    # state, and again after the step's first update; the second warm step
    # runs on the same batch, so the program's loss there is known too
    program_nll, reference_nll = token_loss_fns(cfg, sizes, mesh, rules)
    layer0 = jax.jit(lambda p, t: first_layer(cfg, p, t))
    t0 = time.perf_counter()
    got = program_nll(state.params, batch["tokens"])
    ref = reference_nll(state.params, batch["tokens"])
    agreement = loss_agreement(got, ref)
    del got, ref
    alive, halves, _ = layer0(state.params, batch["tokens"])
    alive = {k: float(v) for k, v in alive.items()}
    halves = {k: float(v) for k, v in halves.items()}
    said = {k: alive[k] for k in ALIVE}     # in every train.report
    losses = []
    for i in range(2):                    # the two warm steps
        if i == 1:
            ref_loss_updated = float(reference_nll(
                state.params, batch["tokens"]).mean())
            reference_s = time.perf_counter() - t0
            marks.append(("checked against the reference", time.time()))
        state, m = compiled(state, batch)
        losses.append(float(jax.device_get(m)["loss"]))
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
        loss = float(jax.device_get(m)["loss"])     # the step is done
        t_step = time.perf_counter()
        session.report({"loss": loss, "step": i, **said})
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
    # (c) the kernel pair alone, at the timed shape, on the first batch's
    # own scan inputs under the trained weights; the state goes first, the
    # plain path's float32 forms take its room
    t0 = time.perf_counter()
    _, _, op_in = layer0(state.params, batch["tokens"])
    jax.block_until_ready(op_in)
    del state
    read = op_agreement(
        op_in, jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                                 op_in[0].shape, jnp.float32),
        min(cfg.mamba_chunk, S))
    op = {"timed": read(cfg.ssd_impl, cfg.dtype)}
    if jnp.dtype(cfg.dtype) != jnp.float32:
        op["float32"] = read(cfg.ssd_impl, jnp.float32)
    op_s = time.perf_counter() - t0
    del op_in, read
    out = {
        "op": op, "op_s": op_s, "alive": alive, "halves": halves,
        "device": device, "program": program, "peak_bytes": peaks,
        "first_loss": losses[0], "second_loss": losses[1],
        "agreement": agreement, "ref_loss_updated": ref_loss_updated,
        "losses_head": losses[:6], "last_loss": losses[-1],
        "all_finite": all(math.isfinite(x) for x in losses),
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


def alive_checks(alive: dict, tol: dict) -> dict:
    lo, hi = tol["scores_dev"]
    return {
        f"alive: the first layer's softmax scores read a deviation of "
        f"{alive['alive_scores_dev']:.3f}, inside [{lo}, {hi}]":
            lo <= alive["alive_scores_dev"] <= hi,
        f"alive: the first layer's per-step decay exp(dt A) spreads from "
        f"{alive['alive_decay_p01']:.4f} (1st percentile, <= "
        f"{tol['decay_p01_max']}) to {alive['alive_decay_p99']:.4f} (99th, "
        f">= {tol['decay_p99_min']}); median {alive['alive_decay_p50']:.4f}":
            alive["alive_decay_p01"] <= tol["decay_p01_max"]
            and alive["alive_decay_p99"] >= tol["decay_p99_min"],
    }


def op_checks(op: dict, tol: dict) -> dict:
    return {f"the scan's calls alone, {which}: the output and five "
            f"gradients within {tol['op_rel_' + which]} of the plain path "
            f"in float32 (relative L2: " + ", ".join(
                f"{k} {v:.2e}" for k, v in read.items()) + ")":
            max(read.values()) <= tol["op_rel_" + which]
            for which, read in op.items()}


def run(cell: dict, args, ctx: dict) -> dict:
    """Parent side. Returns the observations that ``run.py`` turns into
    the result line."""
    # fail before a cluster starts: a program without the family cannot
    # run this kind (the parent commit's)
    if importlib.util.find_spec("ray_tpu.models.falcon") is None:
        raise ctx["Refused"]("this program has no ray_tpu/models/falcon.py: "
                             "it has no block of two first halves for this "
                             "kind to train")
    import ray_tpu
    from ray_tpu.core.node import detect_tpu_chips
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark import flops_falconh1, model_falconh1

    log = ctx["log"]
    recipe, chips = cell["train"], cell["chips"]
    want_tpu = not cell.get("rehearsal", False)
    found = detect_tpu_chips()
    if want_tpu and found < chips:
        raise ctx["Refused"](f"this host shows {found} TPU chip(s), the "
                             f"cell needs {chips}")
    sizes = model_falconh1.sizes(cell["config"])
    batch, seq = cell["mix"]["batch"], cell["mix"]["seq"]
    log(f"train_falconh1: JaxTrainer(1 worker x {chips} chip(s)), mesh "
        f"{recipe['mesh']}, rules {recipe['rules']}, B{batch} x S{seq} "
        f"(+1 id), {sizes['n_layers']} layers of two first halves: "
        f"{sizes['n_heads']} query heads over {sizes['n_kv_heads']} KV heads "
        f"of {sizes['head_width']} beside {sizes['mamba_heads']} mixer heads "
        f"of {sizes['mamba_head_dim']}, a state of {sizes['mamba_state']} in "
        f"{sizes['mamba_groups']} groups, chunk {sizes['mamba_chunk']}; a "
        f"SwiGLU of {sizes['d_ff']}; {sizes['vocab_size']} entries")
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
    device, prog, a = m["device"], m["program"], m["agreement"]
    tol = recipe["check"]
    tok_s_chip = m["steps"] * m["tokens_per_step"] / m["elapsed_s"] \
        / device["count"]
    per_token = flops_falconh1.train_flops_per_token(sizes, seq)
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
        f"{m['reference_s']:.1f} s; the kernel pair alone against the plain "
        f"path {m['op_s']:.1f} s after the window")
    # loss_checks prints both to five places, and the limit is finer
    log(f"  step loss less the reference's: "
        f"{m['first_loss'] - a['ref_loss']:+.2e} before and "
        f"{m['second_loss'] - m['ref_loss_updated']:+.2e} after the first "
        f"update (limit {tol['step_loss_abs']})")
    log("  the first layer adds to the residual stream, RMS: "
        + ", ".join(f"{k} {v:.4f}" for k, v in m["halves"].items()))
    stalls = stall_lines(os.environ.get("RAY_TPU_TMPDIR", ""))
    log(f"  steps over 1.1 x the median: "
        f"{[(round(ms, 1), n) for ms, n in m['long_steps']] or 'none'}; "
        f"stall lines in the workers' logs: {len(stalls)}")
    for ln in stalls:
        log("    " + ln)
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
    # the seeded head reads logits ~N(0, 1): ln(V) + 1/2
    want = math.log(m["vocab"]) + tol.get("first_loss_over_ln_v", 0.5)
    checks = {
        **loss_checks(m, tol),
        **alive_checks(m["alive"], tol),
        **op_checks(m["op"], tol),
        f"first loss within {FIRST_LOSS_TOL} of ln(V) + "
        f"{tol.get('first_loss_over_ln_v', 0.5)} = {want:.4f}":
            abs(m["first_loss"] - want) < FIRST_LOSS_TOL,
        "all losses finite": m["all_finite"],
        f"ran on {chips} device(s)": device["count"] == chips or not want_tpu,
    }
    if device["platform"] == "tpu" and recipe.get("attn_impl") == "flash" \
            and recipe.get("ssd_impl") == "pallas":
        # a body of layers holds flash's forward, dq and dkdv and the
        # scan's forward, its forward again in the replay and its backward
        checks[f"the step program holds the Pallas calls of a block of two "
               f"first halves ({prog['pallas_calls']} >= 6)"] = \
            prog["pallas_calls"] >= 6
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
