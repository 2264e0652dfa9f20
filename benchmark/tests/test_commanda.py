"""The parallel kind's yardstick: ``flops_commanda.py`` by hand, the two
copies of the plain reference, the readers on synthetic traces, the kind's
``run()`` rehearsed on the CPU, and the cell's own limits against wrong
models."""
import inspect
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_commanda, model_commanda, op_scopes
from benchmark import reference_commanda, resolve
from benchmark.readers import (mellum_kernel_roofline, scope_path_share,
                               shared_expert_roofline)

CELL = "train-commandaplus-ep16-s8192-b1"
CONFIG = "command-a-plus-ep16-l4"
TOY = {"d_model": 8, "n_heads": 4, "n_kv_heads": 1, "head_width": 4,
       "d_ff": 4, "n_experts": 8, "top_k": 4, "experts_held": (2, 0),
       "n_layers": 4, "vocab_size": 10, "n_shared": 2, "shared_d_ff": 4,
       "layer_kinds": ("window", "window", "window", "full"),
       "kinds": {"window": {"window": 4}, "full": {"window": None}}}


def test_flops_by_hand():
    parts = flops_commanda.matmul_params_per_token(TOY)
    # q and o 8 x 16 each, k and v 8 x 4 each; four layers
    assert parts["attention projections"] == 4 * (2 * 128 + 2 * 32)
    assert parts["router"] == 4 * 64 and parts["head"] == 80
    # 4 a token, 2 of 8 held: one expert of 3 x 8 x 4 a token and layer
    assert parts["experts held"] == 4 * 96
    # two shared experts of 3 x 8 x 4, every token, every layer
    assert flops_commanda.shared_params(TOY) == 2 * 96
    assert parts["shared experts"] == 4 * 2 * 96
    fwd = flops_commanda.forward_flops_per_token(TOY, 16)
    assert fwd["shared experts"] == 2 * 4 * 2 * 96
    # a window of 4 in 16: 58 pairs a head; 136 without
    assert fwd["attention, window"] == 2 * 3 * 2 * 58 * 16 / 16
    assert fwd["attention, full"] == 2 * 2 * 136 * 16 / 16
    assert flops_commanda.train_flops_per_token(TOY, 16) \
        == 3 * sum(fwd.values())
    # a layer: projections 320, ONE norm 8, router 64, two routed experts
    # 192, two shared 192; the tied table once
    assert flops_commanda.total_params(TOY) == 4 * 776 + 80 + 8
    step = flops_commanda.shared_step(TOY, 16)
    assert step["ops"] == 6 * 4 * 192 * 16
    assert step["bytes"] == 2 * (3 * 4 * 192 + 4 * 4 * 16 * 8)


def test_flops_of_the_cell():
    sizes = model_commanda.sizes(resolve.config(CONFIG))
    assert sizes["n_experts"] == 128 and sizes["experts_held"] == (8, 0)
    assert (sizes["n_heads"], sizes["n_kv_heads"]) == (32, 2)
    assert sizes["top_k"] == 8 and sizes["vocab_size"] == 32768
    assert sizes["head_width"] == 128 and sizes["d_model"] == 4096
    assert (sizes["n_shared"], sizes["shared_d_ff"]) == (4, 4096)
    assert sizes["layer_kinds"] == ("window", "window", "window", "full")
    assert sizes["kinds"] == {
        "window": {"window": 4096, "rope_theta": 50000.0},
        "full": {"window": None, "rope_theta": None}}
    assert model_commanda.stacks(sizes) == 4        # run_layers 1
    assert model_commanda.stacks(dict(sizes, run_layers=0)) == 2
    # ISSUE 40's arithmetic: a sliding layer lets 25.2 M pairs a head
    # through, the full layer 33.6 M
    assert flops_commanda.pairs(8192, 4096) == 25_167_872
    assert flops_commanda.pairs(8192, None) == 33_558_528
    # a layer 35.65 M (attention) + 0.52 M + 8 x 50.33 M + 4 x 50.33 M =
    # 640.15 M; four and the 32,768 x 4096 table: 2,694.8 M
    assert flops_commanda.total_params(sizes) == 2_694_860_800
    fwd = flops_commanda.forward_flops_per_token(sizes, 8192)
    per_layer = {k: v / 1e6 / n for k, v, n in (
        ("projections", fwd["attention projections"], 4),
        ("shared", fwd["shared experts"], 4),
        ("experts", fwd["experts held"], 4),
        ("window", fwd["attention, window"], 3),
        ("full", fwd["attention, full"], 1))}
    # MFLOP a token a layer: 403 in the shared experts, 71 in projections,
    # 50 (window) or 67 (full) under the mask, 50 in the held experts
    assert math.isclose(per_layer["shared"], 402.65, abs_tol=0.05)
    assert math.isclose(per_layer["projections"], 71.3, abs_tol=0.05)
    assert math.isclose(per_layer["window"], 50.34, abs_tol=0.05)
    assert math.isclose(per_layer["full"], 67.12, abs_tol=0.05)
    assert math.isclose(per_layer["experts"], 50.33, abs_tol=0.05)
    assert math.isclose(fwd["head"] / 1e6, 268.4, abs_tol=0.1)
    assert math.isclose(sum(fwd.values()) / 1e6, 2587.9, abs_tol=0.2)
    assert math.isclose(flops_commanda.train_flops_per_token(sizes, 8192)
                        / 1e9, 7.764, abs_tol=5e-3)
    # the published model: 32 layers, every head, expert and row: 218 B
    whole = dict(sizes, n_layers=32, n_heads=128, n_kv_heads=8,
                 experts_held=(128, 0), vocab_size=262144,
                 layer_kinds=sizes["layer_kinds"] * 8)
    assert round(flops_commanda.total_params(whole) / 1e9) == 218


def test_the_configuration_keeps_every_published_number():
    conf = resolve.config(CONFIG)
    published = {
        "head_dim": 128, "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "logit_scale": 1,
        "max_position_embeddings": 200000, "num_experts_per_tok": 8,
        "num_shared_experts": 4, "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rope_theta": 50000,
        "rotary_pct": 1, "sliding_window": 4096, "first_k_dense_replace": 0}
    for key, value in published.items():
        assert conf[key] == value, key
    assert conf["rms_norm_eps"] is None
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "num_attention_heads", "num_key_value_heads",
                               "vocab_size"]
    assert conf["published"] == {
        "num_hidden_layers": 32, "num_experts": 128,
        "num_attention_heads": 128, "num_key_value_heads": 8,
        "vocab_size": 262144}
    assert [conf[k] for k in conf["reduced"]] == [4, 8, 32, 2, 32768]
    # within the guide's floors: a period, 8 routed experts, an eighth of
    # the vocabulary; 16 query heads a KV head as published
    assert conf["num_attention_heads"] // conf["num_key_value_heads"] \
        == 128 // 8
    assert len(conf["layer_types"]) == 32
    assert conf["deployment"]["chips_sharing_a_layer"] == 16
    assert conf["deployment"]["router_experts"] == 128
    assert len(conf["assumed"]) >= 4
    for key in ("assumed", "cut", "memory_plan", "stands_for"):
        assert conf[key] and "TO BE FILLED" not in str(conf[key]), key
    with pytest.raises(ValueError, match="cohere2_moe block"):
        model_commanda.sizes(dict(conf, use_parallel_block=False))
    with pytest.raises(ValueError, match="cohere2_moe block"):
        model_commanda.sizes(dict(
            conf, shared_expert_combination_strategy="sum"))
    with pytest.raises(ValueError, match="rope_parameters"):
        model_commanda.sizes(dict(conf, rope_parameters={
            "rope_type": "yarn", "rope_theta": 50000}))
    with pytest.raises(ValueError, match="deployment"):
        model_commanda.sizes(dict(conf, num_attention_heads=16))
    with pytest.raises(KeyError, match="sliding_window"):
        model_commanda.sizes({k: v for k, v in conf.items()
                              if k != "sliding_window"})


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_commanda as programs

    for name in ("_layer_norm", "_rope", "_attention", "_swiglu", "shared",
                 "routed", "layer", "trunk", "forward", "token_losses",
                 "loss"):
        assert inspect.getsource(getattr(reference_commanda, name)) \
            == inspect.getsource(getattr(programs, name)), name
    # independent of the program: neither copy imports it
    for mod in (reference_commanda, programs):
        src = inspect.getsource(mod)
        assert "import" not in src.replace(
            "from __future__ import annotations", "").replace(
            "import jax.numpy as jnp", "").replace("import jax", "").replace(
            "import math", ""), mod


# --- readers on synthetic traces -------------------------------------------
def _call(results, operands, n=1):
    return (f"%call.{n} = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_commanda.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 4000.0},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


Q = "bf16[1,32,8192,128]{3,2,1,0}"
K = "bf16[1,2,8192,128]{3,2,1,0}"
QF = "f32[1,32,8192,128]{3,2,1,0}"
BACK = f"{Q} %q, {K} %k, {K} %v, {Q} %g, {Q} %o, {QF} %l"
CALLS = {n: c for n, c in enumerate((
    _call(f"({Q}, {QF})", f"{Q} %q, {K} %k, {K} %v", 1),
    _call(Q, BACK, 2), _call(f"({QF}, {QF})", BACK, 3),
    _call(f"({Q}, {QF})", f"{Q} %q, {K} %k, {K} %v", 4),
    _call(Q, BACK, 5), _call(f"({QF}, {QF})", BACK, 6)), 1)}
WHICH = {1: "fwd", 2: "dq", 3: "dkdv", 4: "fwd", 5: "dq", 6: "dkdv"}
META = "s32[] %n, s32[9]{0} %o, s32[40]{0} %g, s32[40]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[8192,4096]{1,0}",
            f"{META}, bf16[8192,4096]{{1,0}} %x, bf16[8,4096,4096]{{2,1,0}} %w", 7)
TGMM = _call("bf16[8,4096,4096]{2,1,0}",
             f"{META}, bf16[8192,4096]{{1,0}} %x, bf16[8192,4096]{{1,0}} %g", 8)


def _scope(kind, call, wrap="jvp(layers)"):
    return {"tf_op": f"jit(step)/{wrap}/checkpoint/attention/{kind}/"
                     f"flash.{call}.loop/pallas_call:"}


LABELS = {CALLS[n]: _scope("window" if n < 4 else "full", WHICH[n],
                           "jvp(layers)" if n in (1, 4)
                           else "transpose(jvp(layers))")
          for n in CALLS}


def test_the_kernel_reader_fits_the_cells_calls(monkeypatch):
    """``mellum_kernel_roofline`` reads this family too: the flash calls at
    32 over 2 heads told apart by their kind's scope, the grouped matmuls
    at a square expert (ONE width: the model's and an expert's are both
    4,096), operations under the mask and at the rows the experts got."""
    obs = _obs()
    kinds = [mellum_kernel_roofline.classify(CALLS[n], obs, LABELS)[0]
             for n in sorted(CALLS)]
    assert kinds == ["flash_window"] * 3 + ["flash_full"] * 3
    assert [mellum_kernel_roofline.classify(c, obs, LABELS)[0]
            for c in (GMM, TGMM)] == ["grouped_matmul"] * 2
    _, call = mellum_kernel_roofline.classify(TGMM, obs, LABELS)
    assert call["ops"] == 2.0 * 4000 * 4096 * 4096
    _, win = mellum_kernel_roofline.classify(CALLS[1], obs, LABELS)
    _, full = mellum_kernel_roofline.classify(CALLS[4], obs, LABELS)
    assert win["ops"] == 2 * 2 * 25_167_872 * 32 * 128
    assert full["ops"] == 2 * 2 * 33_558_528 * 32 * 128
    monkeypatch.setattr(op_scopes, "of_run", lambda: LABELS)
    took = {1: 0.02, 2: 0.03, 3: 0.04, 4: 0.03, 5: 0.04, 6: 0.05}
    obs["trace"] = {
        "device_ops": [[CALLS[n], s] for n, s in took.items()]
        + [[GMM, 0.04], [TGMM, 0.02]],
        "op_calls": {**{CALLS[n]: 12 if n < 4 else 4 for n in CALLS},
                     GMM: 40, TGMM: 12}}
    for spec in ("flash_window_roofline.commanda",
                 "flash_attention_roofline.commanda",
                 "grouped_matmul_roofline.commanda"):
        spec = resolve.layer_metric(spec)
        got = resolve.reader(spec["reader"]).read(spec, obs)
        assert 5 < got < 100, (spec, got)
    foreign = _call("bf16[1,8192,4096]{2,1,0}", "bf16[1,8192,4096]{2,1,0} %x")
    with pytest.raises(ValueError, match="no flash call"):
        mellum_kernel_roofline.classify(foreign, obs, LABELS)


def test_shared_expert_readers(monkeypatch):
    under = lambda path: {"tf_op": f"jit(step)/{path}:"}    # noqa: E731
    labels = {
        "%f.1 = s": under("jvp(layers)/checkpoint/feed_forward/shared/"
                          "dot_general"),
        "%f.2 = s": under("transpose(jvp(layers))/checkpoint/"
                          "rematted_computation/feed_forward/shared/mul"),
        "%f.3 = s": under("transpose(jvp(layers))/checkpoint/feed_forward/"
                          "shared/dot_general"),
        "%f.4 = e": under("jvp(layers)/checkpoint/feed_forward/experts/"
                          "gmm.pallas/pallas_call"),
        "%f.5 = b": under("jvp(layers)/checkpoint/block/reduce"),
        "%f.6 = n": {}}
    monkeypatch.setattr(op_scopes, "of_run", lambda: labels)
    obs = {**_obs(), "trace": {"window_s": 2.5, "device_ops": [
        [n, 0.2 * (i + 1)] for i, n in enumerate(labels)]}}
    share = resolve.layer_metric("shared_expert_device_share")
    assert math.isclose(scope_path_share.read(share, obs),
                        100 * (0.2 + 0.4 + 0.6) / 2.5)
    block = resolve.layer_metric("block_norm_device_share")
    assert math.isclose(scope_path_share.read(block, obs), 100 * 1.0 / 2.5)
    spec = resolve.layer_metric("shared_expert_roofline")
    sizes = obs["sizes"]
    # four traced steps of 8,192 tokens: 6 x 4 x 201.3 M x 8,192 operations
    # a step, 39.6 TFLOP: 0.201 s at the peak
    least = flops.least_seconds(flops_commanda.shared_step(sizes, 8192),
                                obs["peak"])
    assert least["bound"] == "compute"
    assert math.isclose(least["seconds"], 0.2009, abs_tol=1e-3)
    got = shared_expert_roofline.read(spec, obs)
    assert math.isclose(got, 100 * 4 * least["seconds"] / 1.2)
    assert 60 < got < 100
    # nothing to read: no trace, a family without shared experts, a trace
    # without the path, a program without scopes
    assert shared_expert_roofline.read(spec, dict(obs, trace=None)) is None
    assert shared_expert_roofline.read(
        spec, dict(obs, sizes={"d_model": 4096})) is None
    assert shared_expert_roofline.read(
        dict(spec, path=["feed_forward", "absent"]), obs) is None
    monkeypatch.setattr(op_scopes, "of_run", lambda: None)
    assert shared_expert_roofline.read(spec, obs) is None


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_parallel"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    # a later PR may add to what the cell reports: held with <=, not ==
    assert {"shared_expert_device_share", "shared_expert_roofline",
            "block_norm_device_share", "flash_window_roofline.commanda",
            "flash_attention_roofline.commanda",
            "grouped_matmul_roofline.commanda",
            "expert_held_rows_share.commanda",
            "expert_load_max_over_mean.commanda",
            "window_attention_device_share", "full_attention_device_share",
            "train_step_ms", "train_report_ms", "train_report_span_ms",
            "device_idle_share.train", "device_idle_under_report.train",
            "compiles_in_window.train", "compiles_in_trace.train",
            "attention_device_share", "feed_forward_device_share",
            "head_loss_device_share", "optimizer_device_share",
            "layer_loop_device_share", "remat_replay_device_share",
            "unscoped_device_share"} <= names
    for m in per_layer:
        spec = resolve.layer_metric(m["name"])
        assert spec["unit"] == m["unit"], m["name"]
        resolve.reader(spec["reader"])
    e2e = {m["name"] for m in resolve.metrics_for(CELL, "end_to_end",
                                                  cell_kind)}
    assert e2e == {"train_tok_s_chip", "setup_s"}
    man = resolve.manifest()
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == resolve.config(CONFIG)["reduced"]
    assert conf["source"] == resolve.config(CONFIG)["source"]
    mix = resolve.cell(CELL)["mix"]
    assert (mix["seq"], mix["batch"]) == (8192, 1)
    # an unlisted cell of the kind (the rehearsal) reads the kind's own
    own = {m["name"] for m in resolve.metrics_for(
        "rehearse-train-parallel", "per_layer", cell_kind)}
    assert {"shared_expert_roofline", "block_norm_device_share"} <= own
    # the new metrics read nothing where the program has nothing of theirs
    for name in ("shared_expert_device_share", "shared_expert_roofline",
                 "block_norm_device_share", "flash_window_roofline.commanda"):
        spec = resolve.layer_metric(name)
        assert resolve.reader(spec["reader"]).read(spec, {"trace": None}) \
            is None


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_parallel

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("RAY_TPU_CHIPS", "1")
    monkeypatch.setenv("PYTHONPATH", root)
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path / "sessions"))
    os.makedirs(tmp_path / "sessions")
    monkeypatch.chdir(root)

    class Refused(Exception):
        pass

    res = train_parallel.run(
        resolve.cell("rehearse-train-parallel"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 12 and all(res["checks"].values()), \
        res["checks"]
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert 0 < res["obs"]["values"]["held_rows"] <= 2 * 128 * 2


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from benchmark.kinds import train_parallel

    class Refused(Exception):
        pass

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(Refused, match="parallel block"):
        train_parallel.run(resolve.cell("rehearse-train-parallel"), None,
                           {"Refused": Refused})


# --- the cell's own limits refuse wrong models -----------------------------
WRONG = ["as it is", "a serial block", "an RMS norm",
         "tables on the full layers", "rotate_half pairing",
         "shared experts summed", "a softmax router", "window 39 for 40",
         "h // 2 for h // 4", "8-bit shared-expert weights",
         "one held expert fewer"]


@pytest.mark.parametrize("wrong", WRONG)
def test_the_cells_limits_fail_a_wrong_model(wrong):
    """At the toy size in bf16 on the CPU, against the limits the real cell
    is held to (``workloads/<cell>.json`` ``train.check``), which the toy
    as it is has to meet."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmark.kinds import train_parallel
    from ray_tpu.models import moe

    tol = resolve.workload(CELL)["train"]["check"]
    conf = dict(resolve.config("tiny-commanda"),
                run={"dtype": "bfloat16", "param_dtype": "bfloat16",
                     "run_layers": 1})
    sizes = model_commanda.sizes(conf)
    cfg = model_commanda.moe_config(conf, attn_impl="xla")
    params = moe.init_params(jax.random.PRNGKey(7), cfg)
    # router logits of about 1, so that a score is no longer 0.5 whatever
    # it is made with (at 0.02 a lane a sigmoid and a softmax weigh alike)
    params = dict(params, layers=[dict(lay, router=lay["router"] * 8)
                                  for lay in params["layers"]])
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 129), 0,
                                cfg.vocab_size, "int32")
    run_params, run_cfg = params, cfg
    (_, full), (_, window) = cfg.attn_kinds       # sorted by name
    kinds = lambda w, f: (("full", f), ("window", w))       # noqa: E731
    if wrong == "a serial block":
        run_cfg = cfg.replace(parallel_block=False)
        run_params = dict(params, layers=[
            dict(lay, ffn_norm=lay["attn_norm"]) for lay in params["layers"]])
    elif wrong == "an RMS norm":
        run_cfg = cfg.replace(norm="rms")
    elif wrong == "tables on the full layers":
        run_cfg = cfg.replace(attn_kinds=kinds(window, dataclasses.replace(
            full, rope=True, pairs="neighbours")))
    elif wrong == "rotate_half pairing":
        run_cfg = cfg.replace(attn_kinds=kinds(
            dataclasses.replace(window, pairs="halves"), full))
    elif wrong == "shared experts summed":
        run_cfg = cfg.replace(shared_combine="sum")
    elif wrong == "a softmax router":
        run_cfg = cfg.replace(router_score="softmax")
    elif wrong == "window 39 for 40":
        run_cfg = cfg.replace(attn_kinds=kinds(
            dataclasses.replace(window, window=39), full))
    elif wrong == "h // 2 for h // 4":
        # query heads dealt round the two KV heads in place of 0-3, 4-7
        swap = jnp.asarray([0, 2, 4, 6, 1, 3, 5, 7])

        def regroup(lay):
            wq = lay["wq"].reshape(*lay["wq"].shape[:2], 8, 16)[:, :, swap]
            wo = lay["wo"].reshape(-1, 8, 16, 64)[:, swap]
            return dict(lay, wq=wq.reshape(lay["wq"].shape),
                        wo=wo.reshape(lay["wo"].shape))

        run_params = dict(params, layers=[regroup(lay)
                                          for lay in params["layers"]])
    elif wrong == "8-bit shared-expert weights":
        run_params = dict(params, layers=[
            {k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
                 if k.startswith("ws_") else w)
             for k, w in lay.items()} for lay in params["layers"]])
    elif wrong == "one held expert fewer":
        held, first = cfg.experts_held
        run_cfg = cfg.replace(experts_held=(held - 1, first))
        run_params = dict(params, layers=[
            {k: (w[:, :held - 1] if k.startswith("we_") else w)
             for k, w in lay.items()} for lay in params["layers"]])
    else:
        assert wrong == "as it is"
    _, reference = train_parallel.token_loss_fns(cfg, sizes)
    got, routes = train_parallel.token_loss_fns(run_cfg, sizes)[0](
        run_params, tokens)
    ref, _, rec = reference(params, tokens, routes)
    a = train_parallel.loss_agreement(got, ref)
    r = train_parallel.route_agreement(routes, rec, cfg.top_k)
    ok = all(train_parallel.route_checks(r, tol, cfg.top_k).values()) \
        and a["token_mean_abs"] <= tol["token_mean_abs"] \
        and a["token_p999_abs"] <= tol["token_p999_abs"]
    assert ok == (wrong == "as it is"), (a, r, tol)
