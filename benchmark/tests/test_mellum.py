"""The mixed kind's yardstick: ``flops_mellum.py`` by hand, the two copies
of the plain reference, the new readers on synthetic traces, the kind's
``run()`` rehearsed on the CPU, and the cell's own limits against wrong
models."""
import inspect
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_mellum, model_mellum, op_scopes
from benchmark import reference_mellum, resolve
from benchmark.readers import mellum_kernel_roofline, scope_path_share

CELL = "train-mellum2-ep4-s16384-b1"
CONFIG = "mellum2-12b-a2.5b-ep4-l12"
TOY = {"d_model": 8, "n_heads": 4, "n_kv_heads": 1, "head_width": 4,
       "d_ff": 4, "n_experts": 8, "top_k": 4, "experts_held": (2, 0),
       "n_layers": 4, "vocab_size": 10,
       "layer_kinds": ("window", "window", "window", "full"),
       "kinds": {"window": {"window": 4}, "full": {"window": None}}}


def test_flops_by_hand():
    # a window of 4 in 16: rows 0-3 see 1, 2, 3, 4 keys, the other 12 see 4
    assert flops_mellum.pairs(16, 4) == 10 + 48
    assert flops_mellum.pairs(16, None) == 136 == flops_mellum.pairs(16, 16)
    assert flops_mellum.pairs(16, 99) == 136
    # the cell's: 16.25 M visible pairs a head against 134.2 M (ISSUE 38)
    assert flops_mellum.pairs(16384, 1024) == 16_253_440
    assert flops_mellum.pairs(16384, None) == 134_225_920
    assert flops_mellum.layers_of(TOY) == {"window": 3, "full": 1}
    parts = flops_mellum.matmul_params_per_token(TOY)
    # q and o 8 x 16 each, k and v 8 x 4 each; four layers
    assert parts["attention projections"] == 4 * (2 * 128 + 2 * 32)
    assert parts["router"] == 4 * 64
    # 4 a token, 2 of 8 held: one expert of 3 x 8 x 4 a token and layer
    assert flops_mellum.held_per_token(TOY) == 1.0
    assert parts["experts held"] == 4 * 96 and parts["head"] == 80
    # one matmul over the pairs, 4 heads of 4: 2 x pairs x 16
    assert flops_mellum.attention_unit(TOY, 16, "window") == 2 * 58 * 16
    assert flops_mellum.attention_unit(TOY, 16, "full") == 2 * 136 * 16
    fwd = flops_mellum.forward_flops_per_token(TOY, 16)
    assert fwd["attention, window"] == 2 * 3 * 2 * 58 * 16 / 16
    assert fwd["attention, full"] == 2 * 2 * 136 * 16 / 16
    assert flops_mellum.train_flops_per_token(TOY, 16) \
        == 3 * sum(fwd.values())
    # a layer: projections 320, norms 16, router 64, two experts 192
    assert flops_mellum.total_params(TOY) == 4 * 592 + 2 * 80 + 8
    call = flops_mellum.flash_call(TOY, 2, 16, "fwd", "window")
    assert call["ops"] == 2 * 2 * (2 * 58 * 16)
    # q and o 2*16*16*2 bytes each, k and v 2*16*4*2 each
    assert call["bytes"] == 2 * 1024 + 2 * 256
    back = flops_mellum.flash_call(TOY, 2, 16, "dkdv", "full")
    assert back["ops"] == 3 * 2 * (2 * 136 * 16)
    assert back["bytes"] == 3 * 1024 + 4 * 256
    assert flops_mellum.flash_call(TOY, 1, 16, "dq", "full")["bytes"] \
        == 4 * 512 + 2 * 128
    with pytest.raises(KeyError):
        flops_mellum.flash_call(TOY, 1, 16, "bwd", "full")


def test_flops_of_the_cell():
    sizes = model_mellum.sizes(resolve.config(CONFIG))
    assert sizes["n_experts"] == 64 and sizes["experts_held"] == (16, 0)
    assert sizes["top_k"] == 8 and sizes["vocab_size"] == 24576
    assert sizes["head_width"] == 128 and sizes["d_model"] == 2304
    assert sizes["layer_kinds"] == ("window", "window", "window", "full") * 3
    assert sizes["kinds"]["window"] == {"window": 1024,
                                        "rope_theta": 500000.0, "yarn": None}
    full = sizes["kinds"]["full"]
    assert full["window"] is None and full["yarn"] == {
        "factor": 16.0, "original": 8192, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.2772588722239782}
    # ISSUE 38's arithmetic: a layer 21.23 M + 0.15 M + 16 x 6.193 M =
    # 120.5 M; 12 layers and two 24,576 x 2304 matrices: 1.56 B
    assert math.isclose(flops_mellum.total_params(sizes) / 1e9, 1.559,
                        abs_tol=1e-3)
    fwd = flops_mellum.forward_flops_per_token(sizes, 16384)
    per_layer = {k: v / 1e6 / n for k, v, n in (
        ("projections", fwd["attention projections"], 12),
        ("experts", fwd["experts held"], 12),
        ("window", fwd["attention, window"], 9),
        ("full", fwd["attention, full"], 3))}
    # MFLOP a token a layer: 43 in projections, 25 in the held experts, 16
    # (window) or 134 (full) in attention
    assert math.isclose(per_layer["projections"], 42.5, abs_tol=0.1)
    assert math.isclose(per_layer["experts"], 24.8, abs_tol=0.1)
    assert math.isclose(per_layer["window"], 16.25, abs_tol=0.05)
    assert math.isclose(per_layer["full"], 134.2, abs_tol=0.1)
    assert math.isclose(flops_mellum.train_flops_per_token(sizes, 16384)
                        / 1e9, 4.419, abs_tol=5e-3)
    # the published model: 28 layers, every expert, the whole vocabulary
    whole = dict(sizes, n_layers=28, experts_held=(64, 0), vocab_size=98304,
                 layer_kinds=sizes["layer_kinds"][:4] * 7)
    assert math.isclose(flops_mellum.total_params(whole) / 1e9, 12.15,
                        abs_tol=0.01)


def test_the_configuration_keeps_every_published_number():
    conf = resolve.config(CONFIG)
    published = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "moe_intermediate_size": 896, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024}
    for key, value in published.items():
        assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                 "vocab_size": 98304}
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (12, 16, 24576)
    assert len(conf["layer_types"]) == 28 == len(conf["mlp_layer_types"])
    assert conf["deployment"]["chips_sharing_a_layer"] == 4
    assert conf["deployment"]["router_experts"] == 64
    for key in ("assumed", "cut", "memory_plan", "stands_for"):
        assert conf[key], key
    with pytest.raises(ValueError, match="mellum block"):
        model_mellum.sizes(dict(conf, model_type="olmoe"))
    with pytest.raises(ValueError, match="rope_parameters"):
        model_mellum.sizes(dict(conf, rope_parameters={
            **conf["rope_parameters"],
            "sliding_attention": {"rope_type": "linear", "rope_theta": 1}}))
    with pytest.raises(KeyError, match="sliding_window"):
        model_mellum.sizes({k: v for k, v in conf.items()
                            if k != "sliding_window"})


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_mellum as programs

    for name in ("_rms", "_runs", "yarn_range", "inv_freq", "rope_tables",
                 "_rope", "_attention", "experts", "trunk", "forward",
                 "router_losses", "token_losses", "loss"):
        assert inspect.getsource(getattr(reference_mellum, name)) \
            == inspect.getsource(getattr(programs, name)), name
    # independent of the program: neither copy imports it
    for mod in (reference_mellum, programs):
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
    return {"sizes": model_mellum.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 30000.0},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


Q = "bf16[1,32,16384,128]{3,2,1,0}"
K = "bf16[1,4,16384,128]{3,2,1,0}"
QF = "f32[1,32,16384,128]{3,2,1,0}"
LSE = "f32[1,32,16384,128]{3,2,1,0}"
BACK = f"{Q} %q, {K} %k, {K} %v, {Q} %g, {Q} %o, {LSE} %l"
# a window layer's calls (numbered 1..3) and a full layer's (4..6): the
# same shapes, told apart by their scope alone
CALLS = {n: c for n, c in enumerate((
    _call(f"({Q}, {LSE})", f"{Q} %q, {K} %k, {K} %v", 1),
    _call(QF, BACK, 2), _call(f"({QF}, {QF})", BACK, 3),
    _call(f"({Q}, {LSE})", f"{Q} %q, {K} %k, {K} %v", 4),
    _call(QF, BACK, 5), _call(f"({QF}, {QF})", BACK, 6)), 1)}
WHICH = {1: "fwd", 2: "dq", 3: "dkdv", 4: "fwd", 5: "dq", 6: "dkdv"}
META = "s32[] %n, s32[17]{0} %o, s32[80]{0} %g, s32[80]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[65536,896]{1,0}",
            f"{META}, bf16[65536,2304]{{1,0}} %x, bf16[16,2304,896]{{2,1,0}} %w", 7)
TGMM = _call("bf16[16,2304,896]{2,1,0}",
             f"{META}, bf16[65536,2304]{{1,0}} %x, bf16[65536,896]{{1,0}} %g", 8)


def _scope(kind, call, wrap="jvp(layers)"):
    return {"tf_op": f"jit(step)/{wrap}/while/body/checkpoint/attention/"
                     f"{kind}/flash.{call}.stream/pallas_call:"}


LABELS = {CALLS[n]: _scope("window" if n < 4 else "full", WHICH[n],
                           "jvp(layers)" if n in (1, 4)
                           else "transpose(jvp(layers))")
          for n in CALLS}


def test_roofline_reader_tells_the_kinds_apart_by_their_scope(monkeypatch):
    obs = _obs()
    kinds = [mellum_kernel_roofline.classify(CALLS[n], obs, LABELS)[0]
             for n in sorted(CALLS)]
    assert kinds == ["flash_window"] * 3 + ["flash_full"] * 3
    assert [mellum_kernel_roofline.classify(c, obs, LABELS)[0]
            for c in (GMM, TGMM)] == ["grouped_matmul"] * 2
    # the grouped matmul counts the rows the experts got, not the buffer's
    _, call = mellum_kernel_roofline.classify(GMM, obs, LABELS)
    assert call["ops"] == 2.0 * 30000 * 2304 * 896
    # a window call counts the pairs under its mask: 16.25 of 134.2 M
    _, win = mellum_kernel_roofline.classify(CALLS[1], obs, LABELS)
    _, full = mellum_kernel_roofline.classify(CALLS[4], obs, LABELS)
    assert win["ops"] == 2 * 2 * 16_253_440 * 32 * 128
    assert full["ops"] == 2 * 2 * 134_225_920 * 32 * 128
    assert win["bytes"] == full["bytes"]
    monkeypatch.setattr(op_scopes, "of_run", lambda: LABELS)
    took = {1: 0.1, 2: 0.15, 3: 0.2, 4: 0.3, 5: 0.4, 6: 0.5}
    obs["trace"] = {
        "device_ops": [[CALLS[n], s] for n, s in took.items()]
        + [["%fusion.1 = x", 1.0]],
        "op_calls": {**{CALLS[n]: 36 if n < 4 else 12 for n in CALLS},
                     "%fusion.1 = x": 40}}
    sizes = obs["sizes"]
    for kernel, kind, ns in (("flash_window", "window", (1, 2, 3)),
                             ("flash_full", "full", (4, 5, 6))):
        least = sum((36 if kind == "window" else 12) * flops.least_seconds(
            flops_mellum.flash_call(sizes, 1, 16384, WHICH[n], kind),
            obs["peak"])["seconds"] for n in ns)
        got = mellum_kernel_roofline.read({"kernel": kernel}, obs)
        assert math.isclose(got, 100 * least / sum(took[n] for n in ns))
        assert 10 < got < 100
    assert mellum_kernel_roofline.read({"kernel": "grouped_matmul"},
                                       obs) is None        # none in the trace
    assert mellum_kernel_roofline.read(
        {"kernel": "flash_full"}, dict(obs, trace=None)) is None
    # a program of another family (the parent's cells): nothing to read
    assert mellum_kernel_roofline.read(
        {"kernel": "flash_full"}, dict(obs, sizes={"d_model": 4096})) is None


def test_roofline_reader_raises_on_a_call_nobody_knows(monkeypatch):
    obs = _obs()
    foreign = _call("bf16[1,16384,2304]{2,1,0}",
                    "bf16[1,16384,2304]{2,1,0} %x")
    with pytest.raises(ValueError, match="no flash call"):
        mellum_kernel_roofline.classify(foreign, obs, LABELS)
    # a flash call at the quotient 2304 / 32 in place of the stated width
    with pytest.raises(ValueError, match="no flash call"):
        mellum_kernel_roofline.classify(
            CALLS[1].replace(",128]", ",72]"), obs, LABELS)
    # a flash call under no kind's scope: its pairs are nobody's to say
    with pytest.raises(ValueError, match="no one kind"):
        mellum_kernel_roofline.classify(CALLS[1], obs, {})
    with pytest.raises(ValueError, match="no one kind"):
        mellum_kernel_roofline.classify(CALLS[1], obs, {CALLS[1]: {
            "tf_op": "jit(step)/jvp(layers)/while/body/attention/"
                     "flash.fwd.stream/pallas_call:"}})
    monkeypatch.setattr(op_scopes, "of_run", lambda: LABELS)
    obs["trace"] = {"device_ops": [[foreign, 0.1]], "op_calls": {foreign: 1}}
    with pytest.raises(ValueError):
        mellum_kernel_roofline.read({"kernel": "flash_full"}, obs)


def test_scope_path_share_counts_the_ops_under_the_kinds_scope(monkeypatch):
    under = lambda path: {"tf_op": f"jit(step)/{path}:"}    # noqa: E731
    labels = {
        "%f.1 = w": under("jvp(layers)/while/body/checkpoint/attention/"
                          "window/dot_general"),
        "%f.2 = w": under("transpose(jvp(layers))/while/body/checkpoint/"
                          "rematted_computation/attention/window/mul"),
        "%f.3 = f": under("jvp(layers)/while/body/checkpoint/attention/full/"
                          "flash.fwd.stream/pallas_call"),
        # the tables are the attention's, of no kind's layers
        "%f.4 = t": under("jvp(layers)/attention/cos"),
        "%f.5 = x": under("jvp(layers)/while/body/checkpoint/feed_forward/"
                          "router/window"),
        "%f.6 = n": {}}
    monkeypatch.setattr(op_scopes, "of_run", lambda: labels)
    obs = {"trace": {"window_s": 2.0, "device_ops": [
        [n, 0.1 * (i + 1)] for i, n in enumerate(labels)]}}
    window = scope_path_share.read({"path": ["attention", "window"]}, obs)
    assert math.isclose(window, 100 * (0.1 + 0.2) / 2.0)
    full = scope_path_share.read({"path": ["attention", "full"]}, obs)
    assert math.isclose(full, 100 * 0.3 / 2.0)
    assert scope_path_share.read({"path": ["attention", "latent"]},
                                 obs) is None
    assert scope_path_share.read({"path": ["attention", "full"]},
                                 {"trace": None}) is None
    monkeypatch.setattr(op_scopes, "of_run", lambda: None)
    assert scope_path_share.read({"path": ["attention", "full"]},
                                 obs) is None
    assert scope_path_share.holds(["jit(step)", "transpose(", "jvp(",
                                   "attention", "full", "mul"],
                                  ["attention", "full"])
    assert not scope_path_share.holds(["attention", "x", "full"],
                                      ["attention", "full"])


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_mixed"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    # a later PR may add to what the cell reports: held with <=, not ==
    assert {"window_attention_device_share", "full_attention_device_share",
            "flash_window_roofline.mellum", "flash_attention_roofline.mellum",
            "grouped_matmul_roofline.mellum", "expert_held_rows_share.mellum",
            "expert_load_max_over_mean.mellum", "train_step_ms",
            "train_report_ms", "train_report_span_ms",
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
    mix = resolve.cell(CELL)["mix"]
    assert (mix["seq"], mix["batch"]) == (16384, 1)
    # the new metrics read nothing where the program has nothing of theirs
    for name in ("window_attention_device_share",
                 "flash_window_roofline.mellum"):
        spec = resolve.layer_metric(name)
        assert resolve.reader(spec["reader"]).read(spec, {"trace": None}) \
            is None


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_mixed

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

    res = train_mixed.run(
        resolve.cell("rehearse-train-mixed"),
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

    from benchmark.kinds import train_mixed

    class Refused(Exception):
        pass

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(Refused, match="several kinds"):
        train_mixed.run(resolve.cell("rehearse-train-mixed"), None,
                        {"Refused": Refused})


# --- the cell's own limits refuse wrong models -----------------------------
WRONG = ["as it is", "window 39 for 40", "window on every layer",
         "plain table on the full layers", "h // 4 for h // 8",
         "8-bit attention weights", "one held expert fewer"]


@pytest.mark.parametrize("wrong", WRONG)
def test_the_cells_limits_fail_a_wrong_model(wrong):
    """At the toy size in bf16 on the CPU, against the limits the real cell
    is held to (``workloads/<cell>.json`` ``train.check``), which the toy
    as it is has to meet. (A softmax in bf16 cannot be told at this size:
    over 40 to 128 keys it moves a token's loss by 0.0079 on average where
    the bf16 model as it is reads 0.0072; ``tests/test_models_mellum.py``
    holds the float32 program to it.)"""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmark.kinds import train_mixed
    from ray_tpu.models import moe

    tol = resolve.workload(CELL)["train"]["check"]
    conf = dict(resolve.config("tiny-mellum"), num_key_value_heads=2,
                run={"dtype": "bfloat16", "param_dtype": "bfloat16"})
    sizes = model_mellum.sizes(conf)
    cfg = model_mellum.moe_config(conf, attn_impl="xla")
    params = moe.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 129), 0,
                                cfg.vocab_size, "int32")
    run_params, run_cfg = params, cfg
    (_, full), (_, window) = cfg.attn_kinds       # sorted by name
    kinds = lambda w, f: (("full", f), ("window", w))       # noqa: E731
    if wrong == "window 39 for 40":
        run_cfg = cfg.replace(attn_kinds=kinds(
            dataclasses.replace(window, window=39), full))
    elif wrong == "window on every layer":
        run_cfg = cfg.replace(attn_kinds=kinds(
            window, dataclasses.replace(full, window=40)))
    elif wrong == "plain table on the full layers":
        run_cfg = cfg.replace(attn_kinds=kinds(
            window, dataclasses.replace(full, yarn=None)))
    elif wrong == "h // 4 for h // 8":
        # query heads dealt round the two KV heads in place of 0-3, 4-7
        swap = jnp.asarray([0, 2, 4, 6, 1, 3, 5, 7])

        def regroup(lay):
            wq = lay["wq"].reshape(*lay["wq"].shape[:2], 8, 16)[:, :, swap]
            wo = lay["wo"].reshape(-1, 8, 16, 48)[:, swap]
            return dict(lay, wq=wq.reshape(lay["wq"].shape),
                        wo=wo.reshape(lay["wo"].shape))

        run_params = dict(params, layers=[regroup(lay)
                                          for lay in params["layers"]])
    elif wrong == "8-bit attention weights":
        run_params = dict(params, layers=[
            {k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
                 if k in ("wq", "wk", "wv", "wo") else w)
             for k, w in lay.items()} for lay in params["layers"]])
    elif wrong == "one held expert fewer":
        held, first = cfg.experts_held
        run_cfg = cfg.replace(experts_held=(held - 1, first))
        run_params = dict(params, layers=[
            {k: (w[:, :held - 1] if k.startswith("we_") else w)
             for k, w in lay.items()} for lay in params["layers"]])
    else:
        assert wrong == "as it is"
    _, reference = train_mixed.token_loss_fns(cfg, sizes)
    got, routes = train_mixed.token_loss_fns(run_cfg, sizes)[0](run_params,
                                                                tokens)
    ref, _, rec = reference(params, tokens, routes)
    a = train_mixed.loss_agreement(got, ref)
    r = train_mixed.route_agreement(routes, rec, cfg.top_k)
    ok = all(train_mixed.route_checks(r, tol, cfg.top_k).values()) \
        and a["token_mean_abs"] <= tol["token_mean_abs"] \
        and a["token_p999_abs"] <= tol["token_p999_abs"]
    assert ok == (wrong == "as it is"), (a, r, tol)
