"""The Solar-Open2 cell's yardstick: the arithmetic by hand, the reader on
synthetic traces, the manifest's lists, the catalog's numbers, the
benchmark's own copy of the reference, ``model_solar``'s refusals, and the
kind rehearsed on the CPU."""

import inspect
import json
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_solar, model_solar, reference_solar, resolve
from benchmark.kinds import train_solar
from benchmark.readers import solar_kernel_roofline

CELL = "train-solaropen2-ep32-s16384-b1"
CONFIG = "solar-open2-250b-ep32-l4"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _sizes():
    return model_solar.sizes(resolve.config(CONFIG))


def test_flops_by_hand():
    s = _sizes()
    assert (s["conv_taps"], s["d_ff"], s["shared_d_ff"], s["kda_heads"],
            s["kda_head_dim"], s["gate_rank"], s["top_k"], s["n_experts"],
            s["experts_held"]) == (4, 1280, 1280, 64, 128, 128, 8, 320,
                                   (10, 150))
    assert s["kinds"] == ("gqa", "kda", "kda", "kda")
    assert (s["n_heads"], s["n_kv_heads"], s["head_width"]) == (64, 8, 128)
    assert flops_solar.kinds(s) == {"kda": 3, "gqa": 1}
    # a KDA half: q, k, v and the output 4096 x 8192 each, the two pairs
    # 4096 x 128 + 128 x 8192 each, beta 4096 x 64: 137.63 M
    assert flops_solar.kda_params(s) == 4 * 4096 * 8192 + 2 * (
        4096 * 128 + 128 * 8192) + 4096 * 64
    # a grouped-query half: q, the gate and the output 4096 x 8192, k and
    # v 4096 x 1024: 109.05 M
    assert flops_solar.gqa_params(s) == 3 * 4096 * 8192 + 2 * 4096 * 1024
    parts = flops_solar.matmul_params_per_token(s)
    assert parts["router"] == 4 * 4096 * 320
    # 8 x 10 / 320 = a quarter of an expert a token under an even router
    assert parts["experts held"] == 4 * 0.25 * 3 * 4096 * 1280
    assert parts["shared expert"] == 4 * 3 * 4096 * 1280
    assert parts["head"] == 4096 * 24576
    # the cut holds 1,420.9 M parameters (ISSUE 61 counts 1,420.8 M
    # without the norms, the taps and the biases)
    assert flops_solar.total_params(s) == 1_420_916_544
    # the same keys count the whole model: 250.3 B, 14.7 B a token
    whole, used = flops_solar.published_params(s, resolve.config(CONFIG))
    assert round(whole / 1e9, 1) == 250.3 and round(used / 1e9, 1) == 14.7
    fwd = flops_solar.forward_flops_per_token(s, 16384)
    # q k^T and p v over 128 lanes, half the square, one layer
    assert fwd["attention"] == 2 * 16384 * 64 * 128
    assert fwd["delta rule"] == 3 * 7.0 * 64 * 128 * 128
    assert flops_solar.train_flops_per_token(s, 16384) \
        == 3 * sum(fwd.values())
    call = flops_solar.flash_call(s, 1, 16384, "fwd")
    assert call["ops"] == 2.0 * 16384 * 16384 * 64 * 128
    assert call["bytes"] == 16384 * 128 * 2 * (2 * 64 + 2 * 8)
    rule = flops_solar.delta_rule_layer(s, 1, 16384)
    assert rule["ops"] == 21.0 * 64 * 128 * 128 * 16384
    wide, heads = 16384 * 8192, 16384 * 64
    assert rule["bytes"] == (wide * 12 + heads * 4) * 2 + wide * 10 + heads * 4
    assert flops.least_seconds(rule, PEAK)["bound"] == "memory"
    gmm = flops_solar.grouped_matmul_call(4096.0, 10, s)
    assert gmm["ops"] == 2.0 * 4096 * 4096 * 1280


def test_the_program_counts_what_the_yardstick_counts():
    import jax.numpy as jnp

    from ray_tpu.models import solar

    conf = resolve.config(CONFIG)
    cfg = model_solar.solar_config(conf)
    assert solar.num_params(cfg) == flops_solar.total_params(_sizes())
    assert cfg.dtype == jnp.bfloat16 and cfg.run_layers == 1
    assert solar.layer_runs(cfg) == [("gqa", 1), ("kda", 1), ("kda", 1),
                                     ("kda", 1)]


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_solar as programs

    for name in ("_rms", "_sigmoid", "_silu", "_softplus", "layers",
                 "_conv_silu", "_l2", "delta_rule", "kda_inputs", "kda",
                 "gqa", "_swiglu", "experts", "first_half", "layer",
                 "forward", "token_losses", "loss", "biases", "bias_update"):
        assert inspect.getsource(getattr(reference_solar, name)) \
            == inspect.getsource(getattr(programs, name)), name


def test_a_file_whose_stated_form_the_block_is_not_is_refused():
    conf = resolve.config(CONFIG)
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True),
                       ("kda_allow_neg_eigval", False),
                       ("first_k_dense_replace", 1),
                       ("n_shared_experts", 2), ("model_type", "solar")):
        with pytest.raises(ValueError, match="solar_open2 block"):
            model_solar.sizes({**conf, key: value})
    with pytest.raises(ValueError, match="num_kv_heads"):
        model_solar.sizes({**conf, "linear_attn_config": {
            **conf["linear_attn_config"], "num_kv_heads": 8}})
    with pytest.raises(ValueError, match="experts_held"):
        model_solar.sizes({**conf, "n_routed_experts": 8})
    with pytest.raises(ValueError, match="layer 1"):
        model_solar.sizes({**conf, "gqa_layers": [1, 5]})
    with pytest.raises(KeyError, match="kda_gate_rank"):
        model_solar.sizes({k: v for k, v in conf.items()
                           if k != "kda_gate_rank"})


# --- the reader on synthetic traces ------------------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_solar.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 4096.0}, "peak": PEAK}


W = "bf16[1,16384,8192]{2,1,0}"
G = "f32[1,16384,8192]{2,1,0}"
BETA = "f32[1,32,16384,2]{3,2,1,0}"
STATE = "f32[1,256,8192,128]{3,2,1,0}"
RULE_FWD = _call(f"({W}, {STATE})", f"{W} %q, {W} %k, {W} %v, {G} %g, "
                 f"{BETA} %b, f32[1,8192,128]{{2,1,0}} %s")
RULE_BWD = _call(f"({W}, {W}, {W}, {G}, {BETA}, f32[1,8192,128]{{2,1,0}})",
                 f"{W} %q, {W} %k, {W} %v, {G} %g, {BETA} %b, {STATE} %s, "
                 f"{W} %do")
Q = "bf16[1,64,16384,128]{3,2,1,0}"
KV = "bf16[1,8,16384,128]{3,2,1,0}"
FLASH_FWD = _call(f"({Q}, f32[1,64,16384,128]{{3,2,1,0}})",
                  f"{Q} %q, {KV} %k, {KV} %v")
META = "s32[] %n, s32[10]{0} %o, s32[168]{0} %g, s32[168]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[6144,1280]{1,0}", f"{META}, bf16[6144,4096]{{1,0}} %x, "
            "bf16[10,4096,1280]{2,1,0} %w")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    kinds = [solar_kernel_roofline.classify(n, obs)[:2]
             for n in (RULE_FWD, RULE_BWD, FLASH_FWD, GMM)]
    assert kinds == [("delta_rule", "fwd"), ("delta_rule", "bwd"),
                     ("flash_attention", "fwd"), ("grouped_matmul", "")]
    # three KDA layers, four traced steps: the forward runs twice a layer
    # (the replay), the backward once
    obs["trace"] = {
        "device_ops": [[RULE_FWD, 0.8], [RULE_BWD, 0.7], [FLASH_FWD, 0.2],
                       [GMM, 0.01], ["%fusion.1 = x", 1.0]],
        "op_calls": {RULE_FWD: 24, RULE_BWD: 12, FLASH_FWD: 4, GMM: 24,
                     "%fusion.1 = x": 40}}
    layer = flops.least_seconds(flops_solar.delta_rule_layer(
        obs["sizes"], 1, 16384), PEAK)["seconds"]
    got = solar_kernel_roofline.read({"kernel": "delta_rule"}, obs)
    assert math.isclose(got, 100 * 12 * layer / 1.5) and 1 < got < 100
    flash = flops.least_seconds(flops_solar.flash_call(
        obs["sizes"], 1, 16384, "fwd"), PEAK)["seconds"]
    assert math.isclose(solar_kernel_roofline.read(
        {"kernel": "flash_attention"}, obs), 100 * 4 * flash / 0.2)
    assert 0 < solar_kernel_roofline.read({"kernel": "grouped_matmul"},
                                          obs) < 100
    # a program of another family (the parent's cells): nothing to read
    assert solar_kernel_roofline.read(
        {"kernel": "delta_rule"}, dict(obs, sizes={"d_model": 4096})) is None
    assert solar_kernel_roofline.read({"kernel": "delta_rule"},
                                      dict(obs, trace=None)) is None
    with pytest.raises(ValueError, match="no delta-rule call"):
        solar_kernel_roofline.classify(RULE_FWD.replace(",8192]", ",4096]"),
                                       obs)
    # Ling's flash call (32 heads of 192) is none of this cell's
    with pytest.raises(ValueError, match="no flash call"):
        solar_kernel_roofline.classify(
            FLASH_FWD.replace("[1,64,16384,128]", "[1,32,16384,192]")
            .replace("[1,8,16384,128]", "[1,32,16384,192]"), obs)


NEW = {"kda_device_share.solar", "kda_row_work_device_share.solar",
       "delta_rule_roofline.solar", "gqa_device_share.solar",
       "flash_attention_roofline.solar", "grouped_matmul_roofline.solar",
       "expert_layer_device_share.solar",
       "expert_dispatch_device_share.solar",
       "shared_expert_device_share.solar", "expert_held_rows_share.solar",
       "expert_load_max_over_mean.solar"}


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_solar"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert NEW | {
        "mixer_device_share", "router_device_share",
        "held_pass_walked_share", "held_pass_live_share",
        "held_further_pass_share", "remat_kept_gb",
        "train_step_ms", "train_report_ms", "train_report_span_ms",
        "device_idle_share.train", "device_idle_under_report.train",
        "compiles_in_window.train", "compiles_in_trace.train",
        "attention_device_share", "feed_forward_device_share",
        "head_loss_device_share", "optimizer_device_share",
        "layer_loop_device_share", "remat_replay_device_share",
        "unscoped_device_share", "setup_cluster_s", "setup_worker_group_s",
        "setup_chips_open_s", "setup_trace_lower_s",
        "setup_program_compile_s", "setup_program_load_s",
        "setup_host_freeze_s", "setup_unspanned_share"} == names
    man = resolve.manifest()
    for m in per_layer:
        spec = resolve.layer_metric(m["name"])
        assert spec["unit"] == m["unit"], m["name"]
        resolve.reader(spec["reader"])
    for m in man["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] \
                == "train_tok_s_chip"
            assert resolve.layer_metric(m["name"])["kinds"] == ["train_solar"]
    # an unlisted cell of the kind (the rehearsal) takes the kind's files
    assert {m["name"] for m in resolve.metrics_for(
        "rehearse-train-solar", "per_layer", cell_kind)} == NEW
    e2e = {m["name"] for m in resolve.metrics_for(CELL, "end_to_end",
                                                  cell_kind)}
    assert e2e == {"train_tok_s_chip", "setup_s"}
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == resolve.config(CONFIG)["reduced"] \
        == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert man["workloads"][-1]["name"] == CELL \
        and 13 <= len(man["workloads"]) <= 24
    cell = resolve.cell(CELL)
    assert (cell["mix"]["batch"], cell["mix"]["seq"], cell["chips"]) \
        == (1, 16384, 1)
    assert set(cell["train"]["check"]) == set(cell["train"]["check_why"])


def test_every_published_number_stands_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if '"name": "Solar-Open2-250B"' in ln)
    conf = resolve.config(CONFIG)
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    for key in ("assumed", "stands_for", "cut", "memory_plan", "deployment"):
        assert conf[key], key


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
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

    res = train_solar.run(
        resolve.cell("rehearse-train-solar"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 15 and all(res["checks"].values()), \
        res["checks"]
    assert any(k.startswith("the delta rule's calls alone, timed")
               for k in res["checks"])
    assert any(k.startswith("the first KDA layer's gate left the old bound")
               for k in res["checks"])
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert 0 < res["obs"]["values"]["held_rows"] <= 3 * 128 * 2
    assert res["obs"]["sizes"]["gate_rank"] == 8


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    """The parent commit's program has no ``models/solar.py``; the kind says
    so before a cluster starts."""
    import importlib.util

    class Refused(Exception):
        pass

    find = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a:
                        None if name == "ray_tpu.models.solar"
                        else find(name, *a))
    t0 = time.time()
    with pytest.raises(Refused, match="models/solar.py"):
        train_solar.run(resolve.cell(CELL), None, {"Refused": Refused})
    assert time.time() - t0 < 2.0
