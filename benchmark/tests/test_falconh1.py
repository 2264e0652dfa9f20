"""The Falcon-H1 cell's yardstick: the arithmetic by hand, the reader on
synthetic traces, the manifest's lists, the catalog's numbers, the
benchmark's own copy of the reference, ``model_falconh1``'s refusals, and
the kind rehearsed on the CPU."""

import inspect
import json
import math
import os
import time
import types

import pytest

from benchmark import (flops, flops_falconh1, model_falconh1,
                       reference_falconh1, resolve)
from benchmark.kinds import train_falconh1
from benchmark.readers import falconh1_kernel_roofline

CELL = "train-falconh1-l4-s16384-b1"
CONFIG = "falcon-h1-34b-l4"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _sizes():
    return model_falconh1.sizes(resolve.config(CONFIG))


def test_flops_by_hand():
    s = _sizes()
    assert (s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_width"],
            s["d_ff"], s["n_layers"], s["vocab_size"]) == (
        5120, 20, 4, 128, 21504, 4, 32640)
    assert (s["mamba_heads"], s["mamba_head_dim"], s["mamba_state"],
            s["mamba_groups"], s["mamba_conv"], s["mamba_chunk"]) == (
        32, 128, 256, 2, 4, 128)
    assert s["rope_theta"] == 1e11 and isinstance(s["rope_theta"], float)
    # the attention half: Wq and Wo 5120 x 2560, Wk and Wv 5120 x 512
    assert flops_falconh1.attention_params(s) == 31_457_280
    # the mixer: in_proj 5120 x 9248 (z 4096 | x 4096 | B 512 | C 512 | dt
    # 32), out_proj 4096 x 5120
    assert flops_falconh1.mixer_sizes(s) == (4096, 5120, 9248)
    assert flops_falconh1.mixer_params(s) == 5120 * 9248 + 4096 * 5120
    # a layer, every leaf: ISSUE 63's 430,120,032; the cut 2,054,718,848;
    # the whole model, from the same keys, 33.64 B: the row's "34B"
    assert flops_falconh1.layer_params(s) == 430_120_032
    assert flops_falconh1.total_params(s) == 2_054_718_848
    whole = flops_falconh1.published_params(s, resolve.config(CONFIG)[
        "published"])
    assert whole == 72 * 430_120_032 + 2 * 261_120 * 5120 + 5120
    assert round(whole / 1e9, 2) == 33.64
    fwd = flops_falconh1.forward_flops_per_token(s, 16384)
    assert fwd["swiglu"] == 2 * 4 * 3 * 5120 * 21504
    # q k^T and p v over 128 lanes, half the square, four layers
    assert fwd["attention"] == 4 * 2 * 16384 * 20 * 128
    # a group's C B^T once (256 x 128 a token and group), a head's M u and
    # its state's two products
    assert fwd["scan"] == 4 * (2 * 256 * 128 + 32 * (128 * 128
                                                    + 4 * 128 * 256))
    assert flops_falconh1.train_flops_per_token(s, 16384) \
        == 3 * sum(fwd.values())
    # the SwiGLU leads: 64% of the forward's operations
    assert 0.63 < fwd["swiglu"] / sum(fwd.values()) < 0.65
    call = flops_falconh1.ssd_call(s, 1, 16384, "fwd")
    assert call["bytes"] == 2 * 16384 * 4096 * 2 + 2 * 16384 * 512 * 2 \
        + 2 * 16384 * 32 * 4 + 128 * 4096 * 256 * 4
    assert flops_falconh1.flash_call(s, 1, 16384, "fwd") \
        == flops.flash_call({"d_model": 2560, "n_heads": 20, "n_kv_heads": 4},
                            1, 16384, "fwd")


def test_the_program_counts_what_the_yardstick_counts():
    import jax.numpy as jnp

    from ray_tpu.models import falcon

    cfg = model_falconh1.falcon_config(resolve.config(CONFIG))
    assert falcon.num_params(cfg) == flops_falconh1.total_params(_sizes())
    assert cfg.dtype == jnp.bfloat16 and cfg.run_layers == 1
    assert falcon.layer_runs(cfg) == [("both", 1)] * 4
    assert cfg.attn_scale == 0.011048543456039804 * 128 ** -0.5
    # every mechanism alive under the multipliers: the factors' readings
    by = train_falconh1.seed_factors(cfg, 16384)
    assert math.isclose(by["wq"] ** 2 * cfg.key_multiplier, 1.0)
    assert [round(f * cfg.ssm_in_multiplier * m, 6) for f, m in zip(
        by["in_proj"], cfg.ssm_multipliers)] == [1, 1, 1, 1, 2]
    assert math.isclose(by["w_gate"] * cfg.mlp_multipliers[0], 1.0)
    assert by["lm_head"] == 128.0


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_falconh1 as programs

    for name in ("_rms", "_silu", "_in_blocks", "mup_vector", "_mixer",
                 "_rotary", "_attention", "_mlp", "hidden", "forward",
                 "token_losses", "loss"):
        assert inspect.getsource(getattr(reference_falconh1, name)) \
            == inspect.getsource(getattr(programs, name)), name


def test_a_file_whose_stated_form_the_block_is_not_is_refused():
    conf = resolve.config(CONFIG)
    for key, value in (("mamba_norm_before_gate", True),
                       ("mamba_rms_norm", False), ("mamba_conv_bias", False),
                       ("attn_layer_indices", [0, 2]),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"type": "yarn"}),
                       ("model_type", "falcon")):
        with pytest.raises(ValueError, match="falcon_h1 block"):
            model_falconh1.sizes({**conf, key: value})
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        model_falconh1.sizes({**conf, "mamba_d_ssm": 10240})
    with pytest.raises(ValueError, match="five values"):
        model_falconh1.sizes({**conf, "ssm_multipliers": [1.0, 1.0]})
    with pytest.raises(KeyError, match="key_multiplier"):
        model_falconh1.sizes({k: v for k, v in conf.items()
                              if k != "key_multiplier"})


# --- the reader on synthetic traces ------------------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_falconh1.sizes(cell["config"]), "cell": cell,
            "values": {}, "peak": PEAK}


U = "bf16[1,16384,4096]{2,1,0}"
BC = "bf16[1,16384,512]{2,1,0}"
COL, ROW = "f32[1,16384,32]{2,1,0}", "f32[1,32,16384]{2,1,0}"
STATE = "f32[1,128,4096,256]{3,2,1,0}"
SCAN_FWD = _call(f"({U}, {STATE})",
                 f"{U} %u, {BC} %b, {BC} %c, {COL} %col, {ROW} %row")
PART = "f32[1,2,16384,256]{3,2,1,0}"
SCAN_BWD = _call(
    f"({U}, {PART}, {PART}, f32[1,2,16384,32]{{3,2,1,0}}, {ROW})",
    f"{U} %u, {BC} %b, {BC} %c, {COL} %col, {ROW} %row, {STATE} %h, {U} %dy")
Q = "bf16[1,20,16384,128]{3,2,1,0}"
KV = "bf16[1,4,16384,128]{3,2,1,0}"
FLASH_FWD = _call(f"({Q}, f32[1,20,16384,128]{{3,2,1,0}})",
                  f"{Q} %q, {KV} %k, {KV} %v")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    kinds = [falconh1_kernel_roofline.classify(n, obs)[0]
             for n in (SCAN_FWD, SCAN_BWD, FLASH_FWD)]
    assert kinds == ["ssd_scan", "ssd_scan", "flash_attention"]
    # four layers, four traced steps: the scan's forward runs twice a layer
    # (the replay), its backward once
    obs["trace"] = {
        "device_ops": [[SCAN_FWD, 0.12], [SCAN_BWD, 0.2], [FLASH_FWD, 0.3],
                       ["%fusion.1 = x", 1.0]],
        "op_calls": {SCAN_FWD: 32, SCAN_BWD: 16, FLASH_FWD: 16,
                     "%fusion.1 = x": 40}}
    least = lambda which: flops.least_seconds(               # noqa: E731
        flops_falconh1.ssd_call(obs["sizes"], 1, 16384, which),
        PEAK)["seconds"]
    got = falconh1_kernel_roofline.read({"kernel": "ssd_scan"}, obs)
    assert math.isclose(got, 100 * (32 * least("fwd") + 16 * least("bwd"))
                        / 0.32) and 1 < got < 100
    flash = flops.least_seconds(flops_falconh1.flash_call(
        obs["sizes"], 1, 16384, "fwd"), PEAK)["seconds"]
    assert math.isclose(falconh1_kernel_roofline.read(
        {"kernel": "flash_attention"}, obs), 100 * 16 * flash / 0.3)
    # a program of another family (the parent's cells): nothing to read
    assert falconh1_kernel_roofline.read(
        {"kernel": "ssd_scan"}, dict(obs, sizes={"d_model": 4096})) is None
    assert falconh1_kernel_roofline.read({"kernel": "ssd_scan"},
                                         dict(obs, trace=None)) is None
    # Nemotron's scan (64 heads of 64) and Solar's flash are none of ours
    with pytest.raises(ValueError, match="no scan call"):
        falconh1_kernel_roofline.classify(
            SCAN_FWD.replace(",512]", ",1024]"), obs)
    with pytest.raises(ValueError, match="no flash call"):
        falconh1_kernel_roofline.classify(
            FLASH_FWD.replace("[1,20,", "[1,64,").replace("[1,4,", "[1,8,"),
            obs)


# four, not ISSUE 63's six: the manifest holds 128 per-layer metrics at
# most and had 124; the mixer's and the SwiGLU's whole shares are the
# accepted `mixer_device_share` and `feed_forward_device_share`
NEW = {"mixer_row_work_device_share.falconh1", "ssd_scan_roofline.falconh1",
       "flash_attention_roofline.falconh1", "block_sum_device_share.falconh1"}


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_falconh1"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert NEW | {
        "mixer_device_share",
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
            assert resolve.layer_metric(m["name"])["kinds"] \
                == ["train_falconh1"]
    # an unlisted cell of the kind (the rehearsal) takes the kind's files
    assert {m["name"] for m in resolve.metrics_for(
        "rehearse-train-falconh1", "per_layer", cell_kind)} == NEW
    e2e = {m["name"] for m in resolve.metrics_for(CELL, "end_to_end",
                                                  cell_kind)}
    assert e2e == {"train_tok_s_chip", "setup_s"}
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == resolve.config(CONFIG)["reduced"] \
        == ["num_hidden_layers", "vocab_size"]
    # (not "the last cell": a later PR appends its own after it)
    assert CELL in [w["name"] for w in man["workloads"]] \
        and 14 <= len(man["workloads"]) <= 24 and len(man["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    cell = resolve.cell(CELL)
    assert (cell["mix"]["batch"], cell["mix"]["seq"], cell["chips"]) \
        == (1, 16384, 1)
    assert set(cell["train"]["check"]) == set(cell["train"]["check_why"])


def test_every_published_number_stands_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if '"name": "Falcon-H1-34B-Instruct"' in ln)
    conf = resolve.config(CONFIG)
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    assert (conf["num_hidden_layers"], conf["vocab_size"]) == (4, 32640)
    assert 8 * conf["vocab_size"] == row["config"]["vocab_size"]
    for key in ("assumed", "stands_for", "cut", "memory_plan"):
        assert conf[key] and "TO BE" not in conf[key], key


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

    res = train_falconh1.run(
        resolve.cell("rehearse-train-falconh1"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 11 and all(res["checks"].values()), \
        res["checks"]
    assert any(k.startswith("the scan's calls alone, timed")
               for k in res["checks"])
    assert sum(k.startswith("alive: ") for k in res["checks"]) == 2
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert res["obs"]["sizes"]["ssm_multipliers"] == (0.7, 0.5, 0.35, 1.4,
                                                      0.6)
    # the block's instant and the alive readings are in the job's timeline
    with open(tmp_path / "bench_rehearse-train-falconh1"
              / "timeline.json") as f:
        events = json.load(f)
    events = events["traceEvents"] if isinstance(events, dict) else events
    plans = {e["name"]: e["args"]["attrs"] for e in events
             if e["name"] in ("hybrid.layer_plan", "ssd.plan", "mixer.plan")}
    assert plans["hybrid.layer_plan"]["first_halves"] == "attention+mixer"
    assert plans["hybrid.layer_plan"]["key_multiplier"] == "0.25"
    assert plans["ssd.plan"]["groups"] == 2 and plans["ssd.plan"]["state"] \
        == 32 and plans["mixer.plan"]["groups"] == 2


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    """The parent commit's program has no ``models/falcon.py``; the kind
    says so before a cluster starts."""
    import importlib.util

    class Refused(Exception):
        pass

    find = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a:
                        None if name == "ray_tpu.models.falcon"
                        else find(name, *a))
    t0 = time.time()
    with pytest.raises(Refused, match="models/falcon.py"):
        train_falconh1.run(resolve.cell(CELL), None, {"Refused": Refused})
    assert time.time() - t0 < 2.0
