"""The LFM2-8B-A1B cell's yardstick: the arithmetic by hand, the readers on
synthetic traces, the manifest's lists, the catalog's numbers, the
benchmark's own copy of the reference, and the kind rehearsed on the CPU."""

import inspect
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_lfm2, model_lfm2, reference_lfm2, resolve
from benchmark.readers import lfm2_expert_share, lfm2_kernel_roofline

CELL = "train-lfm2-ep4-s16384-b1"
CONFIG = "lfm2-8b-a1b-ep4"


def _sizes():
    return model_lfm2.sizes(resolve.config(CONFIG))


def test_flops_by_hand():
    s = _sizes()
    assert (s["conv_taps"], s["n_dense"], s["dense_d_ff"], s["d_ff"],
            s["head_width"], s["top_k"]) == (3, 2, 7168, 1792, 64, 4)
    assert flops_lfm2.kinds(s) == {"conv": 18, "attention": 6, "dense": 2,
                                   "experts": 22}
    parts = flops_lfm2.matmul_params_per_token(s)
    # 730 M multiply-adds a token forward on this chip (729 without the
    # routers)
    assert parts["convolution projections"] == 18 * 4 * 2048 * 2048
    assert parts["attention projections"] == 6 * (2 * 2048 * 2048
                                                  + 2 * 2048 * 512)
    assert parts["dense layers"] == 2 * 3 * 2048 * 7168
    assert parts["experts held"] == 22 * 1.0 * 3 * 2048 * 1792
    assert parts["head"] == 2048 * 16384
    assert round(sum(parts.values()) / 1e6) == 730
    assert flops_lfm2.total_params(s) == 2_425_961_920
    fwd = flops_lfm2.forward_flops_per_token(s, 16384)
    assert fwd["attention"] == 2.0 * 16384 * 32 * 64 * 6
    assert flops_lfm2.train_flops_per_token(s, 16384) == 3 * sum(fwd.values())
    call = flops_lfm2.flash_call(s, 1, 16384, "fwd")
    assert call["ops"] == 2.0 * 16384 * 16384 * 32 * 64
    assert call["bytes"] == 2 * 16384 * 64 * 2 * (32 + 8)
    gate = flops_lfm2.gate_conv_step(s, 16384)
    assert gate["bytes"] == 18 * 11 * 16384 * 2048 * 2
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(gate, peak)["bound"] == "memory"


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_lfm2 as programs

    for name in ("_rms", "_silu", "layers", "short_conv", "_rotary",
                 "attention", "_swiglu", "experts", "operator", "layer",
                 "forward", "token_losses", "loss", "biases", "bias_update"):
        assert inspect.getsource(getattr(reference_lfm2, name)) \
            == inspect.getsource(getattr(programs, name)), name


# --- readers on synthetic traces -------------------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_lfm2.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 16384.0},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


Q, K = "bf16[1,32,16384,64]{3,2,1,0}", "bf16[1,8,16384,64]{3,2,1,0}"
FLASH_FWD = _call(f"({Q}, f32[1,32,16384,128]{{3,2,1,0}})",
                  f"{Q} %q, {K} %k, {K} %v")
META = "s32[] %n, s32[10]{0} %o, s32[168]{0} %g, s32[168]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[24576,1792]{1,0}",
            f"{META}, bf16[24576,2048]{{1,0}} %x, "
            "bf16[8,2048,1792]{2,1,0} %w")
TGMM = _call("bf16[8,1792,2048]{2,1,0}",
             f"{META}, bf16[24576,1792]{{1,0}} %h, bf16[24576,2048]{{1,0}} %g")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    kinds = [lfm2_kernel_roofline.classify(n, obs)[0]
             for n in (FLASH_FWD, GMM, TGMM)]
    assert kinds == ["flash_attention", "grouped_matmul", "grouped_matmul"]
    _, call = lfm2_kernel_roofline.classify(GMM, obs)
    assert call["ops"] == 2.0 * 16384 * 2048 * 1792
    # heads padded to a lane tile: the same call, reckoned at 64
    padded = FLASH_FWD.replace(",16384,64]", ",16384,128]")
    assert lfm2_kernel_roofline.classify(padded, obs)[1] \
        == lfm2_kernel_roofline.classify(FLASH_FWD, obs)[1]
    obs["trace"] = {"device_ops": [[FLASH_FWD, 0.060], [GMM, 0.002],
                                   ["%fusion.1 = x", 1.0]],
                    "op_calls": {FLASH_FWD: 6, GMM: 22, "%fusion.1 = x": 40}}
    least = flops.least_seconds(flops_lfm2.flash_call(
        obs["sizes"], 1, 16384, "fwd"), obs["peak"])["seconds"]
    got = lfm2_kernel_roofline.read({"kernel": "flash_attention"}, obs)
    assert math.isclose(got, 100 * 6 * least / 0.060)
    assert 1 < got < 100
    # a program of another family (the parent's cells): nothing to read
    assert lfm2_kernel_roofline.read(
        {"kernel": "flash_attention"}, dict(obs, sizes={"d_model": 4096})) \
        is None
    with pytest.raises(ValueError, match="no flash call"):
        lfm2_kernel_roofline.classify(
            FLASH_FWD.replace("[1,32,", "[1,16,"), obs)


def test_the_expert_share_reader_fits_the_shapes():
    obs = _obs()
    ops = [[FLASH_FWD, 0.03], [GMM, 0.02],
           ["%fusion.2 = bf16[1,16384,6144]{2,1,0} fusion(x)", 0.10],
           ["%fusion.4 = bf16[16384,2048]{1,0} fusion(x)", 0.50],
           ["%fusion.5 = bf16[24576,2048]{1,0} fusion(x)", 0.04],
           ["%fusion.6 = s32[65536]{0} fusion(x)", 0.01]]
    obs["trace"] = {"device_ops": ops, "window_s": 1.0,
                    "op_calls": {n: 1 for n, _ in ops}}
    whole = resolve.layer_metric("expert_layer_device_share.lfm2")
    apart = resolve.layer_metric("expert_dispatch_device_share.lfm2")
    assert (whole["reader"], apart["reader"]) == ("lfm2_expert_share",) * 2
    assert math.isclose(lfm2_expert_share.read(whole, obs), 7.0)
    assert math.isclose(lfm2_expert_share.read(apart, obs), 5.0)
    assert lfm2_expert_share.read(whole, dict(obs, sizes={})) is None


NEW = {"short_conv_device_share", "short_conv_gate_roofline",
       "flash_attention_roofline.lfm2", "grouped_matmul_roofline.lfm2",
       "expert_layer_device_share.lfm2", "expert_dispatch_device_share.lfm2",
       "expert_held_rows_share.lfm2", "expert_load_max_over_mean.lfm2",
       "dense_ffn_device_share"}


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_shortconv"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert NEW | {
        "mixer_device_share", "held_pass_walked_share",
        "held_pass_live_share", "held_further_pass_share", "remat_kept_gb",
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
                == ["train_shortconv"]
    e2e = {m["name"] for m in resolve.metrics_for(CELL, "end_to_end",
                                                  cell_kind)}
    assert e2e == {"train_tok_s_chip", "setup_s"}
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == resolve.config(CONFIG)["reduced"] \
        == ["num_experts", "vocab_size"]
    cell = resolve.cell(CELL)
    assert (cell["mix"]["batch"], cell["mix"]["seq"], cell["chips"]) \
        == (1, 16384, 1)


def test_every_published_number_stands_but_the_reduced():
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f if '"LFM2-8B-A1B"' in ln)
    conf = resolve.config(CONFIG)
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    assert conf["num_hidden_layers"] == 24 == len(conf["layer_types"])


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_shortconv

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

    res = train_shortconv.run(
        resolve.cell("rehearse-train-shortconv"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 13 and all(res["checks"].values()), \
        res["checks"]
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert 0 < res["obs"]["values"]["held_rows"] <= 3 * 128 * 2
    assert res["obs"]["sizes"]["conv_taps"] == 3


def test_a_program_without_the_block_is_refused_at_once(monkeypatch):
    """The parent commit's program: its HybridConfig has no taps, no dense
    layers and no norm a head; the kind says so before a cluster starts."""
    import dataclasses

    from benchmark.kinds import train_shortconv
    from ray_tpu.models import hybrid

    class Refused(Exception):
        pass

    @dataclasses.dataclass(frozen=True)
    class Older:
        layer_types: tuple = ()

    monkeypatch.setattr(hybrid, "HybridConfig", Older)
    with pytest.raises(Refused, match="conv_taps"):
        train_shortconv.run(resolve.cell("rehearse-train-shortconv"),
                            None, {"Refused": Refused})
