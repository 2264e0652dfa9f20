"""The alternating kind's yardstick: ``flops_nemotron.py`` by hand, the two
copies of the plain reference, the new readers on synthetic traces, the
manifest's lists, the kind's ``run()`` rehearsed on the CPU and refused at
once by a program without the block."""
import inspect
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_nemotron, model_nemotron
from benchmark import reference_nemotron, resolve
from benchmark.readers import held_expert_share, nemotron_kernel_roofline

CELL = "train-nemotron3nano-ep8-s8192-b2"
CONFIG = "nemotron-3-nano-30b-a3b-ep8"


def _sizes():
    return model_nemotron.sizes(resolve.config(CONFIG))


def test_flops_by_hand():
    s = _sizes()
    assert (s["mamba_heads"], s["mamba_head_dim"], s["mamba_state"],
            s["mamba_groups"], s["mamba_chunk"]) == (64, 64, 128, 8, 128)
    assert flops_nemotron.mixer_widths(s) == (4096, 6144, 10304)
    # C.B once a GROUP and chunk, the [Q, Q] and the state products a head
    assert flops_nemotron.scan_flops_per_token(s) \
        == 8 * 128 * 128 + 64 * (64 * 128 + 4 * 64 * 128)
    kinds = flops_nemotron.kinds(s)
    assert kinds == {"mamba": 9, "attention": 3, "experts": 8}
    parts = flops_nemotron.matmul_params_per_token(s)
    assert parts["mixer projections"] == 9 * (2688 * 10304 + 4096 * 2688)
    assert parts["attention projections"] == 3 * (
        2 * 2688 * 4096 + 2 * 2688 * 256)
    # TWO matrices an expert, at the published 1,856; 6 x 16 / 128 held
    assert parts["experts held"] == 8 * 0.75 * 2 * 2688 * 1856
    assert parts["shared"] == 8 * 2 * 2688 * 3712
    assert parts["head"] == 2688 * 16384
    fwd = flops_nemotron.forward_flops_per_token(s, 8192)
    assert fwd["attention"] == 2 * 3 * 8192 * 32 * 128      # 2 units a block
    assert flops_nemotron.train_flops_per_token(s, 8192) \
        == 3.0 * sum(fwd.values())
    # 9 x 38.74 M + 3 x 23.40 M + 8 x 179.95 M + 88.1 M
    assert round(flops_nemotron.total_params(s) / 1e6, 1) == 1946.6
    call = flops_nemotron.ssd_call(s, 2, 8192, "fwd")
    assert call["ops"] == 16384 * flops_nemotron.scan_flops_per_token(s)
    assert call["bytes"] == (2 * 16384 * 4096 * 2 + 2 * 16384 * 1024 * 2
                             + 2 * 16384 * 64 * 4 + 2 * 64 * 4096 * 128 * 4)
    bwd = flops_nemotron.ssd_call(s, 2, 8192, "bwd")
    assert bwd["ops"] == 2 * call["ops"] + 16384 * 8 * 128 * 128
    assert flops_nemotron.flash_call(s, 2, 8192, "fwd")["ops"] \
        == 2.0 * 8192 * 8192 * 32 * 128 * 2
    # the published width whatever a program stores
    assert flops_nemotron.grouped_matmul_call(1000, 16, s)["ops"] \
        == 2.0 * 1000 * 2688 * 1856
    assert flops_nemotron.shared_step(s, 16384)["ops"] \
        == 6.0 * 8 * 2 * 2688 * 3712 * 16384


def test_the_count_is_the_programs():
    from ray_tpu.models import hybrid

    conf = resolve.config(CONFIG)
    assert flops_nemotron.total_params(_sizes()) \
        == hybrid.num_params(model_nemotron.hybrid_config(conf))


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_nemotron as programs

    for name in ("_rms", "blocks", "_mamba", "_attention", "_relu2",
                 "_experts", "block", "forward", "token_losses", "loss",
                 "biases", "bias_update"):
        assert inspect.getsource(getattr(reference_nemotron, name)) \
            == inspect.getsource(getattr(programs, name)), name


# --- readers on synthetic traces -------------------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_nemotron.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 12288.0},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


U, BC = "bf16[2,8192,4096]{2,1,0}", "bf16[2,8192,1024]{2,1,0}"
CUM, CUMT = "f32[2,8192,64]{2,1,0}", "f32[2,64,8192]{2,1,0}"
STATES = "f32[2,64,4096,128]{3,2,1,0}"
SCAN_FWD = _call(f"({U}, {STATES})",
                 f"{U} %u, {BC} %b, {BC} %c, {CUM} %col, {CUMT} %row")
SCAN_BWD = _call(
    f"({U}, f32[2,8,8192,128]{{3,2,1,0}}, f32[2,8,8192,128]{{3,2,1,0}}, "
    f"f32[2,8,8192,64]{{3,2,1,0}}, {CUMT})",
    f"{U} %u, {BC} %b, {BC} %c, {CUM} %col, {CUMT} %row, {STATES} %h, "
    f"{U} %dy")
Q, K = "bf16[2,32,8192,128]{3,2,1,0}", "bf16[2,2,8192,128]{3,2,1,0}"
FLASH_FWD = _call(f"({Q}, f32[2,32,8192,128]{{3,2,1,0}})",
                  f"{Q} %q, {K} %k, {K} %v")
META = "s32[] %n, s32[10]{0} %o, s32[168]{0} %g, s32[168]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[24576,1856]{1,0}",
            f"{META}, bf16[24576,2688]{{1,0}} %x, "
            "bf16[16,2688,1856]{2,1,0} %w")
TGMM = _call("bf16[16,1856,2688]{2,1,0}",
             f"{META}, bf16[24576,1856]{{1,0}} %h, bf16[24576,2688]{{1,0}} %g")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    kinds = {n: nemotron_kernel_roofline.classify(n, obs)[0]
             for n in (SCAN_FWD, SCAN_BWD, FLASH_FWD, GMM, TGMM)}
    assert list(kinds.values()) == ["ssd_scan", "ssd_scan", "flash_attention",
                                    "grouped_matmul", "grouped_matmul"]
    _, call = nemotron_kernel_roofline.classify(GMM, obs)
    assert call["ops"] == 2.0 * 12288 * 2688 * 1856
    # weights stored at 1,920: the same call, the published operations
    padded = GMM.replace("1856", "1920")
    assert nemotron_kernel_roofline.classify(padded, obs)[1] == call
    obs["trace"] = {"device_ops": [[SCAN_FWD, 0.030], [SCAN_BWD, 0.050],
                                   [FLASH_FWD, 0.010], ["%fusion.1 = x", 1.0]],
                    "op_calls": {SCAN_FWD: 6, SCAN_BWD: 6, FLASH_FWD: 1,
                                 "%fusion.1 = x": 40}}
    least = sum(flops.least_seconds(flops_nemotron.ssd_call(
        obs["sizes"], 2, 8192, w), obs["peak"])["seconds"]
        for w in ("fwd", "bwd"))
    got = nemotron_kernel_roofline.read({"kernel": "ssd_scan"}, obs)
    assert math.isclose(got, 100 * 6 * least / 0.080)
    assert 1 < got < 100
    assert nemotron_kernel_roofline.read({"kernel": "grouped_matmul"},
                                         obs) is None      # none in the trace
    # a program of another family (the parent's cells): nothing to read
    assert nemotron_kernel_roofline.read(
        {"kernel": "ssd_scan"}, dict(obs, sizes={"d_model": 4096})) is None
    # the one-group scan's B is foreign here
    with pytest.raises(ValueError, match="no scan call"):
        nemotron_kernel_roofline.classify(
            SCAN_FWD.replace("[2,8192,1024]", "[2,8192,128]"), obs)


def test_the_expert_share_reader_fits_the_shapes():
    """The expert layer and its dispatch alone, through the cell's own
    metric files: the rows held (read from the grouped matmul's operand)
    and the T K assignments; the mixers' [B, S, .] rows (as wide as the
    attention blocks' q and o here: 64 x 64 = 32 x 128) are not in."""
    obs = _obs()
    ops = [[SCAN_FWD, 0.03], [GMM, 0.02],
           ["%fusion.2 = bf16[2,8192,10304]{2,1,0} fusion(x)", 0.10],
           ["%fusion.3 = bf16[2,8192,2048]{2,1,0} fusion(x)", 0.05],
           ["%fusion.4 = bf16[2,8192,2688]{2,1,0} fusion(x)", 0.50],
           ["%fusion.5 = bf16[24576,2688]{1,0} fusion(x)", 0.04],
           ["%fusion.6 = s32[98304]{0} fusion(x)", 0.01]]
    obs["trace"] = {"device_ops": ops, "window_s": 1.0,
                    "op_calls": {n: 1 for n, _ in ops}}
    whole = resolve.layer_metric("expert_layer_device_share.nemotron")
    apart = resolve.layer_metric("expert_dispatch_device_share.nemotron")
    assert (whole["reader"], apart["reader"]) == ("held_expert_share",) * 2
    assert math.isclose(held_expert_share.read(whole, obs), 7.0)
    assert math.isclose(held_expert_share.read(apart, obs), 5.0)


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_alternating"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert {"ssd_scan_roofline.nemotron", "grouped_matmul_roofline.nemotron",
            "flash_attention_roofline.nemotron",
            "shared_expert_roofline.nemotron",
            "expert_held_rows_share.nemotron",
            "expert_load_max_over_mean.nemotron",
            "expert_layer_device_share.nemotron",
            "expert_dispatch_device_share.nemotron", "mixer_device_share",
            "shared_expert_device_share", "held_pass_walked_share",
            "remat_kept_gb", "train_step_ms", "train_report_ms",
            "train_report_span_ms", "device_idle_share.train",
            "device_idle_under_report.train", "compiles_in_window.train",
            "compiles_in_trace.train", "attention_device_share",
            "feed_forward_device_share", "head_loss_device_share",
            "optimizer_device_share", "layer_loop_device_share",
            "remat_replay_device_share", "unscoped_device_share"} <= names
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
    cell = resolve.cell(CELL)
    assert (cell["mix"]["batch"], cell["mix"]["seq"]) == (2, 8192)
    # every new metric lists its cells
    assert all("workloads" in m for m in man["per_layer"]
               if m["name"].endswith(".nemotron"))


def test_every_published_number_stands_but_the_reduced():
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16" in ln)
    conf = resolve.config(CONFIG)
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    assert row["config"]["hybrid_override_pattern"].startswith(
        conf["hybrid_override_pattern"])


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_alternating

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

    res = train_alternating.run(
        resolve.cell("rehearse-train-alternating"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 13 and all(res["checks"].values()), \
        res["checks"]
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert 0 < res["obs"]["values"]["held_rows"] <= 2 * 128 * 2
    assert res["obs"]["sizes"]["mamba_groups"] == 2


def test_a_program_without_the_block_is_refused_at_once(monkeypatch):
    """The parent commit's program: its HybridConfig has no groups, no
    one-half blocks and no two-matrix experts; the kind says so before a
    cluster starts."""
    import dataclasses

    from benchmark.kinds import train_alternating
    from ray_tpu.models import hybrid

    class Refused(Exception):
        pass

    @dataclasses.dataclass(frozen=True)
    class Older:
        layer_types: tuple = ()

    monkeypatch.setattr(hybrid, "HybridConfig", Older)
    with pytest.raises(Refused, match="mamba_groups"):
        train_alternating.run(resolve.cell("rehearse-train-alternating"),
                              None, {"Refused": Refused})
