"""The expert kind's yardstick: ``flops_moe.py`` by hand, the two copies
of the plain reference, the new readers on synthetic traces, the kind's
``run()`` rehearsed on the CPU, and the cell's own limits against wrong
models."""
import inspect
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_moe, model_moe, reference_olmoe, resolve
from benchmark.readers import expert_share, moe_kernel_roofline

CELL = "train-olmoe1b7b-s4096-b4"


def test_flops_by_hand():
    # 6 rows of width 4 -> 2 through 3 experts: 2*6*4*2 = 96 operations;
    # bf16 bytes: rows in 6*4, rows out 6*2, matrices 3*4*2 = 60 elements
    call = flops_moe.grouped_matmul_call(6, 4, 2, 3)
    assert call == {"ops": 96.0, "bytes": 120.0}
    # the weight gradient [6,4]^T [6,2] by group -> [3,4,2]: the same
    assert flops_moe.grouped_matmul_call(6, 2, 4, 3) == call
    cfg = {"d_model": 8, "n_heads": 2, "n_kv_heads": 2, "d_ff": 4,
           "n_experts": 4, "top_k": 2, "n_layers": 3, "vocab_size": 10}
    # a layer: q, k, v, o 4*8*8 = 256; router 8*4 = 32; 2 experts of
    # 3*8*4 = 96 -> 192; head 8*10 = 80
    assert flops_moe.active_matmul_params(cfg) == 3 * (256 + 32 + 192) + 80
    # all 4 experts 384, four norms 2*8 + 8 + 8; embedding, head, final norm
    assert flops_moe.total_params(cfg) == 3 * (256 + 32 + 384 + 32) + 160 + 8
    # attention: 6 units of S*S*H*HD/S a layer and token = 6*16*8 = 768
    assert flops_moe.train_flops_per_token(cfg, 16) \
        == 6 * 1520 + 3 * 768


def test_flops_of_the_cell():
    sizes = model_moe.sizes(resolve.config("olmoe-1b-7b-1chip"))
    assert sizes["n_experts"] == 64 and sizes["top_k"] == 8
    assert sizes["d_ff"] == 1024 and sizes["qk_norm"] is True
    per_layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert flops_moe.active_matmul_params(sizes) \
        == 4 * per_layer + 2048 * 50304
    assert math.isclose(flops_moe.train_flops_per_token(sizes, 4096) / 1e9,
                        2.433, abs_tol=1e-3)
    assert math.isclose(flops_moe.total_params(sizes) / 1e9, 1.884,
                        abs_tol=1e-3)
    full = dict(sizes, n_layers=16)
    assert math.isclose(flops_moe.total_params(full) / 1e9, 6.92,
                        abs_tol=0.01)


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_olmoe as programs

    for name in ("_rms", "_rope", "_experts", "forward", "router_losses",
                 "token_losses", "loss"):
        assert inspect.getsource(getattr(reference_olmoe, name)) \
            == inspect.getsource(getattr(programs, name)), name


# --- readers on synthetic traces -------------------------------------------
def _gmm(result, a, b):
    meta = ("s32[] %n, s32[65]{0} %o, s32[511]{0} %g, s32[511]{0} %t, "
            "s32[1]{0} %f")
    return (f"%gmm.1 = {result}{{1,0:T(8,128)(2,1)}} custom-call({meta}, "
            f"{a}{{1,0}} %x, {b}{{2,1,0}} %w), "
            'custom_call_target="tpu_custom_call", operand_layout')


FLASH_FWD = ("%closed_call.1 = (bf16[4,16,4096,128]{3,2,1,0}, "
             "f32[4,16,4096,128]{3,2,1,0}) custom-call(bf16[4,16,4096,128]"
             "{3,2,1,0} %q, bf16[4,16,4096,128]{3,2,1,0} %k, "
             "bf16[4,16,4096,128]{3,2,1,0} %v), "
             'custom_call_target="tpu_custom_call", x')
FORWARD = _gmm("bf16[131072,1024]", "bf16[131072,2048]", "bf16[64,2048,1024]")
INPUT_GRAD = _gmm("bf16[131072,2048]", "bf16[131072,1024]",
                  "bf16[64,2048,1024]")
WEIGHT_GRAD = _gmm("bf16[64,2048,1024]", "bf16[131072,2048]",
                   "bf16[131072,1024]")
FOREIGN = ("%other.1 = bf16[128,128]{1,0} custom-call(bf16[128,128]{1,0} %a),"
           ' custom_call_target="tpu_custom_call", y')
GATHER = ("%fusion.9 = bf16[131072,2048]{1,0:T(8,128)(2,1)} fusion("
          "bf16[16384,2048]{1,0} %x, s32[131072]{0} %order), kind=kCustom")
COMBINE = ("%fusion.10 = bf16[16384,2048]{1,0} fusion(bf16[16384,8,2048]"
           "{2,1,0} %rows, f32[16384,8]{1,0} %w), kind=kLoop")
DENSE = ("%fusion.11 = bf16[16384,2048]{1,0} fusion(bf16[16384,2048]{1,0} "
         "%x, bf16[2048,2048]{1,0} %w), kind=kOutput")
WHILE = ("%while.1 = (s32[], bf16[4,131072,8]{2,1,0}) while((s32[], "
         "bf16[131072,2048]{1,0}) %t), condition=%c, body=%b")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _obs(ops: dict) -> dict:
    cell = resolve.cell(CELL)
    return {"trace": {"window_s": 1.0, "busy_s": 0.9,
                      "device_ops": [[n, s] for n, (s, _) in ops.items()],
                      "op_calls": {n: c for n, (_, c) in ops.items()}},
            "peak": PEAK, "cell": cell,
            "sizes": model_moe.sizes(cell["config"])}


def test_roofline_reader_tells_the_calls_apart():
    least = flops.least_seconds(flops_moe.grouped_matmul_call(
        131072, 2048, 1024, 64), PEAK)
    assert least["bound"] == "compute"
    assert math.isclose(least["seconds"], 2 * 131072 * 2048 * 1024 / 197e12)
    # three grouped calls at twice their least time, a flash forward at 4x
    flash = flops.least_seconds(flops.flash_call(
        {"n_heads": 16, "n_kv_heads": 16, "d_model": 2048}, 4, 4096, "fwd"),
        PEAK)["seconds"]
    obs = _obs({FORWARD: (2 * least["seconds"], 1),
                INPUT_GRAD: (4 * least["seconds"], 2),
                WEIGHT_GRAD: (2 * least["seconds"], 1),
                FLASH_FWD: (4 * flash, 1), GATHER: (0.1, 4)})
    read = moe_kernel_roofline.read
    assert math.isclose(read({"kernel": "grouped_matmul"}, obs), 50.0)
    assert math.isclose(read({"kernel": "flash_attention"}, obs), 25.0)
    assert moe_kernel_roofline.classify(WEIGHT_GRAD, obs)[0] \
        == "grouped_matmul"


def test_roofline_reader_raises_on_a_foreign_mosaic_call():
    obs = _obs({FORWARD: (0.01, 1), FLASH_FWD: (0.01, 1),
                FOREIGN: (0.01, 1)})
    with pytest.raises(ValueError, match="no flash call.*no grouped"):
        moe_kernel_roofline.read({"kernel": "grouped_matmul"}, obs)
    # a grouped matmul of other rows than the cell's is foreign too
    other = _gmm("bf16[65536,1024]", "bf16[65536,2048]", "bf16[64,2048,1024]")
    with pytest.raises(ValueError):
        moe_kernel_roofline.read({"kernel": "grouped_matmul"},
                                 _obs({other: (0.01, 1)}))


def test_roofline_reader_reads_nothing_without_its_kernel():
    obs = _obs({FLASH_FWD: (0.01, 1), GATHER: (0.1, 4)})
    assert moe_kernel_roofline.read({"kernel": "grouped_matmul"}, obs) is None
    assert moe_kernel_roofline.read({"kernel": "grouped_matmul"},
                                    {"trace": None, "peak": PEAK}) is None


def test_expert_share_counts_what_is_rows_wide():
    obs = _obs({FORWARD: (0.20, 4), GATHER: (0.10, 4), COMBINE: (0.05, 4),
                DENSE: (0.30, 4), FLASH_FWD: (0.10, 4), WHILE: (0.01, 1)})
    assert math.isclose(expert_share.read({"with_matmuls": True}, obs), 35.0)
    assert math.isclose(expert_share.read({"with_matmuls": False}, obs), 15.0)
    # a program with no expert layer (the parent, a dense cell): nothing
    dense = _obs({DENSE: (0.3, 4), FLASH_FWD: (0.1, 4)})
    assert expert_share.read({"with_matmuls": True}, dense) is None
    no_moe = dict(dense, sizes={"d_model": 2048})
    assert expert_share.read({"with_matmuls": True}, no_moe) is None


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_moe"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert {"grouped_matmul_roofline", "flash_attention_roofline.olmoe",
            "expert_layer_device_share", "expert_dispatch_device_share",
            "expert_load_max_over_mean", "train_step_ms", "train_report_ms",
            "train_report_span_ms", "device_idle_share.train",
            "device_idle_under_report.train", "compiles_in_window.train",
            "compiles_in_trace.train"} == names
    assert "flash_attention_roofline" not in names   # that reader raises
    for m in per_layer:
        spec = resolve.layer_metric(m["name"])
        assert spec["unit"] == m["unit"], m["name"]
        resolve.reader(spec["reader"])
    e2e = {m["name"] for m in resolve.metrics_for(CELL, "end_to_end",
                                                  cell_kind)}
    assert e2e == {"train_tok_s_chip", "setup_s"}


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_moe

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

    res = train_moe.run(
        resolve.cell("rehearse-train-moe"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 12 and all(res["checks"].values()), \
        res["checks"]
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}


def test_a_program_without_the_expert_layer_is_refused_at_once(monkeypatch):
    import importlib.util

    from benchmark.kinds import train_moe

    class Refused(Exception):
        pass

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(Refused, match="grouped_matmul"):
        train_moe.run(resolve.cell("rehearse-train-moe"), None,
                      {"Refused": Refused})


# --- the cell's own limits refuse wrong models -----------------------------
@pytest.mark.parametrize("wrong", ["8-bit expert weights", "one expert fewer",
                                   "renormalised weights"])
def test_the_cells_limits_fail_a_wrong_model(wrong):
    """At the toy size in bf16 on the CPU, against the limits the real cell
    is held to (``workloads/<cell>.json`` ``train.check``)."""
    import jax

    from benchmark.kinds import train_moe
    from ray_tpu.models import moe

    tol = resolve.workload(CELL)["train"]["check"]
    conf = dict(resolve.config("tiny-olmoe"),
                run={"dtype": "bfloat16", "param_dtype": "bfloat16"})
    sizes = model_moe.sizes(conf)
    cfg = model_moe.moe_config(conf, attn_impl="xla")
    params = moe.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 257), 0,
                                cfg.vocab_size, "int32")
    run_params, run_cfg = params, cfg
    if wrong == "8-bit expert weights":
        run_params = dict(params, layers={
            k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
                if k.startswith("we_") else w)
            for k, w in params["layers"].items()})
    elif wrong == "one expert fewer":
        run_cfg = cfg.replace(top_k=cfg.top_k - 1)
    else:
        run_cfg = cfg.replace(norm_topk=True)
    _, reference = train_moe.token_loss_fns(cfg, sizes)
    got, routes = train_moe.token_loss_fns(run_cfg, sizes)[0](run_params,
                                                              tokens)
    ref, _, rec = reference(params, tokens, routes)
    a = train_moe.loss_agreement(got, ref)
    r = train_moe.route_agreement(routes, rec, cfg.top_k)
    ok = all(train_moe.route_checks(r, tol, cfg.top_k).values()) \
        and a["token_mean_abs"] <= tol["token_mean_abs"] \
        and a["token_p999_abs"] <= tol["token_p999_abs"]
    assert not ok, (a, r, tol)
