"""The sparse-latent kind's yardstick: ``flops_glm52.py`` by hand, the two
copies of the plain reference, the roofline reader on synthetic Mosaic
lines, the cell's metric set, and the kind's ``run()`` rehearsed on the
CPU."""
import inspect
import os
import time
import types

import pytest

from benchmark import flops_glm52, model_glm52, reference_glm52, resolve
from benchmark.readers import glm52_kernel_roofline

CELL = "train-glm52-ep32-s16384-b1"
CONFIG = "glm-5.2-ep32-l5"
TOY = {"d_model": 8, "n_heads": 2, "q_rank": 4, "kv_rank": 2,
       "qk_nope_dim": 3, "qk_rope_dim": 1, "v_dim": 4, "n_experts": 8,
       "top_k": 4, "experts_held": (2, 0), "d_ff": 4, "shared_d_ff": 6,
       "dense_d_ff": 10, "n_layers": 3, "n_dense": 1, "vocab_size": 10,
       "index_heads": 2, "index_dim": 4, "index_topk": 3,
       "index_full": (True, False, True)}


def test_flops_by_hand():
    # sets of 3 keys over 5 queries: 1 + 2 + 3 + 3 + 3
    assert flops_glm52.selected_pairs(5, 3) == 12
    assert flops_glm52.selected_pairs(2, 3) == 3          # every earlier key
    assert flops_glm52.causal_pairs(5) == 15
    mla = 8 * 4 + 4 * 2 * 4 + 8 * (2 + 1) + 2 * 2 * (3 + 4) + 2 * 4 * 8
    assert flops_glm52.mla_params(TOY) == mla == 180
    index = 4 * 2 * 4 + 8 * (4 + 2)
    assert flops_glm52.index_params(TOY) == index == 80
    per = flops_glm52.matmul_params_per_token(TOY)
    assert per == {"latent projections": 3 * 180, "indexer projections": 160,
                   "dense layer": 3 * 8 * 10, "router": 2 * 64,
                   "shared": 2 * 3 * 8 * 6,
                   "experts held": 2 * 1.0 * 3 * 8 * 4, "head": 80}
    fwd = flops_glm52.forward_flops_per_token(TOY, 5)
    # 12 pairs x 2 heads x (4 + 4) lanes x 2, three blocks, a token of 5
    assert fwd["attention"] == 2.0 * 12 * 2 * 8 * 3 / 5
    # 15 causal pairs x 2 heads x 4 lanes x 2, two full layers
    assert fwd["index scores"] == 2.0 * 15 * 2 * 4 * 2 / 5
    assert flops_glm52.train_flops_per_token(TOY, 5) == 3 * sum(fwd.values())
    layer = flops_glm52.sparse_attention_layer(TOY, 1, 5)
    assert layer["ops"] == 3.5 * 2.0 * 12 * 2 * 8
    assert layer["bytes"] == 12 * (5 * 2 * 4 * 2) + 2 * 25


def test_flops_of_the_cell():
    cfg = model_glm52.sizes(resolve.config(CONFIG))
    assert flops_glm52.total_params(cfg) == 2_301_313_024
    assert flops_glm52.selected_pairs(16384, 2048) == 31_458_304
    fwd = flops_glm52.forward_flops_per_token(cfg, 16384)
    assert round(sum(fwd.values()) / 1e6) == 2473
    # the head is 9.6% of the forward, attention over the sets 12.7%
    assert 0.09 < fwd["head"] / sum(fwd.values()) < 0.10
    assert 0.12 < fwd["attention"] / sum(fwd.values()) < 0.13
    layer = flops_glm52.sparse_attention_layer(cfg, 1, 16384)
    assert round(layer["ops"] / 1e12, 2) == 3.61


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_glm52 as programs

    for name in ("_rms", "_layer_norm", "_swiglu", "_turn", "_heads",
                 "_index_inputs", "own_set", "_attention", "_experts",
                 "block", "forward", "token_losses", "loss", "biases",
                 "bias_update"):
        assert inspect.getsource(getattr(reference_glm52, name)) \
            == inspect.getsource(getattr(programs, name)), name
    for mod in (reference_glm52, programs):
        src = inspect.getsource(mod)
        assert "import" not in src.replace(
            "from __future__ import annotations", "").replace(
            "import jax.numpy as jnp", "").replace("import jax", ""), mod


# --- the reader on synthetic Mosaic lines ----------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_glm52.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 4096.0},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


Q = "bf16[1,32,16384,256]{3,2,1,0}"
LSE = "f32[1,32,16384,128]{3,2,1,0}"
SET = "s8[1,16384,16384]{2,1,0}"
FWD = _call(f"({Q}, {LSE})", f"{Q} %q, {Q} %k, {Q} %v, {SET} %m")
PROBS = _call("f32[1,16384,16384]{2,1,0}", f"{Q} %q, {Q} %k, {SET} %m, {LSE} %l")
DQ = _call(Q, f"{Q} %q, {Q} %k, {Q} %v, {SET} %m, {Q} %g, {Q} %o, {LSE} %l")
DKDV = _call(f"({Q}, {Q})",
             f"{Q} %q, {Q} %k, {Q} %v, {SET} %m, {Q} %g, {Q} %o, {LSE} %l")
META = "s32[] %n, s32[9]{0} %o, s32[72]{0} %g, s32[72]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[16384,2048]{1,0}",
            f"{META}, bf16[16384,6144]{{1,0}} %x, bf16[8,6144,2048]{{2,1,0}} %w")
TGMM = _call("bf16[8,6144,2048]{2,1,0}",
             f"{META}, bf16[16384,6144]{{1,0}} %x, bf16[16384,2048]{{1,0}} %g")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    for line, which in ((FWD, "fwd"), (PROBS, "probs"), (DQ, "dq"),
                        (DKDV, "dkdv")):
        assert glm52_kernel_roofline.classify(line, obs) == (
            "sparse_attention", which)
    for line in (GMM, TGMM):
        kernel, call = glm52_kernel_roofline.classify(line, obs)
        assert kernel == "grouped_matmul"
        assert call["ops"] == 2.0 * 4096 * 6144 * 2048
    trace = {"device_ops": [[GMM, 0.004], [TGMM, 0.006], [DQ, 0.1]],
             "op_calls": {GMM: 8, TGMM: 4, DQ: 5}, "window_s": 1.0}
    got = glm52_kernel_roofline.read({"kernel": "grouped_matmul"},
                                     {**obs, "trace": trace})
    least = 12 * max(2.0 * 4096 * 6144 * 2048 / 197e12, (
        4096 * 6144 + 4096 * 2048 + 8 * 6144 * 2048) * 2 / 819e9)
    assert got == pytest.approx(100 * least / 0.010)
    # nothing to read: no trace, another family's sizes, no scopes recorded
    assert glm52_kernel_roofline.read({"kernel": "grouped_matmul"},
                                      dict(obs, trace=None)) is None
    assert glm52_kernel_roofline.read(
        {"kernel": "grouped_matmul"},
        {**obs, "trace": trace, "sizes": {"kv_rank": 512}}) is None


def test_roofline_reader_raises_on_a_foreign_mosaic_call():
    obs = _obs()
    flash = _call(f"({Q}, {LSE})", f"{Q} %q, {Q} %k, {Q} %v")
    with pytest.raises(ValueError, match="no sparse-attention call"):
        glm52_kernel_roofline.classify(flash, obs)
    other = _call(f"({Q}, {LSE})",
                  f"bf16[1,16,16384,256]{{3,2,1,0}} %q, {Q} %k, {Q} %v, {SET} %m")
    with pytest.raises(ValueError):
        glm52_kernel_roofline.classify(other, obs)


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_sparse"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert {"sparse_attention_device_share", "indexer_device_share",
            "index_select_device_share", "index_loss_device_share",
            "sparse_attention_roofline", "grouped_matmul_roofline.glm52",
            "expert_held_rows_share.glm52",
            "expert_load_max_over_mean.glm52", "index_loss",
            "index_selected_share", "index_overlap", "remat_kept_gb",
            "held_pass_walked_share",
            # the fourteen every train cell reports
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
    assert (mix["seq"], mix["batch"]) == (16384, 1)
    assert [w["chips"] for w in man["workloads"]].count(4) <= 1
    assert 8 <= len(man["workloads"]) <= 24


def test_every_published_width_is_unchanged():
    """Against the catalog's numbers as ISSUE 45 quotes them."""
    conf = resolve.config(CONFIG)
    for key, value in {
            "hidden_size": 6144, "q_lora_rank": 2048, "kv_lora_rank": 512,
            "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
            "qk_head_dim": 256, "v_head_dim": 256, "index_n_heads": 32,
            "index_head_dim": 128, "index_topk": 2048,
            "intermediate_size": 12288, "moe_intermediate_size": 2048,
            "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
            "rms_norm_eps": 1e-05, "n_shared_experts": 1}.items():
        assert conf[key] == value, key
    assert conf["rope_parameters"] == {"rope_theta": 8000000,
                                       "rope_type": "default"}
    assert conf["deployment"]["router_experts"] == 256
    assert set(conf["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "num_attention_heads", "num_key_value_heads", "vocab_size",
        "num_nextn_predict_layers", "indexer_types", "mlp_layer_types"}
    assert conf["indexer_types"] == ["full", "shared", "shared", "shared",
                                     "full"]
    assert len(conf["assumed"]) >= 5


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_sparse

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

    res = train_sparse.run(
        resolve.cell("rehearse-train-sparse"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 18 and all(res["checks"].values()), \
        res["checks"]
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert 0 < res["obs"]["values"]["held_rows"] <= 2 * 128 * 2


def test_a_program_without_the_selection_is_refused_at_once(monkeypatch):
    import importlib.util

    from benchmark.kinds import train_sparse

    class Refused(Exception):
        pass

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(Refused, match="learned selection"):
        train_sparse.run(resolve.cell("rehearse-train-sparse"), None,
                         {"Refused": Refused})
