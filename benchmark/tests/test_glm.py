"""The latent kind's yardstick: ``flops_glm.py`` by hand, the two copies of
the plain reference, the new readers on synthetic traces, the kind's
``run()`` rehearsed on the CPU, and the cell's own limits against wrong
models."""
import inspect
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_glm, model_glm, reference_glm, resolve
from benchmark.readers import glm_kernel_roofline, glm_share

CELL = "train-glm47flash-ep8-s8192-b2"
CONFIG = "glm-4.7-flash-ep8-l12"
TOY = {"d_model": 8, "n_heads": 2, "q_rank": 4, "kv_rank": 2,
       "qk_nope_dim": 3, "qk_rope_dim": 1, "v_dim": 4, "n_experts": 8,
       "top_k": 4, "experts_held": (2, 0), "d_ff": 4, "shared_d_ff": 6,
       "dense_d_ff": 10, "n_layers": 3, "n_dense": 1, "n_mtp": 1,
       "vocab_size": 10}


def test_flops_by_hand():
    # 8 -> 4 -> 2 heads of 4; 8 -> 2 + 1; 2 -> 2 heads of 3 + 4; 8 -> 8
    assert flops_glm.mla_params(TOY) == 32 + 32 + 24 + 28 + 64
    parts = flops_glm.matmul_params_per_token(TOY)
    # one dense block, two sparse and the prediction module's
    assert parts["latent projections"] == 4 * 180
    assert parts["dense layer"] == 3 * 8 * 10
    assert parts["router"] == 3 * 8 * 8 and parts["shared"] == 3 * 3 * 8 * 6
    # 4 a token, 2 of 8 held: one expert of 3*8*4 a token and sparse block
    assert flops_glm.held_per_token(TOY) == 1.0
    assert parts["experts held"] == 3 * 96
    assert parts["prediction module's projection"] == 2 * 8 * 8
    assert parts["heads"] == 2 * 8 * 10               # one head, two passes
    # scores over 3 + 1 lanes and values over 4, 2 heads, the causal half
    assert flops_glm.attention_unit(TOY, 16) == 16 * 16 * 2 * 8
    fwd = flops_glm.forward_flops_per_token(TOY, 16)
    assert fwd["attention"] == 4 * 4096 / 16
    assert sum(fwd.values()) == 2 * (720 + 240 + 192 + 432 + 288 + 128 + 160) \
        + 1024
    assert flops_glm.train_flops_per_token(TOY, 16) == 3 * sum(fwd.values())
    # a block's attention 180 + its four norms 22; the dense layer 240; a
    # sparse one router 64, bias 8, shared 144, two experts 192; the module
    # 128 + three norms; embedding, head and the last norm
    assert flops_glm.total_params(TOY) \
        == 442 + 3 * 610 + 152 + 168
    call = flops_glm.flash_call(TOY, 1, 16, "fwd")
    assert call == flops.flash_call(
        {"n_heads": 2, "n_kv_heads": 2, "d_model": 8}, 1, 16, "fwd")


def test_flops_of_the_cell():
    sizes = model_glm.sizes(resolve.config(CONFIG))
    assert sizes["n_experts"] == 64 and sizes["experts_held"] == (8, 0)
    assert sizes["top_k"] == 4 and sizes["vocab_size"] == 19360
    assert (sizes["n_layers"], sizes["n_dense"], sizes["n_mtp"]) == (12, 1, 1)
    assert (sizes["q_rank"], sizes["kv_rank"], sizes["qk_nope_dim"],
            sizes["qk_rope_dim"], sizes["v_dim"]) == (768, 512, 192, 64, 256)
    assert sizes["route_scale"] == 1.8 and sizes["shared_d_ff"] == 1536
    assert math.isclose(flops_glm.total_params(sizes) / 1e9, 1.454,
                        abs_tol=1e-3)
    fwd = flops_glm.forward_flops_per_token(sizes, 8192)
    total = sum(fwd.values())
    assert math.isclose(total / 1e6, 2300.6, abs_tol=0.5)
    assert math.isclose(fwd["heads"] / 1e6, 158.6, abs_tol=0.1)
    # a sparse layer's forward, a token: 43.5 MFLOP in the five latent
    # projections, 83.9 in scores and values, 18.9 shared, 9.4 held
    blocks = 13
    assert math.isclose(fwd["latent projections"] / blocks / 1e6, 43.5,
                        abs_tol=0.05)
    assert math.isclose(fwd["attention"] / blocks / 1e6, 83.9, abs_tol=0.05)
    assert math.isclose(fwd["shared"] / 12 / 1e6, 18.9, abs_tol=0.05)
    assert math.isclose(fwd["experts held"] / 12 / 1e6, 9.4, abs_tol=0.05)
    assert math.isclose(
        flops_glm.train_flops_per_token(sizes, 8192) / 1e9, 6.902,
        abs_tol=2e-3)
    # the published model: 47 layers, every expert, the whole vocabulary
    full = dict(sizes, n_layers=47, experts_held=(64, 0), vocab_size=154880)
    assert math.isclose(flops_glm.total_params(full) / 1e9, 30.59,
                        abs_tol=0.01)
    whole = sum(flops_glm.forward_flops_per_token(full, 8192).values())
    assert math.isclose(whole / 1e6, 11980, rel_tol=0.01), whole


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_glm as programs

    for name in ("_rms", "_swiglu", "_turn", "_attention", "_experts",
                 "_block", "forward", "token_losses", "loss", "biases",
                 "bias_update"):
        assert inspect.getsource(getattr(reference_glm, name)) \
            == inspect.getsource(getattr(programs, name)), name
    # independent of the program: neither copy imports it
    for mod in (reference_glm, programs):
        src = inspect.getsource(mod)
        assert "import" not in src.replace(
            "from __future__ import annotations", "").replace(
            "import jax.numpy as jnp", "").replace("import jax", ""), mod


# --- readers on synthetic traces -------------------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_glm.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 8192.0},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


Q = "bf16[2,20,8192,256]{3,2,1,0}"
QF = "f32[2,20,8192,256]{3,2,1,0}"
LSE = "f32[2,20,8192,128]{3,2,1,0}"
FLASH_FWD = _call(f"({Q}, {LSE})", f"{Q} %q, {Q} %k, {Q} %v")
FLASH_DQ = _call(QF, f"{Q} %q, {Q} %k, {Q} %v, {Q} %g, {Q} %o, {LSE} %l")
FLASH_DKDV = _call(f"({QF}, {QF})",
                   f"{Q} %q, {Q} %k, {Q} %v, {Q} %g, {Q} %o, {LSE} %l")
META = "s32[] %n, s32[9]{0} %o, s32[72]{0} %g, s32[72]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[16384,1536]{1,0}",
            f"{META}, bf16[16384,2048]{{1,0}} %x, bf16[8,2048,1536]{{2,1,0}} %w")
TGMM = _call("bf16[8,2048,1536]{2,1,0}",
             f"{META}, bf16[16384,2048]{{1,0}} %x, bf16[16384,1536]{{1,0}} %g")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    kinds = [glm_kernel_roofline.classify(n, obs)[0]
             for n in (FLASH_FWD, FLASH_DQ, FLASH_DKDV, GMM, TGMM)]
    assert kinds == ["flash_attention"] * 3 + ["grouped_matmul"] * 2
    # the grouped matmul counts the rows the experts got, not the buffer's
    _, call = glm_kernel_roofline.classify(GMM, obs)
    assert call["ops"] == 2.0 * 8192 * 2048 * 1536
    obs["trace"] = {"device_ops": [[FLASH_FWD, 0.30], [FLASH_DQ, 0.40],
                                   [FLASH_DKDV, 0.50],
                                   ["%fusion.1 = x", 1.0]],
                    "op_calls": {FLASH_FWD: 26, FLASH_DQ: 13, FLASH_DKDV: 13,
                                 "%fusion.1 = x": 40}}
    sizes = obs["sizes"]
    least = sum(n * flops.least_seconds(flops_glm.flash_call(
        sizes, 2, 8192, w), obs["peak"])["seconds"]
        for n, w in ((26, "fwd"), (13, "dq"), (13, "dkdv")))
    got = glm_kernel_roofline.read({"kernel": "flash_attention"}, obs)
    assert math.isclose(got, 100 * least / 1.2)
    assert 10 < got < 100
    assert glm_kernel_roofline.read({"kernel": "grouped_matmul"},
                                    obs) is None           # none in the trace
    assert glm_kernel_roofline.read(
        {"kernel": "flash_attention"}, dict(obs, trace=None)) is None
    # a program of another family (the parent's cells): nothing to read
    assert glm_kernel_roofline.read(
        {"kernel": "flash_attention"},
        dict(obs, sizes={"d_model": 4096})) is None


def test_roofline_reader_raises_on_a_foreign_mosaic_call():
    obs = _obs()
    foreign = _call("bf16[2,8192,2048]{2,1,0}", "bf16[2,8192,2048]{2,1,0} %x")
    with pytest.raises(ValueError, match="no flash call"):
        glm_kernel_roofline.classify(foreign, obs)
    # a flash call at another head width is foreign too
    with pytest.raises(ValueError, match="no flash call"):
        glm_kernel_roofline.classify(FLASH_FWD.replace(",256]", ",128]"), obs)
    obs["trace"] = {"device_ops": [[foreign, 0.1]], "op_calls": {foreign: 1}}
    with pytest.raises(ValueError):
        glm_kernel_roofline.read({"kernel": "flash_attention"}, obs)


RESIDUAL = ("%f.4 = bf16[2,8192,2048]{2,1,0} fusion(bf16[2,8192,2048]{2,1,0} "
            "%x, bf16[2048]{0} %n), kind=kLoop")


def _share(part, mine, others, window=4.0):
    obs = _obs()
    ops = [[n, 0.1] for n in mine] + [[n, 0.3] for n in others]
    obs["trace"] = {"window_s": window, "device_ops": ops,
                    "op_calls": {n: 1 for n, _ in ops}}
    return glm_share.read({"part": part}, obs), obs


def test_mla_share_counts_what_only_the_attention_half_has():
    mine = [
        "%f.1 = bf16[2,8192,768]{2,1,0} fusion(bf16[2,8192,2048]{2,1,0} %h, "
        "bf16[2048,768]{1,0} %w), kind=kOutput",
        "%f.2 = bf16[2,8192,576]{2,1,0} fusion(bf16[2,8192,2048]{2,1,0} %h, "
        "bf16[2048,576]{1,0} %w), kind=kOutput",
        "%f.3 = bf16[2,8192,20,448]{3,2,1,0} fusion(bf16[2,8192,512]{2,1,0} "
        "%c), kind=kOutput",
        "%f.5 = bf16[2,20,8192,256]{3,2,1,0} fusion(bf16[2,8192,1,64]"
        "{3,2,1,0} %kr, f32[8192,32]{1,0} %cos), kind=kLoop",
        "%f.6 = bf16[2,8192,2048]{2,1,0} fusion(bf16[2,8192,5120]{2,1,0} "
        "%o, bf16[5120,2048]{1,0} %wo), kind=kOutput",
        "%f.7 = bf16[11,768,5120]{2,1,0} fusion(bf16[11,768,5120]{2,1,0} "
        "%stack, bf16[16384,768]{1,0} %c), kind=kOutput",
        FLASH_FWD, FLASH_DQ, FLASH_DKDV]
    others = [
        RESIDUAL, GMM,
        "%f.8 = bf16[16384,1536]{1,0} fusion(bf16[16384,2048]{1,0} %x, "
        "bf16[2048,1536]{1,0} %ws), kind=kOutput",
        "%f.9 = bf16[2,8192,19360]{2,1,0} fusion(bf16[2,8192,2048]{2,1,0} "
        "%x, bf16[2048,19360]{1,0} %head), kind=kOutput",
        "%f.10 = f32[16384,64]{1,0} fusion(bf16[16384,2048]{1,0} %x, "
        "bf16[2048,64]{1,0} %router), kind=kOutput",
        "%while.1 = (bf16[2,8192,5120]{2,1,0}) while(%t), body=%b"]
    got, obs = _share("mla", mine, others)
    assert math.isclose(got, 100 * 0.9 / 4.0)
    assert glm_share.read({"part": "mla"},
                          dict(obs, sizes={"d_model": 4096})) is None
    assert glm_share.read({"part": "mla"}, dict(obs, trace=None)) is None
    with pytest.raises(ValueError, match="unknown part"):
        glm_share.read({"part": "norms"}, obs)


def test_head_share_counts_the_vocabulary_and_the_joined_rows():
    mine = [
        "%f.9 = bf16[2,8192,19360]{2,1,0} fusion(bf16[2,8192,2048]{2,1,0} "
        "%x, bf16[2048,19360]{1,0} %head), kind=kOutput",
        "%f.11 = f32[2,8192]{1,0} fusion(bf16[2,8192,19360]{2,1,0} %l), "
        "kind=kInput",
        "%f.12 = bf16[19360,2048]{1,0} fusion(s32[2,8192]{1,0} %t, "
        "bf16[2,8192,2048]{2,1,0} %g), kind=kLoop",
        "%f.13 = bf16[2,8192,2048]{2,1,0} fusion(bf16[2,8192,4096]{2,1,0} "
        "%joined, bf16[4096,2048]{1,0} %eh), kind=kOutput"]
    others = [RESIDUAL, FLASH_FWD, GMM,
              "%f.1 = bf16[2,8192,768]{2,1,0} fusion(bf16[2,8192,2048]"
              "{2,1,0} %h, bf16[2048,768]{1,0} %w), kind=kOutput"]
    got, _ = _share("mtp_head", mine, others)
    assert math.isclose(got, 100 * 0.4 / 4.0)


def test_expert_share_counts_the_rows_held_and_the_routed_rows():
    short = GMM.replace("16384,", "4096,")         # a further pass's call
    mine = [
        "%f.5 = bf16[16384,2048]{1,0} fusion(bf16[2,8192,2048]{2,1,0} %x, "
        "s32[16384]{0} %i), kind=kLoop",
        "%sort.1 = (s32[65536]{0}, s32[65536]{0}) sort(s32[65536]{0} %e, "
        "s32[65536]{0} %i)",
        "%f.9 = s32[8]{0} fusion(s32[16384,4]{1,0} %experts), kind=kLoop",
        GMM, TGMM, short]
    others = [RESIDUAL, FLASH_FWD,
              "%f.1 = bf16[2,8192,768]{2,1,0} fusion(bf16[2,8192,2048]"
              "{2,1,0} %h, bf16[2048,768]{1,0} %w), kind=kOutput",
              "%while.1 = (bf16[16384,2048]{1,0}) while(%t), body=%b"]
    got, obs = _share("experts", mine, others)
    # NOTE: the held rows of a pass are as many as the step's tokens here
    # (16,384 = twice the even share of 65,536 / 8), so the shared SwiGLU's
    # [16384, .] rows count with them: the whole expert layer
    assert math.isclose(got, 100 * 0.6 / 4.0)
    bare = dict(obs, trace={**obs["trace"], "op_calls": {FLASH_FWD: 1}})
    assert glm_share.read({"part": "experts"}, bare) is None


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_latent"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert {"flash_attention_roofline.glm", "mla_device_share",
            "grouped_matmul_roofline.glm", "expert_layer_device_share.glm",
            "expert_held_rows_share.glm", "expert_load_max_over_mean.glm",
            "mtp_head_loss_device_share", "train_step_ms", "train_report_ms",
            "train_report_span_ms", "device_idle_share.train",
            "device_idle_under_report.train", "compiles_in_window.train",
            "compiles_in_trace.train"} == names
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
    assert (mix["seq"], mix["batch"]) == (8192, 2)
    assert [w["chips"] for w in man["workloads"]].count(4) == 1
    assert len(man["workloads"]) == 5


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_latent

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

    res = train_latent.run(
        resolve.cell("rehearse-train-latent"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 15 and all(res["checks"].values()), \
        res["checks"]
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert 0 < res["obs"]["values"]["held_rows"] <= 2 * 128 * 2


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from benchmark.kinds import train_latent

    class Refused(Exception):
        pass

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(Refused, match="latent"):
        train_latent.run(resolve.cell("rehearse-train-latent"), None,
                         {"Refused": Refused})


def test_placement_moves_the_bias_with_its_column_and_keeps_the_routes():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.kinds import train_latent

    key = jax.random.PRNGKey(0)
    x = jnp.abs(jax.random.normal(key, (512, 16)))

    def stack(r, n):
        k = jax.random.fold_in(key, r)
        return {"router": jax.random.normal(k, (n, 16, 8)),
                "router_bias": 0.3 * jax.random.normal(
                    jax.random.fold_in(k, 1), (n, 8))}

    params = {"layers": [{"w_gate": jnp.zeros((1, 4, 4))}, stack(1, 2)],
              "mtp": {"block": stack(2, 1)}}
    where = train_latent.expert_layers(params)
    assert where == [(("layers", 1), 0), (("layers", 1), 1),
                     (("mtp", "block"), 0)]

    def routes_of(p):
        stacks = [p["layers"][1], p["mtp"]["block"]]
        routers = jnp.concatenate([s["router"] for s in stacks])
        bias = jnp.concatenate([s["router_bias"] for s in stacks])
        score = jax.nn.sigmoid(jnp.einsum("td,lde->lte", x, routers) * 0.1)
        return jax.lax.top_k(score + bias[:, None, :], 2)[1]

    sizes = {"experts_held": (2, 4), "n_experts": 8}
    placed, (before, after) = train_latent.place_experts(params, routes_of,
                                                         sizes)
    old, new = np.asarray(routes_of(params)), np.asarray(routes_of(placed))
    for layer in range(3):
        share = np.isin(new[layer], (4, 5)).mean()
        assert share == pytest.approx(after[layer])
        assert abs(share - 0.25) <= abs(before[layer] - 0.25) + 1e-9
        # a relabelling: as many tokens an expert, under other names
        assert sorted(np.bincount(old[layer].ravel(), minlength=8)) \
            == sorted(np.bincount(new[layer].ravel(), minlength=8))
    assert placed["layers"][1]["router"].shape == (2, 16, 8)
    assert placed["mtp"]["block"]["router_bias"].shape == (1, 8)
    assert sorted(np.asarray(placed["layers"][1]["router_bias"][0])) \
        == sorted(np.asarray(params["layers"][1]["router_bias"][0]))


def test_bias_agreement_judges_the_decided_counts_only():
    import numpy as np

    from benchmark.kinds import train_latent

    sizes = {"bias_rate": 0.001}
    ref = np.array([[10., 30., 20., 20.]])            # mean 20
    own = np.array([[11., 29., 20., 20.]])            # one apart
    before = np.zeros((1, 4), np.float32)
    good = np.array([[0.001, -0.001, 0.0, 0.0]], np.float32)
    ok = train_latent.bias_agreement(good, before, own, ref, sizes)
    # decided: further from the mean than 1 + 2 x 1
    assert ok["wrong"] == 0 and ok["decided_share"] == 0.5
    assert ok["moved"] == 2 and ok["counts_apart_max"] == 1.0
    bad = train_latent.bias_agreement(-good, before, own, ref, sizes)
    assert bad["wrong"] == 2
    # an undecided entry may fall either way
    near = np.array([[0.001, -0.001, 0.001, -0.001]], np.float32)
    assert train_latent.bias_agreement(near, before, own, ref,
                                       sizes)["wrong"] == 0


# --- the cell's own limits refuse wrong models -----------------------------
WRONG = ["as it is", "8-bit latent projections", "8-bit expert weights",
         "the rotary key dropped", "the bias left out of the selection",
         "the scaling factor dropped", "the prediction module's loss dropped"]


@pytest.mark.parametrize("wrong", WRONG)
def test_the_cells_limits_fail_a_wrong_model(wrong):
    """At the toy size in bf16 on the CPU, against the limits the real cell
    is held to (``workloads/<cell>.json`` ``train.check``), which the toy
    as it is has to meet. The routers' biases are drawn (they start at 0
    in a cell, where leaving them out of the selection changes nothing
    before the first update)."""
    import jax
    import jax.numpy as jnp

    from benchmark.kinds import train_latent
    from ray_tpu.models import latent

    tol = resolve.workload(CELL)["train"]["check"]
    conf = dict(resolve.config("tiny-glm"),
                run={"dtype": "bfloat16", "param_dtype": "bfloat16"})
    sizes = model_glm.sizes(conf)
    cfg = model_glm.latent_config(conf, attn_impl="xla")
    params = latent.init_params(jax.random.PRNGKey(7), cfg)

    def every_stack(change, tree):
        return dict(tree, layers=[change(s) for s in tree["layers"]],
                    mtp=dict(tree["mtp"], block=change(tree["mtp"]["block"])))

    keys = iter(jax.random.split(jax.random.PRNGKey(9), 8))
    params = every_stack(lambda s: dict(s, router_bias=0.05 * jax.random.normal(
        next(keys), s["router_bias"].shape)) if "router_bias" in s else s,
        params)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 258), 0,
                                cfg.vocab_size, "int32")
    run_params, run_cfg = params, cfg

    def eight_bit(names):
        return every_stack(lambda s: {
            k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
                if k in names else w) for k, w in s.items()}, params)

    if wrong == "8-bit latent projections":
        run_params = eight_bit(("wq_b", "wkv_b"))
    elif wrong == "8-bit expert weights":
        run_params = eight_bit(("we_gate", "we_up", "we_down", "ws_gate",
                                "ws_up", "ws_down"))
    elif wrong == "the rotary key dropped":
        # the joint projection's last qk_rope columns make the shared key
        run_params = every_stack(lambda s: dict(s, wkv_a=s["wkv_a"].at[
            ..., cfg.kv_rank:].set(0)), params)
    elif wrong == "the bias left out of the selection":
        run_params = every_stack(lambda s: dict(
            s, router_bias=jnp.zeros_like(s["router_bias"]))
            if "router_bias" in s else s, params)
    elif wrong == "the scaling factor dropped":
        run_cfg = cfg.replace(route_scale=1.0)
    elif wrong == "the prediction module's loss dropped":
        run_cfg = cfg.replace(mtp_weight=0.0)
    _, reference = train_latent.token_loss_fns(cfg, sizes)
    got, got_ahead, routes, _ = train_latent.token_loss_fns(run_cfg, sizes)[0](
        run_params, tokens)
    ref, ref_ahead, ref_total, rec = reference(params, tokens, routes)
    step_loss = float(latent.loss_fn(run_params, {"tokens": tokens},
                                     run_cfg)[0])
    a = train_latent.loss_agreement(got, ref)
    ah = train_latent.loss_agreement(got_ahead, ref_ahead)
    r = train_latent.route_agreement(routes, rec, cfg.top_k)
    ok = all(train_latent.route_checks(r, tol, cfg.top_k).values()) \
        and a["token_mean_abs"] <= tol["token_mean_abs"] \
        and a["token_p999_abs"] <= tol["token_p999_abs"] \
        and ah["token_mean_abs"] <= tol["mtp_token_mean_abs"] \
        and ah["token_p999_abs"] <= tol["mtp_token_p999_abs"] \
        and abs(step_loss - float(ref_total)) <= tol["step_loss_abs"]
    assert ok == (wrong == "as it is"), (a, ah, r, step_loss,
                                         float(ref_total), tol)
