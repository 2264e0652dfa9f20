"""The hybrid kind's yardstick: ``flops_granite.py`` by hand, the two
copies of the plain reference, the new readers on synthetic traces, the
kind's ``run()`` rehearsed on the CPU, and the cell's own limits against
wrong models."""
import inspect
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_granite, model_granite, reference_granite
from benchmark import resolve
from benchmark.readers import (granite_kernel_roofline, held_expert_share,
                               mixer_share)

CELL = "train-granite4hs-ep8-s8192-b2"
CONFIG = "granite-4.0-h-small-ep8-l10"
TOY = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_ff": 4,
       "shared_d_ff": 6, "n_experts": 8, "top_k": 4, "experts_held": (2, 0),
       "n_layers": 3, "vocab_size": 10, "mamba_heads": 4, "mamba_head_dim": 4,
       "mamba_state": 2, "mamba_conv": 4, "mamba_chunk": 8,
       "layer_types": ("mamba", "attention", "mamba")}


def test_flops_by_hand():
    parts = flops_granite.matmul_params_per_token(TOY)
    # a mixer: 8 -> 2*16 + 2*2 + 4 = 40 and 16 -> 8: 320 + 128; two of them
    assert parts["mixer projections"] == 2 * 448
    # q and o 8*8, k and v 8*4 each
    assert parts["attention projections"] == 2 * 64 + 2 * 32
    assert parts["router"] == 3 * 8 * 8 and parts["shared"] == 3 * 3 * 8 * 6
    # 4 a token, 2 of 8 held: one expert of 3*8*4 a token and layer
    assert flops_granite.held_per_token(TOY) == 1.0
    assert parts["experts held"] == 3 * 96 and parts["head"] == 80
    # the scan, a token and layer: scores 2*8/2 shared... N Q = 16; a head
    # P Q + 4 P N = 32 + 32, four heads 256
    assert flops_granite.scan_flops_per_token(TOY) == 16 + 256
    fwd = flops_granite.forward_flops_per_token(TOY, 16)
    assert fwd["scan"] == 2 * 272
    assert fwd["attention"] == 2 * flops.causal_attention_unit(TOY, 16) / 16
    assert flops_granite.train_flops_per_token(TOY, 16) \
        == 3 * sum(fwd.values())
    # a mixer 8*40 + 16*8 + 5*20 + 3*4 + 16; attention 192; a layer's rest
    # 16 + 64 + 144 + 2*96; embedding 80 and the last norm
    assert flops_granite.total_params(TOY) \
        == 2 * (448 + 100 + 12 + 16) + 192 + 3 * 416 + 88
    call = flops_granite.ssd_call(TOY, 1, 16, "fwd")
    assert call["ops"] == 16 * 272
    # x and y 16*16*2 each, B and C 16*2*2 each, the decay twice 16*4*4,
    # two chunks' states 16*2*4
    assert call["bytes"] == 2 * 512 + 2 * 64 + 2 * 256 + 2 * 128
    back = flops_granite.ssd_call(TOY, 1, 16, "bwd")
    assert back["ops"] == 2 * 16 * 272 + 16 * 16
    assert back["bytes"] == 3 * 512 + 4 * 64 + 4 * 256 + 256
    with pytest.raises(ValueError):
        flops_granite.ssd_call(TOY, 1, 16, "dq")


def test_flops_of_the_cell():
    sizes = model_granite.sizes(resolve.config(CONFIG))
    assert sizes["n_experts"] == 72 and sizes["experts_held"] == (9, 0)
    assert sizes["top_k"] == 10 and sizes["vocab_size"] == 12544
    assert sizes["layer_types"] == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    assert math.isclose(flops_granite.total_params(sizes) / 1e9, 2.055,
                        abs_tol=1e-3)
    fwd = flops_granite.forward_flops_per_token(sizes, 8192)
    total = sum(fwd.values())
    assert math.isclose(total / 1e9, 2.770, abs_tol=2e-3)
    assert math.isclose(fwd["mixer projections"] / total, 0.664, abs_tol=2e-3)
    assert math.isclose(fwd["scan"] / total, 0.021, abs_tol=1e-3)
    assert math.isclose(
        flops_granite.train_flops_per_token(sizes, 8192) / 1e9, 8.311,
        abs_tol=2e-3)
    # the published model: 40 layers, every expert, the whole vocabulary
    full = dict(sizes, n_layers=40, experts_held=(72, 0), vocab_size=100352,
                layer_types=sizes["layer_types"] * 4)
    assert math.isclose(flops_granite.total_params(full) / 1e9, 32.2,
                        abs_tol=0.1)


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_granite as programs

    for name in ("_rms", "_runs", "_mamba", "_attention", "_experts",
                 "forward", "router_losses", "token_losses", "loss"):
        assert inspect.getsource(getattr(reference_granite, name)) \
            == inspect.getsource(getattr(programs, name)), name


# --- readers on synthetic traces -------------------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_granite.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 20480.0},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


U, BC = "bf16[2,8192,8192]{2,1,0}", "bf16[2,8192,128]{2,1,0}"
CUM, CUMT = "f32[2,8192,128]{2,1,0}", "f32[2,128,8192]{2,1,0}"
STATES = "f32[2,32,8192,128]{3,2,1,0}"
SCAN_FWD = _call(f"({U}, {STATES})",
                 f"{U} %u, {BC} %b, {BC} %c, {CUM} %col, {CUMT} %row")
SCAN_BWD = _call(
    f"({U}, f32[2,8,8192,128]{{3,2,1,0}}, f32[2,8,8192,128]{{3,2,1,0}}, "
    f"f32[2,8,8192,128]{{3,2,1,0}}, {CUMT})",
    f"{U} %u, {BC} %b, {BC} %c, {CUM} %col, {CUMT} %row, {STATES} %h, "
    f"{U} %dy")
Q, K = "bf16[2,32,8192,128]{3,2,1,0}", "bf16[2,8,8192,128]{3,2,1,0}"
FLASH_FWD = _call(f"({Q}, f32[2,32,8192,128]{{3,2,1,0}})",
                  f"{Q} %q, {K} %k, {K} %v")
META = "s32[] %n, s32[10]{0} %o, s32[168]{0} %g, s32[168]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[40960,768]{1,0}",
            f"{META}, bf16[40960,4096]{{1,0}} %x, bf16[9,4096,768]{{2,1,0}} %w")
TGMM = _call("bf16[9,4096,768]{2,1,0}",
             f"{META}, bf16[40960,4096]{{1,0}} %x, bf16[40960,768]{{1,0}} %g")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    kinds = {n: granite_kernel_roofline.classify(n, obs)[0]
             for n in (SCAN_FWD, SCAN_BWD, FLASH_FWD, GMM, TGMM)}
    assert list(kinds.values()) == ["ssd_scan", "ssd_scan", "flash_attention",
                                    "grouped_matmul", "grouped_matmul"]
    # the grouped matmul counts the rows the experts got, not the buffer's
    _, call = granite_kernel_roofline.classify(GMM, obs)
    assert call["ops"] == 2.0 * 20480 * 4096 * 768
    obs["trace"] = {"device_ops": [[SCAN_FWD, 0.054], [SCAN_BWD, 0.081],
                                   [FLASH_FWD, 0.010], ["%fusion.1 = x", 1.0]],
                    "op_calls": {SCAN_FWD: 9, SCAN_BWD: 9, FLASH_FWD: 1,
                                 "%fusion.1 = x": 40}}
    sizes = obs["sizes"]
    least = sum(flops.least_seconds(flops_granite.ssd_call(
        sizes, 2, 8192, w), obs["peak"])["seconds"] for w in ("fwd", "bwd"))
    got = granite_kernel_roofline.read({"kernel": "ssd_scan"}, obs)
    assert math.isclose(got, 100 * 9 * least / 0.135)
    assert 10 < got < 100
    assert granite_kernel_roofline.read({"kernel": "grouped_matmul"},
                                        obs) is None       # none in the trace
    assert granite_kernel_roofline.read(
        {"kernel": "ssd_scan"}, dict(obs, trace=None)) is None
    # a program of another family (the parent's cells): nothing to read
    assert granite_kernel_roofline.read(
        {"kernel": "ssd_scan"}, dict(obs, sizes={"d_model": 4096})) is None


def test_roofline_reader_raises_on_a_foreign_mosaic_call():
    obs = _obs()
    foreign = _call("bf16[2,8192,4096]{2,1,0}", "bf16[2,8192,4096]{2,1,0} %x")
    with pytest.raises(ValueError, match="no scan call"):
        granite_kernel_roofline.classify(foreign, obs)
    # a scan call of other shapes is foreign too
    with pytest.raises(ValueError, match="no scan call"):
        granite_kernel_roofline.classify(
            SCAN_FWD.replace("[2,8192,8192]", "[2,8192,4096]"), obs)
    obs["trace"] = {"device_ops": [[foreign, 0.1]], "op_calls": {foreign: 1}}
    with pytest.raises(ValueError):
        granite_kernel_roofline.read({"kernel": "ssd_scan"}, obs)


def test_mixer_share_counts_what_only_the_mixer_has():
    obs = _obs()
    mine = ["%f.1 = bf16[2,8192,16768]{2,1,0} fusion(bf16[2,8192,4096]{2,1,0} "
            "%x, bf16[4096,16768]{1,0} %w), kind=kOutput",
            "%f.2 = f32[2,8192,8448]{2,1,0} fusion(bf16[2,8195,8448]{2,1,0} "
            "%p), kind=kLoop",
            "%f.3 = bf16[5,8192,4096]{2,1,0} fusion(bf16[2,8192,8192]{2,1,0} "
            "%y, bf16[2,8192,4096]{2,1,0} %g), kind=kOutput",
            SCAN_FWD, SCAN_BWD]
    others = ["%f.4 = bf16[2,8192,4096]{2,1,0} fusion(bf16[2,8192,4096]"
              "{2,1,0} %x), kind=kLoop",
              "%f.5 = bf16[40960,4096]{1,0} fusion(bf16[16384,4096]{1,0} %x, "
              "s32[40960]{0} %i), kind=kLoop",
              "%f.6 = bf16[2,8192,12544]{2,1,0} fusion(bf16[2,8192,4096]"
              "{2,1,0} %x, bf16[12544,4096]{1,0} %e), kind=kOutput",
              "%f.7 = bf16[5,2,8192,4096]{3,2,1,0} fusion(bf16[5,2,8192,4096]"
              "{3,2,1,0} %s, bf16[2,8192,4096]{2,1,0} %x), kind=kLoop",
              FLASH_FWD, GMM,
              "%while.1 = (bf16[2,8192,8192]{2,1,0}) while(%t), body=%b"]
    obs["trace"] = {"window_s": 2.0, "device_ops": [[n, 0.1] for n in mine]
                    + [[n, 0.3] for n in others]}
    assert math.isclose(mixer_share.read({}, obs), 100 * 0.5 / 2.0)
    dense = dict(obs, sizes={"d_model": 4096})
    assert mixer_share.read({}, dense) is None


def test_held_expert_share_counts_the_rows_held_and_the_routed_rows():
    obs = _obs()
    short = GMM.replace("40960", "10240")      # a further pass's call
    dispatch = ["%f.5 = bf16[40960,4096]{1,0} fusion(bf16[16384,4096]{1,0} "
                "%x, s32[40960]{0} %i), kind=kLoop",
                "%f.8 = f32[16384,4096]{1,0} fusion(bf16[10240,4096]{1,0} "
                "%y, s32[10240]{0} %t), kind=kLoop",
                "%sort.1 = (s32[163840]{0}, s32[163840]{0}) sort(s32[163840]"
                "{0} %e, s32[163840]{0} %i)",
                "%f.9 = s32[9]{0} fusion(s32[16384,10]{1,0} %experts), "
                "kind=kLoop"]
    matmuls = [GMM, TGMM, short]
    others = ["%f.4 = bf16[2,8192,4096]{2,1,0} fusion(bf16[2,8192,4096]"
              "{2,1,0} %x), kind=kLoop",
              "%f.6 = bf16[16384,1536]{1,0} fusion(bf16[16384,4096]{1,0} %x, "
              "bf16[4096,1536]{1,0} %w), kind=kOutput",
              SCAN_FWD, FLASH_FWD,
              "%while.1 = (bf16[40960,4096]{1,0}) while(%t), body=%b"]
    ops = ([[n, 0.1] for n in dispatch] + [[n, 0.2] for n in matmuls]
           + [[n, 0.3] for n in others])
    obs["trace"] = {"window_s": 4.0, "device_ops": ops,
                    "op_calls": {n: 1 for n, _ in ops}}
    assert held_expert_share.grouped(obs["trace"], obs) == {
        GMM: 40960, TGMM: 40960, short: 10240}
    assert math.isclose(held_expert_share.read({"with_matmuls": True}, obs),
                        100 * (0.4 + 0.6) / 4.0)
    assert math.isclose(held_expert_share.read({"with_matmuls": False}, obs),
                        100 * 0.4 / 4.0)
    # no grouped matmul in the trace, another family, no trace: nothing
    bare = dict(obs, trace={**obs["trace"], "op_calls": {SCAN_FWD: 1}})
    assert held_expert_share.read({"with_matmuls": True}, bare) is None
    assert held_expert_share.read(
        {"with_matmuls": True}, dict(obs, sizes={"d_model": 4096})) is None
    assert held_expert_share.read({"with_matmuls": True},
                                  dict(obs, trace=None)) is None


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_hybrid"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert {"ssd_scan_roofline", "ssm_mixer_device_share",
            "flash_attention_roofline.granite",
            "grouped_matmul_roofline.granite", "expert_held_rows_share",
            "expert_layer_device_share.granite",
            "expert_dispatch_device_share.granite",
            "expert_load_max_over_mean.granite", "train_step_ms", "train_report_ms", "train_report_span_ms",
            "device_idle_share.train", "device_idle_under_report.train",
            "compiles_in_window.train", "compiles_in_trace.train"} == names
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
    assert resolve.cell(CELL)["mix"]["seq"] == 8192


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_hybrid

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

    res = train_hybrid.run(
        resolve.cell("rehearse-train-hybrid"),
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


def test_balanced_share_and_the_relabelled_routers():
    """Eight groups of nine by falling load: the group taken is within a
    few assignments of the even share whatever the loads; the routers'
    columns move, the routes of every token stay what they were."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.kinds import train_hybrid

    rng = np.random.default_rng(3)
    for _ in range(5):
        counts = rng.multinomial(163840, rng.dirichlet(np.full(72, 0.6)))
        mine = train_hybrid.balanced_share(counts.tolist(), 9)
        assert len(set(mine)) == 9
        if counts.max() < 163840 / 8:
            assert abs(counts[mine].sum() / 163840 - 0.125) < 0.005, counts
    # two runs of layers; routes from the routers themselves (a toy model)
    key = jax.random.PRNGKey(0)
    x = jnp.abs(jax.random.normal(key, (512, 16)))
    params = {"layers": [
        {"router": jax.random.normal(jax.random.fold_in(key, r), (n, 16, 8))}
        for r, n in enumerate((2, 1))]}

    def routes_of(p):
        routers = jnp.concatenate([run["router"] for run in p["layers"]])
        return jax.lax.top_k(jnp.einsum("td,lde->lte", x, routers), 2)[1]

    sizes = {"experts_held": (2, 4), "n_experts": 8}
    placed, (before, after) = train_hybrid.place_experts(params, routes_of,
                                                         sizes)
    old, new = np.asarray(routes_of(params)), np.asarray(routes_of(placed))
    for layer in range(3):
        share = np.isin(new[layer], (4, 5)).mean()
        assert share == pytest.approx(after[layer])
        assert abs(share - 0.25) <= abs(before[layer] - 0.25) + 1e-9
        # a relabelling: as many tokens an expert, under other names
        assert sorted(np.bincount(old[layer].ravel(), minlength=8)) \
            == sorted(np.bincount(new[layer].ravel(), minlength=8))
    assert [run["router"].shape for run in placed["layers"]] \
        == [(2, 16, 8), (1, 16, 8)]


def test_a_program_without_the_scan_is_refused_at_once(monkeypatch):
    import importlib.util

    from benchmark.kinds import train_hybrid

    class Refused(Exception):
        pass

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(Refused, match="ssd"):
        train_hybrid.run(resolve.cell("rehearse-train-hybrid"), None,
                         {"Refused": Refused})


# --- the cell's own limits refuse wrong models -----------------------------
WRONG = ["as it is", "8-bit mixer weights", "D left out", "decay without dt",
         "one held expert fewer", "the residual multiplier dropped"]


@pytest.mark.parametrize("wrong", WRONG)
def test_the_cells_limits_fail_a_wrong_model(wrong, monkeypatch):
    """At the toy size in bf16 on the CPU, against the limits the real cell
    is held to (``workloads/<cell>.json`` ``train.check``), which the toy
    as it is has to meet. The toy's logits are not divided by 16: its
    three narrow mixers then move a token's loss about as the cell's nine
    do (as it is: mean 0.0014 here, 0.0008 in the cell on the chip)."""
    import jax
    import jax.numpy as jnp

    from benchmark.kinds import train_hybrid
    from ray_tpu.models import hybrid

    tol = resolve.workload(CELL)["train"]["check"]
    conf = dict(resolve.config("tiny-granite"), logits_scaling=1,
                run={"dtype": "bfloat16", "param_dtype": "bfloat16"})
    sizes = model_granite.sizes(conf)
    cfg = model_granite.hybrid_config(conf, attn_impl="xla")
    params = hybrid.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 257), 0,
                                cfg.vocab_size, "int32")
    run_params, run_cfg = params, cfg
    mamba = [i for i, lay in enumerate(params["layers"]) if "in_proj" in lay]

    def with_mixers(change):
        return dict(params, layers=[
            change(lay) if i in mamba else lay
            for i, lay in enumerate(params["layers"])])

    if wrong == "as it is":
        pass
    elif wrong == "8-bit mixer weights":
        run_params = with_mixers(lambda lay: {
            k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
                if k in ("in_proj", "out_proj") else w)
            for k, w in lay.items()})
    elif wrong == "D left out":
        run_params = with_mixers(lambda lay: dict(
            lay, d_skip=jnp.zeros_like(lay["d_skip"])))
    elif wrong == "decay without dt":
        # exp(A) a step in place of exp(dt A): dt enters x alone
        real = hybrid.ssd_scan
        monkeypatch.setattr(hybrid, "ssd_scan", lambda x, dt, *a, **kw: real(
            (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype),
            jnp.ones_like(dt), *a, **kw))
    elif wrong == "one held expert fewer":
        held, first = cfg.experts_held
        run_cfg = cfg.replace(experts_held=(held - 1, first))
        run_params = dict(params, layers=[
            {k: (w[:, :held - 1] if k.startswith("we_") else w)
             for k, w in lay.items()} for lay in params["layers"]])
    else:
        run_cfg = cfg.replace(residual_multiplier=1.0)
    _, reference = train_hybrid.token_loss_fns(cfg, sizes)
    got, routes = train_hybrid.token_loss_fns(run_cfg, sizes)[0](run_params,
                                                                 tokens)
    ref, _, rec = reference(params, tokens, routes)
    a = train_hybrid.loss_agreement(got, ref)
    r = train_hybrid.route_agreement(routes, rec, cfg.top_k)
    ok = all(train_hybrid.route_checks(r, tol, cfg.top_k).values()) \
        and a["token_mean_abs"] <= tol["token_mean_abs"] \
        and a["token_p999_abs"] <= tol["token_p999_abs"]
    assert ok == (wrong == "as it is"), (a, r, tol)
