"""The block-set kind's yardstick: ``flops_sala.py`` by hand, the two
copies of the plain reference, the roofline reader on synthetic Mosaic
lines, the cell's metric set, wrong models that ``correct`` must refuse,
and the kind's ``run()`` rehearsed on the CPU."""
import inspect
import os
import time
import types

import pytest

from benchmark import flops, flops_sala, model_sala, reference_sala, resolve
from benchmark.readers import sala_kernel_roofline

CELL = "train-minicpmsala-l4-s16384-b1"
CONFIG = "minicpm-sala-9b-l4"
TOY = {"d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_width": 2,
       "d_ff": 6, "vocab_size": 10, "lightning_heads": 3,
       "layer_types": ("sparse", "lightning", "lightning"),
       "sparse_kernel": 4, "sparse_stride": 2, "sparse_block": 4,
       "sparse_topk": 2, "sparse_init_blocks": 1, "sparse_window": 4,
       "dense_len": 4}


def _sizes():
    return model_sala.sizes(resolve.config(CONFIG))


def test_flops_by_hand():
    # blocks of 4, 2 a query, 10 queries: queries 0-3 their own block up
    # to themselves (1 + 2 + 3 + 4), queries 4-9 one whole block and
    # theirs (4 + 1 .. 4 + 4, 4 + 1, 4 + 2)
    assert flops_sala.selected_pairs(10, TOY) == 10 + (5 + 6 + 7 + 8) + 11
    assert flops_sala.selected_pairs(4, TOY) == 10       # dense: causal
    assert flops_sala.causal_pairs(10) == 55
    assert [flops_sala.blocks_held(t, TOY) for t in (0, 3, 4, 9)] \
        == [1, 1, 2, 2]
    assert flops_sala.kinds(TOY) == {"sparse": 1, "lightning": 2}
    # q, gate, o over 4 x 2 lanes, k and v over 2 x 2; lightning 3 x 2 all
    assert flops_sala.mixer_params(TOY, "sparse") == 3 * 8 * 8 + 2 * 8 * 4
    assert flops_sala.mixer_params(TOY, "lightning") == 5 * 8 * 6
    per = flops_sala.matmul_params_per_token(TOY)
    assert per == {"sparse projections": 256, "lightning projections": 480,
                   "swiglu": 3 * 3 * 8 * 6, "head": 80}
    fwd = flops_sala.forward_flops_per_token(TOY, 10)
    # 47 pairs x 4 heads x (2 + 2) lanes x 2
    assert fwd["attention"] == 2.0 * 47 * 4 * 4 / 10
    # 4 kernels, half the rectangle: 10 x 4 / 2 x 4 heads x 2 lanes x 2
    assert fwd["selection"] == 2.0 * 10 * 4 / 2 * 4 * 2 / 10
    assert fwd["recurrence"] == 2 * 5.0 * 3 * 2 * 2
    assert flops_sala.train_flops_per_token(TOY, 10) \
        == 3 * sum(fwd.values()) - 2 * fwd["selection"]
    layer = flops_sala.block_sparse_attention_layer(TOY, 1, 10)
    assert layer["ops"] == 3.5 * 2.0 * 47 * 4 * 4
    q, kv, the_set = 10 * 4 * 2 * 2, 10 * 2 * 2 * 2, 2 * 10 * 2
    assert layer["bytes"] == 2 * q + 2 * kv + the_set + 4 * q + 4 * kv \
        + the_set
    scan = flops_sala.lightning_layer(TOY, 1, 10)
    assert scan["ops"] == 3.0 * 10 * 5.0 * 3 * 4
    assert scan["bytes"] == 11 * 10 * 3 * 2 * 2


def test_flops_of_the_cell():
    s = _sizes()
    assert flops_sala.total_params(s) == 1_711_117_696
    # 58.3 M of 134.2 M causal pairs a head
    assert flops_sala.selected_pairs(16384, s) == 58_335_232
    assert flops_sala.causal_pairs(16384) == 134_225_920
    assert flops_sala.selected_pairs(8192, s) == flops_sala.causal_pairs(8192)
    fwd = flops_sala.forward_flops_per_token(s, 16384)
    assert round(sum(fwd.values()) / 1e6) == 2891
    # the two mechanisms are 2.3% of the forward's arithmetic, the head 21%
    assert 0.02 < (fwd["attention"] + fwd["recurrence"]) \
        / sum(fwd.values()) < 0.03
    assert 0.20 < fwd["head"] / sum(fwd.values()) < 0.22
    layer = flops_sala.block_sparse_attention_layer(s, 1, 16384)
    assert round(layer["ops"] / 1e12, 2) == 3.35
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(layer, peak)["bound"] == "compute"
    assert flops.least_seconds(flops_sala.lightning_layer(s, 1, 16384),
                               peak)["bound"] == "memory"


def test_the_count_is_the_programs():
    from ray_tpu.models import sala

    cfg = model_sala.sala_config(resolve.config(CONFIG))
    assert sala.num_params(cfg) == flops_sala.total_params(_sizes())
    assert sala.layer_runs(cfg) == [("sparse", 1), ("lightning", 3)]
    assert cfg.residual_multiplier == 1.4 / 32 ** 0.5
    assert cfg.logits_scaling == 16.0 and cfg.embedding_multiplier == 12


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_sala as programs

    for name in ("_rms", "_runs", "_by_rows", "_swiglu", "_rope", "_heads",
                 "pooled_keys", "block_scores", "forced", "own_set",
                 "_sparse_attention", "_lightning_attention", "block",
                 "forward", "token_losses", "loss"):
        assert inspect.getsource(getattr(reference_sala, name)) \
            == inspect.getsource(getattr(programs, name)), name
    for mod in (reference_sala, programs):
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
    return {"sizes": model_sala.sizes(cell["config"]), "cell": cell,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


Q = "bf16[1,32,16384,128]{3,2,1,0}"
K = "bf16[1,2,16384,128]{3,2,1,0}"
LSE = "f32[1,32,16384,128]{3,2,1,0}"
SET = "s8[1,2,16384,256]{3,2,1,0}"
FWD = _call(f"({Q}, {LSE})", f"{Q} %q, {K} %k, {K} %v, {SET} %m")
DQ = _call(Q, f"{Q} %q, {K} %k, {K} %v, {SET} %m, {Q} %g, {Q} %o, {LSE} %l")
DKDV = _call(f"({K}, {K})",
             f"{Q} %q, {K} %k, {K} %v, {SET} %m, {Q} %g, {Q} %o, {LSE} %l")
U = "bf16[1,16384,4096]{2,1,0}"
STATES = "f32[1,64,4096,128]{3,2,1,0}"
SCAN_FWD = _call(f"({U}, {STATES})", f"f32[32]{{0}} %a, {U} %u, {U} %b, {U} %c")
SCAN_BWD = _call(f"({U}, {U}, {U})",
                 f"f32[32]{{0}} %a, {U} %u, {U} %b, {U} %c, {STATES} %h, "
                 f"{U} %g")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    for line, which in ((FWD, "fwd"), (DQ, "dq"), (DKDV, "dkdv")):
        assert sala_kernel_roofline.classify(line, obs) == (
            "block_sparse_attention", which)
    for line, which in ((SCAN_FWD, "fwd"), (SCAN_BWD, "bwd")):
        assert sala_kernel_roofline.classify(line, obs) == ("ssd_scan", which)
    # three lightning layers a step, four steps: the forward twice a layer
    trace = {"device_ops": [[SCAN_FWD, 0.12], [SCAN_BWD, 0.12], [DQ, 0.1]],
             "op_calls": {SCAN_FWD: 24, SCAN_BWD: 12, DQ: 4},
             "window_s": 1.0}
    got = sala_kernel_roofline.read({"kernel": "ssd_scan"},
                                    {**obs, "trace": trace})
    layer = flops_sala.lightning_layer(obs["sizes"], 1, 16384)
    least = 12 * flops.least_seconds(layer, obs["peak"])["seconds"]
    assert got == pytest.approx(100.0 * least / 0.24)
    assert 0 < got < 100


def test_roofline_reader_reads_nothing_of_another_program():
    obs = _obs()
    trace = {"device_ops": [[SCAN_FWD, 0.1]], "op_calls": {SCAN_FWD: 3},
             "window_s": 1.0}
    # no backward call in the trace, another family's sizes, no trace
    assert sala_kernel_roofline.read({"kernel": "ssd_scan"},
                                     {**obs, "trace": trace}) is None
    assert sala_kernel_roofline.read(
        {"kernel": "ssd_scan"}, {**obs, "sizes": {"d_model": 8},
                                 "trace": trace}) is None
    assert sala_kernel_roofline.read({"kernel": "ssd_scan"}, obs) is None


def test_roofline_reader_raises_on_a_foreign_mosaic_call():
    obs = _obs()
    wrong = _call(f"({Q}, {LSE})", f"{Q} %q, {Q} %k, {Q} %v")     # dense flash
    with pytest.raises(ValueError, match="no sparse-attention call"):
        sala_kernel_roofline.classify(wrong, obs)
    other = FWD.replace("s8[1,2,16384,256]", "s8[1,16384,16384]")
    with pytest.raises(ValueError, match="no sparse-attention call"):
        sala_kernel_roofline.classify(other, obs)


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_blockset"
    per_layer = resolve.metrics_for(CELL, "per_layer", cell_kind)
    names = {m["name"] for m in per_layer}
    assert {"block_select_device_share",
            "block_sparse_attention_device_share", "lightning_device_share",
            "block_sparse_attention_roofline", "ssd_scan_roofline.sala",
            "block_selected_share", "block_walked_over_selected",
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
    assert len(man["workloads"]) == 10


def test_every_published_number_stands_but_the_reduced():
    """Against the catalog's numbers as ISSUE 52 quotes them."""
    conf = resolve.config(CONFIG)
    for key, value in {
            "hidden_size": 4096, "intermediate_size": 16384,
            "num_attention_heads": 32, "num_key_value_heads": 2,
            "head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
            "lightning_head_dim": 128, "vocab_size": 73448,
            "rms_norm_eps": 1e-06, "rope_theta": 10000, "scale_emb": 12,
            "scale_depth": 1.4, "dim_model_base": 256,
            "mup_denominator": 32, "max_position_embeddings": 524288,
            "tie_word_embeddings": False, "attn_use_rope": False,
            "qk_norm": True}.items():
        assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert conf["num_hidden_layers"] == 4
    assert conf["mixer_types"] == conf["published"]["mixer_types"][:4] == [
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
    assert conf["published"]["num_hidden_layers"] == 32
    assert len(conf["published"]["mixer_types"]) == 32
    assert conf["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert len(conf["assumed"]) >= 8


# --- wrong models that ``correct`` must refuse (tiny sizes, float32) --------
@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import sala

    conf = resolve.config("tiny-sala")
    sizes = model_sala.sizes(conf)
    cfg = model_sala.sala_config(conf, report_sets=True)
    params = jax.jit(lambda k: sala.init_params(k, cfg))(jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 129), 0,
                                cfg.vocab_size, jnp.int32)
    return cfg, sizes, params, tokens


def _judge(cfg, sizes, params, tokens, patch=None):
    """(set checks, loss agreement) of a program against the reference on
    the program's own sets, as the kind computes them."""
    import contextlib

    from benchmark.kinds import train_blockset

    with patch() if patch else contextlib.nullcontext():
        program, reference = train_blockset.token_loss_fns(cfg, sizes)
        got, sets = program(params, tokens)
    ref, rec = reference(params, tokens, sets)
    tol = resolve.workload("rehearse-train-blockset")["train"]["check"]
    return (train_blockset.set_checks(
        train_blockset.set_agreement(sets, rec, sizes), tol, sizes),
        train_blockset.loss_agreement(got, ref), tol)


def test_the_program_as_it_is_passes(tiny):
    checks, losses, tol = _judge(*tiny)
    assert all(checks.values()), checks
    assert losses["token_mean_abs"] <= tol["token_mean_abs"]
    assert losses["token_p999_abs"] <= tol["token_p999_abs"]


@pytest.mark.parametrize("wrong", ["top-2 for top-4", "no forced blocks"])
def test_a_wrong_selection_is_refused_by_the_sets(tiny, wrong):
    cfg, sizes, params, tokens = tiny
    bad = cfg.replace(sparse_topk=2) if wrong == "top-2 for top-4" \
        else cfg.replace(sparse_init_blocks=0, sparse_window=8)
    checks, _, _ = _judge(bad, sizes, params, tokens)
    assert not all(checks.values()), checks


def test_a_scan_without_its_decay_is_refused_by_the_losses(tiny):
    from unittest import mock

    import jax.numpy as jnp

    from ray_tpu.models import sala

    cfg, sizes, params, tokens = tiny
    checks, losses, tol = _judge(
        cfg, sizes, params, tokens, lambda: mock.patch.object(
            sala, "slopes", lambda heads: jnp.zeros((heads,), jnp.float32)))
    # (the last sparse layer selects from what the wrong scans handed it:
    # its sets may differ from the reference's too)
    assert losses["token_mean_abs"] > 10 * tol["token_mean_abs"], losses


def test_eight_bit_lightning_projections_are_refused_by_the_losses(tiny):
    import jax

    cfg, sizes, params, tokens = tiny
    rounded = dict(params, layers=[{
        k: (jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)
            if k in ("wq", "wk", "wv") and "o_norm" in s else w)
        for k, w in s.items()} for s in params["layers"]])
    program = _judge(cfg, sizes, rounded, tokens)
    # the reference on the ROUNDED weights agrees; against the model's own
    # it does not
    assert program[1]["token_mean_abs"] <= program[2]["token_mean_abs"]
    from benchmark.kinds import train_blockset

    got, sets = train_blockset.token_loss_fns(cfg, sizes)[0](rounded, tokens)
    ref, _ = train_blockset.token_loss_fns(cfg, sizes)[1](params, tokens,
                                                          sets)
    apart = train_blockset.loss_agreement(got, ref)
    assert apart["token_mean_abs"] > 10 * program[2]["token_mean_abs"], apart


# --- the kind, rehearsed on the CPU (a cluster starts and stops) -----------
def test_rehearsal_walks_the_kind_on_the_cpu(monkeypatch, tmp_path):
    """Not through run.py: ``resolve.metrics_for`` looks an unlisted cell's
    kind up in ``E2E_OF_KIND``, which knows ``train`` and ``serve`` only."""
    from benchmark.kinds import train_blockset

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

    res = train_blockset.run(
        resolve.cell("rehearse-train-blockset"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 14 and all(res["checks"].values()), \
        res["checks"]
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    counters = res["obs"]["counters"]
    assert counters["sparse_pairs_selected"] == flops_sala.selected_pairs(
        128, res["obs"]["sizes"])
    assert counters["sparse_pairs_walked"] >= counters["causal_pairs"]


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    import importlib.util

    from benchmark.kinds import train_blockset

    class Refused(Exception):
        pass

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(Refused, match="no attention over a set of blocks"):
        train_blockset.run(resolve.cell("rehearse-train-blockset"), None,
                           {"Refused": Refused})


def test_a_sequence_within_the_dense_length_is_refused(monkeypatch):
    from benchmark.kinds import train_blockset

    class Refused(Exception):
        pass

    cell = resolve.cell("rehearse-train-blockset")
    cell["mix"] = dict(cell["mix"], seq=32)
    with pytest.raises(Refused, match="dense length"):
        train_blockset.run(cell, None, {"Refused": Refused, "log": print})
