"""The Ling-3.0-flash cell's yardstick: the arithmetic by hand, the readers
on synthetic traces, the manifest's lists, the catalog's numbers, the
benchmark's own copy of the reference, the placement inside a group, and
the kind rehearsed on the CPU."""

import inspect
import json
import math
import os
import time
import types

import pytest

from benchmark import flops, flops_ling, model_ling, reference_ling, resolve
from benchmark.kinds import train_kda
from benchmark.readers import ling_kernel_roofline, scope_paths_share

CELL = "train-ling3flash-ep32-s16384-b1"
CONFIG = "ling-3.0-flash-ep32-l8"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _sizes():
    return model_ling.sizes(resolve.config(CONFIG))


def test_flops_by_hand():
    s = _sizes()
    assert (s["conv_taps"], s["n_dense"], s["dense_d_ff"], s["d_ff"],
            s["kda_head_dim"], s["top_k"], s["n_group"], s["topk_group"],
            s["q_rank"]) == (4, 2, 6144, 768, 128, 8, 8, 4, 0)
    assert s["kinds"] == ("kda.dense", "kda.dense", "kda", "kda", "kda",
                          "mla", "kda", "kda")
    assert flops_ling.kinds(s) == {"kda": 7, "mla": 1, "dense": 2,
                                   "experts": 6}
    # a KDA half: q, k, v and the gate 2560 x 4096 each, beta and the
    # output gate 2560 x 32, the output 4096 x 2560: 52.59 M
    assert flops_ling.kda_params(s) == 5 * 2560 * 4096 + 2 * 2560 * 32
    # an MLA half: 15.73 + 1.47 + 4.19 + 0.08 + 10.49 = 31.97 M
    assert flops_ling.mla_params(s) == (2560 * 32 * 192 + 2560 * 576
                                        + 512 * 32 * 256 + 2560 * 32
                                        + 32 * 128 * 2560)
    parts = flops_ling.matmul_params_per_token(s)
    assert parts["dense layers"] == 2 * 3 * 2560 * 6144
    assert parts["router"] == 6 * 2560 * 512
    # 8 x 16 / 512 = a quarter of an expert a token under an even router
    assert parts["experts held"] == 6 * 0.25 * 3 * 2560 * 768
    assert parts["shared expert"] == 6 * 3 * 2560 * 768
    assert parts["head"] == 2560 * 19648
    assert flops_ling.total_params(s) == 1_204_989_024
    fwd = flops_ling.forward_flops_per_token(s, 16384)
    # q k^T over 192 lanes and p v over 128, half the square, one layer
    assert fwd["attention"] == 16384 * 32 * (192 + 128)
    assert fwd["delta rule"] == 7 * 7.0 * 32 * 128 * 128
    assert flops_ling.train_flops_per_token(s, 16384) == 3 * sum(fwd.values())
    call = flops_ling.flash_call(s, 1, 16384, "fwd")
    assert call["ops"] == 16384 * 16384 * 32 * (192 + 128)
    assert call["bytes"] == 16384 * 32 * 2 * (2 * 192 + 2 * 128)
    assert flops_ling.flash_call(s, 1, 16384, "dkdv")["ops"] \
        == 16384 * 16384 * 32 * (2 * 192 + 2 * 128)
    rule = flops_ling.delta_rule_layer(s, 1, 16384)
    assert rule["ops"] == 21.0 * 32 * 128 * 128 * 16384
    wide, heads = 16384 * 4096, 16384 * 32
    assert rule["bytes"] == (wide * 12 + heads * 4) * 2 + wide * 10 + heads * 4
    assert flops.least_seconds(rule, PEAK)["bound"] == "memory"


def test_the_benchmark_keeps_its_own_copy_of_the_reference():
    from ray_tpu.models import reference_ling as programs

    for name in ("_rms", "_sigmoid", "_silu", "layers", "_conv_silu", "_l2",
                 "delta_rule", "kda", "_rotary_pairs", "mla", "_swiglu",
                 "choose", "experts", "first_half", "layer", "forward",
                 "token_losses", "loss", "biases", "bias_update"):
        assert inspect.getsource(getattr(reference_ling, name)) \
            == inspect.getsource(getattr(programs, name)), name


# --- readers on synthetic traces -------------------------------------------
def _call(results, operands):
    return (f"%call.1 = {results} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", operand_layout')


def _obs():
    cell = resolve.cell(CELL)
    return {"sizes": model_ling.sizes(cell["config"]), "cell": cell,
            "values": {"held_rows": 4096.0}, "peak": PEAK}


W = "bf16[1,16384,4096]{2,1,0}"
G = "f32[1,16384,4096]{2,1,0}"
BETA = "f32[1,16,16384,2]{3,2,1,0}"
STATE = "f32[1,256,4096,128]{3,2,1,0}"
RULE_FWD = _call(f"({W}, {STATE})", f"{W} %q, {W} %k, {W} %v, {G} %g, "
                 f"{BETA} %b, f32[1,4096,128]{{2,1,0}} %s")
RULE_BWD = _call(f"({W}, {W}, {W}, {G}, {BETA}, f32[1,4096,128]{{2,1,0}})",
                 f"{W} %q, {W} %k, {W} %v, {G} %g, {BETA} %b, {STATE} %s, "
                 f"{W} %do")
Q = "bf16[1,32,16384,192]{3,2,1,0}"
FLASH_FWD = _call(f"({Q}, f32[1,32,16384,128]{{3,2,1,0}})",
                  f"{Q} %q, {Q} %k, {Q} %v")
META = "s32[] %n, s32[10]{0} %o, s32[168]{0} %g, s32[168]{0} %t, s32[1]{0} %f"
GMM = _call("bf16[6144,768]{1,0}", f"{META}, bf16[6144,2560]{{1,0}} %x, "
            "bf16[16,2560,768]{2,1,0} %w")


def test_roofline_reader_tells_the_calls_apart():
    obs = _obs()
    kinds = [ling_kernel_roofline.classify(n, obs)[:2]
             for n in (RULE_FWD, RULE_BWD, FLASH_FWD, GMM)]
    assert kinds == [("delta_rule", "fwd"), ("delta_rule", "bwd"),
                     ("flash_attention", "fwd"), ("grouped_matmul", "")]
    # q and k padded to whole lane tiles: the same call, reckoned at 192
    padded = FLASH_FWD.replace(",16384,192]", ",16384,256]")
    assert ling_kernel_roofline.classify(padded, obs)[2] \
        == ling_kernel_roofline.classify(FLASH_FWD, obs)[2]
    # seven KDA layers, four traced steps: the forward runs twice a layer
    # (the replay), the backward once
    obs["trace"] = {
        "device_ops": [[RULE_FWD, 0.9], [RULE_BWD, 1.1], [FLASH_FWD, 0.2],
                       [GMM, 0.01], ["%fusion.1 = x", 1.0]],
        "op_calls": {RULE_FWD: 56, RULE_BWD: 28, FLASH_FWD: 4, GMM: 24,
                     "%fusion.1 = x": 40}}
    layer = flops.least_seconds(flops_ling.delta_rule_layer(
        obs["sizes"], 1, 16384), PEAK)["seconds"]
    got = ling_kernel_roofline.read({"kernel": "delta_rule"}, obs)
    assert math.isclose(got, 100 * 28 * layer / 2.0) and 1 < got < 100
    flash = flops.least_seconds(flops_ling.flash_call(
        obs["sizes"], 1, 16384, "fwd"), PEAK)["seconds"]
    assert math.isclose(ling_kernel_roofline.read(
        {"kernel": "flash_attention"}, obs), 100 * 4 * flash / 0.2)
    assert 0 < ling_kernel_roofline.read({"kernel": "grouped_matmul"},
                                         obs) < 100
    # a program of another family (the parent's cells): nothing to read
    assert ling_kernel_roofline.read(
        {"kernel": "delta_rule"}, dict(obs, sizes={"d_model": 4096})) is None
    with pytest.raises(ValueError, match="no delta-rule call"):
        ling_kernel_roofline.classify(RULE_FWD.replace(",4096]", ",2048]"),
                                      obs)


def test_the_paths_reader_counts_an_op_once(monkeypatch):
    from benchmark import op_scopes

    ops = [["a", 0.1], ["b", 0.2], ["c", 0.3], ["d", 0.4]]
    parts = {"a": ["layers", "mixer", "kda", "conv"],
             "b": ["layers", "mixer", "kda", "scan", "kda.fwd.pallas"],
             "c": ["transpose(", "mixer", "kda", "out"],
             "d": ["attention", "mla"]}
    monkeypatch.setattr(op_scopes, "of_run", lambda: {})
    monkeypatch.setattr(op_scopes, "labelled", lambda ops, labels: [
        (n, s, parts[n]) for n, s in ops])
    spec = resolve.layer_metric("kda_row_work_device_share")
    assert spec["reader"] == "scope_paths_share"
    obs = {"trace": {"device_ops": ops, "window_s": 2.0}}
    assert math.isclose(scope_paths_share.read(spec, obs), 100 * 0.4 / 2.0)
    # a held share's ``combine`` encloses the walk's dispatch and experts:
    # the layer whole, and without its grouped matmuls
    parts.update(a=["feed_forward", "router"],
                 b=["feed_forward", "combine", "experts", "gmm.pallas"],
                 c=["transpose(", "feed_forward", "combine", "while", "body",
                    "dispatch"],
                 d=["feed_forward", "shared"])
    whole = resolve.layer_metric("expert_layer_device_share.ling")
    rest = resolve.layer_metric("expert_dispatch_device_share.ling")
    assert math.isclose(scope_paths_share.read(whole, obs), 100 * 0.6 / 2.0)
    assert math.isclose(scope_paths_share.read(rest, obs), 100 * 0.4 / 2.0)
    monkeypatch.setattr(op_scopes, "of_run", lambda: None)
    assert scope_paths_share.read(spec, obs) is None


NEW = {"kda_device_share", "kda_row_work_device_share", "delta_rule_roofline",
       "mla_device_share.ling", "flash_attention_roofline.ling",
       "grouped_matmul_roofline.ling", "expert_held_rows_share.ling",
       "expert_load_max_over_mean.ling", "expert_group_kept_share",
       "expert_layer_device_share.ling", "expert_dispatch_device_share.ling",
       "shared_expert_device_share.ling", "dense_ffn_device_share.ling"}


def test_the_manifest_lists_the_cell_for_every_metric_it_reports():
    cell_kind = resolve.workload(CELL)["kind"]
    assert cell_kind == "train_kda"
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
            assert resolve.layer_metric(m["name"])["kinds"] == ["train_kda"]
    e2e = {m["name"] for m in resolve.metrics_for(CELL, "end_to_end",
                                                  cell_kind)}
    assert e2e == {"train_tok_s_chip", "setup_s"}
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == resolve.config(CONFIG)["reduced"] \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert man["workloads"][-1]["name"] == CELL and len(man["workloads"]) == 12
    cell = resolve.cell(CELL)
    assert (cell["mix"]["batch"], cell["mix"]["seq"], cell["chips"]) \
        == (1, 16384, 1)


def test_every_published_number_stands_but_the_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f
                   if '"name": "Ling-3.0-flash-VL"' in ln)
    conf = resolve.config(CONFIG)
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    # the clamp lists are 0 in every layer of the cut
    assert not any(conf["expert_swiglu_limit_list"][:8]
                   + conf["share_expert_swiglu_limit_list"][:8])
    for key in ("assumed", "stands_for", "cut", "memory_plan", "deployment"):
        assert conf[key], key


def test_placement_permutes_inside_the_held_group_only():
    """512 experts in 8 groups of 64, this chip serves group 3 from expert
    192 on: the relabelling is the identity outside experts 192..255, a
    permutation inside them, and the 16 held labels get a set of even
    load."""
    import random

    s = _sizes()
    rng = random.Random(7)
    counts = [rng.randrange(100, 400) for _ in range(512)]
    order, mine = train_kda.group_order(counts, s)
    assert sorted(order) == list(range(512))
    assert order[:192] == list(range(192)) and order[256:] \
        == list(range(256, 512))
    assert sorted(order[192:256]) == list(range(192, 256))
    assert order[192:208] == mine and len(set(mine)) == 16
    group = sum(counts[192:256])
    assert abs(sum(counts[e] for e in mine) - group / 4) < 0.02 * group / 4
    # a group's experts keep competing with the same experts: its two
    # largest counts are what they were
    for g in range(8):
        new = sorted(counts[order[e]] for e in range(64 * g, 64 * g + 64))
        assert new == sorted(counts[64 * g:64 * g + 64])


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

    res = train_kda.run(
        resolve.cell("rehearse-train-kda"),
        types.SimpleNamespace(seed=2147483659, seconds=1.0, trace=0),
        {"log": print, "t_start": time.time(), "out_dir": str(tmp_path),
         "trace_dir": str(tmp_path / "trace"), "peak": resolve.peak,
         "Refused": Refused})
    assert res["device"]["platform"] == "cpu"
    assert len(res["checks"]) >= 15 and all(res["checks"].values()), \
        res["checks"]
    assert any(k.startswith("the delta rule's calls alone, timed")
               for k in res["checks"])
    assert res["attempted"] >= 2 and res["end_to_end"]["train_tok_s_chip"] > 0
    assert set(res["obs"]) == {"counters", "values", "trace", "sizes", "cell"}
    assert 0 < res["obs"]["values"]["held_rows"] <= 3 * 128 * 2
    assert 0.3 < res["obs"]["values"]["group_kept_share"] < 0.7
    assert res["obs"]["sizes"]["kda_head_dim"] == 16


def test_a_program_without_the_family_is_refused_at_once(monkeypatch):
    """The parent commit's program has no ``models/ling.py``; the kind says
    so before a cluster starts."""
    import importlib.util

    class Refused(Exception):
        pass

    find = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec", lambda name, *a: None
                        if name == "ray_tpu.models.ling" else find(name, *a))
    with pytest.raises(Refused, match="Kimi Delta Attention"):
        train_kda.run(resolve.cell("rehearse-train-kda"), None,
                      {"Refused": Refused})


def test_the_seeded_weights_open_the_gate():
    """What ``seeded_weights`` draws: a gate's bias about 0, so that the
    gate spreads over (-5, 0), where the source's draw leaves nine
    channels in ten without decay."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import ling

    cfg = ling.PRESETS["tiny"]
    own = ling.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    seeded = train_kda.seeded_weights(jax.random.PRNGKey(0),
                                      cfg)["layers"][0]
    f = jnp.zeros((1, 1, cfg.kda_width))

    def gate(stack):
        return ling.decay_gate(f, stack["a_log"][0], stack["dt_bias"][0],
                               -5.0, cfg.kda_head_dim)

    assert float(jnp.mean(gate(own) > -0.25)) > 0.8
    spread = gate(seeded)
    assert float(jnp.mean(spread < -2.5)) > 0.25 \
        and float(jnp.mean(spread > -2.5)) > 0.25
    assert float(jnp.abs(seeded["o_norm"] - 1).max()) > 0.1


def test_the_kernel_pair_alone_is_read_and_a_rounded_state_is_refused():
    """Part (e) at the tiny size: the first layer's own scan inputs, the
    kernel path (interpreted) against the plain one within the rehearsal
    cell's limit in all six parts; the plain path with its state rounded
    to bfloat16 from chunk to chunk reads above it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import ling
    from ray_tpu.ops import delta_rule

    limit = resolve.cell("rehearse-train-kda")["train"]["check"][
        "op_rel_timed"]
    cfg = ling.PRESETS["tiny"].replace(dtype=jnp.float32,
                                       param_dtype=jnp.float32)
    params = train_kda.seeded_weights(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 129), 0,
                                cfg.vocab_size)
    inputs = jax.jit(lambda p, t: train_kda.scan_inputs(cfg, p, t))(params,
                                                                    tokens)
    assert [t.shape for t in inputs] == [(2, 128, 2, 16)] * 4 + [(2, 128, 2)]
    assert inputs[3].dtype == jnp.float32 and float(inputs[3].min()) >= -5.0
    read = train_kda.op_agreement(
        inputs, jax.random.normal(jax.random.PRNGKey(5), inputs[2].shape),
        cfg.kda_lower_bound)
    kernel = read(lambda *a: delta_rule.gated_delta_rule(*a, impl="pallas"),
                  jnp.float32)
    assert set(kernel) == set(train_kda.OP_PARTS)
    assert max(kernel.values()) <= limit, kernel

    def rounded(state, *a):
        state, o = delta_rule._chunk_xla(state, *a)
        return jax.lax.reduce_precision(state, 8, 7), o

    def wrong(q, k, v, g, beta):
        f32 = lambda t: t.astype(jnp.float32)                  # noqa: E731
        body = jax.checkpoint(lambda s, x: rounded(s, *x))
        B, S, H, dk = q.shape

        def chunks(a):
            a = a.reshape(B, S // 64, 64, *a.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

        _, o = jax.lax.scan(body, jnp.zeros((B, H, dk, v.shape[-1])),
                            tuple(chunks(f32(t)) for t in (q, k, v, g, beta)))
        return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, S, H, -1)

    carried = read(wrong, jnp.float32)
    assert max(carried.values()) > limit, carried
    print("the kernel pair", kernel, "a rounded state", carried)
