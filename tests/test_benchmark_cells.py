"""Every train cell's program config still builds, here on the CPU.

A cell's recipe (``benchmark/workloads/<cell>.json``, group ``train``) names
fields of ``LlamaConfig`` / ``MoEConfig``; the harness hands them over in the
worker that holds the chip (``benchmark/kinds/train.py``, ``train_moe.py``),
so a field that the program loses while a recipe still names it would fail
only there. ``benchmark/tests/`` is not on the driver's command; this is. It
reads the benchmark's files and changes none.
"""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)

# the recipe keys each kind hands to its config builder
# (benchmark/kinds/train.py, benchmark/kinds/train_moe.py: run())
RECIPE_KEYS = {"train": ("attn_impl", "remat", "f32_logits"),
               "train_moe": ("attn_impl", "gmm_impl", "remat", "f32_logits"),
               "train_hybrid": ("attn_impl", "gmm_impl", "ssd_impl", "remat",
                                "f32_logits"),
               "train_latent": ("attn_impl", "gmm_impl", "remat",
                                "f32_logits"),
               "train_mixed": ("attn_impl", "gmm_impl", "remat",
                               "f32_logits"),
               "train_parallel": ("attn_impl", "gmm_impl", "remat",
                                  "f32_logits"),
               "train_sparse": ("attn_impl", "gmm_impl", "remat",
                                "f32_logits"),
               "train_alternating": ("attn_impl", "gmm_impl", "ssd_impl",
                                     "remat", "f32_logits"),
               "train_blockset": ("attn_impl", "ssd_impl", "remat",
                                  "f32_logits"),
               "train_shortconv": ("attn_impl", "gmm_impl", "remat",
                                   "f32_logits"),
               "train_kda": ("attn_impl", "gmm_impl", "kda_impl", "remat",
                             "f32_logits"),
               "train_solar": ("attn_impl", "gmm_impl", "kda_impl", "remat",
                               "f32_logits"),
               "train_falconh1": ("attn_impl", "ssd_impl", "remat",
                                  "f32_logits")}
# published config.json key -> the program's field
WIDTHS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
          "vocab_size": "vocab_size", "rope_theta": "rope_theta",
          "rms_norm_eps": "norm_eps", "sliding_window": "sliding_window"}
MOE_WIDTHS = {"num_experts": "n_experts", "num_experts_per_tok": "top_k",
              "norm_topk_prob": "norm_topk"}


def _train_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [w["name"] for w in json.load(f)["workloads"]]
    rehearsals = sorted(
        os.path.basename(p)[:-5] for p in glob.glob(os.path.join(
            ROOT, "benchmark", "workloads", "rehearse-train*.json")))
    return listed + rehearsals


@pytest.mark.parametrize("name", _train_cells())
def test_cell_program_config_builds_at_its_published_widths(name):
    import jax
    import jax.numpy as jnp

    from benchmark import (model, model_commanda, model_glm, model_glm52,
                           model_granite, model_lfm2, model_ling,
                           model_mellum, model_moe, model_nemotron,
                           model_falconh1, model_sala, model_solar, resolve)

    cell = resolve.cell(name)
    kind, conf, recipe = cell["kind"], cell["config"], cell["train"]
    build = {"train": model.llama_config,
             "train_moe": model_moe.moe_config,
             "train_hybrid": model_granite.hybrid_config,
             "train_latent": model_glm.latent_config,
             "train_mixed": model_mellum.moe_config,
             "train_parallel": model_commanda.moe_config,
             "train_sparse": model_glm52.latent_config,
             "train_alternating": model_nemotron.hybrid_config,
             "train_blockset": model_sala.sala_config,
             "train_shortconv": model_lfm2.hybrid_config,
             "train_kda": model_ling.ling_config,
             "train_solar": model_solar.solar_config,
             "train_falconh1": model_falconh1.falcon_config}[kind]
    passed = {k: recipe[k] for k in RECIPE_KEYS[kind] if k in recipe}
    cfg = build(conf, **passed)

    widths = {"train": WIDTHS, "train_moe": {**WIDTHS, **MOE_WIDTHS},
              "train_hybrid": model_granite.HF_TO_FIELD,
              "train_latent": model_glm.HF_TO_FIELD,
              "train_mixed": model_mellum.HF_TO_FIELD,
              "train_parallel": {
                  k: f for k, f in model_commanda.HF_TO_FIELD.items()
                  if f != "logit_scale"},
              "train_sparse": model_glm52.HF_TO_FIELD,
              "train_alternating": model_nemotron.HF_TO_FIELD,
              "train_blockset": model_sala.HF_TO_FIELD,
              "train_shortconv": model_lfm2.HF_TO_FIELD,
              "train_kda": model_ling.HF_TO_FIELD,
              "train_solar": model_solar.HF_TO_FIELD,
              "train_falconh1": model_falconh1.HF_TO_FIELD}[kind]
    for key, field in widths.items():
        assert getattr(cfg, field) == conf[key], (name, key)
    if kind == "train_parallel":
        # the block, the norm, the router and the shared experts are the
        # published keys'; the router's width and the experts and heads
        # held the deployment's
        dep = conf["deployment"]
        assert cfg.parallel_block and cfg.norm == "layer" and cfg.tied_head
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["num_experts"],
                                    dep["experts_first"])
        assert (cfg.n_heads, cfg.n_kv_heads) == (dep["heads_held"],
                                                 dep["kv_heads_held"])
        assert cfg.head_dim == conf["head_dim"]
        assert (cfg.n_shared, cfg.shared_d_ff, cfg.shared_combine) == (
            conf["num_shared_experts"], conf["intermediate_size"], "average")
        assert (cfg.router_score, cfg.router_bias, cfg.norm_topk) == (
            "sigmoid", False, True)
        assert cfg.router_aux_weight == cfg.router_z_weight == 0.0
        kinds = {"sliding_attention": "window", "full_attention": "full"}
        assert cfg.layer_kinds == tuple(
            kinds[t] for t in conf["layer_types"][:cfg.n_layers])
        of = dict(cfg.attn_kinds)
        assert of["window"].window == conf["sliding_window"]
        assert of["window"].pairs == "neighbours" and of["window"].rope
        assert of["window"].rope_theta == conf["rope_theta"]
        assert of["full"].window is None and not of["full"].rope
    if kind == "train_hybrid":
        # the router's width and the experts held are the deployment's
        dep = conf["deployment"]
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["num_local_experts"],
                                    dep["experts_first"])
        assert cfg.kinds == tuple(conf["layer_types"][:cfg.n_layers])
    if kind == "train_alternating":
        # the mixer's heads, groups, state, taps and chunk, the stated
        # head width and both expert widths are the published keys' (the
        # map above); the pattern's letters the blocks, ONE half each; the
        # router's width and the experts held the deployment's
        assert {"mamba_num_heads", "mamba_head_dim", "ssm_state_size",
                "n_groups", "conv_kernel", "chunk_size", "head_dim",
                "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor"} <= set(widths)
        dep = conf["deployment"]
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["n_routed_experts"],
                                    dep["experts_first"])
        assert cfg.kinds == tuple(model_nemotron.LETTERS[c] for c in
                                  conf["hybrid_override_pattern"])
        assert cfg.one_half and not cfg.tied_head
        # no two adjacent blocks of a kind: a run a block
        from ray_tpu.models import hybrid
        assert hybrid.layer_runs(cfg) == [(k, 1) for k in cfg.kinds]
        assert (cfg.expert_act, cfg.router_score, cfg.norm_topk) == (
            "relu2", "sigmoid", True)
        assert not cfg.rope and cfg.head_dim == conf["head_dim"]
        assert cfg.mamba_inner == conf["mamba_num_heads"] \
            * conf["mamba_head_dim"]
    if kind == "train_shortconv":
        # the taps, the dense layers' count and width, both feed-forward
        # widths, the rotary's theta and the scale are the published keys'
        # (the map above); every layer's operator `layer_types`', the head
        # width the hidden size over the heads; the router's width and the
        # experts held the deployment's
        assert {"conv_L_cache", "num_dense_layers", "intermediate_size",
                "moe_intermediate_size", "rope_theta",
                "routed_scaling_factor"} <= set(widths)
        dep = conf["deployment"]
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["num_experts"], dep["experts_first"])
        dense = conf["num_dense_layers"]
        assert cfg.kinds == tuple(
            model_lfm2.OPERATORS[t] + (".dense" if i < dense else "")
            for i, t in enumerate(conf["layer_types"]))
        assert cfg.head_dim == conf["hidden_size"] \
            // conf["num_attention_heads"]
        assert cfg.rope and cfg.qk_head_norm and cfg.tied_head
        assert not cfg.one_half and cfg.shared_d_ff == 0
        assert (cfg.expert_act, cfg.router_score, cfg.norm_topk) == (
            "swiglu", "sigmoid", True)
        from ray_tpu.models import hybrid
        assert "lm_head" not in hybrid.param_specs(cfg)
        assert sum(n for _, n in hybrid.layer_runs(cfg)) \
            == conf["num_hidden_layers"]
    if kind == "train_kda":
        # both halves' widths, the taps, the gate's bound, the period, the
        # groups and both feed-forward widths are the published keys' (the
        # map above); no query latent; the router's width, the group served
        # and the experts held the deployment's, inside that group
        assert {"head_dim", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "short_conv_kernel_size",
                "kda_lower_bound", "layer_group_size", "n_group",
                "topk_group", "moe_intermediate_size", "intermediate_size",
                "routed_scaling_factor"} <= set(widths)
        dep = conf["deployment"]
        assert conf["q_lora_rank"] is None and cfg.q_rank == 0
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["num_experts"], dep["experts_first"])
        per_group = cfg.n_experts // cfg.n_group
        assert dep["experts_first"] // per_group == dep["group_held"] == (
            dep["experts_first"] + conf["num_experts"] - 1) // per_group
        period = conf["layer_group_size"]
        assert cfg.kinds == tuple(
            ("mla" if (i + 1) % period == 0 else "kda")
            + (".dense" if i < conf["first_k_dense_replace"] else "")
            for i in range(conf["num_hidden_layers"]))
        assert cfg.v_dim < cfg.head_dim == conf["qk_nope_head_dim"] \
            + conf["qk_rope_head_dim"] and cfg.attn_gate and not cfg.n_mtp
        assert cfg.shared_d_ff == conf["moe_shared_expert_intermediate_size"]
        assert (cfg.router_score, cfg.norm_topk, cfg.rope_dim) == (
            "sigmoid", True, conf["rotary_dim"])
        from ray_tpu.models import ling
        assert sum(n for _, n in ling.layer_runs(cfg)) \
            == conf["num_hidden_layers"]
    if kind == "train_falconh1":
        # every head, group, state, chunk and multiplier is the published
        # key's (the map above and the two tuples); the mixer's inner width
        # is mamba_d_ssm, not mamba_expand x hidden; every layer is of the
        # one kind, a block of two first halves
        assert {"head_dim", "mamba_d_state", "mamba_n_groups",
                "mamba_chunk_size", "key_multiplier",
                "lm_head_multiplier"} <= set(widths)
        assert cfg.ssm_multipliers == tuple(conf["ssm_multipliers"])
        assert cfg.mlp_multipliers == tuple(conf["mlp_multipliers"])
        assert cfg.mamba_inner == conf["mamba_d_ssm"] \
            != conf["mamba_expand"] * conf["hidden_size"]
        assert cfg.attn_scale == conf["key_multiplier"] \
            * conf["head_dim"] ** -0.5 and cfg.rope
        assert cfg.n_heads // cfg.n_kv_heads == 5
        from ray_tpu.models import falcon
        from ray_tpu.models.family import _halves
        runs = falcon.layer_runs(cfg)
        assert sum(n for _, n in runs) == conf["num_hidden_layers"]
        assert all(_halves(cfg, k) == ("both", True) for k, _ in runs)
    if kind == "train_solar":
        # the grouped-query half's heads and stated width, the expert's
        # width, the router's scale and the rank of the pairs are the
        # published keys' (the map above); a KDA half's heads, width and
        # taps the nested group's; which layers attend the file's list; no
        # table, no bound on the gate; the router's width and the experts
        # held the deployment's
        assert {"head_dim", "num_key_value_heads", "moe_intermediate_size",
                "routed_scaling_factor", "kda_gate_rank"} <= set(widths)
        linear, dep = conf["linear_attn_config"], conf["deployment"]
        assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_taps) == (
            linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"])
        assert linear["num_kv_heads"] is None and not conf["use_rope"]
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["n_routed_experts"],
                                    dep["experts_first"])
        assert cfg.kinds == tuple(
            "gqa" if i in conf["gqa_layers"] else "kda"
            for i in range(conf["num_hidden_layers"]))
        assert not cfg.rope and not dict(cfg.attn_kinds)["gqa"].rope
        assert not hasattr(cfg, "kda_lower_bound")
        assert cfg.head_dim == conf["head_dim"] and cfg.n_group == 1
        assert cfg.shared_d_ff == conf["n_shared_experts"] * cfg.d_ff
        assert (cfg.router_score, cfg.norm_topk) == ("sigmoid", True)
        from ray_tpu.models import solar
        assert sum(n for _, n in solar.layer_runs(cfg)) \
            == conf["num_hidden_layers"]
    if kind == "train_blockset":
        # the heads, the stated head width, the SwiGLU's width and the
        # vocabulary are the published keys' (the map above); the kinds of
        # layer `mixer_types`', the multipliers MiniCPM's three over the
        # PUBLISHED depth, the selection's sizes the file's `sparse_config`
        assert {"head_dim", "lightning_nh", "intermediate_size",
                "scale_emb"} <= set(widths)
        assert cfg.kinds == tuple(model_sala.MIXERS[t]
                                  for t in conf["mixer_types"])
        assert cfg.head_dim == conf["head_dim"] == conf["lightning_head_dim"]
        assert cfg.residual_multiplier == conf["scale_depth"] \
            / conf["published"]["num_hidden_layers"] ** 0.5
        assert cfg.logits_scaling == conf["hidden_size"] \
            / conf["dim_model_base"]
        sparse = conf["sparse_config"]
        assert (cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block,
                cfg.sparse_topk, cfg.sparse_init_blocks, cfg.sparse_window,
                cfg.dense_len) == tuple(sparse[k] for k in (
                    "kernel_size", "kernel_stride", "block_size", "topk",
                    "init_blocks", "window_size", "dense_len"))
        from ray_tpu.models import sala
        assert cfg.qk_head_norm and "lm_head" in sala.param_specs(cfg)
    if kind == "train_sparse":
        # the latents' ranks, the head widths and the indexer's sizes are
        # the published keys' (the map above); which layers select, the
        # router's width and the experts and heads held the file's lists
        # and its deployment
        assert {"q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "index_n_heads",
                "index_head_dim", "index_topk"} <= set(widths)
        dep = conf["deployment"]
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["n_routed_experts"],
                                    dep["experts_first"])
        assert cfg.n_heads == dep["heads_held"]
        assert cfg.head_dim == cfg.qk_nope_dim + cfg.qk_rope_dim == cfg.v_dim
        assert cfg.index_full == tuple(
            t == "full" for t in conf["indexer_types"])
        assert cfg.rope_theta == conf["rope_parameters"]["rope_theta"]
        assert cfg.n_mtp == 0 and cfg.router_score == "sigmoid"
        assert cfg.shared_d_ff == conf["n_shared_experts"] * cfg.d_ff
    if kind == "train_latent":
        # the latents' ranks, the three head widths, the leading dense
        # layers and the prediction modules are the published keys' (the
        # map above); the router's width and the experts held the
        # deployment's
        assert {"q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
                "num_nextn_predict_layers"} <= set(widths)
        dep = conf["deployment"]
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["n_routed_experts"],
                                    dep["experts_first"])
        assert cfg.head_dim == cfg.qk_nope_dim + cfg.qk_rope_dim == cfg.v_dim
        assert cfg.router_score == "sigmoid"
        assert cfg.shared_d_ff == conf["n_shared_experts"] * cfg.d_ff
    if kind == "train_mixed":
        # the stated head width, the kinds of layer with their windows and
        # tables are the published keys'; the router's width and the
        # experts held the deployment's
        dep = conf["deployment"]
        assert cfg.n_experts == dep["router_experts"]
        assert cfg.experts_held == (conf["num_experts"],
                                    dep["experts_first"])
        assert cfg.head_dim == conf["head_dim"]
        kinds = {"sliding_attention": "window", "full_attention": "full"}
        assert cfg.layer_kinds == tuple(
            kinds[t] for t in conf["layer_types"][:cfg.n_layers])
        of = dict(cfg.attn_kinds)
        assert of["window"].window == conf["sliding_window"]
        assert of["window"].yarn is None and of["full"].window is None
        rope = conf["rope_parameters"]["full_attention"]
        assert (of["full"].yarn.factor, of["full"].yarn.original) == (
            rope["factor"], rope["original_max_position_embeddings"])
        assert of["full"].rope_theta == rope["rope_theta"]
    for key, value in passed.items():
        assert getattr(cfg, key) == value, (name, key)
    assert cfg.dtype == getattr(jnp, conf["run"]["dtype"])

    # the loss the cell's step differentiates traces with this config at
    # the cell's own batch: shapes only, nothing runs
    mod = sys.modules[type(cfg).__module__]
    mix = cell["mix"]
    params = jax.eval_shape(
        lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
    # a model that predicts further tokens takes as many more ids
    more = 1 + getattr(cfg, "n_mtp", 0)
    tokens = jax.ShapeDtypeStruct((mix["batch"], mix["seq"] + more),
                                  jnp.int32)
    out = jax.eval_shape(lambda p, t: mod.loss_fn(p, {"tokens": t}, cfg),
                         params, tokens)
    loss = out[0] if isinstance(out, tuple) else out
    assert loss.shape == () and loss.dtype == jnp.float32
