"""From a GLM-5.2 style configuration file to the sizes the program takes:
the published keys of a ``glm_moe_dsa`` config.json (DeepSeek-V3's block
with DeepSeek-V3.2's learned sparse attention over it) mapped onto the field
names of ``ray_tpu/models/latent.py``'s LatentConfig.

``n_routed_experts`` and ``num_attention_heads`` are what is HELD here (the
chip's share: the file lists the keys under ``reduced``); how many experts
the router scores is the file's ``deployment`` group. Which layers select
for themselves is the file's ``indexer_types`` (one entry a kept layer),
which feed-forward each has its ``mlp_layer_types``. The weights no key of
the source gives (``bias_update_rate``, ``balance_loss_coef``,
``index_loss_weight``) are the file's own, listed under ``assumed``. The
yardstick's own arithmetic (``flops_glm52.py``, ``reference_glm52.py``)
reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "dense_d_ff",
    "moe_intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "q_lora_rank": "q_rank", "kv_lora_rank": "kv_rank",
    "qk_nope_head_dim": "qk_nope_dim", "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_dim", "routed_scaling_factor": "route_scale",
    "norm_topk_prob": "norm_topk", "first_k_dense_replace": "n_dense",
    "num_nextn_predict_layers": "n_mtp",
    "index_n_heads": "index_heads", "index_head_dim": "index_dim",
    "index_topk": "index_topk",
    "bias_update_rate": "bias_rate", "balance_loss_coef": "router_aux_weight",
    "index_loss_weight": "index_loss_weight",
}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "glm_moe_dsa", "hidden_act": "silu",
         "attention_bias": False, "topk_method": "noaux_tc", "n_group": 1,
         "topk_group": 1, "scoring_func": "sigmoid", "rope_interleave": True,
         "indexer_rope_interleave": True, "moe_layer_freq": 1,
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "deployment", "n_routed_experts",
                           "n_shared_experts", "rope_parameters",
                           "indexer_types", "mlp_layer_types")
               if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"the sparse-latent kind knows the glm_moe_dsa block "
                         f"without a prediction module ({FIXED}); this "
                         f"configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    rope = config["rope_parameters"]
    if rope.get("rope_type") != "default":
        raise ValueError(f"rope_parameters {rope}: only the default rotary")
    out["rope_theta"] = rope["rope_theta"]
    n, types, mlps = (out["n_layers"], config["indexer_types"],
                      config["mlp_layer_types"])
    if len(types) != n or set(types) - {"full", "shared"}:
        raise ValueError(f"indexer_types {types} for {n} layers")
    if mlps != ["dense"] * out["n_dense"] + ["sparse"] * (n - out["n_dense"]):
        raise ValueError(f"mlp_layer_types {mlps} is not first_k_dense_replace "
                         f"{out['n_dense']} dense layers, then sparse ones")
    if config["qk_head_dim"] != out["qk_nope_dim"] + out["qk_rope_dim"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    out["index_full"] = tuple(t == "full" for t in types)
    dep = config["deployment"]
    if dep["experts_held"] != config["n_routed_experts"] \
            or dep["heads_held"] != config["num_attention_heads"]:
        raise ValueError("deployment.experts_held / heads_held are not "
                         "n_routed_experts / num_attention_heads")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    out["shared_d_ff"] = config["n_shared_experts"] * out["d_ff"]
    out["run_layers"] = config["run"].get("run_layers", 0)
    return out


def stacks(cfg: dict) -> list:
    """[(kind, layers), ...]: the stacks of layers the program scans, as
    ``latent.layer_runs`` makes them (a run of adjacent layers of one kind
    cut into ``run_layers`` at most)."""
    import itertools

    kinds = [("dense" if i < cfg["n_dense"] else "sparse")
             + (".full" if full else ".shared")
             for i, full in enumerate(cfg["index_full"])]
    most = cfg["run_layers"] or cfg["n_layers"]
    return [(kind, min(most, n - at))
            for kind, n in ((k, len(list(g)))
                            for k, g in itertools.groupby(kinds))
            for at in range(0, n, most)]


def latent_config(config: dict, **overrides):
    """The program's LatentConfig. Imports jax: call it in the process
    that holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import latent

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])),
              router_score="sigmoid", router_z_weight=0.0)
    kw.update(overrides)
    return latent.LatentConfig(**kw)
