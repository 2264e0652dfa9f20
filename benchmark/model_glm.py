"""From a GLM-4.7-Flash style configuration file to the sizes the program
takes: the published keys of a ``glm4_moe_lite`` config.json (DeepSeek-V3's
block) mapped onto the field names of ``ray_tpu/models/latent.py``'s
LatentConfig.

``n_routed_experts`` is the number of experts HELD here (the chip's share:
the file lists the key under ``reduced``); how many the router scores, and
which of them are held, is the file's ``deployment`` group. The three
weights no key of the source gives (``bias_update_rate``,
``mtp_loss_weight``, ``balance_loss_coef``) are the file's own, listed under
``assumed``. The yardstick's own arithmetic (``flops_glm.py``,
``reference_glm.py``) reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "dense_d_ff",
    "moe_intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len",
    "q_lora_rank": "q_rank", "kv_lora_rank": "kv_rank",
    "qk_nope_head_dim": "qk_nope_dim", "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_dim", "routed_scaling_factor": "route_scale",
    "norm_topk_prob": "norm_topk", "first_k_dense_replace": "n_dense",
    "num_nextn_predict_layers": "n_mtp",
    "bias_update_rate": "bias_rate", "mtp_loss_weight": "mtp_weight",
    "balance_loss_coef": "router_aux_weight",
}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "glm4_moe_lite", "hidden_act": "silu",
         "attention_bias": False, "topk_method": "noaux_tc", "n_group": 1,
         "topk_group": 1, "partial_rotary_factor": 1, "rope_scaling": None,
         "tie_word_embeddings": False}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "deployment", "n_routed_experts",
                           "n_shared_experts") if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"the latent kind knows the glm4_moe_lite block "
                         f"({FIXED}); this configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    dep = config["deployment"]
    if dep["experts_held"] != config["n_routed_experts"]:
        raise ValueError("deployment.experts_held is not n_routed_experts")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    out["shared_d_ff"] = config["n_shared_experts"] * out["d_ff"]
    return out


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
