"""From a Granite-4.0-H style configuration file to the sizes the program
takes: the published keys of a ``granitemoehybrid`` config.json mapped onto
the field names of ``ray_tpu/models/hybrid.py``'s HybridConfig.

``num_local_experts`` is the number of experts HELD here (the chip's share:
the file lists the key under ``reduced``); how many the router scores, and
which of them are held, is the file's ``deployment`` group. ``layer_types``
stays as published and its first ``num_hidden_layers`` entries run. The
yardstick's own arithmetic (``flops_granite.py``, ``reference_granite.py``)
reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "shared_intermediate_size": "shared_d_ff",
    "num_experts_per_tok": "top_k", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "mamba_n_heads": "mamba_heads", "mamba_d_head": "mamba_head_dim",
    "mamba_d_state": "mamba_state", "mamba_d_conv": "mamba_conv",
    "mamba_chunk_size": "mamba_chunk",
    "attention_multiplier": "attn_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "router_aux_loss_coef": "router_aux_weight",
    "router_z_loss_coef": "router_z_weight",
}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "granitemoehybrid", "hidden_act": "silu",
         "position_embedding_type": "nope", "tie_word_embeddings": True,
         "attention_bias": False, "mamba_proj_bias": False,
         "mamba_conv_bias": True, "mamba_n_groups": 1,
         "normalization_function": "rmsnorm"}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "layer_types", "deployment",
                           "num_local_experts") if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"the hybrid kind knows the granitemoehybrid block "
                         f"({FIXED}); this configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    if config["mamba_expand"] * out["d_model"] \
            != out["mamba_heads"] * out["mamba_head_dim"]:
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads x "
                         "mamba_d_head")
    dep = config["deployment"]
    if dep["experts_held"] != config["num_local_experts"]:
        raise ValueError("deployment.experts_held is not num_local_experts")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    out["layer_types"] = tuple(config["layer_types"][:out["n_layers"]])
    if len(out["layer_types"]) != out["n_layers"]:
        raise ValueError("fewer layer_types than num_hidden_layers")
    return out


def hybrid_config(config: dict, **overrides):
    """The program's HybridConfig. Imports jax: call it in the process
    that holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import hybrid

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])))
    kw.update(overrides)
    return hybrid.HybridConfig(**kw)
