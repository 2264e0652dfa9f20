"""From a Nemotron-H style configuration file to the sizes the program
takes: the published keys of a ``nemotron_h`` config.json (Nemotron 3 Nano
30B-A3B) mapped onto the field names of ``ray_tpu/models/hybrid.py``'s
HybridConfig.

``n_routed_experts`` is the number of experts HELD here (the chip's share:
the file lists the key under ``reduced``); how many the router scores, and
which of them are held, is the file's ``deployment`` group.
``hybrid_override_pattern`` holds the letters of the blocks that run, one a
block: ``M`` a Mamba-2 mixer, ``E`` an expert layer, ``*`` an attention
layer, each ALONE in its block. The two weights no key of the source gives
(``bias_update_rate``, ``balance_loss_coef``) are the file's own, listed
under ``assumed``. The yardstick's own arithmetic (``flops_nemotron.py``,
``reference_nemotron.py``) reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_width",
    "moe_intermediate_size": "d_ff",
    "moe_shared_expert_intermediate_size": "shared_d_ff",
    "num_experts_per_tok": "top_k", "norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "mamba_num_heads": "mamba_heads", "mamba_head_dim": "mamba_head_dim",
    "ssm_state_size": "mamba_state", "n_groups": "mamba_groups",
    "conv_kernel": "mamba_conv", "chunk_size": "mamba_chunk",
    "routed_scaling_factor": "route_scale", "norm_topk_prob": "norm_topk",
    "bias_update_rate": "bias_rate", "balance_loss_coef": "router_aux_weight",
}
LETTERS = {"M": "mamba", "E": "experts", "*": "attention"}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
         "mamba_hidden_act": "silu", "attention_bias": False,
         "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
         "use_conv_bias": True, "n_group": 1, "topk_group": 1,
         "n_shared_experts": 1, "tie_word_embeddings": False,
         "layer_norm_epsilon": 1e-05, "sliding_window": None}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "hybrid_override_pattern",
                           "deployment", "n_routed_experts")
               if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"the alternating kind knows the nemotron_h block "
                         f"({FIXED}); this configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != out["n_layers"] or set(pattern) - set(LETTERS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} is not "
                         f"{out['n_layers']} letters of {sorted(LETTERS)}")
    dep = config["deployment"]
    if dep["experts_held"] != config["n_routed_experts"]:
        raise ValueError("deployment.experts_held is not n_routed_experts")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    out["layer_types"] = tuple(LETTERS[c] for c in pattern)
    return out


def hybrid_config(config: dict, **overrides):
    """The program's HybridConfig. Imports jax: call it in the process
    that holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import hybrid

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])),
              one_half=True, tied_head=False,
              expert_act="relu2", router_score="sigmoid",
              router_z_weight=0.0)
    kw.update(overrides)
    return hybrid.HybridConfig(**kw)
