"""From a Ling 3.0 style configuration file to the sizes the program takes:
the published keys of a ``bailing_hybrid`` config.json (the language model
of Ling-3.0-flash) mapped onto the field names of
``ray_tpu/models/ling.py``'s LingConfig.

``num_experts`` is the number of experts HELD here (the chip's share: the
file lists the key under ``reduced``); how many the router scores, which
group of them this chip serves and where in it its experts start is the
file's ``deployment`` group (``router_experts``, ``group_held``,
``experts_first``). ``q_lora_rank`` null is no query latent (``q_rank``
0). A KDA half has ``num_attention_heads`` heads of ``head_dim`` keys and
values (``num_kv_heads_for_linear_attn`` 0: keys and values a head of
their own). The two weights no key of the source gives
(``bias_update_rate``, ``balance_loss_coef``) are the file's own, listed
under ``assumed``; the scan's chunk is the op's (``ops/delta_rule.py``). The
yardstick's own arithmetic (``flops_ling.py``, ``reference_ling.py``) reads
the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "dense_d_ff",
    "moe_intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "max_position_embeddings": "max_seq_len", "kv_lora_rank": "kv_rank",
    "qk_nope_head_dim": "qk_nope_dim", "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_dim", "routed_scaling_factor": "route_scale",
    "norm_topk_prob": "norm_topk", "first_k_dense_replace": "n_dense",
    "n_group": "n_group", "topk_group": "topk_group",
    "layer_group_size": "layer_group_size", "head_dim": "kda_head_dim",
    "short_conv_kernel_size": "conv_taps",
    "kda_lower_bound": "kda_lower_bound",
    "bias_update_rate": "bias_rate", "balance_loss_coef": "router_aux_weight",
}
# what the program's block is, and the file has to say so
FIXED = {"q_lora_rank": None, "score_function": "sigmoid",
         "moe_router_enable_expert_bias": True, "kda_safe_gate": True,
         "no_kda_lora": True, "use_kda_lora": False, "linear_silu": True,
         "use_qk_norm": True, "group_norm_size": 1,
         "gated_attention_proj_granularity_type": "head_wise",
         "num_kv_heads_for_linear_attn": 0, "use_mla_nope": False,
         "use_nGPT": False, "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False, "mtp_use_kda": False}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "deployment", "num_experts",
                           "moe_shared_expert_intermediate_size", "rotary_dim")
               if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k, "absent") for k, v in FIXED.items()
             if config.get(k, "absent") != v}
    if wrong:
        raise ValueError(f"the kda kind knows the bailing_hybrid block "
                         f"({FIXED}); this configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    if config["rotary_dim"] != out["qk_rope_dim"]:
        raise ValueError("rotary_dim is not the latent half's rope lanes")
    dep = config["deployment"]
    if dep["experts_held"] != config["num_experts"]:
        raise ValueError("deployment.experts_held is not num_experts")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    per_group = out["n_experts"] // out["n_group"]
    first, held = dep["experts_first"], dep["experts_held"]
    if first // per_group != dep["group_held"] \
            or (first + held - 1) // per_group != dep["group_held"]:
        raise ValueError(f"experts {first}..{first + held - 1} do not lie in "
                         f"group {dep['group_held']} of {per_group}")
    out["group_held"] = dep["group_held"]
    out["shared_d_ff"] = config["moe_shared_expert_intermediate_size"]
    out["q_rank"] = 0
    run = config["run"]
    # the most layers one stack holds (0: a whole run of adjacent layers
    # of a kind); the file's own, under ``run``
    out["run_layers"] = run.get("run_layers", 0)
    # each layer's kind, as the program derives it: for the arithmetic
    out["kinds"] = tuple(
        ("mla" if (i + 1) % out["layer_group_size"] == 0 else "kda")
        + (".dense" if i < out["n_dense"] else "")
        for i in range(out["n_layers"]))
    return out


def ling_config(config: dict, **overrides):
    """The program's LingConfig. Imports jax: call it in the process that
    holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import ling

    run = config["run"]
    kw = {k: v for k, v in sizes(config).items()
          if k not in ("kinds", "group_held")}
    kw.update(dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])),
              router_score="sigmoid", router_z_weight=0.0)
    kw.update(overrides)
    return ling.LingConfig(**kw)
