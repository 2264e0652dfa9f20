"""From a Mellum2 style configuration file to the sizes the program takes:
the published keys of a ``mellum`` config.json (a Qwen3-MoE style block
whose attention layers are of two kinds, ``layer_types`` and
``rope_parameters``) mapped onto the field names of ``ray_tpu/models/
moe.py``'s MoEConfig.

``num_experts`` is the number of experts HELD here (the chip's share: the
file lists the key under ``reduced``); how many the router scores, and
which of them are held, is the file's ``deployment`` group. ``layer_types``
stays as published and its first ``num_hidden_layers`` entries run;
``sliding_attention`` is the program's kind ``window`` (``sliding_window``
keys, the table of ``rope_parameters.sliding_attention``),
``full_attention`` its kind ``full`` (no window, the table of
``rope_parameters.full_attention``). The two router-loss weights are the
file's own, listed under ``assumed``. The yardstick's own arithmetic
(``flops_mellum.py``, ``reference_mellum.py``) reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_width",
    "moe_intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
    "norm_topk_prob": "norm_topk", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "router_aux_loss_coef": "router_aux_weight",
    "router_z_loss_coef": "router_z_weight",
}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "mellum", "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": False,
         "use_sliding_window": True}
KIND_OF = {"sliding_attention": "window", "full_attention": "full"}
ROPE_KEYS = {"default": {"rope_type", "rope_theta"},
             "yarn": {"rope_type", "rope_theta", "factor",
                      "original_max_position_embeddings", "beta_fast",
                      "beta_slow", "attention_factor"}}


def _kind(published: str, config: dict) -> dict:
    """One kind of layer as the reference takes it: its window, its
    table's theta and, under YaRN, the stretch."""
    rope = config["rope_parameters"][published]
    if set(rope) != ROPE_KEYS.get(rope["rope_type"]):
        raise ValueError(f"rope_parameters.{published}: {sorted(rope)} is not "
                         f"what the mixed kind knows ({ROPE_KEYS})")
    yarn = None
    if rope["rope_type"] == "yarn":
        yarn = {"factor": float(rope["factor"]),
                "original": rope["original_max_position_embeddings"],
                "beta_fast": float(rope["beta_fast"]),
                "beta_slow": float(rope["beta_slow"]),
                "attention_factor": rope["attention_factor"]}
    return {"window": config["sliding_window"]
            if published == "sliding_attention" else None,
            "rope_theta": float(rope["rope_theta"]), "yarn": yarn}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "layer_types", "mlp_layer_types",
                           "rope_parameters", "sliding_window", "deployment",
                           "num_experts") if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"the mixed kind knows the mellum block ({FIXED}); "
                         f"this configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    n = out["n_layers"]
    types = config["layer_types"][:n]
    if len(types) != n or set(config["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("fewer layer_types than num_hidden_layers, or a "
                         "layer whose feed-forward is not sparse")
    dep = config["deployment"]
    if dep["experts_held"] != config["num_experts"]:
        raise ValueError("deployment.experts_held is not num_experts")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    out["layer_kinds"] = tuple(KIND_OF[t] for t in types)
    out["kinds"] = {KIND_OF[t]: _kind(t, config) for t in sorted(set(types))}
    return out


def moe_config(config: dict, **overrides):
    """The program's MoEConfig. Imports jax: call it in the process that
    holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import llama, moe

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])))
    kw["attn_kinds"] = tuple(
        (name, llama.AttentionKind(
            window=of["window"], rope_theta=of["rope_theta"],
            yarn=of["yarn"] and llama.Yarn(**of["yarn"])))
        for name, of in sorted(kw.pop("kinds").items()))
    kw.update(overrides)
    return moe.MoEConfig(**kw)
