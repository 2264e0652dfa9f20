"""From an LFM2 style configuration file to the sizes the program takes:
the published keys of an ``lfm2_moe`` config.json (LFM2-8B-A1B) mapped onto
the field names of ``ray_tpu/models/hybrid.py``'s HybridConfig.

``num_experts`` is the number of experts HELD here (the chip's share: the
file lists the key under ``reduced``); how many the router scores, and
which of them are held, is the file's ``deployment`` group. ``layer_types``
names every layer's operator, ``conv`` (the gated short convolution of
``conv_L_cache`` taps) or ``full_attention``; the first ``num_dense_layers``
layers' feed-forward is a dense SwiGLU of ``intermediate_size``, the others
route over experts of ``moe_intermediate_size``. The config gives no head
width: it is the hidden size over the heads (64), stated to the program.
The two weights no key of the source gives (``bias_update_rate``,
``balance_loss_coef``) are the file's own, listed under ``assumed``. The
yardstick's own arithmetic (``flops_lfm2.py``, ``reference_lfm2.py``) reads
the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "moe_intermediate_size": "d_ff",
    "intermediate_size": "dense_d_ff", "num_dense_layers": "n_dense",
    "num_experts_per_tok": "top_k", "norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "conv_L_cache": "conv_taps", "routed_scaling_factor": "route_scale",
    "norm_topk_prob": "norm_topk", "bias_update_rate": "bias_rate",
    "balance_loss_coef": "router_aux_weight",
}
OPERATORS = {"conv": "conv", "full_attention": "attention"}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "lfm2_moe", "conv_bias": False,
         "use_expert_bias": True}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "layer_types", "deployment",
                           "num_experts") if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"the shortconv kind knows the lfm2_moe block "
                         f"({FIXED}); this configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    types = config["layer_types"]
    if len(types) != out["n_layers"] or set(types) - set(OPERATORS):
        raise ValueError(f"layer_types {types!r} is not {out['n_layers']} "
                         f"of {sorted(OPERATORS)}")
    if out["d_model"] % out["n_heads"]:
        raise ValueError("the hidden size is no multiple of the heads")
    dep = config["deployment"]
    if dep["experts_held"] != config["num_experts"]:
        raise ValueError("deployment.experts_held is not num_experts")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    out["layer_types"] = tuple(OPERATORS[t] for t in types)
    out["head_width"] = out["d_model"] // out["n_heads"]
    out["shared_d_ff"] = 0
    # the most layers one stack holds (0: a whole run of adjacent layers
    # of a kind); the file's own, under ``run``
    out["run_layers"] = config["run"].get("run_layers", 0)
    return out


def hybrid_config(config: dict, **overrides):
    """The program's HybridConfig. Imports jax: call it in the process
    that holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import hybrid

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])),
              rope=True, tied_head=True, qk_head_norm=True,
              router_score="sigmoid", router_z_weight=0.0)
    kw.update(overrides)
    return hybrid.HybridConfig(**kw)
