"""From a Solar Open2 style configuration file to the sizes the program
takes: the published keys of a ``solar_open2`` config.json (the language
model of Solar-Open2-250B) mapped onto the field names of
``ray_tpu/models/solar.py``'s SolarConfig.

``n_routed_experts`` is the number of experts HELD here (the chip's share:
the file lists the key under ``reduced``); how many the router scores and
where this chip's experts start is the file's ``deployment`` group
(``router_experts``, ``experts_first``). A KDA half has
``linear_attn_config.num_heads`` heads of ``linear_attn_config.head_dim``
keys and values (``num_kv_heads`` null: keys and values a head of their
own) behind ``short_conv_kernel_size`` taps; a grouped-query half
``num_attention_heads`` over ``num_key_value_heads`` heads of ``head_dim``;
layer l is grouped-query iff it is in ``gqa_layers``. The shared expert is
``n_shared_experts`` x ``moe_intermediate_size`` wide. The three numbers no
key of the source gives (``kda_gate_rank``, ``bias_update_rate``,
``balance_loss_coef``) are the file's own, listed under ``assumed``; the
scan's chunk is the op's (``ops/delta_rule.py``). ``intermediate_size``,
``rope_theta`` and ``partial_rotary_factor`` are keys of no program: the
model has no dense layer and no position table. The yardstick's own
arithmetic (``flops_solar.py``, ``reference_solar.py``) reads the same
dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_width",
    "moe_intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
    "rms_norm_eps": "norm_eps", "max_position_embeddings": "max_seq_len",
    "routed_scaling_factor": "route_scale", "norm_topk_prob": "norm_topk",
    "kda_gate_rank": "gate_rank", "bias_update_rate": "bias_rate",
    "balance_loss_coef": "router_aux_weight",
}
LINEAR_TO_FIELD = {"num_heads": "kda_heads", "head_dim": "kda_head_dim",
                   "short_conv_kernel_size": "conv_taps"}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "solar_open2", "use_rope": False,
         "use_gqa_gate": True, "kda_use_full_proj": False,
         "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
         "tie_word_embeddings": False, "n_shared_experts": 1}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "deployment", "n_routed_experts",
                           "linear_attn_config", "gqa_layers", "run")
               if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k, "absent") for k, v in FIXED.items()
             if config.get(k, "absent") != v}
    if wrong:
        raise ValueError(f"the solar kind knows the solar_open2 block "
                         f"({FIXED}); this configuration has {wrong}")
    linear = config["linear_attn_config"]
    if linear.get("num_kv_heads") is not None:
        raise ValueError("linear_attn_config.num_kv_heads "
                         f"{linear['num_kv_heads']}: the KDA half has keys "
                         "and values a head of their own (null)")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    out.update({f: linear[k] for k, f in LINEAR_TO_FIELD.items()})
    dep = config["deployment"]
    if dep["experts_held"] != config["n_routed_experts"]:
        raise ValueError("deployment.experts_held is not n_routed_experts")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    if dep["experts_first"] + dep["experts_held"] > out["n_experts"]:
        raise ValueError("the held experts lie outside the router's")
    out["shared_d_ff"] = config["n_shared_experts"] \
        * config["moe_intermediate_size"]
    out["gqa_layers"] = tuple(l for l in config["gqa_layers"]
                              if l < out["n_layers"])
    # the most layers one stack holds (0: a whole run of adjacent layers
    # of a kind); the file's own, under ``run``
    out["run_layers"] = config["run"].get("run_layers", 0)
    # each layer's kind, as the program derives it: for the arithmetic
    out["kinds"] = tuple("gqa" if i in out["gqa_layers"] else "kda"
                         for i in range(out["n_layers"]))
    if out["kinds"][:2] != ("gqa", "kda"):
        raise ValueError(f"layers {out['kinds']}: the kind reads the least g "
                         "and the scan's inputs of layer 1, a KDA layer "
                         "behind a grouped-query one")
    return out


def solar_config(config: dict, **overrides):
    """The program's SolarConfig. Imports jax: call it in the process that
    holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import solar

    run = config["run"]
    kw = {k: v for k, v in sizes(config).items() if k != "kinds"}
    kw.update(dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])))
    kw.update(overrides)
    return solar.SolarConfig(**kw)
