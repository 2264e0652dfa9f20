"""From a Command A+ style configuration file to the sizes the program
takes: the published keys of a ``cohere2_moe`` config.json (a PARALLEL
block: one LayerNorm, attention and experts side by side; sliding layers
with GPT-J rotary beside full layers with no position embedding; a sigmoid
router; shared experts averaged; a tied embedding) mapped onto the field
names of ``ray_tpu/models/moe.py``'s MoEConfig.

``num_experts``, ``num_attention_heads`` and ``num_key_value_heads`` are
what is HELD here (the chip's share: the file lists the keys under
``reduced``); how many experts the router scores, and which are held, is
the file's ``deployment`` group. ``intermediate_size`` is the width of one
routed and of one shared expert (the catalog's reading; the file's
``assumed``). ``layer_types`` stays as published and its first
``num_hidden_layers`` entries run; ``sliding_attention`` is the program's
kind ``window`` (``sliding_window`` keys, rotary at ``rope_theta`` on
neighbouring lanes), ``full_attention`` its kind ``full`` (no window, no
table). The yardstick's own arithmetic (``flops_commanda.py``,
``reference_commanda.py``) reads the same dict.
"""

from __future__ import annotations

import itertools

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_width",
    "intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
    "layer_norm_eps": "norm_eps", "max_position_embeddings": "max_seq_len",
    "num_shared_experts": "n_shared", "logit_scale": "logit_scale",
}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "cohere2_moe", "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": True,
         "use_parallel_block": True, "use_qk_norm": False,
         "use_gated_activation": True, "expert_selection_fn": "sigmoid",
         "norm_topk_prob": True, "position_embedding_type": "rope_gptj",
         "rotary_pct": 1, "shared_expert_combination_strategy": "average",
         "first_k_dense_replace": 0, "rms_norm_eps": None, "logit_scale": 1,
         "router_aux_loss_coef": 0.0, "router_z_loss_coef": 0.0}
KIND_OF = {"sliding_attention": "window", "full_attention": "full"}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, *FIXED, "layer_types",
                           "rope_parameters", "sliding_window", "deployment",
                           "num_experts", "run") if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config[k] for k, v in FIXED.items() if config[k] != v}
    if wrong:
        raise ValueError(f"the parallel kind knows the cohere2_moe block "
                         f"({FIXED}); this configuration has {wrong}")
    rope = config["rope_parameters"]
    if rope != {"rope_theta": config["rope_theta"], "rope_type": "default"}:
        raise ValueError(f"rope_parameters {rope}: the parallel kind knows "
                         "plain tables at rope_theta")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    n = out["n_layers"]
    types = config["layer_types"][:n]
    if len(types) != n:
        raise ValueError("fewer layer_types than num_hidden_layers")
    dep = config["deployment"]
    held = {"experts_held": "num_experts", "heads_held": "num_attention_heads",
            "kv_heads_held": "num_key_value_heads"}
    if any(dep[d] != config[k] for d, k in held.items()):
        raise ValueError(f"the deployment's {sorted(held)} are not the "
                         f"file's {sorted(held.values())}")
    out["n_experts"] = dep["router_experts"]
    out["experts_held"] = (dep["experts_held"], dep["experts_first"])
    out["shared_d_ff"] = out["d_ff"]        # one shared expert's width
    out["layer_kinds"] = tuple(KIND_OF[t] for t in types)
    out["run_layers"] = config["run"].get("run_layers", 0)
    out["kinds"] = {
        "window": {"window": config["sliding_window"],
                   "rope_theta": float(config["rope_theta"])},
        "full": {"window": None, "rope_theta": None}}      # no table
    out["kinds"] = {k: out["kinds"][k] for k in sorted(set(out["layer_kinds"]))}
    return out


def stacks(cfg: dict) -> int:
    """How many stacks of layers (and scans) the program makes of them: a
    run of adjacent layers of one kind, cut into ``run_layers`` at most."""
    most = cfg["run_layers"] or cfg["n_layers"]
    return sum(-(-len(list(run)) // most)
               for _, run in itertools.groupby(cfg["layer_kinds"]))


def moe_config(config: dict, **overrides):
    """The program's MoEConfig. Imports jax: call it in the process that
    holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import llama, moe

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])))
    if kw.pop("logit_scale") != 1:
        raise ValueError("a logit_scale other than 1")
    kw["attn_kinds"] = tuple(
        (name, llama.AttentionKind(
            window=of["window"], rope_theta=of["rope_theta"],
            rope=of["rope_theta"] is not None, pairs="neighbours"))
        for name, of in sorted(kw.pop("kinds").items()))
    kw.update(norm="layer", parallel_block=True, tied_head=True,
              router_score="sigmoid", router_bias=False, norm_topk=True,
              shared_combine="average", rope_theta=float(config["rope_theta"]),
              router_aux_weight=config["router_aux_loss_coef"],
              router_z_weight=config["router_z_loss_coef"])
    kw.update(overrides)
    return moe.MoEConfig(**kw)
