"""From a Falcon-H1 style configuration file to the sizes the program
takes: the published keys of a ``falcon_h1`` config.json mapped onto the
field names of ``ray_tpu/models/falcon.py``'s FalconConfig.

The mixer has ``mamba_n_heads`` heads of ``mamba_d_head`` (their product is
``mamba_d_ssm``, which is NOT ``mamba_expand`` x ``hidden_size``), a state
of ``mamba_d_state`` in ``mamba_n_groups`` groups behind ``mamba_d_conv``
taps, scanned ``mamba_chunk_size`` steps a chunk; the attention half
``num_attention_heads`` over ``num_key_value_heads`` heads of ``head_dim``.
The fourteen multipliers keep their published names. ``mlp_expansion_factor``,
``mamba_expand``, ``num_logits_to_keep`` and ``max_position_embeddings`` past
the run's sequence are keys of no program here. The most layers one stack
holds is the file's own (``run.run_layers``). The yardstick's own arithmetic
(``flops_falconh1.py``, ``reference_falconh1.py``) reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_width",
    "intermediate_size": "d_ff", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "max_position_embeddings": "max_seq_len",
    "mamba_n_heads": "mamba_heads", "mamba_d_head": "mamba_head_dim",
    "mamba_d_state": "mamba_state", "mamba_n_groups": "mamba_groups",
    "mamba_d_conv": "mamba_conv", "mamba_chunk_size": "mamba_chunk",
    "embedding_multiplier": "embedding_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "key_multiplier": "key_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "falcon_h1", "hidden_act": "silu",
         "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
         "mamba_proj_bias": False, "mamba_conv_bias": True,
         "mamba_rms_norm": True, "mamba_norm_before_gate": False,
         "mamba_use_mlp": True, "attn_layer_indices": None,
         "rope_scaling": None, "tie_word_embeddings": False}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, *FIXED, "ssm_multipliers",
                           "mlp_multipliers", "mamba_d_ssm", "run")
               if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config[k] for k, v in FIXED.items() if config[k] != v}
    if wrong:
        raise ValueError(f"the falconh1 kind knows the falcon_h1 block "
                         f"({FIXED}); this configuration has {wrong}")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    if out["mamba_heads"] * out["mamba_head_dim"] != config["mamba_d_ssm"]:
        raise ValueError(f"mamba_d_ssm {config['mamba_d_ssm']} is not "
                         "mamba_n_heads x mamba_d_head")
    if len(config["ssm_multipliers"]) != 5 \
            or len(config["mlp_multipliers"]) != 2:
        raise ValueError("ssm_multipliers has five values (z, x, B, C, dt), "
                         "mlp_multipliers two (gate, down)")
    out["ssm_multipliers"] = tuple(config["ssm_multipliers"])
    out["mlp_multipliers"] = tuple(config["mlp_multipliers"])
    out["run_layers"] = config["run"].get("run_layers", 0)
    # 100000000000 is no int32: the rotary tables take theta as a float
    out["rope_theta"] = float(out["rope_theta"])
    return out


def falcon_config(config: dict, **overrides):
    """The program's FalconConfig. Imports jax: call it in the process that
    holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import falcon

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])))
    kw.update(overrides)
    return falcon.FalconConfig(**kw)
