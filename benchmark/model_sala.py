"""From a MiniCPM-SALA style configuration file to the sizes the program
takes: the published keys of a ``minicpm_sala`` config.json mapped onto the
field names of ``ray_tpu/models/sala.py``'s SalaConfig.

``mixer_types`` holds one name a layer that runs (``minicpm4``: a sparse
layer; ``lightning-attn``: a lightning layer). The three multipliers are
MiniCPM's: the embedding times ``scale_emb``, every half's output times
``scale_depth / sqrt(the PUBLISHED depth)`` (the file's
``published.num_hidden_layers``, kept under a cut of the depth), the normed
stream over ``hidden_size / dim_model_base`` before the head. The sizes the
source's config does not give (the selection's seven, MiniCPM4's
``sparse_config``) are the file's ``sparse_config`` group, listed under
``assumed``. The yardstick's own arithmetic (``flops_sala.py``,
``reference_sala.py``) reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_width",
    "intermediate_size": "d_ff", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "max_position_embeddings": "max_seq_len",
    "lightning_nh": "lightning_heads", "scale_emb": "embedding_multiplier",
}
SPARSE_TO_FIELD = {
    "kernel_size": "sparse_kernel", "kernel_stride": "sparse_stride",
    "block_size": "sparse_block", "topk": "sparse_topk",
    "init_blocks": "sparse_init_blocks", "window_size": "sparse_window",
    "dense_len": "dense_len",
}
MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
# what the program's block is, and the file has to say so
FIXED = {"model_type": "minicpm_sala", "hidden_act": "silu",
         "attention_bias": False, "attn_use_rope": False, "qk_norm": True,
         "lightning_use_rope": True, "lightning_scale": "1/sqrt(d)",
         "use_output_gate": True, "use_output_norm": True,
         "attn_use_output_gate": True, "tie_word_embeddings": False}


def sizes(config: dict) -> dict:
    missing = [k for k in (*HF_TO_FIELD, "mixer_types", "sparse_config",
                           "scale_depth", "dim_model_base", "published",
                           "lightning_head_dim", "lightning_nkv")
               if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    wrong = {k: config.get(k) for k, v in FIXED.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"the block-set kind knows the minicpm_sala block "
                         f"({FIXED}); this configuration has {wrong}")
    if config["lightning_head_dim"] != config["head_dim"] \
            or config["lightning_nkv"] != config["lightning_nh"]:
        raise ValueError("a lightning layer here has heads of the sparse "
                         "layers' width with keys and values of their own")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    out.update({f: config["sparse_config"][k]
                for k, f in SPARSE_TO_FIELD.items()})
    kinds = config["mixer_types"]
    if len(kinds) != out["n_layers"] or set(kinds) - set(MIXERS):
        raise ValueError(f"mixer_types {kinds!r} is not {out['n_layers']} of "
                         f"{sorted(MIXERS)}")
    out["layer_types"] = tuple(MIXERS[k] for k in kinds)
    depth = config["published"].get("num_hidden_layers", out["n_layers"])
    out["residual_multiplier"] = config["scale_depth"] / depth ** 0.5
    out["logits_scaling"] = config["hidden_size"] / config["dim_model_base"]
    return out


def sala_config(config: dict, **overrides):
    """The program's SalaConfig. Imports jax: call it in the process that
    holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import sala

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])))
    kw.update(overrides)
    return sala.SalaConfig(**kw)
