"""From a configuration file to the sizes the program takes.

A configuration file holds the model's published ``config.json`` keys at
its top level, as run (``reduced`` names the keys that differ from the
source), and a ``run`` group with what is not the model's: precision and
the recipe. ``sizes`` maps the published names onto the field names of the
program's ``LlamaConfig``; the yardstick's own arithmetic (``flops.py``,
``reference.py``) reads the same dict.
"""

from __future__ import annotations

HF_TO_FIELD = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "sliding_window": "sliding_window",
}


def sizes(config: dict) -> dict:
    """LlamaConfig field names -> values, from the published keys."""
    missing = [k for k in HF_TO_FIELD if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    if config.get("tie_word_embeddings") or config.get("hidden_act") != "silu":
        raise ValueError("the program's llama block has an untied head and "
                         "SwiGLU; this configuration asks for something else")
    out = {f: config[k] for k, f in HF_TO_FIELD.items()}
    if config.get("head_dim", out["d_model"] // out["n_heads"]) \
            != out["d_model"] // out["n_heads"]:
        raise ValueError("head_dim is not hidden_size / num_attention_heads")
    return out


def llama_config(config: dict, **overrides):
    """The program's LlamaConfig. Imports jax: call it in the process
    that holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    run = config["run"]
    dt = getattr(jnp, run["dtype"])
    kw = dict(sizes(config), dtype=dt, param_dtype=getattr(
        jnp, run.get("param_dtype", run["dtype"])))
    for k in ("attn_impl", "remat", "remat_policy", "f32_logits",
              "fused_matmuls", "scan_layers"):
        if k in run:
            kw[k] = run[k]
    kw.update(overrides)
    return llama.LlamaConfig(**kw)
