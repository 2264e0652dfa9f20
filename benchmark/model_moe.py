"""From an OLMoE-style configuration file to the sizes the program takes:
``model.py``'s mapping of the published keys, and the expert layer's.

``sizes`` gives MoEConfig field names (``d_ff`` is ONE expert's width, as
``intermediate_size`` is in an ``olmoe`` config.json); the yardstick's own
arithmetic (``flops_moe.py``, ``reference_olmoe.py``) reads the same dict.
The two router loss weights are the file's ``router_aux_loss_coef`` and
``router_z_loss_coef`` (listed under its ``assumed``).
"""

from __future__ import annotations

from benchmark import model

HF_TO_FIELD = {"num_experts": "n_experts", "num_experts_per_tok": "top_k",
               "norm_topk_prob": "norm_topk",
               "router_aux_loss_coef": "router_aux_weight",
               "router_z_loss_coef": "router_z_weight"}


def sizes(config: dict) -> dict:
    missing = [k for k in HF_TO_FIELD if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    if config.get("model_type") != "olmoe":
        raise ValueError("the expert kind knows the olmoe block (q/k norm, "
                         "no shared expert, no bias); this configuration "
                         f"is a {config.get('model_type')!r}")
    if config.get("attention_bias") or config.get("clip_qkv") is not None:
        raise ValueError("attention_bias and clip_qkv are not built")
    out = model.sizes(config)
    out.update({f: config[k] for k, f in HF_TO_FIELD.items()})
    out["qk_norm"] = True
    return out


def moe_config(config: dict, **overrides):
    """The program's MoEConfig. Imports jax: call it in the process that
    holds the chip."""
    import jax.numpy as jnp

    from ray_tpu.models import moe

    run = config["run"]
    kw = dict(sizes(config), dtype=getattr(jnp, run["dtype"]),
              param_dtype=getattr(jnp, run.get("param_dtype", run["dtype"])))
    kw.update(overrides)
    return moe.MoEConfig(**kw)
