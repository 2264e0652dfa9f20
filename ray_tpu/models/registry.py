"""Model registry: name -> (config presets, init/forward/loss fns).

Gives Train/Serve/bench one switchboard:
    cfg, mod = registry.get("llama", "tiny")
"""

from __future__ import annotations

import importlib
from typing import Any, Tuple

_FAMILIES = {
    "llama": "ray_tpu.models.llama",
    "gpt2": "ray_tpu.models.gpt2",
    "moe": "ray_tpu.models.moe",
    # Cohere's model_type: moe.py presets "command-a-plus", "tiny-commanda"
    "cohere2_moe": "ray_tpu.models.moe",
    "hybrid": "ray_tpu.models.hybrid",
    # Nemotron-H's model_type: hybrid.py preset "tiny-nemotron" (blocks of
    # one half, grouped mixers, two-matrix squared-ReLU experts)
    "nemotron_h": "ray_tpu.models.hybrid",
    # LFM2's model_type: hybrid.py preset "tiny-lfm2" (gated short
    # convolutions beside attention with a norm a head, leading dense
    # layers, sigmoid-routed experts with no shared one)
    "lfm2_moe": "ray_tpu.models.hybrid",
    "latent": "ray_tpu.models.latent",
    # GLM-5.2's model_type: latent.py presets "glm-5.2-ep32-l5", "tiny-glm52"
    "glm_moe_dsa": "ray_tpu.models.latent",
    # MiniCPM-SALA's model_type: sala.py preset "tiny" (block-set sparse
    # attention layers beside Lightning linear-attention layers)
    "minicpm_sala": "ray_tpu.models.sala",
    # Ling 3.0's model_type: ling.py preset "tiny" (Kimi-Delta-Attention
    # layers beside latent attention, group-limited sigmoid-routed experts)
    "bailing_hybrid": "ray_tpu.models.ling",
    # Solar Open2's model_type: solar.py preset "tiny" (Kimi-Delta-Attention
    # layers with an unbounded gate beside gated grouped-query attention
    # with no position table, sigmoid-routed experts in every layer)
    "solar_open2": "ray_tpu.models.solar",
    # Falcon-H1's model_type: falcon.py preset "tiny" (a block of two first
    # halves: a Mamba-2 mixer and grouped-query attention read one norm
    # side by side, each under its muP multipliers, ahead of a dense SwiGLU)
    "falcon_h1": "ray_tpu.models.falcon",
    "vit": "ray_tpu.models.vit",
}


def get(family: str, preset: str) -> Tuple[Any, Any]:
    """Returns (config, module). Module exposes init_params/forward/loss_fn/
    param_specs."""
    if family not in _FAMILIES:
        raise KeyError(f"unknown model family {family!r}; have {sorted(_FAMILIES)}")
    mod = importlib.import_module(_FAMILIES[family])
    presets = getattr(mod, "PRESETS")
    if preset not in presets:
        raise KeyError(f"unknown {family} preset {preset!r}; have {sorted(presets)}")
    return presets[preset], mod


def families():
    return sorted(_FAMILIES)
