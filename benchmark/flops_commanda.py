"""Operations and bytes of the parallel-block model with shared experts
(Command A+), from shapes alone (the yardstick's arithmetic beside
``flops_mellum.py``, whose count of the pairs under a kind's mask, of a
flash call and of a grouped matmul it takes as they are; nothing here
reads the program). ``cfg`` is ``model_commanda.sizes`` of a configuration
file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): the four attention projections of the heads
HELD here, the router over ALL experts, the routed experts held here that
an even router would send it to (``top_k x held / n_experts`` of them),
every shared expert (``n_shared`` SwiGLUs of ``shared_d_ff``), the tied
head over the vocabulary held; plus attention UNDER THE MASK of each
layer's kind (``flops_mellum.pairs``), counted the same whatever the
kernel skips. Recomputation under remat counts nothing, nor do the norm,
the rotary, the sort and the gathers.
"""

from __future__ import annotations

from benchmark import flops_mellum
from benchmark.flops_mellum import (attention_unit, flash_call,  # noqa: F401
                                    grouped_matmul_call, held_per_token,
                                    layers_of, pairs)


def shared_params(cfg: dict) -> int:
    """Matmul parameters of one layer's shared experts."""
    return cfg["n_shared"] * 3 * cfg["d_model"] * cfg["shared_d_ff"]


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    out = flops_mellum.matmul_params_per_token(cfg)
    out["shared experts"] = cfg["n_layers"] * shared_params(cfg)
    return out


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    for kind, n in layers_of(cfg).items():
        out[f"attention, {kind}"] = 2.0 * attention_unit(cfg, seq, kind) * n \
            / seq
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def total_params(cfg: dict) -> int:
    """The parameters held here: a layer's attention, ONE norm, the
    router, the routed experts held and the shared experts; the tied
    table once, and the final norm."""
    d, hd = cfg["d_model"], cfg["head_width"]
    layer = (2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd
             + d + d * cfg["n_experts"]
             + cfg["experts_held"][0] * 3 * d * cfg["d_ff"]
             + shared_params(cfg))
    return cfg["n_layers"] * layer + cfg["vocab_size"] * d + d


def shared_step(cfg: dict, tokens: int) -> dict:
    """What the shared experts' mathematics needs of one step of
    ``tokens`` tokens, forward and backward, all layers: 6 operations a
    parameter and token (the checkpoint's replay is NOT counted, so the
    count is the same whatever is recomputed or joined); bytes: every
    weight read twice and its gradient written once, the rows in and out."""
    params = cfg["n_layers"] * shared_params(cfg)
    rows = cfg["n_layers"] * tokens * cfg["d_model"]
    return {"ops": 6.0 * params * tokens,
            "bytes": 2.0 * (3 * params + 4 * rows)}
