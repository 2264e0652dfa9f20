"""Operations and bytes of the expert model, from shapes alone (the
yardstick's arithmetic beside ``flops.py``; nothing here reads the
program). ``cfg`` is ``model_moe.sizes`` of a configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): the attention projections, the router, K of
the E experts, the head; plus causal attention. Recomputation under remat
counts nothing, nor do the sort, the gathers and the weighted sum.
"""

from __future__ import annotations

from benchmark import flops


def active_matmul_params(cfg: dict) -> int:
    """Matmul parameters one token passes through."""
    d, h, kv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    flops.head_dim(cfg))
    layer = (d * h * hd + 2 * d * kv * hd + h * hd * d       # q, k, v, o
             + d * cfg["n_experts"]                          # router
             + cfg["top_k"] * 3 * d * cfg["d_ff"])           # K experts
    return cfg["n_layers"] * layer + d * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    d, h, kv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    flops.head_dim(cfg))
    layer = (d * h * hd + 2 * d * kv * hd + h * hd * d + d * cfg["n_experts"]
             + cfg["n_experts"] * 3 * d * cfg["d_ff"]
             + 2 * d + (h + kv) * hd)                        # four norms
    return cfg["n_layers"] * layer + 2 * d * cfg["vocab_size"] + d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    attn = 6.0 * flops.causal_attention_unit(cfg, seq) * cfg["n_layers"] / seq
    return 6.0 * active_matmul_params(cfg) + attn


def grouped_matmul_call(rows: int, k: int, n: int, experts: int,
                        dtype_bytes: int = 2) -> dict:
    """One grouped matmul over ``rows`` rows grouped by expert: the
    forward ([rows, k] x [E, k, n] -> [rows, n]), the gradient of its
    input (the same product with k and n exchanged) or the gradient of
    its weights ([rows, k]^T [rows, n] by group -> [E, k, n]). Each is
    2 x rows x k x n operations, and at the least reads or writes every
    row of the two row-wide arrays and all E matrices once."""
    return {"ops": 2.0 * rows * k * n,
            "bytes": float((rows * k + rows * n + experts * k * n)
                           * dtype_bytes)}
