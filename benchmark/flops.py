"""Operations and bytes that the algorithm needs, from shapes alone.

The yardstick's arithmetic: nothing here reads the program. ``cfg`` is a
configuration file's ``model`` group (LlamaConfig field names).

A training token costs 6 floating-point operations per matmul parameter
(2 forward, 4 backward) plus causal attention. The input embedding is a
lookup and counts nothing; recomputation under remat counts nothing.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["d_model"] // cfg["n_heads"]


def layer_matmul_params(cfg: dict) -> int:
    d, h, kv, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       head_dim(cfg), cfg["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul: layers and the output head."""
    return (cfg["n_layers"] * layer_matmul_params(cfg)
            + cfg["d_model"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    d = cfg["d_model"]
    return (matmul_params(cfg) + cfg["vocab_size"] * d
            + cfg["n_layers"] * 2 * d + d)


def causal_attention_unit(cfg: dict, seq: int) -> float:
    """One S x S x head_dim matmul over all heads of one layer and one
    sequence, causal (half of the square): 2 * S*S/2 * H * HD."""
    return float(seq) * seq * cfg["n_heads"] * head_dim(cfg)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token in a sequence of ``seq``: causal
    attention is two matmuls forward and four backward, 6 units a layer
    and sequence, so 6 * S * H * HD a layer and token."""
    attn = 6.0 * causal_attention_unit(cfg, seq) * cfg["n_layers"] / seq
    return 6.0 * matmul_params(cfg) + attn


# --- flash attention (ops/flash_attention.py): forward, dq, dkdv ----------
# Least work of the algorithm (it never stores the S x S matrix, so the
# backward recomputes it once): forward QK^T and PV = 2 units; backward
# QK^T, dV, dP, dQ, dK = 5 units. The program splits the backward into a
# dq call (3 units as written) and a dkdv call (4 as written); only the 5
# that the mathematics needs are counted, split 2 : 3 between them.
FLASH_UNITS = {"fwd": 2.0, "dq": 2.0, "dkdv": 3.0}


def flash_call(cfg: dict, batch: int, seq: int, which: str,
               dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of one flash call over ``batch`` sequences
    of one layer. Bytes: each of q, k, v (and for the backward o, do and
    the gradients) read or written once."""
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], head_dim(cfg)
    q = batch * seq * h * hd * dtype_bytes
    k = batch * seq * kv * hd * dtype_bytes
    ops = FLASH_UNITS[which] * causal_attention_unit(cfg, seq) * batch
    if which == "fwd":
        nbytes = 2 * q + 2 * k                     # q, o; k, v
    elif which == "dq":
        nbytes = 4 * q + 2 * k                     # q, o, do, dq; k, v
    else:
        nbytes = 3 * q + 4 * k                     # q, o, do; k, v, dk, dv
    return {"ops": ops, "bytes": float(nbytes)}


# --- paged decode attention (ops/paged_attention.py) ------------------------


def paged_decode_call(cfg: dict, context_lens: list, dtype_bytes: int = 2
                      ) -> dict:
    """One decode step's attention of one layer over sequences whose
    caches hold ``context_lens`` tokens: every cached key and value is
    read once (the bound: one multiply-add per byte pair), the query and
    output are small."""
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], head_dim(cfg)
    tokens = float(sum(context_lens))
    n = len(context_lens)
    nbytes = 2 * tokens * kv * hd * dtype_bytes + 2 * n * h * hd * dtype_bytes
    ops = 4.0 * tokens * h * hd                     # QK^T and PV
    return {"ops": ops, "bytes": nbytes}


def least_seconds(call: dict, peak: dict) -> dict:
    """Roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two it is."""
    t_ops = call["ops"] / peak["bf16_flops_per_s"]
    t_mem = call["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
