"""Operations and bytes of the sparse-attention / linear-attention hybrid
(``ray_tpu/models/sala.py``) from its sizes alone (``model_sala.sizes``):
training FLOPs a token, the exact count of the pairs a set of blocks
holds, and the two mechanisms' least operations and bytes a layer, by the
EQUATIONS and not by the form that computes them: attention over the
SELECTED pairs, the linear-attention RECURRENCE a token at a time. A walk
that computes pairs outside the sets, a chunked scan that multiplies
[Q, Q] blocks, a replay under the layer checkpoint: each reads the lower
against these for what it does beyond them.
"""

from __future__ import annotations


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def blocks_held(t: int, cfg: dict) -> int:
    """The blocks query t attends to: all that do not start after it, at
    most ``sparse_topk``."""
    return min(cfg["sparse_topk"], t // cfg["sparse_block"] + 1)


def selected_pairs(seq: int, cfg: dict) -> int:
    """(query, key) pairs a head attends to over one sequence past the
    dense length: a query's blocks whole but its own, which ends with the
    query itself. Up to the dense length: every causal pair."""
    if seq <= cfg["dense_len"]:
        return causal_pairs(seq)
    block = cfg["sparse_block"]
    return sum(block * (blocks_held(t, cfg) - 1) + t % block + 1
               for t in range(seq))


def kinds(cfg: dict) -> dict:
    types = cfg["layer_types"]
    return {k: sum(t == k for t in types) for k in ("sparse", "lightning")}


def mixer_params(cfg: dict, kind: str) -> int:
    """Matmul parameters of one layer's first half: q, the gate and o over
    the heads' lanes, k and v over the KV heads' (a lightning layer's
    heads have keys and values of their own)."""
    d, hd = cfg["d_model"], cfg["head_width"]
    wide = (cfg["lightning_heads"] if kind == "lightning"
            else cfg["n_heads"]) * hd
    narrow = wide if kind == "lightning" else cfg["n_kv_heads"] * hd
    return 3 * d * wide + 2 * d * narrow


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    n, d = kinds(cfg), cfg["d_model"]
    return {
        "sparse projections": n["sparse"] * mixer_params(cfg, "sparse"),
        "lightning projections": n["lightning"]
        * mixer_params(cfg, "lightning"),
        "swiglu": (n["sparse"] + n["lightning"]) * 3 * d * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def attention_unit(cfg: dict, seq: int) -> float:
    """The two matmuls of one sparse layer's attention over one sequence's
    selected pairs, in operations."""
    return 2.0 * selected_pairs(seq, cfg) * cfg["n_heads"] \
        * 2 * cfg["head_width"]


def select_unit(cfg: dict, seq: int) -> float:
    """One sparse layer's scores of the pooled kernels over one sequence
    (every query against every kernel that ends before it: half the
    rectangle), in operations; 0 up to the dense length."""
    if seq <= cfg["dense_len"]:
        return 0.0
    kernels = (seq - cfg["sparse_kernel"]) // cfg["sparse_stride"] + 1
    return 2.0 * seq * kernels / 2 * cfg["n_heads"] * cfg["head_width"]


def recurrence_flops_per_token(cfg: dict) -> float:
    """One lightning layer's recurrence, a token: a head's [HD, HD] state
    decayed (1), a rank-one term added (2) and read by the query (2)."""
    return 5.0 * cfg["lightning_heads"] * cfg["head_width"] ** 2


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    n = kinds(cfg)
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    out["attention"] = attention_unit(cfg, seq) * n["sparse"] / seq
    out["selection"] = select_unit(cfg, seq) * n["sparse"] / seq
    out["recurrence"] = recurrence_flops_per_token(cfg) * n["lightning"]
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Three times the forward, but the selection, which has no backward."""
    fwd = forward_flops_per_token(cfg, seq)
    return 3.0 * sum(fwd.values()) - 2.0 * fwd["selection"]


def total_params(cfg: dict) -> int:
    d, hd, n = cfg["d_model"], cfg["head_width"], kinds(cfg)
    layer = 2 * d + 3 * d * cfg["d_ff"]
    return (n["sparse"] * (layer + mixer_params(cfg, "sparse") + 2 * hd)
            + n["lightning"] * (layer + mixer_params(cfg, "lightning")
                                + 3 * hd)
            + 2 * cfg["vocab_size"] * d + d)


def block_sparse_attention_layer(cfg: dict, batch: int, seq: int,
                                 dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of ONE sparse layer's attention over its
    sets, forward and backward (a replay under remat counts nothing),
    whatever form computes it. Operations: the forward's two matmuls over
    the selected pairs, the backward's five (the scores again, dP, dV, dK,
    dQ): 3.5 x the forward. Bytes, every operand read and every result
    written once: forward q, k, v, the set (a byte a query, KV group and
    block) and o; backward q, k, v, o, dO and the set read, dQ, dK, dV
    written."""
    row = batch * seq * cfg["head_width"] * dtype_bytes
    q, kv = row * cfg["n_heads"], row * cfg["n_kv_heads"]
    the_set = batch * cfg["n_kv_heads"] * seq * (seq // cfg["sparse_block"])
    return {"ops": 3.5 * batch * attention_unit(cfg, seq),
            "bytes": float(2 * q + 2 * kv + the_set
                           + 4 * q + 4 * kv + the_set)}


def lightning_layer(cfg: dict, batch: int, seq: int,
                    dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of ONE lightning layer's recurrence,
    forward and backward (a replay counts nothing), whatever chunk a scan
    walks it in. Operations: the recurrence a token at a time, and twice
    that for its gradient. Bytes: q, k, v read and o written; q, k, v and
    dO read, dq, dk, dv written (a state between chunks is the form's)."""
    tokens = batch * seq
    wide = tokens * cfg["lightning_heads"] * cfg["head_width"] * dtype_bytes
    return {"ops": 3.0 * tokens * recurrence_flops_per_token(cfg),
            "bytes": float(4 * wide + 7 * wide)}
