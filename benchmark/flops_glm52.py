"""Operations and bytes of the latent-attention model with a learned
selection (GLM-5.2: ``ray_tpu/models/latent.py`` with an indexer), from
shapes alone (the yardstick's arithmetic beside ``flops_glm.py``; nothing
here reads the program). ``cfg`` is ``model_glm52.sizes`` of a
configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): the five latent projections of every block
over the heads HELD, a full layer's three indexer projections, the leading
dense SwiGLU, the router over ALL experts, the shared SwiGLU, the experts
held here that an even router would send it to, and the head over the
vocabulary held; plus attention over the SELECTED pairs in every block
(``selected_pairs``: ``k (k + 1) / 2 + (S - k) k`` a head and sequence,
whatever the kernel computes or skips: a form that walks every causal pair
does more work than is counted, not more useful work) and the indexer's
scores over every causal pair, ``S (S + 1) / 2`` x index heads x lanes, in
the full layers. Recomputation under remat counts nothing, nor do the
norms, the rotary, the selection and the gathers.
"""

from __future__ import annotations

from benchmark import flops_moe


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a sequence's sets hold: query t has
    min(topk, t + 1) keys."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def _blocks(cfg: dict) -> tuple:
    """(dense blocks, sparse blocks, full blocks)."""
    return (cfg["n_dense"], cfg["n_layers"] - cfg["n_dense"],
            sum(cfg["index_full"]))


def mla_params(cfg: dict) -> int:
    """Matmul parameters of one block's five latent projections."""
    d, h = cfg["d_model"], cfg["n_heads"]
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    return (d * cfg["q_rank"] + cfg["q_rank"] * h * qk
            + d * (cfg["kv_rank"] + cfg["qk_rope_dim"])
            + cfg["kv_rank"] * h * (cfg["qk_nope_dim"] + cfg["v_dim"])
            + h * cfg["v_dim"] * d)


def index_params(cfg: dict) -> int:
    """Matmul parameters of one full layer's indexer."""
    return (cfg["q_rank"] * cfg["index_heads"] * cfg["index_dim"]
            + cfg["d_model"] * (cfg["index_dim"] + cfg["index_heads"]))


def held_per_token(cfg: dict) -> float:
    """Experts held here that a token is sent to under an even router."""
    return cfg["top_k"] * cfg["experts_held"][0] / cfg["n_experts"]


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d = cfg["d_model"]
    dense, sparse, full = _blocks(cfg)
    return {
        "latent projections": (dense + sparse) * mla_params(cfg),
        "indexer projections": full * index_params(cfg),
        "dense layer": dense * 3 * d * cfg["dense_d_ff"],
        "router": sparse * d * cfg["n_experts"],
        "shared": sparse * 3 * d * cfg["shared_d_ff"],
        "experts held": sparse * held_per_token(cfg) * 3 * d * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def attention_unit(cfg: dict, seq: int) -> float:
    """The two matmuls of one block's attention over one sequence's sets,
    in operations: scores over qk lanes, values over v, the selected pairs
    alone."""
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    return 2.0 * selected_pairs(seq, cfg["index_topk"]) * cfg["n_heads"] \
        * (qk + cfg["v_dim"])


def index_score_unit(cfg: dict, seq: int) -> float:
    """One full layer's index scores over one sequence, in operations."""
    return 2.0 * causal_pairs(seq) * cfg["index_heads"] * cfg["index_dim"]


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    dense, sparse, full = _blocks(cfg)
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    out["attention"] = attention_unit(cfg, seq) * (dense + sparse) / seq
    out["index scores"] = index_score_unit(cfg, seq) * full / seq
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def total_params(cfg: dict) -> int:
    d, e = cfg["d_model"], cfg["n_experts"]
    dense, sparse, full = _blocks(cfg)
    norms = 2 * d + cfg["q_rank"] + cfg["kv_rank"]
    block = mla_params(cfg) + norms
    return (dense * (block + 3 * d * cfg["dense_d_ff"])
            + sparse * (block + d * e + e + 3 * d * cfg["shared_d_ff"]
                        + cfg["experts_held"][0] * 3 * d * cfg["d_ff"])
            + full * (index_params(cfg) + 2 * cfg["index_dim"])
            + 2 * cfg["vocab_size"] * d + d)


def sparse_attention_layer(cfg: dict, batch: int, seq: int,
                           dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of ONE layer's attention over the sets,
    forward and backward (a replay under remat counts nothing), whatever
    form computes it. Operations: the forward's two matmuls over the
    selected pairs, the backward's five (the scores again, dP, dV, dK,
    dQ): 3.5 x the forward. Bytes: every operand read and every result
    written once: forward q, k, v, the set (a byte a pair of the square)
    and o; backward q, k, v, o, dO and the set read, dQ, dK, dV written."""
    heads = batch * seq * cfg["n_heads"] \
        * (cfg["qk_nope_dim"] + cfg["qk_rope_dim"]) * dtype_bytes
    the_set = batch * seq * seq
    return {"ops": 3.5 * batch * attention_unit(cfg, seq),
            "bytes": float(4 * heads + the_set + 8 * heads + the_set)}


def grouped_matmul_call(rows: float, k: int, n: int, experts: int) -> dict:
    """``flops_moe.grouped_matmul_call`` for the rows the held experts
    really got (the buffer is larger and the kernel skips the tiles no
    group covers)."""
    return flops_moe.grouped_matmul_call(rows, k, n, experts)
