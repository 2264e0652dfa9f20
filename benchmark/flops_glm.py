"""Operations and bytes of the latent-attention model with sparse experts
and a prediction module, from shapes alone (the yardstick's arithmetic
beside ``flops.py``, ``flops_moe.py`` and ``flops_granite.py``; nothing here
reads the program). ``cfg`` is ``model_glm.sizes`` of a configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): the five latent projections of every block,
the leading dense SwiGLU, the router over ALL experts, the shared SwiGLU,
the experts HELD here that an even router would send it to (``top_k x held
/ n_experts`` of them: the rest of its K are other chips' work), the
prediction module's joint projection and block, and the head over the
vocabulary held ONCE A PASS (twice with the module); plus causal attention
in every block, in its expanded form: scores over ``qk_nope + qk_rope``
lanes, values over ``v_dim``. Recomputation under remat counts nothing,
nor do the norms, the rotary, the sort and the gathers.
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def _blocks(cfg: dict) -> tuple:
    """(dense blocks, sparse blocks with the prediction module's)."""
    return cfg["n_dense"], cfg["n_layers"] - cfg["n_dense"] + cfg["n_mtp"]


def mla_params(cfg: dict) -> int:
    """Matmul parameters of one block's five latent projections."""
    d, h = cfg["d_model"], cfg["n_heads"]
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    return (d * cfg["q_rank"] + cfg["q_rank"] * h * qk
            + d * (cfg["kv_rank"] + cfg["qk_rope_dim"])
            + cfg["kv_rank"] * h * (cfg["qk_nope_dim"] + cfg["v_dim"])
            + h * cfg["v_dim"] * d)


def held_per_token(cfg: dict) -> float:
    """Experts held here that a token is sent to under an even router."""
    return cfg["top_k"] * cfg["experts_held"][0] / cfg["n_experts"]


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d = cfg["d_model"]
    dense, sparse = _blocks(cfg)
    return {
        "latent projections": (dense + sparse) * mla_params(cfg),
        "dense layer": dense * 3 * d * cfg["dense_d_ff"],
        "router": sparse * d * cfg["n_experts"],
        "shared": sparse * 3 * d * cfg["shared_d_ff"],
        "experts held": sparse * held_per_token(cfg) * 3 * d * cfg["d_ff"],
        "prediction module's projection": cfg["n_mtp"] * 2 * d * d,
        "heads": (1 + cfg["n_mtp"]) * d * cfg["vocab_size"],
    }


def attention_unit(cfg: dict, seq: int) -> float:
    """The two S x S matmuls of one block and one sequence, causal (half
    of the square), in operations: scores over qk lanes, values over v."""
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    return float(seq) * seq * cfg["n_heads"] * (qk + cfg["v_dim"])


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    out["attention"] = attention_unit(cfg, seq) * sum(_blocks(cfg)) / seq
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def total_params(cfg: dict) -> int:
    d, e = cfg["d_model"], cfg["n_experts"]
    dense, sparse = _blocks(cfg)
    norms = 2 * d + cfg["q_rank"] + cfg["kv_rank"]
    block = mla_params(cfg) + norms
    return (dense * (block + 3 * d * cfg["dense_d_ff"])
            + sparse * (block + d * e + e + 3 * d * cfg["shared_d_ff"]
                        + cfg["experts_held"][0] * 3 * d * cfg["d_ff"])
            + cfg["n_mtp"] * (2 * d * d + 3 * d)
            + 2 * cfg["vocab_size"] * d + d)


def flash_call(cfg: dict, batch: int, seq: int, which: str,
               dtype_bytes: int = 2) -> dict:
    """``flops.flash_call`` at this model's heads: q, k and v all
    ``[batch, seq, heads, qk_nope + qk_rope]`` (``v_dim`` is the same
    width), whichever block plan (``loop``, ``stream``, ``resident``) the
    call took: the least work is the plan's to reach, not its own."""
    width = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    heads = {"n_heads": cfg["n_heads"], "n_kv_heads": cfg["n_heads"],
             "d_model": cfg["n_heads"] * width}
    return flops.flash_call(heads, batch, seq, which, dtype_bytes)


def grouped_matmul_call(rows: float, k: int, n: int, experts: int) -> dict:
    """``flops_moe.grouped_matmul_call`` for the rows the held experts
    really got (the buffer is larger and the kernel skips the tiles no
    group covers)."""
    return flops_moe.grouped_matmul_call(rows, k, n, experts)
