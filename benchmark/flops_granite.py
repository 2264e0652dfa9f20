"""Operations and bytes of the state-space / attention hybrid, from shapes
alone (the yardstick's arithmetic beside ``flops.py`` and ``flops_moe.py``;
nothing here reads the program). ``cfg`` is ``model_granite.sizes`` of a
configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): a mixer's two projections or an attention
layer's four, the router over ALL experts, the shared SwiGLU, the experts
HELD here that an even router would send it to (``top_k x held /
n_experts`` of them: the rest of its K are other chips' work), the head
over the vocabulary held; plus causal attention in the attention layers
and the scan in the mixers. Recomputation under remat counts nothing, nor
do the convolution, the norms, the sort and the gathers.

The scan (``ops/ssd.py``), per token, head of width P, state N, chunk Q,
counting the CAUSAL HALF of a chunk (a token sees Q/2 others of its chunk
on average; the kernel computes the whole square and masks it): scores
C.B shared by the heads 2 N Q/2; scores times x 2 P Q/2 a head; the
chunk's end state 2 P N and the term of the state before the chunk 2 P N
a head. The backward is two gradient products for each of those and the
scores once more (they are not stored).
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def _kinds(cfg: dict) -> tuple:
    types = cfg["layer_types"]
    return types.count("mamba"), types.count("attention")


def held_per_token(cfg: dict) -> float:
    """Experts held here that a token is sent to under an even router."""
    return cfg["top_k"] * cfg["experts_held"][0] / cfg["n_experts"]


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d = cfg["d_model"]
    inner = cfg["mamba_heads"] * cfg["mamba_head_dim"]
    proj = 2 * inner + 2 * cfg["mamba_state"] + cfg["mamba_heads"]
    n_mamba, n_attn = _kinds(cfg)
    hd = flops.head_dim(cfg)
    return {
        "mixer projections": n_mamba * (d * proj + inner * d),
        "attention projections": n_attn * (
            2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd),
        "router": cfg["n_layers"] * d * cfg["n_experts"],
        "shared": cfg["n_layers"] * 3 * d * cfg["shared_d_ff"],
        "experts held": cfg["n_layers"] * held_per_token(cfg) * 3 * d
        * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def scan_flops_per_token(cfg: dict) -> float:
    """The scan's forward in ONE mixer layer, a token."""
    h, p, n, q = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                  cfg["mamba_state"], cfg["mamba_chunk"])
    return float(n * q + h * (p * q + 4 * p * n))


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    n_mamba, n_attn = _kinds(cfg)
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    out["scan"] = n_mamba * scan_flops_per_token(cfg)
    out["attention"] = 2.0 * flops.causal_attention_unit(cfg, seq) * n_attn \
        / seq
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def total_params(cfg: dict) -> int:
    d, h = cfg["d_model"], cfg["mamba_heads"]
    inner = h * cfg["mamba_head_dim"]
    conv_dim = inner + 2 * cfg["mamba_state"]
    n_mamba, n_attn = _kinds(cfg)
    hd = flops.head_dim(cfg)
    mixer = (d * (inner + conv_dim + h) + inner * d
             + (cfg["mamba_conv"] + 1) * conv_dim + 3 * h + inner)
    attention = 2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd
    rest = (2 * d + d * cfg["n_experts"] + 3 * d * cfg["shared_d_ff"]
            + cfg["experts_held"][0] * 3 * d * cfg["d_ff"])
    return (n_mamba * mixer + n_attn * attention + cfg["n_layers"] * rest
            + cfg["vocab_size"] * d + d)


def ssd_call(cfg: dict, batch: int, seq: int, which: str,
             dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of one call of the scan over ``batch``
    sequences of one layer: ``fwd`` or ``bwd``. Bytes, each array read or
    written once: x and y (and their gradients) [B, S, H P], B and C (and
    theirs) [B, S, N], the running decay [B, S, H] float32 in its two
    layouts (and its gradient in both), the state entering each chunk
    [B, S / Q, H P, N] float32, written forward and read backward."""
    h, p, n, q = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                  cfg["mamba_state"], cfg["mamba_chunk"])
    tokens = batch * seq
    wide = tokens * h * p * dtype_bytes
    shared = tokens * n * dtype_bytes
    decay = 2 * tokens * h * 4
    states = batch * (seq // q) * h * p * n * 4
    forward = tokens * scan_flops_per_token(cfg)
    if which == "fwd":
        return {"ops": forward,
                "bytes": float(2 * wide + 2 * shared + decay + states)}
    if which != "bwd":
        raise ValueError(f"ssd_call: {which!r} is neither fwd nor bwd")
    return {"ops": 2.0 * forward + tokens * n * q,
            "bytes": float(3 * wide + 4 * shared + 2 * decay + states)}


def grouped_matmul_call(rows: float, k: int, n: int, experts: int) -> dict:
    """``flops_moe.grouped_matmul_call`` for the rows the held experts
    really got (the buffer is larger and the kernel skips the tiles no
    group covers)."""
    return flops_moe.grouped_matmul_call(rows, k, n, experts)
