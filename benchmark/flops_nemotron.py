"""Operations and bytes of Nemotron-H's blocks (Nemotron 3 Nano 30B-A3B),
from shapes alone (the yardstick's arithmetic beside ``flops.py`` and
``flops_granite.py``; nothing here reads the program). ``cfg`` is
``model_nemotron.sizes`` of a configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): a mixer block's two projections, an attention
block's four, an expert block's router over ALL experts, its shared expert
(TWO matrices) and the experts HELD here that an even router would send it
to (``top_k x held / n_experts`` of them, two matrices each, at the
PUBLISHED width whatever a program stores), the head over the vocabulary
held; plus causal attention in the attention blocks and the scan in the
mixers. Recomputation under remat counts nothing, nor do the convolution,
the norms, the sort and the gathers.

The scan (``ops/ssd.py``), per token, H heads of width P in G groups,
state N, chunk Q, counting the CAUSAL HALF of a chunk (a token sees Q/2
others of its chunk on average; the kernel computes the whole square and
masks it): scores C.B ONCE A GROUP 2 N Q/2; scores times x 2 P Q/2 a head;
the chunk's end state 2 P N and the term of the state before the chunk
2 P N a head. The backward is two gradient products for each of those and
the scores once more a group (they are not stored).
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def kinds(cfg: dict) -> dict:
    types = cfg["layer_types"]
    return {k: types.count(k) for k in ("mamba", "attention", "experts")}


def held_per_token(cfg: dict) -> float:
    """Experts held here that a token is sent to under an even router."""
    return cfg["top_k"] * cfg["experts_held"][0] / cfg["n_experts"]


def mixer_widths(cfg: dict) -> tuple:
    """(H P, the convolution's channels, the in-projection's columns)."""
    inner = cfg["mamba_heads"] * cfg["mamba_head_dim"]
    bc = 2 * cfg["mamba_groups"] * cfg["mamba_state"]
    return inner, inner + bc, 2 * inner + bc + cfg["mamba_heads"]


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d, n = cfg["d_model"], kinds(cfg)
    inner, _, proj = mixer_widths(cfg)
    hd = cfg["head_width"]
    return {
        "mixer projections": n["mamba"] * (d * proj + inner * d),
        "attention projections": n["attention"] * (
            2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd),
        "router": n["experts"] * d * cfg["n_experts"],
        "shared": n["experts"] * 2 * d * cfg["shared_d_ff"],
        "experts held": n["experts"] * held_per_token(cfg) * 2 * d
        * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def scan_flops_per_token(cfg: dict) -> float:
    """The scan's forward in ONE mixer block, a token."""
    h, p, n, q, g = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                     cfg["mamba_state"], cfg["mamba_chunk"],
                     cfg["mamba_groups"])
    return float(g * n * q + h * (p * q + 4 * p * n))


def attention_unit(cfg: dict, seq: int) -> float:
    """``flops.causal_attention_unit`` at the STATED head width: one
    S x S x head matmul over all heads of one block and sequence, causal
    (half of the square)."""
    return float(seq) * seq * cfg["n_heads"] * cfg["head_width"]


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    n = kinds(cfg)
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    out["scan"] = n["mamba"] * scan_flops_per_token(cfg)
    out["attention"] = 2.0 * attention_unit(cfg, seq) * n["attention"] / seq
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def total_params(cfg: dict) -> int:
    """Every parameter the chip holds, at the published widths."""
    d, h, n = cfg["d_model"], cfg["mamba_heads"], kinds(cfg)
    inner, conv_dim, proj = mixer_widths(cfg)
    hd = cfg["head_width"]
    mixer = (d + d * proj + inner * d + (cfg["mamba_conv"] + 1) * conv_dim
             + 3 * h + inner)
    attention = d + 2 * d * cfg["n_heads"] * hd \
        + 2 * d * cfg["n_kv_heads"] * hd
    experts = (d + d * cfg["n_experts"] + cfg["n_experts"]
               + 2 * d * cfg["shared_d_ff"]
               + cfg["experts_held"][0] * 2 * d * cfg["d_ff"])
    return (n["mamba"] * mixer + n["attention"] * attention
            + n["experts"] * experts + 2 * cfg["vocab_size"] * d + d)


def ssd_call(cfg: dict, batch: int, seq: int, which: str,
             dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of one call of the scan over ``batch``
    sequences of one block: ``fwd`` or ``bwd``. Bytes, each array read or
    written once: x and y (and their gradients) [B, S, H P], B and C (and
    theirs) [B, S, G N], the running decay [B, S, H] float32 in its two
    layouts (and its gradient in both), the state entering each chunk
    [B, S / Q, H P, N] float32, written forward and read backward."""
    h, p, n, q, g = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                     cfg["mamba_state"], cfg["mamba_chunk"],
                     cfg["mamba_groups"])
    tokens = batch * seq
    wide = tokens * h * p * dtype_bytes
    shared = tokens * g * n * dtype_bytes
    decay = 2 * tokens * h * 4
    states = batch * (seq // q) * h * p * n * 4
    forward = tokens * scan_flops_per_token(cfg)
    if which == "fwd":
        return {"ops": forward,
                "bytes": float(2 * wide + 2 * shared + decay + states)}
    if which != "bwd":
        raise ValueError(f"ssd_call: {which!r} is neither fwd nor bwd")
    return {"ops": 2.0 * forward + tokens * g * n * q,
            "bytes": float(3 * wide + 4 * shared + 2 * decay + states)}


def flash_call(cfg: dict, batch: int, seq: int, which: str,
               dtype_bytes: int = 2) -> dict:
    """``flops.flash_call`` at the stated head width (the hidden size is
    no multiple of the heads' lanes here)."""
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    q = batch * seq * h * hd * dtype_bytes
    k = batch * seq * kv * hd * dtype_bytes
    ops = flops.FLASH_UNITS[which] * attention_unit(cfg, seq) * batch
    nbytes = {"fwd": 2 * q + 2 * k, "dq": 4 * q + 2 * k,
              "dkdv": 3 * q + 4 * k}[which]
    return {"ops": ops, "bytes": float(nbytes)}


def grouped_matmul_call(rows: float, experts: int, cfg: dict) -> dict:
    """One grouped matmul over the ``rows`` the held experts really got,
    at the PUBLISHED widths [d_model, d_ff] whatever the call's operands
    hold (a program that stores its experts wider moves more and computes
    zeros: neither counts)."""
    return flops_moe.grouped_matmul_call(rows, cfg["d_model"], cfg["d_ff"],
                                         experts)


def shared_step(cfg: dict, tokens: int) -> dict:
    """What the shared expert's mathematics needs of one step of ``tokens``
    tokens, forward and backward, all expert blocks: 6 operations a
    parameter and token over its TWO matrices (the checkpoint's replay is
    NOT counted); bytes: every weight read twice and its gradient written
    once, the rows in and out."""
    n = kinds(cfg)["experts"]
    params = n * 2 * cfg["d_model"] * cfg["shared_d_ff"]
    rows = n * tokens * cfg["d_model"]
    return {"ops": 6.0 * params * tokens,
            "bytes": 2.0 * (3 * params + 4 * rows)}
