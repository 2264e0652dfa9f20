"""Operations and bytes of Falcon-H1's block, from shapes alone (the
yardstick's arithmetic beside ``flops.py``; nothing here reads the
program). ``cfg`` is ``model_falconh1.sizes`` of a configuration file.

A training token costs 6 floating-point operations per matmul parameter
(2 forward, 4 backward): the attention half's four projections, the
mixer's two, the SwiGLU's three, the head over the vocabulary held; plus
the causal attention and the mixer's scan in every layer. Recomputation
under remat counts nothing, nor do the convolution, the norms, the gate and
the multipliers.

The scan is reckoned in the chunked form every state-space cell of the
benchmark is held to (``flops_nemotron.scan_flops_per_token``): a group's
``C B^T`` [Q, Q] ONCE for all its heads, a head's ``M u`` and its two
products with the state, whatever block of heads or layout computes them.
"""

from __future__ import annotations

from benchmark import flops


def attention_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_width"]
    return 2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd


def mixer_sizes(cfg: dict) -> tuple:
    """(the heads' lanes H P, the convolution's channels, the
    in-projection's columns)."""
    inner = cfg["mamba_heads"] * cfg["mamba_head_dim"]
    bc = 2 * cfg["mamba_groups"] * cfg["mamba_state"]
    return inner, inner + bc, 2 * inner + bc + cfg["mamba_heads"]


def mixer_params(cfg: dict) -> int:
    inner, _, proj = mixer_sizes(cfg)
    return cfg["d_model"] * (proj + inner)


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    n, d = cfg["n_layers"], cfg["d_model"]
    return {"attention projections": n * attention_params(cfg),
            "mixer projections": n * mixer_params(cfg),
            "swiglu": n * 3 * d * cfg["d_ff"],
            "head": d * cfg["vocab_size"]}


def scan_flops_per_token(cfg: dict) -> float:
    """The scan's forward in ONE layer, a token, in chunks of Q: a group's
    C B^T (2 N Q / 2 a token and group, the causal half counted whole as
    the other cells count it: N Q), a head's M u (P Q) and its state's two
    products (4 P N)."""
    h, p, n, q, g = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                     cfg["mamba_state"], cfg["mamba_chunk"],
                     cfg["mamba_groups"])
    return float(g * n * q + h * (p * q + 4 * p * n))


def _attends(cfg: dict) -> dict:
    """``flops.py`` takes a head's width as d_model / n_heads: the stated
    width, for its attention arithmetic."""
    return {**cfg, "d_model": cfg["n_heads"] * cfg["head_width"]}


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    out["attention"] = 2.0 * flops.causal_attention_unit(
        _attends(cfg), seq) * cfg["n_layers"] / seq
    out["scan"] = cfg["n_layers"] * scan_flops_per_token(cfg)
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def layer_params(cfg: dict) -> int:
    """Every parameter of one layer: the matrices, the convolution with its
    bias, dt_bias, A_log and D a head, the gated norm's scale, two norms."""
    inner, conv, _ = mixer_sizes(cfg)
    return (attention_params(cfg) + mixer_params(cfg)
            + (cfg["mamba_conv"] + 1) * conv + 3 * cfg["mamba_heads"] + inner
            + 3 * cfg["d_model"] * cfg["d_ff"] + 2 * cfg["d_model"])


def total_params(cfg: dict) -> int:
    d = cfg["d_model"]
    return cfg["n_layers"] * layer_params(cfg) + 2 * cfg["vocab_size"] * d + d


def published_params(cfg: dict, published: dict) -> int:
    """The whole model's count from the same keys: the file's ``published``
    depth and vocabulary."""
    return total_params({**cfg, "n_layers": published["num_hidden_layers"],
                         "vocab_size": published["vocab_size"]})


def ssd_call(cfg: dict, batch: int, seq: int, which: str,
             dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of one call of the scan over ``batch``
    sequences of one layer: ``fwd`` or ``bwd``. Bytes, each array read or
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


def flash_call(cfg: dict, batch: int, seq: int, which: str) -> dict:
    """One flash call of the attention half at the stated head width, 5
    query heads a KV head."""
    return flops.flash_call(_attends(cfg), batch, seq, which)
