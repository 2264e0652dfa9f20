"""Operations and bytes of LFM2's blocks (LFM2-8B-A1B), from shapes alone
(the yardstick's arithmetic beside ``flops.py`` and ``flops_nemotron.py``;
nothing here reads the program). ``cfg`` is ``model_lfm2.sizes`` of a
configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): a convolution layer's two projections
(D x 3 D and D x D), an attention layer's four, a dense layer's three
matrices, an expert layer's router over ALL experts and the experts HELD
here that an even router would send it to (``top_k x held / n_experts`` of
them, three matrices each), the head over the vocabulary held; plus causal
attention in the attention layers. Recomputation under remat counts
nothing, nor do the gate-taps-gate pass (5 multiply-adds a channel), the
norms, the rotary, the sort and the gathers.

The gate-taps-gate pass is memory-bound. Its least HBM traffic a layer and
step of T tokens, every operand read once and every result written once in
the activations' type: forward B, C, u in and y out (4 T D items);
backward B, C, u and dy in, dB, dC, du out (7 T D items); the taps and
their gradient are [taps, D] and count nothing. The checkpoint's replay of
the forward is NOT counted: the mathematics needs one forward.
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def kinds(cfg: dict) -> dict:
    """How many layers hold each operator and each feed-forward."""
    types = cfg["layer_types"]
    return {"conv": types.count("conv"),
            "attention": types.count("attention"),
            "dense": cfg["n_dense"],
            "experts": cfg["n_layers"] - cfg["n_dense"]}


def held_per_token(cfg: dict) -> float:
    """Experts held here that a token is sent to under an even router."""
    return cfg["top_k"] * cfg["experts_held"][0] / cfg["n_experts"]


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d, n, hd = cfg["d_model"], kinds(cfg), cfg["head_width"]
    return {
        "convolution projections": n["conv"] * 4 * d * d,
        "attention projections": n["attention"] * (
            2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd),
        "dense layers": n["dense"] * 3 * d * cfg["dense_d_ff"],
        "router": n["experts"] * d * cfg["n_experts"],
        "experts held": n["experts"] * held_per_token(cfg) * 3 * d
        * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def attention_unit(cfg: dict, seq: int) -> float:
    """``flops.causal_attention_unit`` at the head width of 64: one
    S x S x head matmul over all heads of one layer and sequence, causal
    (half of the square)."""
    return float(seq) * seq * cfg["n_heads"] * cfg["head_width"]


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    out["attention"] = 2.0 * attention_unit(cfg, seq) \
        * kinds(cfg)["attention"] / seq
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def total_params(cfg: dict) -> int:
    """Every parameter the chip holds, at the published widths; the head
    is the embedding."""
    d, n, hd = cfg["d_model"], kinds(cfg), cfg["head_width"]
    conv = d + 4 * d * d + cfg["conv_taps"] * d
    attention = d + 2 * d * cfg["n_heads"] * hd \
        + 2 * d * cfg["n_kv_heads"] * hd + 2 * hd
    dense = d + 3 * d * cfg["dense_d_ff"]
    experts = (d + d * cfg["n_experts"] + cfg["n_experts"]
               + cfg["experts_held"][0] * 3 * d * cfg["d_ff"])
    return (n["conv"] * conv + n["attention"] * attention
            + n["dense"] * dense + n["experts"] * experts
            + cfg["vocab_size"] * d + d)


def flash_call(cfg: dict, batch: int, seq: int, which: str,
               dtype_bytes: int = 2) -> dict:
    """``flops.flash_call`` at a head of 64: the causal pairs only, each of
    q, k, v (and for the backward o, do and the gradients) read or written
    once at 64 lanes a head, so a form that pads them to 128 reads as the
    cost it is."""
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    q = batch * seq * h * hd * dtype_bytes
    k = batch * seq * kv * hd * dtype_bytes
    ops = flops.FLASH_UNITS[which] * attention_unit(cfg, seq) * batch
    nbytes = {"fwd": 2 * q + 2 * k, "dq": 4 * q + 2 * k,
              "dkdv": 3 * q + 4 * k}[which]
    return {"ops": ops, "bytes": float(nbytes)}


def grouped_matmul_call(rows: float, experts: int, cfg: dict) -> dict:
    """One grouped matmul over the ``rows`` the held experts really got,
    at the published widths [d_model, d_ff]."""
    return flops_moe.grouped_matmul_call(rows, cfg["d_model"], cfg["d_ff"],
                                         experts)


def gate_conv_step(cfg: dict, tokens: int, dtype_bytes: int = 2) -> dict:
    """What the gate-taps-gate pass's mathematics needs of one step of
    ``tokens`` tokens, forward and backward, all convolution layers (the
    module docstring has the count): 11 T D items a layer; operations: 2
    products and ``taps`` multiply-adds a channel forward, about three
    times that backward."""
    n, d, taps = kinds(cfg)["conv"], cfg["d_model"], cfg["conv_taps"]
    return {"ops": float(n * tokens * d * 3 * (2 + 2 * taps)),
            "bytes": float(n * 11 * tokens * d * dtype_bytes)}
