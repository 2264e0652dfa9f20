"""Operations and bytes of the model whose attention layers are of two
kinds (sliding-window and full) with sparse experts, from shapes alone (the
yardstick's arithmetic beside ``flops.py``, ``flops_moe.py``,
``flops_granite.py`` and ``flops_glm.py``; nothing here reads the program).
``cfg`` is ``model_mellum.sizes`` of a configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): the four attention projections at the STATED
head width, the router over ALL experts, the experts HELD here that an even
router would send it to (``top_k x held / n_experts`` of them: the rest of
its K are other chips' work), the head over the vocabulary held; plus
attention UNDER THE MASK of each layer's kind: a query sees ``pairs`` keys,

    full    S (S + 1) / 2 a head and sequence
    window  W (W + 1) / 2 + (S - W) W        (W <= S; the first W rows see
                                              1..W keys, the rest W each)

counted the same whatever the kernel skips or computes, so that no share
can read over 100% by skipping and none is flattered by masked work.
Recomputation under remat counts nothing, nor do the norms, the rotary,
the sort and the gathers.
"""

from __future__ import annotations

from benchmark import flops, flops_moe


def pairs(seq: int, window) -> int:
    """(query, key) pairs one head and sequence computes under the causal
    mask and, where the kind has one, the window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layers_of(cfg: dict) -> dict:
    """How many layers of each kind: {"window": 9, "full": 3}."""
    return {kind: cfg["layer_kinds"].count(kind) for kind in cfg["kinds"]}


def held_per_token(cfg: dict) -> float:
    """Experts held here that a token is sent to under an even router."""
    return cfg["top_k"] * cfg["experts_held"][0] / cfg["n_experts"]


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d, hd = cfg["d_model"], cfg["head_width"]
    return {
        "attention projections": cfg["n_layers"] * (
            2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd),
        "router": cfg["n_layers"] * d * cfg["n_experts"],
        "experts held": cfg["n_layers"] * held_per_token(cfg) * 3 * d
        * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def attention_unit(cfg: dict, seq: int, kind: str) -> float:
    """One matmul over the visible pairs of one layer of ``kind`` and one
    sequence, all heads, in operations: 2 x pairs x H x head width."""
    return 2.0 * pairs(seq, cfg["kinds"][kind]["window"]) * cfg["n_heads"] \
        * cfg["head_width"]


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
    d, hd = cfg["d_model"], cfg["head_width"]
    layer = (2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd
             + 2 * d + d * cfg["n_experts"]
             + cfg["experts_held"][0] * 3 * d * cfg["d_ff"])
    return cfg["n_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def flash_call(cfg: dict, batch: int, seq: int, which: str, kind: str,
               dtype_bytes: int = 2) -> dict:
    """Operations and HBM bytes of one flash call (``fwd``, ``dq``,
    ``dkdv``) over ``batch`` sequences of one layer of ``kind``: the units
    of ``flops.FLASH_UNITS`` (forward 2; backward 5, split 2 : 3) over the
    pairs the mask lets through; each of q, k, v (and for the backward o,
    do and the gradients) read or written once, whichever block plan the
    call took and whatever it skipped."""
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_width"]
    q = batch * seq * h * hd * dtype_bytes
    k = batch * seq * kv * hd * dtype_bytes
    ops = flops.FLASH_UNITS[which] * attention_unit(cfg, seq, kind) * batch
    nbytes = {"fwd": 2 * q + 2 * k, "dq": 4 * q + 2 * k,
              "dkdv": 3 * q + 4 * k}[which]
    return {"ops": ops, "bytes": float(nbytes)}


def grouped_matmul_call(rows: float, k: int, n: int, experts: int) -> dict:
    """``flops_moe.grouped_matmul_call`` for the rows the held experts
    really got (the buffer is larger and the kernel skips the tiles no
    group covers)."""
    return flops_moe.grouped_matmul_call(rows, k, n, experts)
