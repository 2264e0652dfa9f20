"""Operations and bytes of Ling 3.0's blocks (the language model of
Ling-3.0-flash), from shapes alone (the yardstick's arithmetic beside
``flops.py`` and ``flops_lfm2.py``; nothing here reads the program).
``cfg`` is ``model_ling.sizes`` of a configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): a KDA half's projections (q, k, v and the
gate D x H dk each, beta and the output gate D x H, the output H dv x D),
a latent half's (q D x H (nope + rope), the latent D x (kv + rope), its
expansion kv x H (nope + v), the gate D x H, the output H v x D), a dense
layer's three matrices, an expert layer's router over ALL experts, the
shared expert and the experts HELD here that an even router would send it
to (``top_k x held / n_experts`` of them, three matrices each), the head
over the vocabulary held; plus the causal attention of the latent layers
at the PUBLISHED widths (``q k^T`` over nope + rope lanes, ``p v`` over v
lanes) and the delta rule's recurrence. Recomputation under remat counts
nothing, nor do the convolutions, the norms, the gates, the rotary, the
sort and the gathers.

The delta rule's work is reckoned by its EQUATIONS and not by a form
(``delta_rule_layer``): a step of one head decays the state (dk dv
multiplies), reads it twice (``S^T k`` and ``S^T q``: 2 dk dv each) and
writes a rank-one update (2 dk dv): 7 dk dv operations forward and twice
that backward, 21 dk dv a step and head; a chunked form that multiplies
more (the pair blocks, the inverse) reads the lower for it. Its least HBM
traffic, every operand read once and every result written once: forward q,
k, v in and o out in the activations' type, the gate in float32 and beta;
backward those and do in, dq, dk, dv, dg and dbeta out.
"""

from __future__ import annotations

from benchmark import flops_moe


def kinds(cfg: dict) -> dict:
    """How many layers hold each first half and each feed-forward."""
    first = [k.split(".")[0] for k in cfg["kinds"]]
    return {"kda": first.count("kda"), "mla": first.count("mla"),
            "dense": cfg["n_dense"],
            "experts": cfg["n_layers"] - cfg["n_dense"]}


def held_per_token(cfg: dict) -> float:
    """Experts held here that a token is sent to under an even router."""
    return cfg["top_k"] * cfg["experts_held"][0] / cfg["n_experts"]


def kda_params(cfg: dict) -> int:
    d, h, dk = cfg["d_model"], cfg["n_heads"], cfg["kda_head_dim"]
    return 4 * d * h * dk + 2 * d * h + h * dk * d


def mla_params(cfg: dict) -> int:
    d, h = cfg["d_model"], cfg["n_heads"]
    nope, rope, v, kv = (cfg["qk_nope_dim"], cfg["qk_rope_dim"],
                         cfg["v_dim"], cfg["kv_rank"])
    return (d * h * (nope + rope) + d * (kv + rope) + kv * h * (nope + v)
            + d * h + h * v * d)


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d, n = cfg["d_model"], kinds(cfg)
    return {
        "kda projections": n["kda"] * kda_params(cfg),
        "mla projections": n["mla"] * mla_params(cfg),
        "dense layers": n["dense"] * 3 * d * cfg["dense_d_ff"],
        "router": n["experts"] * d * cfg["n_experts"],
        "shared expert": n["experts"] * 3 * d * cfg["shared_d_ff"],
        "experts held": n["experts"] * held_per_token(cfg) * 3 * d
        * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def attention_units(cfg: dict, seq: int) -> tuple:
    """One layer's and sequence's causal S x S products over all heads at
    the PUBLISHED widths: (``q k^T`` over nope + rope lanes, ``p v`` over
    v lanes), each half of the square."""
    pairs = float(seq) * seq * cfg["n_heads"]
    return (pairs * (cfg["qk_nope_dim"] + cfg["qk_rope_dim"]),
            pairs * cfg["v_dim"])


def delta_rule_ops_per_token(cfg: dict) -> float:
    """The recurrence's forward operations a token and layer: 7 dk dv a
    head (the module docstring has the count)."""
    return 7.0 * cfg["n_heads"] * cfg["kda_head_dim"] ** 2


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    qk, pv = attention_units(cfg, seq)
    n = kinds(cfg)
    out["attention"] = (qk + pv) * n["mla"] / seq    # 2 x half the square
    out["delta rule"] = delta_rule_ops_per_token(cfg) * n["kda"]
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def total_params(cfg: dict) -> int:
    """Every parameter the chip holds, at the published widths."""
    d, n, h = cfg["d_model"], kinds(cfg), cfg["n_heads"]
    dk = cfg["kda_head_dim"]
    kda = d + kda_params(cfg) + 3 * cfg["conv_taps"] * h * dk + h + h * dk \
        + dk
    mla = d + mla_params(cfg) + cfg["kv_rank"]
    dense = d + 3 * d * cfg["dense_d_ff"]
    experts = (d + d * cfg["n_experts"] + cfg["n_experts"]
               + cfg["experts_held"][0] * 3 * d * cfg["d_ff"]
               + 3 * d * cfg["shared_d_ff"])
    return (n["kda"] * kda + n["mla"] * mla + n["dense"] * dense
            + n["experts"] * experts + 2 * cfg["vocab_size"] * d + d)


def flash_call(cfg: dict, batch: int, seq: int, which: str,
               dtype_bytes: int = 2) -> dict:
    """One flash call at the PUBLISHED widths, the causal pairs only:
    ``q k^T`` (and its two gradient products) over nope + rope lanes, ``p
    v`` (and its) over v lanes; q and k read at nope + rope lanes a head,
    v, o and do at v lanes, whatever width the kernels run at (a program
    that pads v to the keys' width reads as the cost it is). Forward: 1
    product of each kind; dq: ``q k^T``, ``do v^T`` and ``ds k``; dkdv:
    ``q k^T``, ``do v^T``, ``p^T do`` and ``ds^T q``."""
    qk, pv = attention_units(cfg, seq)
    n_qk, n_pv = {"fwd": (1, 1), "dq": (2, 1), "dkdv": (2, 2)}[which]
    # a product over the causal half of the square: 2 x pairs / 2
    ops = batch * (n_qk * qk + n_pv * pv)
    rows = batch * seq * cfg["n_heads"] * dtype_bytes
    wide, narrow = (rows * (cfg["qk_nope_dim"] + cfg["qk_rope_dim"]),
                    rows * cfg["v_dim"])
    nbytes = {"fwd": 2 * wide + 2 * narrow,            # q, k; v, o
              "dq": 3 * wide + 3 * narrow,             # q, k, dq; v, o, do
              "dkdv": 3 * wide + 4 * narrow}[which]    # q, k, dk; v, o, do, dv
    return {"ops": float(ops), "bytes": float(nbytes)}


def delta_rule_layer(cfg: dict, batch: int, seq: int,
                     dtype_bytes: int = 2) -> dict:
    """What one KDA layer's recurrence needs of one step of ``batch``
    sequences of ``seq`` tokens, forward and backward (the module
    docstring has the count); the checkpoint's replay of the forward is
    NOT counted: the mathematics needs one forward."""
    h, dk = cfg["n_heads"], cfg["kda_head_dim"]
    tokens = batch * seq
    wide = tokens * h * dk
    forward = wide * (4 * dtype_bytes + 4) + tokens * h * 4
    backward = wide * (4 * dtype_bytes + 4) + tokens * h * 4 \
        + wide * (3 * dtype_bytes + 4) + tokens * h * 4
    return {"ops": 3.0 * delta_rule_ops_per_token(cfg) * tokens,
            "bytes": float(forward + backward)}


def grouped_matmul_call(rows: float, experts: int, cfg: dict) -> dict:
    """One grouped matmul over the ``rows`` the held experts really got,
    at the published widths [d_model, d_ff]."""
    return flops_moe.grouped_matmul_call(rows, cfg["d_model"], cfg["d_ff"],
                                         experts)
