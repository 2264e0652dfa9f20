"""Operations and bytes of Solar Open2's blocks (the language model of
Solar-Open2-250B), from shapes alone (the yardstick's arithmetic beside
``flops.py`` and ``flops_ling.py``; nothing here reads the program).
``cfg`` is ``model_solar.sizes`` of a configuration file.

A training token costs 6 floating-point operations per matmul parameter it
USES (2 forward, 4 backward): a KDA half's projections (q, k, v D x H dk
each, the decay's and the output gate's low-rank pairs D x r and r x H dk,
beta D x H, the output H dv x D), a grouped-query half's (q, the gate and
the output D x H hd, k and v D x KV hd), an expert layer's router over ALL
experts, the shared expert and the experts HELD here that an even router
would send it to (``top_k x held / n_experts`` of them, three matrices
each), the head over the vocabulary held; plus the causal attention of the
grouped-query layers and the delta rule's recurrence. Recomputation under
remat counts nothing, nor do the convolutions, the norms, the gates, the
sort and the gathers.

The delta rule's work is reckoned by its EQUATIONS and not by a form
(``delta_rule_layer``, ``flops_ling``'s count at this model's heads): 7 dk
dv operations a step and head forward and twice that backward; a cut of the
pair products that multiplies more reads the lower for it.
"""

from __future__ import annotations

from benchmark import flops, flops_ling, flops_moe


def kinds(cfg: dict) -> dict:
    """How many layers hold each first half."""
    return {k: cfg["kinds"].count(k) for k in ("kda", "gqa")}


held_per_token = flops_ling.held_per_token


def kda_params(cfg: dict) -> int:
    d, w, r = (cfg["d_model"], cfg["kda_heads"] * cfg["kda_head_dim"],
               cfg["gate_rank"])
    return 4 * d * w + 2 * (d * r + r * w) + d * cfg["kda_heads"]


def gqa_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_width"]
    return 3 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd


def expert_layer_params(cfg: dict, experts: float) -> float:
    """An expert layer's matmul parameters with ``experts`` routed experts
    counted: the router over all of them, the shared expert, the experts."""
    d = cfg["d_model"]
    return (d * cfg["n_experts"] + 3 * d * cfg["shared_d_ff"]
            + experts * 3 * d * cfg["d_ff"])


def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token passes through, by part."""
    d, n = cfg["d_model"], kinds(cfg)
    layers = cfg["n_layers"]
    return {
        "kda projections": n["kda"] * kda_params(cfg),
        "gqa projections": n["gqa"] * gqa_params(cfg),
        "router": layers * d * cfg["n_experts"],
        "shared expert": layers * 3 * d * cfg["shared_d_ff"],
        "experts held": layers * held_per_token(cfg) * 3 * d * cfg["d_ff"],
        "head": d * cfg["vocab_size"],
    }


def _attends(cfg: dict) -> dict:
    """The sizes ``flops``'s attention arithmetic reads, with this model's
    STATED head width (no quotient of the hidden size)."""
    return {**cfg, "d_model": cfg["n_heads"] * cfg["head_width"]}


def _rule(cfg: dict) -> dict:
    """The sizes ``flops_ling``'s delta-rule arithmetic reads."""
    return {**cfg, "n_heads": cfg["kda_heads"]}


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """By part; the sum is the model's forward."""
    out = {k: 2.0 * v for k, v in matmul_params_per_token(cfg).items()}
    n = kinds(cfg)
    # q k^T and p v over the causal half of the square, a layer
    out["attention"] = 2.0 * flops.causal_attention_unit(
        _attends(cfg), seq) * n["gqa"] / seq
    out["delta rule"] = flops_ling.delta_rule_ops_per_token(_rule(cfg)) \
        * n["kda"]
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())


def layer_params(cfg: dict, kind: str, experts: float) -> float:
    """Every parameter of one layer of ``kind`` with ``experts`` routed
    experts: the two norms, the first half with its small leaves (three
    convolutions, A_log, dt_bias, the output norm's scale), the router's
    bias and the expert layer."""
    d, w = cfg["d_model"], cfg["kda_heads"] * cfg["kda_head_dim"]
    first = gqa_params(cfg) if kind == "gqa" else (
        kda_params(cfg) + 3 * cfg["conv_taps"] * w + cfg["kda_heads"] + w
        + cfg["kda_head_dim"])
    return 2 * d + first + cfg["n_experts"] + expert_layer_params(cfg, experts)


def total_params(cfg: dict) -> int:
    """Every parameter the chip holds, at the published widths."""
    return int(sum(layer_params(cfg, k, cfg["experts_held"][0])
                   for k in cfg["kinds"])
               + 2 * cfg["vocab_size"] * cfg["d_model"] + cfg["d_model"])


def published_params(cfg: dict, config: dict) -> tuple:
    """(every parameter, those a token uses) of the WHOLE published model,
    from the same keys with the file's ``published`` numbers in place of
    the cut ones: all the router's experts a layer (``top_k`` of them a
    token), the whole vocabulary, every layer a kind by ``gqa_layers``."""
    layers = config["published"]["num_hidden_layers"]
    vocab = config["published"]["vocab_size"]
    gqa = sum(1 for at in config["gqa_layers"] if at < layers)
    whole = used = 2.0 * vocab * cfg["d_model"] + cfg["d_model"]
    for kind, n in (("gqa", gqa), ("kda", layers - gqa)):
        whole += n * layer_params(cfg, kind, cfg["n_experts"])
        used += n * layer_params(cfg, kind, cfg["top_k"])
    return whole, used


def flash_call(cfg: dict, batch: int, seq: int, which: str) -> dict:
    """One flash call of a grouped-query layer at the stated head width."""
    return flops.flash_call(_attends(cfg), batch, seq, which)


def delta_rule_layer(cfg: dict, batch: int, seq: int) -> dict:
    """What one KDA layer's recurrence needs of one step, forward and
    backward, by the equations (``flops_ling.delta_rule_layer`` at this
    model's heads): the same work whatever cut implements it."""
    return flops_ling.delta_rule_layer(_rule(cfg), batch, seq)


def grouped_matmul_call(rows: float, experts: int, cfg: dict) -> dict:
    """One grouped matmul over the ``rows`` the held experts really got,
    at the published widths [d_model, d_ff]."""
    return flops_moe.grouped_matmul_call(rows, cfg["d_model"], cfg["d_ff"],
                                         experts)
