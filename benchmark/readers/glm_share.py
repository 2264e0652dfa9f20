"""Device time of one part of the latent-attention model's step as a share
of the traced window (``mixer_share.py`` and ``held_expert_share.py`` for a
cell of kind ``train_latent``).

``{"reader": "glm_share", "part": "mla" | "experts" | "mtp_head"}``. A chip
trace carries no named scope (PERF.md 7), an op event's name is its whole
HLO line, so an op belongs to a part when that line holds, as a result or
an operand, a shape only that part has. With B x S the step's batch and
sequence (T = B S), H heads, the latents' ranks q and kv, a head's widths
nope, rope, v:

  mla       everything the attention half runs (``models/latent.py``
            ``attention_half``) and its backward: rows ``[B,S,w]`` or
            ``[T,w]`` of a width w that is the attention's own (q, kv +
            rope, kv, H (nope + rope), H (nope + v), H v), the heads' views
            ``[B,S,H,w]`` and ``[B,H,S,w]`` (w of nope + rope, nope + v, v,
            nope, rope), the shared rotary key ``[B,S,1,rope]``, the five
            projections' weights, alone or with a stack's length before
            them, and the three flash calls. The residual stream is
            ``[B,S,D]``, the experts' rows ``[R,D]`` and ``[R,F]``, the
            router ``[T,E]``: none of these.
  experts   as ``held_expert_share`` with the grouped matmuls: everything
            as wide as the rows of a pass over the held experts'
            assignments (R, read from the grouped matmuls' own first
            operand), everything T*K rows wide, and the grouped matmuls.
  mtp_head  both heads, both cross-entropies and the prediction module's
            joint projection: every op that holds the vocabulary held (V,
            in any shape) or the joined rows ``[B,S,2D]`` / ``[T,2D]`` or
            the projection's weight ``[2D,D]``.

Control flow (``while``, ``conditional``, ``call``) holds its body's ops
on the same line and is counted through them, never by its own line. A
program of another family, or a trace without the part, reads nothing.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce
from benchmark.readers.expert_share import CONTROL
from benchmark.readers.glm_kernel_roofline import classify
from benchmark.readers.kernel_roofline import operand_shapes, signature


def _alt(values) -> str:
    return "|".join(str(v) for v in sorted(set(values)))


def mla_pattern(sizes: dict, mix: dict) -> "re.Pattern":
    b, s, d, h = mix["batch"], mix["seq"], sizes["d_model"], sizes["n_heads"]
    q, kv = sizes["q_rank"], sizes["kv_rank"]
    nope, rope, v = (sizes["qk_nope_dim"], sizes["qk_rope_dim"],
                     sizes["v_dim"])
    rows = _alt(w for w in (q, kv + rope, kv, h * (nope + rope),
                            h * (nope + v), h * v) if w != d)
    heads = _alt((nope + rope, nope + v, v, nope, rope))
    weights = "|".join(f"{k},{n}" for k, n in (
        (d, q), (q, h * (nope + rope)), (d, kv + rope),
        (kv, h * (nope + v)), (h * v, d)))
    return re.compile(
        rf"\[(?:{b},{s},(?:{rows})\]|{b * s},(?:{rows})\]"
        rf"|{b},{s},{h},(?:{heads})\]|{b},{h},{s},(?:{heads})\]"
        rf"|{b},{s},1,{rope}\]|(?:\d+,)?(?:{weights})\])")


def head_pattern(sizes: dict, mix: dict) -> "re.Pattern":
    b, s, d, vocab = (mix["batch"], mix["seq"], sizes["d_model"],
                      sizes["vocab_size"])
    return re.compile(
        rf"[\[,]{vocab}[,\]]|\[{b},{s},{2 * d}\]|\[{b * s},{2 * d}\]"
        rf"|\[{2 * d},{d}\]")


def read(spec: dict, obs: dict):
    t, sizes = obs.get("trace"), obs.get("sizes") or {}
    if not t or not t.get("window_s") or "kv_rank" not in sizes:
        return None
    mix, part = obs["cell"]["mix"], spec["part"]
    kernels = {}                          # a Mosaic call's line -> its kernel
    for name in t.get("op_calls") or {}:
        if signature(name) is not None:
            kernels[name] = classify(name, obs)[0]
    if part == "mla":
        own, mine = mla_pattern(sizes, mix), "flash_attention"
    elif part == "mtp_head":
        own, mine = head_pattern(sizes, mix), None
    elif part == "experts":
        rows = {operand_shapes(n)[5][0] for n, k in kernels.items()
                if k == "grouped_matmul"}
        if not rows:
            return None
        tokens, k = mix["batch"] * mix["seq"], sizes["top_k"]
        own = re.compile(rf"\[(?:{_alt(rows)}|{tokens * k}|{tokens},{k})[,\]]")
        mine = "grouped_matmul"
    else:
        raise ValueError(f"glm_share: unknown part {part!r}")
    total = 0.0
    for name, seconds in t["device_ops"]:
        if trace_reduce.opcode(name) in CONTROL:
            continue
        if signature(name) is not None:          # a Mosaic call
            if mine is not None and kernels.get(name) == mine:
                total += seconds
            continue
        if own.search(re.sub(r"\{[^}]*\}", "", name)):
            total += seconds
    return 100.0 * total / t["window_s"] if total else None
