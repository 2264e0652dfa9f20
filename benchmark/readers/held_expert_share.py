"""Device time of the expert layer of a chip that holds a SHARE of the
experts, as a share of the traced window (``expert_share.py`` for a cell
of kind ``train_hybrid``: ``models/moe.py`` ``_held_experts``).

``{"reader": "held_expert_share", "with_matmuls": true | false}``:
everything as wide as the rows held for this chip's experts (the gathers
into expert order, the SwiGLU between the grouped matmuls, the weighting,
the scatter-adds back into token order, and their backward), everything
T*K rows wide (the sort of ALL the assignments, the counts, the routes),
and the grouped matmuls; with ``"with_matmuls": false`` the same without
the grouped matmuls (the dispatch alone). The router's matmul and the
shared SwiGLU are [T, .] wide as the rest of the block is and are not in.

How an op is told: as ``expert_share`` tells it, by a shape in its HLO
line whose leading dimension is the rows held, ``[R`` followed by ``,`` or
``]``, or the routed rows, ``[T*K`` or ``[T,K``. R is not the program's to
say: it is read from the trace, the rows of the grouped matmuls' own first
operand (``granite_kernel_roofline.classify``). A trace with no grouped
matmul (a program of another family, or the parent's) reads nothing.
Control flow (``while``, ``conditional``, ``call``) is counted through the
ops of its body, never by its own line.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce
from benchmark.readers.expert_share import CONTROL
from benchmark.readers.granite_kernel_roofline import classify
from benchmark.readers.kernel_roofline import operand_shapes, signature


def grouped(trace: dict, obs: dict) -> dict:
    """The grouped matmuls' Mosaic calls of a trace: HLO line -> rows of
    its first matrix operand."""
    out = {}
    for name in trace.get("op_calls") or {}:
        if signature(name) is None:
            continue
        try:
            kernel, _ = classify(name, obs)
        except ValueError:       # the roofline readers raise on it
            continue
        if kernel == "grouped_matmul":
            out[name] = operand_shapes(name)[5][0]
    return out


def read(spec: dict, obs: dict):
    t, sizes = obs.get("trace"), obs.get("sizes") or {}
    if not t or not t.get("window_s") or "experts_held" not in sizes:
        return None
    calls = grouped(t, obs)
    if not calls:
        return None
    mix = obs["cell"]["mix"]
    tokens, k = mix["batch"] * mix["seq"], sizes["top_k"]
    rows = "|".join(str(r) for r in sorted(set(calls.values())))
    wide = re.compile(rf"\[(?:{rows}|{tokens * k}|{tokens},{k})[,\]]")
    total = 0.0
    for name, seconds in t["device_ops"]:
        if trace_reduce.opcode(name) in CONTROL:
            continue
        if signature(name) is not None:          # a Mosaic call
            if spec["with_matmuls"] and name in calls:
                total += seconds
            continue
        if wide.search(re.sub(r"\{[^}]*\}", "", name)):
            total += seconds
    return 100.0 * total / t["window_s"] if total else None
