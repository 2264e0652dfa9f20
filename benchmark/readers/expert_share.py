"""Device time of the expert layer as a share of the traced window.

``{"reader": "expert_share", "with_matmuls": true | false}``: everything
T*K rows wide (T tokens a step, K experts a token): the grouped matmuls,
the sort of the assignments, the gathers into expert order and back, the
SwiGLU between the matmuls, the weighted sum, and their backward; with
``"with_matmuls": false`` the same without the grouped matmuls (the
dispatch alone).

How an op is told: a chip trace carries no named scope (PERF.md 7), an op
event's name is its whole HLO line, so an op belongs to the expert layer
when that line holds, as a result or an operand, a shape whose leading
dimensions are the routed rows: ``[T*K`` followed by ``,`` or ``]`` (at
this cell ``[131072,2048]``, ``[131072,1024]``, ``s32[131072]``) or
``[T,K`` (``[16384,8,2048]``, the rows seen token by token). No other
tensor of the step has such a shape: attention is ``[B,H,S,HD]``, the
router's logits ``[T,E]``, the head ``[B,S,V]``. A grouped matmul is a
Mosaic call (``tpu_custom_call``) among them, or an op whose line holds
``ragged-dot``. Control flow (``while``, ``conditional``, ``call``) holds
its body's ops on the same line and is counted through them (self times,
``trace_reduce.self_times``), never by its own line. A program without an
expert layer has no such op: the metric reads nothing.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce

CONTROL = {"while", "conditional", "call"}


def read(spec: dict, obs: dict):
    t, sizes = obs.get("trace"), obs.get("sizes") or {}
    if not t or not t.get("window_s") or "top_k" not in sizes:
        return None
    mix = obs["cell"]["mix"]
    tokens, k = mix["batch"] * mix["seq"], sizes["top_k"]
    wide = re.compile(rf"\[(?:{tokens * k}|{tokens},{k})[,\]]")
    total = 0.0
    for name, seconds in t["device_ops"]:
        if trace_reduce.opcode(name) in CONTROL or not wide.search(name):
            continue
        if not spec["with_matmuls"] and (
                "tpu_custom_call" in name or "ragged-dot" in name):
            continue
        total += seconds
    return 100.0 * total / t["window_s"] if total else None
