"""Device time of the expert layers of a chip that holds a SHARE of the
experts, as a share of the traced window (``held_expert_share.py`` for a
cell of kind ``train_shortconv``; that reader tells the grouped matmuls by
``granite_kernel_roofline.classify``, which reads a state-space mixer's
sizes this family's configuration has not).

``{"reader": "lfm2_expert_share", "with_matmuls": true | false}``: what
``held_expert_share`` counts, told the same way: everything as wide as the
rows held for this chip's experts, everything T*K rows wide, and the
grouped matmuls (``lfm2_kernel_roofline.classify``); without them the
dispatch alone. The router's matmul is [T, .] wide as the rest of the layer
is and is not in; a dense layer has none of these shapes. A trace with no
grouped matmul, or sizes without short convolutions, reads nothing.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce
from benchmark.readers.expert_share import CONTROL
from benchmark.readers.kernel_roofline import operand_shapes, signature
from benchmark.readers.lfm2_kernel_roofline import classify


def grouped(trace: dict, obs: dict) -> dict:
    """The grouped matmuls' Mosaic calls of a trace: HLO line -> rows of
    its first matrix operand."""
    out = {}
    for name in trace.get("op_calls") or {}:
        if signature(name) is None:
            continue
        try:
            kernel, _ = classify(name, obs)
        except ValueError:       # the roofline reader raises on it
            continue
        if kernel == "grouped_matmul":
            out[name] = operand_shapes(name)[5][0]
    return out


def read(spec: dict, obs: dict):
    t, sizes = obs.get("trace"), obs.get("sizes") or {}
    if not t or not t.get("window_s") or "conv_taps" not in sizes:
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
