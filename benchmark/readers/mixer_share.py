"""Device time of the state-space mixers as a share of the traced window.

``{"reader": "mixer_share"}``: everything the Mamba-2 half of a block runs
(``models/hybrid.py`` ``mixer_half``): the two projections and their
gradients, the causal convolution, the scan's Mosaic calls, the gated
norm, and their backward.

How an op is told: a chip trace carries no named scope (PERF.md 7), an op
event's name is its whole HLO line, so an op belongs to the mixer when
that line holds, as a result or an operand, a shape only the mixer has.
With B x S the step's batch and sequence (T = B S), H heads of width P
(H P = inner), state N: rows ``[B,S,w]`` or ``[T,w]`` of a width w that
is the mixer's own, inner + 2 N (the convolution's channels), 2 inner +
2 N + H (the first projection's output) or inner; the heads' view
``[B,S,H,P]``; the steps ``[B,S,H]`` and ``[B,H,S]``; the convolution's
padded rows ``[B,S+taps-1,`` ...; a projection's weight ``[D,2 inner + 2 N
+ H]`` or ``[inner,D]``, alone or with the length of a run of mamba layers
before it. The
rest of the step has none of these: the residual stream is ``[B,S,D]``,
attention ``[B,H',S,HD]``, the experts' rows ``[R,D]`` and ``[R,F]``, the
head ``[B,S,V]``. (Where inner happens to equal D or S the row shapes are
left to the others; at the cell's sizes S = inner = 8192 and the shape
``[B,S,inner]`` names both dimensions.) The scan's Mosaic calls are told
as ``granite_kernel_roofline`` tells them. Control flow (``while``,
``conditional``, ``call``) holds its body's ops on the same line and is
counted through them, never by its own line. A program without a mixer
has no such op: the metric reads nothing.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce
from benchmark.readers.expert_share import CONTROL
from benchmark.readers.granite_kernel_roofline import SSD
from benchmark.readers.kernel_roofline import operand_shapes, signature


def patterns(sizes: dict, mix: dict) -> "re.Pattern":
    b, s, d = mix["batch"], mix["seq"], sizes["d_model"]
    h, p, n = (sizes["mamba_heads"], sizes["mamba_head_dim"],
               sizes["mamba_state"])
    inner = h * p
    conv, proj = inner + 2 * n, 2 * inner + 2 * n + h
    own = [w for w in (inner, conv, proj) if w not in (d,)]
    rows = "|".join(str(w) for w in own)
    pads = s + sizes["mamba_conv"] - 1
    # a weight's stack has a run's length before it: the lengths of the
    # runs of mamba layers, but for one that reads as [B,S,D] would
    runs, n_run = set(), 0
    for kind in tuple(sizes["layer_types"]) + ("end",):
        if kind == "mamba":
            n_run += 1
        elif n_run:
            runs.add(n_run)
            n_run = 0
    if inner == s:
        runs.discard(b)
    lead = "(?:(?:" + "|".join(str(r) for r in sorted(runs)) + "),)?"
    return re.compile(
        rf"\[(?:{b},{s},(?:{rows})\]|{b * s},(?:{rows})\]"
        rf"|{b},{s},{h},{p}\]|{b},{s},{h}\]|{b},{h},{s}\]|{b},{pads},"
        rf"|{lead}{d},{proj}\]|{lead}{inner},{d}\])")


def read(spec: dict, obs: dict):
    t, sizes = obs.get("trace"), obs.get("sizes") or {}
    if not t or not t.get("window_s") or "mamba_heads" not in sizes:
        return None
    mix = obs["cell"]["mix"]
    own = patterns(sizes, mix)
    scan = [mix["batch"], mix["seq"],
            sizes["mamba_heads"] * sizes["mamba_head_dim"]]
    total = 0.0
    for name, seconds in t["device_ops"]:
        if trace_reduce.opcode(name) in CONTROL:
            continue
        if signature(name) is not None:          # a Mosaic call
            if signature(name) in SSD and operand_shapes(name)[0] == scan:
                total += seconds
            continue
        if own.search(re.sub(r"\{[^}]*\}", "", name)):
            total += seconds
    return 100.0 * total / t["window_s"] if total else None
