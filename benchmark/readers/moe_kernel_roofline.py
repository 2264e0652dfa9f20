"""The Mosaic kernels of the expert model's train step, each kind's share
of its roofline, from the device trace (``kernel_roofline.py`` for a cell
of kind ``train_moe``; that reader raises on a grouped matmul).

``{"reader": "moe_kernel_roofline", "kernel": "grouped_matmul" |
"flash_attention"}``. Every executed Mosaic call is an op event whose HLO
line has ``custom_call_target="tpu_custom_call"``; the trace names none of
them after its kernel, so a call is told by its signature (results,
operands) and its operands' shapes:

  flash forward   3 operands (q, k, v)             -> 2 results (o, lse)
  flash dq        6 operands (q, k, v, do, o, lse) -> 1 result
  flash dkdv      6 operands                       -> 2 results
      q [B, H, S, HD] and k [B, KV, S, HD] first, as ``kernel_roofline``
  grouped matmul  7 operands -> 1 result: five int32 operands (the
      number of tiles, group offsets, group ids, tile ids, the first
      group), then for the forward and the input gradient
      (megablox ``gmm``) lhs [T*K, k] and rhs [E, ., .] -> [T*K, n], and
      for the weight gradient (``tgmm``) [T*K, k] and [T*K, n] ->
      [E, k, n]; T*K the cell's routed rows, E its experts, k and n the
      model's width and one expert's.

The share is the least time the chip could take for the calls seen (the
larger of operations over peak FLOP/s and bytes over peak bytes/s:
``flops.flash_call`` as ``flash_attention_roofline`` has it,
``flops_moe.grouped_matmul_call``) over the time they took. A trace with
no Mosaic call of the asked kind (a program whose grouped matmul is XLA's
``ragged_dot``) reads nothing, and the metric is left out; any Mosaic
call that is none of the above is an error, because its time would be
billed to nobody.
"""

from __future__ import annotations

import re

from benchmark import flops, flops_moe
from benchmark.readers.kernel_roofline import (FLASH, _SHAPE, operand_shapes,
                                               signature)

GROUPED = (1, 7)
_RESULT = re.compile(r" = (.*?) custom-call\(")


def result_shape(name: str) -> list:
    dims = _SHAPE.search(re.sub(r"\{[^}]*\}", "", _RESULT.search(
        name).group(1))).group(1)
    return [int(d) for d in dims.split(",") if d]


def classify(name: str, obs: dict):
    """``(kernel, call)`` of one Mosaic call's HLO line: the kernel's name
    and its least operations and bytes. Raises on a call it does not
    know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq, hd = mix["batch"], mix["seq"], flops.head_dim(sizes)
    sig, shapes = signature(name), operand_shapes(name)
    q = [[batch, sizes[n], seq, hd] for n in ("n_heads", "n_kv_heads")]
    if sig in FLASH and shapes[:2] == q:
        return "flash_attention", flops.flash_call(sizes, batch, seq,
                                                   FLASH[sig])
    rows, e = batch * seq * sizes["top_k"], sizes["n_experts"]
    widths = {sizes["d_model"], sizes["d_ff"]}
    if sig == GROUPED and len(shapes) == 7:
        a, b, out = shapes[5], shapes[6], result_shape(name)
        if len(a) == 2 and a[0] == rows and {a[1], out[-1]} == widths:
            # forward or input gradient: [rows, k] x [E, ., .] -> [rows, n]
            product = (len(b) == 3 and b[0] == e and set(b[1:]) == widths
                       and out == [rows, out[-1]])
            # weight gradient: [rows, k]^T [rows, n] -> [E, k, n]
            weight_grad = b == [rows, out[-1]] and out == [e, a[1], b[1]]
            if product or weight_grad:
                return "grouped_matmul", flops_moe.grouped_matmul_call(
                    rows, a[1], out[-1], e)
    raise ValueError(
        f"a Mosaic call that is no flash call of q, k {q} and no grouped "
        f"matmul of {rows} rows, {e} experts and widths {sorted(widths)}: "
        f"{name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak:
        return None
    seconds = dict(map(tuple, trace["device_ops"]))
    least = took = 0.0
    for name, calls in trace["op_calls"].items():
        if signature(name) is None:
            continue
        kernel, call = classify(name, obs)
        if kernel == spec["kernel"]:
            least += calls * flops.least_seconds(call, peak)["seconds"]
            took += seconds[name]
    return 100.0 * least / took if took else None
