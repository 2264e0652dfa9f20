"""The Mosaic kernels of the latent-attention model's train step, each
kind's share of its roofline, from the device trace
(``granite_kernel_roofline.py`` for a cell of kind ``train_latent``).

``{"reader": "glm_kernel_roofline", "kernel": "flash_attention" |
"grouped_matmul"}``. A call is told by its signature (results, operands)
and its operands' shapes, as in the readers beside this one:

  flash forward   3 operands -> 2 results; dq 6 -> 1; dkdv 6 -> 2; q and
                  k both [B, H, S, qk_nope + qk_rope] first (the expanded
                  form: as many key heads as query heads, 256 wide at
                  GLM-4.7-Flash's sizes), whichever block plan the call
                  took (``loop`` or ``stream``, ``resident`` or ``stream``:
                  the streaming dq and dkdv calls give float32 results)
  grouped matmul  7 operands -> 1 result: five int32 operands, then
                  lhs [R, k] and rhs [E, ., .] -> [R, n] (forward, input
                  gradient) or [R, k] and [R, n] -> [E, k, n] (weight
                  gradient); R the rows of one pass over the held experts'
                  assignments, E the experts held, k and n the model's
                  width and one expert's

The share is the least time the chip could take for the calls seen (the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over
the time they took: ``flops_glm.flash_call``, and for the grouped matmul
the rows the held experts REALLY got, a layer and step on average
(``obs["values"]["held_rows"]``, from the program's
``moe_held_rows_share``). A trace with no Mosaic call of the asked kind
reads nothing, nor does a program of another family; any Mosaic call that
is none of the above is an error, because its time would be billed to
nobody.
"""

from __future__ import annotations

from benchmark import flops, flops_glm
from benchmark.readers.kernel_roofline import (FLASH, operand_shapes,
                                               signature)
from benchmark.readers.moe_kernel_roofline import GROUPED, result_shape


def classify(name: str, obs: dict):
    """``(kernel, call)`` of one Mosaic call's HLO line: the kernel's name
    and its least operations and bytes. Raises on a call it does not
    know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq = mix["batch"], mix["seq"]
    sig, shapes = signature(name), operand_shapes(name)
    q = [[batch, sizes["n_heads"], seq,
          sizes["qk_nope_dim"] + sizes["qk_rope_dim"]]] * 2
    if sig in FLASH and shapes[:2] == q:
        return "flash_attention", flops_glm.flash_call(sizes, batch, seq,
                                                       FLASH[sig])
    e = sizes["experts_held"][0]
    widths = {sizes["d_model"], sizes["d_ff"]}
    if sig == GROUPED and len(shapes) == 7:
        a, b, out = shapes[5], shapes[6], result_shape(name)
        if len(a) == 2 and {a[1], out[-1]} == widths:
            rows = a[0]
            product = (len(b) == 3 and b[0] == e and set(b[1:]) == widths
                       and out == [rows, out[-1]])
            weight_grad = b == [rows, out[-1]] and out == [e, a[1], b[1]]
            if product or weight_grad:
                return "grouped_matmul", flops_glm.grouped_matmul_call(
                    obs["values"]["held_rows"], a[1], out[-1], e)
    raise ValueError(
        f"a Mosaic call that is no flash call of q, k {q} and no grouped "
        f"matmul of {e} experts and widths {sorted(widths)}: {name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "kv_rank" not in (obs.get("sizes") or {}):
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
