"""The Mosaic kernels of Solar Open2's train step, each kind's share of its
roofline, from the device trace (a cell of kind ``train_solar``).

``{"reader": "solar_kernel_roofline", "kernel": "delta_rule" |
"flash_attention" | "grouped_matmul"}``. A Mosaic call is told by its
signature (results, operands) and its operands' shapes, as in the readers
beside this one:

  delta rule fwd  6 operands (q, k, v [B, S, H x dk], the gate likewise in
                  float32, beta, the initial state) -> 2 results (o, the
                  chunks' incoming states); bwd 7 operands (those but the
                  initial state, the states, do) -> 6 results: the calls'
                  signatures are the same whichever cut of the pair
                  products runs inside them
  flash forward   3 operands -> 2 results; dq 6 -> 1; dkdv 6 -> 2; q
                  [B, H, S, hd] and k [B, KV, S, hd] first, H query heads
                  over KV key/value heads of the stated width
  grouped matmul  7 operands -> 1 result: five int32 operands, then
                  lhs [R, k] and rhs [E, ., .] -> [R, n] or [R, k] and
                  [R, n] -> [E, k, n]; E the experts held, k and n the
                  model's width and one expert's

``delta_rule`` is a share of a LAYER's roofline and not of a call's, by the
equations and not by the form (``flops_solar.delta_rule_layer``): the least
time for one KDA layer's recurrence, forward and backward, times the layers
and steps the trace holds (the backward call runs once a layer and step),
over the device time of the rule's calls, the replay's forward with them.
The others are the least time for the calls seen over the time they took
(``flops_solar.flash_call``; for the grouped matmul the rows the held
experts REALLY got). Any Mosaic call that is none of the above is an error,
because its time would be billed to nobody.

A trace with no call of the asked kind, or a program of another family
(``gate_rank`` in no sizes), reads nothing.
"""

from __future__ import annotations

from benchmark import flops, flops_solar
from benchmark.readers.kernel_roofline import (FLASH, operand_shapes,
                                               signature)
from benchmark.readers.ling_kernel_roofline import RULE
from benchmark.readers.moe_kernel_roofline import GROUPED, result_shape


def classify(name: str, obs: dict):
    """``(kernel, which, call)`` of one Mosaic call's HLO line: the
    kernel's name, which of its calls, and (but for the delta rule) the
    call's least operations and bytes. Raises on a call it does not know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq = mix["batch"], mix["seq"]
    sig, shapes = signature(name), operand_shapes(name)
    wide = [batch, seq, sizes["kda_heads"] * sizes["kda_head_dim"]]
    if sig in RULE and shapes[:3] == [wide] * 3:
        return "delta_rule", RULE[sig], None
    heads = [[batch, sizes[n], seq, sizes["head_width"]]
             for n in ("n_heads", "n_kv_heads")]
    if sig in FLASH and shapes[:2] == heads:
        return "flash_attention", FLASH[sig], flops_solar.flash_call(
            sizes, batch, seq, FLASH[sig])
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
                return "grouped_matmul", "", flops_solar.grouped_matmul_call(
                    obs["values"]["held_rows"], e, sizes)
    raise ValueError(
        f"a Mosaic call that is no delta-rule call of q, k, v {wide}, no "
        f"flash call of q and k {heads} and no grouped matmul of {e} "
        f"experts and widths {sorted(widths)}: {name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "gate_rank" not in (obs.get("sizes") or {}):
        return None
    seconds = dict(map(tuple, trace["device_ops"]))
    least = took = layer_steps = 0.0
    for name, calls in trace["op_calls"].items():
        if signature(name) is None:
            continue
        kernel, which, call = classify(name, obs)
        if kernel != spec["kernel"]:
            continue
        took += seconds[name]
        if kernel == "delta_rule":
            layer_steps += calls if which == "bwd" else 0
        else:
            least += calls * flops.least_seconds(call, peak)["seconds"]
    if spec["kernel"] == "delta_rule":
        mix = obs["cell"]["mix"]
        least = layer_steps * flops.least_seconds(
            flops_solar.delta_rule_layer(obs["sizes"], mix["batch"],
                                         mix["seq"]), peak)["seconds"]
    return 100.0 * least / took if took and least else None
