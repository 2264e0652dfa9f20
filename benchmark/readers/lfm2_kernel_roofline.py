"""The kernels of LFM2's train step, each kind's share of its roofline,
from the device trace (``nemotron_kernel_roofline.py`` for a cell of kind
``train_shortconv``: no scan, attention at a head of 64, experts of three
matrices, and the gate-taps-gate pass of the short convolutions).

``{"reader": "lfm2_kernel_roofline", "kernel": "flash_attention" |
"grouped_matmul" | "gated_conv"}``. A Mosaic call is told by its signature
(results, operands) and its operands' shapes, as in the readers beside
this one:

  flash forward   3 operands -> 2 results; dq 6 -> 1; dkdv 6 -> 2;
                  q [B, H, S, HD] and k [B, KV, S, HD] first, HD the head
                  width of 64 or, where a program pads its heads to a lane
                  tile, anything from 64 to 128 (the least time is
                  reckoned at 64 either way: padding reads as the cost it
                  is)
  grouped matmul  7 operands -> 1 result: five int32 operands, then
                  lhs [R, k] and rhs [E, ., .] -> [R, n] or [R, k] and
                  [R, n] -> [E, k, n]; E the experts held, k and n the
                  model's width and one expert's

The share is the least time the chip could take for the calls seen over
the time they took: ``flops_lfm2.flash_call`` (the causal pairs only) and,
for the grouped matmul, the rows the held experts REALLY got
(``flops_lfm2.grouped_matmul_call``). Any Mosaic call that is none of the
above is an error, because its time would be billed to nobody.

``gated_conv`` is no Mosaic call: the pass between a short convolution's
two projections is XLA's fusions under the scopes ``short_conv`` >
``gated_conv`` (``models/hybrid.py``). Its share is the least time the
chip could take for what the pass's MATHEMATICS moves in the traced steps
(``flops_lfm2.gate_conv_step``: every operand read and every result
written once, forward and backward, the checkpoint's replay NOT counted;
memory-bound, so a share of the bandwidth roofline) over the device time
of every op under that path, replay and all: under 100% by construction.

A trace with no call or op of the asked kind, or a program without short
convolutions (``conv_taps`` in no sizes), reads nothing.
"""

from __future__ import annotations

from benchmark import flops, flops_lfm2, op_scopes
from benchmark.readers.kernel_roofline import (FLASH, operand_shapes,
                                               signature)
from benchmark.readers.moe_kernel_roofline import GROUPED, result_shape
from benchmark.readers.scope_path_share import holds

GATE_PATH = ["short_conv", "gated_conv"]


def classify(name: str, obs: dict):
    """``(kernel, call)`` of one Mosaic call's HLO line: the kernel's name
    and its least operations and bytes. Raises on a call it does not
    know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq, hd = mix["batch"], mix["seq"], sizes["head_width"]
    sig, shapes = signature(name), operand_shapes(name)
    if sig in FLASH and len(shapes) >= 2 and all(
            len(s) == 4 and s[:3] == [batch, sizes[n], seq]
            and hd <= s[3] <= max(hd, 128)
            for s, n in zip(shapes[:2], ("n_heads", "n_kv_heads"))):
        return "flash_attention", flops_lfm2.flash_call(
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
                return "grouped_matmul", flops_lfm2.grouped_matmul_call(
                    obs["values"]["held_rows"], e, sizes)
    raise ValueError(
        f"a Mosaic call that is no flash call of q [{batch}, "
        f"{sizes['n_heads']}, {seq}, {hd}..128] and k over "
        f"{sizes['n_kv_heads']} heads and no grouped matmul of {e} experts "
        f"and widths {sorted(widths)}: {name[:400]}")


def _gate(obs: dict):
    trace, peak, sizes = obs["trace"], obs["peak"], obs["sizes"]
    labels = op_scopes.of_run()
    if labels is None:
        return None
    took = sum(s for _, s, parts in op_scopes.labelled(trace["device_ops"],
                                                      labels)
               if holds(parts, GATE_PATH))
    if not took:
        return None
    mix, steps = obs["cell"]["mix"], obs["cell"]["train"].get("trace_steps", 4)
    call = flops_lfm2.gate_conv_step(sizes, mix["batch"] * mix["seq"])
    return 100.0 * steps * flops.least_seconds(call, peak)["seconds"] / took


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "conv_taps" not in (obs.get("sizes") or {}):
        return None
    if spec["kernel"] == "gated_conv":
        return _gate(obs)
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
