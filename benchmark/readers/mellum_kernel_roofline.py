"""The Mosaic kernels of the train step of a model whose attention layers
are of two kinds, each kind's share of its roofline, from the device trace
(``granite_kernel_roofline.py`` for a cell of kind ``train_mixed``).

``{"reader": "mellum_kernel_roofline", "kernel": "flash_window" |
"flash_full" | "grouped_matmul"}``. A call is told by its signature
(results, operands) and its operands' shapes, as in the readers beside
this one:

  flash forward   3 operands -> 2 results; dq 6 -> 1; dkdv 6 -> 2; q
                  [B, H, S, head width] and k [B, KV, S, head width] first,
                  the head width the model STATES (``flops.head_dim``'s
                  quotient is another number here), whichever block plan
                  the call took (the streaming dq and dkdv calls give
                  float32 results)
  grouped matmul  7 operands -> 1 result: five int32 operands, then
                  lhs [R, k] and rhs [E, ., .] -> [R, n] (forward, input
                  gradient) or [R, k] and [R, n] -> [E, k, n] (weight
                  gradient); R the rows of one pass over the held experts'
                  assignments, E the experts held, k and n the model's
                  width and one expert's

A window layer's flash calls and a full layer's have the same shapes. They
are told apart by the scope each was issued in, ``attention/window`` or
``attention/full`` (``llama._layer``), which the trace carries as the
op's ``tf_op`` (``benchmark/op_scopes.py``); a flash call under neither is
an error.

The share is the least time the chip could take for the calls seen (the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over
the time they took: ``flops_mellum.flash_call`` over the pairs the kind's
mask lets through, whatever the kernel skipped or computed, and for the
grouped matmul the rows the held experts REALLY got, a layer and step on
average (``obs["values"]["held_rows"]``, from the program's
``moe_held_rows_share``). A trace with no Mosaic call of the asked kind
reads nothing, nor does a program of another family; any Mosaic call that
is none of the above is an error, because its time would be billed to
nobody.
"""

from __future__ import annotations

from benchmark import flops, flops_mellum, op_scopes
from benchmark.readers.kernel_roofline import (FLASH, operand_shapes,
                                               signature)
from benchmark.readers.moe_kernel_roofline import GROUPED, result_shape
from benchmark.readers.scope_path_share import holds


def classify(name: str, obs: dict, labels: dict):
    """``(kernel, call)`` of one Mosaic call's HLO line: the kernel's name
    and its least operations and bytes. Raises on a call it does not
    know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq = mix["batch"], mix["seq"]
    sig, shapes = signature(name), operand_shapes(name)
    q = [[batch, sizes[n], seq, sizes["head_width"]]
         for n in ("n_heads", "n_kv_heads")]
    if sig in FLASH and shapes[:2] == q:
        parts = op_scopes.elements((labels.get(name) or {}).get("tf_op"))
        kinds = [k for k in sizes["kinds"] if holds(parts, ["attention", k])]
        if len(kinds) != 1:
            raise ValueError(
                f"a flash call under the scope of no one kind of "
                f"{sorted(sizes['kinds'])} (its path: {parts}): {name[:300]}")
        return "flash_" + kinds[0], flops_mellum.flash_call(
            sizes, batch, seq, FLASH[sig], kinds[0])
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
                return "grouped_matmul", flops_mellum.grouped_matmul_call(
                    obs["values"]["held_rows"], a[1], out[-1], e)
    raise ValueError(
        f"a Mosaic call that is no flash call of q, k {q} and no grouped "
        f"matmul of {e} experts and widths {sorted(widths)}: {name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "layer_kinds" not in (obs.get("sizes") or {}):
        return None
    labels = op_scopes.of_run() or {}
    seconds = dict(map(tuple, trace["device_ops"]))
    least = took = 0.0
    for name, calls in trace["op_calls"].items():
        if signature(name) is None:
            continue
        kernel, call = classify(name, obs, labels)
        if kernel == spec["kernel"]:
            least += calls * flops.least_seconds(call, peak)["seconds"]
            took += seconds[name]
    return 100.0 * least / took if took else None
