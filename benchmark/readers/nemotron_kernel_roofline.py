"""The Mosaic kernels of Nemotron-H's train step, each kind's share of its
roofline, from the device trace (``granite_kernel_roofline.py`` for a cell
of kind ``train_alternating``; that reader raises on a scan whose B and C
come in groups and reads attention at hidden / heads lanes a head).

``{"reader": "nemotron_kernel_roofline", "kernel": "ssd_scan" |
"flash_attention" | "grouped_matmul"}``. A call is told by its signature
(results, operands) and its operands' shapes, as in the readers beside
this one:

  scan forward    5 operands (u, B, C, the running decay twice)
                  -> 2 results; u [B, S, H P], B [B, S, G N]
  scan backward   7 operands (those, the states, dy) -> 5 results
  flash forward   3 operands -> 2 results; dq 6 -> 1; dkdv 6 -> 2;
                  q [B, H, S, HD] and k [B, KV, S, HD] first, HD the
                  STATED head width
  grouped matmul  7 operands -> 1 result: five int32 operands, then
                  lhs [R, k] and rhs [E, ., .] -> [R, n] or [R, k] and
                  [R, n] -> [E, k, n]; E the experts held, k and n the
                  model's width and one expert's AS STORED (the published
                  1,856, or wider where a program pads it)

The share is the least time the chip could take for the calls seen over
the time they took: ``flops_nemotron.ssd_call`` (``C B^T`` once a group,
the causal half of a chunk), ``flops_nemotron.flash_call``, and for the
grouped matmul the rows the held experts REALLY got at the PUBLISHED
widths (``flops_nemotron.grouped_matmul_call``), so a padded width counts
no operation nobody asked for. A trace with no Mosaic call of the asked
kind, or a program without grouped mixers (``mamba_groups`` in no sizes),
reads nothing; any Mosaic call that is none of the above is an error,
because its time would be billed to nobody.
"""

from __future__ import annotations

from benchmark import flops, flops_nemotron
from benchmark.readers.granite_kernel_roofline import SSD
from benchmark.readers.kernel_roofline import (FLASH, operand_shapes,
                                               signature)
from benchmark.readers.moe_kernel_roofline import GROUPED, result_shape


def classify(name: str, obs: dict):
    """``(kernel, call)`` of one Mosaic call's HLO line: the kernel's name
    and its least operations and bytes. Raises on a call it does not
    know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq, hd = mix["batch"], mix["seq"], sizes["head_width"]
    sig, shapes = signature(name), operand_shapes(name)
    inner = sizes["mamba_heads"] * sizes["mamba_head_dim"]
    scan = [[batch, seq, inner],
            [batch, seq, sizes["mamba_groups"] * sizes["mamba_state"]]]
    if sig in SSD and shapes[:2] == scan:
        return "ssd_scan", flops_nemotron.ssd_call(sizes, batch, seq,
                                                   SSD[sig])
    q = [[batch, sizes[n], seq, hd] for n in ("n_heads", "n_kv_heads")]
    if sig in FLASH and shapes[:2] == q:
        return "flash_attention", flops_nemotron.flash_call(
            sizes, batch, seq, FLASH[sig])
    e, d = sizes["experts_held"][0], sizes["d_model"]
    if sig == GROUPED and len(shapes) == 7:
        a, b, out = shapes[5], shapes[6], result_shape(name)
        if len(a) == 2:
            rows = a[0]
            # the expert's width as stored: the side that is not the model's
            wide = [w for w in (a[1], out[-1]) if w != d]
            stored = wide[0] if len(wide) == 1 else None
            ok = stored is not None and sizes["d_ff"] <= stored \
                < sizes["d_ff"] + 128
            product = (len(b) == 3 and b[0] == e
                       and set(b[1:]) == {d, stored}
                       and out == [rows, out[-1]])
            weight_grad = b == [rows, out[-1]] and out == [e, a[1], b[1]]
            if ok and (product or weight_grad):
                return "grouped_matmul", flops_nemotron.grouped_matmul_call(
                    obs["values"]["held_rows"], e, sizes)
    raise ValueError(
        f"a Mosaic call that is no scan call of u, B {scan}, no flash call "
        f"of q, k {q} and no grouped matmul of {e} experts of {d} x "
        f"{sizes['d_ff']}: {name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "mamba_groups" not in (obs.get("sizes")
                                                       or {}):
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
