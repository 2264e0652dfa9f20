"""The Mosaic kernels of Falcon-H1's train step, each kind's share of its
roofline, from the device trace (a cell of kind ``train_falconh1``).

``{"reader": "falconh1_kernel_roofline", "kernel": "ssd_scan" |
"flash_attention"}``. A Mosaic call is told by its signature (results,
operands) and its operands' shapes, as in the readers beside this one:

  scan forward   5 operands (u [B, S, H P], B and C [B, S, G N], the
                 running decay in its two layouts) -> 2 results (y, the
                 chunks' incoming states); backward 7 operands (those, the
                 states, dy) -> 5 results: the signatures are the same
                 whichever layout of heads runs inside the call
  flash forward  3 operands -> 2 results; dq 6 -> 1; dkdv 6 -> 2; q
                 [B, H, S, hd] and k [B, KV, S, hd] first, H query heads
                 over KV key/value heads of the stated width

Each is the least time for the calls seen (``flops_falconh1.ssd_call``: a
group's ``C B^T`` counted once, whatever block of heads computes it;
``flops_falconh1.flash_call``) over the time they took, the replay's
forward calls with them. Any Mosaic call that is neither is an error,
because its time would be billed to nobody.

A trace with no call of the asked kind, or a program of another family
(``ssm_multipliers`` in no sizes), reads nothing.
"""

from __future__ import annotations

from benchmark import flops, flops_falconh1
from benchmark.readers.granite_kernel_roofline import SSD
from benchmark.readers.kernel_roofline import (FLASH, operand_shapes,
                                               signature)


def classify(name: str, obs: dict):
    """``(kernel, call)`` of one Mosaic call's HLO line: the kernel's name
    and its least operations and bytes. Raises on a call it does not
    know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq = mix["batch"], mix["seq"]
    sig, shapes = signature(name), operand_shapes(name)
    scan = [[batch, seq, sizes["mamba_heads"] * sizes["mamba_head_dim"]],
            [batch, seq, sizes["mamba_groups"] * sizes["mamba_state"]]]
    if sig in SSD and shapes[:2] == scan:
        return "ssd_scan", flops_falconh1.ssd_call(sizes, batch, seq,
                                                   SSD[sig])
    q = [[batch, sizes[n], seq, sizes["head_width"]]
         for n in ("n_heads", "n_kv_heads")]
    if sig in FLASH and shapes[:2] == q:
        return "flash_attention", flops_falconh1.flash_call(
            sizes, batch, seq, FLASH[sig])
    raise ValueError(f"a Mosaic call that is no scan call of u, B {scan} "
                     f"and no flash call of q, k {q}: {name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "ssm_multipliers" not in (obs.get("sizes")
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
