"""A Pallas kernel's share of its roofline, from the device trace.

``{"reader": "kernel_roofline", "kernel": "flash_attention" |
"paged_attention"}``. Every executed Mosaic call is an op event whose HLO
line has ``custom_call_target="tpu_custom_call"``; the trace names none of
them after its kernel, so a call is told by its signature, the number of
results and operands:

  flash forward   3 operands (q, k, v)            -> 2 results (o, lse)
  flash dq        6 operands (q, k, v, do, o, lse) -> 1 result
  flash dkdv      6 operands                       -> 2 results
  paged decode    7 operands (table, lengths, q, k, v, pools) -> 3 results

The share is the least time the chip could take for the calls seen (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
``flops.py`` at the shapes this device ran) over the time they took. A
flash call's first two operands must also have the shapes this device runs
of the cell, q ``[B, H, S, HD]`` and k ``[B, KV, S, HD]``. A trace with no
Mosaic call reads nothing, and the metric is left out; a Mosaic call in a
train program that is no flash call of those shapes is an error, because
its time would be billed to nobody or to flash at flash's operations.
"""

from __future__ import annotations

import re

from benchmark import flops

_CALL = re.compile(r" = (.*?) custom-call\((.*?)\), custom_call_target="
                   r'"tpu_custom_call"')
FLASH = {(2, 3): "fwd", (1, 6): "dq", (2, 6): "dkdv"}
PAGED = (3, 7)


_SHAPE = re.compile(r"\[([\d,]*)\]")


def signature(name: str):
    m = _CALL.search(name)
    if not m:
        return None
    return m.group(1).count("["), m.group(2).count("%")


def operand_shapes(name: str) -> list:
    """``[[3, 32, 4096, 128], ...]`` of a Mosaic call's operands."""
    return [[int(d) for d in dims.split(",") if d]
            for dims in _SHAPE.findall(
                re.sub(r"\{[^}]*\}", "", _CALL.search(name).group(2)))]


def _local(cell: dict, sizes: dict) -> tuple:
    """What one device runs of the cell's batch and heads under its mesh:
    the batch is split over the data axes, the heads over ``tp``."""
    mesh = cell.get("train", {}).get("mesh", {})
    data = 1
    for axis in ("dp", "fsdp"):
        data *= max(mesh.get(axis, 1), 1)
    tp = max(mesh.get("tp", 1), 1)
    local = dict(sizes, n_heads=sizes["n_heads"] // tp,
                 n_kv_heads=max(sizes["n_kv_heads"] // tp, 1),
                 d_model=sizes["d_model"] // tp)
    return local, cell["mix"]["batch"] // data


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak:
        return None
    seconds = dict(map(tuple, trace["device_ops"]))
    least = took = 0.0
    for name, calls in trace["op_calls"].items():
        sig = signature(name)
        if sig is None:
            continue
        if spec["kernel"] == "flash_attention":
            sizes, batch = _local(obs["cell"], obs["sizes"])
            seq, hd = obs["cell"]["mix"]["seq"], flops.head_dim(obs["sizes"])
            want = [[batch, sizes[n], seq, hd]
                    for n in ("n_heads", "n_kv_heads")]
            if sig not in FLASH or operand_shapes(name)[:2] != want:
                raise ValueError(
                    f"a Mosaic call that is no flash call of q, k {want}: "
                    f"{name[:300]}")
            call = flops.flash_call(sizes, batch, seq, FLASH[sig])
        elif spec["kernel"] == "paged_attention" and sig == PAGED:
            lens = obs.get("values", {}).get("mean_context_lens")
            if not lens:
                return None
            call = flops.paged_decode_call(obs["sizes"], lens)
        else:
            continue
        least += calls * flops.least_seconds(call, peak)["seconds"]
        took += seconds[name]
    return 100.0 * least / took if took else None
