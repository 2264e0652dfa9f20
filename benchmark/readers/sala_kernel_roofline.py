"""The Mosaic kernels of the sparse-attention / linear-attention hybrid's
train step, each mechanism's share of its roofline, from the device trace
(a cell of kind ``train_blockset``).

``{"reader": "sala_kernel_roofline", "kernel": "block_sparse_attention" |
"ssd_scan"}``. A call is told by its signature (results, operands) and its
operands' shapes, as in the readers beside this one:

  sparse forward  4 operands (q, k, v, the set) -> 2 results (o, lse)
  sparse dq       7 operands (q, k, v, the set, dO, o, lse) -> 1 result
  sparse dkdv     7 operands -> 2 results; q [B, H, S, HD] and k
                  [B, KV, S, HD] first, the set [B, KV, S, S / block]
                  among the operands
  scan forward    4 operands (the heads' rates [LH], u, B, C
                  [B, S, LH x HD]) -> 2 results (y, the chunks' states)
  scan backward   6 operands (those, the states, dy) -> 3 results

Both are shares of a LAYER's roofline and not of a call's, by the
equations and not by the form (``flops_sala.py``): the least time for one
sparse layer's attention over its SELECTED pairs, forward and backward
(``block_sparse_attention_layer``), times the layers and steps the trace
holds (the dK/dV call runs once a layer and step), over the device time of
EVERYTHING under the scope ``sparse/block_sparse`` (the calls and what
stands round them); and the least time for one lightning layer's
RECURRENCE, forward and backward (``lightning_layer``), times the layers
and steps (the backward call runs once a layer and step), over the device
time of the scan's calls, the replay's forward with them. So a walk that
computes pairs outside the sets, a chunk that multiplies more than the
recurrence, and a replay each read the lower for it.

A trace with no Mosaic call of the asked kind reads nothing, nor does a
program of another family (its ``sizes`` have no ``sparse_topk``), nor,
for the attention, one whose trace carries no scopes; any Mosaic call that
is none of the above is an error, because its time would be billed to
nobody.
"""

from __future__ import annotations

from benchmark import flops, flops_sala, op_scopes
from benchmark.readers.kernel_roofline import operand_shapes, signature
from benchmark.readers.scope_path_share import holds

SPARSE = {(2, 4): "fwd", (1, 7): "dq", (2, 7): "dkdv"}
SCAN = {(2, 4): "fwd", (3, 6): "bwd"}
SCOPE = ["sparse", "block_sparse"]


def classify(name: str, obs: dict):
    """``(kernel, which)`` of one Mosaic call's HLO line. Raises on a call
    it does not know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq, hd = mix["batch"], mix["seq"], sizes["head_width"]
    sig, shapes = signature(name), operand_shapes(name)
    q = [[batch, sizes[n], seq, hd] for n in ("n_heads", "n_kv_heads")]
    the_set = [batch, sizes["n_kv_heads"], seq, seq // sizes["sparse_block"]]
    if sig in SPARSE and shapes[:2] == q and the_set in shapes:
        return "block_sparse_attention", SPARSE[sig]
    lh = sizes["lightning_heads"]
    scan = [[lh], [batch, seq, lh * hd]]
    if sig in SCAN and shapes[:2] == scan:
        return "ssd_scan", SCAN[sig]
    raise ValueError(
        f"a Mosaic call that is no sparse-attention call of q, k {q} over "
        f"a set {the_set} and no scan call of rates and u {scan}: "
        f"{name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "sparse_topk" not in (obs.get("sizes") or {}):
        return None
    seconds = dict(map(tuple, trace["device_ops"]))
    took = layer_steps = 0.0
    for name, calls in trace["op_calls"].items():
        if signature(name) is None:
            continue
        kernel, which = classify(name, obs)
        if kernel != spec["kernel"]:
            continue
        took += seconds[name]
        layer_steps += calls if which in ("dkdv", "bwd") else 0
    if not layer_steps:
        return None
    mix = obs["cell"]["mix"]
    if spec["kernel"] == "block_sparse_attention":
        labels = op_scopes.of_run()
        if labels is None:
            return None
        layer = flops_sala.block_sparse_attention_layer(
            obs["sizes"], mix["batch"], mix["seq"])
        took = sum(s for _, s, parts in op_scopes.labelled(
            trace["device_ops"], labels) if holds(parts, SCOPE))
    else:
        layer = flops_sala.lightning_layer(obs["sizes"], mix["batch"],
                                           mix["seq"])
    least = layer_steps * flops.least_seconds(layer, peak)["seconds"]
    return 100.0 * least / took if took else None
