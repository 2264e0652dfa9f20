"""The Mosaic kernels of the learned-sparse-attention model's train step,
each kind's share of its roofline, from the device trace
(``glm_kernel_roofline.py`` for a cell of kind ``train_sparse``).

``{"reader": "glm52_kernel_roofline", "kernel": "sparse_attention" |
"grouped_matmul"}``. A call is told by its signature (results, operands)
and its operands' shapes, as in the readers beside this one:

  sparse forward  4 operands (q, k, v, the set) -> 2 results (o, lse)
  sparse probs    4 operands (q, k, the set, lse) -> 1 result (P)
  sparse dq       7 operands (q, k, v, the set, dO, o, lse) -> 1 result
  sparse dkdv     7 operands -> 2 results; q and k both
                  [B, H, S, qk_nope + qk_rope] first, the set [B, S, S]
                  among the operands
  grouped matmul  7 operands -> 1 result: five int32 operands, then
                  lhs [R, k] and rhs [E, ., .] -> [R, n] (forward, input
                  gradient) or [R, k] and [R, n] -> [E, k, n] (weight
                  gradient), as ``glm_kernel_roofline.py`` has it

``sparse_attention`` is a share of a LAYER's roofline and not of a call's:
the least time for one layer's attention over its sets, forward and
backward (``flops_glm52.sparse_attention_layer``: the SELECTED pairs'
operations, every operand read and every result written once; the larger
of the two times), times the layers and steps the trace holds (the dK/dV
call runs once a layer and step), over the device time of EVERYTHING under
the scope ``attention/sparse``, the replay's forward and the head-mean
probabilities with it. So the count is the same whatever form computes the
attention, and a form that walks pairs outside the sets, gathers, or
replays reads the lower for it. ``grouped_matmul`` is as the GLM-4.7-Flash
cell's: the rows the held experts REALLY got
(``obs["values"]["held_rows"]``).

A trace with no Mosaic call of the asked kind reads nothing, nor does a
program of another family (its ``sizes`` have no ``index_topk``), nor one
whose trace carries no scopes; any Mosaic call that is none of the above
is an error, because its time would be billed to nobody.
"""

from __future__ import annotations

from benchmark import flops, flops_glm52, op_scopes
from benchmark.readers import glm_kernel_roofline
from benchmark.readers.kernel_roofline import operand_shapes, signature
from benchmark.readers.scope_path_share import holds

SPARSE = {(2, 4): "fwd", (1, 4): "probs", (1, 7): "dq", (2, 7): "dkdv"}


def classify(name: str, obs: dict):
    """``(kernel, which)`` of one Mosaic call's HLO line: "sparse_attention"
    with the call's name, or "grouped_matmul" with its least operations
    and bytes. Raises on a call it does not know."""
    sizes, mix = obs["sizes"], obs["cell"]["mix"]
    batch, seq = mix["batch"], mix["seq"]
    sig, shapes = signature(name), operand_shapes(name)
    q = [[batch, sizes["n_heads"], seq,
          sizes["qk_nope_dim"] + sizes["qk_rope_dim"]]] * 2
    if sig in SPARSE and shapes[:2] == q and [batch, seq, seq] in shapes:
        return "sparse_attention", SPARSE[sig]
    try:        # the grouped matmuls are the GLM-4.7-Flash cell's, told there
        kernel, call = glm_kernel_roofline.classify(name, obs)
    except ValueError:
        kernel = None
    if kernel == "grouped_matmul":
        return kernel, call
    raise ValueError(
        f"a Mosaic call that is no sparse-attention call of q, k {q} over "
        f"a set and no grouped matmul of {sizes['experts_held'][0]} experts "
        f"and widths {sorted({sizes['d_model'], sizes['d_ff']})}: "
        f"{name[:400]}")


def read(spec: dict, obs: dict):
    trace, peak = obs.get("trace"), obs.get("peak")
    if not trace or not peak or "index_topk" not in (obs.get("sizes") or {}):
        return None
    seconds = dict(map(tuple, trace["device_ops"]))
    least = took = layer_steps = 0.0
    for name, calls in trace["op_calls"].items():
        if signature(name) is None:
            continue
        kernel, call = classify(name, obs)
        if kernel == "sparse_attention":
            layer_steps += calls if call == "dkdv" else 0
        elif kernel == spec["kernel"]:
            least += calls * flops.least_seconds(call, peak)["seconds"]
            took += seconds[name]
    if spec["kernel"] == "sparse_attention":
        labels = op_scopes.of_run()
        if labels is None or not layer_steps:
            return None
        mix = obs["cell"]["mix"]
        layer = flops_glm52.sparse_attention_layer(obs["sizes"], mix["batch"],
                                                   mix["seq"])
        least = layer_steps * flops.least_seconds(layer, peak)["seconds"]
        took = sum(s for _, s, parts in op_scopes.labelled(
            trace["device_ops"], labels) if holds(parts, ["attention",
                                                          "sparse"]))
    return 100.0 * least / took if took else None
