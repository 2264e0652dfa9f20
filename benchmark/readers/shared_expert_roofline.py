"""The shared experts' share of their roofline, from the device trace:
``{"reader": "shared_expert_roofline", "path": ["feed_forward",
"shared"]}`` (a cell of kind ``train_parallel``).

The shared experts of a parallel block are plain matmuls, no kernel call
to tell by a signature: XLA's fusions under the scope ``feed_forward/
shared`` (``moe._finish``), forward, the checkpoint's replay and backward.
The share is the least time the chip could take for what their
MATHEMATICS needs of the traced steps (``flops_commanda.shared_step``: 6
operations a parameter and token, forward and backward, the replay NOT
counted, so that the count is the same whatever is recomputed, split or
joined with another product) over the device time of every op under that
path, replay and all. The time holds work the count leaves out, so the
share stays under 100% by construction. The traced steps are the
recipe's ``trace_steps`` (the kinds start the profiler before the first of
them and stop it after the last).

A trace without the path (a program without shared experts or without the
scopes), a cell whose sizes name no shared experts, or a run without a
trace reads nothing.
"""

from __future__ import annotations

from benchmark import flops, flops_commanda, op_scopes
from benchmark.readers.scope_path_share import holds


def read(spec: dict, obs: dict):
    trace, peak, sizes = obs.get("trace"), obs.get("peak"), obs.get("sizes")
    if not trace or not peak or not (sizes or {}).get("n_shared"):
        return None
    labels = op_scopes.of_run()
    if labels is None:
        return None
    path = list(spec["path"])
    took = sum(s for _, s, parts in op_scopes.labelled(trace["device_ops"],
                                                      labels)
               if holds(parts, path))
    if not took:
        return None
    mix, steps = obs["cell"]["mix"], obs["cell"]["train"].get("trace_steps", 4)
    call = flops_commanda.shared_step(sizes, mix["batch"] * mix["seq"])
    return 100.0 * steps * flops.least_seconds(call, peak)["seconds"] / took
