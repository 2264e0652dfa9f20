"""The shared expert's share of its roofline, from the device trace, for
an expert of TWO matrices (``shared_expert_roofline.py`` for a cell of kind
``train_alternating``; that reader counts a SwiGLU's three):
``{"reader": "nemotron_shared_roofline", "path": ["feed_forward",
"shared"]}``.

The least time the chip could take for what the shared expert's
MATHEMATICS needs of the traced steps (``flops_nemotron.shared_step``: 6
operations a parameter and token over up and down, the replay NOT counted)
over the device time of every op under that path, replay and all: under
100% by construction. A trace without the path, sizes without grouped
mixers (another family's) or a run without a trace reads nothing.
"""

from __future__ import annotations

from benchmark import flops, flops_nemotron, op_scopes
from benchmark.readers.scope_path_share import holds


def read(spec: dict, obs: dict):
    trace, peak, sizes = obs.get("trace"), obs.get("peak"), obs.get("sizes")
    if not trace or not peak or "mamba_groups" not in (sizes or {}) \
            or not sizes.get("shared_d_ff"):
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
    call = flops_nemotron.shared_step(sizes, mix["batch"] * mix["seq"])
    return 100.0 * steps * flops.least_seconds(call, peak)["seconds"] / took
