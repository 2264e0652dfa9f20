"""Device time of the ops issued under ANY of several paths of named
scopes, as a share of the traced window: ``{"reader": "scope_paths_share",
"paths": [["kda", "conv"], ["kda", "gate"], ["kda", "out"]]}``.
``scope_path_share`` for a quantity whose ops lie under sibling scopes (the
row work round a scan: its convolutions, its gate, its output's norm and
gate); an op is counted once whichever of the paths its scope path holds.
``"without": [["experts"]]`` leaves out the ops whose scope path holds any
of those (an expert layer whose ``combine`` encloses the walk's
``dispatch`` and ``experts``: the layer without its grouped matmuls). A
trace that carries none of the paths reads nothing.
"""

from __future__ import annotations

from benchmark import op_scopes
from benchmark.readers.scope_path_share import holds


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    if not t or not t.get("window_s"):
        return None
    labels = op_scopes.of_run()
    if labels is None:
        return None
    paths = [list(p) for p in spec["paths"]]
    without = [list(p) for p in spec.get("without", ())]
    total = sum(s for _, s, parts in op_scopes.labelled(t["device_ops"],
                                                       labels)
                if any(holds(parts, p) for p in paths)
                and not any(holds(parts, p) for p in without))
    return 100.0 * total / t["window_s"] if total else None
