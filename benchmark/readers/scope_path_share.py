"""Device time of the ops issued under a path of named scopes, as a share
of the traced window: ``{"reader": "scope_path_share", "path":
["attention", "window"]}``.

``scope_share`` sorts every op into one of a fixed list of buckets, the
parts every model has. A family whose layers are of several kinds opens a
scope of the kind's name inside the part's (``attention/window``,
``attention/full``: ``llama._layer``), and this reader counts the ops whose
scope path (``benchmark/op_scopes.py``: the stat ``tf_op`` of the op's
event metadata, jax's ``jvp(...)`` and ``transpose(...)`` wrappers taken
off) holds ``path`` as adjacent parts, all passes: forward, the
checkpoint's replay, backward. Times are ``trace_reduce``'s self times, as
the other share readers take them. No shape is read.

A trace that carries no such path (a program without the scopes: another
family, an older commit) reads nothing.
"""

from __future__ import annotations

from benchmark import op_scopes


def holds(parts: list, path: list) -> bool:
    """Whether ``path`` lies in ``parts`` as adjacent scopes (the wrappers'
    markers, ``jvp(`` and the like, are no scopes)."""
    own = [p for p in parts if not p.endswith("(")]
    n = len(path)
    return any(own[i:i + n] == path for i in range(len(own) - n + 1))


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    if not t or not t.get("window_s"):
        return None
    labels = op_scopes.of_run()
    if labels is None:
        return None
    path = list(spec["path"])
    total = sum(s for _, s, parts in op_scopes.labelled(t["device_ops"],
                                                       labels)
                if holds(parts, path))
    return 100.0 * total / t["window_s"] if total else None
