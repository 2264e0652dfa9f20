"""Device time of one part of the model, or of one pass, as a share of
the traced window, told by the named scope each op was issued in:
``{"reader": "scope_share", "bucket": "attention"}`` or ``{"reader":
"scope_share", "pass": "replay"}``.

The step program's scopes (PERF.md 3) reach the chip trace as the stat
``tf_op`` of each op's event metadata; ``benchmark/op_scopes.py`` reads
them from the traced run's own ``.xplane.pb`` and sorts every op into
exactly one bucket (``embed``, ``attention``, ``mixer``, ``feed_forward``,
``head_loss``, ``optimizer``, ``layer_loop``: under ``layers`` and in no
half, the scan's stacks and carries; ``unscoped``: no scope of the
vocabulary, no ``op_name`` at all, or the loop instruction's own name,
which the compiler gives what it makes inside a loop's body) and one pass (``forward``,
``replay``: what the layer checkpoint runs a second time, ``backward``,
``none``). Times are ``trace_reduce``'s self times, as the other share
readers take them, so the buckets add up to the busy share. No shape is
read: a new family names its parts and needs no reader of its own.

A trace whose ops carry no scope of the vocabulary (a program without
scopes: an older commit) reads nothing, and so does a part that took no
time.
"""

from __future__ import annotations

from benchmark import op_scopes


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    if not t or not t.get("window_s"):
        return None
    labels = op_scopes.of_run()
    if labels is None:
        return None
    cells = op_scopes.table(t["device_ops"], labels)
    if cells is None:
        return None
    if ("bucket" in spec) == ("pass" in spec):
        raise ValueError(f"scope_share: one of bucket and pass, got {spec}")
    at, want = (0, spec["bucket"]) if "bucket" in spec else (1, spec["pass"])
    if want not in (op_scopes.BUCKETS, op_scopes.PASSES)[at]:
        raise ValueError(f"scope_share: unknown {want!r}")
    total = sum(s for key, s in cells.items() if key[at] == want)
    return 100.0 * total / t["window_s"] if total else None
