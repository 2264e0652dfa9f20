"""Every device op issued under a path of named scopes, by what it is.

    python tests/ops_under_scope.py feed_forward router
        [--trace TRACE_DIR | FILE.xplane.pb] [--unscoped 'scatter|sort']

after a cell's ``--trace 1`` run (``benchmark/out/trace`` unless given),
from the root of the checkout: the ops whose scope path holds the given
scopes as adjacent parts (``benchmark/readers/scope_path_share.py``
``holds``), summed by class (sort, top-k call, scatter, gather, reduce,
dot, other: by the opcode, the fusion's name, ``hlo_category`` and the
primitives its ``op_name`` lists), pass and result shape, in ms a step and
calls a step, then the largest single ops. ``--unscoped REGEX`` adds the
ops under NO part of the model whose HLO line matches (a router's top-k
gradient scatter carries no scope: PERF.md 5). PERF.md 5's router table
is made from it. A reader by hand, beside ``python3 -m
benchmark.op_scopes``, whose functions it uses; no metric.
"""

import argparse
import os
import re
import sys

sys.path.insert(0, os.getcwd())

from benchmark import op_scopes, trace_reduce  # noqa: E402
from benchmark.readers.scope_path_share import holds  # noqa: E402

# the first class whose pattern the op's opcode, name, category or
# primitives hold; a fusion is named after what it was merged from
CLASSES = (("sort", re.compile(r"\bsort\b|sort[._]")),
           ("top-k call", re.compile(r"top_?k|TopK|approx", re.I)),
           ("scatter", re.compile(r"scatter")),
           ("gather", re.compile(r"gather|take_along|dynamic_slice")),
           ("dot", re.compile(r"\bdot\b|dot_general|convolution")),
           ("reduce", re.compile(r"reduce|argm(ax|in)|cumsum|cumlogsumexp")),
           ("copy", re.compile(r"\bcopy\b|transpose|bitcast")))
_SHAPE = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\])")


def classify(name: str, label: dict) -> str:
    said = " ".join((trace_reduce.short_name(name),
                     label.get("hlo_category", ""),
                     " ".join(p.rsplit("/", 1)[-1] for p in
                              (label.get("tf_op") or "").split(";"))))
    return next((c for c, pat in CLASSES if pat.search(said)), "other")


def report(path: list, where: str, unscoped: str = None) -> None:
    file = where if os.path.isfile(where) else trace_reduce.find_xplane(where)
    devices, _ = trace_reduce.read_planes(file)
    red = trace_reduce.reduce_planes(
        [trace_reduce.reduce_plane(o, m) for o, m in devices])
    labels = op_scopes.read_file(file)
    program, steps = op_scopes._steps(devices[0][1])
    ms = lambda s: 1e3 * s / steps                              # noqa: E731
    print(f"{file}: {steps} executions of {program}, busy "
          f"{ms(red['busy_s']):.2f} ms a step; ops under "
          f"{' > '.join(path)}" + (f", and ops under no part that match "
                                   f"{unscoped!r}" if unscoped else ""))
    extra = re.compile(unscoped) if unscoped else None
    rows, single = {}, []
    for name, s, parts in op_scopes.labelled(red["device_ops"], labels):
        label = labels.get(name) or {}
        if holds(parts, path):
            where_ = "scope"
        elif (extra is not None and op_scopes.bucket(parts) == "unscoped"
              and extra.search(name)):
            where_ = "unscoped"
        else:
            continue
        shape = _SHAPE.search(name)
        key = (where_, classify(name, label), op_scopes.which_pass(parts),
               shape.group(1) if shape else "?")
        at = rows.setdefault(key, [0.0, 0])
        at[0] += s
        at[1] += red["op_calls"].get(name, 0)
        single.append((s, where_, name, label))
    print(f"{'where':<10}{'class':<12}{'pass':<10}{'result':<24}"
          f"{'ms/step':>9}{'calls/step':>11}")
    for key, (s, calls) in sorted(rows.items()):
        print("".join(f"{k:<{w}}" for k, w in zip(key, (10, 12, 10, 24)))
              + f"{ms(s):9.3f}{calls / steps:11.1f}")
    print("by class, ms a step (scope | unscoped):")
    for c in [c for c, _ in CLASSES] + ["other"]:
        inside, outside = (sum(v[0] for k, v in rows.items()
                               if k[0] == w and k[1] == c)
                           for w in ("scope", "unscoped"))
        if inside or outside:
            print(f"  {c:<12}{ms(inside):9.3f}{ms(outside):9.3f}")
    print(f"  {'all':<12}"
          + "".join(f"{ms(sum(v[0] for k, v in rows.items() if k[0] == w)):9.3f}"
                    for w in ("scope", "unscoped")))
    print("largest ops, ms a step (calls a step)")
    for s, where_, name, label in sorted(single, key=lambda r: -r[0])[:40]:
        calls = red["op_calls"].get(name, 0) / steps
        print(f"  {ms(s):8.3f} ({calls:4.1f}) {where_:<9}"
              f"{trace_reduce.short_name(name)} "
              f"{(_SHAPE.search(name) or [None, '?'])[1]} "
              f"[{label.get('hlo_category', '')}] "
              f"{(label.get('tf_op') or 'no op_name')[-150:]}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("scopes", nargs="+", help="the scope path")
    ap.add_argument("--trace", default=op_scopes.TRACE_DIR)
    ap.add_argument("--unscoped", default=None)
    a = ap.parse_args()
    report(a.scopes, a.trace, a.unscoped)
