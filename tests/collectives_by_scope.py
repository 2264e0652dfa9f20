"""Which collectives sat on the core's op line, by the scope that issued them.

    python tests/collectives_by_scope.py [TRACE_DIR | FILE.xplane.pb]

after a cell's ``--trace 1`` run (``benchmark/out/trace`` unless given),
from the root of the checkout: milliseconds a step and calls a step of
every device op that is a collective by its opcode, its name or its
``hlo_category``, so also the ones ``collective_exposed_share`` cannot see
(``fusion ... calls=%all-reduce-scatter``, the ``async-collective-start`` /
``-done`` fusions of an async gather: ROADMAP.md D22), by kind, part of
the model, pass and kernel-call scope (``tp.overlap``, ``tp.gradient``).
PERF.md 5's four-chip paragraph is made from it. A reader by hand, beside
``python3 -m benchmark.op_scopes``, whose functions it uses; no metric.
"""

import os
import re
import sys

sys.path.insert(0, os.getcwd())

from benchmark import op_scopes, trace_reduce  # noqa: E402

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective")


def report(where: str) -> None:
    path = where if os.path.isfile(where) else trace_reduce.find_xplane(where)
    devices, _ = trace_reduce.read_planes(path)
    red = trace_reduce.reduce_planes(
        [trace_reduce.reduce_plane(o, m) for o, m in devices])
    labels = op_scopes.read_file(path)
    program, steps = op_scopes._steps(devices[0][1])
    ms = lambda s: 1e3 * s / steps                              # noqa: E731
    print(f"{path}: {red['devices']} device(s), {steps} executions of "
          f"{program}, busy {ms(red['busy_s']):.2f} ms a step")
    rows, kinds = {}, {}
    for name, s, parts in op_scopes.labelled(red["device_ops"], labels):
        category = (labels.get(name) or {}).get("hlo_category", "")
        opcode = trace_reduce.opcode(name)
        if not COLLECTIVE.search(" ".join(
                (category, opcode, trace_reduce.short_name(name)))):
            continue
        kind = f"{opcode} [{category}]"
        at = rows.setdefault(
            (kind, op_scopes.bucket(parts), op_scopes.which_pass(parts),
             op_scopes.kernel_scope(parts) or "-"), [0.0, 0])
        at[0] += s
        at[1] += red["op_calls"].get(name, 0)
        kinds[kind] = kinds.get(kind, 0.0) + s
    print(f"{'kind':<52}{'part':<14}{'pass':<10}{'scope':<14}"
          f"{'ms/step':>9}{'calls/step':>11}")
    for (kind, part, which, scope), (s, calls) in sorted(rows.items()):
        print(f"{kind:<52}{part:<14}{which:<10}{scope:<14}"
              f"{ms(s):9.2f}{calls / steps:11.1f}")
    print("by kind, ms a step:")
    for kind, s in sorted(kinds.items()):
        print(f"  {kind:<52}{ms(s):9.2f}")
    print(f"  {'all':<52}{ms(sum(kinds.values())):9.2f}")


if __name__ == "__main__":
    report(sys.argv[1] if len(sys.argv) > 1 else op_scopes.TRACE_DIR)
