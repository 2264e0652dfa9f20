"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
one named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation (name, start, duration in ns) and ``XLA Modules``
one per executed program. From those:

  busy_s            union of the op intervals, averaged over device planes
  window_s          the traced window (given by the caller, who started
                    and stopped the trace), else first start to last end
  device_ops        time per operation name, summed over executions,
                    averaged over device planes
  idle_gaps         gaps between op intervals, named by the programs on
                    either side (``a -> b``; ``in a`` inside one program)
  collective_exposed_s   time in which a collective op runs on the device
                    and no other op does
  op_calls          executions per operation name

Pure interval arithmetic below ``reduce_planes`` so that it can be checked
on a small recorded trace (``tests/``) and on synthetic planes.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv)")
# On the chip an op event's name is its whole HLO line:
#   %fusion.167 = (f32[3,4096]{...}, ...) fusion(bf16[...] %copy-done.11,
#   ...), kind=kOutput, ...
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def head(name: str) -> str:
    """The instruction's own name: ``%fusion.167 = ...`` -> ``fusion.167``."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """``fusion``, ``custom-call``, ``while``, ``all-gather-start``...;
    a name that is no HLO line gives its head without the number."""
    if " = " in name:
        m = _OPCODE.search(name.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return re.sub(r"[.\d]+$", "", head(name))


def short_name(name: str) -> str:
    """For the breakdown: ``fusion.167 fusion``, ``checkpoint.24
    custom-call tpu_custom_call``."""
    if " = " not in name:
        return name
    t = _TARGET.search(name)
    return " ".join([head(name), opcode(name)] + ([t.group(1)] if t else []))


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(opcode(name)) or COLLECTIVE.match(head(name)))


def union(intervals: list) -> list:
    """Sorted, merged ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def self_times(ops: list) -> list:
    """``[(name, self_ns), ...]``: an op's duration less that of the ops
    nested inside it (a ``while`` holds its body's ops on the same line),
    so that the times add up to the busy time."""
    out, stack = [], []          # stack of [end, name, self_ns]
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, n, e - s])
    out.extend((n, ns) for _, n, ns in stack)
    return out


def _module_at(modules: list, t: int) -> tuple:
    """``(index, name)`` of the program running at ``t``."""
    for i, (s, e, name) in enumerate(modules):
        if s <= t <= e:
            return i, name
    return -1, "no program"


def reduce_plane(ops: list, modules: list, window: tuple = None) -> dict:
    """One device. ``ops`` and ``modules`` are ``[(start_ns, end_ns,
    name), ...]``; ``window`` clips to ``(start_ns, end_ns)``."""
    if window is not None:
        w0, w1 = window
        ops = [(max(s, w0), min(e, w1), n) for s, e, n in ops
               if e > w0 and s < w1]
    elif ops:
        w0, w1 = min(s for s, _, _ in ops), max(e for _, e, _ in ops)
    else:
        w0 = w1 = 0
    busy = union([(s, e) for s, e, _ in ops])
    # the ops of one core run one after another (a while only nests its
    # body), so an op's self time is the time in which it alone runs
    per_op, calls, exposed = {}, {}, 0
    for n, ns in self_times(ops):
        per_op[n] = per_op.get(n, 0) + ns
        calls[n] = calls.get(n, 0) + 1
        if is_collective(n):
            exposed += ns
    gaps: dict = {}
    edges = [(w0, w0)] + busy + [(w1, w1)]
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 <= e0:
            continue
        a = _module_at(modules, e0) if e0 > w0 else (-2, "window start")
        b = _module_at(modules, s1) if s1 < w1 else (-3, "window end")
        name = f"in {a[1]}" if a == b else f"{a[1]} -> {b[1]}"
        gaps[name] = gaps.get(name, 0) + (s1 - e0)
    return {"window_ns": w1 - w0, "busy_ns": total(busy),
            "collective_exposed_ns": exposed,
            "per_op_ns": per_op, "per_op_calls": calls,
            "gaps_ns": gaps, "events": len(ops)}


def reduce_planes(planes: list, window_s: float = None) -> dict:
    """``planes``: one ``reduce_plane`` result per device; averaged."""
    n = len(planes)
    if n == 0:
        return {}
    win = (window_s if window_s is not None
           else max(p["window_ns"] for p in planes) / 1e9)

    def mean_named(key):
        acc: dict = {}
        for p in planes:
            for name, ns in p[key].items():
                acc[name] = acc.get(name, 0) + ns / n / 1e9
        return sorted(acc.items(), key=lambda kv: -kv[1])

    return {"devices": n, "window_s": win,
            "busy_s": sum(p["busy_ns"] for p in planes) / n / 1e9,
            "collective_exposed_s": sum(p["collective_exposed_ns"]
                                        for p in planes) / n / 1e9,
            "device_ops": mean_named("per_op_ns"),
            "op_calls": {name: sum(p["per_op_calls"].get(name, 0)
                                   for p in planes) / n
                         for p in planes for name in p["per_op_calls"]},
            "idle_gaps": mean_named("gaps_ns"),
            "events": sum(p["events"] for p in planes)}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path: str) -> tuple:
    """``(device planes as (ops, modules) lists, structure)``: the
    structure lists every plane and line with its event count, for a look
    at a trace by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, structure = [], []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            ev = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                  for e in line.events]
            lines[line.name] = ev
            structure.append((plane.name, line.name, len(ev)))
        if DEVICE_PLANE.match(plane.name) and lines.get(OPS_LINE):
            devices.append((lines[OPS_LINE], lines.get(MODULES_LINE, [])))
    return devices, structure


def reduce_file(path: str, window_s: float = None) -> dict:
    """The whole reduction of one trace file. Operation names are kept as
    the trace gives them (on the chip the whole HLO line): the same op of
    the same program adds up over executions, and a reader can tell a
    kernel by its signature. ``short_name`` is for print."""
    devices, structure = read_planes(path)
    out = reduce_planes([reduce_plane(ops, mods) for ops, mods in devices],
                        window_s)
    out["structure"] = structure
    return out
