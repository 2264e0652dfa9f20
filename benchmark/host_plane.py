"""The program's own spans in the profiler's trace, beside the device.

A span of the program (``ray_tpu/util/tracing.py``) is also an event of
jax's profiler: while a profile runs it lands in plane ``/host:CPU`` of
the ``.xplane.pb``, on the line of the thread that opened it, with its
scalar attributes as the event's stats and its start on the same axis as
the device planes. So a device idle gap can be put down to what the host
was doing in it. From one trace file:

  host      the host events named like the program's spans (``PREFIXES``;
            all threads), as ``(start_ns, end_ns, name, stats)``
  devices   per device plane the op intervals and the ``XLA Modules``
            events, as ``trace_reduce.read_planes`` gives them
  since     the start of the first device event: host events before it
            lie where the device was not traced yet, and are left out

Pure interval arithmetic below ``read_file``, on ``trace_reduce.union``
and ``total``, so that it can be checked on synthetic planes (``tests/``).
A trace of a program without spans (an older commit) has a host plane
that holds none: counts read 0, everything else reads nothing.

By hand, after a ``--trace 1`` run:

    python3 -m benchmark.host_plane benchmark/out/trace train.report
"""

from __future__ import annotations

import bisect
import functools
import os
import statistics

from benchmark.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                    find_xplane, total, union)

HOST_PLANE = "/host:CPU"
# what the program names its spans and instants (PERF.md, section 3)
PREFIXES = ("train.", "serve.", "xla.compile", "stall::")
# where both kinds trace to, and what ``run.py`` clears before each run
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "out", "trace")


def read_file(path: str) -> dict:
    """``{"host": [...] or None (no host plane), "devices": [(ops,
    modules), ...], "since": ns}`` of one ``.xplane.pb``."""
    return _read(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=1)           # every reader of a run asks
def _read(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData

    host, devices = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            host = []
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        host.append((int(e.start_ns),
                                     int(e.start_ns + e.duration_ns),
                                     e.name, dict(e.stats)))
        elif DEVICE_PLANE.match(plane.name):
            lines = {ln.name: [(int(e.start_ns),
                                int(e.start_ns + e.duration_ns), e.name)
                               for e in ln.events]
                     for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE):
                devices.append((lines[OPS_LINE], lines.get(MODULES_LINE, [])))
    return {"host": host, "devices": devices, "since": since(devices)}


def of_run(trace_dir: str = None):
    """The planes of the traced run that wrote into ``trace_dir``
    (``TRACE_DIR`` unless given), or None: no trace there."""
    try:
        return read_file(find_xplane(trace_dir or TRACE_DIR))
    except FileNotFoundError:
        return None


def since(devices: list) -> int:
    starts = [s for ops, mods in devices for s, _, _ in ops + mods]
    return min(starts) if starts else 0


def spans(planes: dict, name: str):
    """The events called ``name`` that start inside the device's traced
    stretch, in time order; None when the trace has no host plane."""
    if planes is None or planes["host"] is None:
        return None
    return sorted(e for e in planes["host"]
                  if e[2] == name and e[0] >= planes["since"])


def covered(intervals: list, busy: list) -> int:
    """ns of ``intervals`` (merged) that ``busy`` (merged, sorted)
    overlaps."""
    starts = [s for s, _ in busy]
    out = 0
    for s, e in intervals:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(busy) and busy[i][0] < e:
            out += max(0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
    return out


def idle_under(planes: dict, name: str):
    """Seconds in which a span called ``name`` is open and no op runs on
    the device, mean over the device planes; None without a device plane
    or without such a span (a program that has none reads nothing, not 0)."""
    evs = spans(planes, name)
    if not evs or not planes["devices"]:
        return None
    open_ = union([(s, e) for s, e, _, _ in evs])
    idle = 0
    for ops, _ in planes["devices"]:
        idle += total(open_) - covered(
            open_, union([(s, e) for s, e, _ in ops]))
    return idle / len(planes["devices"]) / 1e9


def module_seconds(planes: dict, match: str):
    """Seconds in which a program whose name contains ``match`` runs,
    mean over the device planes; None without a device plane."""
    if planes is None or not planes["devices"]:
        return None
    ns = sum(total(union([(s, e) for s, e, n in mods if match in n]))
             for _, mods in planes["devices"])
    return ns / len(planes["devices"]) / 1e9


def largest_overlap(planes: dict, name: str) -> list:
    """For a look by hand: per span called ``name`` its length, how long
    after the end of the last device op that had ended it opened, how
    long before the next op's start it closed, and the time for which an
    op ran while it was open (ns; first device plane)."""
    evs = spans(planes, name) or []
    if not planes or not planes["devices"]:
        return []
    busy = union([(s, e) for s, e, _ in planes["devices"][0][0]])
    starts, ends = [s for s, _ in busy], [e for _, e in busy]
    out = []
    for s, e, _, stats in evs:
        i, j = bisect.bisect_right(ends, s), bisect.bisect_left(starts, e)
        out.append({"stats": stats, "span_ns": e - s,
                    "after_last_op_end_ns": s - ends[i - 1] if i else None,
                    "before_next_op_start_ns":
                        starts[j] - e if j < len(starts) else None,
                    "overlap_ns": covered([(s, e)], busy)})
    return out


def summary(planes: dict, name: str) -> dict:
    evs = spans(planes, name) or []
    dur = [(e - s) / 1e6 for s, e, _, _ in evs]
    return {"span": name, "count": len(evs),
            "median_ms": statistics.median(dur) if dur else None,
            "max_ms": max(dur) if dur else None,
            "idle_under_s": idle_under(planes, name)}


if __name__ == "__main__":
    import json
    import sys

    planes = of_run(sys.argv[1])
    names = sys.argv[2:] or sorted({e[2] for e in planes["host"] or []})
    print(f"host plane: {planes['host'] is not None}, device planes: "
          f"{len(planes['devices'])}, since {planes['since']} ns")
    for name in names:
        print(json.dumps(summary(planes, name)))
        for row in largest_overlap(planes, name)[:40]:
            print("   ", json.dumps(row))
