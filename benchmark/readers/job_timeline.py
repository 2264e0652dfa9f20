"""Where a job's set-up went, read from the timeline the program itself
leaves beside the trainer's storage: ``out/bench_<cell>/timeline.json``,
the Chrome trace ``JaxTrainer.fit`` writes as it ends, holding what the
program keeps with tracing off (``core.init``, ``train.fit``,
``train.group_start``, ``train.worker_setup``, ``train.chips_open``,
``train.loop``, ``train.first_report``, ``xla.trace``, ``xla.lower``,
``xla.compile``, ``stall::host_freeze`` and the plan instants).

``{"reader": "job_timeline", "part": "cluster_s"}``: one part of
``budget()``. Set-up is everything from ``core.init``'s start to rank 0's
``train.first_report``; rank 0's process is the one whose ``train.loop``
says ``rank`` 0, and the compiles, stages and freezes counted are that
process's (a freeze only where another process froze with it: the
host's). All times are ``time.time()`` of one host.

Nothing to read, so the metric is left out: no file (a program that
writes none), a file older than ``out/sessions`` (``run.py`` makes that
directory anew at every start: the file is an earlier run's), or a
timeline without ``core.init`` or a first report.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out")
STAGES = ("xla.trace", "xla.lower")
FREEZE = "stall::host_freeze"


def read(spec: dict, obs: dict):
    records = load(os.path.join(OUT, "bench_" + obs["cell"]["name"]),
                   newer_than=os.path.join(OUT, "sessions"))
    if records is None:
        return None
    return budget(records).get(spec["part"])


def load(run_dir: str, newer_than: Optional[str] = None
         ) -> Optional[List[dict]]:
    """The spans and instants of ``<run_dir>/timeline.json`` in time
    order, each ``{"name", "start", "end", "attrs", "worker"}`` in
    seconds; None where there is no file, or it is older than
    ``newer_than``."""
    path = os.path.join(run_dir, "timeline.json")
    try:
        if newer_than is not None and \
                os.path.getmtime(path) < os.path.getmtime(newer_than):
            return None
        with open(path) as f:
            events = json.load(f)
    except OSError:
        return None
    records = []
    for ev in events:
        if ev.get("cat") not in ("span", "instant"):
            continue
        start = ev["ts"] / 1e6
        args = ev.get("args", {})
        records.append({
            "name": ev["name"], "start": start,
            "end": start + ev.get("dur", 0.0) / 1e6,
            "attrs": args.get("attrs") or {}, "worker": args.get("worker")})
    records.sort(key=lambda r: r["start"])
    return records


def named(records: List[dict], name: str, worker=...) -> List[dict]:
    return [r for r in records if r["name"] == name
            and (worker is ... or r["worker"] == worker)]


def setup_of(records: List[dict]) -> Optional[dict]:
    """The stretch that counts as set-up: its ``start`` and ``end``, the
    spans that bound the phases, and rank 0's ``worker``. The last of
    each name: a fit that restarted its group ends with the attempt that
    ran."""
    init, fit = named(records, "core.init"), named(records, "train.fit")
    loops = [r for r in named(records, "train.loop")
             if r["attrs"].get("rank") == 0]
    if not (init and fit and loops):
        return None
    loop = loops[-1]
    first = [r for r in named(records, "train.first_report", loop["worker"])
             if r["start"] >= loop["start"]]
    if not first:
        return None
    opened = [r for r in named(records, "train.chips_open", loop["worker"])
              if r["end"] <= loop["start"]]
    return {"start": init[-1]["start"], "end": first[0]["start"],
            "init": init[-1], "fit": fit[-1], "loop": loop,
            "chips_open": opened[-1] if opened else None,
            "worker": loop["worker"]}


def compiles(records: List[dict], su: dict) -> List[dict]:
    """Rank 0's ``xla.compile`` records that ended inside set-up."""
    return [r for r in named(records, "xla.compile", su["worker"])
            if su["start"] <= r["start"] <= su["end"]]


def host_freezes(records: List[dict], worker) -> List[dict]:
    """``worker``'s freezes that another process woke late through too:
    the HOST stood still (the chip's opening freezes every process of it
    at the same instant). A watcher that alone woke late was starved by
    its own process, a native call that held the interpreter's lock (the
    end of a large compile): the program's seconds, not the machine's."""
    every = named(records, FREEZE)

    def shared(mine: dict) -> bool:
        return any(o["worker"] != worker
                   and o["start"] - _attr(o, "late_s") < mine["start"]
                   and mine["start"] - _attr(mine, "late_s") < o["start"]
                   for o in every)

    return [r for r in every if r["worker"] == worker and shared(r)]


def _attr(record: dict, key: str) -> float:
    return float(record["attrs"].get(key, 0.0))


def union_s(intervals: List[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Seconds of [lo, hi] that some interval covers."""
    covered, at = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, at), min(b, hi)
        if b > a:
            covered += b - a
            at = b
    return covered


def budget(records: List[dict]) -> Dict[str, float]:
    """The eight numbers, by ``part``; empty where set-up cannot be told."""
    su = setup_of(records)
    if su is None:
        return {}
    lo, hi, me = su["start"], su["end"], su["worker"]

    def inside(r):
        return lo <= r["start"] <= hi

    opened = su["chips_open"]
    open_s = opened["end"] - opened["start"] if opened else 0.0
    stages = [r for n in STAGES for r in named(records, n, me) if inside(r)]
    built = compiles(records, su)
    freezes = [r for r in host_freezes(records, me) if inside(r)]
    # an instant is raised as what it times ends
    named_s = union_s(
        [(su["init"]["start"], su["init"]["end"]),
         (su["fit"]["start"], su["loop"]["start"])]
        + [(r["start"], r["end"]) for r in stages]
        + [(r["start"] - _attr(r, "seconds"), r["start"]) for r in built]
        + [(r["start"] - _attr(r, "late_s"), r["start"]) for r in freezes],
        lo, hi)
    return {
        "cluster_s": su["init"]["end"] - su["init"]["start"],
        "worker_group_s": su["loop"]["start"] - su["fit"]["start"] - open_s,
        "chips_open_s": open_s,
        # a jitted function traced inside another's trace is counted once
        "trace_lower_s": union_s([(r["start"], r["end"]) for r in stages],
                                 lo, hi),
        "program_compile_s": sum((_attr(r, "seconds") for r in built
                                  if r["attrs"].get("cache") != "hit"), 0.0),
        "program_load_s": sum((_attr(r, "seconds") for r in built
                               if r["attrs"].get("cache") == "hit"), 0.0),
        "host_freeze_s": sum((_attr(r, "late_s") for r in freezes), 0.0),
        "unspanned_share": 100.0 * (1.0 - named_s / (hi - lo))
        if hi > lo else 0.0,
    }
