"""What a run's own timeline says of its set-up, for a person:

    python3 -m benchmark.tools.job_timeline benchmark/out/bench_<cell>

prints the set-up budget (``readers/job_timeline.py``: the eight
``setup_*`` metrics), every kept span in time order with its seconds,
the compiles of rank 0's process (program, cache, seconds, retrieval) and
every plan instant with its attributes: the cell's own plans at the
cell's own size, which no profile holds because the step is lowered
before the profiler starts.
"""

from __future__ import annotations

import sys

from benchmark.readers import job_timeline as jt


def is_plan(name: str) -> bool:
    return name.endswith((".plan", "_plan"))


def holds(outer: dict, inner: dict) -> bool:
    """``inner`` lies inside ``outer`` in the same process."""
    return outer is not inner and outer["worker"] == inner["worker"] and \
        outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def report(records: list) -> str:
    su = jt.setup_of(records)
    if su is None:
        return ("no set-up to tell: the timeline lacks core.init, train.fit, "
                "rank 0's train.loop or its train.first_report")
    at = su["start"]
    out = [f"set-up {su['end'] - at:.3f} s, core.init's start to rank 0's "
           f"first report (worker {su['worker']})"]
    out += [f"  setup_{part:<20} {value:10.3f}"
            for part, value in jt.budget(records).items()]
    out.append("spans (start from core.init's, seconds, process, attributes; "
               "a trace inside another's is counted with it, not listed):")
    stages = [r for r in records if r["name"] in jt.STAGES]
    for r in records:
        if r["end"] <= r["start"] or \
                r in stages and any(holds(o, r) for o in stages):
            continue
        inside = sum(holds(r, i) for i in stages) if r in stages else 0
        out.append(f"  {r['start'] - at:9.3f} {r['end'] - r['start']:9.3f}  "
                   f"{r['name']:<20} {r['worker'] or 'driver':<12} "
                   f"{r['attrs']}" + (f" ({inside} inside)" if inside else ""))
    out.append("compiles of rank 0's process in set-up (end from "
               "core.init's start, seconds, retrieval, cache, program):")
    out += [f"  {r['start'] - at:9.3f} {a.get('seconds', 0.0):9.3f} "
            f"{a.get('retrieval_s', 0.0):9.3f}  {a.get('cache', '?'):<5} "
            f"{a.get('program', '?')}"
            for r in jt.compiles(records, su) for a in [r["attrs"]]]
    hosts = jt.host_freezes(records, su["worker"])
    out.append("freezes (end, seconds late, armed, process; host: rank 0's "
               "that another process shared):")
    out += [f"  {r['start'] - at:9.3f} {r['attrs'].get('late_s', 0.0):9.3f}  "
            f"{r['attrs'].get('armed')}  {r['worker'] or 'driver'}"
            f"{'  host' if r in hosts else ''}"
            for r in jt.named(records, jt.FREEZE)]
    out.append("plans (at, name, attributes):")
    out += [f"  {r['start'] - at:9.3f}  {r['name']:<18} {r['attrs']}"
            for r in records if is_plan(r["name"])]
    return "\n".join(out)


def main(argv: list) -> int:
    if len(argv) != 2:
        print(__doc__)
        return 2
    records = jt.load(argv[1])
    if records is None:
        print(f"no timeline.json under {argv[1]}")
        return 1
    print(report(records))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
