"""What a loop says of itself as it ends: one attribute of rank 0's last
``train.loop_summary``, the kept instant ``TrainWorker.run`` raises as
``train.loop`` closes (``ray_tpu/train/session.py`` ``LoopFigures``),
read from the timeline the program leaves in every run, traced or not
(``out/bench_<cell>/timeline.json``; ``job_timeline.load``).

``{"reader": "loop_summary", "key": "host_late_ms"}``. The keys:
``steps`` (reports), ``interval_median_ms`` (one report's end to the
next one's: the step and its report, timed where they happen),
``wait_median_ms``, ``wait_max_ms``, ``wait_max_step``,
``report_median_ms``, and what the process's watcher counted from the
first report to the loop's end (``observability/health.py``):
``host_late_ms`` / ``host_late_count`` (wakes more than 20 ms late in
which the process got no CPU: the machine stood still) and
``process_late_ms`` / ``process_late_count`` (the process ran and its
watcher could not: a native call held the interpreter's lock).

Nothing to read, so the metric is left out, under ``job_timeline``'s
rules: no file, a file older than ``out/sessions``, or a timeline
without such an instant of rank 0 (a program that raises none, a loop
that never reported). Of several attempts the last is read.
"""

from __future__ import annotations

import os
from typing import List, Optional

from benchmark.readers import job_timeline

SUMMARY = "train.loop_summary"


def read(spec: dict, obs: dict):
    records = job_timeline.load(
        os.path.join(job_timeline.OUT, "bench_" + obs["cell"]["name"]),
        newer_than=os.path.join(job_timeline.OUT, "sessions"))
    if records is None:
        return None
    return (last_summary(records) or {}).get(spec["key"])


def last_summary(records: List[dict], rank: int = 0) -> Optional[dict]:
    """The attributes of ``rank``'s last ``train.loop_summary``."""
    said = [r for r in job_timeline.named(records, SUMMARY)
            if r["attrs"].get("rank") == rank]
    return said[-1]["attrs"] if said else None
