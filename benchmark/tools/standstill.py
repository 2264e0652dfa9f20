"""What a run says of its own standstills, for a person:

    python3 -m benchmark.tools.standstill <cell> [trace dir]

reads the run that last wrote ``benchmark/out/bench_<cell>/timeline.json``
(any run, traced or not; a CPU rehearsal too) and prints rank 0's
``train.loop_summary`` attribute by attribute, then the four metrics of
``README.standstill.md`` through their own readers and metric files
(``trace_window_standstill_ms`` from the profile under ``out/trace`` or
the directory given; nothing where the run was not traced), then every
``stall::late_wake`` and ``stall::host_freeze`` of the profile.

    python3 -m benchmark.tools.standstill --manifest-entries

prints the four ``per_layer`` entries as ``BENCHMARK.json`` would hold
them (all train cells of the manifest), for the ``benchmark`` PR that
has room for them.
"""

from __future__ import annotations

import json
import os
import sys

from benchmark import host_plane, resolve
from benchmark.readers import job_timeline, late_wakes, loop_summary

# metric -> its layer in the manifest: the machine's standstills beside
# `setup_host_freeze_s`, the loop's own clock beside `train_report_ms`
LAYER = {"host_standstill_ms": "device", "process_stall_ms": "trainer",
         "train_step_interval_ms": "trainer",
         "trace_window_standstill_ms": "device"}


def manifest_entries() -> list:
    man = resolve.manifest()
    cells = [w["name"] for w in man["workloads"]
             if resolve.workload(w["name"])["kind"].startswith("train")]
    return [{"name": name, "unit": resolve.layer_metric(name)["unit"],
             "better": "lower", "source": "program_counter",
             "layer": LAYER[name],
             "moves": "train_tok_s_chip", "workloads": cells}
            for name in LAYER]


def report(cell: str, trace_dir: str = None) -> str:
    records = job_timeline.load(
        os.path.join(job_timeline.OUT, "bench_" + cell))
    if records is None:
        return f"no timeline.json under {job_timeline.OUT}/bench_{cell}"
    said = loop_summary.last_summary(records)
    if said is None:
        return "the timeline holds no train.loop_summary of rank 0"
    out = ["train.loop_summary of rank 0 (the last attempt's):"]
    out += [f"  {key:<22} {value}" for key, value in said.items()]
    planes = host_plane.of_run(trace_dir)
    out.append("metrics:")
    for name in LAYER:
        spec = resolve.layer_metric(name)
        value = (late_wakes.standstill_ms(planes, spec["cause"])
                 if spec["reader"] == "late_wakes" else said.get(spec["key"]))
        out.append(f"  {name:<28} "
                   f"{'nothing to read' if value is None else value}")
    for name in late_wakes.LATENESS:
        for start, _, _, stats in host_plane.spans(planes, name) or []:
            out.append(f"  {name} at {(start - planes['since']) / 1e6:.3f} ms "
                       f"of the traced stretch: {stats}")
    return "\n".join(out)


def main(argv: list) -> int:
    if argv[1:] == ["--manifest-entries"]:
        print(json.dumps(manifest_entries(), indent=1))
        return 0
    if len(argv) not in (2, 3):
        print(__doc__)
        return 2
    print(report(*argv[1:]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
