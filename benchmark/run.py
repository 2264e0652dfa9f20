#!/usr/bin/env python3
"""The benchmark's command: one cell, once, in a new process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell by name (``resolve.py``), hands it to its kind
(``kinds/<kind>.py``), and prints log lines and then ONE last line, a JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` with ``--trace 1``). With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

This process never initialises a jax backend: the workers hold the chips.
No chip, no number: off a TPU, on a device kind that ``peaks.json`` does
not list, or on fewer chips than the cell asks for, it prints no result
and exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# A cluster that dies under a run (worker lost, a daemon's deadline passed
# while the host stood still: PERF.md 6, refusal round) gets one new
# cluster; the line says so (``retried``) and ``setup_s`` pays for it.
ATTEMPTS = 2


class Refused(Exception):
    """The run cannot give a number (no chip, wrong device, wrong cell)."""


def log(msg: str) -> None:
    print(msg, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # the program is the checkout this file sits in, and nothing else
    sys.path.insert(0, ROOT)
    inherited = os.environ.get("PYTHONPATH")
    os.environ["PYTHONPATH"] = ROOT + (
        os.pathsep + inherited if inherited else "")
    os.chdir(ROOT)
    out_dir = os.path.join(HERE, "out")
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    sessions = os.path.join(out_dir, "sessions")
    shutil.rmtree(sessions, ignore_errors=True)   # last run's daemon logs
    os.makedirs(sessions, exist_ok=True)
    # daemons' session directories and logs stay inside the checkout
    os.environ["RAY_TPU_TMPDIR"] = sessions
    try:
        from ray_tpu.core import compile_cache
    except ImportError as e:
        log(f"REFUSED: cannot import ray_tpu next to {HERE}: {e}")
        return 2
    from benchmark import resolve

    cache_dir = compile_cache.env_defaults()      # children inherit it
    try:
        cell = resolve.cell(args.workload)
        kind = resolve.kind(cell["kind"])
        layer_specs = [(m, resolve.layer_metric(m["name"]))
                       for m in resolve.metrics_for(
                           args.workload, "per_layer", cell["kind"])]
        e2e_specs = resolve.metrics_for(args.workload, "end_to_end",
                                        cell["kind"])
    except resolve.UnknownName as e:
        log(f"REFUSED: {e}")
        return 2
    log(f"cell {args.workload}: {cell['kind']} of {cell['config_name']} under "
        f"{cell['mix_name']} on {cell['chips']} chip(s); seed {args.seed}, "
        f"{args.seconds:g} s, trace {args.trace}; compile cache {cache_dir} "
        f"({compile_cache.entry_count(cache_dir)} entries)")
    ctx = {"log": log, "t_start": T_START, "out_dir": out_dir, "trace_dir": trace_dir,
           "peak": resolve.peak, "Refused": Refused}
    failure, res, retried = None, None, 0
    for attempt in range(ATTEMPTS):
        try:
            res, failure = kind.run(cell, args, ctx), None
            break
        except Refused as e:
            failure = f"REFUSED: {e}"
            break
        except Exception as e:   # noqa: BLE001 - the cause is the output
            import traceback

            traceback.print_exc()
            failure = f"FAILED: {type(e).__name__}: {e}"
            _daemon_log_tails(sessions)
            if attempt + 1 < ATTEMPTS:
                log(f"ATTEMPT {attempt + 1} {failure}\n"
                    "the cluster is taken down and the cell runs once more "
                    "on a new one; setup_s keeps the time this cost")
                retried += 1
                _take_down(trace_dir)
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is not None and getattr(bridge, "_backends", None):
        failure = failure or ("FAILED: the parent initialised a jax backend: "
                              f"{list(bridge._backends)}")
    if failure is None:
        dev = res["device"]
        for what, ok in res["checks"].items():
            log(f"  {'ok' if ok else 'WRONG'}: {what}")
        if dev["platform"] != "tpu":
            failure = (f"REFUSED: ran on {dev['platform']!r}: a rehearsal, "
                       "not a measurement; no metric is printed")
        elif dev["count"] != cell["chips"]:
            failure = (f"REFUSED: ran on {dev['count']} chip(s), the cell "
                       f"asks for {cell['chips']}")
        else:
            try:
                resolve.peak(dev["kind"])
            except resolve.UnknownName as e:
                failure = f"REFUSED: {e}"
    if failure is not None:
        log(failure)
        return 1

    e2e = dict(res["end_to_end"])
    e2e["setup_s"] = res["window_start"] - T_START
    metrics = {}
    res["obs"]["peak"] = resolve.peak(res["device"]["kind"])
    if args.trace:
        for m, spec in layer_specs:
            value = resolve.reader(spec["reader"]).read(spec, res["obs"])
            if value is not None:          # nothing to read: left out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e_specs:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    log("end to end: " + json.dumps(e2e))
    line = {"correct": all(res["checks"].values()),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": dict(res["device"]),
            "retried": retried}
    trace = res["obs"].get("trace")
    if args.trace and trace:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        from benchmark.trace_reduce import short_name

        line["breakdown"] = {
            "device_ops": [[short_name(n), s]
                           for n, s in trace["device_ops"][:10]],
            "idle_gaps": [list(x) for x in trace["idle_gaps"][:10]]}
    print(json.dumps(line), flush=True)
    return 0


def _take_down(trace_dir: str) -> None:
    """After a failed attempt: no daemon or worker of it may hold a chip,
    and no trace of it may be read as the next attempt's."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        import ray_tpu

        ray_tpu.shutdown()
    except Exception as e:   # noqa: BLE001 - the next attempt will say
        log(f"  shutdown after the failed attempt: {type(e).__name__}: {e}")
    time.sleep(2.0)


def _daemon_log_tails(sessions: str, lines: int = 25) -> None:
    """After a failure: the end of what the cluster's daemons and workers
    logged (a kill or a refused lease is recorded only there)."""
    import glob

    for path in sorted(glob.glob(os.path.join(
            sessions, "session_[0-9]*", "logs", "*.err"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        if tail:
            log(f"-- last lines of {path}")
            for ln in tail:
                log("   " + ln.rstrip())


if __name__ == "__main__":
    sys.exit(main())
