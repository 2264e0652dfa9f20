"""The loop keeps its own clock (ISSUE 65): `session._report` adds up
what it measures, `TrainWorker.run` says it once as the loop ends
(`train.loop_summary`, kept with tracing off), the worker's telemetry
buffer never drops a kept record for a task state, and the benchmark's
two readers find the summary on a job's timeline and the late wakes on a
profile's host plane. Injected clocks, no compile, no cluster."""

import json
import os
import statistics
import types

import pytest

from ray_tpu.observability import health
from ray_tpu.observability.agent import TelemetryAgent
from ray_tpu.train import session
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.worker_group import TrainWorker
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN_KINDS = sorted(
    f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark", "kinds"))
    if f.startswith("train"))
METRICS = {"host_standstill_ms": ("loop_summary", "host_late_ms"),
           "process_stall_ms": ("loop_summary", "process_late_ms"),
           "train_step_interval_ms": ("loop_summary", "interval_median_ms"),
           "trace_window_standstill_ms": ("late_wakes", "host")}


class _Recorder:
    """Stands in for the runtime: what `tracing` records."""

    mode = "driver"

    def __init__(self):
        self.spans = []

    def record_span(self, span):
        self.spans.append(span)

    def flush_task_events(self, wait=False):
        pass


class _LoopClock:
    """`perf_counter` of the loop: every reading costs `READ_S`, so a
    report (two readings) is `READ_S` long and a wait is what the loop
    slept plus `READ_S`."""

    READ_S = 0.0005

    def __init__(self):
        self.now = 50.0

    def perf_counter(self):
        self.now += self.READ_S
        return self.now - self.READ_S


@pytest.fixture
def loop(monkeypatch, tmp_path):
    """A worker with a context, its loop's clock, the late-wake counters
    it reads (a test's own: the process's running watcher counts into
    the module's) and what it records with tracing off."""
    from ray_tpu.core import runtime as rt_mod

    rec, clock = _Recorder(), _LoopClock()
    counts = dict.fromkeys(health.counters(), 0)
    monkeypatch.setattr(rt_mod, "_global_runtime", rec)
    monkeypatch.setattr(session, "time", types.SimpleNamespace(
        perf_counter=clock.perf_counter, time=lambda: 1.79e9 + clock.now))
    monkeypatch.setattr(health, "counters", lambda: dict(counts))
    tracing.disable()
    worker = TrainWorker._cls(0, 1)
    worker.ctx = session.TrainContext(
        world_rank=0, world_size=1, config={}, run_dir=str(tmp_path),
        scaling=ScalingConfig(num_workers=1, use_tpu=False), checkpoint=None)
    session._set_context(worker.ctx)
    yield types.SimpleNamespace(worker=worker, clock=clock, counts=counts,
                                spans=rec.spans)
    session._set_context(None)
    tracing._enabled = None


def _said(spans):
    return [(s["kind"], s["name"]) for s in spans]


def test_a_loop_sums_itself_up_once_as_it_ends(loop):
    waits = [0.100, 0.104, 0.096, 0.900, 0.100, 0.102]   # the fourth stood

    def user_loop():
        session.report({"step": 10, "loss": 1.0})         # the first: no wait
        for i, wait in enumerate(waits):
            loop.clock.now += wait
            if i == 3:      # while the loop waited the machine stood still
                loop.counts["host_late_ms"] += 790.5
                loop.counts["host_late_count"] += 2
                loop.counts["process_late_ms"] += 31.25
                loop.counts["process_late_count"] += 1
            session.report({"step": 11 + i, "loss": 0.5})
        return "done"

    loop.counts["host_late_ms"] = 5000.0      # set-up's: not the loop's
    assert loop.worker.run(user_loop, {}) == "done"
    # with tracing off a step records NOTHING: the first report, then the
    # summary as the loop's span closes round it
    assert _said(loop.spans) == [("instant", "train.first_report"),
                                 ("instant", "train.loop_summary"),
                                 ("span", "train.loop")]
    first, summary, whole = loop.spans
    assert summary["trace_id"] is None                    # kept, no trace
    assert whole["ts"] <= first["ts"] <= summary["ts"] \
        <= whole["ts"] + whole["dur"]
    read = _LoopClock.READ_S * 1e3
    assert summary["attrs"] == {
        "rank": 0, "steps": 7,
        "interval_median_ms": pytest.approx(
            statistics.median(waits) * 1e3 + 2 * read),
        "wait_median_ms": pytest.approx(statistics.median(waits) * 1e3 + read),
        "wait_max_ms": pytest.approx(900.0 + read), "wait_max_step": 14,
        "report_median_ms": pytest.approx(read),
        "host_late_ms": 790.5, "host_late_count": 2,
        "process_late_ms": 31.25, "process_late_count": 1}
    json.dumps(summary)


def test_a_loop_that_never_reported_says_nothing(loop):
    assert loop.worker.run(lambda: 3, {}) == 3
    assert _said(loop.spans) == [("span", "train.loop")]


def test_a_loop_that_fails_still_sums_up_what_it_did(loop):
    def user_loop():
        for i in range(3):
            loop.clock.now += 0.2
            session.report({"step": i})
        raise ValueError("the fourth batch")

    with pytest.raises(ValueError):
        loop.worker.run(user_loop, {})
    summary = loop.spans[1]
    assert summary["name"] == "train.loop_summary"
    assert summary["attrs"]["steps"] == 3
    assert summary["attrs"]["wait_median_ms"] == pytest.approx(200.5)
    assert "error" in loop.spans[2]["attrs"]


def test_one_report_is_a_count_and_no_median(loop):
    loop.worker.run(lambda: session.report({"loss": 0.1}), {})
    assert loop.spans[1]["attrs"] == {
        "rank": 0, "steps": 1, "host_late_ms": 0, "host_late_count": 0,
        "process_late_ms": 0, "process_late_count": 0}


def test_the_figures_stay_bounded_over_a_long_loop():
    """4,000 reports: at most `CAP` pairs are held, spread over the whole
    loop, so the medians are the loop's and not its end's."""
    figures = session.LoopFigures()
    figures.observe(None, 0.001, 0)
    for i in range(4000):         # waits climb from 0.1 s to 0.5 s
        figures.observe(0.1 + 1e-4 * i, 0.001, i + 1)
    assert len(figures._pairs) <= figures.CAP
    said = figures.summary()
    assert said["steps"] == 4001
    assert said["wait_median_ms"] == pytest.approx(300.0, abs=1.0)
    assert said["interval_median_ms"] == pytest.approx(301.0, abs=1.0)
    assert (said["wait_max_ms"], said["wait_max_step"]) == (
        pytest.approx(499.9), 4000)


# ----------------------------------------------- the worker's telemetry buffer

class _Runtime:
    """What a TelemetryAgent needs of a runtime; `gcs_call` keeps the
    reports."""

    node_id = "n0"

    def __init__(self, cap):
        self.cfg = types.SimpleNamespace(task_event_buffer_size=cap,
                                         telemetry_report_interval_s=3600.0)
        self.worker_id = types.SimpleNamespace(hex=lambda: "feedfacecafe0000")
        self.reports = []

    def gcs_call(self, method, report=None, **kw):
        assert method == "telemetry_report"
        self.reports.append(report)
        return {}


def test_a_burst_of_task_states_drops_no_kept_record():
    """What `train.first_report` and a loop's summary ride: a burst of
    task states (a polling driver, a fan-out) between them and the next
    report drops the OLDEST task states, never a span or an instant."""
    rt = _Runtime(cap=40)
    agent = TelemetryAgent(rt)
    agent._stopped.set()                  # no reporter thread: ship by hand
    agent.record_event({"kind": "instant", "name": "train.first_report",
                        "ts": 1.0, "attrs": {"step": 0}})
    for i in range(100):
        agent.record_event({"task_id": f"t{i}", "name": "poll",
                            "state": "FINISHED", "ts": 2.0 + i})
    agent.record_event({"kind": "instant", "name": "train.loop_summary",
                        "ts": 200.0, "attrs": {"rank": 0, "steps": 9}})
    assert agent._ship()
    shipped, = (r["events"] for r in rt.reports)
    assert [e["name"] for e in shipped if e.get("kind")] == [
        "train.first_report", "train.loop_summary"]
    states = [e["task_id"] for e in shipped if e.get("state")]
    assert states == [f"t{i}" for i in range(60, 100)]   # the newest 40
    assert agent.events_dropped == 60


def test_spans_are_bounded_too_and_a_failed_report_keeps_both_lists():
    rt = _Runtime(cap=10)
    agent = TelemetryAgent(rt)
    agent._stopped.set()
    for i in range(25):
        agent.record_event({"kind": "span", "name": f"s{i}", "ts": float(i)})
    for i in range(5):
        agent.record_event({"task_id": f"t{i}", "state": "RUNNING"})
    assert agent.events_dropped == 15

    def down(method, **kw):
        raise RuntimeError("gcs down")

    up, rt.gcs_call = rt.gcs_call, down
    assert agent._ship() is False
    agent.record_event({"kind": "span", "name": "s25", "ts": 25.0})
    assert [e["name"] for e in agent._spans] == [
        f"s{i}" for i in range(16, 26)]                  # oldest dropped
    assert len(agent._events) == 5 and agent.events_dropped == 16
    rt.gcs_call = up
    assert agent._ship()
    assert len(rt.reports[0]["events"]) == 15


# ------------------------------------------------------ the benchmark's readers

T0 = 1_790_000_000.0
W0, W1 = "aaaaaaaaaaaa", "bbbbbbbbbbbb"


def _instant(name, at, worker, **attrs):
    return {"name": name, "cat": "instant", "ph": "i", "pid": 1, "tid": 2,
            "ts": (T0 + at) * 1e6, "s": "p",
            "args": {"name": name, "trace_id": None, "parent_id": None,
                     "worker": worker, "attrs": attrs}}


def _summary(at, worker, rank, **attrs):
    return _instant("train.loop_summary", at, worker, rank=rank, steps=31,
                    **attrs)


@pytest.fixture
def out_dir(tmp_path, monkeypatch):
    """An `out/` of the benchmark's with `sessions` made, as `run.py`
    makes it at every start."""
    from benchmark.readers import job_timeline

    monkeypatch.setattr(job_timeline, "OUT", str(tmp_path))
    os.makedirs(tmp_path / "sessions")
    os.makedirs(tmp_path / "bench_cell")
    return tmp_path


def _write_timeline(out_dir, events, age_s=0.0):
    path = out_dir / "bench_cell" / "timeline.json"
    with open(path, "w") as f:
        json.dump(events, f)
    then = os.path.getmtime(out_dir / "sessions") + 5.0 - age_s
    os.utime(path, (then, then))


def _read(name):
    from benchmark import resolve

    spec = resolve.layer_metric(name)
    return resolve.reader(spec["reader"]).read(
        spec, {"cell": {"name": "cell"}})


def test_the_reader_takes_rank_0s_last_summary(out_dir):
    _write_timeline(out_dir, [
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
         "args": {"name": "driver"}},
        # a first attempt that died, then the one that ran; rank 1 beside it
        _summary(40.0, W0, 0, interval_median_ms=900.0, host_late_ms=4000.0,
                 process_late_ms=1.0),
        _summary(95.0, W1, 1, interval_median_ms=1310.9, host_late_ms=7.0,
                 process_late_ms=9.0),
        _summary(94.0, W0, 0, interval_median_ms=1309.8, host_late_ms=212.5,
                 process_late_ms=0.0),
        _instant("train.first_report", 50.0, W0, step=2)])
    assert _read("host_standstill_ms") == 212.5
    assert _read("process_stall_ms") == 0.0
    assert _read("train_step_interval_ms") == 1309.8


@pytest.mark.parametrize("case", ["no_file", "stale", "no_summary",
                                  "one_report"])
def test_the_reader_reads_nothing_where_there_is_nothing(case, out_dir):
    if case == "stale":                   # an earlier run's file
        _write_timeline(out_dir, [_summary(9.0, W0, 0, host_late_ms=1.0,
                                           interval_median_ms=5.0)],
                        age_s=60.0)
    elif case == "no_summary":            # a program older than the instant
        _write_timeline(out_dir, [_instant("train.first_report", 5.0, W0)])
    elif case == "one_report":            # counted, but no interval to tell
        _write_timeline(out_dir, [_summary(9.0, W0, 0, host_late_ms=0.0,
                                           process_late_ms=0.0)])
    assert _read("train_step_interval_ms") is None
    assert _read("host_standstill_ms") == (
        0.0 if case == "one_report" else None)


def _planes(host):
    # one device plane busy [1000, 9000): the traced stretch starts at 1000
    ops = [(1000, 9000, "%fusion.1 = f32[] fusion(%a)")]
    return {"host": host, "devices": [(ops, [])], "since": 1000}


def test_late_wakes_inside_the_traced_window_by_cause():
    from benchmark.readers import late_wakes

    host = [
        (500, 500, "stall::late_wake",            # before the device's
         {"late_ms": 99.0, "cpu_ms": 0.0, "cause": "host"}),
        (2000, 2000, "stall::late_wake",
         {"late_ms": 112.5, "cpu_ms": 0.4, "cause": "host"}),
        (3000, 3000, "stall::late_wake",
         {"late_ms": 45.0, "cpu_ms": 44.0, "cause": "process"}),
        (4000, 4000, "stall::late_wake",
         {"late_ms": 23.25, "cpu_ms": 1.0, "cause": "host"}),
        (5000, 5000, "stall::host_freeze",         # a freeze of seconds
         {"late_s": 3.06, "armed": 1, "cpu_s": 0.01, "cause": "host"}),
        (6000, 6000, "stall::host_freeze",         # an older program's
         {"late_s": 2.0, "armed": 1}),
        (7000, 7400, "train.report", {"step": 5})]
    assert late_wakes.standstill_ms(_planes(host), "host") == \
        pytest.approx(112.5 + 23.25 + 3060.0)
    assert late_wakes.standstill_ms(_planes(host), "process") == 45.0
    # a clean window reads 0; no host plane, or no trace, reads nothing
    assert late_wakes.standstill_ms(_planes(host[-1:]), "host") == 0.0
    assert late_wakes.standstill_ms(_planes(None), "host") is None
    assert late_wakes.standstill_ms(None, "host") is None


def test_the_window_metric_reads_nothing_without_a_trace(out_dir,
                                                         monkeypatch):
    from benchmark import host_plane

    monkeypatch.setattr(host_plane, "TRACE_DIR", str(out_dir / "trace"))
    assert _read("trace_window_standstill_ms") is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_files_resolve_for_every_train_kind(name):
    """Each names its reader and every training kind; where the manifest
    lists the metric (a `benchmark` PR's to do: 128 entries are the
    manifest's limit and it holds 128) it lists every train cell."""
    from benchmark import resolve
    from benchmark.tools import standstill

    spec = resolve.layer_metric(name)
    reader, what = METRICS[name]
    assert spec["reader"] == reader and spec["unit"] == "ms"
    assert what in (spec.get("key"), spec.get("cause"))
    assert sorted(spec["kinds"]) == TRAIN_KINDS and len(TRAIN_KINDS) == 13
    assert callable(resolve.reader(reader).read)
    man = resolve.manifest()
    cells = [w["name"] for w in man["workloads"]]
    entry, = (e for e in standstill.manifest_entries() if e["name"] == name)
    assert entry["workloads"] == cells and len(cells) == 14
    assert (entry["moves"], entry["better"], entry["source"]) == (
        "train_tok_s_chip", "lower", "program_counter")
    assert entry["layer"] in {m["layer"] for m in man["per_layer"]}
    for listed in (m for m in man["per_layer"] if m["name"] == name):
        assert listed == entry


def test_the_tool_prints_a_runs_summary_and_its_metrics(out_dir, monkeypatch):
    from benchmark import host_plane
    from benchmark.tools import standstill

    monkeypatch.setattr(host_plane, "TRACE_DIR", str(out_dir / "trace"))
    _write_timeline(out_dir, [_summary(
        94.0, W0, 0, interval_median_ms=1309.8, wait_max_ms=2210.4,
        wait_max_step=5, host_late_ms=212.5, host_late_count=2,
        process_late_ms=0.0, process_late_count=0)])
    text = standstill.report("cell")
    assert "wait_max_step" in text and "2210.4" in text
    assert "host_standstill_ms           212.5" in text
    assert "train_step_interval_ms       1309.8" in text
    assert "trace_window_standstill_ms   nothing to read" in text
    assert "no timeline.json" in standstill.report("another")
