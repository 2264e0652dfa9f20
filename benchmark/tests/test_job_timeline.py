"""The set-up budget (``readers/job_timeline.py``) on a timeline worked by
hand: two processes, overlapping spans, a compile of each outcome, a
freeze, a stale file, a missing file; the manifest's eight entries; the
tool's report."""
import json
import os

import pytest

from benchmark import resolve
from benchmark.readers import job_timeline as jt
from benchmark.tools import job_timeline as tool

T0 = 1_790_000_000.0          # time.time() of the job's host
W0, W1 = "aaaaaaaaaaaa", "bbbbbbbbbbbb"

PARTS = {"setup_cluster_s": ("cluster_s", "s", "core runtime"),
         "setup_worker_group_s": ("worker_group_s", "s", "trainer"),
         "setup_chips_open_s": ("chips_open_s", "s", "device"),
         "setup_trace_lower_s": ("trace_lower_s", "s", "train step"),
         "setup_program_compile_s": ("program_compile_s", "s",
                                     "compile cache"),
         "setup_program_load_s": ("program_load_s", "s", "compile cache"),
         "setup_host_freeze_s": ("host_freeze_s", "s", "device"),
         "setup_unspanned_share": ("unspanned_share", "%", "trainer")}
TRAIN_KINDS = {"train", "train_moe", "train_hybrid", "train_latent",
               "train_mixed", "train_parallel", "train_sparse",
               "train_alternating"}


def span(name, at, dur, worker=None, **attrs):
    return {"name": name, "cat": "span", "ph": "X", "pid": 1, "tid": 1,
            "ts": (T0 + at) * 1e6, "dur": dur * 1e6,
            "args": {"trace_id": None, "span_id": "s", "parent_id": None,
                     "worker": worker, "attrs": attrs}}


def instant(name, at, worker=None, **attrs):
    return {"name": name, "cat": "instant", "ph": "i", "pid": 1, "tid": 2,
            "ts": (T0 + at) * 1e6, "s": "p",
            "args": {"name": name, "trace_id": None, "parent_id": None,
                     "worker": worker, "attrs": attrs}}


def compiled(at, seconds, cache, worker=W0, program="jit(_step)",
             retrieval_s=0.0):
    return instant("xla.compile", at, worker, seconds=seconds, cache=cache,
                   program=program, retrieval_s=retrieval_s)


# Set-up runs from 0 (core.init's start) to 100 (rank 0's first report).
# Named, in seconds of [0, 100]: core.init [0, 2]; train.fit's start to
# rank 0's loop [2, 14], the chips' opening [6, 12] inside it and a freeze
# [7, 11] of every process inside that; the step's trace [20, 23] with a
# kernel's inside it and its lowering [23, 25]; a load from the cache
# [25, 55]; a compile [50, 60] on another thread that overlaps it by 5; an
# uncached compile [70, 71]: 2 + 12 + 5 + 35 + 1 = 55.
EVENTS = [
    {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
     "args": {"name": "driver"}},
    span("core.init", 0.0, 2.0, nodes=1, num_cpus=8.0),
    span("core.init.gcs", 0.0, 1.0),
    span("train.fit", 2.0, 150.0, workers=2, chips_per_worker=1),
    span("train.group_start", 2.0, 4.5, workers=2),
    span("train.worker_setup", 5.0, 0.5, W0, rank=0),
    span("train.worker_setup", 5.2, 0.5, W1, rank=1),
    span("train.chips_open", 6.0, 6.0, W0, platform="tpu", kind="v5e",
         count=1),
    span("train.chips_open", 6.5, 9.0, W1, platform="tpu", kind="v5e",
         count=1),
    instant("stall::host_freeze", 11.0, W0, late_s=4.0, armed=False),
    instant("stall::host_freeze", 11.0, W1, late_s=4.0, armed=False),
    instant("stall::host_freeze", 11.0, None, late_s=4.0, armed=False),
    span("train.loop", 14.0, 130.0, W0, rank=0),
    span("train.loop", 16.0, 128.0, W1, rank=1),
    span("xla.trace", 20.0, 3.0, W0, program="_step"),
    span("xla.lower", 23.0, 2.0, W0, program="jit(_step)"),
    span("xla.trace", 21.0, 0.5, W0, program="gmm"),    # inside _step's
    span("xla.trace", 20.0, 30.0, W1, program="_step"),      # not rank 0's
    instant("flash.fwd_plan", 21.0, W0, path="stream", span=4096),
    instant("flash.bwd_plan", 22.0, W0, path="stream", whole_steps=400),
    compiled(55.0, 30.0, "hit", retrieval_s=29.0),
    compiled(60.0, 10.0, "miss", program="jit(routes)"),
    compiled(71.0, 1.0, "off", program="jit(draw)"),
    compiled(71.0, 50.0, "miss", worker=W1),                 # not rank 0's
    # rank 0's watcher alone woke late as its compile ended: its own
    # process held the interpreter's lock, the host ran on
    instant("stall::host_freeze", 59.5, W0, late_s=3.0, armed=False),
    instant("train.first_report", 100.0, W0, step=2),
    instant("train.first_report", 103.0, W1, step=2),
    compiled(120.0, 7.0, "miss", program="jit(late)"),       # after set-up
    instant("stall::host_freeze", 125.0, W0, late_s=3.0, armed=True),
    span("train.report", 101.0, 0.001, W0, step=3),
]
WANT = {"cluster_s": 2.0, "worker_group_s": 6.0, "chips_open_s": 6.0,
        "trace_lower_s": 5.0, "program_compile_s": 11.0,
        "program_load_s": 30.0, "host_freeze_s": 4.0,
        "unspanned_share": 45.0}


def write(run_dir, events=EVENTS):
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, "timeline.json"), "w") as f:
        json.dump(events, f)
    return str(run_dir)


@pytest.mark.parametrize("part", sorted(WANT))
def test_the_budget_of_a_timeline_worked_by_hand(part, tmp_path):
    records = jt.load(write(tmp_path / "bench_x"))
    assert jt.budget(records)[part] == pytest.approx(WANT[part], abs=1e-3)


def test_a_freeze_is_the_hosts_where_another_process_shares_it(tmp_path):
    records = jt.load(write(tmp_path / "x"))
    mine = jt.named(records, jt.FREEZE, W0)
    assert [r["start"] - T0 for r in mine] == [11.0, 59.5, 125.0]
    assert [r["start"] - T0 for r in jt.host_freezes(records, W0)] == [11.0]
    # the driver's and rank 1's are each other's and rank 0's
    assert len(jt.host_freezes(records, None)) == 1
    alone = [e for e in EVENTS if e["name"] != jt.FREEZE
             or e["args"]["worker"] == W0]
    assert jt.budget(jt.load(write(tmp_path / "y", alone)))[
        "host_freeze_s"] == 0.0


def test_union_counts_an_overlap_once_and_clips():
    assert jt.union_s([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert jt.union_s([(-5, 1), (9, 20)], 0, 10) == 2
    assert jt.union_s([(2, 8), (3, 4)], 0, 10) == 6
    assert jt.union_s([], 0, 10) == 0


def test_set_up_is_rank_zeros_and_the_last_attempts(tmp_path):
    su = jt.setup_of(jt.load(write(tmp_path / "a")))
    assert su["worker"] == W0
    assert (su["start"], su["end"]) == (T0, T0 + 100.0)
    # a fit that restarted its group: the attempt that ran counts
    again = EVENTS + [
        span("train.loop", 200.0, 50.0, "cccccccccccc", rank=0),
        instant("train.first_report", 230.0, "cccccccccccc", step=0)]
    su = jt.setup_of(jt.load(write(tmp_path / "b", again)))
    assert su["worker"] == "cccccccccccc" and su["end"] == T0 + 230.0
    assert su["chips_open"] is None
    assert jt.budget(jt.load(str(tmp_path / "b")))["chips_open_s"] == 0.0


@pytest.mark.parametrize("missing", ["core.init", "train.fit", "train.loop",
                                     "train.first_report"])
def test_a_timeline_without_its_ends_reads_nothing(missing, tmp_path):
    events = [e for e in EVENTS if e["name"] != missing]
    assert jt.budget(jt.load(write(tmp_path / "x", events))) == {}


def test_a_missing_and_a_stale_file_read_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(jt, "OUT", str(tmp_path))
    spec, obs = {"part": "cluster_s"}, {"cell": {"name": "x"}}
    os.makedirs(tmp_path / "sessions")
    assert jt.read(spec, obs) is None                  # a program without
    write(tmp_path / "bench_x")
    assert jt.read(spec, obs) == pytest.approx(2.0)
    # run.py made out/sessions anew after the file was written: last run's
    later = os.path.getmtime(tmp_path / "bench_x" / "timeline.json") + 5
    os.utime(tmp_path / "sessions", (later, later))
    assert jt.read(spec, obs) is None
    assert jt.load(str(tmp_path / "nowhere")) is None


def test_the_manifest_lists_every_cell_for_the_eight_metrics():
    man = resolve.manifest()
    cells = [w["name"] for w in man["workloads"]]
    mine = [m for m in man["per_layer"] if m["name"] in PARTS]
    assert [m["name"] for m in mine] == list(PARTS)
    assert man["per_layer"][-len(PARTS):] == mine          # appended
    for m in mine:
        part, unit, layer = PARTS[m["name"]]
        assert (m["unit"], m["layer"], m["moves"], m["source"],
                m["better"]) == (unit, layer, "setup_s", "program_counter",
                                 "lower")
        # every cell there is now; a later cell is appended
        assert set(cells[:9]) <= set(m["workloads"]) <= set(cells)
        spec = resolve.layer_metric(m["name"])
        assert (spec["reader"], spec["part"], spec["unit"]) == (
            "job_timeline", part, unit)
        assert set(spec["kinds"]) == TRAIN_KINDS
        assert resolve.reader(spec["reader"]) is jt
    # a cell the manifest does not list takes them by its kind
    names = {m["name"] for m in resolve.metrics_for(
        "rehearse-train", "per_layer", "train")}
    assert set(PARTS) <= names
    assert not set(PARTS) & {m["name"] for m in resolve.metrics_for(
        "rehearse-serve", "per_layer", "serve")}


def test_the_tool_prints_phases_compiles_and_plans(tmp_path, capsys):
    assert tool.main(["job_timeline", write(tmp_path / "bench_x")]) == 0
    out = capsys.readouterr().out
    assert "set-up 100.000 s" in out and W0 in out
    assert "setup_program_load_s" in out and "30.000" in out
    lines = out.splitlines()
    compiles = lines[lines.index(next(
        ln for ln in lines if ln.startswith("compiles"))) + 1:][:3]
    assert [ln.split()[3:] for ln in compiles] == [
        ["hit", "jit(_step)"], ["miss", "jit(routes)"], ["off", "jit(draw)"]]
    assert "29.000" in compiles[0]                       # the retrieval
    assert "jit(late)" not in out                        # after set-up
    # a kernel's trace inside the step's is counted with it, not listed
    assert "{'program': '_step'} (1 inside)" in out and "'gmm'" not in out
    plans = [ln for ln in lines if "_plan" in ln]
    assert len(plans) == 2 and "'whole_steps': 400" in plans[1]
    assert tool.main(["job_timeline", str(tmp_path / "nowhere")]) == 1
    assert tool.main(["job_timeline"]) == 2
