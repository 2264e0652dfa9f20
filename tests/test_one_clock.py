"""One clock for host and device (ISSUE 24): a program span is also an
event of jax's profiler, on the trainer's and the engine's hot paths;
compiles are counted in the process; two clocks tell a frozen host from a
device that does not answer."""

import glob
import json
import os
import subprocess
import sys

import pytest

from ray_tpu.observability import flight as flight_mod
from ray_tpu.observability import health
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _host_events(trace_dir):
    """``[(name, stats, start_ns, duration_ns), ...]`` of plane
    /host:CPU, every thread."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, dict(e.stats), e.start_ns, e.duration_ns)
                        for e in line.events]
    return out


class _Profile:
    """A profile as both kinds of the benchmark take it."""

    def __init__(self, trace_dir):
        self.dir = str(trace_dir)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()


class _Recorder:
    """Stands in for the runtime: what `tracing` records, and a flight
    ring that dumps under `tmp`."""

    mode, node_id = "driver", "n0"

    def __init__(self, tmp):
        class _Cfg:
            flight_recorder_size = 256
            flight_recorder_dir = str(tmp)

        class _Id:
            @staticmethod
            def hex():
                return "feedfacecafe0000"

        self.cfg, self.worker_id, self.spans = _Cfg, _Id, []
        self.flight = flight_mod.FlightRecorder(self)

    def record_span(self, span):
        self.spans.append(span)
        self.flight.record(span)


@pytest.fixture
def recorder(tmp_path, monkeypatch):
    from ray_tpu.core import runtime as rt_mod

    rec = _Recorder(tmp_path / "flight")
    monkeypatch.setattr(rt_mod, "_global_runtime", rec)
    health._reset_for_tests()
    yield rec
    tracing._enabled = None


@pytest.mark.parametrize("enabled", [False, True])
def test_span_and_instant_are_profiler_events(enabled, recorder, tmp_path):
    import jax  # noqa: F401 - the annotation exists once jax is imported

    (tracing.enable if enabled else tracing.disable)()
    with _Profile(tmp_path / "trace"):
        with tracing.span("train.report", {"step": 3, "has_state": False,
                                           "skipped": [1, 2]}):
            tracing.instant("xla.compile", {"seconds": 0.25})
    events = {name: stats for name, stats, _, _ in
              _host_events(tmp_path / "trace")}
    assert events["train.report"] == {"step": 3, "has_state": 0}
    assert events["xla.compile"] == {"seconds": 0.25}
    # the GCS half is as before: only with tracing on
    names = [(s["kind"], s["name"]) for s in recorder.spans]
    assert names == ([("instant", "xla.compile"), ("span", "train.report")]
                     if enabled else [])


def test_tracing_off_and_no_profile_reaches_nothing(recorder):
    tracing.disable()
    with tracing.span("serve.admit", {"rows": 1}) as rec:
        assert rec is None
    assert tracing.instant("serve.admitted", {"queue_ms": 1.0}) is None
    assert recorder.spans == [] and len(recorder.flight._ring) == 0
    # a stall is recorded whatever the setting
    assert tracing.instant("stall::host_freeze", {"late_s": 3.0},
                           always=True)["kind"] == "instant"
    assert [s["name"] for s in recorder.spans] == ["stall::host_freeze"]


def test_tracing_imports_no_jax():
    code = ("import sys; import ray_tpu.util.tracing as t, "
            "ray_tpu.core.compile_cache as c\n"
            "with t.span('a', {'x': 1}): t.instant('b')\n"
            "assert c.listen() is False\n"
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == "False", out.stdout + out.stderr


def test_compile_count_rises_for_a_new_shape_only():
    import jax
    import jax.numpy as jnp

    from ray_tpu.core import compile_cache

    assert compile_cache.listen() is True
    f = jax.jit(lambda x: x * 2 + 1)
    a, b = jnp.ones((3,)), jnp.ones((5,))     # eager ops compile too
    f(a)
    n0, s0 = compile_cache.compile_count(), compile_cache.compile_seconds()
    f(a)
    assert compile_cache.compile_count() == n0
    f(b)
    assert compile_cache.compile_count() == n0 + 1
    assert compile_cache.compile_seconds() > s0


def _report_loop(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import session

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    float(f(x)[0, 0])
    jax.profiler.start_trace(config["trace"], profiler_options=opts)
    for i in range(3):
        loss = float(f(x)[0, 0])
        session.report({"loss": loss, "step": i})
    jax.profiler.stop_trace()
    return {"ok": True}


def test_session_report_leaves_train_report(ray_start_regular, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        _report_loop, train_loop_config={"trace": str(tmp_path / "trace")},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=False),
        run_config=RunConfig(name="one_clock",
                             storage_path=str(tmp_path / "run"))).fit()
    assert not result.error, result.error
    reports = [stats for name, stats, _, _ in
               _host_events(tmp_path / "trace") if name == "train.report"]
    assert sorted(r["step"] for r in reports) == [0, 1, 2]
    assert all(r["has_state"] == 0 for r in reports)


@pytest.fixture(scope="module")
def engine():
    from ray_tpu.serve.llm import LLMEngine

    return LLMEngine(preset="tiny", max_slots=4, max_seq_len=64, seed=3,
                     kv_layout="paged", page_size=8)


@pytest.mark.parametrize("tokens,block,n_asked", [(9, 8, 8), (2, 1, 8)])
def test_engine_leaves_its_spans(engine, tmp_path, tokens, block, n_asked):
    """First token at admission, then one block of `block` steps: 8 fused
    steps, or the one-step block that falls through to `step()`."""
    before = dict(engine.metrics)
    with _Profile(tmp_path / "trace"):
        # another prompt each case: no prefix of it is cached
        req = engine.submit(list(range(tokens, tokens + 11)),
                            max_new_tokens=tokens)
        while not req.done_event.is_set():
            engine.step_n(8)
    assert len(req.generated) == tokens
    events = _host_events(tmp_path / "trace")
    by_name = {}
    for name, stats, start, dur in events:
        by_name.setdefault(name, []).append((stats, start, start + dur))
    for name in ("serve.admit", "serve.prefill", "serve.admitted",
                 "serve.decode_block", "serve.decode_block.dispatch",
                 "serve.decode_block.fetch", "serve.deliver"):
        assert name in by_name, sorted(by_name)
    admitted, = by_name["serve.admitted"]
    assert admitted[0]["prompt_tokens"] == 11
    assert admitted[0]["queue_ms"] >= 0 and "prefix_hit_tokens" in admitted[0]
    prefill, = by_name["serve.prefill"]
    assert prefill[0]["rows"] == 1 and prefill[0]["prompt_tokens"] == 11
    blk, = by_name["serve.decode_block"]
    assert blk[0] == {"n": block, "n_asked": n_asked, "active": 1,
                      "max_slots": 4, "context": 11}
    # the two children lie inside the block, dispatch before fetch
    (_, d0, d1), = by_name["serve.decode_block.dispatch"]
    (_, f0, f1), = by_name["serve.decode_block.fetch"]
    assert blk[1] <= d0 <= d1 <= f0 <= f1 <= blk[2]
    # the block is counted whatever its length, a token once
    m = engine.metrics
    assert m["decode_blocks"] - before.get("decode_blocks", 0) == 1
    assert m["tokens_generated"] - before["tokens_generated"] == tokens
    assert m["decode_block_s"] > before.get("decode_block_s", 0.0)


def test_engine_programs_have_names(engine):
    names = {engine._decode_paged.__name__, engine._scatter.__name__,
             engine._prefill_tail.__name__, engine._decode_n_paged.__name__,
             engine._prefill.__name__, engine._decode_n.__name__}
    assert names == {"serve_decode_step_paged", "serve_scatter_pages",
                     "serve_prefill_tail", "serve_decode_block_paged",
                     "serve_prefill", "serve_decode_block"}


def test_server_stats_count_compiles_and_freezes(engine):
    from ray_tpu.serve.llm import LLMServer

    srv = LLMServer.__new__(LLMServer)      # stats() reads these only
    srv.engine, srv._draining, srv.mode = engine, False, "monolithic"
    srv.multiplexed, srv._exporter, srv._adopter = False, None, None
    s = srv.stats()
    assert s["compiles"] > 0 and s["compile_s"] > 0
    assert s["host_freezes"] == health.counters()["host_freezes"]
    assert "ttft_p50_s" not in s and "ttft_p99_s" not in s


class _Clock:
    """A clock that jumps by `late` across every sleep, beside a CPU
    clock of the process that runs for `busy` of every sleep."""

    def __init__(self, late, busy=0.0):
        self.now, self.late = 100.0, late
        self.cpu_now, self.busy, self.slept = 7.0, busy, []

    def __call__(self):
        return self.now

    def cpu(self):
        return self.cpu_now

    def sleep(self, s):
        self.slept.append(s)
        self.now += s + self.late
        self.cpu_now += self.busy

    def watcher(self, counters=None):
        return health.FreezeWatcher(clock=self, sleep=self.sleep,
                                    cpu_clock=self.cpu, counters=counters)


def _no_counts():
    """Counters of a test's own: the process's running watcher (an
    earlier test's cluster started it) counts into the module's."""
    return dict.fromkeys(health.counters(), 0)


def _loop_at_work():
    loop = health.beacon("test:loop", 30.0)
    loop.arm()
    loop.tick()
    return loop


@pytest.mark.parametrize("jump,frozen", [(3.0, True), (0.5, False)])
def test_watcher_tells_a_frozen_host(jump, frozen, recorder):
    loop = health.beacon("test:loop", 30.0)   # a loop is under way: dump
    loop.arm()
    loop.tick()
    clock = _Clock(jump)
    w = health.FreezeWatcher(clock=clock, sleep=clock.sleep)
    late = w.run_once()
    stalls = [s for s in recorder.spans if s["name"] == "stall::host_freeze"]
    if frozen:
        assert late == pytest.approx(3.0)
        assert stalls[0]["attrs"]["late_s"] == pytest.approx(3.0)
        assert health.counters()["host_freezes"] == 1
        assert health.counters()["host_freeze_s"] == pytest.approx(3.0)
        assert recorder.flight.dumps_written == 1
    else:
        assert late is None and stalls == []
        assert health.counters()["host_freezes"] == 0
        assert recorder.flight.dumps_written == 0


def test_long_fetch_with_the_watcher_on_time_is_a_device_wait(recorder):
    watch = health.WaitWatch("serve.decode_block.fetch")
    for _ in range(6):
        assert not watch.observe(0.3, n=8, active=32)
    assert not watch.observe(1.9, n=8, active=32)     # under 2 s
    assert watch.observe(7.5, n=4, active=31, context=20000)
    assert watch.observe(9.0, n=4, active=31)         # rate-limited dump
    stalls = [s for s in recorder.spans if s["name"] == "stall::device_wait"]
    assert len(stalls) == 2
    assert stalls[0]["attrs"]["waited_s"] == 7.5
    assert stalls[0]["attrs"]["host_freezes"] == 0    # the host was on time
    assert stalls[0]["attrs"]["context"] == 20000
    assert recorder.flight.dumps_written == 1
    dump, = flight_mod.list_dumps(recorder.cfg.flight_recorder_dir)
    doc = flight_mod.load_dump(dump)
    assert doc["reason"] == "stall:device_wait:serve.decode_block.fetch"
    assert "stall::device_wait" in flight_mod.render_summary(doc)
    chrome = flight_mod.to_chrome(doc)
    assert any(e.get("ph") == "i" and e["name"] == "stall::device_wait"
               for e in chrome)
    json.dumps(chrome)


def test_a_freeze_before_any_loop_runs_is_counted_and_no_stall(
        recorder, caplog):
    """The opening of a TPU freezes its host at every job's start: with
    no beacon armed, or one armed that has not ticked yet (the train
    worker before its first report), nothing warns and nothing is dumped;
    the instant says `armed` false (the job's timeline counts it as
    set-up that was the machine's)."""
    clock = _Clock(12.0, busy=0.25)
    w = clock.watcher()
    with caplog.at_level("INFO", logger="ray_tpu.health"):
        assert w.run_once() == pytest.approx(12.0)
        loop = health.beacon("test:loop", 30.0)
        loop.arm()
        assert w.run_once() == pytest.approx(12.0)
    assert health.counters()["host_freezes"] == 2
    assert health.counters()["host_freeze_s"] == pytest.approx(24.0)
    # a freeze is a late wake too, and the process's CPU clock says whose
    assert health.counters()["host_late_count"] == 2
    assert health.counters()["host_late_ms"] == pytest.approx(24000.0)
    assert health.counters()["process_late_count"] == 0
    assert [(s["name"], s["attrs"]) for s in recorder.spans] == [
        ("stall::host_freeze", {"late_s": 12.0, "armed": False,
                                "cpu_s": 0.25, "cause": "host"})] * 2
    assert recorder.flight.dumps_written == 0
    assert [r.levelname for r in caplog.records] == ["INFO", "INFO"]
    # under way: a stall, dumped under the recorder's rate limit
    loop.tick()
    w.run_once()
    w.run_once()
    assert [s["attrs"]["armed"] for s in recorder.spans] == [
        False, False, True, True]
    assert recorder.flight.dumps_written == 1


def test_an_unarmed_freeze_is_kept_with_tracing_off(recorder):
    """`FreezeWatcher.run_once` with no armed beacon and tracing off: the
    chip's opening is on the job's timeline as `stall::host_freeze`."""
    tracing.disable()
    clock = _Clock(6.5, busy=6.0)     # a native call held the lock
    late = clock.watcher().run_once()
    assert late == pytest.approx(6.5)
    said, = recorder.spans
    assert (said["kind"], said["name"]) == ("instant", "stall::host_freeze")
    assert said["attrs"] == {"late_s": 6.5, "armed": False,
                             "cpu_s": 6.0, "cause": "process"}
    assert recorder.flight.dumps_written == 0


def test_a_late_wake_under_a_second_is_counted_and_nothing_else(
        recorder, caplog):
    """0.3 s late with a loop under way: four counters move; no WARNING,
    no kept record, no dump, and the freeze counters stand."""
    tracing.disable()
    _loop_at_work()
    clock, counts = _Clock(0.3), _no_counts()
    with caplog.at_level("INFO", logger="ray_tpu.health"):
        assert clock.watcher(counts).run_once() is None
    assert counts == {**_no_counts(), "host_late_count": 1,
                      "host_late_ms": pytest.approx(300.0)}
    assert recorder.spans == [] and len(recorder.flight._ring) == 0
    assert recorder.flight.dumps_written == 0
    assert caplog.records == []


@pytest.mark.parametrize("busy_share,cause", [
    (1.0, "process"), (0.5, "process"), (0.1, "host"), (0.0, "host")])
def test_the_cpu_clock_says_whose_standstill_it_was(
        busy_share, cause, recorder):
    """The process ran for `busy_share` of the lateness while its watcher
    could not wake: from half up the process held it, else the host."""
    late = 0.4
    clock, counts = _Clock(late, busy=busy_share * late), _no_counts()
    clock.watcher(counts).run_once()
    other = "host" if cause == "process" else "process"
    assert counts[cause + "_late_count"] == 1
    assert counts[cause + "_late_ms"] == pytest.approx(400.0)
    assert counts[other + "_late_count"] == 0 and counts[other + "_late_ms"] == 0


def test_a_late_wake_is_an_instant_for_tracing_and_the_profile(
        recorder, tmp_path):
    """Plain, not kept: recorded with tracing on, and under a profile an
    event of /host:CPU with its scalars (on the watcher's own thread's
    line, wherever that runs)."""
    import jax  # noqa: F401 - the annotation exists once jax is imported

    tracing.enable()
    clock = _Clock(0.05, busy=0.04)
    with _Profile(tmp_path / "trace"):
        clock.watcher(_no_counts()).run_once()
    mine = [s for s in recorder.spans if s["name"] == "stall::late_wake"
            and s["attrs"]["late_ms"] == 50.0]
    assert [(s["kind"], s["attrs"]) for s in mine] == [
        ("instant", {"late_ms": 50.0, "cpu_ms": 40.0, "cause": "process"})]
    events = [stats for name, stats, _, _ in _host_events(tmp_path / "trace")
              if name == "stall::late_wake" and stats.get("late_ms") == 50.0]
    assert len(events) == 1 and events[0]["cpu_ms"] == 40.0
    assert events[0]["cause"] == "process"


def test_the_watcher_sleeps_10_ms_only_while_a_loop_is_under_way(recorder):
    clock = _Clock(0.0)
    w = clock.watcher(_no_counts())
    w.run_once()                               # no beacon
    loop = health.beacon("test:loop", 30.0)
    loop.arm()
    w.run_once()                               # armed, not ticked yet
    loop.tick()
    w.run_once()                               # under way
    loop.disarm()
    w.run_once()                               # idle again
    assert clock.slept == [0.1, 0.1, 0.01, 0.1]
    assert (w.PERIOD_S, w.BUSY_PERIOD_S, w.LATE_WAKE_S, w.LATE_S) == (
        0.1, 0.01, 0.02, 1.0)


def test_a_wake_on_time_counts_nothing(recorder):
    """Up to 20 ms late is the scheduler's everyday."""
    _loop_at_work()
    clock, counts = _Clock(0.019), _no_counts()
    assert clock.watcher(counts).run_once() is None
    assert counts == _no_counts()


def test_a_freeze_under_way_still_warns_dumps_and_says_whose(
        recorder, caplog):
    """3 s late with a loop under way: the parent's counters, WARNING and
    dump, the kept instant now with `cpu_s` and `cause`."""
    tracing.disable()
    _loop_at_work()
    clock = _Clock(3.0, busy=2.9)
    with caplog.at_level("INFO", logger="ray_tpu.health"):
        assert clock.watcher().run_once() == pytest.approx(3.0)
    assert health.counters()["host_freezes"] == 1
    assert health.counters()["host_freeze_s"] == pytest.approx(3.0)
    assert health.counters()["process_late_count"] == 1
    said, = recorder.spans
    assert (said["kind"], said["name"]) == ("instant", "stall::host_freeze")
    assert said["attrs"] == {"late_s": 3.0, "armed": True, "cpu_s": 2.9,
                             "cause": "process"}
    assert [r.levelname for r in caplog.records] == ["WARNING"]
    assert "stall::host_freeze" in caplog.records[0].getMessage()
    assert recorder.flight.dumps_written == 1
    dump, = flight_mod.list_dumps(recorder.cfg.flight_recorder_dir)
    doc = flight_mod.load_dump(dump)
    assert doc["reason"] == "host_freeze:3.0s"
    assert doc["extra"]["cause"] == "process"


def test_dumps_go_beside_the_session_directories(monkeypatch, tmp_path):
    monkeypatch.delenv("RAY_TPU_TMPDIR", raising=False)
    assert flight_mod.default_dir() == "/tmp/ray_tpu/flight"
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path))
    assert flight_mod.default_dir() == str(tmp_path / "flight")
    rec = _Recorder(tmp_path)
    rec.cfg.flight_recorder_dir = ""
    path = rec.flight.dump("test")
    assert path.startswith(str(tmp_path / "flight"))
    assert flight_mod.list_dumps() == [path]
