"""The program's spans read from the profiler's trace: interval
arithmetic on synthetic planes with hand-worked overlaps, the readers on a
host plane the test records itself on the CPU and on the trace recorded on
the chip, and the new metrics after a CPU rehearsal of each kind."""
import os
import subprocess
import sys

import pytest

from benchmark import host_plane as hp
from benchmark import resolve
from benchmark.readers import host_span, idle_under_span, module_share

TINY = os.path.join(os.path.dirname(__file__), "tiny_tpu.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# one device: ops busy [100, 200) and [300, 400) (two programs), idle
# between; a second device busy [100, 250)
OPS0 = [(100, 200, "%fusion.1 = f32[] fusion(%a)"),
        (300, 400, "%fusion.2 = f32[] fusion(%b)")]
MODS0 = [(100, 200, "jit_serve_prefill_tail(1)"),
         (300, 400, "jit_serve_decode_block_paged(2)")]
OPS1 = [(100, 250, "%fusion.1 = f32[] fusion(%a)")]
MODS1 = [(100, 250, "jit_serve_prefill(3)")]
HOST = [(50, 90, "train.report", {"step": 0}),       # before the device's
        (180, 320, "train.report", {"step": 1}),     # 20 busy, 100 idle, 20
        (350, 450, "train.report", {"step": 2}),     # 50 busy, 50 after
        (210, 220, "serve.decode_block", {"n": 8, "active": 2,
                                          "max_slots": 4, "context": 100}),
        (230, 240, "serve.decode_block", {"n": 2, "active": 4,
                                          "max_slots": 4, "context": 300}),
        (250, 250, "serve.admitted", {"queue_ms": 5.0}),
        (260, 260, "serve.admitted", {"queue_ms": 50.0})]


def planes(devices=((OPS0, MODS0),), host=HOST):
    devices = list(devices)
    return {"host": host, "devices": devices, "since": hp.since(devices)}


def test_spans_start_inside_the_devices_stretch():
    p = planes()
    assert p["since"] == 100
    assert [st["step"] for _, _, _, st in hp.spans(p, "train.report")] == [1, 2]
    assert hp.spans(p, "no.such") == []
    assert hp.spans(planes(host=None), "train.report") is None
    assert hp.spans(None, "train.report") is None


def test_covered_and_idle_under_by_hand():
    busy = [(100, 200), (300, 400)]
    assert hp.covered([(180, 320)], busy) == 40
    assert hp.covered([(0, 50), (450, 500)], busy) == 0
    assert hp.covered([(0, 500)], busy) == 200
    # spans 1 and 2 are open 140 + 100 ns; ops cover 40 + 50 of them
    assert hp.idle_under(planes(), "train.report") == pytest.approx(150e-9)
    # second device: busy [100, 250) covers 70 of span 1, none of span 2
    two = planes(devices=((OPS0, MODS0), (OPS1, MODS1)))
    assert hp.idle_under(two, "train.report") == pytest.approx(
        (150 + 170) / 2 * 1e-9)
    assert hp.idle_under(planes(devices=()), "train.report") is None
    rows = hp.largest_overlap(planes(), "train.report")
    # span 1 opens while the first op runs and closes in the second;
    # span 2 opens 150 after the first op's end, no op starts after it
    assert [(r["overlap_ns"], r["after_last_op_end_ns"],
             r["before_next_op_start_ns"]) for r in rows] == [
        (40, None, None), (50, 150, None)]
    gap, = hp.largest_overlap(planes(host=[(210, 290, "train.report", {})]),
                              "train.report")
    assert (gap["overlap_ns"], gap["after_last_op_end_ns"],
            gap["before_next_op_start_ns"]) == (0, 10, 10)


def test_module_seconds_by_name():
    two = planes(devices=((OPS0, MODS0), (OPS1, MODS1)))
    assert hp.module_seconds(two, "serve_prefill") == pytest.approx(
        (100 + 150) / 2 * 1e-9)
    assert hp.module_seconds(two, "serve_decode") == pytest.approx(50e-9)
    assert hp.module_seconds(two, "jit__lambda") == 0.0
    assert hp.module_seconds(planes(devices=()), "serve_prefill") is None


def _read(reader, spec, planes_, obs, monkeypatch):
    monkeypatch.setattr(hp, "of_run", lambda: planes_)
    return reader.read(spec, obs)


def test_readers_on_synthetic_planes(monkeypatch):
    obs = {"trace": {"window_s": 1000e-9}}
    p = planes()

    def span(spec, planes_=p, obs=obs):
        return _read(host_span, spec, planes_, obs, monkeypatch)

    blk = {"span": "serve.decode_block"}
    assert span({"span": "train.report", "stat": "median_ms"}) == \
        pytest.approx(120e-6)
    assert span({"span": "train.report", "stat": "count"}) == 2
    # open [180, 320) and [350, 450): 240 of 1000
    assert span({"span": "train.report", "stat": "sum_share",
                 "scale": 100.0}) == pytest.approx(24.0)
    assert span({**blk, "attr": "n", "stat": "mean"}) == 5
    assert span({**blk, "attr": "active", "over": "max_slots", "scale": 100.0,
                 "stat": "weighted_mean", "weight": "n"}) == pytest.approx(
        100.0 * (0.5 * 8 + 1.0 * 2) / 10)
    assert span({**blk, "attr": "context", "stat": "weighted_mean",
                 "weight": "n"}) == pytest.approx((100 * 8 + 300 * 2) / 10)
    assert span({"span": "serve.admitted", "attr": "queue_ms",
                 "stat": "p95"}) == 50.0
    # a program without spans: the count is 0, the rest reads nothing
    bare = planes(host=[])
    assert span({"span": "xla.compile", "stat": "count"}, bare) == 0
    assert span({"span": "train.report", "stat": "median_ms"}, bare) is None
    assert span({**blk, "attr": "n", "stat": "mean"}, bare) is None
    # no host plane, no trace: nothing at all
    for none in (planes(host=None), None):
        assert span({"span": "xla.compile", "stat": "count"}, none) is None
    assert span({"span": "train.report", "stat": "sum_share"}, p, {}) is None
    with pytest.raises(ValueError):
        span({"span": "train.report", "stat": "mode"})

    assert _read(idle_under_span, {"span": "train.report"}, p, obs,
                 monkeypatch) == pytest.approx(15.0)
    assert _read(idle_under_span, {"span": "train.report"}, p, {},
                 monkeypatch) is None
    assert _read(idle_under_span, {"span": "train.report"},
                 planes(host=None), obs, monkeypatch) is None
    assert _read(module_share, {"match": "serve_prefill"}, p, obs,
                 monkeypatch) == pytest.approx(10.0)
    assert _read(module_share, {"match": "serve_prefill"}, None, obs,
                 monkeypatch) is None


def test_chip_trace_without_spans_reads_zero_compiles():
    """``tiny_tpu.xplane.pb`` was recorded with the host tracer off and
    before the program had spans: what a traced run of an older commit
    gives the new readers."""
    p = hp.read_file(TINY)
    assert p["host"] == [] and len(p["devices"]) == 1
    assert p["since"] == 42699305
    assert hp.summary(p, "train.report")["count"] == 0
    assert hp.idle_under(p, "train.report") is None
    # three executions of 6.71 us each
    assert hp.module_seconds(p, "tiny_program") == pytest.approx(
        2.0135e-05, rel=1e-3)


def test_host_plane_recorded_here(tmp_path, monkeypatch):
    """A profile on the CPU around the program's own span primitive, read
    back through the file reader and the metric files' own specs."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import tracing

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for i in range(3):
        f(x).block_until_ready()
        with tracing.span("train.report", {"step": i, "has_state": False}):
            pass
    for n, active in ((8, 2), (1, 4)):
        with tracing.span("serve.decode_block", {
                "n": n, "n_asked": 8, "active": active, "max_slots": 4,
                "context": 100 * active}):
            with tracing.span("serve.decode_block.fetch"):
                pass
    tracing.instant("serve.admitted", {"queue_ms": 12.5, "prompt_tokens": 9,
                                       "prefix_hit_tokens": 0})
    tracing.instant("xla.compile", {"seconds": 0.5})
    jax.profiler.stop_trace()
    monkeypatch.setattr(hp, "TRACE_DIR", str(tmp_path))
    p = hp.of_run()
    assert p["devices"] == [] and p["since"] == 0
    assert [st for _, _, _, st in hp.spans(p, "train.report")] == [
        {"step": i, "has_state": 0} for i in range(3)]

    def metric(name, obs={}):
        spec = resolve.layer_metric(name)
        return resolve.reader(spec["reader"]).read(spec, obs)

    assert 0 < metric("train_report_span_ms") < 50
    assert metric("compiles_in_trace.train") == 1
    assert metric("compiles_in_trace.serve") == 1
    assert metric("queue_wait_p95_ms") == 12.5
    assert metric("decode_steps_per_block") == 4.5
    assert metric("batch_occupancy") == pytest.approx(
        100.0 * (0.5 * 8 + 1.0 * 1) / 9)
    assert metric("live_context_tokens") == pytest.approx(
        (200 * 8 + 400 * 1) / 9)
    assert 0 < metric("decode_fetch_wait_share",
                      {"trace": {"window_s": 10.0}}) < 1
    # the CPU has no device plane: nothing under these names
    for name in ("device_idle_under_report.train", "prefill_device_share.serve",
                 "device_idle_under_admit.serve",
                 "device_idle_under_deliver.serve"):
        assert metric(name, {"trace": {"window_s": 10.0}}) is None


NEW = {"train": ["train_report_span_ms", "device_idle_under_report.train",
                 "compiles_in_trace.train"],
       "serve": ["compiles_in_trace.serve", "queue_wait_p95_ms",
                 "decode_steps_per_block", "batch_occupancy",
                 "live_context_tokens", "decode_fetch_wait_share",
                 "device_idle_under_admit.serve",
                 "device_idle_under_deliver.serve",
                 "prefill_device_share.serve"]}
# what only a device plane or the traced window can give: not on the CPU
NEEDS_DEVICE = {"device_idle_under_report.train", "prefill_device_share.serve",
                "device_idle_under_admit.serve",
                "device_idle_under_deliver.serve"}


@pytest.mark.parametrize("cell,kind,seconds", [
    ("rehearse-train", "train", "2"), ("rehearse-serve", "serve", "4")])
def test_rehearsal_leaves_every_new_metric_of_its_kind(cell, kind, seconds):
    """``run.py`` refuses to print a metric off the chip, so the readers
    are called here, on the trace the rehearsal left in ``out/trace``: a
    cell the manifest does not list takes every metric file of its kind."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_CHIPS="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", seconds, "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 1 and "REFUSED: ran on 'cpu'" in run.stdout, \
        run.stdout[-3000:] + run.stderr[-3000:]
    names = {m["name"] for m in resolve.metrics_for(cell, "per_layer", kind)}
    assert set(NEW[kind]) <= names
    obs = {"trace": {"window_s": float(seconds)}}   # the CPU gives none
    for name in NEW[kind]:
        spec = resolve.layer_metric(name)
        value = resolve.reader(spec["reader"]).read(spec, obs)
        print(f"{cell}: {name} = {value} {spec['unit']}")
        if name in NEEDS_DEVICE:
            assert value is None, name
        else:
            assert value is not None and value >= 0, name
    if kind == "serve":
        spec = resolve.layer_metric("decode_steps_per_block")
        assert 1 <= resolve.reader("host_span").read(spec, obs) <= 4
