"""What a job keeps of its set-up (ISSUE 50): kept spans and instants
recorded with tracing off, every compile by name and cache outcome, the
plans a step was lowered with, and the timeline ``JaxTrainer.fit`` leaves
beside its checkpoints."""

import contextlib
import importlib
import json
import os
import sys

import pytest

import ray_tpu
from ray_tpu.core import compile_cache
from ray_tpu.observability import chrome_trace
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SYSTEM = {"health_check_period_s": 0.2, "worker_idle_timeout_s": 60.0}


class _Recorder:
    """Stands in for the runtime: what `tracing` records."""

    mode = "driver"

    def __init__(self):
        self.spans = []

    def record_span(self, span):
        self.spans.append(span)


@pytest.fixture
def recorder(monkeypatch):
    from ray_tpu.core import runtime as rt_mod

    rec = _Recorder()
    monkeypatch.setattr(rt_mod, "_global_runtime", rec)
    yield rec
    tracing._enabled = None


# ---------------------------------------------------------------- kept records

def test_a_kept_span_with_tracing_off_is_no_context(recorder):
    tracing.disable()
    with tracing.span("train.loop", {"rank": 0}, always=True) as loop:
        assert tracing.current_context() is None
        with tracing.span("train.report", {"step": 0}) as report:
            assert report is None          # the kept span opted nothing in
        assert tracing.instant("serve.admitted") is None
        tracing.plan("flash.fwd_plan", {"path": "loop"})
        tracing.emit_span("data::map", 1.0, 2.0)
        tracing.emit_span("xla.trace", 10.0, 0.5, {"program": "_step"},
                          always=True)
    assert [(s["kind"], s["name"]) for s in recorder.spans] == [
        ("instant", "flash.fwd_plan"), ("span", "xla.trace"),
        ("span", "train.loop")]
    plan, stage, kept = recorder.spans
    assert kept is loop and kept["attrs"] == {"rank": 0} and kept["dur"] >= 0
    assert kept["trace_id"] is None and kept["parent_id"] is None
    assert (stage["ts"], stage["dur"], stage["trace_id"]) == (10.0, 0.5, None)
    assert plan["attrs"] == {"path": "loop"} and plan["parent_id"] is None


def test_a_kept_span_with_tracing_on_chains_as_any_span(recorder):
    tracing.enable()
    with tracing.span("train.fit", always=True) as fit:
        assert tracing.current_context() == {
            "trace_id": fit["trace_id"], "span_id": fit["span_id"]}
        with tracing.span("train.report") as report:
            pass
        stage = tracing.emit_span("xla.lower", 5.0, 1.0, always=True)
    assert tracing.current_context() is None
    assert fit["trace_id"] and fit["parent_id"] is None
    for child in (report, stage):
        assert child["trace_id"] == fit["trace_id"]
        assert child["parent_id"] == fit["span_id"]


def test_a_kept_span_that_raises_says_so_and_is_recorded(recorder):
    tracing.disable()
    with pytest.raises(ValueError):
        with tracing.span("train.loop", always=True):
            raise ValueError("boom")
    kept, = recorder.spans
    assert "boom" in kept["attrs"]["error"]


def test_chrome_trace_keeps_who_when_and_what():
    events = [
        {"kind": "span", "name": "core.init", "trace_id": None,
         "span_id": "a", "parent_id": None, "ts": 100.0, "dur": 1.25,
         "attrs": {"nodes": 1}, "worker": None},
        {"kind": "span", "name": "train.loop", "trace_id": None,
         "span_id": "b", "parent_id": None, "ts": 102.0, "dur": 3.0,
         "attrs": {"rank": 0}, "worker": "w0"},
        {"kind": "span", "name": "train.report", "trace_id": "f" * 32,
         "span_id": "c", "parent_id": "b", "ts": 103.0, "dur": 0.5,
         "attrs": {"step": 1}, "worker": "w0"},
        {"kind": "instant", "name": "xla.compile", "trace_id": None,
         "parent_id": None, "ts": 102.5, "worker": "w0",
         "attrs": {"seconds": 0.4, "program": "jit(_step)", "cache": "hit",
                   "retrieval_s": 0.3}}]
    out = chrome_trace(events)
    lanes = {e["args"]["name"]: e["pid"] for e in out
             if e["ph"] == "M" and e["name"] == "process_name"}
    tracks = {(e["pid"], e["tid"]): e["args"]["name"] for e in out
              if e["ph"] == "M" and e["name"] == "thread_name"}
    by_name = {e["name"]: e for e in out if e["ph"] != "M"}
    init, loop = by_name["core.init"], by_name["train.loop"]
    assert (init["ts"], init["dur"]) == (100.0 * 1e6, 1.25 * 1e6)
    assert init["pid"] == lanes["driver"] and loop["pid"] == lanes["worker:w0"]
    assert init["args"]["attrs"] == {"nodes": 1}
    assert loop["args"]["worker"] == "w0" and init["args"]["worker"] is None
    # kept records of a process share one track; a trace has its own
    assert tracks[loop["pid"], loop["tid"]] == "job"
    report = by_name["train.report"]
    assert tracks[report["pid"], report["tid"]] == "trace:ffffffff"
    compiled = by_name["xla.compile"]
    assert compiled["ph"] == "i" and compiled["ts"] == 102.5 * 1e6
    assert compiled["args"]["worker"] == "w0"
    assert compiled["args"]["attrs"]["cache"] == "hit"
    json.dumps(out)


# ------------------------------------------------------------ the job's timeline

def _two_steps(config):
    from ray_tpu.train import session

    for i in range(2):
        session.report({"step": i, "loss": 1.0 - 0.1 * i})


@pytest.mark.parametrize("tracing_on", [False, True],
                         ids=["tracing_off", "tracing_on"])
def test_fit_leaves_the_jobs_timeline(tracing_on, tmp_path, monkeypatch):
    """Set-up's spans in order and nested as they are made, from the
    driver's process and the worker's on one clock; `train.report` only
    with tracing on."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # the workers take the setting from the environment they inherit
    monkeypatch.setenv("RAY_TPU_TRACING", "1" if tracing_on else "0")
    tracing._enabled = None
    ray_tpu.init(num_cpus=4, _system_config=SYSTEM)
    try:
        result = JaxTrainer(
            _two_steps, scaling_config=ScalingConfig(num_workers=1,
                                                     use_tpu=False),
            run_config=RunConfig(name="job", storage_path=str(tmp_path))
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert not result.error, result.error
    assert result.timeline_path == str(tmp_path / "job" / "timeline.json")
    with open(result.timeline_path) as f:
        events = [e for e in json.load(f) if e["ph"] != "M"]
    assert {e["cat"] for e in events} <= {"span", "instant"}   # no task state
    first = {}
    for e in events:
        first.setdefault(e["name"], e)
    order = ["core.init", "train.fit", "train.group_start",
             "train.worker_setup", "train.loop", "train.first_report",
             "train.loop_summary"]
    assert [n for n in first if n in order] == order      # sorted by start

    def ends(e):
        return e["ts"] + e.get("dur", 0.0)

    def inside(inner, outer):      # to the microsecond the file is in
        return first[outer]["ts"] <= first[inner]["ts"] + 1 and \
            ends(first[inner]) <= ends(first[outer]) + 1

    assert ends(first["core.init"]) <= first["train.fit"]["ts"]
    for phase in ("core.init.gcs", "core.init.nodelet", "core.init.runtime"):
        assert inside(phase, "core.init")
    assert inside("train.group_start", "train.fit")
    assert inside("train.worker_setup", "train.group_start")
    assert ends(first["train.group_start"]) <= first["train.loop"]["ts"]
    assert inside("train.loop", "train.fit")
    assert inside("train.first_report", "train.loop")
    assert inside("train.loop_summary", "train.loop")
    assert first["core.init"]["args"]["attrs"] == {"nodes": 1, "num_cpus": 4.0}
    assert first["train.fit"]["args"]["attrs"] == {
        "workers": 1, "chips_per_worker": 0}
    assert first["train.loop"]["args"]["attrs"] == {"rank": 0}
    assert first["train.first_report"]["args"]["attrs"] == {"step": 0}
    worker = first["train.loop"]["args"]["worker"]
    assert worker and first["train.worker_setup"]["args"]["worker"] == worker
    assert first["train.first_report"]["args"]["worker"] == worker
    # the loop's own account, once, kept whatever the setting
    summary, = (e["args"] for e in events if e["name"] == "train.loop_summary")
    assert summary["worker"] == worker
    assert sorted(summary["attrs"]) == [
        "host_late_count", "host_late_ms", "interval_median_ms",
        "process_late_count", "process_late_ms", "rank",
        "report_median_ms", "steps", "wait_max_ms", "wait_max_step",
        "wait_median_ms"]
    assert (summary["attrs"]["rank"], summary["attrs"]["steps"],
            summary["attrs"]["wait_max_step"]) == (0, 2, 1)
    assert first["train.fit"]["args"]["worker"] is None
    assert "train.chips_open" not in first       # a worker without chips
    reports = [e for e in events if e["name"] == "train.report"]
    if tracing_on:
        assert sorted(e["args"]["attrs"]["step"] for e in reports) == [0, 1]
        assert {e["args"]["trace_id"] for e in reports} == {
            first["train.fit"]["args"]["trace_id"]}
    else:
        assert reports == []
        assert first["train.loop"]["args"]["trace_id"] is None


def test_spans_outlive_the_task_states_that_follow(monkeypatch):
    """The GCS keeps what `tracing` recorded apart from the task states:
    a driver's polling cannot push a job's set-up out of the store."""
    monkeypatch.setenv("RAY_TPU_TRACING", "0")
    tracing._enabled = None
    ray_tpu.init(num_cpus=2, _system_config={**SYSTEM,
                                             "task_event_buffer_size": 40})
    try:
        tracing.plan("flash.fwd_plan", {"path": "stream"})
        # shipped: this process's own buffer is as small as the store's
        ray_tpu.timeline(limit=1, spans_only=True)

        @ray_tpu.remote
        def nothing():
            return 0

        ray_tpu.get([nothing.remote() for _ in range(60)])
        kept = ray_tpu.timeline(limit=1000, spans_only=True)
        both = ray_tpu.timeline(limit=1000)
    finally:
        ray_tpu.shutdown()
    assert {e["kind"] for e in kept} <= {"span", "instant"}
    assert "flash.fwd_plan" in {e["name"] for e in kept}
    assert "core.init" in {e["name"] for e in kept}
    assert any(e.get("state") for e in both)
    assert "flash.fwd_plan" in {e["name"] for e in both if "name" in e}
    stamps = [e.get("ts") or 0.0 for e in both]
    assert stamps == sorted(stamps, reverse=True)        # newest first


# --------------------------------------------- compiles, by name and outcome

@pytest.fixture
def fresh_cache(tmp_path):
    """jax's persistent cache in a directory of this test's, keeping
    every program whatever it took."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_a_compile_says_its_program_and_what_the_cache_did(
        recorder, fresh_cache):
    import jax
    import jax.numpy as jnp

    tracing.enable()                 # every record, whatever it took
    assert compile_cache.listen() is True

    def weighs_the_rows(x):
        return jnp.tanh(x @ x.T).sum(axis=0) * 3.0

    x = jnp.ones((24, 8))            # made before the count starts
    mine = []
    for _ in range(2):
        del recorder.spans[:]
        n0 = compile_cache.compile_count()
        jax.jit(weighs_the_rows).lower(x).compile()
        assert compile_cache.compile_count() == n0 + 1
        mine.append([s for s in recorder.spans
                     if "weighs_the_rows" in s["attrs"].get("program", "")])
        jax.clear_caches()
    (miss,), (hit,) = [[s for s in seen if s["name"] == "xla.compile"]
                       for seen in mine]
    assert miss["attrs"]["program"] == hit["attrs"]["program"] \
        == "jit(weighs_the_rows)"
    assert miss["attrs"]["cache"] == "miss"
    assert miss["attrs"]["retrieval_s"] == 0.0
    assert miss["attrs"]["seconds"] > 0
    assert hit["attrs"]["cache"] == "hit" and hit["attrs"]["retrieval_s"] > 0
    # the Python trace and the lowering of so small a function are under
    # the floor, with tracing on too: a stage is kept only where it took
    # the floor or longer (on a loaded machine the lowering of even this
    # function has: six workers compiling whole steps beside it, PR 58)
    assert [s["name"] for seen in mine for s in seen
            if s["name"] != "xla.compile"
            and s["dur"] < compile_cache.KEPT_S] == []


def test_a_warm_set_up_reads_the_stored_load_and_no_trace_of_the_step(
        recorder, fresh_cache, monkeypatch):
    """ISSUE 62: a step loaded from the program store raises the
    ``xla.compile`` instant a load from jax's cache raises, so the budget
    reads it under ``program_load_s``; its trace and lowering are not
    there to read."""
    import program_store_toy as toy
    from benchmark.readers import job_timeline

    tracing.enable()
    monkeypatch.setattr(compile_cache, "KEPT_S", 0.0)   # every stage kept
    assert compile_cache.listen() is True

    def set_up():
        import jax

        del recorder.spans[:]
        with tracing.span("core.init"):
            pass
        with tracing.span("train.fit"), tracing.span("train.loop",
                                                     {"rank": 0}):
            init_fn, step, _, batch = toy.build(shapes=False)
            step(init_fn(jax.random.PRNGKey(7)), batch)
            tracing.instant("train.first_report")
        return [{"name": s["name"], "start": s["ts"],
                 "end": s["ts"] + s.get("dur", 0.0), "attrs": s["attrs"],
                 "worker": "w"} for s in sorted(recorder.spans,
                                                key=lambda s: s["ts"])]

    def of_the_step(records, name):
        return [r for r in records if r["name"] == name
                and r["attrs"].get("program") in ("_step", "jit(_step)")]

    cold, warm = set_up(), set_up()
    assert of_the_step(cold, "xla.trace") and of_the_step(cold, "xla.lower")
    assert [r["attrs"]["cache"] for r in of_the_step(cold, "xla.compile")] \
        == ["miss"]
    assert not of_the_step(warm, "xla.trace")
    assert not of_the_step(warm, "xla.lower")
    load, = of_the_step(warm, "xla.compile")
    stored, = [r["attrs"] for r in warm if r["name"] == "program.store"
               and r["attrs"]["program"] == "_step"]
    assert stored["hit"] and load["attrs"]["cache"] == "hit"
    assert load["attrs"]["seconds"] == stored["seconds"] > 0
    budgets = [job_timeline.budget(records) for records in (cold, warm)]
    loads = sum(r["attrs"]["seconds"] for r in warm
                if r["name"] == "xla.compile"
                and r["attrs"]["cache"] == "hit")
    assert budgets[1]["program_load_s"] == pytest.approx(loads)
    assert budgets[1]["program_load_s"] >= stored["seconds"]
    assert budgets[0]["program_load_s"] == 0.0
    # the state program's and the step's stages are the cold set-up's
    assert budgets[1]["trace_lower_s"] < budgets[0]["trace_lower_s"]


def test_a_compile_outside_the_cache_says_off(recorder):
    import jax
    import jax.numpy as jnp

    tracing.enable()
    assert compile_cache.listen() is True

    def squares_the_rows(x):
        return (x * x).sum(axis=1)

    x = jnp.ones((6, 4))
    del recorder.spans[:]
    with _cache_off():
        jax.jit(squares_the_rows).lower(x).compile()
    said, = [s for s in recorder.spans if s["name"] == "xla.compile"
             and "squares_the_rows" in s["attrs"]["program"]]
    assert said["attrs"]["cache"] == "off"
    assert said["attrs"]["retrieval_s"] == 0.0


@contextlib.contextmanager
def _cache_off():
    """`jax_enable_compilation_cache` False for a stretch."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("seconds,kept", [(0.2, True), (0.01, False)])
def test_what_is_kept_of_a_compile_with_tracing_off(seconds, kept, recorder):
    """From 0.05 s up a compile, a trace and a lowering are on the job's
    timeline with tracing off; the hundreds of one-op programs are not."""
    tracing.disable()
    compile_cache._on_event("/jax/compilation_cache/compile_requests_use_cache")
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", seconds / 2)
    n0, s0 = compile_cache.compile_count(), compile_cache.compile_seconds()
    compile_cache._on_duration(
        "/jax/core/compile/backend_compile_duration", seconds,
        fun_name="jit(_step)")
    compile_cache._on_time_span(
        "/jax/core/compile/jaxpr_trace_duration", 50.0, 50.0 + seconds,
        fun_name="_step")
    compile_cache._on_time_span(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 51.0,
        51.0 + seconds, fun_name="jit(_step)")
    compile_cache._on_time_span("/jax/some/other/span", 0.0, 9.0)
    # counted whatever it took
    assert compile_cache.compile_count() == n0 + 1
    assert compile_cache.compile_seconds() == pytest.approx(s0 + seconds)
    if not kept:
        assert recorder.spans == []
        return
    compiled, traced, lowered = recorder.spans
    assert compiled["name"] == "xla.compile" and compiled["attrs"] == {
        "seconds": seconds, "program": "jit(_step)", "cache": "hit",
        "retrieval_s": seconds / 2}
    assert (traced["name"], traced["ts"], traced["attrs"]) == (
        "xla.trace", 50.0, {"program": "_step"})
    assert traced["dur"] == pytest.approx(seconds)
    assert (lowered["name"], lowered["ts"]) == ("xla.lower", 51.0)
    # what the cache said belongs to ONE compile: the next starts clean
    compile_cache._on_duration(
        "/jax/core/compile/backend_compile_duration", seconds,
        fun_name="jit(init_fn)")
    assert recorder.spans[-1]["attrs"]["cache"] == "off"
    assert recorder.spans[-1]["attrs"]["retrieval_s"] == 0.0


# ------------------------------------------------- the plans of a lowered step

def _benchmark(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


# family: configuration, the benchmark's map to the program's config, the
# model, and the plan instants its step must leave
FAMILIES = {
    "flash": ("tiny", "model", "llama_config", "llama", {
        "flash.fwd_plan", "flash.bwd_plan", "remat.plan", "attn.kind_plan"}),
    "sparse": ("tiny-glm52", "model_glm52", "latent_config", "latent", {
        "dsa.plan", "sparse.fwd_plan", "sparse.probs_plan", "sparse.bwd_plan",
        "mla.plan", "hybrid.layer_plan", "moe.expert_plan"}),
    "hybrid": ("tiny-granite", "model_granite", "hybrid_config", "hybrid", {
        "ssd.plan", "mixer.plan", "hybrid.layer_plan", "flash.fwd_plan",
        "flash.bwd_plan"}),
    "blockset": ("tiny-sala", "model_sala", "sala_config", "sala", {
        "sala.select_plan", "sparse.fwd_plan", "sparse.bwd_plan", "ssd.plan",
        "hybrid.layer_plan", "remat.plan"}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_lowered_step_leaves_its_plans_with_tracing_off(
        family, ray_start_regular, monkeypatch):
    """The plans are said while the step is traced, before any profile:
    with a runtime up they are on the timeline, tracing off."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention

    sys.path.insert(0, ROOT)
    monkeypatch.setattr(sys, "path", list(sys.path))
    conf, mapper, to_config, model, want = FAMILIES[family]
    tracing.disable()
    monkeypatch.setattr(sparse_attention, "IMPL", "pallas")   # off the chip
    models = importlib.import_module("ray_tpu.models." + model)
    cfg = getattr(importlib.import_module("benchmark." + mapper), to_config)(
        _benchmark("configs", conf), remat=True, attn_impl="flash")
    params = jax.eval_shape(
        lambda: models.init_params(jax.random.PRNGKey(0), cfg))

    def loss(p, tokens):
        out = models.loss_fn(p, {"tokens": tokens}, cfg)
        return out[0] if isinstance(out, tuple) else out

    tokens = jax.ShapeDtypeStruct((2, 129), jnp.int32)
    try:
        off = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
        said = ray_tpu.timeline(limit=5000, spans_only=True)
        tracing.enable()
        on = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
    finally:
        tracing._enabled = None
    assert on == off         # what a step says is no part of its program
    assert all(e["kind"] == "instant" for e in said
               if e["name"].endswith("plan"))
    names = {e["name"] for e in said}
    assert want <= names, sorted(want - names)
    assert "train.report" not in names
    flash = [e["attrs"] for e in said if e["name"] == "flash.fwd_plan"]
    assert all(a["path"] in ("loop", "stream", "band") for a in flash)


# ------------------------------------------- session.report with tracing off

def test_the_first_report_is_said_once_and_a_report_sends_nothing(
        recorder, tmp_path):
    from ray_tpu.train import session
    from ray_tpu.train.config import ScalingConfig

    tracing.disable()
    ctx = session.TrainContext(
        world_rank=0, world_size=1, config={}, run_dir=str(tmp_path),
        scaling=ScalingConfig(num_workers=1, use_tpu=False), checkpoint=None)
    session._set_context(ctx)
    try:
        for i in range(3):
            session.report({"step": i + 5, "loss": 0.5})
    finally:
        session._set_context(None)
    assert [(s["kind"], s["name"], s["attrs"]) for s in recorder.spans] == [
        ("instant", "train.first_report", {"step": 5})]
    assert len(ctx.reports) == 3
