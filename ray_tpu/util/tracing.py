"""Distributed tracing: spans propagated through task/actor calls.

Reference: python/ray/util/tracing/tracing_helper.py — opt-in tracing
that wraps task/actor invocation in spans
(_inject_tracing_into_function:322, _inject_tracing_into_class:447) and
serializes the span context into task metadata
(_function_hydrate_span_args:195) so remote execution continues the
caller's trace.

TPU-shaped re-design: no OpenTelemetry SDK dependency (not in-image).
Spans are plain dicts {trace_id, span_id, parent_id, name, ts, dur, attrs}
riding the existing task-event channel to the GCS (task_event_buffer.h:199
analog), so one store serves task states AND spans, and `ray_tpu.timeline()`
/ the CLI export both as one Chrome trace. Context propagation is a
contextvar here + a `trace_ctx` field on TaskSpec there.

One clock for host and device: a span is ALSO an event of jax's profiler.
Where `jax` is already imported, `span` and `instant` run inside a
`jax.profiler.TraceAnnotation` of the same name, whether or not tracing
is enabled: while a profile runs, the event lands in the `/host:CPU`
plane of the `.xplane.pb`, on the calling thread's line, with its scalar
attributes as stats and its start on the axis of the device planes, so a
device idle gap can be put down to the span that covers it
(`benchmark/host_plane.py` reads them). With no profile running the
annotation is inactive and records nothing. This module never imports
jax itself: a parent that only spawns workers stays off the chip.

What happens once a job is KEPT with tracing off (`always=True`, `plan`):
the cluster's start, the worker group's, a worker's set-up and loop, the
chips' opening, the first report, the loop's own summary as it ends,
every compile, trace and lowering of 0.05 s or more, the plans a step was
lowered with, a freeze of the host.
They ride the same channel to the same store, and `JaxTrainer.fit` writes
them out as it ends (`<run_dir>/timeline.json`, a Chrome trace), so what
the program did before any profiler started outlives the cluster. What a
step or a request does is never kept: with tracing off the hot paths
record and send nothing.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import sys
import time
from typing import Any, Dict, Optional

_ctx: contextvars.ContextVar[Optional[Dict[str, str]]] = \
    contextvars.ContextVar("ray_tpu_trace_ctx", default=None)

_enabled: Optional[bool] = None


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    """Opt-in like the reference (`ray.init(_tracing_startup_hook=...)`):
    enable() in-process or RAY_TPU_TRACING=1 fleet-wide."""
    if _enabled is not None:
        return _enabled
    return os.environ.get("RAY_TPU_TRACING", "0") in ("1", "true")


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def current_context() -> Optional[Dict[str, str]]:
    """The {trace_id, span_id} to stamp onto outgoing TaskSpecs."""
    return _ctx.get()


def _annotation(name: str, attributes: Optional[Dict[str, Any]]):
    """The profiler's half of a span: an unopened TraceAnnotation, or
    None where jax is not imported (yet). It takes its attributes when it
    opens, and only scalars: what a span learns at its end goes on a
    child or on an instant recorded as it closes."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    if not attributes:
        return profiler.TraceAnnotation(name)
    return profiler.TraceAnnotation(name, **{
        k: v for k, v in attributes.items()
        if isinstance(v, (bool, int, float, str))})


def _record(span: Dict[str, Any]) -> None:
    try:
        from ray_tpu import _rt

        rt = _rt.get_runtime()
    except Exception:
        return
    rt.record_span(span)


def _span_record(name: str, attributes: Optional[Dict[str, Any]],
                 parent: Optional[Dict[str, str]], chained: bool,
                 ts: float) -> Dict[str, Any]:
    """A span's record as it opens. A kept span of a process with
    tracing off (`chained` false) belongs to no trace."""
    return {
        "kind": "span",
        "name": name,
        "trace_id": (parent["trace_id"] if parent
                     else _new_id(16) if chained else None),
        "span_id": _new_id(8),
        "parent_id": parent["span_id"] if parent else None,
        "ts": ts,
        "attrs": dict(attributes or {}),
    }


@contextlib.contextmanager
def span(name: str, attributes: Optional[Dict[str, Any]] = None, *,
         always: bool = False):
    """User-facing span (ref: custom spans via util/debug profiling).
    Nested spans chain; spans created inside a task continue the
    submitting caller's trace (a live parent context counts as opt-in
    even when this process never called enable() — that's how worker
    processes participate). With tracing off nothing is recorded or
    sent; the body still runs inside the profiler's annotation (module
    docstring), which is inactive unless a profile is running.

    `always` keeps the span with tracing off too, for what happens once
    a job (the cluster's start, a worker's set-up, the loop as a whole):
    never for what a step or a request does. A kept span recorded with
    tracing off is no context: it has no trace and nothing chains under
    it, so a kept `train.loop` does not opt every `train.report` inside
    it in. With tracing on it chains as any span."""
    ann = _annotation(name, attributes) or contextlib.nullcontext()
    parent = _ctx.get()
    chained = is_enabled() or parent is not None
    if not (chained or always):
        with ann:
            yield None
        return
    rec = _span_record(name, attributes, parent, chained, time.time())
    token = _ctx.set({"trace_id": rec["trace_id"],
                      "span_id": rec["span_id"]}) if chained else None
    try:
        with ann:
            yield rec
    except BaseException as e:
        rec["attrs"]["error"] = repr(e)
        raise
    finally:
        if token is not None:
            _ctx.reset(token)
        rec["dur"] = time.time() - rec["ts"]
        _record(rec)


def instant(name: str, attributes: Optional[Dict[str, Any]] = None, *,
            always: bool = False) -> Optional[dict]:
    """A zero-length span: a value with a time (one admitted request, one
    compile, one stall). Same two halves and the same opt-in rule as
    span(); `always` records it with tracing off too, for the rare event
    a post-mortem or a job's timeline must hold (a stall, a compile of
    seconds). `timeline.chrome_trace` renders the record as an instant
    event."""
    ann = _annotation(name, attributes)
    if ann is not None:
        with ann:
            pass
    parent = _ctx.get()
    if not (always or is_enabled() or parent is not None):
        return None
    rec = {
        "kind": "instant",
        "name": name,
        "trace_id": parent["trace_id"] if parent else None,
        "parent_id": parent["span_id"] if parent else None,
        "ts": time.time(),
        "attrs": dict(attributes or {}),
    }
    _record(rec)
    return rec


_plans_said: contextvars.ContextVar[Optional[list]] = \
    contextvars.ContextVar("ray_tpu_plans_said", default=None)


def plan(name: str, attributes: Dict[str, Any]) -> Optional[dict]:
    """What a kernel or a model chose while a program is traced (a
    `*.plan` / `*_plan` instant): once a traced body, so kept with
    tracing off, and on the job's timeline though the lowering ran
    before any profile. Inside `plans_said` it is also written down, for
    a program that is later loaded and not traced (core/compile_cache.py
    raises it again as it loads)."""
    said = _plans_said.get()
    if said is not None:
        said.append((name, dict(attributes)))
    return instant(name, attributes, always=True)


@contextlib.contextmanager
def plans_said():
    """Yields the list that takes (name, attributes) of every `plan`
    raised inside the block, in order."""
    said: list = []
    token = _plans_said.set(said)
    try:
        yield said
    finally:
        _plans_said.reset(token)


def emit_span(name: str, ts: float, dur: float,
              attributes: Optional[Dict[str, Any]] = None, *,
              always: bool = False) -> Optional[dict]:
    """Record a synthetic complete span for a phase measured elsewhere
    (streaming-executor op lifetimes, replayed timings, jax's own clock
    round a trace or a lowering). Same opt-in rule as span(): a live
    parent context counts as opt-in, and the span chains under it;
    `always` keeps it with tracing off, under span()'s once-a-job
    rule."""
    parent = _ctx.get()
    chained = is_enabled() or parent is not None
    if not (chained or always):
        return None
    rec = _span_record(name, attributes, parent, chained, float(ts))
    rec["dur"] = float(dur)
    _record(rec)
    return rec


@contextlib.contextmanager
def continue_trace(trace_ctx: Optional[Dict[str, str]], name: str,
                   attributes: Optional[Dict[str, Any]] = None):
    """Worker-side: wrap a task execution in a span parented to the
    submitted context (ref: _function_span_consumer_name — the remote
    half of the trace). No-op when tracing is off AND no context came."""
    if not (is_enabled() or trace_ctx):
        yield None
        return
    if trace_ctx:
        token = _ctx.set(dict(trace_ctx))
    else:
        token = None
    try:
        with span(name, attributes) as rec:
            yield rec
    finally:
        if token is not None:
            _ctx.reset(token)
