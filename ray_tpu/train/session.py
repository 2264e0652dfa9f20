"""Per-worker training session.

Reference: python/ray/train/_internal/session.py:84 (_TrainSession;
report:429, get_checkpoint:639, get_dataset_shard:901) and the air session
facade (air/session.py). One module-level context per worker process, set up
by the TrainWorker actor before the user loop runs.
"""

from __future__ import annotations

import statistics
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core import compile_cache as _compile_cache
from ray_tpu.observability import health as _health
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.util import tracing as _tracing

# Step-loop progress beacon deadline: generous — "step" here means
# report() cadence, and big-model steps plus a collective checkpoint
# save can legitimately take minutes.
_STEP_DEADLINE_S = 600.0


class LoopFigures:
    """What a loop's reports add up to, kept as they come and said once
    as the loop ends (`train.loop_summary`). From one report's end to the
    next one's start the loop WAITS (the batch, the step, the fetch: the
    user's code and the device); then the report runs; the two are one
    interval of the loop. Bounded: a count, the longest wait and, for
    the medians, at most `CAP` pairs spread evenly over the loop (when
    the sample is full every second pair goes and from then on only
    every second of those that would have been taken is)."""

    CAP = 512

    def __init__(self) -> None:
        self.reports = 0
        self.wait_max_s, self.wait_max_step = 0.0, None
        self._pairs: List[Tuple[float, float]] = []   # (wait_s, report_s)
        self._stride = 1
        self._late0: Optional[Dict[str, float]] = None

    def observe(self, wait_s: Optional[float], report_s: float,
                step: int) -> None:
        """One report: `wait_s` is None for the loop's first, which
        follows set-up and no report."""
        self.reports += 1
        if wait_s is None:
            # late wakes are the loop's from here on
            self._late0 = _health.counters()
            return
        if wait_s > self.wait_max_s:
            self.wait_max_s, self.wait_max_step = wait_s, step
        if (self.reports - 2) % self._stride == 0:
            self._pairs.append((wait_s, report_s))
            if len(self._pairs) > self.CAP:
                del self._pairs[1::2]
                self._stride *= 2

    def summary(self) -> Optional[Dict[str, Any]]:
        """The attributes of `train.loop_summary`; None for a loop that
        never reported."""
        if self._late0 is None:
            return None
        out: Dict[str, Any] = {"steps": self.reports}
        if self._pairs:
            def median_ms(values) -> float:
                return round(statistics.median(values) * 1e3, 4)

            out.update(
                interval_median_ms=median_ms(w + r for w, r in self._pairs),
                wait_median_ms=median_ms(w for w, _ in self._pairs),
                wait_max_ms=round(self.wait_max_s * 1e3, 4),
                wait_max_step=self.wait_max_step,
                report_median_ms=median_ms(r for _, r in self._pairs))
        now = _health.counters()
        out.update({k: round(now[k] - self._late0[k], 3)
                    for k in _health.LATE_KEYS})
        return out


class TrainContext:
    def __init__(self, *, world_rank: int, world_size: int, config: dict,
                 run_dir: str, scaling, checkpoint: Optional[Checkpoint],
                 datasets: Optional[Dict[str, Any]] = None,
                 num_to_keep: Optional[int] = None,
                 elastic_meta: Optional[dict] = None):
        self.world_rank = world_rank
        self.world_size = world_size
        self.config = config
        self.run_dir = run_dir
        self.scaling = scaling
        self.start_checkpoint = checkpoint
        self.datasets = datasets or {}
        # Elastic gang metadata (ray_tpu/train/elastic.py): run tag for
        # health-event attribution, the generation-suffixed host
        # collective group name, and the per-rank report beacon deadline.
        self.elastic_meta = elastic_meta or {}
        self.reports: List[dict] = []
        self.report_lock = threading.Lock()
        self.latest_checkpoint: Optional[Checkpoint] = checkpoint
        # Every rank gets a manager over the same run_dir so all ranks
        # resolve the same checkpoint_NNNNNN paths; only rank 0 registers
        # (uploads/evicts). In a multi-host jax runtime the orbax save is
        # collective — every process must enter from_state (each writes its
        # addressable shards), so non-zero ranks need the path too.
        self.ckpt_mgr = CheckpointManager(run_dir, num_to_keep)
        self.finished = False
        self._mesh = None
        # one report to the next is one step of the user's loop, most of
        # it a wait for the device: far above its median is a stall
        self.step_watch = _health.WaitWatch("train.report interval")
        self.last_report: Optional[float] = None   # perf_counter
        self.figures = LoopFigures()


_ctx: Optional[TrainContext] = None


def _step_deadline(ctx: TrainContext) -> float:
    dl = ctx.elastic_meta.get("step_deadline_s")
    return float(dl) if dl else _STEP_DEADLINE_S


def _set_context(ctx: Optional[TrainContext]):
    global _ctx
    if ctx is None and _ctx is not None:
        _health.drop_beacon(f"train:r{_ctx.world_rank}")
    _ctx = ctx
    if ctx is not None:
        _compile_cache.listen()        # if jax is imported by now
        # armed for the whole run: a rank that stops reporting past the
        # deadline (wedged collective, dead peer mid-allreduce) flags as
        # a StallEvent naming the rank. The run tag in the context lets
        # an ElasticCoordinator attribute the event to ITS gang.
        _health.beacon(f"train:r{ctx.world_rank}",
                       _step_deadline(ctx)).arm(
            rank=ctx.world_rank, world=ctx.world_size,
            run=ctx.elastic_meta.get("run_tag", ""))


def get_context() -> TrainContext:
    if _ctx is None:
        raise RuntimeError("not inside a ray_tpu.train worker")
    return _ctx


def world_rank() -> int:
    return get_context().world_rank


def world_size() -> int:
    return get_context().world_size


def get_config() -> dict:
    return get_context().config


def report(metrics: Dict[str, Any], *, state: Any = None) -> None:
    """Report metrics (streamed to the trainer) and optionally checkpoint a
    jax pytree `state` (ref: session.report:429).

    Checkpoint contract (same as the reference's distributed checkpointing:
    every train worker must call `train.report` with a checkpoint): when the
    workers form one multi-host jax runtime, EVERY rank must pass `state` on
    the same reports — the orbax save and its barriers are collective, and a
    rank that skips them hangs the gang. Single-process workers: rank 0's
    state is saved, other ranks' is ignored."""
    ctx = get_context()
    attrs = {"has_state": state is not None}
    if isinstance(metrics.get("step"), int):
        attrs["step"] = metrics["step"]
    # an expert model's routing statistics (models/moe.py finish_loss), a
    # learned selection's (models/latent.py finish_loss) and what a loop
    # reads of whether a mechanism is alive under its constants
    # (``alive_``: the softmax scores' deviation, a decay's spread)
    attrs.update({k: v for k, v in metrics.items()
                  if k.startswith(("moe_", "index_", "alive_"))
                  and isinstance(v, float)})
    with _tracing.span("train.report", attrs):
        _report(ctx, metrics, state)


def _report(ctx: TrainContext, metrics: Dict[str, Any], state: Any) -> None:
    _compile_cache.listen()            # the loop has imported jax by now
    now = time.perf_counter()
    step = metrics.get("step", len(ctx.reports))
    step_no = step if isinstance(step, int) else len(ctx.reports)
    waited = None if ctx.last_report is None else now - ctx.last_report
    if waited is not None:
        ctx.step_watch.observe(waited, step=step)
    else:
        # where set-up ends on the job's timeline: kept, once a context
        _tracing.instant("train.first_report", {"step": step_no},
                         always=True)
    entry = dict(metrics)
    entry["_ts"] = time.time()
    entry["_rank"] = ctx.world_rank
    ckpt_path = None
    if state is not None:
        with _tracing.span("train.checkpoint"):
            ckpt_path = _save_checkpoint(ctx, state, entry)
    if ckpt_path:
        entry["_checkpoint"] = ckpt_path
    with ctx.report_lock:
        ctx.reports.append(entry)
    _health.beacon(f"train:r{ctx.world_rank}", _step_deadline(ctx)).tick()
    ctx.last_report = time.perf_counter()   # a save is not a device wait
    ctx.figures.observe(waited, ctx.last_report - now, step_no)


def _save_checkpoint(ctx: TrainContext, state: Any, entry: dict
                     ) -> Optional[str]:
    """The collective (or rank 0's) save of `state`; returns the path
    rank 0 registered, and puts the checkpoint's URI into `entry`."""
    ckpt_path = None
    import jax

    # Collective save: when the workers form one multi-host jax
    # runtime, EVERY process must call from_state (orbax writes each
    # process's addressable shards + a sync barrier). With independent
    # single-process workers (process_count==1), rank 0 saves alone.
    collective = jax.process_count() > 1
    if ctx.world_rank == 0 or collective:
        if collective:
            import numpy as np
            from jax.experimental import multihost_utils

            # all ranks write into rank 0's checkpoint slot — a
            # replacement rank with a fresh staging dir may disagree
            # on the next index
            idx = int(multihost_utils.broadcast_one_to_all(
                np.int32(ctx.ckpt_mgr._index)))
            path = ctx.ckpt_mgr.new_dir(index=idx)
        else:
            path = ctx.ckpt_mgr.new_dir()
        ck = Checkpoint.from_state(state, path)
        if ctx.world_rank != 0 and collective:
            # mirror this rank's shard files + evict per num_to_keep
            # on this host; no marker, no remote eviction; synchronous
            # so the barrier below really covers the upload
            ctx.ckpt_mgr.register(path, primary=False)
        if collective:
            # the primary's completion marker must land after every
            # rank's shard upload
            multihost_utils.sync_global_devices("ray_tpu_ckpt_mirror")
        if ctx.world_rank == 0:
            # single-process mode mirrors on a background thread so
            # the train loop isn't stalled for the upload
            ctx.ckpt_mgr.register(path, primary=True,
                                  sync=collective)
            ctx.latest_checkpoint = ck
            ckpt_path = ck.path
            if ctx.ckpt_mgr.uri:
                import os as _os

                from ray_tpu.train import storage as _storage

                entry["_checkpoint_uri"] = _storage.join_uri(
                    ctx.ckpt_mgr.uri, _os.path.basename(path))
    return ckpt_path


def get_checkpoint() -> Optional[Checkpoint]:
    """The checkpoint to resume from (ref: session.get_checkpoint:639)."""
    return get_context().start_checkpoint


def get_dataset_shard(name: str = "train"):
    """This worker's split of a dataset passed to the trainer
    (ref: session.get_dataset_shard:901 → StreamSplitDataIterator)."""
    ctx = get_context()
    if name not in ctx.datasets:
        raise KeyError(f"no dataset named {name!r} passed to the trainer")
    return ctx.datasets[name]


def get_mesh():
    """The worker's device mesh per ScalingConfig (cached).

    Also binds the mesh (+ the scaling rules) as the process-default for
    `ray_tpu.parallel.presets.sharded_jit` — a function decorated with
    in/out specs resolves its mesh here at call time, so an elastic
    rebuild re-meshes every decorated step by re-running setup, with no
    per-call-site rewiring."""
    ctx = get_context()
    if ctx._mesh is None:
        from ray_tpu.parallel import presets
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        spec = ctx.scaling.mesh or MeshSpec(dp=-1)
        ctx._mesh = build_mesh(spec)
        presets.set_default_mesh(ctx._mesh, rules=get_rules(), spec=spec)
    return ctx._mesh


def get_collective_group() -> Optional[str]:
    """The gang-wide host collective group's CURRENT name, or None.

    Elastic gangs re-form the group under a generation-suffixed name on
    every rebuild (membership is static per incarnation); user loops
    must route collective.* calls through this accessor rather than a
    hard-coded name so they survive a remediation."""
    return get_context().elastic_meta.get("collective_group")


def get_rules():
    from ray_tpu.parallel.sharding import ShardingRules

    return getattr(ShardingRules, get_context().scaling.rules)()
