"""JaxTrainer: fit() orchestration with failure recovery.

Reference: python/ray/train/data_parallel_trainer.py:58 +
base_trainer.py:570 fit + backend_executor.py failure handling
(get_with_failure_handling:564, _restart:625). One trainer class covers what
the reference splits into TorchTrainer/TensorflowTrainer/...: the framework
backend is always JAX, and parallelism comes from ScalingConfig.mesh/rules.
"""

from __future__ import annotations

import json
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.core.status import ActorDiedError, ActorUnavailableError, TaskError
from ray_tpu.train import storage
from ray_tpu.train.backend import TensorflowBackend, TorchBackend
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# the most spans and instants a job's timeline holds (the newest): the
# kept records of a job are a few hundred, with tracing on a span a step
TIMELINE_RECORDS = 20000


@dataclass
class Result:
    metrics: Dict[str, Any] = field(default_factory=dict)
    metrics_history: List[dict] = field(default_factory=list)
    checkpoint: Optional[Checkpoint] = None
    error: Optional[str] = None
    # remediation audit trail when ScalingConfig.elastic drove the run
    # (run_tag, world size per generation, remediation events); None for
    # fixed-size runs — see ray_tpu/train/elastic.py
    elastic: Optional[Dict[str, Any]] = None
    # the job's timeline (`<run_dir>/timeline.json`, a Chrome trace):
    # what was kept of set-up and, with tracing on, every span; None
    # where it could not be written
    timeline_path: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None


class JaxTrainer:
    #: collective bootstrap, overridable per subclass
    #  (ref: DataParallelTrainer's backend_config, data_parallel_trainer.py:58)
    backend_cls: type = None

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[dict] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None,
                 backend=None):
        from ray_tpu.train.backend import JaxBackend

        self.loop = train_loop_per_worker
        self.config = train_loop_config or {}
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_from = resume_from_checkpoint
        self.backend = backend or (self.backend_cls() if self.backend_cls
                                   else JaxBackend())

    def _run_dir(self) -> str:
        base = self.run_config.storage_path or os.path.expanduser(
            "~/ray_tpu_results")
        name = self.run_config.name or f"run_{int(time.time())}"
        if storage.is_uri(base):
            # remote run dir: CheckpointManager stages locally and mirrors
            # to the URI (ref: air RunConfig.storage_path cloud URIs)
            return storage.join_uri(base, name)
        path = os.path.join(base, name)
        os.makedirs(path, exist_ok=True)
        return path

    def fit(self) -> Result:
        if self.scaling.elastic is not None:
            # self-healing gang: health-plane-driven shrink/refill/grow
            # state machine instead of the whole-group retry loop below
            from ray_tpu.train.elastic import ElasticCoordinator

            return ElasticCoordinator(self).fit()
        run_dir = self._run_dir()
        result = Result()
        try:
            with tracing.span("train.fit", {
                    "workers": self.scaling.num_workers,
                    "chips_per_worker": self.scaling.chips_per_worker or 0},
                    always=True):
                return self._fit_retrying(run_dir, result)
        finally:
            result.timeline_path = _write_timeline(run_dir)

    def _fit_retrying(self, run_dir: str, result: Result) -> Result:
        max_failures = self.run_config.failure_config.max_failures
        attempt = 0
        checkpoint = self.resume_from
        while True:
            try:
                return self._fit_once(run_dir, checkpoint, result)
            except (ActorDiedError, ActorUnavailableError,
                    ray_tpu.exceptions.WorkerCrashedError,
                    ray_tpu.exceptions.NodeDiedError) as e:
                attempt += 1
                # resume from the newest checkpoint any attempt produced
                ck = (Checkpoint(result.metrics["_checkpoint"],
                                 uri=result.metrics.get("_checkpoint_uri"))
                      if result.metrics.get("_checkpoint") else checkpoint)
                checkpoint = _latest_checkpoint(run_dir) or ck
                if max_failures >= 0 and attempt > max_failures:
                    result.error = f"worker group failed: {e}"
                    return result

    def _start_group(self, run_dir: str,
                     checkpoint: Optional[Checkpoint]) -> WorkerGroup:
        """The gang, reserved, spawned and set up: span
        `train.group_start`, closed when every `setup()` has returned."""
        with tracing.span("train.group_start", {
                "workers": self.scaling.num_workers}, always=True):
            group = WorkerGroup(self.scaling.num_workers,
                                self.scaling.worker_resources())
            try:
                self._setup_group(group, run_dir, checkpoint)
            except BaseException:
                group.shutdown()
                raise
            return group

    def _setup_group(self, group: WorkerGroup, run_dir: str,
                     checkpoint: Optional[Checkpoint]) -> None:
        # dataset shards: one DataIterator per rank (ref: session.py:901)
        shards: List[Dict[str, Any]] = _split_datasets(
            self.datasets, self.scaling.num_workers)
        coordinator = None
        if self.scaling.num_workers > 1 or self.backend.needs_coordinator:
            if getattr(self.backend, "needs_worker_addresses", False):
                # TF_CONFIG-style backends need the FULL cluster spec:
                # one reserved host:port per rank (each worker holds
                # its reservation until its own setup() releases it)
                infos = ray_tpu.get(
                    [w.host_info.remote() for w in group.workers])
                self.backend.worker_addresses = [
                    f"{i['hostname']}:{i['free_port']}" for i in infos]
                coordinator = self.backend.worker_addresses[0]
            else:
                info = ray_tpu.get(group.workers[0].host_info.remote())
                coordinator = f"{info['hostname']}:{info['free_port']}"
        ray_tpu.get([
            w.setup.remote(self.config, run_dir, self.scaling, checkpoint,
                           shards[i], coordinator,
                           self.run_config.checkpoint_config.num_to_keep,
                           self.backend)
            for i, w in enumerate(group.workers)])

    def _fit_once(self, run_dir: str, checkpoint: Optional[Checkpoint],
                  result: Result) -> Result:
        group = self._start_group(run_dir, checkpoint)
        try:
            run_refs = [w.run.remote(self.loop, self.config)
                        for w in group.workers]
            seen = 0
            hang_timeout = self.run_config.failure_config.hang_timeout_s
            startup_grace = self.run_config.failure_config.startup_grace_s
            last_progress = time.time()
            got_report = False
            while True:
                poll = ray_tpu.get(group.workers[0].poll.remote(seen))
                for r in poll["reports"]:
                    result.metrics_history.append(r)
                    result.metrics = r
                if poll["reports"]:
                    last_progress = time.time()
                    got_report = True
                seen += len(poll["reports"])
                if poll["error"]:
                    result.error = poll["error"]
                    break
                if poll["finished"]:
                    break
                # The no-progress clock effectively starts at the first
                # report: until then the worker is cold-starting (spawn +
                # jax import + first compile — repeated in full by every
                # restarted attempt), so the deadline is the startup
                # grace, not the steady-state report gap.
                limit = (hang_timeout if got_report
                         else max(hang_timeout or 0.0, startup_grace))
                if (hang_timeout is not None
                        and time.time() - last_progress > limit):
                    # stuck pjit program: a live-but-hung worker never
                    # raises, so the death-based retry path would wait
                    # forever — kill the group and surface a crash so
                    # fit()'s restart-from-checkpoint loop takes over
                    group.shutdown()
                    raise ray_tpu.exceptions.WorkerCrashedError(
                        f"train hang watchdog: no "
                        f"{'progress report' if got_report else 'first report'}"
                        f" for {limit}s (SURVEY hung-chip semantics: "
                        f"the group restarts from the last checkpoint)")
                ready, _ = ray_tpu.wait(run_refs, num_returns=len(run_refs),
                                        timeout=0.25)
                if len(ready) == len(run_refs):
                    # drain any last reports
                    poll = ray_tpu.get(group.workers[0].poll.remote(seen))
                    for r in poll["reports"]:
                        result.metrics_history.append(r)
                        result.metrics = r
                    break
            # surface user exceptions (TaskError) from any worker
            for ref in run_refs:
                try:
                    ray_tpu.get(ref, timeout=30)
                except TaskError as e:
                    result.error = str(e)
                    break
            if result.metrics.get("_checkpoint"):
                result.checkpoint = Checkpoint(
                    result.metrics["_checkpoint"],
                    uri=result.metrics.get("_checkpoint_uri"))
            else:
                result.checkpoint = _latest_checkpoint(run_dir)
            return result
        finally:
            group.shutdown()


class TorchTrainer(JaxTrainer):
    """Reference-parity torch trainer (ref: train/torch/torch_trainer.py):
    same orchestration, TorchBackend gloo process group instead of jax
    distributed bootstrap. User loops use torch.distributed +
    ray_tpu.train.prepare_model unchanged."""

    backend_cls = TorchBackend


class TensorflowTrainer(JaxTrainer):
    """Reference-parity TF trainer (ref: train/tensorflow/
    tensorflow_trainer.py + config.py:21,40): same orchestration,
    TF_CONFIG rendezvous exported per worker; user loops build
    tf.distribute.MultiWorkerMirroredStrategy unchanged."""

    backend_cls = TensorflowBackend


def _write_timeline(run_dir: str) -> Optional[str]:
    """The job's spans and instants as a Chrome trace beside the
    checkpoints, by the channel they ride anyway (the workers flushed
    theirs as their loops ended). Returns where it is, or None: a job
    does not fail for its timeline."""
    try:
        data = json.dumps(ray_tpu.timeline(
            limit=TIMELINE_RECORDS, chrome=True, spans_only=True))
        if storage.is_uri(run_dir):
            uri = storage.join_uri(run_dir, "timeline.json")
            fs, path = storage.get_fs_and_path(uri)
            fs.pipe_file(path, data.encode())
            return uri
        path = os.path.join(run_dir, "timeline.json")
        with open(path, "w") as f:
            f.write(data)
        return path
    except Exception:   # noqa: BLE001 - the run's result stands without it
        logger.exception("could not write the job's timeline to %s", run_dir)
        return None


def _latest_checkpoint(run_dir: str) -> Optional[Checkpoint]:
    from ray_tpu.train.checkpoint import CheckpointManager

    return CheckpointManager(run_dir).latest()


def _split_datasets(datasets: Dict[str, Any], n: int) -> List[Dict[str, Any]]:
    shards: List[Dict[str, Any]] = [dict() for _ in range(n)]
    for name, ds in datasets.items():
        if hasattr(ds, "streaming_split"):
            its = ds.streaming_split(n)
            for i in range(n):
                shards[i][name] = its[i]
        else:
            for i in range(n):
                shards[i][name] = ds
    return shards
