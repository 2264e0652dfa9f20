"""Shared fixtures.

- JAX tests run on a virtual 8-device CPU mesh (JAX_PLATFORMS=cpu, so
  xla_force_host_platform_device_count takes effect) — the reference's
  cluster_utils fake-topology idea applied to devices (SURVEY.md §4.2).
- Cluster fixtures mirror python/ray/tests/conftest.py ray_start_regular /
  ray_start_cluster.
"""

import os
import sys

# Must happen before anything imports jax (including transitively).
os.environ["JAX_PLATFORMS"] = "cpu"
# libtpu retries the GCP instance-metadata server for minutes when it is
# unreachable (sleep loops that even swallow SIGINT) — describing a TPU
# topology (tests/described_tpu.py) would hang the whole suite.
# Off-GCP there is nothing to fetch; skip the queries outright.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
# Telemetry ships in one batched report per interval (observability/agent.py).
# The 1 s production cadence is pure added latency for tests that poll for
# task events / metrics right after running a workload — use a quick beat
# suite-wide (explicit _system_config / monkeypatched intervals still win).
os.environ.setdefault("RAY_TPU_TELEMETRY_REPORT_INTERVAL_S", "0.25")
# Persistent XLA compile cache, shared by every process the suite spawns.
# Worker processes re-jit the same tiny test models constantly (each serve
# replica / train worker / rl learner compiles its own copy); with the
# cache those become disk hits — the paged-KV file alone drops 82s -> 41s.
# Workers inherit the env through nodelet spawn, so one knob covers all.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/ray_tpu_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

# the fixtures of the compiles for a described TPU (tests/test_tpu_compile_*):
# registered here for every file, built only in a file that asks for one
from described_tpu import (no_persistent_cache, on_chip_branch,  # noqa: E402,F401
                           one_chip, topo)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`) — sweeps, soak runs")


@pytest.fixture(scope="module", autouse=True)
def few_mappings():
    """A worker leaves every test file with an empty executable cache.
    Every loaded CPU executable holds memory mappings, a file that runs a
    model eagerly loads thousands of them (``tests/test_models_nemotron.py``
    left 18,470 in one process, ``jax.clear_caches()`` 733), and an xdist
    worker carries them from file to file: at the kernel's 65,530
    (``vm.max_map_count``) XLA's next load dies of a segmentation fault in
    ``deserialize_executable``, which xdist reports as whatever case the
    worker was running (ROADMAP.md D31; three whole runs of PR 48's tree
    lost a worker so, in two files). What a later file needs again comes
    from the persistent compile cache."""
    yield
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.clear_caches()


@pytest.fixture(scope="function")
def ray_start_regular():
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                        _system_config={"health_check_period_s": 0.2,
                                        "worker_idle_timeout_s": 60.0})
    yield info
    ray_tpu.shutdown()


@pytest.fixture(scope="function")
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False,
                      system_config={"health_check_period_s": 0.2,
                                     "health_check_failure_threshold": 5})
    yield cluster
    cluster.shutdown()


def _shm_segments_in_use():
    """Names of /dev/shm segments currently mmap'd by any live process.

    mtime is NOT a liveness signal — writes through an existing mmap do
    not reliably update it — so a healthy long-running cluster could look
    'idle for an hour'. /proc/*/maps lists the backing file of every
    mapping, which is authoritative.
    """
    import glob

    used = set()
    for maps in glob.glob("/proc/[0-9]*/maps"):
        try:
            with open(maps) as f:
                for line in f:
                    i = line.find("/dev/shm/")
                    if i >= 0:
                        used.add(line[i:].split()[0])
        except OSError:
            continue
    return used


def _reap_orphan_daemons():
    """Kill ray_tpu daemons orphaned by previous runs (PPID 1). Chaos /
    GCS-FT / cluster tests SIGKILL daemons mid-test; their children
    reparent to init and keep polling forever — dozens of leaked
    nodelets/workers measurably slow a 1-vCPU CI box (observed ~20%
    suite-wide). A healthy in-run cluster keeps gcs/nodelet parented to
    the driver process and workers parented to their nodelet, so at
    session START a PPID-1 daemon can only be leakage. Deliberately
    daemonized clusters (`cli start`) also reparent to init — set
    RAY_TPU_NO_REAP=1 to protect one while running tests."""
    import glob
    import os
    import signal

    if os.environ.get("RAY_TPU_NO_REAP"):
        return
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(os.path.join(os.path.dirname(stat), "cmdline"),
                      "rb") as f:
                argv = f.read().split(b"\0")
            if len(argv) < 3 or argv[1] != b"-m" or \
                    not argv[2].startswith(b"ray_tpu.core."):
                continue
            with open(stat) as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid == 1:
                os.kill(int(os.path.basename(os.path.dirname(stat))),
                        signal.SIGKILL)
        except (OSError, ValueError, IndexError):
            continue


def pytest_sessionstart(session):
    """Remove object-store segments leaked by previous runs' SIGKILLed
    daemons (chaos tests): stale /dev/shm entries accumulate across
    sessions and can pressure tmpfs during the suite. A segment is only
    reaped if NO live process maps it (checked via /proc/*/maps) and it
    is past a short creation grace period, so a LIVE cluster on the same
    machine is never touched. Leaked (orphaned) daemon PROCESSES are
    reaped too — see _reap_orphan_daemons."""
    import glob
    import os
    import time

    _reap_orphan_daemons()

    now = time.time()
    in_use = _shm_segments_in_use()
    for p in glob.glob("/dev/shm/rtx_test_*"):
        if p not in in_use:
            try:
                os.unlink(p)
            except OSError:
                pass
    # Non-test-prefixed segments keep the 1 h age guard ON TOP of the
    # maps check: /proc can hide mappers (other PID namespaces sharing
    # /dev/shm, hidepid mounts, EACCES on other users' maps), so the
    # liveness check alone is not proof of abandonment.
    for p in glob.glob("/dev/shm/raytpu_*") + glob.glob("/dev/shm/rtx_*"):
        if p in in_use:
            continue
        try:
            if now - os.path.getmtime(p) > 3600:
                os.unlink(p)
        except OSError:
            pass
