"""Node/session bootstrap: spawn gcs + nodelet daemons.

Reference: python/ray/_private/node.py (start_head_processes:1148) and
services.py (start_gcs_server:1280, start_raylet:1353). Daemons are separate
OS processes started with a ready-pipe handshake; the session directory holds
logs and liveness metadata.
"""

from __future__ import annotations

import atexit
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, Optional, Tuple

from ray_tpu.core.config import Config

Address = Tuple[str, int]


def _spawn_with_ready(cmd, session_dir: str, log_name: str,
                      timeout: float = 30.0) -> Tuple[subprocess.Popen, str]:
    """Start a daemon that writes "host:port[:...]\n" to --ready-fd."""
    rfd, wfd = os.pipe()
    os.set_inheritable(wfd, True)
    logdir = os.path.join(session_dir, "logs")
    os.makedirs(logdir, exist_ok=True)
    out = open(os.path.join(logdir, log_name + ".out"), "ab")
    err = open(os.path.join(logdir, log_name + ".err"), "ab")
    # pass_fds (implies close_fds=True): only the ready-fd crosses into the
    # daemon — inheriting everything leaks the parent's stdout/stderr pipes
    # into long-lived daemons, which keeps `pytest | tail`-style consumers
    # blocked on EOF forever after the parent exits.
    proc = subprocess.Popen(cmd + ["--ready-fd", str(wfd)],
                            stdout=out, stderr=err, pass_fds=(wfd,),
                            start_new_session=True)
    out.close(); err.close()
    os.close(wfd)
    line = b""
    deadline = time.time() + timeout
    with os.fdopen(rfd, "rb") as f:
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{log_name} died at startup; see {logdir}/{log_name}.err")
            chunk = f.readline()
            if chunk:
                line = chunk
                break
    if not line:
        proc.terminate()
        raise RuntimeError(f"{log_name} did not become ready in {timeout}s")
    return proc, line.decode().strip()


def start_gcs(session_dir: str, cfg: Config, host: str = "127.0.0.1",
              port: int = 0) -> Tuple[subprocess.Popen, Address]:
    proc, ready = _spawn_with_ready(
        [sys.executable, "-m", "ray_tpu.core.gcs", "--host", host,
         "--port", str(port), "--config", cfg.to_json()],
        session_dir, "gcs")
    h, p = ready.rsplit(":", 1)
    return proc, (h, int(p))


def start_nodelet(session_dir: str, cfg: Config, gcs_addr: Address,
                  resources: Optional[Dict[str, float]] = None,
                  labels: Optional[Dict[str, Any]] = None,
                  host: str = "127.0.0.1", port: int = 0,
                  log_name: str = "nodelet"):
    proc, ready = _spawn_with_ready(
        [sys.executable, "-m", "ray_tpu.core.nodelet", "--host", host,
         "--port", str(port), "--gcs", f"{gcs_addr[0]}:{gcs_addr[1]}",
         "--session-dir", session_dir,
         "--resources", json.dumps(resources or {}),
         "--labels", json.dumps(labels or {}),
         "--config", cfg.to_json()],
        session_dir, log_name)
    h, p, node_id_hex, store_name = ready.split(":", 3)
    return proc, (h, int(p)), node_id_hex, store_name


def new_session_dir() -> str:
    base = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
    path = os.path.join(base, f"session_{int(time.time() * 1000)}_{os.getpid()}")
    os.makedirs(os.path.join(path, "logs"), exist_ok=True)
    # convenience symlink like the reference's session_latest
    latest = os.path.join(base, "session_latest")
    try:
        if os.path.islink(latest) or os.path.exists(latest):
            os.remove(latest)
        os.symlink(path, latest)
    except OSError:
        pass
    return path


#: Google's PCI vendor id: what tells a TPU's vfio group from a GPU's,
#: a NIC's or an NVMe's passed through the same driver
_GOOGLE_PCI_VENDOR = "0x1ae0"
_DEV = "/dev"
_IOMMU_GROUPS = "/sys/kernel/iommu_groups"


def detect_tpu_chips() -> int:
    """Local chip count WITHOUT importing jax (daemons must not grab the
    TPU). RAY_TPU_CHIPS overrides. Otherwise count the device nodes the
    TPU driver exposes to this host: /dev/accel<N> (accel driver) or the
    numbered IOMMU groups under /dev/vfio (vfio driver: one group per
    chip, next to the /dev/vfio/vfio control node) that hold a Google
    PCI device. The TPU_* topology variables are not consulted, nor the
    PCI bus alone: a host that is handed one chip of a 2x2 board still
    carries the board's TPU_CHIPS_PER_HOST_BOUNDS=2,2,1 and sees four
    PCI devices, but only its own chip's group under /dev/vfio."""
    env = os.environ.get("RAY_TPU_CHIPS")
    if env is not None:
        return int(env)
    accel = [n for n in _listdir(_DEV) if n.startswith("accel")
             and n[5:].isdigit()]
    return len(accel) or sum(
        n.isdigit() and _vfio_group_is_tpu(n)
        for n in _listdir(f"{_DEV}/vfio"))


def _vfio_group_is_tpu(group: str) -> bool:
    devices = f"{_IOMMU_GROUPS}/{group}/devices"
    for dev in _listdir(devices):
        try:
            with open(f"{devices}/{dev}/vendor") as f:
                if f.read().strip().lower() == _GOOGLE_PCI_VENDOR:
                    return True
        except OSError:
            pass
    return False


def _listdir(path: str) -> list:
    try:
        return os.listdir(path)
    except OSError:
        return []
