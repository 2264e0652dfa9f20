"""CPU rehearsal of chip_smoke.py: the same script and code path at the
tiny preset. Every phase must run and pass on the CPU, and the run must
then FAIL the platform check — "ok": true is only ever printed by a run
that held a TPU."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*args, chips, tmp_path, devices=1):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", RAY_TPU_CHIPS=str(chips),
               RAY_TPU_TMPDIR=str(tmp_path / "sessions"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def _assert_rehearsal_failed(r):
    assert r.returncode != 0, r.stdout[-3000:]
    assert '"ok": true' not in r.stdout
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("FAILED:") and "'cpu'" in last, last


def test_rehearsal_runs_every_phase_then_fails_off_chip(tmp_path):
    r = _run("--size", "tiny", chips=1, tmp_path=tmp_path)
    out = r.stdout
    for phase in ("train", "serve", "reference"):
        assert f"phase {phase} passed on cpu" in out, out[-3000:] + r.stderr[-3000:]
    # what each phase is there to show
    assert "losses fall on the repeated batch" in out
    assert "the repeated prompt returns the same tokens" in out
    assert "admitted from the prefix cache" in out
    assert "every served token is the reference argmax" in out
    assert "is gone after shutdown()" in out
    # the parent spawned everything and opened no backend itself
    assert "the parent process initialised a jax backend" not in out
    _assert_rehearsal_failed(r)


def test_four_chip_option_runs_only_the_sharded_phase(tmp_path):
    r = _run("--size", "tiny", "--chips", "4", chips=4, devices=4,
             tmp_path=tmp_path)
    out = r.stdout
    assert "phase sharded passed on cpu" in out, out[-3000:] + r.stderr[-3000:]
    assert "the mesh lists four distinct devices [0, 1, 2, 3]" in out
    assert "sharded and one-device losses agree" in out
    assert "phase train" not in out and "phase serve" not in out
    _assert_rehearsal_failed(r)


def test_full_size_refuses_a_host_without_a_chip(tmp_path):
    """As the driver runs it in the sandbox: no arguments, no chip."""
    r = _run(chips=0, tmp_path=tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "this host shows 0 TPU chip(s)" in r.stdout


def test_full_size_refuses_a_cpu_backend_before_the_first_trace(tmp_path):
    """A host that advertises a chip while jax is held to the CPU (or the
    chip is taken): the worker fails with the cause, nothing is built."""
    r = _run(chips=1, tmp_path=tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "not 'tpu'" in r.stdout and "refusing to run" in r.stdout
    assert "phase train passed" not in r.stdout


def test_parent_of_the_smoke_holds_no_jax_backend():
    """Importing what the smoke's parent imports initialises no backend:
    a parent that had opened the chip would lock its own workers out."""
    code = (
        "import sys, importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', {SMOKE!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "import ray_tpu\n"
        "from ray_tpu import serve\n"
        "from ray_tpu.parallel import MeshSpec\n"
        "from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig\n"
        "from ray_tpu.core import compile_cache\n"
        "compile_cache.env_defaults({})\n"
        "serve.build_llm_app(use_sim=False, num_replicas=1, preset='tiny')\n"
        "b = sys.modules.get('jax._src.xla_bridge')\n"
        "print('BACKENDS', sorted(getattr(b, '_backends', {})) if b else [])\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BACKENDS []" in r.stdout, r.stdout


@pytest.mark.parametrize("accel,vfio,expect", [
    # accel driver: one node per chip
    (["accel0", "accel1", "accel2", "accel3"], {}, 4),
    # vfio driver as the chip machine shows it: one chip's group under
    # /dev/vfio, the board's other three only on the PCI bus
    ([], {"2": "0x1ae0"}, 1),
    # a GPU, a NIC or an NVMe passed through vfio is not a TPU
    ([], {"2": "0x1ae0", "7": "0x10de", "9": "0x8086"}, 1),
    ([], {"7": "0x10de"}, 0),
    # a group whose devices cannot be read is not counted
    ([], {"5": None}, 0),
    ([], {}, 0),
])
def test_detect_tpu_chips_counts_only_tpu_device_nodes(
        tmp_path, monkeypatch, accel, vfio, expect):
    from ray_tpu.core import node

    dev, groups = tmp_path / "dev", tmp_path / "iommu_groups"
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()
    (dev / "null").touch()
    for name in accel:
        (dev / name).touch()
    for group, vendor in vfio.items():
        (dev / "vfio" / group).touch()
        if vendor is not None:
            pci = groups / group / "devices" / "0000:00:0a.0"
            pci.mkdir(parents=True)
            (pci / "vendor").write_text(vendor + "\n")
    monkeypatch.setattr(node, "_DEV", str(dev))
    monkeypatch.setattr(node, "_IOMMU_GROUPS", str(groups))
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    assert node.detect_tpu_chips() == expect
    monkeypatch.setenv("RAY_TPU_CHIPS", "3")
    assert node.detect_tpu_chips() == 3
