"""The CPU rehearsal that precedes every chip call: each kind walks the
whole command at the tiny size, every check against the plain reference
reads ``ok``, and then the platform check refuses: no result line, exit
code 1. Slow (a cluster starts and stops in each case)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("cell,chips,extra", [
    ("rehearse-train", 1, ["--seconds", "2"]),
    ("rehearse-train4", 4, ["--seconds", "2"]),
    ("rehearse-serve", 1, ["--seconds", "4", "--trace", "1"]),
])
def test_rehearsal_walks_the_command_and_prints_no_metric(cell, chips, extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_CHIPS=str(chips),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", "2147483659"] + extra,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = run.stdout.strip().splitlines()
    assert run.returncode == 1, run.stdout[-3000:] + run.stderr[-3000:]
    assert lines[-1].startswith("REFUSED: ran on 'cpu'"), lines[-5:]
    assert not any(ln.startswith("{") for ln in lines)
    checks = [ln for ln in lines if ln.startswith(("  ok: ", "  WRONG: "))]
    assert len(checks) >= 3 and all(c.startswith("  ok: ") for c in checks), \
        checks


def test_a_lost_cluster_gets_one_new_cluster():
    """``tools/retry_drill.py``: the first attempt's worker raises, the
    second attempt is the cell as committed and walks to the platform
    check; the cause of the first is printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_CHIPS="1")
    run = subprocess.run(
        [sys.executable, "benchmark/tools/retry_drill.py", "--workload",
         "rehearse-train", "--seed", "3234567891", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = run.stdout.strip().splitlines()
    assert run.returncode == 1, run.stdout[-3000:] + run.stderr[-3000:]
    assert sum(ln.startswith("ATTEMPT 1 FAILED") for ln in lines) == 1
    assert any("unknown optimizer" in ln for ln in lines)
    assert lines[-1].startswith("REFUSED: ran on 'cpu'"), lines[-5:]
    checks = [ln for ln in lines if ln.startswith(("  ok: ", "  WRONG: "))]
    assert len(checks) >= 3 and all(c.startswith("  ok: ") for c in checks)
