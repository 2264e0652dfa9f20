"""The trace reduction on a small trace recorded on the chip
(``record_tiny_trace.py``: three executions of one program, a 2 ms host
sleep after each) and on synthetic planes."""
import os

import pytest

from benchmark import trace_reduce as tr

TINY = os.path.join(os.path.dirname(__file__), "tiny_tpu.xplane.pb")


def test_recorded_trace():
    red = tr.reduce_file(TINY)
    assert red["devices"] == 1 and red["events"] == 33
    assert ("/device:TPU:0", "XLA Ops", 33) in red["structure"]
    ops = {tr.short_name(n): (s, red["op_calls"][n])
           for n, s in red["device_ops"]}
    # scan of 3 matmul+tanh, 3 executions: 9 calls of the fused body
    assert ops["convolution_tanh_fusion.2 fusion"][1] == 9
    assert ops["while while"][1] == 3
    # self times add up to the busy time (the while holds its body)
    assert sum(s for s, _ in ops.values()) == pytest.approx(red["busy_s"])
    assert red["busy_s"] == pytest.approx(2.0115e-05, rel=1e-3)
    assert 0.004 < red["window_s"] < 0.02           # two 2 ms sleeps inside
    assert red["collective_exposed_s"] == 0.0
    gaps = dict(red["idle_gaps"])
    (name, gap), = [(k, v) for k, v in gaps.items() if "->" in k]
    assert name.startswith("jit_tiny_program") and gap > 0.004
    assert red["busy_s"] + sum(gaps.values()) == pytest.approx(
        red["window_s"])


def test_names():
    line = ('%checkpoint.24 = (f32[3,32]{1,0:T(8,128)}, f32[3,32]{1,0}) '
            'custom-call(bf16[3]{0:T(8)(2,1)S(1)} %a, bf16[3]{0} %b), '
            'custom_call_target="tpu_custom_call", x={}')
    assert tr.head(line) == "checkpoint.24"
    assert tr.opcode(line) == "custom-call"
    assert tr.short_name(line) == "checkpoint.24 custom-call tpu_custom_call"
    assert tr.is_collective("%x.1 = bf16[8]{0} all-gather-start(bf16[4] %p)")
    assert tr.is_collective("all-reduce.3") and not tr.is_collective(line)


def test_synthetic_plane_nesting_collectives_and_gaps():
    ops = [(0, 100, "%while.1 = () while(%t)"),
           (0, 40, "%fusion.1 = f32[] fusion(%a)"),
           (40, 70, "%ar.1 = f32[] all-reduce(%b)"),
           (70, 100, "%fusion.2 = f32[] fusion(%c)"),
           (150, 200, "%fusion.1 = f32[] fusion(%a)")]
    mods = [(0, 100, "jit_a"), (150, 200, "jit_b")]
    p = tr.reduce_plane(ops, mods, window=(0, 250))
    assert p["busy_ns"] == 150 and p["window_ns"] == 250
    assert p["collective_exposed_ns"] == 30
    assert p["per_op_ns"]["%while.1 = () while(%t)"] == 0
    assert p["per_op_ns"]["%fusion.1 = f32[] fusion(%a)"] == 90
    assert p["per_op_calls"]["%fusion.1 = f32[] fusion(%a)"] == 2
    assert p["gaps_ns"] == {"jit_a -> jit_b": 50, "jit_b -> window end": 50}
    two = tr.reduce_planes([p, tr.reduce_plane(ops[:4], mods, (0, 250))])
    assert two["devices"] == 2 and two["busy_s"] == pytest.approx(125e-9)
    assert tr.reduce_planes([]) == {}


def test_interval_union():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
