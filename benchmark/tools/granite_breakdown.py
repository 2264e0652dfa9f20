"""Where a train_hybrid cell's step goes, from the traced run's own profile
(benchmark/out/trace, after `run.py --trace 1`): device time a step by what an op
is told to be from its HLO line, as the readers tell it (PERF.md 5).

    python3 benchmark/tools/granite_breakdown.py <cell> <traced steps> [top]
"""
import json
import os
import re
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import model_granite, resolve, trace_reduce  # noqa: E402
from benchmark.readers import granite_kernel_roofline, mixer_share  # noqa: E402
from benchmark.readers.expert_share import CONTROL  # noqa: E402
from benchmark.readers.kernel_roofline import signature  # noqa: E402

cell = resolve.cell(sys.argv[1])
steps = int(sys.argv[2])
top = int(sys.argv[3]) if len(sys.argv) > 3 else 25
sizes = model_granite.sizes(cell["config"])
mix = cell["mix"]
red = trace_reduce.reduce_file(trace_reduce.find_xplane(
    os.path.join(ROOT, "benchmark", "out", "trace")))
obs = {"sizes": sizes, "cell": cell, "values": {"held_rows": 1.0}}
mixer = mixer_share.patterns(sizes, mix)
vocab = re.compile(rf"[\[,]{sizes['vocab_size']}[,\]]")
from ray_tpu.models import moe  # noqa: E402

rows = moe.held_rows(types.SimpleNamespace(
    top_k=sizes["top_k"], n_held=sizes["experts_held"][0],
    n_experts=sizes["n_experts"]), mix["batch"] * mix["seq"])
held = re.compile(rf"\[{rows}[,\]]")
assign = re.compile(rf"\[{mix['batch'] * mix['seq'] * sizes['top_k']}[,\]]"
                    rf"|\[{mix['batch'] * mix['seq']},{sizes['top_k']}[,\]]")


def kind(name: str) -> str:
    plain = re.sub(r"\{[^}]*\}", "", name)
    if signature(name) is not None:
        return "mosaic " + granite_kernel_roofline.classify(name, obs)[0]
    if held.search(plain):
        return "held experts' rows (gathers, scatter-adds, SwiGLU)"
    if assign.search(plain):
        return "all assignments (sort, counts, routes)"
    matmul = "convolution" in plain or "kind=kOutput" in plain
    if mixer.search(plain):
        return "mixer " + ("matmul fusions" if matmul else "other fusions")
    if vocab.search(plain):
        return "head and loss"
    if trace_reduce.opcode(name).startswith("copy"):
        return "copies"
    return "other matmul fusions" if matmul else "other fusions"


by, ops = {}, []
for name, seconds in red["device_ops"]:
    if trace_reduce.opcode(name) in CONTROL:
        continue
    k = kind(name)
    by[k] = by.get(k, 0.0) + seconds
    ops.append((seconds, k, name))
total = sum(by.values())
print(json.dumps({"window_s": red["window_s"], "busy_s": red["busy_s"],
                  "steps": steps, "device_ms_a_step": 1e3 * total / steps}))
for k, s in sorted(by.items(), key=lambda kv: -kv[1]):
    print(f"{1e3 * s / steps:9.2f} ms a step {100 * s / total:6.2f}%  {k}")
for seconds, k, name in sorted(ops, reverse=True)[:top]:
    calls = red["op_calls"].get(name, 0)
    print(f"{1e3 * seconds / steps:8.2f} ms x{calls / steps:5.1f}  [{k}]  "
          f"{re.sub(r'{[^}]*}', '', name)[:260]}")
