"""Where a train_latent cell's step goes, from the traced run's own profile
(benchmark/out/trace, after `run.py --trace 1`): device time a step by what an op
is told to be from its HLO line, as the readers tell it (PERF.md 5). An op that
holds a shape of the attention half is the attention's first, then the heads' and
losses', then the expert layer's (a pass over the held experts' rows is as long as
the step's tokens in the GLM cell, so `[T, .]` rows of the shared SwiGLU and of the
router count with it, as `glm_share`'s `experts` counts them).

    python3 benchmark/tools/glm_breakdown.py <cell> <traced steps> [top]
"""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import model_glm, resolve, trace_reduce  # noqa: E402
from benchmark.readers import glm_kernel_roofline, glm_share  # noqa: E402
from benchmark.readers.expert_share import CONTROL  # noqa: E402
from benchmark.readers.kernel_roofline import (FLASH, operand_shapes,  # noqa: E402
                                               signature)

cell = resolve.cell(sys.argv[1])
steps = int(sys.argv[2])
top = int(sys.argv[3]) if len(sys.argv) > 3 else 25
sizes = model_glm.sizes(cell["config"])
mix = cell["mix"]
red = trace_reduce.reduce_file(trace_reduce.find_xplane(
    os.path.join(ROOT, "benchmark", "out", "trace")))
obs = {"sizes": sizes, "cell": cell, "values": {"held_rows": 1.0}}
mla, head = glm_share.mla_pattern(sizes, mix), glm_share.head_pattern(sizes, mix)
tokens, k = mix["batch"] * mix["seq"], sizes["top_k"]
rows = {operand_shapes(n)[5][0] for n in red["op_calls"]
        if signature(n) is not None
        and glm_kernel_roofline.classify(n, obs)[0] == "grouped_matmul"}
held = re.compile(rf"\[(?:{'|'.join(map(str, sorted(rows)))})[,\]]") if rows else None
assign = re.compile(rf"\[{tokens * k}[,\]]|\[{tokens},{k}[,\]]")


def kind(name: str) -> str:
    plain = re.sub(r"\{[^}]*\}", "", name)
    if signature(name) is not None:
        what = glm_kernel_roofline.classify(name, obs)[0]
        return "mosaic " + what + (
            " " + FLASH[signature(name)] if what == "flash_attention" else "")
    matmul = "convolution" in plain or "kind=kOutput" in plain
    if mla.search(plain):
        return "latent attention " + ("matmul fusions" if matmul
                                      else "other fusions")
    if head.search(plain):
        return "heads, losses and the module's projection"
    if assign.search(plain):
        return "all assignments (sort, counts, routes)"
    if held is not None and held.search(plain):
        return "expert layer rows " + ("matmul fusions (router, shared SwiGLU)"
                                       if matmul else
                                       "other fusions (gathers, scatter-adds, SwiGLU)")
    if trace_reduce.opcode(name).startswith("copy"):
        return "copies"
    return "other matmul fusions" if matmul else "other fusions"


by, ops = {}, []
for name, seconds in red["device_ops"]:
    if trace_reduce.opcode(name) in CONTROL:
        continue
    c = kind(name)
    by[c] = by.get(c, 0.0) + seconds
    ops.append((seconds, c, name))
total = sum(by.values())
print(json.dumps({"window_s": red["window_s"], "busy_s": red["busy_s"],
                  "steps": steps, "device_ms_a_step": 1e3 * total / steps}))
for c, s in sorted(by.items(), key=lambda kv: -kv[1]):
    print(f"{1e3 * s / steps:9.2f} ms a step {100 * s / total:6.2f}%  {c}")
for seconds, c, name in sorted(ops, reverse=True)[:top]:
    calls = red["op_calls"].get(name, 0)
    print(f"{1e3 * seconds / steps:8.2f} ms x{calls / steps:5.1f}  [{c}]  "
          f"{re.sub(r'{[^}]*}', '', name)[:260]}")
