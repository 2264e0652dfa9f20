"""The share of a step's assignments that lands on the held experts, layer by layer,
for many seeds' weights and tokens, at a train_hybrid cell's real size on the chip:
what one pass of `models/moe.py` HELD_PASS covers and what is left to further passes
(PERF.md 6, PR 32). One process, no
cluster; one JSON line a seed.

    chiprun --chips 1 -- python3 benchmark/tools/granite_share_probe.py <cell> [seed ...]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import model_granite, resolve  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402

cell = resolve.cell(sys.argv[1])
seeds = [int(s) for s in sys.argv[2:]] or list(range(1, 25))
recipe, mix = cell["train"], cell["mix"]
cfg = model_granite.hybrid_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "ssd_impl", "remat", "f32_logits") if k in recipe})
init = jax.jit(lambda k: hybrid.init_params(k, cfg))
forward = jax.jit(lambda p, t: hybrid.forward_with_stats(p, t, cfg)[1])
per_layer = mix["batch"] * mix["seq"] * cfg.top_k
worst = 0.0
for seed in seeds:
    params = init(jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"]), 0, cfg.vocab_size, "int32")
    stats = jax.device_get(forward(params, tokens))
    shares = (stats["held_counts"].sum(axis=1) / per_layer).tolist()
    worst = max(worst, max(shares))
    print(json.dumps({"seed": seed, "dropped": int(stats["dropped"].sum()),
                      "max": round(max(shares), 4), "mean": round(sum(shares) / len(shares), 4),
                      "layers": [round(s, 4) for s in shares],
                      "largest_expert": round(float(stats["held_counts"].max()) / per_layer, 4)}),
          flush=True)
    del params, stats
print(json.dumps({"worst_layer_share": worst, "seeds": len(seeds)}))
