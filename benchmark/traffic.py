"""The one general traffic generator. A mix is a data file of parameters;
this turns a mix and a seed into work, with no code per mix.

Every seed gets the SAME multiset of sizes in another order and with
other token values, so that runs with different seeds do the same work:
sizes come from a quantile grid of the mix's distributions (paired by a
permutation fixed in the mix), and only the order and the contents come
from ``--seed``.

Mix files (``traffic/<name>.json``):

  train   {"kind": "train", "seq": 4096, "batch": 2,
           "tokens": "uniform"}             a fresh uniform batch a step
  serve   {"kind": "serve", "loop": "closed", "clients": 32,
           "population": 256, "pairing_seed": 0,
           "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                          "min": 64, "max": 2048},
           "output_len": {...}, "shared_prefix_len": 0, "temperature": 0.0}
          "loop": "open" adds "rate_per_s" and "arrivals":
          {"dist": "poisson"} or {"dist": "gamma", "cv": 3.0}.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def quantile_grid(spec: dict, n: int) -> list:
    """``n`` sizes at the mid-quantiles of the distribution, clipped."""
    dist = spec["dist"]
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist == "lognormal":
            x = spec["median"] * math.exp(
                spec["sigma"] * NormalDist().inv_cdf(u))
        elif dist == "uniform":
            x = spec["min"] + u * (spec["max"] - spec["min"])
        elif dist == "fixed":
            x = spec["value"]
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
        out.append(int(min(max(round(x), spec.get("min", 1)),
                           spec.get("max", 1 << 30))))
    return out


def serve_requests(mix: dict, vocab: int, seed: int, count: int) -> list:
    """``count`` requests in this seed's order:
    ``[{"prompt": [...], "max_new_tokens": n, "temperature": t}, ...]``.
    Sizes cycle through the population; token values are always fresh, so
    nothing but ``shared_prefix_len`` tokens is ever shared."""
    n = mix["population"]
    prompts = quantile_grid(mix["prompt_len"], n)
    outputs = quantile_grid(mix["output_len"], n)
    random.Random(mix["pairing_seed"]).shuffle(outputs)   # same every seed
    order = list(range(n))
    rng = random.Random(seed)
    rng.shuffle(order)
    shared = [rng.randrange(vocab)
              for _ in range(mix.get("shared_prefix_len", 0))]
    reqs = []
    for k in range(count):
        i = order[k % n]
        body = [rng.randrange(vocab) for _ in range(prompts[i] - len(shared))]
        reqs.append({"prompt": shared + body, "max_new_tokens": outputs[i],
                     "temperature": mix.get("temperature", 0.0)})
    return reqs


def arrivals(mix: dict, seconds: float, seed: int) -> list:
    """Open loop: the times at which requests are due, from 0. The number
    of arrivals is fixed by the rate (so every seed offers the same load);
    the seed draws the gaps, rescaled to fill the window."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    rng = random.Random(seed ^ 0x9E3779B1)
    spec = mix["arrivals"]
    if spec["dist"] == "poisson":
        gaps = [rng.expovariate(1.0) for _ in range(n)]
    elif spec["dist"] == "gamma":
        shape = 1.0 / (spec["cv"] ** 2)
        gaps = [rng.gammavariate(shape, 1.0 / shape) for _ in range(n)]
    else:
        raise ValueError(f"unknown arrival process {spec['dist']!r}")
    scale = seconds / sum(gaps)
    t, due = 0.0, []
    for g in gaps:
        due.append(t)
        t += g * scale
    return due
