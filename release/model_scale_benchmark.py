"""North-star model-scale feasibility: 7B on v5e-8, 70B on v5p-64.

Compiles the REAL sharded train step (parallel/train_step.py over
models/llama.py loss_fn) against device-less TPU topologies
(jax.experimental.topologies) — the actual XLA:TPU compiler runs, enforces
the per-chip HBM budget (a config that doesn't fit fails compilation with
RESOURCE_EXHAUSTED), and reports the authoritative per-device
`peak_memory_in_bytes`. No TPU pod is needed: only the compiler runs.

This answers BASELINE.md target configs 2-3 (Llama-2 7B DP/FSDP on v5e-8;
Llama-3-class 70B hybrid mesh on v5p-64) with evidence, plus a projected
tokens/s/chip from the measured single-chip MFU (BENCH 1B run) and an ICI
roofline comm model (scaling-book style: compute vs. all-gather/
reduce-scatter bytes over per-axis ICI bandwidth).

Reference analog: the reference proves LLM scale with
release/alpa_tests/train_opt_2_7b_minimum.py (OPT-2.7B via Alpa-on-Ray,
8xV100); here the proof is a compile against the real TPU HBM model plus
a roofline, because multi-chip hardware isn't attached.

Run:  PYTHONPATH=/root/repo python release/model_scale_benchmark.py
Artifacts: release/MODEL_SCALE.json (one entry per case).
"""

from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    # Concrete ops run on CPU; the AOT compiles below target TPU
    # topologies through libtpu regardless of JAX_PLATFORMS.
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

# --- chip model (public v5e/v5p datasheet numbers) ---------------------------
CHIPS = {
    "v5e": {
        "hbm_bytes": 16e9,
        "peak_bf16_flops": 197e12,
        # all-gather bandwidth along one torus axis: 2 ICI links x ~45 GB/s
        "ici_axis_bw": 90e9,
        "topology": "v5e:2x4",
        "n_devices": 8,
    },
    "v5p": {
        "hbm_bytes": 95e9,
        "peak_bf16_flops": 459e12,
        "ici_axis_bw": 180e9,  # 2 links x ~90 GB/s per axis of the 3D torus
        "topology": "v5p:4x4x4",
        "n_devices": 64,
    },
}

# Builders' account from an earlier round's bench.py 1B run (not measured
# on today's chip): the MFU the projection assumes the large model
# sustains per chip. 7B+ models have better arithmetic intensity than 1B,
# so this is conservative.
MEASURED_MFU = 0.5337


def flops_per_token(n_params: int, n_layers: int, seq: int, d_model: int):
    """Train step FLOPs/token: 6N weight flops + attention (bench.py's
    12*L*S*D convention, fwd+bwd causal)."""
    return 6 * n_params + 12 * n_layers * seq * d_model


def project_tokens_per_sec_per_chip(n_params, n_layers, seq, d_model,
                                    per_dev_tokens, n_dev, chip,
                                    mfu=MEASURED_MFU):
    """Roofline projection: compute time at measured MFU vs. FSDP comm
    time (bf16 all-gather fwd + bwd, f32 grad reduce-scatter = 8N bytes
    x (n-1)/n per device per step), assuming compute/comm overlap."""
    c = CHIPS[chip]
    fpt = flops_per_token(n_params, n_layers, seq, d_model)
    compute_s = fpt * per_dev_tokens / (c["peak_bf16_flops"] * mfu)
    comm_bytes = 8 * n_params * (n_dev - 1) / n_dev
    comm_s = comm_bytes / c["ici_axis_bw"]
    step_s = max(compute_s, comm_s)
    return {
        "projected_tokens_per_sec_per_chip": round(per_dev_tokens / step_s, 1),
        "compute_s": round(compute_s, 3),
        "fsdp_comm_s": round(comm_s, 3),
        "bound": "compute" if compute_s >= comm_s else "comm",
        "assumed_mfu": mfu,
    }


def compile_case(preset: str, chip: str, mesh_axes: dict, rules_name: str,
                 batch: int, seq: int, mu_dtype=None):
    """AOT-compile the train step for `preset` on `chip`'s topology.
    Returns the result dict; raises on compile failure (incl. HBM
    RESOURCE_EXHAUSTED, which IS the does-not-fit signal)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from ray_tpu.models import llama
    from ray_tpu.parallel import ShardingRules
    from ray_tpu.parallel.mesh import AXIS_ORDER
    from ray_tpu.parallel.train_step import (batch_sharding,
                                             make_train_state_init,
                                             make_train_step)

    c = CHIPS[chip]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=c["topology"])
    sizes = tuple(mesh_axes.get(a, 1) for a in AXIS_ORDER)
    assert int(np.prod(sizes)) == c["n_devices"], (sizes, c["n_devices"])
    mesh = Mesh(np.array(topo.devices).reshape(sizes), AXIS_ORDER)

    cfg = llama.PRESETS[preset].replace(
        dtype=jnp.bfloat16, remat=True, attn_impl="xla",
        f32_logits=False, max_seq_len=seq)
    rules = getattr(ShardingRules, rules_name)()
    opt = optax.adamw(3e-4, weight_decay=0.01,
                      **({"mu_dtype": mu_dtype} if mu_dtype else {}))

    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), opt, mesh, rules,
        llama.param_specs(cfg))
    state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    state_abs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shapes, state_sh)
    bshape = {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)}
    bsh = batch_sharding(mesh, rules, bshape)
    batch_abs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        bshape, bsh)

    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        opt, mesh, rules, state_sh, batch_shapes=bshape)
    compiled = step.lower(state_abs, batch_abs).compile()
    mem = compiled.memory_analysis()
    peak = mem.peak_memory_in_bytes

    n_params = llama.num_params(cfg)
    per_dev_tokens = batch * seq // c["n_devices"]
    result = {
        "model": preset,
        "params": n_params,
        "chip": chip,
        "topology": c["topology"],
        "n_devices": c["n_devices"],
        "mesh": {k: v for k, v in mesh_axes.items() if v != 1},
        "rules": rules_name,
        "global_batch": batch,
        "seq": seq,
        "optimizer": "adamw" + (f"(mu={mu_dtype.__name__})" if mu_dtype
                                else "(f32)"),
        "peak_hbm_bytes_per_device": int(peak),
        "peak_hbm_gb": round(peak / 1e9, 2),
        "hbm_limit_gb": round(c["hbm_bytes"] / 1e9, 1),
        "fits": bool(peak <= c["hbm_bytes"]),
        **project_tokens_per_sec_per_chip(
            n_params, cfg.n_layers, seq, cfg.d_model, per_dev_tokens,
            c["n_devices"], chip),
    }
    return result


CASES = [
    # BASELINE target 2: Llama-2 7B on v5e-8 (16 GB/chip). Full f32 adam
    # state (84 GB) + activations does NOT fit 128 GB aggregate with
    # gathered copies; the shipping recipe keeps f32 masters and bf16
    # first moment. Verified peak 15.51 GB < 15.75 GB usable.
    dict(preset="7b", chip="v5e", mesh_axes={"fsdp": 8}, rules_name="fsdp",
         batch=8, seq=2048, mu_dtype="bf16"),
    # BASELINE target 3: 70B-class on v5p-64 (95 GB/chip), pure FSDP.
    dict(preset="70b", chip="v5p", mesh_axes={"fsdp": 64},
         rules_name="fsdp", batch=64, seq=4096, mu_dtype=None),
    # 70B hybrid FSDP x TP (Megatron-style tensor axes over tp=4).
    dict(preset="70b", chip="v5p", mesh_axes={"fsdp": 16, "tp": 4},
         rules_name="fsdp_tp", batch=16, seq=4096, mu_dtype=None),
]


def main():
    import jax.numpy as jnp

    out = []
    for case in CASES:
        kw = dict(case)
        kw["mu_dtype"] = jnp.bfloat16 if kw["mu_dtype"] == "bf16" else None
        label = f"{case['preset']}@{case['chip']}:{case['mesh_axes']}"
        try:
            r = compile_case(**kw)
        except Exception as e:  # RESOURCE_EXHAUSTED = does not fit
            msg = str(e)
            r = {"model": case["preset"], "chip": case["chip"],
                 "mesh": case["mesh_axes"], "fits": False,
                 "error": msg[:300]}
        out.append(r)
        print(json.dumps(r), flush=True)

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MODEL_SCALE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}", file=sys.stderr)
    return 0 if all(r.get("fits") for r in out) else 1


if __name__ == "__main__":
    sys.exit(main())
