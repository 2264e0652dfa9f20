"""State-space / attention hybrid with sparse experts, as Granite-4.0-H
has it (transformers ``modeling_granitemoehybrid.py``, whose state-space
layer is Bamba's Mamba-2 mixer, arXiv:2405.21060): most layers mix the
sequence by a selective state-space recurrence, one in ten by causal
grouped-query attention with no position embedding, and every layer is
followed by routed experts beside a shared SwiGLU.

This module is only what differs from ``models/llama.py`` and
``models/moe.py``: the config (the layer pattern, the mixer's sizes, the
three multipliers), the parameter tree and the Mamba-2 mixer
(``mixer_half``). The attention layer is ``llama._attention_half`` (no
rotary tables, ``attn_scale`` for the softmax), the expert layer is
``moe.feed_forward`` (Granite's softmax over the K largest logits IS
``route`` with ``norm_topk``; ``experts_held``, ``shared_d_ff``), the
router losses ``moe.finish_loss``; embedding, the loop over the layers,
remat and its policy, the head (tied to the embedding here) and the
cross-entropy are ``llama.forward_with_stats`` and ``llama.loss_fn``.

Layers of two kinds cannot be one stack: ``params["layers"]`` is a LIST of
stacks, one a run of adjacent layers of one kind (``layer_runs``; the
published pattern is [5 mamba, attention, 4 mamba] four times over: runs
of 5, 1, 9, 1, 9, 1, 9, 1, 4), each run one ``lax.scan``; every run of a
kind is scanned by the same function, traced under the per-layer
checkpoint of ``llama._checkpoint`` (instant ``hybrid.layer_plan``).

The mixer, for u = rms_norm(x) [B, S, D], H heads of width P, state N:

    [z | xBC | dt] = u @ in_proj          (H P | H P + 2 N | H, no bias)
    xBC = silu(conv(xBC) + b)             depthwise, causal, ``mamba_conv`` taps
    x [H, P], B [N], C [N] = split(xBC)   one group: B and C shared by all heads
    dt = softplus(dt + dt_bias); A = -exp(a_log)             float32, a head
    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T; y_t = s_t C_t + D x_t
    y = rms_norm(y * silu(z)) * gate_norm                    over all H P
    out = y @ out_proj

The recurrence is ``ops/ssd.py``: "xla" (plain einsums; the CPU, a mesh)
or "pallas" (the Mosaic calls), chosen by ``ssd_impl`` as ``attn_impl``
and ``gmm_impl`` choose theirs; the kernel path refuses a mesh of several
devices.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama as _ll
from ray_tpu.models import moe as _moe
from ray_tpu.ops.ssd import ssd_scan


@dataclass(frozen=True)
class HybridConfig(_moe.MoEConfig):
    """``n_heads``, ``n_kv_heads`` are the attention layers'; ``d_ff`` is
    the width of ONE routed expert."""
    # one kind a layer, "mamba" or "attention"; () = every layer "mamba"
    layer_types: Tuple[str, ...] = ()
    mamba_heads: int = 8
    mamba_head_dim: int = 16
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_chunk: int = 64
    ssd_impl: str = "xla"               # "xla" | "pallas"
    # (the three multipliers and ``attn_scale`` are LlamaConfig's fields)
    rope: bool = False                  # no position embedding
    norm_topk: bool = True              # softmax over the K largest logits

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def kinds(self) -> Tuple[str, ...]:
        return self.layer_types or ("mamba",) * self.n_layers

    def replace(self, **kw) -> "HybridConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, HybridConfig] = {
    "tiny": HybridConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=32, max_seq_len=128, n_experts=8, top_k=2, shared_d_ff=48,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0, attn_scale=1.0 / 16),
}

# what a mamba layer has not of the expert family's tree: the attention half
_ATTENTION_ONLY = ("attn_norm", "wq", "wk", "wv", "wo")

# what the layer checkpoint keeps beside the layer's input and flash's
# residuals (llama._checkpoint): the expert layer's routes; of a mixer
# nothing, its scan runs again
REMAT_SAVED = _moe.REMAT_SAVED


def layer_runs(cfg: HybridConfig) -> List[Tuple[str, int]]:
    """[(kind, how many adjacent layers of it), ...] in the layers' order."""
    if len(cfg.kinds) != cfg.n_layers:
        raise ValueError(f"{len(cfg.kinds)} layer types for {cfg.n_layers} "
                         "layers")
    runs: List[Tuple[str, int]] = []
    for kind in cfg.kinds:
        if kind not in ("mamba", "attention"):
            raise ValueError(f"unknown layer type {kind!r}")
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def _run_configs(cfg: HybridConfig):
    return [(kind, cfg.replace(n_layers=n)) for kind, n in layer_runs(cfg)]


def _mamba_sizes(cfg: HybridConfig):
    inner, n = cfg.mamba_inner, cfg.mamba_state
    return inner, inner + 2 * n, 2 * inner + 2 * n + cfg.mamba_heads


def param_specs(cfg: HybridConfig) -> Dict[str, Any]:
    L = ("layers",)
    runs = []
    for kind, run in _run_configs(cfg):
        lay = _moe.param_specs(run)["layers"]
        if kind == "mamba":
            for w in _ATTENTION_ONLY:
                del lay[w]
            lay.update({
                "mix_norm": L + ("embed_nr",),
                "in_proj": L + ("embed", "mlp"),
                "conv_w": L + (None, "mlp"), "conv_b": L + ("mlp",),
                "dt_bias": L + (None,), "a_log": L + (None,),
                "d_skip": L + (None,), "gate_norm": L + ("mlp",),
                "out_proj": L + ("mlp", "embed")})
        runs.append(lay)
    return {"embed": ("vocab", "embed"), "layers": runs,
            "final_norm": ("embed_nr",)}


def init_params(key, cfg: HybridConfig) -> Dict[str, Any]:
    """Norms 1, projections normal over the square root of their fan-in,
    the mixer's own as Mamba-2 sets them: ``dt_bias`` the inverse softplus
    of steps log-uniform in [0.001, 0.1], ``a_log`` the log of rates
    uniform in [1, 16], ``d_skip`` 1."""
    pd = cfg.param_dtype
    D, H = cfg.d_model, cfg.mamba_heads
    inner, conv_dim, proj = _mamba_sizes(cfg)
    runs = []
    for i, (kind, run) in enumerate(_run_configs(cfg)):
        k = jax.random.fold_in(key, 100 + i)
        lay = _moe.init_params(k, run.replace(vocab_size=1))["layers"]
        if kind == "mamba":
            for w in _ATTENTION_ONLY:
                del lay[w]
            n = run.n_layers
            ks = jax.random.split(jax.random.fold_in(k, 3), 5)
            step = jnp.exp(jax.random.uniform(
                ks[2], (n, H), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            lay.update({
                "mix_norm": jnp.ones((n, D), pd),
                "in_proj": jax.random.normal(ks[0], (n, D, proj), pd)
                * D ** -0.5,
                "conv_w": jax.random.normal(
                    ks[1], (n, cfg.mamba_conv, conv_dim), pd)
                * cfg.mamba_conv ** -0.5,
                "conv_b": jnp.zeros((n, conv_dim), pd),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(pd),
                "a_log": jnp.log(jax.random.uniform(
                    ks[3], (n, H), minval=1.0, maxval=16.0)).astype(pd),
                "d_skip": jnp.ones((n, H), pd),
                "gate_norm": jnp.ones((n, inner), pd),
                "out_proj": jax.random.normal(ks[4], (n, inner, D), pd)
                * inner ** -0.5})
        runs.append(lay)
    return {"embed": jax.random.normal(jax.random.fold_in(key, 0),
                                       (cfg.vocab_size, D), pd) * 0.02,
            "layers": runs, "final_norm": jnp.ones((D,), pd)}


def num_params(cfg: HybridConfig) -> int:
    D, H = cfg.d_model, cfg.mamba_heads
    inner, conv_dim, proj = _mamba_sizes(cfg)
    attention = (D * cfg.n_heads * cfg.head_dim * 2
                 + 2 * D * cfg.n_kv_heads * cfg.head_dim)
    mamba = (D * proj + (cfg.mamba_conv + 1) * conv_dim + 3 * H + inner
             + inner * D)
    experts = (D * cfg.n_experts + 3 * cfg.n_held * D * cfg.d_ff
               + 3 * D * cfg.shared_d_ff)
    return cfg.vocab_size * D + D + sum(
        2 * D + experts + (mamba if kind == "mamba" else attention)
        for kind in cfg.kinds)


def _causal_conv(x, w, b):
    """Depthwise causal convolution over the sequence: x [B, S, C],
    w [taps, C], b [C] -> out[t] = b + sum_j w[j] x[t - taps + 1 + j],
    float32 (x before the sequence is zero)."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = b.astype(jnp.float32)
    for j in range(taps):
        out = out + padded[:, j:j + s].astype(jnp.float32) \
            * w[j].astype(jnp.float32)
    return out


def mixer_half(x, lp, cfg: HybridConfig, kind: str, mesh=None):
    """The Mamba-2 half of a block: x [B, S, D] -> x + its mixer's output
    (the module docstring has the equations)."""
    assert kind == "mamba", kind
    if cfg.ssd_impl == "pallas" and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "ssd_impl='pallas' runs on one device: GSPMD cannot partition "
            "the Mosaic scan, and the mixer has no shard_map of its own yet "
            f"(mesh {dict(mesh.shape)}); use ssd_impl='xla' on a mesh")
    B, S, _ = x.shape
    H, P, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state
    inner, conv_dim, _ = _mamba_sizes(cfg)
    dt_, f32 = cfg.dtype, jnp.float32
    u = _ll.rms_norm(x, lp["mix_norm"], cfg.norm_eps)
    zxbcdt = u @ _ll._dq(lp["in_proj"], dt_)
    z, xbc, step = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv_dim],
                    zxbcdt[..., inner + conv_dim:])
    xbc = jax.nn.silu(_causal_conv(xbc, lp["conv_w"], lp["conv_b"])
                      ).astype(dt_)
    xs = xbc[..., :inner].reshape(B, S, H, P)
    bm, cm = xbc[..., inner:inner + N], xbc[..., inner + N:]
    step = jax.nn.softplus(step.astype(f32) + lp["dt_bias"].astype(f32))
    y = ssd_scan(xs, step, -jnp.exp(lp["a_log"].astype(f32)), bm, cm,
                 chunk=min(cfg.mamba_chunk, S), impl=cfg.ssd_impl)
    y = y.astype(f32) + xs.astype(f32) * lp["d_skip"].astype(f32)[:, None]
    y = y.reshape(B, S, inner) * jax.nn.silu(z.astype(f32))
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                           + cfg.norm_eps)).astype(dt_) \
        * lp["gate_norm"].astype(dt_)
    return _ll._residual(x, y @ _ll._dq(lp["out_proj"], dt_), cfg)


feed_forward = _moe.feed_forward
finish_loss = _moe.finish_loss
forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn
