"""A block of TWO first halves, as Falcon-H1 has it (transformers
``modeling_falcon_h1.py``, ``model_type`` ``falcon_h1``): a Mamba-2 mixer
and grouped-query attention read ONE norm side by side, each under its muP
multipliers, ahead of a serial dense SwiGLU. Every layer is alike: no
expert, no alternation of mixer layers and attention layers.

This module is only what differs from ``models/llama.py`` and
``models/hybrid.py``, and its record is built FROM llama's
(``llama.FAMILY.replace``): the config (the mixer's sizes, the fourteen
multipliers), the parameter tree, the SwiGLU with its two multipliers
(``feed_forward``) and what the block says of itself. The block's form is
``llama._layer``'s third (``halves`` says "both": serial, parallel, two
first halves); the attention half is ``llama._attention_half`` given its
normed input; the mixer is ``hybrid.mixer_half`` given the same, with a
multiplier a segment of its in-projection's product; the recurrence is
``ops/ssd.py`` ("xla", or "pallas": heads of a lane tile with steps, in
groups, the "tile" layout); embedding, the loop over the layers, remat, the
head and the cross-entropy are llama's.

The equations, each a line of the class named above (x [B, S, D]; every
norm RMS at ``norm_eps`` with a learned scale; the constants are the
config's, under their published names):

    n = norm_in(x)                                  FalconH1DecoderLayer
    x = x + attention_out_multiplier Attn(attention_in_multiplier n)
          + ssm_out_multiplier Mixer(n)
    x = x + MLP(norm_ff(x))

    Attn(h):  q = h Wq [n_heads of head_width]      FalconH1Attention
              k = (h Wk) key_multiplier, v = h Wv   [n_kv_heads]
              rotary (rotate_half pairing, ``rope_theta``, every lane) on q
              and k; causal softmax at head_width^-1/2 (the program gives
              the kernel ONE scale, key_multiplier head_width^-1/2: the
              rotary is linear, a constant folded into a constant); (.) Wo.
              No bias, no norm of q or k, no gate, no window.
    Mixer(n): [z | xBC | dt] = ((ssm_in_multiplier n) in_proj) mup
                                                    FalconH1Mixer
              mup = ssm_multipliers' five on the columns of z (H P), x
              (H P), B (G N), C (G N), dt (H)       compute_mup_vector
              (the program multiplies the product by ssm_in_multiplier mup
              in one pass: constants folded into a constant)
              xBC = silu(conv(xBC) + b)             depthwise, causal,
                                                    ``mamba_conv`` taps
              x_h [H, P], B_g [G, N], C_g [G, N] = split(xBC)
              dt = softplus(dt + dt_bias); A = -exp(a_log)   float32, a
                                                    head; no limit on dt
              s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T
              y_t = s_t C_t + D x_t                 head h with group
                                                    h // (H / G)'s B and C
              y = rms(y silu(z)) gate_norm          over EACH group's
                                                    lanes; the gate BEFORE
                                                    the norm
                                                    (FalconH1RMSNormGated,
                                                    norm_before_gate false)
              y out_proj                            no bias on either
    MLP(h):   (up(h) silu(gate(h) mlp_multipliers[0])) down
              mlp_multipliers[1]                    FalconH1MLP
    model:    x0 = embed(ids) embedding_multiplier
              logits = (norm_f(x_L) lm_head) lm_head_multiplier; the head
              untied; next-token cross-entropy, no auxiliary loss.

Every multiplier is a multiplier of the FORWARD: none is folded into a
trained leaf (that would change the gradient the optimizer sees).
``mamba_d_ssm`` (H P) is NOT ``mamba_expand`` x hidden. ``params["layers"]``
is a list of stacks (``layer_runs``: one run of every layer, or with
``run_layers`` 1 a stack a layer, whose gradients meet the optimizer as the
backward makes them). The kernel paths run on one device. A cache is
refused (``cached._refuse_stated``): two kinds of state side by side in one
layer.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import hybrid as _hy
from ray_tpu.models import llama as _ll

KIND = "both"           # every layer's kind: what ``halves`` says of it
MIX_OFFERED = _hy.MIX_OFFERED
# a block offers the layer checkpoint q, k and v (remat.ATTN_OFFERED), then
# its SwiGLU's gate and up, then the mixer's in-projection: the dearest
# replay a byte first (28, 23 and 15 ms a GB: ``remat._offers``)
REMAT_OFFERED = _ll.FFN_OFFERED + (MIX_OFFERED,)
# the constants of the forward, under their published names
MULTIPLIERS = ("embedding_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "key_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
               "mlp_multipliers", "lm_head_multiplier")


@dataclass(frozen=True)
class FalconConfig(_ll.LlamaConfig):
    """``n_heads`` over ``n_kv_heads`` heads of ``head_width`` in the
    attention half; ``mamba_heads`` of ``mamba_head_dim`` with a state of
    ``mamba_state`` in ``mamba_groups`` groups in the mixer; ``d_ff`` the
    SwiGLU's width. ``attn_scale`` is derived (key_multiplier head_width
    ^-1/2) and not given."""
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    mamba_state: int = 32
    mamba_groups: int = 2
    mamba_conv: int = 4
    mamba_chunk: int = 128
    ssd_impl: str = "xla"               # "xla" | "pallas"
    run_layers: int = 0                 # the most layers a stack holds; 0: all
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5     # z, x, B, C, dt
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)   # gate, down
    lm_head_multiplier: float = 1.0

    def __post_init__(self):
        # the one scale the attention kernel takes
        object.__setattr__(self, "attn_scale",
                           self.key_multiplier * self.head_dim ** -0.5)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    def replace(self, **kw) -> "FalconConfig":
        return dataclasses.replace(self, **kw)


PRESETS: Dict[str, FalconConfig] = {
    # the CPU tests' size, with the ratios that matter: 5 query heads a KV
    # head, 4 mixer heads of 16 in two groups, a state of twice the head,
    # every multiplier another number than 1
    "tiny": FalconConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=5, n_kv_heads=1,
        head_width=16, d_ff=96, max_seq_len=128, mamba_heads=4,
        mamba_head_dim=16, mamba_state=32, mamba_groups=2, mamba_chunk=8,
        embedding_multiplier=5.656854249492381, attention_in_multiplier=2.0,
        attention_out_multiplier=0.3, key_multiplier=0.25,
        ssm_in_multiplier=0.5, ssm_out_multiplier=0.7,
        ssm_multipliers=(0.7, 0.5, 0.35, 1.4, 0.6),
        mlp_multipliers=(0.6, 0.4), lm_head_multiplier=0.125),
}


def layer_runs(cfg: FalconConfig) -> List[Tuple[str, int]]:
    """[(kind, layers), ...]: every layer is of the one kind, ``run_layers``
    of them a stack at most (0: one stack)."""
    most = cfg.run_layers or cfg.n_layers
    return [(KIND, min(most, cfg.n_layers - at))
            for at in range(0, cfg.n_layers, most)]


def halves(cfg: FalconConfig, kind) -> Tuple[str, bool]:
    """A block runs BOTH first halves from one norm, then the SwiGLU."""
    return "both", True


# --- the parameter tree ------------------------------------------------------

_MIXER = tuple(k for k in _hy._MIXER_LEAVES["mamba"] if k != "mix_norm")


def param_specs(cfg: FalconConfig) -> Dict[str, Any]:
    dense = _ll.param_specs(cfg)
    mixer = _hy._mixer_specs()
    stack = {**dense["layers"], **{k: mixer[k] for k in _MIXER}}
    return {**dense, "layers": [stack for _ in layer_runs(cfg)]}


def init_params(key, cfg: FalconConfig) -> Dict[str, Any]:
    """Norms 1, matrices normal over the square root of their fan-in, the
    embedding 0.02; the mixer's own as Mamba-2 sets them (``dt_bias`` the
    inverse softplus of a step log-uniform in [0.001, 0.1], ``d_skip`` 1)
    but ``a_log`` = log(1 .. H), as FalconH1Mixer sets it."""
    def stack(i, n):
        k = jax.random.fold_in(key, 100 + i)
        run = cfg.replace(n_layers=n, vocab_size=1)
        mixer = _hy._mamba_params(k, run, n)
        a_log = jnp.log(jnp.arange(1, cfg.mamba_heads + 1, dtype=jnp.float32))
        mixer["a_log"] = jnp.broadcast_to(a_log, (n, cfg.mamba_heads)).astype(
            cfg.param_dtype)
        return {**_ll.init_params(k, run)["layers"],
                **{name: mixer[name] for name in _MIXER}}

    dense = _ll.init_params(key, cfg.replace(n_layers=1))
    return {**dense, "layers": [stack(i, n) for i, (_, n) in enumerate(
        layer_runs(cfg))]}


def num_params(cfg: FalconConfig) -> int:
    D, H = cfg.d_model, cfg.mamba_heads
    inner, conv_dim, proj = _hy._mamba_sizes(cfg)
    mixer = (D * proj + (cfg.mamba_conv + 1) * conv_dim + 3 * H + inner
             + inner * D)
    return _ll.num_params(cfg) + cfg.n_layers * mixer


# --- the feed-forward and what the block says --------------------------------


def feed_forward(h, lp, cfg: FalconConfig, mesh=None, rules=None, tp=None,
                 kind=None):
    """The dense SwiGLU with its two multipliers: normed h [B, S, D] ->
    ((up(h) silu(gate(h) g_m)) down d_m, None)."""
    assert tp is None, kind
    dt = cfg.dtype
    g_m, d_m = cfg.mlp_multipliers
    # kept across the layer checkpoint where the step's memory has room
    gate, up = (checkpoint_name(h @ _ll._dq(lp[w], dt), name)
                for w, name in zip(("w_gate", "w_up"), _ll.FFN_OFFERED))
    y = (up * jax.nn.silu(gate * g_m)) @ _ll._dq(lp["w_down"], dt)
    return y * d_m, None


def remat_offers(cfg: FalconConfig, kind, rows: int):
    """((name, bytes a layer), ...): the SwiGLU's gate and up, then the
    mixer's in-projection [rows, z | xBC | dt]."""
    item = jnp.dtype(cfg.dtype).itemsize
    return _ll.remat_offers(cfg, kind, rows) + (
        (MIX_OFFERED, rows * _hy._mamba_sizes(cfg)[2] * item),)


def layer_plan_says(cfg: FalconConfig, runs, plan) -> dict:
    """What ``hybrid.layer_plan`` says of a block of two first halves: that
    it runs both, the multipliers by name, and what the layer checkpoint
    kept beyond the parent's list, run by run."""
    kept = [plan.of(at) if plan is not None else () for at in range(len(runs))]
    return {"first_halves": "attention+mixer", "feed_forward": "serial",
            **{name: str(getattr(cfg, name)) for name in MULTIPLIERS},
            "kept": ",".join("+".join(k) or "-" for k in kept)}


forward = _ll.forward
forward_with_stats = _ll.forward_with_stats
loss_fn = _ll.loss_fn

# what the family supplies to the shared layer: llama's record with a mixer
# BESIDE the attention half and a SwiGLU of its own
FAMILY = _ll.FAMILY.replace(
    "falcon", feed_forward=feed_forward, remat_offered=REMAT_OFFERED,
    remat_offers=remat_offers, layer_runs=layer_runs,
    mixer_half=_hy.mixer_half,
    mixer_backward_bytes=_hy.mixer_backward_bytes, halves=halves,
    layer_plan_says=layer_plan_says)
