"""Llama-family transformer (RMSNorm + RoPE + GQA + SwiGLU), TPU-first.

The flagship model for the Train/Serve benchmarks (BASELINE.md configs 2-4:
Llama-2 7B on v5e-8, Llama-3 70B on v5p-64, continuous-batched 7B serving).
Reference analog: the reference has no in-tree LLM — its release tests defer
to Alpa/OPT (release/alpa_tests/train_opt_2_7b_minimum.py); here the model is
first-class so parallelism presets and Pallas kernels apply directly.

Design notes (TPU):
- layers are stacked and iterated with lax.scan => one compiled layer body,
  O(1) compile time in depth; the stacked 'layers' dim is also what pipeline
  parallelism shards (parallel/pipeline.py).
- all matmuls run in bfloat16 with float32 params (casted in), biasless.
- attention dispatch: "xla" (fused by Mosaic/XLA), "flash" (our Pallas
  kernel, ops/flash_attention.py), "ring" (sequence-parallel ring attention,
  ops/ring_attention.py) — chosen by RuntimeFlags, not model code.
- every op says which part of the model issued it: the forward opens
  ``jax.named_scope``s (``embed``, ``layers``, a layer's ``attention`` or
  ``mixer`` and ``feed_forward``, ``head_loss``; PERF.md 3 has the list).
  They are HLO metadata and cost nothing at run time; a chip trace carries
  them, benchmark/op_scopes.py reads them back.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import remat
from ray_tpu.models.family import (Family, _family, _halves,
                                   _takes_attention_half)
from ray_tpu.parallel.collective_matmul import (allgather_matmul,
                                                gather_apply_scatter,
                                                matmul_reduce_scatter,
                                                overlap_plan)
from ray_tpu.util import tracing


@dataclass(frozen=True)
class Yarn:
    """YaRN's stretch of the rotary frequencies (arXiv:2309.00071, as
    transformers' ``_compute_yarn_parameters`` has it): lanes that turn
    more than ``beta_fast`` times over the ``original`` context keep their
    frequency, lanes that turn less than ``beta_slow`` times have it
    divided by ``factor``, a linear ramp between; cos and sin are
    multiplied by ``attention_factor`` (None: 0.1 ln(factor) + 1)."""
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclass(frozen=True)
class AttentionKind:
    """What the attention layers of one kind have of their own."""
    window: Optional[int] = None        # None: every earlier key
    rope_theta: Optional[float] = None  # None: the config's
    yarn: Optional[Yarn] = None         # None: plain tables
    # False: layers of this kind turn no tables (Command A+'s full layers:
    # q and k go to the kernel as projected) though the model has rotary
    rope: bool = True
    # which lanes of a head one angle turns: "halves" (i, i + head_dim / 2)
    # (transformers' rotate_half) or "neighbours" (2i, 2i + 1) (GPT-J's):
    # the signed swap ``apply_rope`` multiplies by and the lanes the tables
    # carry a pair's angle on (``_pair_swap``, ``_on_lanes``)
    pairs: str = "halves"


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32   # master weights
    attn_impl: str = "xla"           # "xla" | "flash" | "ring"
    # Mistral-style sliding-window attention: each query sees only the
    # last `sliding_window` keys (None = full causal). Flash skips
    # blocks outside the band (O(S*W) compute); xla and decode_step
    # apply the band mask (the decode cache stays max_seq-sized; only
    # the attention is banded). Unsupported with ring/ulysses.
    sliding_window: Any = None
    # jax.checkpoint each layer: the backward keeps the layer's input and
    # rebuilds the rest (HBM savings), but for what remat._checkpoint names.
    remat: bool = True
    # Emit [B, S, vocab] logits in f32 (safe default) or keep them in the
    # compute dtype. With the logsumexp-form CE below, bf16 logits with
    # f32-accumulated reductions (XLA fuses the upcast into the reduce)
    # halve the largest activation's HBM traffic in both directions.
    f32_logits: bool = True
    # Pipeline-parallel schedule for forward_pp: "gpipe" (autodiff through
    # the forward scan) or "1f1b" (explicitly-scheduled backward with an
    # O(M)-activation stash; parallel/pipeline.py).
    pp_schedule: str = "gpipe"
    # What a family may state of its own (models/hybrid.py does); None is
    # llama's: rows of the embedding as they are, a half-block added to x
    # as it is, logits undivided, scores times head_dim ** -0.5. Read by
    # forward_with_stats and the layer under it; the cached and paged
    # forwards (models/cached.py) refuse such a config (init_cache).
    embedding_multiplier: Any = None
    residual_multiplier: Any = None
    logits_scaling: Any = None
    attn_scale: Any = None
    rope: bool = True                # rotary position embedding
    # a head's width where the model states one (Mellum2: 32 heads of 128
    # over a hidden size of 2304); None: d_model // n_heads
    head_width: Any = None
    # attention layers of several kinds in one model, by name: ((name,
    # AttentionKind), ...). A family that lists its layers' kinds
    # (models/moe.py ``layer_kinds``) gives each layer its kind's window
    # and rotary tables; with none named every layer is of the one kind
    # ``sliding_window`` and ``rope_theta`` describe.
    attn_kinds: Tuple[Tuple[str, "AttentionKind"], ...] = ()
    # "rms", or "layer": the mean subtracted first, a scale and no bias
    norm: str = "rms"
    # one norm a layer feeds both halves and the layer is x + attention(n)
    # + feed_forward(n) (Cohere's use_parallel_block; no ``ffn_norm``)
    parallel_block: bool = False

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def rope_dim(self) -> int:
        """How many of a head's dims the rotary tables turn: all of them,
        but where a family states fewer (models/latent.py)."""
        return self.head_dim

    def replace(self, **kw) -> "LlamaConfig":
        return dataclasses.replace(self, **kw)


# Size presets (BASELINE.md target configs).
PRESETS: Dict[str, LlamaConfig] = {
    "tiny": LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=128, max_seq_len=128),
    "debug-125m": LlamaConfig(vocab_size=32000, d_model=768, n_layers=12,
                              n_heads=12, n_kv_heads=12, d_ff=2048,
                              max_seq_len=1024),
    "1b": LlamaConfig(vocab_size=32000, d_model=2048, n_layers=16,
                      n_heads=16, n_kv_heads=8, d_ff=5632, max_seq_len=2048),
    # OPT-2.7B-class (the reference's LLM scale proof model,
    # release/alpa_tests/train_opt_2_7b_minimum.py), llama-style shapes
    # with head_dim 128 for MXU/flash-kernel tiling. Largest preset that
    # trains on ONE 16 GB v5e chip (adafactor; adam state would need 32 GB).
    "2b7": LlamaConfig(vocab_size=32000, d_model=2560, n_layers=32,
                       n_heads=20, n_kv_heads=20, d_ff=6912,
                       max_seq_len=2048),
    "7b": LlamaConfig(),  # llama-2 7B shapes
    "70b": LlamaConfig(d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                       d_ff=28672, vocab_size=32000, max_seq_len=4096),
}


def param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """Logical axis names per parameter (see parallel/sharding.py)."""
    L = ("layers",)
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": L + ("embed_nr",),
            "wq": L + ("embed", "heads"),
            "wk": L + ("embed", "kv_heads"),
            "wv": L + ("embed", "kv_heads"),
            "wo": L + ("heads", "embed"),
            "ffn_norm": L + ("embed_nr",),
            "w_gate": L + ("embed", "mlp"),
            "w_up": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed"),
        },
        "final_norm": ("embed_nr",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(key, cfg: LlamaConfig) -> Dict[str, Any]:
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 16))

    def norm(shape):
        return jnp.ones(shape, pd)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, pd) * (fan_in ** -0.5))

    L, D, H, KV, HD, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim, cfg.d_ff)
    return {
        "embed": jax.random.normal(next(k), (cfg.vocab_size, D), pd) * 0.02,
        "layers": {
            "attn_norm": norm((L, D)),
            "wq": dense(next(k), (L, D, H * HD), D),
            "wk": dense(next(k), (L, D, KV * HD), D),
            "wv": dense(next(k), (L, D, KV * HD), D),
            "wo": dense(next(k), (L, H * HD, D), H * HD),
            "ffn_norm": norm((L, D)),
            "w_gate": dense(next(k), (L, D, F), D),
            "w_up": dense(next(k), (L, D, F), D),
            "w_down": dense(next(k), (L, F, D), F),
        },
        "final_norm": norm((D,)),
        "lm_head": dense(next(k), (D, cfg.vocab_size), D),
    }


def num_params(cfg: LlamaConfig) -> int:
    D, H, KV, HD, F, L, V = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, cfg.d_ff, cfg.n_layers,
                             cfg.vocab_size)
    per_layer = 2 * D + D * H * HD + 2 * D * KV * HD + H * HD * D + 3 * D * F
    return V * D + L * per_layer + D + D * V


# --- building blocks --------------------------------------------------------


def quantize_params_int8(params) -> Dict[str, Any]:
    """Weight-only per-channel int8 quantization for SERVING (inference;
    int8 is non-differentiable — training paths reject it implicitly).
    Matmul weights (embed, lm_head, per-layer projections) become
    {"q8": int8, "s8": per-output-channel bf16 scale}; norms stay float.
    Forward paths dequantize ONE layer at a time inside the scan
    (_dq at each use — XLA fuses the convert into the consuming dot, no
    full-layer bf16 round-trip), so HBM at rest holds int8 — llama-7B weights drop
    13.5 GB -> ~6.8 GB, fitting a 16 GB v5e chip with a KV page pool
    (ref: BASELINE.md target 4; the reference's serve scale proofs use
    multi-GPU sharding instead, release/alpa_tests/inference_opt_30b.py)."""
    import jax

    def quant(w, keep_first: bool):
        if isinstance(w, dict) and "q8" in w:
            return w    # idempotent: already-quantized leaves pass through
        a = jnp.asarray(w)
        if a.ndim < 2 or not jnp.issubdtype(a.dtype, jnp.floating):
            return w
        axes = tuple(range(1 if keep_first else 0, a.ndim - 1))
        f = a.astype(jnp.float32)
        s = jnp.max(jnp.abs(f), axis=axes, keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-8)
        q = jnp.clip(jnp.round(f / s), -127, 127).astype(jnp.int8)
        return {"q8": q, "s8": s.astype(jnp.bfloat16)}

    out = {}
    for k, v in params.items():
        if k == "layers":
            out[k] = {kk: (vv if kk.endswith("norm")
                           else quant(vv, keep_first=True))
                      for kk, vv in v.items()}
        elif k in ("embed", "lm_head"):
            out[k] = quant(v, keep_first=False)
        else:
            out[k] = v
    return out


def _dq(w, dt):
    """Dequantize one weight (no-op cast for plain arrays)."""
    if isinstance(w, dict) and "q8" in w:
        return w["q8"].astype(dt) * w["s8"].astype(dt)
    return w.astype(dt)


def _embed(params, tokens, dt):
    """Embedding lookup; for int8 tables gather the rows FIRST and
    dequantize only them — O(tokens x D), never the whole [V, D] table
    (a per-decode-step 262 MB bf16 transient at 7B otherwise)."""
    w = params["embed"]
    if isinstance(w, dict) and "q8" in w:
        return w["q8"][tokens].astype(dt) * w["s8"].astype(dt)
    return w.astype(dt)[tokens]


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def layer_norm(x, scale, eps):
    """A LayerNorm with a scale and no bias, its moments in float32."""
    f = x.astype(jnp.float32)
    f = f - jnp.mean(f, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(f), axis=-1, keepdims=True)
    return (f * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _norm(x, scale, cfg: "LlamaConfig"):
    """The model's norm over the hidden state (``cfg.norm``)."""
    if cfg.norm == "rms":
        return rms_norm(x, scale, cfg.norm_eps)
    if cfg.norm != "layer":
        raise ValueError(f"unknown norm {cfg.norm!r}")
    return layer_norm(x, scale, cfg.norm_eps)


def yarn_range(theta: float, head_dim: int, yarn: Yarn) -> Tuple[int, int]:
    """(low, high): the lanes between which YaRN's ramp runs. dim(r) is
    the lane that turns r times over the original context."""
    def dim(r):
        return head_dim * math.log(yarn.original / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    return (max(math.floor(dim(yarn.beta_fast)), 0),
            min(math.ceil(dim(yarn.beta_slow)), head_dim - 1))


def rope_inv_freq(theta: float, head_dim: int, yarn: Optional[Yarn] = None):
    """The rotary lanes' frequencies, float32 [head_dim / 2]: theta ^
    (-2i / head_dim), under YaRN blended with the same divided by
    ``factor`` along the ramp of ``yarn_range``."""
    turns = theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                      / head_dim)
    if yarn is None:
        return 1.0 / turns
    low, high = yarn_range(theta, head_dim, yarn)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return 1.0 / (yarn.factor * turns) * ramp + 1.0 / turns * (1.0 - ramp)


@functools.partial(jax.jit, static_argnums=(1, 2), inline=True)
def _pair_tables(theta: float, seq_len: int, head_dim: int):
    """cos and sin of the plain rotary, one column a PAIR of lanes: float32
    [seq_len, head_dim / 2] (``_on_lanes`` lays them on a head's lanes)."""
    freqs = rope_inv_freq(theta, head_dim)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)                     # [S, HD/2]
    return jnp.cos(angles), jnp.sin(angles)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), inline=True)
def _yarn_tables(theta: float, seq_len: int, head_dim: int, yarn: Yarn):
    """``_pair_tables`` for a kind of layer under YaRN: the stretched
    frequencies, cos and sin both times the attention factor."""
    freqs = rope_inv_freq(theta, head_dim, yarn)
    angles = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), freqs)
    by = yarn.attention_factor or 0.1 * math.log(yarn.factor) + 1.0
    return jnp.cos(angles) * by, jnp.sin(angles) * by


# which lanes of a head one angle turns (``AttentionKind.pairs``)
PAIRINGS = ("halves", "neighbours")


def _on_lanes(tables, pairs: str):
    """cos and sin one column a pair, [S, HD / 2] -> [S, HD] with a pair's
    angle on BOTH its lanes, as ``apply_rope`` takes them: ``halves`` (lanes
    i and i + HD / 2) cos | cos, ``neighbours`` (lanes 2i and 2i + 1) every
    column twice."""
    if pairs not in PAIRINGS:
        raise ValueError(f"unknown rotary pairing {pairs!r}")
    if pairs == "halves":
        return tuple(jnp.concatenate([t, t], axis=-1) for t in tables)
    return tuple(jnp.repeat(t, 2, axis=-1) for t in tables)


def _rope_tables(theta: float, seq_len: int, head_dim: int):
    """cos and sin of a model whose layers are of ONE kind and pair a
    head's halves: float32 [seq_len, head_dim] (``apply_rope``)."""
    return _on_lanes(_pair_tables(theta, seq_len, head_dim), "halves")


def _kind_pair_tables(cfg: "LlamaConfig", of: AttentionKind, seq_len: int):
    """cos and sin of the layers of one kind, one column a pair:
    [seq_len, rope_dim / 2]. What a family's own attention half takes where
    it lays them on its lanes itself (``Family.rotary_tables``:
    models/latent.py, (1 | cos) over a head's nope and rotary lanes)."""
    theta = cfg.rope_theta if of.rope_theta is None else of.rope_theta
    if of.yarn is None:
        return _pair_tables(theta, seq_len, cfg.rope_dim)
    return _yarn_tables(float(theta), seq_len, cfg.rope_dim, of.yarn)


def _kind_tables(cfg: "LlamaConfig", of: AttentionKind, seq_len: int):
    """cos and sin of the layers of one kind as ``apply_rope`` takes them:
    [seq_len, rope_dim] with a pair's angle on both its lanes, the pairs
    the kind's (``_on_lanes``)."""
    return _on_lanes(_kind_pair_tables(cfg, of, seq_len), of.pairs)


def attention_kind(cfg: LlamaConfig, kind=None) -> AttentionKind:
    """The window and the rotary tables of the layers of ``kind``: a kind
    the config names (``attn_kinds``), else the config's one kind."""
    return dict(cfg.attn_kinds).get(
        kind, AttentionKind(window=cfg.sliding_window))


@functools.cache
def _pair_swap(head_dim: int, pairs: str, dtype):
    """P [HD, HD] of 0 and +-1: x P is a pair's OTHER lane, signed as the
    rotary takes it. ``halves``: (x P)[i] = -x[i + HD/2], (x P)[i + HD/2] =
    x[i]; ``neighbours``: (x P)[2i] = -x[2i + 1], (x P)[2i + 1] = x[2i]. A
    constant of the program, made from the pairing and the head's width
    alone; P^T = -P turns back."""
    if pairs not in PAIRINGS:
        raise ValueError(f"unknown rotary pairing {pairs!r}")
    lane = np.arange(head_dim)
    if pairs == "halves":
        other, first = (lane + head_dim // 2) % head_dim, lane < head_dim // 2
    else:
        other, first = lane ^ 1, lane % 2 == 0
    swap = np.zeros((head_dim, head_dim), np.float32)
    swap[other, lane] = np.where(first, -1.0, 1.0)
    return swap.astype(dtype)


def _turn(x, cos, sin, swap):
    """x cos + (x swap) sin in float32, as x's type: ONE product on the
    matrix unit with the two multiplies and the add as its epilogue. The
    product has one non-zero term a lane, so it is exact (``highest``
    keeps a float32 x whole on the chip; bfloat16 passes once either
    way). x is read as an array of its own (``optimization_barrier``): the
    compiler otherwise lets the layout of whoever made x reach the product
    (rows on the lanes where a per-head norm came first: the product with
    its operands swapped, then float32 relayout copies of both terms)."""
    x = jax.lax.optimization_barrier(x)
    other = jnp.einsum("...d,de->...e", x, jnp.asarray(swap),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos[..., None, :]
            + other * sin[..., None, :]).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotary(x, cos, sin, pairs):
    return _turn(x, cos, sin, _pair_swap(x.shape[-1], pairs, x.dtype))


def _rotary_fwd(x, cos, sin, pairs):
    return _rotary(x, cos, sin, pairs), (cos, sin)


def _rotary_bwd(pairs, tables, dy):
    # a rotation's gradient is the rotation back: dy cos + (dy P^T) sin,
    # the same one product (jax's own transposition of ``_turn`` would
    # round dy sin to x's type ahead of a product of mixed types)
    return _turn(dy, *tables, _pair_swap(dy.shape[-1], pairs, dy.dtype).T), \
        None, None


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def apply_rope(x, cos, sin, pairs: str = "halves"):
    """The rotary of x [B, S, N, HD] by cos and sin float32 [S, HD] (already
    offset for decode; [B, S, HD] where every row has positions of its own)
    with a pair's angle on BOTH its lanes (``_on_lanes``; ``pairs`` says
    which lanes those are): x cos + (x P) sin, P the pairing's signed swap
    (``_pair_swap``). The tables are constants of the step: no gradient
    reaches them. Nothing tells halves' tables from neighbours' (both are
    [S, HD]), so every caller in ray_tpu/ says ``pairs``; the default
    stands for the three-argument call of ``benchmark/kinds/
    train_falconh1.py`` alone, which takes ``_rope_tables``' halves."""
    if cos.shape[-1] != x.shape[-1]:
        raise ValueError(
            f"rotary tables of {cos.shape[-1]} lanes for heads of "
            f"{x.shape[-1]}: apply_rope takes a pair's angle on both its "
            "lanes (llama._on_lanes)")
    with jax.named_scope("rotary"):
        return _rotary(x, cos, sin, pairs)


def _attention_xla(q, k, v, causal: bool, q_offset=0, window=None,
                   scale=None):
    """Plain einsum attention; XLA fuses this well on TPU for moderate S.
    q: [B, S, H, D], k/v: [B, T, KV, D] (GQA broadcast). ``scale``
    multiplies the scores where the model states its own (else D ** -0.5)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    groups = H // KV
    q = q.reshape(B, S, KV, groups, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / (D ** 0.5) if scale is None else scores * scale
    if causal:
        qpos = jnp.arange(S)[:, None] + q_offset
        kpos = jnp.arange(T)[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask = mask & (qpos - kpos < window)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, D)


def _flash_sharded(q, k, v, window, mesh, rules, scale=None):
    """Flash attention under a mesh. GSPMD cannot partition a Mosaic
    kernel, so the kernel runs per shard inside a shard_map: batch over
    the rules' batch axes, heads over their heads axes (only when they
    divide both H and KV, so each shard keeps whole GQA groups). The
    map is manual over EVERY mesh axis — Mosaic's lowering refuses a
    partial-manual context — so q/k/v are replicated over the axes the
    spec does not name. With no batch or heads axis of size > 1 — one
    chip — the kernel is called bare and the program is the one-chip
    program."""
    from ray_tpu.ops.flash_attention import flash_attention

    kernel = functools.partial(flash_attention, causal=True, window=window,
                               scale=scale)
    if mesh is None or rules is None:
        return kernel(q, k, v)
    from ray_tpu.parallel.sharding import mesh_axes

    batch, heads = (mesh_axes(a, rules, mesh) for a in ("batch", "heads"))
    n_head_shards = 1
    for a in heads:
        n_head_shards *= int(mesh.shape[a])
    if q.shape[2] % n_head_shards or k.shape[2] % n_head_shards:
        heads = ()
    if not batch + heads:
        return kernel(q, k, v)
    from jax.sharding import PartitionSpec as P

    spec = P(batch or None, None, heads or None, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _attention(q, k, v, cfg: LlamaConfig, causal=True, q_offset=0,
               mesh=None, rules=None, kind=None):
    win = attention_kind(cfg, kind).window
    scale = cfg.attn_scale                      # None: head_dim ** -0.5
    if scale is not None and cfg.attn_impl in ("ring", "ulysses"):
        raise ValueError("a configured softmax scale is not supported with "
                         "ring/ulysses attention")
    if win is not None and cfg.attn_impl in ("ring", "ulysses"):
        # silently computing FULL attention here would train a different
        # model than the config describes
        raise ValueError(
            "sliding_window is not supported with ring/ulysses attention "
            "(the band would have to chase blocks around the ring); use "
            "attn_impl='flash' or 'xla' for windowed models")
    # flash builds positions from 0, so offset chunks (cache prefill
    # continuation) must take the xla path, which honors q_offset
    at_origin = isinstance(q_offset, int) and q_offset == 0
    if cfg.attn_impl == "flash" and causal and q.shape[1] >= 128 \
            and at_origin:
        return _flash_sharded(q, k, v, win, mesh, rules, scale)
    if cfg.attn_impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name="sp")
    if cfg.attn_impl == "ulysses":
        from ray_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, axis_name="sp")
    return _attention_xla(q, k, v, causal, q_offset, window=win, scale=scale)


def _project(h, lp, cfg: LlamaConfig, w: str, n: int, norm=None):
    """One of a block's three projections of its normed input h [B, S, D],
    split into its n heads: [B, S, n, HD]. q and k (``norm``: the name of
    their learned scale) pass the RMS norm the config asks for:
    ``cfg.qk_norm`` (an OLMoE block), one over the WHOLE projected vector
    before the split, a scale of n x HD; ``cfg.qk_head_norm`` (a MiniCPM
    block: models/sala.py), one over each HEAD after it, a scale of HD
    shared by the heads; neither field or both False, none."""
    y = h @ _dq(lp[w], cfg.dtype)
    if norm is not None and getattr(cfg, "qk_norm", False):
        y = rms_norm(y, lp[norm], cfg.norm_eps)
    y = y.reshape(*h.shape[:2], n, cfg.head_dim)
    if norm is not None and getattr(cfg, "qk_head_norm", False):
        y = rms_norm(y, lp[norm], cfg.norm_eps)
    return y


def _attention_half(x, lp, cfg: LlamaConfig, cos, sin, mesh=None, rules=None,
                    tp=None, kind=None, normed=None):
    """The attention half of a block: x [B, S, D] -> x + attention. A
    parallel block hands its one normed input as ``normed``: the half then
    norms nothing and adds nothing, its result is the attention's output
    alone. ``kind`` names the layer's kind where the config has several
    (``attention_kind``: its window; ``cos`` and ``sin`` are its tables).
    q and k are normed before the split where the config says
    (``_project``). Without rotary tables (``cos`` None: a model with no
    position embedding) q and k go to the kernel as they are; a config's
    ``attn_scale`` replaces the softmax's head_dim ** -0.5 and its
    ``residual_multiplier`` scales what is added to x.
    mesh+rules reach the flash kernel's shard_map (_flash_sharded).
    With a plan ``tp`` (_tp_plan) x is sharded over its rows on the tensor
    axis: q/k/v share one gather of them that runs under their matmuls,
    and ``wo`` ends in a reduce-scatter that runs under its own
    (parallel/collective_matmul.py)."""
    B, S, D = x.shape
    H, KV, HD = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype
    pairs = attention_kind(cfg, kind).pairs

    h = _norm(x, lp["attn_norm"], cfg) if normed is None else normed

    if tp is None:
        q = _project(h, lp, cfg, "wq", H, "q_norm")
        k = _project(h, lp, cfg, "wk", KV, "k_norm")
        v = _project(h, lp, cfg, "wv", KV)
        if cos is not None:
            q = apply_rope(q, cos, sin, pairs)
            k = apply_rope(k, cos, sin, pairs)
        # as the attention call takes them: kept across the layer
        # checkpoint where the step's memory has room (``remat.remat_plan``)
        q, k, v = (checkpoint_name(t, name)
                   for t, name in zip((q, k, v), remat.ATTN_OFFERED))
    else:
        def in_heads(i, y, shard, cos, sin):
            # one shard's rows of q (0), k (1) or v (2), as its matmul
            # leaves them: split into heads and turned, then joined
            y = y.reshape(*y.shape[:2], -1, HD)
            if i == 2:
                return y
            at = lambda t: jax.lax.dynamic_slice_in_dim(       # noqa: E731
                t, shard * y.shape[1], y.shape[1])
            return apply_rope(y, at(cos), at(sin), pairs)

        q, k, v = allgather_matmul(
            h, [_dq(lp[w], dt) for w in ("wq", "wk", "wv")], tp,
            then=in_heads, extras=(cos, sin))

    attn = _attention(q, k, v, cfg, causal=True, mesh=mesh, rules=rules,
                      kind=kind)
    attn = attn.reshape(B, S, H * HD)
    if "w_attn_gate" in lp:
        # a sigmoid gate a CHANNEL of the attention's output, before ``wo``
        # (models/solar.py: the leaf says so, no config field)
        assert tp is None, kind
        with jax.named_scope("gate"):
            attn = attn * jax.nn.sigmoid(
                (h @ _dq(lp["w_attn_gate"], dt)).astype(jnp.float32)
            ).astype(dt)
    if tp is not None:
        out = matmul_reduce_scatter(attn, _dq(lp["wo"], dt), tp)
    else:
        out = attn @ _dq(lp["wo"], dt)
    return out if normed is not None else _residual(x, out, cfg)


def _residual(x, y, cfg: LlamaConfig):
    """x + y, y scaled where the config has a ``residual_multiplier``."""
    by = cfg.residual_multiplier
    return x + y if by is None else x + (y * by).astype(x.dtype)


# checkpoint_name tags of what the dense feed-forward offers the layer
# checkpoint where the step's memory has room (``remat.remat_plan``; it wants
# none kept otherwise): the SwiGLU's two products of x, before the
# activation, as ``moe.SHARED_OFFERED`` for a shared expert
FFN_OFFERED = ("ffn_gate", "ffn_up")


def remat_saved_bytes(cfg: "LlamaConfig", kind, rows: int) -> int:
    return 0


def remat_offers(cfg: "LlamaConfig", kind, rows: int):
    """((name, bytes a layer), ...): gate's and up's products of ``rows``
    tokens, [rows, d_ff] each."""
    each = rows * cfg.d_ff * jnp.dtype(cfg.dtype).itemsize
    return tuple((name, each) for name in FFN_OFFERED)


def feed_forward(h, lp, cfg: LlamaConfig, mesh=None, rules=None, tp=None,
                 kind=None):
    """The dense feed-forward half of a block, a SwiGLU: normed h
    [B, S, D] -> (its output [B, S, D], None). The second value is what a
    family's feed-forward reports of itself layer by layer (an expert
    layer's routing statistics, models/moe.py); the dense one has nothing
    to report. With a plan ``tp`` every shard's rows pass from the gather
    through the SwiGLU to the reduce-scatter on their own. ``kind`` is the
    layer's, for a family whose feed-forward differs by it."""
    dt = cfg.dtype
    if tp is not None:
        return gather_apply_scatter(
            h, [_dq(lp["w_gate"], dt), _dq(lp["w_up"], dt)],
            lambda gate, up: jax.nn.silu(gate) * up,
            _dq(lp["w_down"], dt), tp), None
    # kept across the layer checkpoint where the step's memory has room
    gate, up = (checkpoint_name(h @ _dq(lp[w], dt), name) for w, name in zip(
        ("w_gate", "w_up"), FFN_OFFERED))
    return (jax.nn.silu(gate) * up) @ _dq(lp["w_down"], dt), None


def _layer(x, lp, cfg: LlamaConfig, cos, sin, mesh=None, rules=None, tp=None,
           kind=None, carried=None):
    """One transformer block: the attention half, then the family's
    feed-forward half (dense SwiGLU here, the expert layer for a
    MoEConfig). x: [B, S, D]. Returns (x, stats, carried): stats is what
    the halves report (None where neither does: the dense one), ``carried``
    what a family's attention half hands on to the next layer (``Family``;
    None in, None out). ``kind``: None or
    "attention" for the attention half, or a kind of attention layer the
    config names (``attn_kinds``: the same half with that kind's window,
    ``cos`` and ``sin`` its tables, under a scope of the kind's name); any
    other kind of layer takes its first half from the family's
    ``mixer_half`` (x, lp, cfg, kind -> x, or (x, what it reports: joined
    to the layer's statistics)). A family with an
    ``attention_half`` of its own (x, lp, cfg, cos, sin -> x) supplies
    every layer's: (x, lp, cfg, cos, sin, carried, kind -> x, carried,
    what it reports). ``kind`` goes on to the feed-forward. A config with
    ``parallel_block`` runs the same two halves side by side from one norm
    (``_parallel_layer``). A family whose blocks run TWO first halves, the
    attention half and its mixer side by side from one norm, ahead of the
    serial feed-forward, says "both" (``_two_first_halves``). A family
    whose blocks hold ONE half says which
    (``halves``: cfg, kind -> (a first half, the feed-forward)); a block
    without the feed-forward reports nothing."""
    if cfg.parallel_block:
        return _parallel_layer(x, lp, cfg, cos, sin, mesh, rules, tp,
                               kind) + (carried,)
    family = _family(cfg)
    own = family.attention_half
    named = kind in dict(cfg.attn_kinds)
    said = None
    # ``first`` None: a block that is its feed-forward alone
    first, second = _halves(cfg, kind)
    if first == "attention" and own is not None:
        assert tp is None, kind
        with jax.named_scope("attention"):
            x, carried, said = own(x, lp, cfg, cos, sin, mesh=mesh,
                                   rules=rules, carried=carried, kind=kind)
    elif first == "attention":
        # a trace tells the kinds apart by the inner scope, with no shape
        with jax.named_scope("attention"), \
                jax.named_scope(kind) if named else contextlib.nullcontext():
            x = _attention_half(x, lp, cfg, cos, sin, mesh=mesh, rules=rules,
                                tp=tp, kind=kind)
    elif first == "mixer":
        with jax.named_scope("mixer"):
            x = family.mixer_half(x, lp, cfg, kind, mesh=mesh)
            if isinstance(x, tuple):    # a mixer that reports of itself
                x, said = x
    elif first == "both":
        assert tp is None, kind
        x = _two_first_halves(x, lp, cfg, cos, sin, mesh, rules, kind)
    stats = None
    if second:
        with jax.named_scope("feed_forward"):
            h = _norm(x, lp["ffn_norm"], cfg)
            y, stats = family.feed_forward(
                h, lp, cfg, mesh=mesh, rules=rules, tp=tp, kind=kind)
            x = _residual(x, y, cfg)
    if said is not None:
        stats = {**(stats or {}), **said}
    return x, stats, carried


def _two_first_halves(x, lp, cfg: LlamaConfig, cos, sin, mesh, rules, kind):
    """The first half of a block that has TWO (a family whose ``halves``
    says "both"; models/falcon.py): ONE norm (``attn_norm``) feeds the
    attention half and the family's mixer side by side, neither reads the
    other's result, and x + attention_out_multiplier attention(
    attention_in_multiplier n) + ssm_out_multiplier mixer(n) goes on to the
    serial feed-forward. The norm, the multipliers and the sum are the
    block's own (scope ``block``); the halves are the serial block's code
    under its scopes, each given its normed input."""
    dt = x.dtype
    with jax.named_scope("block"):
        n = _norm(x, lp["attn_norm"], cfg)
        h = n if cfg.attention_in_multiplier == 1 \
            else (n * cfg.attention_in_multiplier).astype(dt)
    with jax.named_scope("attention"):
        a = _attention_half(x, lp, cfg, cos, sin, mesh=mesh, rules=rules,
                            kind=kind, normed=h)
    with jax.named_scope("mixer"):
        m = _family(cfg).mixer_half(x, lp, cfg, kind, mesh=mesh, normed=n)
    with jax.named_scope("block"):
        return x + (a * cfg.attention_out_multiplier).astype(dt) \
            + (m * cfg.ssm_out_multiplier).astype(dt)


def _parallel_layer(x, lp, cfg: LlamaConfig, cos, sin, mesh, rules, tp, kind):
    """``_layer`` for a parallel block (``cfg.parallel_block``): ONE norm
    (``attn_norm``) feeds the attention half and the family's feed-forward
    side by side, neither reads the other's result, and the layer is x +
    attention(n) + feed_forward(n). The norm and the sum are the block's
    own (scope ``block``: in a trace under ``layers`` and in neither half);
    the two halves are the serial block's code under its scopes."""
    if not _takes_attention_half(cfg, kind):
        raise NotImplementedError(
            f"a parallel block whose first half is no attention ({kind!r}): "
            "parallel_block runs attention beside the FEED-FORWARD; for a "
            "mixer BESIDE attention ahead of a serial feed-forward the "
            "family's halves says 'both' (llama._two_first_halves, "
            "models/falcon.py)")
    with jax.named_scope("block"):
        n = _norm(x, lp["attn_norm"], cfg)
    with jax.named_scope("attention"), jax.named_scope(kind) \
            if kind in dict(cfg.attn_kinds) else contextlib.nullcontext():
        a = _attention_half(x, lp, cfg, cos, sin, mesh=mesh, rules=rules,
                            tp=tp, kind=kind, normed=n)
    with jax.named_scope("feed_forward"):
        y, stats = _family(cfg).feed_forward(n, lp, cfg, mesh=mesh,
                                             rules=rules, tp=tp, kind=kind)
    with jax.named_scope("block"):
        x = _residual(_residual(x, a, cfg), y, cfg)
    return x, stats


def _tp_plan(cfg: LlamaConfig, mesh, rules, batch: int, seq: int):
    """How this forward's tensor-parallel matmuls communicate, read from
    what it is given: an ``OverlapPlan`` (parallel/collective_matmul.py:
    the residual stream sharded over the sequence on the tensor axis,
    half-row permutes under the matmuls; the weights entering as stored,
    their gradients reduce-scattered over the batch axis under ``embed``
    by permutes under the gradient products) where the rules put ``heads``
    and ``mlp`` on one mesh axis of n > 1 shards dividing the sequence,
    the heads, the KV heads and the feed-forward's width; else None: the
    plain program, as for a family with a feed-forward of its own."""
    if _family(cfg).feed_forward is not feed_forward:
        return None
    return overlap_plan(mesh, rules, batch, seq,
                        (cfg.n_heads, cfg.n_kv_heads, cfg.d_ff))


def _say_tp_plan(tp, cfg: LlamaConfig, batch: int, seq: int):
    """``tp.overlap_plan``, once a traced forward given a mesh and rules."""
    rows, grads = seq // tp.shards if tp else seq, tp.grad_sites if tp else ()
    tracing.plan("tp.overlap_plan", {
        "path": "overlap" if tp else "plain",
        "shards": tp.shards if tp else 1,
        # a layer's gathers (q/k/v; gate/up) and scatters (wo; w_down)
        "sites": len(tp.sites) if tp else 0, "rows_per_step": rows,
        "bytes_per_permute": batch // tp.batch_shards * rows * cfg.d_model
        * jnp.dtype(cfg.dtype).itemsize if tp else 0,
        # the weights whose gradient the helpers reduce-scatter themselves
        "grad_shards": tp.grad_shards if grads else 1, "grad_sites": len(
            grads), "grad_bytes_per_permute": max(grads, default=0)})


def _say_layer_plan(runs, bodies: int, more: Optional[dict] = None):
    """The instant ``hybrid.layer_plan`` of a trace, once a traced forward
    of a model whose layers are a list of runs: how many kinds of layer,
    how many runs of adjacent layers of one kind (one scan each), how
    many bodies were built for them (one a kind and set of names its runs
    keep across the layer checkpoint) and the runs themselves,
    "kind xN, ..." in the layers' order; and what the family says
    ``more`` of its layers (``layer_plan_says``)."""
    tracing.plan("hybrid.layer_plan", {
        "kinds": len({k for k, _ in runs}), "runs": len(runs),
        "bodies": bodies, "layers": sum(n for _, n in runs),
        "pattern": ", ".join(f"{k} x{n}" for k, n in runs), **(more or {})})


def _say_kind_plan(cfg: LlamaConfig, kind, of: AttentionKind, seq: int):
    """The instant ``attn.kind_plan`` of a trace, once a kind of attention
    layer and traced forward: the kind's window (0: none) and rotary
    tables, and the heads they serve."""
    tracing.plan("attn.kind_plan", {
        "kind": kind or "attention", "window": of.window or 0,
        "rope": ("none" if not (cfg.rope and of.rope) else
                 "yarn" if of.yarn is not None else
                 "gptj" if of.pairs == "neighbours" else "default"),
        "groups": cfg.n_heads // cfg.n_kv_heads,
        "factor": of.yarn.factor if of.yarn is not None else 1.0,
        "S": seq, "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim})


def _say_block_plan(cfg: LlamaConfig):
    """The instant ``block.plan`` of a trace, once a traced forward of a
    model with a parallel block: how the layer is put together, and what
    its feed-forward's shared experts are (0: a family without)."""
    tracing.plan("block.plan", {
        "residual": "parallel", "norm": cfg.norm,
        "shared_experts": getattr(cfg, "n_shared", 0)
        if getattr(cfg, "shared_d_ff", 0) else 0,
        "shared_combine": getattr(cfg, "shared_combine", "sum"),
        "shared_width": getattr(cfg, "shared_d_ff", 0)})


def _act_constraint(mesh, rules, tp=None):
    """Activation sharding constraint [batch, seq, embed] for the dense
    forward. Without it GSPMD is free to re-replicate intermediates — at
    7B the rematted attention backward materialized the FULL-batch
    [B, H, S, S] f32 scores on every device (8 GB/chip at B=16 S=2048),
    blowing v5e HBM; constraining the per-layer activation pins the
    batch axis down and the whole backward stays batch-sharded. With a
    plan ``tp`` the sequence lies over the tensor axis between blocks."""
    if mesh is None or rules is None:
        return lambda x: x
    from ray_tpu.parallel.sharding import named_sharding

    sh = named_sharding(mesh, ("batch", "seq", None), rules) \
        if tp is None else tp.rows_sharding()
    return lambda x: jax.lax.with_sharding_constraint(x, sh)


def _join_stats(runs: list):
    """The runs' stacked statistics joined in the layers' order. Runs that
    report the same things are concatenated leaf by leaf; where they
    differ (a run of layers that report a term another run's lack), each
    name over the runs that report it."""
    if all(jax.tree.structure(s) == jax.tree.structure(runs[0])
           for s in runs):
        return jax.tree.map(lambda *s: jnp.concatenate(s), *runs)
    return {name: jnp.concatenate([s[name] for s in runs if name in s])
            for name in sorted(set().union(*runs))}


def forward(params, tokens, cfg: LlamaConfig, pos_offset=0, mesh=None,
            rules=None):
    """Teacher-forced logits. tokens: [B, S] int32 -> [B, S, vocab] f32.
    pos_offset shifts RoPE positions (sequence-parallel shards pass their
    global chunk offset). mesh+rules (optional) pin per-layer activation
    shardings (see _act_constraint)."""
    return forward_with_stats(params, tokens, cfg, pos_offset, mesh, rules)[0]


def forward_with_stats(params, tokens, cfg: LlamaConfig, pos_offset=0,
                       mesh=None, rules=None):
    """``forward`` and what every layer's feed-forward reported, stacked
    over layers (None for the dense model): (logits, stats)."""
    return _forward(params, tokens, cfg, pos_offset, mesh, rules)[:2]


def _logits(params, x, cfg: LlamaConfig):
    """The head over normed x [B, S, D]: the ``lm_head`` or, tied, the
    embedding; divided (``logits_scaling``) or multiplied
    (``lm_head_multiplier``) where the config says; float32 or as
    computed."""
    dt = cfg.dtype
    if "lm_head" in params:
        logits = x @ _dq(params["lm_head"], dt)
    else:                                   # tied to the embedding
        logits = jnp.einsum("bsd,vd->bsv", x, _dq(params["embed"], dt))
    if cfg.logits_scaling is not None:
        logits = logits / cfg.logits_scaling
    by = getattr(cfg, "lm_head_multiplier", None)   # models/falcon.py
    if by is not None:
        logits = logits * by
    return logits.astype(jnp.float32) if cfg.f32_logits else logits


def _forward(params, tokens, cfg: LlamaConfig, pos_offset=0, mesh=None,
             rules=None):
    """``forward_with_stats`` and what a further pass over the same
    sequences needs (``loss_fn``, a family's ``further_losses``): (logits,
    stats, the residual stream BEFORE the final norm, ``run``: (kind, x,
    stack, which of the family's ``further_stacks`` it is: 0) -> (x,
    stats), the scan of a stack of layers of one kind by the body, the
    tables and the shardings this forward used, the plan).

    What a family's attention half hands on to the layers after it
    (``Family``: ``carried_init``, ``hands_on``) travels beside x through
    the runs and across the layer checkpoint, by one mechanism for any
    family; a family that hands nothing on carries x alone.

    ``params["layers"]`` is one stack of identical layers, scanned by one
    body, or, for a family with layers of several kinds (``layer_runs``:
    models/hybrid.py), a list of stacks, one a run of adjacent layers of
    one kind: each run is scanned by its kind's body, traced from ONE
    function a kind and set of names its runs keep (``remat.remat_plan``)
    whatever the depth. Such a family may also scale the
    embedding (``embedding_multiplier``), do without rotary tables
    (``rope`` False), tie the head to the embedding (no ``lm_head``) and
    divide the logits (``logits_scaling``)."""
    dt = cfg.dtype
    B, S = tokens.shape
    tp = _tp_plan(cfg, mesh, rules, B, S)
    con = _act_constraint(mesh, rules, tp)
    with jax.named_scope("embed"):
        x = _embed(params, tokens, dt)
        if cfg.embedding_multiplier is not None:
            x = (x * cfg.embedding_multiplier).astype(dt)
    x = con(x)
    family = _family(cfg)

    @functools.cache
    def tables_of(kind):
        # once a kind, whatever its runs keep
        of = attention_kind(cfg, kind)
        if _takes_attention_half(cfg, kind):
            _say_kind_plan(cfg, kind, of, S)
        if not (cfg.rope and of.rope):
            return None, None
        make = family.rotary_tables or _kind_tables
        with jax.named_scope("attention"):  # the tables are its rotary's
            if isinstance(pos_offset, int) and pos_offset == 0:
                return make(cfg, of, S)
            return tuple(
                jax.lax.dynamic_slice_in_dim(t, pos_offset, S, axis=0)
                for t in make(cfg, of, cfg.max_seq_len))

    plan = remat.plan_for_step(cfg, params, B, S, mesh) if cfg.remat \
        else None

    # what the attention halves hand from layer to layer beside x (None:
    # nothing, and the scans carry x alone)
    held = family.carried_init(cfg, B, S) if family.carried_init else None

    @functools.cache
    def body_of(kind, kept):
        """A kind's layer under the checkpoint that keeps ``kept`` beyond
        the parent's list: two runs of a kind that keep the same names
        share one traced body."""
        cos, sin = tables_of(kind)

        def body(x, held, lp):
            y, stats, held = _layer(x, lp, cfg, cos, sin, mesh=mesh,
                                    rules=rules, tp=tp, kind=kind,
                                    carried=held)
            return con(y), held, stats

        return remat._checkpoint(body, cfg, kept) if cfg.remat else body

    @functools.cache
    def step_of(kind, kept, carries: bool):
        """The scan's step over a kind's body, traced once a kind and
        names kept: with nothing handed on (x alone is the carry) or with
        the handed value carried beside x."""
        body = body_of(kind, kept)

        def alone(x, lp):
            y, _, stats = body(x, None, lp)
            return y, stats

        def beside(carry, lp):
            y, handed, stats = body(*carry, lp)
            return (y, handed), stats

        return beside if carries else alone

    def run_with(kind, x, held, stack, at):
        """The scan of a stack of layers of one kind, the ``at``-th of
        ``_stacks`` (what it keeps is the plan's for that run): (x, held,
        stats). A kind that replaces the handed value carries it beside x;
        one that only reads it holds it as a constant of the loop, so the
        scan stacks no copy a layer for the backward."""
        kept = plan.of(at) if plan is not None else ()
        # what lies under ``layers`` and in none of a layer's halves is
        # the loop's own: the scan's stacks, its carries, ``con``
        with jax.named_scope("layers"):
            if held is None:
                x, stats = jax.lax.scan(step_of(kind, kept, False), x, stack)
            elif family.hands_on(cfg, kind):
                (x, held), stats = jax.lax.scan(step_of(kind, kept, True),
                                                (x, held), stack)
            else:
                body = body_of(kind, kept)

                def reads(x, lp):
                    y, _, stats = body(x, held, lp)
                    return y, stats

                x, stats = jax.lax.scan(reads, x, stack)
            return x, held, stats

    main = 1 if isinstance(params["layers"], dict) else len(params["layers"])

    def run(kind, x, stack, further: int = 0):
        # a further pass's stack: the ``further``-th after the main runs
        x, _, stats = run_with(kind, x, None, stack, main + further)
        return x, stats

    if isinstance(params["layers"], dict):
        x, held, stats = run_with(None, x, held, params["layers"], 0)
    else:
        runs = family.layer_runs(cfg)
        assert len(runs) == main, (runs, main)
        stats = []
        for at, ((kind, _), stack) in enumerate(zip(runs, params["layers"])):
            x, held, s = run_with(kind, x, held, stack, at)
            if s is not None:       # a run of dense layers reports nothing
                stats.append(s)
        with jax.named_scope("layers"):
            # a dense model in runs (models/falcon.py) reports nothing
            stats = _join_stats(stats) if stats else None
        _say_layer_plan(runs, body_of.cache_info().currsize,
                        family.layer_plan_says(cfg, runs, plan)
                        if family.layer_plan_says else None)
    if mesh is not None and rules is not None:
        _say_tp_plan(tp, cfg, B, S)
    if cfg.parallel_block:
        _say_block_plan(cfg)
    hidden = x
    with jax.named_scope("head_loss"):
        x = _norm(x, params["final_norm"], cfg)
        if tp is not None:    # the head wants every row: one gather a step
            x = jax.lax.with_sharding_constraint(x, tp.gathered_sharding())
        logits = _logits(params, x, cfg)
    return logits, stats, hidden, run, plan


def forward_sp(params, tokens, cfg: LlamaConfig, mesh):
    """Sequence-parallel forward: seq sharded over the 'sp' mesh axis.
    Two interchangeable exchanges (SURVEY.md §5.7): ring attention (KV
    rotates around the ICI ring, ops/ring_attention.py) or Ulysses
    (head-scatter all-to-all, ops/ulysses.py) — set cfg.attn_impl to
    "ring" or "ulysses". Partial-manual shard_map: only 'sp' is manual;
    dp/fsdp/tp stay under GSPMD so the same params shardings apply
    unchanged."""
    from jax.sharding import PartitionSpec as P

    cfg_ring = cfg if cfg.attn_impl == "ulysses" \
        else cfg.replace(attn_impl="ring")
    sp = int(mesh.shape["sp"])

    def fwd_local(params, tok_local):
        S_local = tok_local.shape[1]
        offset = jax.lax.axis_index("sp") * S_local
        return forward(params, tok_local, cfg_ring, pos_offset=offset)

    return jax.shard_map(
        fwd_local, mesh=mesh,
        in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"),
        axis_names={"sp"}, check_vma=False)(params, tokens)


def forward_pp(params, tokens, cfg: LlamaConfig, mesh, num_microbatches=None):
    """Pipeline-parallel forward: layers split into pp stages, GPipe
    microbatch schedule (parallel/pipeline.py). Embedding/head run outside
    the pipelined trunk under plain GSPMD."""
    from ray_tpu.parallel.pipeline import pipeline_trunk, stack_stages

    pp = int(mesh.shape["pp"])
    M = num_microbatches or max(2 * pp, 1)
    dt = cfg.dtype
    B, S = tokens.shape
    x = _embed(params, tokens, dt)
    cos, sin = _rope_tables(cfg.rope_theta, S, cfg.head_dim)

    def stage_fn(stage_layers, x):
        def body(x, lp):
            return _layer(x, lp, cfg, cos, sin)[0], None

        if cfg.remat:
            body = remat._checkpoint(body, cfg)
        x, _ = jax.lax.scan(body, x, stage_layers)
        return x

    stacked = stack_stages(params["layers"], pp)
    trunk = pipeline_trunk(stage_fn, mesh, M, schedule=cfg.pp_schedule)
    x = trunk(stacked, x)
    return _logits(params, _norm(x, params["final_norm"], cfg), cfg)


def loss_fn(params, batch, cfg: LlamaConfig, mesh=None, rules=None):
    """Next-token cross-entropy. batch: {"tokens": [B, S+1]} or
    {"inputs": [B,S], "targets": [B,S], optional "mask": [B,S]}.
    mesh+rules pin activation shardings in the dense path (required for
    HBM-tight FSDP configs; see _act_constraint). A scalar for the dense
    model; for a family with ``finish_loss`` (models/moe.py) the pair
    ``(loss, aux)`` that ``parallel.make_train_step`` takes. A family with
    ``further_losses`` (multi-token prediction, models/latent.py) takes
    {"tokens": [B, S+1+n]}: n more ids a sequence, the targets of its
    further passes over the same S positions."""
    further = _family(cfg).further_losses
    if further is not None:
        if set(batch) != {"tokens"}:
            raise ValueError("a model that predicts further tokens takes "
                             "{'tokens': [B, S + 1 + n]} and no mask")
        S = batch["tokens"].shape[1] - 1 - cfg.n_mtp
        inputs, targets = batch["tokens"][:, :S], batch["tokens"][:, 1:S + 1]
        mask = None
    elif "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        mask = batch.get("mask")
        if mask is not None:
            mask = mask[:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    stats = hidden = run = plan = None
    if (cfg.attn_impl in ("ring", "ulysses") and mesh is not None
            and int(mesh.shape.get("sp", 1)) > 1):
        logits = forward_sp(params, inputs, cfg, mesh)
    elif mesh is not None and int(mesh.shape.get("pp", 1)) > 1:
        logits = forward_pp(params, inputs, cfg, mesh)
    else:
        logits, stats, hidden, run, plan = _forward(
            params, inputs, cfg, mesh=mesh, rules=rules)
    finish = _family(cfg).finish_loss
    if finish is not None and stats is None:
        raise ValueError("a model whose loss needs its layers' statistics "
                         "(router losses) does not train under sp or pp")
    with jax.named_scope("head_loss"):
        loss = cross_entropy(logits, targets, mask)
    if further is not None:
        # the further passes' losses and their layers' statistics join stats
        stats = further(params, batch["tokens"], hidden, stats, cfg, run)
    if finish is None:
        return loss
    # an expert model adds its router losses and returns (loss, aux); the
    # bytes its layer checkpoint keeps beyond the parent's list go with them
    with jax.named_scope("head_loss"):
        loss, aux = finish(loss, stats, cfg)
    kept = plan.kept_bytes if plan is not None else 0
    return loss, {**aux, "moe_remat_kept_gb": jnp.float32(kept / 1e9)}


def token_losses(logits, targets):
    """Every position's cross-entropy, float32 [B, S].
    nll = logsumexp(logits) - logit[target]: same value/gradient as
    log_softmax + gather but never materializes the [B, S, V] log_softmax
    tensor (1 GB f32 at B=8 S=1024 V=32k — pure HBM traffic)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None],
                             axis=-1)[..., 0].astype(jnp.float32)
    return lse - ll


def cross_entropy(logits, targets, mask=None):
    """The mean of ``token_losses``, over the positions ``mask`` keeps."""
    nll = token_losses(logits, targets)
    if mask is None:
        return nll.mean()
    mask = mask.astype(nll.dtype)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# what the dense model supplies to the shared layer (models/family.py)
FAMILY = Family(
    "llama", feed_forward=feed_forward, remat_saved=(),
    remat_offered=FFN_OFFERED, remat_saved_bytes=remat_saved_bytes,
    remat_offers=remat_offers)
