"""Grouped matmul: the rows of ``lhs`` [M, K] are grouped by expert
(``group_sizes`` [E], summing to M, rows of one group adjacent) and each
group is multiplied by its own matrix of ``rhs`` [E, K, N]. The work is
proportional to M, not to M x E: what an expert layer needs once its
token-expert assignments are sorted by expert (models/moe.py).

Two paths, chosen by the caller the way ``attn_impl`` chooses "xla" or
"flash":

- ``"xla"``: ``jax.lax.ragged_dot``, lowered by XLA on any backend and
  partitioned by GSPMD (the CPU tests; a mesh that shards the experts).
  Gradients are jax's own.
- ``"pallas"``: the Mosaic kernels jax ships in
  ``jax.experimental.pallas.ops.tpu.megablox`` (``gmm`` for the forward and
  the gradient of ``lhs``, ``tgmm`` for the gradient of ``rhs``), under a
  custom VJP of our own so that every call takes the tiling written
  below and returns the operand's type. A Mosaic call cannot be
  partitioned by GSPMD; the caller refuses a mesh of several devices.
  Interpret mode off the TPU.

Tiles (tm, tk, tn), chosen at OLMoE's shapes (M 131,072 rows, E 64,
[2048, 1024] and [1024, 2048] matrices, bf16) from a sweep on a v5e
(``benchmark/tools/gmm_sweep.py``; my chip run, PR 26; every tm of 256,
512, 1024 with every tk, tn of 512, 1024, 2048 that fits 15 MiB of VMEM):
``gmm`` is fastest with 256 rows and the WHOLE of K and N in a tile, 3.53-
3.58 ms a call (154-156 TFLOP/s) against 3.79-4.10 at (512, 1024, 1024)
and up to 7.7 with tk 512; ``tgmm`` at (256, 1024, 1024),
4.03 ms (136 TFLOP/s), its larger tiles run out of VMEM. XLA's
``ragged_dot`` takes 5.13-5.55 ms forward and 12.2 ms for both gradients
of one matrix. A tile size is cut to the dimension where the dimension is
smaller; M must be a multiple of tm. Two rules for widths the sweep did not
see (``_fit``; GLM-4.7-Flash's [2048, 1536]): a K or N that is no whole
number of tiles is split into equal tiles of whole lanes (1536 under 1024:
two of 768; megablox would run a second, half-empty tile), and ``gmm``'s N
tile is halved while its tiles (both operands double-buffered, the result
and its float32 accumulator) pass the 15 MiB the sweep held to (2048 x
1536 whole is 16.7 MiB, and Mosaic refuses it). Every shape of the sweep
keeps its tiles. Nemotron 3 Nano's [2688, 1856]: 2,688 = 21 x 128 goes in
three tiles of 896, 1,856 = 29 x 64 whole in ``gmm`` (under its 2,048) and
in ``tgmm`` as 1,024 and a ragged 832
(``benchmark/tools/nemotron_gmm_forms.py`` times these, the parent's
fallback (2,048 of the 2,688 with a ragged 640: Mosaic refuses it, out of
VMEM) and weights stored at 1,920; PERF.md 6, PR 48).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# (tm, tk, tn): forward and lhs-gradient ``gmm`` tile M x K x N of
# [M, K] x [E, K, N]; ``tgmm`` tiles M (the contracted rows), K and N of
# the [E, K, N] gradient. See the module docstring.
GMM_TILING = (256, 2048, 2048)
TGMM_TILING = (256, 1024, 1024)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


_TILE_VMEM_BYTES = 15 * 2 ** 20


def _even(tile: int, dim: int) -> int:
    """The tile for ``dim``: ``tile`` cut to it, or, where ``dim`` is no
    whole number of those, ``dim`` in equal tiles of whole lanes: as few
    as cover it or, where those are no whole lanes, up to twice as many
    (2,688 under 2,048: not two of 1,344 but three of 896). A ``dim`` that
    no tile of whole lanes divides (1,856 = 14.5 x 128) keeps ``tile`` and
    a ragged last one, which the kernels mask (``tgmm`` with 1,856 whole
    is 8-13% faster alone and 0.34 MiB over the scoped VMEM inside the
    step program: PERF.md 6, PR 48)."""
    tile = min(tile, dim)
    parts = -(-dim // tile)
    for n in range(parts, 2 * parts + 1):
        if dim % n == 0 and dim // n % 128 == 0:
            return dim // n
    return tile


def _fit(tiling, m, k, n, itemsize: int = 2, halve_n: bool = False):
    tm, tk, tn = tiling
    tm = min(tm, m)
    if m % tm:
        raise ValueError(f"grouped matmul: {m} rows are no multiple of the "
                         f"row tile {tm}")
    tk, tn = _even(tk, k), _even(tn, n)
    while halve_n and tn % 256 == 0 and (
            2 * (tm * tk + tk * tn) * itemsize
            + tm * tn * (itemsize + 4)) > _TILE_VMEM_BYTES:
        tn //= 2
    return tm, tk, tn


def tiles(m: int, k: int, n: int, itemsize: int = 2):
    """((tm, tk, tn) of ``gmm`` for [m, k] x [E, k, n], the same of
    ``tgmm`` for its weight gradient): what the calls below take."""
    return (_fit(GMM_TILING, m, k, n, itemsize, halve_n=True),
            _fit(TGMM_TILING, m, k, n))


def _backend():
    # the package's ``gmm`` attribute is its custom-VJP function and hides
    # the module of the same name
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    with jax.named_scope("gmm.pallas"):
        return _backend().gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_fit(GMM_TILING, lhs.shape[0], lhs.shape[1], n,
                        lhs.dtype.itemsize, halve_n=True),
            transpose_rhs=transpose_rhs, interpret=_use_interpret())


@jax.custom_vjp
def _pallas(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes)


def _pallas_fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _pallas_bwd(res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = _gmm(g, rhs, group_sizes, transpose_rhs=True)
    # tgmm takes lhs as [K, M] and transposes it back itself: XLA cancels
    # the pair, the kernel reads lhs as it lies
    with jax.named_scope("tgmm.pallas"):
        d_rhs = _backend().tgmm(
            lhs.swapaxes(0, 1), g, group_sizes,
            preferred_element_type=rhs.dtype,
            tiling=_fit(TGMM_TILING, lhs.shape[0], lhs.shape[1], g.shape[1]),
            num_actual_groups=rhs.shape[0], interpret=_use_interpret())
    return d_lhs, d_rhs, None


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def grouped_matmul(lhs, rhs, group_sizes, impl: str = "xla"):
    """lhs [M, K], rhs [E, K, N], group_sizes [E] int32 (sum M) -> [M, N]
    in lhs's type, accumulated in float32. Differentiable in lhs and rhs
    on both paths; a group may be empty."""
    if impl == "pallas":
        return _pallas(lhs, rhs, group_sizes.astype(jnp.int32))
    if impl != "xla":
        raise ValueError(f"grouped_matmul impl must be 'xla' or 'pallas', "
                         f"got {impl!r}")
    with jax.named_scope("gmm.xla"):    # jax transposes it itself
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes.astype(jnp.int32),
            preferred_element_type=jnp.float32).astype(lhs.dtype)
