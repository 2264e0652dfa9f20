"""What the layer checkpoint keeps: a plan made from bytes.

``jax.checkpoint`` round a layer keeps the layer's input and rebuilds the
rest in the backward, but for what is named: flash's ``o`` and ``lse``, what
the family's feed-forward wants kept (``Family.remat_saved``: an expert
layer's routes, a selection's set) and, where the step's memory has room,
names its layers OFFER (q, k and v as the attention call takes them; a dense
or a shared SwiGLU's products of x; a mixer's in-projection). Which of those
each run of layers keeps is decided here, once a traced forward, from shapes
and from what the train step says of its memory
(``parallel.train_step.StepMemory``): an estimate of the step's peak without
them (``_step_estimate``), every kept byte charged by the length of the run
that keeps it (``kept_cost``), the sum held under the device's limit less a
free share (``REMAT_FREE``). The constants are fitted on one v5e chip's
compiled plans under adafactor; under a mesh no estimate is made.

models/llama.py asks (``plan_for_step``) and wraps its layer bodies
(``_checkpoint``); a family says what it has through its record
(models/family.py). Nothing else in models/ knows the train step.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.family import (_family, _halves, _runs_half,
                                   _takes_attention_half)
from ray_tpu.parallel.train_step import state_bytes, step_memory
from ray_tpu.util import tracing

# checkpoint_name tags of what the attention half offers the layer
# checkpoint where the step's memory has room: q, k and v as the attention
# call takes them (after the q/k norm and the rotary)
ATTN_OFFERED = ("attn_q", "attn_k", "attn_v")

# The share of a device's memory that a plan with kept names leaves free:
# the estimate plus what is kept, each byte at its run's cost (below), stays
# under 85% of the limit, 14.37e9 of the 16,909,336,064 a v5e chip states
# (of 16 GiB). The largest step that has run there planned 15.82e9, at
# 15.38e9 XLA rematerialized on its own (38 ``.remat`` instructions, +166
# ms a step; PERF.md 6, PR 42), and the estimate may read 0.5e9 under a
# plan (the LFM2 step's: it plans 14.76e9): 15% keeps all three apart.
REMAT_FREE = 0.15
# One layer's backward, in bytes a byte of its products (every matrix of
# the layer times its rows: a product and its gradient), and in bytes a lane
# of what stands beside them in float32 and twice over: the rows gathered
# into expert order and back, forward and backward, and the query heads
# round the attention call (q under its rotary, dq, the output's gradient).
# The update of a leaf under adafactor holds four float32 temporaries as
# large as the leaf (3.4 and 3.8 read on the l8 and OLMoE plans, whose peak
# it is). All three from the one-chip plans compiled for a described v5e
# (PERF.md 4 and 6).
LAYER_BACKWARD = 2.0
LANE_BYTES = 18
UPDATE_BYTES = 16
# What a kept byte is charged, in bytes of plan, by the length of the run
# that keeps it (``_stacks``' ``n``; read from the one-chip plans compiled
# for a described v5e, PERF.md 6, PR 43, 51, 53 and 55). A run of ONE layer
# stacks nothing: the kept product stands once and the replay's own buffer
# for it goes. Its plans read 0.61 (Nemotron's q, k and v), 0.68 (Command
# A+'s five names), 0.72 (Nemotron's last four in-projection products),
# 0.999 (seven of LFM2's: +1.408e9 for 1.409e9), 1.00 (MiniCPM-SALA's gate
# and up) and 1.02 (Nemotron's shared products and first five
# in-projections: +2.71e9 for 2.66e9): 1.0 holds the most any read to 2%.
# A run that scans two layers or more stacks every layer's residuals for
# its backward scan, and a kept byte has cost 1.58 to 1.64 there (the
# Mellum2 step, nine of whose twelve layers lie in stacks of three: +3.30e9
# for 2.01e9 of q, k and v at passes of 49,152 rows, PR 51; +2.55e9 for
# 1.61e9 of q at 65,536, PR 43); why it is half as much again is unread.
# A family's further pass (``further_stacks``) is charged by ITS OWN
# stack's length: ``run`` scans it like any other run, and no compiled plan
# reads otherwise (the two GLM steps, the only ones with such a pass, have
# no room and keep nothing; the Mellum2 step has no such pass).
KEPT_COST_ONE = 1.0
KEPT_COST_STACK = 1.5
# What no charge a byte holds: q in a run of ONE layer that stands among
# stacks. Such a layer's body lies open in the program between the stacks'
# loops, the compiler is free to place the pieces of its replay, and with
# its q kept it holds the forward call's log-sum-exp on all 128 lanes until
# the backward cuts it (268 MB a layer where 2 are asked for) and relayouts
# of the kept q beside it: +2.88e9 of plan for 0.40e9 of q in the Mellum2
# step's three full layers, 7.1 a byte, where the q of its three stacks
# costs 1.83 (+2.22e9 for 1.21e9) and k and v everywhere 12.0e9 in all
# (the plans compiled for a described v5e, PERF.md 6, PR 64: 17.21e9 with q
# in every run, over what the chip states). A step whose runs are ALL of one
# layer reads 0.61 to 1.02 with q among its names (above). So such a run
# keeps the other names and leaves q to its replay.
LEFT_BY_ONE_AMONG_STACKS = ("attn_q",)


def kept_cost(n: int) -> float:
    """Bytes of plan a byte kept in a run of ``n`` layers is charged."""
    return KEPT_COST_ONE if n == 1 else KEPT_COST_STACK


class RematPlan(NamedTuple):
    """What the layer checkpoint keeps beyond the parent's list, and why."""
    # the names each run of layers keeps, one tuple a stack in ``_stacks``'
    # order, each in the order offered; (): no run keeps any
    kept: Tuple[Tuple[str, ...], ...]
    kept_bytes: int         # over all runs and layers
    estimate: int           # the step's bytes without them; 0: none made
    limit: int              # the device's; 0: it states none
    # "room" | "no room" | "no step" | "no limit" | "mesh"
    why: str
    # what the rule charged for ``kept_bytes``: each run's at ``kept_cost``
    charged: int = 0

    def of(self, run: int) -> Tuple[str, ...]:
        """The names run ``run`` keeps."""
        return self.kept[run] if self.kept else ()


def _stacks(params, cfg: "LlamaConfig"):
    """([(kind, layers, stack), ...], passes): the stacks of layers a
    step's forward scans, those of a family's further passes over the same
    rows last (``further_stacks``), and how many passes that makes."""
    family = _family(cfg)
    if isinstance(params["layers"], dict):
        stacks = [(None, cfg.n_layers, params["layers"])]
    else:
        stacks = [(kind, n, stack) for (kind, n), stack in zip(
            family.layer_runs(cfg), params["layers"])]
    further = family.further_stacks(params, cfg)
    return stacks + further, 1 + len(further)


def _offered(cfg: "LlamaConfig") -> Tuple[str, ...]:
    """Every name a layer of ``cfg``'s family may offer, in the order the
    plan takes them: the dearest replay a byte first."""
    return ATTN_OFFERED + _family(cfg).remat_offered


def _offers(cfg: "LlamaConfig", kind, batch: int, seq: int):
    """((name, bytes), ...) a layer of ``kind`` offers the checkpoint, the
    dearest replay a byte first: 28 ms a GB for q, k and v on the l8 step,
    23 for a dense SwiGLU's gate and up (MiniCPM-SALA's), 22 for the shared
    SwiGLU's on Command A+'s, 15 for a mixer's in-projection (Nemotron's)
    (PERF.md 6)."""
    family, rows = _family(cfg), batch * seq
    head = rows * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    attention = tuple(zip(ATTN_OFFERED, (
        head * cfg.n_heads, head * cfg.n_kv_heads, head * cfg.n_kv_heads))
    ) if _takes_attention_half(cfg, kind) else ()
    return attention + tuple(family.remat_offers(cfg, kind, rows))


def _step_estimate(cfg: "LlamaConfig", params, stacks, passes: int,
                   rows: int, state: int) -> int:
    """The bytes one device holds at the peak of a train step over
    ``params`` (``_stacks``: its stacks of layers and passes) and ``rows``
    tokens with the parent's list kept, from shapes alone: the state
    (the step's own count) plus the larger of
    - the update: the gradients that wait for it (a stack's update runs
      when its backward scan ends, so the largest stack's and those of the
      leaves outside the stacks) and adafactor's float32 temporaries over
      the largest leaf;
    - a layer's backward: those gradients, what every layer keeps (its
      input, the family's ``remat_saved`` and, where its first half is an
      attention call, flash's ``o`` and ``lse``), the logits of the passes
      before a further pass's, and one layer's products, gradients and
      float32 forms;
    - the head: what every layer keeps, the logits and their gradient.
    A block is counted for what its kind holds (``_halves``; a family
    whose feed-forward is no expert layer in every block says in which it
    is: ``routes``)."""
    family, item = _family(cfg), jnp.dtype(cfg.dtype).itemsize
    routes, expert_rows = family.routes, family.expert_rows(cfg, rows)
    heads = rows * cfg.n_heads * cfg.head_dim

    def products(kind, stack):
        # a layer's matrices [L, in, out] times the rows, an expert's
        # [L, E, in, out] times the rows its experts get; beside them the
        # lanes of the rows in expert order (a block with a feed-forward
        # half) and of the query heads (one with an attention half), or
        # what the family says a mixer's backward holds (a block of two
        # first halves holds both at once)
        first, second = _halves(cfg, kind)
        total = 0
        for w in jax.tree.leaves(stack):
            if w.ndim == 3:
                total += rows * w.shape[2] * item
            elif w.ndim == 4:
                total += expert_rows * w.shape[3] * item
        whole = LAYER_BACKWARD * total + LANE_BYTES * (
            expert_rows * cfg.d_model * (second and routes(cfg, kind))
            + heads * _runs_half(first, "attention")) + (
                family.mixer_backward_bytes(cfg, kind, rows)
                if _runs_half(first, "mixer") else 0)
        if first != "both":
            return whole
        # two first halves ahead of a dense SwiGLU: the backward stands at
        # the larger of the SwiGLU's products and the halves', not at their
        # sum (the compiled plan of such a step reads 10.38e9 where the sum
        # read 14.12e9 and the larger reads 10.97e9; PERF.md 6, PR 63)
        ffn = LAYER_BACKWARD * sum(rows * stack[w].shape[2] * item
                                   for w in ("w_gate", "w_up", "w_down"))
        return max(ffn, whole - ffn)

    def keeps(kind):
        flash = heads * item + rows * cfg.n_heads * 4 \
            if _runs_half(_halves(cfg, kind)[0], "attention") else 0
        return rows * cfg.d_model * item + flash \
            + family.remat_saved_bytes(cfg, kind, rows)

    saved = sum(n * keeps(kind) for kind, n, _ in stacks)
    in_stacks = [state_bytes(stack) for _, _, stack in stacks]
    outside = state_bytes(params) - sum(in_stacks)
    waiting = outside + max(in_stacks)
    logits = 2 * rows * cfg.vocab_size * (4 if cfg.f32_logits else item)
    largest = max(x.size for x in jax.tree.leaves(params))
    return int(state + max(
        waiting + UPDATE_BYTES * largest,
        waiting + saved + (passes - 1) * logits
        + max(products(kind, stack) for kind, _, stack in stacks),
        saved + passes * logits + outside))


def remat_plan(cfg: "LlamaConfig", params, batch: int, seq: int, memory,
               mesh=None) -> RematPlan:
    """Which of the names its layers offer the layer checkpoint keeps in
    each run of layers of a step over ``params`` (arrays or shapes) and
    [batch, seq] tokens: a pure function of shapes and of ``memory``
    (parallel.train_step.StepMemory: the device's limit and the state's
    bytes as the step counts them; None outside a train step). The run is
    the unit: names are taken in the order offered (q, k and v, then the
    family's ``remat_offered``), each name in the runs that offer it,
    earliest run first, while the estimate plus what is kept, each run's
    bytes charged by the run's length (``kept_cost``: 1.0 a byte in a run
    of one layer, 1.5 in a stack), stays under the limit less its free
    share (REMAT_FREE); a name that does not fit a run is passed over for
    the next run and the next name, and a run of one layer among stacks
    leaves q to its replay (LEFT_BY_ONE_AMONG_STACKS). With no limit
    (the CPU) or no step nothing more is kept than the parent's list; under
    a mesh of several devices neither: the activations' share of a device
    is not counted here."""
    if memory is None or not memory.limit:
        return RematPlan((), 0, 0, 0, "no step" if memory is None
                         else "no limit")
    if mesh is not None and mesh.size > 1:
        return RematPlan((), 0, 0, memory.limit, "mesh")
    stacks, passes = _stacks(params, cfg)
    offers = [dict(_offers(cfg, kind, batch, seq)) for kind, _, _ in stacks]
    estimate = _step_estimate(cfg, params, stacks, passes, batch * seq,
                              memory.state)
    ceiling = memory.limit * (1 - REMAT_FREE)
    among_stacks = any(n > 1 for _, n, _ in stacks)
    kept, total, charged = [[] for _ in stacks], 0, 0.0
    for name in _offered(cfg):
        for run, (_, n, _) in enumerate(stacks):
            if n == 1 and among_stacks and name in LEFT_BY_ONE_AMONG_STACKS:
                continue
            nbytes = n * offers[run].get(name, 0)
            cost = kept_cost(n) * nbytes
            if nbytes and estimate + charged + cost <= ceiling:
                kept[run].append(name)
                total += nbytes
                charged += cost
    if not total:
        return RematPlan((), 0, estimate, memory.limit, "no room")
    return RematPlan(tuple(map(tuple, kept)), total, estimate, memory.limit,
                     "room", int(charged))


def _say_remat_plan(plan: RematPlan, cfg: "LlamaConfig"):
    """The instant ``remat.plan`` of a trace, once a traced forward under
    the layer checkpoint: the names kept beyond the parent's list by run
    (``kept``: every name some run keeps, in the order offered; ``runs``:
    "name xN, ..." with N the runs that keep it; ``by_run``: the runs'
    names in the layers' order, "+" between a run's, "-" for none), their
    bytes, what the rule charged for them (``charged``: a run's bytes at
    ``kept_cost``; the compiled plan's growth is the chip's answer), the
    estimate they were added to and the limit."""
    names = [n for n in _offered(cfg) if any(n in run for run in plan.kept)]
    tracing.plan("remat.plan", {
        "kept": ",".join(names), "kept_bytes": plan.kept_bytes,
        "charged": plan.charged,
        "runs": ", ".join(f"{n} x{sum(n in run for run in plan.kept)}"
                          for n in names),
        "by_run": ",".join("+".join(run) or "-" for run in plan.kept),
        "estimate": plan.estimate, "limit": plan.limit,
        "ceiling": int(plan.limit * (1 - REMAT_FREE)), "why": plan.why})


def _checkpoint(body, cfg: "LlamaConfig", kept: Tuple[str, ...] = ()):
    """Per-layer jax.checkpoint. Beside the layer's input it keeps the
    flash kernel's output and log-sum-exp (FLASH_RESIDUALS: the output is
    as large as the layer input, B x S x D x 2 bytes a layer in bf16, the
    log-sum-exp B x H x S x 4), so the backward kernels run from them and
    the forward kernel runs once, what the family's feed-forward
    names (``remat_saved``: an expert layer's routes) and ``kept``: the names
    of those the layer offers that the step's memory has room for in THIS
    run of layers (``remat_plan``: q, k and v as the attention call takes
    them, a dense or a shared feed-forward's products of x before the
    activation, a state-space mixer's in-projection); everything else is
    recomputed, the mixer's scan too (ops/ssd.py). A body that holds no
    such name (attn_impl other than "flash", under 128 tokens) saves
    nothing more."""
    from ray_tpu.ops.flash_attention import FLASH_RESIDUALS

    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS, *_family(cfg).remat_saved, *kept))


def plan_for_step(cfg, params, batch: int, seq: int, mesh=None) -> RematPlan:
    """``remat_plan`` for the train step that is tracing this forward (its
    ``StepMemory``; None outside a step), said to the trace."""
    plan = remat_plan(cfg, params, batch, seq, step_memory(), mesh)
    _say_remat_plan(plan, cfg)
    return plan
