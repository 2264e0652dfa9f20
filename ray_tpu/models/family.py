"""What a model family supplies to the shared layer: one record.

The embedding, the loop over the layers, the layer checkpoint, the head and
the loss are written once (models/llama.py, models/remat.py, and for serving
models/cached.py). A family (llama's dense model, models/moe.py,
models/hybrid.py, models/latent.py, models/sala.py, models/ling.py,
models/solar.py, models/falcon.py) is the module that
defines a config class and, at its end, builds ``FAMILY``: a ``Family`` that
names every member the shared code reads. A family that takes a member from
another builds FROM that family's record (``moe.FAMILY.replace("hybrid",
...)``), so what it takes is what it does not name. A member that is
missing, misspelt or of the wrong sort fails there, at import, and not
inside a trace.
"""

from __future__ import annotations

import dataclasses
import sys
from dataclasses import KW_ONLY, dataclass
from typing import Callable, Optional, Tuple


def _both_halves(cfg, kind) -> Tuple[bool, bool]:
    return True, True


def _every_block_routes(cfg, kind) -> bool:
    return True


def _no_expert_rows(cfg, rows: int) -> int:
    return 0


def _no_further_stacks(params, cfg) -> list:
    return []


@dataclass(frozen=True)
class Family:
    """The members of one family, by who reads them: ``llama._layer`` and
    ``llama._forward`` (the halves, the runs, what is handed on),
    ``llama.loss_fn`` (the losses), models/remat.py (the names and their
    bytes) and models/cached.py (the feed-forward; an attention half of its
    own is refused). None: the family has no such member."""
    name: str                           # for the errors below
    _: KW_ONLY
    # (h, lp, cfg, mesh=, rules=, tp=, kind=) -> (y, statistics or None):
    # the feed-forward half of a block over its normed input
    feed_forward: Callable
    # checkpoint_name tags the layer checkpoint always keeps for the family
    remat_saved: Tuple[str, ...]
    # every tag its layers may offer beyond q, k and v where the step's
    # memory has room, the dearest replay a byte first
    remat_offered: Tuple[str, ...]
    # (cfg, kind, rows) -> bytes of a layer's ``remat_saved``
    remat_saved_bytes: Callable
    # (cfg, kind, rows) -> ((tag, bytes a layer), ...) a layer of the kind
    # offers, in ``remat_offered``'s order
    remat_offers: Callable
    # (cfg) -> [(kind, adjacent layers of it), ...]: the stacks of a model
    # whose ``params["layers"]`` is a list (None: one stack, one kind)
    layer_runs: Optional[Callable] = None
    # (x, lp, cfg, cos, sin, mesh=, rules=, carried=, kind=) -> (x, carried,
    # what it reports): every layer's attention half, where it is not three
    # projections of the hidden state
    attention_half: Optional[Callable] = None
    # (cfg, an ``AttentionKind``, seq_len) -> (cos, sin): the tables an
    # ``attention_half`` of the family's own is handed, where they are not
    # ``llama._kind_tables``' (None: a pair's angle on both its lanes)
    rotary_tables: Optional[Callable] = None
    # (x, lp, cfg, kind, mesh=) -> x, or (x, what it reports): the first
    # half of a layer whose kind is no attention
    mixer_half: Optional[Callable] = None
    # (cfg, kind, rows) -> bytes a mixer's backward holds beside its
    # matrices' products (the step's estimate)
    mixer_backward_bytes: Optional[Callable] = None
    # (params, tokens, hidden, stats, cfg, run) -> stats: the passes of a
    # model that predicts further tokens than the next
    further_losses: Optional[Callable] = None
    # (loss, stats, cfg) -> (loss, aux): what the layers' statistics add
    finish_loss: Optional[Callable] = None
    # (cfg, batch, seq) -> what the first layer's attention half is handed
    # and the layers carry on from it (a learned selection's set), or None
    carried_init: Optional[Callable] = None
    # (cfg, kind) -> whether a layer of the kind replaces the carried value
    # (False: it only reads it)
    hands_on: Optional[Callable] = None
    # (cfg, runs, plan) -> what ``hybrid.layer_plan`` says more of the layers
    layer_plan_says: Optional[Callable] = None
    # (cfg, kind) -> (a first half, the feed-forward): what a block holds.
    # The first may be "attention" or "mixer" itself, where a family has
    # both an ``attention_half`` and a ``mixer_half`` and the kind decides,
    # or "both": ``llama._attention_half`` and the ``mixer_half`` read ONE
    # norm side by side (the config states ``attention_in_multiplier``,
    # ``attention_out_multiplier`` and ``ssm_out_multiplier``)
    halves: Callable = _both_halves
    # (cfg, kind) -> whether the block's feed-forward is an expert layer's
    routes: Callable = _every_block_routes
    # (cfg, rows) -> rows of the arrays in expert order
    expert_rows: Callable = _no_expert_rows
    # (params, cfg) -> [(kind, layers, stack), ...] of further passes over
    # the same rows
    further_stacks: Callable = _no_further_stacks

    def __post_init__(self):
        def refuse(member, why):
            raise TypeError(f"family {self.name!r}: {member} {why}")

        for f in dataclasses.fields(self)[1:]:
            value = getattr(self, f.name)
            if f.name in ("remat_saved", "remat_offered"):
                if not isinstance(value, tuple) or not all(
                        isinstance(n, str) for n in value):
                    refuse(f.name, f"is {value!r}, not a tuple of names")
            elif not callable(value) and (value is not None
                                          or f.default is not None):
                refuse(f.name, f"is {value!r}, not a function")
        if len(set(self.remat_offered)) != len(self.remat_offered):
            refuse("remat_offered", f"holds a name twice: "
                                    f"{self.remat_offered}")
        for member, with_it in (("mixer_half", "mixer_backward_bytes"),
                                ("carried_init", "hands_on")):
            if getattr(self, member) is not None \
                    and getattr(self, with_it) is None:
                refuse(member, f"comes without {with_it}")

    def replace(self, name: str, **members) -> "Family":
        """The record of the family ``name`` that takes from this one every
        member it does not name."""
        return dataclasses.replace(self, name=name, **members)


def _family(cfg) -> Family:
    """The ``Family`` of the module that defines ``cfg``'s class."""
    return sys.modules[type(cfg).__module__].FAMILY


def _takes_attention_half(cfg, kind) -> bool:
    """Whether a layer of ``kind`` runs ``llama._attention_half``: the one
    kind of a model that names none, a hybrid's "attention" (what follows a
    "." is its feed-forward's: "attention.dense"), a named kind."""
    family = _family(cfg)
    return (family.attention_half is None
            and (kind is None or kind.split(".")[0] == "attention"
                 or kind in dict(cfg.attn_kinds)
                 or family.halves(cfg, kind)[0] == "both"))


def _runs_half(first, half: str) -> bool:
    """Whether a block whose first half is ``first`` (``_halves``) runs the
    ``half`` ("attention" | "mixer"): itself, or one of "both"."""
    return first in (half, "both")


def _halves(cfg, kind):
    """What a block of ``kind`` holds, as ``llama._layer`` runs it: (its
    first half: "attention" (``llama._attention_half`` or the family's own:
    a call of an attention kernel, whose ``o`` and ``lse`` the checkpoint
    keeps), "mixer" (the family's ``mixer_half``), "both" (the two side by
    side from one norm) or None (a block that is its feed-forward alone);
    whether it runs the feed-forward half)."""
    family = _family(cfg)
    first, second = family.halves(cfg, kind)
    if not first:
        return None, second
    if first in ("attention", "mixer", "both"):     # the family said which
        return first, second
    attends = family.attention_half is not None \
        or _takes_attention_half(cfg, kind)
    return "attention" if attends else "mixer", second
