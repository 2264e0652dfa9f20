"""What a family supplies to the shared layer is one record, checked where
it is built (``ray_tpu/models/family.py``), and the layer checkpoint's plan
is a module that reads it (``ray_tpu/models/remat.py``)."""

import importlib
import os
import re

import pytest

from ray_tpu.models import family, llama, remat
from ray_tpu.models.family import Family

FAMILIES = ("llama", "moe", "hybrid", "latent", "sala")
MODELS = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                      "ray_tpu", "models")
# the names that moved from models/llama.py to models/remat.py
MOVED = ("REMAT_FREE", "LAYER_BACKWARD", "LANE_BYTES", "UPDATE_BYTES",
         "KEPT_COST_ONE", "KEPT_COST_STACK", "kept_cost", "RematPlan",
         "_stacks", "_offers", "_offered", "_step_estimate", "remat_plan",
         "_say_remat_plan", "_checkpoint", "ATTN_OFFERED")


def _dense(**members):
    """llama's record with ``members`` in place of its own."""
    return llama.FAMILY.replace("a test's", **members)


@pytest.mark.parametrize("name", FAMILIES)
def test_a_family_module_builds_its_record_and_the_layer_finds_it(name):
    mod = importlib.import_module(f"ray_tpu.models.{name}")
    assert isinstance(mod.FAMILY, Family) and mod.FAMILY.name == name
    for preset in mod.PRESETS.values():
        assert llama._family(preset) is mod.FAMILY
    # immutable: a family changes no member after its import
    with pytest.raises(AttributeError):
        mod.FAMILY.feed_forward = None


@pytest.mark.parametrize("name,taken,own", [
    ("hybrid", ("remat_saved", "expert_rows", "finish_loss"),
     ("feed_forward", "remat_offered", "mixer_half", "halves", "routes")),
    ("latent", ("remat_offered", "expert_rows", "routes", "halves"),
     ("feed_forward", "remat_saved", "attention_half", "finish_loss",
      "carried_init", "hands_on", "further_stacks")),
    ("sala", ("feed_forward", "remat_offered", "remat_offers",
              "expert_rows"),
     ("remat_saved", "remat_saved_bytes", "attention_half", "finish_loss"))])
def test_a_family_built_from_another_takes_what_it_does_not_name(
        name, taken, own):
    mod = importlib.import_module(f"ray_tpu.models.{name}")
    base = llama if name == "sala" else importlib.import_module(
        "ray_tpu.models.moe")
    for member in taken:
        assert getattr(mod.FAMILY, member) is getattr(base.FAMILY, member), \
            member
    for member in own:
        assert getattr(mod.FAMILY, member) is not getattr(
            base.FAMILY, member), member


def test_the_defaults_are_written_once_in_the_record():
    """What the eleven ``getattr(family, name, default)`` carried: a family
    that names no such member gets these."""
    dense = llama.FAMILY
    assert [getattr(dense, m) for m in (
        "layer_runs", "attention_half", "mixer_half", "mixer_backward_bytes",
        "further_losses", "finish_loss", "carried_init", "hands_on",
        "layer_plan_says")] == [None] * 9
    assert dense.halves(None, "any") == (True, True)
    assert dense.routes(None, "any") is True
    assert dense.expert_rows(None, 10 ** 6) == 0
    assert dense.further_stacks({}, None) == []
    assert (dense.remat_saved, dense.remat_offered) == (
        (), llama.FFN_OFFERED)


@pytest.mark.parametrize("members,says", [
    ({"mixer_half": lambda *a, **k: None},
     "mixer_half comes without mixer_backward_bytes"),
    ({"carried_init": lambda cfg, b, s: None},
     "carried_init comes without hands_on"),
    ({"remat_offered": ("ffn_gate", "ffn_up", "ffn_gate")},
     "remat_offered holds a name twice"),
    ({"remat_saved": "moe_route"}, "remat_saved is 'moe_route', not a tuple"),
    ({"remat_offered": ["ffn_gate"]}, "remat_offered is .*not a tuple"),
    ({"feed_forward": None}, "feed_forward is None, not a function"),
    ({"remat_offers": ()}, "remat_offers is .*not a function"),
    ({"finish_loss": "moe"}, "finish_loss is 'moe', not a function"),
    ({"halves": None}, "halves is None, not a function")],
    ids=["mixer_half", "carried_init", "twice", "saved-a-string",
         "offered-a-list", "feed_forward-none", "offers-a-tuple",
         "finish_loss-a-string", "halves-none"])
def test_a_record_that_cannot_be_right_is_refused_where_it_is_built(
        members, says):
    with pytest.raises(TypeError, match=f"family \"a test's\": {says}"):
        _dense(**members)


@pytest.mark.parametrize("missing", [
    "feed_forward", "remat_saved", "remat_offered", "remat_saved_bytes",
    "remat_offers"])
def test_a_family_without_a_required_member_fails_at_import(missing):
    """A family module builds its record at its end, so what this raises
    the module's import raises, with the member's name."""
    members = {m: getattr(llama.FAMILY, m) for m in (
        "feed_forward", "remat_saved", "remat_offered", "remat_saved_bytes",
        "remat_offers") if m != missing}
    with pytest.raises(TypeError, match=missing):
        Family("forgetful", **members)
    with pytest.raises(TypeError, match="feed_forwrad"):
        Family("misspelt", **members, feed_forwrad=None)


def test_a_config_of_a_module_without_a_record_is_no_familys():
    class Stray(llama.LlamaConfig):
        pass

    with pytest.raises(AttributeError, match="FAMILY"):
        llama._family(Stray())


def _sources():
    return {name: open(os.path.join(MODELS, name)).read()
            for name in sorted(os.listdir(MODELS)) if name.endswith(".py")}


def test_no_getattr_on_a_family_is_left_and_one_place_reads_sys_modules():
    for name, text in _sources().items():
        assert not re.search(r"getattr\(\s*_?family\b", text), name
        assert ("sys.modules" in text) == (name == "family.py"), name


def test_llama_knows_no_train_step_and_keeps_no_alias_of_the_plan():
    assert [n for n in MOVED if hasattr(llama, n)] == []
    assert [n for n in MOVED if not hasattr(remat, n)] == []
    # nothing in models/ but remat.py imports the train step
    knows = [name for name, text in _sources().items()
             if re.search(r"^(from|import) ray_tpu\.parallel\.train_step",
                          text, re.M)]
    assert knows == ["remat.py"], knows
    assert family._family is llama._family
