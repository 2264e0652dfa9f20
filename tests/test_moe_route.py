"""The router's selection by rounds (``moe.top_lanes``, ``at_lanes``,
``kept_groups``, ``route``) against the form it replaced, kept HERE as the
plain reference: ``lax.top_k`` over every score, ``take_along_axis`` for
the weights, the groups by two more ``top_k``s. Routes, weights, scores and
kept groups bit for bit, exact ties and rows short of finite scores
included; the gradient to float32 rounding; and the lowered text of every
benchmark cell's router without a sort, a top-k call or a scatter.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lowered_cells
from benchmark import resolve
from ray_tpu.models import moe

# the ten cells of BENCHMARK.json whose layers route
CELLS = ("train-olmoe1b7b-s4096-b4", "train-granite4hs-ep8-s8192-b2",
         "train-glm47flash-ep8-s8192-b2", "train-mellum2-ep4-s16384-b1",
         "train-commandaplus-ep16-s8192-b1", "train-glm52-ep32-s16384-b1",
         "train-nemotron3nano-ep8-s8192-b2", "train-lfm2-ep4-s16384-b1",
         "train-ling3flash-ep32-s16384-b1",
         "train-solaropen2-ep32-s16384-b1")
# (E, K) of each, as benchmark/configs/ has them (``deployment.router_
# experts``, ``num_experts_per_tok``): a cell whose router changes shows here
SHAPES = {"train-olmoe1b7b-s4096-b4": (64, 8),
          "train-granite4hs-ep8-s8192-b2": (72, 10),
          "train-glm47flash-ep8-s8192-b2": (64, 4),
          "train-mellum2-ep4-s16384-b1": (64, 8),
          "train-commandaplus-ep16-s8192-b1": (128, 8),
          "train-glm52-ep32-s16384-b1": (256, 8),
          "train-nemotron3nano-ep8-s8192-b2": (128, 6),
          "train-lfm2-ep4-s16384-b1": (32, 4),
          "train-ling3flash-ep32-s16384-b1": (512, 8),
          "train-solaropen2-ep32-s16384-b1": (320, 8)}
# router form -> the fields that make it
FORMS = {"softmax": dict(router_score="softmax", router_bias=False),
         "sigmoid": dict(router_score="sigmoid", router_bias=False),
         "sigmoid.bias": dict(router_score="sigmoid", router_bias=True),
         "sigmoid.bias.groups": dict(router_score="sigmoid", router_bias=True,
                                     n_group=8, topk_group=4)}
T = 384


def cell_config(name: str):
    """(the cell's program config by the benchmark's own files, its tokens
    a step)."""
    cell = resolve.cell(name)
    module, make, _ = lowered_cells.KINDS[cell["kind"]]
    cfg = getattr(importlib.import_module(f"benchmark.{module}"), make)(
        cell["config"])
    return cfg, cell["mix"]["batch"] * cell["mix"]["seq"]


def router_config(form: str, n_experts: int, top_k: int, **more):
    kw = dict(n_group=1, topk_group=1, norm_topk=True, route_scale=2.5)
    kw.update(FORMS[form], n_experts=n_experts, top_k=top_k, **more)
    return moe.MoEConfig(vocab_size=64, d_model=16, n_layers=1, n_heads=2,
                         n_kv_heads=2, d_ff=16, **kw)


# --- the plain reference: the parent's selection -------------------------


def sorted_kept_groups(choice, cfg):
    n, E = choice.shape
    best = jax.lax.top_k(choice.reshape(n, cfg.n_group, E // cfg.n_group),
                         2)[0].sum(axis=-1)
    _, kept = jax.lax.top_k(best, cfg.topk_group)
    return jnp.any(kept[:, :, None] == jnp.arange(cfg.n_group), axis=1)


def sorted_route(logits, cfg, bias=None):
    kept = None
    if cfg.router_score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        if bias is None:
            weights, experts = jax.lax.top_k(probs, cfg.top_k)
        else:
            choice = probs + jax.lax.stop_gradient(bias.astype(jnp.float32))
            if cfg.n_group > 1:
                kept = sorted_kept_groups(choice, cfg)
                choice = jnp.where(jnp.repeat(
                    kept, cfg.n_experts // cfg.n_group, axis=1), choice,
                    -jnp.inf)
            _, experts = jax.lax.top_k(choice, cfg.top_k)
            weights = jnp.take_along_axis(probs, experts, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    if cfg.route_scale != 1.0:
        weights = weights * cfg.route_scale
    return weights, experts, probs, kept


# --- inputs --------------------------------------------------------------


def inputs(cfg, seed: int = 0, finite: int = None):
    """Logits [T, E] with rows of exact ties (two lanes of one group; two
    lanes of two groups, each the best of its group; a whole row equal) and
    the router's bias, or None. ``finite``: the bias is -inf on all but so
    many experts a group, so that a row's choice holds that many finite
    scores a group it keeps."""
    E, K, G = cfg.n_experts, cfg.top_k, cfg.n_group
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    logits = 2.0 * jax.random.normal(keys[0], (T, E), jnp.float32)
    per = E // G
    rows = np.arange(T)
    logits = np.array(logits)
    logits[rows % 4 == 1, 1] = logits[rows % 4 == 1, 0]        # one group
    two = rows % 4 == 2                            # the first and last group
    logits[two, E - per] = logits[two, 0] = logits[two].max(axis=1) + 1.0
    logits[rows % 16 == 3] = 0.25                              # every lane
    bias = None
    if moe._has_bias(cfg):
        bias = 0.1 * jax.random.normal(keys[1], (E,), jnp.float32)
        bias = bias.at[2].set(bias[3])
        if finite is not None:
            lane = jax.random.permutation(keys[2], per)[:finite]
            open_ = jnp.zeros((G, per), bool).at[:, lane].set(True)
            bias = jnp.where(open_.reshape(E), bias, -jnp.inf)
    return jnp.asarray(logits), bias


def same(got, want):
    for name, a, b in zip(("weights", "experts", "probs", "kept"), got, want):
        if b is None:
            assert a is None, name
        else:
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


# --- the routes ----------------------------------------------------------


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("cell", CELLS)
def test_routes_weights_scores_and_groups_equal_the_sorted_form(cell, form):
    """Every router form at every cell's (E, K): bit for bit, ties and
    all."""
    cfg = router_config(form, *SHAPES[cell])
    logits, bias = inputs(cfg, seed=len(cell) + len(form))
    same(jax.jit(lambda l, b: moe.route(l, cfg, b))(logits, bias),
         jax.jit(lambda l, b: sorted_route(l, cfg, b))(logits, bias))


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_routes_as_the_sorted_form(cell):
    """The cell's OWN router (its form, norm and scale from the benchmark's
    files): routes bit for bit, the gradient with respect to the logits of
    a scalar of weights and scores to float32 rounding."""
    cfg, _ = cell_config(cell)
    assert (cfg.n_experts, cfg.top_k) == SHAPES[cell]
    logits, bias = inputs(cfg, seed=7)
    same(jax.jit(lambda l, b: moe.route(l, cfg, b))(logits, bias),
         jax.jit(lambda l, b: sorted_route(l, cfg, b))(logits, bias))
    gw = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.top_k))
    gp = jax.random.normal(jax.random.PRNGKey(2), (T, cfg.n_experts))

    def scalar(fn):
        def f(l):
            weights, _, probs, _ = fn(l, cfg, bias)
            return (weights * gw).sum() + (probs * gp).sum()
        return jax.jit(jax.grad(f))

    got, want = scalar(moe.route)(logits), scalar(sorted_route)(logits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6,
                               atol=1e-7 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("finite", ["exactly_k", "fewer_than_k"])
@pytest.mark.parametrize("form", ["sigmoid.bias", "sigmoid.bias.groups"])
def test_rows_short_of_finite_scores_route_as_the_sorted_form(form, finite):
    """A choice that holds -inf already (the experts of the groups a token
    does not keep, an expert whose bias is -inf): with exactly K finite
    scores in what a row keeps the K experts are those; with fewer the rest
    are the lowest lanes NOT yet taken, as ``lax.top_k`` gives them, which a
    -inf written over a taken lane would not."""
    groups = 4 if "groups" in form else 1              # kept ones, or all
    E, K = 64, 8
    cfg = router_config(form, E, K)
    a_group = K // groups - (finite == "fewer_than_k")
    logits, bias = inputs(cfg, seed=3, finite=a_group)
    got = jax.jit(lambda l, b: moe.route(l, cfg, b))(logits, bias)
    same(got, jax.jit(lambda l, b: sorted_route(l, cfg, b))(logits, bias))
    experts = np.asarray(got[1])
    assert all(len(set(row)) == K for row in experts.tolist())
    chosen = np.isfinite(np.asarray(bias))[experts].sum(axis=1)
    np.testing.assert_array_equal(chosen, a_group * groups)


def test_equal_scores_go_to_the_lower_lane_by_hand():
    scores = jnp.array([[0.5, 0.9, 0.9, 0.1, 0.5, -jnp.inf],
                        [-jnp.inf, -jnp.inf, 0.3, -jnp.inf, 0.3, -jnp.inf]])
    values, lanes = moe.top_lanes(scores, 4)
    np.testing.assert_array_equal(lanes, [[1, 2, 0, 4], [2, 4, 0, 1]])
    np.testing.assert_array_equal(values, np.array(
        [[0.9, 0.9, 0.5, 0.5], [0.3, 0.3, -np.inf, -np.inf]], np.float32))
    np.testing.assert_array_equal(moe.at_lanes(scores, lanes), values)


# --- either side of ROUND_MAX_K -----------------------------------------


@pytest.mark.parametrize("k", [moe.ROUND_MAX_K, moe.ROUND_MAX_K + 1])
def test_rounds_and_the_sort_agree_either_side_of_the_limit(k):
    """Up to ROUND_MAX_K lanes by rounds, above it by ``lax.top_k``: the
    same lanes, values, picked scores and gradient either side, and the
    lowered text says which form ran."""
    scores = jax.random.normal(jax.random.PRNGKey(k), (96, 40), jnp.float32)
    scores = scores.at[::3, 5].set(scores[::3, 30]).at[1::3].set(0.5)
    values, lanes = jax.jit(lambda s: moe.top_lanes(s, k))(scores)
    want, at = jax.lax.top_k(scores, k)
    np.testing.assert_array_equal(values, want)
    np.testing.assert_array_equal(lanes, at)
    assert lanes.dtype == at.dtype
    g = jax.random.normal(jax.random.PRNGKey(0), (96, k))
    picked = jax.jit(jax.value_and_grad(
        lambda s: (moe.at_lanes(s, lanes) * g).sum()))
    plain = jax.value_and_grad(
        lambda s: (jnp.take_along_axis(s, at, axis=-1) * g).sum())
    for a, b in zip(picked(scores), plain(scores)):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    cfg = router_config("softmax", 40, k)
    assert moe.route_rounds(cfg) == (k if k <= moe.ROUND_MAX_K else 0)
    text = jax.jit(lambda l: moe.route(l, cfg)).lower(scores).as_text()
    assert bool(_SORTS.search(text)) == (k > moe.ROUND_MAX_K)


# --- the lowered text ----------------------------------------------------

_SORTS = re.compile(r"\bsort\b|top_k|TopK|topk|scatter", re.I)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_router_lowers_to_no_sort_no_top_k_and_no_scatter(cell):
    """At the cell's own tokens a step, forward and with its gradient."""
    cfg, tokens = cell_config(cell)
    logits = jax.ShapeDtypeStruct((tokens, cfg.n_experts), jnp.float32)
    bias = jax.ShapeDtypeStruct((cfg.n_experts,), jnp.float32) \
        if moe._has_bias(cfg) else None

    def scalar(l, b):
        weights, experts, probs, kept = moe.route(l, cfg, b)
        return weights.sum() + jnp.square(probs).sum(), (experts, kept)

    text = jax.jit(jax.value_and_grad(scalar, has_aux=True)).lower(
        logits, bias).as_text()
    found = sorted(set(m.group(0) for m in _SORTS.finditer(text)))
    assert not found, found
    sorted_text = jax.jit(lambda l, b: sorted_route(l, cfg, b)).lower(
        logits, bias).as_text()
    assert _SORTS.search(sorted_text)          # the pattern finds the old form


@pytest.mark.parametrize("cell,rounds", [
    ("train-ling3flash-ep32-s16384-b1", 14), ("train-lfm2-ep4-s16384-b1", 4),
    ("train-nemotron3nano-ep8-s8192-b2", 6),
    ("train-solaropen2-ep32-s16384-b1", 8)])
def test_the_expert_plan_says_the_selections_form_and_rounds(cell, rounds):
    cfg, tokens = cell_config(cell)
    said = moe.expert_plan(cfg, tokens)
    assert (said["route_form"], said["route_rounds"]) == ("rounds", rounds)
    assert moe.expert_plan(cfg.replace(top_k=moe.ROUND_MAX_K + 1), tokens)[
        "route_form"] == "top_k"
