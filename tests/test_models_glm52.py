"""GLM-5.2 on the CPU at a small size (``benchmark/configs/tiny-glm52.json``:
a dense full layer, a period of three shared and one full sparse layers and
one shared layer more, 8 experts with 2 held, 2 of 4 heads held, 2 index
heads, top-k 8 at S 32 so that most queries select): ``models/latent.py``
with its indexer against the plain reference ``reference_glm52.py`` on
seeded weights: losses, each layer's LI, one step's gradients, the two
gradient paths, the wrong models under the cell's own limits, and the
shares that add up to the uncut layer."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import latent, llama
from ray_tpu.models import reference_glm52 as ref

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
B, S = 2, 32
INDEX_LEAVES = ("wi_q", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def few_mappings():
    """Every test of this file starts from an empty executable cache.
    ``_followed`` runs the model eagerly: each call compiles its four
    scans and its eager ops anew (a fresh jaxpr misses jit's cache), and
    every loaded CPU executable holds memory mappings: the seven
    wrong-model cases alone took a process from 175 to 9,450 of them, and
    ``jax.clear_caches()`` back to 693. Under the driver's six workers a
    worker comes to this file with the mappings of the files it ran
    before; at the kernel's 65,530 (``vm.max_map_count``) XLA's next
    compile-and-load (or load from the persistent cache) dies of a
    segmentation fault, which xdist reports as the case it was running:
    ``[P not normalised]``, the file's last eager case, in the driver's
    run of PR 45's tree and in mine of this one."""
    jax.clear_caches()
    yield


@pytest.fixture(scope="module")
def tiny():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import model_glm52

    conf = _load("configs", "tiny-glm52")
    cfg = model_glm52.latent_config(conf, remat=True, attn_impl="flash")
    sizes = model_glm52.sizes(conf)
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    # a key-norm bias that is not zero, so that its place is tested
    for stack in params["layers"]:
        if "wi_k_bias" in stack:
            stack["wi_k_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(5), stack["wi_k_bias"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                                cfg.vocab_size)
    return cfg, sizes, params, tokens


def _program(params, tokens, cfg):
    """(per-token losses, routes [L, B, S, K], sets [F, B, S, S], LI [F])."""
    return _followed(params, tokens, cfg)[:4]


def _followed(params, tokens, cfg):
    """``_program`` and the layers that did NOT attend over the set of the
    full layer at or before them (by the fingerprints they report)."""
    seen = cfg.replace(index_report_sets=True)
    logits, stats = latent.forward_with_stats(params, tokens[:, :-1], seen)
    nll = llama.token_losses(logits, tokens[:, 1:])
    routes = stats["experts"].reshape(stats["experts"].shape[0], B, S, -1)
    sets, attended = stats["index_set"], stats["index_attended"]
    assert attended.shape == (cfg.n_layers,)
    prints = [int(latent.set_fingerprint(s)[0]) for s in sets]
    owner, misled = -1, []
    for n, full in enumerate(cfg.index_full):
        owner += bool(full)
        if int(attended[n]) != prints[owner]:
            misled.append(n)
    return nll, routes, sets, stats["index_loss"], misled


def test_layer_runs_by_both_properties(tiny):
    cfg = tiny[0]
    assert latent.layer_runs(cfg) == [
        ("dense.full", 1), ("sparse.shared", 3), ("sparse.full", 1),
        ("sparse.shared", 1)]
    assert latent.layer_runs(cfg.replace(run_layers=1)) == [
        ("dense.full", 1)] + [("sparse.shared", 1)] * 3 + [
        ("sparse.full", 1), ("sparse.shared", 1)]
    assert latent.layer_runs(cfg.replace(index_heads=0)) == [
        ("dense", 1), ("sparse", 5)]
    params = tiny[2]
    for (kind, _), stack in zip(latent.layer_runs(cfg), params["layers"]):
        assert ("wi_q" in stack) == kind.endswith(".full")
        assert ("router" in stack) == kind.startswith("sparse")
    assert latent.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    with pytest.raises(ValueError, match="one entry a layer"):
        cfg.replace(index_full=(True,))
    with pytest.raises(ValueError, match="no set to attend over"):
        latent.layer_runs(cfg.replace(index_full=(False,) + (True,) * 5))
    with pytest.raises(NotImplementedError, match="prediction module"):
        cfg.replace(n_mtp=1)


def test_program_and_reference_agree_on_losses_terms_and_gradients(tiny):
    cfg, sizes, params, tokens = tiny
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: latent.loss_fn(p, {"tokens": tokens}, cfg),
        has_aux=True))(params)
    nll, routes, sets, index = _program(params, tokens, cfg)
    (want, parts), want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, sizes, routes, sets),
        has_aux=True))(params)
    np.testing.assert_allclose(loss, want, atol=2e-5)
    np.testing.assert_allclose(aux["moe_main_loss"], parts["main"], atol=2e-5)
    np.testing.assert_allclose(index, parts["index"], atol=2e-6)
    np.testing.assert_allclose(
        [aux["index_loss_0"], aux["index_loss_1"]], parts["index"],
        atol=2e-6)
    np.testing.assert_allclose(aux["index_loss"], parts["index"].sum(),
                               atol=2e-6)
    ref_nll, rec = jax.jit(lambda p: ref.token_losses(
        p, tokens, sizes, routes, sets))(params)
    np.testing.assert_allclose(nll, ref_nll, atol=5e-5)
    # the reference's own choices are the program's, to a key and an expert
    assert float(rec["set_differ"].max()) == 0.0
    assert float(rec["route_gap"].max()) == 0.0
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=3e-5)
    assert _followed(params, tokens, cfg)[4] == []
    # what the step reports of the selection
    k = cfg.index_topk
    pairs = k * (k + 1) // 2 + (S - k) * k
    np.testing.assert_allclose(aux["index_selected_share"],
                               pairs / (S * (S + 1) / 2), rtol=1e-6)
    both = (sets[0, 0] & sets[1, 0]).sum() / sets[1, 0].sum()
    np.testing.assert_allclose(aux["index_overlap"], both, rtol=1e-6)
    assert 0.0 < float(both) < 1.0


def test_the_sets_are_exact_causal_and_of_their_size(tiny):
    cfg, sizes, params, tokens = tiny
    _, _, sets, _ = _program(params, tokens, cfg)
    assert sets.shape == (2, B, S, S) and sets.dtype == jnp.int8
    t = np.arange(S)
    assert (np.asarray(sets).sum(-1) == np.minimum(cfg.index_topk,
                                                   t + 1)).all()
    assert not np.asarray(sets)[..., t[None, :] > t[:, None]].any()
    # against the reference's own stable sort, and not the same twice
    _, rec = jax.jit(lambda p: ref.token_losses(p, tokens, sizes))(params)
    assert (np.asarray(rec["own_sets"]) == (np.asarray(sets) != 0)).all()
    assert (np.asarray(sets[0]) != np.asarray(sets[1])).any()


def test_the_two_gradient_paths_share_no_leaf(tiny):
    """``W_I*`` get gradient from LI only; every other leaf from the rest
    only (the program's split, and the reference's)."""
    cfg, sizes, params, tokens = tiny
    _, routes, sets, _ = _program(params, tokens, cfg)

    def split(grads):
        flat = jax.tree_util.tree_leaves_with_path(grads)
        own = [g for path, g in flat
               if jax.tree_util.keystr(path).split("'")[-2] in INDEX_LEAVES]
        rest = [g for path, g in flat
                if jax.tree_util.keystr(path).split("'")[-2]
                not in INDEX_LEAVES]
        assert len(own) == 10 and rest
        return own, rest

    for loss_of in (
            lambda p, w: latent.loss_fn(
                p, {"tokens": tokens},
                cfg.replace(index_loss_weight=w))[0],
            lambda p, w: ref.loss(p, tokens, {**sizes,
                                              "index_loss_weight": w},
                                  routes, sets)[0]):
        without = jax.jit(jax.grad(lambda p: loss_of(p, 0.0)))(params)
        with_li = jax.jit(jax.grad(lambda p: loss_of(p, 1.0)))(params)
        own0, rest0 = split(without)
        own1, rest1 = split(with_li)
        assert all(float(jnp.abs(g).max()) == 0.0 for g in own0)
        assert all(float(jnp.abs(g).max()) > 0.0 for g in own1)
        for a, b in zip(rest0, rest1):
            np.testing.assert_array_equal(a, b)


# --- LI makes its gradient where it makes its value -------------------------
def _plain_index_loss(qI, weight, kI, probs, keep):
    """``latent._index_loss`` as the program had it until PR 47, kept here
    as the reference form: LI left to plain autodiff, each block of
    queries a ``jax.checkpoint`` round its scores, so that LI's backward
    (and with it the head-mean probabilities) runs with the layer's."""
    @jax.checkpoint
    def block(q, w, k, p, on):
        on = on != 0
        scores = jnp.where(on, latent.index_scores(q, w, k), -1e30)
        top = jnp.max(scores, axis=1, keepdims=True)
        norm = top + jnp.log(jnp.sum(
            jnp.where(on, jnp.exp(scores - top), 0.0), axis=1,
            keepdims=True))
        p = jnp.where(on, p, 0.0)
        return jnp.sum(jnp.where(
            p > 0, p * (jnp.log(jnp.maximum(p, 1e-37)) - (scores - norm)),
            0.0))

    with jax.named_scope("index_loss"):
        total = sum(
            block(qI[at:at + rows], weight[at:at + rows], kI[:keys],
                  probs[at:at + rows, :keys], keep[at:at + rows, :keys])
            for at, rows, keys in latent._score_blocks(qI.shape[0]))
    return total / qI.shape[0]


@pytest.mark.parametrize("remat", [True, False],
                         ids=["layer checkpoint", "no checkpoint"])
@pytest.mark.parametrize("weight", [0.0, 1.0, 0.5])
def test_the_index_loss_and_its_plain_autodiff_agree(weight, remat, tiny,
                                                     monkeypatch):
    """The rule that makes LI's gradient in the forward against plain
    autodiff of the same term: LI and the loss to the bit, the ten
    ``W_I*`` gradients to rounding, every other leaf bit-equal."""
    cfg, _, params, tokens = tiny
    cfg = cfg.replace(index_loss_weight=weight, remat=remat)

    def step():
        return jax.jit(jax.value_and_grad(
            lambda p: latent.loss_fn(p, {"tokens": tokens}, cfg),
            has_aux=True))(params)

    (loss, aux), grads = step()
    monkeypatch.setattr(latent, "_index_loss", _plain_index_loss)
    (want, want_aux), want_g = step()
    for name in ("index_loss_0", "index_loss_1", "index_loss"):
        assert np.array_equal(np.asarray(aux[name]),
                              np.asarray(want_aux[name])), name
    assert float(aux["index_loss_0"]) > 0 and float(aux["index_loss_1"]) > 0
    assert np.array_equal(np.asarray(loss), np.asarray(want))
    seen = 0
    for (path, got), ref_g in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(want_g)):
        if jax.tree_util.keystr(path).split("'")[-2] in INDEX_LEAVES:
            seen += 1
            np.testing.assert_allclose(got, ref_g, atol=3e-5)
            assert (float(jnp.abs(got).max()) > 0.0) == (weight > 0)
        else:
            np.testing.assert_array_equal(got, ref_g)
    assert seen == 10


def _paths(jaxpr, prefix=""):
    """(primitive, the whole named-scope path) of every equation, those of
    nested jaxprs with the path of the equation that holds them."""
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, path
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _paths(sub, path)


@pytest.mark.parametrize("form", ["made in the forward", "plain autodiff"])
def test_the_replay_holds_nothing_of_the_index_loss(form, tiny, monkeypatch):
    """The gradient program under the layer checkpoint: each full layer's
    head-mean probabilities and LI's scores are computed once, in the
    forward scan, and the backward scan (the replay and the backward)
    holds no product under ``index_loss``; with LI left to plain autodiff
    the replay runs the probabilities again and LI's products stand in the
    backward scan."""
    from ray_tpu.ops import sparse_attention as sa

    cfg, _, params, tokens = tiny
    monkeypatch.setattr(sa, "IMPL", "pallas")   # the four calls, traced only
    if form == "plain autodiff":
        monkeypatch.setattr(latent, "_index_loss", _plain_index_loss)
    closed = jax.make_jaxpr(jax.grad(lambda p: latent.loss_fn(
        p, {"tokens": tokens}, cfg)[0]))(params)
    eqns = list(_paths(closed.jaxpr))
    backward = "transpose(jvp(layers))"
    assert any(backward in p for _, p in eqns)
    probs = [backward in p for name, p in eqns
             if name == "pallas_call" and "sparse.probs.mask" in p]
    products = [backward in p for name, p in eqns
                if name == "dot_general" and "index_loss" in p]
    full = sum(cfg.index_full)
    if form == "made in the forward":
        # a layer: the scores, their chunks' recompute, the two gradient
        # products, all in the forward scan
        assert probs == [False] * full
        assert products == [False] * (4 * full)
    else:
        assert sorted(probs) == [False] * full + [True] * full
        assert sorted(products) == [False] * full + [True] * (4 * full)


# --- wrong models under the cell's own limits ------------------------------
def _wrong(name, cfg, params):
    """(cfg, params, a patch of the program) of a model that is not the
    configuration's."""
    from unittest import mock

    none = mock.patch.object(latent, "__doc__", latent.__doc__)
    if name == "dense attention":
        return cfg.replace(index_topk=S), params, none
    if name == "top-k one short":
        return cfg.replace(index_topk=cfg.index_topk - 1), params, none
    if name == "a shared layer selecting for itself":
        # the second sparse layer takes a full layer's place with layer 4's
        # indexer weights
        full = list(cfg.index_full)
        full[2] = True
        wrong = cfg.replace(index_full=tuple(full))
        made = latent.init_params(jax.random.PRNGKey(0), wrong)
        return wrong, made, none
    if name == "the set of the wrong full layer":
        # the second full layer's set is handed to nobody: the layer after
        # it attends over the first's
        return cfg, params, mock.patch.object(
            latent, "FAMILY", latent.FAMILY.replace(
                "latent", hands_on=lambda c, kind: kind == "dense.full"))
    if name == "no relu":
        return cfg, params, mock.patch.object(jax.nn, "relu", lambda x: x)
    if name == "rotary on the last index lanes":
        def last(x, cos, sin):
            return jnp.flip(latent_rotary(jnp.flip(x, -1), cos, sin), -1)
        latent_rotary = latent._index_rotary
        return cfg, params, mock.patch.object(latent, "_index_rotary", last)
    if name == "P not normalised":
        # the heads' SUM in place of their mean
        from ray_tpu.ops import sparse_attention as sa

        real = sa.sparse_attention

        def summed(q, k, v, keep, **kw):
            out = real(q, k, v, keep, **kw)
            if kw.get("with_probs"):
                return out[0], out[1] * q.shape[2]
            return out
        return cfg, params, mock.patch.object(sa, "sparse_attention", summed)
    raise AssertionError(name)


WRONG = ["dense attention", "top-k one short",
         "a shared layer selecting for itself",
         "the set of the wrong full layer", "no relu",
         "rotary on the last index lanes", "P not normalised"]


@pytest.mark.parametrize("name", WRONG)
def test_the_cells_limits_refuse_a_wrong_model(name, tiny):
    """Each wrong model's sets, per-token losses and LI against the
    reference's on the right model, held to the cell's ``train.check``:
    at least one limit fails (the right model passes all of them in
    ``test_program_and_reference_agree...`` at a hundredth of the room)."""
    cfg, sizes, params, tokens = tiny
    tol = _load("workloads", "train-glm52-ep32-s16384-b1")["train"]["check"]
    wrong_cfg, wrong_params, patch = _wrong(name, cfg, params)
    with patch:
        nll, routes, sets, index, misled = _followed(wrong_params, tokens,
                                                     wrong_cfg)
    if name == "the set of the wrong full layer":
        # the losses move only as far as the last layer's keys matter: the
        # fingerprint of the set each layer was handed tells exactly
        assert misled == [5]
        return
    assert not misled
    if name == "a shared layer selecting for itself":
        # three sets where the model has two: the reference cannot even be
        # run on them
        assert sets.shape[0] == 3
        return
    ref_nll, rec = jax.jit(lambda p: ref.token_losses(
        p, tokens, sizes, routes, sets))(params)
    sized = (np.asarray(sets).sum(-1) == np.minimum(
        cfg.index_topk, np.arange(S) + 1)).all()
    apart = np.abs(np.asarray(nll) - np.asarray(ref_nll))
    fails = {
        "set sizes": not sized,
        "set_differ_share": float(rec["set_differ"].mean())
        > tol["set_differ_share"],
        "set_gap_max": float(rec["set_gap"].max()) > tol["set_gap_max"],
        "token_mean_abs": float(apart.mean()) > tol["token_mean_abs"],
        "index_loss_abs": float(np.abs(np.asarray(index) - np.asarray(
            rec["index_loss"])).max()) > tol["index_loss_abs"]}
    assert any(fails.values()), (name, fails)


def test_the_selection_is_not_differentiated_through(tiny):
    """No gradient of the language-model loss reaches the indexer through
    the set: with LI's weight 0 the indexer's leaves get exactly nothing
    (``test_the_two_gradient_paths...``), and the set itself is an integer
    array behind a stop-gradient in the traced program."""
    cfg, _, params, tokens = tiny
    jaxpr = str(jax.make_jaxpr(lambda p: latent.loss_fn(
        p, {"tokens": tokens}, cfg)[0])(params))
    assert "i8[2,32,32]" in jaxpr and "stop_gradient" in jaxpr


# --- the shares add up -----------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """One sparse full layer, one input: the two head shares' attention
    outputs, the shared expert once and the four expert shares add up to
    the uncut reference's layer (4 heads, all 8 experts held)."""
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import model_glm52

    conf = _load("configs", "tiny-glm52")
    sizes = model_glm52.sizes(conf)
    H, dn, dr, dv = 4, sizes["qk_nope_dim"], sizes["qk_rope_dim"], \
        sizes["v_dim"]
    whole = {**sizes, "n_heads": H, "experts_held": None}
    cfg = model_glm52.latent_config(conf).replace(
        n_heads=H, n_kv_heads=H, experts_held=None, n_layers=2, n_dense=1,
        index_full=(True, True))
    stack = latent.init_params(jax.random.PRNGKey(4), cfg)["layers"][1]
    lp = jax.tree.map(lambda w: w[0].astype(jnp.float32), stack)
    x = jax.random.normal(jax.random.PRNGKey(6), (S, sizes["d_model"]))
    with jax.default_matmul_precision("highest"):
        y = ref._rms(x, lp["attn_norm"], sizes["norm_eps"])
        a, keep, _ = ref._attention(y, lp, whole, None, None, 16)
        mid = x + a
        z = ref._rms(mid, lp["ffn_norm"], sizes["norm_eps"])
        ffn, rec = ref._experts(z, lp, whole, None)
        total = mid + ffn

        # attention by head share, on the uncut layer's set
        parts = []
        for first in (0, 2):
            cols = lambda w, width: w.reshape(w.shape[0], H, width)[  # noqa
                :, first:first + 2].reshape(w.shape[0], -1)
            mine = {**lp, "wq_b": cols(lp["wq_b"], dn + dr),
                    "wkv_b": cols(lp["wkv_b"], dn + dv),
                    "wo": lp["wo"].reshape(H, dv, -1)[first:first + 2]
                    .reshape(2 * dv, -1)}
            parts.append(ref._attention(y, mine, {**sizes, "n_heads": 2},
                                        keep, None, 16)[0])
        np.testing.assert_allclose(parts[0] + parts[1], a, atol=2e-5)

        # the experts by share of two, the shared expert counted once
        shared = ref._swiglu(z, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        routed = jnp.zeros_like(z)
        for first in range(0, 8, 2):
            mine = {**lp, **{n: lp[n][first:first + 2]
                             for n in ("we_gate", "we_up", "we_down")}}
            part, _ = ref._experts(z, mine, {**sizes,
                                             "experts_held": (2, first)},
                                   rec["experts"])
            routed = routed + part - shared
        np.testing.assert_allclose(routed + shared, ffn, atol=2e-5)
        np.testing.assert_allclose(x + parts[0] + parts[1] + routed + shared,
                                   total, atol=4e-5)


def test_the_registry_knows_the_family_and_its_presets_are_the_files():
    """``glm_moe_dsa`` -> ``models/latent.py``; the presets are what the
    benchmark's configuration files build (but for the toy's dtype)."""
    import dataclasses
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import model_glm52
    from ray_tpu.models import registry

    for name, but in (("tiny-glm52", {"dtype", "param_dtype"}),
                      ("glm-5.2-ep32-l5", set())):
        cfg, mod = registry.get("glm_moe_dsa", name)
        assert mod is latent
        want = model_glm52.latent_config(_load("configs", name))
        differ = {f.name for f in dataclasses.fields(cfg)
                  if getattr(cfg, f.name) != getattr(want, f.name)}
        assert differ <= but, differ
