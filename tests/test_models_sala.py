"""``models/sala.py`` (MiniCPM-SALA's block: attention over a set of blocks a
KV group beside Lightning linear-attention layers) against the plain
reference ``reference_sala.py`` in float32 at a small size, seeded random
weights; ``ops/sparse_attention.block_sparse_attention`` (Pallas interpret
mode) against plain ``jax.numpy`` on random block sets at 16 query heads a
KV head; ``ops/ssd.ssd_scan``'s wide calls against the recurrence a token
at a time at heads of 128; the selection against a sort on hand-made
scores; and the calls the other families make, traced to the text they
had before this family came."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, registry, sala
from ray_tpu.models import reference_sala as ref
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops import ssd


def _sizes(cfg):
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out["head_width"] = cfg.head_dim
    return out


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    # the tiny size's 128 steps in 8 chunks, not in one
    monkeypatch.setattr(sala, "LIGHTNING_CHUNK", 16)


@pytest.fixture(scope="module")
def tiny():
    cfg, mod = registry.get("minicpm_sala", "tiny")
    assert mod is sala
    cfg = cfg.replace(dtype=jnp.float32, param_dtype=jnp.float32)
    params = jax.jit(lambda k: sala.init_params(k, cfg))(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def _program_losses(cfg, params, tokens):
    seen = cfg.replace(report_sets=True)

    def run(p):
        logits, stats = sala.forward_with_stats(p, tokens[:, :-1], seen)
        return llama.token_losses(logits, tokens[:, 1:]), stats

    return jax.jit(run)(params)


# --- the family against the plain reference ---------------------------------
def test_forward_and_sets_agree_with_the_reference(tiny):
    cfg, params, tokens = tiny
    got, stats = _program_losses(cfg, params, tokens)
    want, rec = jax.jit(lambda p: ref.token_losses(
        p, tokens, _sizes(cfg), q_block=64))(params)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # two sparse layers, each [B, KV, S, S / 8]; the reference chose alone
    assert stats["block_set"].shape == rec["set"].shape == (2, 2, 2, 128, 16)
    assert bool(jnp.all(stats["block_set"] == rec["set"]))


def test_reference_on_the_programs_sets_reports_no_difference(tiny):
    cfg, params, tokens = tiny
    got, stats = _program_losses(cfg, params, tokens)
    want, rec = jax.jit(lambda p, s: ref.token_losses(
        p, tokens, _sizes(cfg), s, q_block=32))(params, stats["block_set"])
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(rec["set_differ"].max()) == 0.0
    assert float(rec["set_gap"].max()) == 0.0
    # a wrong set is told: the first sparse layer's without its window
    wrong = stats["block_set"].at[0, :, :, 20:, :].set(
        stats["block_set"][0, :, :, 20:, :] * (jnp.arange(16) < 2))
    _, rec = jax.jit(lambda p, s: ref.token_losses(
        p, tokens, _sizes(cfg), s, q_block=32))(params, wrong)
    assert float(rec["set_differ"][0].max()) >= 0.5


def test_loss_and_gradients_agree_with_the_reference(tiny):
    cfg, params, tokens = tiny
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: sala.loss_fn(p, {"tokens": tokens}, cfg),
        has_aux=True))(params)
    want, wgrads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, _sizes(cfg), q_block=64)[0]))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    apart = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9)),
        grads, wgrads)
    assert max(jax.tree.leaves(apart)) < 1e-3, apart
    # no gradient passes through the selection, every leaf gets one
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))
    assert {k: int(v) for k, v in aux.items() if k.startswith("sparse_")} == {
        "sparse_blocks_selected": 3712, "sparse_layers_selecting": 2,
        "sparse_pairs_selected": 26112, "sparse_pairs_walked": 256,
        "sparse_set_forced": 2880}


def test_within_the_dense_length_a_sparse_layer_attends_densely(tiny):
    cfg, params, tokens = tiny
    short = tokens[:, :33]                       # 32 tokens: dense_len
    logits, stats = jax.jit(lambda p: sala.forward_with_stats(
        p, short[:, :-1], cfg))(params)
    want, rec = jax.jit(lambda p: ref.token_losses(p, short, _sizes(cfg)))(
        params)
    np.testing.assert_allclose(llama.token_losses(logits, short[:, 1:]),
                               want, atol=2e-5)
    assert rec == {} and int(stats["sparse_blocks_selected"].sum()) == 0


def test_the_kernel_paths_agree_with_the_plain_ones(tiny, monkeypatch):
    """``ssd_impl`` "pallas" and the block-set kernels (interpret mode)
    give the plain paths' losses and gradients."""
    cfg, params, tokens = tiny

    def grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: sala.loss_fn(p, {"tokens": tokens}, cfg)[0]))(params)

    plain = grads(cfg)
    monkeypatch.setattr(sa, "IMPL", "pallas")
    monkeypatch.setattr(sa, "BLOCK_Q", 64)
    monkeypatch.setattr(sa, "BLOCK_K", 64)
    kernels = grads(cfg.replace(ssd_impl="pallas"))
    assert abs(float(plain[0]) - float(kernels[0])) < 1e-5
    apart = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9)),
        kernels[1], plain[1])
    assert max(jax.tree.leaves(apart)) < 1e-3, apart


def test_params_specs_and_count(tiny):
    cfg, params, _ = tiny
    assert sum(x.size for x in jax.tree.leaves(params)) == sala.num_params(cfg)
    specs = sala.param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda x: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))
    assert sala.layer_runs(cfg) == [("sparse", 1), ("lightning", 3),
                                    ("sparse", 1)]
    assert "o_norm" in params["layers"][1] and "o_norm" not in params[
        "layers"][0]
    with pytest.raises(ValueError, match="unknown layer type"):
        sala.layer_runs(cfg.replace(layer_types=("mamba",) * 5))


def test_the_cached_forwards_refuse_the_family(tiny):
    from ray_tpu.models import cached

    with pytest.raises(NotImplementedError, match="an attention half of its "
                                                   "own"):
        cached.init_cache(tiny[0], 1)
    with pytest.raises(NotImplementedError, match="several kinds"):
        cached.init_paged_cache(tiny[0], 4, 16)


def test_project_norms_a_head_where_the_config_asks(tiny):
    cfg, params, _ = tiny
    lp = jax.tree.map(lambda a: a[0], params["layers"][0])
    lp = dict(lp, q_norm=jnp.linspace(0.5, 1.5, cfg.head_dim))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model))
    q = llama._project(h, lp, cfg, "wq", cfg.n_heads, "q_norm")
    raw = (h @ lp["wq"]).reshape(1, 8, cfg.n_heads, cfg.head_dim)
    want = raw * jax.lax.rsqrt(jnp.mean(raw * raw, -1, keepdims=True)
                               + cfg.norm_eps) * lp["q_norm"]
    np.testing.assert_allclose(q, want, rtol=1e-5, atol=1e-6)
    # v takes none; a config that asks for none norms nothing
    np.testing.assert_allclose(
        llama._project(h, lp, cfg, "wv", cfg.n_kv_heads),
        (h @ lp["wv"]).reshape(1, 8, cfg.n_kv_heads, cfg.head_dim))
    np.testing.assert_allclose(llama._project(
        h, lp, cfg.replace(qk_head_norm=False), "wq", cfg.n_heads, "q_norm"), raw)


def test_plans_are_said_once_a_traced_body(tiny, monkeypatch):
    from ray_tpu.util import tracing

    cfg, params, tokens = tiny
    seen = []
    monkeypatch.setattr(tracing, "plan",
                        lambda name, attrs: seen.append((name, attrs)))
    jax.jit(lambda p: sala.loss_fn(p, {"tokens": tokens}, cfg.replace(
        ssd_impl="pallas"))[0]).lower(params)
    said = {n: a for n, a in seen}
    assert said["sala.select_plan"]["kernels"] == 63
    assert said["sala.select_plan"]["blocks"] == 16
    assert said["sala.select_plan"]["forced"] == 3
    assert said["sala.select_plan"]["exact"] is True
    scan = said["ssd.plan"]
    assert (scan["layout"], scan["decay"], scan["heads_per_block"],
            scan["path"], scan["groups"]) == ("wide", "steady", 4, "pallas",
                                              4)
    assert said["hybrid.layer_plan"]["pattern"] \
        == "sparse x1, lightning x3, sparse x1"


# --- the selection against a sort on hand-made scores -----------------------
CFG = sala.SalaConfig(sparse_block=8, sparse_stride=2, sparse_kernel=4,
                      sparse_topk=4, sparse_init_blocks=1, sparse_window=16)


def _by_sort(scores, t, cfg):
    """The rule by a stable sort, in numpy."""
    scores = np.asarray(scores, np.float64)
    out = np.zeros(scores.shape, np.int8)
    for r, q in enumerate(np.asarray(t)):
        own = q // cfg.sparse_block
        ranked = scores[r].copy()
        for b in range(scores.shape[1]):
            forced = b < cfg.sparse_init_blocks or \
                own - cfg.sparse_window // cfg.sparse_block < b <= own
            ranked[b] = np.inf if forced else ranked[b]
            if b > own:
                ranked[b] = -np.inf
        order = np.argsort(-ranked, kind="stable")[:cfg.sparse_topk]
        out[r, [b for b in order if b <= own]] = 1
    return out


@pytest.mark.parametrize("kind", ["random", "ties", "zeros", "late best"])
def test_top_blocks_is_the_rule_by_a_sort(kind):
    rows, blocks = 96, 12
    t = jnp.arange(rows)
    key = jax.random.PRNGKey(5)
    scores = {
        "random": jax.random.uniform(key, (rows, blocks)),
        "ties": jax.random.randint(key, (rows, blocks), 0, 3).astype(
            jnp.float32),
        "zeros": jnp.zeros((rows, blocks)),
        "late best": jnp.broadcast_to(jnp.arange(blocks, dtype=jnp.float32),
                                      (rows, blocks)),
    }[kind]
    got = np.asarray(sala.top_blocks(scores, t, CFG))
    np.testing.assert_array_equal(got, _by_sort(scores, t, CFG))
    held = got.sum(axis=-1)
    np.testing.assert_array_equal(
        held, np.minimum(CFG.sparse_topk, np.arange(rows) // 8 + 1))
    # the forced blocks: the first, the query's own and the one before it
    own = np.arange(rows) // 8
    assert got[np.arange(rows), own].all() and got[:, 0].all()
    assert got[np.arange(8, rows), own[8:] - 1].all()


def test_fewer_blocks_than_the_set_holds_are_all_kept():
    t = jnp.arange(24)
    got = np.asarray(sala.top_blocks(jnp.zeros((24, 3)), t, CFG))
    np.testing.assert_array_equal(
        got, (np.arange(3)[None, :] <= (np.arange(24) // 8)[:, None]))


def test_block_scores_pool_five_kernels_at_stride_four():
    # 15 kernels -> 4 blocks: kernels 4 b - 1 .. 4 b + 3, the edges cut
    scores = jnp.asarray([[3., 0, 0, 9, 0, 0, 0, 0, 1, 0, 0, 0, 0, 0, 7]])
    got = sala.block_scores(scores, CFG)
    np.testing.assert_array_equal(got, [[9., 9., 1., 7.]])
    pooled = sala.pooled_keys(jnp.arange(16, dtype=jnp.float32).reshape(
        1, 16, 1, 1), CFG)
    # kernels of 4 at stride 2 over 16 keys: 7, means 1.5, 3.5, ...
    np.testing.assert_allclose(pooled[0, :, 0, 0], 1.5 + 2 * np.arange(7))


def test_set_counts():
    sel = jnp.zeros((1, 1, 16, 2), jnp.int8).at[0, 0, :, 0].set(1)
    sel = sel.at[0, 0, 8:, 1].set(1)
    counts = sala.set_counts(sel, CFG)
    assert int(counts["sparse_blocks_selected"]) == 24
    # queries 0-7: 1..8 pairs; queries 8-15: block 0 whole and 1..8
    assert int(counts["sparse_pairs_selected"]) == 36 + 64 + 36
    assert int(counts["sparse_set_forced"]) == 24


def test_the_walks_pairs_are_the_ops_own_count(monkeypatch):
    # 2 q-blocks of 512 over 2 k-blocks: 3 tiles of the causal band a head
    q = jax.ShapeDtypeStruct((1, 1024, 4, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)
    sel = jax.ShapeDtypeStruct((1, 2, 1024, 16), jnp.int8)
    for call, tiles in (("fwd", 3), ("dq", 3), ("dkdv", 3)):
        said = sa.plan(B=1, H=4, S=1024, T=1024, D=128, dtype=jnp.bfloat16,
                       call=call, blocks=16, group=2)
        assert said["walk_tiles"] == 4 * tiles
    # plain jax.numpy (off the chip) computes every pair, the kernels'
    # walk the band's tiles whole: the counter follows the path taken
    assert sa.block_pairs_walked(q, k, sel) == 4 * 1024 * 1024
    monkeypatch.setattr(sa, "IMPL", "pallas")
    assert sa.block_pairs_walked(q, k, sel) == 4 * 3 * 512 * 512
    long = sa.plan(B=1, H=32, S=16384, T=16384, D=128, dtype=jnp.bfloat16,
                   call="fwd", blocks=256, group=16)
    assert long["walk_tiles"] == 32 * 528 == sa.plan(
        B=1, H=32, S=16384, T=16384, D=128, dtype=jnp.bfloat16, call="dkdv",
        blocks=256, group=16)["walk_tiles"]


def test_on_the_chip_a_set_the_kernels_do_not_take_is_refused(monkeypatch):
    # 12,288 keys in blocks of 64: 192 blocks a row, no whole lane tiles;
    # the plain path's scores there would be [B, KV, 16, S, T] float32
    q = jax.ShapeDtypeStruct((1, 12288, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 12288, 2, 128), jnp.bfloat16)
    sel = jax.ShapeDtypeStruct((1, 2, 12288, 192), jnp.int8)
    assert sa._block_path(q, k, sel) == "xla"           # off the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="T a multiple of 8192"):
        sa._block_path(q, k, sel)
    with pytest.raises(ValueError, match="whole lane tiles"):
        sa.block_sparse_attention(q, k, k, sel)
    sel = jax.ShapeDtypeStruct((1, 2, 16384, 256), jnp.int8)
    q, k = (jax.ShapeDtypeStruct((1, 16384, n, 128), jnp.bfloat16)
            for n in (32, 2))
    assert sa._block_path(q, k, sel) == "blocks"
    with pytest.raises(ValueError, match="want \\[B, KV, S, T / block\\]"):
        sa._block_path(q, k, jax.ShapeDtypeStruct((1, 2, 16384, 100),
                                                  jnp.int8))


# --- attention over a set of blocks: the kernels ------------------------------
def _random_sets(key, B, KV, S, block, share):
    nb = S // block
    own = (jnp.arange(S) // block)[:, None]
    j = jnp.arange(nb)[None, :]
    sel = (jax.random.uniform(key, (B, KV, S, nb)) < share) & (j <= own)
    return (sel | (j == own)).astype(jnp.int8)


def _plain(q, k, v, sel, block):
    B, S, H, D = q.shape
    KV = k.shape[2]
    keep = (jnp.repeat(sel, block, axis=-1) != 0) \
        & (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])
    s = jnp.einsum("bskgd,btkd->bkgst", q.reshape(B, S, KV, H // KV, D),
                   k) / D ** 0.5
    p = jax.nn.softmax(jnp.where(keep[:, :, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", p, v).reshape(B, S, H, D)


# (S, tile, block, heads, KV heads): 16 query heads a KV head as the model
# has them; tiles of one block and of several; two sequences
BLOCK_SETS = [(256, 64, 64, 16, 1), (256, 128, 64, 32, 2),
              (512, 128, 32, 4, 2), (384, 128, 64, 2, 2)]


@pytest.mark.parametrize("S,tile,block,H,KV", BLOCK_SETS)
def test_block_set_kernels_agree_with_a_plain_masked_softmax(
        S, tile, block, H, KV, monkeypatch):
    monkeypatch.setattr(sa, "IMPL", "pallas")
    monkeypatch.setattr(sa, "BLOCK_Q", tile)
    monkeypatch.setattr(sa, "BLOCK_K", tile)
    B, D = 2 if S == 384 else 1, 16
    ks = jax.random.split(jax.random.PRNGKey(S + H), 5)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    sel = _random_sets(ks[3], B, KV, S, block, 0.4)
    lanes = jax.random.normal(ks[4], (B, S, H, D))

    def ours(q, k, v):
        return (sa.block_sparse_attention(q, k, v, sel) * lanes).sum()

    def plain(q, k, v):
        return (_plain(q, k, v, sel, block) * lanes).sum()

    np.testing.assert_allclose(sa.block_sparse_attention(q, k, v, sel),
                               _plain(q, k, v, sel, block), atol=2e-5)
    np.testing.assert_allclose(sa._reference_blocks(q, k, v, sel, D ** -0.5),
                               _plain(q, k, v, sel, block), atol=2e-5)
    got = jax.grad(ours, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5 * float(
            jnp.abs(b).max()), err_msg="d" + name)


def test_block_set_plans_and_refusals():
    plans = {c: sa.plan(B=1, H=32, S=16384, T=16384, D=128,
                        dtype=jnp.bfloat16, call=c, blocks=256, group=16)
             for c in ("fwd", "dq", "dkdv")}
    for call, plan in plans.items():
        assert plan["path"] == "blocks" and plan["set_blocks"] == 256
        assert plan["group"] == 16 and plan["span"] > 1, plan
        assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan
    # a set of pairs says what it said
    assert "set_blocks" not in sa.plan(B=1, H=32, S=16384, T=16384, D=256,
                                       dtype=jnp.bfloat16, call="fwd")
    q = jnp.zeros((1, 128, 4, 8))
    with pytest.raises(ValueError, match="want \\[B, KV, S, T / block\\]"):
        sa.block_sparse_attention(q, q[:, :, :2], q[:, :, :2],
                                  jnp.zeros((1, 4, 128, 2), jnp.int8))
    with pytest.raises(ValueError, match="power of two"):
        sa.block_sparse_attention(q[:, :96], q[:, :96, :2], q[:, :96, :2],
                                  jnp.zeros((1, 2, 96, 4), jnp.int8))


# --- the wide scan ------------------------------------------------------------
def _recurrence(x, a, bm, cm):
    """s_t = exp(a) s_{t-1} + x_t B_t^T; y_t = s_t C_t, a token at a time."""
    B, S, H, P = x.shape

    def step(s, at):
        xt, bt, ct = at
        s = s * jnp.exp(a)[None, :, None, None] \
            + jnp.einsum("bhp,bhn->bhpn", xt, bt)
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, bm.shape[-1])), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, bm, cm)))
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("impl,B,S,H,chunk", [
    ("pallas", 1, 64, 8, 16), ("pallas", 2, 48, 4, 16),
    ("pallas", 1, 64, 6, 32), ("xla", 1, 64, 8, 16)])
def test_wide_scan_is_the_recurrence_at_heads_of_128(impl, B, S, H, chunk):
    P = 128
    ks = jax.random.split(jax.random.PRNGKey(H), 4)
    x = jax.random.normal(ks[0], (B, S, H, P))
    bm = jax.random.normal(ks[1], (B, S, H, P)) * 0.1
    cm = jax.random.normal(ks[2], (B, S, H, P)) * 0.1
    lanes = jax.random.normal(ks[3], (B, S, H, P))
    a = -sala.slopes(H)

    def ours(x, bm, cm):
        return (ssd.ssd_scan(x, None, a, bm, cm, chunk=chunk,
                             impl=impl) * lanes).sum()

    def plain(x, bm, cm):
        return (_recurrence(x, a, bm, cm) * lanes).sum()

    np.testing.assert_allclose(
        ssd.ssd_scan(x, None, a, bm, cm, chunk=chunk, impl=impl),
        _recurrence(x, a, bm, cm), atol=2e-5)
    got = jax.grad(ours, argnums=(0, 1, 2))(x, bm, cm)
    want = jax.grad(plain, argnums=(0, 1, 2))(x, bm, cm)
    for g, w, name in zip(got, want, ("x", "B", "C")):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()),
                                   err_msg="d" + name)


def test_scan_plans_and_refusals():
    wide = ssd.plan(S=16384, H=32, P=128, N=128, chunk=256,
                    dtype=jnp.bfloat16, impl="pallas", G=32, steady=True)
    assert (wide["layout"], wide["decay"], wide["heads_per_block"]) \
        == ("wide", "steady", 4)
    assert wide["vmem_bytes"] <= 16 * 2 ** 20
    pairs = ssd.plan(S=8192, H=64, P=64, N=128, chunk=128,
                     dtype=jnp.bfloat16, impl="pallas", G=8)
    assert (pairs["layout"], pairs["decay"], pairs["heads_per_block"]) \
        == ("pairs", "stepped", 8)
    x = jnp.zeros((1, 32, 3, 64))
    with pytest.raises(ValueError, match="an even number of them a group"):
        ssd.ssd_scan(x, jnp.ones((1, 32, 3)), -jnp.ones((3,)),
                     jnp.zeros((1, 32, 3, 16)), jnp.zeros((1, 32, 3, 16)),
                     chunk=16, impl="pallas")
    with pytest.raises(ValueError, match="a constant decay at 4 heads of 64 "
                                         "in 2 groups"):
        ssd.ssd_scan(jnp.zeros((1, 32, 4, 64)), None, -jnp.ones((4,)),
                     jnp.zeros((1, 32, 2, 16)), jnp.zeros((1, 32, 2, 16)),
                     chunk=16, impl="pallas")


# --- the calls the other families make are the calls they made ---------------
def _text(fn, *shapes) -> str:
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*shapes)).encode()) \
        .hexdigest()[:16]


def _scan_text(B, S, H, P, N, G, chunk):
    f32, bf16 = jnp.float32, jnp.bfloat16
    bm = jax.ShapeDtypeStruct((B, S, N) if G == 1 else (B, S, G, N), bf16)

    def loss(x, dt, a, bm, cm):
        return ssd.ssd_scan(x, dt, a, bm, cm, chunk=chunk,
                            impl="pallas").astype(f32).sum()

    return _text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                 jax.ShapeDtypeStruct((B, S, H, P), bf16),
                 jax.ShapeDtypeStruct((B, S, H), f32),
                 jax.ShapeDtypeStruct((H,), f32), bm, bm)


@pytest.mark.parametrize("cell,shape,text", [
    ("granite", (2, 8192, 128, 64, 128, 1, 256), "62709ec55e03b8a7"),
    ("nemotron", (2, 8192, 64, 64, 128, 8, 128), "838a890bda80f650")])
def test_the_mamba_cells_scans_trace_to_their_parents_text(cell, shape, text):
    """The jaxpr of the scan's forward and backward (the kernels' bodies in
    it) at the cell's shape, hashed on the commit before this family."""
    assert _scan_text(*shape) == text


def test_the_glm52_cells_sparse_calls_trace_to_their_parents_text(
        monkeypatch):
    monkeypatch.setattr(sa, "IMPL", "pallas")
    q = jax.ShapeDtypeStruct((1, 16384, 32, 256), jnp.bfloat16)
    keep = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8)

    def loss(q, k, v, keep):
        o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
        return o.astype(jnp.float32).sum(), p

    assert _text(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True),
                 q, q, q, keep) == "3d719fc8bb621aa4"
