"""Ask the TPU's compiler, without a chip: attention over latents and sets.

The GLM-5.2 cell's whole step, its attention over a learned set and its
indexer's selection; one latent-attention half at the GLM-4.7-Flash cell's
widths (what streams, what the layout bought, its scopes)
(``models/latent.py``, ``ops/sparse_attention.py``); the MiniCPM-SALA cell's
step, its attention over a set of blocks and its wide scan
(``models/sala.py``); and the digests of the plans PR 49 left alone, the
sparse calls' among them.

Compiles against a described (device-less) v5e; ``tests/described_tpu.py``
has the fixtures, the helpers and the rule that put each case where it is.
"""

import pytest

from described_tpu import _compile_cell_step, _computations, _entry_ops, _sds


# --- GLM-5.2: attention over a learned set (ops/sparse_attention.py) -------
GLM52_ATTENTION = (1, 16384, 32, 256)      # the cell's B, S, heads held, D


def test_glm52_step_fits_with_its_set_kept_and_selects_once(
        topo, on_chip_branch, monkeypatch):
    """The GLM-5.2 cell's step at 32 heads held, a stack a layer: the plan
    stays under 13.73e9 bytes (13,246,264,320 when this was written,
    13,276,408,320 before LI's gradient was made in the forward and
    14,093,904,384 with it made there a block wherever the scheduler
    liked: ``latent._index_loss_fwd``'s barriers; whole runs as stacks
    planned 17,861,688,832, over the chip) and XLA
    rematerializes nothing of its own; the layer checkpoint keeps each
    full layer's set so that no replay selects again (the selection's
    counting loop is in the program twice, once a full layer, not four
    times) and LI's gradients, so that the head-mean probabilities are
    computed twice a step, once a full layer, and the backward scan holds
    of LI its gradients' scaling and no loop or product; every kind of
    Mosaic call is there."""
    compiled, plan, said = _compile_cell_step(
        "train-glm52-ep32-s16384-b1", topo, monkeypatch)
    assert [(p["kept"], p["why"]) for p in said] == [("", "no room")]
    assert plan <= 13.73e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    # (LI is computed a sequence at a time: its scope stands under vmap)
    for scope in ("sparse.fwd.mask", "sparse.probs.mask", "sparse.dq.mask",
                  "sparse.dkdv.mask", "attention/indexer",
                  "attention/select", "attention/vmap(index_loss)"):
        assert scope in text, scope
    lines = text.splitlines()
    replayed = [ln for ln in lines
                if "rematted_computation/attention/select" in ln
                and "while" in ln]
    assert not replayed, replayed[:2]
    calls = {scope: sum("tpu_custom_call" in ln and scope in ln
                        for ln in lines)
             for scope in ("sparse.fwd.mask", "sparse.probs.mask")}
    assert calls == {"sparse.fwd.mask": 5, "sparse.probs.mask": 2}, calls
    late = [ln for ln in lines if "transpose(jvp(layers))" in ln
            and "vmap(index_loss)" in ln]
    assert late and not [ln for ln in late if " while(" in ln
                         or "cjd,td->cjt" in ln
                         or "rematted_computation/attention/vmap" in ln], \
        late[:2]


_GLM_BLOCK = {}    # the compiled attention half, shared by its two tests


def _glm_attention_block(one_chip):
    """(cfg, B, S, compiled text, {instant: [attributes]}) of ONE latent-
    attention half at the GLM-4.7-Flash cell's widths (B2 x S8192, 20 heads
    of 192 + 64 over latents of 768 and 512): forward, replay under
    ``jax.checkpoint`` and backward, compiled for the chip."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import latent, llama

    if _GLM_BLOCK:
        return _GLM_BLOCK["block"]
    tracing = importlib.import_module("ray_tpu.ops.flash_attention").tracing
    plans, instant = {}, tracing.instant
    tracing.instant = lambda name, attrs=None, **kw: plans.setdefault(
        name, []).append(attrs)
    bf = jnp.bfloat16
    cfg = latent.LatentConfig(
        vocab_size=19360, d_model=2048, n_layers=2, n_heads=20, n_kv_heads=20,
        d_ff=1536, n_experts=64, top_k=4, experts_held=(8, 0),
        shared_d_ff=1536, q_rank=768, kv_rank=512, qk_nope_dim=192,
        qk_rope_dim=64, v_dim=256, dense_d_ff=10240, rope_theta=1e6,
        attn_impl="flash", dtype=bf, param_dtype=bf)
    B, S = 2, 8192
    stack = jax.eval_shape(
        lambda: latent.init_params(jax.random.PRNGKey(0), cfg))["layers"][0]
    lp = {k: _sds(stack[k].shape[1:], bf, one_chip) for k in (
        "attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
        "wkv_b", "wo")}
    cos, sin = llama._pair_tables(cfg.rope_theta, S, cfg.rope_dim)

    def half(x, lp):
        with jax.named_scope("attention"):      # as llama._layer opens it
            return latent.attention_half(x, lp, cfg, cos, sin)[0]

    def loss(x, lp):
        y = jax.checkpoint(half)(x, lp)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            _sds((B, S, cfg.d_model), bf, one_chip), lp).compile().as_text()
    finally:
        tracing.instant = instant
    _GLM_BLOCK["block"] = (cfg, B, S, text, plans)
    return _GLM_BLOCK["block"]


def test_latent_attention_block_at_glm_widths_streams_and_fits(
        one_chip, on_chip_branch, monkeypatch):
    """One latent-attention half at the GLM-4.7-Flash cell's widths,
    forward, replay under ``jax.checkpoint`` and backward, compiled for the
    chip: all three flash calls take their streaming plan by the bytes
    alone (``flash.fwd_plan``, ``flash.bwd_plan``), Mosaic accepts them in
    the VMEM a call gets without asking, and the calls keep the face the
    readers know them by (``benchmark/readers/glm_kernel_roofline.py``).
    The loop kernel at this shape is what the compiler refuses."""
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmark.readers import kernel_roofline

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    _, B, S, text, plans = _glm_attention_block(one_chip)
    calls = sorted(kernel_roofline.signature(ln) for ln in text.splitlines()
                   if kernel_roofline.signature(ln) is not None)
    assert calls == [(1, 6), (2, 3), (2, 3), (2, 6)], calls
    assert {a["path"] for a in plans["flash.fwd_plan"]} == {"stream"}
    assert plans["flash.fwd_plan"][0]["kv_block_bytes"] == 16 * 2 ** 20
    assert [plans["flash.fwd_plan"][0][n] for n in ("span", "in_flight")] == [
        4096, 2]
    back = plans["flash.bwd_plan"][0]
    assert back["path"] == "stream" and back["dq_path"] == "stream"
    assert (back["dq_span"], back["dq_in_flight"]) == (4096, 1)
    assert back["resident_bytes"] > fa._vmem_bytes() // 4
    assert plans["mla.plan"][0]["k_bytes"] == B * S * 20 * 256 * 2
    # what the bytes say, the compiler says: the loop kernel does not fit
    monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES", 2 ** 40)
    q = _sds((B, S, 20, 256), jnp.bfloat16, one_chip)
    with pytest.raises(Exception, match="(?i)vmem|memory|exceed"):
        jax.jit(lambda q, k, v: fa.flash_attention(q, k, v)).lower(
            q, q, q).compile()


def test_a_latent_attention_halfs_passes_at_glm_widths(one_chip,
                                                       on_chip_branch):
    """The same compiled block, held to what the layout bought (PR 35):
    what tells a later refactor that it brought a pass back. The
    projections write q, k and v where the kernel reads them, ``[B, H, S,
    .]`` with a head's lanes minor, so between fusions there is no result
    of 1 or 63 lanes (the rotary's rolled pairs), no float32 array of the
    heads' rotary lanes and no bf16 ``[B, S, H, nope + v]`` (K and V in one
    array). The non-matmul fusions move 1.64 GB, under the form's own
    account (``mla.plan``: 2 x ``hbm_bytes_fwd`` + ``hbm_bytes_bwd`` = 3.00
    GB); with the ops left outside every fusion (0.61 GB: the broadcast of
    the log-sum-exp over 128 lanes for the kernels, pads of the tables)
    2.25 GB, 17.6% of the parent's 12.76 GB (my compile of ``94745a7``:
    fusions 6.37, copies 3.81, slices 2.02, broadcasts 0.52, a pad 0.04;
    the layout chosen there had the sequence minor in the projections'
    results, so every view by head was a slice and a relayout copy)."""
    import re

    cfg, B, S, text, plans = _glm_attention_block(one_chip)
    H, dn, R, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                    cfg.v_dim)
    ops = _entry_ops(text)
    gone = re.compile(
        rf"\[{B},{S},{H},(1|{R - 1})\]|\[{B},{H},{S},{R - 1}\]"
        rf"|f32\[{B},({S},{H}|{H},{S}),{R}\]"
        rf"|bf16\[{B},{S},({H},{dn + dv}|{H * (dn + dv)})\]")
    back = [(op, result[:200]) for op, result, *_ in ops
            if gone.search(result)]
    assert not back, back
    passes = sum(r + w for op, _, r, w, matmul in ops
                 if op == "fusion" and not matmul)
    plan = plans["mla.plan"][0]
    assert (plan["rope"], plan["kv"]) == ("projected", "split_weights")
    assert plan["extra_columns"] == 21 * 64
    assert passes < 1.8e9, passes
    assert passes < 2 * plan["hbm_bytes_fwd"] + plan["hbm_bytes_bwd"] \
        < 3.1e9, plan
    alone = sum(r + w for op, _, r, w, _ in ops if op in (
        "copy", "slice", "broadcast", "pad", "concatenate", "convert",
        "transpose"))
    assert passes + alone < 2.5e9 < 0.6 * 12.76e9, (passes, alone)


def test_latent_block_keeps_its_scopes_through_the_chips_compiler(
        one_chip, on_chip_branch):
    """The same compiled block, read as a chip trace's labels are
    (``benchmark/op_scopes.py``; an instruction's ``op_name`` is the
    ``tf_op`` of its events): the TPU compiler's fusion and layout passes
    leave the scope ``attention`` on every fusion that holds a convolution
    and on every Mosaic call, each call in the kernel-call scope of the
    plan it took, and forward, replay and backward are all there. What
    carries no ``op_name`` at all is the compiler's own (copies, bitcasts,
    tuple plumbing): its share of the entry's instructions is printed."""
    import re

    from benchmark import op_scopes

    comps = _computations(_glm_attention_block(one_chip)[3])
    entry = [ln for ln in comps["ENTRY"] if " = " in ln]
    matmuls, calls, passes, bare = 0, {}, set(), 0
    for ln in entry:
        name = re.search(r'op_name="([^"]*)"', ln)
        parts = op_scopes.elements(name.group(1) if name else "")
        bare += name is None
        body = re.search(r" fusion\(.*calls=%?([\w.\-]+)", ln)
        if body and any(" convolution(" in b for b in comps[body.group(1)]):
            matmuls += 1
            assert op_scopes.bucket(parts) == "attention", ln[:300]
            passes.add(op_scopes.which_pass(parts))
        if 'custom_call_target="tpu_custom_call"' in ln:
            assert op_scopes.bucket(parts) == "attention", ln[:300]
            calls[op_scopes.kernel_scope(parts)] = \
                calls.get(op_scopes.kernel_scope(parts), 0) + 1
    assert matmuls >= 20 and passes == {"forward", "replay", "backward"}, (
        matmuls, passes)
    # the replay makes the forward call again here: nothing of this
    # block's checkpoint keeps ``o`` and ``lse`` by name
    assert calls == {"flash.fwd.stream": 2, "flash.dq.stream": 1,
                     "flash.dkdv.stream": 1}, calls
    print(f"latent block: {bare} of {len(entry)} entry instructions carry "
          f"no op_name ({100.0 * bare / len(entry):.1f}%)")


def test_sparse_attention_calls_compile_at_the_glm52_cells_shape(
        one_chip, on_chip_branch):
    """The four Mosaic calls of the attention over a set (forward, the
    head-mean probabilities, dQ, dK/dV) lower for a v5e at the cell's
    shape, sets of 2,048 as a 0/1 int8 square, within the 16 MiB a call
    gets that asks for no more; the plans' fields are what the trace and
    the roofline reader go by."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention as sa

    B, S, H, D = GLM52_ATTENTION
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    keep = _sds((B, S, S), jnp.int8, one_chip)

    def loss(q, k, v, keep):
        o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
        return o.astype(jnp.float32).sum(), p

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                      has_aux=True)).lower(
        q, q, q, keep).compile().as_text()
    assert text.count("tpu_custom_call") == 4, text[:2000]
    for scope in ("sparse.fwd.mask", "sparse.probs.mask", "sparse.dq.mask",
                  "sparse.dkdv.mask"):
        assert scope in text, scope
    for call in ("fwd", "probs", "dq", "dkdv"):
        plan = sa.plan(B=B, H=H, S=S, T=S, D=D, dtype=jnp.bfloat16,
                       call=call)
        assert {"path", "call", "block_q", "block_k", "span", "in_flight",
                "vmem_bytes", "grid_steps", "live_steps"} <= set(plan)
        assert plan["span"] > 1, plan     # a span of blocks a grid step
        assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


def test_index_score_blocks_and_the_selection_fit_at_the_cells_shape(
        one_chip, no_persistent_cache):
    """One block of the indexer's scores and its exact selection at the
    cell's shape (2,048 queries' 32 heads of 128 over all 16,384 keys):
    the per-head products of 256 queries are alive at once, not the
    block's (4.3e9 bytes), and no sort is in the program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import latent

    rows, S, IH, ID, topk = 2048, 16384, 32, 128, 2048

    def block(qI, w, kI):
        return latent.select(latent.index_scores(qI, w, kI), S - rows,
                             topk)

    compiled = jax.jit(block).lower(
        _sds((rows, IH, ID), jnp.bfloat16, one_chip),
        _sds((rows, IH), jnp.float32, one_chip),
        _sds((S, ID), jnp.bfloat16, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert " sort(" not in text and "topk" not in text.lower()


# --- MiniCPM-SALA: attention over a set of blocks, the wide scan ------------
SALA_ATTENTION = (1, 16384, 32, 2, 128)    # the cell's B, S, H, KV, D


def test_sala_step_fits_with_its_set_kept_and_selects_once(
        topo, on_chip_branch, monkeypatch):
    """The MiniCPM-SALA cell's step (one sparse layer, three lightning
    layers, the whole vocabulary): the sparse layer, a run of its own,
    keeps its SwiGLU's gate and up (1.07e9 bytes: the estimate reads
    12.39e9 of the 14.37e9 the rule leaves; the lightning layers' stack of
    three would need 2.42e9 a name), the plan stays under that ceiling
    (12,512,892,928 since PR 64 took the split rotary's float32 halves out
    of the lightning layers' body, 11,439,376,896 with nothing kept;
    13,769,958,400 before, 12,696,442,368 then with nothing kept), XLA
    rematerializes nothing of its own, the layer
    checkpoint keeps the sparse layer's set so that the replay selects
    nothing (the selection's top-k is in the program once), and every kind
    of Mosaic call is there."""
    compiled, plan, said = _compile_cell_step(
        "train-minicpmsala-l4-s16384-b1", topo, monkeypatch)
    assert [(p["kept"], p["by_run"], p["kept_bytes"], p["why"])
            for p in said] == [
        ("ffn_gate,ffn_up", "ffn_gate+ffn_up,-", 2 * 16384 * 16384 * 2,
         "room")]
    assert 12.0e9 < plan < 14.37e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    for scope in ("sparse.fwd.blocks", "sparse.dq.blocks",
                  "sparse.dkdv.blocks", "ssd.fwd.pallas", "ssd.bwd.pallas",
                  "attention/sparse/block_select",
                  "attention/lightning/scan"):
        assert scope in text, scope
    lines = text.splitlines()
    replayed = [ln for ln in lines
                if "rematted_computation/attention/sparse/block_select" in ln]
    assert not replayed, replayed[:2]
    calls = {scope: sum("tpu_custom_call" in ln and scope in ln
                        for ln in lines)
             for scope in ("sparse.fwd.blocks", "ssd.fwd.pallas",
                           "ssd.bwd.pallas")}
    # the sparse forward once (o and lse are kept); the scan's forward in
    # the forward and again in the replay, one body a run of three layers
    assert calls == {"sparse.fwd.blocks": 1, "ssd.fwd.pallas": 2,
                     "ssd.bwd.pallas": 1}, calls


def test_block_set_calls_compile_at_the_sala_cells_shape(one_chip,
                                                         on_chip_branch):
    """The three Mosaic calls of attention over a set of blocks a KV group
    (forward, dQ, dK/dV with the group's 16 heads innermost) lower for a
    v5e at the cell's shape, the set [1, 2, 16384, 256] int8, within the
    16 MiB a call gets that asks for no more."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention as sa

    B, S, H, KV, D = SALA_ATTENTION
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    k = _sds((B, S, KV, D), jnp.bfloat16, one_chip)
    sel = _sds((B, KV, S, S // sa.SET_BLOCK), jnp.int8, one_chip)

    def loss(q, k, v, sel):
        return sa.block_sparse_attention(q, k, v, sel).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k, sel).compile().as_text()
    assert text.count("tpu_custom_call") == 3, text[:2000]
    for scope in ("sparse.fwd.blocks", "sparse.dq.blocks",
                  "sparse.dkdv.blocks"):
        assert scope in text, scope
    for call in ("fwd", "dq", "dkdv"):
        plan = sa.plan(B=B, H=H, S=S, T=S, D=D, dtype=jnp.bfloat16,
                       call=call, blocks=S // sa.SET_BLOCK, group=H // KV)
        assert plan["path"] == "blocks" and plan["span"] > 1, plan
        assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


@pytest.mark.parametrize("chunk", [128, 256])
def test_ssd_scan_compiles_at_lightning_widths(chunk, one_chip,
                                               on_chip_branch):
    """The wide scan (32 heads of 128 with keys of their own, a constant
    decay a head, the rates in SMEM) lowers for a v5e at the cell's shape:
    two Mosaic calls, no [B, S, H] array of steps among their operands."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    B, S, H, _, P = SALA_ATTENTION
    x = _sds((B, S, H, P), jnp.bfloat16, one_chip)
    a = _sds((H,), jnp.float32, one_chip)

    def loss(x, bm, cm, a):
        return ssd.ssd_scan(x, None, a, bm, cm, chunk=chunk,
                            impl="pallas").astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, a).compile().as_text()
    assert text.count("tpu_custom_call") == 2, text[:2000]
    assert f"f32[{B},{S},{H}]" not in text
    plan = ssd.plan(S=S, H=H, P=P, N=P, chunk=chunk, dtype=jnp.bfloat16,
                    impl="pallas", G=H, steady=True)
    assert plan["layout"] == "wide" and plan["heads_per_block"] == 4, plan
    assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


def test_delta_rule_calls_compile_at_the_ling_cells_shape(one_chip,
                                                          on_chip_branch):
    """The chunked gated delta rule (32 heads of 128 keys and values over
    16,384 steps, a gate a key channel in float32, chunks of 64, the
    block's heads two to an inverse of side 128) lowers for a v5e at the
    Ling-3.0-flash cell's shape: exactly two Mosaic calls with the operands
    and results ``benchmark/readers/ling_kernel_roofline.py`` tells them by
    (forward 6 -> 2, backward 7 -> 6, q, k, v first at [1, 16384, 4096]),
    the chunks' incoming states the only state among their results, and
    what one instance holds in VMEM under the plan's count."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta_rule as dr

    B, S, H, d = 1, 16384, 32, 128
    x = _sds((B, S, H, d), jnp.bfloat16, one_chip)
    g = _sds((B, S, H, d), jnp.float32, one_chip)
    beta = _sds((B, S, H), jnp.float32, one_chip)

    def loss(q, k, v, g, beta):
        return dr.gated_delta_rule(q, k, v, g, beta,
                                   impl="pallas").astype(jnp.float32).sum()

    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    text = both.lower(x, x, x, g, beta).compile().as_text()
    # part (e) of the cell's ``correct`` runs the calls on float32 q, k, v
    # too: twice the blocks in VMEM, and they fit
    both.lower(g, g, g, g, beta).compile()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2, text[:2000]
    wide = f"[{B},{S},{H * d}]"
    read = []
    for line in calls:
        results, operands = re.search(
            r"= (.*?) custom-call\((.*?)\), custom_call_target", line).groups()
        shapes = re.findall(r"\w+(\[[\d,]*\])", line.split(
            "operand_layout_constraints={")[1].split("}}")[0])
        assert shapes[:3] == [wide] * 3, shapes
        read.append((operands.count("%"), results.count("[")))
    assert sorted(read) == [(6, 2), (7, 6)], read
    plan = dr.plan(B=B, S=S, H=H, dk=d, dv=d, dtype=jnp.bfloat16,
                   impl="pallas")
    assert plan["chunk"] == 64 and plan["inverse_side"] == 128
    assert f"f32[{B},{S // 64},{H * d},{d}]" in text
    assert plan["state_bytes_kept"] == 536_870_912
    assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


def test_delta_rule_calls_compile_at_the_solar_cells_shape(one_chip,
                                                           on_chip_branch):
    """The delta rule told NO bound on its gate (the cut of the pair
    products in halves: six levels a chunk, no factor over 1; since PR 66
    the three smallest by shifted multiply-adds on the vector unit, seven
    unrolled subdiagonals each way, the three others a product of the odd
    halves' rows gathered as whole tiles) at the Solar-Open2 cell's shape, 64 heads of 128 over 16,384
    steps: exactly two Mosaic calls with the signatures the bounded cut has
    (forward 6 -> 2, backward 7 -> 6, q, k, v first at [1, 16384, 8192]:
    ``benchmark/readers/solar_kernel_roofline.py`` tells them by these),
    in bfloat16 and in float32 (part (e) of the cell's ``correct``), the
    chunks' incoming states the only state among their results (1.07 GB),
    and what one instance holds in VMEM under the plan's count."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta_rule as dr

    B, S, H, d = 1, 16384, 64, 128
    x = _sds((B, S, H, d), jnp.bfloat16, one_chip)
    g = _sds((B, S, H, d), jnp.float32, one_chip)
    beta = _sds((B, S, H), jnp.float32, one_chip)

    def loss(q, k, v, g, beta):
        return dr.gated_delta_rule(q, k, v, g, beta, impl="pallas",
                                   lower_bound=None).astype(jnp.float32).sum()

    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    text = both.lower(x, x, x, g, beta).compile().as_text()
    both.lower(g, g, g, g, beta).compile()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2, text[:2000]
    wide = f"[{B},{S},{H * d}]"
    read = []
    for line in calls:
        results, operands = re.search(
            r"= (.*?) custom-call\((.*?)\), custom_call_target", line).groups()
        shapes = re.findall(r"\w+(\[[\d,]*\])", line.split(
            "operand_layout_constraints={")[1].split("}}")[0])
        assert shapes[:3] == [wide] * 3, shapes
        read.append((operands.count("%"), results.count("[")))
    assert sorted(read) == [(6, 2), (7, 6)], read
    plan = dr.plan(B=B, S=S, H=H, dk=d, dv=d, dtype=jnp.bfloat16,
                   impl="pallas", lower_bound=None)
    assert plan["cut"] == "halving" and plan["chunk"] == 64 \
        and plan["inverse_side"] == 128 and plan["vector_levels"] == 3
    assert plan["mxu_rows_fwd"] < 768 * 6 and plan["mxu_rows_bwd"] < 13568
    assert f"f32[{B},{S // 64},{H * d},{d}]" in text
    assert plan["state_bytes_kept"] == 1_073_741_824
    assert plan["hbm_bytes_per_head"] * H == 5_377_097_728
    assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


# The flash calls of the cells that do NOT stream, and the four calls of the
# attention over a set at the GLM-5.2 cell's shape: sha256[:16] of the jaxpr
# of the call and its gradient (the kernels' bodies are in it), taken at PR
# 49's PARENT (274de6d, jax JAXPRS_FROM). PR 49 rebuilt the stream plans of
# ``ops/flash_attention.py`` and moved ``_span_walk`` there; whoever changes
# those files next and means to leave a plan alone finds out here, without
# unpacking a parent (the recipe PR 39 and PR 46 ran by hand). A digest that
# moves with a change that MEANS to change the plan is replaced, and says so.
# (batch, seq, heads, kv heads, head width, stated scale, window)
JAXPRS_FROM = "0.9.0"


PARENT_FLASH_JAXPRS = {
    "train-deepseek7b-l8": (
        (3, 4096, 32, 32, 128, None, None), "a29ac8cac53c77ad"),
    "train-deepseek7b-fsdp2tp2": (
        (2, 4096, 16, 16, 128, None, None), "3dae55e1e888278a"),
    "train-olmoe1b7b-s4096-b4": (
        (4, 4096, 16, 16, 128, None, None), "79bbd65e4a7c0ae1"),
    "train-granite4hs-ep8-s8192-b2": (
        (2, 8192, 32, 8, 128, 0.0078125, None), "dae59988254d481e"),
    "train-nemotron3nano-ep8-s8192-b2": (
        (2, 8192, 32, 2, 128, None, None), "549b8bf55f644cd8"),
    "train-mellum2-ep4-s16384-b1/window": (
        (1, 16384, 32, 4, 128, None, 1024), "f0738639ded60b83"),
    "train-commandaplus-ep16-s8192-b1/full": (
        (1, 8192, 32, 2, 128, None, None), "9ac5c76f7f7ff904"),
    "train-commandaplus-ep16-s8192-b1/window": (
        (1, 8192, 32, 2, 128, None, 4096), "c64410a606bd06ce"),
    "train-glm52-ep32-s16384-b1/sparse": (None, "b598c4e3fb200c19"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_FLASH_JAXPRS))
def test_the_plans_pr49_left_alone_trace_to_its_parents_programs(
        cell, on_chip_branch):
    """loop / resident / band at the seven other cells' shapes, and the
    sparse forward, head-mean probabilities, dQ and dK/dV: to the
    character."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention as sa
    from ray_tpu.ops.flash_attention import flash_attention

    shape, want = PARENT_FLASH_JAXPRS[cell]
    if shape is None:
        B, S, H, D = GLM52_ATTENTION
        q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
        args = (q, q, q, jax.ShapeDtypeStruct((B, S, S), jnp.int8))

        def loss(q, k, v, keep):
            o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
            return o.astype(jnp.float32).sum() + p.sum()
    else:
        B, S, H, KV, D, scale, window = shape
        kv = jax.ShapeDtypeStruct((B, S, KV, D), jnp.bfloat16)
        args = (jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16), kv, kv)

        def loss(q, k, v):
            return flash_attention(q, k, v, scale=scale, window=window
                                   ).astype(jnp.float32).sum()

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    assert "pallas_call" in str(closed)
    if jax.__version__ == JAXPRS_FROM:
        text = re.sub(r" at 0x[0-9a-f]+", "", str(closed))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want
