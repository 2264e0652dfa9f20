"""Ask the TPU's compiler, without a chip: the expert layer's cells.

The Mellum2 and the Command A+ cells' whole steps (``models/moe.py`` with
layers of several kinds), one expert layer that holds a share of its experts
(the walks of a pass, at Granite's widths and Mellum2's) and the grouped
matmuls at every cell's widths (``ops/grouped_matmul.py``).

Compiles against a described (device-less) v5e; ``tests/described_tpu.py``
has the fixtures, the helpers and the rule that put each case where it is.
"""

import pytest

from described_tpu import _compile_cell_step, _sds, _while_bodies


def test_mellum2_step_keeps_q_beside_k_and_v(topo, on_chip_branch,
                                             monkeypatch):
    """The Mellum2 cell's step with passes of 49,152 rows: the estimate
    (11.17e9) leaves room for q beside k and v in the three stacks of
    window layers and for k and v in the three full layers, runs of one
    among stacks that leave q to their replay (1.61e9 bytes over twelve
    layers; at 65,536 rows it kept k and v alone and planned
    12,806,373,888), the plan stays under 15.0e9 (14,212,359,168 when this
    was written; with q kept in the full layers too 14,549,401,600 until
    PR 64 and 17,207,286,272 with its rotary, over what the chip states:
    ``remat.LEFT_BY_ONE_AMONG_STACKS``; k and v alone 11,997,049,856; with
    q kept at the OLD pass it compiled to 15.10e9, PR 43) and XLA
    rematerializes nothing of its own."""
    compiled, plan, said = _compile_cell_step(
        "train-mellum2-ep4-s16384-b1", topo, monkeypatch)
    assert [(p["kept"], p["kept_bytes"], p["by_run"], p["why"])
            for p in said] == [
        ("attn_q,attn_k,attn_v", 1_610_612_736,
         ",".join(["attn_q+attn_k+attn_v", "attn_k+attn_v"] * 3), "room")]
    assert 11.0e9 < plan <= 15.0e9, plan
    assert compiled.as_text().count(".remat") == 0


def test_command_a_plus_step_keeps_its_names_and_fits(topo, on_chip_branch,
                                                      monkeypatch):
    """The Command A+ cell's step with the names its plan keeps (all five:
    q, k, v, the shared SwiGLU's gate and up; 2.45e9 bytes over four
    layers): the plan stays under 12.0e9 (9,282,964,480 with none kept;
    10,938,735,616 when this was written), XLA rematerializes nothing of
    its own, and no checkpoint body computes a shared product again (the
    parent's held 8: gate and up, a layer)."""
    compiled, plan, said = _compile_cell_step(
        "train-commandaplus-ep16-s8192-b1", topo, monkeypatch)
    assert [(p["kept"], p["kept_bytes"], p["why"]) for p in said] == [
        ("attn_q,attn_k,attn_v,shared_gate,shared_up", 2_449_473_536,
         "room")]
    assert 9.3e9 < plan <= 12.0e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    assert text.count("tpu_custom_call") == 100
    replayed = [ln for ln in text.splitlines() if "rematted_computation/"
                "feed_forward/shared/dot_general" in ln]
    assert not replayed, replayed[:2]
    assert "checkpoint/feed_forward/shared/dot_general" in text


# widths -> (T, D, F, E, K, held; a first pass's rows, a chunk of its
# gather, the loops that walk chunks, the layer's temporary bytes at most)
_HELD_WALKS = {
    # 3/2 of the even 20,480: a sixteenth is under 2,048 rows, one chunk
    # (789,279,232 bytes when this was written; 1,350,433,792 at 40,960)
    "granite": ((16384, 4096, 768, 72, 10, 9), 30720, 30720, 0,
                797_000_000),
    # 3/2 of the even 32,768 in 16 chunks (931,812,864; 1,225,576,448 at
    # the 65,536 rows of a pass twice the even share)
    "mellum2": ((16384, 2304, 896, 64, 8, 16), 49152, 3072, 2,
                941_000_000),
}


@pytest.mark.parametrize("widths", list(_HELD_WALKS))
def test_the_held_experts_walks_at_the_cells_widths(widths, one_chip,
                                                    on_chip_branch):
    """One expert layer holding a share of the experts, forward, replay
    under ``jax.checkpoint`` and backward at the Granite cell's widths (9
    of 72 experts, 16,384 tokens of 4,096) and the Mellum2 cell's (16 of
    64, 16,384 of 2,304), compiled for the chip. Where the pass gives
    chunks (Mellum2) the gather of x into expert order is a loop whose
    length the data decide, once forward and once in the replay, and its
    body holds no copy of the pass's buffer or of x (the buffer is updated
    in place); where a sixteenth is under ``HELD_CHUNK_ROWS`` (Granite
    since the pass is 3/2 of the even share) it is one op and no loop
    walks chunks. The Mosaic calls are the 22 the whole-pass gather had
    (16 ``gmm``, 6 ``tgmm``, the further passes' among them); the layer's
    temporary bytes follow the pass (three fifths to three quarters of
    what a pass twice the even share took: ``_HELD_WALKS``)."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    bf = jnp.bfloat16
    (T, D, F, E, K, held), want_rows, want_chunk, loops, temp = \
        _HELD_WALKS[widths]
    cfg = moe.MoEConfig(
        vocab_size=256, d_model=D, n_layers=1, n_heads=8, n_kv_heads=8,
        d_ff=F, n_experts=E, top_k=K, experts_held=(held, 0), shared_d_ff=0,
        dtype=bf, param_dtype=bf, gmm_impl="pallas")
    rows = moe.held_rows(cfg, T)
    chunk = moe.held_chunk(rows)
    assert (rows, chunk) == (want_rows, want_chunk)
    lp = {"router": _sds((D, E), bf, one_chip),
          "we_gate": _sds((held, D, F), bf, one_chip),
          "we_up": _sds((held, D, F), bf, one_chip),
          "we_down": _sds((held, F, D), bf, one_chip)}

    def loss(lp, x):
        y = jax.checkpoint(lambda lp, x: moe.feed_forward(x, lp, cfg)[0])(
            lp, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        lp, _sds((1, T, D), bf, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= temp
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 22
    walks = {} if chunk == rows else {
        name: body for name, body in _while_bodies(text).items()
        if any(re.search(rf"\[{chunk},{D}\]", ln) for ln in body)
        and not any("tpu_custom_call" in ln for ln in body)}
    assert len(walks) == loops, sorted(walks)
    big = re.compile(rf"= \S*\[({rows}|{T}),{D}\]\S* copy\(")
    copies = [ln[:160] for body in walks.values() for ln in body
              if big.search(ln)]
    assert not copies, copies


def test_the_ling_routed_layer_selects_with_no_sort_and_no_scatter(
        one_chip, on_chip_branch):
    """One routed layer at the Ling cell's widths (16,384 tokens of 2,560,
    a router 512 wide, 8 experts a token inside 4 of 8 groups, 16 experts
    of 768 held from the 192nd on, the shared expert), forward, replay
    under ``jax.checkpoint`` and backward, compiled for the chip: no
    instruction issued under the scope ``router`` is a sort, a top-k call,
    a gather or a scatter (the selection is ``moe.top_lanes``' rounds, the
    weights ``at_lanes``' masked sums, whose gradient is a select), and
    the sorts the layer keeps are ``dispatch``'s, of the assignments, and
    the ones XLA makes of ``combine``'s scatter-adds."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    bf = jnp.bfloat16
    T, D, F, E, held = 16384, 2560, 768, 512, 16
    cfg = moe.MoEConfig(
        vocab_size=256, d_model=D, n_layers=1, n_heads=8, n_kv_heads=8,
        d_ff=F, n_experts=E, top_k=8, experts_held=(held, 192),
        shared_d_ff=F, router_score="sigmoid", n_group=8, topk_group=4,
        norm_topk=True, route_scale=2.5, dtype=bf, param_dtype=bf,
        gmm_impl="pallas")
    assert moe.expert_plan(cfg, T)["route_rounds"] == 14
    lp = {"router": _sds((D, E), bf, one_chip),
          "router_bias": _sds((E,), jnp.float32, one_chip),
          "we_gate": _sds((held, D, F), bf, one_chip),
          "we_up": _sds((held, D, F), bf, one_chip),
          "we_down": _sds((held, F, D), bf, one_chip),
          "ws_gate": _sds((D, F), bf, one_chip),
          "ws_up": _sds((D, F), bf, one_chip),
          "ws_down": _sds((F, D), bf, one_chip)}

    def loss(lp, x):
        y, stats = jax.checkpoint(
            lambda lp, x: moe.feed_forward(x, lp, cfg))(lp, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + stats["balance"]

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        lp, _sds((1, T, D), bf, one_chip)).compile().as_text()
    walks = re.compile(r" (sort|scatter|gather)\(|TopK|top_k", re.I)
    issued = [ln for ln in text.splitlines() if re.search(
        r'op_name="[^"]*[/(]router[/)]', ln)]
    assert len(issued) > 20, len(issued)
    bad = [ln.strip()[:200] for ln in issued
           if walks.search(ln.split("metadata=")[0])]
    assert not bad, bad[:3]
    sorts = [ln for ln in text.splitlines() if re.search(r" sort\(", ln)]
    names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in sorts]
    assert names and all(re.search(r"[/(](dispatch|combine)[/)]", n)
                         for n in names), names


# (rows, experts, model width, one expert's width) of a cell's grouped
# matmuls: OLMoE-1B-7B's 131,072 routed rows over 64 experts of 2048 x 1024;
# GLM-4.7-Flash's one pass of 16,384 rows over the 8 experts held, 2048 x
# 1536 (no whole number of the N tiles; whole, the forward's tiles pass
# the VMEM a call gets: ``ops/grouped_matmul.py`` ``_fit``)
GMM_WIDTHS = {"olmoe": (131072, 64, 2048, 1024),
              "glm": (16384, 8, 2048, 1536),
              # Command A+'s one pass of 8,192 rows over the 8 experts held,
              # 4096 x 4096: two K tiles AND several N tiles in one call
              "commanda": (8192, 8, 4096, 4096),
              # GLM-5.2's one pass of 16,384 rows over the 8 experts held,
              # 6144 x 2048: THREE K tiles of 2,048, an expert's matrix
              # 24 MiB
              "glm52": (16384, 8, 6144, 2048),
              # Nemotron 3 Nano's one pass of 24,576 rows over the 16
              # experts held, up [2688, 1856] and down [1856, 2688]: 1,856
              # is no whole number of lanes (whole, or 1,024 + a ragged
              # 832), 2,688 three tiles of 896
              "nemotron up": (24576, 16, 2688, 1856),
              "nemotron down": (24576, 16, 1856, 2688)}


@pytest.mark.parametrize("model", sorted(GMM_WIDTHS))
def test_grouped_matmul_compiles_at_the_cells_widths(model, one_chip,
                                                     on_chip_branch):
    """The expert layer's Mosaic calls with the tiles
    ops/grouped_matmul.py names: forward, input gradient, weight gradient."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.grouped_matmul import grouped_matmul

    rows, e, d, f = GMM_WIDTHS[model]
    x = _sds((rows, d), jnp.bfloat16, one_chip)
    w = _sds((e, d, f), jnp.bfloat16, one_chip)
    sizes = _sds((e,), jnp.int32, one_chip)

    def loss(x, w, sizes):
        y = grouped_matmul(x, w, sizes, impl="pallas").astype(jnp.float32)
        return jnp.sum(y * y)               # wants the forward's product too

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, w, sizes).compile().as_text()
    # forward, input gradient (gmm with the matrices transposed) and
    # weight gradient (tgmm)
    assert text.count("tpu_custom_call") >= 3, text[:2000]
    assert f"bf16[{e},{d},{f}]" in text
